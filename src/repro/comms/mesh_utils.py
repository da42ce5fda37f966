"""Mesh helpers: factorized axes are how OpTree's m-ary tree lands on a mesh.

A paper "k-stage m-ary tree over N ring nodes" becomes a device axis of size
N split into named sub-axes (m_1, ..., m_k), *major first*: the linear device
position along the logical axis is

    p = i_1 * (N/m_1) + i_2 * (N/(m_1 m_2)) + ... + i_k

which is exactly `jax.make_mesh((m_1, ..., m_k), names)` device order.  Stage
j of the paper (subsets = "same position across the m_j siblings") is an
all-gather over sub-axis j.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax

from ..compat import make_mesh

__all__ = ["make_factorized_mesh"]


def make_factorized_mesh(
    factors: Sequence[int],
    names: Sequence[str],
    *,
    devices=None,
) -> jax.sharding.Mesh:
    """Mesh whose axes are the stage factors of one logical OpTree axis
    (optionally combined with other parallelism axes by the caller)."""
    if len(factors) != len(names):
        raise ValueError("factors and names must align")
    n = math.prod(factors)
    devs = devices if devices is not None else jax.devices()
    if n > len(devs):
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return make_mesh(tuple(factors), tuple(names), devices=devs[:n])
