"""Plain reference of a data-parallel gradient sync: the sum over the chips.

Each chip's gradient tree is made from the seed, leaf by leaf, by
``local_grad`` (chip ``k`` of leaf ``i``: normal(0, 1) from
``fold_in(fold_in(key(seed), i), k)``, in the tree's dtype).  The tree has
the shapes of the decoder reference's weights (``decoder.init_weights``).

A reduce-scatter then an all-gather must return, on every chip, the sum
of all the chips' leaves; after the reduce-scatter, chip ``c`` holds block
``c`` of each leaf's leading dimension.  The reference remakes every
chip's leaf locally and adds them in float32: it moves nothing between
chips and imports nothing of the program.

``quant=True`` is the control: each chip's leaf is rounded to float8
(e4m3, one scale per leaf; ``decoder.fake_quant``) before the sum.
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent))

import decoder  # noqa: E402

__all__ = ["leaf_shapes", "local_grad", "leaf_sum"]


def leaf_shapes(m: Dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """(path, shape) of every leaf of the tree, in ``jax.tree`` order."""
    shapes = jax.eval_shape(lambda: decoder.init_weights(m, 0))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [(jax.tree_util.keystr(p), tuple(a.shape)) for p, a in flat]


def tree_def(m: Dict):
    return jax.tree.structure(jax.eval_shape(lambda: decoder.init_weights(m, 0)))


def local_grad(seed, leaf: int, chip, shape, dtype):
    """Chip ``chip``'s gradient of leaf ``leaf`` (traceable in ``chip``)."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(jnp.uint32(seed % 2**32) if isinstance(seed, int)
                       else seed), leaf), chip)
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def leaf_sum(seed, leaf: int, chips: int, shape, dtype, quant: bool = False):
    """The sum over ``chips`` chips of leaf ``leaf``, in float32."""
    acc = jnp.zeros(shape, jnp.float32)
    for k in range(chips):
        g = local_grad(seed, leaf, k, shape, dtype).astype(jnp.float32)
        acc = acc + (decoder.fake_quant(g, None) if quant else g)
    return acc
