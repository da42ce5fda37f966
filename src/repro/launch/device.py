"""Process set-up shared by the entry points (``launch/serve.py``,
``launch/train.py``, ``chip_smoke.py``): where JAX keeps its compile cache,
which kernel backend runs, and which device the process holds.

Call these from ``main()``, never at import time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

import jax

from ..kernels import ops

__all__ = ["CACHE_DIR", "place_compile_cache", "select_kernel_backend",
           "device_info"]

#: the checkout's own cache directory (listed in .gitignore).  A fixed path:
#: it is part of what lets a later process find the entries again.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to :data:`CACHE_DIR`.

    The cache key includes the programs' metadata: by default JAX leaves it
    out, so a program whose ``repro.obs`` scopes changed would load the
    executable of its older source, and a trace would show that source's
    op names instead of its own stages.  Source files enter the metadata by
    their base names only, so the key does not depend on where the
    checkout lies."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", r".*/")
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def select_kernel_backend(gspmd_mesh=None) -> str:
    """Compiled Pallas kernels on a TPU, the jnp reference elsewhere.

    GSPMD cannot partition a Pallas (Mosaic) kernel, so a program that
    shards its activations over a ``gspmd_mesh`` of more than one device
    keeps the reference.  Inside ``shard_map`` each kernel runs per device:
    pass no mesh there."""
    on_tpu = jax.devices()[0].platform == "tpu"
    unpartitioned = gspmd_mesh is None or gspmd_mesh.size == 1
    name = "pallas" if on_tpu and unpartitioned else "ref"
    ops.set_backend(name)
    return name


def device_info() -> Dict[str, object]:
    """The device as JAX reports it: platform, kind and count."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
