"""Compile guards: the main-path Pallas kernels at published widths, and one
planned collective on four chips, compiled for a described (not attached)
TPU v5e 2x2.  Nothing runs; the TPU compiler refuses here what the chip
would refuse (block tiling, VMEM, unsupported lowering), at no chip time.

The topology and everything built from it live in module-scoped fixtures:
only the worker that runs this file loads the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    with ops.backend_scope("pallas", interpret=False):
        return jax.jit(fn).lower(*shapes).compile().as_text()


# granite-3-2b: d_model 2048, d_ff 8192, 32/8 heads of 64; 4096 rows is a
# (2, 2048) prefill.  rwkv6-7b: 64 heads of 64; S=1000 is not a multiple of
# the kernel's 128-step chunk, so the ops wrapper pads it.
KERNELS = {
    "rmsnorm": (lambda x, s: ops.rmsnorm(x, s),
                [((4096, 2048), jnp.bfloat16), ((2048,), jnp.bfloat16)]),
    "swiglu": (lambda g, u: ops.swiglu(g, u),
               [((4096, 8192), jnp.bfloat16)] * 2),
    "flash_attention": (lambda q, k, v: ops.flash_attention(q, k, v,
                                                            causal=True),
                        [((1, 32, 2048, 64), jnp.bfloat16)]
                        + [((1, 8, 2048, 64), jnp.bfloat16)] * 2),
    "rwkv6_scan": (lambda r, k, v, w, u: ops.rwkv6_scan(r, k, v, w, u),
                   [((1, 64, 1000, 64), jnp.bfloat16)] * 4
                   + [((64, 64), jnp.bfloat16)]),
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(kernel, one_chip):
    fn, shapes = KERNELS[kernel]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_planned_all_gather_compiles_on_four_chips(topo):
    """The context-planned all-gather of a 1 MiB shard over a (4,) mesh of
    described chips lowers to collectives, not to a kernel-free no-op."""
    from repro.comms import api
    from repro.compat import make_mesh

    mesh = make_mesh((4,), ("x",), devices=topo.devices)
    x = jax.ShapeDtypeStruct((4 * 2**18,), jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))
    with api.comm_context(mesh, ("x",)) as ctx:
        text = jax.jit(lambda v: api.all_gather(v)).lower(x).compile().as_text()
    assert [p.collective for p in ctx.plans()] == ["ag"]
    assert "collective-permute" in text or "all-gather" in text
