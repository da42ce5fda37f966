"""Pallas kernels vs. pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    flash_attention_pallas,
    rmsnorm_pallas,
    rwkv6_scan_pallas,
    swiglu_pallas,
)
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 128), (3, 5, 256), (2, 7, 384), (1, 1, 512)])
def test_rmsnorm_kernel(shape, dtype):
    x = _rand(shape, dtype)
    scale = _rand(shape[-1:], dtype) * 0.1 + 1.0
    got = rmsnorm_pallas(x, scale, interpret=True)
    want = ref.rmsnorm(x, scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 128), (2, 3, 512), (5, 77), (1, 1000)])
def test_swiglu_kernel(shape, dtype):
    g, u = _rand(shape, dtype), _rand(shape, dtype)
    got = swiglu_pallas(g, u, interpret=True)
    want = ref.swiglu(g, u)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hkv,S,hd,causal",
    [
        (1, 2, 2, 128, 64, True),
        (2, 4, 2, 256, 64, True),
        (1, 8, 2, 128, 128, True),
        (2, 2, 1, 256, 32, False),
        (1, 2, 2, 200, 64, True),  # unaligned S -> padding path
    ],
)
def test_flash_attention_kernel(B, H, Hkv, S, hd, causal, dtype):
    q = _rand((B, H, S, hd), dtype) * 0.5
    k = _rand((B, Hkv, S, hd), dtype) * 0.5
    v = _rand((B, Hkv, S, hd), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, interpret=True,
                                 block_q=64, block_k=64)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("B,H,S,hd,chunk", [(1, 2, 64, 16, 32), (2, 3, 128, 32, 64),
                                            (1, 1, 32, 8, 32)])
def test_rwkv6_kernel(B, H, S, hd, chunk, dtype):
    r = _rand((B, H, S, hd), dtype) * 0.5
    k = _rand((B, H, S, hd), dtype) * 0.5
    v = _rand((B, H, S, hd), dtype)
    w = jnp.asarray(jax.nn.sigmoid(_rand((B, H, S, hd), jnp.float32)) * 0.5 + 0.45, dtype)
    u = _rand((H, hd), dtype) * 0.1
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    got_y, got_s = rwkv6_scan_pallas(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    want_y, want_s = ref.rwkv6_scan(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), rtol=1e-4, atol=1e-4)


def test_rwkv6_state_chaining():
    # running two half-sequences with state carry == one full run
    B, H, S, hd = 1, 2, 64, 16
    args = [_rand((B, H, S, hd), jnp.float32) * 0.3 for _ in range(3)]
    w = jnp.asarray(RNG.uniform(0.5, 0.95, (B, H, S, hd)), jnp.float32)
    u = _rand((H, hd), jnp.float32) * 0.1
    y_full, s_full = ref.rwkv6_scan(*args, w, u)
    half = S // 2
    y1, s1 = ref.rwkv6_scan(*(a[:, :, :half] for a in args), w[:, :, :half], u)
    y2, s2 = ref.rwkv6_scan(*(a[:, :, half:] for a in args), w[:, :, half:], u, s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 2)),
                               np.asarray(y_full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), rtol=1e-5, atol=1e-5)


def test_ops_backend_dispatch_and_grad():
    x = _rand((4, 128), jnp.float32)
    scale = jnp.ones((128,), jnp.float32)

    def loss_ref(x):
        return jnp.sum(ops.rmsnorm(x, scale) ** 2)

    g_ref = jax.grad(loss_ref)(x)
    with ops.backend_scope("pallas", interpret=True):
        assert ops.get_backend() == "pallas"
        g_pal = jax.grad(loss_ref)(x)
        y = ops.swiglu(x, x)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_pal), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref.swiglu(x, x)), rtol=1e-5, atol=1e-5)


def test_interpret_mode_only_on_request():
    """The Pallas backend compiles its kernels unless a caller asks for
    interpret mode, and a scope restores both settings on exit."""
    assert ops.get_backend() == "ref" and ops._INTERPRET is False
    with ops.backend_scope("pallas", interpret=True):
        assert ops._INTERPRET is True
    assert ops.get_backend() == "ref" and ops._INTERPRET is False


@pytest.mark.parametrize("S", [1, 150])
def test_ops_rwkv6_pads_to_chunk(S):
    """A sequence that is not a multiple of the kernel chunk is padded with
    state-preserving steps (k = v = 0, w = 1), not routed to the oracle."""
    B, H, hd = 1, 2, 8
    r, k, v = (_rand((B, H, S, hd), jnp.float32) * 0.5 for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.5, 0.95, (B, H, S, hd)), jnp.float32)
    u = _rand((H, hd), jnp.float32) * 0.1
    s0 = _rand((B, H, hd, hd), jnp.float32) * 0.1
    want_y, want_s = ref.rwkv6_scan(r, k, v, w, u, s0)
    with ops.backend_scope("pallas", interpret=True):
        got_y, got_s = ops.rwkv6_scan(r, k, v, w, u, s0)
    assert got_y.shape == (B, H, S, hd)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S,chunk,causal,Hkv", [(2048, 512, True, 2), (4096, 1024, True, 4),
                                                (2048, 512, False, 1)])
def test_flash_attention_chunked_matches_naive(S, chunk, causal, Hkv):
    """The q/kv-chunked online-softmax path (dry-run/prefill default above
    4k context) is numerically identical to the naive oracle."""
    B, H, hd = 1, 4, 32
    q = _rand((B, H, S, hd), jnp.float32) * 0.3
    k = _rand((B, Hkv, S, hd), jnp.float32) * 0.3
    v = _rand((B, Hkv, S, hd), jnp.float32)
    want = ref.flash_attention(q, k, v, causal=causal)
    got = ref.flash_attention_chunked(q, k, v, causal=causal, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-6, atol=3e-6)


def test_flash_attention_chunked_grad_matches():
    B, H, Hkv, S, hd = 1, 2, 2, 2048, 16
    q = _rand((B, H, S, hd), jnp.float32) * 0.3
    k = _rand((B, Hkv, S, hd), jnp.float32) * 0.3
    v = _rand((B, Hkv, S, hd), jnp.float32)
    g1 = jax.grad(lambda q: ref.flash_attention(q, k, v, causal=True).sum())(q)
    g2 = jax.grad(
        lambda q: ref.flash_attention_chunked(q, k, v, causal=True, chunk=512).sum()
    )(q)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,H,S,P,N,chunk", [(1, 2, 64, 16, 8, 32),
                                             (2, 2, 32, 8, 8, 32)])
def test_mamba2_ssd_kernel(B, H, S, P, N, chunk):
    from repro.kernels.mamba2_scan import mamba2_ssd_pallas

    x = _rand((B, S, H, P), jnp.float32) * 0.5
    Bm = _rand((B, S, N), jnp.float32) * 0.5
    Cm = _rand((B, S, N), jnp.float32) * 0.5
    decay = jnp.asarray(RNG.uniform(0.6, 0.95, (B, S, H)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, (B, S, H)), jnp.float32)
    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    got_y, got_s = mamba2_ssd_pallas(x, Bm, Cm, decay, dt, s0, chunk=chunk,
                                     interpret=True)
    want_y, want_s = ref.mamba2_ssd_scan(x, Bm, Cm, decay, dt, s0)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-4, atol=1e-4)


def test_mamba2_ssd_ref_matches_model_block():
    """The extracted SSD ref oracle equals the recurrence inside
    models.mamba2 (state chaining over two halves)."""
    B, S, H, P, N = 1, 32, 2, 8, 8
    x = _rand((B, S, H, P), jnp.float32) * 0.3
    Bm = _rand((B, S, N), jnp.float32) * 0.3
    Cm = _rand((B, S, N), jnp.float32) * 0.3
    decay = jnp.asarray(RNG.uniform(0.7, 0.95, (B, S, H)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.2, 0.8, (B, S, H)), jnp.float32)
    y_full, s_full = ref.mamba2_ssd_scan(x, Bm, Cm, decay, dt)
    h = S // 2
    y1, s1 = ref.mamba2_ssd_scan(x[:, :h], Bm[:, :h], Cm[:, :h],
                                 decay[:, :h], dt[:, :h])
    y2, s2 = ref.mamba2_ssd_scan(x[:, h:], Bm[:, h:], Cm[:, h:],
                                 decay[:, h:], dt[:, h:], s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-5, atol=1e-5)
