"""granite-4.0-h-micro (Mamba2 + NoPE GQA layers, each with its own MLP)
held to the plain float32 reference (``repro.models.reference``) at a small
size that keeps one whole period of its layer pattern, on seeded random
weights; and the Mamba2 scan kernel against the jnp scan."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import ops, ref
from repro.models import init_params
from repro.models.reference import hybrid_logits
from repro.runtime import BatchedServer, ServerConfig


def small_hybrid():
    """One period (5 Mamba, 1 attention, 4 Mamba layers) at d_model 64,
    Mamba2 heads of 16 with d_state 8, in float32, chunk 8 so that the
    prompts span several chunks of the scan."""
    cfg = reduced(get_config("granite-4.0-h-micro"))
    return dataclasses.replace(
        cfg, ssm=dataclasses.replace(cfg.ssm, scan_chunk=8))


def hybrid_weights(cfg, seed):
    """``init_params`` with the Mamba2 scalars drawn as a trained model has
    them (A in [1, 16], dt in [0.001, 0.1], D around 1) and every norm
    scale and conv bias random, so no term of the equations is trivial."""
    p = init_params(jax.random.key(seed), cfg)
    rng = np.random.default_rng(seed)

    def draw(a, lo, hi):
        return jnp.asarray(rng.uniform(lo, hi, a.shape), a.dtype)

    m = p["layers"]["mamba"]
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), m["dt_bias"].shape))
    m["a_log"] = jnp.log(draw(m["a_log"], 1.0, 16.0))
    m["dt_bias"] = jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
    m["d_skip"] = draw(m["d_skip"], 0.5, 1.5)
    m["conv_b"] = draw(m["conv_b"], -0.1, 0.1)
    m["norm_scale"] = draw(m["norm_scale"], 0.5, 1.5)
    for k in ("ln1", "ln2"):
        p["layers"][k]["scale"] = draw(p["layers"][k]["scale"], 0.5, 1.5)
    return p


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_server_logits_match_reference(backend):
    """Prefill, then decode through ``BatchedServer`` with three slots at
    different positions (four requests, so one slot serves two): every
    emitted token's logits row equals the reference's full forward pass
    over prompt plus served tokens at that position.

    Tolerance 1e-6 absolute on logits of magnitude ~0.2 (the head divides
    by 8): both sides compute in float32 and differ only in the order of
    float32 sums (the chunked scan against the sequential one, a decode
    step against the whole sequence), 3e-8 here on both backends.  The
    program in bfloat16 misses the reference by 5e-4 (the next test)."""
    cfg = small_hybrid()
    params = hybrid_weights(cfg, 0)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 17, 9, 12)]
    server = BatchedServer(cfg, params, ServerConfig(
        batch_size=3, max_seq=32, max_new_tokens=6, keep_logits=True))
    with ops.backend_scope(backend, interpret=True):
        for p in prompts:
            server.submit(p)
        results = server.run_until_drained()
    assert server.stats.state_resets == len(prompts)
    for rid, prompt in enumerate(prompts):
        served = results[rid]
        toks = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        want = np.asarray(hybrid_logits(cfg, params, toks))[len(prompt) - 1:]
        got = np.stack(server.logits[rid])
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6,
                                   err_msg=f"request {rid}")


def test_reference_is_not_the_program_in_bfloat16():
    """The tolerance above is tight enough to tell: the program in bfloat16
    (weights and activations) misses the float32 reference by more than
    100 times it (5e-4 against 1e-6)."""
    cfg = small_hybrid()
    params = hybrid_weights(cfg, 0)
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size, 16)
    from repro.models import forward

    low = dataclasses.replace(cfg, dtype="bfloat16")
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.dtype == jnp.float32 and a.ndim > 1 else a, params)
    got, _, _ = forward(low, p16, {"tokens": jnp.asarray(toks)[None]})
    want = np.asarray(hybrid_logits(cfg, params, toks))
    assert np.abs(np.asarray(got[0]) - want).max() > 100 * 1e-6


@pytest.mark.parametrize("S,chunk", [(64, 16), (256, 64)])
def test_ops_mamba2_scan_pallas_matches_jnp_scan(S, chunk):
    """``ops.mamba2_scan`` on the Pallas backend (interpret mode), from a
    nonzero initial state over several chunks, equals the sequential jnp
    scan of ``kernels.ref``.  Tolerance 2e-5 relative to the largest
    output: float32 sums in another order (within-chunk matrix products
    against step-by-step updates)."""
    rng = np.random.default_rng(S)
    B, H, P, N = 2, 3, 16, 8
    x = jnp.asarray(rng.normal(size=(B, S, H, P)) * 0.5, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.5, jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (B, S, H)), jnp.float32)
    dA = -dt * jnp.asarray(rng.uniform(0.5, 4.0, H), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(B, H, P, N)), jnp.float32)
    with ops.backend_scope("pallas", interpret=True):
        y, s = ops.mamba2_scan(x, Bm, Cm, dA, dt, s0, chunk=chunk)
    want_y, want_s = ref.mamba2_ssd_scan(x, Bm, Cm, jnp.exp(dA), dt, s0)
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(y, want_y, rtol=0, atol=2e-5 * scale)
    np.testing.assert_allclose(s, want_s, rtol=0,
                               atol=2e-5 * float(jnp.abs(want_s).max()))
