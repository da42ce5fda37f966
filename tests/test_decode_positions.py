"""``decode_step`` with one cache position per lane: lanes at different
positions decode in one call and each gets what it gets decoded alone."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import decode_step, init_decode_state, init_params

ARCH = {"dense": "granite-3-2b", "ssm": "rwkv6-7b", "hybrid": "zamba2-2.7b"}
B, T = 3, 16


def _model(family):
    cfg = reduced(get_config(ARCH[family]))
    if family == "dense":
        cfg = dataclasses.replace(cfg, num_layers=2, vocab_size=128)
    return cfg, init_params(jax.random.key(1), cfg)


def _random_state(cfg, key):
    """A decode state with every entry random (KV rows past a lane's
    position included, so a wrong mask or write shows)."""
    leaves, tree = jax.tree.flatten(init_decode_state(cfg, B, T))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])


def _lane(tree, b):
    """Lane ``b`` of a decode state (batch is every leaf's second dim)."""
    return jax.tree.map(lambda a: a[:, b:b + 1], tree)


@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid"])
@pytest.mark.parametrize("cache_pos", [[5, 11, 7], 6],
                         ids=["per_lane", "scalar"])
def test_batched_decode_matches_each_lane_alone(family, cache_pos):
    """One call with ``cache_pos`` of shape ``(B,)`` (or ``()``) and a lane
    mask gives every written lane the logits and state of that lane decoded
    alone at its scalar position; the masked lane's state is left bit for
    bit as it was (its logits attend an unwritten row: no server keeps
    them)."""
    cfg, params = _model(family)
    state = _random_state(cfg, jax.random.key(2))
    tokens = jnp.asarray([[3], [17], [42]], jnp.int32)
    lanes = jnp.asarray([True, True, False])
    pos = jnp.asarray(cache_pos, jnp.int32)
    logits, new = decode_step(cfg, params, state, tokens, pos,
                              cache_lanes=lanes)
    lane_pos = np.broadcast_to(np.asarray(cache_pos), (B,))
    for b in range(B):
        want_logits, want_state = decode_step(
            cfg, params, _lane(state, b), tokens[b:b + 1],
            jnp.asarray(lane_pos[b], jnp.int32))
        got_state = _lane(new, b)
        if lanes[b]:
            np.testing.assert_allclose(logits[b], want_logits[0],
                                       rtol=1e-5, atol=1e-5)
            for g, w in zip(jax.tree.leaves(got_state),
                            jax.tree.leaves(want_state)):
                np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
        else:
            for g, w in zip(jax.tree.leaves(got_state),
                            jax.tree.leaves(_lane(state, b))):
                np.testing.assert_array_equal(g, w)
