"""Continuous batching of recurrent state: a slot that takes a new request
starts it from a zero recurrent state, not from the state the slot's
previous request left (``BatchedServer`` zeroes the written lane's Mamba
conv/SSM or RWKV shift/WKV state before each prefill)."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import init_params
from repro.runtime import BatchedServer, ServerConfig


def _serve(cfg, params, prompts):
    server = BatchedServer(cfg, params, ServerConfig(
        batch_size=1, max_seq=48, max_new_tokens=4, keep_logits=True))
    for p in prompts:
        server.submit(p)
    server.run_until_drained()
    return server


@pytest.mark.parametrize("arch", ["granite-4.0-h-micro", "zamba2-2.7b",
                                  "rwkv6-7b"])
def test_request_after_another_in_the_slot_gives_its_own_logits(arch):
    """One slot: request B served after request A gives the logits B gives
    served alone, to 1e-6: the same programs on the same inputs.  With the
    reset left out, A's state moves them by 2.7e-5 (granite-4.0-h-micro,
    whose logits are divided by 8 and whose Mamba layers forget fast at
    these weights), 0.76 (zamba2) and 0.91 (rwkv6)."""
    cfg = reduced(get_config(arch))
    params = init_params(jax.random.key(3), cfg)
    rng = np.random.default_rng(4)
    a = rng.integers(0, cfg.vocab_size, 30).astype(np.int32)
    b = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    after = _serve(cfg, params, [a, b])
    alone = _serve(cfg, params, [b])
    assert after.stats.state_resets == 2 and alone.stats.state_resets == 1
    np.testing.assert_allclose(np.stack(after.logits[1]),
                               np.stack(alone.logits[0]), rtol=0, atol=1e-6)
