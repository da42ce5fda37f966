#!/usr/bin/env python3
"""Record the small trace ``data/program/`` that ``test_program_trace.py``
reads, on a host with four TPU chips:

    python chipbench/tests/record_program_trace.py <output directory>

Inside one ``chipbench.window`` span: a four-layer decoder behind the
program's ``BatchedServer`` serves three requests of 8, 12 and 8 tokens
on two slots of the first chip, four tokens each (decode at two positions
at once, so some steps hold two ``repro.server.decode`` spans), then the
gradient-sync cell's ``planned_sync`` program (``drivers/grad_sync.py``)
runs twice over a tree of two small bf16 leaves on the 2x2 mesh: the
planned reduce-scatter and all-gather, their stages under the program's
``stage<k>`` scopes.  Every shape is compiled before the trace starts and
the Python tracer is off, so the file stays small.  Prints the server's
counters on the last line.
"""
import dataclasses
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import harness  # noqa: E402
from repro.comms import api  # noqa: E402
from repro.compat import make_mesh  # noqa: E402
from repro.configs import get_config, reduced  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.runtime import BatchedServer, ServerConfig  # noqa: E402


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < 4:
        raise SystemExit("record_program_trace: needs four TPU chips")
    cfg = dataclasses.replace(
        reduced(get_config("granite-3-2b")), num_layers=4, d_model=256,
        num_heads=4, num_kv_heads=2, head_dim=64, d_ff=512, vocab_size=1000)
    srv = BatchedServer(cfg, init_params(jax.random.key(0), cfg),
                        ServerConfig(batch_size=2, max_seq=64,
                                     max_new_tokens=4))
    prompts = [np.arange(n, dtype=np.int32) % 997 for n in (8, 12, 8)]
    grad_sync = harness.load_module(HERE.parent / "drivers" / "grad_sync.py")
    axes = ("data_a", "data_b")
    mesh = make_mesh((2, 2), axes, devices=jax.devices()[:4])
    sync = grad_sync.planned_sync_fn(mesh, axes)
    grads = [jax.device_put(
        jnp.ones((4, 256 * (i + 1), 128), jnp.bfloat16),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(axes)))
        for i in range(2)]
    for p in prompts:  # compile every shape before the trace
        srv.submit(p)
    srv.run_until_drained()
    srv.reset()
    with api.comm_context(mesh, axes):  # the sync plans while it traces
        jax.block_until_ready(sync(grads))

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(out, profiler_options=opts):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for p in prompts:
                srv.submit(p)
            srv.run_until_drained()
            for _ in range(2):
                jax.block_until_ready(sync(grads))
    print(json.dumps(srv.stats.to_json()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
