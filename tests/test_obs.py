"""The program's own trace points (``repro.obs``): the serving loop's spans
and counters under the profiler, the plan stages' scopes in the compiled
collectives, and the jitted programs' names that the benchmark's readers
find in a trace."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

SERVER_SPANS = ("repro.server.engine_step", "repro.server.prefill",
                "repro.server.decode", "repro.server.fetch")


def _tiny_server(batch_size=2):
    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.runtime import BatchedServer, ServerConfig

    cfg = dataclasses.replace(
        reduced(get_config("granite-3-2b")), num_layers=2, d_model=32,
        num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64, vocab_size=128)
    return BatchedServer(cfg, init_params(jax.random.key(0), cfg),
                         ServerConfig(batch_size=batch_size, max_seq=32,
                                      max_new_tokens=5))


def _serve(srv, prompts):
    for p in prompts:
        srv.submit(p)
    return srv.run_until_drained()


def _spans(trace_dir):
    """``(name, start_ns, end_ns, stats)`` of every ``repro.`` host span."""
    from jax.profiler import ProfileData

    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(spans, i):
    """The innermost span that holds span ``i``."""
    _, s, e, _ = spans[i]
    holders = [j for j, (_, s2, e2, _) in enumerate(spans)
               if j != i and s2 <= s and e <= e2]
    return max(holders, key=lambda j: spans[j][1]) if holders else None


def test_server_spans_and_stats(tmp_path):
    """Under ``jax.profiler.trace`` the serving loop writes nested
    ``repro.server.*`` spans with their ids: one ``decode`` span per jitted
    decode call and per ``ServerStats.decode_calls``, one ``fetch`` inside
    each prefill and decode, and the same tokens as an untraced run."""
    rng = np.random.default_rng(0)
    # mixed prompt lengths over 2 slots: decode at distinct positions
    prompts = [rng.integers(0, 128, size=n).astype(np.int32)
               for n in (6, 9, 6, 11)]
    srv = _tiny_server()
    untraced = {k: list(v) for k, v in _serve(srv, prompts).items()}
    assert srv.stats.decode_calls > 0
    srv.reset()
    assert srv.stats.to_json() == {"engine_steps": 0, "prefill_calls": 0,
                                   "decode_calls": 0, "tokens": 0}

    runs = {"decode": 0}
    jitted = srv._decode

    def counted(*a):
        runs["decode"] += 1
        return jitted(*a)

    srv._decode = counted
    with jax.profiler.trace(str(tmp_path)):
        traced = {k: list(v) for k, v in _serve(srv, prompts).items()}
    assert traced == untraced

    st = srv.stats
    assert st.tokens == sum(len(v) for v in traced.values())
    assert st.prefill_calls == len(prompts)
    assert st.decode_calls == runs["decode"]

    spans = _spans(tmp_path)
    names = [s[0] for s in spans]
    assert set(names) == set(SERVER_SPANS)
    assert names.count("repro.server.engine_step") == st.engine_steps
    assert names.count("repro.server.prefill") == st.prefill_calls
    assert names.count("repro.server.decode") == st.decode_calls
    assert names.count("repro.server.fetch") == (st.prefill_calls
                                                 + st.decode_calls)
    want_parent = {"repro.server.engine_step": None,
                   "repro.server.prefill": "repro.server.engine_step",
                   "repro.server.decode": "repro.server.engine_step",
                   "repro.server.fetch": ("repro.server.prefill",
                                          "repro.server.decode")}
    lanes = 0
    for i, (name, _, _, stats) in enumerate(spans):
        p = _parent(spans, i)
        got = None if p is None else spans[p][0]
        want = want_parent[name]
        assert got in (want if isinstance(want, tuple) else (want,)), \
            (name, got)
        if name == "repro.server.engine_step":
            assert stats["step"] >= 1
        elif name == "repro.server.prefill":
            assert set(stats) == {"rid", "slot", "len"}
            assert stats["len"] == len(prompts[stats["rid"]])
        elif name == "repro.server.decode":
            assert set(stats) == {"max_pos", "lanes"} and stats["lanes"] >= 1
            lanes += stats["lanes"]
    steps = [s[3]["step"] for s in spans if s[0] == "repro.server.engine_step"]
    assert steps == list(range(1, st.engine_steps + 1))
    # every token is the prefill's or one decoded lane's
    assert lanes + st.prefill_calls == st.tokens


@pytest.mark.slow
@pytest.mark.subproc
def test_stage_scopes_in_compiled_plans():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = f"{REPO / 'src'}:{env.get('PYTHONPATH', '')}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "subproc" /
                             "check_stage_scopes.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(report) == {"oneshot", "ring", "chunked", "hybrid", "exchange"}
    assert report["ring"]["rs"] == ["stage0.data.ring", "stage1.pod.ring"]
    assert report["ring"]["ar"] == ["stage0.data.ring", "stage1.pod.ring",
                                    "stage2.pod.ring", "stage3.data.ring"]
    assert "a2a" in report["hybrid"] and "a2a" not in report["exchange"]


def _module_name(lowered) -> str:
    return lowered.as_text().split("module @", 1)[1].split(" ", 1)[0]


def test_serving_program_names():
    """The trace's readers find the serving programs as ``jit_decode`` and
    ``jit_prefill``."""
    srv = _tiny_server()
    lanes = jnp.ones((2,), bool)
    dec = srv._decode.lower(srv.params, srv.state,
                            jnp.zeros((2, 1), jnp.int32),
                            jnp.asarray([3, 5], jnp.int32), lanes)
    pre = srv._prefill.lower(srv.params,
                             {"tokens": jnp.zeros((2, 8), jnp.int32)},
                             srv.state, lanes)
    assert _module_name(dec) == "jit_decode"
    assert _module_name(pre) == "jit_prefill"


def test_sync_program_names():
    """The benchmark's gradient-sync programs are found as
    ``jit_planned_sync`` and ``jit_xla_sync``."""
    sys.path.insert(0, str(REPO / "chipbench"))
    try:
        import harness

        drv = harness.load_module(REPO / "chipbench" / "drivers" /
                                  "grad_sync.py")
    finally:
        sys.path.remove(str(REPO / "chipbench"))
    from repro.comms import api
    from repro.compat import make_mesh

    axes = ("data_a", "data_b")
    mesh = make_mesh((1, 1), axes)
    grads = [jnp.zeros((1, 8, 4), jnp.float32)]
    with api.comm_context(mesh, axes):
        planned = drv.planned_sync_fn(mesh, axes).lower(grads)
        xla = drv.xla_sync_fn(mesh, axes).lower(grads)
    assert _module_name(planned) == "jit_planned_sync"
    assert _module_name(xla) == "jit_xla_sync"


def test_drain_report_prints_stats(capsys):
    """``launch/serve.py``'s drain report prints the server's counters."""
    from repro.launch.serve import report_serving, serve_prompts

    srv = _tiny_server()
    prompts = [np.arange(n, dtype=np.int32) for n in (5, 7, 5)]
    report_serving(srv, *serve_prompts(srv, prompts))
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("[serve/drain] stats="))
    assert json.loads(line.split("=", 1)[1]) == srv.stats.to_json()
    assert srv.stats.tokens == 15 and srv.stats.prefill_calls == 3
