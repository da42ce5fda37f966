"""Stage scopes in the compiled planned collectives — run in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=4
(tests/test_obs.py drives it).

For a planned reduce-scatter, all-gather, all-reduce and all-to-all on a
(2, 2) mesh whose slow axis makes the reduce-scatter's stage order
non-canonical, in every execution mode (oneshot, ring, chunked, hybrid,
and exchange, which has no all-to-all), the compiled HLO text shows:

  * every communication instruction under ``plan.<collective>/stage<k>.
    <axis>.<mode>``, with the stages numbered as the plan's (an
    all-reduce's gather stages after its scatter stages);
  * the all-gather's finalize transpose and the reduce-scatter's block
    permute under ``plan.<collective>`` but outside every stage scope;
  * with the scopes turned off, the same program once the metadata is
    stripped: the scopes change metadata only.

Prints one JSON line: {mode: {collective: [stage scope names]}}.
"""
import contextlib
import dataclasses
import json
import os
import re

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""), (
    "run me via tests/test_obs.py"
)

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh, shard_map
from repro.comms import (exchange_executor, plan_executor, ring_executor,
                         staged_allgather, staged_collectives)
from repro.comms.api import CommContext, PlanPolicy
from repro.comms.plan_executor import execute_plan

AXES = ("pod", "data")  # "pod" is a DCN axis: RS runs it last
mesh = make_mesh((2, 2), AXES)
x = jnp.arange(4 * 64 * 8, dtype=jnp.float32).reshape(4, 64, 8)

COMM = re.compile(r"= (\([^)]*\)|\S+) (all-gather|reduce-scatter|"
                  r"collective-permute|all-to-all|all-reduce)(-start)?\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
STAGE_MODE = {"oneshot": "oneshot", "chunked": "oneshot", "ring": "ring",
              "hybrid": "ring", "exchange": "exchange"}
STAGE = re.compile(r"/(stage(\d+)\.([A-Za-z_]\w*)\.(oneshot|ring|exchange))/")


def plans(mode):
    regime = "latency" if mode == "exchange" else "bandwidth"
    ctx = CommContext(mesh, AXES, policy=PlanPolicy(regime=regime))
    out = {}
    colls = ("rs", "ag", "ar") + (() if mode == "exchange" else ("a2a",))
    for coll in colls:
        plan = ctx.plan(coll, 64 * 8 * 4 / 4, axes=AXES, regime=regime)
        if mode in ("ring", "hybrid"):
            plan = dataclasses.replace(
                plan, mode="perhop" if mode == "ring" else "hybrid",
                num_chunks=1 if mode == "ring" else 2,
                stages=tuple(dataclasses.replace(s, mode="perhop")
                             for s in plan.stages))
        elif mode == "chunked":
            plan = dataclasses.replace(plan, mode="chunked", num_chunks=2)
        elif mode == "oneshot":
            plan = plan.with_mode("oneshot")
        out[coll] = plan
    return out


def compiled_text(ps):
    def body(g):
        s = execute_plan(g[0], ps["rs"])
        out = (s, execute_plan(s, ps["ag"]),
               execute_plan(g[0] + 1.0, ps["ar"]))
        if "a2a" in ps:
            out += (execute_plan(g[0] * 3.0, ps["a2a"]),)
        return out

    specs = (P(AXES), P(), P()) + ((P(AXES),) if "a2a" in ps else ())
    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(AXES),
                          out_specs=specs))
    return f.lower(x).compile().as_text()


@contextlib.contextmanager
def scopes_off():
    off = lambda *a: contextlib.nullcontext()  # noqa: E731
    mods = (ring_executor, staged_collectives, exchange_executor,
            staged_allgather)
    saved = [m.stage_scope for m in mods] + [plan_executor.scope]
    for m in mods:
        m.stage_scope = off
    plan_executor.scope = off
    try:
        yield
    finally:
        for m, s in zip(mods, saved):
            m.stage_scope = s
        plan_executor.scope = saved[-1]


def strip(text):
    """The program without its metadata: no op metadata, and none of the
    source-location tables that follow the computations."""
    text = text.split("\nFileNames\n")[0]
    return "\n".join(re.sub(r",? metadata=\{[^}]*\}", "", ln)
                     for ln in text.splitlines()
                     if not ln.startswith("HloModule"))


report = {}
for mode in ("oneshot", "ring", "chunked", "hybrid", "exchange"):
    ps = plans(mode)
    if mode == "exchange":
        assert all(s.mode == "exchange" for p in ps.values() for s in p.stages)
    assert ps["rs"].axes != AXES, ps["rs"].axes  # the RS permutes its blocks
    text = compiled_text(ps)
    seen = {c: set() for c in ps}
    layout = {c: 0 for c in ps}
    for line in text.splitlines():
        name = OP_NAME.search(line)
        name = name.group(1) if name else ""
        coll = next((c for c in seen if f"/plan.{c}/" in name + "/"), None)
        if COMM.search(line):
            st = STAGE.search(name + "/")
            assert coll and st, (mode, line)
            p = ps[coll]
            k, axis, how = int(st.group(2)), st.group(3), st.group(4)
            assert k < len(p.stages) and p.stages[k].axis == axis, (mode, line)
            seen[coll].add(st.group(1))
        elif coll and "transpose" in name.rsplit("/", 1)[-1]:
            assert not STAGE.search(name + "/"), (mode, line)
            layout[coll] += 1
    for coll in ("rs", "ag"):
        assert layout[coll], (mode, coll, "no layout op outside the stages")
    for coll in set(ps) - {"rs", "ag"}:
        assert seen[coll] == {f"stage{k}.{s.axis}.{STAGE_MODE[mode]}"
                              for k, s in enumerate(ps[coll].stages)}, \
            (mode, coll, seen)
    with scopes_off():
        plain = compiled_text(ps)
    assert "stage0" not in plain
    assert strip(plain) == strip(text), mode
    report[mode] = {c: sorted(v) for c, v in seen.items()}

print(json.dumps(report, sort_keys=True))
