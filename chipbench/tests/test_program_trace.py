"""The reader of the program's own spans and scopes, on hand-built planes
whose answers are worked out by hand, and on a small trace recorded on a
TPU v5e (``record_program_trace.py``)."""
from pathlib import Path

import pytest

import program_trace as pt
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
STEP, PREFILL, DECODE, FETCH = pt.STEP, pt.PREFILL, pt.DECODE, pt.FETCH


def test_self_intervals_innermost():
    spans = [(STEP, 0.0, 10.0), (DECODE, 1.0, 4.0), (FETCH, 2.0, 3.5),
             (DECODE, 5.0, 9.0), (FETCH, 6.0, 9.5)]  # runs past its holder
    pieces = pt.self_intervals(spans)
    assert pieces == [(STEP, 0.0, 1.0), (DECODE, 1.0, 2.0), (FETCH, 2.0, 3.5),
                      (DECODE, 3.5, 4.0), (STEP, 4.0, 5.0), (DECODE, 5.0, 6.0),
                      (FETCH, 6.0, 9.0), (STEP, 9.0, 10.0)]
    # the pieces tile the outermost span
    assert sum(e - s for _, s, e in pieces) == pytest.approx(10.0)


def test_charge():
    free = [(0.5, 1.5), (3.0, 6.0)]
    pieces = [("a", 0.0, 1.0), ("b", 1.0, 3.5), ("a", 3.5, 4.0),
              ("c", 4.0, 7.0)]
    got = pt.charge(free, pieces)
    assert got == pytest.approx({"a": 1.0, "b": 1.0, "c": 2.0})


def test_serving_split_by_hand():
    # one engine step [0, 10]: a prefill [0.5, 3] with its fetch [2, 3],
    # a decode [4, 8] with its fetch [6, 8]; the device runs the prefill
    # program over [1, 2.5] and the decode program over [4.5, 7]
    spans = [(STEP, 0.0, 10.0), (PREFILL, 0.5, 3.0), (FETCH, 2.0, 3.0),
             (DECODE, 4.0, 8.0), (FETCH, 6.0, 8.0),
             (STEP, 11.0, 11.5)]  # a second step, all idle
    busy = [(1.0, 2.5), (4.5, 7.0)]
    got = pt.serving_split(busy, (0.0, 12.0), spans)
    # idle [0, 1] [2.5, 4.5] [7, 12]: fetch 0.5 + 1; prefill 0.5 (before
    # the program); decode 0.5; engine_step 0.5 + 0.5 + 0.5 + 2 + 0.5
    assert got["fetch_idle_s"] == pytest.approx(1.5)
    assert got["dispatch_idle_s"] == pytest.approx(1.0)
    assert got["bookkeeping_idle_s"] == pytest.approx(4.0)
    assert got["engine_step_idle_s"] == pytest.approx(6.5)
    assert (got["decode_spans"], got["prefill_spans"]) == (1, 1)
    # everything inside the engine steps, as the reduction charges it
    r = tr.reduce_planes({"/device:TPU:0": {"ops": [("op", s, e)
                                                    for s, e in busy],
                                            "modules": []}},
                         [("chipbench.engine_step", 0.0, 10.0),
                          ("chipbench.engine_step", 11.0, 11.5)],
                         window=(0.0, 12.0))
    assert dict(r["idle_gaps"])["chipbench.engine_step"] == \
        pytest.approx(got["engine_step_idle_s"])


def test_stage_split_by_hand():
    s0, s1 = "jit(planned_sync)/shard_map/plan.rs/stage0.data_b.oneshot", \
        "jit(planned_sync)/shard_map/plan.ag/stage1.data_b.ring"
    dev = [  # (program id, op_name, instruction, seconds)
        (7, s0 + "/reduce_scatter:", "reduce_scatter.3", 4.0),
        (7, "jit(planned_sync)/shard_map/plan.rs/transpose:",
         "transpose_fusion", 1.0),
        (7, s1 + "/ppermute:", "collective-permute.1", 3.0),
        (7, "", "all-gather.2.remat", 0.5),  # no op_name: outside
        (7, "", "copy.4", 0.5),
        (9, "jit(xla_sync)/reduce_scatter:", "reduce_scatter.1", 10.0),
    ]
    modules = [("jit_planned_sync", 7, 9.5), ("jit_planned_sync", 7, 9.5),
               ("jit_xla_sync", 9, 10.0)]
    got = pt.stage_split([("/device:TPU:0", dev, modules),
                          ("/device:TPU:1", dev, modules)], "planned_sync")
    assert got["runs"] == 2 and got["program_s"] == pytest.approx(19.0)
    assert got["stage_s"] == pytest.approx(7.0)
    assert got["layout_s"] == pytest.approx(2.0)
    assert got["collective_s"] == pytest.approx(7.5)
    assert got["collective_stage_s"] == pytest.approx(7.0)
    # the layout by the plan scope and the scope under it
    assert got["layout_by_scope"] == pytest.approx(
        {"plan.rs/transpose": 1.0, "no plan scope": 1.0})
    # a program with no stage scopes reads as nothing
    bare = [(p, "", o, s) for p, _, o, s in dev]
    assert pt.stage_split([("/device:TPU:0", bare, modules)],
                          "planned_sync") is None


def test_stage_pattern():
    yes = ["jit(f)/plan.ag/stage0.data.oneshot/all_gather:",
           "stage12.pod.exchange/ppermute", "x/stage3.a_b.ring"]
    no = ["jit(f)/plan.ag/transpose:", "jit(f)/backstage0.a.ring/x",
          "jit(f)/stage0.a.chunked/x", ""]
    assert all(pt.STAGE.search(s) for s in yes)
    assert not any(pt.STAGE.search(s) for s in no)


def test_readers_without_program_trace(tmp_path, monkeypatch):
    """A window whose trace has no program spans or scopes (the program
    before it recorded them), or no trace at all, reads as nothing."""
    import harness

    monkeypatch.setattr(pt, "HERE", tmp_path)
    ctx = {"trace": {"window_s": 1.0, "idle_gaps": [], "modules": {}},
           "counts": {"recv_bytes_per_sync": 10}, "peaks": {}}
    for name in ("fetch_idle_share.chat", "dispatch_idle_share.offline",
                 "sync_layout_ms", "sync_stage_ici_share"):
        assert harness.metric_reader(name).read(name, ctx) is None
    win = tmp_path / ".traces" / "granite-chat" / "window"
    win.mkdir(parents=True)
    (win / "t.xplane.pb").write_bytes((DATA / "small.xplane.pb").read_bytes())
    ctx["trace"] = tr.reduce_dir(win)
    for name in ("fetch_idle_share.chat", "dispatch_idle_share.chat",
                 "sync_layout_ms", "sync_stage_ici_share"):
        assert harness.metric_reader(name).read(name, ctx) is None


def test_recorded_program_trace():
    """A trace recorded on a TPU v5e host of four chips by
    ``record_program_trace.py`` (``ServerStats`` at its end: 6 engine
    steps, 3 prefills, 9 decode calls, 12 tokens): the serving spans on
    chip 0, then two runs of the sync cell's ``planned_sync`` program on
    the 2x2 mesh."""
    path = DATA / "program" / "program.xplane.pb"
    r = tr.reduce_dir(path.parent)
    assert r["devices"] == 4
    assert r["window_s"] == pytest.approx(0.039676018)
    # the serving programs ran on chip 0 only (module counts are means)
    assert r["modules"]["jit_decode"]["count"] * r["devices"] == 9
    assert r["modules"]["jit_prefill"]["count"] * r["devices"] == 3

    busy, window, spans = pt._load_serving(path)
    got = pt.serving_split(busy, window, spans)
    assert (got["decode_spans"], got["prefill_spans"]) == (9, 3)
    assert sum(n == STEP for n, _, _ in spans) == 6
    assert sum(n == FETCH for n, _, _ in spans) == 12
    assert got["fetch_idle_s"] == pytest.approx(0.020500788)
    assert got["dispatch_idle_s"] == pytest.approx(0.014958746)
    assert got["bookkeeping_idle_s"] == pytest.approx(0.000365392)
    # the split accounts for every idle second inside the engine steps, as
    # the reduction charges them to a span around each step
    steps = [("chipbench.engine_step", s, e) for n, s, e in spans
             if n == STEP]
    bench = tr.reduce_planes(
        {"/device:TPU:0": {"ops": [("op", s, e) for s, e in busy],
                           "modules": []}}, steps, window=window)
    assert dict(bench["idle_gaps"])["chipbench.engine_step"] == \
        pytest.approx(got["engine_step_idle_s"])
    assert got["engine_step_idle_s"] == pytest.approx(
        got["fetch_idle_s"] + got["dispatch_idle_s"]
        + got["bookkeeping_idle_s"])

    sync = pt.stage_split(list(pt.device_planes(path)), "planned_sync")
    assert sync["runs"] == 2
    # picoseconds here, whole nanoseconds in the reduction
    assert sync["program_s"] == pytest.approx(
        r["modules"]["jit_planned_sync"]["seconds"], rel=1e-4)
    # every collective under its stage scope; the finalize copies outside
    assert sync["collective_stage_s"] == sync["collective_s"] > 0
    assert sync["stage_s"] == pytest.approx(6.70175195e-05)
    assert sync["layout_s"] == pytest.approx(1.6171875e-06)
    # the layout is the all-gather's finalize transpose, and copies that
    # XLA added outside every op_name
    assert sync["layout_by_scope"] == pytest.approx(
        {"plan.ag/transpose": 1.5613865e-06, "no plan scope": 5.5801e-08})
    # the serving programs carry no stage scope
    assert pt.stage_split(list(pt.device_planes(path)), "decode") is None


def test_plan_pattern():
    got = {t: pt.PLAN.search(t) for t in (
        "jit(planned_sync)/shard_map/plan.ag/transpose:",
        "jit(f)/plan.rs:", "x/plan.ag/concatenate/y", "jit(f)/transpose:",
        "jit(f)/plan.ag/reshape;jit(f)/plan.ag/concatenate:")}
    assert {t: m and "".join(m.groups("")) for t, m in got.items()} == {
        "jit(planned_sync)/shard_map/plan.ag/transpose:": "plan.ag/transpose",
        "jit(f)/plan.rs:": "plan.rs", "x/plan.ag/concatenate/y":
        "plan.ag/concatenate", "jit(f)/transpose:": None,
        # a fusion lists each op_name it holds: the first one names it
        "jit(f)/plan.ag/reshape;jit(f)/plan.ag/concatenate:":
        "plan.ag/reshape"}


def test_log_sync_by_plan(capsys):
    split = {"runs": 2.0, "program_s": 0.02, "stage_s": 0.012,
             "layout_s": 0.007, "collective_s": 0.012,
             "collective_stage_s": 0.012,
             "layout_by_scope": {"plan.rs/slice": 0.002,
                                 "plan.ag/concatenate": 0.003,
                                 "plan.ag/transpose": 0.001,
                                 "no plan scope": 0.001}}
    pt.log_sync(split)
    err = capsys.readouterr().err.splitlines()
    assert err[1] == (
        "[chipbench] layout outside the stage scopes, per run: no plan scope "
        "0.5 ms; plan.ag 2.0 ms (concatenate 1.5, transpose 0.5); plan.rs "
        "1.0 ms (slice 1.0)")


def test_mismatched_trace_is_logged(tmp_path, monkeypatch, capsys):
    """A window trace that holds the program's spans and scopes but is not
    the one the reduction read (another length, other runs) reads as
    nothing, and says so."""
    monkeypatch.setattr(pt, "HERE", tmp_path)
    win = tmp_path / ".traces" / "granite-chat" / "window"
    win.mkdir(parents=True)
    (win / "t.xplane.pb").write_bytes(
        (DATA / "program" / "program.xplane.pb").read_bytes())
    ctx = {"trace": dict(tr.reduce_dir(win), window_s=1.0)}
    assert pt.serving(ctx) is None
    ctx["trace"]["modules"] = {"jit_planned_sync": {"count": 3.0,
                                                    "seconds": 1.0}}
    assert pt.sync(ctx) is None
    err = capsys.readouterr().err
    assert "s against 1.0 s)" in err
    assert "2 planned_sync runs against 3" in err
    # the reduction that matches reads
    ctx["trace"] = tr.reduce_dir(win)
    assert pt.serving(ctx)["decode_spans"] == 9
    assert pt.sync(ctx)["runs"] == 2
