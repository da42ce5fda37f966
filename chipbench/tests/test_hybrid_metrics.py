"""The hybrid cell's readers, ``mamba_time_share`` and
``mamba2_scan_roofline``, on hand-built traces whose answers are worked
out by hand, and on the small trace recorded on a TPU v5e
(``record_program_trace.py``), whose programs carry no ``mamba2`` scope
and run no ``mamba2_scan`` kernel: there they read nothing."""
import json
from pathlib import Path

import pytest

import flops_hybrid
import harness
import program_trace as pt
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MODEL = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                    "granite-4.0-h-micro.json").read_text())["model"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    return harness.metric_reader(name)


def test_scope_pattern():
    pat = _reader("mamba_time_share").SCOPE
    hits = {t: bool(pat.search(t)) for t in (
        "jit(prefill)/while/body/mamba2/dot_general:",
        "jit(decode)/while/body/closed_call/checkpoint/mamba2/mul",
        "mamba2/exp", "jit(prefill)/while/body/mamba2:",
        "jit(prefill)/while/body/mlp/dot_general:",
        "jit(prefill)/mamba2_scan:", "jit(f)/xmamba2/add")}
    assert hits == {
        "jit(prefill)/while/body/mamba2/dot_general:": True,
        "jit(decode)/while/body/closed_call/checkpoint/mamba2/mul": True,
        "mamba2/exp": True, "jit(prefill)/while/body/mamba2:": True,
        "jit(prefill)/while/body/mlp/dot_general:": False,
        "jit(prefill)/mamba2_scan:": False, "jit(f)/xmamba2/add": False}


def test_time_share_by_hand():
    # device 0 runs prefill (id 3) once for 10 s and decode (id 5) twice
    # for 2 s each; a third program (id 9) is not serving
    m2 = "jit(prefill)/while/body/mamba2/"
    ops = [(3, m2 + "dot_general:", "fusion.1", 3.0),
           (3, m2 + "pallas_call:", "mamba2_scan.2", 1.0),
           (3, "jit(prefill)/while/body/mlp/dot_general:", "fusion.3", 4.0),
           (3, "jit(prefill)/while:", "while.4", 9.5),  # holds the others
           (5, "jit(decode)/while/body/mamba2/mul:", "fusion.5", 1.5),
           (5, "jit(decode)/while/body/attention/dot:", "fusion.6", 1.0),
           (9, "jit(other)/mamba2/add:", "add.7", 7.0)]
    modules = [("jit_prefill", 3, 10.0), ("jit_decode", 5, 2.0),
               ("jit_decode", 5, 2.0), ("jit_other", 9, 7.0)]
    got = _reader("mamba_time_share").split([("/device:TPU:0", ops, modules)])
    assert got == {"runs": 3, "program_s": 14.0, "scope_s": 5.5}
    # no op under the scope: nothing
    bare = [(p, t.replace("mamba2", "mlp"), o, s) for p, t, o, s in ops]
    assert _reader("mamba_time_share").split(
        [("/device:TPU:0", bare, modules)]) is None


def test_time_share_reads_the_window(tmp_path, monkeypatch):
    """``read`` finds the newest window trace; the recorded trace's serving
    programs have no ``mamba2`` scope (a granite-3 decoder), so it reads
    nothing, as it does with no trace at all."""
    monkeypatch.setattr(pt, "HERE", tmp_path)
    ctx = {"trace": {"window_s": 1.0, "modules": {}}}
    name = "mamba_time_share.hybrid"
    assert _reader(name).read(name, ctx) is None
    win = tmp_path / ".traces" / "granite4h-docs-offline" / "window"
    win.mkdir(parents=True)
    (win / "t.xplane.pb").write_bytes(
        (DATA / "program" / "program.xplane.pb").read_bytes())
    ctx["trace"] = tr.reduce_dir(win)
    assert ctx["trace"]["modules"]["jit_prefill"]["count"] > 0
    assert _reader(name).read(name, ctx) is None


def test_scan_roofline_by_hand():
    """Two prefills of 1024 and 4096 tokens, 36 Mamba layers each, against
    0.5 s of kernel time: the scan is bound by its bytes."""
    ctx = {"trace": {"ops": {"mamba2_scan.7": 0.3, "mamba2_scan.9": 0.2,
                             "flash_attention.1": 9.0}},
           "counts": {"prefill_lens": [1024, 4096]},
           "model": {"model": MODEL}, "peaks": PEAKS}
    least = 0.0
    for s in (1024, 4096):
        c = flops_hybrid.mamba2_scan_cost(MODEL, 1, s)
        assert c["bytes"] / 819e9 > c["flops"] / 197e12
        least += 36 * c["bytes"] / 819e9
    # 36 layers of both prompts' least time over the 0.5 s of kernel time
    got = _reader("mamba2_scan_roofline").read("mamba2_scan_roofline", ctx)
    assert got == pytest.approx(100 * least / 0.5)
    assert 0 < got < 100


def test_scan_roofline_reads_nothing_without_the_kernel():
    r = tr.reduce_dir(DATA / "program")
    ctx = {"trace": r, "counts": {"prefill_lens": [8, 12, 8]},
           "model": {"model": MODEL}, "peaks": PEAKS}
    assert not any("mamba2_scan" in k for k in r["ops"])
    assert _reader("mamba2_scan_roofline").read(
        "mamba2_scan_roofline", ctx) is None
    ctx["counts"]["prefill_lens"] = []
    ctx["trace"] = {"ops": {"mamba2_scan.1": 1.0}}
    assert _reader("mamba2_scan_roofline").read(
        "mamba2_scan_roofline", ctx) is None
