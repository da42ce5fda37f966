"""The control, the plain reference computed in the precision below the
configuration's (float8 for bfloat16) in the program's place, must come
out not correct under each cell's limits.  On the chip ``control.py`` runs
it through the whole benchmark at the cells' own sizes; here at a size a
test run can hold."""
import subprocess
import sys
from pathlib import Path

import numpy as np

import _cpu
import harness
import run as bench

HERE = Path(__file__).resolve().parent


def _serve():
    return harness.load_module(HERE.parent / "drivers" / "serve.py")


def test_serving_control_fails_the_limit():
    """granite-3-2b at its published widths and vocabulary, four layers:
    the fp8 reference's first token, at every position of random token
    sequences, against the float32 reference's best logit."""
    cell = harness.load_cell("granite-chat")
    ref = cell.reference()
    m = dict(cell.config["model"], num_layers=4)
    weights = ref.init_weights(m, 3_000_000_031)
    rng = np.random.default_rng(5)
    items = [(rng.integers(0, m["vocab_size"], 128).astype(np.int32),
              rng.integers(0, m["vocab_size"], 256).astype(np.int32))
             for _ in range(3)]
    got = _serve().compare(ref, m, weights, items, 384, quant="fp8")
    for name in ("granite-chat", "granite-docs-offline"):
        checks = {"logit_gap": {"value": got["gap_control"],
                                "limit": harness.load_cell(name).limits[
                                    "logit_gap"]}}
        assert bench.judge(checks) is False, (name, got)


def test_serving_control_is_what_a_control_run_compares():
    """With the control in the program's place the control's reading, not
    the program's, is the number judged: a control that puts the worst
    token first makes the run not correct, the same run without it is
    correct."""
    cell = _cpu.small_cell("granite-docs-offline")
    ref = cell.reference()
    logits = ref.logits

    def worst_first(model, weights, tokens, quant=None):
        out = logits(model, weights, tokens, quant=quant)
        return -out if quant else out

    ref.logits = worst_first
    cell.reference = lambda: ref
    assert _cpu.execute(cell)["correct"] is True
    res = _cpu.execute(cell, control=True)
    assert res["correct"] is False, res["checks"]


def test_sync_control_fails_the_limit():
    proc = subprocess.run(
        [sys.executable, str(HERE / "_sync_fault.py"), "control"],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().splitlines()[-1] == "correct False", \
        proc.stdout[-2000:]
