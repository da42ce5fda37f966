"""The reduction from trace to metrics, on small traces whose answers are
worked out by hand."""
from pathlib import Path

import pytest

import trace_reduce as tr


def test_union_and_gaps():
    busy = tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert busy == [(0, 3), (5, 7)]
    assert tr.gaps(busy, 0, 10) == [(3, 5), (7, 10)]
    assert tr.gaps(busy, 1, 6) == [(3, 5)]


def test_program_name():
    assert tr.program_name("jit_decode(123)") == "jit_decode"
    assert tr.program_name("jit_prefill") == "jit_prefill"


def _two_devices():
    # device 0: two decode programs and one prefill; device 1: one decode
    d0 = {"ops": [("fusion.1", 1.0, 2.0), ("flash_kernel", 1.5, 2.5),
                  ("fusion.1", 4.0, 5.0), ("fusion.2", 7.0, 8.0)],
          "modules": [("jit_decode", 1.0, 2.5), ("jit_decode", 4.0, 5.0),
                      ("jit_prefill", 7.0, 8.0)]}
    d1 = {"ops": [("fusion.1", 2.0, 4.0)],
          "modules": [("jit_decode", 2.0, 4.0)]}
    return {"/device:TPU:0": d0, "/device:TPU:1": d1}


def test_reduce_planes():
    spans = [("chipbench.window", 0.0, 10.0),
             ("chipbench.engine_step", 0.5, 5.5),
             ("chipbench.wait_for_request", 5.5, 6.5),
             ("chipbench.engine_step", 6.5, 8.5)]
    r = tr.reduce_planes(_two_devices(), spans)
    assert r["window_s"] == 10.0
    # device 0 busy [1, 2.5] + [4, 5] + [7, 8] = 3.5; device 1: 2
    assert r["busy_s"] == pytest.approx((3.5 + 2.0) / 2)
    assert r["modules"]["jit_decode"]["count"] == pytest.approx(3 / 2)
    assert r["modules"]["jit_decode"]["seconds"] == pytest.approx(4.5 / 2)
    assert r["modules"]["jit_prefill"]["seconds"] == pytest.approx(0.5)
    assert r["ops"]["flash_kernel"] == pytest.approx(0.5)
    assert r["top_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    # device 0 idle: [0, 1] [2.5, 4] [5, 7] [8, 10] = 6.5 s, charged by
    # overlap: engine_step 0.5 + 1.5 + (0.5 + 0.5) + 0.5, wait 1.0, the
    # rest (0.5 before the first step, 1.5 after the last) between spans
    idle = dict(r["idle_gaps"])
    assert idle["chipbench.engine_step"] == pytest.approx(3.5)
    assert idle["chipbench.wait_for_request"] == pytest.approx(1.0)
    assert idle["between benchmark spans"] == pytest.approx(2.0)
    assert sum(idle.values()) == pytest.approx(10.0 - 3.5)


def test_window_clips_events():
    r = tr.reduce_planes(_two_devices(), [], window=(1.5, 4.5))
    # device 0: [1.5, 2.5] + [4, 4.5]; device 1: [2, 4]
    assert r["busy_s"] == pytest.approx((1.5 + 2.0) / 2)
    assert r["window_s"] == pytest.approx(3.0)
    # programs are counted where they start inside the window
    assert r["modules"]["jit_decode"]["count"] == pytest.approx(2 / 2)


def test_op_name():
    assert tr.op_name("%flash_attention_pallas.6 = bf16[256,1024,64] "
                      "custom-call(bf16[256,1024,64] %bitcast.167)") == \
        "flash_attention_pallas.6"


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e: inside a ``chipbench.window`` span,
    three ``jit_decode`` calls, each in a ``chipbench.engine_step`` span and
    followed by a 20 ms ``chipbench.wait_for_request`` sleep, then one
    ``jit_prefill``.  The profiler caught two of the three decodes."""
    r = tr.reduce_dir(Path(__file__).resolve().parent / "data")
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.065178966)
    assert r["modules"]["jit_decode"]["count"] == 2
    assert r["modules"]["jit_prefill"]["count"] == 1
    busy = r["busy_s"]
    assert 0 < busy < sum(m["seconds"] for m in r["modules"].values()) + 1e-9
    assert busy == pytest.approx(3.703e-05, rel=1e-3)
    # the device is idle for all but ~37 us: almost all of it charged to
    # the three sleeps, the rest to the steps around the programs
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - busy)
    assert idle["chipbench.wait_for_request"] > 0.06
    assert set(idle) <= {"chipbench.wait_for_request",
                         "chipbench.engine_step", "between benchmark spans"}
    assert r["top_ops"][0][0] == "convolution_tanh_fusion"
