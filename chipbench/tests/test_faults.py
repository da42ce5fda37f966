"""A run with the timed path broken underneath must come out not correct,
once for each fault the cell can have.  Serving: a token altered where it
is produced.  Gradient sync: the exchange between chips left out, and an
answer altered where it is produced.  Each is checked against a sound run
of the same small cell, which must come out correct."""
import subprocess
import sys
from pathlib import Path

import pytest

import _cpu

HERE = Path(__file__).resolve().parent


@pytest.mark.parametrize("cell", ["granite-chat", "granite-docs-offline"])
def test_serving_altered_token(cell, monkeypatch):
    from repro.runtime import BatchedServer

    assert _cpu.execute(_cpu.small_cell(cell))["correct"]
    step = BatchedServer.engine_step
    emitted = {"n": 0}

    def altered(self):
        step(self)
        for slot in self.slots:  # every seventh token emitted is another
            if slot.request_id is not None and slot.generated:
                emitted["n"] += 1
                if emitted["n"] % 7 == 0:
                    slot.generated[-1] = (slot.generated[-1] + 1) % 1000

    monkeypatch.setattr(BatchedServer, "engine_step", altered)
    res = _cpu.execute(_cpu.small_cell(cell))
    assert emitted["n"] >= 7
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["sound", "no_exchange", "altered"])
def test_sync_faults(fault):
    """In a child process: the sync needs four devices, and the test
    process keeps one."""
    proc = subprocess.run(
        [sys.executable, str(HERE / "_sync_fault.py"), fault],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last == ("correct True" if fault == "sound" else "correct False"), \
        proc.stdout[-2000:]
