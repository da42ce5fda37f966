"""Launch-layer unit tests: HLO collective parser, roofline math, cell
enumeration, elastic replanning.  (The heavy lower+compile path is covered
by tests/test_dryrun_small.py in a subprocess.)"""
import json
from pathlib import Path

import pytest

from repro.launch.dryrun import collective_bytes_from_hlo, iter_cells
from repro.launch.roofline import (
    CHIPS,
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS,
    model_flops_per_device,
    roofline_for_cell,
)

REPO = Path(__file__).resolve().parent.parent

HLO_SAMPLE = """
  %p0 = bf16[4,512,128]{2,1,0} parameter(0)
  %fus = f32[16,4096]{1,0} fusion(%p0), kind=kLoop
  %ag.1 = bf16[4,1024,128]{2,1,0} all-gather(%p0), channel_id=1
  %ar = f32[16,4096]{1,0} all-reduce(%fus), to_apply=%add
  %rs.2 = f32[8,4096]{1,0} reduce-scatter(%ar), channel_id=3
  %cp = bf16[4,512,128]{2,1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  %a2a.7 = f32[16,4096]{1,0} all-to-all(%fus), channel_id=9
"""


class TestHloParser:
    def test_counts_and_operand_bytes(self):
        r = collective_bytes_from_hlo(HLO_SAMPLE)
        assert r["counts"] == {
            "all-gather": 1, "all-reduce": 1, "reduce-scatter": 1,
            "collective-permute": 1, "all-to-all": 1,
        }
        p0 = 4 * 512 * 128 * 2
        fus = 16 * 4096 * 4
        assert r["bytes_by_kind"]["all-gather"] == p0
        assert r["bytes_by_kind"]["all-reduce"] == fus
        assert r["bytes_by_kind"]["collective-permute"] == p0
        assert r["bytes_by_kind"]["all-to-all"] == fus
        # result bytes differ from operand bytes for gather/scatter
        assert r["result_bytes_by_kind"]["all-gather"] == 2 * p0
        assert r["result_bytes_by_kind"]["reduce-scatter"] == 8 * 4096 * 4

    def test_ignores_non_collectives(self):
        r = collective_bytes_from_hlo("%x = f32[2]{0} add(%a, %b)\n")
        assert r["total_bytes"] == 0 and not r["counts"]


class TestCellEnumeration:
    def test_31_runnable_9_skipped(self):
        cells = list(iter_cells())
        runnable = [c for c in cells if c[2]]
        skipped = [c for c in cells if not c[2]]
        # the ten assigned archs give 31; granite-4.0-h-micro runs all four
        # shapes (a hybrid runs long_500k)
        assert len(runnable) == 31 + 4
        assert len(skipped) == 9
        assert all(why for *_, why in skipped)


class TestRooflineMath:
    def _cell(self, flops=1e15, hbytes=1e12, cbytes=1e11):
        return {
            "ok": True, "arch": "qwen3-32b", "shape": "train_4k",
            "calibrated": {
                "flops": flops, "bytes_accessed": hbytes,
                "collective_bytes": cbytes,
            },
        }

    def test_terms_and_bottleneck(self):
        r = roofline_for_cell(self._cell())
        assert r.compute_s == pytest.approx(1e15 / PEAK_FLOPS)
        assert r.memory_s == pytest.approx(1e12 / HBM_BW)
        assert r.collective_s == pytest.approx(1e11 / ICI_BW)
        assert r.bottleneck == "compute"
        assert 0 < r.roofline_fraction <= 1.0

    def test_bottleneck_flips(self):
        r = roofline_for_cell(self._cell(flops=1e12, cbytes=1e13))
        assert r.bottleneck == "collective"
        assert r.roofline_fraction < 0.1

    def test_model_flops_scaling(self):
        train = model_flops_per_device("qwen3-32b", "train_4k")
        prefill = model_flops_per_device("qwen3-32b", "prefill_32k")
        decode = model_flops_per_device("qwen3-32b", "decode_32k")
        assert train == pytest.approx(3 * prefill)  # 6ND vs 2ND, same tokens
        assert decode < prefill / 1000  # 1 token vs 32768

    def test_failed_cell_returns_none(self):
        assert roofline_for_cell({"ok": False}) is None


class TestElasticReplan:
    def test_replan_adapts_to_world_size(self):
        from repro.runtime.trainer import replan

        p256 = replan(256, 4 * 2**20)
        p64 = replan(64, 4 * 2**20)
        import math

        assert math.prod(p256.factors) == 256
        assert math.prod(p64.factors) == 64
        assert p256.total_time_s > 0


class TestCommTelemetry:
    """launch/train.py emits per-plan comm telemetry every --log-every
    steps (ISSUE 5): cache counters + per-plan mode/chunks/order/issue
    counts, including the order-search verdict when the policy ran one.
    Exercised meshless (axis_sizes planning) — no devices needed."""

    def _ctx(self, **policy):
        from repro.comms.api import CommContext, PlanPolicy
        from repro.core.planner import LinkSpec

        links = {"a": LinkSpec("fast", 50e9, 1e-6),
                 "b": LinkSpec("slow", 1e9, 1e-5)}
        return CommContext(axis_names=("a", "b"), links=links,
                           axis_sizes={"a": 2, "b": 4},
                           policy=PlanPolicy(**policy))

    def test_lines_cover_cache_and_plans(self):
        from repro.launch.train import comm_plan_telemetry

        ctx = self._ctx()
        ctx.plan("ag", 2**20)
        ctx.plan("ar", 2**16)
        ctx.plan("ag", 2**20)  # hit
        lines = comm_plan_telemetry(ctx)
        assert lines[0].startswith("comm plans=2 ")
        assert "hits=1" in lines[0] and "misses=2" in lines[0]
        assert "latency_plans=" in lines[0] and "ring_plans=" in lines[0]
        # header + crossover note + one line per cached plan
        assert len(lines) == 4
        assert "regime crossover(ar)" in lines[1]
        ag_line = next(l for l in lines[2:] if l.strip().startswith("ag"))
        assert "order=[" in ag_line and "mode=" in ag_line
        assert "regime=bandwidth" in ag_line  # 1 MiB: rings win
        assert "issued=x2" in ag_line  # deduplicated plan, issued twice

    def test_order_search_verdict_surfaces(self):
        import dataclasses

        from repro.core.cost_model import TERARACK
        from repro.launch.train import comm_plan_telemetry

        sys2 = dataclasses.replace(TERARACK, n_nodes=8, wavelengths=2)
        ctx = self._ctx(order="optical", optical=sys2)
        ctx.plan("ag", 2**20)
        lines = comm_plan_telemetry(ctx)
        ag_line = next(l for l in lines[1:] if l.strip().startswith("ag"))
        assert "picked_by=optical" in ag_line
        assert "flipped=True" in ag_line  # asymmetric table: worlds disagree

    def test_invalidation_visible(self):
        from repro.core.planner import LinkSpec
        from repro.launch.train import comm_plan_telemetry

        ctx = self._ctx()
        ctx.plan("ag", 2**20)
        ctx.update_links({"a": LinkSpec("fitted", 40e9, 2e-6)})
        lines = comm_plan_telemetry(ctx)
        assert "invalidated=1" in lines[0]
        # cache dropped; no stale plan lines (crossover note remains)
        assert len(lines) == 2 and "crossover" in lines[1]

    def test_regime_telemetry_and_crossover(self):
        """Decode-size psums plan latency (exchange) plans, training-size
        payloads keep rings, and the telemetry reports the split plus the
        crossover payload between the two families (ISSUE 8)."""
        from repro.launch.train import comm_plan_telemetry

        ctx = self._ctx()
        small = ctx.plan("ar", 1024)        # decode-size: latency regime
        big = ctx.plan("ar", 2**20)         # training-size: rings
        assert small.meta["regime"] == "latency"
        assert all(s.mode == "exchange" for s in small.stages)
        assert big.meta["regime"] == "bandwidth"
        assert not any(s.mode == "exchange" for s in big.stages)
        st = ctx.cache_stats
        assert st.latency_plans == 1 and st.ring_plans == 1
        xover = ctx.latency_crossover("ar")
        assert xover is not None and 1024 <= xover <= 2**20
        lines = comm_plan_telemetry(ctx)
        assert "latency_plans=1" in lines[0] and "ring_plans=1" in lines[0]
        assert f"{xover:.0f}B" in lines[1]
        lat_line = next(l for l in lines[2:] if "regime=latency" in l)
        assert "mode=oneshot" in lat_line


class TestArtifacts:
    """The committed dry-run artifacts stay self-consistent."""

    def test_dryrun_artifacts_if_present(self):
        from pathlib import Path

        d = Path("runs/dryrun")
        if not d.exists():
            pytest.skip("no dry-run artifacts in this checkout")
        cells = [json.loads(p.read_text()) for p in d.glob("*__singlepod.json")]
        assert len(cells) == 31
        assert all(c["ok"] for c in cells)
        multien = list(d.glob("*__multipod.json"))
        assert len(multien) == 31


class TestChipEntry:
    """chip_smoke.py refuses to run anywhere but on a TPU, and the entry
    points' compile cache can be placed from outside."""

    def test_chip_smoke_fails_without_tpu(self):
        import os
        import subprocess
        import sys

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode != 0
        assert "no TPU" in proc.stderr
        assert '"ok"' not in proc.stdout

    def test_compile_cache_placement(self, monkeypatch, tmp_path):
        import importlib.util

        import jax
        import numpy as np

        from repro.launch import device

        prev = jax.config.jax_compilation_cache_dir
        key = "jax_compilation_cache_include_metadata_in_key"
        files = "jax_hlo_source_file_canonicalization_regex"
        prev_key, prev_files = getattr(jax.config, key), \
            getattr(jax.config, files)
        # one function, from the same source in two checkouts
        fns = []
        for where in ("one", "two/deeper"):
            d = tmp_path / where
            d.mkdir(parents=True)
            (d / "prog.py").write_text(
                "import jax\n\n\ndef f(x):\n"
                "    with jax.named_scope('stage0.x.oneshot'):\n"
                "        return x * 2\n")
            spec = importlib.util.spec_from_file_location(
                f"prog_{len(fns)}", d / "prog.py")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            fns.append(mod.f)

        def lowered(f):  # what the key hashes: the module with its metadata
            return jax.jit(f).lower(np.ones(4, np.float32)).as_text(
                debug_info=True)

        try:
            jax.config.update("jax_compilation_cache_dir", None)
            jax.config.update(key, False)
            jax.config.update(files, None)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert device.place_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir is None  # JAX's own
            # scopes are metadata: they must be part of the key
            assert getattr(jax.config, key) is True
            # and the checkout's place is not
            a, b = (lowered(f) for f in fns)
            assert "stage0.x.oneshot" in a and str(tmp_path) not in a
            assert a == b
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            assert device.place_compile_cache() == str(device.CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == str(device.CACHE_DIR)
            assert device.CACHE_DIR.parent == REPO
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
            jax.config.update(key, prev_key)
            jax.config.update(files, prev_files)
