"""Cluster serving subsystem tests (ISSUE 9): seeded-trace determinism,
simulator determinism (bit-identical event logs), makespan monotone in
arrival rate (deterministic grid + optional hypothesis), routing-policy
ordering on heterogeneous replicas, BatchedServer per-request timestamps,
and the measured-vs-simulated 2-replica validation."""
import dataclasses
import json

import numpy as np
import pytest

from repro.cluster import (
    BYTES_PER_TOKEN,
    ClusterServer,
    ClusterSim,
    ReplicaSpec,
    Request,
    bursty_trace,
    make_policy,
    make_trace,
    measure_replica_times,
    poisson_trace,
    replay_trace,
    trace_to_json,
)
from repro.core.planner import DCN_LINK, ICI_LINK

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def hetero_specs(batch=4):
    """The canonical fast+slow pair: 4x decode-step gap, different links."""
    return [
        ReplicaSpec.from_times("fast", batch, prefill_token_s=1e-4,
                               decode_step_s=5e-4, link=ICI_LINK),
        ReplicaSpec.from_times("slow", batch, prefill_token_s=4e-4,
                               decode_step_s=2e-3, link=DCN_LINK),
    ]


class TestTraces:
    def test_same_seed_bit_identical(self):
        for gen in (lambda s: poisson_trace(32, rate_rps=100.0, seed=s),
                    lambda s: bursty_trace(32, rate_rps=100.0, burst=4,
                                           seed=s)):
            a, b = gen(7), gen(7)
            assert a == b  # frozen dataclasses: full field equality
            assert gen(7) != gen(8)

    def test_arrivals_sorted_rids_in_order(self):
        t = bursty_trace(20, rate_rps=50.0, burst=3, seed=1)
        assert [r.rid for r in t] == list(range(20))
        assert all(t[i].arrival_s <= t[i + 1].arrival_s
                   for i in range(len(t) - 1))

    def test_bursts_share_instants(self):
        t = bursty_trace(12, rate_rps=100.0, burst=4, seed=0)
        instants = {r.arrival_s for r in t}
        assert len(instants) == 3  # 12 requests / burst 4

    def test_same_seed_rate_scaling(self):
        """Same seed at 2x the rate: arrivals exactly halve (the coupling
        the monotonicity property rides on); shapes unchanged."""
        lo = poisson_trace(16, rate_rps=50.0, seed=3)
        hi = poisson_trace(16, rate_rps=100.0, seed=3)
        for a, b in zip(lo, hi):
            assert b.arrival_s == pytest.approx(a.arrival_s / 2.0, rel=1e-12)
            assert (a.prompt_tokens, a.new_tokens) == \
                (b.prompt_tokens, b.new_tokens)

    def test_replay_round_trip(self, tmp_path):
        t = poisson_trace(10, rate_rps=30.0, seed=2)
        p = tmp_path / "trace.json"
        p.write_text(json.dumps(trace_to_json(t)))
        assert replay_trace(str(p)) == t

    def test_make_trace_specs(self):
        assert make_trace("poisson:100", n=8, seed=1) == \
            poisson_trace(8, rate_rps=100.0, seed=1)
        assert make_trace("bursty:100,2", n=8, seed=1) == \
            bursty_trace(8, rate_rps=100.0, burst=2, seed=1)
        with pytest.raises(ValueError):
            poisson_trace(4, rate_rps=0.0, seed=0)


class TestSimDeterminism:
    @pytest.mark.parametrize("tname,trace", [
        ("poisson", poisson_trace(48, rate_rps=200.0, seed=11)),
        ("bursty", bursty_trace(48, rate_rps=200.0, burst=4, seed=11)),
    ])
    @pytest.mark.parametrize("policy", ["round-robin", "jsq", "greedy",
                                        "max-flow"])
    def test_bit_identical_event_log_and_stats(self, tname, trace, policy):
        runs = []
        for _ in range(2):
            sim = ClusterSim(hetero_specs(), make_policy(policy))
            st_ = sim.run(trace)
            runs.append((list(sim.event_log),
                         json.dumps(st_.to_json(), sort_keys=True)))
        assert runs[0][0] == runs[1][0]
        assert runs[0][1] == runs[1][1]

    def test_worlds_price_differently_but_route_deterministically(self):
        trace = poisson_trace(32, rate_rps=200.0, seed=4)
        for world in ("electrical", "optical"):
            a = ClusterSim(hetero_specs(), make_policy("greedy"), world=world)
            b = ClusterSim(hetero_specs(), make_policy("greedy"), world=world)
            assert a.run(trace).to_json() == b.run(trace).to_json()

    def test_all_requests_finish_with_full_timestamps(self):
        st_ = ClusterSim(hetero_specs(), make_policy("jsq")).run(
            bursty_trace(24, rate_rps=150.0, burst=3, seed=9))
        assert len(st_.records) == 24
        for r in st_.records:
            assert r.enqueue_s is not None
            assert r.arrival_s <= r.enqueue_s <= r.prefill_start_s \
                <= r.prefill_done_s <= r.finish_s
            if r.new_tokens > 1:
                assert r.prefill_done_s <= r.decode_start_s <= r.finish_s


class TestMonotoneMakespan:
    def _makespan(self, rate, seed=13, n=40):
        trace = poisson_trace(n, rate_rps=rate, seed=seed)
        return ClusterSim(hetero_specs(),
                          make_policy("round-robin")).run(trace).makespan_s

    def test_monotone_in_rate_grid(self):
        """Same seed => time-scaled arrivals; with arrival-order routing
        and work-conserving FIFO replicas, compressing the arrivals can
        never stretch the makespan."""
        for seed in (0, 7, 21):
            prev = None
            for rate in (25.0, 50.0, 100.0, 200.0, 400.0, 800.0):
                m = self._makespan(rate, seed=seed)
                if prev is not None:
                    assert m <= prev + 1e-12, (seed, rate)
                prev = m

    if HAVE_HYPOTHESIS:
        @settings(max_examples=25, deadline=None)
        @given(seed=st.integers(0, 2**20),
               rate=st.floats(10.0, 500.0),
               factor=st.floats(1.1, 8.0))
        def test_monotone_in_rate_property(self, seed, rate, factor):
            assert self._makespan(rate * factor, seed=seed) <= \
                self._makespan(rate, seed=seed) + 1e-12


class TestPolicyOrdering:
    def test_greedy_strictly_beats_round_robin_p99(self):
        """The acceptance criterion: on a seeded heterogeneous trace the
        cost-model-aware policy strictly beats round-robin on simulated
        p99 — under BOTH cost worlds."""
        trace = poisson_trace(64, rate_rps=200.0, seed=7)
        for world in ("electrical", "optical"):
            rr = ClusterSim(hetero_specs(), make_policy("round-robin"),
                            world=world).run(trace)
            gr = ClusterSim(hetero_specs(), make_policy("greedy"),
                            world=world).run(trace)
            assert gr.latency_p99_s() < rr.latency_p99_s(), world
            assert gr.routed["fast"] > rr.routed["fast"]

    def test_max_flow_spreads_bursts_within_capacity(self):
        """On simultaneous-arrival bursts the flow round must not overfill
        any replica while free slots exist elsewhere: a burst the size of
        the total free slots lands split, not piled on one replica."""
        specs = hetero_specs(batch=4)
        trace = bursty_trace(8, rate_rps=50.0, burst=8, seed=3)
        sim = ClusterSim(specs, make_policy("max-flow"))
        st_ = sim.run(trace)
        assert st_.routed["fast"] >= 4 and st_.routed["slow"] >= 1
        assert st_.latency_p99_s() <= ClusterSim(
            specs, make_policy("round-robin")).run(trace).latency_p99_s() + 1e-12

    def test_jsq_balances_in_flight(self):
        trace = bursty_trace(16, rate_rps=100.0, burst=4, seed=5)
        st_ = ClusterSim(hetero_specs(), make_policy("jsq")).run(trace)
        assert st_.routed["fast"] > 0 and st_.routed["slow"] > 0

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError):
            make_policy("nope")


class TestReplicaSpec:
    def test_from_times_calibration(self):
        s = ReplicaSpec.from_times("r", 2, prefill_token_s=1e-3,
                                   decode_step_s=4e-3)
        assert s.prefill_time_s(8) == pytest.approx(8e-3)
        # single-token prompts never prefill faster than one engine step
        assert s.prefill_time_s(1) == pytest.approx(4e-3)
        assert s.decode_step_time_s(1) == pytest.approx(4e-3)
        assert s.decode_step_time_s(2) == pytest.approx(4e-3)  # memory-bound
        req = Request(rid=0, arrival_s=0.0, prompt_tokens=8, new_tokens=5)
        assert s.request_service_s(req) == pytest.approx(8e-3 + 4 * 4e-3)

    def test_from_config_uses_roofline(self):
        from repro.configs import get_config, reduced
        from repro.launch.roofline import decode_step_time_s, prefill_time_s

        cfg = reduced(get_config("granite-3-2b"))
        s = ReplicaSpec.from_config("r", cfg, 4)
        assert s.prefill_time_s(64) == pytest.approx(prefill_time_s(cfg, 64))
        assert s.decode_step_time_s(2) == pytest.approx(
            decode_step_time_s(cfg, 2))

    def test_tx_pricing_worlds(self):
        spec = hetero_specs()[0]
        sim_e = ClusterSim([spec], make_policy("round-robin"))
        sim_o = ClusterSim([spec], make_policy("round-robin"),
                           world="optical")
        nbytes = 64 * BYTES_PER_TOKEN
        assert sim_e.tx_time_s(spec, nbytes) == pytest.approx(
            ICI_LINK.alpha_s + nbytes / ICI_LINK.bandwidth_bytes)
        from repro.core.cost_model import TERARACK, step_time
        assert sim_o.tx_time_s(spec, nbytes) == pytest.approx(
            step_time(TERARACK, nbytes))


# ---------------------------------------------------------------------------
# measured side: BatchedServer timestamps + the 2-replica validation
# ---------------------------------------------------------------------------

def tiny_cfg(layers=2, d_ff=64):
    from repro.configs import get_config, reduced

    return dataclasses.replace(
        reduced(get_config("granite-3-2b")), num_layers=layers, d_model=32,
        num_heads=2, num_kv_heads=2, head_dim=16, d_ff=d_ff, vocab_size=128,
    )


class TestServerTimestamps:
    def test_phase_timestamps_ordered(self):
        import jax
        from repro.models import init_params
        from repro.runtime import BatchedServer, ServerConfig

        cfg = tiny_cfg()
        srv = BatchedServer(cfg, init_params(jax.random.key(0), cfg),
                            ServerConfig(batch_size=2, max_seq=32,
                                         max_new_tokens=4))
        rng = np.random.default_rng(0)
        rids = [srv.submit(rng.integers(0, cfg.vocab_size, size=6))
                for _ in range(3)]
        srv.run_until_drained()
        rep = srv.drain_report()
        assert rep["requests"] == 3 and rep["tokens"] == 12
        assert rep["latency_p99_s"] >= rep["latency_p50_s"] > 0
        assert len(rep["per_request"]) == 3
        for rid in rids:
            t = srv.records[rid]
            assert t.enqueue_s <= t.prefill_start_s <= t.prefill_done_s \
                <= t.decode_start_s <= t.finish_s
            assert t.generated == 4
            assert t.ttft_s >= 0 and t.queue_s >= 0

    def test_reset_returns_server_to_fresh_state(self):
        """Public reset (ISSUE 10): drains in-flight work, clears queue/
        results/records/ids and the decode state, keeps the compiled jits
        — a reset server re-serves identically from rid 0."""
        import jax
        from repro.models import init_params
        from repro.runtime import BatchedServer, ServerConfig

        cfg = tiny_cfg()
        srv = BatchedServer(cfg, init_params(jax.random.key(0), cfg),
                            ServerConfig(batch_size=2, max_seq=32,
                                         max_new_tokens=4))
        prompt = np.arange(6, dtype=np.int32)
        rid = srv.submit(prompt)
        srv.run_until_drained()
        first = list(srv.results[rid])
        srv.submit(prompt)  # left in flight: reset must drain, not abandon
        srv.reset()
        assert not srv.pending_work()
        assert srv.results == {} and srv.records == {}
        assert srv.active_count() == 0
        rid2 = srv.submit(prompt)
        assert rid2 == 0  # id space restarts
        srv.run_until_drained()
        # same prompt on the reset (zeroed-state) server decodes the same
        assert srv.results[rid2] == first

    @pytest.mark.parametrize("family", ["dense", "ssm", "hybrid"])
    def test_mixed_positions_keep_each_slots_cache(self, family):
        """A request served beside a shorter prompt keeps the cache and the
        logits it has when served alone: a decode step at one slot's
        position keeps its new cache only on that position's lanes.
        Compares KV (or recurrent state) and logits, never tokens — with
        random weights greedy decoding repeats one token and would hide a
        corrupted cache."""
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config, reduced
        from repro.models import forward, init_params
        from repro.runtime import BatchedServer, ServerConfig

        arch = {"ssm": "rwkv6-7b", "hybrid": "zamba2-2.7b"}.get(family)
        cfg = reduced(get_config(arch)) if arch else tiny_cfg()
        params = init_params(jax.random.key(3), cfg)
        scfg = ServerConfig(batch_size=2, max_seq=32, max_new_tokens=6,
                            keep_logits=True)
        rng = np.random.default_rng(4)
        long_p = rng.integers(0, cfg.vocab_size, size=9).astype(np.int32)
        short_p = rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)

        def serve(prompts, steps=3):
            srv = BatchedServer(cfg, params, scfg)
            for p in prompts:
                srv.submit(p)
            for _ in range(steps):
                srv.engine_step()
            lane0 = jax.tree.map(lambda a: np.asarray(a[:, 0]), srv.state)
            return srv, lane0

        alone, state_alone = serve([long_p])
        mixed, state_mixed = serve([long_p, short_p])
        assert mixed.slots[0].pos != mixed.slots[1].pos
        for a, b in zip(jax.tree.leaves(state_alone),
                        jax.tree.leaves(state_mixed)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        got = np.stack(mixed.logits[0])
        np.testing.assert_allclose(got, np.stack(alone.logits[0]),
                                   rtol=1e-5, atol=1e-5)
        # and both equal a no-cache forward over the emitted tokens
        toks = np.concatenate([long_p, mixed.slots[0].generated[:-1]])
        ref, _, _ = forward(cfg, params, {"tokens": jnp.asarray(toks[None])})
        want = np.asarray(ref[0, len(long_p) - 1:])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_single_token_request_finishes_at_prefill(self):
        import jax
        from repro.models import init_params
        from repro.runtime import BatchedServer, ServerConfig

        cfg = tiny_cfg()
        srv = BatchedServer(cfg, init_params(jax.random.key(0), cfg),
                            ServerConfig(batch_size=2, max_seq=32,
                                         max_new_tokens=1))
        rid = srv.submit(np.arange(5, dtype=np.int32))
        srv.run_until_drained()
        t = srv.records[rid]
        assert t.finish_s is not None and t.decode_start_s is None
        assert len(srv.results[rid]) == 1

    @staticmethod
    def _counted_server(batch_size, max_new_tokens):
        """A tiny server whose decode calls are recorded as
        ``(engine step, positions, lanes)``."""
        import jax
        from repro.models import init_params
        from repro.runtime import BatchedServer, ServerConfig

        cfg = tiny_cfg()
        srv = BatchedServer(cfg, init_params(jax.random.key(0), cfg),
                            ServerConfig(batch_size=batch_size, max_seq=32,
                                         max_new_tokens=max_new_tokens))
        calls = []
        jitted = srv._decode

        def counted(p, state, tokens, pos, lanes):
            calls.append((srv.stats.engine_steps, np.asarray(pos),
                          np.asarray(lanes)))
            return jitted(p, state, tokens, pos, lanes)

        srv._decode = counted
        return srv, calls

    def test_slots_at_distinct_positions_decode_in_one_call(self):
        """Three slots at three cache positions: one decode call, each lane
        at its own position, every slot's token emitted."""
        srv, calls = self._counted_server(batch_size=4, max_new_tokens=8)
        for n in (5, 7, 9):
            srv.submit(np.arange(n, dtype=np.int32))
        srv.engine_step()
        assert len(calls) == 1
        _, pos, lanes = calls[0]
        assert pos.shape == (4,) and pos.dtype == np.int32
        assert pos.tolist() == [5, 7, 9, 0]
        assert lanes.tolist() == [True, True, True, False]
        assert [len(s.generated) for s in srv.slots[:3]] == [2, 2, 2]
        assert [s.pos for s in srv.slots[:3]] == [6, 8, 10]
        assert srv.stats.decode_calls == 1 and srv.stats.tokens == 6

    def test_decode_calls_count_steps_that_decode(self):
        """Over a drained run with slots refilled at staggered positions,
        ``ServerStats.decode_calls`` equals the engine steps that decoded,
        each made exactly one decode call, and every decoded lane is one
        token."""
        srv, calls = self._counted_server(batch_size=2, max_new_tokens=4)
        for n in (5, 9, 6, 11, 3):
            srv.submit(np.arange(n, dtype=np.int32))
        srv.run_until_drained()
        steps = [c[0] for c in calls]
        assert len(set(steps)) == len(steps)  # one call a step
        assert srv.stats.decode_calls == len(steps) == srv.stats.engine_steps
        assert any(len(set(c[1][c[2]].tolist())) > 1 for c in calls)
        lanes = sum(int(c[2].sum()) for c in calls)
        assert lanes == srv.stats.tokens - srv.stats.prefill_calls == 5 * 3


class TestClusterServerMeasured:
    def test_measured_ordering_matches_simulation(self):
        """Acceptance: a 2-replica ClusterServer run on host meshes gives
        measured per-request latencies whose greedy-vs-round-robin p99
        ordering matches the simulator's prediction (underloaded regime —
        see docs/serving.md for why ordering, not absolute times, is the
        validated signal).

        The two sides are deliberately decoupled (ISSUE 10): the SIM side
        runs on FIXED synthetic ``ReplicaSpec.from_times`` constants — the
        simulator's greedy < round-robin prediction is a property of the
        model, not of this host's wall clock, so it must hold on every
        seed deterministically.  Only the MEASURED side uses
        ``measure_replica_times`` wall-clock constants (that's the signal
        being validated), with a seed-retry loop absorbing host noise."""
        import jax
        from repro.models import init_params
        from repro.runtime import BatchedServer, ServerConfig

        fast_cfg, slow_cfg = tiny_cfg(2), tiny_cfg(24, d_ff=512)
        fp = init_params(jax.random.key(0), fast_cfg)
        sp = init_params(jax.random.key(1), slow_cfg)
        scfg = ServerConfig(batch_size=2, max_seq=64, max_new_tokens=6)
        pf, df = measure_replica_times(fast_cfg, fp, scfg, prompt_tokens=8,
                                       warmup=2)
        ps, ds = measure_replica_times(slow_cfg, sp, scfg, prompt_tokens=8,
                                       warmup=2)
        assert ds > df  # structurally slower replica measures slower
        mspecs = [
            ReplicaSpec.from_times("fast", 2, prefill_token_s=pf,
                                   decode_step_s=df),
            ReplicaSpec.from_times("slow", 2, prefill_token_s=ps,
                                   decode_step_s=ds),
        ]
        sim_specs = hetero_specs(batch=2)
        probe = Request(rid=0, arrival_s=0.0, prompt_tokens=8, new_tokens=6)
        sim_rate = 0.25 / sim_specs[1].request_service_s(probe)
        rate = 0.25 / mspecs[1].request_service_s(probe)
        attempts = []
        for seed in (5, 17, 29):
            # sim side: synthetic constants, deterministic on EVERY seed
            sim_trace = poisson_trace(12, rate_rps=sim_rate, seed=seed,
                                      prompt_tokens=(8, 8), new_tokens=(6, 6))
            sim_p99 = {
                pol: ClusterSim(sim_specs,
                                make_policy(pol)).run(sim_trace).latency_p99_s()
                for pol in ("round-robin", "greedy")}
            assert sim_p99["greedy"] < sim_p99["round-robin"], (seed, sim_p99)
            # measured side: wall clock — accept the first seed whose
            # measured ordering matches (the strict one-shot gate lives in
            # `launch/perf.py --cluster`)
            trace = poisson_trace(12, rate_rps=rate, seed=seed,
                                  prompt_tokens=(8, 8), new_tokens=(6, 6))
            meas_p99 = {}
            for pol in ("round-robin", "greedy"):
                servers = [BatchedServer(fast_cfg, fp, scfg),
                           BatchedServer(slow_cfg, sp, scfg)]
                for srv in servers:  # warm jits out of the measured window
                    srv.submit(np.arange(8, dtype=np.int32) % 128)
                    srv.run_until_drained()
                    srv.reset()
                cs = ClusterServer(servers, mspecs, make_policy(pol))
                meas = cs.run_trace(trace, prompts=[
                    np.arange(r.prompt_tokens, dtype=np.int32) % 128
                    for r in trace])
                assert len(meas.records) == len(trace)
                for r in meas.records:
                    assert r.finish_s is not None and r.latency_s > 0
                meas_p99[pol] = meas.latency_p99_s()
            attempts.append({"sim": sim_p99, "measured": meas_p99})
            if meas_p99["greedy"] < meas_p99["round-robin"]:
                break
        else:
            pytest.fail(f"measured ordering never matched sim: {attempts}")

    def test_results_and_routing_accounting(self):
        import jax
        from repro.models import init_params
        from repro.runtime import BatchedServer, ServerConfig

        cfg = tiny_cfg()
        params = init_params(jax.random.key(0), cfg)
        scfg = ServerConfig(batch_size=2, max_seq=32, max_new_tokens=3)
        specs = [ReplicaSpec.from_times(f"r{i}", 2, prefill_token_s=1e-4,
                                        decode_step_s=1e-3)
                 for i in range(2)]
        servers = [BatchedServer(cfg, params, scfg) for _ in range(2)]
        cs = ClusterServer(servers, specs, make_policy("round-robin"))
        gids = cs.submit_batch([np.arange(4, dtype=np.int32)
                                for _ in range(4)])
        res = cs.run_until_drained()
        assert sorted(res) == sorted(gids)
        assert all(len(v) == 3 for v in res.values())
        assert cs.routed == {"r0": 2, "r1": 2}  # round-robin striping
        rep = cs.drain_report()
        assert rep.total_tokens() == 12
        assert set(rep.to_json()["routed"]) == {"r0", "r1"}
