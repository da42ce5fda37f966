"""Batched serving runtime: continuous batching over a fixed slot pool.

Requests (prompt token arrays) queue up; the server keeps ``batch_size``
decode slots. Each engine step decodes one token for every active slot;
finished slots (EOS or max_new_tokens) are immediately refilled from the
queue — the standard continuous-batching pattern (vLLM-style, cache-slot
granularity) built on ``models.decode_step``.

Prefill is per-request: it runs over the whole slot batch with the prompt
in the slot's lane, and only that lane of the new cache is kept.  Decode
is one call per engine step for every active slot, each lane at its own
cache position (``decode_step`` with a ``(B,)`` position); the lanes of
empty slots keep their cache.

Each engine step, prefill, decode call and token read runs in a host span
(``repro.obs.span``: ``server.engine_step`` holding ``server.prefill`` and
``server.decode``, each holding one ``server.fetch``), and
:class:`ServerStats` counts them; ``docs/serving.md`` says how to trace a
live server and read both.

Every request carries a :class:`RequestTiming` record (enqueue /
prefill-start / prefill-done / decode-start / finish, on the server's
``clock``), exposed per request in :meth:`BatchedServer.drain_report` —
the measured counterpart of the cluster simulator's event timestamps
(``repro.cluster.sim``), so simulated and measured latency distributions
compare field-for-field.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs.base import ModelConfig
from ..models import decode_step, forward, init_decode_state

__all__ = ["ServerConfig", "BatchedServer", "RequestTiming", "ServerStats"]


@dataclass(frozen=True)
class ServerConfig:
    batch_size: int = 4
    max_seq: int = 128
    max_new_tokens: int = 16
    eos_id: int = -1  # -1: disabled (synthetic vocab has no real EOS)
    # keep the float32 logits row of every emitted token, per request
    # (BatchedServer.logits) — for checks against a reference forward
    keep_logits: bool = False


@dataclass
class RequestTiming:
    """Per-request phase timestamps on the server's clock (seconds).

    ``decode_start_s`` stays None for single-token requests (the prefill
    emits token 1, so a ``max_new_tokens=1`` request never decodes)."""

    rid: int
    prompt_tokens: int
    enqueue_s: float
    prefill_start_s: Optional[float] = None
    prefill_done_s: Optional[float] = None
    decode_start_s: Optional[float] = None
    finish_s: Optional[float] = None
    generated: int = 0

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.finish_s is None else self.finish_s - self.enqueue_s

    @property
    def ttft_s(self) -> Optional[float]:
        """Time to first token (the prefill's argmax is token 1)."""
        if self.prefill_done_s is None:
            return None
        return self.prefill_done_s - self.enqueue_s

    @property
    def queue_s(self) -> Optional[float]:
        if self.prefill_start_s is None:
            return None
        return self.prefill_start_s - self.enqueue_s

    def to_json(self) -> Dict[str, Any]:
        return {
            "rid": self.rid, "prompt_tokens": self.prompt_tokens,
            "enqueue_s": self.enqueue_s,
            "prefill_start_s": self.prefill_start_s,
            "prefill_done_s": self.prefill_done_s,
            "decode_start_s": self.decode_start_s,
            "finish_s": self.finish_s, "generated": self.generated,
        }


@dataclass
class ServerStats:
    """Work counters since the server was built or last reset: engine
    steps, prefill calls, decode calls (one per engine step that decodes)
    and tokens emitted (one per prefill, one per decoded lane)."""

    engine_steps: int = 0
    prefill_calls: int = 0
    decode_calls: int = 0
    tokens: int = 0

    def to_json(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


@dataclass
class _Slot:
    request_id: Optional[int] = None
    pos: int = 0
    generated: List[int] = field(default_factory=list)


def _percentile(vals: List[float], p: float) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), p)) if vals else 0.0


class BatchedServer:
    def __init__(self, cfg: ModelConfig, params, scfg: ServerConfig,
                 *, clock: Callable[[], float] = time.perf_counter):
        self.cfg, self.params, self.scfg = cfg, params, scfg
        self.clock = clock
        self.state = init_decode_state(cfg, scfg.batch_size, scfg.max_seq)
        self.slots = [_Slot() for _ in range(scfg.batch_size)]
        self.queue: collections.deque = collections.deque()
        self.results: Dict[int, List[int]] = {}
        self.records: Dict[int, RequestTiming] = {}
        self.logits: Dict[int, List[np.ndarray]] = {}
        self._next_id = 0
        self._tokens = np.zeros((scfg.batch_size, 1), np.int32)
        self.stats = ServerStats()

        def decode(p, state, tokens, pos, lanes):
            return decode_step(cfg, p, state, tokens, pos, cache_lanes=lanes)

        def prefill(p, batch, state, lanes):
            logits, new, _ = forward(cfg, p, batch, cache=state,
                                     cache_pos=jnp.zeros((), jnp.int32),
                                     cache_lanes=lanes)
            return logits, new

        # one cached jit each — a fresh lambda per request would recompile
        # every prefill (it retraces only per distinct prompt length); the
        # old state is donated, since every call replaces it
        self._decode = jax.jit(decode, donate_argnums=1)
        self._prefill = jax.jit(prefill, donate_argnums=2)

    # ---- API -------------------------------------------------------------
    def submit(self, prompt: np.ndarray) -> int:
        rid = self._next_id
        self._next_id += 1
        prompt = np.asarray(prompt, np.int32)
        self.queue.append((rid, prompt))
        self.records[rid] = RequestTiming(
            rid=rid, prompt_tokens=len(prompt), enqueue_s=self.clock())
        return rid

    def reset(self) -> None:
        """Return the server to its just-constructed state: drain any
        in-flight work (finishing it cleanly rather than abandoning slots
        mid-decode), then clear the queue, results, timing records and the
        request-id counter and the :class:`ServerStats`, and zero the
        decode state.  The compiled
        decode/prefill jits are KEPT — a reset server re-serves warm,
        which is the point of resetting instead of rebuilding (e.g. the
        cluster front end re-running a trace under a different routing
        policy on the same replicas)."""
        if self.pending_work():
            self.run_until_drained()
        self.queue.clear()
        self.results.clear()
        self.records.clear()
        self.logits.clear()
        self._next_id = 0
        self.slots = [_Slot() for _ in range(self.scfg.batch_size)]
        self.state = init_decode_state(
            self.cfg, self.scfg.batch_size, self.scfg.max_seq)
        self._tokens = np.zeros((self.scfg.batch_size, 1), np.int32)
        self.stats = ServerStats()

    def active_count(self) -> int:
        """Occupied decode slots (the scheduler's in-flight signal)."""
        return sum(1 for s in self.slots if s.request_id is not None)

    def pending_work(self) -> bool:
        return bool(self.queue) or self.active_count() > 0

    def _lanes(self, idxs: List[int]) -> jax.Array:
        lanes = np.zeros((self.scfg.batch_size,), bool)
        lanes[idxs] = True
        return jnp.asarray(lanes)

    def _prefill_into_slot(self, slot_idx: int, rid: int, prompt: np.ndarray):
        """Run the prompt through the model writing KV/state for this slot."""
        rec = self.records[rid]
        rec.prefill_start_s = self.clock()
        S = len(prompt)
        # batch the prompt across the full slot dim (only slot_idx's lanes
        # are kept — simple and correct; per-slot cache views are a perf
        # optimization on real hardware)
        with obs.span("server.prefill", rid=rid, slot=slot_idx, len=S):
            toks = np.zeros((self.scfg.batch_size, S), np.int32)
            toks[slot_idx] = prompt
            logits, self.state = self._prefill(
                self.params, {"tokens": jnp.asarray(toks)}, self.state,
                self._lanes([slot_idx]))
            with obs.span("server.fetch"):
                nxt = int(jnp.argmax(logits[slot_idx, -1]))
            if self.scfg.keep_logits:
                self.logits.setdefault(rid, []).append(
                    np.asarray(logits[slot_idx, -1], np.float32))
        self.stats.prefill_calls += 1
        self.stats.tokens += 1
        slot = self.slots[slot_idx]
        slot.request_id = rid
        slot.pos = S
        slot.generated = [nxt]
        self._tokens[slot_idx, 0] = nxt
        rec.prefill_done_s = self.clock()
        rec.generated = 1
        if self.scfg.max_new_tokens <= 1 or nxt == self.scfg.eos_id:
            self._finish_slot(slot_idx)

    def _finish_slot(self, slot_idx: int):
        slot = self.slots[slot_idx]
        rec = self.records[slot.request_id]
        rec.finish_s = self.clock()
        rec.generated = len(slot.generated)
        self.results[slot.request_id] = slot.generated
        self.slots[slot_idx] = _Slot()

    def _refill(self):
        for i, slot in enumerate(self.slots):
            if slot.request_id is None and self.queue:
                rid, prompt = self.queue.popleft()
                self._prefill_into_slot(i, rid, prompt)

    def engine_step(self):
        self.stats.engine_steps += 1
        with obs.span("server.engine_step", step=self.stats.engine_steps):
            self._refill()
            active = [i for i, s in enumerate(self.slots)
                      if s.request_id is not None]
            if active:
                self._decode_slots(active)

    def _decode_slots(self, idxs: List[int]):
        """One decode call for the slots ``idxs``, each at its own cache
        position; the other lanes pass position 0 and keep their cache."""
        step_start = self.clock()
        pos = np.zeros((self.scfg.batch_size,), np.int32)
        pos[idxs] = [self.slots[i].pos for i in idxs]
        with obs.span("server.decode", max_pos=int(pos.max()),
                      lanes=len(idxs)):
            logits, self.state = self._decode(
                self.params, self.state, jnp.asarray(self._tokens),
                jnp.asarray(pos), self._lanes(idxs),
            )
            with obs.span("server.fetch"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            rows = (np.asarray(logits, np.float32) if self.scfg.keep_logits
                    else None)
        self.stats.decode_calls += 1
        self.stats.tokens += len(idxs)
        for i in idxs:
            slot = self.slots[i]
            rec = self.records[slot.request_id]
            if rec.decode_start_s is None:
                rec.decode_start_s = step_start
            tok = int(nxt[i])
            if rows is not None:
                self.logits.setdefault(slot.request_id, []).append(rows[i])
            slot.generated.append(tok)
            slot.pos += 1
            self._tokens[i, 0] = tok
            rec.generated = len(slot.generated)
            done = (
                len(slot.generated) >= self.scfg.max_new_tokens
                or tok == self.scfg.eos_id
                or slot.pos >= self.scfg.max_seq - 1
            )
            if done:
                self._finish_slot(i)

    def run_until_drained(self, max_steps: int = 1000) -> Dict[int, List[int]]:
        steps = 0
        while (self.queue or any(s.request_id is not None for s in self.slots)):
            self.engine_step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("server did not drain")
        return self.results

    def drain_report(self) -> Dict[str, Any]:
        """Per-request timestamps + aggregate latency/throughput stats for
        every finished request — the measured record the cluster layer
        compares against simulated :class:`~repro.cluster.sim.ClusterStats`.
        Aggregate-only stats block simulator-vs-measured validation; this
        report keeps every phase timestamp per request."""
        done = [r for r in self.records.values() if r.finish_s is not None]
        lat = [r.latency_s for r in done]
        ttft = [r.ttft_s for r in done if r.ttft_s is not None]
        toks = sum(r.generated for r in done)
        span = (max(r.finish_s for r in done) - min(r.enqueue_s for r in done)
                if done else 0.0)
        return {
            "requests": len(done),
            "tokens": toks,
            "makespan_s": span,
            "throughput_tok_s": (toks / span) if span > 0 else 0.0,
            "latency_p50_s": _percentile(lat, 50),
            "latency_p99_s": _percentile(lat, 99),
            "ttft_p50_s": _percentile(ttft, 50),
            "per_request": [r.to_json() for r in sorted(
                done, key=lambda r: r.rid)],
        }
