"""Serving driver: a decoder LM behind the program's ``BatchedServer``.

Set-up makes the weights from the seed (the configuration's reference
module lays them out as the program serves them), builds the server with
the traffic's slots and ``max_seq``, and warms every
prompt length of the mix and the decode step: one request at each length,
two tokens each, then ``reset()``.

The window runs the traffic's schedule (``traffic.py``) on one thread:
requests are submitted when due, ``engine_step`` runs while there is work,
and the loop sleeps until the next due time when there is none.  Token
times are taken when ``engine_step`` returns.  A request is finished by
the driver when it has its own output length (the server has one
``max_new_tokens`` for all requests).  The window closes when the step
running at its end returns.

End-to-end metrics, all from the host clock:

* ``ttft_p50_ms``: median over every request due in the window of the time
  from when it was due to its first token; a request still waiting when
  the window closes counts with the time it has waited.
* ``itl_p95_ms``: 95th percentile of every gap between consecutive tokens
  of a request, both inside the window.
* ``output_tokens_per_s``: every token emitted in the window over the
  window.

``correct``: once the window has closed and the server is freed, a sample
drawn from the seed of the requests finished in the window, the longest
among them, is run through the plain reference over prompt plus served
tokens.  The number compared is the widest gap by which a served token's
reference logit lies below the reference's best at that position.  With
the control (``control.py``) the number compared is instead the gap of
the token that the float8 reference puts first at the same positions.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import flops  # noqa: E402
import traffic as traffic_gen  # noqa: E402


def program_config(model: Dict):
    """The program's ModelConfig with every size taken from the
    benchmark's configuration file."""
    from repro.configs import get_config

    cfg = get_config(model["program_config"])
    sizes = {k: model[k] for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "rope_theta", "norm_eps", "tie_embeddings",
        "dtype")}
    cfg = dataclasses.replace(cfg, **sizes)
    if cfg.padded_vocab != model["padded_vocab"]:
        raise SystemExit(f"program pads the vocabulary to {cfg.padded_vocab},"
                         f" the configuration says {model['padded_vocab']}")
    return cfg


def _percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


class _Book:
    """The driver's record of every request and token in the window."""

    def __init__(self, reqs, t0: float):
        self.reqs, self.t0 = reqs, t0
        self.rid_of: Dict[int, int] = {}  # request index -> server rid
        self.index_of: Dict[int, int] = {}
        self.seen: Dict[int, int] = {}
        self.first: Dict[int, float] = {}
        self.last: Dict[int, float] = {}
        self.gaps: List[float] = []
        self.tokens = 0
        self.done: List[int] = []  # request indices finished in the window
        self.prefill_lens: List[int] = []
        self.decode_flops = 0
        self.decode_bytes = 0
        self.late: List[float] = []
        self.t_exit = t0
        self.waiting_half = self.waiting_close = None

    def submitted(self, i: int, rid: int, now: float) -> None:
        self.rid_of[i], self.index_of[rid] = rid, i
        self.seen[rid] = 0
        self.late.append(now - (self.t0 + self.reqs[i].due_s))

    def step(self, server, model: Dict, t: float) -> None:
        """Account for the tokens one engine step emitted."""
        decoded_at: List[int] = []
        for rid in list(self.seen):
            rec = server.records[rid]
            new = rec.generated - self.seen[rid]
            if new <= 0:
                continue
            i = self.index_of[rid]
            plen = self.reqs[i].prompt_len
            for j in range(self.seen[rid] + 1, rec.generated + 1):
                if j == 1:
                    self.first[i] = t
                    self.prefill_lens.append(plen)
                else:
                    self.gaps.append(t - self.last.get(i, t))
                    pos = plen + j - 2  # cache position the decode wrote
                    decoded_at.append(pos)
                    self.decode_flops += flops.decode_token_flops(model, pos)
                self.last[i] = t
            self.tokens += new
            self.seen[rid] = rec.generated
        if decoded_at:
            self.decode_bytes += flops.decode_step_bytes(model, decoded_at)

    def finish_due(self, server) -> None:
        """Finish every slot whose request has its own output length."""
        for k, slot in enumerate(server.slots):
            rid = slot.request_id
            if rid is None:
                continue
            if len(slot.generated) >= self.reqs[self.index_of[rid]].output_len:
                server._finish_slot(k)
        for rid in [r for r in self.seen if server.records[r].finish_s]:
            self.done.append(self.index_of[rid])
            del self.seen[rid]


def _serve_window(run, server, reqs, prompts, model) -> Dict:
    clock = time.perf_counter
    n = len(reqs)
    with run.window() as t0:
        book = _Book(reqs, t0)
        end = t0 + run.seconds
        nxt = 0
        while True:
            now = clock()
            if now >= end:
                break
            if book.waiting_half is None and now >= t0 + run.seconds / 2:
                book.waiting_half = len(server.queue)
            while nxt < n and t0 + reqs[nxt].due_s <= now:
                with run.spans.span("submit"):
                    rid = server.submit(prompts[nxt])
                book.submitted(nxt, rid, now)
                nxt += 1
            if not server.pending_work():
                wake = end if nxt >= n else min(end, t0 + reqs[nxt].due_s)
                with run.spans.span("wait_for_request"):
                    time.sleep(max(0.0, wake - clock()))
                continue
            with run.spans.span("engine_step"):
                server.engine_step()
            t = clock()
            with run.spans.span("bookkeeping"):
                book.step(server, model, t)
                book.finish_due(server)
        book.t_exit = clock()
        book.waiting_close = len(server.queue)
    return book


def _warm(server, lengths) -> None:
    """Compile prefill at every prompt length of the mix, the decode step,
    the eager ops around them and the state reset."""
    for length in lengths:
        server.submit(np.zeros(length, np.int32))
        server.engine_step()  # prefill (token 1) and one decode (token 2)
        for k, slot in enumerate(server.slots):
            if slot.request_id is not None:
                server._finish_slot(k)
    server.reset()


def _sample(done: List[int], reqs, rng, min_tokens: int,
            max_requests: int) -> List[int]:
    """The longest finished request, then others drawn from the seed until
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    if not done:
        return []
    longest = max(done, key=lambda i: (reqs[i].output_len, reqs[i].prompt_len))
    rest = [i for i in rng.permutation(done) if i != longest]
    pick, tokens = [longest], reqs[longest].output_len
    for i in rest:
        if tokens >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(int(i))
        tokens += reqs[i].output_len
    return pick


def compare(reference, model: Dict, weights, items, max_seq: int,
            quant=None) -> Dict[str, float]:
    """Widest gap, over every served token of ``items`` ((prompt, served)
    pairs), between the reference's best logit and the logit of the served
    token; with ``quant`` also the gap of the token the lower-precision
    reference puts first at the same positions."""
    worst, worst_q, count = 0.0, 0.0, 0
    for prompt, served in items:
        toks = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        padded = np.zeros(max_seq, np.int32)
        padded[: len(toks)] = toks
        rows = slice(len(prompt) - 1, len(toks))
        ref = np.asarray(reference.logits(model, weights, padded))[rows]
        served = np.asarray(served)
        best = ref.max(-1)
        worst = max(worst, float(np.max(best - ref[np.arange(len(served)),
                                                   served])))
        if quant:
            low = np.asarray(reference.logits(model, weights, padded,
                                              quant=quant))[rows]
            pick = low.argmax(-1)
            worst_q = max(worst_q, float(np.max(best - ref[np.arange(len(pick)),
                                                           pick])))
        count += len(served)
    return {"gap": worst, "gap_control": worst_q, "tokens": count}


def setup(run):
    """Weights, server and schedule of a run: everything before the
    window."""
    import jax

    from repro.launch.device import select_kernel_backend
    from repro.runtime import BatchedServer, ServerConfig

    model = run.cell.config["model"]
    tr = run.cell.traffic
    backend = select_kernel_backend()
    cfg = program_config(model)
    reference = run.cell.reference()
    weights = reference.init_weights(model, run.seed)
    jax.block_until_ready(weights)
    run.mark("weights")
    jax.clear_caches()  # unload the init program before the server's load
    reqs = traffic_gen.schedule(tr, run.seconds)
    prompts = [traffic_gen.prompt_tokens(run.seed, i, r.prompt_len,
                                         model["vocab_size"])
               for i, r in enumerate(reqs)]
    server = BatchedServer(cfg, weights, ServerConfig(
        batch_size=run.cell.traffic["slots"], max_seq=tr["max_seq"],
        max_new_tokens=max(tr["output_tokens"]["values"])))
    run.mark("server")
    _warm(server, sorted(set(tr["prompt_tokens"]["values"])))
    run.mark("warm-up")
    return {"model": model, "reference": reference, "weights": weights,
            "reqs": reqs, "prompts": prompts, "server": server,
            "backend": backend}


def run(run) -> Dict:
    s = setup(run)
    model, reqs, prompts = s["model"], s["reqs"], s["prompts"]
    book = _serve_window(run, s["server"], reqs, prompts, model)
    peak = run.memory_peak_bytes()

    # results of the finished requests, then free the program's state
    served = {i: list(s["server"].results[book.rid_of[i]]) for i in book.done}
    del s["server"]
    gc.collect()

    window = book.t_exit - book.t0
    due = [i for i, r in enumerate(reqs) if book.t0 + r.due_s <= book.t_exit]
    ttft = [(book.first[i] if i in book.first else book.t_exit)
            - (book.t0 + reqs[i].due_s) for i in due]
    e2e = {"output_tokens_per_s": book.tokens / window}
    if ttft:
        e2e["ttft_p50_ms"] = 1e3 * _percentile(ttft, 50)
    if book.gaps:
        e2e["itl_p95_ms"] = 1e3 * _percentile(book.gaps, 95)

    lim = run.cell.limits
    rng = np.random.default_rng([run.seed % 2**64, 4])
    pick = _sample(book.done, reqs, rng, lim["sample_tokens"],
                   lim["sample_requests"])
    got = compare(s["reference"], model, s["weights"],
                  [(prompts[i], served[i]) for i in pick],
                  run.cell.traffic["max_seq"],
                  quant="fp8" if run.control else None)
    # the control stands in the program's place: its reading is compared
    gap = got["gap_control"] if run.control else got["gap"]
    checks = {"logit_gap": {"value": gap if pick else float("nan"),
                            "limit": lim["logit_gap"]}}
    late = np.asarray(book.late) if book.late else np.zeros(1)
    notes = [
        f"kernels {s['backend']}; requests due {len(due)}, submitted "
        f"{len(book.rid_of)}, finished {len(book.done)}; tokens "
        f"{book.tokens}; generator lateness mean {late.mean() * 1e3:.3f} ms "
        f"max {late.max() * 1e3:.3f} ms; requests waiting for a slot at "
        f"half the window {book.waiting_half}, at its close "
        f"{book.waiting_close}",
        f"compared {len(pick)} requests, {got['tokens']} served tokens; "
        f"program's logit gap {got['gap']!r}"
        + (f", fp8 control's {got['gap_control']!r}" if run.control else ""),
    ] + [f"{k} {v!r}" for k, v in e2e.items()]
    counts = {"tokens": book.tokens, "prefill_lens": book.prefill_lens,
              "decode_flops": book.decode_flops,
              "decode_bytes": book.decode_bytes}
    out = {"e2e": e2e, "attempted": len(due), "failed": 0,
           "checks": checks, "memory_peak_bytes": peak, "counts": counts,
           "notes": notes}
    return out
