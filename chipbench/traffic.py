"""Request schedules, read from a traffic file (``traffic/<name>.json``).

One generator serves every serving mix.  A mix names its arrivals, its
length distributions and the seed of its order:

    {"arrival": {"kind": "poisson", "rate_per_s": 0.3},
     "prompt_tokens": {"values": [128, 256], "weights": [0.6, 0.4]},
     "output_tokens": {"values": [32, 64], "weights": [0.5, 0.5]},
     "block": 20, "order_seed": 1}

* ``poisson``: open-loop arrivals at ``rate_per_s``.  The inter-arrival gaps
  are the quantiles of the exponential distribution at that rate for the
  ``round(rate * seconds)`` requests due in the window, shuffled (the
  arrival arithmetic of ``repro.cluster.traces``, with the draws replaced
  by quantiles).
* ``backlog``: ``jobs`` requests, all due at the window's start.

Lengths come in blocks of ``block`` requests, each block holding every
length in proportion to its weight (largest remainder), shuffled.

The shuffles draw from the mix's ``order_seed``, not from the run's seed:
a window holds a few tens of requests, and their order alone decides how
many tokens fall inside it (on a TPU v5e, six seeds that reordered the
chat mix read 22.4-32.1 tokens/s; two runs of one seed agreed within
0.5%).  The
run's seed draws the prompts' tokens (``prompt_tokens``) and the weights.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

__all__ = ["Request", "block_counts", "schedule", "prompt_tokens"]


@dataclass(frozen=True)
class Request:
    due_s: float  # from the window's start
    prompt_len: int
    output_len: int


def block_counts(weights, block: int) -> List[int]:
    """Largest-remainder split of ``block`` slots by ``weights``."""
    w = np.asarray(weights, np.float64)
    if w.ndim != 1 or w.size == 0 or np.any(w < 0) or w.sum() <= 0:
        raise ValueError(f"bad weights {weights!r}")
    share = w / w.sum() * block
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts), kind="stable")[: block - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def _lengths(rng: np.random.Generator, spec: Dict, n: int,
             block: int) -> np.ndarray:
    one = np.repeat(np.asarray(spec["values"], np.int64),
                    block_counts(spec["weights"], block))
    blocks = [rng.permutation(one) for _ in range(math.ceil(n / block))]
    return np.concatenate(blocks)[:n]


def _rng(seed: int, stream: int) -> np.random.Generator:
    # any whole number, negative or past 64 bits, seeds the same way
    return np.random.default_rng([int(seed) % 2**64, stream])


def schedule(traffic: Dict, seconds: float) -> List[Request]:
    """The requests of a window of ``seconds``, sorted by due time."""
    seed = int(traffic["order_seed"])
    arrival = traffic["arrival"]
    kind = arrival["kind"]
    if kind == "poisson":
        rate = float(arrival["rate_per_s"])
        n = max(1, round(rate * seconds))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / rate
        gaps = _rng(seed, 0).permutation(gaps)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    elif kind == "backlog":
        n = int(arrival["jobs"])
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    block = int(traffic["block"])
    prompts = _lengths(_rng(seed, 1), traffic["prompt_tokens"], n, block)
    outputs = _lengths(_rng(seed, 2), traffic["output_tokens"], n, block)
    return [Request(float(d), int(p), int(o))
            for d, p, o in zip(due, prompts, outputs)]


def prompt_tokens(seed: int, index: int, length: int,
                  vocab: int) -> np.ndarray:
    """Token ids of request ``index``'s prompt: uniform over the vocabulary,
    from the run's seed and the index alone."""
    rng = np.random.default_rng([int(seed) % 2**64, 3, index])
    return rng.integers(0, vocab, size=length, dtype=np.int64).astype(np.int32)
