"""Open-loop traffic from a mix file and a seed.

A mix file (``bench/traffic/<mix>.json``) holds only parameters:

    rate_per_s       requests offered per second of the run (fixed; for
                     open-loop traffic found once by a knee sweep on the
                     chip, see PERF.md)
    arrivals         "poisson" (default): open loop at ``rate_per_s``;
                     "backlog": every request is due at once
    prompt_tokens    {"dist": "log_uniform", "lo": a, "hi": b} or
                     {"dist": "fixed", "tokens": n}
    output_tokens    same form
    check_tokens     served tokens the correctness sample must cover
    check_requests   most requests in that sample

A run of ``seconds`` offers ``n = round(rate * seconds)`` requests.  Their
lengths are a fixed quantile ladder of the stated distribution (the i-th of
n takes the ``(i + 0.5) / n`` quantile), and their gaps the same ladder of
the exponential distribution at the mix's rate (all 0 for a backlog).  The
three ladders are paired in one order, drawn from a stream no seed
changes, so every seed offers the same schedule; the seed draws the prompt
token ids.  With a few requests in a window the order alone decides which
request arrives while another decodes, and so whether its first token
waits behind decode steps: a seed that reordered them would change the
work, not only its content.  The warm-up draws from a stream no run uses.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np

RUN_STREAM, WARMUP_STREAM, SAMPLE_STREAM, ORDER_STREAM = 0, 1, 2, 3


@dataclasses.dataclass
class Offer:
    """One request as the generator offers it."""
    uid: int
    arrival_s: float
    prompt: np.ndarray          # (prompt_len,) int32
    max_new: int


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one (seed, stream); seeds of any sign and size."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


def ladder(dist: Dict, n: int) -> np.ndarray:
    """The n-point quantile ladder of a length distribution (int)."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "fixed":
        return np.full(n, int(dist["tokens"]), np.int64)
    if dist["dist"] != "log_uniform":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    lo, hi = math.log(dist["lo"]), math.log(dist["hi"])
    return np.rint(np.exp(lo + u * (hi - lo))).astype(np.int64)


def gap_ladder(rate: float, n: int) -> np.ndarray:
    """The n-point quantile ladder of exponential gaps at ``rate``."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def num_requests(mix: Dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def offers(mix: Dict, seconds: float, seed: int, vocab: int,
           rate: float = 0.0) -> List[Offer]:
    """The run's requests, arrivals in order; ``rate`` overrides the mix's
    (the knee sweep)."""
    rate = rate or mix["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    order = rng(0, ORDER_STREAM)
    prompts = order.permutation(ladder(mix["prompt_tokens"], n))
    outs = order.permutation(ladder(mix["output_tokens"], n))
    gaps = order.permutation(gap_ladder(rate, n))
    g = rng(seed, RUN_STREAM)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    if mix.get("arrivals", "poisson") == "backlog":
        arrivals = np.zeros(n)
    elif mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    return [Offer(i, float(arrivals[i]),
                  g.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                  int(outs[i])) for i in range(n)]


def warmup_offers(mix: Dict, seconds: float, vocab: int, buckets,
                  max_new: int = 2, n: int = 0) -> List[Offer]:
    """One request per sequence bucket the run's ladder reaches, at the
    longest ladder length in that bucket, all due at once: the warm-up
    compiles every program the window will use and no other.  ``n``
    overrides the ladder's size (the knee sweep's fixed request count)."""
    lengths = ladder(mix["prompt_tokens"], n or num_requests(mix, seconds))
    by_bucket: Dict[int, int] = {}
    for n in lengths:
        b = next((b for b in sorted(buckets) if n <= b), max(buckets))
        by_bucket[b] = max(by_bucket.get(b, 0), int(min(n, b)))
    g = rng(0, WARMUP_STREAM)
    return [Offer(i, 0.0, g.integers(0, vocab, n, dtype=np.int32), max_new)
            for i, (_, n) in enumerate(sorted(by_bucket.items()))]
