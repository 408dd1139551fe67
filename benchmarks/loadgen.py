"""The general traffic generators: a traffic file's parameters and a
seed in, a fixed schedule out. Pure functions of (parameters, seed,
seconds): the same seed gives the same requests at the same due times.

Every seed gets the SAME multiset of prompt lengths and the SAME arrival
gaps, in another order — a seed must not change the amount of work, only
its order and its token values (the builder's contract: "give every seed
the same set of sizes and arrivals, in another order"). The lengths and
gaps are the quantiles of their distributions (stratified), drawn once
per traffic file from ``base_seed``, then permuted by the run's seed.
"""
from __future__ import annotations

import math

import numpy as np


def _rng(*ints) -> np.random.Generator:
    # SeedSequence takes any non-negative ints; run seeds pass 2**31
    return np.random.default_rng(np.random.SeedSequence(
        [int(i) & 0xFFFFFFFFFFFFFFFF for i in ints]))


def _lognormal_quantiles(n: int, median: float, sigma: float,
                         lo: int, hi: int) -> np.ndarray:
    """``n`` stratified quantiles of a log-normal, clipped to [lo, hi]."""
    from statistics import NormalDist
    nd = NormalDist()
    qs = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(q)) for q in qs])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def _exponential_quantiles(n: int, mean: float) -> np.ndarray:
    """``n`` stratified quantiles of an exponential with that mean,
    rescaled so they sum to exactly n x mean (the window always holds
    the same number of arrivals)."""
    qs = (np.arange(n) + 0.5) / n
    gaps = -mean * np.log1p(-qs)
    return gaps * (n * mean / gaps.sum())


def open_loop_schedule(traffic: dict, vocab: int, seed: int,
                       seconds: float) -> list[dict]:
    """Requests of an open-loop mix due in [0, seconds): a list of
    ``{"id", "due_s", "prompt"}`` sorted by due time. ``prompt`` is a
    list of 1-based token ids (the program's LookupTable convention).

    Traffic keys: ``rate_rps``; ``arrivals`` ("poisson" = exponential
    gaps, "uniform" = even gaps); ``prompt_len`` = {"dist": "lognormal",
    "median", "sigma", "min", "max"} or {"dist": "fixed", "value"};
    ``shared_prefix_tokens`` (default 0): that many leading tokens are
    common to every request of the run.
    """
    rate = float(traffic["rate_rps"])
    n = max(1, int(math.floor(rate * seconds)))
    mean_gap = seconds / n
    order = _rng(seed, 1)
    if traffic.get("arrivals", "poisson") == "poisson":
        gaps = _exponential_quantiles(n, mean_gap)
        gaps = gaps[order.permutation(n)]
    else:
        gaps = np.full(n, mean_gap)
    # the first request is due half a mean gap in; the last lies inside
    # the window because the gaps sum to exactly `seconds`
    due = np.cumsum(gaps) - gaps[0] + 0.5 * min(gaps[0], mean_gap)
    due = np.minimum(due, np.nextafter(seconds, 0))
    spec = traffic["prompt_len"]
    if spec["dist"] == "lognormal":
        lens = _lognormal_quantiles(n, spec["median"], spec["sigma"],
                                    spec["min"], spec["max"])
    elif spec["dist"] == "fixed":
        lens = np.full(n, int(spec["value"]))
    else:
        raise ValueError(f"prompt_len dist {spec['dist']!r}")
    lens = lens[order.permutation(n)]
    toks = _rng(seed, 2)
    shared = int(traffic.get("shared_prefix_tokens", 0))
    prefix = toks.integers(1, vocab + 1, size=shared).tolist()
    out = []
    for i in range(n):
        body = toks.integers(1, vocab + 1,
                             size=max(int(lens[i]) - shared, 1)).tolist()
        out.append({"id": i, "due_s": float(due[i]),
                    "prompt": (prefix + body)[:max(int(lens[i]), 1)]})
    return out


def prompt_buckets(traffic: dict, bucket_of) -> list[int]:
    """The distinct prefill buckets this traffic can produce, given the
    program's bucketing rule: what warm-up must compile and no more."""
    spec = traffic["prompt_len"]
    lo, hi = ((spec["min"], spec["max"]) if spec["dist"] == "lognormal"
              else (spec["value"], spec["value"]))
    buckets, n = [], int(lo)
    while True:
        b = bucket_of(n)
        buckets.append(b)
        if b >= hi:
            return buckets
        n = b + 1


def train_batches(vocab: int, batch: int, seq: int, seed: int):
    """An endless iterator of fresh (data, labels) next-token batches:
    ``batch`` sequences of ``seq + 1`` uniform tokens from the seed, data
    = the first ``seq``, labels = the last ``seq`` (1-based ids)."""
    rng = _rng(seed, 3)
    while True:
        toks = rng.integers(1, vocab + 1, size=(batch, seq + 1),
                            dtype=np.int64).astype(np.int32)
        yield toks[:, :-1], toks[:, 1:]
