"""The one traffic generator: reads a mix's parameters and makes its requests
from the seed.

Every seed gets the same work in a different order. Sizes are drawn by
stratified quantiles: each block of ``block`` requests holds the same
``block`` quantiles of the length distributions, shuffled by the seed, so
any prefix of whole blocks has the same composition whatever the seed.
Inter-arrival gaps and profile ranks are the stratified quantiles of the
whole stream, shuffled; the rank-to-profile mapping is a seeded
permutation. Prompt token ids are uniform over the vocabulary.

A mix file (``bench/traffic/<name>.json``)::

    {"driver": "serve_open_loop",
     "arrivals": {"process": "poisson", "rate_per_s": 12.0},
     "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.7,
                       "min": 32, "max": 896},
     "output_tokens": {"dist": "uniform", "min": 16, "max": 128},
     "profiles": {"count": 1024, "popularity": "zipf", "s": 1.0},
     "block": 32}

``arrivals.process`` is ``poisson`` (open loop: requests are due over the
window at the rate, rounded to whole blocks) or ``backlog`` (every request
is due at once; ``max_rate_per_s`` bounds how many the window can finish,
so the queue never empties).
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

from bench.model import host_rng

# host streams of one seed (bench.model.host_rng)
_STREAM_SIZES, _STREAM_GAPS, _STREAM_PROFILES, _STREAM_TOKENS = 10, 11, 12, 13


@dataclass
class Arrival:
    uid: int
    due_s: float                # seconds after the window opens
    prompt: np.ndarray          # [T] int32
    max_new: int
    profile: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _inverse(dist: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def _stratified(dist: dict, n: int, block: int,
                rng: np.random.Generator) -> np.ndarray:
    per_block = _inverse(dist, _quantiles(block))
    out = [rng.permutation(per_block) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n]


def request_count(mix: dict, seconds: float, rate_scale: float = 1.0) -> int:
    """Whole blocks of requests: the offered rate (or, for a backlog, its
    bound) times the window, to the nearest block."""
    arr = mix["arrivals"]
    rate = arr["rate_per_s"] if arr["process"] == "poisson" \
        else arr["max_rate_per_s"]
    block = mix.get("block", 32)
    return block * max(1, int(round(rate * rate_scale * seconds / block)))


def profile_ranks(prof: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    P = prof["count"]
    u = _quantiles(n)
    if prof["popularity"] == "uniform":
        ranks = np.floor(u * P).astype(np.int64)
    elif prof["popularity"] == "zipf":
        w = np.arange(1, P + 1, dtype=np.float64) ** -prof["s"]
        cdf = np.cumsum(w) / w.sum()
        ranks = np.searchsorted(cdf, u)
    else:
        raise ValueError(f"unknown popularity {prof['popularity']!r}")
    return rng.permutation(np.minimum(ranks, P - 1))


def rank_order(prof: dict, seed: int) -> np.ndarray:
    """rank -> profile id, a seeded permutation (rank 0 most popular)."""
    return host_rng(seed, _STREAM_PROFILES + 100).permutation(prof["count"])


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             rate_scale: float = 1.0) -> List[Arrival]:
    """The requests of one run, in due order. ``rate_scale`` multiplies an
    open-loop mix's rate (the knee sweep)."""
    arr = mix["arrivals"]
    block = mix.get("block", 32)
    n = request_count(mix, seconds, rate_scale)
    r_sizes = host_rng(seed, _STREAM_SIZES)
    prompt = _stratified(mix["prompt_tokens"], n, block, r_sizes)
    out = _stratified(mix["output_tokens"], n, block, r_sizes)
    if arr["process"] == "poisson":
        # n exponential gaps spanning the window: the first request is due
        # as it opens, the last one gap before it closes
        gaps = -np.log1p(-_quantiles(n))
        gaps = host_rng(seed, _STREAM_GAPS).permutation(gaps)
        gaps *= seconds / gaps.sum()
        due = np.cumsum(gaps) - gaps
    elif arr["process"] == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    ranks = profile_ranks(mix["profiles"], n, host_rng(seed,
                                                       _STREAM_PROFILES))
    pid = rank_order(mix["profiles"], seed)[ranks]
    r_tok = host_rng(seed, _STREAM_TOKENS)
    return [Arrival(uid=i, due_s=float(due[i]),
                    prompt=r_tok.integers(0, vocab, int(prompt[i]),
                                          dtype=np.int32),
                    max_new=int(out[i]), profile=int(pid[i]))
            for i in range(n)]


def hottest_profiles(mix: dict, seed: int, count: int) -> List[int]:
    """The ``count`` most popular profile ids, most popular first."""
    order = rank_order(mix["profiles"], seed)
    return [int(p) for p in order[:min(count, len(order))]]

