"""Open-loop request schedules made from a traffic file and a seed.

`prompt_tokens` is copied from the program's `repro/data/workloads.py`,
and `pad_to_bucket` from `_pad_to_bucket` in `repro/launch/serve.py`, so
that a later change there cannot move the yardstick. The rest is the
benchmark's own.

Every seed gets the same sizes and arrival times, in the same order; the
seed draws each request's topic and prompt tokens (and the weights).
Prompt and output lengths are the quantiles of a log-uniform law, and the
gaps between arrivals the quantiles of the exponential law at the file's
rate (a Poisson process's gaps), each put once in an order drawn from
`ORDER` in which every run of `BLOCK` consecutive entries holds one value
from each quarter of the sorted values. A 51 s window holds about six
arrivals at this system's speed: an order drawn from the run's seed moved
the prompt tokens arriving in the window by a fifth from seed to seed, and
the window's numbers by 11-40%, the draw's spread and not the system's.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

BLOCK = 4
ORDER = 0


def prompt_tokens(topic: int, length: int, vocab_size: int,
                  rng: np.random.Generator, n_topics: int = 4,
                  zipf: float = 1.2) -> np.ndarray:
    """Topic-blocked Zipf token ids for a request."""
    block = max(2, vocab_size // n_topics)
    ranks = np.arange(1, block + 1, dtype=np.float64)
    p = 1.0 / ranks ** zipf
    p /= p.sum()
    base = rng.choice(block, p=p, size=length)
    return ((topic % n_topics) * block + base).astype(np.int32) % vocab_size


def pad_to_bucket(toks: np.ndarray, bucket: int = 16) -> np.ndarray:
    """Right-pad prompts to bucket multiples to bound prefill recompiles."""
    T = len(toks)
    padded = ((T + bucket - 1) // bucket) * bucket
    if padded == T:
        return toks
    return np.concatenate([toks, np.zeros(padded - T, toks.dtype)])


def log_uniform(n: int, lo: int, hi: int) -> np.ndarray:
    """The n quantiles (i + 1/2) / n of a log-uniform law on [lo, hi],
    rounded to whole numbers."""
    u = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
                   ).astype(np.int64)


def exp_gaps(n: int, rate_rps: float) -> np.ndarray:
    """The n quantiles (i + 1/2) / n of the exponential law of mean 1/rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate_rps


def spread_order(values: np.ndarray, rng: np.random.Generator,
                 k: int = BLOCK) -> np.ndarray:
    """`values` in an order drawn from `rng` in which every run of about k
    consecutive entries holds one value from each of the k bands of the
    sorted values."""
    v = np.sort(values)
    key = np.empty(len(v))
    for band in np.array_split(np.arange(len(v)), k):
        key[band] = rng.permutation(len(band)) + rng.random(len(band))
    return v[np.argsort(key, kind="stable")]


def n_requests(traffic: Dict, seconds: float, rate_rps: float) -> int:
    """Requests enough to keep arrivals coming through the ramp and the
    window, with a margin."""
    span = traffic["ramp_s"] + seconds
    return int(math.ceil(rate_rps * span * 1.1)) + 4


def schedule(traffic: Dict, seed: int, vocab_size: int, n: int,
             rate_rps: float) -> List[Dict]:
    """n requests: arrival time (s from the start of serving), prompt token
    ids and output length. rate_rps = inf puts every arrival at 0."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(ORDER)        # the same for every seed
    gaps = spread_order(exp_gaps(n - 1, rate_rps), order)
    plo, phi = traffic["prompt_len"]
    olo, ohi = traffic["output_len"]
    n_topics = traffic["topics"]
    plen = spread_order(log_uniform(n, plo, phi), order)
    olen = spread_order(log_uniform(n, olo, ohi), order)
    topics = rng.permutation(np.arange(n) % n_topics)
    if math.isinf(rate_rps):
        arrivals = np.zeros(n)
    else:
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)])
    out = []
    for i in range(n):
        toks = prompt_tokens(int(topics[i]), int(plen[i]), vocab_size, rng,
                             n_topics, traffic["zipf"])
        out.append({"arrival_s": float(arrivals[i]),
                    "prompt": pad_to_bucket(toks, traffic["pad_bucket"]),
                    "max_new": int(olen[i])})
    return out


def max_seq(traffic: Dict) -> int:
    """Cache positions the longest request needs."""
    b = traffic["pad_bucket"]
    return -(-traffic["prompt_len"][1] // b) * b + traffic["output_len"][1]
