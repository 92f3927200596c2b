"""The one traffic generator: reads a mix's data file and a seed.

Every seed offers the same requests in the same order: each size and
arrival gap is the ((i + 0.5) / n)-quantile of its distribution, put in
one fixed shuffled order, and the seed draws only the token ids.  So
two seeds offer the same work, and a window of fixed length sees the
same requests whatever the seed.

Mix file keys (``bench/traffic/<name>.json``):

    arrivals  "poisson" (open loop: exponential gaps at the cell's rate)
              | "closed" (``batch`` requests queued at t=0, more than the
                window serves, so that the slots never run dry)
    prompt, output   {"dist": "lognormal", "median", "sigma", "min", "max"}
    temperature      0.0 (greedy)
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

#: seed of the one fixed order of sizes and gaps, shared by every run
ORDER_SEED = 0


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The n stratified quantiles of a length distribution, as ints."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
    v = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(v, dist["min"], dist["max"]).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """The n stratified quantiles of an exponential gap at ``rate``/s."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def generate(mix: Dict[str, Any], seed: int, vocab: int, n: int,
             rate: float = 0.0) -> List[Dict[str, Any]]:
    """``n`` requests: {"prompt", "max_new", "arrival", "temperature"}.

    Open loop: arrivals are the cumulative sums of the shuffled gaps,
    the first at t=0.  Closed loop: every arrival is 0."""
    order = np.random.default_rng(ORDER_SEED)
    prompts = order.permutation(quantiles(mix["prompt"], n))
    outs = order.permutation(quantiles(mix["output"], n))
    if mix["arrivals"] == "poisson":
        g = order.permutation(gaps(rate, n))
        arrivals = np.concatenate([[0.0], np.cumsum(g[:-1])])
    elif mix["arrivals"] == "closed":
        arrivals = np.zeros(n)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    rng = np.random.default_rng(seed)
    return [{"prompt": rng.integers(0, vocab, int(p)).astype(np.int32),
             "max_new": int(o), "arrival": float(a),
             "temperature": float(mix.get("temperature", 0.0))}
            for p, o, a in zip(prompts, outs, arrivals)]


def count(mix: Dict[str, Any], rate: float, seconds: float) -> int:
    """Requests offered in one window: ``rate * seconds`` for an open
    loop, ``batch`` for a closed one."""
    if mix["arrivals"] == "poisson":
        return max(1, int(math.floor(rate * seconds + 0.5)))
    return int(mix["batch"])
