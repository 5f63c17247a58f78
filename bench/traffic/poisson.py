"""Open-loop serving traffic: requests from independent users.

A mix file names this generator and gives ``rate`` (requests per second),
prompt-length buckets with their weights, and the output lengths as a
lognormal (median, sigma) clipped to [min, max].  A window of S seconds
gets N = round(rate * S) requests.  Every seed gets the same multiset of
sizes: the prompt lengths are the buckets in counts apportioned by weight,
the output lengths the lognormal's quantiles at (i + 0.5) / N; the seed
shuffles them, draws the N arrival times uniformly over the window (a
Poisson process given its count) and draws the prompts' token ids.  So the
work of a window is fixed and the seed changes only its order and timing.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def apportion(n: int, weights) -> List[int]:
    """Largest-remainder counts of n items over the weights."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def sizes(mix: Dict[str, Any], n: int):
    """The (prompt lengths, output lengths) of a window of n requests, in a
    fixed order."""
    prompts = np.repeat(mix["prompt_buckets"],
                        apportion(n, mix["prompt_weights"]))
    nd = NormalDist()
    q = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    outs = np.exp(math.log(mix["output_median"]) + mix["output_sigma"] * q)
    outs = np.clip(np.round(outs), mix["output_min"], mix["output_max"])
    return prompts.astype(int), outs.astype(int)


def make_requests(mix: Dict[str, Any], seed: int, seconds: float,
                  vocab_size: int):
    """The window's requests, sorted by arrival (seconds from its open)."""
    from repro.serve.slots import Request

    n = max(1, int(round(mix["rate"] * seconds)))
    prompts, outs = sizes(mix, n)
    rng = np.random.default_rng(seed)
    prompts = rng.permutation(prompts)
    outs = rng.permutation(outs)
    arrivals = np.sort(rng.uniform(0.0, seconds, n))
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab_size, int(prompts[i]),
                                        dtype=np.int64).astype(np.int32),
                    max_new=int(outs[i]), arrival=float(arrivals[i]))
            for i in range(n)]
