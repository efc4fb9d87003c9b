"""Serving traffic: an open loop of independent users, log-normal lengths.

One general generator; a mix is a data file beside this one that names it
and gives the arrival rate and the two length distributions. ``schedule``
returns every request due in the window: when it is due (seconds from the
window's start), its prompt and how many tokens it asks for.

Arrivals are Poisson: exponential gaps of mean ``1 / rate``. The gaps and
the lengths are not drawn afresh for every run: each is the set of ``n``
evenly spaced quantiles of its distribution (``n = rate * seconds``
requests), put in one order by the mix's own ``order_seed``. The run's
seed draws the tokens of the prompts and nothing else, so every seed
offers the same requests at the same times.

Why one order (PERF.md section 4 has the numbers). A step of the engine
costs what the longest row of its batch costs, and a request holds a row
for seconds, so the order of arrivals decides which requests share a
batch and how long a burst queues: another order of the same requests is
other work, not the same work again. Shuffling by the run's seed, and
rotating one cycle by it, both moved the bounded metrics between seeds by
more than any bound may be. The weights are random and decoding is greedy
to a fixed length, so the prompts' tokens change no work; what the seed
still varies is the inputs. ``tools/sweep_rate.py --order-seeds`` measures
what another order does.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, median: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    """The n evenly spaced quantiles of a log-normal, clipped to [lo, hi]."""
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """The n evenly spaced quantiles of an exponential with mean 1/rate,
    rescaled to sum to exactly n / rate."""
    gaps = -np.log1p(-_quantiles(n))
    return gaps * (n / rate) / gaps.sum()


def schedule(params: Dict[str, Any], *, seed: int, seconds: float,
             vocab: int) -> List[Dict[str, Any]]:
    n = max(1, int(round(params["rate_per_s"] * seconds)))
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(int(params["order_seed"]))
    p, o = params["prompt_len"], params["output_len"]
    prompts = order.permutation(lognormal_lengths(
        n, p["median"], p["sigma"], p["min"], p["max"]))
    outputs = order.permutation(lognormal_lengths(
        n, o["median"], o["sigma"], o["min"], o["max"]))
    gaps = order.permutation(exponential_gaps(n, params["rate_per_s"]))
    # the first request is due half a gap in, the last before the end
    due = np.cumsum(gaps) - gaps[0] / 2.0
    return [{
        "due_s": float(due[i]),
        "prompt": rng.integers(2, vocab, size=int(prompts[i])).tolist(),
        "max_new": int(outputs[i]),
    } for i in range(n)]


def describe(params: Dict[str, Any], seconds: float) -> Dict[str, float]:
    n = max(1, int(round(params["rate_per_s"] * seconds)))
    o = params["output_len"]
    outs = lognormal_lengths(n, o["median"], o["sigma"], o["min"], o["max"])
    return {"requests": n, "output_tokens": int(outs.sum()),
            "offered_tokens_per_s": float(outs.sum()) / seconds,
            "mean_output_len": float(outs.mean())}
