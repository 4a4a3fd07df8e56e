"""One general generator of serving traffic, driven by a traffic file.

A traffic file (``perfbench/traffic/<name>.json``) sets:

* ``arrival``: ``"poisson"`` (an open loop: requests are due at Poisson
  times at the cell's ``rate_hz`` over the run's seconds) or ``"closed"``
  (one queue of ``queue_per_lane`` x lanes requests, all due at once);
* ``prompt`` and ``output``: a length distribution each, one of
  ``{"dist": "lognormal", "median" or "mean", "sigma"}``,
  ``{"dist": "uniform", "min", "max"}`` or ``{"dist": "choice", "values"}``.
  Any of them may add ``"keep_below"`` (draw from the distribution
  conditioned on a length at most this: a source that drops longer
  requests), ``"min"`` and ``"max"`` (clip) and ``"round_up_to"``: the
  sorted lengths a drawn value is rounded up to (values above the last
  become the last).

Every seed gets the same work: request ``i`` of ``n`` takes quantile
``(i + 0.5) / n`` of each distribution, and the prompt lengths, the output
lengths and the gaps between arrivals keep one order (a fixed permutation
each).  The seed draws every token id.  The order of the lengths decides
when a cell's lanes fill up, which prefills stall which decode steps and
how long a closed queue's tail runs: with it drawn from the seed, two seeds
of the same code differed far more than two runs of one seed.
"""

from __future__ import annotations

import bisect
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def quantile(dist: Dict, u: float) -> int:
    """Quantile ``u`` in (0, 1) of one length distribution, as an int."""
    kind = dist["dist"]
    if "keep_below" in dist:
        u *= _cdf(dist, dist["keep_below"])
    if kind == "lognormal":
        x = _median(dist) * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "choice":
        vals = dist["values"]
        x = vals[min(int(u * len(vals)), len(vals) - 1)]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = int(math.ceil(x))
    x = max(x, dist.get("min", x))
    x = min(x, dist.get("max", x))
    grid = dist.get("round_up_to")
    if grid:
        x = grid[min(bisect.bisect_left(grid, x), len(grid) - 1)]
    return x


def _median(dist: Dict) -> float:
    """A log-normal's median, from its ``median`` or its ``mean``."""
    if "median" in dist:
        return float(dist["median"])
    return dist["mean"] * math.exp(-dist["sigma"] ** 2 / 2)


def _cdf(dist: Dict, x: float) -> float:
    """The share of the distribution at or below ``x``."""
    kind = dist["dist"]
    if kind == "lognormal":
        return NormalDist().cdf(math.log(x / _median(dist)) / dist["sigma"])
    if kind == "uniform":
        return min(1.0, max(0.0, (x - dist["min"]) / (dist["max"] - dist["min"])))
    raise ValueError(f"keep_below is not defined for {kind!r}")


#: the order of output lengths and arrival gaps, the same for every seed
FIXED_ORDER = 20260101


def lengths(dist: Dict, n: int, rng: np.random.Generator) -> List[int]:
    """``n`` stratified draws of ``dist`` in ``rng``'s order."""
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    return [vals[j] for j in rng.permutation(n)]


def allowed_lengths(dist: Dict) -> List[int]:
    """Every length the distribution can give: the prefill shapes a cell
    warms up."""
    if dist.get("round_up_to"):
        return list(dist["round_up_to"])
    if dist["dist"] == "choice":
        return sorted(set(dist["values"]))
    raise ValueError("a prompt distribution needs round_up_to or choice, so "
                     "that the cell compiles a fixed set of prefill shapes")


def max_length(dist: Dict) -> int:
    return max(quantile(dist, 1 - 1e-12), quantile(dist, 1e-12))


def n_requests(traffic: Dict, cell: Dict, seconds: float) -> int:
    """Requests in one window (Poisson) or in the queue (closed)."""
    if traffic["arrival"] == "poisson":
        return max(1, int(round(cell["rate_hz"] * seconds)))
    if traffic["arrival"] == "closed":
        return int(traffic["queue_per_lane"]) * int(cell["lanes"])
    raise ValueError(f"unknown arrival {traffic['arrival']!r}")


def requests(traffic: Dict, cell: Dict, seconds: float, vocab: int,
             rng: np.random.Generator) -> List[Dict]:
    """One window's (or one queue's) requests: dicts with ``prompt`` (int32
    token ids), ``max_new`` and ``due_s`` (offset from the window's start)."""
    n = n_requests(traffic, cell, seconds)
    fixed = np.random.default_rng(FIXED_ORDER)
    outs = lengths(traffic["output"], n, fixed)
    plens = lengths(traffic["prompt"], n, fixed)
    if traffic["arrival"] == "poisson":
        rate = float(cell["rate_hz"])
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
        gaps = [gaps[j] for j in fixed.permutation(n)]
        due = np.cumsum(gaps)          # the last is due at sum(gaps)
    else:
        due = np.zeros(n)
    return [{"prompt": rng.integers(0, vocab, size=p).astype(np.int32),
             "max_new": o, "due_s": float(t)}
            for p, o, t in zip(plens, outs, due)]
