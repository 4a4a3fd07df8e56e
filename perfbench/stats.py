"""Percentiles over every sample, and the serving timings they are taken of."""

from __future__ import annotations

import bisect
import math
from typing import Dict, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) of all ``values``, interpolated
    linearly between the two nearest ranks (numpy's default method)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def token_times(req: Dict, step_ends: List[float]) -> List[float]:
    """When each of a request's tokens reached the host.

    The first comes from its prefill (``t_first``); each later one from a
    decode step, and a running request takes a token from every decode
    step that ends after its first token and no later than its last
    (``t_done``).  ``step_ends`` is sorted."""
    lo = bisect.bisect_right(step_ends, req["t_first"])
    hi = bisect.bisect_right(step_ends, req["t_done"])
    times = [req["t_first"]] + step_ends[lo:hi]
    if len(times) != req["n_out"]:
        raise RuntimeError(
            f"request {req['rid']}: {req['n_out']} tokens but {len(times)} "
            "token times (was it preempted?)")
    return times


def tokens_by(reqs: List[Dict], step_ends: List[float],
              t: float) -> List[int]:
    """How many of each request's tokens had reached the host by ``t``."""
    return [bisect.bisect_right(token_times(r, step_ends), t)
            if r["t_first"] is not None else 0 for r in reqs]


def ttfts(reqs: List[Dict]) -> List[float]:
    """First token's time minus the time the request was due, in seconds."""
    return [r["t_first"] - r["due"] for r in reqs if r["t_first"] is not None]


def inter_token_gaps(reqs: List[Dict], step_ends: List[float],
                     window_end: float) -> List[float]:
    """Every gap between consecutive tokens of every request whose later
    token reached the host by ``window_end``, in seconds."""
    gaps = []
    for r in reqs:
        ts = token_times(r, step_ends)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if b <= window_end)
    return gaps
