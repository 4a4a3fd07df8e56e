"""The comparison that decides ``correct``.

After the window, a sample of the requests the program finished, drawn from
the seed and always holding the one with most served tokens, goes through
the plain float32 reference of the cell's architecture: each prompt with
every token the program served for it, teacher-forced, at the cell's own
context size.  The number compared is the widest gap by which a served
token's logit lies below the reference's best at its position
(``logit_gap``), and the mean over the sampled requests of each one's
widest gap (``logit_gap_mean``), which is steadier from seed to seed and
separates bfloat16 serving from the program's weight-only int8 path where
the widest gap alone does not.  Greedy serving of the same weights in
bfloat16 lands on the reference's best or on a near-tie; a token computed
wrongly lands anywhere in the distribution.  The limits are the cell
file's ``limits``, set from the readings that ``PERF.md`` lists.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import traffic


def sample(requests: List[Dict], k: int, seed: int) -> List[Dict]:
    """``k`` finished requests: the longest served and ``k - 1`` drawn
    from the seed."""
    done = [r for r in requests if r["n_out"] > 0]
    longest = max(done, key=lambda r: (r["n_out"], r["prompt_len"], -r["rid"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed % 2 ** 64, 0xC4EC]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gaps(cell, requests: List[Dict], key, control: bool = False) -> Dict:
    """The reference's gaps for each request's served tokens (and the
    float8 control's, with ``control``)."""
    arch = cell.arch()
    seqs = [np.concatenate([r["prompt"], np.asarray(r["out_tokens"],
                                                    np.int32)])
            for r in requests]
    starts = [r["prompt_len"] for r in requests]
    return arch.reference_gaps(
        cell.config, key, seqs, starts, length=int(cell.cell["max_ctx"]),
        n_pos=traffic.max_length(cell.traffic["output"]), control=control)


def numbers(cell, gap: List[np.ndarray],
            requests: List[Dict]) -> Dict[str, Dict[str, float]]:
    """The numbers compared, each beside its limit: from the gaps of the
    sampled requests' served tokens (the program's, or a control's in its
    place) and from every request of the window."""
    lim = cell.cell["limits"]
    widest = [float(x.max()) for x in gap]
    return {
        "logit_gap": {"value": max(widest),
                      "limit": float(lim["logit_gap"])},
        "logit_gap_mean": {"value": sum(widest) / len(widest),
                           "limit": float(lim["logit_gap_mean"])},
        # an answer that never came: every request is owed max_new tokens
        "missing_tokens": {"value": int(sum(r["max_new"] - r["n_out"]
                                            for r in requests)),
                           "limit": 0},
    }


def verdict(nums: Dict[str, Dict[str, float]]) -> bool:
    """``correct``: every number at or under its limit."""
    return all(v["value"] <= v["limit"] for v in nums.values())


def compare(cell, run, key, seed: int) -> Dict[str, Dict[str, float]]:
    reqs = sample(run.requests, int(cell.cell["check"]["requests"]), seed)
    return numbers(cell, gaps(cell, reqs, key)["gap"], run.requests)
