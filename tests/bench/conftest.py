"""Tiny cells for rehearsing the benchmark on the CPU."""

from __future__ import annotations

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import spec  # noqa: E402

#: published shapes cut to a size the CPU runs in seconds
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256}


def tiny_cell(workload: str) -> spec.Cell:
    """``workload``'s cell with tiny widths, lanes and lengths: the same
    files, the same code paths."""
    c = spec.cell(workload)
    c = copy.deepcopy(c)
    c.config.update(TINY)
    c.config["num_key_value_heads"] = 2 if c.config["qk_norm"] else 4
    if c.traffic["arrival"] == "poisson":
        c.traffic["prompt"] = {"dist": "lognormal", "median": 24, "sigma": 0.8,
                               "round_up_to": [16, 24, 48]}
        c.traffic["output"] = {"dist": "lognormal", "median": 6, "sigma": 0.5,
                               "min": 3, "max": 12}
        c.cell.update(lanes=4, max_ctx=64, rate_hz=8.0)
    else:
        c.traffic["prompt"] = {"dist": "choice", "values": [16, 32, 48]}
        c.traffic["output"] = {"dist": "uniform", "min": 4, "max": 12}
        c.cell.update(lanes=2, max_ctx=64)
    c.cell["check"] = {"requests": 3}
    c.cell["limits"] = {"logit_gap": 0.05, "logit_gap_mean": 0.02}
    return c


@pytest.fixture
def isolated_caches(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setenv("REPRO_PLAN_DB", str(tmp_path / "plans.json"))
    return tmp_path
