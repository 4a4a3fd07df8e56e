"""The readers over a window's records: what falls inside the window, and
the comparison's numbers beside their limits."""

from __future__ import annotations

import numpy as np
import pytest
from conftest import tiny_cell

from perfbench import check, harness, readings


def _req(rid, first, done, n_out, prompt_len=8):
    return dict(rid=rid, due=0.0, t_submit=0.0, t_first=first, t_done=done,
                n_out=n_out, max_new=n_out, prompt_len=prompt_len)


@pytest.fixture
def run():
    """Two lanes, a window of 1 s from t=10; decode steps every 0.25 s
    from 10.25 to 11.5, the last two after the window closed."""
    ends = [10.25, 10.5, 10.75, 11.0, 11.25, 11.5]
    steps = [(e - 0.2, e, 2 if e < 11.2 else 1) for e in ends]
    steps[2] = (10.75 - 0.24, 10.75, 2)
    a = _req(0, 10.1, 10.75 + 1e-6, 4)           # 10.1, 10.25, 10.5, 10.75
    b = _req(1, 10.2, 11.5 + 1e-6, 7)            # 10.2, 10.25, ... , 11.5
    cell = tiny_cell("deepseek-7b.batch")
    cell.cell["lanes"] = 2
    raw = dict(t_start=10.0, t_end=11.6, requests=[a, b], stats={},
               steps=steps, compiles=0)
    return harness.make_run(cell, raw, 1.0, 3.0)


def test_output_rate_counts_the_tokens_inside_the_window(run):
    # a: all 4; b: its prefill token and the steps ending 10.25 .. 11.0
    assert readings.output_tok_per_s(run) == pytest.approx(4 + 5)


def test_occupancy_reads_the_steps_that_ended_inside_the_window(run):
    assert readings.decode_occupancy(run) == pytest.approx(100.0)


def test_the_longest_decode_step_is_the_stall(run):
    assert readings.decode_step_max_ms(run) == pytest.approx(240.0)


def test_mfu_counts_the_work_of_the_tokens_inside_the_window(run):
    run.peaks = {"bf16_flops_per_s": 1e12}
    arch, conf = run.arch, run.conf
    work = (arch.request_flops(conf, 8, 4) + arch.request_flops(conf, 8, 5))
    assert readings.mfu(run) == pytest.approx(100.0 * work / 1e12)


def test_numbers_stand_beside_their_limits_and_decide_correct():
    cell = tiny_cell("qwen3-8b.chat")
    reqs = [dict(max_new=3, n_out=3), dict(max_new=2, n_out=2)]
    lim = cell.cell["limits"]
    ok = check.numbers(cell, [np.array([0.0, 0.01]), np.array([0.02])], reqs)
    assert ok["logit_gap"] == {"value": 0.02, "limit": lim["logit_gap"]}
    assert ok["logit_gap_mean"]["value"] == pytest.approx(0.015)
    assert ok["missing_tokens"] == {"value": 0, "limit": 0}
    assert check.verdict(ok)
    # each number alone fails it
    wide = check.numbers(cell, [np.array([lim["logit_gap"] * 2])], reqs)
    assert not check.verdict(wide)
    short = check.numbers(cell, [np.array([0.0])],
                          reqs + [dict(max_new=2, n_out=1)])
    assert short["missing_tokens"]["value"] == 1 and not check.verdict(short)
    mean = check.numbers(cell, [np.array([lim["logit_gap_mean"] * 1.5])] * 2,
                         reqs)
    assert mean["logit_gap"]["value"] <= lim["logit_gap"]
    assert not check.verdict(mean)
