"""The benchmark's percentile arithmetic and serving timings."""

from __future__ import annotations

import numpy as np
import pytest

from perfbench import stats


@pytest.mark.parametrize("n", [1, 2, 5, 11, 101, 1000])
@pytest.mark.parametrize("q", [50, 90, 95, 99])
def test_percentile_matches_numpy_over_all_samples(n, q):
    xs = np.random.default_rng(n).lognormal(size=n).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q),
                                                    rel=1e-12)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def _req(rid, due, first, done, n_out):
    return dict(rid=rid, due=due, t_first=first, t_done=done, n_out=n_out)


def test_ttft_is_from_the_due_time_not_the_submit():
    reqs = [_req(0, 10.0, 10.25, 11.0, 3), _req(1, 10.5, 10.75, 11.0, 2)]
    for r in reqs:
        r["t_submit"] = r["due"] + 0.2     # a late submit does not hide
    assert stats.ttfts(reqs) == pytest.approx([0.25, 0.25])


def test_token_times_are_the_ends_of_the_request_decode_steps():
    ends = [1.0, 1.1, 1.2, 1.3, 1.4]
    # first token at 1.05 from prefill; steps ending 1.1, 1.2, 1.3 give
    # tokens 2-4, and the request finished right after the 1.3 step
    r = _req(7, 0.9, 1.05, 1.3 + 1e-6, 4)
    assert stats.token_times(r, ends) == pytest.approx([1.05, 1.1, 1.2, 1.3])


def test_token_times_refuse_a_count_that_does_not_match():
    r = _req(7, 0.9, 1.05, 1.3 + 1e-6, 5)
    with pytest.raises(RuntimeError, match="token times"):
        stats.token_times(r, [1.1, 1.2, 1.3])


def test_gaps_count_every_gap_whose_later_token_is_in_the_window():
    ends = [1.0, 1.2, 1.4, 1.6, 1.8]
    a = _req(0, 0.8, 0.9, 1.4 + 1e-6, 4)        # 0.9, 1.0, 1.2, 1.4
    b = _req(1, 1.1, 1.3, 1.8 + 1e-6, 4)        # 1.3, 1.4, 1.6, 1.8
    gaps = stats.inter_token_gaps([a, b], ends, window_end=1.7)
    assert sorted(gaps) == pytest.approx(sorted([0.1, 0.2, 0.2, 0.1, 0.2]))


def test_tokens_by_a_time_count_each_request_tokens_that_came():
    ends = [1.0, 1.2, 1.4, 1.6, 1.8]
    a = _req(0, 0.8, 0.9, 1.4 + 1e-6, 4)        # 0.9, 1.0, 1.2, 1.4
    b = _req(1, 1.1, 1.3, 1.8 + 1e-6, 4)        # 1.3, 1.4, 1.6, 1.8
    c = dict(_req(2, 1.5, None, None, 0))       # never started
    assert stats.tokens_by([a, b, c], ends, 1.5) == [4, 2, 0]
    assert stats.tokens_by([a, b, c], ends, 0.5) == [0, 0, 0]
