"""Work from shapes, the peaks table and the traffic generator."""

from __future__ import annotations

import collections
import math
from statistics import NormalDist

import numpy as np
import pytest

from perfbench import peaks, spec, traffic
from perfbench.arch import dense_decoder as dd


@pytest.fixture(scope="module")
def qwen():
    return spec.cell("qwen3-8b.chat").config


@pytest.fixture(scope="module")
def deepseek():
    return spec.cell("deepseek-7b.batch").config


def test_matmul_params_at_published_widths(qwen, deepseek):
    # q 4096x4096, k/v 4096x1024, o 4096x4096, gate/up 4096x12288,
    # down 12288x4096: 192,937,984 a layer; LM head 4096 x 151936
    assert dd.matmul_params(qwen) == 8 * 192_937_984 + 4096 * 151_936
    # four 4096x4096 attention projections, three of 4096x11008
    assert dd.matmul_params(deepseek) == 8 * (4 * 4096 ** 2
                                              + 3 * 4096 * 11008) \
        + 4096 * 102_400


def test_kv_bytes_per_token(qwen, deepseek):
    assert dd.kv_bytes_per_token(qwen) == 32 * 1024         # 8 kv heads
    assert dd.kv_bytes_per_token(deepseek) == 128 * 1024    # 32 kv heads


def test_weight_bytes_count_bf16_projections_and_f32_norms(qwen):
    norms = 8 * (2 * 4096 + 2 * 128) + 4096
    assert dd.weight_bytes(qwen) == 2 * dd.matmul_params(qwen) + 4 * norms


@pytest.mark.parametrize("plen,n_out", [(1, 1), (5, 3), (64, 17)])
def test_request_flops_closed_form_is_the_sum_over_tokens(deepseek, plen,
                                                          n_out):
    want = sum(dd.flops(deepseek, c) for c in range(1, plen + 1))
    want += sum(dd.flops(deepseek, plen + k) for k in range(1, n_out))
    assert dd.request_flops(deepseek, plen, n_out) == pytest.approx(want)


def test_decode_kv_tokens():
    assert dd.decode_kv_tokens(10, 1) == 0
    assert dd.decode_kv_tokens(10, 4) == 11 + 12 + 13


def test_gemm_shapes_of_a_decode_step(qwen):
    shapes = dd.gemm_shapes(qwen, 32)
    assert len(shapes) == 8 * 7 + 1
    assert shapes[-1] == (32, 4096, 151_936)
    assert collections.Counter(shapes)[(32, 4096, 1024)] == 16     # k, v
    flops = sum(2 * m * k * n for m, k, n in shapes)
    assert flops == 2 * 32 * dd.matmul_params(qwen)


def test_peaks_of_v5e_and_an_unknown_chip():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["int8_ops_per_s"] == 393e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("workload", ["qwen3-8b.chat", "deepseek-7b.batch"])
def test_two_seeds_offer_the_same_work_with_other_tokens(workload):
    """The same lengths and arrival gaps in one order for every seed; the
    seed draws the token ids."""
    c = spec.cell(workload)
    a = traffic.requests(c.traffic, c.cell, 40, 1000,
                         np.random.default_rng(1))
    b = traffic.requests(c.traffic, c.cell, 40, 1000,
                         np.random.default_rng(2))
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    # the lengths are not sorted: long and short requests mix
    p = [len(r["prompt"]) for r in a]
    assert p != sorted(p) and p != sorted(p, reverse=True)
    allowed = set(traffic.allowed_lengths(c.traffic["prompt"]))
    assert set(p) <= allowed
    out = c.traffic["output"]
    assert all(out["min"] <= r["max_new"] <= traffic.max_length(out)
               for r in a)


def test_chat_prompts_are_heavy_tailed_and_half_unaligned():
    c = spec.cell("qwen3-8b.chat")
    lens = traffic.allowed_lengths(c.traffic["prompt"])
    assert sum(n % 128 == 0 for n in lens) * 2 == len(lens)
    rs = traffic.requests(c.traffic, c.cell, 40, 1000,
                          np.random.default_rng(0))
    p = sorted(len(r["prompt"]) for r in rs)
    # the source's median prompt is 1020 tokens, rounded up here
    assert 1024 <= p[len(p) // 2] <= 1296 and p[-1] == 3072 and p[0] == 400
    o = sorted(r["max_new"] for r in rs)
    assert 112 <= o[len(o) // 2] <= 146 and o[-1] == 512


def test_poisson_arrivals_span_the_window_at_the_rate():
    c = spec.cell("qwen3-8b.chat")
    rs = traffic.requests(c.traffic, c.cell, 40, 1000,
                          np.random.default_rng(0))
    assert len(rs) == round(40 * c.cell["rate_hz"])
    assert 0.8 * 40 < rs[-1]["due_s"] < 1.1 * 40


def test_lognormal_from_its_mean_and_kept_below_a_length():
    """``mean`` sets the median at mean * exp(-sigma^2 / 2); ``keep_below``
    draws from the distribution conditioned on lengths at most that, as a
    source that drops longer requests does."""
    d = {"dist": "lognormal", "mean": 338, "sigma": 1.0}
    assert traffic.quantile(d, 0.5) == math.ceil(338 * math.exp(-0.5))
    kept = dict(d, keep_below=1024)
    share = NormalDist().cdf(math.log(1024 / (338 * math.exp(-0.5))))
    for u in (0.1, 0.5, 0.9):
        assert traffic.quantile(kept, u) == traffic.quantile(d, u * share)
    assert traffic.max_length(kept) == 1024
    assert traffic.max_length(d) > 10 ** 5


def test_a_closed_queue_holds_its_depth_per_lane_all_due_at_once():
    c = spec.cell("deepseek-7b.batch")
    rs = traffic.requests(c.traffic, c.cell, 51, 1000,
                          np.random.default_rng(0))
    assert len(rs) == c.traffic["queue_per_lane"] * c.cell["lanes"]
    assert all(r["due_s"] == 0.0 for r in rs)
    # no prompt or answer is longer than the source's filter keeps
    assert max(len(r["prompt"]) for r in rs) <= 1024
    assert max(len(r["prompt"]) + r["max_new"] for r in rs) <= 2048
