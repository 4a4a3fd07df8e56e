"""The arithmetic of the metric readers in ``perfbench/metrics``, shared by
readers of the same quantity in different cells.  Each returns None where
the run left nothing to read."""

from __future__ import annotations

from typing import Optional

from .stats import inter_token_gaps, percentile, tokens_by, ttfts


def setup_s(run) -> float:
    return run.setup_s


def ttft_ms(run, q: float) -> float:
    return percentile(ttfts(run.requests), q) * 1e3


def itl_ms(run, q: float) -> Optional[float]:
    gaps = inter_token_gaps(run.requests, run.step_ends(),
                            run.t_start + run.seconds)
    return percentile(gaps, q) * 1e3 if gaps else None


def output_tok_per_s(run) -> float:
    """Every output token that reached the host inside the window, over
    the window's seconds."""
    return sum(tokens_by(run.requests, run.step_ends(), run.close)) \
        / run.seconds


def submit_lag_ms(run, q: float) -> float:
    return percentile([r["t_submit"] - r["due"] for r in run.requests],
                      q) * 1e3


def window_compiles(run) -> int:
    return run.window_compiles


def prefill_share(run) -> float:
    return 100.0 * run.stats["prefill_s"] / (run.t_end - run.t_start)


def idle_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def decode_occupancy(run) -> Optional[float]:
    """Mean share of the lanes live in the decode steps that ended inside
    the window."""
    lives = [live for _, end, live in run.steps if end <= run.close]
    if not lives:
        return None
    return 100.0 * sum(lives) / (len(lives) * run.lanes)


def decode_step_max_ms(run) -> Optional[float]:
    """The longest decode step of the run, host clock: a stall of the
    engine holds every lane and every waiting request for its length."""
    if not run.steps:
        return None
    return 1e3 * max(end - start for start, end, _ in run.steps)


def decode_step_ms(run) -> Optional[float]:
    n = run.stats["decode_steps"]
    return 1e3 * run.stats["decode_s"] / n if n else None


def mfu(run) -> Optional[float]:
    """Model FLOPs of every token served inside the window (its prompt's
    prefill and the decode steps before it) over the window's seconds at
    the chip's bf16 peak."""
    if run.peaks is None:
        return None
    arch, conf = run.arch, run.conf
    done = tokens_by(run.requests, run.step_ends(), run.close)
    work = sum(arch.request_flops(conf, r["prompt_len"], k)
               for r, k in zip(run.requests, done) if k)
    return 100.0 * work / (run.seconds * run.peaks["bf16_flops_per_s"])


def step_hbm_share(run) -> Optional[float]:
    """Bytes the window's decode steps had to read (every weight once a
    step, and each live request's cache) over the steps' device time at
    peak HBM bandwidth."""
    if run.trace is None or run.peaks is None or not run.trace["decode_busy_s"]:
        return None
    arch, conf = run.arch, run.conf
    done = tokens_by(run.requests, run.step_ends(), run.close)
    kv = arch.kv_bytes_per_token(conf) * sum(
        arch.decode_kv_tokens(r["prompt_len"], k)
        for r, k in zip(run.requests, done))
    need = run.trace["decode_runs"] * arch.weight_bytes(conf) + kv
    return 100.0 * need / (run.trace["decode_busy_s"]
                           * run.peaks["hbm_bytes_per_s"])


def gemm_roofline(run) -> Optional[float]:
    """The decode steps' weight GEMMs: their least time at the chip's peaks
    (from shapes) over the device time of the ops that computed them."""
    if run.trace is None or run.peaks is None or not run.trace["gemm_s"]:
        return None
    pk = run.peaks
    shapes = run.arch.gemm_shapes(run.conf, run.lanes)
    per_step = 0.0
    for i, (m, k, n) in enumerate(shapes):
        out = 4 if i == len(shapes) - 1 else 2     # f32 logits, bf16 else
        b = 2 * (m * k + k * n) + out * m * n
        per_step += max(2.0 * m * k * n / pk["bf16_flops_per_s"],
                        b / pk["hbm_bytes_per_s"])
    return 100.0 * per_step * run.trace["decode_runs"] / run.trace["gemm_s"]
