"""Each cell rehearsed on the CPU at a tiny size: the whole run, the same
compiled programs for every seed, the reference against the served tokens,
and ``correct`` coming out false when the timed path is broken."""

from __future__ import annotations

import time

import jax
import pytest
from conftest import tiny_cell

from perfbench import check, harness

WORKLOADS = ["qwen3-8b.chat", "deepseek-7b.batch"]


@pytest.fixture
def compile_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    jax.clear_caches()
    yield tmp_path / "jax"
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])
    cc.reset_cache()
    jax.clear_caches()


def _run(workload, seed, trace=False, cell=None):
    return harness.run_cell(workload, seed, 1.0, trace, time.perf_counter(),
                            allow_cpu=True, cell=cell or tiny_cell(workload))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_two_seeds_compile_the_same_programs(workload, isolated_caches,
                                             compile_cache):
    a = _run(workload, 11)
    files = sorted(p.name for p in compile_cache.iterdir())
    jax.clear_caches()
    b = _run(workload, 2 ** 31 + 12345)
    assert a["correct"] and b["correct"], (a["check"], b["check"])
    assert a["window_compiles"] == b["window_compiles"] == 0
    for phase in ("weights", "kv_pool", "warm_up"):
        pa, pb = a["setup_phases"][phase], b["setup_phases"][phase]
        assert pa["compiles"] == pb["compiles"], phase
        assert pb["cache_misses"] == 0 and pb["cache_hits"] == pb["compiles"]
    assert sorted(p.name for p in compile_cache.iterdir()) == files
    e2e = {"qwen3-8b.chat": {"ttft_p90_ms", "itl_p95_ms", "setup_s"},
           "deepseek-7b.batch": {"output_tok_per_s", "setup_s"}}[workload]
    assert set(a["metrics"]) == e2e
    assert a["failed"] == 0 and a["attempted"] > 0
    assert list(a)[-1] == "check"


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traced_run_reads_the_host_side_metrics(workload, isolated_caches):
    out = _run(workload, 5, trace=True)
    assert out["correct"]
    # on the CPU there is no device plane: the trace readers stay silent
    want = {"qwen3-8b.chat": {"submit_lag_p95_ms.chat",
                              "decode_step_max_ms.chat",
                              "window_compiles.chat", "prefill_share.chat"},
            "deepseek-7b.batch": {"decode_occupancy.batch",
                                  "decode_step_ms.batch"}}[workload]
    assert set(out["metrics"]) == want


@pytest.mark.parametrize("workload", WORKLOADS)
def test_reference_agrees_and_a_float8_control_does_not(workload,
                                                        isolated_caches):
    """The served tokens, through the run's own comparison, come out
    correct; the float8 control's choices at the same positions, through
    the same numbers and limits, do not."""
    cell = tiny_cell(workload)
    key, rng = harness.seed_key(3)
    server = harness.Server(cell, key, harness.CompileClock())
    raw = harness.window(server, 1.0, rng, harness.CompileClock())
    server.close()
    reqs = check.sample(raw["requests"], 6, 3)
    g = check.gaps(cell, reqs, key, control=True)
    program = check.numbers(cell, g["gap"], raw["requests"])
    control = check.numbers(cell, g["control_gap"], raw["requests"])
    assert check.verdict(program), program
    assert not check.verdict(control), control
    assert control["logit_gap"]["value"] > 3 * cell.cell["limits"]["logit_gap"]


FAULTS = ["state_unchanged", "half_batch", "decode_token", "prefill_token"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(fault, isolated_caches,
                                            monkeypatch):
    """Each fault planted under the window: a decode step that returns the
    KV pools unchanged; the upper half of the lanes given the lower half's
    tokens; a token altered where the decode step or the prefill makes
    it."""
    from repro.launch.serving import runners

    dec, pre = runners.DecodeRunner.__call__, runners.PrefillRunner.__call__

    def decode(self, params, pools, bt, lens, toks):
        tok, new = dec(self, params, pools, bt, lens, toks)
        if fault == "state_unchanged":
            return tok, pools
        if fault == "half_batch":
            h = self.lanes // 2
            return tok.at[h:2 * h].set(tok[:h]), new
        return (tok + 1) % self.cfg.vocab, new

    def prefill(self, params, pools, context, pages):
        tok, pools = pre(self, params, pools, context, pages)
        return (tok + 1) % self.cfg.vocab, pools

    if fault == "prefill_token":
        monkeypatch.setattr(runners.PrefillRunner, "__call__", prefill)
    else:
        monkeypatch.setattr(runners.DecodeRunner, "__call__", decode)
    workload = "deepseek-7b.batch" if fault == "half_batch" else \
        "qwen3-8b.chat"
    out = _run(workload, 21)
    assert not out["correct"], out["check"]
