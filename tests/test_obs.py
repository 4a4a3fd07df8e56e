"""repro.obs — spans, metrics exactness, the no-op contract, plan-explain.

What this suite pins:

* **Spans** nest through the thread-local stack and export valid
  Chrome-trace events (name/cat/ph/ts/dur/pid/tid + depth/parent args);
  ``scripts/obs_report.py --trace`` accepts a ``trace_dump``.
* **Metrics exactness** — counters record exactly what a scripted
  search/plan-DB sweep did: one plandb.miss on a cold DB, one plandb.hit
  on the re-search, a version_miss when the DB holds only a stale-format
  key, and beam counters equal to the search's own reported stats.
* **Histograms** match ``numpy.percentile``'s default linear
  interpolation bit-for-bit.
* **REPRO_OBS=0 is a strict no-op** — handles are the shared do-nothing
  singleton, the registry and the trace buffer stay empty.
* **Explain round-trip** — the roofline terms ``search_schedule``
  persists come back out of ``obs.explain`` as a ranked table for a
  human selector, through the real plan-DB file.
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import explain as explain_mod
from repro.obs import log as log_mod
from repro.obs.metrics import Histogram, _NOOP, registry


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Each test starts with an empty registry/trace and obs enabled."""
    monkeypatch.delenv("REPRO_OBS", raising=False)
    obs.metrics_reset()
    obs.trace_reset()
    yield
    obs.metrics_reset()
    obs.trace_reset()


# ---------------------------------------------------------------- spans


def test_span_nesting_records_depth_and_parent():
    with obs.span("outer", spec="matmul"):
        with obs.span("inner"):
            pass
    evs = obs.trace_events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    assert outer["args"]["depth"] == 0 and "parent" not in outer["args"]
    assert inner["args"]["depth"] == 1
    assert inner["args"]["parent"] == "outer"
    assert outer["args"]["spec"] == "matmul"
    # the inner span lies inside the outer one on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_span_chrome_trace_schema(tmp_path):
    with obs.span("a"):
        pass
    doc = obs.trace_json()
    assert isinstance(doc["traceEvents"], list)
    ev = doc["traceEvents"][0]
    for k in ("name", "cat", "ph", "ts", "dur", "pid", "tid", "args"):
        assert k in ev
    assert ev["ph"] == "X"
    # the dump must be loadable and pass the report script's validator
    path = obs.trace_dump(str(tmp_path / "t.json"))
    with open(path) as f:
        assert json.load(f)["traceEvents"]
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "obs_report",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "obs_report.py"),
    )
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    rep.run_trace(path)  # SystemExit(1) on schema drift


def test_span_threads_have_independent_stacks():
    def worker():
        with obs.span("thread-span"):
            pass

    with obs.span("main-span"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    evs = {e["name"]: e for e in obs.trace_events()}
    # the thread's span must NOT see main's stack as its parent
    assert evs["thread-span"]["args"]["depth"] == 0
    assert "parent" not in evs["thread-span"]["args"]
    assert evs["thread-span"]["tid"] != evs["main-span"]["tid"]


def test_span_survives_exception():
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    assert [e["name"] for e in obs.trace_events()] == ["boom"]
    # and the stack unwound — a following span is top-level again
    with obs.span("after"):
        pass
    assert obs.trace_events()[-1]["args"]["depth"] == 0


def test_bound_annotation_wraps_each_span_and_complete_event_skips_it():
    from repro.obs import trace as obs_trace

    calls = []

    class Recorder:
        def __init__(self, name, **args):
            self.name = name
            calls.append(("new", name, args))

        def __enter__(self):
            calls.append(("enter", self.name))

        def __exit__(self, *exc):
            calls.append(("exit", self.name))

    bound = obs_trace._annotation
    obs.bind_annotation(Recorder)
    try:
        with obs.span("outer", spec="matmul"):
            with obs.span("inner"):
                pass
        obs.complete_event("retro", 0.0, 1.0)
    finally:
        obs.bind_annotation(bound)
    assert calls == [("new", "outer", {"spec": "matmul"}),
                     ("enter", "outer"), ("new", "inner", {}),
                     ("enter", "inner"), ("exit", "inner"),
                     ("exit", "outer")]
    assert [e["name"] for e in obs.trace_events()] == ["inner", "outer",
                                                       "retro"]


def test_epoch_is_the_origin_of_event_times():
    import time

    from repro.obs import trace as obs_trace

    t0 = time.perf_counter()
    with obs.span("timed"):
        pass
    t1 = time.perf_counter()
    (ev,) = obs.trace_events()
    start = obs_trace.epoch() + ev["ts"] * 1e-6
    assert t0 <= start <= t1


# -------------------------------------------------------------- metrics


def test_counter_gauge_exact():
    obs.counter("c").inc()
    obs.counter("c").inc(3)
    obs.gauge("g").set(2.5)
    j = obs.metrics_json()
    assert j["counters"] == {"c": 4}
    assert j["gauges"] == {"g": 2.5}


def test_metric_kind_mismatch_raises():
    obs.counter("x")
    with pytest.raises(TypeError):
        obs.gauge("x")


def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 5, 101):
        h = Histogram("h")
        vals = rng.uniform(0, 10, size=n)
        for v in vals:
            h.observe(float(v))
        for p in (0, 25, 50, 90, 99, 100):
            assert h.percentile(p) == pytest.approx(
                float(np.percentile(vals, p)), rel=1e-12, abs=1e-12
            )
        s = h.summary()
        assert s["count"] == n
        assert s["p50"] == h.percentile(50)
        assert s["p99"] == h.percentile(99)


def test_metrics_dump_passes_report_validation(tmp_path):
    obs.counter("plandb.hit").inc(2)
    obs.histogram("lat").observe(0.1)
    obs.histogram("empty")  # zero-observation histogram stays valid
    path = obs.metrics_dump(str(tmp_path / "m.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["counters"]["plandb.hit"] == 2
    assert doc["histograms"]["lat"]["count"] == 1
    assert doc["histograms"]["empty"] == {"count": 0, "sum": 0.0}
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "obs_report",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "obs_report.py"),
    )
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    rep.run_metrics(path)  # SystemExit(1) on schema drift


# -------------------------------------------------- the no-op contract


def test_disabled_is_strict_noop(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "0")
    assert not obs.enabled()
    c = obs.counter("never")
    c.inc(10)
    obs.gauge("never.g").set(1.0)
    obs.histogram("never.h").observe(1.0)
    assert c is _NOOP
    assert registry().names() == []
    assert obs.metrics_json() == {
        "counters": {}, "gauges": {}, "histograms": {},
    }
    with obs.span("never.span"):
        pass
    with obs.gc_spans():
        import gc

        gc.collect()
    assert obs.trace_events() == []
    # flipping the env back re-enables without any reload
    monkeypatch.delenv("REPRO_OBS")
    obs.counter("now").inc()
    assert obs.metrics_json()["counters"] == {"now": 1}


# ------------------------------------- scripted sweep: counters exact


def _tiny_search(db, **kw):
    from repro.core.enumerate import matmul_spec
    from repro.search import search_schedule

    return search_schedule(
        matmul_spec(128, 128, 128), beam_width=4, topk=2,
        measure=False, plan_db=db, **kw
    )


def test_plandb_hit_miss_counters_match_scripted_sweep(tmp_path):
    from repro.search import PlanDB

    db = PlanDB(str(tmp_path / "plans.json"))
    result = _tiny_search(db)  # cold DB: one lookup, one miss
    j = obs.metrics_json()["counters"]
    assert j["plandb.miss"] == 1
    assert "plandb.hit" not in j
    # beam counters mirror the search's own reported stats exactly
    assert j["search.candidates"] == result.stats.considered
    assert j["search.pruned_bound"] == result.stats.pruned_bound
    assert j["search.pruned_beam"] == result.stats.pruned_beam
    assert result.stats.considered > 0

    _tiny_search(db)  # warm DB: the cached ladder served, no re-search
    j2 = obs.metrics_json()["counters"]
    assert j2["plandb.hit"] == 1
    assert j2["plandb.miss"] == 1
    assert j2["search.candidates"] == result.stats.considered  # unchanged


def test_plandb_version_miss_counter(tmp_path):
    """A DB holding only a stale-format key counts a version_miss, so an
    operator can tell 'plans went cold on upgrade' from 'never swept'."""
    import repro.codegen.cache as cache_mod
    from repro.core.enumerate import matmul_spec
    from repro.search import PlanDB
    from repro.search.plandb import PLAN_VERSION, plan_key

    db = PlanDB(str(tmp_path / "plans.json"))
    spec = matmul_spec(128, 128, 128)
    hw = cache_mod.hardware_fingerprint()
    old_key = plan_key(spec, np.float32, hw, version=PLAN_VERSION - 1)
    db._cache.put(old_key, {"v": PLAN_VERSION - 1, "ranked": []})
    obs.metrics_reset()

    assert db.get(spec, np.float32, hw) is None
    j = obs.metrics_json()["counters"]
    assert j["plandb.version_miss"] == 1
    assert j["plandb.miss"] == 1

    # a truly-cold key is a plain miss, no version_miss
    obs.metrics_reset()
    assert db.get(matmul_spec(256, 128, 128), np.float32, hw) is None
    j = obs.metrics_json()["counters"]
    assert j["plandb.miss"] == 1
    assert "plandb.version_miss" not in j


def test_search_spans_recorded(tmp_path):
    from repro.search import PlanDB

    db = PlanDB(str(tmp_path / "plans.json"))
    _tiny_search(db)
    names = {e["name"] for e in obs.trace_events()}
    assert {"search.enumerate", "search.beam", "search.persist"} <= names
    beam = next(e for e in obs.trace_events() if e["name"] == "search.beam")
    assert beam["args"]["spec"] == "matmul"


def test_capture_dispatch_counters_match_report(tmp_path, monkeypatch):
    """The capture.harvested/dispatched/fallback counters record exactly
    what the capture report says happened — same numbers, one source of
    truth (the report), two surfaces (report JSON and the obs registry)."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setenv("REPRO_PLAN_DB", str(tmp_path / "plans.json"))
    import jax
    import jax.numpy as jnp

    from repro import capture
    from repro.models.api import get_api

    cfg = capture.demo_configs()["dense"]
    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(7)
    toks = jnp.asarray(
        rng.integers(0, cfg.vocab, (capture.DEMO_BATCH, capture.DEMO_SEQ)),
        jnp.int32,
    )
    batch = {"tokens": toks, "labels": toks}
    report = capture.optimize(
        lambda p, b: api.loss(p, cfg, b), interpret=True, label="obs-dense"
    ).report_for(params, batch)

    j = obs.metrics_json()["counters"]
    assert j["capture.harvested"] == report.harvested
    assert j["capture.dispatched"] == report.dispatched
    assert j["capture.fallback"] == report.fallback
    # per-op breakdown sums back to the dispatched total
    per_op = {k: v for k, v in j.items()
              if k.startswith("capture.dispatched.")}
    assert sum(per_op.values()) == report.dispatched
    names = {e["name"] for e in obs.trace_events()}
    assert {"capture.trace", "capture.harvest"} <= names


# ------------------------------------------------- explain round-trip


def test_explain_roundtrip_through_plan_db(tmp_path):
    from repro.search import PlanDB

    db = PlanDB(str(tmp_path / "plans.json"))
    result = _tiny_search(db)
    out = explain_mod.explain(db.path, "matmul@128x128x128")
    assert out.startswith("plan matmul@128x128x128")
    # the winner's roofline terms made it to disk and back
    best = result.best
    assert best.explain, "search did not attach explain terms"
    for term in ("compute_s", "hbm_s", "comm_s", "penalty"):
        assert term in best.explain
    with open(db.path) as f:
        entry = next(
            e for e in json.load(f).values()
            if isinstance(e, dict) and e.get("ranked")
        )
    assert entry["ranked"][0]["explain"] == pytest.approx(best.explain)
    # and the rendered table shows them as columns
    assert "compute_s" in out and "hbm_s" in out


def test_explain_selector_grammar():
    p = explain_mod.parse_selector("matmul@512x512x512@mesh=2x4@dtype=bfloat16")
    assert p == {
        "name": "matmul", "shape": "512x512x512",
        "mesh": "2x4", "dtype": "bfloat16",
    }
    assert explain_mod.parse_selector("matmul.dA")["name"] == "matmul.dA"
    with pytest.raises(ValueError):
        explain_mod.parse_selector("matmul@bogus=1")
    with pytest.raises(ValueError):
        explain_mod.parse_selector("")


def test_explain_unknown_selector_lists_names(tmp_path):
    from repro.search import PlanDB

    db = PlanDB(str(tmp_path / "plans.json"))
    _tiny_search(db)
    with pytest.raises(LookupError, match="matmul"):
        explain_mod.explain(db.path, "nope@1x1x1")


# ------------------------------------------------------------ obs.log


def test_log_levels(capsys, monkeypatch):
    monkeypatch.delenv("REPRO_LOG", raising=False)
    log_mod.info("serve", "hello")
    log_mod.debug("serve", "noisy")
    out = capsys.readouterr().out
    assert out == "[serve] hello\n"  # byte-identical to the old print
    monkeypatch.setenv("REPRO_LOG", "quiet")
    log_mod.info("serve", "hidden")
    assert capsys.readouterr().out == ""
    monkeypatch.setenv("REPRO_LOG", "debug")
    log_mod.debug(None, "bare line")
    assert capsys.readouterr().out == "bare line\n"
