"""The serving engine's spans: the decode step's phases in the order they
run, the blocking prefill span that carries its request's ``rid``, the
``host.gc`` span, and nothing at all with ``REPRO_OBS=0``."""

from __future__ import annotations

import gc

import numpy as np
import pytest

pytest.importorskip("jax")

from repro import obs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.serving import (  # noqa: E402
    ContinuousEngine,
    Gateway,
    ServeRequest,
)
from repro.obs import trace as obs_trace  # noqa: E402

DECODE = ["serve.decode.prepare", "serve.decode.dispatch",
          "serve.decode.wait", "serve.decode.emit"]


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    bound = obs_trace._annotation
    obs.metrics_reset()
    obs.trace_reset()
    yield
    obs.bind_annotation(bound)
    obs.metrics_reset()
    obs.trace_reset()


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("qwen3-8b").smoke()
    return ContinuousEngine(cfg, lanes=2, page_size=4, n_pages=17, max_ctx=32)


def _requests(vocab, n=3):
    rng = np.random.default_rng(5)
    return [ServeRequest(rid=10 + i, max_new=3 + i,
                         prompt=rng.integers(0, vocab, size=5 + 2 * i)
                         .astype(np.int32)) for i in range(n)]


def _start(e) -> float:
    return obs_trace.epoch() + e["ts"] * 1e-6


def _end(e) -> float:
    return obs_trace.epoch() + (e["ts"] + e["dur"]) * 1e-6


def _by_step(evs):
    out = {}
    for e in evs:
        if e["name"].startswith("serve.decode."):
            out.setdefault(e["args"]["step"], {})[e["name"]] = e
    return out


def test_decode_phases_run_in_order_and_nest_in_the_step(engine):
    st = engine.run(_requests(engine.cfg.vocab))
    steps = _by_step(obs.trace_events())
    assert len(steps) == st["decode_steps"] > 0
    for k, ph in sorted(steps.items()):
        assert set(ph) == set(DECODE) | {"serve.decode.step"}
        starts = [_start(ph[n]) for n in DECODE]
        assert starts == sorted(starts), k
        step = ph["serve.decode.step"]
        assert _end(ph["serve.decode.prepare"]) <= _start(step)
        assert _end(step) <= _start(ph["serve.decode.emit"])
        for child in ("serve.decode.dispatch", "serve.decode.wait"):
            c = ph[child]
            assert c["args"]["parent"] == "serve.decode.step"
            assert c["args"]["depth"] == step["args"]["depth"] + 1
            assert _start(step) <= _start(c) and _end(c) <= _end(step)
        assert _end(ph["serve.decode.dispatch"]) \
            <= _start(ph["serve.decode.wait"])
        # the step keeps its args for the readers of its extent
        assert step["args"]["step"] == k and step["args"]["live"] >= 1


def test_prefill_span_carries_rid_and_contains_its_wait(engine):
    reqs = _requests(engine.cfg.vocab)
    engine.run(reqs)
    evs = obs.trace_events()
    pre = [e for e in evs if e["name"] == "serve.prefill"]
    assert sorted(e["args"]["rid"] for e in pre) == [r.rid for r in reqs]
    for p in pre:
        assert p["args"]["tokens"] <= p["args"]["padded"]
        kids = {e["name"]: e for e in evs
                if e["name"].startswith("serve.prefill.")
                and _start(p) <= _start(e) and _end(e) <= _end(p)}
        assert set(kids) == {"serve.prefill.dispatch", "serve.prefill.wait"}
        assert all(e["args"]["parent"] == "serve.prefill"
                   for e in kids.values())
        assert _end(kids["serve.prefill.dispatch"]) \
            <= _start(kids["serve.prefill.wait"])


def test_prefill_start_lies_between_submit_and_first_token(engine):
    reqs = _requests(engine.cfg.vocab, n=4)
    engine.run(reqs)
    start = {e["args"]["rid"]: _start(e) for e in obs.trace_events()
             if e["name"] == "serve.prefill"}
    for r in reqs:
        assert r.t_submit <= start[r.rid] <= r.t_first, r.rid


def test_counts_and_no_gateway_span(engine):
    reqs = _requests(engine.cfg.vocab)
    st = Gateway(engine).run(reqs)
    j = obs.metrics_json()["counters"]
    assert j["serve.tokens"] == st["tokens"] == sum(r.max_new for r in reqs)
    assert j["serve.requests"] == len(reqs)
    assert "serve.preempted" not in j
    names = {e["name"] for e in obs.trace_events()}
    assert "serve.gateway" not in names and "serve.engine" in names


def test_obs_off_records_nothing_and_enters_nothing(engine, monkeypatch):
    entered = []

    class Recorder:
        def __init__(self, name, **args):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    obs.bind_annotation(Recorder)
    monkeypatch.setenv("REPRO_OBS", "0")
    before = list(gc.callbacks)
    seen = []

    def probe(phase, info):
        seen.append(list(gc.callbacks))

    gc.callbacks.append(probe)
    try:
        engine.run(_requests(engine.cfg.vocab, n=2))
        gc.collect()
    finally:
        gc.callbacks.remove(probe)
    assert obs.trace_events() == []
    assert entered == []
    assert gc.callbacks == before
    # no hook but the probe was registered while the engine ran
    assert all(cbs == before + [probe] for cbs in seen)


def test_bound_annotation_is_entered_by_the_engine(engine):
    entered = []

    class Recorder:
        def __init__(self, name, **args):
            self.name, self.args = name, args

        def __enter__(self):
            entered.append(("enter", self.name, self.args))
            return self

        def __exit__(self, *exc):
            entered.append(("exit", self.name, self.args))

    obs.bind_annotation(Recorder)
    engine.run(_requests(engine.cfg.vocab, n=1))
    names = [n for kind, n, _ in entered if kind == "enter"]
    for n in ["serve.engine", "serve.prefill", "serve.prefill.dispatch",
              "serve.prefill.wait"] + DECODE + ["serve.decode.step"]:
        assert n in names, n
    rid = next(a for kind, n, a in entered
               if kind == "enter" and n == "serve.prefill")["rid"]
    assert rid == 10
    # every annotation entered is exited, innermost first
    stack = []
    for kind, n, _ in entered:
        if kind == "enter":
            stack.append(n)
        else:
            assert stack.pop() == n
    assert stack == []


def test_gc_spans_time_oldest_generation_collections_only():
    before = list(gc.callbacks)
    auto = gc.isenabled()
    gc.disable()            # only the collections asked for below
    try:
        with obs.gc_spans():
            assert len(gc.callbacks) == len(before) + 1
            gc.collect(0)
            gc.collect(1)
            assert obs.trace_events() == []
            gc.collect()
        assert gc.callbacks == before
        (ev,) = obs.trace_events()
        assert ev["name"] == "host.gc" and ev["dur"] >= 0
        obs.trace_reset()
        gc.collect()        # outside the block: not recorded
        assert obs.trace_events() == []
    finally:
        if auto:
            gc.enable()


def test_a_collection_inside_a_span_exit_records_its_span(monkeypatch):
    """A full collection that runs while a span's exit holds the trace
    lock (the interpreter may collect at any bytecode) records its
    ``host.gc`` span there, under a bound annotation, and does not hang."""
    import threading

    entered = []

    class Recorder:
        def __init__(self, name, **args):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            pass

    class Collecting(list):
        def append(self, ev):
            super().append(ev)
            if ev["name"] == "outer":
                gc.collect(2)

    monkeypatch.setattr(obs_trace, "_events", Collecting())
    obs.bind_annotation(Recorder)

    def body():
        with obs.gc_spans():
            with obs.span("outer"):
                pass

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(timeout=30)
    if t.is_alive():
        # the hung thread holds the lock for good: free the rest of the run
        obs_trace._lock = threading.RLock()
        pytest.fail("a collection inside a span's exit hung")
    assert [e["name"] for e in obs.trace_events()] == ["outer", "host.gc"]
    assert entered == ["outer", "host.gc"]
