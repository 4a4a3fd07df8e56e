"""The program's spans on the profiler's host plane, the idle gaps named by
them, the decode step's named scopes, and the readers built on them."""

from __future__ import annotations

import collections
import os
import re

import jax
import numpy as np
import pytest
from conftest import tiny_cell

from perfbench import harness, hostplane, spec, xtrace
from perfbench.hostplane import Span, View

from repro import obs
from repro.obs import trace as obs_trace


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    bound = obs_trace._annotation
    obs.trace_reset()
    monkeypatch.setattr(hostplane, "_cached", (None, None))
    yield
    obs.bind_annotation(bound)
    obs.trace_reset()


def _profile(path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return hostplane.load(str(path))


def test_a_cpu_profile_holds_the_obs_spans_on_its_host_plane(tmp_path):
    obs.bind_annotation(jax.profiler.TraceAnnotation)
    f = jax.jit(lambda x: (x @ x).sum())
    x = np.ones((64, 64), np.float32)
    f(x).block_until_ready()

    def body():
        with obs.span("serve.engine"):
            for i in range(2):
                with obs.span("serve.decode.step", step=i, live=2):
                    with obs.span("serve.decode.dispatch", step=i):
                        y = f(x)
                    with obs.span("serve.decode.wait", step=i):
                        y.block_until_ready()
            with obs.span("search.beam"):     # not a serving span
                pass

    spans = hostplane.host_spans(_profile(tmp_path, body))
    assert [(s.name, s.depth) for s in spans] == [
        ("serve.engine", 0),
        ("serve.decode.step", 1), ("serve.decode.dispatch", 2),
        ("serve.decode.wait", 2),
        ("serve.decode.step", 1), ("serve.decode.dispatch", 2),
        ("serve.decode.wait", 2)]
    steps = [s for s in spans if s.name == "serve.decode.step"]
    assert [(s.args["step"], s.args["live"]) for s in steps] == [(0, 2), (1, 2)]
    eng = spans[0]
    assert all(eng.start_ns <= s.start_ns and s.end_ns <= eng.end_ns
               for s in spans[1:])
    obs_steps = [e for e in obs.trace_events()
                 if e["name"] == "serve.decode.step"]
    # the same extents on both clocks, to within the annotation's own cost
    for h, o in zip(steps, obs_steps):
        assert abs((h.end_ns - h.start_ns) * 1e-3 - o["dur"]) < 200


def test_without_a_bound_annotation_the_host_plane_holds_no_span(tmp_path):
    obs.bind_annotation(None)

    def body():
        with obs.span("serve.engine"):
            pass

    assert hostplane.host_spans(_profile(tmp_path, body)) == []
    assert len(obs.trace_events()) == 1


def _sp(name, s, t, depth, **args):
    return Span(name, float(s), float(t), depth, args)


SPANS = [_sp("serve.engine", 0, 100, 0),
         _sp("serve.decode.step", 10, 40, 1, step=0),
         _sp("serve.decode.dispatch", 10, 15, 2, step=0),
         _sp("serve.decode.wait", 15, 40, 2, step=0),
         _sp("serve.decode.emit", 40, 50, 1, step=0),
         _sp("host.gc", 42, 48, 2),
         _sp("serve.decode.prepare", 53, 55, 1, step=1)]


@pytest.mark.parametrize("gap,name", [
    ((16, 30), "serve.decode.wait"),
    ((41, 49), "host.gc"),
    ((38, 45), "host.gc"),        # mostly emit, and inside it the collection
    ((12, 14), "serve.decode.dispatch"),
    ((50, 53), "engine loop"),    # the engine's own work between phases
    ((101, 110), "engine loop"),  # no span at all
])
def test_gaps_are_named_by_the_deepest_span_covering_most(gap, name):
    assert hostplane.name_gap(SPANS, *gap) == name


def test_gap_naming_picks_the_child_that_covers_most():
    spans = [_sp("serve.engine", 0, 100, 0),
             _sp("serve.prefill", 10, 30, 1, rid=3),
             _sp("serve.prefill.dispatch", 10, 12, 2),
             _sp("serve.prefill.wait", 12, 30, 2),
             _sp("serve.decode.prepare", 30, 31, 1)]
    assert hostplane.name_gap(spans, 11, 31) == "serve.prefill.wait"
    assert hostplane.name_gap(spans, 9, 11.5) == "serve.prefill.dispatch"


SCOPED_HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[8,64]) -> bf16[8,64] {
  %param_0 = bf16[8,64]{1,0} parameter(0)
  ROOT %gather.1 = bf16[8,64]{1,0} copy(%param_0), metadata={op_name="jit(step)/paged_view/gather"}
}

%body (p: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {
  %p = (s32[], bf16[8,64]{1,0}) parameter(0)
  %get-tuple-element.1 = bf16[8,64]{1,0} get-tuple-element(%p), index=1
  %dot.2 = bf16[8,64]{1,0} dot(%get-tuple-element.1, %get-tuple-element.1), lhs_contracting_dims={1}, rhs_contracting_dims={1}, metadata={op_name="jit(step)/while/body/decode_attention/dot_general"}
  ROOT %tuple.1 = (s32[], bf16[8,64]{1,0}) tuple(%get-tuple-element.1, %dot.2)
}

%gather_body (q: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {
  %q = (s32[], bf16[8,64]{1,0}) parameter(0)
  %get-tuple-element.8 = bf16[8,64]{1,0} get-tuple-element(%q), index=1
  %dynamic-update-slice_fusion.7 = bf16[8,64]{1,0} fusion(%get-tuple-element.8), kind=kLoop, calls=%fused_computation.1
  ROOT %tuple.8 = (s32[], bf16[8,64]{1,0}) tuple(%get-tuple-element.8, %dynamic-update-slice_fusion.7)
}

%gather_cond (r: (s32[], bf16[8,64])) -> pred[] {
  %r = (s32[], bf16[8,64]{1,0}) parameter(0)
  ROOT %compare.9 = pred[] compare(%r, %r), direction=LT
}

ENTRY %main (a: bf16[8,64]) -> bf16[8,64] {
  %a = bf16[8,64]{1,0} parameter(0)
  %while.6 = (s32[], bf16[8,64]{1,0}) while(%a), condition=%gather_cond, body=%gather_body, metadata={op_name="jit(step)/paged_view/gather"}
  %while.7 = (s32[], bf16[8,64]{1,0}) while(%a), condition=%gather_cond, body=%body, metadata={op_name="jit(step)/while"}
  %gather_fusion = bf16[8,64]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/paged_view/gather"}
  %copy.3 = bf16[8,64]{1,0} copy(%gather_fusion)
  %scatter.4 = bf16[8,64]{1,0} scatter(%copy.3, %a), metadata={op_name="jit(step)/scatter_token/paged_view/scatter"}
  ROOT %add.5 = bf16[8,64]{1,0} add(%scatter.4, %a), metadata={op_name="jit(step)/add"}
}
"""


def test_scopes_map_the_ops_that_run_and_the_innermost_scope_wins():
    """The op inside an unscoped loop counts for its own scope; a scoped
    loop counts whole, and the ops inside it not again."""
    counted, inside = hostplane.scope_instructions(SCOPED_HLO)
    assert counted == {"gather_fusion": "paged_view", "dot.2": "decode_attention",
                       "scatter.4": "paged_view", "while.6": "paged_view"}
    assert inside == {"q", "get-tuple-element.8",
                      "dynamic-update-slice_fusion.7", "tuple.8", "r",
                      "compare.9"}


@pytest.mark.parametrize("cut", [None, 2])
def test_the_first_chip_is_read_as_xtrace_reads_it(cut):
    """The recorded v5e trace of five runs of a program: the one-pass read
    gives xtrace's module runs, op times, module busy time and idle gaps,
    over the whole trace and cut at the third run's start."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(os.path.join(DATA, "scan_dots.xplane.pb"))
    ref = xtrace.from_profile(pd)
    d = ref.devices[0]
    module = xtrace.module_name(d.modules[0].name)
    lo = d.modules[0].start_ns - 1
    hi = d.ops[-1].end_ns + 1 if cut is None else d.modules[cut].start_ns
    start, modules, ops = hostplane.chip_lines(pd)
    chip = hostplane.read_chip(modules, ops, module, lo, hi)
    names = {xtrace.op_name(e.name) for e in d.ops}
    assert start == ref.start_wall_ns
    assert chip.runs == [(m.start_ns, m.end_ns) for m in d.modules
                         if xtrace.module_name(m.name) == module]
    assert chip.op_ns == pytest.approx(xtrace.ops_ns(d, module, names, lo, hi))
    assert chip.decode_ns == pytest.approx(
        xtrace.module_busy_ns(d, module, lo, hi))
    assert chip.gaps == xtrace.idle_gaps(d, lo, hi)
    assert 0 < len(chip.op_ns) and chip.decode_ns > 0


def test_an_unordered_line_is_read_whole():
    """Ops out of time order: once a line shows it is not in time order,
    it is read past ``hi``, and every op before ``hi`` counts."""
    Ev = collections.namedtuple("Ev", "name start_ns duration_ns")
    mods = [Ev("jit_step(1)", 0, 100), Ev("jit_other(2)", 100, 50)]
    ops = [Ev("%b.2 = f32[] b()", 50, 30), Ev("%a.1 = f32[] a()", 10, 20),
           Ev("%late.9 = f32[] c()", 200, 5), Ev("%o.3 = f32[] o()", 110, 20)]
    chip = hostplane.read_chip(mods, ops, "jit_step", 0, 150)
    assert chip.runs == [(0.0, 100.0)]
    assert chip.op_ns == {"a.1": 20.0, "b.2": 30.0}
    assert chip.decode_ns == 50.0
    assert chip.gaps == [(0, 10.0), (30.0, 50.0), (80.0, 110.0),
                         (130.0, 150)]


def test_a_cpu_compiled_decode_step_carries_the_three_scopes(isolated_caches):
    from repro.launch.serving import ContinuousEngine
    from repro.search import serving_phase

    cell = tiny_cell("deepseek-7b.batch")
    serve = cell.serve()
    cfg = serve.program_config(cell.config)
    eng = ContinuousEngine(cfg, lanes=2, page_size=16, n_pages=9, max_ctx=64)
    bt = np.zeros((2, eng.max_pages), np.int32)
    z = np.zeros((2,), np.int32)
    with serving_phase("decode"):
        hlo = eng.decode._step.lower(eng.params, eng.pools, bt, z,
                                     z).compile().as_text()
    counted, _ = hostplane.scope_instructions(hlo)
    assert set(counted.values()) == set(hostplane.SCOPES)


# ---------------------------------------------------------------------------
# the readers, on a synthetic run
# ---------------------------------------------------------------------------

OFF = 5e9       # trace ns minus perf_counter ns


def _run():
    """Two requests submitted at 10.0 and 10.1 s, prefilled at 10.02 and
    10.3; two decode steps inside the window [10, 11), one after it."""
    cell = tiny_cell("qwen3-8b.chat")
    reqs = [dict(rid=0, due=10.0, t_submit=10.0, t_first=10.05, t_done=10.9,
                 n_out=3, max_new=3, prompt_len=8),
            dict(rid=1, due=10.1, t_submit=10.1, t_first=10.35, t_done=11.2,
                 n_out=3, max_new=3, prompt_len=8),
            dict(rid=2, due=10.2, t_submit=10.2, t_first=None, t_done=10.2,
                 n_out=0, max_new=0, prompt_len=8)]
    steps = [(10.4, 10.5, 2), (10.6, 10.7, 2), (11.1, 11.2, 1)]
    raw = dict(t_start=10.0, t_end=11.3, requests=reqs, stats={},
               steps=steps, compiles=0)
    run = harness.make_run(cell, raw, 1.0, 3.0)
    run.trace = {"decode_module": "jit_step"}
    return run


def _ns(t):
    return t * 1e9 + OFF


def _view():
    spans = [_sp("serve.engine", _ns(10.0), _ns(11.3), 0),
             _sp("serve.prefill", _ns(10.02), _ns(10.05), 1, rid=0),
             _sp("serve.prefill", _ns(10.3), _ns(10.35), 1, rid=1)]
    for k, (s, t, _) in enumerate(_run().steps):
        spans += [_sp("serve.decode.prepare", _ns(s - 0.003), _ns(s), 1,
                      step=k),
                  _sp("serve.decode.step", _ns(s), _ns(t), 1, step=k, live=2),
                  _sp("serve.decode.dispatch", _ns(s), _ns(s + 0.001), 2,
                      step=k),
                  _sp("serve.decode.wait", _ns(s + 0.001), _ns(t), 2, step=k),
                  _sp("serve.decode.emit", _ns(t), _ns(t + 0.002), 1, step=k)]
    scopes = {"paged_view": 30.0, "scatter_token": 10.0,
              "decode_attention": 20.0}
    return View(spans, _ns(10.0), _ns(11.0), OFF, scopes, 100.0)


def _read(name, run):
    return spec.reader(name)(run)


def test_readers_on_a_synthetic_run(monkeypatch):
    monkeypatch.setattr(hostplane, "view", lambda run: _view())
    run = _run()
    # waits 0.02 and 0.2 s
    assert _read("queue_wait_mean_ms.chat", run) == pytest.approx(110.0)
    # the two steps that ended inside the window: 3 + 1 + 2 ms each
    assert _read("decode_host_ms.batch", run) == pytest.approx(6.0)
    for name in ("paged_kv_share.batch", "paged_kv_share.chat"):
        assert _read(name, run) == pytest.approx(40.0)


def test_readers_leave_out_what_the_run_did_not_leave(monkeypatch):
    run = _run()
    # no device trace: nothing read, nothing loaded
    run.trace = None
    monkeypatch.setattr(hostplane, "load", lambda *a: pytest.fail("loaded"))
    for name in ("queue_wait_mean_ms.chat", "decode_host_ms.batch",
                 "paged_kv_share.batch", "paged_kv_share.chat"):
        assert _read(name, run) is None
    # a trace whose host plane lost a prefill and a step's emit, and a
    # step with no scoped instruction
    v = _view()
    v.spans = [s for s in v.spans if not (
        s.name == "serve.prefill" and s.args["rid"] == 1
        or s.name == "serve.decode.emit" and s.args["step"] == 1)]
    v.scope_ns = {}
    monkeypatch.setattr(hostplane, "view", lambda run: v)
    for name in ("queue_wait_mean_ms.chat", "decode_host_ms.batch",
                 "paged_kv_share.batch"):
        assert _read(name, _run()) is None


def test_a_program_without_annotations_reads_none(tmp_path, monkeypatch):
    """A traced run of a program that binds no annotation: the readers
    find no program span on the host plane and leave their metrics out,
    without raising."""
    obs.bind_annotation(None)

    def body():
        with obs.span("serve.engine"):
            jax.numpy.ones(4).block_until_ready()

    pd = _profile(tmp_path, body)
    monkeypatch.setattr(hostplane, "load", lambda *a: pd)
    run = _run()
    for name in ("queue_wait_mean_ms.chat", "decode_host_ms.batch",
                 "paged_kv_share.chat"):
        assert _read(name, run) is None


def _cpu_window(tmp_path):
    """A tiny chat window under a CPU profile: the run and the profile."""
    obs.bind_annotation(jax.profiler.TraceAnnotation)
    cell = tiny_cell("qwen3-8b.chat")
    key, rng = harness.seed_key(4)
    server = harness.Server(cell, key, harness.CompileClock())
    raw = {}

    def body():
        raw.update(harness.window(server, 1.0, rng, harness.CompileClock()))

    pd = _profile(tmp_path, body)
    server.close()
    return harness.make_run(cell, raw, 1.0, 0.0), pd


def test_a_traced_cpu_window_puts_each_request_on_the_trace_clock(
        tmp_path, isolated_caches):
    """On the trace's clock, each request's prefill starts after its submit
    stamp and before its first token, and every decode step ending in the
    window has its host work."""
    run, pd = _cpu_window(tmp_path)
    spans = hostplane.host_spans(pd)
    eng = next(s for s in spans if s.name == hostplane.LOOP)
    off = eng.start_ns - hostplane.engine_start_pc() * 1e9
    waits = hostplane.queue_waits(run.requests, spans, off)
    reqs = [r for r in run.requests if r["max_new"] > 0]
    assert len(waits) == len(reqs) > 0
    for w, r in zip(waits, reqs):
        # the two clocks agree to within the annotations' own cost
        assert -1e-4 <= w <= r["t_first"] - r["t_submit"] + 1e-4
    n = sum(end <= run.close for _, end, _ in run.steps)
    host = hostplane.decode_host_s(spans, n)
    assert n > 0 and len(host) == n and all(h > 0 for h in host)


def test_the_whole_view_of_a_traced_window_with_a_chip_beside_it(
        tmp_path, isolated_caches, monkeypatch, capsys):
    """The CPU window's host plane, and beside it a chip that ran the
    decode module of ``SCOPED_HLO`` inside each ``serve.decode.wait``, busy
    for 90 % of each run, 50 % under ``paged_view``."""
    run, pd = _cpu_window(tmp_path)
    waits = [s for s in hostplane.host_spans(pd)
             if s.name == "serve.decode.wait"]
    Ev = collections.namedtuple("Ev", "name start_ns duration_ns")
    mods, ops = [], []
    for w in waits:
        a, d = w.start_ns, (w.end_ns - w.start_ns) / 100
        mods.append(Ev("jit_step(7)", a, 100 * d))
        for name, s, t in (("gather_fusion", 0, 40), ("scatter.4", 40, 50),
                           ("dot.2", 50, 70), ("add.5", 70, 90)):
            ops.append(Ev(f"%{name} = bf16[8,64]{{1,0}} op(%a)",
                          a + s * d, (t - s) * d))
    hlo = tmp_path / "decode.hlo.txt"
    hlo.write_text(SCOPED_HLO)
    monkeypatch.setattr(hostplane, "HLO_PATH", str(hlo))
    monkeypatch.setattr(hostplane, "load", lambda *a: pd)
    monkeypatch.setattr(hostplane, "chip_lines", lambda pd: (0, mods, ops))
    run.trace = {"decode_module": "jit_step"}
    assert hostplane.paged_kv_share(run) == pytest.approx(100 * 50 / 90)
    assert hostplane.decode_host_ms(run) > 0
    assert hostplane.queue_wait_ms(run) >= 0
    err = capsys.readouterr().err
    n = sum(w.end_ns <= hostplane.view(run).hi for w in waits)
    assert f"clock: {n} of the window's {n} serve.decode.wait spans" in err
    # each run starts as its step's wait does, after the dispatch, and
    # ends with the wait: both bounds on the host plane's skew hold
    m = re.search(r"dispatch start, median (\S+) us, least (\S+) us; wait "
                  r"end minus module end, median (\S+) us, least (\S+) us", err)
    lead, least_lead, lag, least_lag = (float(x) for x in m.groups())
    assert 0 < least_lead <= lead and lag == least_lag == 0
    assert "idle gaps by host-plane span: " in err
    assert "unscoped" in err and "jit_step/add.5" in err
