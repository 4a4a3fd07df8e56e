"""The program's own spans on the profiler's host plane, and the device time
of the decode step's named scopes: the readings that put the program's
spans and the chip's ops on one clock.

While a profile is being taken, each ``repro.obs`` span also enters a
``jax.profiler.TraceAnnotation`` under its name and args (the serving tier
binds it on import).  The profiler puts those on the ``/host:CPU`` plane,
one line per thread, with the args as the event's stats, in the
nanoseconds-from-start timebase of the device planes.  The engine's span
``serve.engine`` is on both the host plane and ``repro.obs``'s
``perf_counter`` clock, which maps the engine's request stamps onto the
trace.

The decode step's compiled text keeps each instruction's
``jax.named_scope`` path in its ``op_name`` metadata (a fusion's is its
root's), which names the device time of ``paged_view``, ``scatter_token``
and ``decode_attention``.

A program that binds no annotation leaves no program span on the host
plane, and one without the scopes leaves no scoped instruction: the
readings are then None.  So is every reading of a run without a device
trace (``run.trace`` None: no profile, or one that lost decode steps of
the window).
"""

from __future__ import annotations

import bisect
import dataclasses
import gc
import glob
import math
import os
import re
import statistics
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

from . import xtrace
from .harness import TRACE_DIR, log
from .spec import ROOT

#: name prefixes of the program's spans
PROGRAM = ("serve.", "host.gc")
#: the engine loop's own span, over the whole window
LOOP = "serve.engine"
SCOPES = ("paged_view", "scatter_token", "decode_attention")
#: the scopes whose ops move the paged KV cache
PAGED_KV = ("paged_view", "scatter_token")
#: the host work on a decode step's critical path
DECODE_HOST = ("serve.decode.prepare", "serve.decode.dispatch",
               "serve.decode.emit")
HLO_PATH = os.path.join(ROOT, ".perfbench", "decode.hlo.txt")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    depth: int          # program spans around it on its thread's line
    args: Dict

    def overlap(self, s: float, t: float) -> float:
        return max(0.0, min(self.end_ns, t) - max(self.start_ns, s))


def host_spans(pd) -> List[Span]:
    """The program's spans on the host planes of a ``ProfileData``, sorted
    by start, each with its nesting depth among them."""
    out: List[Span] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((float(e.start_ns), float(e.duration_ns), e)
                          for e in line.events if e.name.startswith(PROGRAM)),
                         key=lambda x: (x[0], -x[1]))
            ends: List[float] = []
            for s, d, e in evs:
                while ends and ends[-1] <= s:
                    ends.pop()
                out.append(Span(e.name, s, s + d, len(ends), dict(e.stats)))
                ends.append(s + d)
    out.sort(key=lambda x: (x.start_ns, x.depth))
    return out


def name_gap(spans: Sequence[Span], s: float, t: float) -> str:
    """What the host was doing in the idle gap [s, t): from the outermost
    span that overlaps the gap most, down into whichever of its children
    overlaps it most, to the deepest.  ``"engine loop"`` where no span
    under the engine's own covers any of it."""
    near = [x for x in spans if x.overlap(s, t) > 0]
    chosen: Optional[Span] = None
    while True:
        depth = 0 if chosen is None else chosen.depth + 1
        kids = [x for x in near if x.depth == depth and (
            chosen is None or chosen.start_ns <= x.start_ns
            and x.end_ns <= chosen.end_ns)]
        if not kids:
            break
        chosen = max(kids, key=lambda x: x.overlap(s, t))
    return "engine loop" if chosen is None or chosen.name == LOOP \
        else chosen.name


_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_BODIES = re.compile(r"(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)|branch_computations=\{([^}]*)\}")


def scope_instructions(hlo_text: str, scopes: Sequence[str] = SCOPES
                       ) -> Tuple[Dict[str, str], Set[str]]:
    """Which instructions of a compiled module count for which of
    ``scopes``, and which are inside a scoped container.

    An instruction that runs as an op of its own (one outside every fused
    computation) counts for the innermost scope on its ``op_name`` path.  A
    while, conditional or call under a scope counts whole: the compiler's
    loop for a large gather carries no metadata on the ops inside it, so
    its body's time is the container's, and nothing inside it (the second
    set) is counted again."""
    comps: Dict[str, List[Tuple[str, str, List[str]]]] = {}
    fused = set()
    cur = None
    for line in hlo_text.splitlines():
        m = xtrace._COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = xtrace._INSTR.match(line)
        if cur is None or not m:
            continue
        name, _, op, rest = m.groups()
        if op == "fusion":
            fused.update(_CALLS.findall(rest))
        bodies = []
        if op in xtrace.CONTAINERS:
            for one, many in _BODIES.findall(rest):
                bodies += [one] if one else re.findall(r"%([\w.\-]+)", many)
        path = _OP_NAME.search(rest)
        cur.append((name, path.group(1) if path else "", bodies))

    def scope(path: str) -> Optional[str]:
        inner = [p for p in path.split("/") if p in scopes]
        return inner[-1] if inner else None

    covered = [c for instrs in comps.values()
               for _, path, bodies in instrs if scope(path) for c in bodies]
    inside_comps = set()
    while covered:
        c = covered.pop()
        if c not in inside_comps:
            inside_comps.add(c)
            covered += [k for _, _, bodies in comps.get(c, ()) for k in bodies]
    counted, inside = {}, set()
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for name, path, _ in instrs:
            if comp in inside_comps:
                inside.add(name)
            elif scope(path):
                counted[name] = scope(path)
    return counted, inside


def chip_lines(pd):
    """The profile's wall-clock start and the first chip's ``XLA Modules``
    and ``XLA Ops`` events (None where the profile holds no chip)."""
    start, planes = 0, []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = int(dict(plane.stats).get("profile_start_time", 0))
        elif plane.name.startswith("/device:TPU:"):
            planes.append(plane)
    if not planes:
        return start, None, None
    lines = {ln.name: ln for ln in min(planes, key=lambda pl: pl.name).lines}
    return start, *(lines[k].events if k in lines else ()
                    for k in ("XLA Modules", "XLA Ops"))


@dataclasses.dataclass
class Chip:
    """What the first chip did in a window, as :mod:`xtrace` reads it."""
    runs: List[Tuple[float, float]]     # the decode module's executions
    op_ns: Dict[str, float]             # device time of each of their ops
    op_text: Dict[str, str]             # an event's text for each such op
    decode_ns: float                    # the union of those ops' intervals
    gaps: List[Tuple[float, float]]     # the idle gaps of the window


def read_chip(modules, ops, module: str, lo: float, hi: float) -> Chip:
    """The window [lo, hi) of one chip's lines, in one pass over its ops.

    An op counts for ``module`` where its start lies in the window and in
    one of the module's executions, as in ``xtrace._in_module``; the gaps
    are ``xtrace.idle_gaps``.  A line in time order is read no further
    than its first event at or past ``hi`` (a closed queue's drain after
    the window is not read), and only the module's ops are named: an
    event's name is the instruction's whole text."""
    runs = sorted((float(m.start_ns), float(m.start_ns) + float(m.duration_ns))
                  for m in modules if xtrace.module_name(m.name) == module)
    starts = [s for s, _ in runs]
    items, last, ordered = [], -math.inf, True
    for e in ops:
        s = float(e.start_ns)
        ordered = ordered and s >= last
        last = s
        if s >= hi:
            if ordered:
                break
            continue
        t = s + float(e.duration_ns)
        if t <= lo:
            continue
        k = bisect.bisect_right(starts, s) - 1
        items.append((s, t, e.name if s >= lo and k >= 0 and s < runs[k][1]
                      else None))
    if not ordered:
        items.sort(key=lambda x: x[0])
    op_ns: Dict[str, float] = {}
    op_text: Dict[str, str] = {}
    names: Dict[str, str] = {}
    gaps, at = [], lo
    decode_ns, cur = 0.0, None
    for s, t, text in items:
        cs, ct = max(s, lo), min(t, hi)
        if ct > cs:
            if cs > at:
                gaps.append((at, cs))
            at = max(at, ct)
        if text is None:
            continue
        n = names.get(text)
        if n is None:
            n = names[text] = xtrace.op_name(text)
            op_text.setdefault(n, text)
        op_ns[n] = op_ns.get(n, 0.0) + (t - s)
        if cur is not None and s <= cur[1]:
            cur[1] = max(cur[1], t)
        else:
            if cur is not None:
                decode_ns += cur[1] - cur[0]
            cur = [s, t]
    if cur is not None:
        decode_ns += cur[1] - cur[0]
    if hi > at:
        gaps.append((at, hi))
    return Chip(runs, op_ns, op_text, decode_ns, gaps)


@dataclasses.dataclass
class View:
    """One traced window on the trace's clock."""
    spans: List[Span]
    lo: float                   # the window, trace ns
    hi: float
    offset_ns: float            # trace ns − perf_counter ns
    scope_ns: Dict[str, float]  # device time of each scope's ops
    decode_ns: float            # the decode module's device time


def load(log_dir: str = TRACE_DIR):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return ProfileData.from_file(paths[-1]) if paths else None


def engine_start_pc() -> Optional[float]:
    """When the window's ``serve.engine`` span began, ``perf_counter``."""
    from repro import obs
    from repro.obs import trace as obs_trace

    ev = next((e for e in obs.trace_events() if e["name"] == LOOP), None)
    if ev is None or not hasattr(obs_trace, "epoch"):
        return None
    return obs_trace.epoch() + ev["ts"] * 1e-6


_cached: Tuple[object, Optional[View]] = (None, None)


def view(run) -> Optional[View]:
    """The run's trace on one clock, read once for every reader.  The
    cyclic collector is paused while it reads: the trace's events are
    millions of objects, and collections over them would double the
    time."""
    global _cached
    if _cached[0] is not run:
        auto = gc.isenabled()
        gc.disable()
        try:
            _cached = (run, _view(run))
        finally:
            if auto:
                gc.enable()
    return _cached[1]


def _view(run) -> Optional[View]:
    if run.trace is None:
        return None
    tick = [time.perf_counter()]
    pd = load()
    if pd is None:
        return None
    tick.append(time.perf_counter())
    spans = host_spans(pd)
    tick.append(time.perf_counter())
    eng = next((x for x in spans if x.name == LOOP), None)
    pc = engine_start_pc()
    if eng is None or pc is None:
        log("host plane: no program span on it (the program binds no "
            "profiler annotation); its readings are left out")
        return None
    off = eng.start_ns - pc * 1e9
    close = min(run.t_end, run.t_start + run.seconds)
    lo, hi = run.t_start * 1e9 + off, close * 1e9 + off
    module = run.trace["decode_module"]
    start_wall, modules, ops = chip_lines(pd)
    if modules is None:
        return None
    chip = read_chip(modules, ops, module, lo, hi)
    del pd, modules, ops
    with open(HLO_PATH) as f:
        scoped, inside = scope_instructions(f.read())
    scope_ns = {sc: 0.0 for sc in SCOPES}
    for n, ns in chip.op_ns.items():
        if n in scoped:
            scope_ns[scoped[n]] += ns
    v = View(spans, lo, hi, off, scope_ns if scoped else {}, chip.decode_ns)
    tick.append(time.perf_counter())
    log(f"host plane: read in {tick[3] - tick[0]:.3f} s (profile "
        f"{tick[1] - tick[0]:.3f}, host spans {tick[2] - tick[1]:.3f}, chip "
        f"{tick[3] - tick[2]:.3f})")
    _log_clock(v, run, start_wall, chip.runs, module)
    gaps = sorted(chip.gaps, key=lambda g: g[0] - g[1])[:10]
    log("idle gaps by host-plane span: " + ", ".join(
        f"{name_gap(spans, s, t)} {(t - s) * 1e-9:.6f} s" for s, t in gaps))
    if not scoped:
        log(f"scopes: no instruction of {module} carries {', '.join(SCOPES)}")
        return v
    rest = chip.decode_ns - sum(scope_ns.values())
    log(f"scopes: {module} device time {chip.decode_ns * 1e-9:.4f} s: "
        + ", ".join(f"{k} {ns * 1e-9:.4f} s" for k, ns in scope_ns.items())
        + f", unscoped {rest * 1e-9:.4f} s")
    top = sorted(((n, ns) for n, ns in chip.op_ns.items()
                  if n not in scoped and n not in inside
                  and xtrace.opcode(chip.op_text[n]) not in xtrace.CONTAINERS),
                 key=lambda kv: -kv[1])[:8]
    log("scopes: top unscoped ops " + ", ".join(
        f"{module}/{n} {ns * 1e-9:.4f} s" for n, ns in top))
    return v


def _us(xs: Sequence[float]) -> str:
    return (f"median {statistics.median(xs) * 1e-3:.1f} us, least "
            f"{min(xs) * 1e-3:.1f} us" if xs else "none")


def _log_clock(v: View, run, start_wall: int,
               runs: Sequence[Tuple[float, float]], module: str) -> None:
    """How the host plane's clock agrees with the device planes' and with
    the window's ``(perf_counter, time_ns)`` mapping.

    A step's module run cannot start before the host dispatched it, nor end
    after the host's wait for it returned.  So were the host plane ``d``
    late against the device planes, the module start minus its step's
    ``serve.decode.dispatch`` start would read ``d`` less, and the wait end
    minus the module end ``d`` more: ``d`` lies between minus the least of
    the first and the least of the second."""
    # the mapping the window's bounds are read with elsewhere: a
    # (perf_counter, time_ns) pair against the profile's wall-clock start
    pc, wall = time.perf_counter(), time.time_ns()
    mapped = wall + (run.t_start - pc) * 1e9 - start_wall
    log(f"clock: window start mapped by (perf_counter, time_ns) minus by "
        f"the host plane's {LOOP} span: {(mapped - v.lo) * 1e-3:.1f} us")
    dispatch = {int(x.args["step"]): x.start_ns for x in v.spans
                if x.name == "serve.decode.dispatch" and "step" in x.args}
    waits = [x for x in v.spans if x.name == "serve.decode.wait"
             and v.lo <= x.end_ns <= v.hi]
    lag, lead, j = [], [], 0
    for w in waits:
        while j < len(runs) and runs[j][1] < w.start_ns:
            j += 1
        hit = [r for r in runs[j:j + 3] if r[0] < w.end_ns and w.start_ns < r[1]]
        if hit:
            lag.append(w.end_ns - hit[-1][1])
            d = dispatch.get(int(w.args.get("step", -1)))
            if d is not None:
                lead.append(hit[-1][0] - d)
    if waits:
        log(f"clock: {len(lag)} of the window's {len(waits)} "
            f"serve.decode.wait spans overlap a run of {module} "
            f"({100.0 * len(lag) / len(waits):.2f} %); module start minus "
            f"its step's serve.decode.dispatch start, {_us(lead)}; wait end "
            f"minus module end, {_us(lag)}")


# ---------------------------------------------------------------------------
# the readings
# ---------------------------------------------------------------------------

def queue_waits(requests: Sequence[Dict], spans: Sequence[Span],
                offset_ns: float) -> Optional[List[float]]:
    """Each request's wait for service, s: the start of its own first
    ``serve.prefill`` (by ``rid``) minus its submit stamp.  None where a
    request that had tokens to make has no prefill span."""
    start: Dict[int, float] = {}
    for x in spans:
        if x.name == "serve.prefill" and "rid" in x.args:
            start.setdefault(int(x.args["rid"]), x.start_ns)
    out = []
    for r in requests:
        if r["max_new"] <= 0:
            continue
        if r["rid"] not in start:
            return None
        out.append((start[r["rid"]] - offset_ns) * 1e-9 - r["t_submit"])
    return out


def queue_wait_ms(run) -> Optional[float]:
    """Mean wait for service over the window's requests: a few that arrive
    behind another's prefill move it, where a tail over ~60 requests
    reads only the bookkeeping between submit and prefill."""
    v = view(run)
    if v is None:
        return None
    waits = queue_waits(run.requests, v.spans, v.offset_ns)
    if waits is None:
        log("host plane: a request's serve.prefill is missing; "
            "queue_wait is left out")
        return None
    return 1e3 * statistics.fmean(waits) if waits else None


def decode_host_s(spans: Sequence[Span], n_steps: int) -> Optional[List[float]]:
    """Host seconds on the critical path of decode steps 0 .. n_steps-1:
    their prepare, dispatch and emit spans.  None where one is missing."""
    per: Dict[int, Dict[str, float]] = {}
    for x in spans:
        if x.name in DECODE_HOST and "step" in x.args:
            per.setdefault(int(x.args["step"]), {})[x.name] = \
                (x.end_ns - x.start_ns) * 1e-9
    out = []
    for i in range(n_steps):
        ph = per.get(i, {})
        if len(ph) != len(DECODE_HOST):
            return None
        out.append(sum(ph.values()))
    return out


def decode_host_ms(run) -> Optional[float]:
    """Mean host time on the critical path of the decode steps that ended
    inside the window."""
    v = view(run)
    if v is None:
        return None
    n = sum(end <= run.close for _, end, _ in run.steps)
    host = decode_host_s(v.spans, n)
    if host is None:
        log("host plane: a decode step's spans are missing; decode_host "
            "is left out")
        return None
    return 1e3 * statistics.fmean(host) if host else None


def paged_kv_share(run) -> Optional[float]:
    """Device time of the paged-KV scopes' ops over the decode module's
    device time, in the window."""
    v = view(run)
    if v is None or not v.scope_ns or not v.decode_ns:
        return None
    return 100.0 * sum(v.scope_ns[s] for s in PAGED_KV) / v.decode_ns
