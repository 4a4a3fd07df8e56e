"""From a JAX profiler trace to device busy time, per-op time and GEMM time.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU each
chip has a plane ``/device:TPU:<n>`` with, among others, the lines

* ``XLA Modules``: one event per execution of a compiled program, named
  ``jit_<function>(<fingerprint>)``;
* ``XLA Ops``: one event per HLO instruction executed, named by the
  instruction's text (``%fusion.16 = bf16[...] fusion(...), kind=...``).
  A ``while`` or ``conditional`` is an event of its own that spans the
  events of its body.

Event times are nanoseconds from the trace's start, which the plane
``Task Environment`` gives in wall-clock nanoseconds (``profile_start_time``).

Which instructions implement the GEMMs of a program comes from that
program's compiled HLO text (:func:`gemm_instructions`), not from fusion
numbers: names are only looked up inside the executions of that module.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import math
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

#: ops that only contain other ops: their time is their body's
CONTAINERS = ("while", "conditional", "call")

_NAME = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r"^%?[\w.\-]+ = .*?\s([\w\-]+)\(")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Device:
    """One chip's modules and ops, each sorted by start."""
    name: str
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Trace:
    start_wall_ns: int
    devices: List[Device]


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def from_profile(pd) -> Trace:
    start = 0
    devices = []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            start = int(stats.get("profile_start_time", 0))
        elif plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ev = {k: sorted((Event(e.name, float(e.start_ns),
                                   float(e.duration_ns))
                             for e in lines[k].events),
                            key=lambda e: e.start_ns)
                  if k in lines else []
                  for k in ("XLA Modules", "XLA Ops")}
            devices.append(Device(plane.name, ev["XLA Modules"],
                                  ev["XLA Ops"]))
    devices.sort(key=lambda d: d.name)
    return Trace(start, devices)


def op_name(event_name: str) -> str:
    """``fusion.16`` from ``%fusion.16 = bf16[...] fusion(...)``."""
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name


def opcode(event_name: str) -> str:
    m = _OPCODE.match(event_name)
    return m.group(1) if m else ""


def module_name(event_name: str) -> str:
    """``jit_step`` from ``jit_step(1234)``."""
    return event_name.split("(", 1)[0]


def _clip(ev: Iterable[Event], lo: float, hi: float):
    for e in ev:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            yield s, t


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(dev: Device, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi) in which some op ran on the chip."""
    return sum(t - s for s, t in union(_clip(dev.ops, lo, hi)))


def idle_gaps(dev: Device, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The intervals of [lo, hi) in which no op ran."""
    gaps, at = [], lo
    for s, t in union(_clip(dev.ops, lo, hi)):
        if s > at:
            gaps.append((at, s))
        at = max(at, t)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def _in_module(dev: Device, module: Optional[str], lo: float, hi: float):
    """The op events of [lo, hi), and of executions of ``module`` only
    when it is given, with the module execution each lies in."""
    mods = [m for m in dev.modules
            if module is None or module_name(m.name) == module]
    j = 0
    for e in dev.ops:
        if e.start_ns < lo or e.start_ns >= hi:
            continue
        while j < len(mods) and mods[j].end_ns <= e.start_ns:
            j += 1
        if j < len(mods) and mods[j].start_ns <= e.start_ns:
            yield mods[j], e


def op_seconds(dev: Device, lo: float, hi: float,
               top: int = 10) -> List[Tuple[str, float]]:
    """The ops that took most device time in [lo, hi): ``module/op``,
    seconds summed over executions, containers left out."""
    tot: Dict[str, float] = {}
    for m, e in _in_module(dev, None, lo, hi):
        if opcode(e.name) in CONTAINERS:
            continue
        k = f"{module_name(m.name)}/{op_name(e.name)}"
        tot[k] = tot.get(k, 0.0) + e.dur_ns
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [(k, v * 1e-9) for k, v in ranked]


def module_runs(dev: Device, module: str, lo: float, hi: float) -> List[Event]:
    return [m for m in dev.modules
            if module_name(m.name) == module and lo <= m.start_ns < hi]


def module_busy_ns(dev: Device, module: str, lo: float, hi: float) -> float:
    """Device time of ``module``'s executions in [lo, hi): the union of
    their ops' intervals."""
    return sum(t - s for s, t in union(
        (e.start_ns, e.end_ns) for _, e in _in_module(dev, module, lo, hi)))


def ops_ns(dev: Device, module: str, names: Set[str], lo: float,
           hi: float) -> Dict[str, float]:
    """Device time of each named instruction inside ``module``'s
    executions in [lo, hi), summed over executions."""
    out: Dict[str, float] = {}
    for _, e in _in_module(dev, module, lo, hi):
        n = op_name(e.name)
        if n in names:
            out[n] = out.get(n, 0.0) + e.dur_ns
    return out


# ---------------------------------------------------------------------------
# compiled HLO: which instructions implement a GEMM
# ---------------------------------------------------------------------------

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
# the shape may be a tuple, with spaces; the opcode is the first word after
# it that opens a parenthesis
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
_DIMS = re.compile(r"^\w+\[([\d,]*)\]")


def _dims(shape: str) -> Optional[Tuple[int, ...]]:
    m = _DIMS.match(shape)
    if not m:
        return None
    return tuple(int(x) for x in m.group(1).split(",") if x)


def _parse_hlo(text: str):
    """Computations of an HLO module: name -> list of (instr, shape,
    opcode, operand names, calls)."""
    comps: Dict[str, list] = {}
    cur = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        args = rest.split(")", 1)[0]
        operands = re.findall(r"%([\w.\-]+)", args)
        calls = re.findall(r"(?:calls|body|to_apply)=%([\w.\-]+)", rest)
        cur.append((name, shape, op, operands, calls))
    return comps


def gemm_instructions(hlo_text: str,
                      gemms: Sequence[Tuple[int, int, int]]) -> Set[str]:
    """Instructions of a compiled module that compute one of ``gemms``.

    An instruction that runs as an op of its own (one not inside a fused
    computation) computes a GEMM when it is a ``dot``, a ``convolution``,
    a custom call (a generated kernel) or a fusion that contains a dot or
    convolution, and one of its operands is a GEMM's weight: dims ending
    in (K, N) or (N, K) of an (M, K, N) in ``gemms``, stacked over layers
    or not.  Whatever the compiler fused into such an op (the slice of the
    layer's weight, a norm, rope, a residual add, an argmax over the
    logits) is part of its time.  A weight the compiler reshaped still has
    the element count of one layer's weight or of all layers' stacked."""
    comps = _parse_hlo(hlo_text)
    weights, sizes = set(), set()
    for (_, k, n), c in collections.Counter(gemms).items():
        weights.update({(k, n), (n, k)})
        sizes.update({k * n, c * k * n})

    def has_dot(comp: str, seen=()) -> bool:
        for name, shape, op, operands, calls in comps.get(comp, []):
            if op in ("dot", "convolution"):
                return True
            if op == "fusion" and any(c not in seen and has_dot(
                    c, seen + (comp,)) for c in calls):
                return True
        return False

    fused = {c for instrs in comps.values() for i in instrs
             if i[2] == "fusion" for c in i[4]}
    out = set()
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        shapes = {i[0]: _dims(i[1]) for i in instrs}
        for name, shape, op, operands, calls in instrs:
            if not (op in ("dot", "convolution", "custom-call")
                    or (op == "fusion" and any(has_dot(c) for c in calls))):
                continue
            dims = [shapes.get(o) for o in operands]
            if any(d is not None and len(d) >= 2 and (
                    d[-2:] in weights or math.prod(d) in sizes)
                   for d in dims):
                out.add(name)
    return out
