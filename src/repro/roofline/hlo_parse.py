"""Trip-count-aware cost extraction from compiled HLO text.

``compiled.cost_analysis()`` counts each ``while`` body ONCE, which
undercounts scanned layer stacks by a factor of n_layers (and remat loops on
top).  This parser walks the HLO module, finds each while loop's trip count
(the canonical scan form compares the induction variable against an s32
constant inside the condition computation), and accumulates per-computation:

  * dot FLOPs        (2 * prod(output shape) * contracted extent, operand
                      shapes resolved through a per-computation symbol table)
  * collective bytes (payload of all-gather/all-reduce/reduce-scatter/
                      all-to-all/collective-permute; -start tuples halved)
  * op output bytes  (a proxy for HBM traffic)

then resolves the call graph from ENTRY, multiplying by enclosing trip
counts.  Only dot/convolution flops are counted — elementwise flops are
noise for these models — so the compute term is a *dot roofline*, the honest
number for MXU utilization.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_PARAM_RE = re.compile(r"([\w\.\-]+):\s*(\w+\[[\d,]*\])")
_WHILE_RE = re.compile(
    r"while\(.*?\).*?condition=%?([\w\.\-]+).*?body=%?([\w\.\-]+)"
)
_CALL_RE = re.compile(r"(?:calls=|to_apply=)%?([\w\.\-]+)")
_CONST_RE = re.compile(r"\bconstant\((\d+)\)")
_OPERANDS_RE = re.compile(r"\(([^()]*(?:\([^()]*\)[^()]*)*)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
def _split_operands(opstr: str) -> List[str]:
    """Split a dot operand list on commas OUTSIDE []/{} (shape commas)."""
    out, depth, cur = [], 0, []
    for ch in opstr:
        if ch in "[{(":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur).strip())
    return out

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _first_shape(type_str: str) -> Optional[Tuple[str, Tuple[int, ...]]]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    dt, dims = m.groups()
    return dt, tuple(int(d) for d in dims.split(",") if d)


def _all_shapes_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CompStats:
    dot_flops: float = 0.0
    dot_bytes: float = 0.0  # dot operand + output bytes (HBM traffic floor)
    coll_bytes: float = 0.0
    coll_by_type: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in _COLLECTIVES}
    )
    out_bytes: float = 0.0
    whiles: List[Tuple[str, str]] = dataclasses.field(default_factory=list)
    calls: List[str] = dataclasses.field(default_factory=list)
    max_const: int = 0


#: ops whose outputs are aliases/bookkeeping, not HBM materializations —
#: excluded from the bytes proxy (a loop-carried tuple GTE would otherwise
#: count the whole stacked parameter tree once per scan step)
_NO_TRAFFIC_OPS = (
    "get-tuple-element", "tuple(", "parameter(", "constant(", "bitcast(",
    "while(", "conditional(", "after-all(", "custom-call(",
)


def split_computations(hlo: str) -> Tuple[Dict[str, List[str]], Optional[str]]:
    comps: Dict[str, List[str]] = {}
    headers: Dict[str, str] = {}
    cur: Optional[str] = None
    entry_name = None
    for raw in hlo.splitlines():
        s = raw.strip()
        if cur is None:
            if s.endswith("{") and "->" in s and "(" in s:
                head = s[: s.find("(")].strip()
                name = head.split()[-1].lstrip("%")
                if not name:
                    continue
                cur = name
                comps[cur] = []
                headers[cur] = s
                if s.startswith("ENTRY"):
                    entry_name = cur
            continue
        if s == "}" or s.startswith("} "):
            cur = None
            continue
        comps[cur].append(s)
    # prepend headers so param shapes are visible to the symbol pass
    for name, h in headers.items():
        comps[name].insert(0, "//HEADER// " + h)
    return comps, entry_name


def _analyze_computation(lines: List[str]) -> CompStats:
    st = CompStats()
    symbols: Dict[str, Tuple[str, Tuple[int, ...]]] = {}

    # pass 1: symbol table (defs + header params)
    for s in lines:
        if s.startswith("//HEADER//"):
            for name, ty in _PARAM_RE.findall(s):
                sh = _first_shape(ty)
                if sh:
                    symbols[name] = sh
            continue
        m = _DEF_RE.match(s)
        if m:
            sh = _first_shape(m.group(2))
            if sh:
                symbols[m.group(1)] = sh

    # pass 2: stats
    for s in lines:
        if s.startswith("//HEADER//"):
            continue
        for mc in _CONST_RE.finditer(s):
            st.max_const = max(st.max_const, int(mc.group(1)))
        m = _DEF_RE.match(s)
        if not m:
            continue
        rhs = m.group(2)

        if " dot(" in rhs:
            idx = rhs.find(" dot(")
            out = _first_shape(rhs[:idx])
            opm = _OPERANDS_RE.search(rhs[idx:])
            ctm = _CONTRACT_RE.search(rhs)
            if out:
                out_elems = 1
                for d in out[1]:
                    out_elems *= d
                contract = 0
                op_bytes = 0.0
                if opm:
                    # one entry per operand token, positional: a token
                    # may carry its type inline (``f32[8,16]{1,0} %name``)
                    # or be just ``%name`` (sigil optional) — resolve
                    # the type if present, else the symbol table
                    shapes = []
                    for tok in _split_operands(opm.group(1)):
                        sh = _first_shape(tok)
                        if sh is None and tok:
                            nm = tok.split()[-1].lstrip("%")
                            sh = symbols.get(nm)
                        shapes.append(sh)
                    for sh in shapes:
                        if sh:
                            n = 1
                            for d in sh[1]:
                                n *= d
                            op_bytes += n * _DTYPE_BYTES.get(sh[0], 4)
                    lhs = shapes[0] if shapes else None
                    if lhs and ctm:
                        dims = [int(d) for d in ctm.group(1).split(",") if d]
                        contract = 1
                        for d in dims:
                            if d < len(lhs[1]):
                                contract *= lhs[1][d]
                    elif lhs and lhs[1]:
                        contract = lhs[1][-1]
                if contract == 0:
                    contract = 1
                st.dot_flops += 2.0 * out_elems * contract
                st.dot_bytes += op_bytes + out_elems * _DTYPE_BYTES.get(
                    out[0], 4
                )
        elif " convolution(" in rhs:
            out = _first_shape(rhs[: rhs.find(" convolution(")])
            if out:
                out_elems = 1
                for d in out[1]:
                    out_elems *= d
                st.dot_flops += 2.0 * out_elems  # lower bound

        for coll in _COLLECTIVES:
            started = f" {coll}-start(" in rhs
            plain = f" {coll}(" in rhs
            if not (started or plain):
                continue
            tok = f" {coll}-start(" if started else f" {coll}("
            idx = rhs.find(tok)
            type_str = rhs[:idx]
            b = _all_shapes_bytes(type_str)
            if started and type_str.strip().startswith("("):
                b //= 2
            st.coll_bytes += b
            st.coll_by_type[coll] += b
            break

        mw = _WHILE_RE.search(rhs)
        if mw:
            st.whiles.append((mw.group(1), mw.group(2)))
        else:
            for mc2 in _CALL_RE.finditer(rhs):
                st.calls.append(mc2.group(1))

        # elementwise/materialization proxy: skip bookkeeping ops AND dots
        # (dot traffic is tracked separately in dot_bytes)
        if " dot(" not in rhs and " convolution(" not in rhs and not any(
            tok in rhs for tok in _NO_TRAFFIC_OPS
        ):
            paren = rhs.find("(")
            type_part = rhs[:paren] if paren > 0 else rhs
            st.out_bytes += _all_shapes_bytes(type_part)
    return st


def analyze_hlo(hlo: str) -> Dict[str, float]:
    comps, entry = split_computations(hlo)
    stats = {name: _analyze_computation(lines) for name, lines in comps.items()}

    def trip_count(cond_name: str) -> int:
        st = stats.get(cond_name)
        # also look through fusions called by the condition
        best = st.max_const if st else 0
        if st:
            for c in st.calls:
                sub = stats.get(c)
                if sub:
                    best = max(best, sub.max_const)
        return max(best, 1)

    memo: Dict[str, Tuple] = {}

    def resolve(name: str, depth=0):
        if name in memo:
            return memo[name]
        st = stats.get(name)
        zero = (0.0, 0.0, 0.0, 0.0, {k: 0.0 for k in _COLLECTIVES})
        if st is None or depth > 64:
            return zero
        memo[name] = zero  # cycle guard
        flops, coll, byts = st.dot_flops, st.coll_bytes, st.out_bytes
        dbytes = st.dot_bytes
        by_type = dict(st.coll_by_type)
        for callee in st.calls:
            f, c, b, db, bt = resolve(callee, depth + 1)
            flops += f; coll += c; byts += b; dbytes += db
            for k in by_type:
                by_type[k] += bt[k]
        for cond, body in st.whiles:
            n = trip_count(cond)
            f, c, b, db, bt = resolve(body, depth + 1)
            flops += n * f
            coll += n * c
            byts += n * b
            dbytes += n * db
            for k in by_type:
                by_type[k] += n * bt[k]
        memo[name] = (flops, coll, byts, dbytes, by_type)
        return memo[name]

    if entry is None:
        return {"dot_flops": 0.0, "collective_bytes": 0.0,
                "out_bytes_proxy": 0.0, "dot_bytes": 0.0}
    flops, coll, byts, dbytes, by_type = resolve(entry)
    out = {
        "dot_flops": flops,
        "collective_bytes": coll,
        "out_bytes_proxy": byts,
        "dot_bytes": dbytes,
        "n_computations": float(len(comps)),
    }
    for k, v in by_type.items():
        out[f"coll_{k}"] = v
    return out
