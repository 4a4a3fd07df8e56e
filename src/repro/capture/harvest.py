"""Jaxpr-level GEMM harvest: every ``dot_general`` becomes a ContractionSpec.

The model zoo (``repro.models``) lowers its matmuls through ``jnp.dot`` /
``jnp.einsum``, i.e. through the ``dot_general`` primitive — not through
``repro.ops`` — so before this module only hand-rewired call sites owned
plan-DB/autotune keys.  ``harvest_jaxpr`` walks a traced function (recursing
into ``jit``/``scan``/``remat``/``cond``/``while``/``custom_*`` sub-jaxprs),
classifies each ``dot_general`` equation against the spec families of
``core.enumerate`` (the single home of contraction naming, so every harvested
site owns the same plan-DB and autotune-cache keys a hand-rewired ``ops``
call would), and reports per site whether the capture rewriter
(``capture.rewrite``) can dispatch it through the generated-kernel pipeline
or must leave it untouched — with the reason.

Classification is the *single source of truth* shared with the rewriter:
``classify_dot_general`` decides, ``rewrite`` obeys.  Eligibility reuses the
``repro.ops`` kernel-dispatch predicates verbatim, so a site is dispatched
exactly when the equivalent ``ops`` entry point would run a generated kernel
for those shapes on this backend.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
from jax.extend import core as jcore

from ..core.enumerate import (
    ContractionSpec,
    attention_spec,
    batched_matmul_spec,
    grouped_matmul_spec,
    matmul_spec,
    transposed_matmul_spec,
)

#: dtypes the generated-kernel pipeline stores/accumulates correctly
SUPPORTED_DTYPES = ("float32", "bfloat16")

#: sub-jaxpr-carrying primitives the rewriter knows how to re-emit; sites
#: inside any *other* jaxpr-carrying primitive are fallback by containment
REWRITABLE_HOPS = frozenset({
    "jit", "closed_call", "core_call",
    "scan", "while", "cond",
    "remat", "remat2", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
})


@dataclasses.dataclass
class CaptureSite:
    """One ``dot_general`` equation of the traced function."""

    site_id: int
    path: str                  # eqn trail, e.g. "scan/remat2/eqn12"
    lhs_shape: Tuple[int, ...]
    rhs_shape: Tuple[int, ...]
    out_shape: Tuple[int, ...]
    dtype: str
    out_dtype: str
    dimension_numbers: Any
    op: Optional[str] = None           # dense | dense_transposed | batched_dense
    spec: Optional[ContractionSpec] = None
    status: str = "fallback"           # dispatched | fallback
    reason: str = ""                   # why a fallback site fell back

    @property
    def dispatched(self) -> bool:
        return self.status == "dispatched"

    def as_dict(self) -> Dict[str, Any]:
        return {
            "site_id": self.site_id,
            "path": self.path,
            "lhs_shape": list(self.lhs_shape),
            "rhs_shape": list(self.rhs_shape),
            "out_shape": list(self.out_shape),
            "dtype": self.dtype,
            "out_dtype": self.out_dtype,
            "op": self.op,
            "spec": None if self.spec is None else self.spec.name,
            "extents": None if self.spec is None else dict(self.spec.extents),
            "status": self.status,
            "reason": self.reason,
        }


def spec_key(spec: ContractionSpec, dtype: str) -> Tuple:
    """Plan-key granularity for deduplicating harvested GEMM sites — the
    single home of this tuple (report dedup, model sweeps, serve warmup
    all key on it)."""
    return (spec.name, tuple(sorted(spec.extents.items())), str(dtype))


@dataclasses.dataclass
class CaptureReport:
    """Per-site accounting for one captured function."""

    label: str = ""
    sites: List[CaptureSite] = dataclasses.field(default_factory=list)

    @property
    def harvested(self) -> int:
        return len(self.sites)

    @property
    def dispatched(self) -> int:
        return sum(1 for s in self.sites if s.dispatched)

    @property
    def fallback(self) -> int:
        return self.harvested - self.dispatched

    def dispatched_sites(self) -> List[CaptureSite]:
        return [s for s in self.sites if s.dispatched]

    def unique_specs(self) -> List[Tuple[ContractionSpec, str]]:
        """Deduplicated (spec, dtype) pairs of the dispatched sites — the
        sweepable GEMM set of this function (plan-DB key granularity)."""
        seen: Dict[Tuple, Tuple[ContractionSpec, str]] = {}
        for s in self.sites:
            if s.spec is None or not s.dispatched:
                continue
            seen.setdefault(spec_key(s.spec, s.dtype), (s.spec, s.dtype))
        return list(seen.values())

    def summary(self) -> str:
        return (
            f"capture[{self.label or '?'}]: {self.harvested} site(s) "
            f"harvested, {self.dispatched} dispatched, "
            f"{self.fallback} fallback"
        )

    def as_dict(self) -> Dict[str, Any]:
        return {
            "label": self.label,
            "harvested": self.harvested,
            "dispatched": self.dispatched,
            "fallback": self.fallback,
            "sites": [s.as_dict() for s in self.sites],
        }

    def to_json(self, **kwargs) -> str:
        kwargs.setdefault("indent", 2)
        kwargs.setdefault("sort_keys", True)
        return json.dumps(self.as_dict(), **kwargs)


# ---------------------------------------------------------------------------
# classification — shared with capture.rewrite
# ---------------------------------------------------------------------------


class _Shaped:
    """Minimal shape/ndim carrier for the ops dispatch predicates."""

    __slots__ = ("shape", "ndim")

    def __init__(self, shape):
        self.shape = tuple(shape)
        self.ndim = len(self.shape)


def classify_dot_general(
    lhs_aval, rhs_aval, out_aval, params: Dict[str, Any], *,
    interpret: bool, site_id: int = 0, path: str = "",
    grouped_lhs: bool = False,
) -> CaptureSite:
    """Map one ``dot_general`` equation to a ContractionSpec + dispatch verdict.

    Eligible layouts (everything else falls back untouched):

      * ``(..., M, D) @ (D, F)`` contracting the last lhs axis with the
        first rhs axis, no batch dims -> ``matmul`` (leading lhs axes are
        flattened into M, exactly what the models' reshape-then-dense
        call sites do by hand);
      * ``(D, M) @ (D, F)`` contracting axis 0 with axis 0 ->
        ``transposed_matmul`` (the weight-gradient layout);
      * ``(B, M, D) @ (B, D, F)`` batched on axis 0 -> ``batched_matmul``
        (MoE expert FFNs, attention-free batched contractions).

    The dispatch verdict then applies the exact ``repro.ops`` kernel
    predicates, so "dispatched" means "the equivalent ops entry point runs
    a generated kernel here" — alignment, backend and dtype included.
    """
    from .. import ops

    (lc, rc), (lb, rb) = params["dimension_numbers"]
    site = CaptureSite(
        site_id=site_id,
        path=path,
        lhs_shape=tuple(lhs_aval.shape),
        rhs_shape=tuple(rhs_aval.shape),
        out_shape=tuple(out_aval.shape),
        dtype=np.dtype(lhs_aval.dtype).name,
        out_dtype=np.dtype(out_aval.dtype).name,
        dimension_numbers=params["dimension_numbers"],
    )

    if np.dtype(lhs_aval.dtype) != np.dtype(rhs_aval.dtype):
        site.reason = (
            f"mixed operand dtypes {lhs_aval.dtype}/{rhs_aval.dtype}"
        )
        return site
    if site.dtype not in SUPPORTED_DTYPES:
        site.reason = f"unsupported dtype {site.dtype}"
        return site

    ln, rn = len(site.lhs_shape), len(site.rhs_shape)
    lc, rc, lb, rb = tuple(lc), tuple(rc), tuple(lb), tuple(rb)

    if not lb and rn == 2 and rc == (0,) and ln >= 2 and lc == (ln - 1,):
        # (..., M, D) @ (D, F): the workhorse dense layout
        d = site.lhs_shape[-1]
        m = int(np.prod(site.lhs_shape[:-1], dtype=np.int64))
        f = site.rhs_shape[1]
        site.op = "dense"
        site.spec = matmul_spec(m, d, f)
        if ops._dense_kernel_ok(
            _Shaped((m, d)), _Shaped((d, f)), interpret
        ):
            site.status = "dispatched"
        else:
            if not (ops._use_pallas() or interpret):
                site.reason = "cpu backend without interpret mode"
            else:
                site.reason = (
                    f"dense kernel needs 128-aligned (M,D,F)=({m},{d},{f})"
                )
        return site

    if not lb and ln == 2 and rn == 2 and lc == (0,) and rc == (0,):
        # (D, M) @ (D, F) -> (M, F): stored-transposed contraction
        d, m = site.lhs_shape
        f = site.rhs_shape[1]
        site.op = "dense_transposed"
        site.spec = transposed_matmul_spec(m, d, f)
        if ops._generic_kernel_ok(interpret):
            site.status = "dispatched"
        else:
            site.reason = "cpu backend without interpret mode"
        return site

    if (
        lb == (0,) and rb == (0,) and ln == 3 and rn == 3
        and lc == (2,) and rc == (1,)
    ):
        b, m, d = site.lhs_shape
        f = site.rhs_shape[2]
        if grouped_lhs:
            # the lhs rows were routed here by a scatter (MoE dispatch):
            # expert slab b of the rhs multiplies only *its* row block, so
            # this is the uniform-group case of the ragged grouped GEMM —
            # one searched group-offset kernel instead of a batched one
            site.op = "grouped_dense"
            site.spec = grouped_matmul_spec((m,) * b, d, f)
            if ops._grouped_kernel_ok(_Shaped((b * m, d)), interpret):
                site.status = "dispatched"
            else:
                site.reason = "cpu backend without interpret mode"
            return site
        site.op = "batched_dense"
        site.spec = batched_matmul_spec(b, m, d, f)
        if ops._batched_kernel_ok(
            _Shaped((b, m, d)), _Shaped((b, d, f)), interpret
        ):
            site.status = "dispatched"
        else:
            site.reason = "cpu backend without interpret mode"
        return site

    site.reason = (
        f"unsupported contraction layout ndim=({ln},{rn}) "
        f"contract=({lc},{rc}) batch=({lb},{rb})"
    )
    return site


# ---------------------------------------------------------------------------
# fused-pattern analysis: attention motif + scatter-tainted grouped GEMMs
# ---------------------------------------------------------------------------

#: mask fills below this count as "minus infinity" for motif purposes
_MASK_FLOOR = -1e20

#: producers the motif matcher looks through (layout/dtype plumbing)
_TRANSPARENT = frozenset({
    "reshape", "broadcast_in_dim", "convert_element_type",
    "squeeze", "expand_dims",
})


@dataclasses.dataclass
class AttentionMotif:
    """One matched einsum-softmax-einsum chain, rewritable as one fused op.

    ``terminal`` is the closing ``div`` equation (its outvar carries the
    attention output); ``interior`` holds ids of every equation whose
    value exists only to feed the terminal — the rewriter skips them and
    evaluates ``ops.attention(q, k, v)`` at the terminal instead.
    """

    terminal_id: int
    interior: frozenset
    q: Any
    k: Any
    v: Any
    causal: bool
    site: CaptureSite


@dataclasses.dataclass
class JaxprAnalysis:
    """Fused-pattern facts of ONE jaxpr level (sub-jaxprs analyzed apart).

    ``interior`` maps interior-equation id -> owning terminal id, so the
    rewriter can skip an equation only when its motif actually dispatches.
    """

    motifs: Dict[int, AttentionMotif] = dataclasses.field(
        default_factory=dict
    )
    interior: Dict[int, int] = dataclasses.field(default_factory=dict)
    grouped: frozenset = frozenset()


def _peel(atom, producers, visited):
    """Follow layout-only producers back; returns (atom, defining eqn)."""
    while isinstance(atom, jcore.Var) and atom in producers:
        eqn = producers[atom]
        if eqn.primitive.name in _TRANSPARENT:
            visited.append(eqn)
            atom = eqn.invars[0]
        else:
            return atom, eqn
    return atom, None


def _is_causal_pred(pred, producers) -> bool:
    """pred == (col_iota <= row_iota), structurally — no constant masks."""
    if not isinstance(pred, jcore.Var) or pred not in producers:
        return False
    cmp = producers[pred]
    if cmp.primitive.name not in ("le", "ge") or len(cmp.invars) != 2:
        return False
    dims = []
    for v in cmp.invars:
        if not isinstance(v, jcore.Var) or v not in producers:
            return False
        src = producers[v]
        if src.primitive.name != "iota":
            return False
        dims.append(src.params["dimension"])
    want = (2, 1) if cmp.primitive.name == "le" else (1, 2)
    return tuple(dims) == want


def _match_attention(div_eqn, producers, consumers, live_out, *, interpret):
    """Match the plain-path attention chain ending at ``div_eqn``.

    Expected (walking backwards, through layout-only ops):

        div(num, rowsum)  <- num = dot_general(exp_p, V)  b(0,0) c(2,1)
                             rowsum = reduce_sum(exp_p, axes=(2,))
        exp_p = exp(scores_masked - reduce_max(scores_masked, axes=(2,)))
        scores_masked = [where(col<=row, ., -big)] (mul(dot1, d**-0.5))
        dot1 = dot_general(Q, K)  b(0,0) c(2,2)

    Every interior value must be consumed only inside the chain — the
    rewrite replaces the whole region with one ``ops.attention`` call.
    """
    from .. import ops

    chain: List[Any] = []
    _, dot2 = _peel(div_eqn.invars[0], producers, chain)
    if dot2 is None or dot2.primitive.name != "dot_general":
        return None
    (lc, rc), (lb, rb) = dot2.params["dimension_numbers"]
    if (tuple(lb), tuple(rb), tuple(lc), tuple(rc)) != \
            ((0,), (0,), (2,), (1,)):
        return None
    chain.append(dot2)

    _, rsum = _peel(div_eqn.invars[1], producers, chain)
    if (
        rsum is None or rsum.primitive.name != "reduce_sum"
        or tuple(rsum.params["axes"]) != (2,)
    ):
        return None
    chain.append(rsum)

    _, exp_a = _peel(rsum.invars[0], producers, chain)
    _, exp_b = _peel(dot2.invars[0], producers, chain)
    if exp_a is None or exp_a is not exp_b or exp_a.primitive.name != "exp":
        return None
    chain.append(exp_a)

    _, sub = _peel(exp_a.invars[0], producers, chain)
    if sub is None or sub.primitive.name != "sub":
        return None
    chain.append(sub)
    _, rmax = _peel(sub.invars[1], producers, chain)
    if (
        rmax is None or rmax.primitive.name != "reduce_max"
        or tuple(rmax.params["axes"]) != (2,)
    ):
        return None
    chain.append(rmax)
    _, masked = _peel(sub.invars[0], producers, chain)
    _, masked2 = _peel(rmax.invars[0], producers, chain)
    if masked is None or masked is not masked2:
        return None

    causal = False
    if (
        masked.primitive.name == "jit"
        and str(masked.params.get("name")) in ("_where", "where")
        and len(masked.invars) == 3
    ):
        pred, scores_in, fill = masked.invars
        if not (
            isinstance(fill, jcore.Literal)
            and float(fill.val) <= _MASK_FLOOR
        ):
            return None
        if not _is_causal_pred(pred, producers):
            return None
        causal = True
        chain.append(masked)
        _, mul = _peel(scores_in, producers, chain)
    else:
        mul = masked
    if mul is None or mul.primitive.name != "mul":
        return None
    chain.append(mul)

    scale_lit = dot1 = None
    for a, b in (mul.invars, reversed(mul.invars)):
        if isinstance(b, jcore.Literal) and np.ndim(b.val) == 0:
            _, cand = _peel(a, producers, chain)
            if cand is not None and cand.primitive.name == "dot_general":
                scale_lit, dot1 = float(b.val), cand
            break
    if dot1 is None:
        return None
    (lc, rc), (lb, rb) = dot1.params["dimension_numbers"]
    if (tuple(lb), tuple(rb), tuple(lc), tuple(rc)) != \
            ((0,), (0,), (2,), (2,)):
        return None
    chain.append(dot1)

    q_atom, k_atom = dot1.invars
    v_atom = dot2.invars[1]
    qa, ka, va = (x.aval for x in (q_atom, k_atom, v_atom))
    if qa.ndim != 3 or ka.ndim != 3 or va.ndim != 3:
        return None
    h, s, d = qa.shape
    t = ka.shape[1]
    e = va.shape[2]
    if ka.shape != (h, t, d) or va.shape[:2] != (h, t):
        return None
    if abs(scale_lit - d ** -0.5) > 1e-6 * d ** -0.5:
        return None  # non-standard scaling: not the op we generate

    # the fused call replaces the whole region — nothing outside it may
    # observe an interior value
    interior_ids = {id(c) for c in chain}
    for c in chain:
        for ov in c.outvars:
            if ov in live_out:
                return None
            for user in consumers.get(ov, ()):
                if id(user) not in interior_ids and user is not div_eqn:
                    return None

    site = CaptureSite(
        site_id=0,
        path="",
        lhs_shape=tuple(qa.shape),
        rhs_shape=tuple(ka.shape),
        out_shape=tuple(div_eqn.outvars[0].aval.shape),
        dtype=np.dtype(qa.dtype).name,
        out_dtype=np.dtype(div_eqn.outvars[0].aval.dtype).name,
        dimension_numbers=dot1.params["dimension_numbers"],
        op="attention",
        spec=attention_spec(h, s, t, d, e=e, causal=causal),
    )
    if np.dtype(qa.dtype) != np.dtype(ka.dtype) or \
            np.dtype(qa.dtype) != np.dtype(va.dtype):
        site.reason = "mixed attention operand dtypes"
    elif site.dtype not in SUPPORTED_DTYPES:
        site.reason = f"unsupported dtype {site.dtype}"
    elif ops._attention_kernel_ok(_Shaped((h, s, d)), interpret):
        site.status = "dispatched"
    else:
        site.reason = "cpu backend without interpret mode"
    return AttentionMotif(
        terminal_id=id(div_eqn),
        interior=frozenset(interior_ids),
        q=q_atom, k=k_atom, v=v_atom,
        causal=causal,
        site=site,
    )


def analyze_jaxpr(jaxpr: jcore.Jaxpr, *, interpret: bool) -> JaxprAnalysis:
    """Fused-pattern pass over one jaxpr level.

    * attention motifs: einsum-softmax-einsum chains rewritable as ONE
      ``ops.attention`` call (``_match_attention``);
    * grouped taint: values written by scatter-family primitives (the MoE
      dispatch) taint everything downstream, and a batched ``dot_general``
      whose lhs is tainted classifies as ``grouped_dense`` — its rows
      were *routed* to slabs, so the uniform grouped kernel (numerically
      identical to the batched one) keeps the site in the searched family
      that also covers the ragged case.

    Sub-jaxprs are analyzed separately by their own walk/eval level;
    taint deliberately does not cross higher-order primitive boundaries.
    """
    producers: Dict[Any, Any] = {}
    consumers: Dict[Any, List[Any]] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                consumers.setdefault(v, []).append(eqn)
        for v in eqn.outvars:
            producers[v] = eqn
    live_out = {v for v in jaxpr.outvars if isinstance(v, jcore.Var)}

    analysis = JaxprAnalysis()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "div":
            continue
        motif = _match_attention(
            eqn, producers, consumers, live_out, interpret=interpret
        )
        if motif is None:
            continue
        if any(i in analysis.interior for i in motif.interior):
            continue  # overlapping match: first one wins
        analysis.motifs[id(eqn)] = motif
        for i in motif.interior:
            analysis.interior[i] = id(eqn)

    tainted: set = set()
    grouped: set = set()
    for eqn in jaxpr.eqns:
        hit = any(
            isinstance(v, jcore.Var) and v in tainted for v in eqn.invars
        )
        if eqn.primitive.name == "dot_general" and hit:
            lhs = eqn.invars[0]
            if isinstance(lhs, jcore.Var) and lhs in tainted:
                grouped.add(id(eqn))
        if hit or eqn.primitive.name.startswith("scatter"):
            tainted.update(eqn.outvars)
    analysis.grouped = frozenset(grouped)
    return analysis


# ---------------------------------------------------------------------------
# jaxpr walk
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn) -> List[Tuple[str, jcore.Jaxpr]]:
    """All jaxprs carried in an equation's params (generic, any primitive)."""
    out: List[Tuple[str, jcore.Jaxpr]] = []
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jcore.ClosedJaxpr):
                out.append((name, v.jaxpr))
            elif isinstance(v, jcore.Jaxpr):
                out.append((name, v))
    return out


def harvest_jaxpr(
    closed: jcore.ClosedJaxpr, *, interpret: bool, label: str = "",
) -> CaptureReport:
    """Walk a traced function and classify every ``dot_general`` site.

    Recurses into all jaxpr-carrying params.  Sites nested inside a
    higher-order primitive the rewriter cannot re-emit (anything outside
    ``REWRITABLE_HOPS``) are forced to fallback with the containing
    primitive named in the reason — the report never over-promises what
    ``capture.optimize`` will actually dispatch.
    """
    report = CaptureReport(label=label)

    def blocked(site: CaptureSite, blocked_by: Optional[str]) -> None:
        if site.dispatched and blocked_by is not None:
            site.status = "fallback"
            site.reason = (
                "inside a higher-order primitive the rewriter "
                f"does not re-emit ({blocked_by})"
            )

    def walk(
        jaxpr: jcore.Jaxpr, trail: Tuple[str, ...],
        blocked_by: Optional[str],
    ):
        analysis = analyze_jaxpr(jaxpr, interpret=interpret)
        for i, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            motif = analysis.motifs.get(id(eqn))
            if motif is not None:
                site = motif.site
                site.site_id = len(report.sites)
                site.path = "/".join(trail + (f"eqn{i}",))
                blocked(site, blocked_by)
                report.sites.append(site)
                continue
            if name == "dot_general":
                if id(eqn) in analysis.interior:
                    continue  # folded into an attention site above
                site = classify_dot_general(
                    eqn.invars[0].aval, eqn.invars[1].aval,
                    eqn.outvars[0].aval, eqn.params,
                    interpret=interpret,
                    site_id=len(report.sites),
                    path="/".join(trail + (f"eqn{i}",)),
                    grouped_lhs=id(eqn) in analysis.grouped,
                )
                blocked(site, blocked_by)
                report.sites.append(site)
                continue
            subs = _sub_jaxprs(eqn)
            if subs:
                # a non-rewritable primitive blocks everything below it;
                # report the NEAREST such ancestor (an inner blocker is
                # the one that actually stops the rewrite, even when an
                # outer one exists too)
                block = name if name not in REWRITABLE_HOPS else blocked_by
                for _, sub in subs:
                    walk(sub, trail + (name,), block)

    walk(closed.jaxpr, (), None)
    return report
