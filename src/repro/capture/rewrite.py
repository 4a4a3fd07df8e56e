"""Re-emit a traced function with eligible GEMMs dispatched through ops.

``optimize(fn)`` is the whole-model counterpart of hand-rewiring a call
site to ``repro.ops``: it traces ``fn`` to a jaxpr (shape-specialized,
cached per input signature, exactly like ``jit``), then evaluates that
jaxpr equation by equation — every ``dot_general`` that
``harvest.classify_dot_general`` marks dispatchable is replaced by the
corresponding ``repro.ops`` entry point (``dense`` / ``dense_transposed``
/ ``batched_dense``), which routes through the ranked plan DB, the
persistent autotune cache and, with ``differentiable=True`` (the
default), the ``repro.grad`` custom-VJP wrappers — so ``jax.grad`` of a
captured loss runs derived-spec generated kernels on the backward tape
too.  Ineligible sites re-bind their original equation untouched.

Higher-order primitives are re-emitted structurally so rewriting reaches
inside them:

  ======================  ==============================================
  primitive               re-emission
  ======================  ==============================================
  ``jit`` / calls         inlined (the caller's ``jit`` re-fuses)
  ``scan``                rebuilt with ``lax.scan`` over the rewritten body
  ``while``               rebuilt with ``lax.while_loop``
  ``cond``                rebuilt with ``lax.switch``
  ``remat2``              rebuilt with ``jax.checkpoint`` (policy kept)
  ``custom_jvp/vjp_call`` re-bound **unmodified** unless the primal
                          jaxpr contains a dispatchable site.  Unmodified
                          re-bind keeps the custom derivative — crucially
                          including ``repro.ops``'s own custom-VJP sites
                          already present in the traced function, whose
                          primal is a ``pallas_call`` JAX cannot
                          differentiate (inlining those would break
                          ``jax.grad`` of every captured model that
                          already routes through ``ops`` on the kernel
                          path).  When the primal *does* contain a
                          dispatchable GEMM, the primal is inlined so the
                          site dispatches, and JAX re-derives the
                          gradient through the dispatched op's own VJP —
                          a user-supplied custom derivative around such a
                          site is superseded.
  ======================  ==============================================

Anything else that carries a sub-jaxpr is bound unmodified, and the
harvest report marks the sites inside it as fallback-by-containment.
Per-equation classification verdicts are memoized on the traced entry
(they depend only on avals + the interpret flag), so replaying a cached
signature does no re-classification work.

Numerics: a dispatched site accumulates in float32 and casts to the
equation's original output dtype, like every ``ops`` entry point; the
equation's ``precision`` hint is dropped (the generated kernel is always
the highest-precision MXU path).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
from jax.extend import core as jcore
from jax import lax

from .harvest import CaptureReport, classify_dot_general, harvest_jaxpr


def _interpret_default() -> bool:
    """Kernel dispatch needs a TPU or the Pallas interpreter; the
    ``REPRO_INTERPRET=1`` switch turns the latter on for CPU CI."""
    return os.environ.get("REPRO_INTERPRET", "") == "1"


class _Ctx:
    __slots__ = (
        "interpret", "dispatch", "quant", "site_memo", "contains_memo",
        "analysis_memo",
    )

    def __init__(self, interpret: bool, dispatch: bool = True,
                 quant: Optional[str] = None,
                 site_memo: Optional[dict] = None,
                 contains_memo: Optional[dict] = None,
                 analysis_memo: Optional[dict] = None):
        self.interpret = interpret
        self.dispatch = dispatch
        self.quant = quant
        # id(eqn) -> CaptureSite, id(jaxpr) -> bool / JaxprAnalysis; keyed
        # by identity, which is stable for the lifetime of the traced
        # _Entry that owns both the jaxpr and these memos
        self.site_memo = {} if site_memo is None else site_memo
        self.contains_memo = {} if contains_memo is None else contains_memo
        self.analysis_memo = {} if analysis_memo is None else analysis_memo

    def analyze(self, jaxpr):
        """Per-level fused-pattern facts (attention motifs, grouped taint),
        the same pass the harvest report is built from."""
        from .harvest import analyze_jaxpr

        hit = self.analysis_memo.get(id(jaxpr))
        if hit is None:
            hit = analyze_jaxpr(jaxpr, interpret=self.interpret)
            self.analysis_memo[id(jaxpr)] = hit
        return hit

    def classify(self, eqn, grouped_lhs: bool = False) -> "object":
        site = self.site_memo.get(id(eqn))
        if site is None:
            site = classify_dot_general(
                eqn.invars[0].aval, eqn.invars[1].aval,
                eqn.outvars[0].aval, eqn.params,
                interpret=self.interpret,
                grouped_lhs=grouped_lhs,
            )
            self.site_memo[id(eqn)] = site
        return site

    def contains_dispatchable(self, closed: jcore.ClosedJaxpr) -> bool:
        """Whether rewriting can reach a dispatchable site inside
        ``closed`` (recursing only through re-emittable primitives, like
        the rewriter itself does)."""
        from .harvest import REWRITABLE_HOPS, _sub_jaxprs

        jaxpr = closed.jaxpr if isinstance(
            closed, jcore.ClosedJaxpr
        ) else closed
        hit = self.contains_memo.get(id(jaxpr))
        if hit is not None:
            return hit
        found = False
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                if self.classify(eqn).dispatched:
                    found = True
                    break
            elif eqn.primitive.name in REWRITABLE_HOPS:
                if any(
                    self.contains_dispatchable(sub)
                    for _, sub in _sub_jaxprs(eqn)
                ):
                    found = True
                    break
        self.contains_memo[id(jaxpr)] = found
        return found


def _bind(eqn, invals):
    """Re-bind an equation exactly as traced (``core.eval_jaxpr``'s
    mechanism): ``get_bind_params`` reconstructs the callable params of
    custom_jvp/vjp-style primitives, so their custom derivatives — and
    hence differentiability of e.g. ``pallas_call``-backed primals —
    survive the replay."""
    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    out = eqn.primitive.bind(*subfuns, *invals, **bind_params)
    return list(out) if eqn.primitive.multiple_results else [out]


def _apply_site(site, lhs, rhs, interpret: bool, quant: Optional[str] = None):
    """Evaluate a dispatched site through its ``repro.ops`` entry point.

    ``quant`` threads the capture-level quantization policy into the
    ``dense`` entry point only — projections are the weight-heavy sites
    the int8/fp8 tier targets; the other entry points stay full-precision
    (the quant tier is inference-oriented and has no custom VJP).
    """
    from .. import ops

    if site.op == "dense":
        x = lhs.reshape(-1, lhs.shape[-1]) if lhs.ndim > 2 else lhs
        out = ops.dense(
            x, rhs, out_dtype=site.out_dtype, interpret=interpret,
            quant=quant,
        )
        return out.reshape(site.out_shape)
    if site.op == "dense_transposed":
        return ops.dense_transposed(
            lhs, rhs, out_dtype=site.out_dtype, interpret=interpret
        )
    if site.op == "batched_dense":
        return ops.batched_dense(
            lhs, rhs, out_dtype=site.out_dtype, interpret=interpret
        )
    if site.op == "grouped_dense":
        b, m, d = site.lhs_shape
        out = ops.grouped_dense(
            lhs.reshape(b * m, d), rhs, (m,) * b,
            out_dtype=site.out_dtype, interpret=interpret,
        )
        return out.reshape(site.out_shape)
    raise AssertionError(f"unhandled capture op {site.op!r}")


def _eval_jaxpr(
    closed: jcore.ClosedJaxpr, args, ctx: _Ctx,
) -> List[Any]:
    jaxpr = closed.jaxpr
    env: Dict[jcore.Var, Any] = {}

    def read(a):
        return a.val if isinstance(a, jcore.Literal) else env[a]

    def write_all(vs, vals):
        for v, val in zip(vs, vals):
            env[v] = val

    write_all(jaxpr.constvars, closed.consts)
    write_all(jaxpr.invars, args)

    analysis = ctx.analyze(jaxpr)

    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name

        if ctx.dispatch:
            motif = analysis.motifs.get(id(eqn))
            if motif is not None and motif.site.dispatched:
                # terminal of a matched attention chain: the whole region
                # collapses into one fused op on the chain's roots
                from .. import ops

                out = ops.attention(
                    read(motif.q), read(motif.k), read(motif.v),
                    causal=motif.causal,
                    out_dtype=motif.site.out_dtype,
                    interpret=ctx.interpret,
                )
                write_all(eqn.outvars, [out])
                continue
            owner = analysis.interior.get(id(eqn))
            if owner is not None and \
                    analysis.motifs[owner].site.dispatched:
                # interior of a dispatching motif: its value is never
                # observed outside the chain (verified at match time)
                continue

        invals = [read(x) for x in eqn.invars]

        if name == "dot_general":
            site = ctx.classify(
                eqn, grouped_lhs=id(eqn) in analysis.grouped
            )
            if ctx.dispatch and site.dispatched:
                outs = [_apply_site(
                    site, invals[0], invals[1], ctx.interpret, ctx.quant
                )]
            else:
                outs = _bind(eqn, invals)

        elif name in ("jit", "closed_call", "core_call"):
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            outs = _eval_jaxpr(inner, invals, ctx)

        elif name in ("remat2", "remat", "checkpoint"):
            inner = eqn.params["jaxpr"]  # open Jaxpr, no consts

            def body(*a, _inner=inner):
                return _eval_jaxpr(jcore.ClosedJaxpr(_inner, ()), a, ctx)

            outs = jax.checkpoint(
                body,
                policy=eqn.params.get("policy"),
                prevent_cse=eqn.params.get("prevent_cse", True),
            )(*invals)

        elif name == "scan":
            p = eqn.params
            nc, ncar = p["num_consts"], p["num_carry"]
            consts = invals[:nc]
            init = tuple(invals[nc:nc + ncar])
            xs = tuple(invals[nc + ncar:])
            body_jaxpr = p["jaxpr"]

            def body(carry, x, _j=body_jaxpr, _c=tuple(consts), _n=ncar):
                res = _eval_jaxpr(_j, [*_c, *carry, *x], ctx)
                return tuple(res[:_n]), tuple(res[_n:])

            carry_out, ys = lax.scan(
                body, init, xs,
                length=p["length"], reverse=p["reverse"],
                unroll=p.get("unroll", 1),
            )
            outs = [*carry_out, *ys]

        elif name == "while":
            p = eqn.params
            cn, bn = p["cond_nconsts"], p["body_nconsts"]
            cconsts, bconsts = invals[:cn], invals[cn:cn + bn]
            init = tuple(invals[cn + bn:])
            cond_j, body_j = p["cond_jaxpr"], p["body_jaxpr"]
            outs = list(lax.while_loop(
                lambda c: _eval_jaxpr(cond_j, [*cconsts, *c], ctx)[0],
                lambda c: tuple(_eval_jaxpr(body_j, [*bconsts, *c], ctx)),
                init,
            ))

        elif name == "cond":
            branches = eqn.params["branches"]
            idx, ops_ = invals[0], invals[1:]
            fns = [
                (lambda *a, _b=b: tuple(_eval_jaxpr(_b, a, ctx)))
                for b in branches
            ]
            outs = list(lax.switch(idx, fns, *ops_))

        elif name in (
            "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
        ):
            inner = eqn.params.get("call_jaxpr") or eqn.params["fun_jaxpr"]
            if ctx.dispatch and ctx.contains_dispatchable(inner):
                # a dispatchable GEMM lives inside: inline the primal so
                # it reaches ops; the dispatched op's own VJP takes over
                outs = _eval_jaxpr(inner, invals, ctx)
            else:
                # keep the custom derivative intact — this is the path
                # repro.ops's own custom-VJP sites take (their primal is
                # a pallas_call, not differentiable if inlined)
                outs = _bind(eqn, invals)

        else:
            outs = _bind(eqn, invals)

        write_all(eqn.outvars, outs)

    return [read(v) for v in jaxpr.outvars]


# ---------------------------------------------------------------------------
# the user-facing wrapper
# ---------------------------------------------------------------------------


class _Entry:
    __slots__ = ("closed", "out_tree", "report", "site_memo", "contains_memo")

    def __init__(self, closed, out_tree, report):
        self.closed, self.out_tree, self.report = closed, out_tree, report
        self.site_memo: dict = {}
        self.contains_memo: dict = {}


class CapturedFunction:
    """``optimize(fn)`` result: trace-once, dispatch-per-call wrapper.

    Shape-specialized like ``jit``: the first call for an input signature
    traces ``fn`` (via ``jax.make_jaxpr``, so abstract
    ``ShapeDtypeStruct`` inputs work too — see ``report_for``) and
    harvests its GEMM sites; subsequent calls replay the rewritten jaxpr.
    Differentiable and jittable: replay just re-binds JAX primitives, and
    dispatched sites carry ``repro.grad`` custom VJPs.
    """

    def __init__(
        self, fn: Callable, *,
        interpret: Optional[bool] = None,
        dispatch: bool = True,
        label: str = "",
        quant: Optional[str] = None,
    ):
        self._fn = fn
        self._interpret = (
            _interpret_default() if interpret is None else bool(interpret)
        )
        self._dispatch = dispatch
        self._quant = quant
        self._label = label or getattr(fn, "__name__", "captured")
        self._entries: Dict[Tuple, _Entry] = {}

    # -- tracing ------------------------------------------------------------

    @staticmethod
    def _signature(flat_args) -> Tuple:
        return tuple(
            (tuple(getattr(a, "shape", ())), str(getattr(a, "dtype", type(a))))
            for a in flat_args
        )

    def _entry_for(self, args, kwargs) -> Tuple[_Entry, List[Any], Any]:
        flat, in_tree = jax.tree.flatten((args, kwargs))
        key = (in_tree, self._signature(flat))
        entry = self._entries.get(key)
        if entry is None:
            out_store: Dict[str, Any] = {}

            def flat_fn(*flat_in):
                a, k = jax.tree.unflatten(in_tree, flat_in)
                out = self._fn(*a, **k)
                out_flat, out_tree = jax.tree.flatten(out)
                out_store["tree"] = out_tree
                return out_flat

            from ..obs import counter, span

            with span("capture.trace", label=self._label):
                closed = jax.make_jaxpr(flat_fn)(*flat)
            with span("capture.harvest", label=self._label):
                report = harvest_jaxpr(
                    closed, interpret=self._interpret, label=self._label,
                )
            if not self._dispatch:
                for s in report.sites:
                    if s.dispatched:
                        s.status = "fallback"
                        s.reason = "dispatch disabled (harvest-only capture)"
            # per-signature dispatch telemetry: aggregate counts plus a
            # per-op breakdown (capture.dispatched.dense etc.) so a fleet
            # dump shows WHICH entry points the model's GEMMs route to
            counter("capture.harvested").inc(report.harvested)
            counter("capture.dispatched").inc(report.dispatched)
            counter("capture.fallback").inc(report.fallback)
            for s in report.sites:
                if s.dispatched:
                    counter(f"capture.dispatched.{s.op}").inc()
            entry = _Entry(closed, out_store["tree"], report)
            self._entries[key] = entry
        return entry, flat, in_tree

    # -- calling ------------------------------------------------------------

    def __call__(self, *args, **kwargs):
        entry, flat, _ = self._entry_for(args, kwargs)
        outs = _eval_jaxpr(
            entry.closed, flat,
            _Ctx(self._interpret, self._dispatch, quant=self._quant,
                 site_memo=entry.site_memo,
                 contains_memo=entry.contains_memo),
        )
        return jax.tree.unflatten(entry.out_tree, outs)

    # -- reporting ----------------------------------------------------------

    def report_for(self, *args, **kwargs) -> CaptureReport:
        """The harvest report for this input signature (traces if needed).

        Accepts concrete arrays or ``jax.ShapeDtypeStruct`` trees — no
        allocation or execution happens for abstract inputs.
        """
        entry, _, _ = self._entry_for(args, kwargs)
        return entry.report

    @property
    def reports(self) -> List[CaptureReport]:
        """Reports of every input signature traced so far."""
        return [e.report for e in self._entries.values()]

    @property
    def interpret(self) -> bool:
        return self._interpret


def optimize(
    fn: Callable, *,
    interpret: Optional[bool] = None,
    dispatch: bool = True,
    label: str = "",
    quant: Optional[str] = None,
) -> CapturedFunction:
    """Capture ``fn`` and dispatch its eligible GEMMs through ``repro.ops``.

    ``interpret=None`` (default) reads ``$REPRO_INTERPRET`` — on a TPU the
    flag is irrelevant (kernels run natively); on CPU set it to run the
    generated kernels under the Pallas interpreter (CI/conformance mode).
    ``dispatch=False`` degrades to a pure harvest: the function replays
    byte-identically (every equation re-bound as traced) but the report
    still says what *would* dispatch.
    ``quant`` ('int8' | 'fp8') routes dispatched ``dense`` sites through
    the dynamic-quantized tier (``ops.dense(..., quant=...)``) — an
    inference-only policy: the quant path has no custom VJP, so don't
    ``jax.grad`` a quantized capture.
    """
    return CapturedFunction(
        fn, interpret=interpret, dispatch=dispatch, label=label, quant=quant
    )


def capture_report(
    fn: Callable, *args, interpret: Optional[bool] = None, label: str = "",
    **kwargs,
) -> CaptureReport:
    """One-shot harvest of ``fn`` at the given (possibly abstract) inputs."""
    return CapturedFunction(
        fn, interpret=interpret, label=label
    ).report_for(*args, **kwargs)
