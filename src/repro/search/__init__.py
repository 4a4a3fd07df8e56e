"""repro.search — cost-guided variant search, rewrite rules to measured kernels.

This package closes the loop the paper describes but the repo only had in
pieces: ``core.enumerate`` walks the SJT permutation space, ``core.cost``
scores variants analytically, ``codegen`` compiles a hand-picked Schedule.
``search_schedule`` chains them end to end:

    ContractionSpec + shapes
      │  space.candidate_orders      SJT walk, deduped by lowering identity
      │  space.block_choices         subdivision choices per hierarchy tier
      ▼
    beam.beam_search                 analytic roofline prune (sound bound
      │                              cut + configurable-width beam trim)
      ▼
    measure.measure_schedules        top-K lowered via codegen, timed on the
      │                              chip (the interpreter off a TPU)
      ▼
    plandb.PlanDB                    ranked plans persisted next to the
                                     autotune cache; ops.dense asks here
                                     before falling back to tune_schedule

``ops.dense`` & friends consult ``default_plan_db()`` first, so one offline
sweep (``scripts/search_sweep.py``) or one ``serve --search-gemms`` warmup
upgrades every later call for the same spec/shape/dtype — batched, chained
and transposed contractions included.  With ``--with-grads`` (or
``search_schedule_with_grads``) the sweep also covers the derived backward
specs of ``repro.grad``, so training's cotangent GEMMs are searched too.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .. import obs
from ..core.cost import TPU
from ..core.enumerate import (
    ContractionSpec,
    attention_spec,
    batched_matmul_spec,
    chain_matmul_spec,
    matmul_spec,
    matvec_spec,
    transposed_matmul_spec,
    uniform_grouped_spec,
    weighted_matmul_spec,
)
from ..core.schedule import Schedule
from .beam import CostEstimate, ScoredCandidate, SearchStats, beam_search, estimate
from .measure import (
    Measurement,
    einsum_reference,
    measure_schedules,
    mesh_for_schedules,
    reference_arrays,
    schedule_mesh_axes,
)
from .plandb import (
    PlanDB,
    active_phase,
    default_plan_db,
    entry_from,
    grad_plan_keys,
    plan_key,
    serving_phase,
)
from .space import (
    QUANT_TIERS,
    Candidate,
    MeshVariant,
    block_choices,
    candidate_orders,
    candidate_schedule,
    dtype_tier_specs,
    make_candidate,
    mesh_descriptor,
    mesh_variants,
    parse_mesh_shape,
    sweep_specs,
)

#: spec families the sweep CLI / serve warmup can name; value = (ctor, arity)
SPEC_FAMILIES = {
    "matmul": (matmul_spec, 3),
    "matvec": (matvec_spec, 2),
    "weighted_matmul": (weighted_matmul_spec, 3),
    "batched_matmul": (batched_matmul_spec, 4),
    "chain_matmul": (chain_matmul_spec, 4),
    "transposed_matmul": (transposed_matmul_spec, 3),
    # fused families: attention takes (heads, q_seq, kv_seq, head_dim);
    # grouped_matmul takes (groups, rows_per_group, k, f) — the CLI's
    # uniform-partition entry into the ragged GroupedSpec
    "attention": (attention_spec, 4),
    "grouped_matmul": (uniform_grouped_spec, 4),
}


def spec_from_name(name: str, shape: Sequence[int]) -> ContractionSpec:
    if name not in SPEC_FAMILIES:
        raise ValueError(
            f"unknown spec {name!r}; choose from {sorted(SPEC_FAMILIES)}"
        )
    ctor, arity = SPEC_FAMILIES[name]
    if len(shape) != arity:
        raise ValueError(f"{name} takes {arity} extents, got {list(shape)}")
    return ctor(*shape)


@dataclasses.dataclass
class RankedPlan:
    """One rung of the search output ladder."""

    schedule: Schedule
    score: float
    lower_bound: float
    fits_vmem: bool
    measured_s: Optional[float] = None
    max_err: Optional[float] = None
    source: str = "search"  # "default"/"mesh-naive" for baseline entries
    collective: str = ""    # finishing-collective strategy of a mesh plan
    #: roofline terms the rank was decided from (beam.CostEstimate:
    #: compute_s/hbm_s/comm_s/penalty/seq_steps/shards) — persisted into
    #: the plan DB and rendered by ``obs.explain``
    explain: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def sharded(self) -> bool:
        from .measure import schedule_mesh_axes

        return bool(schedule_mesh_axes(self.schedule))


@dataclasses.dataclass
class SearchResult:
    spec: ContractionSpec
    dtype: str
    ranked: List[RankedPlan]  # best first
    stats: SearchStats
    db_key: Optional[str] = None
    mesh: Optional[str] = None  # mesh descriptor ('2x4') of a mesh search

    @property
    def best(self) -> RankedPlan:
        return self.ranked[0]

    def baseline(self) -> Optional[RankedPlan]:
        for p in self.ranked:
            if p.source == "default":
                return p
        return None

    def mesh_baseline(self) -> Optional[RankedPlan]:
        """The naive-psum lowering of the best sharded subdivision."""
        for p in self.ranked:
            if p.source == "mesh-naive":
                return p
        return None

    def best_sharded(self) -> Optional[RankedPlan]:
        for p in self.ranked:
            if p.sharded:
                return p
        return None


def search_schedule(
    spec: ContractionSpec,
    *,
    dtype=np.float32,
    beam_width: int = 8,
    topk: int = 4,
    elem_bytes: Optional[int] = None,
    hw: dict = TPU,
    measure: bool = True,
    interpret: Optional[bool] = None,
    repeats: int = 2,
    arrays: Optional[Dict[str, np.ndarray]] = None,
    include_default: bool = True,
    plan_db: Optional[PlanDB] = None,
    use_cached_plan: bool = True,
    mesh_shape=None,
    phase: Optional[str] = None,
) -> SearchResult:
    """The end-to-end pipeline: enumerate -> prune -> measure -> persist.

    Returns the ranked ladder best-first.  Every rung fits the VMEM
    budget its kernel is compiled with; a spec where none does raises
    ``ValueError``.  When ``measure`` is on, the ranking is by measured
    seconds and — because ``include_default`` puts the un-searched
    ``codegen.default_schedule`` into the measured set wherever it fits —
    the winner is by construction never slower than the default on the
    measurement harness used.

    ``mesh_shape`` ('2x4' or (2, 4)) extends the search to the mesh tier:
    legal mesh subdivisions × collective strategies join the beam under
    the communication-aware cost (``beam.estimate``), the ladder always
    surfaces at least one ``mesh:*`` plan, and a "mesh-naive" baseline
    (the plain-psum, unblocked lowering of the best sharded subdivision)
    rides through measurement so the searched sharded winner is by
    construction never slower than it.  Sharded candidates are measured
    through ``codegen.bind_mesh`` over the visible devices (force a CPU
    mesh with ``--xla_force_host_platform_device_count``); a measured
    mesh search in a process that cannot host the mesh raises
    ``ValueError`` (``measure=False`` ranks the ladder analytically
    without devices).  The ladder persists under the mesh-qualified plan
    key (``matmul@mesh=2x4``-style).

    ``plan_db`` (or pass ``default_plan_db()``) persists the ladder;
    ``use_cached_plan`` short-circuits a repeated search of the same
    spec/dtype/hardware/mesh from the DB.

    ``phase`` ('prefill'/'decode') persists the ladder under the
    serving-phase-qualified key (``plandb.plan_key(phase=...)``) — the
    ladder the serving runners consult via ``plandb.serving_phase`` while
    an unphased sweep of the same shape stays untouched.
    """
    spec = spec.root()
    dt = np.dtype(dtype)
    if elem_bytes is None:
        elem_bytes = dt.itemsize
    if isinstance(mesh_shape, str):
        mesh_shape = parse_mesh_shape(mesh_shape)
    mesh_desc = mesh_descriptor(mesh_shape)
    if mesh_desc is None:
        mesh_shape = None

    if plan_db is not None and use_cached_plan:
        cached = plan_db.get(spec, dt, mesh=mesh_desc, phase=phase)
        if (
            cached
            and cached.get("ranked")
            and measure
            and cached["ranked"][0].get("measured_s") is None
        ):
            # an analytic-only (--no-measure) ladder must not satisfy a
            # measured request: fall through and run the full pipeline
            cached = None
        if cached and cached.get("ranked"):
            ranked = []
            for e in cached["ranked"]:
                try:
                    sched = _sched_from(e["schedule"], spec)
                except Exception:
                    continue
                ranked.append(
                    RankedPlan(
                        schedule=sched,
                        score=e.get("score", float("inf")),
                        lower_bound=e.get("lower_bound", 0.0),
                        fits_vmem=e.get("fits_vmem", True),
                        measured_s=e.get("measured_s"),
                        source=e.get("source", "search"),
                        collective=e.get("collective", ""),
                        explain=dict(e.get("explain") or {}),
                    )
                )
            if ranked:
                stats = SearchStats()
                for k, v in (cached.get("stats") or {}).items():
                    if hasattr(stats, k):
                        setattr(stats, k, v)
                return SearchResult(
                    spec=spec, dtype=str(dt), ranked=ranked, stats=stats,
                    db_key=plan_key(spec, dt, mesh=mesh_desc, phase=phase),
                    mesh=mesh_desc,
                )

    with obs.span("search.beam", spec=spec.name, mesh=mesh_desc):
        survivors, stats = beam_search(
            spec, beam_width=beam_width, topk=topk,
            elem_bytes=elem_bytes, hw=hw, mesh_shape=mesh_shape,
        )
    obs.counter("search.candidates").inc(stats.considered)
    obs.counter("search.pruned_bound").inc(stats.pruned_bound)
    obs.counter("search.pruned_beam").inc(stats.pruned_beam)
    obs.counter("search.mesh_variants").inc(stats.mesh_variants)
    plans: List[RankedPlan] = [
        RankedPlan(
            schedule=sc.candidate.to_schedule(),
            score=sc.cost.score,
            lower_bound=sc.cost.lower_bound,
            fits_vmem=sc.cost.fits_vmem,
            collective=sc.candidate.collective,
            explain=_explain_of(sc.cost),
        )
        for sc in survivors
    ]
    if include_default:
        from ..codegen import default_schedule

        base_sched = default_schedule(spec)
        base_dict = _sched_dict(base_sched)
        hit = [p for p in plans if _sched_dict(p.schedule) == base_dict]
        for p in hit:
            p.source = "default"
        est = estimate(
            spec, spec.indices,
            {i: spec.extents[i] for i in spec.indices},
            elem_bytes=elem_bytes, hw=hw,
        )
        # the unblocked kernel is a baseline only where it would compile
        if not hit and est.fits_vmem:
            plans.append(
                RankedPlan(
                    schedule=base_sched,
                    score=est.score,
                    lower_bound=est.lower_bound,
                    fits_vmem=est.fits_vmem,
                    source="default",
                    explain=_explain_of(est),
                )
            )

    # mesh searches also measure the NAIVE lowering of the best sharded
    # subdivision — same mesh assignment, plain psum, no inner blocking —
    # so "searched-sharded never slower than naive psum" holds by
    # construction on the measurement harness (the mesh analogue of the
    # include_default guarantee)
    if mesh_shape is not None:
        best_sharded_sc = next(
            (sc for sc in survivors if sc.candidate.mesh), None
        )
        if best_sharded_sc is not None:
            naive_sched = candidate_schedule(
                spec, spec.indices, {},
                mesh=best_sharded_sc.candidate.mesh_dict,
            )
            naive_dict = _sched_dict(naive_sched)
            naive_hit = [
                p for p in plans
                if _sched_dict(p.schedule) == naive_dict
                and (p.collective or "psum") == "psum"
            ]
            if naive_hit:
                for p in naive_hit:
                    p.source = "mesh-naive"
            else:
                from .space import local_extents

                naive_mesh = best_sharded_sc.candidate.mesh_dict
                est = estimate(
                    spec, spec.indices,
                    local_extents(spec, naive_mesh),
                    elem_bytes=elem_bytes, hw=hw,
                    mesh=naive_mesh, collective="psum",
                )
                if est.fits_vmem:
                    plans.append(
                        RankedPlan(
                            schedule=naive_sched,
                            score=est.score,
                            lower_bound=est.lower_bound,
                            fits_vmem=est.fits_vmem,
                            source="mesh-naive",
                            collective="psum",
                            explain=_explain_of(est),
                        )
                    )

    if not plans:
        raise ValueError(
            f"no schedule for {spec.name} {spec.extents} fits the "
            f"{hw['vmem_bytes']} B VMEM budget"
        )
    if measure:
        sharded = [p for p in plans if p.sharded]
        mesh = mesh_for_schedules([p.schedule for p in sharded])
        if mesh is None and sharded:
            raise ValueError(
                f"measuring the {mesh_desc} mesh ladder needs "
                f"{math.prod(mesh_shape)} devices; {jax.device_count()} "
                f"visible (measure=False ranks it analytically)"
            )
        with obs.span("search.measure", spec=spec.name, n=len(plans)):
            ms = measure_schedules(
                spec, [p.schedule for p in plans],
                arrays=arrays, dtype=dt, interpret=interpret,
                repeats=repeats, mesh=mesh,
                collectives=[p.collective for p in plans],
            )
        for p, m in zip(plans, ms):
            p.measured_s = m.seconds
            p.max_err = m.max_err
        stats.measured += len(ms)
        obs.counter("search.measured").inc(len(ms))
        plans.sort(key=lambda p: (p.measured_s, p.score))
    else:
        plans.sort(key=lambda p: p.score)

    result = SearchResult(
        spec=spec, dtype=str(dt), ranked=plans, stats=stats,
        mesh=mesh_desc,
    )
    if mesh_desc is not None:
        sharded_best = result.best_sharded()
        if sharded_best is not None:
            # which finishing collective won the mesh tier — the
            # ring-vs-psum pick, surfaced fleet-wide through obs
            obs.counter(
                f"search.collective.{sharded_best.collective or 'psum'}"
            ).inc()
    if plan_db is not None and plans:
        with obs.span("search.persist", spec=spec.name, mesh=mesh_desc):
            result.db_key = plan_db.put(
                spec, dt,
                [
                    entry_from(
                        p.schedule,
                        score=p.score,
                        lower_bound=p.lower_bound,
                        fits_vmem=p.fits_vmem,
                        measured_s=p.measured_s,
                        source=p.source,
                        collective=p.collective,
                        explain=p.explain,
                    )
                    for p in plans
                ],
                stats=stats.as_dict(),
                mesh=mesh_desc,
                cuts=[
                    {"key": k, "lower_bound": lb, "best_score": bs}
                    for k, lb, bs in stats.bound_log[:_MAX_CUTS]
                ],
                phase=phase,
            )
    return result


#: bound-cut sample size persisted per entry — enough for the explain
#: table's why-not side without bloating the fleet DB on big sweeps
_MAX_CUTS = 12


def _explain_of(est: CostEstimate) -> Dict[str, float]:
    """The CostEstimate terms a plan-DB rung keeps (``explain`` field)."""
    return {
        "compute_s": float(est.compute_s),
        "hbm_s": float(est.hbm_s),
        "comm_s": float(est.comm_s),
        "penalty": float(est.penalty),
        "seq_steps": int(est.seq_steps),
        "shards": int(est.shards),
    }


def _sched_dict(s: Schedule) -> str:
    import json

    from ..codegen.cache import schedule_to_dict

    return json.dumps(schedule_to_dict(s), sort_keys=True)


def _sched_from(d, root: ContractionSpec) -> Schedule:
    from ..codegen.cache import schedule_from_dict

    return schedule_from_dict(d, root)


def search_schedule_with_grads(
    spec: ContractionSpec, **kwargs
) -> Dict[str, SearchResult]:
    """Sweep a forward spec together with its derived backward specs.

    Runs the full ``search_schedule`` pipeline once per point of
    ``space.sweep_specs(spec, with_grads=True)`` — the forward contraction
    plus every cotangent GEMM from ``grad.derive`` (dA = g·Bᵀ etc.), each
    persisted under its own plan key.  Returns ``{label -> SearchResult}``
    with labels ``fwd``, ``dA``, ``dB``, ...  This is how training's
    backward GEMMs pick up *searched* (not just analytically tuned)
    schedules: ``ops``'s custom VJPs consult the plan DB by derived-spec
    key on every backward pass.
    """
    return {
        label: search_schedule(s, **kwargs)
        for label, s in sweep_specs(spec, with_grads=True)
    }


def search_dtype_ladder(
    spec: ContractionSpec,
    *,
    dtype=np.float32,
    tiers: Sequence[str] = QUANT_TIERS,
    **kwargs,
) -> Dict[str, SearchResult]:
    """Search the dtype axis: the baseline tier plus each quant tier.

    Runs the full ``search_schedule`` pipeline once per point of
    ``space.dtype_tier_specs`` — the caller's spec at its full/half
    precision, then the int8 and fp8 re-taggings at their 1-byte storage
    dtypes.  Every tier persists under its own dtype-qualified plan key
    (``matmul@...@dtype=int8`` in ``obs.explain`` selector terms), so
    ``ops.dense(quant=...)`` and the quantized serving path pick up the
    matching ladder at trace time.  Returns ``{tier -> SearchResult}``
    with ``"baseline"`` always present; rank tiers against each other
    with ``best_dtype_tier``.
    """
    return {
        tier: search_schedule(s, dtype=dt, **kwargs)
        for tier, s, dt in dtype_tier_specs(spec, dtype=dtype, tiers=tiers)
    }


def best_dtype_tier(results: Dict[str, SearchResult]) -> str:
    """The precision tier the roofline ranks fastest for this shape.

    Compared on the *analytic* score of each tier's best plan — the
    quant-aware byte model is exactly what distinguishes tiers (operand
    traffic shrinks 4x at matched shapes), whereas interpreter wall-clock
    cannot see memory bandwidth.  Accuracy policy stays with the caller;
    this only says what the hardware model prefers.
    """
    if not results:
        raise ValueError("no tiers searched")
    return min(results, key=lambda t: (results[t].best.score, t))


def search_gemm_plans(
    shapes: Sequence[Tuple[int, int, int]],
    *,
    dtype=np.float32,
    beam_width: int = 8,
    topk: int = 3,
    interpret: Optional[bool] = None,
    measure: bool = True,
    plan_db: Optional[PlanDB] = None,
    with_grads: bool = False,
    mesh_shape=None,
    phase: Optional[str] = None,
) -> int:
    """Search + persist plans for (m, k, n) GEMMs; returns #plans readied.

    The serving analogue of ``ops.warm_dense_cache``: where warmup fills
    the autotune cache with the analytic pick, this runs the full
    enumerate->prune->measure pipeline and stores the ranked ladder, so
    ``ops.dense`` serves the *searched* schedule from then on.  With
    ``with_grads`` each GEMM's derived backward specs are swept too (the
    count then includes them), preparing the training fleet's cotangent
    GEMMs from the same warmup.  With ``mesh_shape`` ('2x4') every point
    is additionally swept at the mesh tier, persisting sharded ladders
    under the mesh-qualified keys that ``ops._tuned_kernel`` consults
    when a matching mesh is active (the count includes those sweeps).
    With ``phase`` the ladders persist under the serving-phase-qualified
    keys — how the prefill/decode runners each sweep their own ladder for
    the same shape family.
    """
    db = plan_db if plan_db is not None else default_plan_db()
    n = 0
    for m, k, nn in shapes:
        spec = matmul_spec(m, k, nn)
        kw = dict(
            dtype=dtype, beam_width=beam_width, topk=topk,
            interpret=interpret, measure=measure, plan_db=db,
            phase=phase,
        )
        meshes = [None] + ([mesh_shape] if mesh_shape is not None else [])
        for ms in meshes:
            if with_grads:
                n += len(
                    search_schedule_with_grads(spec, mesh_shape=ms, **kw)
                )
            else:
                search_schedule(spec, mesh_shape=ms, **kw)
                n += 1
    return n


__all__ = [
    "Candidate",
    "CostEstimate",
    "Measurement",
    "MeshVariant",
    "PlanDB",
    "RankedPlan",
    "ScoredCandidate",
    "SearchResult",
    "SearchStats",
    "SPEC_FAMILIES",
    "QUANT_TIERS",
    "active_phase",
    "beam_search",
    "best_dtype_tier",
    "block_choices",
    "candidate_orders",
    "candidate_schedule",
    "default_plan_db",
    "dtype_tier_specs",
    "einsum_reference",
    "entry_from",
    "estimate",
    "grad_plan_keys",
    "make_candidate",
    "measure_schedules",
    "mesh_descriptor",
    "mesh_for_schedules",
    "mesh_variants",
    "parse_mesh_shape",
    "plan_key",
    "reference_arrays",
    "schedule_mesh_axes",
    "search_dtype_ladder",
    "search_gemm_plans",
    "search_schedule",
    "search_schedule_with_grads",
    "serving_phase",
    "spec_from_name",
    "sweep_specs",
]
