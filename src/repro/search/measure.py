"""Measure surviving candidates through the kernel generator.

The paper measures *every* variant; after the analytic cut only the beam's
top-K reach this stage.  Each survivor is lowered with ``codegen.compile``
(the same path ``ops.dense`` uses) and timed.  ``interpret`` defaults to
what the backend implies (``ops.default_interpret``): on a TPU the real
kernel is timed, elsewhere the Pallas interpreter closes the loop.

Schedules with ``mesh:*`` levels are lowered through ``codegen.bind_mesh``
over a real device mesh: on a multi-chip host that is the hardware mesh,
in CI it is the ``--xla_force_host_platform_device_count``-forced CPU mesh
(``tests/test_mesh_search.py`` and the mesh-smoke job force 8).
``mesh_for_schedules`` builds the smallest mesh the candidate set needs
from the visible devices, or returns None when the process cannot host it
— in which case sharded candidates keep their analytic score and only the
single-device ones are timed.

Timing uses min-over-repeats after a warmup call (compilation is excluded),
each repeat ending in ``block_until_ready`` so it times the kernel and not
a copy of its output to the host.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.enumerate import ContractionSpec
from ..core.schedule import MESH_TIERS, Schedule


@dataclasses.dataclass
class Measurement:
    schedule: Schedule
    seconds: float
    max_err: Optional[float]  # vs einsum reference; None when skipped


def schedule_mesh_axes(schedule: Schedule) -> Dict[str, int]:
    """{mesh axis -> size} a schedule's mesh levels require (may be {})."""
    out: Dict[str, int] = {}
    for l in schedule.levels:
        if l.tier in MESH_TIERS:
            axis = l.tier.split(":", 1)[1]
            out[axis] = out.get(axis, 1) * l.extent
    return out


def mesh_for_schedules(schedules: Sequence[Schedule]):
    """The smallest debug mesh hosting every sharded schedule, or None.

    Every schedule that uses a mesh axis must use the whole axis (that is
    what ``space.mesh_variants`` emits — an axis is either assigned to an
    index at its full size or left unused/replicated), so conflicting
    sizes for one axis are a caller bug and raise.  Returns None when no
    schedule has mesh levels or the process has too few devices (run
    under ``--xla_force_host_platform_device_count`` to force more).
    """
    need: Dict[str, int] = {}
    for s in schedules:
        for axis, size in schedule_mesh_axes(s).items():
            if need.setdefault(axis, size) != size:
                raise ValueError(
                    f"schedules disagree on mesh axis {axis!r} size: "
                    f"{need[axis]} vs {size}"
                )
    if not need:
        return None
    import math as _math

    import jax

    from ..launch.mesh import make_debug_mesh

    # canonical axis order (pod, data, model) per core.schedule.MESH_TIERS
    order = [t.split(":", 1)[1] for t in MESH_TIERS]
    axes = tuple(a for a in order if a in need)
    shape = tuple(need[a] for a in axes)
    if _math.prod(shape) > jax.device_count():
        return None
    return make_debug_mesh(shape, axes)


def reference_arrays(
    spec: ContractionSpec, dtype=np.float32, seed: int = 0
) -> Dict[str, np.ndarray]:
    """Standard-normal operand arrays in ``spec.operands`` order.

    Integer dtypes (the int8 quant tier) draw small ints instead — every
    product and partial sum is then exactly representable, so the f64
    einsum oracle doubles as the *dequantized* oracle.  fp8 callers pass
    an fp8 ``dtype``: the normal draw rounds through storage precision
    here, which charges input quantization to the data (where it belongs),
    not to the kernel under test.
    """
    rng = np.random.default_rng(seed)
    spec = spec.root()
    dt = np.dtype(dtype)

    def draw(shape):
        if dt.kind in ("i", "u"):
            return rng.integers(-4, 5, size=shape).astype(dt)
        return rng.standard_normal(shape).astype(dt)

    return {
        name: draw(tuple(spec.extents[i] for i in axes))
        for name, axes in spec.operands.items()
    }


def einsum_reference(
    spec: ContractionSpec, arrays: Dict[str, np.ndarray]
) -> np.ndarray:
    """np.einsum oracle for a root spec (f64 accumulation).

    Fused families are not single einsums — attention gets a stable f64
    softmax oracle, grouped_matmul a per-group f64 loop.
    """
    from ..core.enumerate import einsum_formula

    spec = spec.root()
    kind = getattr(spec, "fused_kind", "")
    if kind == "attention":
        q, k, v = (
            np.asarray(arrays[n], np.float64) for n in ("Q", "K", "V")
        )
        s = np.einsum("hsd,htd->hst", q, k) * spec.extents["d"] ** -0.5
        if spec.causal:
            t_ids = np.arange(spec.extents["t"])[None, None, :]
            s_ids = np.arange(spec.extents["s"])[None, :, None]
            s = np.where(t_ids <= s_ids, s, -np.inf)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        p = p / p.sum(axis=-1, keepdims=True)
        return np.einsum("hst,hte->hse", p, v)
    if kind == "grouped_matmul":
        names = tuple(spec.operands)
        vals = {n: np.asarray(arrays[n], np.float64) for n in names}
        sizes = spec.group_sizes
        if "g" in spec.output:  # dW orientation: out[g,o1,o2]
            _, o1, o2 = spec.output
            lhs = next(n for n in names if o1 in spec.operands[n])
            rhs = next(n for n in names if o2 in spec.operands[n])
            out = np.zeros(
                tuple(spec.extents[i] for i in spec.output), np.float64
            )
            o = 0
            for g, s_g in enumerate(sizes):
                out[g] = vals[lhs][o : o + s_g].T @ vals[rhs][o : o + s_g]
                o += s_g
            return out
        # row orientation (fwd / dX): out[n, oc]
        xname, wname = names
        oc = spec.output[1]
        c = spec.operands[xname][1]
        w_axes = spec.operands[wname]
        out = np.zeros(
            tuple(spec.extents[i] for i in spec.output), np.float64
        )
        o = 0
        for g, s_g in enumerate(sizes):
            wg = vals[wname][g]
            if w_axes.index(c) == 2:  # shared axis last -> transpose
                wg = wg.T
            out[o : o + s_g] = vals[xname][o : o + s_g] @ wg
            o += s_g
        return out
    # optimize=True contracts through BLAS: the plain einsum loop takes
    # half a minute at a 512x4096x12288 projection
    return np.einsum(
        einsum_formula(spec),
        *(np.asarray(arrays[n], np.float64) for n in spec.operands),
        optimize=True,
    )


def measure_schedules(
    spec: ContractionSpec,
    schedules: Sequence[Schedule],
    *,
    arrays: Optional[Dict[str, np.ndarray]] = None,
    dtype=np.float32,
    interpret: Optional[bool] = None,
    repeats: int = 2,
    check: bool = True,
    tol: Optional[float] = None,
    mesh=None,
    collectives: Optional[Sequence[str]] = None,
) -> List[Measurement]:
    """Lower + time each schedule; same operand data for every candidate.

    With ``check=True`` every measured kernel is verified against the
    einsum oracle and a mismatch raises — a schedule that computes the
    wrong answer must never win the search.  The default tolerance is
    dtype-appropriate: 1e-3 relative for >= 32-bit floats, 5e-2 for
    half-precision (bf16/f16 round the *stored* output even though the
    generated kernels accumulate in f32).

    Schedules with ``mesh:*`` levels lower through ``codegen.bind_mesh``
    over ``mesh`` (default: ``mesh_for_schedules`` over the visible
    devices; a sharded schedule with no hostable mesh raises).
    ``collectives`` optionally names the finishing-collective lowering per
    schedule (``"psum"``/``"ring"``, ignored for unsharded entries); the
    operands stay global arrays either way, so the oracle check is
    identical for sharded and single-device candidates.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from ..codegen import cached_compile, operand_partition_spec
    from ..ops import default_interpret

    if interpret is None:
        interpret = default_interpret()
    spec = spec.root()
    quantized = np.dtype(dtype).itemsize == 1
    if tol is None:
        # quantized operands (itemsize 1) are exactly representable by
        # construction (reference_arrays), so the kernel only differs from
        # the f64 oracle by f32 accumulation order — full-precision tol
        tol = (
            1e-3 if np.dtype(dtype).itemsize >= 4 or quantized else 5e-2
        )
    if arrays is None:
        arrays = reference_arrays(spec, dtype=dtype)
    jarrs = tuple(jnp.asarray(arrays[n]) for n in spec.operands)
    ref = einsum_reference(spec, arrays) if check else None
    if mesh is None:
        mesh = mesh_for_schedules(schedules)

    out: List[Measurement] = []
    for pos, sched in enumerate(schedules):
        sharded = bool(schedule_mesh_axes(sched))
        if sharded and mesh is None:
            raise ValueError(
                f"schedule {sched.levels} needs a device mesh but none is "
                f"available (devices visible: see jax.device_count(); force "
                f"more with --xla_force_host_platform_device_count)"
            )
        coll = (collectives[pos] if collectives else "") or "psum"
        kern = cached_compile(
            spec, sched, interpret=interpret,
            # 1-byte operands must not round-trip the accumulator through
            # int8/fp8 storage on the way out — measure the f32 result
            out_dtype=jnp.float32 if quantized else None,
            mesh=mesh if sharded else None,
            collective=coll,
        )
        args = jarrs
        if sharded:
            # each shard where the shard_map reads it: operands left on one
            # device would be copied out on every call, and the copy timed
            args = tuple(
                jax.device_put(a, NamedSharding(
                    mesh, operand_partition_spec(kern.plan, n)
                ))
                for a, n in zip(jarrs, spec.operands)
            )
        result = np.asarray(kern(*args))  # warmup (compile + first run)
        err = None
        if check:
            err = float(np.abs(result - ref).max() / max(np.abs(ref).max(), 1e-30))
            if err > tol:
                raise AssertionError(
                    f"schedule {sched.levels} produced wrong output "
                    f"(rel err {err:.3g} > {tol}) — refusing to rank it"
                )
        best = float("inf")
        for _ in range(max(repeats, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(kern(*args))
            best = min(best, time.perf_counter() - t0)
        out.append(Measurement(schedule=sched, seconds=best, max_err=err))
    return out
