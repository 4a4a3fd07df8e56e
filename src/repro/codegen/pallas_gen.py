"""KernelPlan -> Pallas kernel: the array half of kernel generation.

Generated kernel shape (one output tile per grid step, fully parallel):

    grid = plan.grid_shape                  # one dim per grid-tier level
    per-operand BlockSpec: block = folded leaf blocks (seq axes resident
      at full local extent), index map routes program_ids to grid axes
    kernel body:
      acc (out_block, f32, VMEM scratch)  = 0
      fori_loop over prod(seq steps):     # the schedule's seq tiers
        slice a chunk of every seq axis (pl.ds)
        acc += dot_general-fold of the operand chunks   # the mxu tier
      store epilogue(acc) -> out block

The dot_general fold (``_contract``) is a minimal einsum: operands are
contracted pairwise left-to-right; indices shared with later operands or
with the output become dot_general *batch* dims, the rest contract.  All
dots accumulate in float32 (``preferred_element_type``), so bf16 inputs
get f32 accumulation exactly like the hand-written kernels.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.cost import VMEM_LIMIT_BYTES
from ..core.enumerate import ContractionSpec
from ..core.schedule import Schedule
from .epilogue import Epilogue
from .plan import KernelPlan, build_plan


def _contract(
    vals: List[jax.Array],
    axlists: List[Tuple[str, ...]],
    out_axes: Tuple[str, ...],
    acc_dtype=jnp.float32,
) -> jax.Array:
    """Contract named-axis blocks down to ``out_axes`` via lax.dot_general.

    Pairs are folded greedily by smallest intermediate: a reduce index
    shared with a *later* operand becomes a dot_general batch dim, so
    naive left-to-right folding of e.g. A_ij B_jk g_j would materialize a
    (j, bm, bn) block; folding (A, g) first keeps every intermediate no
    larger than its inputs' footprint.
    """
    terms = list(zip(vals, [list(a) for a in axlists]))
    while len(terms) > 1:
        best = None
        for x in range(len(terms)):
            for y in range(x + 1, len(terms)):
                (a, ax), (b, bx) = terms[x], terms[y]
                rest = {
                    i
                    for z, (_, axs) in enumerate(terms)
                    if z not in (x, y)
                    for i in axs
                }
                shared = [i for i in ax if i in bx]
                contract = [
                    i for i in shared if i not in out_axes and i not in rest
                ]
                batch = [i for i in shared if i not in contract]
                res_axes = (
                    batch
                    + [i for i in ax if i not in shared]
                    + [i for i in bx if i not in shared]
                )
                sizes = {**dict(zip(bx, b.shape)), **dict(zip(ax, a.shape))}
                elems = math.prod(sizes[i] for i in res_axes) if res_axes else 1
                if best is None or elems < best[0]:
                    best = (elems, x, y, contract, batch, res_axes)
        _, x, y, contract, batch, res_axes = best
        (a, ax), (b, bx) = terms[x], terms[y]
        dn = (
            (
                tuple(ax.index(i) for i in contract),
                tuple(bx.index(i) for i in contract),
            ),
            (
                tuple(ax.index(i) for i in batch),
                tuple(bx.index(i) for i in batch),
            ),
        )
        res = lax.dot_general(a, b, dn, preferred_element_type=acc_dtype)
        terms = [
            t for z, t in enumerate(terms) if z not in (x, y)
        ]
        terms.insert(0, (res, res_axes))
    val, axes = terms[0]
    extra = [i for i in axes if i not in out_axes]
    if extra:  # reduce axes touched by a single operand
        val = jnp.sum(
            val.astype(acc_dtype),
            axis=tuple(axes.index(i) for i in extra),
        )
        axes = [i for i in axes if i not in extra]
    perm = tuple(axes.index(i) for i in out_axes)
    return jnp.transpose(val.astype(acc_dtype), perm)


def _index_map(plan: KernelPlan, axes: Sequence[str]):
    dims = tuple(plan.axes[a].grid_dim for a in axes)

    def imap(*pids):
        return tuple(pids[d] if d is not None else 0 for d in dims)

    return imap


def _make_kernel(
    plan: KernelPlan,
    names: Tuple[str, ...],
    epilogue: Optional[Epilogue],
    acc_dtype=jnp.float32,
):
    spec = plan.spec
    out_axes = spec.output
    seq_roots = plan.seq
    seq_shape = plan.seq_shape
    nsteps = math.prod(seq_shape) if seq_shape else 1
    vec_names = epilogue.vector_names if epilogue else ()
    out_rank = len(out_axes)

    def kernel(*refs):
        op_refs = refs[: len(names)]
        vec_refs = refs[len(names) : len(names) + len(vec_names)]
        o_ref = refs[len(names) + len(vec_names)]
        acc_ref = refs[-1]
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def body(t, carry):
            pos: Dict[str, object] = {}
            rem = t
            for i, r in enumerate(seq_roots):
                below = math.prod(seq_shape[i + 1 :]) if i + 1 < len(
                    seq_shape
                ) else 1
                pos[r] = rem // below
                rem = rem % below
            vals, axlists = [], []
            for name, ref in zip(names, op_refs):
                axes = spec.operands[name]
                idx = tuple(
                    pl.ds(pos[a] * plan.axes[a].chunk, plan.axes[a].chunk)
                    if a in pos
                    else slice(None)
                    for a in axes
                )
                v = ref[idx]
                # quantized operands (int8 / fp8) land in VMEM at storage
                # precision; the MXU-side contraction runs on the upcast
                if v.dtype != acc_dtype and v.dtype.itemsize == 1:
                    v = v.astype(acc_dtype)
                vals.append(v)
                axlists.append(axes)
            acc_ref[...] += _contract(vals, axlists, out_axes, acc_dtype)
            return carry

        if nsteps == 1:
            body(0, 0)
        else:
            lax.fori_loop(0, nsteps, body, 0)

        out = acc_ref[...]
        if epilogue is not None and not epilogue.is_identity:
            vectors = {}
            for vname, vref in zip(vec_names, vec_refs):
                row = vref[...].astype(jnp.float32)  # (1, block_last)
                vectors[vname] = row.reshape(
                    (1,) * (out_rank - 1) + (row.shape[-1],)
                )
            out = epilogue.apply(out, vectors)
        o_ref[...] = out.astype(o_ref.dtype)

    return kernel


@dataclasses.dataclass
class CompiledKernel:
    """A generated kernel bound to one (spec, schedule) pair.

    Call with the operand arrays in ``spec.operands`` order; epilogue
    vectors (bias/mean/var/scale) go by keyword.  Shapes are the *local*
    (per-shard) shapes; use ``codegen.bind_mesh`` / ``mesh=`` for the
    sharded version.
    """

    spec: ContractionSpec
    schedule: Schedule
    plan: KernelPlan
    epilogue: Optional[Epilogue]
    out_dtype: Optional[object]
    interpret: bool
    _fn: object = dataclasses.field(repr=False, default=None)

    def __post_init__(self):
        if self._fn is None:
            self._fn = jax.jit(self._build())

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(self.spec.operands)

    def _build(self):
        plan, spec = self.plan, self.spec
        names = self.names
        epilogue = self.epilogue
        vec_names = epilogue.vector_names if epilogue else ()
        # low-precision specs carry their accumulator: int8 products sum
        # exactly in an int32 VMEM scratch; fp8 accumulates in f32
        quant = getattr(spec.root(), "quant", None)
        acc_dtype = (
            jnp.int32
            if quant is not None and quant.accum == "int32"
            else jnp.float32
        )
        grid = plan.grid_shape or (1,)
        last = spec.output[-1]
        last_dim = plan.axes[last].grid_dim
        block_last = plan.axes[last].block

        in_specs = [
            pl.BlockSpec(plan.operand_block(n), _index_map(plan, spec.operands[n]))
            for n in names
        ]

        def vec_imap(*pids):
            return (0, pids[last_dim] if last_dim is not None else 0)

        in_specs += [
            pl.BlockSpec((1, block_last), vec_imap) for _ in vec_names
        ]
        out_spec = pl.BlockSpec(plan.out_block(), _index_map(plan, spec.output))
        kernel = _make_kernel(plan, names, epilogue, acc_dtype)

        def fn(*arrays):
            ops = arrays[: len(names)]
            vecs = arrays[len(names) :]
            if self.out_dtype is not None:
                out_dtype = self.out_dtype
            elif quant is not None:
                # int8×int8→int32 (fp8→f32): the accumulator IS the
                # result, unless a dequant epilogue already rescaled it
                # back to real values
                out_dtype = (
                    jnp.float32
                    if epilogue is not None and epilogue.dequant
                    else acc_dtype
                )
            else:
                out_dtype = ops[0].dtype
            rows = tuple(v.reshape(1, -1) for v in vecs)
            return pl.pallas_call(
                kernel,
                grid=grid,
                in_specs=in_specs,
                out_specs=out_spec,
                out_shape=jax.ShapeDtypeStruct(plan.out_shape(), out_dtype),
                scratch_shapes=[pltpu.VMEM(plan.out_block(), acc_dtype)],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel",) * len(grid),
                    vmem_limit_bytes=VMEM_LIMIT_BYTES,
                ),
                interpret=self.interpret,
            )(*ops, *rows)

        return fn

    def __call__(self, *arrays, **vectors):
        names = self.names
        if len(arrays) != len(names):
            raise TypeError(
                f"{self.spec.name} takes {len(names)} operands "
                f"{names}, got {len(arrays)}"
            )
        for name, arr in zip(names, arrays):
            want = tuple(
                self.plan.axes[i].local_extent
                for i in self.spec.operands[name]
            )
            if tuple(arr.shape) != want:
                raise ValueError(
                    f"operand {name}: expected local shape {want}, "
                    f"got {tuple(arr.shape)}"
                )
        vec_names = self.epilogue.vector_names if self.epilogue else ()
        missing = set(vec_names) - set(vectors)
        if missing:
            raise TypeError(f"epilogue vectors missing: {sorted(missing)}")
        vecs = tuple(vectors[v] for v in vec_names)
        return self._fn(*arrays, *vecs)


def compile_kernel(
    spec: ContractionSpec,
    schedule: Schedule,
    *,
    epilogue: Optional[Epilogue] = None,
    out_dtype=None,
    interpret: bool = False,
    mesh=None,
    collective: str = "psum",
):
    """Compile any ContractionSpec + Schedule into a runnable kernel.

    ``spec`` may be the root spec or the schedule's own (subdivided) spec;
    they must share a root.  Returns a ``CompiledKernel`` (local shapes),
    or — when ``mesh`` is given and the schedule has mesh tiers — the
    shard_map-wrapped ``MeshBoundKernel`` over global arrays.
    ``collective`` picks the finishing-reduction strategy for mesh-sharded
    reduce indices (``"psum"`` | ``"ring"``, see ``codegen.collectives``).
    """
    if spec.root() is not schedule.spec.root() and (
        spec.root().operands != schedule.spec.root().operands
        or spec.root().extents != schedule.spec.root().extents
    ):
        raise ValueError("spec and schedule disagree on the root contraction")
    if getattr(spec.root(), "fused_kind", ""):
        from .fused_gen import compile_fused

        return compile_fused(
            spec, schedule,
            epilogue=epilogue, out_dtype=out_dtype, interpret=interpret,
            mesh=mesh, collective=collective,
        )
    from ..obs import span

    with span("codegen.compile", spec=spec.root().name,
              sharded=mesh is not None):
        plan = build_plan(schedule)
        kernel = CompiledKernel(
            spec=plan.spec,
            schedule=schedule,
            plan=plan,
            epilogue=epilogue,
            out_dtype=out_dtype,
            interpret=interpret,
        )
        if mesh is not None:
            from .mesh_gen import bind_mesh

            return bind_mesh(kernel, mesh, collective=collective)
        return kernel


_KERNEL_MEMO: Dict[tuple, CompiledKernel] = {}


def compiled_kernels() -> List[object]:
    """Every kernel ``cached_compile`` has built in this process — the
    kernels the ops dispatched (``CompiledKernel``, ``FusedKernel`` or
    ``MeshBoundKernel``)."""
    return list(_KERNEL_MEMO.values())


def cached_compile(
    spec: ContractionSpec,
    schedule: Schedule,
    *,
    epilogue: Optional[Epilogue] = None,
    out_dtype=None,
    interpret: bool = False,
    mesh=None,
    collective: str = "psum",
):
    """compile_kernel memoized on (spec, schedule, epilogue, dtype, interpret,
    mesh identity, collective).

    Hot-path entry for ``ops``/``launch``: repeated calls with the same
    contraction reuse one jitted kernel instead of re-tracing.  Mesh-bound
    kernels key on the mesh's axes and (for a concrete mesh) device ids,
    so two distinct meshes of the same shape get distinct shard_map
    closures.
    """
    import json

    from .cache import schedule_to_dict, spec_signature

    mesh_key = None
    if mesh is not None:
        mesh_key = (
            tuple(mesh.axis_names),
            tuple(int(s) for s in mesh.axis_sizes),
            # an abstract mesh (what jit tracing sees) runs on the devices
            # of the concrete mesh jax.set_mesh activated
            tuple(int(d.id) for d in mesh.devices.flat)
            if isinstance(mesh, jax.sharding.Mesh) else (),
        )
    key = (
        json.dumps(spec_signature(spec), sort_keys=True),
        json.dumps(schedule_to_dict(schedule), sort_keys=True),
        epilogue,
        str(out_dtype) if out_dtype is not None else None,
        interpret,
        mesh_key,
        collective if mesh is not None else None,
    )
    from ..obs import counter

    kern = _KERNEL_MEMO.get(key)
    counter(f"codegen.memo.{'miss' if kern is None else 'hit'}").inc()
    if kern is None:
        kern = compile_kernel(
            spec,
            schedule,
            epilogue=epilogue,
            out_dtype=out_dtype,
            interpret=interpret,
            mesh=mesh,
            collective=collective,
        )
        _KERNEL_MEMO[key] = kern
    return kern
