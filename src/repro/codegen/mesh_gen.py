"""Mesh tiers -> shard_map: the cluster/device half of the hierarchy.

A ``mesh:*`` level shards its root index over the named mesh axis:

  * map (output) indices  -> the operand and output axes are partitioned
    with a ``PartitionSpec`` entry naming the mesh axis;
  * reduce indices        -> operands are partitioned, each shard computes
    a partial contraction, and a collective over the axis completes the
    reduction.  The lowering of that collective is a per-plan **strategy**
    (``collective=``): plain ``lax.psum``, or the ring-overlap form
    (``collectives.ring_psum``, promoted from ``launch.overlap``) whose
    ppermute hops can hide behind compute on TPU.  The search treats the
    strategy as part of the variant (``search.space.COLLECTIVES``).

``bind_mesh`` wraps a ``CompiledKernel`` (which always works on local,
per-shard shapes) into a ``MeshBoundKernel`` over global arrays.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
from jax.sharding import PartitionSpec as P

from .collectives import STRATEGIES, all_reduce
from .plan import KernelPlan


def _axis_entry(plan: KernelPlan, index: str):
    axes = plan.axes[index].mesh_axes
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def operand_partition_spec(plan: KernelPlan, name: str) -> P:
    return P(*(_axis_entry(plan, i) for i in plan.spec.operands[name]))


def output_partition_spec(plan: KernelPlan) -> P:
    return P(*(_axis_entry(plan, i) for i in plan.spec.output))


def reduce_mesh_axes(plan: KernelPlan) -> Tuple[str, ...]:
    """Mesh axes carrying a reduce index (need a psum to finish)."""
    out = []
    for r in plan.spec.reduce_indices:
        out.extend(plan.axes[r].mesh_axes)
    return tuple(out)


@dataclasses.dataclass
class MeshBoundKernel:
    """A generated kernel shard_mapped over a device mesh.

    Call with GLOBAL arrays (operands in spec order, epilogue vectors by
    keyword); carries the inner ``CompiledKernel`` so callers that
    introspect ``.schedule``/``.plan`` (tests, ``ops._tuned_kernel``) see
    the same surface as the single-device object.
    """

    kernel: object            # the local-shape CompiledKernel
    mesh: object
    collective: str
    _call: object = dataclasses.field(repr=False, default=None)

    @property
    def spec(self):
        return self.kernel.spec

    @property
    def schedule(self):
        return self.kernel.schedule

    @property
    def plan(self) -> KernelPlan:
        return self.kernel.plan

    def __call__(self, *arrays, **vectors):
        return self._call(*arrays, **vectors)


def bind_mesh(kernel, mesh, collective: str = "psum") -> MeshBoundKernel:
    """Wrap a CompiledKernel into a shard_map over ``mesh``.

    Returns a ``MeshBoundKernel`` called on GLOBAL arrays.  Epilogue
    vectors are sharded like the last output axis.  ``collective`` picks
    the finishing-reduction lowering for mesh-sharded reduce indices
    (``"psum"`` or ``"ring"``, see ``collectives``).

    Ordering with sharded reductions: the epilogue must see the FULL sum,
    not per-shard partials — act(psum(partial) + bias), never
    psum(act(partial + bias)).  When a reduce index is mesh-sharded the
    in-kernel epilogue is disabled and re-applied here after the psum.
    """
    import jax.numpy as jnp

    if collective not in STRATEGIES:
        raise ValueError(
            f"unknown collective {collective!r}; choose from {STRATEGIES}"
        )
    plan = kernel.plan
    names = kernel.names
    epilogue = kernel.epilogue
    vec_names = epilogue.vector_names if epilogue else ()
    in_specs = tuple(operand_partition_spec(plan, n) for n in names)
    vec_spec = P(_axis_entry(plan, plan.spec.output[-1]))
    psum_axes = reduce_mesh_axes(plan)
    out_spec = output_partition_spec(plan)

    defer_epilogue = bool(psum_axes) and epilogue is not None and (
        not epilogue.is_identity
    )
    inner = kernel
    if defer_epilogue:
        inner = dataclasses.replace(
            kernel, epilogue=None, out_dtype=jnp.float32, _fn=None
        )
    out_rank = len(plan.spec.output)

    def local_fn(*args):
        ops = args[: len(names)]
        vecs = args[len(names) :]
        out = inner._fn(*ops) if defer_epilogue else inner._fn(*args)
        if psum_axes:
            out = all_reduce(out, psum_axes, collective)
        if defer_epilogue:
            vectors = {
                nm: v.astype(jnp.float32).reshape(
                    (1,) * (out_rank - 1) + (-1,)
                )
                for nm, v in zip(vec_names, vecs)
            }
            out_dtype = kernel.out_dtype or ops[0].dtype
            out = epilogue.apply(out, vectors).astype(out_dtype)
        return out

    wrapped = jax.jit(jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=in_specs + (vec_spec,) * len(vec_names),
        out_specs=out_spec,
        check_vma=False,
    ))

    def call(*arrays, **vectors):
        missing = set(vec_names) - set(vectors)
        if missing:
            raise TypeError(f"epilogue vectors missing: {sorted(missing)}")
        return wrapped(*arrays, *(vectors[v] for v in vec_names))

    return MeshBoundKernel(
        kernel=kernel, mesh=mesh, collective=collective, _call=call
    )
