"""Fused-family Pallas builders: flash attention + grouped (MoE) matmul.

``pallas_gen`` lowers any plain product-reduce contraction; the two fused
spec families (``core.enumerate.AttentionSpec`` / ``GroupedSpec``) carry
semantics the generic fold cannot express, so ``compile_kernel`` routes
them here.  Both consume the same ``KernelPlan`` a Schedule produces —
the searched block sizes drive the fused grids:

attention (out = softmax_t(Q·Kᵀ/√d + mask) · V)
    grid = (H/bh, S/bs, T/bt) with the KV axis LAST and ``arbitrary``
    (sequential) semantics: running max / sum / f32 accumulator live in
    VMEM scratch across the T steps (the online-softmax rescale), init
    under ``pl.when(t == 0)`` and the final ``acc / l`` store under
    ``pl.when(t == nt - 1)``.  ``bt`` is the schedule's seq-tier chunk of
    ``t``; bh/bs are the grid blocks of h/s; d and e stay whole
    (``AttentionSpec.whole_indices``).  Causal / kv-length masking uses
    2-D ``broadcasted_iota`` offset by the program ids.  Masked scores
    are set to ``MASK_VALUE`` (not -inf: exp of a -inf difference is NaN)
    and masked probabilities re-zeroed so a fully-masked *block* cannot
    pollute the running sum.

grouped matmul (out[n,:] = x[n,:] @ w[group(n)])
    row mode (fwd / dX): grid = (OC/bn, G) with the group axis last and
    ``arbitrary`` semantics — the (N, bn) output block stays resident in
    a f32 VMEM accumulator while every group adds its row stripe.  Group
    offsets are STATIC (``group_sizes`` lives on the spec), dispatched as
    a ``pl.when(g == const)`` chain; each group walks its rows in
    ``bm``-sized tiles (the schedule's block of ``n``) with the start
    clamped to stay in bounds and a row-mask write so ragged tails and
    size-1/empty groups come out exactly.
    dW mode (output carries ``g``): one (K, bn) tile per (group, column
    block), rows outside the group zeroed before the xᵀ·g dot — blocks
    are disjoint per group so no accumulator is needed.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.cost import VMEM_LIMIT_BYTES
from ..core.enumerate import ContractionSpec
from ..core.schedule import Schedule
from .plan import KernelPlan, build_plan

#: large-but-finite score for masked positions — exp(MASK - m) underflows
#: to 0 while exp(-inf - (-inf)) would be NaN (boom guide §3)
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _attention_fn(
    plan: KernelPlan,
    causal: bool,
    with_lengths: bool,
    out_dtype,
    interpret: bool,
):
    spec = plan.spec
    H, S, T = (spec.extents[i] for i in ("h", "s", "t"))
    D, E = spec.extents["d"], spec.extents["e"]
    bh, bs = plan.axes["h"].block, plan.axes["s"].block
    bt = plan.axes["t"].chunk
    nh, ns, nt = H // bh, S // bs, T // bt
    scale = float(D) ** -0.5

    def kernel(*refs):
        if with_lengths:
            q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref, acc_ref = refs
        else:
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        j = pl.program_id(1)
        kp = pl.program_id(2)

        @pl.when(kp == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        s = lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        valid = None
        if causal or with_lengths:
            col = lax.broadcasted_iota(jnp.int32, (bh, bs, bt), 2) + kp * bt
        if causal:
            row = lax.broadcasted_iota(jnp.int32, (bh, bs, bt), 1) + j * bs
            valid = col <= row
        if with_lengths:
            lm = col < len_ref[...][:, :, None]
            valid = lm if valid is None else (valid & lm)
        if valid is not None:
            s = jnp.where(valid, s, MASK_VALUE)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=2))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :, None])
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2)
        v = v_ref[...].astype(jnp.float32)
        pv = lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha[:, :, None] + pv
        m_ref[...] = m_next

        @pl.when(kp == nt - 1)
        def _done():
            l = l_ref[...]
            l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0, not NaN
            o_ref[...] = (acc_ref[...] / l[:, :, None]).astype(o_ref.dtype)

    in_specs = [
        pl.BlockSpec((bh, bs, D), lambda i, j, kp: (i, j, 0)),
        pl.BlockSpec((bh, bt, D), lambda i, j, kp: (i, kp, 0)),
        pl.BlockSpec((bh, bt, E), lambda i, j, kp: (i, kp, 0)),
    ]
    if with_lengths:
        in_specs.append(pl.BlockSpec((bh, 1), lambda i, j, kp: (i, 0)))

    def fn(*arrays):
        dt = out_dtype or arrays[0].dtype
        return pl.pallas_call(
            kernel,
            grid=(nh, ns, nt),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bh, bs, E), lambda i, j, kp: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((H, S, E), dt),
            scratch_shapes=[
                pltpu.VMEM((bh, bs), jnp.float32),
                pltpu.VMEM((bh, bs), jnp.float32),
                pltpu.VMEM((bh, bs, E), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
            ),
            interpret=interpret,
        )(*arrays)

    return jax.jit(fn)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------


def _group_offsets(group_sizes: Tuple[int, ...]):
    offs, o = [], 0
    for s in group_sizes:
        offs.append(o)
        o += s
    return offs


def _grouped_row_fn(
    plan: KernelPlan,
    group_sizes: Tuple[int, ...],
    out_dtype,
    interpret: bool,
):
    """fwd (out[n,f] = x@w[g]) and dX (out[n,k] = g@w[g] over f) lowering.

    Introspects the spec so both orientations share one builder: the
    first operand is (n, c); the 3-D operand is (g, ·, ·) with the shared
    axis ``c`` in either trailing slot.
    """
    spec = plan.spec
    xname, wname = tuple(spec.operands)
    n_ax, c_ax = spec.operands[xname]
    w_axes = spec.operands[wname]
    g_ax = w_axes[0]
    oc_ax = spec.output[1]
    N, C, OC = spec.extents[n_ax], spec.extents[c_ax], spec.extents[oc_ax]
    G = len(group_sizes)
    wc = w_axes.index(c_ax) - 1  # contract dim of the squeezed (2-D) w tile
    bm = plan.axes[n_ax].block
    bn = plan.axes[oc_ax].block
    nj = OC // bn
    offsets = _group_offsets(group_sizes)

    w_block = tuple(
        1 if a == g_ax else (C if a == c_ax else bn) for a in w_axes
    )

    def w_imap(j, g):
        return tuple(
            g if a == g_ax else (0 if a == c_ax else j) for a in w_axes
        )

    def kernel(x_ref, w_ref, o_ref, acc_ref):
        g = pl.program_id(1)

        @pl.when(g == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        wm = w_ref[...][0]  # (C, bn) or (bn, C)
        for gg in range(G):
            s_g = group_sizes[gg]
            if s_g == 0:
                continue
            o = offsets[gg]
            ntile = -(-s_g // bm)

            @pl.when(g == gg)
            def _acc(o=o, s_g=s_g, ntile=ntile):
                for i in range(ntile):
                    r0 = min(o + i * bm, N - bm)
                    rows = x_ref[r0 : r0 + bm, :].astype(jnp.float32)
                    part = lax.dot_general(
                        rows, wm, (((1,), (wc,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    rid = r0 + lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
                    ok = (rid >= o + i * bm) & (rid < o + s_g)
                    cur = acc_ref[r0 : r0 + bm, :]
                    acc_ref[r0 : r0 + bm, :] = jnp.where(ok, cur + part, cur)

        @pl.when(g == G - 1)
        def _done():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    def fn(x, w):
        dt = out_dtype or x.dtype
        return pl.pallas_call(
            kernel,
            grid=(nj, G),
            in_specs=[
                pl.BlockSpec((N, C), lambda j, g: (0, 0)),
                pl.BlockSpec(w_block, w_imap),
            ],
            out_specs=pl.BlockSpec((N, bn), lambda j, g: (0, j)),
            out_shape=jax.ShapeDtypeStruct((N, OC), dt),
            scratch_shapes=[pltpu.VMEM((N, bn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
            ),
            interpret=interpret,
        )(x, w)

    return jax.jit(fn)


def _grouped_dw_fn(
    plan: KernelPlan,
    group_sizes: Tuple[int, ...],
    out_dtype,
    interpret: bool,
):
    """dW mode: out[g,k,f] = sum_{n in group g} x[n,k] * dout[n,f]."""
    spec = plan.spec
    g_ax, o1, o2 = spec.output
    names = tuple(spec.operands)
    lhs = next(nm for nm in names if o1 in spec.operands[nm])  # (n, o1)
    rhs = next(nm for nm in names if o2 in spec.operands[nm])  # (n, o2)
    n_ax = spec.operands[lhs][0]
    N, K1, K2 = spec.extents[n_ax], spec.extents[o1], spec.extents[o2]
    G = len(group_sizes)
    bn = plan.axes[o2].block
    nj = K2 // bn
    offsets = _group_offsets(group_sizes)
    order = (0, 1) if names[0] == lhs else (1, 0)

    def kernel(*refs):
        l_ref, r_ref = refs[order[0]], refs[order[1]]
        o_ref = refs[2]
        g = pl.program_id(0)
        for gg in range(G):

            @pl.when(g == gg)
            def _emit(o=offsets[gg], s_g=group_sizes[gg]):
                lv = l_ref[...].astype(jnp.float32)
                rid = lax.broadcasted_iota(jnp.int32, (N, 1), 0)
                ok = (rid >= o) & (rid < o + s_g)
                lv = jnp.where(ok, lv, 0.0)  # empty group -> exact zeros
                rv = r_ref[...].astype(jnp.float32)
                res = lax.dot_general(
                    lv, rv, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                o_ref[...] = res[None].astype(o_ref.dtype)

    lhs_spec = pl.BlockSpec((N, K1), lambda g, j: (0, 0))
    rhs_spec = pl.BlockSpec((N, bn), lambda g, j: (0, j))
    in_specs = (
        [lhs_spec, rhs_spec] if names[0] == lhs else [rhs_spec, lhs_spec]
    )

    def fn(a, b):
        dt = out_dtype or a.dtype
        return pl.pallas_call(
            kernel,
            grid=(G, nj),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, K1, bn), lambda g, j: (g, 0, j)),
            out_shape=jax.ShapeDtypeStruct((G, K1, K2), dt),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
            ),
            interpret=interpret,
        )(a, b)

    return jax.jit(fn)


# ---------------------------------------------------------------------------
# wrapper + entry point
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FusedKernel:
    """Generated fused kernel bound to one (spec, schedule) pair.

    Call with the operand arrays in ``spec.operands`` order; attention
    additionally accepts ``kv_lengths=`` (int32 per folded head, the
    PR 7 plumbing) which routes through a lazily-built second variant.
    """

    spec: ContractionSpec
    schedule: Schedule
    plan: KernelPlan
    out_dtype: Optional[object]
    interpret: bool
    kind: str
    epilogue: Optional[object] = None  # parity with CompiledKernel
    _fn: object = dataclasses.field(repr=False, default=None)
    _fn_lengths: object = dataclasses.field(repr=False, default=None)

    def __post_init__(self):
        root = self.spec.root()
        if self._fn is None:
            if self.kind == "attention":
                self._fn = _attention_fn(
                    self.plan, bool(root.causal), False,
                    self.out_dtype, self.interpret,
                )
            elif "g" in self.spec.output:
                self._fn = _grouped_dw_fn(
                    self.plan, tuple(root.group_sizes),
                    self.out_dtype, self.interpret,
                )
            else:
                self._fn = _grouped_row_fn(
                    self.plan, tuple(root.group_sizes),
                    self.out_dtype, self.interpret,
                )

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(self.spec.operands)

    def __call__(self, *arrays, kv_lengths=None):
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
        if kv_lengths is None:
            return self._fn(*arrays)
        if self.kind != "attention":
            raise TypeError("kv_lengths only applies to attention kernels")
        lengths = jnp.asarray(kv_lengths, jnp.int32).reshape(-1, 1)
        H = self.spec.extents["h"]
        if lengths.shape[0] != H:
            raise ValueError(
                f"kv_lengths: expected {H} entries, got {lengths.shape[0]}"
            )
        if self._fn_lengths is None:
            self._fn_lengths = _attention_fn(
                self.plan, bool(self.spec.root().causal), True,
                self.out_dtype, self.interpret,
            )
        return self._fn_lengths(*arrays, lengths)


def compile_fused(
    spec: ContractionSpec,
    schedule: Schedule,
    *,
    epilogue=None,
    out_dtype=None,
    interpret: bool = False,
    mesh=None,
    collective: str = "psum",
) -> FusedKernel:
    """Lower a fused-family spec + Schedule; ``compile_kernel`` dispatches
    here whenever ``spec.root().fused_kind`` is set."""
    root = spec.root()
    kind = getattr(root, "fused_kind", "")
    if not kind:
        raise ValueError(f"{root.name} is not a fused spec")
    if epilogue is not None and not getattr(epilogue, "is_identity", False):
        raise NotImplementedError("fused kernels take no epilogue")
    if mesh is not None:
        raise NotImplementedError("fused families have no mesh tier yet")
    from ..obs import span

    with span("codegen.compile_fused", spec=root.name, kind=kind):
        plan = build_plan(schedule)
        return FusedKernel(
            spec=plan.spec,
            schedule=schedule,
            plan=plan,
            out_dtype=out_dtype,
            interpret=interpret,
            kind=kind,
        )
