"""Framework-level dense ops, routed through the kernel generator.

``dense`` is the single entry point every model matmul goes through.  On
TPU backends (or with ``interpret=True``) 2-D contractions compile through
``repro.codegen``: the Schedule comes from the ranked plan database
(``repro.search`` — measured winners of the cost-guided variant search)
when a sweep has run for the shape, else from the persistent autotune
cache (``codegen.tune_schedule``), so a serving replica reuses the fleet's
searched/tuned block shapes instead of re-tuning at import time.  On CPU
and in the dry-run everything lowers to ``lax.dot_general`` so GSPMD can
partition it.  This is where the paper's technique meets the model zoo.

New scenario entry points (all generated — the repo had no kernels for
these before ``codegen`` existed):

  ``batched_dense``   out[b,i,k] = sum_j x[b,i,j] w[b,j,k]
  ``chain_dense``     out[i,l]   = sum_jk a[i,j] b[j,k] c[k,l]
  ``dense_transposed``out[i,k]   = sum_j a[j,i] b[j,k]
  ``weighted_dense``  out[i,k]   = sum_j x[i,j] w[j,k] g[j]  (paper eq 2;
                      generated replacement for kernels/fused_rnz)
  ``dense_act``       epilogue-fused dense+bias+norm+activation
                      (the generated replacement for kernels/fused_dense_act)

Whole-model entry: ``repro.capture.optimize(fn)`` harvests a traced
function's plain ``dot_general`` sites and dispatches the eligible ones
through these entry points — the predicates below (``_dense_kernel_ok``
etc.) are the shared single source of truth for what "eligible" means.

All entry points are **differentiable by default**: whenever the call
would dispatch to a generated kernel, ``differentiable=True`` routes
through the ``repro.grad`` custom_vjp wrappers, whose backward GEMMs are
derived ContractionSpecs (``grad.derive``) compiled through this same
plan-DB/autotune pipeline — so ``jax.grad`` of a loss built on these ops
runs generated kernels on both sides of the tape (``launch.steps``).  On
the non-kernel paths (CPU, unaligned shapes) the op stays a plain
einsum/dot, so JAX's native autodiff — forward mode included — applies
unchanged.  Pass ``differentiable=False`` to get the bare primal (no VJP
registered; ``jax.grad`` through a raw Pallas kernel raises).

Caveat: ``jax.custom_vjp`` supports reverse mode only, so forward-mode
autodiff (``jax.jvp`` / ``jax.jacfwd`` / ``jax.linearize``) raises
exactly where the generated-kernel dispatch fires (the raw Pallas kernel
has no JVP either way); everywhere else it works as before.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """The ``interpret`` the backend implies: real kernels on a TPU, the
    Pallas interpreter everywhere else."""
    return not _use_pallas()


def _mesh_plan_kernel(spec, dtype, *, epilogue=None, interpret=False):
    """Sharded generated kernel from a mesh-qualified plan, or None.

    When the calling context runs under a device mesh (``jax.set_mesh``
    — checked at trace time), the plan DB is
    consulted under the mesh-shape-qualified key ('2x4'-style,
    ``search.plandb.plan_key(mesh=...)``) for the best rung that actually
    distributes (``best_sharded_entry`` — under a live mesh the operands
    are sharded, so a mesh ladder's single-device reference rungs do not
    apply).  A sharded plan whose mesh axes match the active mesh
    compiles through ``codegen.bind_mesh`` with the plan's measured
    collective strategy.  Any mismatch (axis names/sizes, no plan)
    returns None and the caller falls back to the unqualified lookup — a
    replica without mesh sweeps behaves exactly as before.
    """
    from .. import codegen
    from ..launch.mesh import active_mesh, mesh_shape_descriptor

    mesh = active_mesh()
    if mesh is None or getattr(mesh, "size", 1) <= 1:
        return None
    from ..search import default_plan_db, schedule_mesh_axes

    sched, entry = default_plan_db().best_sharded_entry(
        spec, np.dtype(dtype), mesh=mesh_shape_descriptor(mesh)
    )
    if sched is None:
        return None
    axes = schedule_mesh_axes(sched)
    if any(mesh.shape.get(a) != n for a, n in axes.items()):
        return None
    return codegen.cached_compile(
        spec, sched, epilogue=epilogue, interpret=interpret,
        mesh=mesh, collective=entry.get("collective") or "psum",
    )


def _tuned_kernel(spec, dtype, *, epilogue=None, out_dtype=None,
                  interpret=False):
    """Generated kernel for ``spec``: searched plan first, tuned fallback.

    The ranked plan database (``repro.search``) is consulted before the
    analytic tuner: an offline ``scripts/search_sweep.py`` run or a
    ``serve --search-gemms`` warmup leaves a measured-best schedule there,
    and every later call for the same spec/shape/dtype picks it up.  When
    a device mesh is active the mesh-shape-qualified key is consulted
    first (``_mesh_plan_kernel``), so a ``--mesh`` sweep upgrades every
    op under that mesh to sharded generated kernels.  When a serving
    phase is active (``search.serving_phase`` — entered by the
    prefill/decode runners around their jitted steps) the
    phase-qualified ladder is consulted before the unphased one, so the
    decode runner's bandwidth-bound skinny GEMMs serve their own searched
    winner rather than the prefill ladder's.  With no plan on record this
    degrades to PR-1 behaviour (``codegen.tune_schedule`` + persistent
    autotune cache).
    """
    from .. import codegen
    from ..search import active_phase, default_plan_db

    kern = _mesh_plan_kernel(
        spec, dtype, epilogue=epilogue, interpret=interpret
    )
    if kern is not None:
        return kern
    # PlanDB.best_schedule degrades a corrupt or stale entry to a miss;
    # anything else it raises is a fault and propagates
    schedule = None
    phase = active_phase()
    if phase is not None:
        schedule = default_plan_db().best_schedule(
            spec, np.dtype(dtype), phase=phase
        )
    if schedule is None:
        schedule = default_plan_db().best_schedule(spec, np.dtype(dtype))
    if schedule is None:
        schedule = codegen.tune_schedule(spec, dtype=np.dtype(dtype))
    return codegen.cached_compile(
        spec, schedule, epilogue=epilogue, out_dtype=out_dtype,
        interpret=interpret,
    )


def warm_dense_cache(shapes, dtype=jnp.bfloat16) -> int:
    """Pre-tune schedules for (m, k, n) GEMMs; returns #schedules readied.

    Called by serving entry points at startup so the first request never
    pays tuning latency; hits the persistent cache when the fleet has
    tuned these shapes before.
    """
    from .. import codegen
    from ..core.enumerate import matmul_spec

    count = 0
    for m, k, n in shapes:
        codegen.tune_schedule(matmul_spec(m, k, n), dtype=np.dtype(dtype))
        count += 1
    return count


def _dt_name(dtype) -> str:
    """Hashable dtype key for the grad factory caches."""
    return np.dtype(dtype).name


# -- kernel-dispatch predicates, shared with the grad.vjp backward passes --
# The custom_vjp wrapping is gated on exactly these: where an op lowers to
# a plain einsum/dot anyway, native JAX autodiff (fwd mode included) stays
# in charge and the wrapper would only subtract capability.


def _dense_kernel_ok(x, w, interpret: bool) -> bool:
    return (_use_pallas() or interpret) and x.ndim == 2 and all(
        s % 128 == 0 for s in (*x.shape, w.shape[1])
    )


def _batched_kernel_ok(x, w, interpret: bool) -> bool:
    return (_use_pallas() or interpret) and x.ndim == 3 and w.ndim == 3


def _generic_kernel_ok(interpret: bool) -> bool:
    return _use_pallas() or interpret


def _dense_raw(x, w, out_dtype, interpret):
    if _dense_kernel_ok(x, w, interpret):
        from ..core.enumerate import matmul_spec

        m, d = x.shape
        _, f = w.shape
        kern = _tuned_kernel(
            matmul_spec(m, d, f), x.dtype, interpret=interpret
        )
        return kern(x, w).astype(out_dtype)
    return jnp.dot(
        x, w, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def _dense_quant(x, w, fmt, out_dtype, interpret):
    """Dynamic-quantized dense: int8/fp8 storage, dequant epilogue.

    ``x`` is quantized per-tensor (one absmax scale), ``w`` per output
    channel (one scale per column of F) — the combined ``qscale = sx * sw``
    row is exactly what the generated kernel's dequant epilogue multiplies
    into the accumulator, so the kernel streams 1-byte operands and writes
    real-valued output in one pass.  Kernel-ineligible shapes take the
    dequantize-then-dot fallback with identical quantization semantics.
    """
    from ..core.enumerate import QUANT_FORMATS, quantized_matmul_spec
    from ..optim.quant import quantize_channels, quantize_tensor

    if fmt not in QUANT_FORMATS:
        raise ValueError(
            f"quant must be one of {sorted(QUANT_FORMATS)}, got {fmt!r}"
        )
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    qx, sx = quantize_tensor(x2, fmt)
    qw, sw = quantize_channels(w, fmt)
    qscale = (sx * sw).astype(jnp.float32)
    if _dense_kernel_ok(x2, w, interpret):
        from .. import codegen

        m, d = x2.shape
        f = w.shape[1]
        kern = _tuned_kernel(
            quantized_matmul_spec(m, d, f, fmt), qx.dtype,
            epilogue=codegen.Epilogue(dequant=True),
            out_dtype=jnp.float32, interpret=interpret,
        )
        out = kern(qx, qw, qscale=qscale)
    else:
        out = jnp.dot(
            qx.astype(jnp.float32), qw.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * qscale[None, :]
    return out.reshape(*lead, w.shape[1]).astype(out_dtype)


def dense(x: jax.Array, w: jax.Array, out_dtype=None,
          interpret: bool = False, differentiable: bool = True,
          quant=None) -> jax.Array:
    """x: (..., D) @ w: (D, F) -> (..., F), f32 accumulation.

    With ``differentiable`` (the default), a call dispatching to the
    generated kernel goes through ``grad.dense_vjp``: same primal, plus a
    custom VJP whose dA/dB GEMMs compile through the generated-kernel
    pipeline under their own derived-spec keys (``matmul.dA`` /
    ``matmul.dB``).  Fallback paths stay natively differentiable.

    ``quant`` ('int8' | 'fp8') takes the low-precision tier instead:
    operands are dynamically quantized (x per-tensor, w per-channel), the
    contraction runs on the dtype-qualified searched kernel
    (``matmul@...@dtype=int8`` plans), and the scales are applied by the
    kernel's dequant epilogue.  The quant tier is inference-oriented —
    the quantize ops are differentiable only through the fallback path.
    """
    out_dtype = out_dtype or x.dtype
    if quant is not None:
        return _dense_quant(x, w, quant, out_dtype, interpret)
    if differentiable and _dense_kernel_ok(x, w, interpret):
        from ..grad import dense_vjp

        return dense_vjp(_dt_name(out_dtype), bool(interpret))(x, w)
    return _dense_raw(x, w, out_dtype, interpret)


def _weighted_kernel_ok(x, interpret: bool) -> bool:
    return (_use_pallas() or interpret) and x.ndim == 2


def _weighted_dense_raw(x, w, g, out_dtype, interpret):
    if _weighted_kernel_ok(x, interpret):
        from ..core.enumerate import weighted_matmul_spec

        m, d = x.shape
        _, f = w.shape
        kern = _tuned_kernel(
            weighted_matmul_spec(m, d, f), x.dtype, interpret=interpret
        )
        return kern(x, w, g).astype(out_dtype)
    return jnp.dot(
        x * g[None, :], w, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def weighted_dense(x, w, g, out_dtype=None, interpret: bool = False,
                   differentiable: bool = True):
    """sum_j x_.j w_jk g_j — paper eq 2, through the generator.

    Generated three-operand contraction (``weighted_matmul`` spec) with
    its own plan-DB/autotune keys; the hand-written ``kernels/fused_rnz``
    kernel remains as a verification baseline.  The backward dg spec is a
    genuine three-operand contraction (dg[j] = sum_ik g_out[i,k] A[i,j]
    B[j,k]) — a derived expression treated as a first-class mapping
    problem, per Linnea/LAMP.
    """
    out_dtype = out_dtype or x.dtype
    if differentiable and _weighted_kernel_ok(x, interpret):
        from ..grad import weighted_dense_vjp

        return weighted_dense_vjp(
            _dt_name(out_dtype), bool(interpret)
        )(x, w, g)
    return _weighted_dense_raw(x, w, g, out_dtype, interpret)


def _batched_dense_raw(x, w, out_dtype, interpret):
    if _batched_kernel_ok(x, w, interpret):
        from ..core.enumerate import batched_matmul_spec

        b, m, d = x.shape
        _, _, f = w.shape
        kern = _tuned_kernel(
            batched_matmul_spec(b, m, d, f), x.dtype, interpret=interpret
        )
        return kern(x, w).astype(out_dtype)
    return jnp.einsum(
        "bmd,bdf->bmf", x, w, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def batched_dense(x, w, out_dtype=None, interpret: bool = False,
                  differentiable: bool = True):
    """x: (B, M, D) @ w: (B, D, F) -> (B, M, F) through the generator."""
    out_dtype = out_dtype or x.dtype
    if differentiable and _batched_kernel_ok(x, w, interpret):
        from ..grad import batched_dense_vjp

        return batched_dense_vjp(_dt_name(out_dtype), bool(interpret))(x, w)
    return _batched_dense_raw(x, w, out_dtype, interpret)


def _chain_dense_raw(a, b, c, out_dtype, interpret):
    if _generic_kernel_ok(interpret):
        from ..core.enumerate import chain_matmul_spec

        m, k1 = a.shape
        _, k2 = b.shape
        _, n = c.shape
        kern = _tuned_kernel(
            chain_matmul_spec(m, k1, k2, n), a.dtype, interpret=interpret
        )
        return kern(a, b, c).astype(out_dtype)
    ab = jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jnp.dot(
        ab.astype(a.dtype), c, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def chain_dense(a, b, c, out_dtype=None, interpret: bool = False,
                differentiable: bool = True):
    """a @ b @ c without materializing the intermediate in HBM.

    The backward specs are three-operand contractions (e.g.
    ``chain_matmul.dB``: dB[j,k] = sum_il A[i,j] g[i,l] C[k,l]) — derived
    expressions treated as first-class mapping problems, per Linnea/LAMP.
    """
    out_dtype = out_dtype or a.dtype
    if differentiable and _generic_kernel_ok(interpret):
        from ..grad import chain_dense_vjp

        return chain_dense_vjp(_dt_name(out_dtype), bool(interpret))(a, b, c)
    return _chain_dense_raw(a, b, c, out_dtype, interpret)


def _dense_transposed_raw(a, b, out_dtype, interpret):
    if _generic_kernel_ok(interpret):
        from ..core.enumerate import transposed_matmul_spec

        d, m = a.shape
        _, f = b.shape
        kern = _tuned_kernel(
            transposed_matmul_spec(m, d, f), a.dtype, interpret=interpret
        )
        return kern(a, b).astype(out_dtype)
    return jnp.einsum(
        "dm,df->mf", a, b, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def dense_transposed(a, b, out_dtype=None, interpret: bool = False,
                     differentiable: bool = True):
    """a: (D, M) (stored transposed) , b: (D, F) -> (M, F) = a.T @ b."""
    out_dtype = out_dtype or a.dtype
    if differentiable and _generic_kernel_ok(interpret):
        from ..grad import dense_transposed_vjp

        return dense_transposed_vjp(
            _dt_name(out_dtype), bool(interpret)
        )(a, b)
    return _dense_transposed_raw(a, b, out_dtype, interpret)


def _dense_act_raw(x, w, beta, mean, var, *, act, eps, out_dtype, interpret):
    if _generic_kernel_ok(interpret):
        from .. import codegen
        from ..core.enumerate import matmul_spec

        m, d = x.shape
        _, f = w.shape
        epi = codegen.Epilogue(act=act, bias=True, norm=True, eps=eps)
        kern = _tuned_kernel(
            matmul_spec(m, d, f), x.dtype, epilogue=epi, interpret=interpret
        )
        return kern(x, w, bias=beta, mean=mean, var=var).astype(out_dtype)
    from ..kernels.fused_dense_act.ref import fused_dense_act_ref

    return fused_dense_act_ref(
        x, w, beta, mean, var, act=act, eps=eps
    ).astype(out_dtype)


def _attention_kernel_ok(q, interpret: bool) -> bool:
    return (_use_pallas() or interpret) and q.ndim == 3


def _attention_ref_jnp(q, k, v, *, causal, kv_lengths, out_dtype):
    """Pure-jnp fused-attention reference: f32 stable softmax + masks.

    Fully-masked rows (possible only under ``kv_lengths``) produce exact
    zeros, matching the generated kernel's ``l == 0`` guard.
    """
    import math

    h, s, d = q.shape
    t = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    sc = jnp.einsum(
        "hsd,htd->hst", q, k, preferred_element_type=jnp.float32
    ) * scale
    valid = jnp.ones((h, s, t), dtype=bool)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (h, s, t), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (h, s, t), 2)
        valid &= col <= row
    if kv_lengths is not None:
        col = jax.lax.broadcasted_iota(jnp.int32, (h, s, t), 2)
        valid &= col < kv_lengths.astype(jnp.int32).reshape(h, 1, 1)
    sc = jnp.where(valid, sc, -jnp.inf)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - jnp.where(jnp.isfinite(m), m, 0.0))
    p = jnp.where(valid, p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0.0, 1.0, denom)
    return jnp.einsum(
        "hst,hte->hse", p, v, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def _attention_raw(q, k, v, *, causal, kv_lengths, out_dtype, interpret):
    if _attention_kernel_ok(q, interpret):
        from ..core.enumerate import attention_spec

        h, s, d = q.shape
        t = k.shape[1]
        e = v.shape[2]
        kern = _tuned_kernel(
            attention_spec(h, s, t, d, e=e, causal=causal),
            q.dtype, interpret=interpret,
        )
        if kv_lengths is not None:
            return kern(q, k, v, kv_lengths=kv_lengths).astype(out_dtype)
        return kern(q, k, v).astype(out_dtype)
    return _attention_ref_jnp(
        q, k, v, causal=causal, kv_lengths=kv_lengths, out_dtype=out_dtype
    )


def attention(q, k, v, *, causal: bool = False, kv_lengths=None,
              out_dtype=None, interpret: bool = False,
              differentiable: bool = True):
    """Fused QK^T -> online-softmax -> PV through the searched kernel.

    q: (H, S, D), k: (H, T, D), v: (H, T, E) -> (H, S, E).  Scores are
    scaled by D^-0.5 and accumulated in f32; the KV axis runs as an
    in-schedule reduction tier carrying running max/sum in VMEM, so the
    (S, T) probability matrix never exists in HBM
    (``codegen.fused_gen``).  ``kv_lengths`` (per-head int32, PR 7's
    paged-KV convention) masks columns ``>= length``; rows with no valid
    column return exact zeros.

    Differentiable calls without lengths wrap in ``grad.attention_vjp``
    (flash-style recompute backward whose GEMMs are the hand-derived
    ``attention.dQ/.dK/.dV`` specs); ``kv_lengths`` + ``differentiable``
    routes to the natively-differentiable jnp reference instead.
    """
    out_dtype = out_dtype or q.dtype
    if q.ndim != 3 or k.ndim != 3 or v.ndim != 3:
        raise ValueError(
            f"attention expects 3-D (H, S|T, D|E) operands; got "
            f"{q.shape}, {k.shape}, {v.shape}"
        )
    if differentiable and kv_lengths is not None:
        return _attention_ref_jnp(
            q, k, v, causal=causal, kv_lengths=kv_lengths,
            out_dtype=out_dtype,
        )
    if differentiable and _attention_kernel_ok(q, interpret):
        from ..grad import attention_vjp

        return attention_vjp(
            bool(causal), _dt_name(out_dtype), bool(interpret)
        )(q, k, v)
    return _attention_raw(
        q, k, v, causal=causal, kv_lengths=kv_lengths,
        out_dtype=out_dtype, interpret=interpret,
    )


def _grouped_kernel_ok(x, interpret: bool) -> bool:
    return (_use_pallas() or interpret) and x.ndim == 2


def _grouped_ref_jnp(x, w, group_sizes, out_dtype):
    """Static per-group dot loop — the semantic definition of the op."""
    parts = []
    off = 0
    for g, size in enumerate(group_sizes):
        if size:
            parts.append(jax.lax.dot_general(
                x[off:off + size], w[g],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
        off += size
    if not parts:
        return jnp.zeros((x.shape[0], w.shape[-1]), out_dtype)
    return jnp.concatenate(parts, axis=0).astype(out_dtype)


def _grouped_raw(x, w, group_sizes, out_dtype, interpret):
    if x.shape[0] and _grouped_kernel_ok(x, interpret):
        from ..core.enumerate import grouped_matmul_spec

        kern = _tuned_kernel(
            grouped_matmul_spec(group_sizes, x.shape[1], w.shape[2]),
            x.dtype, interpret=interpret,
        )
        return kern(x, w).astype(out_dtype)
    return _grouped_ref_jnp(x, w, group_sizes, out_dtype)


def grouped_dense(x, w, group_sizes, *, out_dtype=None,
                  interpret: bool = False, differentiable: bool = True):
    """Ragged grouped GEMM: row block g of ``x`` hits expert matrix w[g].

    x: (N, K) with N = sum(group_sizes), w: (G, K, F) -> (N, F).  One
    searched kernel walks the static group offsets in its Pallas grid
    (``codegen.fused_gen``) instead of G separate dispatches — the MoE
    expert-FFN pattern (``models.moe``).  Empty and size-1 groups are
    legal; empty groups contribute no rows and cost no grid steps.

    The backward specs stay ragged (``grouped_matmul.dX/.dW`` are
    GroupedSpecs with the same sizes) — a plain einsum would wrongly sum
    over the group axis, so even the fallback VJP is a per-group loop.
    """
    out_dtype = out_dtype or x.dtype
    group_sizes = tuple(int(s) for s in group_sizes)
    if x.ndim != 2 or w.ndim != 3:
        raise ValueError(
            f"grouped_dense expects x (N, K) and w (G, K, F); got "
            f"{x.shape}, {w.shape}"
        )
    if len(group_sizes) != w.shape[0]:
        raise ValueError(
            f"{len(group_sizes)} group sizes for {w.shape[0]} expert slabs"
        )
    if sum(group_sizes) != x.shape[0]:
        raise ValueError(
            f"group sizes sum to {sum(group_sizes)} but x has "
            f"{x.shape[0]} rows"
        )
    if differentiable and x.shape[0] and _grouped_kernel_ok(x, interpret):
        from ..grad import grouped_vjp

        return grouped_vjp(
            group_sizes, _dt_name(out_dtype), bool(interpret)
        )(x, w)
    return _grouped_raw(x, w, group_sizes, out_dtype, interpret)


def dense_act(
    x, w, beta, mean, var,
    *, act: str = "gelu", eps: float = 1e-5,
    out_dtype=None, interpret: bool = False, differentiable: bool = True,
):
    """Generated dense + bias + normalization + activation (paper eqs 3-5).

    Subsumes ``kernels/fused_dense_act``: the epilogue runs on the f32
    accumulator tile before the store, so y and z never round-trip HBM.
    The custom backward (``grad.dense_act_vjp``) recomputes the accumulator
    with one extra GEMM, runs the elementwise epilogue VJP on it, and
    routes dacc through the derived dA/dB GEMM specs.
    """
    out_dtype = out_dtype or x.dtype
    if differentiable and _generic_kernel_ok(interpret):
        from ..grad import dense_act_vjp

        return dense_act_vjp(
            act, float(eps), _dt_name(out_dtype), bool(interpret)
        )(x, w, beta, mean, var)
    return _dense_act_raw(
        x, w, beta, mean, var,
        act=act, eps=eps, out_dtype=out_dtype, interpret=interpret,
    )
