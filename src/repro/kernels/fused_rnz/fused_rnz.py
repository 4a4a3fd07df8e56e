"""Fused weighted contraction kernel — paper eq 2 / eq 6:

    C_ik = sum_j A_ij * B_jk * g_j

The paper's motivating point: BLAS-style libraries force ``A' = A .* g`` (a
temporary the size of A) before the GEMM.  The rnz-nzip fusion rule (eq 27)
folds the scaling into the reduction zipper; in the kernel that means the
``g`` chunk rides along the k-grid dimension and scales the A block in VMEM —
zero extra HBM traffic beyond g itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.cost import VMEM_LIMIT_BYTES


def _fused_rnz_kernel(a_ref, b_ref, g_ref, o_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_scaled = a_ref[...] * g_ref[...]  # (bm, bk) * (1, bk): the fused zipper
    acc_ref[...] += jnp.dot(
        a_scaled, b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def weighted_matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    g: jax.Array,
    *,
    block_m: int,
    block_n: int,
    block_k: int,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    m, ka = a.shape
    kb, n = b.shape
    assert ka == kb and g.shape == (ka,)
    assert m % block_m == 0 and n % block_n == 0 and ka % block_k == 0
    out_dtype = out_dtype or a.dtype
    k_steps = ka // block_k
    g2 = g.reshape(1, ka)
    return pl.pallas_call(
        functools.partial(_fused_rnz_kernel, k_steps=k_steps),
        grid=(m // block_m, n // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_k), lambda i, j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(a, b, g2)
