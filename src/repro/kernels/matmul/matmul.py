"""Blocked matmul Pallas kernel — the TPU realization of the paper's §4 winner.

The paper's best matmul variant subdivides the reduction (``rnz``) and nests
``mapA / rnz / mapB / rnz``: stream blocks of the reduction dimension while
holding an output tile resident.  On TPU this is exactly a 3-D-grid Pallas
kernel with a revisited output block and a float32 VMEM accumulator:

  grid = (M/bm, N/bn, K/bk)       # mapA-blocks x mapB-blocks x rnz-blocks
  A block (bm, bk), B block (bk, bn) stream HBM -> VMEM per grid step
  acc (bm, bn) f32 lives in VMEM across the k-steps (the rnz accumulator)

Block shapes come from ``core.autotune.choose_matmul_blocks`` (the paper's
subdiv factors chosen by the cost model) and must be MXU-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.cost import VMEM_LIMIT_BYTES


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int,
    block_n: int,
    block_k: int,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with explicit VMEM tiling.

    A: (M, K), B: (K, N); block sizes must divide the operand extents.
    """
    m, ka = a.shape
    kb, n = b.shape
    assert ka == kb, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and ka % block_k == 0, (
        (m, n, ka),
        (block_m, block_n, block_k),
    )
    out_dtype = out_dtype or a.dtype
    k_steps = ka // block_k
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(m // block_m, n // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(a, b)
