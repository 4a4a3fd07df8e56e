"""Fused dense + normalization + nonlinearity kernel — paper eqs 3-5:

    y_k^b = sum_i W_ik x_i^b + beta_k          (dense)
    z_k   = (y_k^b - E_k) / sqrt(V_k + eps)    (normalization, given stats)
    r_k   = h(z_k)                             (elementwise nonlinearity)

The paper's NN motivating example: the last two stages are low arithmetic
density, so materializing y and z wastes HBM round-trips.  Fusion rules
(eq 19/27) fold them into the matmul epilogue: they run on the accumulator
tile while it is still resident in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.cost import VMEM_LIMIT_BYTES

_ACTIVATIONS = {
    "relu": lambda z: jnp.maximum(z, 0.0),
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "id": lambda z: z,
}


def _fused_dense_kernel(
    x_ref, w_ref, beta_ref, mean_ref, var_ref, o_ref, acc_ref,
    *, k_steps: int, act: str, eps: float,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        y = acc_ref[...] + beta_ref[...]
        z = (y - mean_ref[...]) * jax.lax.rsqrt(var_ref[...] + eps)
        o_ref[...] = _ACTIVATIONS[act](z).astype(o_ref.dtype)


def fused_dense_act_pallas(
    x: jax.Array,      # (B, I)
    w: jax.Array,      # (I, K)
    beta: jax.Array,   # (K,)
    mean: jax.Array,   # (K,)
    var: jax.Array,    # (K,)
    *,
    act: str = "gelu",
    eps: float = 1e-5,
    block_b: int,
    block_k: int,
    block_i: int,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    b, i = x.shape
    i2, k = w.shape
    assert i == i2 and beta.shape == mean.shape == var.shape == (k,)
    assert b % block_b == 0 and k % block_k == 0 and i % block_i == 0
    out_dtype = out_dtype or x.dtype
    k_steps = i // block_i
    row = lambda v: v.reshape(1, -1).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _fused_dense_kernel, k_steps=k_steps, act=act, eps=eps
        ),
        grid=(b // block_b, k // block_k, k_steps),
        in_specs=[
            pl.BlockSpec((block_b, block_i), lambda bi, ki, ii: (bi, ii)),
            pl.BlockSpec((block_i, block_k), lambda bi, ki, ii: (ii, ki)),
            pl.BlockSpec((1, block_k), lambda bi, ki, ii: (0, ki)),
            pl.BlockSpec((1, block_k), lambda bi, ki, ii: (0, ki)),
            pl.BlockSpec((1, block_k), lambda bi, ki, ii: (0, ki)),
        ],
        out_specs=pl.BlockSpec((block_b, block_k), lambda bi, ki, ii: (bi, ki)),
        out_shape=jax.ShapeDtypeStruct((b, k), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(x, w, row(beta), row(mean), row(var))
