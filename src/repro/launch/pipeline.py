"""GPipe-style pipeline parallelism over a mesh axis (the multi-pod mesh's
``pod`` axis, switchable from hierarchical DP per DESIGN.md §5).

``pipeline_apply`` runs a stage function over P = |axis| stages and M
microbatches inside shard_map: each of the M + P - 1 ticks every stage
applies its layer block to the activation it holds, then the ring
``ppermute`` shifts activations downstream — the classic bubble schedule
(bubble fraction (P-1)/(M+P-1)).  Stage s's parameters are the s-th slice of
the stacked parameter tree (sharded over the pipe axis, so each device
stores only its stage).

The schedule is the paper's subdiv/flip vocabulary one more time: the layer
stack is ``subdiv``-ed into P stages bound to a mesh axis, and the exchange
that makes it work is a rotation (ppermute) instead of a transposition.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(
    stage_fn: Callable,   # (stage_params, x) -> y   (same shape)
    stage_params,         # pytree, leaves lead with the LOCAL stage dim (=1)
    microbatches: jax.Array,  # (M, mb, ...) — replicated across the axis
    axis_name: str,
):
    """Run inside shard_map.  Returns (M, mb, ...) outputs (on every member,
    via a final psum-style broadcast)."""
    p = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    m = microbatches.shape[0]
    ticks = m + p - 1

    params_local = jax.tree.map(lambda w: w[0], stage_params)
    state = jnp.zeros_like(microbatches[0])
    outbuf = jnp.zeros_like(microbatches)

    def tick(t, carry):
        state, outbuf = carry
        # stage 0 ingests microbatch t (while available)
        mb_idx = jnp.clip(t, 0, m - 1)
        fresh = lax.dynamic_index_in_dim(
            microbatches, mb_idx, 0, keepdims=False
        )
        x = lax.select(
            jnp.logical_and(stage == 0, t < m),
            fresh.astype(state.dtype), state,
        )
        y = stage_fn(params_local, x)
        # last stage emits microbatch t - (p - 1)
        out_idx = jnp.clip(t - (p - 1), 0, m - 1)
        emit = jnp.logical_and(stage == p - 1, t >= p - 1)
        outbuf = lax.cond(
            emit,
            lambda ob: lax.dynamic_update_index_in_dim(
                ob, y.astype(ob.dtype), out_idx, 0
            ),
            lambda ob: ob,
            outbuf,
        )
        # shift downstream (ring; stage 0 receives garbage it overwrites)
        state = lax.ppermute(
            y, axis_name, perm=[(i, (i + 1) % p) for i in range(p)]
        )
        return state, outbuf

    _, outbuf = lax.fori_loop(0, ticks, tick, (state, outbuf))
    # broadcast the last stage's buffer to every member so out_specs can be
    # replicated: everyone else holds zeros
    outbuf = lax.psum(
        jnp.where(stage == p - 1, 1.0, 0.0).astype(outbuf.dtype) * outbuf,
        axis_name,
    )
    return outbuf


def bubble_fraction(p: int, m: int) -> float:
    return (p - 1) / (m + p - 1)
