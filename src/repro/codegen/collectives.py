"""Collective lowerings for mesh-tier schedules (promoted from launch.overlap).

A schedule that shards a *reduce* index over a mesh axis leaves every
device with a partial local output; ``bind_mesh`` finishes it with one of
two strategies, chosen per plan by the search (``search.space.COLLECTIVES``
— the finishing collective is part of the variant, cost-ranked like any
other rewrite choice):

  * ``"psum"`` — plain ``lax.psum``: one blocking all-reduce after the
    kernel; simplest, fully exposed on the interconnect.
  * ``"ring"`` — ``ring_psum``: an explicit ppermute ring (reduce-scatter
    then all-gather).  On TPU each hop's ICI transfer can overlap the
    neighbouring chunk's compute (Wang et al.-style), which is why the
    cost model (``roofline.analysis.sharded_reduce_seconds``) credits the
    reduce-scatter phase against compute; on CPU the two strategies are
    differentially tested equal.

``ring_gather_matmul`` / ``naive_gather_matmul`` — the ppermute-pipelined
TP gather-matmul pair — also live here now; ``launch.overlap`` re-exports
them for its existing callers.  This is the distribution-level analogue of
the paper's pipelined subdivision: the reduction over shards is an ``rnz``
whose blocks arrive one ``flip`` (ring rotation) at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: strategies ``bind_mesh(collective=...)`` accepts
STRATEGIES = ("psum", "ring")


def _ring_perm(p: int):
    return [(i, (i + 1) % p) for i in range(p)]


def ring_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce of ``x`` over ``axis_name`` as an explicit ppermute ring.

    Equivalent to ``lax.psum(x, axis_name)``: a ring reduce-scatter
    (``p - 1`` hops, each accumulating one payload chunk) followed by a
    ring all-gather (``p`` hops).  The payload is flattened and split into
    ``p`` chunks; a payload that does not divide evenly is zero-padded so
    the last chunk is a remainder shard (exercised by the differential
    tests alongside the even fast path).  ``p == 1`` is the cut path: no
    ring to run, the partial *is* the sum.
    """
    p = lax.axis_size(axis_name)
    if p == 1:
        return x  # cut path: a single shard needs no collective
    idx = lax.axis_index(axis_name)
    flat = x.reshape(-1)
    n = flat.shape[0]
    chunk = -(-n // p)  # ceil division; pad covers the remainder shard
    pad = chunk * p - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(p, chunk)
    perm = _ring_perm(p)

    # reduce-scatter: after p-1 hops device d holds the FULL sum of chunk
    # (d + 1) % p.  Each hop sends the running partial to the neighbour,
    # which folds in its own local copy of that chunk.
    def rs_step(carry, s):
        recv = lax.ppermute(carry, axis_name, perm=perm)
        j = (idx - s - 1) % p
        own = lax.dynamic_index_in_dim(chunks, j, axis=0, keepdims=False)
        return recv + own, None

    init = lax.dynamic_index_in_dim(chunks, idx % p, axis=0, keepdims=False)
    full_chunk, _ = lax.scan(rs_step, init, jnp.arange(p - 1))

    # all-gather: rotate the completed chunks around the ring, recording
    # (owner, value) pairs, then scatter them back into payload order —
    # the same idiom as ring_gather_matmul below.
    def ag_step(carry, _):
        val, j = carry
        nxt = lax.ppermute(val, axis_name, perm=perm)
        return (nxt, (j - 1) % p), (j, val)

    (_, _), (js, vals) = lax.scan(
        ag_step, (full_chunk, (idx + 1) % p), None, length=p
    )
    order = jnp.argsort(js)
    summed = jnp.take(vals, order, axis=0).reshape(p * chunk)[:n]
    return summed.reshape(x.shape)


def all_reduce(x: jax.Array, axis_names, collective: str = "psum") -> jax.Array:
    """Finish a sharded reduction over ``axis_names`` with ``collective``."""
    if collective not in STRATEGIES:
        raise ValueError(
            f"unknown collective {collective!r}; choose from {STRATEGIES}"
        )
    if not axis_names:
        return x
    if collective == "ring":
        for ax in axis_names:
            x = ring_psum(x, ax)
        return x
    return lax.psum(x, tuple(axis_names))


def ring_gather_matmul(x_shard: jax.Array, w: jax.Array, axis_name: str):
    """Inside shard_map: x_shard (m_loc, k), w (k, n) -> y rows for ALL
    shards, (P * m_loc, n), equal to all_gather(x) @ w.

    The explicit ring exposes the overlap to the scheduler; the naive form
    must finish the all-gather before the first flop.
    """
    p = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)

    def step(carry, _):
        x_cur, src = carry
        y_part = jnp.dot(x_cur, w, preferred_element_type=jnp.float32)
        x_nxt = lax.ppermute(x_cur, axis_name, perm=_ring_perm(p))
        src_nxt = (src - 1) % p
        return (x_nxt, src_nxt), (src, y_part)

    (_, _), (srcs, parts) = lax.scan(step, (x_shard, idx), None, length=p)
    # parts[i] are the rows originating from shard srcs[i]; scatter to order
    order = jnp.argsort(srcs)
    parts = jnp.take(parts, order, axis=0)  # (P, m_loc, n)
    m_loc, n = x_shard.shape[0], w.shape[1]
    return parts.reshape(p * m_loc, n).astype(x_shard.dtype)


def naive_gather_matmul(x_shard: jax.Array, w: jax.Array, axis_name: str):
    """Reference: blocking all-gather then one big dot."""
    x_full = lax.all_gather(x_shard, axis_name, axis=0, tiled=True)
    return jnp.dot(
        x_full, w, preferred_element_type=jnp.float32
    ).astype(x_shard.dtype)
