"""Shared model layers: norms, rotary, GQA attention (blockwise/flash-style
training path + cached decode path), MLPs, embeddings.

Parameter trees are nested dicts; every init function returns
``(params, axes)`` where ``axes`` mirrors the structure with tuples of
*logical axis names* consumed by ``launch.sharding`` (the distributed-level
realization of the paper's subdiv: a mesh axis is just the outermost
subdivision of that logical dimension).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from .. import ops

F32 = jnp.float32
NEG_INF = -1e30


def remat(fn):
    """Activation-checkpoint a scan body under the active remat policy.

    $REPRO_REMAT_POLICY: 'nothing' (default — recompute everything, incl.
    re-gathering FSDP weights in backward), 'dots' (save matmul outputs —
    trades HBM for skipping the backward re-gather), 'dots_no_batch'.
    A §Perf knob; see EXPERIMENTS.md.
    """
    import os

    pol = os.environ.get("REPRO_REMAT_POLICY", "nothing")
    if pol == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots
        )
    if pol == "dots_no_batch":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )
    return jax.checkpoint(fn)


class PA:
    """A (param value, logical axes) pair.

    Deliberately NOT a pytree: ``jax.tree.map`` treats it as a leaf, so
    building/stacking annotated parameter trees never descends into the axis
    metadata.  ``split_params`` separates the twins at the end of init.
    """

    __slots__ = ("value", "axes")

    def __init__(self, value, axes):
        self.value, self.axes = value, axes


def split_params(tree):
    """Split a PA-leaf tree into (params, axes) twins."""
    if isinstance(tree, dict):
        p, a = {}, {}
        for k, v in tree.items():
            p[k], a[k] = split_params(v)
        return p, a
    return tree.value, tree.axes


def stack_annotated(trees):
    """Stack a list of PA-leaf trees along a new leading axis."""
    return jax.tree.map(
        lambda *xs: PA(jnp.stack([x.value for x in xs]), xs[0].axes),
        *trees,
        is_leaf=lambda x: isinstance(x, PA),
    )


def _init(key, shape, axes, dtype, scale: Optional[float] = None):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[0])
    w = jax.random.normal(key, shape, dtype=F32) * scale
    return PA(w.astype(dtype), axes)


def _zeros(shape, axes, dtype):
    return PA(jnp.zeros(shape, dtype=dtype), axes)


def _ones(shape, axes, dtype):
    return PA(jnp.ones(shape, dtype=dtype), axes)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(cfg: ModelConfig, dim=None):
    dim = dim or cfg.d_model
    return {"scale": _ones((dim,), ("embed",), F32)}


def rmsnorm(params, x, eps: float):
    h = x.astype(F32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    out = h * lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


def layernorm_init(cfg: ModelConfig, dim=None):
    dim = dim or cfg.d_model
    return {
        "scale": _ones((dim,), ("embed",), F32),
        "bias": _zeros((dim,), ("embed",), F32),
    }


def layernorm(params, x, eps: float):
    h = x.astype(F32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.var(h, axis=-1, keepdims=True)
    out = (h - mu) * lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, N, hd), positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=F32) / half)
    )
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(F32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate(
        (x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention_init(key, cfg: ModelConfig):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.param_dtype
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init(ks[0], (d, h * hd), ("embed", "heads"), dt),
        "wk": _init(ks[1], (d, kv * hd), ("embed", "kv"), dt),
        "wv": _init(ks[2], (d, kv * hd), ("embed", "kv"), dt),
        "wo": _init(ks[3], (h * hd, d), ("heads", "embed"), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = _zeros((h * hd,), ("heads",), dt)
        p["bk"] = _zeros((kv * hd,), ("kv",), dt)
        p["bv"] = _zeros((kv * hd,), ("kv",), dt)
    if cfg.qk_norm:
        p["q_norm"] = _ones((hd,), (None,), F32)
        p["k_norm"] = _ones((hd,), (None,), F32)
    return p


def _qk_norm(x, scale, eps):
    h = x.astype(F32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    return (h * lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _project_qkv(params, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = ops.dense(x.reshape(B * S, -1), params["wq"]).reshape(B, S, h, hd)
    k = ops.dense(x.reshape(B * S, -1), params["wk"]).reshape(B, S, kv, hd)
    v = ops.dense(x.reshape(B * S, -1), params["wv"]).reshape(B, S, kv, hd)
    if cfg.qkv_bias:
        q = q + params["bq"].reshape(h, hd)
        k = k + params["bk"].reshape(kv, hd)
        v = v + params["bv"].reshape(kv, hd)
    if cfg.qk_norm:
        q = _qk_norm(q, params["q_norm"], cfg.norm_eps)
        k = _qk_norm(k, params["k_norm"], cfg.norm_eps)
    if cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def blockwise_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, T, KV, hd)
    v: jax.Array,  # (B, T, KV, hd)
    *,
    causal: bool = True,
    q_block: int = 512,
    k_block: int = 512,
    kv_lengths: Optional[jax.Array] = None,  # (B,) valid key counts
) -> jax.Array:
    """Flash-style online-softmax attention: O(S*block) memory, pure JAX.

    This is the rnz-subdivision of the softmax reduction: the key/value
    sequence is ``subdiv``-ed into blocks and the reduction regrouped over
    them (the paper's eq 44' with an online-rescaled monoid).

    ``kv_lengths`` masks out keys at positions >= the per-sequence length
    — the attention half of variable-length (right-padded) prefill.  With
    causal masking and right padding no *real* query row can reach a pad
    key anyway (pads sit after every real position), so real rows are
    bitwise identical with or without it; the mask guarantees pad rows
    cannot leak even on non-causal uses.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    # snap block sizes to divisors of the sequence lengths
    q_block = math.gcd(S, min(q_block, S))
    k_block = math.gcd(T, min(k_block, T))
    nq, nk = S // q_block, T // k_block
    scale = hd ** -0.5

    if nq == 1 and nk == 1 and kv_lengths is None:
        # Single-block path: one unblocked softmax-attention, emitted as
        # the exact primitive chain ``capture.harvest`` recognizes as the
        # fused-attention motif (fold heads -> QK^T -> scale -> [iota
        # causal mask] -> max-shift -> exp -> PV -> div by rowsum), so
        # ``capture.optimize`` can dispatch it through ``ops.attention``.
        # Numerically identical to the blockwise path at nq == nk == 1
        # (same f32 accumulation, no rescale steps).
        qh = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        kx = k if G == 1 else jnp.repeat(k, G, axis=2)
        vx = v if G == 1 else jnp.repeat(v, G, axis=2)
        kh = kx.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
        vh = vx.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
        s = jnp.einsum(
            "hsd,htd->hst", qh.astype(F32), kh.astype(F32)
        ) * scale
        if causal:
            row = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            col = lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(col <= row, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        num = jnp.einsum("hst,hte->hse", p, vh.astype(F32))
        out = num / jnp.sum(p, axis=-1, keepdims=True)
        out = out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
        return out.astype(q.dtype)

    qs = q.reshape(B, nq, q_block, KV, G, hd)
    ks = k.reshape(B, nk, k_block, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, k_block, KV, hd).transpose(1, 0, 2, 3, 4)

    import os

    causal_skip = causal and os.environ.get("REPRO_CAUSAL_SKIP") == "1"

    def per_q_chunk(qi, qc):  # qc: (B, qb, KV, G, hd)
        q_pos = qi * q_block + jnp.arange(q_block)

        def k_body(ki, kc, vc, carry):
            m, l, acc = carry
            s = jnp.einsum(
                "bqkgh,bpkh->bkgqp", qc.astype(F32), kc.astype(F32)
            ) * scale
            k_pos = ki * k_block + jnp.arange(k_block)
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            if kv_lengths is not None:
                valid = k_pos[None, :] < kv_lengths[:, None]  # (B, kb)
                s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqp,bpkh->bkgqh", p, vc.astype(F32)
            )
            return (m_new, l_new, acc_new)

        init = (
            jnp.full((B, KV, G, q_block), NEG_INF, F32),
            jnp.zeros((B, KV, G, q_block), F32),
            jnp.zeros((B, KV, G, q_block, hd), F32),
        )
        if causal_skip:
            # §Perf knob: dynamic loop bound skips fully-masked key blocks —
            # the rnz over key blocks only runs up to the causal frontier
            # (~2x fewer attention flops/bytes at long sequence)
            k_hi = (qi * q_block + q_block + k_block - 1) // k_block

            def fori_body(ki, carry):
                kc = lax.dynamic_index_in_dim(ks, ki, 0, keepdims=False)
                vc = lax.dynamic_index_in_dim(vs, ki, 0, keepdims=False)
                return k_body(ki, kc, vc, carry)

            m, l, acc = lax.fori_loop(0, k_hi, fori_body, init)
        else:
            def k_step(carry, inp):
                ki, kc, vc = inp
                return k_body(ki, kc, vc, carry), None

            (m, l, acc), _ = lax.scan(
                k_step, init, (jnp.arange(nk), ks, vs)
            )
        out = acc / l[..., None]
        return out  # (B, KV, G, qb, hd)

    outs = jax.vmap(per_q_chunk, in_axes=(0, 1), out_axes=1)(
        jnp.arange(nq), qs
    )  # (B, nq, KV, G, qb, hd)
    out = outs.transpose(0, 1, 4, 2, 3, 5).reshape(B, S, H, hd)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,       # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, T, KV, hd)
    v_cache: jax.Array,
    cache_len: jax.Array,  # (B,) valid lengths (including current token)
) -> jax.Array:
    B, _, H, hd = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum(
        "bkgh,btkh->bkgt", qg.astype(F32), k_cache.astype(F32)
    ) * scale
    valid = jnp.arange(T)[None, :] < cache_len[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", p, v_cache.astype(F32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def read_pages(pool: jax.Array, layer, block_table: jax.Array) -> jax.Array:
    """One layer's KV pages in block-table order, as a dense cache.

    pool (layers, n_pages, page, kv, hd); block_table (B, max_pages) page
    ids.  One gather indexed by (layer, page) gives a fresh
    (B, max_pages * page, kv, hd) buffer: logical position ``p`` of lane
    ``b`` is page ``block_table[b, p // page]``, offset ``p % page``.
    """
    g = pool[layer, block_table]  # (B, max_pages, page, kv, hd)
    B, max_pages, page = g.shape[:3]
    return g.reshape(B, max_pages * page, *g.shape[3:])


def attention_apply(
    params,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    positions: jax.Array,
    causal: bool = True,
    cache: Optional[Dict] = None,
    q_block: int = 512,
    k_block: int = 512,
    lengths: Optional[jax.Array] = None,
):
    """Returns (y, new_cache).  cache = {k, v, len} for decode.

    A paged cache {k, v, layer, block_table, len} (k and v the stacked
    pools, see :func:`read_pages`) decodes one token straight from the
    pages: ``new_cache`` is then just the new {k, v} row per lane, which
    the caller writes back to its page.

    ``lengths`` (B,) marks right-padded prefill: keys past each
    sequence's true length are masked out of the attention and the cache
    ``len`` starts at the true length (not the padded S), so decode
    writes its first token over the first pad slot and never attends pad
    KV — the fix for the mixed-length batching leak.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    if cache is None:
        y = blockwise_attention(
            q, k, v, causal=causal, q_block=q_block, k_block=k_block,
            kv_lengths=lengths,
        )
        new_cache = None
    elif "block_table" in cache:
        idx = cache["len"]  # (B,) rows present = this token's position
        bidx = jnp.arange(B)
        k_row = k[:, 0].astype(cache["k"].dtype)
        v_row = v[:, 0].astype(cache["v"].dtype)
        # the layer's own view of its pages, with the new row in place
        with jax.named_scope("paged_view"):
            k_cache = read_pages(cache["k"], cache["layer"],
                                 cache["block_table"]).at[bidx, idx].set(k_row)
            v_cache = read_pages(cache["v"], cache["layer"],
                                 cache["block_table"]).at[bidx, idx].set(v_row)
        with jax.named_scope("decode_attention"):
            y = decode_attention(q, k_cache, v_cache, idx + 1)
        new_cache = {"k": k_row, "v": v_row}
    elif S == 1:
        idx = cache["len"]  # (B,) current write positions
        bidx = jnp.arange(B)
        k_cache = cache["k"].at[bidx, idx].set(k[:, 0])
        v_cache = cache["v"].at[bidx, idx].set(v[:, 0])
        with jax.named_scope("decode_attention"):
            y = decode_attention(q, k_cache, v_cache, idx + 1)
        new_cache = {"k": k_cache, "v": v_cache, "len": idx + 1}
    else:
        # prefill into an empty cache
        T = cache["k"].shape[1]
        k_cache = lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)
        )
        v_cache = lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)
        )
        y = blockwise_attention(
            q, k, v, causal=causal, q_block=q_block, k_block=k_block,
            kv_lengths=lengths,
        )
        new_cache = {
            "k": k_cache, "v": v_cache,
            "len": (jnp.full((B,), S, jnp.int32) if lengths is None
                    else lengths.astype(jnp.int32)),
        }
    y = ops.dense(y.reshape(B * S, -1), params["wo"]).reshape(B, S, -1)
    return y, new_cache


def attention_cache_init(
    cfg: ModelConfig, batch: int, max_len: int, dtype=None
):
    dtype = dtype or cfg.param_dtype
    kv, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, max_len, kv, hd), dtype),
        "v": jnp.zeros((batch, max_len, kv, hd), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


#: logical axes of the attention cache (for sharding long-context decode)
CACHE_AXES = {"k": ("batch", "seq_kv", "kv", None),
              "v": ("batch", "seq_kv", "kv", None),
              "len": ("batch",)}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.param_dtype
    ks = jax.random.split(key, 3)
    if cfg.act == "silu":  # SwiGLU
        return {
            "w_gate": _init(ks[0], (d, f), ("embed", "mlp"), dt),
            "w_up": _init(ks[1], (d, f), ("embed", "mlp"), dt),
            "w_down": _init(ks[2], (f, d), ("mlp", "embed"), dt),
        }
    return {  # plain 2-layer (whisper-style gelu)
        "w1": _init(ks[0], (d, f), ("embed", "mlp"), dt),
        "b1": _zeros((f,), ("mlp",), dt),
        "w2": _init(ks[1], (f, d), ("mlp", "embed"), dt),
        "b2": _zeros((d,), ("embed",), dt),
    }


def mlp_apply(params, cfg: ModelConfig, x):
    B, S, D = x.shape
    h = x.reshape(B * S, D)
    if cfg.act == "silu":
        g = ops.dense(h, params["w_gate"])
        u = ops.dense(h, params["w_up"])
        out = ops.dense(jax.nn.silu(g.astype(F32)).astype(x.dtype) * u,
                        params["w_down"])
    else:
        h1 = jax.nn.gelu(
            (ops.dense(h, params["w1"]) + params["b1"]).astype(F32)
        ).astype(x.dtype)
        out = ops.dense(h1, params["w2"]) + params["b2"]
    return out.reshape(B, S, D)


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------


def embedding_init(key, cfg: ModelConfig):
    dt = cfg.param_dtype
    ks = jax.random.split(key, 2)
    p = {"tok": _init(ks[0], (cfg.vocab, cfg.d_model), ("vocab", "embed"),
                      dt, scale=0.02)}
    if not cfg.tie_embeddings:
        p["unembed"] = _init(
            ks[1], (cfg.d_model, cfg.vocab), ("embed", "vocab"), dt
        )
    return p


def embed(params, tokens):
    return params["tok"][tokens]


def logits(params, cfg: ModelConfig, x):
    B, S, D = x.shape
    w = (
        params["tok"].T if cfg.tie_embeddings else params["unembed"]
    )
    return jnp.dot(
        x.reshape(B * S, D), w, preferred_element_type=F32
    ).reshape(B, S, -1)


def cross_entropy(logits_: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token NLL, numerically stable, f32.

    The gold logit is extracted with a fused one-hot multiply-reduce rather
    than take_along_axis: under a vocab-sharded unembed (TP) this keeps the
    reduction local per shard + one small all-reduce, instead of gathering
    the full (tokens, vocab) logits to pick one column.
    """
    logits_ = logits_.astype(F32)
    lse = jax.scipy.special.logsumexp(logits_, axis=-1)
    onehot = jax.nn.one_hot(labels, logits_.shape[-1], dtype=F32)
    gold = jnp.sum(logits_ * onehot, axis=-1)
    return jnp.mean(lse - gold)
