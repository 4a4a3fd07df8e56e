"""Decoder-only LM covering the dense and MoE families.

Layers are grouped into homogeneous *segments* so the layer stack runs under
``lax.scan`` (small HLO, fast multi-pod compiles even at 88 layers):

  dense arch            ->  [ (('dense',), L) ]
  kimi-style MoE        ->  [ (('dense',), first_dense), (('moe',), L-fd) ]
  llama4-style MoE      ->  [ (('dense','moe'), L//2) ]   (interleaved)

Each segment's parameters are stacked along a leading ``layers`` axis; decode
caches are stacked the same way and scanned jointly with the params.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from . import layers as L
from .moe import moe_apply, moe_init


# --------------------------------------------------------------------------
# segment plan
# --------------------------------------------------------------------------


def segment_plan(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    if cfg.family not in ("moe",):
        return [(("dense",), cfg.n_layers)]
    m = cfg.moe
    plan: List[Tuple[Tuple[str, ...], int]] = []
    rest = cfg.n_layers
    if m.first_dense:
        plan.append((("dense",), m.first_dense))
        rest -= m.first_dense
    if m.moe_every == 1:
        plan.append((("moe",), rest))
    elif m.moe_every == 2:
        assert rest % 2 == 0
        plan.append((("dense", "moe"), rest // 2))
    else:
        raise NotImplementedError(f"moe_every={m.moe_every}")
    return plan


def _layer_init(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 2)
    d_ff = cfg.d_ff
    if kind == "dense" and cfg.moe is not None and cfg.moe.dense_ff:
        d_ff = cfg.moe.dense_ff
    p = {
        "attn_norm": L.rmsnorm_init(cfg),
        "attn": L.attention_init(ks[0], cfg),
        "mlp_norm": L.rmsnorm_init(cfg),
    }
    if kind == "moe":
        p["moe"] = moe_init(ks[1], cfg)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg, d_ff=d_ff)
    return p


def _stack(trees):
    return L.stack_annotated(trees)


def init(cfg: ModelConfig, key) -> Tuple[Dict, Dict]:
    """Returns (params, logical_axes)."""
    keys = jax.random.split(key, cfg.n_layers + 1)
    tree: Dict = {"embedding": L.embedding_init(keys[0], cfg),
                  "final_norm": L.rmsnorm_init(cfg)}
    li = 0
    for si, (pattern, count) in enumerate(segment_plan(cfg)):
        reps = []
        for _ in range(count):
            rep = {}
            for kind in pattern:
                rep[kind] = _layer_init(keys[1 + li], cfg, kind)
                li += 1
            reps.append(rep)
        tree[f"seg{si}"] = _stack(reps)
    params, axes = L.split_params(tree)
    # prepend the stacked-layers axis to every segment leaf's logical axes
    for si in range(len(segment_plan(cfg))):
        axes[f"seg{si}"] = jax.tree.map(
            lambda a: ("layers",) + tuple(a) if isinstance(a, tuple) else a,
            axes[f"seg{si}"],
            is_leaf=lambda a: isinstance(a, tuple) or a is None,
        )
    return params, axes


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _block(
    lp, cfg: ModelConfig, kind: str, x, *, positions, cache=None,
    q_block=512, k_block=512, lengths=None,
):
    h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
    y, new_cache = L.attention_apply(
        lp["attn"], cfg, h,
        positions=positions, cache=cache,
        q_block=q_block, k_block=k_block, lengths=lengths,
    )
    x = x + y
    h = L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps)
    if kind == "moe":
        x = x + moe_apply(lp["moe"], cfg, h)
    else:
        x = x + L.mlp_apply(lp["mlp"], cfg, h)
    return x, new_cache


def _run_segments(
    params, cfg: ModelConfig, x, *, positions, caches=None,
    q_block=512, k_block=512, lengths=None,
):
    """caches: same segment structure, stacked; returns (x, new_caches).

    A paged segment cache (its leaves the stacked pools, see
    ``paged_decode_step``) stays out of the scan's ``xs``: each layer gets
    its index and reads its own pages from the pools."""
    new_caches: Dict = {}
    for si, (pattern, count) in enumerate(segment_plan(cfg)):
        seg = params[f"seg{si}"]
        seg_cache = None if caches is None else caches[f"seg{si}"]
        paged = seg_cache is not None and "block_table" in next(
            iter(seg_cache.values()))

        def step(carry, xs, pattern=pattern, paged=paged, pools=seg_cache):
            h = carry
            lp, lc = xs
            ncs = {}
            for kind in pattern:
                if paged:
                    c = dict(pools[kind], layer=lc)
                else:
                    c = None if lc is None else lc[kind]
                h, nc = _block(
                    lp[kind], cfg, kind, h,
                    positions=positions, cache=c,
                    q_block=q_block, k_block=k_block, lengths=lengths,
                )
                if nc is not None:
                    ncs[kind] = nc
            return h, (ncs if ncs else None)

        if cfg.remat and caches is None:
            step = L.remat(step)
        xs = (seg, jnp.arange(count) if paged else seg_cache)
        x, seg_new_cache = lax.scan(step, x, xs)
        new_caches[f"seg{si}"] = seg_new_cache
    return x, (new_caches if caches is not None else None)


def forward(params, cfg: ModelConfig, tokens, *, q_block=512, k_block=512):
    """Training/prefill forward without cache: tokens (B, S) -> logits."""
    x = L.embed(params["embedding"], tokens).astype(cfg.param_dtype)
    positions = jnp.arange(tokens.shape[1])[None, :].astype(jnp.int32)
    x, _ = _run_segments(
        params, cfg, x, positions=positions,
        q_block=q_block, k_block=k_block,
    )
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.logits(params["embedding"], cfg, x)


def loss_fn(params, cfg: ModelConfig, tokens, labels, **kw):
    lg = forward(params, cfg, tokens, **kw)
    return L.cross_entropy(lg, labels)


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------


def cache_init(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    def layer_cache():
        return L.attention_cache_init(cfg, batch, max_len)

    caches: Dict = {}
    for si, (pattern, count) in enumerate(segment_plan(cfg)):
        reps = []
        for _ in range(count):
            reps.append({kind: layer_cache() for kind in pattern})
        caches[f"seg{si}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
    return caches


def cache_axes(cfg: ModelConfig) -> Dict:
    """Logical axes tree matching cache_init's structure."""
    def one():
        return {k: ("layers",) + tuple(v) for k, v in L.CACHE_AXES.items()}

    axes: Dict = {}
    for si, (pattern, _) in enumerate(segment_plan(cfg)):
        axes[f"seg{si}"] = {kind: one() for kind in pattern}
    return axes


def decode_step(params, cfg: ModelConfig, caches, tokens):
    """One-token decode: tokens (B, 1); caches hold the context."""
    x = L.embed(params["embedding"], tokens).astype(cfg.param_dtype)
    # current position per sequence = cache length (same for every layer)
    pos = _first_cache_len(caches)
    positions = pos[:, None]
    x, new_caches = _run_segments(
        params, cfg, x, positions=positions, caches=caches
    )
    # serving needs only the next-token distribution: unembed the last
    # position (a full 32k x 152k-vocab prefill logit tensor would dwarf
    # the KV cache itself)
    x = L.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    return L.logits(params["embedding"], cfg, x), new_caches


def paged_decode_step(params, cfg: ModelConfig, pools, block_table, lens,
                      tokens):
    """One-token decode straight from paged KV pools.

    pools mirror ``cache_init``'s structure with {"k", "v"} leaves of
    (layers, n_pages, page, kv, hd); block_table (B, max_pages) page ids;
    lens (B,) KV rows present per lane, the position of ``tokens`` (B, 1).
    Each layer reads its own pages, writes its new row into that
    transient copy and attends over ``lens + 1`` rows.  Returns (logits,
    rows): rows mirror pools with (layers, B, kv, hd) leaves, each lane's
    new K/V row for the caller to write back at position ``lens``.
    """
    x = L.embed(params["embedding"], tokens).astype(cfg.param_dtype)
    caches = {
        seg: {kind: dict(pv, block_table=block_table, len=lens)
              for kind, pv in kinds.items()}
        for seg, kinds in pools.items()
    }
    x, rows = _run_segments(
        params, cfg, x, positions=lens[:, None], caches=caches
    )
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.logits(params["embedding"], cfg, x), rows


def _first_cache_len(caches) -> jax.Array:
    for seg in caches.values():
        def find(t):
            if isinstance(t, dict):
                if "len" in t:
                    return t["len"]
                for v in t.values():
                    r = find(v)
                    if r is not None:
                        return r
            return None
        r = find(seg)
        if r is not None:
            return r[0]  # strip the stacked-layers axis
    raise ValueError("no attention cache found")


def prefill(params, cfg: ModelConfig, tokens, max_len: int, lengths=None):
    """Prefill: forward over the prompt, building the KV caches.

    ``lengths`` (B,) declares right-padded prompts: positions past each
    row's true length are excluded from attention, the caches start at
    the true lengths, and the returned logits come from each row's last
    *real* position — so a short prompt batched with longer ones decodes
    identically to running it solo.
    """
    B, S = tokens.shape
    caches = cache_init(cfg, B, max_len)
    x = L.embed(params["embedding"], tokens).astype(cfg.param_dtype)
    positions = jnp.arange(S)[None, :].astype(jnp.int32)
    x, new_caches = _run_segments(
        params, cfg, x, positions=positions, caches=caches, lengths=lengths
    )
    # serving needs only the next-token distribution: unembed the last
    # position (a full 32k x 152k-vocab prefill logit tensor would dwarf
    # the KV cache itself)
    if lengths is None:
        x_last = x[:, -1:]
    else:
        idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, S - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    x = L.rmsnorm(params["final_norm"], x_last, cfg.norm_eps)
    return L.logits(params["embedding"], cfg, x), new_caches
