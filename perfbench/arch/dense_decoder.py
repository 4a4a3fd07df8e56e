"""Plain float32 reference of a dense decoder-only transformer.

Covers the Llama family (deepseek-7b: multi-head attention) and Qwen3
(qwen3-8b: grouped-query attention with an RMSNorm over each query and key
head).  Everything here is the benchmark's own and imports nothing of the
program under test:

* :func:`make_weights` draws every weight from the seed's key in one jitted
  call, in bfloat16 as served.  :func:`layer_weights` and
  :func:`embed_weights` draw the same values again, one layer at a time,
  for the reference.
* :func:`reference_gaps` runs the reference layer by layer over prompts
  plus the tokens the program served, and reports by how much each served
  token's logit lies below the reference's best.  With ``control=True`` it
  also runs the same reference with every matmul operand rounded to
  float8 e4m3, and reports the gap of the token that precision puts first.
* the functions at the end count work
  from shapes.

The layer equations, after the HF modeling code of ``LlamaForCausalLM`` and
``Qwen3ForCausalLM``::

    h = x + Wo . attn(rope(qn(Wq . rms(x))), rope(kn(Wk . rms(x))), Wv . rms(x))
    y = h + Wd . (silu(Wg . rms(h)) * (Wu . rms(h)))

with ``rms`` an RMSNorm with a learned scale, ``qn``/``kn`` the per-head
RMSNorms of Qwen3 (identity for Llama), causal softmax attention scaled by
``head_dim ** -0.5``, and query head ``j`` reading key/value head
``j // (heads / kv_heads)``.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST

NORMS = ("attn_norm", "mlp_norm", "q_norm", "k_norm")


class Dims:
    """The sizes of one configuration file, under short names."""

    def __init__(self, conf: Dict):
        self.d = int(conf["hidden_size"])
        self.ff = int(conf["intermediate_size"])
        self.heads = int(conf["num_attention_heads"])
        self.kv = int(conf["num_key_value_heads"])
        self.hd = int(conf.get("head_dim") or self.d // self.heads)
        self.layers = int(conf["num_hidden_layers"])
        self.vocab = int(conf["vocab_size"])
        self.eps = float(conf["rms_norm_eps"])
        self.theta = float(conf["rope_theta"])
        self.qk_norm = bool(conf.get("qk_norm", False))
        if conf.get("hidden_act", "silu") != "silu":
            raise ValueError("dense_decoder covers SwiGLU (silu) models only")
        if conf.get("tie_word_embeddings"):
            raise ValueError("dense_decoder covers untied LM heads only")

    def layer_shapes(self) -> Dict[str, tuple]:
        d, hd = self.d, self.hd
        s = {
            "attn_norm": (d,), "mlp_norm": (d,),
            "wq": (d, self.heads * hd), "wk": (d, self.kv * hd),
            "wv": (d, self.kv * hd), "wo": (self.heads * hd, d),
            "w_gate": (d, self.ff), "w_up": (d, self.ff),
            "w_down": (self.ff, d),
        }
        if self.qk_norm:
            s["q_norm"] = (hd,)
            s["k_norm"] = (hd,)
        return s


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _layer_key(key, i):
    return jax.random.fold_in(key, 1000 + i)


def _draw_layer(dims: Dims, key, i) -> Dict[str, jax.Array]:
    """One layer's weights: projections bf16, norm scales f32 ones."""
    lk = _layer_key(key, i)
    out = {}
    for n, (name, shape) in enumerate(sorted(dims.layer_shapes().items())):
        if name in NORMS:
            out[name] = jnp.ones(shape, F32)
            continue
        w = jax.random.normal(jax.random.fold_in(lk, n), shape, F32)
        out[name] = (w * (1.0 / np.sqrt(shape[0]))).astype(jnp.bfloat16)
    return out


def _draw_embed(dims: Dims, key) -> Dict[str, jax.Array]:
    tok = jax.random.normal(jax.random.fold_in(key, 0),
                            (dims.vocab, dims.d), F32) * 0.02
    un = jax.random.normal(jax.random.fold_in(key, 1),
                           (dims.d, dims.vocab), F32) * (1.0 / np.sqrt(dims.d))
    return {"tok": tok.astype(jnp.bfloat16), "unembed": un.astype(jnp.bfloat16),
            "final_norm": jnp.ones((dims.d,), F32)}


def make_weights(conf: Dict, key) -> Dict:
    """Every weight, layers stacked on a leading axis: ``{"embed": {...},
    "layers": {name: (layers, ...)}}``.  Call under ``jax.jit``."""
    dims = Dims(conf)
    layers = jax.vmap(lambda i: _draw_layer(dims, key, i))(
        jnp.arange(dims.layers))
    return {"embed": _draw_embed(dims, key), "layers": layers}


@functools.lru_cache(maxsize=None)
def _layer_fn(conf_key):
    dims = Dims(dict(conf_key))
    return jax.jit(lambda key, i: _draw_layer(dims, key, i))


@functools.lru_cache(maxsize=None)
def _embed_fn(conf_key):
    dims = Dims(dict(conf_key))
    return jax.jit(lambda key: _draw_embed(dims, key))


def _frozen(conf: Dict):
    return tuple(sorted((k, v) for k, v in conf.items()
                        if isinstance(v, (int, float, str, bool))))


def layer_weights(conf: Dict, key, i: int) -> Dict[str, jax.Array]:
    """Layer ``i``'s weights, the same values :func:`make_weights` drew."""
    return _layer_fn(_frozen(conf))(key, jnp.int32(i))


def embed_weights(conf: Dict, key) -> Dict[str, jax.Array]:
    return _embed_fn(_frozen(conf))(key)


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------


def _fp8(x, axis):
    """Round to float8 e4m3 under an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, low: bool):
    """a (..., K) @ w (K, N) in f32; ``low`` rounds both operands to fp8
    (activations per row, weights per output column)."""
    if low:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.dot(a, w, precision=HIGHEST, preferred_element_type=F32)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (T, N, hd): rotate the two halves of each head by position."""
    T, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), -1)


def _layer(dims: Dims, w, x, low: bool):
    """One layer over one sequence x (T, d), causal from position 0."""
    T = x.shape[0]
    f32 = {k: v.astype(F32) for k, v in w.items()}
    h = _rms(x, f32["attn_norm"], dims.eps)
    q = _mm(h, f32["wq"], low).reshape(T, dims.heads, dims.hd)
    k = _mm(h, f32["wk"], low).reshape(T, dims.kv, dims.hd)
    v = _mm(h, f32["wv"], low).reshape(T, dims.kv, dims.hd)
    if dims.qk_norm:
        q = _rms(q, f32["q_norm"], dims.eps)
        k = _rms(k, f32["k_norm"], dims.eps)
    q, k = _rope(q, dims.theta), _rope(k, dims.theta)
    g = dims.heads // dims.kv
    k = jnp.repeat(k, g, axis=1)          # query head j reads kv head j // g
    v = jnp.repeat(v, g, axis=1)
    if low:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    s = jnp.einsum("tnh,snh->nts", q, k, precision=HIGHEST) * dims.hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if low:
        p = _fp8(p, -1)
    o = jnp.einsum("nts,snh->tnh", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(T, -1), f32["wo"], low)
    h = _rms(x, f32["mlp_norm"], dims.eps)
    a = jax.nn.silu(_mm(h, f32["w_gate"], low)) * _mm(h, f32["w_up"], low)
    return x + _mm(a, f32["w_down"], low)


@functools.lru_cache(maxsize=None)
def _layer_apply(conf_key, low: bool):
    dims = Dims(dict(conf_key))
    # one sequence at a time keeps the (heads, T, T) scores of one in memory
    return jax.jit(lambda w, xs: lax.map(
        lambda x: _layer(dims, w, x, low), xs))


@functools.lru_cache(maxsize=None)
def _head_fn(conf_key, low: bool):
    dims = Dims(dict(conf_key))

    def head(e, xs, pos, served, valid, low_xs=None):
        """Gaps at the selected positions of each sequence.

        xs (B, T, d) final hidden states; pos (B, P) the positions whose
        next token was served; served (B, P) those tokens; valid (B, P)."""
        un = e["unembed"].astype(F32)
        fn = e["final_norm"]

        def logits(h, lo):
            sel = jnp.take_along_axis(h, pos[:, :, None], axis=1)
            return _mm(_rms(sel, fn, dims.eps), un, lo)

        ref = logits(xs, False)                              # (B, P, V)
        best = ref.max(-1)
        got = jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
        gap = jnp.where(valid, best - got, 0.0)
        out = {"gap": gap}
        if low_xs is not None:
            ctl_tok = logits(low_xs, True).argmax(-1)
            ctl = jnp.take_along_axis(ref, ctl_tok[..., None], -1)[..., 0]
            out["control_gap"] = jnp.where(valid, best - ctl, 0.0)
        return out

    return jax.jit(head)


def reference_gaps(conf: Dict, key, seqs: Sequence[np.ndarray],
                   starts: Sequence[int], length: int, n_pos: int,
                   control: bool = False) -> Dict[str, List[np.ndarray]]:
    """Run the reference over each sequence and read the served tokens' gaps.

    ``seqs[i]`` is a prompt followed by every token the program served for
    it; ``starts[i]`` is the prompt's length, so the tokens at positions
    ``starts[i]:`` were served, each predicted from the positions before it.
    Sequences are padded to ``length`` (causal masking keeps the pad out of
    every real position) and the served positions to ``n_pos``, so one
    compiled program serves every sample of a cell.  Returns, per sequence,
    the gap of each served token (``"gap"``) and, with ``control``, the gap
    of the token the float8 reference puts first (``"control_gap"``).
    """
    ck = _frozen(conf)
    dims = Dims(conf)
    B = len(seqs)
    toks = np.zeros((B, length), np.int32)
    pos = np.zeros((B, n_pos), np.int32)
    served = np.zeros((B, n_pos), np.int32)
    valid = np.zeros((B, n_pos), bool)
    for b, (s, st) in enumerate(zip(seqs, starts)):
        if len(s) > length + 1 or len(s) - st > n_pos:
            raise ValueError(f"sequence {b} is longer than the cell allows")
        toks[b, :len(s) - 1] = s[:-1]
        n = len(s) - st
        pos[b, :n] = np.arange(st - 1, len(s) - 1)
        served[b, :n] = s[st:]
        valid[b, :n] = True
    e = embed_weights(conf, key)
    x = e["tok"][jnp.asarray(toks)].astype(F32)
    xl = x if control else None
    for i in range(dims.layers):
        w = layer_weights(conf, key, i)
        x = _layer_apply(ck, False)(w, x)
        if control:
            xl = _layer_apply(ck, True)(w, xl)
        del w
    out = _head_fn(ck, control)(e, x, jnp.asarray(pos), jnp.asarray(served),
                                jnp.asarray(valid), xl)
    res = {k: np.asarray(v) for k, v in out.items()}
    return {k: [v[b, :len(s) - st] for b, (s, st) in enumerate(zip(seqs, starts))]
            for k, v in res.items()}


# ---------------------------------------------------------------------------
# work from shapes
# ---------------------------------------------------------------------------


def matmul_params(conf: Dict) -> int:
    """Weights a token's matmuls read: every projection and the LM head."""
    dims = Dims(conf)
    per_layer = sum(int(np.prod(s)) for n, s in dims.layer_shapes().items()
                    if n not in NORMS)
    return dims.layers * per_layer + dims.d * dims.vocab


def weight_bytes(conf: Dict) -> int:
    """Bytes of every weight a decode step reads: bf16 projections and LM
    head, f32 norm scales (the embedding table is gathered, one row a
    token, and not counted)."""
    dims = Dims(conf)
    norms = sum(int(np.prod(s)) for n, s in dims.layer_shapes().items()
                if n in NORMS)
    return 2 * matmul_params(conf) + 4 * (dims.layers * norms + dims.d)


def kv_bytes_per_token(conf: Dict, itemsize: int = 2) -> int:
    dims = Dims(conf)
    return 2 * dims.layers * dims.kv * dims.hd * itemsize


def flops(conf: Dict, context: int) -> float:
    """Model FLOPs of one token that attends to ``context`` positions
    (itself included): 2 per matmul weight, plus QK^T and PV."""
    dims = Dims(conf)
    return 2.0 * matmul_params(conf) + 4.0 * dims.layers * dims.heads \
        * dims.hd * context


def request_flops(conf: Dict, prompt_len: int, n_out: int) -> float:
    """Model FLOPs to serve one request: its prompt once, causally, then
    each generated token but the last (the last is never fed back)."""
    dims = Dims(conf)
    att = 4.0 * dims.layers * dims.heads * dims.hd
    # contexts 1..P for the prompt, then P+1 .. P+n_out-1
    last = prompt_len + max(n_out - 1, 0)
    return 2.0 * matmul_params(conf) * last + att * last * (last + 1) / 2


def decode_kv_tokens(prompt_len: int, n_out: int) -> int:
    """Cache positions the decode steps of one request must read: the step
    that makes token k+1 attends to prompt_len + k positions."""
    return sum(prompt_len + k for k in range(1, n_out))


def gemm_shapes(conf: Dict, m: int) -> List[tuple]:
    """The (M, K, N) of every weight GEMM of one step over ``m`` rows."""
    dims = Dims(conf)
    s = dims.layer_shapes()
    per_layer = [(m,) + s[n] for n in ("wq", "wk", "wv", "wo",
                                      "w_gate", "w_up", "w_down")]
    return per_layer * dims.layers + [(m, dims.d, dims.vocab)]
