"""Uniform model API over the six families.

Everything downstream (launcher, dry-run, benchmarks, tests) talks to models
through this adapter:

    api = get_api(cfg)
    params, axes = api.init(cfg, key)
    loss = api.loss(params, cfg, batch)            # batch: dict of arrays
    logits, caches = api.prefill(params, cfg, batch, max_len)
    logits, caches = api.decode_step(params, cfg, caches, tokens)
    logits, rows = api.paged_decode_step(params, cfg, pools, block_table,
                                         lens, tokens)   # dense, moe

``batch_spec`` defines the exact input tensors for every (family x shape
kind), which is also what ``launch.dryrun.input_specs`` materializes as
ShapeDtypeStructs.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from . import encdec, hybrid, transformer, vlm

N_PATCHES = 256  # VLM stub: patches per image sequence prefix


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    init: Callable
    forward: Callable        # (params, cfg, batch) -> logits
    loss: Callable           # (params, cfg, batch) -> scalar
    prefill: Callable        # (params, cfg, batch, max_len) -> (logits, caches)
    decode_step: Callable    # (params, cfg, caches, tokens) -> (logits, caches)
    cache_init: Callable     # (cfg, batch, max_len) -> caches
    cache_axes: Callable     # (cfg) -> logical axes tree
    # (params, cfg, pools, block_table, lens, tokens) -> (logits, rows);
    # the families whose KV cache pages (``launch.serving.paged``)
    paged_decode_step: Optional[Callable] = None


def _lm_api() -> ModelAPI:
    return ModelAPI(
        init=transformer.init,
        forward=lambda p, c, b, **kw: transformer.forward(
            p, c, b["tokens"], **kw
        ),
        loss=lambda p, c, b, **kw: transformer.loss_fn(
            p, c, b["tokens"], b["labels"], **kw
        ),
        prefill=lambda p, c, b, max_len, **kw: transformer.prefill(
            p, c, b["tokens"], max_len, lengths=b.get("lengths")
        ),
        decode_step=transformer.decode_step,
        cache_init=transformer.cache_init,
        cache_axes=transformer.cache_axes,
        paged_decode_step=transformer.paged_decode_step,
    )


def _hybrid_prefill(p, c, b, max_len):
    if b.get("lengths") is not None:
        # SSM recurrences fold every input token into the state — a pad
        # token pollutes it no matter what the attention layers mask, so
        # right-padded batching is attention-family only.
        raise NotImplementedError(
            "lengths-masked prefill is not supported for ssm/hybrid "
            "families; serve them with per-request (batch-1) prefill"
        )
    return hybrid.prefill(p, c, b["tokens"], max_len)


def _hybrid_api() -> ModelAPI:
    return ModelAPI(
        init=hybrid.init,
        forward=lambda p, c, b, **kw: hybrid.forward(p, c, b["tokens"], **kw),
        loss=lambda p, c, b, **kw: hybrid.loss_fn(
            p, c, b["tokens"], b["labels"], **kw
        ),
        prefill=lambda p, c, b, max_len, **kw: _hybrid_prefill(
            p, c, b, max_len
        ),
        decode_step=hybrid.decode_step,
        cache_init=hybrid.cache_init,
        cache_axes=hybrid.cache_axes,
    )


def _encdec_api() -> ModelAPI:
    return ModelAPI(
        init=encdec.init,
        forward=lambda p, c, b, **kw: encdec.forward(
            p, c, b["frames"], b["tokens"], **kw
        ),
        loss=lambda p, c, b, **kw: encdec.loss_fn(
            p, c, b["frames"], b["tokens"], b["labels"], **kw
        ),
        prefill=lambda p, c, b, max_len, **kw: encdec.prefill(
            p, c, b["frames"], b["tokens"], max_len
        ),
        decode_step=encdec.decode_step,
        cache_init=lambda c, batch, max_len: encdec.cache_init(
            c, batch, max_len, enc_len=max_len
        ),
        cache_axes=encdec.cache_axes,
    )


def _vlm_api() -> ModelAPI:
    return ModelAPI(
        init=vlm.init,
        forward=lambda p, c, b, **kw: vlm.forward(
            p, c, b["tokens"], b["patches"], **kw
        ),
        loss=lambda p, c, b, **kw: vlm.loss_fn(
            p, c, b["tokens"], b["patches"], b["labels"], **kw
        ),
        prefill=lambda p, c, b, max_len, **kw: vlm.prefill(
            p, c, b["tokens"], b["patches"], max_len
        ),
        decode_step=vlm.decode_step,
        cache_init=vlm.cache_init,
        cache_axes=vlm.cache_axes,
    )


_APIS = {
    "dense": _lm_api,
    "moe": _lm_api,
    "ssm": _hybrid_api,
    "hybrid": _hybrid_api,
    "encdec": _encdec_api,
    "vlm": _vlm_api,
}


def get_api(cfg: ModelConfig) -> ModelAPI:
    return _APIS[cfg.family]()


# ---------------------------------------------------------------------------
# input specifications per (family x shape kind)
# ---------------------------------------------------------------------------


def batch_spec(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Tuple]:
    """name -> (shape, dtype) for the *step inputs* of this cell.

    train/prefill: full-sequence inputs.  decode: a single new token — the
    KV/state caches are separate step inputs (see dryrun.input_specs).
    Sequence-length budget S is split per family:
      encdec: S/2 encoder frames + S/2 decoder tokens
      vlm:    N_PATCHES image patches + (S - N_PATCHES) text tokens
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "decode":
        return {"tokens": ((B, 1), i32)}
    if cfg.family in ("dense", "moe", "ssm", "hybrid"):
        spec = {"tokens": ((B, S), i32)}
    elif cfg.family == "encdec":
        spec = {
            "frames": ((B, S // 2, cfg.d_model), cfg.param_dtype),
            "tokens": ((B, S // 2), i32),
        }
    elif cfg.family == "vlm":
        spec = {
            "patches": ((B, N_PATCHES, vlm.VIT_DIM), cfg.param_dtype),
            "tokens": ((B, S - N_PATCHES), i32),
        }
    else:
        raise KeyError(cfg.family)
    if shape.kind == "train":
        spec["labels"] = (spec["tokens"][0], i32)
    return spec
