"""The program under test for a dense decoder: its model configuration and
its parameter tree, built from the benchmark's configuration file and the
benchmark's own weights (:mod:`perfbench.arch.dense_decoder`)."""

from __future__ import annotations

from typing import Dict

from repro.configs.base import ModelConfig

from .dense_decoder import Dims


def program_config(conf: Dict) -> ModelConfig:
    """The program's ``ModelConfig`` at the file's sizes, in bfloat16."""
    dims = Dims(conf)
    if conf.get("torch_dtype") != "bfloat16":
        raise ValueError("the served dtype must be bfloat16")
    return ModelConfig(
        arch_id=conf["name"], family="dense",
        n_layers=dims.layers, d_model=dims.d, n_heads=dims.heads,
        n_kv_heads=dims.kv, head_dim=dims.hd, d_ff=dims.ff, vocab=dims.vocab,
        qk_norm=dims.qk_norm, qkv_bias=bool(conf.get("attention_bias")),
        rope_theta=dims.theta, norm_eps=dims.eps, act="silu",
        tie_embeddings=False, dtype="bfloat16",
        max_seq=int(conf["max_position_embeddings"]),
    )


def program_params(weights: Dict) -> Dict:
    """Re-nest :func:`make_weights`' arrays as ``repro.models.transformer``
    holds them (one segment of stacked dense layers).  No copies."""
    e, ly = weights["embed"], weights["layers"]
    attn = {k: ly[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
            if k in ly}
    layer = {
        "attn_norm": {"scale": ly["attn_norm"]},
        "attn": attn,
        "mlp_norm": {"scale": ly["mlp_norm"]},
        "mlp": {k: ly[k] for k in ("w_gate", "w_up", "w_down")},
    }
    return {
        "embedding": {"tok": e["tok"], "unembed": e["unembed"]},
        "final_norm": {"scale": e["final_norm"]},
        "seg0": {"dense": layer},
    }
