"""Step builders: sharded train_step / prefill_step / serve_step.

These are what both the real drivers (train.py / serve.py) and the dry-run
lower.  All sharding is expressed as in/out NamedShardings derived from the
logical-axes trees (launch.sharding); GSPMD inserts the collectives.

Training differentiates through the generated kernels directly: every
model matmul is a ``repro.ops`` entry point, which registers a
``jax.custom_vjp`` (``repro.grad``) whose backward GEMMs are derived
ContractionSpecs compiled through the same plan-DB/autotune pipeline as
the forward.  ``jax.value_and_grad`` below therefore needs no
``dot_general`` fallback on TPU — both sides of the tape run searched/
tuned Pallas kernels (sweep them together with
``scripts/search_sweep.py --with-grads``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..models.api import ModelAPI, batch_spec, get_api
from ..optim import AdamWConfig, Quantized
from ..optim import adamw as optim
from . import sharding as shd


@dataclasses.dataclass
class StepBundle:
    """Everything needed to lower/run one (arch x shape x mesh) cell."""

    fn: Callable                      # the jittable step function
    in_shapes: Tuple                  # ShapeDtypeStructs (with shardings)
    static_name: str                  # train_step | prefill_step | serve_step
    out_shardings: Any = None


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def eval_params(cfg: ModelConfig, api: ModelAPI):
    """Abstract param shapes + captured logical axes (no allocation)."""
    captured = {}

    def f(key):
        p, a = api.init(cfg, key)
        captured["axes"] = a
        return p

    shapes = jax.eval_shape(f, jax.random.key(0))
    return shapes, captured["axes"]


def param_shardings(mesh, cfg: ModelConfig, api: ModelAPI):
    shapes, axes = eval_params(cfg, api)
    return shapes, axes, shd.tree_shardings(mesh, shapes, axes)


def opt_shardings(mesh, opt_shapes, param_shardings_tree):
    """Moments inherit the param sharding; Quantized moments shard their
    flat block axis across the whole mesh."""

    def like_params(moments):
        flat_p, treedef = jax.tree.flatten(param_shardings_tree)
        flat_m = treedef.flatten_up_to(moments)
        out = []
        for psh, m in zip(flat_p, flat_m):
            if isinstance(m, Quantized) or hasattr(m, "q"):
                qsh = shd.quantized_sharding(mesh, m)
                out.append(Quantized(qsh["q"], qsh["scale"], m.shape, m.dtype))
            else:
                out.append(psh)
        return treedef.unflatten(out)

    return optim.AdamWState(
        step=NamedSharding(mesh, P()),
        m=like_params(opt_shapes.m),
        v=like_params(opt_shapes.v),
    )


def batch_shardings(mesh, cfg: ModelConfig, shape: ShapeConfig):
    spec = batch_spec(cfg, shape)
    out = {}
    for name, (shp, dtype) in spec.items():
        out[name] = _sds(
            shp, dtype,
            NamedSharding(mesh, shd.batch_spec_for(mesh, shp, seq_axis=1)),
        )
    return out


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: AdamWConfig,
    lr_schedule: Optional[Callable] = None,
    microbatch: int = 1,
    capture: Optional[bool] = None,
):
    """Loss + grad + optimizer update for one (micro)batch.

    ``jax.value_and_grad`` here differentiates straight through the
    generated kernels: the model's ``ops.dense``/``ops.dense_act`` calls
    carry custom VJPs (``repro.grad``) whose cotangent GEMMs
    (dA = g·Bᵀ, dB = Aᵀ·g) compile under their own derived-spec keys —
    the backward pass is generated-kernel traffic, not a dot_general
    fallback.

    ``capture`` (or ``$REPRO_CAPTURE=1``) additionally routes the loss
    through ``repro.capture.optimize``: the model's *remaining* plain
    ``dot_general`` sites — everything not already a ``repro.ops`` call —
    are harvested into ContractionSpecs and, where eligible, dispatched
    through the same plan-DB pipeline, fwd and bwd.  Ineligible sites run
    untouched, so this is a strict superset of the uncaptured step.

    Trace the step under ``with jax.set_mesh(mesh)`` (outside the jit,
    which is the only place JAX accepts it) and ``ops._tuned_kernel``
    consults the mesh-shape-qualified plan keys a ``--mesh`` sweep
    persisted, so eligible GEMMs dispatch through the sharded generated
    kernels (``codegen.bind_mesh``).
    """
    import os

    api = get_api(cfg)
    if capture is None:
        capture = os.environ.get("REPRO_CAPTURE", "") == "1"
    base_loss = lambda p, b: api.loss(p, cfg, b)  # noqa: E731
    if capture:
        from .. import capture as _capture

        loss_fn = _capture.optimize(
            base_loss, label=f"{cfg.arch_id}:train_step"
        )
    else:
        loss_fn = base_loss

    def train_step(params, opt_state, batch):
        if microbatch > 1:
            def split(x):
                return x.reshape(microbatch, x.shape[0] // microbatch,
                                 *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def acc_step(carry, mb):
                loss_acc, grad_acc = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (
                    loss_acc + l / microbatch,
                    jax.tree.map(
                        lambda a, b: a + b.astype(a.dtype) / microbatch,
                        grad_acc, g,
                    ),
                ), None

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (loss, grads), _ = jax.lax.scan(
                acc_step, (jnp.zeros((), jnp.float32), zero), micro
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)

        lr_scale = lr_schedule(opt_state.step) if lr_schedule else 1.0
        new_params, new_opt, metrics = optim.update(
            grads, opt_state, params, opt_cfg, lr_scale=lr_scale
        )
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return train_step


def train_bundle(
    mesh,
    cfg: ModelConfig,
    shape: ShapeConfig,
    opt_cfg: Optional[AdamWConfig] = None,
    microbatch: int = 1,
    capture: Optional[bool] = None,
) -> StepBundle:
    api = get_api(cfg)
    if opt_cfg is None:
        import os

        # kimi-class models need 8-bit moments to fit (DESIGN.md); the
        # zero1 §Perf knob forces them for everyone
        big = cfg.moe is not None and cfg.moe.n_experts >= 256
        use_int8 = big or os.environ.get("REPRO_OPT_INT8") == "1"
        opt_cfg = AdamWConfig(moments_dtype="int8" if use_int8 else "float32")
    p_shapes, axes, p_shard = param_shardings(mesh, cfg, api)
    o_shapes = jax.eval_shape(lambda p: optim.init(p, opt_cfg), p_shapes)
    o_shard = opt_shardings(mesh, o_shapes, p_shard)
    b_sds = batch_shardings(mesh, cfg, shape)

    p_sds = jax.tree.map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), p_shapes, p_shard
    )
    # Quantized is a pytree node: its q/scale children align leaf-wise
    o_sds = jax.tree.map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), o_shapes, o_shard
    )

    step = make_train_step(cfg, opt_cfg, microbatch=microbatch,
                           capture=capture)
    metrics_shard = {
        "grad_norm": NamedSharding(mesh, P()),
        "clip_scale": NamedSharding(mesh, P()),
        "loss": NamedSharding(mesh, P()),
    }
    return StepBundle(
        fn=step,
        in_shapes=(p_sds, o_sds, b_sds),
        static_name="train_step",
        out_shardings=(p_shard, o_shard, metrics_shard),
    )


# ---------------------------------------------------------------------------
# serve (prefill + decode)
# ---------------------------------------------------------------------------


def cache_shardings(mesh, cfg: ModelConfig, api: ModelAPI, batch, max_len):
    c_shapes = jax.eval_shape(
        lambda: api.cache_init(cfg, batch, max_len)
    )
    c_axes = api.cache_axes(cfg)

    def one(shape_leaf, ax):
        return NamedSharding(
            mesh,
            shd.spec_for(
                mesh, ax, tuple(shape_leaf.shape),
                rules={**shd.PARAM_RULES, "heads": shd.PARAM_RULES["heads"]},
            ),
        )

    # cache axes tree: per-segment {kind: {leaf: axes}} must align with
    # c_shapes structure; flatten up to the axes tree's leaves
    flat_shapes, treedef = jax.tree.flatten(c_shapes)
    # align by broadcasting the axes tree over the shapes tree
    shard_tree = _map_axes_over(c_shapes, c_axes, one)
    return c_shapes, shard_tree


def _map_axes_over(shapes_tree, axes_tree, fn):
    """Walk shapes_tree; at each leaf find the matching axes entry by key
    path suffix (the axes trees omit the stacked-segment nesting)."""

    def walk(s, a):
        if isinstance(s, dict):
            return {
                k: walk(v, a[k] if isinstance(a, dict) and k in a else a)
                for k, v in s.items()
            }
        # s is a leaf; a should be a tuple of logical names (or dict miss)
        ax = a if isinstance(a, (tuple, type(None))) else None
        return fn(s, ax)

    return walk(shapes_tree, axes_tree)


def serve_bundle(
    mesh, cfg: ModelConfig, shape: ShapeConfig
) -> StepBundle:
    """decode_*: one new token against a seq_len-deep cache."""
    api = get_api(cfg)
    B, S = shape.global_batch, shape.seq_len
    p_shapes, axes, p_shard = param_shardings(mesh, cfg, api)
    p_sds = jax.tree.map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), p_shapes, p_shard
    )
    c_shapes, c_shard = cache_shardings(mesh, cfg, api, B, S)
    c_sds = jax.tree.map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), c_shapes, c_shard
    )
    tok_sds = _sds(
        (B, 1), jnp.int32,
        NamedSharding(mesh, shd.batch_spec_for(mesh, (B, 1))),
    )

    def serve_step(params, caches, tokens):
        return api.decode_step(params, cfg, caches, tokens)

    logits_shard = NamedSharding(
        mesh, shd.batch_spec_for(mesh, (B, 1, cfg.vocab))
    )
    return StepBundle(
        fn=serve_step,
        in_shapes=(p_sds, c_sds, tok_sds),
        static_name="serve_step",
        out_shardings=(logits_shard, c_shard),
    )


def prefill_bundle(mesh, cfg: ModelConfig, shape: ShapeConfig) -> StepBundle:
    api = get_api(cfg)
    B, S = shape.global_batch, shape.seq_len
    p_shapes, axes, p_shard = param_shardings(mesh, cfg, api)
    p_sds = jax.tree.map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), p_shapes, p_shard
    )
    b_sds = batch_shardings(mesh, cfg, shape)
    max_len = {"dense": S, "moe": S}.get(cfg.family, S)

    def prefill_step(params, batch):
        return api.prefill(params, cfg, batch, max_len)

    # determine cache output shardings from an eval_shape of the caches
    dec_len = b_sds["tokens"].shape[1]
    _, c_shard = cache_shardings(mesh, cfg, api, B, max_len)
    logits_shard = NamedSharding(
        mesh, shd.batch_spec_for(mesh, (B, dec_len, cfg.vocab))
    )
    return StepBundle(
        fn=prefill_step,
        in_shapes=(p_sds, b_sds),
        static_name="prefill_step",
        out_shardings=(logits_shard, c_shard),
    )
