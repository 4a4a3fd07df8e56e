"""Model-level harvest + offline sweep: a config's full GEMM set in one pass.

Bridges ``capture`` to the search pipeline: trace a model's train loss /
prefill / decode step **abstractly** (``jax.ShapeDtypeStruct`` inputs — no
parameter allocation, so harvesting a 400B config costs only a trace),
collect the dispatched sites' ContractionSpecs, and run each through
``search.search_schedule`` — with ``with_grads`` the derived backward
specs (``grad.derive``) are swept alongside, so one offline pass readies
ranked plans for the model's forward *and* backward GEMM traffic.

Consumers: ``scripts/search_sweep.py --from-model``, ``serve --capture``
and the CI capture-report artifact (``scripts/capture_report.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..configs.base import ModelConfig
from .harvest import CaptureReport, spec_key
from .rewrite import CapturedFunction

#: trace points a model exposes to the harvester
KINDS = ("train", "prefill", "decode")


def _abstract_params(cfg: ModelConfig, api):
    return jax.eval_shape(lambda key: api.init(cfg, key)[0], jax.random.key(0))


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def model_capture(
    cfg: ModelConfig,
    *,
    batch: int,
    seq: int,
    kind: str = "train",
    interpret: Optional[bool] = None,
    dispatch: bool = True,
) -> Tuple[CapturedFunction, CaptureReport]:
    """Capture one model entry point abstractly; returns (fn, report).

    ``kind``: ``train`` traces the loss (the GEMM set training runs
    forward; with ``with_grads`` sweeps, its derived specs cover the
    backward), ``prefill``/``decode`` trace the serving steps.
    """
    from ..configs.base import ShapeConfig
    from ..models.api import batch_spec, get_api

    if kind not in KINDS:
        raise ValueError(f"kind must be one of {KINDS}, got {kind!r}")
    api = get_api(cfg)
    p = _abstract_params(cfg, api)
    shape = ShapeConfig(f"capture_{kind}", seq, batch,
                        "train" if kind == "train" else "prefill")
    b_sds = {
        name: _sds(shp, dt)
        for name, (shp, dt) in batch_spec(cfg, shape).items()
    }

    if kind == "train":
        fn = lambda params, bt: api.loss(params, cfg, bt)  # noqa: E731
        args = (p, b_sds)
    elif kind == "prefill":
        fn = lambda params, bt: api.prefill(params, cfg, bt, seq)  # noqa: E731
        args = (p, b_sds)
    else:
        caches = jax.eval_shape(lambda: api.cache_init(cfg, batch, seq))
        toks = _sds((batch, 1), np.int32)
        fn = lambda params, c, t: api.decode_step(  # noqa: E731
            params, cfg, c, t
        )
        args = (p, caches, toks)

    captured = CapturedFunction(
        fn, interpret=interpret, dispatch=dispatch,
        label=f"{cfg.arch_id}:{kind}",
    )
    report = captured.report_for(*args)
    return captured, report


def model_gemm_specs(
    cfg: ModelConfig,
    *,
    batch: int,
    seq: int,
    kinds: Sequence[str] = ("train",),
    interpret: Optional[bool] = None,
) -> List[Tuple[str, object, str]]:
    """Deduplicated ``(label, spec, dtype)`` GEMM set across trace points."""
    seen: Dict[Tuple, Tuple[str, object, str]] = {}
    for kind in kinds:
        _, report = model_capture(
            cfg, batch=batch, seq=seq, kind=kind, interpret=interpret,
        )
        for spec, dtype in report.unique_specs():
            seen.setdefault(
                spec_key(spec, dtype), (f"{kind}:{spec.name}", spec, dtype)
            )
    return list(seen.values())


def sweep_captured(
    points: Sequence[Tuple[str, object, str]],
    *,
    with_grads: bool = True,
    plan_db=None,
    beam_width: int = 4,
    topk: int = 2,
    interpret: Optional[bool] = None,
    measure: bool = True,
    repeats: int = 1,
    verbose: bool = False,
    mesh_shape=None,
    quant=None,
) -> int:
    """Search + persist ranked plans for every harvested GEMM point.

    Each point expands through ``search.space.sweep_specs`` (fwd plus the
    derived dA/dB/... specs when ``with_grads``), so the plan DB ends up
    covering the captured model's full fwd+bwd GEMM traffic.  With
    ``mesh_shape`` ('2x4') every sweep point is *additionally* swept at
    the mesh tier, persisting sharded ladders under the mesh-qualified
    keys — the whole-model analogue of ``scripts/search_sweep.py --mesh``:
    a captured model then serves/trains through sharded generated kernels
    whenever a matching mesh is active (``ops._mesh_plan_kernel``).
    With ``quant`` ('int8' | 'fp8') every *forward* sweep point also gets
    a quantized leg — the spec re-searched at the low-precision tier under
    its dtype-qualified plan key — so a quantized capture/serve run finds
    its ranked plans warm.  Quant legs run at mesh=None only (the quant
    tier, like the fused families, has no mesh lowering yet) and skip
    fused/derived specs that refuse quantization.
    Returns the number of (spec, dtype, mesh) sweep points persisted.
    """
    from ..core.enumerate import QUANT_FORMATS, quantize_spec
    from ..search import default_plan_db, search_schedule, sweep_specs

    db = plan_db if plan_db is not None else default_plan_db()
    if quant is not None and quant not in QUANT_FORMATS:
        raise ValueError(
            f"quant must be one of {sorted(QUANT_FORMATS)}, got {quant!r}"
        )
    n = 0
    meshes = [None] + ([mesh_shape] if mesh_shape is not None else [])
    for label, spec, dtype in points:
        for sub_label, sub in sweep_specs(spec, with_grads=with_grads):
            legs = [(sub_label, sub, np.dtype(dtype), meshes)]
            if quant is not None and sub_label == "fwd":
                try:
                    qspec = quantize_spec(sub, fmt=quant)
                    qdt = np.dtype(QUANT_FORMATS[quant].dtype)
                except (NotImplementedError, ValueError, TypeError):
                    qspec = None  # fused family / unregistered fp8 dtype
                if qspec is not None:
                    legs.append(
                        (f"{sub_label}@{quant}", qspec, qdt, [None])
                    )
            for leg_label, leg_spec, leg_dt, leg_meshes in legs:
                for ms in leg_meshes:
                    res = search_schedule(
                        leg_spec,
                        dtype=leg_dt,
                        beam_width=beam_width,
                        topk=topk,
                        interpret=interpret,
                        measure=measure,
                        repeats=repeats,
                        plan_db=db,
                        mesh_shape=ms,
                    )
                    n += 1
                    if verbose:
                        from ..obs import log

                        best = res.best
                        t = ("-" if best.measured_s is None
                             else f"{best.measured_s * 1e3:.2f}ms")
                        at = f"@mesh={res.mesh}" if res.mesh else ""
                        log.info("capture-sweep",
                                 f"{label}/{leg_label}{at} "
                                 f"dtype={leg_dt} best={t} (db={db.path})")
    return n


# ---------------------------------------------------------------------------
# demo configs — the capture conformance trio
# ---------------------------------------------------------------------------


def demo_configs() -> Dict[str, ModelConfig]:
    """Three tiny, 128-aligned configs (dense / MoE / SSM) used by
    ``tests/test_capture.py``, the ``capture.*`` bench rows and the CI
    capture-report artifact.

    Derived from the real arch smokes but with extents snapped to the
    dense kernel's 128-alignment so the 2-D projection sites actually
    dispatch in interpret mode (the point of the conformance run);
    ``float32`` keeps the fwd/bwd comparison tolerances tight.
    """
    from ..configs import get_config

    dense = dataclasses.replace(
        get_config("qwen3-8b").smoke(),
        n_layers=2, d_model=128, n_heads=2, n_kv_heads=2, head_dim=64,
        d_ff=128, vocab=256, dtype="float32",
    )
    moe_base = get_config("kimi-k2-1t-a32b").smoke()
    moe = dataclasses.replace(
        moe_base,
        n_layers=2, d_model=128, n_heads=2, n_kv_heads=2, head_dim=64,
        d_ff=128, vocab=256, dtype="float32",
        moe=dataclasses.replace(
            moe_base.moe, n_experts=4, top_k=2, expert_ff=64,
            first_dense=1, dense_ff=128, shared_expert_ff=0,
        ),
    )
    ssm_base = get_config("mamba2-130m").smoke()
    ssm = dataclasses.replace(
        ssm_base,
        n_layers=2, d_model=128, n_heads=2, n_kv_heads=0, head_dim=64,
        d_ff=128, vocab=256, dtype="float32",
    )
    return {"dense": dense, "moe": moe, "ssm": ssm}


#: (batch, seq) used with the demo configs: batch*seq = 128 keeps the
#: flattened token dim aligned for the dense-kernel dispatch predicate
DEMO_BATCH, DEMO_SEQ = 2, 64
