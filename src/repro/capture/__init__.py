"""repro.capture — whole-model GEMM capture into the plan-DB pipeline.

PRs 1-3 built a per-op pipeline: a call site hand-rewired to ``repro.ops``
gets cost-guided search (``repro.search``), ranked plans, persistent
autotuning (``repro.codegen``) and derived-spec backward kernels
(``repro.grad``).  Everything else — the whole model zoo under
``repro.models`` — still lowered its GEMMs through plain ``dot_general``.
This package closes that gap at the jaxpr level, the move Linnea
(arXiv:1912.12924) and the LAMP survey (arXiv:1911.09421) frame as the
real prize: mapping *whole expressions*, not single kernels, onto
optimized primitives.

    from repro import capture

    loss_c = capture.optimize(loss_fn)        # trace-once wrapper
    loss_c(params, batch)                     # eligible GEMMs -> ops/plan DB
    jax.grad(loss_c)(params, batch)           # bwd GEMMs: derived-spec kernels
    loss_c.report_for(params, batch).summary()
    # "capture[loss_fn]: 18 site(s) harvested, 15 dispatched, 3 fallback"

Layers:

  ``harvest``   walk a jaxpr (recursing through scan/remat/jit/...),
                classify every ``dot_general`` into a ``ContractionSpec``
                named by ``core.enumerate`` — so each site owns the same
                plan-DB/autotune keys a hand-rewired op would — and report
                dispatched vs fallback per site, with reasons.
  ``rewrite``   ``optimize(fn)``: re-emit the function with eligible sites
                dispatched through ``repro.ops`` (differentiable via
                ``repro.grad``), ineligible sites re-bound untouched.
  ``sweep``     abstract whole-model harvest (ShapeDtypeStruct tracing; no
                allocation) + offline sweep of the harvested GEMM set,
                fwd+bwd, into the ranked plan DB.

Integration points: ``launch.steps.make_train_step(capture=True)`` /
``launch.train --capture`` (training through captured losses),
``launch.serve --capture`` (warm + sweep a serving model's harvested
specs), ``scripts/search_sweep.py --from-model`` (offline fleet sweeps)
and the ``capture.*`` rows of ``benchmarks/kernel_bench.py``.
"""

from .harvest import (
    REWRITABLE_HOPS,
    SUPPORTED_DTYPES,
    CaptureReport,
    CaptureSite,
    classify_dot_general,
    harvest_jaxpr,
    spec_key,
)
from .rewrite import CapturedFunction, capture_report, optimize
from .sweep import (
    DEMO_BATCH,
    DEMO_SEQ,
    demo_configs,
    model_capture,
    model_gemm_specs,
    sweep_captured,
)

__all__ = [
    "CaptureReport",
    "CaptureSite",
    "CapturedFunction",
    "DEMO_BATCH",
    "DEMO_SEQ",
    "REWRITABLE_HOPS",
    "SUPPORTED_DTYPES",
    "capture_report",
    "classify_dot_general",
    "demo_configs",
    "harvest_jaxpr",
    "model_capture",
    "model_gemm_specs",
    "optimize",
    "spec_key",
    "sweep_captured",
]
