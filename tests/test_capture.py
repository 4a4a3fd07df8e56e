"""Conformance suite for repro.capture — whole-model GEMM capture.

The acceptance bar (ISSUE 4): ``capture.optimize`` on three model configs
(dense transformer, MoE, SSM) must dispatch every *eligible*
``dot_general`` site through the plan-DB pipeline, with captured fwd+bwd
outputs matching the uncaptured model within dtype tolerance.  Runs
entirely on CPU: dispatched sites execute under the Pallas interpreter
(``interpret=True`` — what ``REPRO_INTERPRET=1`` selects in CI).

Also covered here: the jaxpr re-emission of the higher-order primitives
(scan / remat / cond), harvest-only mode replaying byte-identically,
abstract (ShapeDtypeStruct) whole-model harvest with no allocation, the
report JSON artifact, and dispatched sites actually consulting the ranked
plan DB after a ``sweep_captured`` pass.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import capture  # noqa: E402
from repro.models.api import get_api  # noqa: E402

F32 = jnp.float32

#: fwd/bwd agreement vs the uncaptured model (f32 configs; the generated
#: kernels accumulate in f32 exactly like the XLA dots they replace)
TOL = dict(rtol=2e-5, atol=2e-5)

CONFIGS = capture.demo_configs()
B, S = capture.DEMO_BATCH, capture.DEMO_SEQ


@pytest.fixture(autouse=True)
def _isolated_caches(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setenv("REPRO_PLAN_DB", str(tmp_path / "plans.json"))


def _model_case(name):
    cfg = CONFIGS[name]
    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}

    def loss(p, b):
        return api.loss(p, cfg, b)

    return cfg, loss, params, batch


# ---------------------------------------------------------------------------
# the acceptance matrix: three families, fwd + bwd, full dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_captured_model_matches_uncaptured(name):
    """Captured fwd+bwd == uncaptured fwd+bwd, with every eligible site
    dispatched (no site may be classified dispatchable yet fall back)."""
    cfg, loss, params, batch = _model_case(name)
    cf = capture.optimize(loss, interpret=True, label=name)
    report = cf.report_for(params, batch)

    assert report.harvested > 0, "model traced to zero dot_general sites?"
    assert report.dispatched > 0, (
        f"{name}: no site dispatched — alignment/dtype drift in the "
        f"demo config?\n{report.to_json()}"
    )
    # every site is either dispatched or carries a concrete reason: there
    # is no third state, so "every eligible site dispatched" holds exactly
    # when no fallback site has an empty reason
    for site in report.sites:
        if not site.dispatched:
            assert site.reason, f"undocumented fallback: {site.as_dict()}"
        else:
            assert site.spec is not None and site.op is not None

    ref = loss(params, batch)
    out = cf(params, batch)
    np.testing.assert_allclose(float(out), float(ref), **TOL)

    g_ref = jax.grad(loss)(params, batch)
    g_cap = jax.grad(cf)(params, batch)
    for path_ref, path_cap in zip(
        jax.tree.leaves(g_ref), jax.tree.leaves(g_cap)
    ):
        scale = max(float(jnp.max(jnp.abs(path_ref))), 1.0)
        np.testing.assert_allclose(
            np.asarray(path_cap, np.float64) / scale,
            np.asarray(path_ref, np.float64) / scale,
            **TOL,
            err_msg=f"{name}: captured backward diverges from uncaptured",
        )


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_capture_dispatch_counts(name):
    """The demo configs are built so the aligned projection GEMMs dispatch:
    pin the per-family floor so a predicate regression is loud."""
    cfg, loss, params, batch = _model_case(name)
    report = capture.optimize(
        loss, interpret=True, label=name
    ).report_for(params, batch)
    floors = {"dense": 8, "moe": 10, "ssm": 2}
    assert report.dispatched >= floors[name], report.to_json()
    # any remaining fallback (e.g. SSD einsums with multiple batch dims)
    # must carry a concrete reason — attention/MoE motifs now dispatch as
    # fused sites instead of falling back
    assert all(
        s.reason for s in report.sites if not s.dispatched
    )


def test_jit_through_captured_loss():
    cfg, loss, params, batch = _model_case("dense")
    cf = capture.optimize(loss, interpret=True)
    assert np.isclose(
        float(jax.jit(cf)(params, batch)), float(loss(params, batch)),
        rtol=2e-5, atol=2e-5,
    )


# ---------------------------------------------------------------------------
# plan-DB pipeline pickup
# ---------------------------------------------------------------------------


def test_dispatched_sites_consult_plan_db():
    """After sweep_captured persists ranked plans for the harvested specs,
    a captured call must hit the plan DB (the ops._tuned_kernel lookup)."""
    from repro.search import default_plan_db

    cfg, loss, params, batch = _model_case("dense")
    cf = capture.optimize(loss, interpret=True)
    report = cf.report_for(params, batch)
    specs = report.unique_specs()
    assert specs, "dense demo config must harvest dispatched specs"

    db = default_plan_db()
    n = capture.sweep_captured(
        [("t", spec, dt) for spec, dt in specs[:2]],
        with_grads=False, plan_db=db,
        beam_width=2, topk=1, repeats=1, interpret=True,
    )
    assert n == len(specs[:2])
    hits0 = db.lookup_hits
    cf(params, batch)
    assert db.lookup_hits > hits0, (
        "captured call did not consult the ranked plan DB"
    )


def test_backward_uses_derived_spec_keys(tmp_path, monkeypatch):
    """jax.grad of a captured loss populates the autotune cache under the
    *derived-spec* keys (<spec>.dA / <spec>.dB) of repro.grad: the grad
    cache ends up strictly larger than a forward-only cache, and the
    extra keys are exactly the derived specs' tune keys."""
    from repro.codegen import tune_schedule
    from repro.grad import derived_specs

    cfg, loss, params, batch = _model_case("dense")

    fwd_cache = tmp_path / "fwd.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(fwd_cache))
    capture.optimize(loss, interpret=True)(params, batch)
    fwd_entries = json.loads(fwd_cache.read_text())

    grad_cache = tmp_path / "grad.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(grad_cache))
    jax.grad(capture.optimize(loss, interpret=True))(params, batch)
    grad_entries = json.loads(grad_cache.read_text())

    assert len(grad_entries) > len(fwd_entries), (
        "backward pass produced no derived-spec tune entries"
    )
    # every dA/dB derived spec of a dispatched forward site must have been
    # tuned: re-tuning them now against the grad cache is all hits
    report = capture.optimize(
        loss, interpret=True
    ).report_for(params, batch)
    matmul_specs = [
        spec for spec, dt in report.unique_specs() if spec.name == "matmul"
    ]
    assert matmul_specs
    before = len(json.loads(grad_cache.read_text()))
    for spec in matmul_specs:
        for dspec in derived_specs(spec).values():
            tune_schedule(dspec, dtype=np.dtype(np.float32))
    assert len(json.loads(grad_cache.read_text())) == before, (
        "derived-spec keys were missing from the backward-pass cache"
    )


# ---------------------------------------------------------------------------
# jaxpr re-emission units
# ---------------------------------------------------------------------------


def _aligned(seed, *shape):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), F32
    )


def test_scan_remat_cond_reemission():
    w = _aligned(1, 128, 128)
    x = _aligned(0, 3, 128, 128)

    def fn(x, w):
        def body(c, xs):
            return c + jnp.dot(xs, w), (xs * 2).sum()

        out, ys = jax.lax.scan(body, jnp.zeros((128, 128), F32), x)
        out = jax.checkpoint(lambda o: o @ w)(out)
        return jax.lax.cond(
            ys.sum() > 0, lambda o: o.sum(), lambda o: -o.sum(), out
        )

    cf = capture.optimize(fn, interpret=True)
    report = cf.report_for(x, w)
    assert report.harvested == 2 and report.dispatched == 2
    paths = {s.path for s in report.sites}
    assert any("scan" in p for p in paths)
    assert any("remat" in p for p in paths)
    np.testing.assert_allclose(
        float(cf(x, w)), float(fn(x, w)), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(jax.grad(cf)(x, w)), np.asarray(jax.grad(fn)(x, w)),
        rtol=1e-5, atol=1e-5,
    )


def test_grad_through_existing_ops_custom_vjp_site():
    """A traced function that ALREADY routes through a repro.ops
    custom-VJP kernel site must stay differentiable after capture: the
    replay re-binds the custom_vjp equation unmodified (inlining its
    pallas_call primal would make jax.grad crash).  Regression for the
    TPU `train --capture` path, where every model ops.dense call is such
    a site."""
    from repro import ops

    x, w = _aligned(10, 128, 128), _aligned(11, 128, 128)

    def loss(x_, w_):
        return ops.dense(x_, w_, interpret=True).sum()

    cf = capture.optimize(loss, interpret=True)
    report = cf.report_for(x, w)
    # the GEMM is hidden inside the custom_vjp primal as a pallas_call,
    # so there is nothing to harvest — and nothing must break
    assert report.dispatched == 0
    g_ref = jax.grad(loss)(x, w)
    g_cap = jax.grad(cf)(x, w)
    np.testing.assert_allclose(
        np.asarray(g_cap), np.asarray(g_ref), rtol=1e-5, atol=1e-5
    )


def test_custom_vjp_wrapping_dispatchable_site_is_inlined():
    """The counterpart rule: a custom_vjp whose primal holds a plain
    dispatchable dot_general gets inlined so the site dispatches (the
    user's custom derivative is superseded by the op's own VJP)."""

    @jax.custom_vjp
    def f(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    f.defvjp(
        lambda a, b: (f(a, b), (a, b)),
        lambda res, g: (g @ res[1].T, res[0].T @ g),
    )

    a, b = _aligned(12, 128, 128), _aligned(13, 128, 128)
    cf = capture.optimize(lambda a_, b_: f(a_, b_).sum(), interpret=True)
    report = cf.report_for(a, b)
    assert report.dispatched == 1
    np.testing.assert_allclose(
        float(cf(a, b)), float(f(a, b).sum()), rtol=1e-5, atol=1e-4
    )


def test_transposed_and_batched_sites():
    a = _aligned(2, 16, 8)   # (D, M): contract dim 0 with dim 0
    b = _aligned(3, 16, 12)
    xb = _aligned(4, 4, 8, 16)
    wb = _aligned(5, 4, 16, 8)

    def fn(a, b, xb, wb):
        t = jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())))
        bt = jax.lax.dot_general(xb, wb, (((2,), (1,)), ((0,), (0,))))
        return t.sum() + bt.sum()

    cf = capture.optimize(fn, interpret=True)
    report = cf.report_for(a, b, xb, wb)
    ops_seen = {s.op for s in report.sites if s.dispatched}
    assert ops_seen == {"dense_transposed", "batched_dense"}
    np.testing.assert_allclose(
        float(cf(a, b, xb, wb)), float(fn(a, b, xb, wb)),
        rtol=1e-5, atol=1e-5,
    )


def test_harvest_only_mode_replays_identically():
    cfg, loss, params, batch = _model_case("dense")
    cf = capture.optimize(loss, interpret=True, dispatch=False)
    report = cf.report_for(params, batch)
    assert report.dispatched == 0
    # sites that would have dispatched must carry the harvest-only
    # annotation; genuinely ineligible sites keep their own reason
    annotated = [
        s for s in report.sites if "dispatch disabled" in s.reason
    ]
    dispatchable = capture.optimize(
        loss, interpret=True
    ).report_for(params, batch).dispatched
    assert len(annotated) == dispatchable > 0
    assert all(s.reason for s in report.sites)
    # replay re-binds the original equations: bitwise-equal output
    assert float(cf(params, batch)) == float(loss(params, batch))


def test_cpu_without_interpret_falls_back_entirely():
    """interpret=False on a CPU backend: nothing dispatches, everything
    still runs (production no-op safety)."""
    cfg, loss, params, batch = _model_case("dense")
    cf = capture.optimize(loss, interpret=False)
    report = cf.report_for(params, batch)
    assert report.dispatched == 0
    assert float(cf(params, batch)) == pytest.approx(
        float(loss(params, batch)), rel=1e-6
    )


# ---------------------------------------------------------------------------
# abstract whole-model harvest + report artifact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_abstract_model_capture(kind):
    """ShapeDtypeStruct tracing: harvest without allocating parameters."""
    cfg = CONFIGS["dense"]
    _, report = capture.model_capture(
        cfg, batch=B, seq=S, kind=kind, interpret=True,
    )
    assert report.harvested > 0
    if kind == "train":
        assert report.dispatched > 0


def test_abstract_harvest_matches_concrete():
    cfg, loss, params, batch = _model_case("dense")
    concrete = capture.optimize(
        loss, interpret=True
    ).report_for(params, batch)
    _, abstract = capture.model_capture(
        cfg, batch=B, seq=S, kind="train", interpret=True,
    )
    assert (abstract.harvested, abstract.dispatched, abstract.fallback) == (
        concrete.harvested, concrete.dispatched, concrete.fallback,
    )


def test_model_gemm_specs_dedupes():
    cfg = CONFIGS["dense"]
    points = capture.model_gemm_specs(
        cfg, batch=B, seq=S, kinds=("train",), interpret=True,
    )
    assert points
    keys = [
        (spec.name, tuple(sorted(spec.extents.items())), dt)
        for _, spec, dt in points
    ]
    assert len(keys) == len(set(keys))


def test_report_json_roundtrip():
    cfg, loss, params, batch = _model_case("dense")
    report = capture.optimize(
        loss, interpret=True
    ).report_for(params, batch)
    blob = json.loads(report.to_json())
    assert blob["harvested"] == report.harvested
    assert blob["dispatched"] == report.dispatched
    assert len(blob["sites"]) == report.harvested
    for site in blob["sites"]:
        assert site["status"] in ("dispatched", "fallback")
        if site["status"] == "dispatched":
            assert site["spec"] in (
                "matmul", "transposed_matmul", "batched_matmul",
                "attention", "grouped_matmul",
            )


# ---------------------------------------------------------------------------
# fallback-by-containment blame
# ---------------------------------------------------------------------------


def test_fallback_names_nearest_blocking_ancestor(monkeypatch):
    """A site under nested non-rewritable primitives must blame the
    NEAREST one — the primitive that actually stops the rewrite — not the
    outermost.  Regression: the walk used to latch the first blocker and
    never replace it.  ``while``/``cond`` are removed from the rewritable
    set so both act as blockers; the dot lives inside cond inside while,
    so ``cond`` is the true blocker."""
    from repro.capture import harvest as hmod

    monkeypatch.setattr(
        hmod, "REWRITABLE_HOPS",
        frozenset({"jit", "closed_call", "core_call"}),
    )
    w = _aligned(6, 128, 128)

    def fn(x):
        def body(c):
            return jax.lax.cond(
                c.sum() > 0,
                lambda a: jnp.dot(a, w, preferred_element_type=F32),
                lambda a: a * 1.0,
                c,
            ) * 0.5

        return jax.lax.while_loop(
            lambda c: c[0, 0] < 1.0, body, x
        ).sum()

    report = capture.optimize(fn, interpret=True).report_for(w)
    sites = [s for s in report.sites if s.op == "dense"]
    assert sites, report.to_json()
    for s in sites:
        assert not s.dispatched
        assert "(cond)" in s.reason, s.reason
        assert "while" not in s.reason, s.reason
