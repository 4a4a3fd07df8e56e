"""Kernel benches: block-shape sweep + generated-kernel + search scenarios.

No TPU in this container, so wall-clock is the interpret-mode *correctness*
path only; the reported ``derived`` column is the analytic HBM-traffic model
(core.autotune napkin math) that ranks block shapes for the real chip —
this is the §Perf lever for the kernel level.

The ``gen.*`` rows go through ``repro.codegen``: the schedule-driven
generator compiling plain / batched / chained / transposed contractions
(none of which had kernels before the generator existed), checked against
the hand-written baseline and jnp references.  The ``search.*`` rows run
the full ``repro.search`` pipeline (enumerate -> prune -> measure) and
report how much of the variant space the analytic early-cut removed before
measurement.  The ``grad.*`` rows exercise the training half
(``repro.grad``): forward + backward through the custom_vjp ops, the
epilogue-aware dense_act backward, and the backward GEMMs picking up
searched plans under their derived-spec keys.  The ``capture.*`` rows
cover whole-model capture (``repro.capture``): per demo config, sites
harvested/dispatched/fallback, plus the jitted captured-vs-uncaptured
step-time ratio (the no-op safety bar).  ``--smoke`` (or
``run(smoke=True)``) keeps shapes tiny for CI.

Rows that do arithmetic carry ``flops=`` in the derived column so
``scripts/bench_smoke.py`` can report GFLOP/s in ``BENCH_pr3.json``.

Bench sections are individually guarded: a failing row emits
``error=<type>:<msg>`` in its derived column instead of killing the run,
and ``scripts/bench_smoke.py`` turns any such row into a non-zero exit.
"""

import argparse
import os
import time

import jax.numpy as jnp
import numpy as np

from repro.core.autotune import choose_matmul_blocks
from repro.core.cost import TPU
from repro.core.enumerate import matmul_spec
from repro.kernels.matmul.matmul import matmul_pallas
from repro.kernels.matmul.ref import matmul_ref

from .common import emit, timeit


def guarded(name):
    """Run a bench section; an exception becomes an ``error=`` row."""

    def deco(fn):
        def wrapper(*a, **k):
            try:
                fn(*a, **k)
            except Exception as e:  # noqa: BLE001 — bench must keep going
                msg = str(e).replace(",", ";").replace("\n", " ")[:120]
                emit(name, 0.0, f"error={type(e).__name__}:{msg}")
        return wrapper

    return deco


def traffic(m, n, k, bm, bn, bk):
    return m * k * (n / bn) + k * n * (m / bm) + m * n


def _rnd(*shape, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), jnp.float32
    )


@guarded("search.matmul")
def _bench_search(smoke: bool):
    """The search pipeline end to end: candidates -> prune -> measure.

    Reports (a) the winner's measured time with the space statistics and
    (b) the winner vs the un-searched ``default_schedule`` — both timed in
    the *same* measurement pass, and the default is always in the measured
    set, so ``not_slower`` holds by construction (the ISSUE-2 acceptance
    bar) rather than by luck of the clock.
    """
    from repro.search import einsum_reference, reference_arrays, search_schedule

    s = 2 if smoke else 1
    m = k = n = 128 // s
    spec = matmul_spec(m, k, n)
    arrays = reference_arrays(spec, seed=42)
    res = search_schedule(
        spec, beam_width=6, topk=3, interpret=True,
        measure=True, arrays=arrays, plan_db=None,
    )
    st = res.stats
    win = res.best
    emit(
        "search.matmul", win.measured_s,
        f"max_err={win.max_err:.2e};candidates={st.considered};"
        f"pruned={st.pruned_bound + st.pruned_beam};measured={st.measured}",
    )
    base = res.baseline()
    if base is None or base.measured_s is None:
        raise RuntimeError("default_schedule missing from measured set")
    emit(
        "search.vs_default", base.measured_s,
        f"not_slower={win.measured_s <= base.measured_s};"
        f"winner_s={win.measured_s:.3g};default_s={base.measured_s:.3g}",
    )


@guarded("kernel.gen")
def _bench_generated(smoke: bool):
    """Generated kernels vs references, interpret mode (CPU container)."""
    from repro import codegen

    s = 2 if smoke else 1
    m, k, n = 128 // s, 128 // s, 128 // s
    a, b = _rnd(m, k, seed=0), _rnd(k, n, seed=1)

    spec = matmul_spec(m, k, n)
    sched = codegen.default_schedule(spec, {"i": 32, "k": 32, "j": 32})
    kern = codegen.compile(spec, sched, interpret=True)
    t = timeit(lambda: np.asarray(kern(a, b)), repeats=1)
    err = np.abs(np.asarray(kern(a, b)) - np.asarray(matmul_ref(a, b))).max()
    emit("kernel.gen.matmul", t, f"max_err={err:.2e};flops={2*m*k*n}")

    base = np.abs(
        np.asarray(
            matmul_pallas(a, b, block_m=32, block_n=32, block_k=32,
                          interpret=True)
        ) - np.asarray(kern(a, b))
    ).max()
    emit("kernel.gen.vs_handwritten", 0.0, f"max_err={base:.2e}")

    bsz = 2 if smoke else 4
    sb = codegen.batched_matmul_schedule(
        bsz, m // 2, k // 2, n // 2, block_m=16, block_n=16, block_k=16
    )
    ab = _rnd(bsz, m // 2, k // 2, seed=2)
    bb = _rnd(bsz, k // 2, n // 2, seed=3)
    kb = codegen.compile(sb.spec, sb, interpret=True)
    t = timeit(lambda: np.asarray(kb(ab, bb)), repeats=1)
    err = np.abs(
        np.asarray(kb(ab, bb))
        - np.einsum("bij,bjk->bik", np.asarray(ab), np.asarray(bb))
    ).max()
    emit("kernel.gen.batched", t,
         f"max_err={err:.2e};flops={2*bsz*(m//2)*(k//2)*(n//2)}")

    sc = codegen.chain_matmul_schedule(
        m // 2, k // 2, k // 2, n // 2,
        block_m=16, block_n=16, block_k1=16, block_k2=16,
    )
    ac, bc = _rnd(m // 2, k // 2, seed=4), _rnd(k // 2, k // 2, seed=5)
    cc = _rnd(k // 2, n // 2, seed=6)
    kc = codegen.compile(sc.spec, sc, interpret=True)
    t = timeit(lambda: np.asarray(kc(ac, bc, cc)), repeats=1)
    err = np.abs(
        np.asarray(kc(ac, bc, cc))
        - np.einsum("ij,jk,kl->il", *(np.asarray(x) for x in (ac, bc, cc)))
    ).max()
    chain_flops = 2 * (m // 2) * (k // 2) * (k // 2 + n // 2)
    emit("kernel.gen.chain", t, f"max_err={err:.2e};flops={chain_flops}")

    st = codegen.transposed_matmul_schedule(
        m // 2, k // 2, n // 2, block_m=16, block_n=16, block_k=16
    )
    at = _rnd(k // 2, m // 2, seed=7)
    bt = _rnd(k // 2, n // 2, seed=8)
    kt = codegen.compile(st.spec, st, interpret=True)
    t = timeit(lambda: np.asarray(kt(at, bt)), repeats=1)
    err = np.abs(
        np.asarray(kt(at, bt))
        - np.einsum("ji,jk->ik", np.asarray(at), np.asarray(bt))
    ).max()
    emit("kernel.gen.transposed", t,
         f"max_err={err:.2e};flops={2*(m//2)*(k//2)*(n//2)}")


@guarded("grad.dense")
def _bench_grad_dense(smoke: bool):
    """Training fwd+bwd through ops.dense's custom_vjp (repro.grad).

    The backward GEMMs are the derived ``matmul.dA``/``matmul.dB`` specs
    compiled through the same generated-kernel pipeline as the forward —
    128-aligned extents so dense's kernel dispatch fires in interpret mode.
    """
    import jax

    from repro import ops

    m = k = n = 128
    x, w = _rnd(m, k, seed=20), _rnd(k, n, seed=21)
    flops = 2 * m * k * n

    t_f = timeit(lambda: np.asarray(ops.dense(x, w, interpret=True)),
                 repeats=1)
    err_f = np.abs(
        np.asarray(ops.dense(x, w, interpret=True))
        - np.asarray(matmul_ref(x, w))
    ).max()
    emit("grad.dense.fwd", t_f, f"max_err={err_f:.2e};flops={flops}")

    grad_fn = jax.grad(
        lambda x_, w_: jnp.sum(ops.dense(x_, w_, interpret=True)),
        argnums=(0, 1),
    )
    t_b = timeit(
        lambda: [np.asarray(v) for v in grad_fn(x, w)], repeats=1
    )
    gx, gw = grad_fn(x, w)
    ones = np.ones((m, n), np.float32)
    err_b = max(
        np.abs(np.asarray(gx) - ones @ np.asarray(w).T).max(),
        np.abs(np.asarray(gw) - np.asarray(x).T @ ones).max(),
    )
    # grad_fn runs fwd + dA + dB: three GEMMs' worth of work
    emit("grad.dense.bwd", t_b, f"max_err={err_b:.2e};flops={3*flops}")


@guarded("grad.dense_act")
def _bench_grad_dense_act(smoke: bool):
    """Epilogue backward: recompute-acc GEMM + elementwise VJP + dA/dB."""
    import jax

    from repro import ops
    from repro.kernels.fused_dense_act.ref import fused_dense_act_ref

    m = d = f = 32 if smoke else 64
    x, w = _rnd(m, d, seed=22), _rnd(d, f, seed=23)
    beta, mean = _rnd(f, seed=24), _rnd(f, seed=25) * 0.1
    var = jnp.abs(_rnd(f, seed=26)) + 0.5

    grad_fn = jax.grad(
        lambda *a: jnp.sum(ops.dense_act(*a, interpret=True)),
        argnums=(0, 1, 2),
    )
    ref_fn = jax.grad(
        lambda *a: jnp.sum(fused_dense_act_ref(*a)), argnums=(0, 1, 2)
    )
    t = timeit(
        lambda: [np.asarray(v) for v in grad_fn(x, w, beta, mean, var)],
        repeats=1,
    )
    err = max(
        np.abs(np.asarray(a) - np.asarray(b)).max()
        for a, b in zip(grad_fn(x, w, beta, mean, var),
                        ref_fn(x, w, beta, mean, var))
    )
    # 4 GEMMs: primal fwd + accumulator recompute + dA + dB
    emit("grad.dense_act.bwd", t, f"max_err={err:.2e};flops={4*2*m*d*f}")


@guarded("grad.plandb")
def _bench_grad_plandb(smoke: bool):
    """Backward GEMMs picking up *searched* plans by derived-spec key.

    Sweeps fwd+dA+dB into a private plan DB (search_schedule_with_grads),
    then runs jax.grad through ops.dense and reports how many plan-DB
    lookups the tape hit — the ISSUE-3 acceptance bar, as a bench row.
    """
    import tempfile

    import jax

    from repro import ops
    from repro.grad import derived_specs
    from repro.search import default_plan_db, search_schedule_with_grads

    m = k = n = 128
    tmp = tempfile.mkdtemp(prefix="repro-grad-bench-")
    saved = {
        v: os.environ.get(v)
        for v in ("REPRO_PLAN_DB", "REPRO_AUTOTUNE_CACHE")
    }
    os.environ["REPRO_PLAN_DB"] = os.path.join(tmp, "plans.json")
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(tmp, "autotune.json")
    try:
        spec = matmul_spec(m, k, n)
        db = default_plan_db()
        t0 = time.perf_counter()
        res = search_schedule_with_grads(
            spec, dtype=np.float32, beam_width=4, topk=2,
            interpret=True, repeats=1, plan_db=db,
        )
        sweep_s = time.perf_counter() - t0
        keys_ok = all(
            db.best_schedule(s, np.float32) is not None
            for s in (spec, *derived_specs(spec).values())
        )
        hits0 = db.lookup_hits
        x, w = _rnd(m, k, seed=27), _rnd(k, n, seed=28)
        gx, gw = jax.grad(
            lambda a, b: jnp.sum(ops.dense(a, b, interpret=True)),
            argnums=(0, 1),
        )(x, w)
        hits = db.lookup_hits - hits0
        ones = np.ones((m, n), np.float32)
        err = max(
            np.abs(np.asarray(gx) - ones @ np.asarray(w).T).max(),
            np.abs(np.asarray(gw) - np.asarray(x).T @ ones).max(),
        )
        ok = keys_ok and hits >= 3 and err < 1e-3
        emit(
            "grad.plandb", sweep_s,
            f"ok={ok};plans={len(res)};db_hits={hits};max_err={err:.2e}",
        )
    finally:
        for v, val in saved.items():
            if val is None:
                os.environ.pop(v, None)
            else:
                os.environ[v] = val
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


def _mesh_shape_for_devices(n: int):
    """Largest conventional (data, model) mesh the process can host."""
    if n >= 8:
        return (2, 4)
    if n >= 4:
        return (2, 2)
    if n >= 2:
        return (1, 2)
    return None


@guarded("mesh.search")
def _bench_mesh_search(smoke: bool):
    """The mesh (distributed) tier of the search, end to end.

    Runs ``search_schedule`` with an active mesh shape over the forced
    device mesh (the mesh-smoke CI job forces 8 CPU devices via
    ``--xla_force_host_platform_device_count``), then reports:

      * ``mesh.search``  — the sharded winner: measured over the real
        mesh via ``codegen.bind_mesh``, differentially checked against
        the einsum oracle in the same pass; ``ok`` requires a ``mesh:*``
        plan in the ladder, measured, with a mesh-qualified DB key.
      * ``mesh.vs_psum`` — searched-sharded vs the naive plain-psum
        lowering of the same subdivision.  The naive baseline is part of
        the measured set (``search_schedule``'s mesh-naive entry), so
        ``not_slower`` holds by construction on this harness.

    Sections emit nothing when the process has fewer than 2 devices —
    the plain bench-smoke job runs single-device and only the mesh-smoke
    job (``scripts/bench_smoke.py --mesh``) gates on these rows.
    """
    import tempfile

    import jax

    from repro.search import PlanDB, reference_arrays, search_schedule

    shape = _mesh_shape_for_devices(jax.device_count())
    if shape is None:
        return
    import shutil

    s = 2 if smoke else 1
    m = k = n = 128 // s
    spec = matmul_spec(m, k, n)
    arrays = reference_arrays(spec, seed=7)
    tmp = tempfile.mkdtemp(prefix="repro-mesh-bench-")
    try:
        db = PlanDB(os.path.join(tmp, "plans.json"))
        res = search_schedule(
            spec, beam_width=6, topk=3, interpret=True, measure=True,
            arrays=arrays, plan_db=db, mesh_shape=shape,
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    st = res.stats
    win = res.best_sharded()
    measured = win is not None and win.measured_s is not None
    ok = (
        measured
        and res.db_key is not None
        and res.mesh == "x".join(map(str, shape))
    )
    err = win.max_err if win is not None else float("nan")
    emit(
        "mesh.search",
        win.measured_s if measured else 0.0,
        f"ok={ok};mesh={res.mesh};max_err={err:.2e};"
        f"candidates={st.considered};mesh_variants={st.mesh_variants};"
        f"pruned={st.pruned_bound + st.pruned_beam};"
        f"measured={st.measured};flops={spec.flops()}",
    )
    naive = res.mesh_baseline()
    if naive is None or naive.measured_s is None or not measured:
        # report the failure as a row rather than crash the section: the
        # --mesh gate fails on ok=False with this diagnostic attached
        emit(
            "mesh.vs_psum", 0.0,
            f"ok=False;not_slower=False;"
            f"sharded_measured={measured};"
            f"naive_measured={naive is not None and naive.measured_s is not None}",
        )
        return
    emit(
        "mesh.vs_psum", naive.measured_s,
        f"ok=True;"
        f"not_slower={win.measured_s <= naive.measured_s};"
        f"sharded_s={win.measured_s:.3g};naive_s={naive.measured_s:.3g}",
    )


@guarded("mesh.ring")
def _bench_mesh_ring(smoke: bool):
    """Ring (ppermute) all-reduce vs lax.psum: equality + relative cost.

    The ring strategy is what a searched plan with ``collective=ring``
    lowers to (``codegen.collectives.ring_psum``); the row pins its
    differential correctness against psum on an odd-sized payload (the
    remainder-shard path) over the largest hostable device ring.
    """
    import jax
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.codegen.collectives import ring_psum
    from repro.launch.mesh import make_debug_mesh

    p = min(jax.device_count(), 8)
    if p < 2:
        return
    mesh = make_debug_mesh((p,), ("data",))
    rows = 3 if smoke else 5  # odd payload: exercises the padded shard
    x = _rnd(p, rows, 33, seed=11)

    def run_with(fn):
        f = shard_map(
            lambda xs: fn(xs[0]), mesh=mesh,
            in_specs=P("data"), out_specs=P(), check_vma=False,
        )
        return f(x)

    ring_s = timeit(lambda: np.asarray(run_with(
        lambda v: ring_psum(v, "data"))), repeats=2)
    psum_s = timeit(lambda: np.asarray(run_with(
        lambda v: lax.psum(v, "data"))), repeats=2)
    got = np.asarray(run_with(lambda v: ring_psum(v, "data")))
    want = np.asarray(run_with(lambda v: lax.psum(v, "data")))
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    emit(
        "mesh.ring", ring_s,
        f"ok={err < 1e-5};max_err={err:.2e};shards={p};"
        f"psum_s={psum_s:.3g}",
    )


@guarded("capture.sites")
def _bench_capture_sites(smoke: bool):
    """Whole-model capture accounting per demo config (repro.capture).

    Abstract harvest (ShapeDtypeStruct trace — no params allocated, no
    kernels run): one row per config counting dot_general sites
    harvested / dispatched / fallback, the ISSUE-4 acceptance counters.
    The reported time is the trace+harvest cost itself.
    """
    import time as _time

    from repro import capture

    for name, cfg in sorted(capture.demo_configs().items()):
        t0 = _time.perf_counter()
        _, rep = capture.model_capture(
            cfg, batch=capture.DEMO_BATCH, seq=capture.DEMO_SEQ,
            kind="train", interpret=True,
        )
        t = _time.perf_counter() - t0
        emit(
            f"capture.sites.{name}", t,
            f"harvested={rep.harvested};dispatched={rep.dispatched};"
            f"fallback={rep.fallback}",
        )


@guarded("capture.step")
def _bench_capture_step(smoke: bool):
    """End-to-end jitted train-loss step: captured vs uncaptured.

    interpret=False on CPU means every site falls back, so the two jitted
    programs are semantically identical — the row measures the capture
    replay's compile-through overhead, which must stay ~1x (the no-op
    safety bar for turning ``--capture`` on in production).  Dispatch
    counters live in the ``capture.sites.*`` rows above.
    """
    import jax

    from repro import capture
    from repro.models.api import get_api

    cfg = capture.demo_configs()["dense"]
    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.key(0))
    B, S = capture.DEMO_BATCH, capture.DEMO_SEQ
    toks = jnp.zeros((B, S), jnp.int32)
    batch = {"tokens": toks, "labels": toks}

    def loss(p, b):
        return api.loss(p, cfg, b)

    captured = capture.optimize(loss, interpret=False)
    base_fn = jax.jit(loss)
    cap_fn = jax.jit(captured)
    base_s = timeit(lambda: float(base_fn(params, batch)), repeats=2)
    cap_s = timeit(lambda: float(cap_fn(params, batch)), repeats=2)
    err = abs(
        float(cap_fn(params, batch)) - float(base_fn(params, batch))
    )
    emit(
        "capture.step", cap_s,
        f"max_err={err:.2e};baseline_s={base_s:.3g};"
        f"ratio={cap_s / max(base_s, 1e-12):.3g}",
    )


@guarded("obs.overhead")
def _bench_obs(smoke: bool):
    """Observability overhead gate: obs-on vs obs-off on a hot kernel call.

    Times the same memoized ``ops.dense`` dispatch (the serving hot path:
    plan-DB consult + kernel-memo lookup + generated kernel) with
    ``REPRO_OBS`` off and on.  The instrumentation on that path is a few
    env reads and counter increments, so the min-over-repeats ratio must
    stay <= 1.02 — ``scripts/bench_smoke.py`` gates on it, which is what
    keeps obs safe to leave on by default in production.
    """
    from repro import ops

    n = 128
    x, w = _rnd(n, n, seed=0), _rnd(n, n, seed=1)

    def call():
        return np.asarray(ops.dense(x, w, interpret=True))

    call()  # tune + compile once: both arms time the memoized path

    prev = os.environ.get("REPRO_OBS")
    try:
        os.environ["REPRO_OBS"] = "0"
        off_s = timeit(call, repeats=5, warmup=1)
        os.environ["REPRO_OBS"] = "1"
        on_s = timeit(call, repeats=5, warmup=1)
    finally:
        if prev is None:
            os.environ.pop("REPRO_OBS", None)
        else:
            os.environ["REPRO_OBS"] = prev
    ratio = on_s / max(off_s, 1e-12)
    emit(
        "obs.overhead", on_s,
        f"baseline_s={off_s:.3g};ratio={ratio:.3g};flops={2 * n**3}",
    )


@guarded("attn.fused")
def _bench_attn_fused(smoke: bool):
    """Fused flash-attention kernel vs the unfused program it replaces.

    The baseline is exactly what capture would otherwise leave in the
    jaxpr: TWO generated interpret-mode GEMM kernels (QK^T and P·V,
    compiled through the same ``codegen`` pipeline) around a jnp softmax.
    Wall-clock here is interpret-mode correctness only (header note) — a
    Python-interpreted softmax inside the fused kernel can never beat an
    XLA-compiled one outside it — so ``not_slower`` is the same analytic
    HBM-traffic claim the ``kernel.matmul.b*`` rows make: the fused
    kernel reads Q/K/V and writes O once, while the unfused program
    additionally round-trips the (h,s,t) score AND probability tensors
    through HBM.  Fused bytes < unfused bytes for every shape, so the
    gate holds by construction and is the statement that matters on the
    real chip; both interpret times are reported alongside for the
    correctness record.
    """
    from repro import codegen, ops
    from repro.core.enumerate import ContractionSpec, attention_spec
    from repro.search import einsum_reference

    s_ = 2 if smoke else 1
    h, s, t, d = 4, 128 // s_, 128 // s_, 8
    spec = attention_spec(h, s, t, d)
    q, k, v = (_rnd(h, n, d, seed=30 + i)
               for i, n in enumerate((s, t, t)))

    def fused():
        return np.asarray(ops.attention(q, k, v, interpret=True,
                                        differentiable=False))

    qk = ContractionSpec(
        name="qk", operands={"Q": ("h", "s", "d"), "K": ("h", "t", "d")},
        output=("h", "s", "t"), extents={"h": h, "s": s, "t": t, "d": d},
    )
    pv = ContractionSpec(
        name="pv", operands={"P": ("h", "s", "t"), "V": ("h", "t", "e")},
        output=("h", "s", "e"), extents={"h": h, "s": s, "t": t, "e": d},
    )
    k1 = codegen.compile(qk, codegen.default_schedule(qk), interpret=True)
    k2 = codegen.compile(pv, codegen.default_schedule(pv), interpret=True)
    import jax

    @jax.jit
    def _softmax(sc):
        return jax.nn.softmax(sc * d ** -0.5, axis=-1)

    def unfused():
        p = _softmax(k1(q, k))
        return np.asarray(k2(p, v))

    fused_s = timeit(fused, repeats=3, warmup=1)
    base_s = timeit(unfused, repeats=3, warmup=1)
    ref = einsum_reference(spec, {"Q": np.asarray(q), "K": np.asarray(k),
                                  "V": np.asarray(v)})
    err = max(
        np.abs(fused() - ref).max(), np.abs(unfused() - ref).max()
    )
    # analytic HBM roofline (f32): fused streams operands + output once;
    # unfused also writes then re-reads scores and probabilities
    io = h * (s * d + t * d + t * d + s * d)
    scores = h * s * t
    fused_hbm_s = io * 4 / TPU["hbm_bw"]
    base_hbm_s = (io + 4 * scores) * 4 / TPU["hbm_bw"]
    emit(
        "attn.fused", fused_s,
        f"not_slower={fused_hbm_s <= base_hbm_s};max_err={err:.2e};"
        f"hbm_s={fused_hbm_s:.3g};baseline_hbm_s={base_hbm_s:.3g};"
        f"interpret_baseline_s={base_s:.3g};flops={spec.flops()}",
    )


@guarded("moe.grouped")
def _bench_moe_grouped(smoke: bool):
    """Ragged grouped GEMM: one group-offset dispatch vs G separate dots.

    The baseline is the semantic definition (per-group dot loop, one
    dispatch per non-empty group); the row's gate is correctness (ok= +
    max_err) on a genuinely ragged partition with an empty group, not a
    speed claim — interpret mode cannot see the dispatch-count win.
    """
    from jax import lax

    from repro import ops
    from repro.core.enumerate import grouped_matmul_spec

    s_ = 2 if smoke else 1
    k_, f = 64 // s_, 64 // s_
    sizes = (24 // s_, 0, 40 // s_, 8 // s_)
    n = sum(sizes)
    spec = grouped_matmul_spec(sizes, k_, f)
    x = _rnd(n, k_, seed=40)
    w = _rnd(len(sizes), k_, f, seed=41)

    def grouped():
        return np.asarray(ops.grouped_dense(x, w, sizes, interpret=True,
                                            differentiable=False))

    def loop():
        parts, off = [], 0
        for g, sz in enumerate(sizes):
            if sz:
                parts.append(lax.dot_general(
                    x[off:off + sz], w[g], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ))
            off += sz
        return np.asarray(jnp.concatenate(parts, axis=0))

    t_g = timeit(grouped, repeats=3, warmup=1)
    t_l = timeit(loop, repeats=3, warmup=1)
    err = np.abs(grouped().astype(np.float64) - loop()).max()
    ok = err < 1e-3
    emit(
        "moe.grouped", t_g,
        f"ok={ok};max_err={err:.2e};loop_s={t_l:.3g};"
        f"groups={len(sizes)};flops={spec.flops()}",
    )


@guarded("quant.gemm")
def _bench_quant(smoke: bool):
    """Searched int8/fp8 quant tier vs the bf16 baseline at matched shapes.

    Wall-clock here is interpret-mode correctness only (header note) — a
    CPU interpreter cannot see the bandwidth win of 1-byte operands — so
    the rows' reported seconds are the analytic one-pass HBM floor
    (``roofline.analysis.quant_hbm_bytes`` / TPU hbm_bw), exactly like
    the ``kernel.matmul.b*`` rows.  With the same ``flops=`` on every
    row, ``BENCH_quant.json`` then reports analytic GFLOP/s, and the
    ISSUE-10 gate "quant GFLOP/s >= bf16 at matched shapes" is the
    ``not_slower`` byte claim: quantized operands stream at 1 B/elem vs
    bf16's 2, and the 4-byte accumulator output cannot eat the saving at
    these shapes.  Correctness is NOT analytic: each quant row runs the
    searched ladder (``search_schedule`` over the quantized spec,
    measure=True) and reports the kernel-vs-dequantized-f64-oracle
    ``max_err`` — exact for int8 (integer products, exact accumulation),
    f32-accumulation-bounded for fp8.  ``quant.dense`` adds the
    end-to-end ``ops.dense(..., quant=)`` path, where dynamic input
    quantization error is charged to the data, hence a relative (not
    max_err) gate.
    """
    from repro import ops
    from repro.core.enumerate import QUANT_FORMATS, quantize_spec
    from repro.roofline.analysis import quant_hbm_bytes
    from repro.search import reference_arrays, search_schedule

    s_ = 2 if smoke else 1
    # reduction-dominant shape: operand traffic (saved by 1-byte storage)
    # must dominate the 4-byte accumulator output, else the byte floors
    # tie exactly — on a cube, 2N²·1 + N²·4 == 2N²·2 + N²·2.  k >> m, n
    # is also the regime the tier serves (weight GEMMs).
    m = n = 128 // s_
    k = 2048 // s_
    base = matmul_spec(m, k, n)
    flops = base.flops()
    # matched-shape bf16 baseline: same one-pass floor at 2 B/elem
    bf16_bytes = quant_hbm_bytes(base, elem_bytes=2)
    bf16_hbm_s = bf16_bytes / TPU["hbm_bw"]
    emit(
        "quant.bf16", bf16_hbm_s,
        f"ok=True;hbm_bytes={bf16_bytes:.0f};flops={flops}",
    )

    for fmt in ("int8", "fp8"):
        spec = quantize_spec(base, fmt=fmt)
        dt = np.dtype(QUANT_FORMATS[fmt].dtype)
        arrays = reference_arrays(spec, dtype=dt, seed=50)
        res = search_schedule(
            spec, dtype=dt, beam_width=4, topk=2, interpret=True,
            measure=True, arrays=arrays, plan_db=None,
        )
        win = res.best
        if win.measured_s is None or win.max_err is None:
            raise RuntimeError(f"quant {fmt} winner was not measured")
        qbytes = quant_hbm_bytes(spec)
        hbm_s = qbytes / TPU["hbm_bw"]
        emit(
            f"quant.{fmt}", hbm_s,
            f"ok=True;not_slower={hbm_s <= bf16_hbm_s};"
            f"max_err={win.max_err:.2e};hbm_bytes={qbytes:.0f};"
            f"bf16_hbm_s={bf16_hbm_s:.3g};"
            f"interpret_s={win.measured_s:.3g};flops={flops}",
        )

    # end-to-end: ops.dense with dynamic input quantization (the capture /
    # serving entry point).  128-aligned so the kernel dispatch fires.
    me = ke = ne = 128
    x, w = _rnd(me, ke, seed=52), _rnd(ke, ne, seed=53)
    ref = np.asarray(ops.dense(x, w, interpret=True), np.float64)
    t_q = timeit(
        lambda: np.asarray(ops.dense(x, w, interpret=True, quant="int8")),
        repeats=1,
    )
    out = np.asarray(
        ops.dense(x, w, interpret=True, quant="int8"), np.float64
    )
    rel = np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30)
    emit(
        "quant.dense", t_q,
        f"ok={rel < 0.05};rel_err={rel:.2e};flops={2 * me * ke * ne}",
    )


def run_attn(smoke: bool = False):
    """The --attn sections alone (the attn-smoke CI job's bench half)."""
    _bench_attn_fused(smoke)
    _bench_moe_grouped(smoke)


def run_quant(smoke: bool = False):
    """The --quant sections alone (the quant-smoke CI job's bench half)."""
    _bench_quant(smoke)


def run(smoke: bool = False):
    m = n = k = 4096
    cands = [
        (128, 128, 512), (256, 256, 512), (512, 512, 512),
        (512, 1024, 512), (1024, 512, 512), (256, 512, 1024),
    ]
    budget = TPU["vmem_bytes"] // 2 // 2
    for bm, bn, bk in cands:
        fits = (bm * bk + bk * bn + bm * bn) <= budget
        tr = traffic(m, n, k, bm, bn, bk)
        hbm_s = tr * 2 / TPU["hbm_bw"]
        emit(
            f"kernel.matmul.b{bm}x{bn}x{bk}", hbm_s,
            f"hbm_bytes={tr*2:.3g};fits_vmem={fits}",
        )
    best = choose_matmul_blocks(m, n, k, elem_bytes=2)
    emit("kernel.matmul.autotuned", 0.0, f"blocks={best}")

    # interpret-mode correctness spot-check at a scaled-down shape
    a = _rnd(128, 128, seed=0)
    b = _rnd(128, 128, seed=1)
    t = timeit(
        lambda: np.asarray(
            matmul_pallas(a, b, block_m=64, block_n=64, block_k=64,
                          interpret=True)
        ),
        repeats=1,
    )
    err = np.abs(
        np.asarray(
            matmul_pallas(a, b, block_m=64, block_n=64, block_k=64,
                          interpret=True)
        ) - np.asarray(matmul_ref(a, b))
    ).max()
    emit("kernel.matmul.interpret_check", t, f"max_err={err:.2e}")

    _bench_generated(smoke)
    _bench_search(smoke)
    _bench_mesh_search(smoke)
    _bench_mesh_ring(smoke)
    _bench_grad_dense(smoke)
    _bench_grad_dense_act(smoke)
    _bench_grad_plandb(smoke)
    _bench_capture_sites(smoke)
    _bench_capture_step(smoke)
    _bench_obs(smoke)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI")
    ap.add_argument("--attn", action="store_true",
                    help="run only the fused attention + grouped-GEMM "
                         "sections (the attn-smoke CI job)")
    ap.add_argument("--quant", action="store_true",
                    help="run only the int8/fp8 quant-tier sections "
                         "(the quant-smoke CI job)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.attn:
        run_attn(smoke=args.smoke)
    elif args.quant:
        run_quant(smoke=args.smoke)
    else:
        run(smoke=args.smoke)
