"""Serving-tier correctness: paged KV, continuous batching, phase ladders.

The load-bearing guarantees pinned here:

* **paged mapping** — the block-table gather presents pages in list
  order, and the post-step scatter lands the appended KV row on exactly
  page ``pages[len // page_size]``, offset ``len % page_size``.
* **batched == solo** — right-padded prefill with a length mask means a
  short prompt batched with longer ones produces bitwise-identical
  greedy tokens to running it alone (the padding-leak regression).
* **continuous == fixed == solo** — the differential acceptance test:
  all three execution strategies agree per request.
* **preemption is exact** — recompute-style eviction under page pressure
  yields the same tokens as an uninterrupted run.
* **accounting** — tok/s counts decode-produced tokens over decode time
  only, no trailing wasted dispatch, max_new=0 requests still observe
  latency and count as served, EOS finishes both engines early.
* **phase ladders** — plan keys gain a phase qualifier without
  perturbing existing (unphased) keys, and ``ops._tuned_kernel``
  consults the active phase's ladder first.
"""

from __future__ import annotations

import numpy as np
import pytest

pytest.importorskip("jax")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.serve import BatchServer, Request  # noqa: E402
from repro.launch.serving import (  # noqa: E402
    ContinuousEngine,
    FixedEngine,
    Gateway,
    PagePool,
    Scheduler,
    ServeRequest,
    synthetic_trace,
)
from repro.launch.serving import paged, runners  # noqa: E402
from repro.models import layers, transformer  # noqa: E402
from repro.models.api import get_api  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    obs.metrics_reset()
    yield
    obs.metrics_reset()


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen3-8b").smoke()


@pytest.fixture(scope="module")
def solo_server(cfg):
    return BatchServer(cfg, batch_size=1, max_len=16)


def _prompt(rng, n, vocab):
    return rng.integers(0, vocab, size=n).astype(np.int32)


def _solo_tokens(solo_server, prompt, max_new, eos_id=None):
    req = Request(rid=0, prompt=prompt, max_new=max_new)
    solo_server.run([req], eos_id=eos_id)
    return req.out_tokens


# --------------------------------------------------------------------------
# page pool + scheduler (pure host-side units)
# --------------------------------------------------------------------------


class TestPagePool:
    def test_alloc_free_roundtrip(self):
        pool = PagePool(8, page_size=4)
        assert pool.capacity == 7
        got = pool.alloc(3)
        assert len(got) == 3 and paged.SINK_PAGE not in got
        assert pool.free_count == 4
        # an unsatisfiable alloc takes nothing
        assert pool.alloc(5) is None and pool.free_count == 4
        pool.free(got)
        assert pool.free_count == 7

    def test_double_free_rejected(self):
        pool = PagePool(4, page_size=2)
        got = pool.alloc(1)
        pool.free(got)
        with pytest.raises(ValueError, match="double free"):
            pool.free(got)

    def test_sink_page_never_allocated(self):
        pool = PagePool(4, page_size=2)
        assert paged.SINK_PAGE not in pool.alloc(3)

    def test_pages_for(self):
        pool = PagePool(4, page_size=4)
        assert pool.pages_for(0) == 1       # even an empty ctx owns a page
        assert pool.pages_for(4) == 1
        assert pool.pages_for(5) == 2


def _sreq(rid, plen, max_new):
    return ServeRequest(
        rid=rid, prompt=np.zeros(plen, np.int32), max_new=max_new
    )


class TestScheduler:
    def test_fcfs_admission_respects_watermark(self):
        sched = Scheduler(PagePool(10, 2), lanes=4, watermark=4)
        for i in range(3):
            sched.submit(_sreq(i, plen=4, max_new=2))   # 2 pages each
        admitted = sched.admit()
        # 9 free: req0 -> 7 spare, req1 -> 5 spare, req2 would leave 3 < 4
        assert [r.rid for r in admitted] == [0, 1]
        assert [r.rid for r in sched.queue] == [2]     # head-of-line waits

    def test_progress_guarantee_overrides_watermark_when_idle(self):
        sched = Scheduler(PagePool(4, 2), lanes=1, watermark=100)
        sched.submit(_sreq(0, plen=4, max_new=1))
        assert [r.rid for r in sched.admit()] == [0]

    def test_grow_preempts_newest_and_requeues_at_head(self):
        pool = PagePool(4, 2)                          # 3 usable pages
        sched = Scheduler(pool, lanes=2, watermark=0)
        sched.submit(_sreq(0, plen=2, max_new=4))
        sched.submit(_sreq(1, plen=2, max_new=4))
        old, new = sched.admit()
        # both generated 2 tokens -> both now need a second page
        for r in (old, new):
            r.out_tokens = [1, 2]
        preempted = sched.grow()
        assert preempted == [new]
        assert new.state == "queued" and new.pages == [] and new.lane == -1
        assert new.preemptions == 1
        assert sched.queue[0] is new                   # FCFS head, not tail
        assert len(old.pages) == 2                     # oldest got the page

    def test_finish_releases_lane_and_pages_immediately(self):
        pool = PagePool(4, 2)
        sched = Scheduler(pool, lanes=1, watermark=0)
        sched.submit(_sreq(0, plen=2, max_new=1))
        (req,) = sched.admit()
        before = pool.free_count
        sched.finish(req)
        assert pool.free_count == before + 1
        assert req.state == "finished" and not sched.running

    def test_oversized_request_rejected_at_submit(self):
        sched = Scheduler(PagePool(3, 2), lanes=1)
        with pytest.raises(ValueError, match="pages"):
            sched.submit(_sreq(0, plen=8, max_new=8))


# --------------------------------------------------------------------------
# paged read/write mapping, and the paged decode step
# --------------------------------------------------------------------------


def test_paged_view_and_scatter_mapping(cfg):
    page_size = 2
    pools = paged.pool_init(cfg, n_pages=5, page_size=page_size)

    def stamp(leaf):
        # value at (page p, slot s) = 100p + s, broadcast over other axes
        L, P, ps, kv, hd = leaf.shape
        vals = (100 * jnp.arange(P)[:, None] + jnp.arange(ps)[None, :])
        return jnp.broadcast_to(
            vals[None, :, :, None, None].astype(leaf.dtype), leaf.shape
        )

    pools = jax.tree.map(stamp, pools)
    bt = jnp.asarray([[3, 1], [2, 0]], jnp.int32)
    lens = jnp.asarray([3, 1], jnp.int32)

    seg = next(iter(pools))
    kind = next(iter(pools[seg]))
    k = layers.read_pages(pools[seg][kind]["k"], 0, bt)
    # lane 0's view is page 3 then page 1, in block-table order
    np.testing.assert_array_equal(
        np.asarray(k[0, :, 0, 0]), [300.0, 301.0, 100.0, 101.0]
    )
    np.testing.assert_array_equal(
        np.asarray(k[1, :, 0, 0]), [200.0, 201.0, 0.0, 1.0]
    )

    # the row each lane appended at position lens, for every layer
    rows = jax.tree.map(
        lambda p: jnp.broadcast_to(
            jnp.asarray([777.0, 888.0], p.dtype)[None, :, None, None],
            (p.shape[0], 2) + p.shape[3:]),
        pools,
    )
    pools2 = paged.scatter_token(pools, rows, bt, lens, page_size)
    k2 = pools2[seg][kind]["k"]
    # lane 0: position 3 -> page bt[0, 1]=1, offset 1
    assert float(k2[0, 1, 1, 0, 0]) == 777.0
    # lane 1: position 1 -> page bt[1, 0]=2, offset 1
    assert float(k2[0, 2, 1, 0, 0]) == 888.0
    # untouched slots keep their stamp
    assert float(k2[0, 3, 0, 0, 0]) == 300.0


PS, MAX_PAGES, LANES = 4, 4, 4     # max_ctx 16, pages 1..16 for the lanes

#: (lens, live) per lane; a lane that is not live points at the sink
DECODE_CASES = {
    # lane 0 starts a fresh page
    "page_boundary": ([8, 5, 0, 3], [True, True, False, True]),
    # lane 1 writes the last position of its last page
    "max_ctx_minus_1": ([6, 15, 1, 0], [True, True, True, False]),
    # three idle lanes write their garbage onto the sink together
    "idle_sink": ([0, 6, 0, 0], [False, True, False, False]),
}


def _decode_inputs(cfg, case, seed=0):
    lens, live = DECODE_CASES[case]
    rng = np.random.default_rng(seed)
    pages = rng.permutation(np.arange(1, 1 + LANES * MAX_PAGES))
    bt = np.zeros((LANES, MAX_PAGES), np.int32)
    toks = np.zeros((LANES,), np.int32)
    for lane, (n, on) in enumerate(zip(lens, live)):
        if on:
            need = n // PS + 1      # the page the step writes is allocated
            bt[lane, :need] = pages[lane * MAX_PAGES:lane * MAX_PAGES + need]
            toks[lane] = rng.integers(1, cfg.vocab)
    pools = paged.pool_init(cfg, 1 + LANES * MAX_PAGES, PS)
    keys = iter(jax.random.split(jax.random.key(seed),
                                 len(jax.tree.leaves(pools))))
    pools = jax.tree.map(
        lambda p: jax.random.normal(next(keys), p.shape).astype(p.dtype), pools
    )
    return pools, jnp.asarray(bt), jnp.asarray(lens, jnp.int32), jnp.asarray(toks)


def _dense_composition(params, cfg, pools, bt, lens, toks):
    """The decode step as gather -> ``decode_step`` -> scatter: every
    layer's pages gathered into a dense cache, the model's dense-cache
    decode, and each lane's new row picked out of the new cache."""
    lanes, max_pages = bt.shape
    caches = jax.tree.map(
        lambda p: p[:, bt].reshape(p.shape[0], lanes, max_pages * p.shape[2],
                                   *p.shape[3:]), pools)
    for kinds in caches.values():
        for c in kinds.values():
            c["len"] = jnp.broadcast_to(
                lens, (c["k"].shape[0], lanes)).astype(jnp.int32)
    logits, new = transformer.decode_step(params, cfg, caches, toks[:, None])
    lane = jnp.arange(lanes)
    page_of, off = bt[lane, lens // PS], lens % PS
    out = {
        seg: {kind: {n: pv[n].at[:, page_of, off].set(
            new[seg][kind][n][:, lane, lens]) for n in ("k", "v")}
            for kind, pv in kinds.items()}
        for seg, kinds in pools.items()
    }
    return logits, out


@pytest.fixture(scope="module", params=["dense", "moe-interleaved",
                                        "moe-segments"])
def decode_model(request):
    arch = {"dense": "qwen3-8b", "moe-interleaved": "llama4-maverick-400b-a17b",
            "moe-segments": "kimi-k2-1t-a32b"}[request.param]
    c = get_config(arch).smoke()
    params, _ = transformer.init(c, jax.random.key(1))
    return c, params


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_paged_decode_step_equals_the_dense_composition(
        decode_model, case, monkeypatch):
    """One decode step straight from the pages gives the logits and
    tokens of gather -> dense decode -> scatter, and bit-identical pages,
    through the model's entry and through the runner's donated step."""
    c, params = decode_model
    pools, bt, lens, toks = _decode_inputs(c, case)
    want_logits, want_pools = _dense_composition(params, c, pools, bt, lens,
                                                 toks)

    logits, rows = transformer.paged_decode_step(params, c, pools, bt, lens,
                                                 toks[:, None])
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    got = paged.scatter_token(pools, rows, bt, lens, PS)
    jax.tree.map(np.testing.assert_array_equal, got, want_pools)

    monkeypatch.setattr(runners, "_in_place", lambda: True)
    runner = runners.DecodeRunner(c, get_api(c), PS, LANES, MAX_PAGES)
    tok, got = runner(params, jax.tree.map(jnp.copy, pools), bt, lens, toks)
    np.testing.assert_array_equal(
        np.asarray(tok), np.asarray(jnp.argmax(want_logits[:, -1], axis=-1)))
    jax.tree.map(np.testing.assert_array_equal, got, want_pools)


def test_the_decode_step_donates_the_pools_and_builds_no_dense_view(
        cfg, monkeypatch):
    """The step the chip runs, lowered: every pool leaf is donated, no
    array has the all-layer view's shape, and the only outputs are the
    tokens and the pools.  The prefill store donates the pools too."""
    monkeypatch.setattr(runners, "_in_place", lambda: True)
    lanes, max_pages, ps = 3, 5, 4
    pools = paged.pool_init(cfg, 1 + lanes * max_pages, ps)
    params, _ = transformer.init(cfg, jax.random.key(0))
    api = get_api(cfg)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    step = runners.DecodeRunner(cfg, api, ps, lanes, max_pages)._step.lower(
        params, pools, i32(lanes, max_pages), i32(lanes), i32(lanes))

    n_pools = len(jax.tree.leaves(pools))
    donated = jax.tree.leaves(step.args_info[0][1],
                              is_leaf=lambda a: hasattr(a, "donated"))
    assert len(donated) == n_pools and all(a.donated for a in donated)
    text = step.as_text()
    assert text.count("tf.aliasing_output") == n_pools
    L = cfg.n_layers
    view = f"tensor<{L}x{lanes}x{max_pages * ps}x{cfg.n_kv_heads}x{cfg.hd}x"
    assert view not in text
    outs = [tuple(o.shape) for o in jax.tree.leaves(step.out_info)]
    assert sorted(outs) == sorted([(lanes,)] + [
        p.shape for p in jax.tree.leaves(pools)])

    pre = runners.PrefillRunner(cfg, api, ps)
    caches = transformer.cache_init(cfg, 1, 2 * ps)
    store = pre._store.lower(pools, caches, i32(2))
    assert store.as_text().count("tf.aliasing_output") == n_pools


# --------------------------------------------------------------------------
# padding leak: batched mixed lengths == solo (fixed server)
# --------------------------------------------------------------------------


def test_batched_mixed_lengths_equals_solo(cfg, solo_server):
    rng = np.random.default_rng(0)
    prompts = [_prompt(rng, n, cfg.vocab) for n in (3, 9, 5)]
    max_new = 4
    server = BatchServer(cfg, batch_size=3, max_len=16)
    batch = [
        Request(rid=i, prompt=p, max_new=max_new)
        for i, p in enumerate(prompts)
    ]
    server.run(batch)
    for i, p in enumerate(prompts):
        assert batch[i].out_tokens == _solo_tokens(solo_server, p, max_new), (
            f"request {i} (prompt len {len(p)}) decoded differently "
            "batched with longer prompts than solo — padding is leaking "
            "into attention"
        )


def test_prefill_lengths_mask_matches_unpadded(cfg):
    """Model-level: a right-padded prefill with lengths equals the
    unpadded prefill on logits AND on the cache contents it will serve."""
    from repro.models.api import get_api

    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(1)
    prompt = _prompt(rng, 5, cfg.vocab)

    lg_solo, _ = api.prefill(
        params, cfg, {"tokens": jnp.asarray(prompt[None, :])}, 12
    )
    padded = np.zeros((1, 9), np.int32)
    padded[0, :5] = prompt
    lg_masked, _ = api.prefill(
        params, cfg,
        {"tokens": jnp.asarray(padded),
         "lengths": jnp.asarray([5], jnp.int32)},
        12,
    )
    np.testing.assert_allclose(
        np.asarray(lg_solo[0, -1]), np.asarray(lg_masked[0, -1]),
        rtol=2e-5, atol=2e-5,
    )


# --------------------------------------------------------------------------
# engine differential: continuous == fixed == solo
# --------------------------------------------------------------------------


def test_continuous_equals_fixed_equals_solo(cfg, solo_server):
    t_cont = synthetic_trace(5, vocab=cfg.vocab, seed=3, rate_hz=0.0,
                             prompt_lens=(3, 5, 9), max_news=(2, 5))
    t_fixed = synthetic_trace(5, vocab=cfg.vocab, seed=3, rate_hz=0.0,
                              prompt_lens=(3, 5, 9), max_news=(2, 5))
    eng = ContinuousEngine(cfg, lanes=2, page_size=4, n_pages=13, max_ctx=16)
    st = Gateway(eng).run(t_cont)
    fst = FixedEngine(cfg, lanes=2, max_ctx=16).run(t_fixed)

    for a, b in zip(t_cont, t_fixed):
        assert a.out_tokens == b.out_tokens, (
            f"request {a.rid}: continuous {a.out_tokens} != fixed "
            f"{b.out_tokens}"
        )
    for r in t_cont:
        assert r.out_tokens == _solo_tokens(
            solo_server, r.prompt, r.max_new
        ), f"request {r.rid} differs from solo execution"

    assert st["tokens"] == sum(r.max_new for r in t_cont)
    assert fst["tokens"] == st["tokens"]
    # every request produced exactly one prefill-credited token
    assert st["prefill_tokens"] == len(t_cont)


def test_preemption_recompute_is_deterministic(cfg):
    def mk():
        rng = np.random.default_rng(7)
        return [
            ServeRequest(rid=i, prompt=_prompt(rng, 4, cfg.vocab), max_new=8)
            for i in range(3)
        ]

    starved, roomy = mk(), mk()
    st = ContinuousEngine(
        cfg, lanes=3, page_size=2, n_pages=10, max_ctx=12, watermark=0
    ).run(starved)
    assert st["preemptions"] > 0, "pool was sized to force preemption"
    ContinuousEngine(cfg, lanes=3, page_size=2, n_pages=40, max_ctx=12).run(
        roomy
    )
    for a, b in zip(starved, roomy):
        assert a.out_tokens == b.out_tokens, (
            f"request {a.rid}: preempted run {a.out_tokens} != "
            f"uninterrupted {b.out_tokens} — recompute is not exact"
        )


# --------------------------------------------------------------------------
# accounting, max_new=0, EOS
# --------------------------------------------------------------------------


def test_throughput_counts_decode_tokens_only(cfg):
    rng = np.random.default_rng(2)
    server = BatchServer(cfg, batch_size=2, max_len=16)
    reqs = [
        Request(rid=i, prompt=_prompt(rng, 4, cfg.vocab), max_new=5)
        for i in range(2)
    ]
    stats = server.run(reqs)
    assert stats["tokens"] == 10
    # the first token per request came from prefill logits
    assert stats["decode_tokens"] == 8
    # 4 decode dispatches produce tokens 2..5; the loop must not run a
    # 5th, wasted, dispatch after the final emit
    assert stats["decode_steps"] == 4
    assert stats["tok_per_s"] == pytest.approx(
        stats["decode_tokens"] / stats["decode_s"]
    )
    j = obs.metrics_json()
    assert j["counters"]["serve.tokens"] == 10
    assert j["counters"]["serve.requests"] == 2
    assert j["gauges"]["serve.tok_per_s"] == pytest.approx(stats["tok_per_s"])
    assert j["histograms"]["serve.request_latency_s"]["count"] == 2


def test_max_new_zero_fixed_server(cfg):
    rng = np.random.default_rng(3)
    server = BatchServer(cfg, batch_size=2, max_len=8)
    reqs = [
        Request(rid=0, prompt=_prompt(rng, 3, cfg.vocab), max_new=0),
        Request(rid=1, prompt=_prompt(rng, 3, cfg.vocab), max_new=2),
    ]
    stats = server.run(reqs)
    assert reqs[0].done and reqs[0].out_tokens == []
    assert reqs[1].done and len(reqs[1].out_tokens) == 2
    j = obs.metrics_json()
    # the zero-budget request is served, counted, and its latency observed
    assert j["counters"]["serve.requests"] == 2
    assert j["histograms"]["serve.request_latency_s"]["count"] == 2
    assert stats["tokens"] == 2

    # all-zero batch: not a single decode dispatch
    obs.metrics_reset()
    reqs = [
        Request(rid=i, prompt=_prompt(rng, 3, cfg.vocab), max_new=0)
        for i in range(2)
    ]
    stats = server.run(reqs)
    assert stats["decode_steps"] == 0 and stats["tokens"] == 0
    assert obs.metrics_json()["counters"]["serve.requests"] == 2


def test_max_new_zero_continuous_engine(cfg):
    rng = np.random.default_rng(4)
    eng = ContinuousEngine(cfg, lanes=2, page_size=4, n_pages=9, max_ctx=16)
    reqs = [
        ServeRequest(rid=0, prompt=_prompt(rng, 3, cfg.vocab), max_new=0),
        ServeRequest(rid=1, prompt=_prompt(rng, 3, cfg.vocab), max_new=2),
    ]
    stats = eng.run(reqs)
    assert reqs[0].state == "finished" and reqs[0].out_tokens == []
    assert len(reqs[1].out_tokens) == 2
    assert stats["requests"] == 2
    j = obs.metrics_json()
    assert j["counters"]["serve.requests"] == 2
    assert j["histograms"]["serve.request_latency_s"]["count"] == 2
    # the zero-budget request never allocated pages
    assert eng.pool.free_count == eng.pool.capacity


def test_eos_finishes_both_engines_early(cfg, solo_server):
    rng = np.random.default_rng(5)
    prompt = _prompt(rng, 4, cfg.vocab)
    free_run = _solo_tokens(solo_server, prompt, 6)
    assert len(free_run) == 6
    eos = free_run[2]
    expected = free_run[: free_run.index(eos) + 1]

    got_fixed = _solo_tokens(solo_server, prompt, 6, eos_id=eos)
    assert got_fixed == expected

    req = ServeRequest(rid=0, prompt=prompt, max_new=6)
    eng = ContinuousEngine(cfg, lanes=1, page_size=4, n_pages=5, max_ctx=16)
    stats = eng.run([req], eos_id=eos)
    assert req.out_tokens == expected
    # EOS finish still observes latency / counts the request
    assert obs.metrics_json()["counters"]["serve.requests"] >= 1
    assert stats["tokens"] == len(expected)


# --------------------------------------------------------------------------
# phase-tagged plan ladders
# --------------------------------------------------------------------------


def test_plan_key_phase_qualifier_is_compat():
    from repro.core.enumerate import matmul_spec
    from repro.search.plandb import plan_key

    spec = matmul_spec(128, 128, 128)
    # phase=None must hash byte-identically to the pre-phase key — the
    # fleet's existing plan DBs stay warm
    assert plan_key(spec, np.float32) == plan_key(spec, np.float32,
                                                  phase=None)
    decode = plan_key(spec, np.float32, phase="decode")
    assert decode != plan_key(spec, np.float32)
    assert decode != plan_key(spec, np.float32, phase="prefill")


def test_plandb_phase_ladders_are_separate(tmp_path):
    from repro.codegen import default_schedule
    from repro.core.enumerate import matmul_spec
    from repro.search.plandb import PlanDB, entry_from

    db = PlanDB(str(tmp_path / "plans.json"))
    spec = matmul_spec(128, 128, 128)
    db.put(
        spec, np.float32,
        [entry_from(default_schedule(spec), score=1.0, lower_bound=0.0,
                    fits_vmem=True)],
        phase="decode",
    )
    assert db.best_schedule(spec, np.float32) is None
    assert db.best_schedule(spec, np.float32, phase="prefill") is None
    assert db.best_schedule(spec, np.float32, phase="decode") is not None


def test_serving_phase_context_nests():
    from repro.search import active_phase, serving_phase

    assert active_phase() is None
    with serving_phase("prefill"):
        assert active_phase() == "prefill"
        with serving_phase("decode"):
            assert active_phase() == "decode"
        assert active_phase() == "prefill"
    assert active_phase() is None


def test_tuned_kernel_consults_active_phase_first(monkeypatch):
    import repro.search as search
    from repro.core.enumerate import matmul_spec
    from repro.ops import _tuned_kernel
    from repro.search import serving_phase

    lookups = []

    class Recording:
        def best_schedule(self, spec, dtype, phase=None):
            lookups.append(phase)
            return None                      # force tuner fallback

    monkeypatch.setattr(search, "default_plan_db", lambda: Recording())
    spec = matmul_spec(128, 128, 128)
    with serving_phase("decode"):
        _tuned_kernel(spec, np.float32, interpret=True)
    # phased lookup first, unphased fallback second
    assert lookups == ["decode", None]

    lookups.clear()
    _tuned_kernel(spec, np.float32, interpret=True)
    assert lookups == [None]


# --------------------------------------------------------------------------
# trace generator
# --------------------------------------------------------------------------


def test_synthetic_trace_is_seeded_and_ordered():
    a = synthetic_trace(8, vocab=50, seed=9, rate_hz=100.0)
    b = synthetic_trace(8, vocab=50, seed=9, rate_hz=100.0)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.arrival_s, x.tenant) == (
            y.max_new, y.arrival_s, y.tenant
        )
    arrivals = [r.arrival_s for r in a]
    assert arrivals == sorted(arrivals)
    assert len({r.tenant for r in a}) >= 2
    saturated = synthetic_trace(4, vocab=50, seed=0, rate_hz=0.0)
    assert all(r.arrival_s == 0.0 for r in saturated)
