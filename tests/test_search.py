"""Property tests for the cost-guided search pipeline (ISSUE 2).

Covers the two properties the issue names:

  * **cost-model/rewrite consistency** — applying any exchange rule (a loop
    reorder) or subdivision rule to a variant never changes the analytic
    FLOP count, and the roofline compute term agrees;
  * **prune soundness** — the search's bound cut never discards a candidate
    whose cost lower-bound beats the best complete candidate's score (the
    measured proxy); every cut is recorded in ``SearchStats.bound_log`` and
    audited here, and with an unbounded beam the search is exhaustive.

Plus end-to-end pipeline checks: plan DB round-trip, ``ops.dense`` pickup,
and ``candidate_schedule`` vs ``default_schedule`` agreement.
"""

from __future__ import annotations

import itertools
import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.codegen import default_schedule  # noqa: E402
from repro.codegen.cache import schedule_to_dict  # noqa: E402
from repro.core.cost import TPU  # noqa: E402
from repro.core.enumerate import (  # noqa: E402
    chain_matmul_spec,
    matmul_spec,
    matvec_spec,
    variant_orders,
    weighted_matmul_spec,
)
from repro.search import (  # noqa: E402
    PlanDB,
    beam_search,
    block_choices,
    candidate_orders,
    candidate_schedule,
    estimate,
    make_candidate,
    search_schedule,
)

SPECS = [
    matmul_spec(16, 8, 32),
    matvec_spec(24, 16),
    weighted_matmul_spec(8, 16, 8),
    chain_matmul_spec(8, 8, 16, 8),
]


# ---------------------------------------------------------------------------
# cost-model / rewrite consistency
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_flops_invariant_under_subdivision(spec):
    """The subdivision rule (paper eq 44) regroups, never adds, work."""
    rng = np.random.default_rng(0)
    base = spec.flops()
    for _ in range(20):
        s = spec
        for _ in range(int(rng.integers(1, 4))):
            idx = str(rng.choice(list(s.indices)))
            divs = [d for d in range(2, s.extents[idx] + 1)
                    if s.extents[idx] % d == 0]
            if not divs:
                continue
            s = s.subdivide(idx, int(rng.choice(divs)))
        assert s.flops() == base, (s.split_chain(), s.extents)


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_flops_invariant_under_exchange(spec):
    """Exchange rules permute the nest; work and the roofline compute term
    must not move.  Orders come from the SJT walk, where each neighbour is
    one exchange-rule application away."""
    blocks = {i: spec.extents[i] for i in spec.indices}
    ref = None
    for order in variant_orders(spec, dedup_rnz=False):
        est = estimate(spec, order, blocks)
        if ref is None:
            ref = est.compute_s
        assert est.compute_s == ref, order
    # specs reached by the subdiv rule keep the same FLOP count too
    for idx in spec.indices:
        divs = [d for d in range(2, spec.extents[idx] + 1)
                if spec.extents[idx] % d == 0]
        for d in divs[:2]:
            assert spec.subdivide(idx, d).flops() == spec.flops()


def test_score_never_below_lower_bound():
    """score = bound x penalties with penalties >= 1 — the invariant the
    sound cut relies on."""
    spec = matmul_spec(64, 32, 128)
    choices = block_choices(spec, TPU)
    for order in candidate_orders(spec):
        for combo in itertools.product(*(choices[i] for i in spec.indices)):
            blocks = dict(zip(spec.indices, combo))
            est = estimate(spec, order, blocks)
            assert est.score >= est.lower_bound - 1e-18, (order, blocks)


# ---------------------------------------------------------------------------
# prune soundness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_bound_cut_never_discards_a_winner(spec):
    """Every bound-cut candidate's lower bound was >= the best complete
    candidate's score at the moment of the cut — so no cut candidate (nor
    any completion of it) could have beaten the proxy."""
    survivors, stats = beam_search(spec, beam_width=4, topk=4)
    assert survivors, "search must always return at least one candidate"
    best_score = survivors[0].cost.score
    for key, lower_bound, best_at_prune in stats.bound_log:
        assert lower_bound >= best_at_prune, (
            f"unsound cut: bound {lower_bound} beat the proxy "
            f"{best_at_prune} for {key}"
        )
        # the proxy only improves over time, so nothing cut could beat the
        # final winner either
        assert lower_bound >= best_score or best_at_prune >= best_score


@pytest.mark.parametrize("spec", SPECS[:3], ids=lambda s: s.name)
def test_unbounded_beam_is_exhaustive(spec):
    """With width >= |space| the beam finds the analytic optimum: the same
    minimum score as brute-force enumeration of every (order, blocks)."""
    choices = block_choices(spec, TPU)
    orders = candidate_orders(spec)
    brute = min(
        estimate(spec, order, dict(zip(spec.indices, combo))).score
        for order in orders
        for combo in itertools.product(*(choices[i] for i in spec.indices))
    )
    survivors, _ = beam_search(spec, beam_width=10_000, topk=1)
    assert survivors[0].cost.score == pytest.approx(brute, rel=1e-12)


def test_beam_width_one_still_returns_a_plan():
    survivors, stats = beam_search(matmul_spec(32, 32, 32), beam_width=1, topk=3)
    assert len(survivors) >= 1
    assert stats.considered > 0


# ---------------------------------------------------------------------------
# schedules and dedup
# ---------------------------------------------------------------------------


def test_candidate_schedule_matches_default_schedule():
    """With loop order == spec.indices the search's schedule builder and
    PR-1's default_schedule emit the identical Schedule."""
    spec = matmul_spec(32, 16, 64)
    blocks = {"i": 8, "j": 8, "k": 16}
    a = candidate_schedule(spec, spec.indices, blocks)
    b = default_schedule(spec, blocks)
    assert schedule_to_dict(a) == schedule_to_dict(b)


def test_canonical_key_collapses_exchange_equivalents():
    """Orders that differ only by a map/rnz exchange lower identically and
    must share a canonical key (the beam's dedup)."""
    spec = matmul_spec(16, 16, 16)
    blocks = {"i": 8, "j": 16, "k": 8}
    a = make_candidate(spec, ("i", "j", "k"), blocks)
    b = make_candidate(spec, ("i", "k", "j"), blocks)
    # j is whole-extent (no seq level) and the grid order (i then k) is the
    # same in both, so these lower to the same kernel:
    assert a.canonical_key() == b.canonical_key()
    # but a genuine grid reorder is a different kernel:
    c = make_candidate(spec, ("k", "i", "j"), blocks)
    assert c.canonical_key() != a.canonical_key()


# ---------------------------------------------------------------------------
# pipeline: plan DB round-trip and ops pickup
# ---------------------------------------------------------------------------


def test_search_pipeline_roundtrip_and_ops_pickup(tmp_path, monkeypatch):
    spec = matmul_spec(128, 128, 128)
    db = PlanDB(str(tmp_path / "plans.json"))
    res = search_schedule(
        spec, beam_width=4, topk=2, measure=False, plan_db=db,
    )
    assert res.ranked and res.db_key
    # default baseline rides along un-measured
    assert any(p.source == "default" for p in res.ranked)

    stored = db.best_schedule(spec, np.float32)
    assert stored is not None
    assert schedule_to_dict(stored) == schedule_to_dict(res.best.schedule)

    # a second search call returns the persisted ladder without re-searching
    res2 = search_schedule(spec, beam_width=4, topk=2, measure=False, plan_db=db)
    assert schedule_to_dict(res2.best.schedule) == schedule_to_dict(
        res.best.schedule
    )

    # ops._tuned_kernel consults the plan DB before the tuner
    monkeypatch.setenv("REPRO_PLAN_DB", str(db.path))
    from repro.ops import _tuned_kernel

    kern = _tuned_kernel(spec, np.float32, interpret=True)
    assert schedule_to_dict(kern.schedule) == schedule_to_dict(
        res.best.schedule
    )


def test_unmeasured_cache_does_not_satisfy_measured_request(tmp_path):
    """An analytic-only (--no-measure) ladder must not mask a later
    measured search for the same spec/dtype."""
    spec = matmul_spec(64, 64, 64)
    db = PlanDB(str(tmp_path / "plans.json"))
    res = search_schedule(spec, beam_width=4, topk=2, measure=False, plan_db=db)
    assert res.best.measured_s is None
    res2 = search_schedule(
        spec, beam_width=4, topk=2, measure=True, interpret=True, plan_db=db
    )
    assert res2.best.measured_s is not None
    # and the measured ladder overwrote the analytic one
    res3 = search_schedule(spec, beam_width=4, topk=2, measure=False, plan_db=db)
    assert res3.best.measured_s is not None


def test_plan_db_corrupt_entry_degrades_to_miss(tmp_path):
    spec = matmul_spec(64, 64, 64)
    db = PlanDB(str(tmp_path / "plans.json"))
    from repro.search.plandb import plan_key

    db._cache.put(
        plan_key(spec, np.float32),
        {"v": 1, "ranked": [{"schedule": {"splits": [["zz", 7]], "levels": []}}]},
    )
    assert db.best_schedule(spec, np.float32) is None


def test_measured_search_winner_not_slower_than_default(tmp_path):
    """The ISSUE-2 acceptance bar, enforced structurally: the default
    schedule is part of the measured set, so the measured winner can never
    be slower than it on the same harness."""
    from repro.search import reference_arrays

    spec = matmul_spec(64, 64, 64)
    res = search_schedule(
        spec, beam_width=4, topk=2, interpret=True, measure=True,
        arrays=reference_arrays(spec, seed=3),
        plan_db=PlanDB(str(tmp_path / "plans.json")),
    )
    base = res.baseline()
    assert base is not None and base.measured_s is not None
    assert res.best.measured_s <= base.measured_s
    assert res.stats.measured == len(res.ranked)


# ---------------------------------------------------------------------------
# VMEM budget and the measurement backend
# ---------------------------------------------------------------------------


def test_beam_returns_only_candidates_that_fit_vmem(tmp_path):
    """A candidate whose kernel exceeds the VMEM it is compiled with would
    be refused by the chip's compiler: it is dropped, not ranked last —
    the unblocked default baseline included."""
    from repro.core.cost import kernel_vmem_bytes

    spec = matmul_spec(256, 1024, 512)
    hw = dict(TPU, vmem_bytes=4 * 2**20)
    assert kernel_vmem_bytes(spec, {}, 4) > hw["vmem_bytes"]
    ranked, _ = beam_search(spec, beam_width=8, topk=8, hw=hw)
    assert ranked
    for sc in ranked:
        assert sc.cost.fits_vmem
        assert kernel_vmem_bytes(spec, sc.candidate.block_dict, 4) <= (
            hw["vmem_bytes"]
        )
    res = search_schedule(
        spec, beam_width=8, topk=8, hw=hw, measure=False,
        plan_db=PlanDB(str(tmp_path / "plans.json")),
    )
    assert res.ranked and all(p.fits_vmem for p in res.ranked)
    assert all(p.source != "default" for p in res.ranked)


def test_search_refuses_a_spec_nothing_fits(tmp_path):
    spec = matmul_spec(256, 1024, 512)
    hw = dict(TPU, vmem_bytes=64 * 1024)
    assert beam_search(spec, hw=hw)[0] == []
    db = PlanDB(str(tmp_path / "plans.json"))
    with pytest.raises(ValueError, match="VMEM budget"):
        search_schedule(spec, hw=hw, measure=False, plan_db=db)
    assert db.best_schedule(spec, np.float32) is None


def test_interpret_default_follows_the_backend(monkeypatch):
    """Unless told otherwise, measurement runs the real kernel on a TPU
    and the Pallas interpreter elsewhere — a search on the chip that does
    not pass ``interpret`` never times the interpreter."""
    import repro.codegen as codegen
    from repro import ops

    assert ops.default_interpret() == (jax.default_backend() != "tpu")
    seen = []
    real = codegen.cached_compile

    def spy(*args, **kw):
        seen.append(kw["interpret"])
        return real(*args, **{**kw, "interpret": True})  # no chip here

    monkeypatch.setattr(codegen, "cached_compile", spy)
    spec = matmul_spec(64, 64, 64)
    search_schedule(spec, beam_width=2, topk=1, repeats=1)
    assert seen and set(seen) == {ops.default_interpret()}
    seen.clear()
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)  # as on a TPU
    search_schedule(spec, beam_width=2, topk=1, repeats=1)
    assert seen and set(seen) == {False}
