"""Mesh-tier search: differential matrix + communication-cost properties.

ISSUE-5 coverage, three layers:

* **In-process properties** (property-engine-driven — hypothesis in CI,
  the seeded fallback engine on bare machines): legality of every mesh
  subdivision ``space.mesh_variants`` proposes, the communication term's
  invariants (psum fully exposed >= ring's overlapped exposure; map-only
  sharding needs no collective; score >= lower bound with the comm term
  enabled), the PR-2-style bound-cut soundness audit on a mesh search,
  and the mesh-qualified plan-key discipline.

* **Differential matrix** (subprocess per forced device count, shared
  ``forced_devices`` fixture): every legal mesh schedule the space
  enumeration proposes for the count's conventional mesh — all mesh
  variants x collective strategies, whole-extent and seeded-random inner
  blockings — lowered through ``codegen.bind_mesh`` and checked against
  the ``np.einsum`` f64 oracle AND the HoF reference interpreter
  (``core.interp`` via ``evaluate_variant``), f32 everywhere and bf16 on
  a stride of the variants.  Seeded like ``test_differential.py``: every
  case reproduces from (family, devices, variant index) alone.

* **Acceptance path**: a swept ``--mesh 2x4`` plan DB serves/trains
  through sharded generated kernels — ``ops.dense`` under an active 2x4
  mesh dispatches a ``MeshBoundKernel`` fwd and bwd (derived-spec mesh
  plans) and a captured model's step matches the unsharded baseline
  within the differential tolerances under 8 forced devices.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.enumerate import (  # noqa: E402
    matmul_spec,
    transposed_matmul_spec,
    weighted_matmul_spec,
)
from repro.search import (  # noqa: E402
    PlanDB,
    beam_search,
    estimate,
    plan_key,
    search_schedule,
)
from repro.search.space import (  # noqa: E402
    local_extents,
    mesh_descriptor,
    mesh_variants,
    parse_mesh_shape,
)

#: conventional mesh per forced device count (data x model)
MESH_FOR_DEVICES = {1: (1, 1), 2: (1, 2), 4: (2, 2), 8: (2, 4)}

extent_pool = st.sampled_from([2, 4, 8, 16])
seeds = st.integers(min_value=0, max_value=10_000)


# ---------------------------------------------------------------------------
# space: legality of the mesh enumeration
# ---------------------------------------------------------------------------


@given(m=extent_pool, k=extent_pool, n=extent_pool, seed=seeds)
@settings(max_examples=25, deadline=None)
def test_mesh_variants_are_legal(m, k, n, seed):
    """Every proposed subdivision divides its index's extent, axes shard
    distinct indices, and the collective strategy appears exactly when a
    reduce index is sharded."""
    rng = np.random.default_rng(seed)
    shape = tuple(int(rng.choice([1, 2, 4])) for _ in range(2))
    spec = matmul_spec(m, k, n)
    variants = mesh_variants(spec, shape)
    assert variants, "enumeration must at least propose unsharded"
    assert any(not v.assignment for v in variants), "unsharded variant gone"
    seen = set()
    for v in variants:
        key = (v.assignment, v.collective)
        assert key not in seen, f"duplicate variant {key}"
        seen.add(key)
        indices = [i for i, _ in v.assignment]
        assert len(set(indices)) == len(indices)
        for i, (axis, size) in v.assignment:
            assert axis in ("pod", "data", "model")
            assert size > 1
            assert spec.extents[i] % size == 0
        sharded_reduce = any(i not in spec.output for i in indices)
        if sharded_reduce:
            assert v.collective in ("psum", "ring")
        else:
            assert v.collective == ""
        # the denoted schedule must build and validate
        from repro.search.space import make_candidate

        cand = make_candidate(
            spec, spec.indices, {}, mesh=v.as_dict(), collective=v.collective
        )
        sched = cand.to_schedule()
        mesh_levels = [l for l in sched.levels if l.tier.startswith("mesh:")]
        assert len(mesh_levels) == len(v.assignment)


# ---------------------------------------------------------------------------
# cost: the communication term
# ---------------------------------------------------------------------------


@given(m=extent_pool, k=extent_pool, n=extent_pool)
@settings(max_examples=25, deadline=None)
def test_comm_term_invariants(m, k, n):
    """Reduce-sharding pays a collective (psum fully exposed >= ring's
    overlapped exposure >= 0); map-only sharding pays none; the score
    never drops below the lower bound with the comm term enabled."""
    spec = matmul_spec(max(m, 2), max(k, 2), max(n, 2))
    blocks = dict(local_extents(spec, {"j": ("model", 2)}))
    psum = estimate(
        spec, spec.indices, blocks,
        mesh={"j": ("model", 2)}, collective="psum",
    )
    ring = estimate(
        spec, spec.indices, blocks,
        mesh={"j": ("model", 2)}, collective="ring",
    )
    assert psum.comm_s > 0.0
    assert ring.comm_s >= 0.0
    assert psum.comm_s >= ring.comm_s  # overlap can only help
    for est in (psum, ring):
        assert est.score >= est.lower_bound - 1e-18
        assert est.lower_bound >= est.comm_s - 1e-18  # comm is in the bound
        assert est.shards == 2
    map_only = estimate(
        spec, spec.indices, dict(local_extents(spec, {"i": ("data", 2)})),
        mesh={"i": ("data", 2)},
    )
    assert map_only.comm_s == 0.0
    # per-device compute shrinks with the shard count
    whole = estimate(
        spec, spec.indices, {i: spec.extents[i] for i in spec.indices}
    )
    assert map_only.compute_s == pytest.approx(whole.compute_s / 2)


def test_roofline_collective_model():
    """The interconnect model the comm term is built on."""
    from repro.roofline.analysis import (
        collective_seconds,
        sharded_reduce_seconds,
    )

    nbytes, p, bw = 1e6, 4, 50e9
    ar = collective_seconds("all-reduce", nbytes, p, bw)
    rs = collective_seconds("reduce-scatter", nbytes, p, bw)
    ag = collective_seconds("all-gather", nbytes, p, bw)
    assert ar == pytest.approx(rs + ag)
    assert ar == pytest.approx(2 * nbytes * (p - 1) / p / bw)
    assert collective_seconds("psum", nbytes, 1, bw) == 0.0
    # ring: reduce-scatter hides behind compute, all-gather stays exposed
    assert sharded_reduce_seconds(
        nbytes, p, collective="ring", compute_s=1.0, hw_ici_bw=bw
    ) == pytest.approx(ag)
    assert sharded_reduce_seconds(
        nbytes, p, collective="ring", compute_s=0.0, hw_ici_bw=bw
    ) == pytest.approx(rs + ag)
    assert sharded_reduce_seconds(
        nbytes, p, collective="psum", compute_s=123.0, hw_ici_bw=bw
    ) == pytest.approx(ar)  # psum never overlaps


# ---------------------------------------------------------------------------
# beam: mesh plans surface, bound cut stays sound with the comm term
# ---------------------------------------------------------------------------


def test_mesh_search_surfaces_mesh_plan_and_audit_is_sound():
    """The ISSUE-5 acceptance core, analytic half: an active 2x4 mesh
    search returns at least one ``mesh:*`` plan, and every bound cut made
    with the communication term enabled passes the PR-2 soundness audit
    (lower bound >= best complete score at the moment of the cut)."""
    spec = matmul_spec(64, 32, 64)
    survivors, stats = beam_search(
        spec, beam_width=6, topk=4, mesh_shape=(2, 4)
    )
    assert survivors
    assert stats.mesh_variants > 0
    assert any(sc.candidate.mesh for sc in survivors), (
        "mesh search surfaced no sharded plan"
    )
    assert stats.bound_log, "expected bound cuts in a mesh-widened space"
    for key, lower_bound, best_at_prune in stats.bound_log:
        assert lower_bound >= best_at_prune, (
            f"unsound cut with comm term: bound {lower_bound} beat the "
            f"proxy {best_at_prune} for {key}"
        )
    # at least one scored state actually carried a comm term (a sharded
    # reduce variant is in the space for this spec)
    sharded_reduce = [
        sc for sc in survivors
        if any(i not in spec.output for i, _ in sc.candidate.mesh)
    ]
    for sc in sharded_reduce:
        assert sc.cost.comm_s > 0.0


def test_mesh_plan_keys_are_qualified_and_disjoint(tmp_path):
    spec = matmul_spec(64, 64, 64)
    k_plain = plan_key(spec, np.float32)
    k_mesh = plan_key(spec, np.float32, mesh="2x4")
    k_mesh2 = plan_key(spec, np.float32, mesh="2x2")
    assert len({k_plain, k_mesh, k_mesh2}) == 3
    assert mesh_descriptor((2, 4)) == "2x4"
    assert mesh_descriptor((1, 1)) is None
    assert parse_mesh_shape("2x4") == (2, 4)
    with pytest.raises(ValueError):
        parse_mesh_shape("banana")

    db = PlanDB(str(tmp_path / "plans.json"))
    res = search_schedule(
        spec, beam_width=4, topk=2, measure=False, plan_db=db,
        mesh_shape=(2, 4),
    )
    assert res.mesh == "2x4"
    assert any(p.sharded for p in res.ranked)
    # the mesh ladder round-trips only under the mesh-qualified key
    assert db.best_schedule(spec, np.float32, mesh="2x4") is not None
    assert db.best_schedule(spec, np.float32) is None
    sched, entry = db.best_entry(spec, np.float32, mesh="2x4")
    assert sched is not None and "collective" in entry


def test_sharded_plans_rank_behind_measured_without_devices(tmp_path):
    """Single-device process + measured mesh search: the sharded
    candidates cannot be measured, and that is an error — not a ladder
    whose sharded rungs silently keep their analytic rank.  Nothing is
    persisted."""
    if jax.device_count() >= 8:
        pytest.skip("process has a real mesh; covered by the matrix test")
    spec = matmul_spec(64, 64, 64)
    db = PlanDB(str(tmp_path / "plans.json"))
    with pytest.raises(ValueError, match="needs 8 devices"):
        search_schedule(
            spec, beam_width=4, topk=3, measure=True, interpret=True,
            plan_db=db, mesh_shape=(2, 4),
        )
    assert db.best_schedule(spec, np.float32, mesh="2x4") is None


# ---------------------------------------------------------------------------
# the differential matrix (subprocess per forced device count)
# ---------------------------------------------------------------------------

_MATRIX_CODE = """
import numpy as np
import jax
import jax.numpy as jnp

from repro.core.enumerate import (
    evaluate_variant,
    matmul_spec,
    transposed_matmul_spec,
    weighted_matmul_spec,
)
from repro.codegen import cached_compile
from repro.search import (
    einsum_reference,
    mesh_for_schedules,
    reference_arrays,
    schedule_mesh_axes,
)
from repro.search.space import local_extents, make_candidate, mesh_variants

DEVICES = __DEVICES__
SHAPE = __SHAPE__
assert jax.device_count() == DEVICES, jax.device_count()

TOL = {"float32": (1e-4, 1e-4), "bfloat16": (6e-2, 6e-2)}
#: family -> (ctor, extents, seed offset) — offsets keep streams disjoint
#: and stable, mirroring tests/test_differential.py
FAMILIES = [
    ("matmul", matmul_spec, (8, 4, 8), 1000),
    ("weighted_matmul", weighted_matmul_spec, (4, 8, 4), 3000),
    ("transposed_matmul", transposed_matmul_spec, (8, 8, 4), 5000),
]

checked = 0
for fam, ctor, extents, offset in FAMILIES:
    spec = ctor(*extents)
    variants = mesh_variants(spec, SHAPE)
    for vi, v in enumerate(variants):
        rng = np.random.default_rng(offset + 37 * DEVICES + vi)
        mesh_asgn = v.as_dict()
        loc = local_extents(spec, mesh_asgn)
        order = list(spec.indices)
        rng.shuffle(order)
        blocks = {
            i: int(rng.choice(
                [d for d in range(1, loc[i] + 1) if loc[i] % d == 0]
            ))
            for i in spec.indices
        }
        # the primary family runs the whole-extent schedule too; the
        # others keep one random schedule per variant to bound runtime
        cases = [(tuple(order), blocks)]
        if fam == "matmul":
            cases.append((tuple(spec.indices), {}))
        dtypes = ["float32"] if vi % 3 else ["float32", "bfloat16"]
        for ci, (c_order, c_blocks) in enumerate(cases):
            cand = make_candidate(
                spec, c_order, c_blocks,
                mesh=mesh_asgn, collective=v.collective,
            )
            sched = cand.to_schedule()
            sharded = bool(schedule_mesh_axes(sched))
            mesh = mesh_for_schedules([sched]) if sharded else None
            if sharded:
                assert mesh is not None, (fam, vi, sched.levels)
            for dt_name in (dtypes if ci == 0 else ["float32"]):
                dt = jnp.bfloat16 if dt_name == "bfloat16" else np.float32
                rtol, atol = TOL[dt_name]
                arrays = reference_arrays(
                    spec, dtype=np.float32, seed=offset + vi
                )
                ref = einsum_reference(spec, arrays)
                interp = evaluate_variant(spec, c_order, arrays)
                np.testing.assert_allclose(
                    interp, ref, rtol=1e-4, atol=1e-4
                )
                kern = cached_compile(
                    spec, sched, interpret=True,
                    mesh=mesh, collective=v.collective or "psum",
                )
                args = tuple(
                    jnp.asarray(arrays[n], dt) for n in spec.operands
                )
                got = np.asarray(kern(*args), np.float64)
                np.testing.assert_allclose(
                    got, ref, rtol=rtol, atol=atol,
                    err_msg=f"{fam} devices={DEVICES} variant={vi} "
                            f"case={ci} dtype={dt_name} "
                            f"mesh={v.assignment} coll={v.collective} "
                            f"levels={sched.levels}",
                )
                checked += 1
print("CHECKED", checked)
print("OK")
"""


@pytest.mark.parametrize("devices", sorted(MESH_FOR_DEVICES))
def test_mesh_schedule_differential_matrix(forced_devices, devices):
    """Every legal mesh schedule from the space enumeration, lowered under
    the forced device count, matches the einsum oracle and core.interp
    for f32 (all variants) and bf16 (every third variant)."""
    shape = MESH_FOR_DEVICES[devices]
    out = forced_devices(
        _MATRIX_CODE.replace("__DEVICES__", str(devices)).replace(
            "__SHAPE__", repr(shape)),
        devices=devices,
        timeout=1200,
    )
    assert "OK" in out
    checked = int(out.split("CHECKED")[1].split()[0])
    # device counts with a real mesh must cover a non-trivial variant set
    assert checked >= (3 if devices == 1 else 12), out


# ---------------------------------------------------------------------------
# acceptance: swept mesh plans serve/train through sharded kernels
# ---------------------------------------------------------------------------


def test_mesh_swept_model_serves_and_trains_sharded(forced_devices, tmp_path):
    """ISSUE-5 acceptance, executable half: sweep a captured GEMM with
    ``mesh_shape=2x4`` (fwd + derived backward specs), then — under an
    active 2x4 mesh on 8 forced devices — ``ops.dense`` must dispatch a
    ``MeshBoundKernel`` (sharded generated kernel) on the forward AND
    value_and_grad tape, with outputs/gradients matching the unsharded
    baseline within the differential tolerances.  A captured
    (``capture.optimize``) step with a raw dot_general site takes the
    same route."""
    out = forced_devices("""
        import numpy as np
        import jax
        import jax.numpy as jnp

        from repro import capture, ops
        from repro.codegen import MeshBoundKernel
        from repro.core.enumerate import matmul_spec
        from repro.launch.mesh import make_debug_mesh
        from repro.search import default_plan_db, search_schedule_with_grads

        M = D = F = 128  # the dense predicate's 128-alignment floor
        spec = matmul_spec(M, D, F)
        db = default_plan_db()
        res = search_schedule_with_grads(
            spec, beam_width=4, topk=2, interpret=True, repeats=1,
            plan_db=db, mesh_shape=(2, 4),
        )
        assert set(res) == {"fwd", "dA", "dB"}, sorted(res)
        for label, r in res.items():
            assert any(p.sharded for p in r.ranked), label

        mesh = make_debug_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((M, D)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((D, F)), jnp.float32)

        # the lookup ops performs must now return a sharded kernel
        from repro.ops import _mesh_plan_kernel
        with jax.set_mesh(mesh):
            kern = _mesh_plan_kernel(spec, np.float32, interpret=True)
        assert isinstance(kern, MeshBoundKernel), type(kern)
        assert any(
            l.tier.startswith("mesh:") for l in kern.schedule.levels
        )

        def loss(a, b):
            return jnp.mean(ops.dense(a, b, interpret=True) ** 2)

        base_l, (base_gx, base_gw) = jax.value_and_grad(
            loss, argnums=(0, 1))(x, w)
        with jax.set_mesh(mesh):
            mesh_l, (mesh_gx, mesh_gw) = jax.value_and_grad(
                loss, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(
            float(mesh_l), float(base_l), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(mesh_gx), np.asarray(base_gx), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(mesh_gw), np.asarray(base_gw), rtol=1e-4, atol=1e-4)

        # captured step: a raw dot_general site dispatches through the
        # same mesh-qualified plans once capture rewrites it onto ops
        def step(a, b):
            return jnp.tanh(a @ b).sum()

        captured = capture.optimize(step, interpret=True)
        want = float(step(x, w))
        with jax.set_mesh(mesh):
            got = float(captured(x, w))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        print("OK")
    """, devices=8, timeout=1200, env_extra={
        "REPRO_PLAN_DB": str(tmp_path / "plans.json"),
        "REPRO_AUTOTUNE_CACHE": str(tmp_path / "autotune.json"),
    })
    assert "OK" in out


def test_sharded_candidates_are_timed_on_their_shards(forced_devices):
    """A sharded candidate is timed with each operand already split over
    the mesh as its shard_map reads it — not from one device, which would
    time the copy out to every shard on each call."""
    out = forced_devices("""
        import jax
        import numpy as np
        from jax.sharding import NamedSharding

        import repro.codegen as codegen
        from repro.core.enumerate import matmul_spec
        from repro.search import measure_schedules, search_schedule

        spec = matmul_spec(128, 4096, 128)
        res = search_schedule(spec, mesh_shape=(2, 2), measure=False)
        sched = next(p.schedule for p in res.ranked if p.sharded)
        seen = []
        real = codegen.cached_compile

        def spy(*a, **kw):
            kern = real(*a, **kw)

            def call(*args):
                seen.append([x.sharding for x in args])
                return kern(*args)

            call.plan = kern.plan
            return call if kw.get("mesh") is not None else kern

        codegen.cached_compile = spy
        measure_schedules(spec, [sched], interpret=True, repeats=2)
        assert len(seen) == 3, len(seen)   # warm-up + 2 repeats
        for shardings in seen:
            for s in shardings:
                assert isinstance(s, NamedSharding), s
                assert len(s.device_set) == 4, s
        print("OK")
    """, devices=4)
    assert "OK" in out
