"""repro.codegen: generated kernels vs oracles + the persistent cache.

Every kernel here runs in Pallas interpreter mode (CPU container).
Equivalence oracles: the hand-written ``kernels/matmul`` baseline and
``jnp.einsum``, per the acceptance criteria — plain, batched, chained,
and transposed contractions across f32/bf16.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codegen
from repro.codegen.plan import build_plan
from repro.core.enumerate import (
    batched_matmul_spec,
    chain_matmul_spec,
    matmul_spec,
    transposed_matmul_spec,
    weighted_matmul_spec,
)
from repro.kernels.matmul.matmul import matmul_pallas
from repro.kernels.matmul.ref import matmul_ref


def rnd(*shape, dtype=jnp.float32, seed=0):
    x = np.random.default_rng(seed + sum(shape)).standard_normal(shape)
    return jnp.asarray(x, dtype=dtype)


# bf16 atol covers 1-ulp noise from blocked accumulation order
TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=1e-1)}


def assert_close(out, ref, dtype):
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **TOL[dtype],
    )


# -- plain matmul -------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,k,n,bm,bn,bk",
    [
        (32, 32, 32, 16, 16, 16),
        (64, 80, 48, 16, 16, 16),
        (128, 64, 128, 64, 32, 32),
        (16, 256, 128, 8, 128, 128),
        (32, 32, 32, 32, 32, 32),   # single block, no grid, no seq loop
    ],
)
def test_generated_matmul_matches_einsum_and_baseline(m, k, n, bm, bn, bk, dtype):
    a, b = rnd(m, k, dtype=dtype), rnd(k, n, dtype=dtype, seed=1)
    spec = matmul_spec(m, k, n)
    sched = codegen.default_schedule(spec, {"i": bm, "k": bn, "j": bk})
    kern = codegen.compile(spec, sched, interpret=True)
    out = kern(a, b)
    assert out.dtype == a.dtype
    ein = jnp.einsum(
        "ij,jk->ik", a.astype(jnp.float32), b.astype(jnp.float32)
    )
    assert_close(out, ein, dtype)
    # the hand-written kernel is the verification baseline
    base = matmul_pallas(a, b, block_m=bm, block_n=bn, block_k=bk,
                         interpret=True)
    assert_close(out, base, dtype)


# -- the three scenarios the repo could not express before --------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_generated_batched_matmul(dtype):
    b, m, k, n = 4, 32, 48, 16
    x = rnd(b, m, k, dtype=dtype)
    w = rnd(b, k, n, dtype=dtype, seed=1)
    sched = codegen.batched_matmul_schedule(
        b, m, k, n, block_m=16, block_n=8, block_k=16
    )
    kern = codegen.compile(sched.spec, sched, interpret=True)
    out = kern(x, w)
    ein = jnp.einsum(
        "bij,bjk->bik", x.astype(jnp.float32), w.astype(jnp.float32)
    )
    assert out.shape == (b, m, n)
    assert_close(out, ein, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_generated_chain_matmul(dtype):
    m, k1, k2, n = 32, 48, 24, 16
    a = rnd(m, k1, dtype=dtype)
    b = rnd(k1, k2, dtype=dtype, seed=1)
    c = rnd(k2, n, dtype=dtype, seed=2)
    sched = codegen.chain_matmul_schedule(
        m, k1, k2, n, block_m=16, block_n=8, block_k1=16, block_k2=12
    )
    kern = codegen.compile(sched.spec, sched, interpret=True)
    out = kern(a, b, c)
    ein = jnp.einsum(
        "ij,jk,kl->il",
        a.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32),
    )
    assert_close(out, ein, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_generated_transposed_matmul(dtype):
    m, k, n = 32, 48, 16
    a = rnd(k, m, dtype=dtype)   # stored transposed
    b = rnd(k, n, dtype=dtype, seed=1)
    sched = codegen.transposed_matmul_schedule(
        m, k, n, block_m=16, block_n=8, block_k=16
    )
    kern = codegen.compile(sched.spec, sched, interpret=True)
    out = kern(a, b)
    ein = jnp.einsum(
        "ji,jk->ik", a.astype(jnp.float32), b.astype(jnp.float32)
    )
    assert_close(out, ein, dtype)


def test_generated_weighted_matmul():
    """A 3-operand contraction with a shared reduce index (paper eq 2)."""
    m, k, n = 32, 48, 16
    a, b, g = rnd(m, k), rnd(k, n, seed=1), rnd(k, seed=2)
    spec = weighted_matmul_spec(m, k, n)
    sched = codegen.default_schedule(spec, {"i": 16, "k": 8, "j": 16})
    kern = codegen.compile(spec, sched, interpret=True)
    out = kern(a, b, g)
    ein = np.einsum(
        "ij,jk,j->ik", *(np.asarray(x, np.float32) for x in (a, b, g))
    )
    assert_close(out, ein, jnp.float32)


def test_generated_epilogue_subsumes_fused_dense_act():
    from repro.kernels.fused_dense_act.ref import fused_dense_act_ref

    m, d, f = 32, 64, 48
    x, w = rnd(m, d), rnd(d, f, seed=1)
    beta, mean = rnd(f, seed=2), rnd(f, seed=3)
    var = jnp.abs(rnd(f, seed=4)) + 0.5
    spec = matmul_spec(m, d, f)
    sched = codegen.default_schedule(spec, {"i": 16, "k": 16, "j": 16})
    epi = codegen.Epilogue(act="gelu", bias=True, norm=True)
    kern = codegen.compile(spec, sched, epilogue=epi, interpret=True)
    out = kern(x, w, bias=beta, mean=mean, var=var)
    ref = fused_dense_act_ref(x, w, beta, mean, var, act="gelu")
    assert_close(out, ref, jnp.float32)


def test_ops_layer_routes_through_generator(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "REPRO_AUTOTUNE_CACHE", str(tmp_path / "cache.json")
    )
    from repro import ops

    x, w = rnd(128, 128), rnd(128, 128, seed=1)
    out = ops.dense(x, w, interpret=True)
    assert_close(out, np.asarray(x) @ np.asarray(w), jnp.float32)

    xb, wb = rnd(2, 32, 48), rnd(2, 48, 16, seed=1)
    outb = ops.batched_dense(xb, wb, interpret=True)
    assert_close(
        outb,
        np.einsum("bij,bjk->bik", np.asarray(xb), np.asarray(wb)),
        jnp.float32,
    )

    a, b, c = rnd(32, 48), rnd(48, 24, seed=1), rnd(24, 16, seed=2)
    outc = ops.chain_dense(a, b, c, interpret=True)
    assert_close(
        outc,
        np.asarray(a) @ np.asarray(b) @ np.asarray(c),
        jnp.float32,
    )

    at, bt = rnd(48, 32), rnd(48, 16, seed=1)
    outt = ops.dense_transposed(at, bt, interpret=True)
    assert_close(outt, np.asarray(at).T @ np.asarray(bt), jnp.float32)


# -- plan derivation ----------------------------------------------------------


def test_plan_respects_schedule_tiers():
    spec = matmul_spec(64, 32, 48)
    sched = codegen.default_schedule(spec, {"i": 16, "k": 8, "j": 16})
    plan = build_plan(sched)
    assert plan.grid == ("i", "k")
    assert plan.seq == ("j",)
    assert plan.grid_shape == (4, 6)
    assert plan.axes["j"].seq_steps == 2 and plan.axes["j"].chunk == 16
    # operand blocks: seq axes resident at full extent
    assert plan.operand_block("A") == (16, 32)
    assert plan.operand_block("B") == (32, 8)
    assert plan.out_block() == (16, 8)


def test_plan_rejects_reduce_on_grid():
    from repro.core.schedule import Level, Schedule

    spec = matmul_spec(32, 32, 32).subdivide("j", 16)
    levels = (
        Level("jo", "grid", 2),   # reduction on the parallel grid: invalid
        Level("i", "mxu", 32),
        Level("ji", "mxu", 16),
        Level("k", "mxu", 32),
    )
    with pytest.raises(ValueError, match="reduce index"):
        build_plan(Schedule(spec, levels))


def test_mesh_partition_specs():
    spec = matmul_spec(64, 32, 64)
    sched = codegen.schedules.sharded_schedule(
        spec,
        blocks={"i": 16, "k": 16, "j": 16},
        mesh_shards={"i": ("data", 2), "k": ("model", 2)},
    )
    plan = build_plan(sched)
    assert codegen.operand_partition_spec(plan, "A") == jax.sharding.PartitionSpec("data", None)
    assert codegen.operand_partition_spec(plan, "B") == jax.sharding.PartitionSpec(None, "model")
    assert codegen.output_partition_spec(plan) == jax.sharding.PartitionSpec("data", "model")


# -- persistent autotune cache ------------------------------------------------


def test_cache_roundtrip_tune_persist_reload(tmp_path):
    spec = matmul_spec(64, 32, 64)
    path = str(tmp_path / "autotune.json")

    cache = codegen.AutotuneCache(path)
    s1 = codegen.tune_schedule(spec, dtype=np.float32, cache=cache)
    assert cache.misses == 1 and cache.hits == 0
    assert os.path.exists(path)

    # same process, same cache object
    s2 = codegen.tune_schedule(spec, dtype=np.float32, cache=cache)
    assert cache.hits == 1

    # "new process": a fresh cache object reloads from disk
    cache2 = codegen.AutotuneCache(path)
    s3 = codegen.tune_schedule(spec, dtype=np.float32, cache=cache2)
    assert cache2.hits == 1 and cache2.misses == 0

    for sa, sb in [(s1, s2), (s1, s3)]:
        assert sa.spec.split_chain() == sb.spec.split_chain()
        assert [(l.index, l.tier, l.extent) for l in sa.levels] == [
            (l.index, l.tier, l.extent) for l in sb.levels
        ]
    # and the reloaded schedule still compiles + is correct
    a, b = rnd(64, 32), rnd(32, 64, seed=1)
    out = codegen.compile(spec, s3, interpret=True)(a, b)
    assert_close(out, np.asarray(a) @ np.asarray(b), jnp.float32)


def test_cache_key_distinguishes_dtype_and_shapes():
    s1 = matmul_spec(64, 32, 64)
    s2 = matmul_spec(64, 32, 128)
    k = codegen.cache_key
    assert k(s1, dtype="float32") != k(s2, dtype="float32")
    assert k(s1, dtype="float32") != k(s1, dtype="bfloat16")
    assert k(s1, dtype="float32") == k(s1, dtype="float32")


def test_cache_survives_corrupt_file(tmp_path):
    path = tmp_path / "autotune.json"
    path.write_text("{not json!!")
    cache = codegen.AutotuneCache(str(path))
    assert cache.get("anything") is None
    cache.put("k", {"v": 1})
    assert codegen.AutotuneCache(str(path)).get("k") == {"v": 1}


def test_core_tune_cache_skips_remeasurement(tmp_path, monkeypatch):
    """Acceptance criterion: repeated tune() hits the cache, no re-measure."""
    import repro.core.autotune as at

    spec = matmul_spec(16, 16, 16)
    arrays = {
        "A": np.random.default_rng(0).standard_normal((16, 16)),
        "B": np.random.default_rng(1).standard_normal((16, 16)),
    }
    calls = {"n": 0}
    orig = at.execute_variant

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(at, "execute_variant", counting)
    cache = codegen.AutotuneCache(str(tmp_path / "t.json"))
    r1 = at.tune(spec, {"j": [8]}, measure_with=arrays, cache=cache)
    measured_once = calls["n"]
    assert measured_once > 0

    r2 = at.tune(spec, {"j": [8]}, measure_with=arrays, cache=cache)
    assert calls["n"] == measured_once, "cache hit must not re-measure"
    assert [tv.order for tv in r1] == [tv.order for tv in r2]
    assert [tv.measured_s for tv in r1] == [tv.measured_s for tv in r2]

    # fresh process simulation
    cache2 = codegen.AutotuneCache(str(tmp_path / "t.json"))
    r3 = at.tune(spec, {"j": [8]}, measure_with=arrays, cache=cache2)
    assert calls["n"] == measured_once
    assert [tv.order for tv in r3] == [tv.order for tv in r1]


# -- no silent fallbacks ------------------------------------------------------


def test_tune_schedule_raises_when_no_block_fits():
    """No block choice within the VMEM budget is an error, not a kernel of
    all-1 output blocks that nobody would choose."""
    from repro.core.cost import TPU

    with pytest.raises(ValueError, match="VMEM budget"):
        codegen.tune_schedule(
            matmul_spec(256, 1024, 512), hw=dict(TPU, vmem_bytes=64 * 1024),
            use_default_cache=False,
        )


def test_backend_errors_propagate(monkeypatch):
    """A backend that fails to initialise is not taken for "not a TPU"."""
    from repro import ops

    def broken():
        raise RuntimeError("backend failed to initialise")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        ops._use_pallas()


def test_plan_db_failure_propagates(monkeypatch):
    """A plan DB that cannot be opened fails the op instead of warning once
    and tuning analytically from then on."""
    import repro.search as search
    from repro import ops

    def broken():
        raise OSError("plan DB unreadable")

    monkeypatch.setattr(search, "default_plan_db", broken)
    x = jnp.ones((128, 128), jnp.float32)
    with pytest.raises(OSError, match="unreadable"):
        ops.dense(x, x, interpret=True)
