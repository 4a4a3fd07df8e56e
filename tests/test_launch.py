"""Launch-layer tests.

Device-count-sensitive pieces (meshes, shard_map collectives, lower+compile)
run through the shared ``forced_devices`` fixture (``tests/conftest.py``):
a subprocess under ``--xla_force_host_platform_device_count`` so the main
pytest process keeps its single-device view (per the dry-run contract:
only dryrun.py forces 512 devices).
"""

import pytest


# -- pure unit tests (no devices) ---------------------------------------------


def test_spec_for_rules_divisibility():
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import spec_for

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    mesh = FakeMesh()
    # FSDP x TP for a weight
    assert spec_for(mesh, ("embed", "mlp"), (4096, 11008)) == P("data", "model")
    # vocab not divisible -> replicated dim
    assert spec_for(mesh, ("vocab", "embed"), (51865, 512)) == P(None, "data")
    # MQA cache: kv=1 cannot shard, seq takes model
    assert spec_for(
        mesh, ("batch", "seq_kv", "kv", None), (128, 32768, 1, 128)
    ) == P("data", "model")
    # deepseek cache: kv=32 takes model, seq falls back to data... but batch
    # already used data -> seq stays unsharded
    assert spec_for(
        mesh, ("batch", "seq_kv", "kv", None), (128, 32768, 32, 128)
    ) == P("data", None, "model")


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
      %ag = bf16[16,1024]{1,0} all-gather(%x), replica_groups={}
      %ar.1 = f32[256]{0} all-reduce(%y), to_apply=%add
      %rs = f32[8,128]{1,0} reduce-scatter(%z), dimensions={0}
      %cp = (f32[4,4]{1,0}, f32[4,4]{1,0}) collective-permute-start(%w)
      %nothing = f32[2,2]{1,0} add(%a, %b)
    """
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 1024 * 2
    assert out["all-reduce"] == 256 * 4
    assert out["reduce-scatter"] == 8 * 128 * 4
    assert out["collective-permute"] == 4 * 4 * 4  # tuple payload counted once
    assert out["count"] == 4


def test_roofline_terms_math():
    from repro.roofline.analysis import analyze_cell

    rec = dict(
        status="ok", arch="x", shape="train_4k", mesh="16x16", chips=256,
        step="train_step", flops=197e12, bytes_accessed=819e9,
        collectives={"all-gather": 50e9, "all-reduce": 0,
                     "reduce-scatter": 0, "all-to-all": 0,
                     "collective-permute": 0, "count": 1},
    )
    out = analyze_cell(rec)
    assert out["compute_s"] == pytest.approx(1.0)
    assert out["memory_s"] == pytest.approx(1.0)
    assert out["collective_s"] == pytest.approx(1.0)


# -- subprocess tests (multi-device) -------------------------------------------


def test_debug_mesh_train_bundle_compiles(forced_devices):
    """A smoke-scale arch lowers+compiles on a 2x2 mesh with the same
    sharding machinery as the production dry-run."""
    out = forced_devices("""
        import jax
        from repro.configs import get_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import train_bundle
        from repro.configs.base import ShapeConfig

        cfg = get_config("qwen3-8b").smoke()
        mesh = make_debug_mesh((2, 2), ("data", "model"))
        shape = ShapeConfig("tiny", 32, 8, "train")
        with jax.set_mesh(mesh):
            b = train_bundle(mesh, cfg, shape)
            compiled = jax.jit(
                b.fn, out_shardings=b.out_shardings
            ).lower(*b.in_shapes).compile()
        cost = compiled.cost_analysis()
        print("FLOPS", cost.get("flops", 0))
        print("OK")
    """)
    assert "OK" in out


def test_debug_mesh_serve_bundle_compiles(forced_devices):
    out = forced_devices("""
        import jax
        from repro.configs import get_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import serve_bundle
        from repro.configs.base import ShapeConfig

        cfg = get_config("granite-34b").smoke()   # MQA decode path
        mesh = make_debug_mesh((2, 2), ("data", "model"))
        shape = ShapeConfig("tinydecode", 64, 8, "decode")
        with jax.set_mesh(mesh):
            b = serve_bundle(mesh, cfg, shape)
            compiled = jax.jit(
                b.fn, out_shardings=b.out_shardings
            ).lower(*b.in_shapes).compile()
        print("OK")
    """)
    assert "OK" in out


def test_train_step_runs_on_mesh_and_loss_decreases(forced_devices):
    """End-to-end: real data -> sharded train_step on a 4-device mesh; the
    loss must fall (integration of models+optim+sharding+data)."""
    out = forced_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import make_train_step
        from repro.optim import AdamWConfig
        from repro.optim import adamw as optim
        from repro.models.api import get_api
        from repro.data.pipeline import DataConfig, batch_at

        cfg = get_config("deepseek-7b").smoke()
        api = get_api(cfg)
        mesh = make_debug_mesh((2, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            params, _ = api.init(cfg, jax.random.key(0))
            ocfg = AdamWConfig(lr=1e-2, moments_dtype="float32")
            opt = optim.init(params, ocfg)
            step = jax.jit(make_train_step(cfg, ocfg))
            dc = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
            losses = []
            for i in range(20):
                b = {k: jnp.asarray(v) for k, v in batch_at(dc, i).items()}
                params, opt, m = step(params, opt, b)
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses
        print("LOSS", losses[0], "->", losses[-1])
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_hierarchical_compressed_psum(forced_devices):
    """shard_map int8 cross-pod gradient reduction on a (2,4) pod x data
    mesh: result within quantization error of the exact psum."""
    out = forced_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_debug_mesh
        from repro.optim.compress import hierarchical_psum

        mesh = make_debug_mesh((2, 4), ("pod", "data"))
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal((8, 64)), jnp.float32
        )

        def f(xs):
            return hierarchical_psum(xs, pod_axis="pod", inner_axis="data",
                                     compress=True)

        g = shard_map(
            f, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(),
            check_vma=False,
        )
        got = np.asarray(g(x))
        want = np.asarray(x).sum(axis=0, keepdims=True).repeat(1, 0)
        want = np.asarray(x).reshape(8, 64).sum(0)
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 0.02, rel
        print("REL", rel)
        print("OK")
    """, devices=8)
    assert "OK" in out


@pytest.mark.parametrize("devices", [2, 4, 8])
def test_ring_collective_matmul_property(forced_devices, devices):
    """Property: ring_gather_matmul == naive_gather_matmul == unsharded
    oracle for seeded-random shard counts and shapes, plus the
    codegen-integrated ring lowering (``codegen.collectives.ring_psum``,
    what a searched plan with ``collective=ring`` executes): ring psum ==
    lax.psum == the unsharded sum, including the ``p == 1`` cut path and
    payloads that leave a remainder shard (padding path)."""
    out = forced_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_debug_mesh
        from repro.codegen.collectives import ring_psum
        from repro.launch.overlap import naive_gather_matmul, ring_gather_matmul

        P_TOTAL = jax.device_count()
        rng = np.random.default_rng(100 + P_TOTAL)
        checked_hlo = False
        for case in range(4):
            # random shard count dividing the device pool, random shapes
            ps = [p for p in (1, 2, 4, 8) if P_TOTAL % p == 0 and p <= P_TOTAL]
            # case 0 pins the p == 1 cut path; the rest draw randomly
            p = 1 if case == 0 else int(rng.choice(ps))
            m_loc = int(rng.integers(1, 5))
            k = int(rng.integers(1, 9))
            n = int(rng.integers(1, 9))
            m = p * m_loc
            mesh = make_debug_mesh((p,), ("model",))
            x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
            w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

            ring = shard_map(
                lambda xs, ws: ring_gather_matmul(xs, ws, "model"),
                mesh=mesh, in_specs=(P("model", None), P()),
                out_specs=P(), check_vma=False,
            )
            naive = shard_map(
                lambda xs, ws: naive_gather_matmul(xs, ws, "model"),
                mesh=mesh, in_specs=(P("model", None), P()),
                out_specs=P(), check_vma=False,
            )
            got, want = np.asarray(ring(x, w)), np.asarray(naive(x, w))
            ref = np.asarray(x) @ np.asarray(w)
            np.testing.assert_allclose(want, ref, rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
            if p > 1 and not checked_hlo:
                # the ring variant must collective-permute, not all-gather
                hlo = jax.jit(ring).lower(x, w).compile().as_text()
                assert "collective-permute" in hlo
                checked_hlo = True

            # codegen-integrated ring all-reduce: cut path (p == 1 above
            # when drawn), even split, and remainder payloads
            rows = int(rng.integers(1, 7))   # rows*cols rarely divides p
            cols = int(rng.integers(1, 11))
            y = jnp.asarray(
                rng.standard_normal((p, rows, cols)), jnp.float32
            )
            rp = shard_map(
                lambda v: ring_psum(v[0], "model"), mesh=mesh,
                in_specs=P("model"), out_specs=P(), check_vma=False,
            )
            pp = shard_map(
                lambda v: lax.psum(v[0], "model"), mesh=mesh,
                in_specs=P("model"), out_specs=P(), check_vma=False,
            )
            got_r, want_r = np.asarray(rp(y)), np.asarray(pp(y))
            oracle = np.asarray(y).sum(0)
            np.testing.assert_allclose(want_r, oracle, rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(got_r, oracle, rtol=1e-4, atol=1e-5)
        print("OK")
    """, devices=devices)
    assert "OK" in out


def test_pipeline_parallelism_over_pod_axis(forced_devices):
    """GPipe schedule over a 4-stage pipe axis == sequential layer stack."""
    out = forced_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.pipeline import bubble_fraction, pipeline_apply

        P_STAGES, M, MB, D = 4, 6, 3, 8
        mesh = make_debug_mesh((4,), ("pipe",))
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.standard_normal((P_STAGES, D, D)) * 0.5,
                         jnp.float32)
        xs = jnp.asarray(rng.standard_normal((M, MB, D)), jnp.float32)

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        piped = shard_map(
            lambda ws, mb: pipeline_apply(stage_fn, ws, mb, "pipe"),
            mesh=mesh,
            in_specs=(P("pipe", None, None), P()),
            out_specs=P(),
            check_vma=False,
        )
        got = np.asarray(piped(Ws, xs))

        ref = np.asarray(xs)
        for s in range(P_STAGES):
            ref = np.tanh(ref @ np.asarray(Ws[s]))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
        assert abs(bubble_fraction(4, 6) - 3/9) < 1e-9
        print("OK")
    """, devices=4)
    assert "OK" in out


# -- entry-point set-up: the compile cache and --mesh -------------------------


@pytest.fixture
def restore_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_compile_cache_honours_the_env_var(
    monkeypatch, tmp_path, restore_compile_cache
):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_falls_back_to_a_fixed_repo_dir(
    monkeypatch, restore_compile_cache
):
    """Without the variable the cache is ``.jax_cache/`` at the repository
    root on every call: a path derived from a pid, a temporary name or the
    time would never be found again."""
    import os

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert enable_compile_cache() == want
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_mesh_with_too_few_devices_is_an_error(monkeypatch, capsys):
    """``--mesh`` that the process cannot host fails; it never serves on a
    single device instead."""
    import sys

    import jax

    from repro.launch import serve
    from repro.launch.mesh import hosted_mesh

    n = jax.device_count()
    with pytest.raises(ValueError, match=f"needs {2 * (n + 1)} devices"):
        hosted_mesh((2, n + 1))
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "qwen3-8b", "--smoke", "--mesh", f"2x{n + 1}",
    ])
    with pytest.raises(SystemExit) as exit_:
        serve.main()
    assert exit_.value.code == 2
    assert f"needs {2 * (n + 1)} devices" in capsys.readouterr().err
