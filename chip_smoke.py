"""Bring-up check: qwen3-8b serving and its generated Pallas kernels on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --mesh 2x2   # four chips: the mesh tier only

On one chip, in one process, the phases are:

1. device  — fail unless JAX's first device is a TPU;
2. model   — qwen3-8b at every published width, cut to 8 of its 36 layers
             so the random bf16 weights fit one 16 GB chip;
3. serve   — the continuous-batching engine behind the gateway (the objects
             ``python -m repro.launch.serve`` builds) serves 8 requests with
             prompts of 128/256/512 tokens on 128 decode lanes, after a
             warm-up that compiles every shape; every request must finish
             with its ``max_new`` tokens, the first request served alone must
             repeat its batched tokens, and both the prefill and the decode
             step must contain generated kernels (``tpu_custom_call``);
4. kernels — every matmul kernel the serve phase dispatched, run once more
             against an f32 ``jnp.dot`` of the same bf16 operands;
5. search  — the paper's pipeline (enumerate, prune, measure on the chip)
             for the 512x4096x12288 up projection; every measured candidate
             is checked against the einsum oracle.

``--mesh 2x2`` runs only the mesh tier: the sharded-reduction search for
the 512x12288x4096 down projection on the real mesh (psum and ring both
measured), then ``ops.dense`` under ``jax.set_mesh`` dispatching the
winner as a ``MeshBoundKernel``, compared with the single-chip kernel and
the einsum oracle.

Every phase prints what it found.  A failure raises and exits non-zero
with no result line; on success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The autotune cache and the plan DB start empty (``.repro_cache/``), so
what runs comes from the committed files alone; compiled programs persist
in JAX's compilation cache (``repro.launch.compile_cache``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: the autotune cache and plan DB of this run, emptied at start
CACHE_DIR = os.path.join(ROOT, ".repro_cache")
os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(CACHE_DIR, "autotune.json")
os.environ["REPRO_PLAN_DB"] = os.path.join(CACHE_DIR, "plans.json")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import codegen, obs, ops  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.enumerate import matmul_spec  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import hosted_mesh  # noqa: E402
from repro.launch.serving import (  # noqa: E402
    ContinuousEngine,
    Gateway,
    ServeRequest,
    synthetic_trace,
)
from repro.search import (  # noqa: E402
    default_plan_db,
    einsum_reference,
    parse_mesh_shape,
    search_schedule,
    serving_phase,
)

#: bf16 keeps 8 significant bits, so rounding the f32 accumulator into the
#: bf16 output costs up to 2^-8 of each value; twice that leaves room for
#: accumulation-order differences
KERNEL_TOL = 2.0 ** -7

#: a sharded reduction rounds each shard's partial sum to bf16 before the
#: all-reduce and then rounds the sum: three roundings of up to 2^-8 of
#: values that can exceed the result, so four times that
MESH_TOL = 2.0 ** -6

#: published qwen3-8b widths (hf:Qwen/Qwen3-8B config.json)
PUBLISHED = dict(d_model=4096, n_heads=32, n_kv_heads=8, hd=128,
                 d_ff=12288, vocab=151936, dtype="bfloat16")
SERVE_LAYERS = 8


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class CompileClock:
    """Backend compiles (persistent-cache reads included) and cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.seconds, self.count, self.cache_hits)

    def since(self, snap) -> str:
        s, n, h = snap
        return (f"{self.count - n} compile(s) in {self.seconds - s:.1f} s, "
                f"{self.cache_hits - h} read from the persistent cache")


def kernel_calls(hlo: str, what: str) -> int:
    """Generated (Mosaic) kernel calls in a lowered or compiled program."""
    n = hlo.count("tpu_custom_call")
    assert n > 0, f"{what} dispatches no generated kernel"
    return n


def device_phase():
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU chip found — JAX sees {len(devices)} "
            f"{dev.platform} device(s) ({dev.device_kind})"
        )
    say("device", f"{dev.device_kind} x{len(devices)} "
        f"(platform {dev.platform}, jax {jax.__version__})")
    return dev, len(devices)


def model_phase():
    cfg = dataclasses.replace(get_config("qwen3-8b"), n_layers=SERVE_LAYERS)
    got = {k: (str(cfg.param_dtype) if k == "dtype" else getattr(cfg, k))
           for k in PUBLISHED}
    assert got == PUBLISHED, f"not the published widths: {got}"
    say("model", f"qwen3-8b at published widths {got}, "
        f"{cfg.n_layers} of 36 layers")
    return cfg


def _request(rid, plen, max_new, vocab, rng):
    return ServeRequest(
        rid=rid, prompt=rng.integers(0, vocab, size=plen).astype(np.int32),
        max_new=max_new,
    )


def serve_phase(cfg, clock, *, lanes=128, page_size=16, n_requests=8,
                prompt_lens=(128, 256, 512), max_news=(16, 32), seed=0):
    max_ctx = max(prompt_lens) + max(max_news) + 1
    pages_per_req = -(-max_ctx // page_size)
    # the pool holds every request at full length: no admission headroom
    # (watermark) is needed and nothing is ever preempted
    t0 = time.perf_counter()
    engine = ContinuousEngine(
        cfg, lanes=lanes, page_size=page_size,
        n_pages=1 + n_requests * pages_per_req, max_ctx=max_ctx,
        watermark=0,
    )
    jax.block_until_ready(engine.params)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    pool_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.pools))
    say("model", f"{param_bytes} parameter bytes "
        f"({param_bytes / 1e9:.2f} GB), KV pool {pool_bytes / 1e9:.2f} GB, "
        f"initialised in {time.perf_counter() - t0:.1f} s")

    rng = np.random.default_rng(seed)
    gateway = Gateway(engine)
    memo = obs.counter("codegen.memo.miss")
    snap = clock.snapshot()
    t0 = time.perf_counter()
    warm = [_request(i, p, 2, cfg.vocab, rng)
            for i, p in enumerate(prompt_lens)]
    gateway.run(warm)
    say("serve", f"warm-up of {len(warm)} request(s) took "
        f"{time.perf_counter() - t0:.1f} s: {clock.since(snap)}; "
        f"{memo.value} generated kernel(s) built")
    assert memo.value > 0, "serving built no generated kernel"

    trace = synthetic_trace(
        n_requests, vocab=cfg.vocab, seed=seed, rate_hz=0,
        prompt_lens=prompt_lens, max_news=max_news,
    )
    snap = clock.snapshot()
    st = gateway.run(trace)
    for r in trace:
        assert r.state == "finished" and len(r.out_tokens) == r.max_new, (
            r.rid, r.state, len(r.out_tokens), r.max_new)
        assert all(0 <= t < cfg.vocab for t in r.out_tokens), r.rid
    say("serve", f"{len(trace)} request(s), prompts "
        f"{sorted(len(r.prompt) for r in trace)}, {st['tokens']} tokens: "
        f"prefill {st['prefill_s']:.3f} s, decode {st['decode_s']:.3f} s "
        f"over {st['decode_steps']} step(s) ({st['tok_per_s']:.1f} decode "
        f"tok/s), {st['preemptions']} preemption(s); {clock.since(snap)}")

    first = trace[0]
    solo = ServeRequest(rid=first.rid, prompt=first.prompt,
                        max_new=first.max_new)
    gateway.run([solo])
    assert solo.out_tokens == first.out_tokens, (
        solo.out_tokens, first.out_tokens)
    say("serve", f"request {first.rid} served alone repeats its "
        f"{len(first.out_tokens)} batched tokens")
    return engine


def steps_phase(engine, plen=128):
    """Both serving steps, lowered as they run, must call generated
    kernels."""
    lanes = engine.lanes
    toks = jnp.zeros((1, plen), jnp.int32)
    with serving_phase("prefill"):
        prefill_hlo = engine.prefill._run.lower(
            engine.params, toks, jnp.full((1,), plen, jnp.int32)
        ).as_text()
    bt = jnp.zeros((lanes, engine.max_pages), jnp.int32)
    with serving_phase("decode"):
        decode_hlo = engine.decode._step.lower(
            engine.params, engine.pools, bt, jnp.zeros((lanes,), jnp.int32),
            jnp.zeros((lanes,), jnp.int32),
        ).as_text()
    for name, hlo in (("prefill", prefill_hlo), ("decode", decode_hlo)):
        say("serve", f"{name} step holds {kernel_calls(hlo, name)} "
            f"generated-kernel call(s)")


def kernel_phase(seed=0):
    kernels = [
        k for k in codegen.compiled_kernels()
        if isinstance(k, codegen.CompiledKernel) and k.spec.name == "matmul"
    ]
    assert kernels, "no matmul kernel was dispatched"
    key = jax.random.key(seed)
    worst = 0.0
    for kern in sorted(kernels, key=lambda k: tuple(k.spec.extents.values())):
        m, k, n = (kern.spec.root().extents[i] for i in ("i", "j", "k"))
        ka, kb, key = jax.random.split(key, 3)
        a = jax.random.normal(ka, (m, k), jnp.bfloat16)
        b = (jax.random.normal(kb, (k, n), jnp.float32)
             / math.sqrt(k)).astype(jnp.bfloat16)
        out = kern(a, b).astype(jnp.float32)
        ref = jnp.dot(a, b, preferred_element_type=jnp.float32)
        assert out.shape == ref.shape and bool(jnp.isfinite(out).all())
        err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        blocks = {i: kern.plan.axes[i].block for i in ("i", "j", "k")}
        say("kernels", f"matmul {m}x{k}x{n} blocks {blocks}: "
            f"max rel err {err:.2e}")
        assert err <= KERNEL_TOL, (m, k, n, err)
        worst = max(worst, err)
    say("kernels", f"{len(kernels)} kernel(s) within {KERNEL_TOL:.2e} "
        f"(bf16 output rounding); worst {worst:.2e}")


def _levels(schedule):
    return " ".join(f"{l.index}:{l.tier}:{l.extent}"
                    for l in schedule.levels if l.tier != "mxu")


def search_phase():
    spec = matmul_spec(512, 4096, 12288)
    t0 = time.perf_counter()
    res = search_schedule(
        spec, dtype=jnp.bfloat16, interpret=False, measure=True,
        plan_db=default_plan_db(),
    )
    measured = [p for p in res.ranked if p.measured_s is not None]
    assert len(measured) >= 2, f"measured {len(measured)} candidate(s)"
    for p in measured:
        say("search", f"{p.source:>7} {p.measured_s * 1e6:9.1f} us "
            f"err {p.max_err:.2e}  {_levels(p.schedule) or 'unblocked'}")
    best = res.best
    say("search", f"512x4096x12288 bf16: {res.stats.considered} considered, "
        f"{len(measured)} measured on the chip in "
        f"{time.perf_counter() - t0:.1f} s; winner "
        f"{_levels(best.schedule)} at {best.measured_s * 1e6:.1f} us")


def mesh_phase(mesh_shape, m=512, k=12288, n=4096):
    n_dev = math.prod(mesh_shape)
    mesh = hosted_mesh(mesh_shape)
    spec = matmul_spec(m, k, n)
    dt = jnp.bfloat16
    res = search_schedule(
        spec, dtype=dt, mesh_shape=mesh_shape, interpret=False,
        measure=True, plan_db=default_plan_db(),
    )
    for p in res.ranked:
        say("mesh", f"{p.collective or '-':>4} {p.measured_s * 1e6:9.1f} us "
            f"err {p.max_err:.2e}  {_levels(p.schedule)}")
    measured = {p.collective for p in res.ranked if p.sharded}
    assert {"psum", "ring"} <= measured, f"collectives measured: {measured}"

    rng = np.random.default_rng(0)
    x_np = rng.standard_normal((m, k)).astype(dt)
    w_np = (rng.standard_normal((k, n)) / math.sqrt(k)).astype(dt)
    ref = einsum_reference(spec, {"A": x_np, "B": w_np})
    scale = float(np.abs(ref).max())

    before = len(codegen.compiled_kernels())
    with jax.set_mesh(mesh):
        dense = jax.jit(ops.dense).lower(x_np, w_np).compile()
        out = dense(x_np, w_np)
    bound = [k for k in codegen.compiled_kernels()[before:]
             if isinstance(k, codegen.MeshBoundKernel)]
    assert bound, "ops.dense under the mesh dispatched no MeshBoundKernel"
    kern = bound[0]
    kernel_calls(dense.as_text(), "ops.dense under the mesh")
    devices = out.sharding.device_set
    assert len(devices) == n_dev, f"result lives on {len(devices)} device(s)"
    mesh_err = float(np.abs(np.asarray(out, np.float64) - ref).max()) / scale
    say("mesh", f"ops.dense under the {'x'.join(map(str, mesh_shape))} mesh "
        f"ran {_levels(kern.schedule)} with {kern.collective} over "
        f"{len(devices)} devices: rel err vs oracle {mesh_err:.2e}")
    assert mesh_err <= MESH_TOL, mesh_err

    single = codegen.cached_compile(
        spec, codegen.tune_schedule(spec, dtype=np.dtype(dt))
    )
    dev0 = jax.devices()[0]
    one = single(jax.device_put(x_np, dev0), jax.device_put(w_np, dev0))
    one_err = float(np.abs(np.asarray(one, np.float64) - ref).max()) / scale
    gap = float(np.abs(np.asarray(out, np.float64)
                       - np.asarray(one, np.float64)).max()) / scale
    say("mesh", f"single-chip kernel {_levels(single.schedule)}: rel err vs "
        f"oracle {one_err:.2e}, vs the mesh result {gap:.2e}")
    assert one_err <= KERNEL_TOL and gap <= MESH_TOL, (one_err, gap)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, metavar="AxB",
                    help="run only the mesh tier on this mesh (e.g. 2x2)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the requests and kernel operands")
    args = ap.parse_args()

    shutil.rmtree(CACHE_DIR, ignore_errors=True)
    os.makedirs(CACHE_DIR)
    say("caches", f"compilation cache {enable_compile_cache()}; autotune "
        f"cache and plan DB emptied under {CACHE_DIR}")
    dev, count = device_phase()
    if args.mesh:
        mesh_phase(parse_mesh_shape(args.mesh))
    else:
        clock = CompileClock()
        cfg = model_phase()
        steps_phase(serve_phase(cfg, clock, seed=args.seed))
        kernel_phase(seed=args.seed)
        search_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count,
    }}))


if __name__ == "__main__":
    main()
