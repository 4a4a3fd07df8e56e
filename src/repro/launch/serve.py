"""Serving CLI: continuous-batching paged-KV engine, fixed-slot baseline.

``--engine continuous`` (default) drives the ``launch.serving`` tier: a
block-table paged KV cache, FCFS continuous batching (requests join the
decode batch the step after prefill, free their pages the step they
finish, preempt-newest recompute when the pool runs dry) and separate
phase-tagged prefill/decode plan ladders.  ``--engine fixed`` keeps this
module's original :class:`BatchServer` — requests packed into a fixed
decode batch that rounds every group up to its longest member — as the
differential and throughput baseline.  Both engines emit one token per
step per live request until max_new or ``--eos-id``, and under greedy
decoding produce identical per-request outputs.  On the production mesh
the cache shardings come from launch.steps.serve_bundle.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --requests 8 --prompt-len 32 --max-new 16 --engine continuous

Observability (``repro.obs``): prefill and every decode step run inside
trace spans, each finished request records into the
``serve.request_latency_s`` histogram (p50/p99 in the metrics dump), and
``serve.tokens``/``serve.tok_per_s`` plus the plan-DB/autotune hit
counters quantify how much of the traffic ran searched kernels.
``--metrics-out FILE`` / ``--trace-out FILE`` write the registry snapshot
and the Chrome trace after the run; ``scripts/obs_report.py`` renders
both.  Log lines go through ``obs.log`` (``REPRO_LOG=quiet|info|debug``).
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs import get_config
from ..models.api import get_api
from ..obs import log
from .compile_cache import enable_compile_cache
from .mesh import hosted_mesh


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (prompt_len,)
    max_new: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class BatchServer:
    """Fixed-slot batch server (the slot count is the serving batch size)."""

    def __init__(self, cfg, *, batch_size: int, max_len: int,
                 extra_batch=None, warm_gemms=(), search_gemms=(),
                 search_grads: bool = True, capture: bool = False,
                 mesh_shape=None, quant: Optional[str] = None):
        self.cfg = cfg
        self.api = get_api(cfg)
        self.batch_size = batch_size
        self.max_len = max_len
        self.extra_batch = extra_batch or {}
        # --quant int8: weight-only serving quantization.  Params are
        # quantized ONCE at load (optim.quant.quantize_tree — Quantized is
        # a registered pytree node, so the 8-bit tree flows through jit)
        # and expanded INSIDE the jitted step closures: live weights stay
        # int8 + scales in device memory, the f32 copies are jit
        # temporaries.
        self.quant = quant
        # --mesh AxB: sweeps below additionally persist mesh-qualified
        # sharded ladders, and the serving steps trace under the mesh so
        # ops._tuned_kernel dispatches through the sharded generated
        # kernels (codegen.bind_mesh).
        self.mesh = None
        self.mesh_shape = None
        if mesh_shape:
            self.mesh = hosted_mesh(mesh_shape)
            self.mesh_shape = tuple(self.mesh.axis_sizes)
        # Whole-model capture: harvest the prefill + decode GEMM sets
        # (abstract trace — no allocation), sweep every harvested spec
        # into the ranked plan DB (fwd, plus derived bwd specs unless
        # --no-search-grads so a co-located training fleet benefits from
        # the same warmup), and route serving steps through
        # capture.optimize so the eligible sites dispatch.
        self.capture = capture
        if capture:
            from .. import capture as _capture
            from ..search import default_plan_db

            # One abstract trace per serving entry point covers the
            # report, the sweepable spec set AND the summary.
            # interpret=True classifies eligibility as if kernels can run
            # (what a TPU replica dispatches); the measurement below still
            # uses the interpreter only where there is no TPU.
            points = {}
            for kind in ("prefill", "decode"):
                _, rep = _capture.model_capture(
                    cfg, batch=batch_size, seq=max_len, kind=kind,
                    interpret=True,
                )
                log.info("serve", rep.summary())
                for spec, dt in rep.unique_specs():
                    points.setdefault(
                        _capture.spec_key(spec, dt),
                        (f"{kind}:{spec.name}", spec, dt),
                    )
            db = default_plan_db()
            n = _capture.sweep_captured(
                list(points.values()), with_grads=search_grads, plan_db=db,
                mesh_shape=self.mesh_shape,
                quant=self.quant,
            )
            log.info("serve", f"capture swept {n} plan point(s) "
                     f"({len(points)} unique GEMM spec(s)) -> {db.path}")
        # Serving replicas reuse the fleet's tuned kernel schedules: warm
        # the persistent codegen cache before the first request arrives.
        if warm_gemms:
            from ..codegen import default_cache
            from ..ops import warm_dense_cache

            cache = default_cache()
            n = warm_dense_cache(warm_gemms)
            log.info("serve", f"warmed {n} GEMM schedule(s) "
                     f"(cache {cache.path}: {cache.hits} hit, "
                     f"{cache.misses} miss)")
        # The stronger warmup: run the full cost-guided search (enumerate
        # -> prune -> measure) and persist the ranked plans; ops.dense
        # prefers these over the analytic tuner from then on.  Hits the
        # plan DB on repeat shapes, so restarts pay nothing.
        if search_gemms:
            from ..search import default_plan_db, search_gemm_plans

            db = default_plan_db()
            # bfloat16 to match warm_dense_cache: the plan key must equal
            # the one ops.dense derives from the serving activations.
            # search_grads: the plan DB is fleet-shared, so the same
            # warmup also sweeps each GEMM's derived backward specs
            # (repro.grad) and training replicas pick up searched
            # cotangent kernels; --no-search-grads skips the 2 extra
            # sweeps per shape on inference-only replicas.
            n = search_gemm_plans(
                search_gemms,
                dtype=jnp.bfloat16,
                plan_db=db,
                with_grads=search_grads,
                mesh_shape=self.mesh_shape,
            )
            what = "fwd + derived bwd" if search_grads else "fwd only"
            at = (f" + mesh={'x'.join(map(str, self.mesh_shape))}"
                  if self.mesh_shape else "")
            log.info("serve", f"searched {n} GEMM plan(s) "
                     f"({what}{at}) -> {db.path}")
        # pre-register the cache-effectiveness counters so a metrics dump
        # always carries plan-DB/autotune hit counts, zero included (a
        # replica whose traffic never consulted the DB should say 0, not
        # omit the row)
        for name in ("plandb.hit", "plandb.miss", "autotune.hit",
                     "autotune.miss"):
            obs.counter(name).inc(0)
        self.params, _ = self.api.init(cfg, jax.random.key(0))
        if self.quant:
            from ..optim.quant import (dequantize_tree, quantize_tree,
                                       tree_quant_bytes)

            self.params = quantize_tree(self.params, fmt=self.quant)
            qb = tree_quant_bytes(self.params)
            obs.gauge("serve.quant_bytes").set(qb)
            log.info("serve", f"weight-only {self.quant}: "
                     f"{qb / 2**20:.2f} MiB held as quantized leaves")
            _deq = dequantize_tree
        else:
            _deq = lambda p: p  # noqa: E731
        decode_fn = lambda p, c, t: self.api.decode_step(  # noqa: E731
            _deq(p), self.cfg, c, t
        )
        prefill_fn = lambda p, b: self.api.prefill(  # noqa: E731
            _deq(p), self.cfg, b, self.max_len
        )
        if self.capture:
            from .. import capture as _capture

            decode_fn = _capture.optimize(
                decode_fn, label=f"{cfg.arch_id}:decode", quant=self.quant
            )
            prefill_fn = _capture.optimize(
                prefill_fn, label=f"{cfg.arch_id}:prefill", quant=self.quant
            )
        self._decode = jax.jit(decode_fn)
        self._prefill_fn = prefill_fn

    def _mesh_ctx(self):
        """Trace/run context: the serving mesh, if any, else a no-op.

        Entering the mesh at call time is what lets ``ops._tuned_kernel``
        (consulted while jit traces the step) see an active mesh and pick
        the mesh-qualified sharded plans this server swept.
        """
        import contextlib

        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _prefill(self, tokens: np.ndarray, lengths=None):
        batch = {"tokens": jnp.asarray(tokens), **self.extra_batch}
        if lengths is not None:
            batch["lengths"] = jnp.asarray(lengths, jnp.int32)
        with self._mesh_ctx():
            return self._prefill_fn(self.params, batch)

    def _pack(self, requests: List[Request]):
        """Pack prompts into the slot matrix; returns (tokens, lengths).

        Attention families right-pad and carry per-row true lengths, so
        prefill masks the pads out and a short prompt decodes identically
        batched or solo.  SSM/hybrid recurrences fold every input token
        into their state — no attention mask can unpollute it — so those
        keep the legacy left-pad (lengths=None) and equal-length prompts.
        """
        plen = max(len(r.prompt) for r in requests)
        toks = np.zeros((self.batch_size, plen), np.int32)
        if self.cfg.family in ("ssm", "hybrid"):
            for i, r in enumerate(requests):
                toks[i, plen - len(r.prompt):] = r.prompt
            return toks, None
        lengths = np.ones((self.batch_size,), np.int32)
        for i, r in enumerate(requests):
            toks[i, :len(r.prompt)] = r.prompt
            lengths[i] = len(r.prompt)
        return toks, lengths

    def run(self, requests: List[Request], greedy: bool = True,
            eos_id: Optional[int] = None):
        assert len(requests) <= self.batch_size
        latency = obs.histogram("serve.request_latency_s")
        t0 = time.time()

        def finish(r: Request):
            r.done = True
            # request latency = arrival (run entry) to last token — or to
            # prefill completion for max_new=0, which still counts as a
            # served request
            latency.observe(time.time() - t0)
            obs.counter("serve.requests").inc()

        def emit(next_host: np.ndarray):
            """Append one token per live request; finish on max_new/EOS."""
            for i, r in enumerate(requests):
                if r.done:
                    continue
                tok = int(next_host[i])
                r.out_tokens.append(tok)
                if (len(r.out_tokens) >= r.max_new
                        or (eos_id is not None and tok == eos_id)):
                    finish(r)

        toks, lengths = self._pack(requests)
        with obs.span("serve.prefill", batch=len(requests),
                      prompt_len=toks.shape[1]):
            logits, caches = self._prefill(toks, lengths)
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        prefill_s = time.time() - t0

        # max_new=0 requests are complete the moment prefill returns:
        # nothing to emit, but the latency/served-request accounting must
        # still see them (they used to spin the full decode loop and never
        # record).
        for r in requests:
            if not r.done and r.max_new <= 0:
                finish(r)
        # Each request's first token comes from the *prefill* logits —
        # emit it before the decode clock starts so tok/s measures pure
        # decode throughput.
        if not all(r.done for r in requests):
            emit(np.asarray(next_tok))
        n_prefill_tokens = sum(len(r.out_tokens) for r in requests)

        t1 = time.time()
        steps = 0
        with obs.span("serve.decode", batch=len(requests)):
            # while-before-dispatch: when emit() finishes the last
            # request, the loop exits without a wasted trailing decode
            # dispatch
            while not all(r.done for r in requests):
                with obs.span("serve.decode.step", step=steps):
                    with self._mesh_ctx():
                        logits, caches = self._decode(
                            self.params, caches, next_tok[:, None]
                        )
                    next_tok = jnp.argmax(
                        logits[:, -1], axis=-1
                    ).astype(jnp.int32)
                steps += 1
                emit(np.asarray(next_tok))
        decode_s = time.time() - t1
        n_tokens = sum(len(r.out_tokens) for r in requests)
        n_decode_tokens = n_tokens - n_prefill_tokens
        tok_per_s = n_decode_tokens / max(decode_s, 1e-9)
        obs.counter("serve.tokens").inc(n_tokens)
        obs.gauge("serve.tok_per_s").set(tok_per_s)
        return dict(
            prefill_s=prefill_s,
            decode_s=decode_s,
            decode_steps=steps,
            tokens=n_tokens,
            decode_tokens=n_decode_tokens,
            tok_per_s=tok_per_s,
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument(
        "--engine", choices=("continuous", "fixed"), default="continuous",
        help="'continuous': slot-free continuous batching over the paged "
             "KV pool (launch.serving) — requests join decode the step "
             "after their prefill and free pages the step they finish.  "
             "'fixed': the legacy fixed-slot BatchServer, kept as the "
             "differential baseline.  Non-attention families (ssm/hybrid) "
             "always serve fixed",
    )
    ap.add_argument(
        "--lanes", type=int, default=4,
        help="decode batch width: concurrent requests per decode step "
             "(continuous) / slots per group (fixed)",
    )
    ap.add_argument(
        "--page-size", type=int, default=16,
        help="KV page size in tokens (continuous engine)",
    )
    ap.add_argument(
        "--pages", type=int, default=0,
        help="physical KV pages in the pool; 0 sizes it so every lane "
             "can reach max context without preemption",
    )
    ap.add_argument(
        "--eos-id", type=int, default=None,
        help="token id that finishes a request early (default: none — "
             "requests run to max_new)",
    )
    ap.add_argument(
        "--rate-hz", type=float, default=200.0,
        help="Poisson arrival rate of the synthetic trace; 0 = all "
             "requests arrive at t=0 (saturated queue)",
    )
    ap.add_argument("--seed", type=int, default=0,
                    help="trace seed (prompts, lengths, arrivals)")
    ap.add_argument(
        "--warm-gemms", default="",
        help="semicolon-separated M,K,N GEMM shapes to pre-tune "
             "through the codegen cache, e.g. '4096,4096,4096;128,4096,512'",
    )
    ap.add_argument(
        "--search-gemms", default="",
        help="semicolon-separated M,K,N GEMM shapes to run the full "
             "cost-guided variant search on (enumerate -> prune -> "
             "measure) and persist as ranked plans; ops.dense then "
             "serves the measured winner.  Derived backward specs "
             "(repro.grad) are swept alongside each shape unless "
             "--no-search-grads",
    )
    ap.add_argument(
        "--no-search-grads", action="store_true",
        help="with --search-gemms/--capture, sweep only the forward "
             "specs (inference-only replicas skip the backward-plan "
             "cost)",
    )
    ap.add_argument(
        "--mesh", default=None, metavar="AxB",
        help="mesh shape ('2x4' = data x model) for the distributed "
             "schedule tier: --search-gemms/--capture sweeps also persist "
             "mesh-qualified sharded ladders, and the serving steps trace "
             "under it so eligible GEMMs dispatch through sharded "
             "generated kernels.  An error when this process has fewer "
             "devices than the mesh",
    )
    ap.add_argument(
        "--metrics-out", default=None, metavar="FILE",
        help="write the repro.obs metrics registry (per-request latency "
             "p50/p99, tokens/sec, plan-DB/autotune hit counts, capture "
             "dispatch counts) as JSON after the run",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="FILE",
        help="write the Chrome-trace/Perfetto span JSON (prefill, decode "
             "steps, search/codegen phases) after the run; load it at "
             "chrome://tracing or summarize with scripts/obs_report.py "
             "--trace",
    )
    ap.add_argument(
        "--quant", choices=("none", "int8"), default="none",
        help="weight-only serving quantization: parameters are quantized "
             "once at load (block-wise int8 + per-block f32 scales, "
             "optim.quant.quantize_tree) and dequantized inside the "
             "jitted serving steps, so live weights stay 8-bit in device "
             "memory.  With --capture the dispatched dense sites also run "
             "the dynamic-quantized kernel tier and the capture sweep "
             "persists quantized plan legs",
    )
    ap.add_argument(
        "--capture", action="store_true",
        help="whole-model capture (repro.capture): harvest the prefill "
             "+ decode GEMM sets, sweep every harvested spec into the "
             "ranked plan DB, and serve through the captured steps so "
             "eligible dot_general sites dispatch through generated "
             "kernels",
    )
    args = ap.parse_args()
    if args.mesh:
        try:
            hosted_mesh(args.mesh)
        except ValueError as e:
            ap.error(f"--mesh: {e}")
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()

    def _parse_shapes(flag: str, raw: str):
        try:
            shapes = tuple(
                tuple(int(x) for x in part.split(","))
                for part in raw.split(";")
                if part.strip()
            )
            if any(len(t) != 3 for t in shapes):
                raise ValueError(shapes)
            return shapes
        except ValueError:
            ap.error(f"{flag} expects 'M,K,N[;M,K,N...]', got {raw!r}")

    warm = _parse_shapes("--warm-gemms", args.warm_gemms)
    search = _parse_shapes("--search-gemms", args.search_gemms)
    quant = None if args.quant == "none" else args.quant

    from .serving import (ContinuousEngine, FixedEngine, Gateway,
                          synthetic_trace)

    trace = synthetic_trace(
        args.requests,
        vocab=cfg.vocab,
        seed=args.seed,
        rate_hz=args.rate_hz,
        prompt_lens=tuple(sorted({
            max(1, args.prompt_len // 4),
            max(1, args.prompt_len // 2),
            args.prompt_len,
        })),
        max_news=tuple(sorted({max(1, args.max_new // 4), args.max_new})),
    )
    max_ctx = args.prompt_len + args.max_new + 1
    engine_kind = args.engine
    if engine_kind == "continuous" and cfg.family not in ("dense", "moe"):
        log.info("serve", f"family {cfg.family!r} has unpageable state — "
                 "serving fixed-slot")
        engine_kind = "fixed"
    if engine_kind == "continuous":
        pages_per_req = -(-max_ctx // args.page_size)
        n_pages = args.pages or (1 + args.lanes * pages_per_req)
        engine = ContinuousEngine(
            cfg,
            lanes=args.lanes,
            page_size=args.page_size,
            n_pages=n_pages,
            max_ctx=max_ctx,
            search_gemms=search,
            search_grads=not args.no_search_grads,
            mesh_shape=args.mesh,
            quant=quant,
        )
    else:
        engine = FixedEngine(
            cfg,
            lanes=args.lanes,
            max_ctx=max_ctx,
            warm_gemms=warm,
            search_gemms=search,
            search_grads=not args.no_search_grads,
            capture=args.capture,
            mesh_shape=args.mesh,
            quant=quant,
        )
    stats = Gateway(engine).run(trace, eos_id=args.eos_id)
    log.info(
        "serve",
        f"[{engine_kind}] prefill {stats['prefill_s']*1e3:.1f} ms, "
        f"decode {stats['decode_s']*1e3:.1f} ms over "
        f"{stats['decode_steps']} step(s), {stats['tokens']} tokens at "
        f"{stats['tok_per_s']:.1f} decode tok/s"
    )
    if args.metrics_out:
        log.info("serve", f"metrics -> {obs.metrics_dump(args.metrics_out)}")
    if args.trace_out:
        log.info("serve", f"trace -> {obs.trace_dump(args.trace_out)}")


if __name__ == "__main__":
    main()
