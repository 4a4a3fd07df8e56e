"""Serving engines: continuous batching over paged KV, and the legacy
fixed-slot server behind the same ``run()`` interface.

:class:`ContinuousEngine` is slot-free.  Each loop iteration:

1. moves arrived requests into the scheduler (fast-forwarding the clock
   when everything is idle, so a sparse trace doesn't busy-wait),
2. admits FCFS from the queue head into free decode lanes — each
   admission prefills its context batch-1 (phase ``prefill``) straight
   into freshly allocated pages and emits its first token,
3. grows every running request's block table for the position its next
   decode writes, preempting the newest admission when the pool is dry,
4. runs ONE decode step across all lanes (phase ``decode``, fixed
   shapes, compiled once) and emits one token per live request.

Spans, in the order they run: ``serve.prefill`` (``rid``; children
``serve.prefill.dispatch`` and ``serve.prefill.wait``) per admission, then
per decode step ``serve.decode.prepare`` (block table, lengths, tokens),
``serve.decode.step`` (children ``serve.decode.dispatch``, the jitted call,
and ``serve.decode.wait``, the blocking copy of the tokens to the host) and
``serve.decode.emit`` (emit, finish, free pages); ``host.gc`` around each
collection of Python's oldest generation.

Requests therefore join the decode batch the step after their prefill
completes and leave it — freeing pages immediately — the step they
finish; short and long requests share lanes without rounding every batch
up to the longest member, which is where the throughput over the
fixed-slot server comes from.

Both engines return the same stats dict (``tok_per_s`` counts *decode*
tokens over decode seconds only — prefill-produced first tokens are
accounted to prefill) and under greedy decoding produce bitwise-equal
per-request outputs, which the differential tests pin.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from ... import obs
from ...configs.base import ModelConfig
from ...models.api import get_api
from . import paged
from .runners import DecodeRunner, PrefillRunner
from .scheduler import Scheduler, ServeRequest


class ContinuousEngine:
    """Continuous-batching serving engine over a paged KV pool."""

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        lanes: int = 4,
        page_size: int = 16,
        n_pages: int = 64,
        max_ctx: Optional[int] = None,
        watermark: Optional[int] = None,
        params=None,
        search_gemms=(),
        search_grads: bool = False,
        mesh_shape=None,
        quant: Optional[str] = None,
    ):
        self.cfg = cfg
        self.api = get_api(cfg)
        self.lanes = lanes
        self.page_size = page_size
        if max_ctx is None:
            # default per-request ceiling: an even share of the pool
            max_ctx = page_size * max(1, (n_pages - 1) // max(1, lanes))
        self.max_pages = -(-max_ctx // page_size)
        self.max_ctx = self.max_pages * page_size
        self.pool = paged.PagePool(n_pages, page_size)
        self.sched = Scheduler(
            self.pool, lanes,
            watermark=lanes if watermark is None else watermark,
        )
        if params is None:
            params, _ = self.api.init(cfg, jax.random.key(0))
        # --quant int8: weight-only tier.  Quantize the tree once here
        # (Quantized leaves are registered pytree nodes) and let the
        # runners dequantize inside their jitted closures — live weights
        # stay 8-bit + scales, f32 copies are jit temporaries.
        self.quant = quant
        if quant:
            from ...obs import log
            from ...optim.quant import quantize_tree, tree_quant_bytes

            params = quantize_tree(params, fmt=quant)
            qb = tree_quant_bytes(params)
            obs.gauge("serve.quant_bytes").set(qb)
            log.info("serve", f"weight-only {quant}: "
                     f"{qb / 2**20:.2f} MiB held as quantized leaves")
        self.params = params
        self.pools = paged.pool_init(cfg, n_pages, page_size)
        self.prefill = PrefillRunner(cfg, self.api, page_size, quant=quant)
        self.decode = DecodeRunner(
            cfg, self.api, page_size, lanes, self.max_pages, quant=quant
        )
        if search_gemms:
            self.prefill.sweep(
                search_gemms, with_grads=search_grads, mesh_shape=mesh_shape
            )
            self.decode.sweep(search_gemms, mesh_shape=mesh_shape)
        # pre-register so a metrics dump always carries the cache counters
        for name in ("plandb.hit", "plandb.miss",
                     "autotune.hit", "autotune.miss"):
            obs.counter(name).inc(0)

    def run(
        self, requests: List[ServeRequest], *, eos_id: Optional[int] = None
    ) -> Dict:
        latency = obs.histogram("serve.request_latency_s")
        ttft = obs.histogram("serve.ttft_s")
        served = obs.counter("serve.requests")
        tokens = obs.counter("serve.tokens")
        pending = collections.deque(
            sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        )
        t0 = time.perf_counter()
        st = dict(prefill_s=0.0, decode_s=0.0, decode_steps=0,
                  prefill_tokens=0, decode_tokens=0, preemptions=0)

        def finish(req: ServeRequest) -> None:
            req.t_done = time.perf_counter()
            if req.state == "running":
                self.sched.finish(req)       # pages freed this very step
            else:
                req.state = "finished"
            latency.observe(req.t_done - req.t_submit)
            served.inc()
            obs.complete_event(
                "serve.request", req.t_submit, req.t_done - req.t_submit,
                rid=req.rid, tenant=req.tenant, prompt_len=len(req.prompt),
                new_tokens=len(req.out_tokens), preemptions=req.preemptions,
            )

        def emit(req: ServeRequest, tok: int, *, from_prefill: bool) -> None:
            req.out_tokens.append(tok)
            if req.t_first is None:
                req.t_first = time.perf_counter()
                ttft.observe(req.t_first - req.t_submit)
            st["prefill_tokens" if from_prefill else "decode_tokens"] += 1
            tokens.inc()
            if (len(req.out_tokens) >= req.max_new
                    or (eos_id is not None and tok == eos_id)):
                finish(req)

        def submit_next() -> None:
            req = pending.popleft()
            req.t_submit = time.perf_counter()
            if req.max_new <= 0:
                # nothing to generate: complete at admission, but the
                # request still counts and its latency is still observed
                finish(req)
                return
            self.sched.submit(req)

        with obs.gc_spans(), obs.span("serve.engine", engine="continuous",
                                      requests=len(requests)):
            while pending or self.sched.queue or self.sched.running:
                now = time.perf_counter() - t0
                while pending and pending[0].arrival_s <= now:
                    submit_next()
                if pending and not self.sched.queue and not self.sched.running:
                    submit_next()   # idle: fast-forward to the next arrival

                for req in self.sched.admit():
                    tp = time.perf_counter()
                    # its start is the request's admission-to-service stamp
                    with obs.span("serve.prefill", rid=req.rid,
                                  tokens=len(req.context_tokens),
                                  padded=len(req.pages) * self.page_size):
                        tok, self.pools = self.prefill(
                            self.params, self.pools, req.context_tokens,
                            req.pages,
                        )
                    st["prefill_s"] += time.perf_counter() - tp
                    emit(req, tok, from_prefill=True)

                if not self.sched.running:
                    continue
                st["preemptions"] += len(self.sched.grow())
                if not self.sched.running:
                    continue

                step = st["decode_steps"]
                with obs.span("serve.decode.prepare", step=step):
                    bt = np.zeros((self.lanes, self.max_pages), np.int32)
                    lens = np.zeros((self.lanes,), np.int32)
                    toks = np.zeros((self.lanes,), np.int32)
                    for lane, req in self.sched.running.items():
                        bt[lane, :len(req.pages)] = req.pages
                        # the last emitted token's KV is not cached yet —
                        # the step about to run writes it at ctx_len - 1
                        lens[lane] = req.ctx_len - 1
                        toks[lane] = req.out_tokens[-1]
                td = time.perf_counter()
                with obs.span("serve.decode.step", step=step,
                              live=len(self.sched.running)):
                    with obs.span("serve.decode.dispatch", step=step):
                        next_tok, self.pools = self.decode(
                            self.params, self.pools, bt, lens, toks
                        )
                    with obs.span("serve.decode.wait", step=step):
                        next_host = np.asarray(next_tok)
                st["decode_s"] += time.perf_counter() - td
                st["decode_steps"] += 1
                with obs.span("serve.decode.emit", step=step):
                    for lane, req in list(self.sched.running.items()):
                        emit(req, int(next_host[lane]), from_prefill=False)

        st["tokens"] = st["prefill_tokens"] + st["decode_tokens"]
        st["tok_per_s"] = st["decode_tokens"] / max(st["decode_s"], 1e-9)
        st["requests"] = len(requests)
        obs.gauge("serve.tok_per_s").set(st["tok_per_s"])
        return st


class FixedEngine:
    """The legacy fixed-slot ``BatchServer`` behind the continuous
    engine's ``run()`` interface — the differential/throughput baseline.

    Requests are chunked FCFS into slot-sized groups; each group prefills
    together and decodes until its last member finishes (the fixed-slot
    cost model: every batch rounds up to its longest request)."""

    def __init__(self, cfg: ModelConfig, *, lanes: int = 4,
                 max_ctx: int = 128, params=None, **server_kw):
        from ..serve import BatchServer

        self.lanes = lanes
        self.server = BatchServer(
            cfg, batch_size=lanes, max_len=max_ctx, **server_kw
        )
        if params is not None:
            self.server.params = params

    def run(
        self, requests: List[ServeRequest], *, eos_id: Optional[int] = None
    ) -> Dict:
        from ..serve import Request

        ordered = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        st = dict(prefill_s=0.0, decode_s=0.0, decode_steps=0,
                  prefill_tokens=0, decode_tokens=0, preemptions=0)
        with obs.span("serve.engine", engine="fixed",
                      requests=len(requests)):
            for i in range(0, len(ordered), self.lanes):
                group = ordered[i:i + self.lanes]
                batch = [
                    Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
                    for r in group
                ]
                t_sub = time.perf_counter()
                for r in group:
                    r.t_submit = t_sub
                s = self.server.run(batch, eos_id=eos_id)
                done = time.perf_counter()
                for r, b in zip(group, batch):
                    r.out_tokens = list(b.out_tokens)
                    r.state = "finished"
                    r.t_done = done
                st["prefill_s"] += s["prefill_s"]
                st["decode_s"] += s["decode_s"]
                st["decode_steps"] += s["decode_steps"]
                st["decode_tokens"] += s["decode_tokens"]
                st["prefill_tokens"] += s["tokens"] - s["decode_tokens"]
        st["tokens"] = st["prefill_tokens"] + st["decode_tokens"]
        st["tok_per_s"] = st["decode_tokens"] / max(st["decode_s"], 1e-9)
        st["requests"] = len(requests)
        return st
