"""One run of one cell: set-up, the measured window, the readings and the
comparison that decides ``correct``.

The window drives the program's own entry, ``Gateway.run`` over a
``ContinuousEngine``, with requests from :mod:`.traffic`.  The engine keeps
``t_submit``, ``t_first`` and ``t_done`` for each request and an obs span
``serve.decode.step`` for each decode step; the benchmark adds the time each
request was due, and reads a token's time as the end of its decode step.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from . import spec, traffic, xtrace
from .peaks import peaks

ROOT = spec.ROOT
TRACE_DIR = os.path.join(ROOT, ".perfbench", "trace")


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Compiles (persistent-cache reads included), cache hits and misses,
    and their seconds, from JAX's monitoring events."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.hits = 0
        self.requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def snapshot(self) -> Dict:
        return dict(compiles=self.count, compile_s=self.seconds,
                    cache_hits=self.hits,
                    cache_misses=self.requests - self.hits)

    def since(self, snap: Dict) -> Dict:
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}


def seed_key(seed: int):
    """The weights' key and the traffic's generator from one seed of any
    size: a SeedSequence folds all of its bits."""
    ss = np.random.SeedSequence(seed % 2 ** 64)
    a, b = (int(x) for x in ss.generate_state(2))
    key = jax.random.fold_in(jax.random.key(a), b)
    return key, np.random.default_rng(ss.spawn(1)[0])


def device_info(chips: int, allow_cpu: bool = False) -> Dict:
    devs = jax.devices()
    if devs[0].platform == "cpu" and not allow_cpu:
        raise SystemExit(f"perfbench: no accelerator: JAX sees "
                         f"{len(devs)} cpu device(s)")
    if len(devs) < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chip(s), JAX "
                         f"sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


@dataclasses.dataclass
class Run:
    """Everything one window left behind, for the metric readers."""
    cell: spec.Cell
    seconds: float
    setup_s: float
    t_start: float
    t_end: float
    requests: List[Dict]
    stats: Dict
    steps: List[tuple]                    # (start, end, live) per decode step
    window_compiles: int
    peaks: Optional[Dict]
    trace: Optional[Dict] = None

    @property
    def conf(self) -> Dict:
        return self.cell.config

    @property
    def lanes(self) -> int:
        return int(self.cell.cell["lanes"])

    @property
    def arch(self):
        return self.cell.arch()

    @property
    def close(self) -> float:
        """When the measured window closed: ``seconds`` after it opened."""
        return self.t_start + self.seconds

    def step_ends(self) -> List[float]:
        return sorted(e for _, e, _ in self.steps)


class Server:
    """The program under test, built for one cell: weights from the seed in
    one jitted call, the KV pool, and a warm-up of exactly the programs the
    window runs.  ``quant`` switches on the program's weight-only quantized
    path (the control of ``perfbench/control.py``; no cell runs it)."""

    def __init__(self, cell: spec.Cell, key, clock: CompileClock,
                 quant: Optional[str] = None):
        from repro.launch.serving import ContinuousEngine, Gateway

        self.cell = cell
        arch, serve = cell.arch(), cell.serve()
        conf, c = cell.config, cell.cell
        self.cfg = serve.program_config(conf)
        self.phases: Dict[str, Dict] = {}
        self._gen = jax.jit(
            lambda k: serve.program_params(arch.make_weights(conf, k)))

        snap, t = clock.snapshot(), time.perf_counter()
        params = self._gen(key)
        jax.block_until_ready(params)
        self.phases["weights"] = dict(s=time.perf_counter() - t,
                                      **clock.since(snap))

        ps = int(c["page_size"])
        self.max_pages = -(-int(c["max_ctx"]) // ps)
        lanes = int(c["lanes"])
        longest = (traffic.max_length(cell.traffic["prompt"])
                   + traffic.max_length(cell.traffic["output"]))
        if longest > self.max_pages * ps:
            raise ValueError(f"{cell.name}: max_ctx {c['max_ctx']} < the "
                             f"longest request, {longest} tokens")
        snap, t = clock.snapshot(), time.perf_counter()
        # every lane can hold a request at max_ctx: nothing is preempted,
        # so no recompute prefill of a length the warm-up never saw
        self.engine = ContinuousEngine(
            self.cfg, lanes=lanes, page_size=ps,
            n_pages=1 + lanes * self.max_pages, max_ctx=self.max_pages * ps,
            watermark=0, params=params, quant=quant)
        del params          # the engine holds what it serves
        jax.block_until_ready(self.engine.pools)
        self.phases["kv_pool"] = dict(s=time.perf_counter() - t,
                                      **clock.since(snap))
        self.gateway = Gateway(self.engine)

        snap, t = clock.snapshot(), time.perf_counter()
        self.warm_up()
        self.phases["warm_up"] = dict(s=time.perf_counter() - t,
                                      **clock.since(snap))

    def warm_up(self) -> None:
        """One request per prompt length the cell allows, two tokens each:
        every prefill and store program, and the one decode step."""
        from repro.launch.serving import ServeRequest

        rng = np.random.default_rng(0)
        lens = traffic.allowed_lengths(self.cell.traffic["prompt"])
        reqs = [ServeRequest(rid=-1 - i, max_new=2, prompt=rng.integers(
            0, self.cfg.vocab, size=n).astype(np.int32))
            for i, n in enumerate(lens)]
        for i in range(0, len(reqs), int(self.cell.cell["lanes"])):
            self.gateway.run(reqs[i:i + int(self.cell.cell["lanes"])])

    def set_weights(self, key) -> None:
        """Serve the weights of another seed with the same programs."""
        self.engine.params = None
        gc.collect()
        self.engine.params = self._gen(key)
        jax.block_until_ready(self.engine.params)

    def decode_hlo(self) -> str:
        """The compiled text of the decode step the window ran."""
        from repro.search import serving_phase

        lanes = int(self.cell.cell["lanes"])
        bt = np.zeros((lanes, self.max_pages), np.int32)
        z = np.zeros((lanes,), np.int32)
        with serving_phase("decode"):
            return self.engine.decode._step.lower(
                self.engine.params, self.engine.pools, bt, z, z
            ).compile().as_text()

    def close(self) -> None:
        self.engine.params = None
        self.engine.pools = None
        self.engine = self.gateway = None
        gc.collect()


def _obs_reset() -> None:
    from repro import obs

    obs.trace_reset()
    obs.metrics_reset()


def _decode_steps() -> List[tuple]:
    from repro import obs
    from repro.obs import trace as obs_trace

    ep = obs_trace._EPOCH
    return [(ep + e["ts"] * 1e-6, ep + (e["ts"] + e["dur"]) * 1e-6,
             int(e["args"]["live"]))
            for e in obs.trace_events() if e["name"] == "serve.decode.step"]


def _spans() -> List[tuple]:
    from repro import obs
    from repro.obs import trace as obs_trace

    ep = obs_trace._EPOCH
    return [(e["name"], ep + e["ts"] * 1e-6, ep + (e["ts"] + e["dur"]) * 1e-6)
            for e in obs.trace_events()
            if e["name"] in ("serve.decode.step", "serve.prefill")]


def _record(sr, due: float, prompt) -> Dict:
    return dict(rid=sr.rid, prompt=prompt, prompt_len=len(prompt),
                max_new=sr.max_new, due=due, t_submit=sr.t_submit,
                t_first=sr.t_first, t_done=sr.t_done,
                n_out=len(sr.out_tokens), out_tokens=list(sr.out_tokens))


def window(server: Server, seconds: float, rng: np.random.Generator,
           clock: CompileClock) -> Dict:
    """Drive the window: one open-loop trace over ``seconds``, or one
    closed queue, through one call of the program's entry.  Returns the
    raw records."""
    from repro.launch.serving import ServeRequest

    cell = server.cell
    plan = traffic.requests(cell.traffic, cell.cell, seconds,
                            server.cfg.vocab, rng)
    _obs_reset()
    snap = clock.snapshot()
    srs = [ServeRequest(rid=i, prompt=p["prompt"], max_new=p["max_new"],
                        arrival_s=p["due_s"]) for i, p in enumerate(plan)]
    t_start = time.perf_counter()
    st = server.gateway.run(srs)
    t_end = time.perf_counter()
    stats = {k: st[k] for k in ("prefill_s", "decode_s", "decode_steps",
                                "decode_tokens", "prefill_tokens",
                                "preemptions")}
    reqs = [_record(s, t_start + p["due_s"], p["prompt"])
            for s, p in zip(srs, plan)]
    return dict(t_start=t_start, t_end=t_end, requests=reqs, stats=stats,
                steps=_decode_steps(), spans=_spans(),
                compiles=clock.since(snap)["compiles"])


def make_run(cell: spec.Cell, raw: Dict, seconds: float, setup_s: float,
             pk: Optional[Dict] = None) -> "Run":
    """The readers' view of one window."""
    return Run(cell=cell, seconds=seconds, setup_s=setup_s,
               t_start=raw["t_start"], t_end=raw["t_end"],
               requests=raw["requests"], stats=raw["stats"],
               steps=raw["steps"], window_compiles=raw["compiles"], peaks=pk)


def reduce_trace(server: Server, raw: Dict, clock_ref,
                 seconds: float) -> Optional[Dict]:
    """Device busy time, the top ops, the idle gaps by what the host was
    in, and the decode step's GEMM and total device time, over the
    measured window: its first ``seconds`` (the profiler keeps a bounded
    number of events, and a closed queue's drain runs on after the
    window).  None where the trace lost decode steps of the window."""
    tr = xtrace.load(TRACE_DIR)
    pc_ref, wall_ref = clock_ref

    def rel(t_pc: float) -> float:
        return wall_ref + (t_pc - pc_ref) * 1e9 - tr.start_wall_ns

    close = min(raw["t_end"], raw["t_start"] + seconds)
    lo, hi = rel(raw["t_start"]), rel(close)
    devs = tr.devices[:server.cell.chips]
    if not devs:
        raise RuntimeError("the trace holds no TPU device plane")
    busy = [xtrace.busy_ns(d, lo, hi) for d in devs]
    hlo = server.decode_hlo()
    with open(os.path.join(ROOT, ".perfbench", "decode.hlo.txt"), "w") as f:
        f.write(hlo)            # what the GEMM attribution was read from
    module = hlo.split(None, 2)[1].rstrip(",")
    gemms = xtrace.gemm_instructions(
        hlo, server.cell.arch().gemm_shapes(server.cell.config,
                                            int(server.cell.cell["lanes"])))
    d0 = devs[0]
    spans = [(n, rel(a), rel(b)) for n, a, b in raw["spans"]]
    gaps = []
    # only the ten longest are reported: name those alone
    for s, t in sorted(xtrace.idle_gaps(d0, lo, hi),
                       key=lambda g: g[0] - g[1])[:10]:
        best, name = 0.0, "engine loop"
        for n, a, b in spans:
            ov = min(b, t) - max(a, s)
            if ov > best:
                best, name = ov, n
        gaps.append((name, (t - s) * 1e-9))
    per_gemm = xtrace.ops_ns(d0, module, gemms, lo, hi)
    runs = len(xtrace.module_runs(d0, module, lo, hi))
    steps = sum(raw["t_start"] <= a and b <= close for a, b, _ in raw["steps"])
    log(f"trace: {runs} runs of {module} in the window, {steps} decode "
        f"steps inside it; GEMM ops in the HLO {sorted(gemms)}, their time "
        + ", ".join(f"{k} {v * 1e-9:.4f} s" for k, v in sorted(per_gemm.items())))
    if runs < steps:
        log(f"trace: the profiler kept {runs} of the window's {steps} decode "
            "steps; its readings are left out")
        return None
    return dict(
        window_s=(hi - lo) * 1e-9,
        busy_s=float(np.mean(busy)) * 1e-9,
        device_ops=xtrace.op_seconds(d0, lo, hi),
        idle_gaps=gaps,
        decode_module=module,
        decode_runs=runs,
        decode_busy_s=xtrace.module_busy_ns(d0, module, lo, hi) * 1e-9,
        gemm_s=sum(per_gemm.values()) * 1e-9,
        gemm_ops=len(gemms),
    )


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, *, t_jax: Optional[float] = None,
             allow_cpu: bool = False, cell: Optional[spec.Cell] = None) -> Dict:
    """One run as the benchmark's command makes it; returns the result
    line's object.  ``t_process`` is when the process started and
    ``t_jax`` when it had imported JAX (both ``time.perf_counter()``)."""
    cell = cell or spec.cell(workload)
    per_layer = trace
    clock = CompileClock()
    t = time.perf_counter()
    phases = {"import_jax": dict(s=(t_jax or t) - t_process,
                                 **clock.snapshot())}
    device = device_info(cell.chips, allow_cpu=allow_cpu)
    phases["backend"] = dict(s=time.perf_counter() - t, **clock.snapshot())
    pk = None if device["platform"] == "cpu" else peaks(device["kind"])
    t = time.perf_counter()
    import repro.launch.serving  # noqa: F401  (the program under test)

    phases["import_program"] = dict(s=time.perf_counter() - t,
                                    **clock.snapshot())
    key, rng = seed_key(seed)
    server = Server(cell, key, clock)
    phases.update(server.phases)
    # a CPU rehearsal has no device plane to read: its trace readers
    # find nothing and leave their metrics out
    trace = trace and device["platform"] != "cpu"
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    clock_ref = (time.perf_counter(), time.time_ns())
    setup_s = time.perf_counter() - t_process
    raw = window(server, seconds, rng, clock)
    if trace:
        jax.profiler.stop_trace()
    log("set-up phases: " + "; ".join(
        f"{k} {v['s']:.3f} s ({v['compiles']} compiles, {v['cache_hits']} "
        f"cache hits, {v['cache_misses']} misses, {v['compile_s']:.3f} s "
        "compiling)" for k, v in phases.items()))
    log(f"window: {len(raw['requests'])} requests in "
        f"{raw['t_end'] - raw['t_start']:.3f} s, {raw['compiles']} "
        "compiles inside it")
    run = make_run(cell, raw, seconds, setup_s, pk)
    if trace:
        run.trace = reduce_trace(server, raw, clock_ref, seconds)
    device["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[:cell.chips])
    server.close()
    del server
    gc.collect()

    metrics = {}
    for m in (cell.per_layer if per_layer else cell.end_to_end):
        v = spec.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]

    from . import check

    t = time.perf_counter()
    numbers = check.compare(cell, run, key, seed)
    log(f"comparison with the reference took {time.perf_counter() - t:.3f} s")
    correct = check.verdict(numbers)
    out = {"correct": correct, "attempted": len(run.requests),
           "failed": sum(r["n_out"] < r["max_new"] for r in run.requests),
           "metrics": metrics, "device": device,
           "setup_phases": {k: {kk: round(vv, 6) if isinstance(vv, float)
                                else vv for kk, vv in v.items()}
                            for k, v in phases.items()},
           "window_compiles": run.window_compiles}
    if run.trace:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["check"] = numbers
    for name, v in numbers.items():
        log(f"check {name} {v['value']!r} limit {v['limit']!r}")
    return out
