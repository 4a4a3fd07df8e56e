"""repro.obs — zero-dependency observability: tracing, metrics, explain.

The search/codegen/serve pipeline makes its decisions (beam cuts, plan-DB
picks, dispatch-vs-fallback, collective choice) from cost terms it used to
throw away after the fact.  This package keeps them visible, in three
layers that the rest of the repo reports through:

* ``obs.trace`` — nestable spans (``with span("search.beam"): ...``) with
  a thread-local stack and Chrome-trace/Perfetto JSON export; a bound
  profiler annotation (``bind_annotation``) puts them on a profile's
  host plane too.  Load the dump at ``chrome://tracing`` /
  https://ui.perfetto.dev, or summarize it with
  ``scripts/obs_report.py --trace out.json``.
* ``obs.metrics`` — a process-global registry of counters, gauges and
  exact-value histograms (p50/p99) wired into the pipeline's previously
  unsurfaced counters: autotune/plan-DB hits and misses, capture dispatch
  per site, beam candidates/cuts, collective picks, per-request serve
  latency, straggler-watchdog step times.  ``dump()``/``to_json()``
  serialize; ``serve --metrics-out FILE`` writes one per run.
* ``obs.explain`` — renders the per-candidate roofline terms the search
  persists into plan-DB entries (``scripts/obs_report.py --explain``).

``obs.log`` is the structured stdout logger the ad-hoc ``print()``s moved
to; it honors ``REPRO_LOG=quiet|info|debug`` and keeps the human-readable
lines byte-identical at the default level.

Everything is a strict no-op when ``REPRO_OBS=0`` (on by default): spans
cost one dict lookup and record nothing, metric handles are a shared
do-nothing singleton, and the registry stays empty.  The bench gate
``obs.overhead`` (``benchmarks/kernel_bench.py``) holds the obs-on/off
ratio of a hot kernel call at <= 1.02.

Stdlib-only by design — ``runtime.fault`` (no jax imports) and the test
harness use it too.
"""

from __future__ import annotations

import os

__all__ = [
    "enabled",
    "span",
    "gc_spans",
    "bind_annotation",
    "complete_event",
    "trace_events",
    "trace_json",
    "trace_dump",
    "trace_reset",
    "counter",
    "gauge",
    "histogram",
    "metrics_json",
    "metrics_dump",
    "metrics_reset",
    "registry",
]


def enabled() -> bool:
    """Observability master switch — ``REPRO_OBS=0`` turns it all off.

    Read from the environment on every call (it is one dict lookup) so
    tests can flip it per-case without reloading modules.
    """
    return os.environ.get("REPRO_OBS", "1") != "0"


from .metrics import (  # noqa: E402
    counter,
    gauge,
    histogram,
    metrics_dump,
    metrics_json,
    metrics_reset,
    registry,
)
from .trace import (  # noqa: E402
    bind_annotation,
    complete_event,
    gc_spans,
    span,
    trace_dump,
    trace_events,
    trace_json,
    trace_reset,
)
