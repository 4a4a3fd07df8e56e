"""Continuous-batching paged-KV serving tier.

Layers, bottom to top:

* :mod:`.paged` — physical KV pages: host free-list bookkeeping plus the
  gather/scatter that presents pages to the unchanged model API as a
  dense cache view.
* :mod:`.scheduler` — FCFS admission under a page-budget watermark,
  immediate reclaim on finish, preempt-newest recompute when the pool
  runs dry.
* :mod:`.runners` — the prefill (compute-bound) and decode
  (bandwidth-bound, skinny-M) phases, each consulting and sweeping its
  own phase-tagged plan-DB ladder via ``search.serving_phase``.
* :mod:`.engine` — :class:`ContinuousEngine` (slot-free continuous
  batching) and :class:`FixedEngine` (the legacy fixed-slot server,
  kept as the differential/throughput baseline).
* :mod:`.gateway` / :mod:`.trace` — drive a seeded multi-tenant Poisson
  trace through either engine with per-request observability.

Importing the tier binds ``jax.profiler.TraceAnnotation`` to ``repro.obs``:
while a profile is being taken, every obs span also lands on the
profile's host plane, on the device planes' clock.

``launch.serve --engine continuous`` is the CLI entry point;
``benchmarks/serve_bench.py`` gates continuous >= fixed throughput.
"""

import jax

from ... import obs
from .engine import ContinuousEngine, FixedEngine
from .gateway import Gateway
from .paged import PagePool, pool_init
from .scheduler import Scheduler, ServeRequest
from .trace import synthetic_trace

obs.bind_annotation(jax.profiler.TraceAnnotation)

__all__ = [
    "ContinuousEngine",
    "FixedEngine",
    "Gateway",
    "PagePool",
    "pool_init",
    "Scheduler",
    "ServeRequest",
    "synthetic_trace",
]
