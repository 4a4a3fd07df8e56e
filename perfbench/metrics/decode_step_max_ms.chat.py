"""The longest decode step of the run (``serve.decode.step`` span), ms: the engine's stalls, which the TTFT p90 misses where they hold up under a tenth of the requests."""

from perfbench.readings import decode_step_max_ms as read  # noqa: F401
