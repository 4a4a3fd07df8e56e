"""The engine's blocking decode seconds per decode step, ms."""

from perfbench.readings import decode_step_ms as read  # noqa: F401
