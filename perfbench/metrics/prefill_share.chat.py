"""The engine's blocking prefill seconds over the window's seconds, %."""

from perfbench.readings import prefill_share as read  # noqa: F401
