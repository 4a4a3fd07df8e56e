"""Model FLOPs served in the window over its time at the bf16 peak, %."""

from perfbench.readings import mfu as read  # noqa: F401
