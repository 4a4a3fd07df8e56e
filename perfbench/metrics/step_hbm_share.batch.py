"""Bytes the decode steps must read over their device time at peak HBM bandwidth, %."""

from perfbench.readings import step_hbm_share as read  # noqa: F401
