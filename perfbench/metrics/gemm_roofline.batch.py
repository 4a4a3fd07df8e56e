"""The decode steps' weight GEMMs: least time at the chip's peaks over the device time of the ops computing them, %."""

from perfbench.readings import gemm_roofline as read  # noqa: F401
