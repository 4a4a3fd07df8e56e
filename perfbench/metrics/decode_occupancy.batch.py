"""Mean over the decode steps that ended inside the window of live requests over lanes, %."""

from perfbench.readings import decode_occupancy as read  # noqa: F401
