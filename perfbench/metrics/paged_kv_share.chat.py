"""Device time of the decode step's ``paged_view`` and ``scatter_token`` ops over the decode step's device time in the window, %."""

from perfbench.hostplane import paged_kv_share as read  # noqa: F401
