"""Process start to the first timed request, seconds."""

from perfbench.readings import setup_s as read  # noqa: F401
