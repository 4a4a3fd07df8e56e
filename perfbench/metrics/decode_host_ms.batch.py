"""Mean host time on a decode step's critical path (``serve.decode.prepare`` + ``.dispatch`` + ``.emit`` on the profile's host plane) over the steps that ended in the window, ms."""

from perfbench.hostplane import decode_host_ms as read  # noqa: F401
