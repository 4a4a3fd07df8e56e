"""Mean over the window's requests of each one's wait for service: the start of its own ``serve.prefill`` on the profile's host plane minus its submit stamp, ms."""

from perfbench.hostplane import queue_wait_ms as read  # noqa: F401
