"""90th percentile over every request of its first token's time minus the time it was due, ms."""

from perfbench.readings import ttft_ms


def read(run):
    return ttft_ms(run, 90)
