"""95th percentile of the engine's submit stamp minus the request's due time, ms."""

from perfbench.readings import submit_lag_ms


def read(run):
    return submit_lag_ms(run, 95)
