"""95th percentile over every gap between consecutive tokens of every request whose later token came inside the window, ms."""

from perfbench.readings import itl_ms


def read(run):
    return itl_ms(run, 95)
