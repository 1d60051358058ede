"""Percent of the traced window in which no operation ran on the device
(1 - busy union / window, from the profiler trace)."""
from chipbench.readers import idle_share


def read(run):
    return idle_share(run)
