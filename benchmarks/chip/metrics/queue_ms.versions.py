"""Median wait of a version read in the front door's queue, in ms, from
submit to the wave that takes it: ``FrontDoor.stats()``
``latency.queue``."""


def read(run):
    s = run.program["frontdoor"]["latency"]["queue"]
    return s["p50_ms"] if s["n"] else None
