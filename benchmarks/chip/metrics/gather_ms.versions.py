"""Median gather stage wall per read wave, in ms: ``FrontDoor.stats()``
``latency.gather`` (the store's ``_StageTimer`` stage: fused per-field value gathers and their copy to the host), over the waves of the
window."""


def read(run):
    s = run.program["frontdoor"]["latency"]["gather"]
    return s["p50_ms"] if s["n"] else None
