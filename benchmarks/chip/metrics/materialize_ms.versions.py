"""Median materialize stage wall per read wave, in ms: ``FrontDoor.stats()``
``latency.materialize`` (the store's ``_StageTimer`` stage: view assembly, host key lists), over the waves of the
window."""


def read(run):
    s = run.program["frontdoor"]["latency"]["materialize"]
    return s["p50_ms"] if s["n"] else None
