"""Median scan stage wall per read wave, in ms: ``FrontDoor.stats()``
``latency.scan`` (the store's ``_StageTimer`` stage: superlog build, the batched masked-cumsum scan, exists resolution), over the waves of the
window."""


def read(run):
    s = run.program["frontdoor"]["latency"]["scan"]
    return s["p50_ms"] if s["n"] else None
