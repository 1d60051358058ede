"""Median wall of the gather stage's copy to the host per read wave, in
ms: ``FrontDoor.stats()`` ``latency["gather.copy"]`` (the store's
``gather.copy`` leaf: waiting for the take, the device-to-host copy and
the zeroing), over the waves of the window."""


def read(run):
    s = run.program["frontdoor"]["latency"].get("gather.copy")
    return s["p50_ms"] if s and s["n"] else None
