"""Median wall of one ingest wave in the window, in ms: the program's
``ingest.wave_wall`` histogram (route, fingerprint and apply one batch)."""


def read(run):
    s = run.program["wave_wall"]
    return s["p50_ms"] if s["n"] else None
