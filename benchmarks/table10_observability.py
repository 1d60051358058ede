"""Table X (new): observability layer — instrumentation overhead.

``table10.<primitive>`` — the cost of one observability primitive
(counter inc, histogram record, span open/close, flight-recorder append,
one ``StageTimer`` leaf entered and left with the profiler off): the
instrumentation-overhead budget. These are the numbers that keep the
"≲5% serving overhead" claim honest. Kernel time is not measured here: it
comes from the device trace of the chip benchmark (``benchmarks/chip``).

Also dumps the combined ``repro.obs.snapshot_all()`` payload (registry
metrics, flight-recorder ring) to ``BENCH_metrics.json`` at the repo root
— uploaded as a CI artifact next to BENCH_results.json.
"""
from __future__ import annotations

import json
import os

from repro import obs
from repro.obs import FlightRecorder, MetricsRegistry, StageTimer, span

from ._util import timeit

PROBE_REPS = 10_000
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS_OUT = os.path.join(_ROOT, "BENCH_metrics.json")


def _probe_rows() -> list[tuple[str, float, str]]:
    """Single-primitive overhead: us per counter inc / histogram record /
    span open+close / recorder append / ``StageTimer`` leaf enter+exit
    (no profiler trace running), on private instances where the
    primitive allows, so the probe does not pollute the flight-recorder
    ring (a leaf records into the registry's ``stage.probe.leaf``)."""
    reg = MetricsRegistry()
    c = reg.counter("probe")
    h = reg.histogram("probe_h", 4096)
    rec = FlightRecorder(cap=512)

    def counters():
        for _ in range(PROBE_REPS):
            c.inc()

    def hists():
        for _ in range(PROBE_REPS):
            h.record(1e-3)

    def spans():
        for _ in range(PROBE_REPS // 10):
            with span("probe"):
                pass

    def records():
        for _ in range(PROBE_REPS):
            rec.record("probe", i=1)

    trace: dict[str, float] = {}

    def leaves():
        for _ in range(PROBE_REPS // 10):
            with StageTimer(trace, "probe", "leaf"):
                pass

    rows = []
    for name, fn, calls in (("counter_inc", counters, PROBE_REPS),
                            ("histogram_record", hists, PROBE_REPS),
                            ("span", spans, PROBE_REPS // 10),
                            ("recorder_record", records, PROBE_REPS),
                            ("stage_leaf", leaves, PROBE_REPS // 10)):
        t, _ = timeit(fn, reps=2, warmup=1)
        rows.append((f"table10.{name}", t * 1e6 / calls, "per_call"))
    return rows


def run() -> list[tuple[str, float, str]]:
    rows = _probe_rows()
    with open(METRICS_OUT, "w") as f:
        json.dump(obs.snapshot_all(), f, indent=2, default=str)
    return rows
