"""Table X (new): observability layer — kernel rooflines + overhead.

Two row families:

  * ``table10.roofline_<kernel>`` — drive each instrumented kernel launch
    path (``batched_select`` via a batched ``get_versions`` wave,
    ``shard_route`` via ``route_keys``, ``delta_codec`` via
    ``chain_pack``/``chain_unpack``) and report the per-kernel telemetry
    ``KernelTelemetry`` aggregated: wall us/launch plus the derived
    roofline fraction and achieved GB/s against the running chip's peaks
    in ``launch/roofline.PEAKS`` (``none`` on a device with no entry,
    the CPU included). A collapsing fraction (or exploding
    us/launch) gates CI via tools/bench_compare.py.
  * ``table10.<primitive>`` — the cost of one observability primitive
    (counter inc, histogram record, span open/close, flight-recorder
    append): the instrumentation-overhead budget. These are the numbers
    that keep the "≲5% serving overhead" claim honest.

Also dumps the combined ``repro.obs.snapshot_all()`` payload (registry
metrics, kernel telemetry, flight-recorder ring) to ``BENCH_metrics.json``
at the repo root — uploaded as a CI artifact next to BENCH_results.json.
"""
from __future__ import annotations

import json
import os

import numpy as np

from repro import obs
from repro.core.store import FieldSchema, VersionedStore
from repro.kernels.delta_codec import chain_pack, chain_unpack
from repro.kernels.shard_route import route_keys
from repro.obs import FlightRecorder, MetricsRegistry, span
from repro.obs.kerneltel import KERNELS

from ._util import frac, synth_release, timeit

N = int(os.environ.get("BENCH_OBS_N",
                       os.environ.get("BENCH_BATCH_N", 8_000)))
PROBE_REPS = 10_000
# roofline rows sample SEVERAL warm launches (calls > 1), so a single
# stray compile can't dominate the per-call wall numbers
ROOFLINE_REPS = int(os.environ.get("BENCH_OBS_REPS", 5))
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS_OUT = os.path.join(_ROOT, "BENCH_metrics.json")


def _probe_rows() -> list[tuple[str, float, str]]:
    """Single-primitive overhead: us per counter inc / histogram record /
    span open+close / recorder append, on private instances so the probe
    does not pollute the process-wide registry or flight-recorder ring."""
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

    rows = []
    for name, fn, calls in (("counter_inc", counters, PROBE_REPS),
                            ("histogram_record", hists, PROBE_REPS),
                            ("span", spans, PROBE_REPS // 10),
                            ("recorder_record", records, PROBE_REPS)):
        t, _ = timeit(fn, reps=2, warmup=1)
        rows.append((f"table10.{name}", t * 1e6 / calls, "per_call"))
    return rows


def _build_state():
    """Build the bench state ONCE, outside the sampled region — store
    construction (ingest) must not ride along in the roofline rows."""
    st = VersionedStore("obs", [FieldSchema("sequence", 16, "int32"),
                                FieldSchema("length", 1, "int32")],
                        capacity=N + N // 4)
    rel = synth_release(N, seq_w=16, seed=7)
    st.update(10, *rel)
    for v in range(1, 4):
        rel = synth_release(0, base=rel, frac_updated=0.05, n_new=N // 100,
                            seed=v + 7)
        st.update((v + 1) * 10, *rel)
    ts_list = [((i % 4) + 1) * 10 for i in range(32)]
    keys = [f"P{i:08d}".encode() for i in range(N)]
    rng = np.random.default_rng(11)
    rows = np.sort(rng.integers(0, max(N // 4, 1), size=N)).astype(np.int64)
    vals = rng.integers(0, 100, size=(N, 16)).astype(np.int32)
    return st, ts_list, keys, rows, vals


def _drive_kernels(state, reps: int = 1) -> None:
    """Exercise every instrumented launch site at bench scale."""
    st, ts_list, keys, rows, vals = state
    for _ in range(reps):
        # batched_select: a 32-version fused batch over the 4-release store
        st.get_versions(ts_list, fields=["sequence"])
        # shard_route: hash the whole keyspace across 8 shards
        route_keys(keys, 8)
        # delta_codec: pack + unpack one (row, ts)-sorted chain run
        packed, meta = chain_pack(vals, rows)
        chain_unpack(packed, rows, meta, np.dtype(np.int32))


def run() -> list[tuple[str, float, str]]:
    rows = _probe_rows()
    state = _build_state()
    _drive_kernels(state)    # warmup: compile/trace cost stays out of the
    KERNELS.clear()          # telemetry attributed to the timed drive
    _drive_kernels(state, reps=ROOFLINE_REPS)   # warm steady-state sample
    snap = KERNELS.snapshot()
    for kernel in ("batched_select", "shard_route", "delta_codec"):
        k = snap.get(kernel)
        if k is None:        # a kernel path went dark — that IS the signal
            rows.append((f"table10.roofline_{kernel}", float("nan"),
                         "missing=1"))
            continue
        rows.append((
            f"table10.roofline_{kernel}", k["us_per_call"],
            f"roofline_frac={frac(k['roofline_fraction'])};"
            f"gbytes_per_s={k['gbytes_per_s']:.2f};"
            f"dominant={k['dominant']};calls={k['calls']}"))
    with open(METRICS_OUT, "w") as f:
        json.dump(obs.snapshot_all(), f, indent=2, default=str)
    return rows
