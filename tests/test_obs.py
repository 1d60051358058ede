"""Observability layer: registry correctness under concurrency, histogram
bounds, span nesting/propagation under a seeded thread stress, flight
recorder ring semantics, kernel telemetry, and the front door's trace-id
minting + per-tenant rejection accounting."""
from __future__ import annotations

import json
import random
import threading

import numpy as np
import pytest

from repro.core.store import FieldSchema, VersionedStore
from repro.launch.roofline import PEAKS, kernel_roofline
from repro.obs import (FlightRecorder, Histogram, MetricsRegistry, RECORDER,
                       StageTimer, current_span, current_trace_id,
                       new_trace_id, span)
from repro.obs.kerneltel import KernelTelemetry


# -- metrics registry ---------------------------------------------------------

def test_counter_concurrent_increments_are_exact():
    reg = MetricsRegistry()
    n_threads, per_thread = 8, 5_000

    def work():
        c = reg.counter("hits")          # get-or-create races too
        for _ in range(per_thread):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("hits").value == n_threads * per_thread


def test_gauge_set_and_add():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(3)
    g.add(2.5)
    assert g.value == 5.5


def test_histogram_ring_is_bounded_but_n_counts_everything():
    h = Histogram(cap=16)
    for i in range(100):
        h.record(i / 1000)
    s = h.snapshot()
    assert s["n"] == 100
    # only the last 16 samples (84..99 ms) are in the ring
    assert 83.0 <= s["p50_ms"] <= 100.0
    assert s["p99_ms"] <= 99.5


def test_histogram_empty_snapshot():
    assert Histogram(cap=4).snapshot() == {"n": 0, "p50_ms": 0.0,
                                           "p99_ms": 0.0}


def test_registry_kind_collision_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_registry_snapshot_json_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("reqs").inc(3)
    reg.gauge("pressure").set(0.5)
    reg.histogram("lat").record(0.002)
    snap = reg.snapshot()
    assert snap["reqs"] == 3 and snap["pressure"] == 0.5
    assert snap["lat"]["n"] == 1
    payload = json.loads(reg.to_json(run="r1"))
    assert payload["metrics"]["reqs"] == 3 and payload["run"] == "r1"
    text = reg.to_prometheus()
    assert "# TYPE reqs counter" in text
    assert "lat_count 1" in text and "lat_p50_ms" in text


# -- trace spans --------------------------------------------------------------

def test_trace_ids_are_unique_and_prefixed():
    a, b = new_trace_id(), new_trace_id("wave")
    assert a != b and a.startswith("req-") and b.startswith("wave-")


def test_span_nesting_inherits_trace_and_links_parent():
    assert current_span() is None
    with span("outer", trace_id="req-xyz") as outer:
        assert current_trace_id() == "req-xyz"
        with span("inner") as inner:
            assert inner.trace_id == "req-xyz"       # inherited
            assert inner.parent_id == "req-xyz"
            assert current_span() is inner
        assert current_span() is outer
    assert current_span() is None


def test_span_exit_records_event_and_histogram():
    rec_before = len(RECORDER.events("span"))
    with span("unit_test_span", tenant="t0"):
        with StageTimer(None, "unit_test_stage"):
            pass
    evs = RECORDER.events("span")
    assert len(evs) == rec_before + 1
    e = evs[-1]
    assert e["name"] == "unit_test_span" and e["tenant"] == "t0"
    assert "unit_test_stage" in e["stages"]
    from repro.obs import REGISTRY
    assert REGISTRY.histogram("span.unit_test_span").snapshot()["n"] >= 1


def test_stage_timer_keeps_additive_trace_contract():
    trace: dict[str, float] = {}
    for _ in range(3):
        with StageTimer(trace, "scan"):
            pass
    assert set(trace) == {"scan"} and trace["scan"] > 0


def test_span_stress_seeded_threads_never_cross_traces():
    """N threads each open nested spans around random sleeps; thread-local
    stacks mean no thread ever observes another's trace id."""
    n_threads, per_thread = 8, 40
    errors: list[str] = []

    def work(tid: int):
        rng = random.Random(tid)            # seeded: deterministic schedule
        for i in range(per_thread):
            my = f"t{tid}-{i}"
            with span("stress", trace_id=my):
                if current_trace_id() != my:
                    errors.append(f"outer leak in {my}")
                with span("stress_inner"):
                    if current_trace_id() != my:
                        errors.append(f"inner leak in {my}")
                    if rng.random() < 0.3:
                        threading.Event().wait(0.0005)
            if current_span() is not None:
                errors.append(f"stack not empty after {my}")

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:5]


# -- flight recorder ----------------------------------------------------------

def test_recorder_ring_bounds_and_drop_accounting():
    rec = FlightRecorder(cap=8)
    for i in range(20):
        rec.record("tick", i=i)
    d = rec.dump()
    assert d["cap"] == 8 and d["recorded"] == 20 and d["dropped"] == 12
    assert [e["i"] for e in d["events"]] == list(range(12, 20))
    assert all(e["kind"] == "tick" for e in d["events"])


def test_recorder_attaches_active_trace():
    rec = FlightRecorder(cap=4)
    with span("ctx", trace_id="req-trace-test"):
        rec.record("inside")
    rec.record("outside")
    inside, outside = rec.events()
    assert inside["trace"] == "req-trace-test"
    assert "trace" not in outside


def test_recorder_dump_json_roundtrip(tmp_path):
    rec = FlightRecorder(cap=4)
    rec.record("boom", error="CorruptSegmentError('x')")
    path = rec.dump_json(str(tmp_path / "flight.json"))
    with open(path) as f:
        d = json.load(f)
    assert d["events"][0]["kind"] == "boom"


# -- kernel telemetry ---------------------------------------------------------

def test_kernel_telemetry_aggregates_and_derives_roofline():
    tel = KernelTelemetry()
    with tel.launch("k", nbytes=1e6, flops=2e6):
        pass
    with tel.launch("k", nbytes=1e6, flops=2e6):
        pass
    snap = tel.snapshot()["k"]
    assert snap["calls"] == 2
    assert snap["bytes"] == 2e6 and snap["flops"] == 4e6
    # the CPU has no entry in the peak table: no roofline is reported
    assert snap["device_kind"] not in PEAKS
    assert snap["roofline_fraction"] is None and snap["dominant"] is None
    # a listed chip divides by its own peaks: 1 MB at 819 GB/s is the
    # memory-bound minimum, so a 2x slower wall is half the roofline
    kind = "TPU v5 lite"
    t_min = 1e6 / PEAKS[kind].hbm_bw
    r = kernel_roofline(2e6, 1e6, 2 * t_min, device_kind=kind)
    assert r["dominant"] == "memory"
    assert r["roofline_fraction"] == pytest.approx(0.5)


def test_kernel_telemetry_skips_failed_launches():
    tel = KernelTelemetry()
    with pytest.raises(ValueError):
        with tel.launch("k", nbytes=1, flops=1):
            raise ValueError("kernel blew up")
    assert tel.snapshot() == {}


def test_batched_select_launches_are_recorded():
    from repro.obs.kerneltel import KERNELS
    st = VersionedStore("T", [FieldSchema("a", 4, "int32")], capacity=64)
    keys = [f"K{i}" for i in range(32)]
    st.update(10, keys, {"a": np.arange(128, dtype=np.int32).reshape(32, 4)})
    before = KERNELS.snapshot().get("batched_select", {}).get("calls", 0)
    st.get_versions([10, 20, 30], fields=["a"])   # distinct ts: fused scan
    after = KERNELS.snapshot()["batched_select"]["calls"]
    assert after > before


# -- front door integration ---------------------------------------------------

def _mini_door(**cfg_kwargs):
    from repro.serve.frontdoor import FrontDoor, FrontDoorConfig
    st = VersionedStore("S", [FieldSchema("a", 2, "int32")], capacity=64)
    st.update(10, ["K0", "K1"],
              {"a": np.arange(4, dtype=np.int32).reshape(2, 2)})
    return FrontDoor({"S": st}, config=FrontDoorConfig(**cfg_kwargs))


def test_frontdoor_mints_trace_ids_into_dispatch_log():
    fd = _mini_door()
    fut = fd.submit("t0", "S", 10)
    fd.pump()
    fut.result(0)
    assert len(fd.dispatch_log) == 1
    assert fd.dispatch_log[0]["trace"].startswith("req-")


def test_frontdoor_per_tenant_rejection_counters():
    from repro.serve.frontdoor import QueueFull
    fd = _mini_door(max_queue_per_tenant=1)
    fd.submit("t0", "S", 10)
    with pytest.raises(QueueFull):
        fd.submit("t0", "S", 10)
    s = fd.stats()
    assert s["counters"]["rejected_queue_full"] == 1
    assert s["per_tenant"]["t0"]["rejected_queue_full"] == 1
    assert s["per_tenant"]["t0"]["rejected_pressure"] == 0
    rejects = [e for e in RECORDER.events("admission_reject")
               if e.get("tenant") == "t0" and e["reason"] == "queue_full"]
    assert rejects
    fd.pump()


def test_two_frontdoors_do_not_alias_histograms():
    fd1, fd2 = _mini_door(), _mini_door()
    f = fd1.submit("t0", "S", 10)
    fd1.pump()
    f.result(0)
    assert fd1.stats()["latency"]["total"]["n"] == 1
    assert fd2.stats()["latency"]["total"]["n"] == 0
