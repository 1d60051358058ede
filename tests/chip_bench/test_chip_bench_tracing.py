"""The per-layer metrics that read the program's profiler leaves and its
``storage.bytes_written`` counter, each run on a synthetic run: a share
where the trace holds gaps named by leaves, None without a trace or
from a program that writes no leaf; and the trace reduction's naming of
a gap inside a leaf."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
METRICS = os.path.join(BENCH, "metrics")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import spec, tracecut  # noqa: E402

LEAF_SHARES = {"idle_copy.versions": "gestore.gather.copy",
               "idle_select.increments": "gestore.scan.select",
               "idle_wait_parse.ingest": "gestore.ingest.wait_parse"}
UNTRACED = ["idle_untraced.versions", "idle_untraced.increments",
            "idle_untraced.ingest"]


def _run(gaps=None, *, window_s=20.0, program=None):
    trace = None if gaps is None else {"window_s": window_s, "busy_s": 1.0,
                                       "gaps": gaps}
    return SimpleNamespace(trace=trace, program=program or {})


def test_every_new_metric_is_in_the_benchmark_with_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in [*LEAF_SHARES, *UNTRACED, "copy_ms.versions",
                 "written_bytes_per_entry.ingest"]:
        cell = name.split(".", 1)[1]
        assert [w.split(".", 1)[1] for w in per_layer[name]["workloads"]] \
            == [cell], name
        assert callable(spec.load_reader(METRICS, name).read)


@pytest.mark.parametrize("name,leaf", sorted(LEAF_SHARES.items()))
def test_leaf_share_reads_the_gaps_named_by_its_leaf(name, leaf):
    read = spec.load_reader(METRICS, name).read
    gaps = [[leaf, 4.0], ["gestore.materialize", 1.0],
            ["np.asarray(jax.Array)", 0.5], [tracecut.UNTRACED, 0.25]]
    assert read(_run(gaps)) == pytest.approx(20.0)
    assert read(_run([["gestore.other", 1.0]])) == 0.0
    assert read(_run()) is None
    # a program without the leaves (the parent of this change): no reading
    assert read(_run([["np.asarray(jax.Array)", 3.0]])) is None
    # no device ran an operation (a CPU run): no reading
    run = _run(gaps)
    run.trace["busy_s"] = None
    assert read(run) is None


@pytest.mark.parametrize("name", UNTRACED)
def test_untraced_share_reads_gaps_no_leaf_names(name):
    read = spec.load_reader(METRICS, name).read
    gaps = [["gestore.gather.copy", 4.0], ["np.asarray(jax.Array)", 0.5],
            ["shard_args", 1.0], [tracecut.UNTRACED, 0.5]]
    assert read(_run(gaps)) == pytest.approx(10.0)
    assert read(_run()) is None
    assert read(_run([["shard_args", 2.0]])) is None


def test_copy_ms_reads_the_front_doors_leaf_histogram():
    read = spec.load_reader(METRICS, "copy_ms.versions").read
    lat = {"gather": {"n": 4, "p50_ms": 9.0, "p99_ms": 12.0},
           "gather.copy": {"n": 4, "p50_ms": 7.5, "p99_ms": 10.0}}
    assert read(_run(program={"frontdoor": {"latency": lat}})) == 7.5
    del lat["gather.copy"]
    assert read(_run(program={"frontdoor": {"latency": lat}})) is None
    lat["gather.copy"] = {"n": 0, "p50_ms": 0.0, "p99_ms": 0.0}
    assert read(_run(program={"frontdoor": {"latency": lat}})) is None


def test_written_bytes_per_entry_reads_the_storage_counter():
    from repro.obs import REGISTRY
    read = spec.load_reader(METRICS, "written_bytes_per_entry.ingest").read
    REGISTRY.clear()
    assert read(_run(program={"entries_routed": 1000.0})) is None
    REGISTRY.counter("storage.bytes_written").inc(880_000)
    assert read(_run(program={"entries_routed": 1000.0})) == 880.0
    assert read(_run(program={"entries_routed": 0.0})) is None


def test_a_gap_inside_a_leaf_is_named_after_the_leaf():
    """JAX's own events nest inside a leaf; a gap they cover as much as
    the leaf does goes to the leaf, which started first."""
    host = [("gestore.gather.copy", 0, 100),
            ("np.asarray(jax.Array)", 10, 90),
            ("gestore.scan.select", 100, 160),
            ("shard_args", 120, 160),
            ("PjitFunction(fingerprint)", 170, 180)]
    gaps = [(20, 80), (110, 150), (165, 190)]
    named = [n for n, _s in tracecut._name_gaps(host, gaps)]
    assert named == ["gestore.gather.copy", "gestore.scan.select",
                     "PjitFunction(fingerprint)"]
