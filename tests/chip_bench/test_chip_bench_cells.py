"""Every cell of the chip benchmark, run on the CPU at a tiny size through
the harness's own entry (``run_cell``, past the look for a chip): a sound
run is correct, the control (the reference in the program's place, one
guarantee broken) is not, and a run whose timed path is broken
underneath comes out not correct."""
import io
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import run, spec  # noqa: E402

SEED = 2**31 + 101      # larger than 32 signed bits hold
WORKLOADS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def tiny(workload: str) -> spec.Cell:
    cell = spec.load_cell(ROOT, workload)
    d = cell.config["deployment"]
    if d["kind"] == "release_history":
        d["entries"] = 600
    else:
        d.update(entries_per_release=300)
        d["ingest"]["batch_entries"] = 128
    return cell


def run_tiny(workload, tmp_path, *, trace=False, seconds=0.4):
    return run.run_cell(tiny(workload), seed=SEED, seconds=seconds,
                        trace=trace, t_start=time.perf_counter(),
                        work_root=str(tmp_path), devices=jax.devices(),
                        control=True, log=io.StringIO())


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_is_correct_and_its_control_is_not(workload, tmp_path):
    res = run_tiny(workload, tmp_path)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    cell = tiny(workload)
    assert set(res["metrics"]) == {m.name for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks" or list(res)[-2:] == ["checks",
                                                           "control"]
    assert res["checks"]["wrong_rows"] == {"value": 0, "max": 0}
    # the control answers what the configuration's guarantees forbid, and
    # the harness's own limits rule it not correct
    ctl = res["control"]
    assert ctl["correct"] is False, ctl
    assert ctl["checks"]["compared"]["value"] == \
        res["checks"]["compared"]["value"]
    assert ctl["checks"]["wrong_rows"]["value"] > 0
    assert not os.listdir(tmp_path)   # the run's data is removed


def test_traced_run_reads_the_program_layers(tmp_path):
    res = run_tiny("swissprot.versions", tmp_path, trace=True)
    assert res["correct"] is True
    assert res["device"]["window_s"] > 0
    m = res["metrics"]
    for name in ("queue_ms.versions", "riders_per_wave.versions",
                 "scan_ms.versions", "gather_ms.versions",
                 "materialize_ms.versions"):
        assert m[name]["value"] > 0, name
    assert m["riders_per_wave.versions"]["value"] >= 1
    # no device ran an operation here: no roofline, no idle share
    assert "select_roofline.versions" not in m
    assert "idle_share.versions" not in m


def _alter_view(orig):
    def get_versions(self, ts_list, **kw):
        views = orig(self, ts_list, **kw)
        for v in views:
            f = next(iter(v.values))
            v.values[f] = v.values[f].copy()
            v.values[f][0, 0] += 1
        return views
    return get_versions


def _halve(orig):
    """Half of each answer's rows left out."""
    def call(self, arg, **kw):
        out = orig(self, arg, **kw)
        for a in out:
            n = len(a.row_idx) // 2
            a.row_idx, a.keys = a.row_idx[:n], a.keys[:n]
            a.values = {f: v[:n] for f, v in a.values.items()}
            if hasattr(a, "kind"):
                a.kind = a.kind[:n]
        return out
    return call


def _alter_increment(orig):
    def get_increments(self, pairs, **kw):
        incs = orig(self, pairs, **kw)
        for inc in incs:
            inc.kind = inc.kind.copy()
            inc.kind[0] = (inc.kind[0] + 1) % 3
        return incs
    return get_increments


def _alter_ingest(orig):
    def apply(self, keys, table, **kw):
        if b"P00000000" in keys:
            table = dict(table)
            table["taxid"] = np.array(table["taxid"], copy=True)
            table["taxid"][keys.index(b"P00000000")] += 1
        return orig(self, keys, table, **kw)
    return apply


def _drop_wave(orig):
    def apply(self, keys, table, **kw):
        if b"P00000000" in keys:
            return len(keys)        # the wave returns, nothing applied
        return orig(self, keys, table, **kw)
    return apply


@pytest.mark.parametrize("workload,target,fault", [
    ("swissprot.versions", "VersionedStore.get_versions", _alter_view),
    ("swissprot.increments", "VersionedStore.get_increments",
     _alter_increment),
    ("swissprot_stream4.ingest", "ReleaseSession.apply", _alter_ingest),
    ("swissprot_stream4.ingest", "ReleaseSession.apply", _drop_wave),
    ("swissprot.versions", "VersionedStore.get_versions", _halve),
    ("swissprot.increments", "VersionedStore.get_increments", _halve),
], ids=["answer_altered", "increment_altered", "ingest_value_altered",
        "ingest_wave_dropped", "version_half_rows", "increment_half_rows"])
def test_broken_timed_path_is_not_correct(workload, target, fault, tmp_path,
                                          monkeypatch):
    from repro.core import store
    cls_name, meth = target.split(".")
    cls = getattr(store, cls_name)
    monkeypatch.setattr(cls, meth, fault(getattr(cls, meth)))
    res = run_tiny(workload, tmp_path)
    assert res["correct"] is False
    assert res["checks"]["wrong_rows"]["value"] > 0
