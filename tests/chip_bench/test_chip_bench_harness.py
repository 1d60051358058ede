"""The chip benchmark's harness on the CPU: the files it is driven by, the
refusal to run without a TPU, the plain reference, and the reduction of
a recorded profiler trace. The cells themselves run in
``test_chip_bench_cells.py``."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from chipbench import history, readers, spec, tracecut  # noqa: E402

SMALL_TRACE = os.path.join(BENCH, "testdata", "small.xplane.pb")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")


def _line(text):
    return 1 <= len(text) <= 200 and not set(text) & {"\n", "\t"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keys_names_and_units():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for p in b["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= b["run_seconds"] <= 51
    assert 1 <= len(b["command"]) <= 32
    for word in b["command"]:
        assert _line(word)
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert NAME.match(c["name"]), c["name"]
        names.append(c["name"])
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k]), w[k]
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                              "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    names += [w["name"] for w in b["workloads"]]
    assert len(names) == len(set(names))
    assert len(json.dumps(b)) <= 64 * 1024


@pytest.mark.parametrize("workload", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_loads_with_its_metrics(workload):
    cell = spec.load_cell(ROOT, workload)
    assert any(m.name == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    e2e = {m.name for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m.moves in e2e, (m.name, m.moves)


#: a throwaway deployment kind: one release of a seeded history in one
#: store, the history as its reference
ONE_RELEASE = """
import os

import numpy as np

from chipbench.history import FIELDS, History


class Deployment:
    def __init__(self, config, seed, root):
        from repro.core.increment import GeStore
        from repro.core.parsers import UniProtParser
        from repro.core.plugins import PluginRegistry
        d = config["deployment"]
        self.config = config
        self.hist = History(entries=d["entries"], releases=1, churn=0.0,
                            new_frac=0.0, delete_frac=0.0, seq_width=512,
                            annot_width=256, seed=seed)
        registry = PluginRegistry()
        parser = UniProtParser()
        registry.register_parser(parser)
        self.gs = GeStore(os.path.join(root, "gestore"), registry)
        self.store = self.gs.create_store("one", parser.schema(),
                                          capacity=self.hist.universe)
        state = self.hist.base
        ids = np.nonzero(state["alive"])[0]
        self.store.update(1, [self.hist.keys[i] for i in ids],
                          {f: state[f][ids] for f in FIELDS})

    def close(self):
        self.store = self.gs = None
"""

#: a throwaway op: one client reads the taxa of release 1
TAXA = """
from chipbench import history as H
from chipbench.traffic import ClosedLoop


class Op(ClosedLoop):
    def warm(self):
        self.dep.store.get_versions([1], fields=["taxid"])

    def request(self, g, c, grp, name, i):
        view = self.dep.store.get_versions([1], fields=["taxid"])[0]
        return view, None

    def collect(self):
        return {}

    def close(self):
        self.hist = self.dep.hist
        super().close()

    def check(self, control=False):
        state = self.hist.base
        if control:     # the reference, one taxon off
            state = {k: v.copy() for k, v in state.items()}
            state["taxid"][0] += 1
        wrong = sum(H.view_errors(v, state, self.hist.keys, ["taxid"])
                    for v, _ in self.kept)
        return wrong, len(self.kept)
"""


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """A throwaway configuration with a deployment kind of its own, a mix
    with an op of its own, and a per-layer metric, added as new files and
    entries beside unchanged copies of the existing ones; the cell then
    runs through the harness's entry on the CPU."""
    import io
    import time

    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from chipbench import run

    here = tmp_path / "chip"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    b = _bench()
    (here / "configs" / "tiny.json").write_text(json.dumps(
        {"deployment": {"kind": "one_release", "entries": 300}}))
    (here / "deployments" / "one_release.py").write_text(ONE_RELEASE)
    (here / "ops" / "taxa.py").write_text(TAXA)
    (here / "traffic" / "taxa_only.json").write_text(json.dumps(
        {"op": "taxa", "clients": [{"tool": "blast", "count": 1}]}))
    (here / "metrics" / "answers.tiny.py").write_text(
        "def read(run):\n    return len(run.done_in_window())\n")
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "chip/configs/tiny.json", "reduced": [],
                         "why": "t"})
    b["workloads"].append({"name": "tiny.taxa_only", "config": "tiny",
                           "traffic": "taxa_only", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "answers.tiny", "unit": "requests",
                           "better": "higher", "source": "host_clock",
                           "layer": "front door", "moves": "setup_s",
                           "workloads": ["tiny.taxa_only"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell(str(tmp_path), "tiny.taxa_only", here=str(here))
    assert cell.config["deployment"]["entries"] == 300
    assert cell.op.__module__ == "chipbench_op_taxa"
    assert [m.name for m in cell.per_layer] == ["answers.tiny"]
    assert [m.name for m in cell.end_to_end] == ["setup_s"]
    # no file that was there before has changed
    assert all(p.read_bytes() == data for p, data in before.items())
    res = run.run_cell(cell, seed=2**31 + 3, seconds=0.3, trace=False,
                       t_start=time.perf_counter(),
                       work_root=str(tmp_path / "work"),
                       devices=jax.devices(), control=True,
                       log=io.StringIO())
    assert res["correct"] is True and res["attempted"] >= 1, res
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["control"]["correct"] is False
    with pytest.raises(spec.CellError):
        spec.load_cell(str(tmp_path), "no.such_cell", here=str(here))
    b["workloads"][-1]["traffic"] = "no_such_mix"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    with pytest.raises(spec.CellError):
        spec.load_cell(str(tmp_path), "tiny.taxa_only", here=str(here))


def _bench_cmd(root, workload="swissprot.increments"):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("GESTORE_")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "chip", "bench.py"),
         "--workload", workload, "--seed", "2147483999",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("workload",
                         [w["name"] for w in _bench()["workloads"]])
def test_refuses_to_run_without_a_tpu(workload):
    out = _bench_cmd(ROOT, workload)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_to_run_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in _bench()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench_cmd(str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


# -- the plain reference ----------------------------------------------------------

@pytest.fixture(scope="module")
def hist():
    return history.History(entries=400, releases=4, churn=0.031,
                           new_frac=0.004, delete_frac=0.001, seq_width=512,
                           annot_width=256, seed=2**31 + 7)


def _view(state, keys, fields):
    from types import SimpleNamespace
    ids = np.nonzero(state["alive"])[0]
    return SimpleNamespace(row_idx=ids.copy(), keys=[keys[i] for i in ids],
                           values={f: state[f][ids].copy() for f in fields})


def test_reference_accepts_the_release_and_counts_each_corruption(hist):
    states = {t: {k: v.copy() for k, v in s.items()}
              for t, s in hist.states()}
    st = states[3]
    good = _view(st, hist.keys, history.FIELDS)
    assert history.view_errors(good, st, hist.keys, history.FIELDS) == 0
    bad = _view(st, hist.keys, history.FIELDS)
    bad.values["taxid"][5, 0] += 1
    bad.values["sequence"][9, 0] ^= 1
    assert history.view_errors(bad, st, hist.keys, history.FIELDS) == 2
    bad = _view(st, hist.keys, history.FIELDS)
    bad.keys[0] = b"P99999999"
    assert history.view_errors(bad, st, hist.keys, history.FIELDS) == 1
    bad = _view(st, hist.keys, history.FIELDS)
    bad.row_idx, bad.keys = bad.row_idx[1:], bad.keys[1:]
    bad.values = {f: v[1:] for f, v in bad.values.items()}
    assert history.view_errors(bad, st, hist.keys, history.FIELDS) == 1
    # the adjacent release is no stand-in for a pinned one
    assert history.view_errors(_view(states[2], hist.keys, history.FIELDS),
                               st, hist.keys, history.FIELDS) > 0


def test_reference_increment_matches_consecutive_releases(hist):
    states = {t: {k: v.copy() for k, v in s.items()}
              for t, s in hist.states()}
    a, b = states[2], states[4]
    sig = ["sequence", "length"]
    ids, kind, vals = history.expected_increment(hist, b, 2, 4, sig, sig)
    changed = np.zeros(hist.universe, bool)
    for r in (3, 4):
        for f in sig:
            changed |= (states[r][f] != states[r - 1][f]).any(axis=1)
    e0, e1 = a["alive"], b["alive"]
    want = np.nonzero((e1 & ~e0) | (e0 & ~e1) | (e1 & e0 & changed))[0]
    assert np.array_equal(ids, want)
    assert (kind == history.KIND_DELETED).sum() == (e0 & ~e1).sum()
    assert not vals["sequence"][kind == history.KIND_DELETED].any()


def test_reference_cell_count_matches_the_store(hist):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.store import VersionedStore
    st = VersionedStore("s", [], capacity=hist.universe)
    from repro.core.parsers import UniProtParser
    for fs in UniProtParser().schema():
        st.add_field(fs)
    for t, s in hist.states():
        ids = np.nonzero(s["alive"])[0]
        st.update(t, [hist.keys[i] for i in ids],
                  {f: s[f][ids] for f in history.FIELDS})
    assert st.superlog().n_cells == hist.n_cells()


# -- the trace reduction ----------------------------------------------------------

def test_runs_after_takes_only_the_consumers_in_order():
    runs = [["scan", 1.0], ["take", 2.0], ["where", 4.0], ["take", 8.0],
            ["scan", 16.0], ["where", 32.0], ["other", 64.0]]
    assert tracecut.runs_after(runs, {"scan"}, ("take", "where")) == 55.0
    assert tracecut.runs_after(runs, {"none"}, ("take",)) == 0.0
    assert tracecut.module_name("jit__take(1223366)") == "jit__take"


def test_union_and_gap_naming():
    assert tracecut._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3],
                                                                  [5, 9]]
    host = [("scan", 0, 10), ("materialize", 10, 40), ("tiny", 12, 13)]
    named = list(tracecut._name_gaps(host, [(2, 8), (20, 30), (50, 60)]))
    assert [n for n, _ in named] == ["scan", "materialize",
                                     tracecut.UNTRACED]
    assert named[1][1] == pytest.approx(10e-9)


def test_reduction_of_the_recorded_trace():
    """A trace recorded on one TPU v5e around a few store calls, without
    the Python tracer's events (as the harness records): the reduction
    agrees with a plain pass over its events."""
    from jax.profiler import ProfileData
    r = tracecut.reduce_trace(SMALL_TRACE)
    pd = ProfileData.from_file(SMALL_TRACE)
    win = [(e.start_ns, e.start_ns + e.duration_ns)
           for p in pd.planes if p.name.startswith("/host:")
           for ln in p.lines for e in ln.events
           if e.name == tracecut.WINDOW][0]
    ops = [(e.name, max(e.start_ns, win[0]),
            min(e.start_ns + e.duration_ns, win[1]))
           for p in pd.planes if p.name.startswith("/device:")
           for ln in p.lines if ln.name == tracecut.OPS_LINE
           for e in ln.events
           if e.start_ns + e.duration_ns > win[0] and e.start_ns < win[1]]
    assert ops and r["devices"] == 1
    # busy union: every elementary segment between event edges that
    # some operation covers
    edges = sorted({x for _n, s, e in ops for x in (s, e)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for _n, s, e in ops))
    assert r["window_s"] == pytest.approx((win[1] - win[0]) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < r["busy_s"] <= sum(r["op_s"].values()) + 1e-12
    by_name = {}
    for n, s, e in ops:
        by_name[n] = by_name.get(n, 0.0) + (e - s) * 1e-9
    op_s = {}
    for k, v in r["op_s"].items():
        module, op = k.split(": ", 1)
        assert module.startswith("jit_"), k   # every op ran in a module
        op_s[op] = op_s.get(op, 0.0) + v
    assert op_s == pytest.approx(by_name)
    # selection: the scan kernel's module and the boundary sampling that
    # consumes its output, three times over
    names = [n for n, _s in r["module_runs"]]
    assert names.count("jit__batched_masked_cumsum") == 3
    sel = tracecut.runs_after(r["module_runs"],
                              {"jit__batched_masked_cumsum"},
                              ("jit__take", "jit_broadcast_in_dim",
                               "jit__where"))
    scan = sum(s for n, s in r["module_runs"]
               if n == "jit__batched_masked_cumsum")
    assert scan < sel < sum(s for _n, s in r["module_runs"])
    gaps = sum(s for _n, s in r["gaps"])
    assert gaps + r["busy_s"] == pytest.approx(r["window_s"])

    class Run:
        trace = r
        device_kind = "TPU v5 lite"
    share = readers.idle_share(Run)
    assert 0 < share < 100
    assert share == pytest.approx(100 * (1 - r["busy_s"] / r["window_s"]))
    with pytest.raises(KeyError):
        readers.peaks("TPU v99")
