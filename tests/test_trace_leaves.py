"""Profiler leaves (``repro.obs.trace.StageTimer``): a coarse stage is the
sum of its leaves, every leaf of the read, serving and ingest paths shows
up as a ``gestore.*`` host event in a CPU ``jax.profiler`` trace, no two
overlap on the thread that drives the device, and no other thread writes
one. Also: ``get_increments``' ``trace=``, the front door's per-leaf
latency histograms, ``repro.obs`` without JAX, and the
``storage.bytes_written`` counter."""
from __future__ import annotations

import glob
import os
import stat
import subprocess
import sys

import numpy as np
import pytest

from repro.core import ingest as ingest_mod
from repro.core.ingest import IngestConfig, ingest_release, write_synth_uniprot
from repro.core.parsers.uniprot import UniProtParser
from repro.core.placement import plan_placement
from repro.core.shard import ShardedStore
from repro.core.store import FieldSchema, VersionedStore
from repro.obs import REGISTRY, StageTimer, span
from repro.serve.frontdoor import FrontDoor

P = UniProtParser()

READ_LEAVES = {"scan.build", "scan.select", "scan.exists", "gather.take",
               "gather.copy", "diff", "materialize"}
SERVE_LEAVES = {"frontdoor.form", "frontdoor.finish", "serve.plan",
                "serve.deliver"}
INGEST_LEAVES = {"ingest.wait_parse", "ingest.journal", "ingest.route",
                 "ingest.fingerprint", "ingest.dispatch", "ingest.commit"}


def _store(n=48):
    """Three releases of a two-field store: churn, new and deleted rows."""
    st = VersionedStore("S", [FieldSchema("a", 3, "int32"),
                              FieldSchema("b", 1, "int32")], capacity=128)
    rng = np.random.default_rng(7)
    keys = [f"K{i:03d}" for i in range(n)]
    a = rng.integers(0, 50, (n, 3)).astype(np.int32)
    b = rng.integers(0, 50, (n, 1)).astype(np.int32)
    for ts in (10, 20, 30):
        st.update(ts, keys, {"a": a.copy(), "b": b.copy()})
        a[rng.choice(len(keys), 6, replace=False)] += 1
        b[rng.choice(len(keys), 4, replace=False)] += 1
        keys = keys[2:] + [f"K{ts + 100 + i:03d}" for i in range(3)]
        a = np.concatenate([a[2:], rng.integers(0, 50, (3, 3))]).astype(
            np.int32)
        b = np.concatenate([b[2:], rng.integers(0, 50, (3, 1))]).astype(
            np.int32)
    return st


def _sum_of_leaves(trace, stage):
    return sum(v for k, v in trace.items() if k.startswith(stage + "."))


# -- the leaf contract ---------------------------------------------------------

def test_leaf_fills_coarse_and_leaf_keys_and_coarse_is_their_sum():
    trace: dict[str, float] = {}
    with span("leaf_test_span") as sp:
        for leaf in ("take", "copy", "take"):
            with StageTimer(trace, "gather", leaf):
                sum(range(2000))
        with StageTimer(trace, "materialize"):
            pass
    assert set(trace) == {"gather", "gather.take", "gather.copy",
                          "materialize"}
    assert trace["gather"] == pytest.approx(_sum_of_leaves(trace, "gather"),
                                            rel=1e-12)
    assert trace["gather.take"] > 0 and trace["gather.copy"] > 0
    # the enclosing span carries both keys; the registry the leaf key
    assert sp.stages["gather"] == pytest.approx(trace["gather"])
    assert sp.stages["gather.copy"] == pytest.approx(trace["gather.copy"])
    assert REGISTRY.histogram("stage.gather.copy").snapshot()["n"] >= 1


@pytest.mark.parametrize("distinct", [2, 1], ids=["fused", "cold"])
def test_store_stage_walls_are_the_sums_of_their_leaves(distinct):
    """Both read paths: the fused superlog scan (two distinct versions)
    and the cold single-version path (a store that never built one)."""
    st = _store()
    trace: dict[str, float] = {}
    st.get_versions([30, 20][:distinct], trace=trace)
    leaves = {"scan.select", "scan.exists", "gather.take", "gather.copy",
              "materialize"} | ({"scan.build"} if distinct == 2 else set())
    assert leaves <= set(trace)
    for stage in ("scan", "gather"):
        assert trace[stage] == pytest.approx(_sum_of_leaves(trace, stage),
                                             rel=1e-12)


@pytest.mark.parametrize("kind", ["unsharded", "serial", "stacked"])
def test_get_increments_trace_fills_stages_and_keeps_answers(kind):
    st = _store()
    if kind != "unsharded":
        ref = st
        st = ShardedStore("S", [c.schema for c in ref.fields.values()],
                          n_shards=3, capacity=128)
        st.placement = plan_placement(3, force=("serial" if kind == "serial"
                                                else "parallel"))
        for v in ref.versions:
            view = ref.get_version(v.ts)
            st.update(v.ts, view.keys, view.values)
    pairs = [(10, 20), (10, 30), (20, 30)]
    plain = st.get_increments(pairs, significant_fields=["a"])
    trace: dict[str, float] = {}
    traced = st.get_increments(pairs, significant_fields=["a"], trace=trace)
    assert {"scan", "diff", "gather", "materialize"} <= set(trace)
    assert trace["gather"] == pytest.approx(_sum_of_leaves(trace, "gather"),
                                            rel=1e-12)
    for p, q in zip(plain, traced):
        assert p.keys == q.keys and np.array_equal(p.kind, q.kind)
        assert np.array_equal(p.row_idx, q.row_idx)
        for f in ("a", "b"):
            assert np.array_equal(p.values[f], q.values[f])
    assert any(len(p.keys) for p in plain)


def test_frontdoor_latency_carries_every_leaf_key():
    fd = FrontDoor({"S": _store()})
    futs = [fd.submit("t0", "S", 20), fd.submit("t1", "S", 30)]
    fd.pump()
    for f in futs:
        f.result(0)
    lat = fd.stats()["latency"]
    for key in ("gather.copy", "gather.take", "scan.select", "serve.plan",
                "serve.deliver"):
        assert lat[key]["n"] >= 1, key
    assert lat["gather"]["n"] == lat["gather.copy"]["n"]


def _python(code: str) -> str:
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    return subprocess.run([sys.executable, "-c", code],
                          env={**os.environ, "PYTHONPATH": src},
                          capture_output=True, text=True,
                          check=True).stdout.strip()


def test_importing_obs_does_not_import_jax():
    assert _python("import sys, repro.obs\n"
                   "print('jax' in sys.modules)") == "False"
    # a leaf imports jax.profiler on its first use ...
    assert _python("import sys, repro.obs as o\n"
                   "with o.StageTimer(None, 'probe'):\n"
                   "    pass\n"
                   "print('jax' in sys.modules)") == "True"
    # ... and is a plain host-clock timer where JAX cannot be imported
    assert _python("import sys\n"
                   "sys.modules['jax'] = None\n"
                   "import repro.obs as o\n"
                   "t = {}\n"
                   "with o.StageTimer(t, 'scan', 'select'):\n"
                   "    pass\n"
                   "print(sorted(t))") == "['scan', 'scan.select']"


# -- leaves in a profiler trace ------------------------------------------------

@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One CPU profiler trace around the read, serving and ingest paths,
    all driven from this thread; the ingest runs with a reader thread,
    parse workers and shard workers. Returns (gestore events by host
    line, thread-pool walls recorded meanwhile)."""
    import jax
    from jax.profiler import ProfileData

    root = tmp_path_factory.mktemp("leaves")
    st = _store()
    fd = FrontDoor({"S": _store()})
    sharded = ShardedStore("ing", P.schema(), n_shards=2, capacity=256)
    path = os.path.join(str(root), "rel.dat")
    write_synth_uniprot(path, 120, seed=3)
    walls = {h: REGISTRY.histogram(h).snapshot()["n"]
             for h in ("ingest.parse_wall", "ingest.shard_apply_wall")}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    mp = pytest.MonkeyPatch()
    mp.setattr(ingest_mod, "_cpu_count", lambda: 4)  # threaded pipeline
    jax.profiler.start_trace(str(root / "trace"), profiler_options=opts)
    try:
        st.get_versions([10, 30])
        st.get_increments([(10, 20), (20, 30)])
        fut = fd.submit("t0", "S", 20)
        fd.pump()
        fut.result(0)
        ingest_release(sharded, path, P, 1,
                       config=IngestConfig(batch_entries=32,
                                           parse_workers=2),
                       journal_dir=str(root / "journal"),
                       store_dir=str(root / "store"))
    finally:
        jax.profiler.stop_trace()
        mp.undo()
    walls = {h: REGISTRY.histogram(h).snapshot()["n"] - n
             for h, n in walls.items()}
    trace = glob.glob(str(root / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)[0]
    by_line: dict[int, list] = {}
    lines = [ln for p in ProfileData.from_file(trace).planes
             if p.name.startswith("/host:") for ln in p.lines]
    for i, ln in enumerate(lines):
        for e in ln.events:
            if e.name.startswith("gestore."):
                by_line.setdefault(i, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name))
    return by_line, walls


def test_every_leaf_is_a_host_event(profiled):
    by_line, _ = profiled
    names = {n[len("gestore."):] for evs in by_line.values()
             for _s, _e, n in evs}
    missing = (READ_LEAVES | SERVE_LEAVES | INGEST_LEAVES) - names
    assert not missing, missing


def test_leaves_never_overlap_on_the_driving_thread(profiled):
    by_line, _ = profiled
    for evs in by_line.values():
        evs = sorted(evs)
        for (s0, e0, n0), (s1, e1, n1) in zip(evs, evs[1:]):
            assert s1 >= e0, (n0, n1)


def test_no_leaf_comes_from_parse_or_shard_worker_threads(profiled):
    by_line, walls = profiled
    # the parse and shard-worker threads did run (on the host clock) ...
    assert walls["ingest.parse_wall"] > 0
    assert walls["ingest.shard_apply_wall"] > 0
    # ... and every leaf is on one thread's line: the one that drove it all
    assert len(by_line) == 1


@pytest.mark.parametrize("kind", ["unsharded", "stacked"])
def test_multi_field_read_starts_every_copy_before_collecting(tmp_path,
                                                              kind):
    """A version read of three fields of a UniProt store's shapes, on one
    store and through the sharded facade's stacked path: the
    ``gather.take`` leaves launch every field's gather and start its
    copy, then one ``gather.copy`` leaf collects them all; the leaves
    fill the gather stage and do not overlap on the thread that drives
    them."""
    import jax
    from jax.profiler import ProfileData

    rng = np.random.default_rng(5)
    schema = [FieldSchema("seq", 512, "int8"), FieldSchema("len", 1, "int32"),
              FieldSchema("ann", 256, "int8")]
    if kind == "unsharded":
        st = VersionedStore("W", schema, capacity=64)
    else:
        st = ShardedStore("W", schema, n_shards=2, capacity=64)
        st.placement = plan_placement(2, force="parallel")
    keys = [f"K{i:02d}" for i in range(32)]
    for ts in (10, 20):
        st.update(ts, keys, {
            "seq": rng.integers(-128, 128, (32, 512)).astype(np.int8),
            "len": rng.integers(0, 999, (32, 1)).astype(np.int32),
            "ann": rng.integers(-128, 128, (32, 256)).astype(np.int8)})
    st.get_versions([10, 20])  # compile outside the trace
    before = REGISTRY.counter("gather.word_copies").value
    trace: dict[str, float] = {}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        views = st.get_versions([10, 20], trace=trace)
    finally:
        jax.profiler.stop_trace()
    assert REGISTRY.counter("gather.word_copies").value - before == 3
    assert all(set(v.values) == {"seq", "len", "ann"} for v in views)
    assert trace["gather.take"] > 0 and trace["gather.copy"] > 0
    assert trace["gather"] == pytest.approx(_sum_of_leaves(trace, "gather"),
                                            rel=1e-12)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    lines = [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for e in ln.events if e.name.startswith("gestore.gather.")]
             for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for ln in p.lines]
    lines = [sorted(evs) for evs in lines if evs]
    assert len(lines) == 1
    evs = lines[0]
    # the row selection and the launches are take leaves; one copy leaf
    # after the last of them collects every field
    assert [n for *_se, n in evs][-1] == "gestore.gather.copy"
    assert {n for *_se, n in evs[:-1]} == {"gestore.gather.take"}
    for (_s0, e0, _n0), (s1, _e1, _n1) in zip(evs, evs[1:]):
        assert s1 >= e0


# -- storage.bytes_written -----------------------------------------------------

def _regular_bytes(root):
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _dirs, files in os.walk(root) for f in files)


def test_bytes_written_counts_a_save_and_a_journaled_ingest(tmp_path,
                                                            monkeypatch):
    """Every file the store and the journal write is fsynced right after
    it is written, from a fresh open: the size of each regular file at its
    fsync is what was written to it."""
    synced = []
    real_fsync = os.fsync

    def fsync(fd):
        st = os.fstat(fd)
        if stat.S_ISREG(st.st_mode):
            synced.append(st.st_size)
        return real_fsync(fd)

    monkeypatch.setattr(os, "fsync", fsync)
    counter = lambda: REGISTRY.counter("storage.bytes_written").value  # noqa: E731
    st = ShardedStore("b", P.schema(), n_shards=2, capacity=256)
    path = os.path.join(str(tmp_path), "rel.dat")
    write_synth_uniprot(path, 90, seed=1)
    ingest_release(st, path, P, 1, config=IngestConfig(batch_entries=32))

    before = counter()
    st.save(str(tmp_path / "store"))        # a first save: each file once
    saved = counter() - before
    assert saved == _regular_bytes(tmp_path / "store") == sum(synced) > 0

    synced.clear()
    before = counter()
    write_synth_uniprot(path, 90, seed=2, churn=0.2)
    ingest_release(st, path, P, 2, config=IngestConfig(batch_entries=32),
                   journal_dir=str(tmp_path / "journal"))
    journaled = counter() - before
    assert journaled == sum(synced) > 0
    # the chunks and the journal's manifest are left; earlier manifests
    # were rewritten in place, so the journal wrote more than it left
    assert journaled >= _regular_bytes(tmp_path / "journal") > 0
