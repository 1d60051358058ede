#!/usr/bin/env python3
"""Smoke run of the GeStore main path on a TPU.

One process drives the store lifecycle once, through the entry points a
user calls, on a store the size of UniProtKB/Swiss-Prot (about 570,000
entries, ``UniProtParser`` schema: ``sequence`` 512 x int8, ``length``
int32, ``annotation`` 256 x int8, ``taxid`` int32):

  * build     -- ``GeStore.create_store`` + three full releases through
                 ``update``, 3.1% of the entries churning per release
                 (the paper's monthly churn), plus a few new and deleted
                 entries per release;
  * stream    -- one more release of synthetic UniProt text streamed
                 through ``GeStore.add_release_stream(shards=4)``: parser,
                 ``shard_route``, ``fingerprint``, the ingest journal;
  * reads     -- pinned-version reads from three tenants through a
                 ``FrontDoor`` (``pump()``); every future is ``result()``-ed,
                 so a failed wave fails the run;
  * increments-- ``get_increments`` over consecutive and two-release
                 windows, all fields and BLAST-significant fields;
  * flush     -- ``GeStore.flush()`` (segment save through ``chain_pack``),
                 then a fresh ``GeStore`` reopens the stores from disk and
                 answers a cold read and a cold increment;
  * compact   -- ``compact()`` at release 2, in memory and on disk.

Every answer is checked exactly against a plain numpy oracle built from
the same seeded arrays. The data is made from ``--seed``; making it is
set-up and is timed as its own phase.

With ``--chips 4`` the script runs only the four-chip path: a 4-shard
store whose batched reads run over a ``("shard",)`` mesh, one shard per
chip, compared byte for byte with the same store on the serial
placement, and with the oracle.

Usage (from the repository root, on a TPU host)::

    python chip_smoke.py                 # one chip: the lifecycle
    python chip_smoke.py --chips 4       # four chips: mesh vs serial

The script refuses to run unless JAX's first device is a TPU, and unless
it sits at the root of a checkout of this repository. It starts no child
process. Compiled programs persist in ``JAX_COMPILATION_CACHE_DIR`` when
that is set, else in ``.jax_cache/`` beside this file. Every line but the
last reports a phase (wall time with compile time apart, compile count),
the device memory, or the resolved kernel tiles; the last line is one
JSON object naming the device, printed only when every check passed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: UniProtKB/Swiss-Prot holds about 570,000 reviewed entries (UniProt
#: release notes); the paper's releases churn 3.1% of entries per month
SWISSPROT_ENTRIES = 570_000
CHURN = 0.031
#: per release: new entries and deletions, as fractions of live entries
NEW_FRAC = 0.004
DELETE_FRAC = 0.001
N_RELEASES = 3
SEQ_W, ANNOT_W = 512, 256
AA = np.frombuffer(b"ACDEFGHIKLMNPQRSTVWY", np.uint8)
FIELDS = ("sequence", "length", "annotation", "taxid")
BLAST_FIELDS = ["sequence", "length"]
MAIN, STREAM, SHARDED = "sprot", "sprot_stream", "sprot4"


class SmokeFailure(RuntimeError):
    """An answer differed from the oracle, or a phase did not run."""


def expect(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- compile accounting -------------------------------------------------------

class _Compiles:
    """Counts XLA compilations and sums their backend compile time through
    ``jax.monitoring``'s duration events. Tracing is left in the run time:
    a jit traced inside another's trace reports a nested duration, so
    summing trace events would count it twice."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.secs = 0.0

    def install(self) -> None:
        import jax

        def listener(event, duration, **_kw):
            if event == self.BACKEND:
                self.n += 1
                self.secs += duration

        jax.monitoring.register_event_duration_secs_listener(listener)


COMPILES = _Compiles()


@contextlib.contextmanager
def phase(name: str, **extra):
    """Time one phase; prints its wall, the compile time inside it, and
    the number of compilations it triggered."""
    n0, s0 = COMPILES.n, COMPILES.secs
    t0 = time.perf_counter()
    yield extra
    wall = time.perf_counter() - t0
    comp = COMPILES.secs - s0
    fields = " ".join(f"{k}={v}" for k, v in extra.items())
    print(f"phase={name} wall_s={wall!r} compile_s={comp!r} "
          f"run_s={wall - comp!r} compiles={COMPILES.n - n0}"
          + (f" {fields}" if fields else ""), flush=True)


# -- seeded data + numpy oracle -----------------------------------------------

def keys_of(ids) -> list[bytes]:
    return [b"P%08d" % i for i in ids]


def make_history(n: int, seed: int) -> list[dict]:
    """Three full releases over one entry universe: per release, a dict of
    universe-indexed field arrays plus the ``alive`` mask. Entry ``i`` has
    key ``P%08d`` and, because releases list live entries in id order and
    new ids only ever grow, store row ``i``."""
    rng = np.random.default_rng(seed)
    n_new = max(1, round(n * NEW_FRAC))
    u = n + (N_RELEASES - 1) * n_new
    length = np.clip(rng.lognormal(np.log(300), 0.6, u), 30, SEQ_W
                     ).astype(np.int32)
    seq = AA[rng.integers(0, len(AA), (u, SEQ_W), dtype=np.uint8)]
    seq[np.arange(SEQ_W)[None, :] >= length[:, None]] = 0
    annot_len = rng.integers(60, ANNOT_W + 1, u)
    annot = rng.integers(32, 127, (u, ANNOT_W), dtype=np.uint8)
    annot[np.arange(ANNOT_W)[None, :] >= annot_len[:, None]] = 0
    alive = np.zeros(u, bool)
    alive[:n] = True
    cur = {"sequence": seq.view(np.int8), "length": length[:, None],
           "annotation": annot.view(np.int8),
           "taxid": rng.integers(1, 2_000_000, (u, 1)).astype(np.int32),
           "alive": alive}
    hist = [cur]
    inv = np.zeros(256, np.int64)
    inv[AA] = np.arange(len(AA))
    for r in range(1, N_RELEASES):
        cur = {k: v.copy() for k, v in hist[-1].items()}
        live = np.nonzero(cur["alive"])[0]
        n_churn = round(len(live) * CHURN)
        n_del = max(1, round(len(live) * DELETE_FRAC))
        pick = rng.choice(live, n_churn + n_del, replace=False)
        churned, gone = pick[:n_churn], pick[n_churn:]
        # a third of the churn substitutes a residue (a quarter of those
        # also grow by one); the rest is annotation-only, the release
        # churn BLAST must not see
        seq_rows = churned[: n_churn // 3]
        ln = cur["length"][seq_rows, 0]
        p = (rng.random(len(seq_rows)) * ln).astype(np.int64)
        s8 = cur["sequence"].view(np.uint8)
        s8[seq_rows, p] = AA[(inv[s8[seq_rows, p]] + 1
                              + rng.integers(0, len(AA) - 1, len(seq_rows)))
                             % len(AA)]
        grow = seq_rows[(rng.random(len(seq_rows)) < 0.25) & (ln < SEQ_W)]
        s8[grow, cur["length"][grow, 0]] = AA[rng.integers(0, len(AA),
                                                           len(grow))]
        cur["length"][grow, 0] += 1
        ann_rows = churned[n_churn // 3:]
        a8 = cur["annotation"].view(np.uint8)
        q = rng.integers(0, 16, len(ann_rows))
        a8[ann_rows, q] = ((a8[ann_rows, q].astype(np.int64) - 32 + 1
                            + rng.integers(0, 94, len(ann_rows))) % 95 + 32)
        tax_rows = churned[rng.random(n_churn) < 0.05]
        cur["taxid"][tax_rows, 0] += 1
        cur["alive"][gone] = False
        cur["alive"][n + (r - 1) * n_new: n + r * n_new] = True
        hist.append(cur)
    return hist


def release_table(rel: dict):
    ids = np.nonzero(rel["alive"])[0]
    return keys_of(ids), {f: rel[f][ids] for f in FIELDS}


def check_view(label: str, view, rel: dict, fields) -> None:
    ids = np.nonzero(rel["alive"])[0]
    expect(np.array_equal(np.asarray(view.row_idx), ids),
           f"{label}: rows differ from the oracle")
    expect(list(view.keys) == keys_of(ids), f"{label}: keys differ")
    for f in fields:
        expect(np.array_equal(view.values[f], rel[f][ids]),
               f"{label}: field {f} differs from the oracle")


def check_increment(label: str, inc, hist: list[dict], t0: int, t1: int,
                    sig, fields) -> None:
    """Oracle for the window (t0, t1] over releases at ts 1..N: an entry is
    updated when a significant field changed in some release inside the
    window (a new cell), new/deleted by its alive flag at the two ends."""
    a, b = hist[t0 - 1], hist[t1 - 1]
    changed = np.zeros(len(a["alive"]), bool)
    for r in range(t0, t1):
        for f in sig:
            changed |= (hist[r][f] != hist[r - 1][f]).any(axis=1)
    e0, e1 = a["alive"], b["alive"]
    new, deleted, updated = e1 & ~e0, e0 & ~e1, e1 & e0 & changed
    ids = np.nonzero(new | deleted | updated)[0]
    kind = np.where(new[ids], 0, np.where(updated[ids], 1, 2)).astype(np.int8)
    expect(np.array_equal(np.asarray(inc.row_idx), ids),
           f"{label}: changed rows differ from the oracle")
    expect(list(inc.keys) == keys_of(ids), f"{label}: keys differ")
    expect(np.array_equal(inc.kind, kind), f"{label}: kinds differ")
    for f in fields:
        want = b[f][ids].copy()
        want[kind == 2] = 0
        expect(np.array_equal(inc.values[f], want),
               f"{label}: field {f} differs from the oracle")


def stream_oracle(n: int, seed: int, seq_len: int, churn: float) -> dict:
    """What ``synth_uniprot_chunks(n, seed=seed, churn=churn,
    seq_len=seq_len)`` holds, rebuilt from its seeding rule as arrays in
    the parser's schema (keys ``P%08d``, one row per entry)."""
    rng = np.random.RandomState(seed)
    seq = np.zeros((n, SEQ_W), np.uint8)
    taxid = np.zeros((n, 1), np.int32)
    annot = np.zeros((n, ANNOT_W), np.uint8)
    for i in range(n):
        mutate = churn > 0 and rng.random_sample() < churn
        erng = np.random.RandomState(
            (i * 2654435761 + (seed + 1 if mutate else 0)) % (2**31))
        seq[i, :seq_len] = AA[erng.randint(0, len(AA), seq_len)]
        taxid[i, 0] = erng.randint(1, 99999)
        text = (f"RecName: Full=Synthetic protein {i}; | "
                "Synthetica gestorensis.").encode()[:ANNOT_W]
        annot[i, :len(text)] = np.frombuffer(text, np.uint8)
    return {"sequence": seq.view(np.int8),
            "length": np.full((n, 1), seq_len, np.int32),
            "annotation": annot.view(np.int8), "taxid": taxid,
            "alive": np.ones(n, bool)}


# -- device checks ------------------------------------------------------------

def memory_line(label: str) -> None:
    import jax
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        print(f"memory={label} device={d.id} "
              f"bytes_in_use={st.get('bytes_in_use')} "
              f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
              f"bytes_limit={st.get('bytes_limit')}", flush=True)


def check_dispatch() -> None:
    """Every main-path kernel's default dispatch compiles to a Pallas
    ``tpu_custom_call`` on this device: nothing falls back to its jnp
    reference or to interpret mode."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import _compat, compact_rewrite, launch
    from repro.kernels.batched_select import (batched_masked_cumsum,
                                              stacked_masked_cumsum)
    from repro.kernels.delta_codec import delta_pack, delta_unpack
    from repro.kernels.fingerprint import fingerprint
    from repro.kernels.shard_route import shard_route
    from repro.kernels.version_select import masked_cumsum

    expect(not _compat.interpret_default(),
           "kernels would dispatch to interpret mode / jnp references")
    i32 = jnp.int32
    ts = jnp.zeros((5000,), i32)
    qs = jnp.zeros((8,), i32)
    lanes = jnp.zeros((3000, 3), i32)
    a8 = jnp.zeros((1500, ANNOT_W), jnp.int8)
    probes = {
        "batched_select": (lambda t, q: batched_masked_cumsum(t, q), ts, qs),
        "stacked_select": (lambda t, q: stacked_masked_cumsum(t, q),
                           jnp.zeros((4, 4096), i32), qs),
        "masked_cumsum": (lambda t: masked_cumsum(t, 3), ts),
        "fingerprint": (lambda x: fingerprint(x), lanes),
        "shard_route": (lambda x, n: shard_route(x, n, 4), lanes,
                        jnp.zeros((3000,), i32)),
        "delta_pack": (lambda a, b: delta_pack(a, b)[0], a8, a8),
        "delta_unpack": (lambda a, b: delta_unpack(a, b), a8, a8),
        "keep_mask": (lambda t: compact_rewrite._keep_mask(
            t, cutoff=2, interpret=False,
            tile=launch.tile_for("compact_rewrite")), ts),
    }
    for name, (fn, *args) in probes.items():
        text = jax.jit(fn).lower(*args).compile().as_text()
        expect("tpu_custom_call" in text,
               f"{name}: no Pallas kernel in the compiled program")
    print(f"pallas_kernels={','.join(probes)}", flush=True)


# -- the one-chip lifecycle ---------------------------------------------------

def run_lifecycle(*, entries: int, stream_entries: int, seed: int,
                  root: str) -> None:
    from repro.core.increment import GeStore
    from repro.core.ingest import IngestConfig, synth_uniprot_chunks
    from repro.core.parsers import UniProtParser
    from repro.core.plugins import PluginRegistry
    from repro.serve import FrontDoor

    with phase("data", entries=entries):
        hist = make_history(entries, seed)
        tables = [release_table(rel) for rel in hist]
    registry = PluginRegistry()
    parser = UniProtParser()
    registry.register_parser(parser)
    gs = GeStore(root, registry)

    with phase("build", releases=N_RELEASES):
        st = gs.create_store(MAIN, parser.schema(),
                             capacity=len(hist[0]["alive"]))
        for ts, (keys, table) in enumerate(tables, start=1):
            info = st.update(ts, keys, table, label=f"release-{ts}")
            expect(info.n_entries == len(keys), f"release {ts}: entry count")
    del tables

    seq_len = 360
    with phase("stream", entries=stream_entries, shards=4):
        rep = gs.add_release_stream(
            STREAM, 1, synth_uniprot_chunks(stream_entries, seed=seed,
                                            churn=CHURN, seq_len=seq_len),
            parser_name=parser.format_name, shards=4,
            config=IngestConfig(batch_entries=4096))
    expect(rep.n_entries == stream_entries and rep.info is not None,
           "stream: not every entry was ingested")
    print(f"stream_entries_per_s={rep.entries_per_s!r}", flush=True)
    stream_rel = stream_oracle(stream_entries, seed, seq_len, CHURN)

    # reads: three tenants, pinned versions and field subsets
    reads = [("analysis-a", MAIN, 1, None), ("analysis-a", MAIN, 2, None),
             ("analysis-a", MAIN, 3, None), ("blast", MAIN, 3, BLAST_FIELDS),
             ("blast", MAIN, 1, BLAST_FIELDS), ("blast", MAIN, 2, BLAST_FIELDS),
             ("curation", MAIN, 2, ["annotation", "taxid"]),
             ("curation", MAIN, 3, ["annotation", "taxid"]),
             ("curation", STREAM, 1, None), ("analysis-a", MAIN, 3, None)]
    fd = FrontDoor(gs)
    with phase("reads", requests=len(reads)) as info:
        futs = [fd.submit(tenant, store, ts, fields=fields)
                for tenant, store, ts, fields in reads]
        info["waves"] = fd.pump()
        views = [f.result() for f in futs]
    memory_line("resident")
    for (tenant, store, ts, fields), view in zip(reads, views):
        rel = stream_rel if store == STREAM else hist[ts - 1]
        check_view(f"read {tenant} {store}@{ts}", view, rel,
                   fields or FIELDS)

    with phase("increments", windows=3):
        incs = st.get_increments([(1, 2), (2, 3)])
        blast = st.get_increments([(1, 3)], significant_fields=BLAST_FIELDS)
    check_increment("increment (1,2]", incs[0], hist, 1, 2, FIELDS, FIELDS)
    check_increment("increment (2,3]", incs[1], hist, 2, 3, FIELDS, FIELDS)
    check_increment("blast increment (1,3]", blast[0], hist, 1, 3,
                    BLAST_FIELDS, FIELDS)

    with phase("flush") as info:
        stats = gs.flush()
        info["bytes_written"] = sum(s["bytes_written"] for s in stats.values())
    with phase("reopen_cold_read"):
        st2 = GeStore(root, registry).open_store(MAIN)
        v3 = st2.get_version(3)
        inc23 = st2.get_increment(2, 3, significant_fields=BLAST_FIELDS)
    check_view("reopened sprot@3", v3, hist[2], FIELDS)
    check_increment("reopened blast increment (2,3]", inc23, hist, 2, 3,
                    BLAST_FIELDS, FIELDS)
    del st2, v3, inc23

    with phase("compact", horizon=2) as info:
        info["cells_dropped"] = st.compact(
            2, path=gs.store_path(MAIN))["cells_dropped"]
        after = st.get_versions([2, 3])
    for ts, view in zip((2, 3), after):
        check_view(f"compacted sprot@{ts}", view, hist[ts - 1], FIELDS)
    check_increment("compacted increment (2,3]",
                    st.get_increment(2, 3), hist, 2, 3, FIELDS, FIELDS)
    reopened = GeStore(root, registry).open_store(MAIN).get_version(3)
    check_view("compacted, reopened sprot@3", reopened, hist[2], FIELDS)
    memory_line("end")


# -- the four-chip path -------------------------------------------------------

def run_mesh(*, entries: int, seed: int, root: str, chips: int) -> None:
    import jax
    from repro.core.increment import GeStore
    from repro.core.parsers import UniProtParser
    from repro.core.placement import plan_placement
    from repro.core.plugins import PluginRegistry

    with phase("data", entries=entries):
        hist = make_history(entries, seed)
        tables = [release_table(rel) for rel in hist]
    registry = PluginRegistry()
    parser = UniProtParser()
    registry.register_parser(parser)
    gs = GeStore(root, registry)
    with phase("build", releases=N_RELEASES, shards=chips):
        st = gs.create_store(SHARDED, parser.schema(), shards=chips,
                             capacity=len(hist[0]["alive"]))
        for ts, (keys, table) in enumerate(tables, start=1):
            st.update(ts, keys, table, label=f"release-{ts}")
    del tables

    pins = [1, 2, 3]
    windows = [(1, 2), (2, 3)]
    results = {}
    for mode in ("mesh", "serial"):
        st.placement = plan_placement(
            chips, devices=jax.devices()[:chips],
            force="serial" if mode == "serial" else None)
        expect(st.placement.mode == mode,
               f"placement planned {st.placement.mode}, wanted {mode}")
        with phase(f"reads_{mode}", versions=len(pins)):
            views = st.get_versions(pins)
        with phase(f"increments_{mode}", windows=len(windows)):
            incs = st.get_increments(windows)
        memory_line(f"after_{mode}")
        results[mode] = (views, incs)
    for ts, view in zip(pins, results["mesh"][0]):
        check_view(f"mesh sprot4@{ts}", view, hist[ts - 1], FIELDS)
    for (t0, t1), inc in zip(windows, results["mesh"][1]):
        check_increment(f"mesh increment ({t0},{t1}]", inc, hist, t0, t1,
                        FIELDS, FIELDS)
    for a, b in zip(results["mesh"][0], results["serial"][0]):
        expect(a.keys == b.keys and np.array_equal(a.row_idx, b.row_idx)
               and all(np.array_equal(a.values[f], b.values[f])
                       and a.values[f].dtype == b.values[f].dtype
                       for f in FIELDS),
               f"mesh and serial reads differ at ts {a.ts}")
    for a, b in zip(results["mesh"][1], results["serial"][1]):
        expect(a.keys == b.keys and np.array_equal(a.kind, b.kind)
               and all(np.array_equal(a.values[f], b.values[f])
                       for f in FIELDS),
               f"mesh and serial increments differ at ({a.t0},{a.t1}]")
    print("mesh_matches_serial=true", flush=True)


# -- entry point --------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--entries", type=int, default=SWISSPROT_ENTRIES,
                    help="entries in the first release")
    ap.add_argument("--stream-entries", type=int, default=20_000)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh-vs-serial path")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke.py must sit at the root of a GeStore checkout "
              f"(no src/repro beside {ROOT})", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devices[0].platform} "
              f"({devices[0].device_kind}); refusing to run",
              file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return 3

    # built-in tiles and default placement: no launch state from outside
    # the checkout decides what is compiled
    for k in [k for k in os.environ if k.startswith("GESTORE_")]:
        del os.environ[k]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels import launch
    cache = launch.use_compile_cache(ROOT)
    COMPILES.install()
    tiles = {k: launch.tile_for(k) for k in launch.DEFAULT_TILES}
    d0 = devices[0]
    print(f"device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    print(f"compile_cache={cache}", flush=True)
    print(f"tiles={json.dumps(tiles, sort_keys=True)}", flush=True)
    if args.entries < SWISSPROT_ENTRIES:
        print(f"cut: entries={args.entries} (Swiss-Prot size "
              f"{SWISSPROT_ENTRIES})", flush=True)

    root = os.path.join(ROOT, ".smoke")
    shutil.rmtree(root, ignore_errors=True)
    try:
        expect(tiles == launch.DEFAULT_TILES, f"tiles resolved to {tiles}")
        if args.chips == 4:
            run_mesh(entries=args.entries, seed=args.seed, root=root,
                     chips=args.chips)
        else:
            with phase("dispatch"):
                check_dispatch()
            run_lifecycle(entries=args.entries,
                          stream_entries=args.stream_entries,
                          seed=args.seed, root=root)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
