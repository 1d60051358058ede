"""Unified launch helper (kernels/launch.py): tile resolution + autotune
cache, recompile-proof shape bucketing under continuous ingest, and the
byte-equivalence suites pinning the new device paths (in-kernel chain
decode, two-lane 8-byte codec, device compact rewrite) to their host
oracles in kernels/ref.py."""
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.store import FieldSchema, VersionedStore
from repro.kernels import launch, ops, ref
from repro.kernels.compact_rewrite import compact_rewrite, ref_compact_rewrite
from repro.kernels.delta_codec import (chain_pack, chain_unpack,
                                       delta_pack_wide, delta_unpack_wide)


# ---------------------------------------------------------------------------
# tile resolution + autotune cache
# ---------------------------------------------------------------------------

@pytest.fixture
def tile_cache(tmp_path, monkeypatch):
    """Point the winner cache at a throwaway file and drop the in-memory
    mirror on both sides of the test (the mirror outlives monkeypatch)."""
    path = tmp_path / "tiles.json"
    monkeypatch.setenv(launch.CACHE_ENV, str(path))
    launch.reset_cache()
    yield path
    launch.reset_cache()


def test_pow2_bucket():
    assert launch.pow2_bucket(0) == 1
    assert launch.pow2_bucket(1) == 1
    assert launch.pow2_bucket(5) == 8
    assert launch.pow2_bucket(8) == 8
    assert launch.pow2_bucket(9) == 16
    assert launch.pow2_bucket(3, floor=8) == 8
    assert launch.pow2_bucket(900, floor=512) == 1024


def test_tile_env_override_wins(tile_cache, monkeypatch):
    launch.record_winner("batched_select", 8192, 4096)
    monkeypatch.setenv(launch.ENV_PREFIX + "BATCHED_SELECT", "1024")
    assert launch.tile_for("batched_select", n=5000) == 1024
    # malformed overrides, and tiles the TPU compiler would refuse for the
    # kernel's blocks, are rejected before any launch is built
    for bad in ("zero", "0", "-128", "1000"):
        monkeypatch.setenv(launch.ENV_PREFIX + "BATCHED_SELECT", bad)
        with pytest.raises(ValueError, match="multiple of 128"):
            launch.tile_for("batched_select", n=5000)
    monkeypatch.setenv(launch.ENV_PREFIX + "SHARD_ROUTE", "512")
    with pytest.raises(ValueError, match="multiple of 1024"):
        launch.tile_for("shard_route", n=5000)
    monkeypatch.delenv(launch.ENV_PREFIX + "BATCHED_SELECT")
    assert launch.tile_for("batched_select", n=5000) == 4096
    # other buckets still see the built-in default
    assert launch.tile_for("batched_select", n=100) \
        == launch.DEFAULT_TILES["batched_select"]


def test_sweep_records_and_caches(tile_cache):
    calls = []

    def bench(tile):
        calls.append(tile)
        return 1.0 if tile != 2048 else 0.5

    # candidates the chip would refuse never reach the bench
    with pytest.raises(ValueError, match="refuses"):
        launch.sweep("shard_route", bench, n=900, candidates=(512, 1024))
    assert calls == []
    res = launch.sweep("shard_route", bench, n=900,
                       candidates=(256 * 4, 512 * 4, 1024 * 4))
    assert res["tile"] == 2048 and not res["cached"]
    assert res["bucket"] == 1024
    assert sorted(calls) == [1024, 2048, 4096]
    # winner persisted to the env-pointed file...
    with open(tile_cache) as f:
        disk = json.load(f)
    assert any(k.startswith("shard_route/") and k.endswith("/b1024")
               for k in disk)
    # ...the serving path resolves it, and a repeat sweep is a cache read
    assert launch.tile_for("shard_route", n=900) == 2048
    calls.clear()
    res2 = launch.sweep("shard_route", bench, n=1000)
    assert res2["cached"] and res2["tile"] == 2048 and calls == []
    # force=True re-runs even with a winner on disk
    res3 = launch.sweep("shard_route", bench, n=900,
                        candidates=(1024, 2048), force=True)
    assert not res3["cached"] and calls == [1024, 2048]


def test_winner_cache_survives_reset(tile_cache, monkeypatch):
    launch.record_winner("delta_codec", 2048, 1024)
    launch.reset_cache()  # drop the mirror: must re-read from disk
    assert launch.tile_for("delta_codec", n=1500) == 1024
    # a cached winner the chip would refuse falls back to the built-in
    launch.record_winner("shard_route", 2048, 512)
    assert launch.tile_for("shard_route", n=1500) \
        == launch.DEFAULT_TILES["shard_route"]
    # without GESTORE_TILE_CACHE nothing is read from or written to disk:
    # the built-in tiles are the only launch state
    monkeypatch.delenv(launch.CACHE_ENV)
    launch.reset_cache()
    assert launch.cache_path() is None
    assert launch.tile_for("delta_codec", n=1500) \
        == launch.DEFAULT_TILES["delta_codec"]
    launch.record_winner("delta_codec", 2048, 256)
    assert launch.tile_for("delta_codec", n=1500) == 256
    with open(tile_cache) as f:
        assert json.load(f)["delta_codec/cpu/b2048"] == 1024


# ---------------------------------------------------------------------------
# recompile stability under continuous ingest (the table9 stall)
# ---------------------------------------------------------------------------

def _mk_rel(rng, keys):
    return {"a": rng.integers(0, 50, (len(keys), 4)).astype(np.int32),
            "b": rng.normal(size=(len(keys), 2)).astype(np.float32)}


def test_epoch_rolls_bounded_by_buckets(rng):
    """N epoch rolls under continuous ingest must compile at most one scan
    per visited pow2 cell bucket — not one per ingest."""
    before = ops.scan_cache_size()
    if before < 0:
        pytest.skip("jit cache probing unavailable on this jax")
    st = VersionedStore("t", [FieldSchema("a", 4, "int32"),
                              FieldSchema("b", 2, "float32")])
    n_rolls = 12
    buckets = set()
    for v in range(n_rolls):
        keys = [f"K{i:04d}" for i in range((v + 1) * 40)]
        st.update((v + 1) * 10, keys, _mk_rel(rng, keys))
        st.get_versions([(v + 1) * 10, v * 10 + 5], fields=["a"])
        buckets.add(ops.scan_bucket(st._superlog.n_cells))
    grew = ops.scan_cache_size() - before
    # every ingest changes the cell count; without bucketing this is
    # >= n_rolls traces. With it: at most one per (bucket, query-shape)
    assert grew <= len(buckets) + 1, \
        f"{grew} compiles for {n_rolls} rolls over {len(buckets)} buckets"
    assert grew < n_rolls


def test_bucketed_scan_matches_unpadded_ref(rng):
    """Sentinel-padding the cell axis to its pow2 bucket never changes the
    logical columns of the scan."""
    for c in (1, 7, 100, 2047, 2049, 5000):
        ts = np.sort(rng.integers(0, 97, c)).astype(np.int32)
        tq = np.array([-1, 0, 50, 96, 97], np.int32)
        c_pad = ops.scan_bucket(c)
        padded = np.concatenate(
            [ts, np.full(c_pad - c, np.iinfo(np.int32).max, np.int32)])
        got = np.asarray(ops.batched_masked_cumsum(
            jnp.asarray(padded), jnp.asarray(tq), interpret=True))[:, :c]
        want = np.asarray(ref.ref_batched_masked_cumsum(
            jnp.asarray(ts), jnp.asarray(tq)))
        assert np.array_equal(got, want), f"c={c}"


# ---------------------------------------------------------------------------
# in-kernel chain decode == host depth loop
# ---------------------------------------------------------------------------

def _chains(rng, c, w, dtype, lo, hi):
    rows = np.sort(rng.integers(0, max(c // 4, 1), c))
    heads = np.ones(c, bool)
    heads[1:] = rows[1:] != rows[:-1]
    vals = rng.integers(lo, hi, (c, w)).astype(dtype)
    return rows, heads, vals


@pytest.mark.parametrize("dtype", [np.int16, np.int32])
def test_chain_decode_matches_ref(dtype, rng):
    rows, heads, vals = _chains(rng, 500, 3, dtype,
                                np.iinfo(dtype).min, np.iinfo(dtype).max)
    prev = np.roll(vals, 1, axis=0)
    prev[heads] = 0
    with np.errstate(over="ignore"):
        deltas = vals - prev  # stored-dtype wraparound is part of the format
    got = np.asarray(ops.chain_decode(jnp.asarray(deltas),
                                      jnp.asarray(heads)))
    want = ref.ref_chain_decode(deltas, heads)
    assert np.array_equal(got, want)
    # truncation back to the stored dtype recovers the original values
    assert np.array_equal(got.astype(dtype), vals)


def test_chain_decode_xor_lanes(rng):
    rows, heads, _ = _chains(rng, 300, 2, np.int32, -1, 1)
    vals = rng.normal(size=(300, 2)).astype(np.float32)
    prev = np.roll(vals, 1, axis=0)
    prev[heads] = 0
    deltas = vals.view(np.int32) ^ prev.view(np.int32)
    got = np.asarray(ops.chain_decode(jnp.asarray(deltas),
                                      jnp.asarray(heads), xor=True))
    assert np.array_equal(got.view(np.float32).view(np.int32),
                          vals.view(np.int32))


def test_packed_superlog_matches_unpacked(rng, monkeypatch):
    """get_versions over a packed-on-device superlog is byte-identical to
    the unpacked store (GESTORE_PACKED_SUPERLOG=0)."""
    def build():
        st = VersionedStore("t", [FieldSchema("a", 4, "int32"),
                                  FieldSchema("b", 2, "float32")])
        r = np.random.default_rng(7)
        pool = [f"K{i:03d}" for i in range(64)]
        for v in range(5):
            sub = sorted(r.choice(pool, size=r.integers(20, 64),
                                  replace=False))
            st.update((v + 1) * 10, sub, _mk_rel(r, sub))
        return st.get_versions([10, 25, 30, 50, 55], fields=["a", "b"])

    monkeypatch.setenv("GESTORE_PACKED_SUPERLOG", "0")
    plain = build()
    monkeypatch.setenv("GESTORE_PACKED_SUPERLOG", "1")
    packed = build()
    for p, q in zip(plain, packed):
        assert list(p.keys) == list(q.keys)
        for f in ("a", "b"):
            assert np.array_equal(p.values[f], q.values[f])


# ---------------------------------------------------------------------------
# two-lane 8-byte codec == 64-bit host oracle
# ---------------------------------------------------------------------------

def test_wide_codec_int64_roundtrip(rng):
    new = rng.integers(-2**62, 2**62, (257, 3)).astype(np.int64)
    old = rng.integers(-2**62, 2**62, (257, 3)).astype(np.int64)
    # force modular wraparound through the lane arithmetic
    new[0] = np.iinfo(np.int64).min
    old[0] = np.iinfo(np.int64).max
    new[1] = np.iinfo(np.int64).max
    old[1] = -1
    d = delta_pack_wide(new, old, interpret=True)
    assert np.array_equal(d, ref.ref_delta_pack64(new, old))
    back = delta_unpack_wide(d, old, interpret=True)
    assert np.array_equal(back, new)


def test_wide_codec_float64_xor(rng):
    new = rng.normal(size=(100, 2)).astype(np.float64)
    old = rng.normal(size=(100, 2)).astype(np.float64)
    new[0, 0] = np.nan  # bit-exact through XOR, even non-finite
    old[1, 1] = np.inf
    d = delta_pack_wide(new, old, interpret=True)
    assert np.array_equal(d.view(np.int64),
                          ref.ref_delta_pack64(new, old).view(np.int64))
    back = delta_unpack_wide(d, old, interpret=True)
    assert np.array_equal(back.view(np.int64), new.view(np.int64))


@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_chain_codec_8byte_roundtrip(dtype, rng):
    """chain_pack/chain_unpack on 8-byte cells round-trips bit-exactly
    through whichever lane path the backend picked."""
    c = 400
    rows = np.sort(rng.integers(0, 60, c)).astype(np.int64)
    if np.issubdtype(np.dtype(dtype), np.floating):
        vals = rng.normal(size=(c, 2)).astype(dtype)
    else:
        vals = rng.integers(-2**62, 2**62, (c, 2)).astype(dtype)
    packed, meta = chain_pack(vals, rows)
    back = chain_unpack(packed, rows, meta, np.dtype(dtype))
    assert back.dtype == np.dtype(dtype)
    assert np.array_equal(back.view(np.int64), vals.view(np.int64))


# ---------------------------------------------------------------------------
# device compact rewrite == numpy oracle
# ---------------------------------------------------------------------------

def _mk_log(rng, n_rows, c, w, dtype=np.int32):
    rows = np.sort(rng.integers(0, n_rows, c)).astype(np.int32)
    tss = rng.integers(0, 1000, c).astype(np.int64)
    order = np.lexsort((tss, rows))
    rows, tss = rows[order], tss[order]
    vals = rng.integers(-50, 50, (c, w)).astype(dtype)
    ptr = np.zeros(n_rows + 1, np.int32)
    np.add.at(ptr, rows + 1, 1)
    return vals, tss, np.cumsum(ptr).astype(np.int32)


@pytest.mark.parametrize("c,horizon", [(1, 0), (7, 500), (513, 500),
                                       (1000, 0), (1000, 2000)])
def test_compact_rewrite_matches_oracle(c, horizon, rng):
    n_rows = 40
    vals, tss, ptr = _mk_log(rng, n_rows, c, 3)
    base_vals = rng.integers(-50, 50, (n_rows, 3)).astype(np.int32)
    base_found = rng.random(n_rows) < 0.7
    want = ref_compact_rewrite(vals, tss, ptr, base_vals, base_found,
                               horizon, n_rows)
    got = compact_rewrite(vals, tss, ptr, base_vals, base_found,
                          horizon, n_rows, interpret=True)
    for g, w in zip(got, want):
        assert np.array_equal(g, w)


def test_store_compact_preserves_history_reads(rng):
    """End-to-end: compacting through the device rewrite keeps every
    still-visible version byte-identical."""
    st = VersionedStore("t", [FieldSchema("a", 4, "int32"),
                              FieldSchema("b", 2, "float32")])
    pool = [f"K{i:03d}" for i in range(48)]
    for v in range(6):
        sub = sorted(rng.choice(pool, size=rng.integers(16, 48),
                                replace=False))
        st.update((v + 1) * 10, sub, _mk_rel(rng, sub))
    qs = [35, 40, 55, 60]
    before = st.get_versions(qs, fields=["a", "b"])
    st.compact(before_ts=30)
    after = st.get_versions(qs, fields=["a", "b"])
    for p, q in zip(before, after):
        assert list(p.keys) == list(q.keys)
        for f in ("a", "b"):
            assert np.array_equal(p.values[f], q.values[f])
