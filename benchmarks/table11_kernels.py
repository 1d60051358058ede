"""Table 11: kernel launch tuning — tile sweeps + steady-state rooflines.

Two row families, both built on the unified launch helper
(``src/repro/kernels/launch.py``):

  * ``table11.sweep_<kernel>`` — run the explicit autotune sweep for each
    kernel at bench scale and report the winning tile's us/call. The
    derived column records ``tile``/``bucket``/``cached`` (``cached=1``
    means the on-disk winner cache answered and no sweep ran — which is
    exactly what the CI ``actions/cache`` restore of
    ``GESTORE_TILE_CACHE`` buys). The winner is persisted per
    (kernel, platform, pow2 shape bucket), so serving picks it up with no
    env knobs set.
  * ``table11.steady_<kernel>`` — WARM steady-state launches only: the
    drive runs once to compile, telemetry is cleared, then ``REPS`` more
    launches are sampled. The derived column carries the padded-byte
    roofline fraction plus both achieved bandwidths (padded = what moved,
    logical = the useful fraction of it); a collapsing ``roofline_frac``
    or a padded/logical ratio drifting far from 1 gates CI via
    tools/bench_compare.py.

Scale with ``BENCH_KERNEL_N`` (falls back to ``BENCH_BATCH_N``); widen
the sweep with ``GESTORE_TILE_<KERNEL>`` unset (an env override bypasses
the cache entirely, by design).
"""
from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np

from repro.core.store import FieldSchema, VersionedStore
from repro.kernels import launch
from repro.kernels.batched_select import batched_masked_cumsum
from repro.kernels.delta_codec import chain_pack, chain_unpack, delta_pack
from repro.kernels.shard_route import key_lanes, route_keys, shard_route
from repro.obs.kerneltel import KERNELS

from ._util import frac, synth_release, timeit

N = int(os.environ.get("BENCH_KERNEL_N",
                       os.environ.get("BENCH_BATCH_N", 8_000)))
REPS = int(os.environ.get("BENCH_KERNEL_REPS", 5))
SWEEP_KERNELS = ("batched_select", "shard_route", "delta_codec")


def _benches() -> dict:
    """bench(tile) -> wall seconds, one closure per swept kernel. Each
    closure launches the device entry point with an explicit static tile
    (tile=None would re-resolve and hide the candidate under test)."""
    rng = np.random.default_rng(3)
    ts = jnp.asarray(rng.integers(0, 10_000, N).astype(np.int32))
    tq = jnp.asarray(np.linspace(0, 10_000, 32).astype(np.int32))
    lanes, lens = key_lanes([f"P{i:08d}".encode() for i in range(N)])
    lanes, lens = jnp.asarray(lanes), jnp.asarray(lens)
    a = jnp.asarray(rng.integers(-500, 500, (N, 16)).astype(np.int32))
    b = jnp.asarray(rng.integers(-500, 500, (N, 16)).astype(np.int32))

    def bench_select(tile):
        def go():
            batched_masked_cumsum(ts, tq, tile=tile).block_until_ready()
        t, _ = timeit(go, reps=3, warmup=1)
        return t

    def bench_route(tile):
        def go():
            shard_route(lanes, lens, 8, tile=tile).block_until_ready()
        t, _ = timeit(go, reps=3, warmup=1)
        return t

    def bench_codec(tile):
        def go():
            d, _stat = delta_pack(a, b, tile=tile)
            d.block_until_ready()
        t, _ = timeit(go, reps=3, warmup=1)
        return t

    return {"batched_select": bench_select, "shard_route": bench_route,
            "delta_codec": bench_codec}


def _sweep_rows() -> list[tuple[str, float, str]]:
    rows = []
    benches = _benches()
    for kernel in SWEEP_KERNELS:
        bench = benches[kernel]
        res = launch.sweep(kernel, bench, n=N)
        # cached winners skipped the sweep; still time the winner once so
        # the row value stays comparable across cached/uncached runs
        wall = res["walls"].get(res["tile"]) or bench(res["tile"])
        rows.append((
            f"table11.sweep_{kernel}", wall * 1e6,
            f"tile={res['tile']};bucket={res['bucket']};"
            f"cached={int(res['cached'])};n={N}"))
    return rows


def _steady_state() -> list[tuple[str, float, str]]:
    """Warm per-launch telemetry through the real instrumented call sites
    (the store's fused scan, route_keys, the chain codec)."""
    st = VersionedStore("t11", [FieldSchema("sequence", 16, "int32"),
                                FieldSchema("length", 1, "int32")],
                        capacity=N + N // 4)
    rel = synth_release(N, seq_w=16, seed=5)
    st.update(10, *rel)
    for v in range(1, 4):
        rel = synth_release(0, base=rel, frac_updated=0.05, n_new=N // 100,
                            seed=v + 5)
        st.update((v + 1) * 10, *rel)
    ts_list = [((i % 4) + 1) * 10 for i in range(32)]
    keys = [f"P{i:08d}".encode() for i in range(N)]
    rng = np.random.default_rng(13)
    crows = np.sort(rng.integers(0, max(N // 4, 1), size=N)).astype(np.int64)
    cvals = rng.integers(0, 100, size=(N, 16)).astype(np.int32)

    def drive():
        st.get_versions(ts_list, fields=["sequence"])
        route_keys(keys, 8)
        packed, meta = chain_pack(cvals, crows)
        chain_unpack(packed, crows, meta, np.dtype(np.int32))

    drive()                  # compile/trace + autotune-cache read
    KERNELS.clear()          # telemetry now sees only warm launches
    for _ in range(REPS):
        drive()
    snap = KERNELS.snapshot()
    rows = []
    for kernel in SWEEP_KERNELS:
        k = snap.get(kernel)
        if k is None:        # an instrumented path went dark: that IS the row
            rows.append((f"table11.steady_{kernel}", float("nan"),
                         "missing=1"))
            continue
        rows.append((
            f"table11.steady_{kernel}", k["us_per_call"],
            f"roofline_frac={frac(k['roofline_fraction'])};"
            f"gbytes_per_s={k['gbytes_per_s']:.2f};"
            f"logical_gbytes_per_s={k['logical_gbytes_per_s']:.2f};"
            f"calls={k['calls']}"))
    return rows


def run() -> list[tuple[str, float, str]]:
    return _sweep_rows() + _steady_state()
