"""Table 11: kernel launch tuning — tile sweeps.

``table11.sweep_<kernel>`` rows, built on the unified launch helper
(``src/repro/kernels/launch.py``): run the explicit autotune sweep for
each kernel at bench scale and report the winning tile's us/call. The
derived column records ``tile``/``bucket``/``cached`` (``cached=1``
means the on-disk winner cache answered and no sweep ran — which is
exactly what the CI ``actions/cache`` restore of ``GESTORE_TILE_CACHE``
buys). The winner is persisted per (kernel, platform, pow2 shape
bucket), so serving picks it up with no env knobs set. Kernel time and
roofline shares come from the device trace of the chip benchmark
(``benchmarks/chip``), not from host-clock walls.

Scale with ``BENCH_KERNEL_N`` (falls back to ``BENCH_BATCH_N``); widen
the sweep with ``GESTORE_TILE_<KERNEL>`` unset (an env override bypasses
the cache entirely, by design).
"""
from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np

from repro.kernels import launch
from repro.kernels.batched_select import batched_masked_cumsum
from repro.kernels.delta_codec import delta_pack
from repro.kernels.shard_route import key_lanes, shard_route

from ._util import timeit

N = int(os.environ.get("BENCH_KERNEL_N",
                       os.environ.get("BENCH_BATCH_N", 8_000)))
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


def run() -> list[tuple[str, float, str]]:
    return _sweep_rows()
