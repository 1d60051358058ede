"""Pallas key->shard routing kernel (sharded-store scatter step).

The sharded meta-database facade (core/shard.py) hash-partitions the entry
keyspace over N independent stores, mirroring the paper's spread of
meta-database rows across HBase region servers (§II.B/§V). Routing must be
a *persistent* function of the key alone — the same key has to land on the
same shard across releases, processes, and batch compositions — so the hash
folds zero-padded little-endian key lanes with a zero-transparent
xor-rotate mix (a padded zero lane contributes nothing) and disambiguates
real trailing zero bytes via the key length. ``ref.ref_shard_route`` is the
semantic ground truth; the kernel is a tiled VPU fold exactly like
fingerprint.py (reads N*W*4 bytes, writes N*4 -> bandwidth-bound).

The gather step of scatter-gather (merging per-shard row selections back
into global row order) is ``merge_shard_rows`` below: per-shard global-row
arrays are each ascending and mutually disjoint, so one argsort over the
concatenation reproduces the unsharded store's row order exactly.
"""
from __future__ import annotations

import functools
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import launch, ref
from ._compat import interpret_default

#: routing-function version tag, persisted in shard manifests: a store
#: written under one tag must never be extended by a different hash.
ROUTING_VERSION = "xor-rotate-fold-v1"


def _shard_route_kernel(lanes_ref, len_ref, out_ref, *, w: int, n_shards: int):
    h = jnp.zeros((lanes_ref.shape[0],), jnp.int32)
    for j in range(w):  # static unroll over lanes (keys are a few lanes wide)
        t = lanes_ref[:, j] * ref.RT_MUL1
        t = t ^ jax.lax.shift_right_logical(t, 15)
        t = t * ref.RT_MUL2
        r = (j % 31) + 1
        h = h ^ ((t << r) | jax.lax.shift_right_logical(t, 32 - r))
    h = h ^ (len_ref[:] * ref.RT_MUL3)
    h = h ^ jax.lax.shift_right_logical(h, 16)
    h = h * ref.RT_MUL4
    h = h ^ jax.lax.shift_right_logical(h, 13)
    out_ref[:] = (h & jnp.int32(0x7FFFFFFF)) % jnp.int32(n_shards)


def shard_route(lanes: jax.Array, lengths: jax.Array, n_shards: int, *,
                interpret: bool | None = None,
                tile: int | None = None) -> jax.Array:
    """lanes: (N, W) int32; lengths: (N,) int32 -> (N,) int32 shard ids.

    interpret=None: Pallas kernel on TPU, jitted ref oracle on CPU;
    interpret=True: force the kernel body via the Pallas interpreter."""
    if tile is None:
        tile = launch.tile_for("shard_route", n=lanes.shape[0])
    return _shard_route(lanes, lengths, int(n_shards), interpret=interpret,
                        tile=int(tile))


@functools.partial(jax.jit, static_argnames=("n_shards", "interpret", "tile"))
def _shard_route(lanes, lengths, n_shards, *, interpret, tile):
    if interpret is None:
        if interpret_default():
            return ref.ref_shard_route(lanes, lengths, n_shards)
        interpret = False
    n, w = lanes.shape
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    (out,) = launch.tiled_rows(
        functools.partial(_shard_route_kernel, w=w, n_shards=n_shards),
        [lanes, lengths], [((), jnp.int32)],
        tile=tile, interpret=interpret)
    return out


# -- host plumbing ------------------------------------------------------------

def key_lanes(keys: Sequence[bytes]) -> tuple[np.ndarray, np.ndarray]:
    """Pack byte keys into (lanes (N, W) int32, lengths (N,) int32): each
    key's bytes little-endian into 4-byte lanes, zero-padded to the batch
    max width (the hash is width-stable, so the batch max is just a packing
    convenience, not part of the route)."""
    n = len(keys)
    lens = np.fromiter((len(k) for k in keys), np.int32, count=n)
    wb = max((int(lens.max(initial=1)) + 3) // 4, 1) * 4
    buf = np.zeros((n, wb), np.uint8)
    for i, k in enumerate(keys):
        buf[i, : len(k)] = np.frombuffer(k, np.uint8)
    # explicit little-endian lane packing: the route (and therefore the
    # persisted partitioning) must not depend on host byte order
    lanes = buf.view("<u4").astype(np.uint32).view(np.int32)
    return lanes, lens


def route_keys(keys: Sequence[bytes], n_shards: int) -> np.ndarray:
    """Stable shard id per key: (N,) host int32 in [0, n_shards)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if not keys:
        return np.zeros(0, np.int32)
    if n_shards == 1:
        return np.zeros(len(keys), np.int32)
    lanes, lens = key_lanes(keys)
    n, w = lanes.shape
    # traffic model: read (N, W) lanes + (N,) lengths, write (N,) ids;
    # arithmetic: ~8 integer ops per lane in the xor-rotate fold + the
    # 5-op finalizer per key; padded counts the tile-multiple row slack
    n_pad = launch.round_up_tile(n, launch.tile_for("shard_route", n=n))
    with launch.measured("shard_route", nbytes=4 * (n * w + 2 * n),
                         flops=n * (8 * w + 5),
                         padded_nbytes=4 * (n_pad * w + 2 * n_pad)):
        return np.asarray(shard_route(jnp.asarray(lanes), jnp.asarray(lens),
                                      int(n_shards)))


def merge_shard_rows(parts: Sequence[np.ndarray]) -> tuple[np.ndarray, np.ndarray]:
    """Gather step: K per-shard ascending global-row arrays -> (merged rows,
    gather order into their concatenation). Shards partition the row space,
    so one argsort over the concatenation reproduces the exact ascending
    row order the unsharded store would have produced."""
    cat = (np.concatenate(parts) if len(parts)
           else np.zeros(0, np.int64))
    order = np.argsort(cat, kind="stable")
    return cat[order], order
