"""Unified kernel-launch plumbing: tiles, buckets, autotune, telemetry.

Every kernel in the family (``batched_select``, ``shard_route``,
``delta_codec``, ``compact_rewrite``) used to carry its own copy of the
same host-side launch logic — pad the leading axis to a hardcoded tile
multiple, build the grid/BlockSpec boilerplate, pick interpret mode, and
wrap the host-sync site in ``kerneltel``. This module is that plumbing,
written once:

  * **Tile resolution** (:func:`tile_for`): ``GESTORE_TILE_<KERNEL>`` env
    override > autotuned winner from the on-disk cache > built-in default.
    Every tile must be one the TPU compiler accepts for that kernel's
    blocks (:data:`TILE_QUANTUM`); an override that is not is rejected
    here instead of failing deep inside Mosaic. Resolution is pure host
    Python and happens *outside* jit, so the tile is a static launch
    parameter.
  * **Power-of-two shape buckets** (:func:`pow2_bucket`): the retrace
    killer. Operand leading dims are padded up to the next power of two so
    a continuously growing superlog (every ingest changes the cell count)
    revisits a small set of static shapes instead of recompiling per
    ingest — the same trick ``chain_pack`` has always used for segment
    cell runs.
  * **Autotune sweep** (:func:`sweep`): explicit, never implicit. The
    serving path only ever *reads* the cache; the sweep runs when
    ``benchmarks/table11_kernels.py`` (or a caller) asks for it, and the
    winning tile per ``(kernel, platform, shape bucket)`` is kept in memory
    and, when ``GESTORE_TILE_CACHE`` names a file, persisted there so it
    runs once per machine. There is no default file: without the variable
    every launch uses the built-in tiles, which live in this (committed)
    module. CI points the variable at a cached artifact so repeat runs
    skip the sweep entirely.
  * **Row-tiled pallas_call builder** (:func:`tiled_rows`): the shared
    1-D-grid launch shape (pad rows to a tile multiple, per-tile row
    blocks, slice back to the logical row count).
  * **Telemetry** (:func:`measured`): the ``kerneltel.launch`` wrap used
    by every host-facing call site, carrying *both* the logical traffic
    model and the padded bytes that actually move (bucket slack must not
    skew roofline fractions — see obs/kerneltel.py).

On the CPU backend the kernels dispatch to their jnp reference oracles, so
tile choice is a no-op there; the sweep still records a winner (cheap) to
keep the cache shape identical across platforms.
"""
from __future__ import annotations

import json
import os
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._compat import cdiv

#: built-in tiles: the launch tile of each kernel when neither an env
#: override nor a cached sweep winner applies.
DEFAULT_TILES = {
    "batched_select": 2048,
    "shard_route": 1024,
    "delta_codec": 512,
    "compact_rewrite": 1024,
}

#: every tile of a kernel must be a multiple of its quantum, the block
#: granule the TPU compiler accepts: the scan's tile is the lane axis of
#: its blocks (128 lanes); shard_route and compact_rewrite stream 1-D
#: int32 rows, which XLA lays out in 1024-element tiles; the codec's row
#: blocks hold int8 lanes, packed 32 rows to a sublane tile.
TILE_QUANTUM = {
    "batched_select": 128,
    "shard_route": 1024,
    "delta_codec": 32,
    "compact_rewrite": 1024,
}

#: default sweep candidates per kernel (table11 can widen via env).
SWEEP_CANDIDATES = {
    "batched_select": (512, 1024, 2048, 4096),
    "shard_route": (1024, 2048, 4096, 8192),
    "delta_codec": (256, 512, 1024, 2048),
    "compact_rewrite": (1024, 2048, 4096, 8192),
}

ENV_PREFIX = "GESTORE_TILE_"
CACHE_ENV = "GESTORE_TILE_CACHE"

_lock = threading.Lock()
#: in-memory mirror of the on-disk winner cache; None = not loaded yet.
_winners: dict[str, int] | None = None


# -- shape buckets ------------------------------------------------------------

def pow2_bucket(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor) (and >= 1): the static-shape
    bucket for a logically ``n``-long axis."""
    n = max(int(n), int(floor), 1)
    return 1 << (n - 1).bit_length()


def round_up_tile(n: int, tile: int) -> int:
    """Pad ``n`` up to a multiple of ``tile`` (at least one tile)."""
    return cdiv(max(int(n), 1), tile) * tile


# -- tile resolution ----------------------------------------------------------

def cache_path() -> str | None:
    """The on-disk autotune winner cache named by ``GESTORE_TILE_CACHE``,
    or None: winners then live in memory only and nothing outside the
    repository changes which tiles a launch compiles with."""
    return os.environ.get(CACHE_ENV, "").strip() or None


def tile_ok(kernel: str, tile: int) -> bool:
    """Whether the TPU compiler accepts ``tile`` for ``kernel``'s blocks."""
    return tile > 0 and tile % TILE_QUANTUM.get(kernel, 8) == 0


def _cache_key(kernel: str, bucket: int, platform: str | None = None) -> str:
    plat = platform or jax.default_backend()
    return f"{kernel}/{plat}/b{int(bucket)}"


def _load_winners() -> dict[str, int]:
    global _winners
    with _lock:
        if _winners is None:
            _winners = {}
            path = cache_path()
            try:
                if path is None:
                    raise FileNotFoundError
                with open(path) as f:
                    raw = json.load(f)
                _winners = {str(k): int(v) for k, v in raw.items()
                            if isinstance(v, (int, float))}
            except (OSError, ValueError, TypeError):
                pass  # missing or corrupt cache: start empty
        return _winners


def reset_cache() -> None:
    """Drop the in-memory winner mirror (tests / env changes re-read disk)."""
    global _winners
    with _lock:
        _winners = None


def record_winner(kernel: str, bucket: int, tile: int,
                  platform: str | None = None) -> None:
    """Record an autotuned winner in memory and, when ``GESTORE_TILE_CACHE``
    is set, in that file (best effort: an unwritable cache dir degrades to
    in-memory only)."""
    winners = _load_winners()
    with _lock:
        winners[_cache_key(kernel, bucket, platform)] = int(tile)
        payload = dict(winners)
    path = cache_path()
    if path is None:
        return
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


def tile_for(kernel: str, n: int | None = None) -> int:
    """Resolve the launch tile for ``kernel`` (leading-axis length ``n``).

    Precedence: ``GESTORE_TILE_<KERNEL>`` env var > autotuned winner for
    this (kernel, platform, pow2 bucket of n) > ``DEFAULT_TILES``. Always
    a plain positive int — callers pass it to jit as a static arg.

    Raises:
      ValueError: the env override is not an integer the chip accepts
        for this kernel (see :data:`TILE_QUANTUM`). A cached winner that
        the chip would refuse is ignored instead.
    """
    name = ENV_PREFIX + kernel.upper()
    env = os.environ.get(name, "").strip()
    if env:
        try:
            t = int(env)
        except ValueError:
            t = 0
        if not tile_ok(kernel, t):
            raise ValueError(
                f"{name}={env!r}: the {kernel} tile must be a positive "
                f"multiple of {TILE_QUANTUM.get(kernel, 8)}")
        return t
    if n is not None:
        w = _load_winners().get(_cache_key(kernel, pow2_bucket(n)))
        if w and tile_ok(kernel, w):
            return w
    return DEFAULT_TILES.get(kernel, 512)


# -- autotune sweep -----------------------------------------------------------

def sweep(kernel: str, bench, *, n: int, candidates=None,
          force: bool = False) -> dict:
    """Time ``bench(tile) -> wall_seconds`` over candidate tiles and persist
    the winner for this (kernel, platform, bucket of n).

    Never called implicitly from a serving path: table11 (or an explicit
    caller) owns the sweep. With a cached winner and ``force=False`` the
    sweep is skipped entirely — that is what makes the CI cache artifact
    worth persisting.

    Returns ``{"tile", "bucket", "cached", "walls"}`` where ``walls`` maps
    tile -> measured seconds (empty when the cache answered).
    """
    bucket = pow2_bucket(n)
    if not force:
        w = _load_winners().get(_cache_key(kernel, bucket))
        if w:
            return {"tile": w, "bucket": bucket, "cached": True, "walls": {}}
    cands = tuple(candidates or SWEEP_CANDIDATES.get(
        kernel, (256, 512, 1024, 2048)))
    bad = [t for t in cands if not tile_ok(kernel, int(t))]
    if bad:
        raise ValueError(f"{kernel}: the chip refuses tiles {bad} (not "
                         f"multiples of {TILE_QUANTUM.get(kernel, 8)})")
    walls = {int(t): float(bench(int(t))) for t in cands}
    best = min(walls, key=walls.get)
    record_winner(kernel, bucket, best)
    return {"tile": best, "bucket": bucket, "cached": False, "walls": walls}


# -- shared row-tiled pallas_call plumbing ------------------------------------

def _row_map(ndim: int):
    """Block index map that walks the leading axis and pins the rest."""
    if ndim == 1:
        return lambda i: (i,)
    if ndim == 2:
        return lambda i: (i, 0)
    return lambda i: (i,) + (0,) * (ndim - 1)


def tiled_rows(body, inputs, outs, *, tile: int, interpret: bool):
    """Run ``body`` over a 1-D grid of row tiles — the whole kernel family's
    launch shape in one place.

    Args:
      body: pallas kernel taking input refs then output refs in order.
      inputs: arrays sharing a leading axis N; each is zero-padded along
        axis 0 to a ``tile`` multiple (callers that need a non-zero pad
        value pad before calling, as batched_select does with its
        above-every-query sentinel).
      outs: list of ``(trailing_shape, dtype)`` per-row outputs (block
        ``(tile, *trailing)``, sliced back to N). Per-tile statistics are
        not kernel outputs: a ``(1,)`` block breaks the TPU's (8, 128)
        block rule, so callers reduce the row outputs in XLA instead.
      tile: static tile size from :func:`tile_for`.
      interpret: pallas interpret flag (resolved by the caller's dispatch).

    Returns the tuple of outputs.
    """
    n = inputs[0].shape[0]
    n_pad = round_up_tile(n, tile)
    if n_pad != n:
        inputs = [jnp.pad(a, ((0, n_pad - n),) + ((0, 0),) * (a.ndim - 1))
                  for a in inputs]
    in_specs = [pl.BlockSpec((tile,) + a.shape[1:], _row_map(a.ndim))
                for a in inputs]
    out_specs = [pl.BlockSpec((tile,) + tuple(t), _row_map(1 + len(t)))
                 for t, _d in outs]
    out_shape = [jax.ShapeDtypeStruct((n_pad,) + tuple(t), d)
                 for t, d in outs]
    res = pl.pallas_call(body, grid=(n_pad // tile,), in_specs=in_specs,
                         out_specs=out_specs, out_shape=out_shape,
                         interpret=interpret)(*inputs)
    return tuple(r[:n] for r in res)


# -- telemetry ----------------------------------------------------------------

def measured(kernel: str, *, nbytes: float, flops: float,
             padded_nbytes: float | None = None):
    """The kernel family's ``kerneltel.launch`` wrap: logical traffic model
    plus the padded bytes that actually cross HBM (bucket/tile slack)."""
    from repro.obs import kerneltel
    return kerneltel.launch(kernel, nbytes=nbytes, flops=flops,
                            padded_nbytes=padded_nbytes)


# -- persistent compile cache -------------------------------------------------

#: the compile cache's directory under the checkout root when
#: ``JAX_COMPILATION_CACHE_DIR`` does not name one
COMPILE_CACHE_DIR = ".jax_cache"


def use_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compile cache for an entry point; returns
    its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it
    is: JAX reads it itself and nothing else is set here. Otherwise the
    cache goes to the fixed ``<root>/.jax_cache`` — the path is part of
    every cache key, so a directory that moved from run to run would
    never hit. Entry points (``chip_smoke.py``, ``benchmarks/run.py``)
    call this; importing the library never does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    path = os.path.join(os.path.abspath(root), COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
