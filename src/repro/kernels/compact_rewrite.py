"""Device-side ``compact()`` segment rewrite (core/store.py's horizon fold).

Compaction collapses every row's cell history at or below a horizon into
one base cell and splices the surviving tail back in (row, ts) order. The
math used to live entirely in host numpy; the heavy parts — the horizon
keep-mask over the cell timestamps and the (C, W) value-byte rewrite into
the new CSR order — now run on device through the shared launch helper
(kernels/launch.py), under the ``compact_rewrite`` tile name:

  * a row-tiled Pallas kernel computes the ``ts > horizon`` keep mask
    (bandwidth-bound, same launch family as shard_route);
  * ONE fused device gather permutes base + surviving cell values into
    the final lexsorted order (the host only handles the small int32
    index vectors: chain heads, lexsort keys, CSR pointer rebuild).

Dispatch matches the rest of the family: device path on TPU, numpy
reference (:func:`ref_compact_rewrite` — the exact pre-device code) on the
CPU backend, ``interpret=True`` forcing the device path through the Pallas
interpreter for byte-equivalence tests. 8-byte value dtypes always take
the host path (a 32-bit jax gather would silently downcast them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import launch
from ._compat import interpret_default


def _keep_mask_kernel(ts_ref, keep_ref, *, cutoff: int):
    keep_ref[:] = (ts_ref[:] > cutoff).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cutoff", "interpret", "tile"))
def _keep_mask(ts32, *, cutoff, interpret, tile):
    (keep,) = launch.tiled_rows(
        functools.partial(_keep_mask_kernel, cutoff=cutoff),
        [ts32], [((), jnp.int32)], tile=tile, interpret=interpret)
    return keep


def ref_compact_rewrite(vals, tss, ptr, base_vals, base_found, before_ts,
                        n_rows):
    """Host oracle: the exact numpy rewrite ``compact()`` always did."""
    keep = tss > before_ts
    rows_all = np.repeat(np.arange(n_rows, dtype=np.int32), np.diff(ptr))
    base_rows = np.nonzero(base_found)[0].astype(np.int32)
    new_rows = np.concatenate([base_rows, rows_all[keep]])
    new_tss = np.concatenate([
        np.full(len(base_rows), before_ts, np.int64), tss[keep]])
    new_vals = np.concatenate([base_vals[base_found], vals[keep]])
    order = np.lexsort((new_tss, new_rows))
    nptr = np.zeros(n_rows + 1, np.int32)
    np.add.at(nptr, new_rows + 1, 1)
    return (new_vals[order], new_tss[order], new_rows[order],
            np.cumsum(nptr).astype(np.int32))


def compact_rewrite(vals, tss, ptr, base_vals, base_found, before_ts,
                    n_rows, *, interpret: bool | None = None,
                    tile: int | None = None):
    """Rewrite one cell log for a compaction at horizon ``before_ts``.

    Args:
      vals: (C, W) cell values sorted by (row, ts).
      tss: (C,) int64 cell timestamps (same order).
      ptr: (n_rows+1,) CSR row pointers.
      base_vals / base_found: ``select_at(n_rows, before_ts)`` output —
        the per-row folded base value at the horizon.
      before_ts: compaction horizon (inclusive).
      n_rows: row count.

    Returns:
      (new_vals, new_tss int64, new_rows int32, new_ptr int32) — the
      compacted log in (row, ts) order, byte-identical across dispatch
      paths (pinned by the equivalence tests).
    """
    c = len(tss)
    if ((interpret is None and interpret_default())
            or vals.dtype.itemsize == 8 or c == 0):
        return ref_compact_rewrite(vals, tss, ptr, base_vals, base_found,
                                   before_ts, n_rows)
    return _device_rewrite(vals, tss, ptr, base_vals, base_found,
                           before_ts, n_rows, interpret=bool(interpret),
                           tile=launch.tile_for("compact_rewrite", n=c))


def _device_rewrite(vals, tss, ptr, base_vals, base_found, before_ts,
                    n_rows, *, interpret, tile):
    # stored device timestamps are int32 by convention (core/store.py
    # clamps queries below TS_MAX), so the mask kernel compares in int32
    cutoff = int(min(max(int(before_ts), -(2**31) + 1), 2**31 - 2))
    keep_dev = _keep_mask(jnp.asarray(tss.astype(np.int32)),
                                 cutoff=cutoff, interpret=interpret,
                                 tile=tile)
    keep = np.asarray(keep_dev).astype(bool)
    keep_idx = np.nonzero(keep)[0].astype(np.int32)
    rows_all = np.repeat(np.arange(n_rows, dtype=np.int32), np.diff(ptr))
    base_rows = np.nonzero(base_found)[0].astype(np.int32)
    new_rows = np.concatenate([base_rows, rows_all[keep_idx]])
    new_tss = np.concatenate([
        np.full(len(base_rows), before_ts, np.int64), tss[keep_idx]])
    order = np.lexsort((new_tss, new_rows))
    # the value bytes (the heavy part) move in ONE fused device gather:
    # output position -> source row in concat(full base table, old cells)
    cat_pos = np.concatenate([base_rows, n_rows + keep_idx])
    src = jnp.asarray(cat_pos[order].astype(np.int32))
    cat = jnp.concatenate([jnp.asarray(base_vals), jnp.asarray(vals)],
                          axis=0)
    new_vals = np.asarray(jnp.take(cat, src, axis=0))
    nptr = np.zeros(n_rows + 1, np.int32)
    np.add.at(nptr, new_rows + 1, 1)
    return (new_vals, new_tss[order], new_rows[order],
            np.cumsum(nptr).astype(np.int32))
