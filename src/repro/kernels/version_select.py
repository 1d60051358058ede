"""Single-query version selection: the cold path behind get_version /
get_increment.

GeStore materializes version T by selecting, for every row's cell chain, the
newest cell with ts <= T (paper §III.C). With the cell log in CSR order
(sorted by (row, ts)), timestamps are ascending inside each row segment, so
the per-row answer index is ``row_ptr[i] + count(ts_segment <= T) - 1`` and
the count is a difference of the GLOBAL inclusive cumsum of the 0/1 mask
(ts <= T) at segment boundaries. That cumsum is the batched scan kernel
(batched_select.py) asked one query.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .batched_select import batched_masked_cumsum


def masked_cumsum(ts: jax.Array, t_query, *,
                  interpret: bool | None = None) -> jax.Array:
    """ts: (C,) -> (C,) int32 inclusive cumsum of (ts <= t_query).
    interpret=None: kernel on TPU, jitted ref on CPU; True: force kernel."""
    tq = jnp.asarray(t_query, ts.dtype).reshape(1)
    return batched_masked_cumsum(ts, tq, interpret=interpret)[0]


def version_select(log_vals, log_ts, row_ptr, t_query, *, interpret: bool | None = None):
    """CSR segmented last-cell-with-ts<=T selection (see ref.ref_version_select)."""
    if log_ts.shape[0] == 0:  # empty log: nothing found anywhere
        n = row_ptr.shape[0] - 1
        return (jnp.zeros((n,) + log_vals.shape[1:], log_vals.dtype),
                jnp.zeros((n,), bool))
    cum = masked_cumsum(log_ts, t_query, interpret=interpret)
    cum0 = jnp.concatenate([jnp.zeros((1,), jnp.int32), cum])
    lo = row_ptr[:-1]
    hi = row_ptr[1:]
    cnt = cum0[hi] - cum0[lo]
    found = cnt > 0
    idx = jnp.clip(lo + cnt - 1, 0, max(log_ts.shape[0] - 1, 0))
    out = jnp.where(found[:, None], log_vals[idx], 0)
    return out, found
