"""Batched Pallas masked-cumsum: one launch materializes MANY versions.

Materializing version T selects, per row, the newest cell with ts <= T;
with the cell log in CSR order that is a difference of the running count
of ``ts <= T`` at segment boundaries (version_select.py has the
derivation). Production platforms re-run analyses against many pinned
versions concurrently (the paper's §III.C workload; OrpheusDB's
multi-version checkout), so one launch computes that running count for a
*vector* of Q query timestamps over S stacked cell logs: grid
``(shard, ts_tile)``, each step reading one ts tile once and writing the
whole ``(Q, tile)`` block of counts. The Q query timestamps ride in SMEM
(scalar prefetch), and each shard's count carries across its tiles in a
VMEM scratch, so the kernel writes final counts with no second pass.

Inside a tile the prefix sum is a log-step shift-and-add over the lane
axis (``pltpu.roll``): integer adds, exact by construction, and a
primitive the TPU lowering has (it has no ``cumsum``).

The ts tile is no longer hardcoded: ``launch.tile_for("batched_select")``
resolves it (env override > autotuned winner > default), and callers are
expected to pre-pad the cell axis to a :func:`scan_bucket` power-of-two
bucket so a continuously growing superlog reuses a handful of compiled
executables instead of retracing per ingest (core/store.py does this for
the fused superlog; padding *inside* the jit boundary cannot help, the
trace has already happened by then).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import launch, ref
from ._compat import interpret_default


def scan_bucket(n: int) -> int:
    """Power-of-two cell bucket for the fused scan, floored at the launch
    tile so the bucketed length is always a whole number of tiles."""
    return launch.pow2_bucket(n, floor=tile())


def tile() -> int:
    """The resolved scan tile (env > autotune cache > default)."""
    return launch.tile_for("batched_select")


def _lane_cumsum(m, lane):
    """Inclusive prefix sum of ``m`` along its lane axis: log2(width)
    steps, each adding the copy rolled ``s`` lanes up, masked below lane
    ``s`` so nothing wraps around."""
    s = 1
    while s < m.shape[1]:
        m = m + jnp.where(lane >= s, pltpu.roll(m, s, 1), 0)
        s *= 2
    return m


def _scan_kernel(tq_ref, ts_ref, cum_ref, carry_ref):
    """One (shard, ts tile) step: for every query q, the count of cells
    with ts <= tq[q] up to each position of the shard's log. ``carry_ref``
    holds each query's count through the shard's previous tiles."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    ts = ts_ref[...]                                   # (1, tile)
    lane = jax.lax.broadcasted_iota(jnp.int32, ts.shape, 1)
    for q in range(cum_ref.shape[0]):
        c = (_lane_cumsum((ts <= tq_ref[q]).astype(jnp.int32), lane)
             + carry_ref[q:q + 1, :1])
        cum_ref[q:q + 1, :] = c
        carry_ref[q:q + 1, :] = jnp.broadcast_to(c[:, -1:],
                                                 (1, carry_ref.shape[1]))


def _scan(ts_stack, t_queries, *, interpret: bool, tile: int):
    """(S, C) ts rows, (Q,) queries -> (S, Q, C) inclusive counts of
    ts <= t_q (traced inside the jitted wrappers below). Pads the cell
    axis to a tile multiple; the pad value is irrelevant because counts
    only carry forward and the padded columns are sliced off."""
    s, c = ts_stack.shape
    (q,) = t_queries.shape
    if s == 0 or c == 0 or q == 0:
        return jnp.zeros((s, q, c), jnp.int32)
    c_pad = launch.round_up_tile(c, tile)
    if c_pad != c:
        pad = jnp.full((s, c_pad - c), jnp.iinfo(ts_stack.dtype).max,
                       ts_stack.dtype)
        ts_stack = jnp.concatenate([ts_stack, pad], axis=1)
    out = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, c_pad // tile),
            in_specs=[pl.BlockSpec((None, 1, tile),
                                   lambda k, i, tq: (k, 0, i))],
            out_specs=pl.BlockSpec((None, q, tile),
                                   lambda k, i, tq: (k, 0, i)),
            scratch_shapes=[pltpu.VMEM((q, 128), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((s, q, c_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(t_queries.astype(jnp.int32), ts_stack[:, None, :])
    return out[:, :, :c]


def batched_masked_cumsum(ts: jax.Array, t_queries: jax.Array, *,
                          interpret: bool | None = None,
                          tile: int | None = None) -> jax.Array:
    """ts: (C,); t_queries: (Q,) -> (Q, C) int32 inclusive cumsum of
    (ts <= t_q) per query. interpret=None: kernel on TPU, jitted ref on CPU;
    True: force kernel (interpret mode off-TPU). ``tile`` overrides the
    resolved launch tile (static; autotune sweeps pass it explicitly)."""
    if tile is None:
        tile = launch.tile_for("batched_select", n=ts.shape[0])
    return _batched_masked_cumsum(ts, t_queries, interpret=interpret,
                                  tile=int(tile))


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _batched_masked_cumsum(ts, t_queries, *, interpret, tile):
    t_queries = jnp.asarray(t_queries, dtype=ts.dtype)
    if interpret is None:
        if interpret_default():
            return ref.ref_batched_masked_cumsum(ts, t_queries)
        interpret = False
    return _scan(ts[None], t_queries, interpret=interpret, tile=tile)[0]


def stacked_masked_cumsum(ts_stack: jax.Array, t_queries: jax.Array, *,
                          interpret: bool | None = None,
                          tile: int | None = None) -> jax.Array:
    """ts_stack: (S, C); t_queries: (Q,) -> (S, Q, C) int32 inclusive
    cumsum of (ts <= t_q) per (shard, query) — the batched kernel with one
    extra grid axis over shards, so S independent fused superlogs scan in
    ONE launch. Pad rows (and ragged tails) with a value above every
    query (int32 max > TS_MAX); padded cells never count."""
    if tile is None:
        tile = launch.tile_for("batched_select", n=ts_stack.shape[-1])
    return _stacked_masked_cumsum(ts_stack, t_queries, interpret=interpret,
                                  tile=int(tile))


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _stacked_masked_cumsum(ts_stack, t_queries, *, interpret, tile):
    t_queries = jnp.asarray(t_queries, dtype=ts_stack.dtype)
    if interpret is None:
        if interpret_default():
            return ref.ref_stacked_masked_cumsum(ts_stack, t_queries)
        interpret = False
    return _scan(ts_stack, t_queries, interpret=interpret, tile=tile)


def scan_cache_size() -> int:
    """Number of compiled entries behind the jitted scan wrappers — the
    recompile-stability regression tests probe this to prove epoch rolls
    under continuous ingest stay bounded by the shape-bucket count."""
    n = 0
    for fn in (_batched_masked_cumsum, _stacked_masked_cumsum):
        try:
            n += int(fn._cache_size())
        except (AttributeError, TypeError):  # older/newer jax internals
            return -1
    return n


def _boundary_take(cum: jax.Array, boundaries: jax.Array) -> jax.Array:
    """Sample an (S, Q, C) stacked cumsum at per-shard CSR boundaries
    (S, B) -> (S, Q, B): entry 0 prepended so boundary 0 reads count 0."""
    s, q, _ = cum.shape
    cum0 = jnp.concatenate([jnp.zeros((s, q, 1), jnp.int32), cum], axis=2)
    idx = jnp.broadcast_to(boundaries[:, None, :].astype(jnp.int32),
                           (s, q, boundaries.shape[1]))
    return jnp.take_along_axis(cum0, idx, axis=2)


def stacked_boundary_select(ts_stack, t_queries, boundaries, *, mesh=None,
                            interpret: bool | None = None):
    """Device-parallel batched-select over S stacked fused superlogs.

    ts_stack: (S, Cmax) int32 fused per-shard ts rows padded with int32
    max; t_queries: (Q,) clamped query timestamps; boundaries: (S, Bmax)
    int32 per-shard CSR boundary positions (zero-padded). Returns the
    (S, Q, Bmax) boundary cumsums — the per-shard _SuperLog.boundary_cums
    numbers for every shard from ONE launch.

    With ``mesh`` (a 1-D ("shard",) mesh of exactly S devices) the scan
    runs under shard_map, one shard per device, and the caller should have
    device_put the stacked operands with NamedSharding(mesh, P("shard",
    None)) so no resharding happens on the hot path. Without a mesh the
    same stacked computation runs on whatever device holds the operands —
    still one launch instead of S, byte-identical either way.
    """
    if mesh is None:
        cum = stacked_masked_cumsum(ts_stack, t_queries, interpret=interpret)
        return _boundary_take(cum, jnp.asarray(boundaries))
    return _mesh_boundary_select(mesh, interpret)(
        ts_stack, t_queries, boundaries)


@functools.lru_cache(maxsize=8)
def _mesh_boundary_select(mesh, interpret: bool | None):
    """Compiled shard_map'd boundary select for one mesh, cached so the
    serving hot path never retraces (jit keyed per operand shape)."""
    from jax.sharding import PartitionSpec as P

    def body(ts, qs, bnd):
        cum = stacked_masked_cumsum(ts, qs, interpret=interpret)
        return _boundary_take(cum, bnd)

    # check_vma=False: the Pallas scan's out_shape carries no varying-axes
    # annotation; every shard's output is its own (in/out specs say so)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("shard", None), P(), P("shard", None)),
        out_specs=P("shard", None, None), check_vma=False))


def batched_version_select(log_vals, log_ts, row_ptr, t_queries, *,
                           interpret: bool | None = None):
    """Segmented last-cell-with-ts<=T selection for Q query timestamps.

    log_vals: (C, W); log_ts: (C,) ascending within each row segment;
    row_ptr: (N+1,); t_queries: (Q,). Returns (out (Q, N, W), found (Q, N)).
    One batched scan replaces Q independent ``version_select`` launches.
    """
    t_queries = jnp.asarray(t_queries)
    (q,) = t_queries.shape
    n = row_ptr.shape[0] - 1
    if log_ts.shape[0] == 0:  # empty log: nothing found anywhere
        return (jnp.zeros((q, n) + log_vals.shape[1:], log_vals.dtype),
                jnp.zeros((q, n), bool))
    cum = batched_masked_cumsum(log_ts, t_queries, interpret=interpret)
    cum0 = jnp.concatenate([jnp.zeros((q, 1), jnp.int32), cum], axis=1)
    lo = row_ptr[:-1]
    hi = row_ptr[1:]
    cnt = cum0[:, hi] - cum0[:, lo]
    found = cnt > 0
    idx = jnp.clip(lo[None, :] + cnt - 1, 0, max(log_ts.shape[0] - 1, 0))
    out = jnp.where(found[..., None], log_vals[idx], 0)
    return out, found
