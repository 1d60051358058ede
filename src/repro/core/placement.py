"""Shard->device placement: device-parallel scatter-gather execution.

The paper's scalability story is many HBase region servers answering in
parallel (§II.B/§V); ``ShardedStore`` (core/shard.py) reproduces the
partitioning, and this module supplies the parallelism. Each shard's fused
superlog is pinned to its own JAX device over a 1-D ``("shard",)`` mesh
(launch/mesh.py), and the per-shard batched-select scans collapse into ONE
``shard_map``-style launch over a cross-shard stacked copy of the fused ts
arrays (kernels/batched_select.stacked_boundary_select) — so batched
``get_versions``/``get_increments`` throughput grows with shard count
instead of paying the serial per-shard Python loop.

Execution modes, planned by :func:`plan_placement`:

  * ``mesh`` — ``len(jax.devices()) >= n_shards``: one shard per device,
    stacked operands laid out with ``NamedSharding(mesh, P("shard",
    None))`` so the scan partitions with zero communication. Value
    materialization then pays ONE fused cross-shard gather per field
    (``start_cells``) instead of one per (shard, field).
  * ``stacked`` — fewer devices than shards but parallelism forced
    (``GESTORE_PARALLEL=1`` or an explicit plan): the same single stacked
    launch and fused gathers on one device. Still amortizes per-shard
    launch overhead; no cross-device parallelism.
  * ``serial`` — the PR-3 behavior (per-shard ``get_versions`` loop).
    This is the graceful fallback whenever the host has fewer devices
    than shards, and the explicit opt-out (``GESTORE_PARALLEL=0``).

Every mode returns byte-identical results: the stacked scan computes the
exact per-shard boundary cumsums the serial path does (pinned by the
equivalence suite across device counts), so the choice is pure placement
and composes with the ``log_epoch`` plan-cache contract unchanged — equal
facade epoch still implies identical bytes no matter which mode answered.

Residency-awareness: a :class:`PlacedSuperLog` is built from whatever
shards are resident (the facade forces residency first, exactly like the
serial path) and is keyed on the tuple of shard epochs. ``TieredStorePool``
shard-by-shard eviction composes cleanly: a spill freezes the shard's
epoch, the lazy reload floors back to it, and an unchanged epoch tuple
means the cached stacked copy is still byte-valid — no restack after a
spill/reload cycle. The facade's ``drop_superlog``/``nbytes`` account for
the stacked device buffers so the device->host eviction tier reclaims them.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import launch as klaunch
from repro.kernels.batched_select import scan_bucket, stacked_boundary_select
from repro.launch.mesh import make_shard_mesh

from .store import (_SuperLog, _clamp_ts, _gather_collect,
                    _gather_start)

#: env override: "0"/"off"/"serial" forces serial, "1"/"on"/"parallel"
#: forces the stacked launch even with fewer devices than shards.
PARALLEL_ENV = "GESTORE_PARALLEL"

_FORCE_ON = ("1", "on", "parallel", "stacked", "force")
_FORCE_OFF = ("0", "off", "serial")


@dataclasses.dataclass(frozen=True)
class ShardPlacement:
    """One shard->device execution plan (see module docstring for modes)."""
    mode: str                 # "mesh" | "stacked" | "serial"
    devices: tuple = ()       # shard id -> device (mesh mode only)
    mesh: object = None       # 1-D ("shard",) mesh (mesh mode only)

    @property
    def parallel(self) -> bool:
        return self.mode != "serial"

    def device_for(self, shard: int):
        """Pinned device of ``shard``, or None (default device)."""
        return self.devices[shard] if shard < len(self.devices) else None


def plan_placement(n_shards: int, *, devices=None,
                   force: str | None = None) -> ShardPlacement:
    """Plan shard->device placement for an ``n_shards``-way store.

    Args:
      n_shards: shard count of the facade.
      devices: explicit device list (default: ``jax.devices()``).
      force: override the auto decision — any of ``_FORCE_ON`` forces the
        stacked/mesh parallel path, ``_FORCE_OFF`` forces serial; None
        reads the ``GESTORE_PARALLEL`` env var, then auto-plans: mesh when
        the host has at least one device per shard, else serial (the
        graceful fallback the serving tier relies on).
    """
    if force is None:
        force = os.environ.get(PARALLEL_ENV)
    if force is not None:
        force = str(force).strip().lower() or None
    devs = list(devices) if devices is not None else jax.devices()
    if n_shards < 2 or force in _FORCE_OFF:
        return ShardPlacement("serial")
    if len(devs) >= n_shards:
        mesh = make_shard_mesh(n_shards, devs)
        if mesh is not None:
            return ShardPlacement("mesh", tuple(devs[:n_shards]), mesh)
    if force in _FORCE_ON:
        return ShardPlacement("stacked")
    return ShardPlacement("serial")


class PlacedSuperLog:
    """Cross-shard stacked fused-superlog state for one facade epoch.

    Holds (S, Cmax) stacked per-shard fused ts rows (padded with int32
    max, which no clamped query timestamp can reach) and (S, Bmax) stacked
    CSR boundary positions (zero-padded; boundary 0 reads count 0), laid
    out across the shard mesh in ``mesh`` mode. ``boundary_cums`` then
    answers every shard's ``_SuperLog.boundary_cums`` in ONE launch.

    Immutable once built; the facade caches one instance keyed on
    ``epochs`` (the per-shard ``log_epoch`` tuple) and rebuilds whenever
    any shard's epoch moves — the same invalidation contract as the
    per-store superlog, so plan-cache semantics are unchanged.
    """

    def __init__(self, superlogs, placement: ShardPlacement):
        self.epochs = tuple(sl.epoch for sl in superlogs)
        self.mesh = placement.mesh if placement.mode == "mesh" else None
        self.b_widths = [len(sl.boundaries) for sl in superlogs]
        self.n_cells = sum(sl.n_cells for sl in superlogs)
        # per-field fused cross-shard value arrays, uploaded lazily on the
        # first gather of that field (name -> (dev, offs, total, w, dtype));
        # content validity follows from the epoch contract, so rebuild-time
        # callers pass their CURRENT superlog list and never retain ours
        self._fused: dict[str, tuple] = {}
        s = len(superlogs)
        # bucket the stacked cell/boundary axes to powers of two (same
        # trick as the per-store superlog): mid-run epoch rolls under
        # continuous ingest then reuse the compiled stacked scan instead
        # of retracing every time any shard's cell count moves
        cmax = scan_bucket(max((sl.n_cells for sl in superlogs), default=0))
        bmax = klaunch.pow2_bucket(max(self.b_widths, default=0), floor=8)
        ts = np.full((s, cmax), np.iinfo(np.int32).max, np.int32)
        bnd = np.zeros((s, bmax), np.int32)
        for i, sl in enumerate(superlogs):
            if sl.ts_host is not None:
                ts[i, : sl.n_cells] = sl.ts_host
            bnd[i, : self.b_widths[i]] = sl.boundaries.astype(np.int32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            sharding = NamedSharding(self.mesh, P("shard", None))
            self._ts = jax.device_put(ts, sharding)
            self._bnd = jax.device_put(bnd, sharding)
        else:
            self._ts = jnp.asarray(ts)
            self._bnd = jnp.asarray(bnd)

    def boundary_cums(self, ts_list) -> list[np.ndarray]:
        """Per-shard (Q, B_s) boundary cumsums for ``ts_list`` — the exact
        numbers each shard's ``_SuperLog.boundary_cums`` would return,
        from one device-parallel stacked launch."""
        qs = np.asarray([_clamp_ts(t) for t in ts_list], np.int32)
        if self.n_cells == 0 or not len(qs):
            return [np.zeros((len(qs), w), np.int32) for w in self.b_widths]
        q = len(qs)
        # bucket the query axis too (repeat the last query; extra columns
        # are sliced off) so wave-width churn cannot retrace the scan
        q_pad = klaunch.pow2_bucket(q, floor=8)
        qs_in = qs if q_pad == q else np.concatenate(
            [qs, np.full(q_pad - q, qs[-1], np.int32)])
        out = np.asarray(stacked_boundary_select(
            self._ts, jnp.asarray(qs_in), self._bnd, mesh=self.mesh))
        return [out[i, :q, : w] for i, w in enumerate(self.b_widths)]

    # -- fused cross-shard value gathers --------------------------------------
    def _fused_field(self, name: str, superlogs) -> tuple:
        """Cross-shard concatenation of one field's cell values: a single
        device array with per-shard cell offsets, so a materialization wave
        pays ONE ``take`` per field instead of one per (shard, field). The
        host copies come from the caller's current superlogs (equal epochs
        imply identical cells, so the cached upload stays byte-valid across
        spill/reload); only the device buffer and offsets are cached."""
        ent = self._fused.get(name)
        if ent is None:
            f0 = superlogs[0].fields[name]
            parts, offs, off = [], [], 0
            for sl in superlogs:
                f = sl.fields[name]
                offs.append(off)
                if f.vals_host is not None:
                    parts.append(f.vals_host)
                off += f.n_cells
            dev = None
            if off:
                dev = jnp.asarray(parts[0] if len(parts) == 1
                                  else np.concatenate(parts))
            ent = (dev, offs, off, f0.width, f0.dtype)
            self._fused[name] = ent
        return ent

    def field_offsets(self, name: str, superlogs) -> list[int]:
        """Per-shard cell offset of ``name`` in the fused value array."""
        return self._fused_field(name, superlogs)[1]

    def start_cells(self, name: str, idx: np.ndarray, keep: np.ndarray,
                    superlogs) -> tuple:
        """Launch one fused device gather for a whole wave and start its
        copy to the host, without waiting for either: ``idx`` holds global
        cell positions (already permuted into every query's final merged
        row order, queries back to back) and ``keep`` masks rows whose
        value must be zeroed (no cell at the query time / deleted rows) —
        the semantics of ``_SuperLog.gather_dispatch``. Returns the handle
        ``collect_cells`` takes."""
        dev, _offs, total, width, dtype = self._fused_field(name, superlogs)
        return _gather_start(
            dev, np.where(keep, np.clip(idx, 0, total - 1), -1), dtype, width)

    @staticmethod
    def collect_cells(handle: tuple, lens) -> list[np.ndarray]:
        """The host rows of a ``start_cells``, split per query (``lens``
        rows each): read-only views of one host copy."""
        out = _gather_collect(handle)
        cum = np.cumsum([0] + list(lens))
        return [out[cum[i]: cum[i + 1]] for i in range(len(lens))]

    def exists_matrices(self, bcums, superlogs) -> list[tuple]:
        """Per-shard ``(alive, ever)`` — ``_SuperLog.exists_matrix`` for
        every shard from ONE fused EXISTS gather instead of S launches."""
        name = _SuperLog.EXISTS
        dev, offs, total, _w, _d = self._fused_field(name, superlogs)
        cnts, evers, idxs = [], [], []
        for s, sl in enumerate(superlogs):
            f = sl.fields[name]
            cnt = sl.counts(name, bcums[s])
            cnts.append(cnt)
            evers.append(cnt > 0)
            idxs.append(offs[s] + np.clip(f.ptr[None, :-1] + cnt - 1, 0,
                                          max(f.n_cells - 1, 0)))
        if dev is None:
            return [(np.zeros_like(e), e) for e in evers]
        idx = np.clip(np.concatenate(idxs, axis=1), 0, total - 1)
        v = np.asarray(jnp.take(dev[:, 0], jnp.asarray(idx), axis=0))
        out, col = [], 0
        for ever in evers:
            n = ever.shape[1]
            out.append((((v[:, col: col + n] > 0) & ever), ever))
            col += n
        return out

    def nbytes(self) -> int:
        """Device bytes held by the stacked scan operands plus the fused
        per-field value uploads (facade accounting)."""
        n = int(self._ts.nbytes + self._bnd.nbytes)
        for dev, *_ in self._fused.values():
            if dev is not None:
                n += int(dev.nbytes)
        return n
