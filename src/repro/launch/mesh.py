"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is pure
data parallelism whose gradient all-reduce is the only cross-pod collective
(ICI within a pod, DCN across pods).

Defined as functions, not module constants: importing this module never
touches jax device state (device count is locked at first jax init, and the
smoke tests must see 1 CPU device while the dry-run sees 512).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """A mesh whose axes GSPMD shards automatically. The model code places
    parameters by NamedSharding and activations by sharding constraints
    and lets the partitioner propagate the rest; ``jax.make_mesh``'s
    default of explicit axes would instead demand an output sharding for
    every ambiguous op (e.g. the gather from the vocab-sharded embedding
    table)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_shard_mesh(n_shards: int, devices=None):
    """1-D ("shard",) mesh over the first ``n_shards`` devices — the
    layout core/placement.py pins sharded-store superlogs across so the
    scatter-gather batched select runs one shard per device. Returns None
    when fewer than ``n_shards`` devices exist (the placement layer then
    falls back to serial or single-device stacked execution)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_shards < 1 or len(devs) < n_shards:
        return None
    return jax.make_mesh((n_shards,), ("shard",), devices=devs[:n_shards])


def make_test_mesh(devices: int | None = None):
    """Small mesh for CPU distributed tests (8 host devices -> (2, 4))."""
    n = devices or len(jax.devices())
    if n >= 8:
        return _auto_mesh((n // 4, 4), ("data", "model"))
    if n >= 2:
        return _auto_mesh((n // 2, 2), ("data", "model"))
    return _auto_mesh((1, 1), ("data", "model"))
