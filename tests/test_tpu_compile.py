"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The CPU test run dispatches every kernel to its jnp reference (or runs
the body in Pallas interpret mode), which never shows a kernel to the
TPU compiler. These tests do: each compiles one kernel at the widths a
UniProtKB-class store uses (``interpret=False``) for a *described* v5e —
no chip is needed — and checks that the Pallas kernel is in the
compiled program (``tpu_custom_call``). A block shape or an operation
the chip refuses fails here, before any run on the chip.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time, and a
pytest worker that loaded it keeps it until it exits. Keep every such
compile in this one file, so one worker holds the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import (batched_select, compact_rewrite, delta_codec,
                           launch, shard_route)
from repro.kernels.fingerprint import fingerprint
from repro.kernels.version_select import masked_cumsum

#: fused-superlog cell bucket of a Swiss-Prot-sized store (~570k entries,
#: 4 fields + EXISTS, 3 releases)
C_FUSED = 1 << 22
#: one field's cell log / one segment save of such a store
N_ROWS = 605_000
#: UniProtParser lane widths: sequence 512 x int8, annotation 256 x int8,
#: length / taxid 1 x int32
UNIPROT_WIDTHS = [(512, jnp.int8), (256, jnp.int8), (1, jnp.int32)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("c,q", [(C_FUSED, 8), (N_ROWS, 1), (N_ROWS, 2)],
                         ids=["fused_q8", "cold_select_q1",
                              "changed_counts_q2"])
def test_scan_compiles(one_chip, c, q):
    """The superlog scan at its real bucket, and the one-query cold
    ``select_at`` / two-query ``changed_counts`` scans over one field's
    unbucketed log (padded to a tile inside the launch)."""
    _assert_kernel(batched_select._batched_masked_cumsum.lower(
        _arg((c,), jnp.int32, one_chip), _arg((q,), jnp.int32, one_chip),
        interpret=False, tile=launch.DEFAULT_TILES["batched_select"]))


def test_masked_cumsum_compiles(one_chip):
    """The single-query public entry of the cold ``select_at`` path."""
    _assert_kernel(jax.jit(
        lambda ts: masked_cumsum(ts, 3, interpret=False)).lower(
            _arg((N_ROWS,), jnp.int32, one_chip)))


def test_stacked_scan_compiles(one_chip):
    _assert_kernel(batched_select._stacked_masked_cumsum.lower(
        _arg((4, C_FUSED // 4), jnp.int32, one_chip),
        _arg((8,), jnp.int32, one_chip),
        interpret=False, tile=launch.DEFAULT_TILES["batched_select"]))


def test_mesh_boundary_select_compiles(topo):
    """The shard_map'd stacked scan over a 4-chip ("shard",) mesh, one
    shard's superlog per chip."""
    mesh = jax.sharding.Mesh(topo.devices[:4], ("shard",))
    rows = NamedSharding(mesh, P("shard", None))
    fn = batched_select._mesh_boundary_select(mesh, False)
    _assert_kernel(fn.lower(
        _arg((4, C_FUSED // 4), jnp.int32, rows),
        _arg((8,), jnp.int32, NamedSharding(mesh, P())),
        _arg((4, 1 << 20), jnp.int32, rows)))


@pytest.mark.parametrize("w", [512, 256, 1])
def test_fingerprint_compiles(one_chip, w):
    """Change detection over a release's int32 row lanes (one lane per
    int8 byte of sequence / annotation)."""
    _assert_kernel(fingerprint.lower(
        _arg((N_ROWS, w), jnp.int32, one_chip), interpret=False))


@pytest.mark.parametrize("op", ["pack", "unpack"])
@pytest.mark.parametrize("w,dtype", UNIPROT_WIDTHS,
                         ids=["sequence", "annotation", "length"])
def test_delta_codec_compiles(one_chip, op, w, dtype):
    """Segment save/load chain codec at a pow2 cell bucket."""
    fn = delta_codec._delta_pack if op == "pack" else delta_codec._delta_unpack
    n = launch.pow2_bucket(N_ROWS)
    _assert_kernel(fn.lower(
        _arg((n, w), dtype, one_chip), _arg((n, w), dtype, one_chip),
        interpret=False, tile=launch.DEFAULT_TILES["delta_codec"]))


@pytest.mark.parametrize("op", ["sub", "add", "xor"])
def test_wide_2lane_compiles(one_chip, op):
    """The two-lane 8-byte codec path (int64 / float64 fields)."""
    a = _arg((N_ROWS, 2), jnp.int32, one_chip)
    _assert_kernel(delta_codec._wide_2lane.lower(
        a, a, a, a, op=op, interpret=False,
        tile=launch.DEFAULT_TILES["delta_codec"]))


def test_shard_route_compiles(one_chip):
    """Key routing for a streamed release: ``P00000000``-style accessions
    are 9 bytes, three int32 lanes."""
    _assert_kernel(shard_route._shard_route.lower(
        _arg((20_000, 3), jnp.int32, one_chip),
        _arg((20_000,), jnp.int32, one_chip), 4,
        interpret=False, tile=launch.DEFAULT_TILES["shard_route"]))


def test_keep_mask_compiles(one_chip):
    """compact()'s horizon mask over one field's cell timestamps."""
    _assert_kernel(compact_rewrite._keep_mask.lower(
        _arg((N_ROWS,), jnp.int32, one_chip), cutoff=20, interpret=False,
        tile=launch.DEFAULT_TILES["compact_rewrite"]))
