"""Pallas row-fingerprint kernel (change-detection hot spot, paper §III.A).

GeStore's update path compares every entry of a new meta-database release
against the stored head version. Byte-comparing 240 GB is memory-bound; we
instead hash each row's significant-field lanes to a 2x32-bit fingerprint and
compare fingerprints. The fold runs lane by lane (a ``fori_loop`` over the
W lanes) and is vectorized across rows: the launch lays the (N, W) lanes
out lane-major as (W, N/128, 128), so each loop step reads one dense
(8, 128) tile of 1024 rows' lane j, and the two running hashes stay in
vector registers whatever W is. A row-major block read column by column
would not: at the UniProt sequence width (512 lanes) it needs ~200 MB of
register spill space in VMEM, and the chip's compiler refuses it.

Roofline: reads N*W*4 bytes, writes N*8 bytes, does ~2*W int32 mul+xor per
row -> arithmetic intensity ~0.5 op/byte: bandwidth-bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from ._compat import cdiv, interpret_default

#: rows per grid step: one (8, 128) int32 tile per lane
TILE_N = 1024


def _fingerprint_kernel(lanes_ref, out_ref):
    """lanes_ref: (W, 8, 128) — lane j of 1024 rows per step;
    out_ref: (2, 8, 128) — the two hashes of those rows."""
    shape = lanes_ref.shape[1:]

    def fold(j, h):
        h1, h2 = h
        x = lanes_ref[j]
        return (h1 ^ x) * ref.FNV1_MUL, (h2 * ref.FNV2_MUL) ^ (x + (j + 1))

    h1, h2 = jax.lax.fori_loop(
        0, lanes_ref.shape[0], fold,
        (jnp.full(shape, ref.FNV1_INIT, jnp.int32),
         jnp.full(shape, ref.FNV2_INIT, jnp.int32)))
    h1 = h1 ^ (h2 << 13)
    h2 = h2 ^ (h1 >> 7)
    out_ref[0] = h1
    out_ref[1] = h2


@functools.partial(jax.jit, static_argnames=("interpret",))
def fingerprint(lanes: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """lanes: (N, W) int32 -> (N, 2) int32 row fingerprints.

    interpret=None: Pallas kernel on TPU, jitted ref oracle on CPU (interpret
    mode is for validation, not production CPU throughput).
    interpret=True: force the kernel body via the Pallas interpreter."""
    if interpret is None:
        if interpret_default():
            return ref.ref_fingerprint(lanes)
        interpret = False
    n, w = lanes.shape
    if n == 0:
        return jnp.zeros((0, 2), jnp.int32)
    n_pad = cdiv(n, TILE_N) * TILE_N
    # lane-major, rows packed into (8, 128) tiles: one fused XLA copy
    lt = jnp.pad(lanes, ((0, n_pad - n), (0, 0))).T.reshape(w, n_pad // 128,
                                                           128)
    rows = TILE_N // 128
    out = pl.pallas_call(
        _fingerprint_kernel,
        grid=(n_pad // TILE_N,),
        in_specs=[pl.BlockSpec((w, rows, 128), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((2, rows, 128), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, n_pad // 128, 128), jnp.int32),
        interpret=interpret,
    )(lt)
    return out.reshape(2, n_pad).T[:n]
