"""Pallas delta-codec kernels (versioned-cell storage compression, §III.B).

GeStore stores a set of database versions with delta compression (HBase
timestamped cells + Snappy). Our on-disk cell segments store, for each
updated row, the delta against the row's previous value: arithmetic
difference for integer fields and bitwise XOR for float fields (unchanged
exponent/mantissa bytes zero out, which downstream byte-level entropy coding
exploits). Both directions are single-pass streaming VPU kernels; pack
also returns the max |delta| (the nonzero count for XOR lanes), reduced
in XLA over the kernel's output, so the host can narrow int32 deltas to
int16/int8 segments. Integer lanes are widened to int32 inside the
kernels: the TPU's vector unit has no 8- or 16-bit integer add or
subtract, and truncating the int32 result back is exactly the stored
dtype's wraparound.

On-disk chain format (used by ``core/segments.py`` segment files): cells
are sorted by (row, ts); within each row's run ("chain") the first cell is
packed against zero (i.e. stored raw) and every later cell against its
predecessor. Chains never cross a segment boundary, so every segment file
is self-contained and can be decoded without any other segment — the
property that makes lazy, per-timestamp-range loading possible.
``chain_pack`` / ``chain_unpack`` are the host-facing wrappers around the
``delta_pack`` / ``delta_unpack`` kernels implementing that format.

8-byte dtypes (int64/float64) cannot ride through the 32-bit jax kernels
directly — with x64 disabled ``jnp.asarray`` silently downcasts them — so
they take a *two-lane* device path: each 8-byte value is split host-side
into little-endian (lo, hi) int32 lanes and ``delta_pack_wide`` /
``delta_unpack_wide`` do exact 64-bit modular subtract/add with an
explicit borrow/carry lane (unsigned compares via the int32 sign-flip
trick). On the CPU backend the host numpy fallback remains the dispatch
default, exactly like every other kernel in the family.

``chain_decode`` is the device-side inverse of the chain format: a
segmented (head-flagged) associative scan that reconstructs cell values
from deltas *on device*, so the fused superlog can stay delta-packed in
HBM and decode inside the gather path (core/store.py) instead of
uploading fully decoded cells.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import launch, ref
from ._compat import interpret_default

# sign-bit flip constant for unsigned int32 compares; kept a Python int so
# Pallas kernels don't capture a traced array constant
_I32_SIGN = -(2**31)


def _pack_int_kernel(new_ref, old_ref, delta_ref):
    d = new_ref[...].astype(jnp.int32) - old_ref[...].astype(jnp.int32)
    delta_ref[...] = d.astype(delta_ref.dtype)


def _unpack_int_kernel(delta_ref, old_ref, new_ref):
    v = delta_ref[...].astype(jnp.int32) + old_ref[...].astype(jnp.int32)
    new_ref[...] = v.astype(new_ref.dtype)


def _xor_kernel(a_ref, b_ref, out_ref):
    """Float lanes: XOR packs and unpacks alike (it is its own inverse)."""
    out_ref[...] = a_ref[...] ^ b_ref[...]


def _run_2d(kernel, a, b, *, interpret, tile):
    """The codec family's launch shape, via the shared helper: two (N, W)
    inputs and an (N, W) output of the same lane dtype."""
    (out,) = launch.tiled_rows(kernel, [a, b], [(a.shape[1:], a.dtype)],
                               tile=tile, interpret=interpret)
    return out


def _as_int_lanes(x: jax.Array) -> tuple[jax.Array, jnp.dtype]:
    if jnp.issubdtype(x.dtype, jnp.floating):
        ib = {4: jnp.int32, 2: jnp.int16}[x.dtype.itemsize]
        return x.view(ib), ib
    return x, x.dtype


def delta_pack(new: jax.Array, old: jax.Array, *,
               interpret: bool | None = None, tile: int | None = None):
    """Pack (new, old) -> (delta, stat). Floats: XOR lanes + nonzero count;
    ints: arithmetic delta + max|delta| (for narrowing); ``stat`` is (1,).
    interpret=None: kernel on TPU, jitted ref on CPU; True: force kernel."""
    if tile is None:
        tile = launch.tile_for("delta_codec", n=new.shape[0])
    return _delta_pack(new, old, interpret=interpret, tile=int(tile))


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _delta_pack(new, old, *, interpret, tile):
    is_float = jnp.issubdtype(new.dtype, jnp.floating)
    if interpret is None and interpret_default():
        delta = ref.ref_delta_pack(new, old)
    else:
        a, _ = _as_int_lanes(new)
        b, _ = _as_int_lanes(old)
        kernel = _xor_kernel if is_float else _pack_int_kernel
        delta = _run_2d(kernel, a, b, interpret=bool(interpret), tile=tile)
        if is_float:
            delta = delta.view(new.dtype)
    di, _ = _as_int_lanes(delta)
    # widen before |.|: abs(int8 -128) overflows in the stored dtype
    stat = (jnp.sum((di != 0).astype(jnp.int32))[None] if is_float
            else jnp.max(jnp.abs(di.astype(jnp.int32)))[None])
    return delta, stat


def delta_unpack(delta: jax.Array, old: jax.Array, *,
                 interpret: bool | None = None, tile: int | None = None):
    if tile is None:
        tile = launch.tile_for("delta_codec", n=delta.shape[0])
    return _delta_unpack(delta, old, interpret=interpret, tile=int(tile))


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _delta_unpack(delta, old, *, interpret, tile):
    if interpret is None:
        if interpret_default():
            return ref.ref_delta_unpack(delta, old)
        interpret = False
    is_float = jnp.issubdtype(delta.dtype, jnp.floating)
    a, _ = _as_int_lanes(delta)
    b, _ = _as_int_lanes(old)
    kernel = _xor_kernel if is_float else _unpack_int_kernel
    new = _run_2d(kernel, a, b, interpret=interpret, tile=tile)
    if is_float:
        new = new.view(delta.dtype)
    return new


# -- two-lane 8-byte device path ----------------------------------------------

def _pack_wide_kernel(alo_ref, ahi_ref, blo_ref, bhi_ref,
                      dlo_ref, dhi_ref):
    """64-bit modular subtract on (lo, hi) int32 lanes: lo borrows into hi
    when unsigned a_lo < b_lo (sign-flip trick — int32 has no uint compare)."""
    alo, ahi = alo_ref[:, :], ahi_ref[:, :]
    blo, bhi = blo_ref[:, :], bhi_ref[:, :]
    borrow = ((alo ^ _I32_SIGN) < (blo ^ _I32_SIGN)).astype(jnp.int32)
    dlo_ref[:, :] = alo - blo
    dhi_ref[:, :] = ahi - bhi - borrow


def _unpack_wide_kernel(dlo_ref, dhi_ref, olo_ref, ohi_ref,
                        nlo_ref, nhi_ref):
    """64-bit modular add on (lo, hi) lanes: the lo sum wrapped (unsigned
    sum < either addend) iff a carry must propagate into hi."""
    dlo, dhi = dlo_ref[:, :], dhi_ref[:, :]
    olo, ohi = olo_ref[:, :], ohi_ref[:, :]
    lo = dlo + olo
    carry = ((lo ^ _I32_SIGN) < (dlo ^ _I32_SIGN)).astype(jnp.int32)
    nlo_ref[:, :] = lo
    nhi_ref[:, :] = dhi + ohi + carry


def _xor_wide_kernel(alo_ref, ahi_ref, blo_ref, bhi_ref,
                     dlo_ref, dhi_ref):
    """float64 XOR decomposes lane-wise — same kernel packs and unpacks."""
    dlo_ref[:, :] = alo_ref[:, :] ^ blo_ref[:, :]
    dhi_ref[:, :] = ahi_ref[:, :] ^ bhi_ref[:, :]


def split_lanes64(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host: (C, W) int64/float64 -> ((C, W) lo, (C, W) hi) little-endian
    int32 lanes. Explicit LE so lane semantics never depend on host byte
    order (same contract as shard_route.key_lanes)."""
    c, w = x.shape
    lanes = (np.ascontiguousarray(x).view(np.int64).astype("<i8")
             .view("<i4").reshape(c, w, 2))
    return (np.ascontiguousarray(lanes[..., 0]),
            np.ascontiguousarray(lanes[..., 1]))


def join_lanes64(lo: np.ndarray, hi: np.ndarray,
                 dtype: np.dtype) -> np.ndarray:
    """Host: inverse of :func:`split_lanes64`."""
    c, w = lo.shape
    lanes = np.empty((c, w, 2), "<i4")
    lanes[..., 0] = lo
    lanes[..., 1] = hi
    out = lanes.view("<i8").reshape(c, w).astype(np.int64)
    return out.view(dtype) if np.dtype(dtype) != np.int64 else out


@functools.partial(jax.jit, static_argnames=("op", "interpret", "tile"))
def _wide_2lane(alo, ahi, blo, bhi, *, op, interpret, tile):
    kernel = {"sub": _pack_wide_kernel, "add": _unpack_wide_kernel,
              "xor": _xor_wide_kernel}[op]
    w = alo.shape[1]
    lo, hi = launch.tiled_rows(
        kernel, [alo, ahi, blo, bhi],
        [((w,), jnp.int32), ((w,), jnp.int32)],
        tile=tile, interpret=interpret)
    return lo, hi


def delta_pack_wide(new: np.ndarray, old: np.ndarray, *,
                    interpret: bool | None = None,
                    tile: int | None = None) -> np.ndarray:
    """8-byte delta pack on device via two int32 lanes (exact 64-bit
    modular arithmetic; XOR lanes for float64). Host in, host out — the
    chain codec is a host-facing path. interpret=None: device kernel on
    TPU, host numpy on CPU; True forces the kernel (tests)."""
    if interpret is None and interpret_default():
        return ref.ref_delta_pack64(new, old)
    if tile is None:
        tile = launch.tile_for("delta_codec", n=new.shape[0])
    op = "xor" if np.issubdtype(new.dtype, np.floating) else "sub"
    alo, ahi = split_lanes64(new)
    blo, bhi = split_lanes64(old)
    lo, hi = _wide_2lane(jnp.asarray(alo), jnp.asarray(ahi),
                         jnp.asarray(blo), jnp.asarray(bhi),
                         op=op, interpret=bool(interpret), tile=int(tile))
    return join_lanes64(np.asarray(lo), np.asarray(hi), new.dtype)


def delta_unpack_wide(delta: np.ndarray, old: np.ndarray, *,
                      interpret: bool | None = None,
                      tile: int | None = None) -> np.ndarray:
    """Inverse of :func:`delta_pack_wide` (64-bit modular add / XOR)."""
    if interpret is None and interpret_default():
        return ref.ref_delta_unpack64(delta, old)
    if tile is None:
        tile = launch.tile_for("delta_codec", n=delta.shape[0])
    op = "xor" if np.issubdtype(delta.dtype, np.floating) else "add"
    dlo, dhi = split_lanes64(delta)
    olo, ohi = split_lanes64(old)
    lo, hi = _wide_2lane(jnp.asarray(dlo), jnp.asarray(dhi),
                         jnp.asarray(olo), jnp.asarray(ohi),
                         op=op, interpret=bool(interpret), tile=int(tile))
    return join_lanes64(np.asarray(lo), np.asarray(hi), delta.dtype)


# -- device-side chain decode (segmented scan) --------------------------------

@functools.partial(jax.jit, static_argnames=("xor",))
def chain_decode(deltas: jax.Array, heads: jax.Array, *,
                 xor: bool = False) -> jax.Array:
    """Decode chain deltas ON DEVICE: deltas (C, W) int lanes where the
    first cell of every chain is raw and ``heads`` (C,) flags those cells.
    A segmented inclusive scan (reset at heads) reconstructs values —
    modular int32 addition, so truncating the widened scan back to the
    stored dtype reproduces the host depth-loop byte-for-byte. ``xor=True``
    scans with XOR (float lane chains; XOR is its own inverse).

    This is what lets the fused superlog keep fields delta-packed in HBM
    and decode inside the gather path instead of uploading decoded cells.
    Jitted: run eagerly, the scan's log-depth ladder of slices and adds
    would compile one small program per op and per operand shape.
    """
    h = jnp.asarray(heads, bool).reshape(-1, 1)
    if xor:
        d = deltas

        def comb(a, b):
            av, af = a
            bv, bf = b
            return jnp.where(bf, bv, av ^ bv), af | bf
    else:
        d = deltas.astype(jnp.int32)

        def comb(a, b):
            av, af = a
            bv, bf = b
            return jnp.where(bf, bv, av + bv), af | bf
    v, _ = jax.lax.associative_scan(comb, (d, h), axis=0)
    return v


def narrow_dtype(maxabs: int, base=jnp.int32):
    """Pick the narrowest int dtype that can hold every delta in a segment."""
    if maxabs < 128:
        return jnp.int8
    if maxabs < 32768:
        return jnp.int16
    if maxabs < 2**31:
        return jnp.int32
    return base


# -- host-facing chain codec (the on-disk segment cell format) ---------------

def _chain_heads(rows: np.ndarray) -> np.ndarray:
    first = np.ones(len(rows), bool)
    first[1:] = rows[1:] != rows[:-1]
    return first


def chain_pack(vals: np.ndarray, rows: np.ndarray) -> tuple[np.ndarray, dict]:
    """Delta-pack a (row, ts)-sorted cell run for on-disk storage.

    Args:
      vals: (C, W) cell values, sorted so equal-row cells are adjacent and
        in ascending ts order within the row ("chains").
      rows: (C,) row index of each cell (defines the chain boundaries).

    Returns:
      (packed, meta): ``packed`` has the same shape as ``vals`` — the first
      cell of each chain raw, later cells as deltas vs their predecessor
      (arithmetic for ints, XOR lanes for floats, via the ``delta_pack``
      kernel). Integer deltas are narrowed to int8/int16 when the whole run
      allows it. ``meta`` records ``mode`` ("raw" for empty input, else
      "delta"), the original ``dtype`` name, and optionally ``narrow``.
    """
    if len(vals) == 0:
        return vals.copy(), {"mode": "raw", "dtype": vals.dtype.name}
    # traffic model: read new + predecessor cells, write the delta;
    # arithmetic: one sub/xor per element (the narrowing stat rides along).
    # logical = the cells themselves; padded adds the pow2 bucket slack the
    # kernel actually streams (8-byte host path: no padding happens)
    n = len(vals)
    n_pad = n if vals.dtype.itemsize == 8 else _codec_bucket(n)
    with launch.measured("delta_codec", nbytes=3 * vals.nbytes,
                         flops=vals.size,
                         padded_nbytes=3 * n_pad * vals.itemsize
                         * (vals.size // n)):
        return _chain_pack_timed(vals, rows)


def _codec_bucket(n: int) -> int:
    """pow2 cell bucket for chain codec launches (floored at the tile so a
    bucket is a whole number of tiles — the original 512 floor)."""
    return launch.pow2_bucket(n, floor=launch.tile_for("delta_codec"))


def _chain_pack_timed(vals: np.ndarray, rows: np.ndarray) -> tuple[np.ndarray, dict]:
    first = _chain_heads(rows)
    prev = np.roll(vals, 1, axis=0)
    prev[first] = 0  # chain heads pack against zero (stored raw)
    if vals.dtype.itemsize == 8:
        if interpret_default():
            # CPU backend: delta on host (the 32-bit jax default would
            # silently downcast int64/float64 through jnp.asarray)
            if np.issubdtype(vals.dtype, np.floating):
                delta = (vals.view(np.int64)
                         ^ prev.view(np.int64)).view(vals.dtype)
            else:
                # two's-complement wraparound; chain_unpack's add inverts it
                # exactly, so overflowing deltas still round-trip
                with np.errstate(over="ignore"):
                    delta = vals - prev
        else:
            # TPU: exact 64-bit modular delta via the two-lane int32 kernel
            delta = delta_pack_wide(vals, prev)
    else:
        # pad the cell count to a power-of-two bucket: every incremental
        # save has a unique cell count, and an unbucketed call would
        # re-trace the jitted kernel per save (zero rows delta to zero, so
        # results and the narrowing stat are unaffected)
        n = len(vals)
        n_pad = _codec_bucket(n)
        if n_pad != n:
            pad = ((0, n_pad - n), (0, 0))
            vals_in = np.pad(vals, pad)
            prev_in = np.pad(prev, pad)
        else:
            vals_in, prev_in = vals, prev
        delta, _stat = delta_pack(jnp.asarray(vals_in), jnp.asarray(prev_in))
        delta = np.asarray(delta)[:n]
    meta = {"mode": "delta", "dtype": vals.dtype.name}
    if np.issubdtype(vals.dtype, np.integer) and vals.dtype.itemsize >= 4:
        # bound via min/max lifted to Python ints — exact even for
        # int64-min, where np.abs silently wraps negative
        if delta.size:
            maxabs = max(-int(delta.min()), int(delta.max()))
        else:
            maxabs = 0
        narrow = narrow_dtype(
            maxabs, base=jnp.int64 if vals.dtype.itemsize == 8 else jnp.int32)
        if np.dtype(narrow) != vals.dtype:
            delta = delta.astype(narrow)
            meta["narrow"] = np.dtype(narrow).name
    return delta, meta


def chain_unpack(packed: np.ndarray, rows: np.ndarray, meta: dict,
                 out_dtype: np.dtype) -> np.ndarray:
    """Invert ``chain_pack``: reconstruct (C, W) cell values.

    Chains are rebuilt one depth level per pass (chains are short — one
    cell per version the row changed in), so the cost is
    O(cells x max_chain_depth / chain_count) vectorized steps.

    Raises:
      KeyError/TypeError: if ``meta`` does not come from ``chain_pack``.
    """
    if meta["mode"] == "raw" or len(packed) == 0:
        return packed.astype(out_dtype)
    # traffic model mirrors chain_pack's: read delta + predecessor,
    # write the reconstruction; one add/xor per element (the host depth
    # loop moves logical bytes only — no pad slack on the unpack side)
    with launch.measured("delta_codec", nbytes=3 * packed.nbytes,
                         flops=packed.size):
        return _chain_unpack_timed(packed, rows, meta, out_dtype)


def _chain_unpack_timed(packed: np.ndarray, rows: np.ndarray, meta: dict,
                        out_dtype: np.dtype) -> np.ndarray:
    stored = np.dtype(meta["dtype"])
    delta = packed.astype(stored) if "narrow" in meta else packed
    out = delta.copy()
    first = _chain_heads(rows)
    starts = np.nonzero(first)[0]
    lens = np.diff(np.append(starts, len(rows)))
    is_float = np.issubdtype(stored, np.floating)
    ib = {8: np.int64, 4: np.int32, 2: np.int16}.get(stored.itemsize, np.int32)
    for depth in range(1, int(lens.max()) if len(lens) else 0):
        idx = starts[lens > depth] + depth
        if is_float:
            out[idx] = (out[idx].view(ib) ^ out[idx - 1].view(ib)).view(out.dtype)
        else:
            out[idx] = out[idx] + out[idx - 1]
    return out.astype(out_dtype)
