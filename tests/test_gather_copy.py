"""The fused gather's copy to the host (``core/store.py`` ``_take_words``,
``_gather_start``, ``_gather_collect``): every field of a version read or
an increment comes back byte for byte what the field's own
``_CellLog.select_at`` gives, with rows that have no cell at the query
time and the deleted rows of an increment zeroed; the values are
read-only, on the fused path and on the cold per-field one; and the
``gather.*`` counters count what crossed to the host as flat words."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import store as store_mod
from repro.core.store import KIND_DELETED, FieldSchema, VersionedStore
from repro.obs import REGISTRY

#: (dtype, width, GESTORE_PACKED_SUPERLOG) of the field under test
CASES = {
    "int8x1": ("int8", 1, "1"),
    "int8x3": ("int8", 3, "1"),
    "int8x256": ("int8", 256, "1"),
    "int8x512": ("int8", 512, "1"),
    "int16x5": ("int16", 5, "1"),
    "int32x1_packed": ("int32", 1, "1"),
    "int32x1_unpacked": ("int32", 1, "0"),
    "float32x4": ("float32", 4, "1"),
    "boolx2": ("bool", 2, "1"),
}
COUNTERS = ("gather.host_bytes", "gather.word_copies")
N = 40


def _values(rng, dtype, n, width, small):
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return rng.integers(0, 2, (n, width)).astype(bool)
    if dt.kind == "f":
        return rng.standard_normal((n, width)).astype(dt)
    info = np.iinfo(dt)
    lo, hi = (-3, 4) if small else (info.min, info.max)
    return rng.integers(lo, hi, (n, width), endpoint=True).astype(dt)


def _store(dtype, width):
    """Four releases: ``x`` (the field under test) and ``h``. Release 20 is
    a patch that brings new keys with ``h`` only, so those rows are alive
    with no ``x`` cell until release 30 writes one; releases 30 and 40
    churn ``x``, add keys and delete some. Small integer deltas let the
    int32 field delta-pack on the device."""
    rng = np.random.default_rng(11)
    st = VersionedStore("G", [FieldSchema("x", width, dtype),
                              FieldSchema("h", 1, "int32")], capacity=64)
    small = np.dtype(dtype) == np.int32
    keys = [f"K{i:02d}" for i in range(N)]
    x = _values(rng, dtype, N, width, small)
    h = rng.integers(0, 9, (N, 1)).astype(np.int32)
    st.update(10, keys, {"x": x, "h": h})
    late = [f"L{i:02d}" for i in range(6)]
    st.update(20, late, {"h": np.ones((len(late), 1), np.int32)},
              full_release=False)
    keys = keys[4:] + late
    x = np.concatenate([x[4:], _values(rng, dtype, len(late), width, small)])
    for ts in (30, 40):
        churn = rng.choice(len(keys), 9, replace=False)
        x = x.copy()
        x[churn] = _values(rng, dtype, len(churn), width, small)
        st.update(ts, keys, {"x": x, "h": np.ones((len(keys), 1), np.int32)})
        keys = keys[3:] + [f"N{ts}{i}" for i in range(4)]
        x = np.concatenate([x[3:], _values(rng, dtype, 4, width, small)])
    return st


def _select_at(st, t):
    """The field's own log at ``t``, in the field's dtype (the select
    kernel returns a bool field's cells as int32 0/1)."""
    vals, _found = st.fields["x"].log.select_at(st.n_rows, t)
    return vals.astype(st.fields["x"].schema.np_dtype, copy=False)


def _same_bytes(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.ascontiguousarray(got).tobytes() == \
        np.ascontiguousarray(want).tobytes()


def _counts():
    return {c: REGISTRY.counter(c).value for c in COUNTERS}


@pytest.mark.parametrize("steps", ["one_step", "stepped"])
@pytest.mark.parametrize("case", list(CASES))
def test_gathered_values_match_select_at_byte_for_byte(case, steps,
                                                       monkeypatch):
    dtype, width, packed = CASES[case]
    monkeypatch.setenv("GESTORE_PACKED_SUPERLOG", packed)
    if steps == "stepped":  # a block of several loop steps of 8 rows
        monkeypatch.setattr(store_mod, "_WORD_STEP_ROWS", 8)
    st = _store(dtype, width)
    dt = np.dtype(dtype)
    words = store_mod._row_words(dt, width)

    # one version or one window of a store whose fused log is not built
    # yet: the per-field cold path, read-only too
    cold = st.get_versions([20], fields=["x"])[0]
    _same_bytes(cold.values["x"], _select_at(st, 20)[cold.row_idx])
    assert not cold.values["x"].flags.writeable
    inc = st.get_increments([(30, 40)], fields=["x"])[0]
    assert not inc.values["x"].flags.writeable
    assert not inc.values["x"][inc.kind == KIND_DELETED].any()
    assert st._superlog_fresh() is False

    before = _counts()
    tss = [10, 20, 30, 40]
    views = st.get_versions(tss, fields=["x"])
    sl = st.superlog()
    assert (sl.fields["x"].packed_host is not None) == (case ==
                                                        "int32x1_packed")
    n_rows = sum(len(v) for v in views)
    saw_absent = False
    for t, v in zip(tss, views):
        want = _select_at(st, t)[v.row_idx]
        _same_bytes(v.values["x"], want)
        assert not v.values["x"].flags.writeable
        _, found = st.fields["x"].log.select_at(st.n_rows, t)
        absent = ~found[v.row_idx]
        assert not v.values["x"][absent].any()
        saw_absent |= bool(absent.any())
    assert saw_absent  # release 20's keys have no x cell at 20

    pairs = [(10, 30), (20, 40), (30, 40)]
    incs = st.get_increments(pairs, fields=["x"])
    inc_rows = sum(len(i) for i in incs)
    saw_deleted = False
    for (t0, t1), inc in zip(pairs, incs):
        want = _select_at(st, t1)[inc.row_idx].copy()
        deleted = inc.kind == KIND_DELETED
        want[deleted] = 0
        _same_bytes(inc.values["x"], want)
        assert not inc.values["x"][deleted].any()
        assert not inc.values["x"].flags.writeable
        saw_deleted |= bool(deleted.any())
    assert saw_deleted

    after = _counts()
    moved = {c: after[c] - before[c] for c in COUNTERS}
    # one gather per call for the one field, each copied once as words,
    # each block padded to whole steps of a multiple of 8 rows
    assert moved["gather.word_copies"] == 2
    want_bytes = 0
    for r in (n_rows, inc_rows):
        n_steps = -(-r // store_mod._WORD_STEP_ROWS)
        rows = -(-r // n_steps)
        want_bytes += n_steps * (-(-rows // 8) * 8) * 4 * words
    assert moved["gather.host_bytes"] == want_bytes
