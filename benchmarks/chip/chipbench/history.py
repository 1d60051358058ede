"""A Swiss-Prot-sized history of full releases, and the plain reference
that answers for it.

The data is made from the seed (the generator of the repository's
``chip_smoke.py``, kept here so that the yardstick cannot move with the
program): a universe of entries with ``UniProtParser``'s schema, and
releases that churn a share of the live entries, add new ones and delete
a few. The reference keeps the first release whole and every later one as
its edits, so that any release is the base with the edits up to it.

The checks count what differs from the reference; they never raise.
"""
from __future__ import annotations

import dataclasses

import numpy as np

AA = np.frombuffer(b"ACDEFGHIKLMNPQRSTVWY", np.uint8)
FIELDS = ("sequence", "length", "annotation", "taxid")
STORE = "sprot"
KIND_NEW, KIND_UPDATED, KIND_DELETED = 0, 1, 2


def keys_of(ids) -> list[bytes]:
    return [b"P%08d" % i for i in ids]


@dataclasses.dataclass
class Edit:
    """One release's changes against the release before it."""
    fields: dict      # field -> (rows, new values), rows whose value changed
    born: np.ndarray  # rows that appear
    gone: np.ndarray  # rows that are deleted


class History:
    """``releases`` full releases over one entry universe. Entry ``i`` has
    key ``P%08d`` and store row ``i``: releases list live entries in id
    order and new ids only ever grow. Each later release churns ``churn``
    of release 1's entries, deletes ``delete_frac`` of them and adds
    ``new_frac`` new ones; no entry is churned or deleted twice."""

    def __init__(self, *, entries: int, releases: int, churn: float,
                 new_frac: float, delete_frac: float, seq_width: int,
                 annot_width: int, seed: int):
        self.n, self.releases = entries, releases
        self.seq_w, self.annot_w = seq_width, annot_width
        rng = np.random.default_rng(seed)
        n_new = max(1, round(entries * new_frac))
        u = entries + (releases - 1) * n_new
        self.universe = u
        length = np.clip(rng.lognormal(np.log(300), 0.6, u), 30, seq_width
                         ).astype(np.int32)
        seq = AA[rng.integers(0, len(AA), (u, seq_width), dtype=np.uint8)]
        seq[np.arange(seq_width)[None, :] >= length[:, None]] = 0
        annot_len = rng.integers(60, annot_width + 1, u)
        annot = rng.integers(32, 127, (u, annot_width), dtype=np.uint8)
        annot[np.arange(annot_width)[None, :] >= annot_len[:, None]] = 0
        alive = np.zeros(u, bool)
        alive[:entries] = True
        self.base = {"sequence": seq.view(np.int8), "length": length[:, None],
                     "annotation": annot.view(np.int8),
                     "taxid": rng.integers(1, 2_000_000, (u, 1)
                                           ).astype(np.int32),
                     "alive": alive}
        self.keys = keys_of(range(u))
        self.edits: list[Edit] = []          # edits[r - 2]: release r
        cur = {k: v.copy() for k, v in self.base.items()}
        inv = np.zeros(256, np.int64)
        inv[AA] = np.arange(len(AA))
        # every count below is fixed by the sizes alone, so that every seed
        # gives the same shapes and only the rows and values move: churned
        # and deleted entries are drawn without overlap from release 1's
        n_churn = round(entries * churn)
        n_del = max(1, round(entries * delete_frac))
        per = n_churn + n_del
        if per * (releases - 1) > entries:
            raise ValueError("too few entries for disjoint churn")
        order = rng.permutation(entries)
        for r in range(1, releases):
            pick = order[(r - 1) * per: r * per]
            churned, gone = pick[:n_churn], pick[n_churn:]
            old = {f: cur[f][churned].copy() for f in FIELDS}
            # a third of the churn substitutes a residue (a quarter of
            # those also grow by one); the rest is annotation-only, the
            # release churn BLAST must not see; a twentieth of each part
            # also changes taxon
            seq_rows = churned[: n_churn // 3]
            ln = cur["length"][seq_rows, 0]
            p = (rng.random(len(seq_rows)) * ln).astype(np.int64)
            s8 = cur["sequence"].view(np.uint8)
            s8[seq_rows, p] = AA[(inv[s8[seq_rows, p]] + 1
                                  + rng.integers(0, len(AA) - 1,
                                                 len(seq_rows))) % len(AA)]
            can_grow = seq_rows[ln < seq_width]
            grow = rng.choice(can_grow, round(len(seq_rows) / 4),
                              replace=False)
            s8[grow, cur["length"][grow, 0]] = AA[rng.integers(0, len(AA),
                                                               len(grow))]
            cur["length"][grow, 0] += 1
            ann_rows = churned[n_churn // 3:]
            a8 = cur["annotation"].view(np.uint8)
            q = rng.integers(0, 16, len(ann_rows))
            a8[ann_rows, q] = ((a8[ann_rows, q].astype(np.int64) - 32 + 1
                                + rng.integers(0, 94, len(ann_rows)))
                               % 95 + 32)
            tax_rows = np.concatenate([seq_rows[: round(len(seq_rows) / 20)],
                                       ann_rows[: round(len(ann_rows) / 20)]])
            cur["taxid"][tax_rows, 0] += 1
            fields = {}
            for f in FIELDS:
                moved = (cur[f][churned] != old[f]).any(axis=1)
                rows = np.sort(churned[moved])
                fields[f] = (rows, cur[f][rows].copy())
            born = np.arange(entries + (r - 1) * n_new, entries + r * n_new)
            cur["alive"][gone] = False
            cur["alive"][born] = True
            self.edits.append(Edit(fields, born, np.sort(gone)))
        del cur

    # -- releases -------------------------------------------------------------
    def states(self):
        """Yield ``(t, state)`` for t = 1..releases, one working copy moved
        forward edit by edit (the same dict each time: copy what you keep)."""
        cur = {k: v.copy() for k, v in self.base.items()}
        yield 1, cur
        for t, e in enumerate(self.edits, start=2):
            for f, (rows, vals) in e.fields.items():
                cur[f][rows] = vals
            cur["alive"][e.gone] = False
            cur["alive"][e.born] = True
            yield t, cur

    def alive_at(self, t: int) -> np.ndarray:
        alive = np.zeros(self.universe, bool)
        if t < 1:
            return alive
        alive[:] = self.base["alive"]
        for e in self.edits[: t - 1]:
            alive[e.gone] = False
            alive[e.born] = True
        return alive

    def changed_rows(self, t0: int, t1: int, sig) -> np.ndarray:
        """Rows whose significant fields changed in a release in (t0, t1]."""
        changed = np.zeros(self.universe, bool)
        for r in range(max(t0, 1) + 1, t1 + 1):
            for f in sig:
                changed[self.edits[r - 2].fields[f][0]] = True
        return changed

    def n_cells(self) -> int:
        """Cells the store holds once every release is in: per field one
        for each live entry of the first release, each change and each
        entry born; in the EXISTS log one per entry born or deleted. The
        ts array that every version read scans has this many entries."""
        c = len(FIELDS) * self.n + self.n
        for e in self.edits:
            c += sum(len(rows) for rows, _ in e.fields.values())
            c += (len(FIELDS) + 1) * len(e.born) + len(e.gone)
        return c


# -- comparisons against the reference ------------------------------------------

def _row_errors(got_rows, got_keys, got_vals: dict, want_rows, keys,
                want_vals: dict, got_kind=None, want_kind=None) -> int:
    """Rows that differ: rows present on one side only, plus rows on both
    whose key, kind or any compared field differs."""
    got_rows = np.asarray(got_rows, np.int64)
    want_rows = np.asarray(want_rows, np.int64)
    if len(got_keys) != len(got_rows):
        return max(len(got_rows), len(want_rows), 1)
    common, gi, wi = np.intersect1d(got_rows, want_rows, assume_unique=True,
                                    return_indices=True)
    bad = len(got_rows) + len(want_rows) - 2 * len(common)
    wrong = np.zeros(len(common), bool)
    for f, want in want_vals.items():
        got = got_vals.get(f)
        if got is None or got.shape[1:] != want.shape[1:]:
            return bad + len(common)
        wrong |= (np.asarray(got)[gi] != want[wi]).reshape(len(common), -1
                                                           ).any(axis=1)
    if want_kind is not None:
        wrong |= np.asarray(got_kind)[gi] != want_kind[wi]
    gk = [got_keys[i] for i in gi]
    wk = [keys[r] for r in common]
    if gk != wk:
        wrong |= np.fromiter((a != b for a, b in zip(gk, wk)), bool,
                             count=len(common))
    return int(bad + wrong.sum())


def view_errors(view, state: dict, keys, fields) -> int:
    """Rows of a served version that differ from the release ``state``:
    row ids, keys and every requested field's values."""
    ids = np.nonzero(state["alive"])[0]
    return _row_errors(view.row_idx, view.keys, view.values, ids, keys,
                       {f: state[f][ids] for f in fields})


def expected_increment(hist: History, state_t1: dict, t0: int, t1: int,
                       sig, fields):
    """(rows, kinds, values) of the window (t0, t1]: an entry is new or
    deleted by its alive flag at the two ends, and updated when a
    significant field changed in a release inside the window; values at
    t1, zeroed for deleted entries."""
    e0, e1 = hist.alive_at(t0), state_t1["alive"]
    changed = hist.changed_rows(t0, t1, sig)
    new, deleted, updated = e1 & ~e0, e0 & ~e1, e1 & e0 & changed
    ids = np.nonzero(new | deleted | updated)[0]
    kind = np.where(new[ids], KIND_NEW,
                    np.where(updated[ids], KIND_UPDATED, KIND_DELETED)
                    ).astype(np.int8)
    vals = {}
    for f in fields:
        v = state_t1[f][ids].copy()
        v[kind == KIND_DELETED] = 0
        vals[f] = v
    return ids, kind, vals


def increment_errors(inc, hist: History, state_t1: dict, t0: int, t1: int,
                     sig, fields) -> int:
    ids, kind, vals = expected_increment(hist, state_t1, t0, t1, sig, fields)
    return _row_errors(inc.row_idx, inc.keys, inc.values, ids, hist.keys,
                       vals, got_kind=inc.kind, want_kind=kind)
