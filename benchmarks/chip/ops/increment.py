"""``increment``: ``VersionedStore.get_increments`` calls with one window
each. A block row is ``[releases the window spans, releases its end lies
behind the newest, index into significant]``.
"""
from __future__ import annotations

from types import SimpleNamespace

from chipbench import history as H
from chipbench.traffic import ClosedLoop, StoreProbe, block_row


class Op(ClosedLoop):
    """Increments for tools, one window per ``get_increments`` call."""

    keep_per_client = 12

    def __init__(self, dep, mix, seed):
        super().__init__(dep, mix, seed)
        self.probe = StoreProbe(dep.store)
        self.newest = dep.newest
        self.sigs = [s if s is not None else list(H.FIELDS)
                     for s in mix["significant"]]
        for k, b, _s in mix["block"]:
            if not (k >= 1 and b >= 0 and self.newest - b - k >= 1):
                raise ValueError(f"block row {[k, b, _s]}: the window must "
                                 f"lie in releases 1..{self.newest}")

    def window(self, row):
        k, b, s = row
        t1 = self.newest - b
        return t1 - k, t1, self.sigs[s]

    def warm(self) -> None:
        """Every (window, significant fields) of the block: the gathers
        compile per changed-row count. A two-window call goes first and
        builds the fused superlog, as a store that serves reads has it; a
        single window against a store without one would take the host
        path instead."""
        self.dep.store.get_increments([(1, 2), (1, self.newest)])
        windows = {(t0, t1, tuple(sig))
                   for t0, t1, sig in map(self.window, self.mix["block"])}
        for t0, t1, sig in sorted(windows):
            self.dep.store.get_increments([(t0, t1)],
                                          significant_fields=list(sig),
                                          fields=list(sig))

    def request(self, g, c, grp, name, i):
        t0, t1, sig = self.window(block_row(self.mix["block"], self.seed, i,
                                            g, c))
        inc = self.probe.get_increments([(t0, t1)], significant_fields=sig,
                                        fields=sig)[0]
        return inc, (t0, t1, sig)

    def collect(self) -> dict:
        return {"calls": list(self.probe.calls),
                "n_cells": self.dep.hist.n_cells(),
                "n_rows": self.dep.hist.universe}

    def check(self, control: bool = False) -> tuple[int, int]:
        """(rows wrong, answers compared); the control answers the window
        (t0, t1] with the window one release longer, (t0 - 1, t1]."""
        hist = self.hist
        by_t1: dict[int, list] = {}
        for inc, (t0, t1, sig) in self.kept:
            by_t1.setdefault(t1, []).append((inc, t0, sig))
        wrong = 0
        for t, state in hist.states():
            for inc, t0, sig in by_t1.get(t, ()):
                if control:
                    ids, kind, vals = H.expected_increment(
                        hist, state, t0 - 1, t, sig, sig)
                    inc = SimpleNamespace(row_idx=ids, kind=kind, values=vals,
                                          keys=[hist.keys[i] for i in ids])
                wrong += H.increment_errors(inc, hist, state, t0, t, sig, sig)
        return wrong, len(self.kept)

    def close(self):
        self.hist = self.dep.hist
        self.probe = None
        super().close()
