"""``read_version``: pinned-version reads through ``FrontDoor.submit``.

Each client group of the mix names its tool's fields, and a block row
gives the release each client of the group asks for, counted back from
the newest. The group's clients share the order, and the dispatcher
starts once every client's first request is queued, so the clients of a
tool ride the same waves with the rows' pairs of releases.
"""
from __future__ import annotations

import itertools
import time

from chipbench import history as H
from chipbench.traffic import ClosedLoop, StoreProbe, block_row, view_of


class Op(ClosedLoop):
    """Pinned-version reads through the front door."""

    def __init__(self, dep, mix, seed):
        super().__init__(dep, mix, seed)
        from repro.serve import FrontDoor
        self.probe = StoreProbe(dep.store)
        self.newest = dep.newest
        for row in mix["block"]:
            if any(len(row) != grp["count"] for grp in mix["clients"]) or \
                    not all(0 <= b < self.newest for b in row):
                raise ValueError(f"block row {row}: one release per client, "
                                 f"each under {self.newest} back")
        self.fd = FrontDoor({dep.store_name: self.probe},
                            **dep.config.get("service", {}))

    def warm(self) -> None:
        """Every wave a window can form: one or two distinct versions of
        a client group's fields (two clients per tool, one outstanding
        request each). Every pair, not only the block's: two clients that
        fall out of step pair other rows. Gathers compile per total row
        count, and the all-field read takes every field's gather for
        every count. Pairs go first: they build the fused superlog, which
        a single version read of a store that has not built it would
        bypass."""
        ts = range(1, self.newest + 1)
        for q in (2, 1):
            for pick in itertools.combinations(ts, q):
                self.dep.store.get_versions(list(pick))

    def start(self, t_end):
        """The clients first, then the dispatcher once every first request
        is queued: the first waves pair the clients of each tool as the
        block's rows do."""
        super().start(t_end)
        n = sum(grp["count"] for grp in self.mix["clients"])
        while self.fd.queued() < n and time.perf_counter() < t_end:
            time.sleep(1e-4)
        self.fd.start()

    def request(self, g, c, grp, name, i):
        t = self.newest - block_row(self.mix["block"], self.seed, i, g)[c]
        fields = grp["fields"]
        view = self.fd.submit(name, self.dep.store_name, t,
                              fields=fields).result()
        return view, (t, fields or list(H.FIELDS))

    def stop(self):
        self.fd.stop(drain=True)

    def collect(self) -> dict:
        """What the per-layer readers take from the program, read once the
        window is drained: the front door's counters and stage walls, and
        the question shape of each scan."""
        return {"frontdoor": self.fd.stats(), "calls": list(self.probe.calls),
                "n_cells": self.dep.hist.n_cells(),
                "n_rows": self.dep.hist.universe}

    def close(self):
        self.hist = self.dep.hist
        self.fd = self.probe = None
        super().close()

    def check(self, control: bool = False) -> tuple[int, int]:
        """(rows wrong, answers compared) over the kept sample, each
        answer against its release. The control answers version t from
        the adjacent release (t - 1, or 2 for t = 1)."""
        hist = self.hist
        by_t: dict[int, list] = {}
        for view, (t, fields) in self.kept:
            by_t.setdefault(t, []).append((view, fields))
        wrong, prev = 0, None
        for t, state in hist.states():
            for view, fields in by_t.get(t, ()):
                if control and t > 1:
                    view = view_of(prev, hist.keys, fields)
                if not control or t > 1:
                    wrong += H.view_errors(view, state, hist.keys, fields)
            if control and t == 2:
                for _view, fields in by_t.get(1, ()):
                    wrong += H.view_errors(view_of(state, hist.keys, fields),
                                           prev, hist.keys, fields)
            if control:
                prev = {k: v.copy() for k, v in state.items()}
        return wrong, len(self.kept)
