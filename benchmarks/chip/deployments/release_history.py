"""The ``release_history`` deployment: one ``VersionedStore`` in a
``GeStore``, holding every release of a seeded Swiss-Prot-sized history
(``chipbench/history.py``), which is also its plain reference."""
from __future__ import annotations

import os

import numpy as np

from chipbench.history import FIELDS, STORE, History


class Deployment:
    """A ``GeStore`` holding one ``VersionedStore`` with every release of
    a seeded ``History``, and that history as the plain reference."""

    def __init__(self, config: dict, seed: int, root: str):
        from repro.core.increment import GeStore
        from repro.core.parsers import UniProtParser
        from repro.core.plugins import PluginRegistry

        d = config["deployment"]
        self.config = config
        self.hist = History(entries=d["entries"], releases=d["releases"],
                            churn=d["churn"], new_frac=d["new_frac"],
                            delete_frac=d["delete_frac"],
                            seq_width=d["seq_width"],
                            annot_width=d["annot_width"], seed=seed)
        registry = PluginRegistry()
        parser = UniProtParser(seq_width=d["seq_width"],
                               annot_width=d["annot_width"])
        registry.register_parser(parser)
        self.gs = GeStore(os.path.join(root, "gestore"), registry)
        self.store_name = STORE
        self.store = self.gs.create_store(STORE, parser.schema(),
                                          capacity=self.hist.universe)
        for t, cur in self.hist.states():
            ids = np.nonzero(cur["alive"])[0]
            info = self.store.update(t, [self.hist.keys[i] for i in ids],
                                     {f: cur[f][ids] for f in FIELDS},
                                     label=f"release-{t}")
            if info.n_entries != len(ids):
                raise RuntimeError(f"release {t}: {info.n_entries} entries "
                                   f"stored, {len(ids)} sent")
        self.newest = self.hist.releases

    def close(self) -> None:
        self.store = None
        self.gs = None
