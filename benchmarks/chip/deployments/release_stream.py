"""The ``release_stream`` deployment: consecutive whole UniProtKB releases
streamed as ``.dat`` text (``chipbench/stream.py``) into a hash-sharded
store through ``GeStore.add_release_stream``.

Each release's text is rendered whole before it is streamed, as a
deployment reads a downloaded release: ``render_ahead(k)`` keeps ``k``
releases ready, and the first ``releases_at_setup`` are streamed in when
the deployment is built.
"""
from __future__ import annotations

import collections
import os

import numpy as np

from chipbench.stream import ReleaseText

STORE = "sprot_stream"


class Deployment:
    """A ``GeStore`` with one hash-sharded store fed through
    ``add_release_stream``."""

    def __init__(self, config: dict, seed: int, root: str):
        from repro.core.increment import GeStore
        from repro.core.ingest import IngestConfig
        from repro.core.parsers import UniProtParser
        from repro.core.placement import plan_placement
        from repro.core.plugins import PluginRegistry

        d = config["deployment"]
        self.config = config
        self.text = ReleaseText(entries=d["entries_per_release"],
                                churn=d["churn"], seq_len=d["seq_len"],
                                seq_width=d["seq_width"],
                                annot_width=d["annot_width"], seed=seed,
                                entries_per_chunk=d["entries_per_chunk"])
        registry = PluginRegistry()
        self.parser = UniProtParser(seq_width=d["seq_width"],
                                    annot_width=d["annot_width"])
        registry.register_parser(self.parser)
        self.gs = GeStore(os.path.join(root, "gestore"), registry)
        self.shards = d["shards"]
        self.store_name = STORE
        self.store = self.gs.create_store(STORE, self.parser.schema(),
                                          shards=self.shards,
                                          capacity=self.text.n)
        self.store.placement = plan_placement(self.shards,
                                              force=d["placement"])
        self.ingest_config = IngestConfig(**d["ingest"])
        self.resumable = d["resumable"]
        self.committed: list[int] = []
        self.last_wave_rows = self._last_wave_rows(d["ingest"])
        self._raw = None          # letters and taxa of the newest rendered
        self._entries: list = []  # its text, one string per entry
        self._ready = collections.deque()   # (t, chunks), not yet streamed
        self.rendered_late = 0    # releases rendered when one was due
        for _ in range(d["releases_at_setup"]):
            self.render_ahead(1)
            self.ingest_next()

    def _last_wave_rows(self, ingest: dict) -> np.ndarray:
        per = ingest.get("batch_entries", 1024)
        return np.arange((self.text.n - 1) // per * per, self.text.n)

    def _render_next(self) -> None:
        t = len(self.committed) + len(self._ready) + 1
        if t == 1:
            self._raw = self.text.raw(1)
            self._entries = self.text.entries_text(*self._raw,
                                                   range(self.text.n))
        else:
            self._raw, rows = self.text.advance(self._raw, t)
            for i, s in zip(rows, self.text.entries_text(*self._raw, rows)):
                self._entries[i] = s
        self._ready.append((t, self.text.chunks(self._entries)))

    def render_ahead(self, k: int) -> None:
        """Have the text of the next ``k`` releases ready."""
        while len(self._ready) < k:
            self._render_next()

    def ingest_next(self):
        """Stream the next release in; returns its ``IngestReport``."""
        if not self._ready:
            self.rendered_late += 1
            self._render_next()
        t, chunks = self._ready.popleft()
        rep = self.gs.add_release_stream(
            self.store_name, t, iter(chunks),
            parser_name=self.parser.format_name, shards=self.shards,
            config=self.ingest_config, resumable=self.resumable)
        del chunks
        if rep.info is None or rep.n_entries != self.text.n:
            raise RuntimeError(f"release {t}: {rep.n_entries} of "
                               f"{self.text.n} entries ingested")
        self.committed.append(t)
        return rep

    def close(self) -> None:
        self._ready.clear()
        self._entries = []
        self.store = None
        self.gs = None
