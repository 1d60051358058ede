"""``release_stream``: whole releases streamed back to back through
``GeStore.add_release_stream`` by one feeder."""
from __future__ import annotations

import threading
import time

from chipbench import history as H
from chipbench.traffic import DRAIN_S, Record, view_of


class Op:
    """Whole releases streamed back to back by one feeder thread; the
    release in flight when the window closes is finished after it."""

    def __init__(self, dep, mix, seed):
        from repro.obs import REGISTRY
        self.dep, self.mix = dep, mix
        self.registry = REGISTRY
        self.records: list[Record] = []
        self._thread: threading.Thread | None = None

    def warm(self) -> None:
        """Set-up streamed the deployment's first releases in, through the
        same path. The text of the next ``releases_ahead`` releases is
        rendered now, so that the window times the ingest of text the
        deployment holds, not the benchmark making it; a fresh registry
        then counts the window alone."""
        self.dep.render_ahead(self.mix["releases_ahead"])
        self.registry.clear()

    def entries_routed(self) -> float:
        return self.registry.counter("ingest.entries_routed").value

    def start(self, t_end: float) -> None:
        self._thread = threading.Thread(target=self._loop, args=(t_end,),
                                        name="feeder", daemon=True)
        self._thread.start()

    def _loop(self, t_end: float) -> None:
        while time.perf_counter() < t_end:
            rec = Record("feeder", time.perf_counter())
            self.records.append(rec)
            try:
                rep = self.dep.ingest_next()
            except Exception as e:  # noqa: BLE001 — counted as failed
                rec.error = repr(e)
                return
            rec.t_done = time.perf_counter()
            rec.entries = rep.n_entries

    def at_close(self) -> dict:
        """Entries whose ingest wave was applied by the window's close."""
        return {"entries_routed": self.entries_routed()}

    def join(self, timeout: float = DRAIN_S) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    def stop(self) -> None:
        pass

    def collect(self) -> dict:
        """The ingest counters and wave walls of the window, and every
        committed release read back at its ts from all shards."""
        wave = self.registry.histogram("ingest.wave_wall").snapshot()
        self.views = self.dep.store.get_versions(list(self.dep.committed))
        return {"entries_routed": self.entries_routed(), "wave_wall": wave,
                "releases_streamed": len(self.records),
                "rendered_late": self.dep.rendered_late}

    def close(self) -> None:
        self.text = self.dep.text
        self.committed = list(self.dep.committed)
        self.last_wave_rows = self.dep.last_wave_rows
        self.dep = None

    def check(self, control: bool = False) -> tuple[int, int]:
        """(rows wrong, releases compared). The control loses each
        release's last ingest wave, whose entries read back as they were
        in the release before (absent from the first)."""
        wrong = 0
        keys = H.keys_of(range(self.text.n))
        prev = raw = None
        for t, view in zip(self.committed, self.views):
            raw = self.text.raw(t) if raw is None else \
                self.text.advance(raw, t)[0]
            state = self.text.state(t, raw)
            if control:
                fake = {k: v.copy() for k, v in state.items()}
                rows = self.last_wave_rows
                if prev is None:
                    fake["alive"][rows] = False
                else:
                    for f in H.FIELDS:
                        fake[f][rows] = prev[f][rows]
                view = view_of(fake, keys, H.FIELDS)
            wrong += H.view_errors(view, state, keys, H.FIELDS)
            prev = state
        return wrong, len(self.views)
