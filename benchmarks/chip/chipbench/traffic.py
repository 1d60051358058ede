"""The one traffic generator: a mix file's parameters drive it.

A mix (``traffic/<mix>.json``) names an ``op`` and its clients. The op is
the module ``ops/<op>.py``, found by name like every other part of a
cell; it defines ``Op(deployment, mix, seed)`` with ``warm()``,
``start(t_close)``, ``at_close()``, ``join()``, ``stop()``,
``collect()``, ``close()`` and ``check(control=False)``, and keeps
``records``. The closed loop below is what the ops share: each client
sends its next request only when the last one has answered. What it asks
comes from the mix's ``block``: a fixed set of rows, taken again and
again, each round in an order drawn from the seed. So every seed does the
same work in another order, and one seed gives the same requests in the
same order.

Each op warms every shape its window will use before the window opens,
records every request (submit and answer times on the host clock,
entries answered, failures), keeps a sample of answers drawn from the
seed, and compares them with the deployment's plain reference once the
window has closed and the program's state is freed. ``check(control=
True)`` puts the reference in the program's place with one guarantee
broken, so the comparison must fail.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from types import SimpleNamespace

import numpy as np

#: how long the answers still due at the window's close may take
DRAIN_S = 60.0


@dataclasses.dataclass
class Record:
    client: str
    t_submit: float
    t_done: float | None = None
    entries: int = 0
    error: str | None = None


class StoreProbe:
    """The store, with the question shape of each query noted on the way
    in: the distinct timestamps one scan answers. Everything else passes
    through to the store."""

    def __init__(self, store):
        self._store = store
        self.calls: list[tuple[float, int]] = []   # (host time, queries)

    def __getattr__(self, name):
        return getattr(self._store, name)

    def get_versions(self, ts_list, **kw):
        self.calls.append((time.perf_counter(), len(set(ts_list))))
        return self._store.get_versions(ts_list, **kw)

    def get_increments(self, pairs, **kw):
        ends = {t for p in pairs for t in p}
        self.calls.append((time.perf_counter(), len(ends)))
        return self._store.get_increments(pairs, **kw)


def client_rng(seed: int, group: int, client: int) -> np.random.Generator:
    return np.random.default_rng([seed, group, client])


def block_row(block: list, seed: int, i: int, *key: int):
    """Row ``i`` of an endless schedule: ``block`` taken again and again,
    each round in an order drawn from (seed, key, round)."""
    n = len(block)
    order = np.random.default_rng([seed, *key, i // n]).permutation(n)
    return block[order[i % n]]


class ClosedLoop:
    """Client threads that each call ``self.request(group, client, group
    entry, name, request index)`` until the window closes, and keep a
    reservoir sample of ``keep_per_client`` answers each, drawn from the
    seed."""

    keep_per_client = 1

    def __init__(self, dep, mix: dict, seed: int):
        self.dep, self.mix, self.seed = dep, mix, seed
        self.records: list[Record] = []
        self.kept: list[tuple] = []
        self._lock = threading.Lock()
        self._threads: list[threading.Thread] = []

    def clients(self):
        for g, grp in enumerate(self.mix["clients"]):
            for c in range(grp["count"]):
                yield g, c, grp, f"{grp['tool']}-{c}"

    def start(self, t_end: float) -> None:
        for g, c, grp, name in self.clients():
            th = threading.Thread(target=self._loop,
                                  args=(g, c, grp, name, t_end),
                                  name=f"client-{name}", daemon=True)
            self._threads.append(th)
        for th in self._threads:
            th.start()

    def _loop(self, g, c, grp, name, t_end) -> None:
        keep_rng = client_rng(self.seed, g, c + 10_000)
        sample: list = []
        seen = 0
        for i in itertools.count():
            if time.perf_counter() >= t_end:
                break
            rec = Record(name, time.perf_counter())
            with self._lock:
                self.records.append(rec)
            try:
                answer, meta = self.request(g, c, grp, name, i)
            except Exception as e:  # noqa: BLE001 — counted as failed
                rec.error = repr(e)
                continue
            rec.t_done = time.perf_counter()
            rec.entries = len(answer)
            seen += 1
            if len(sample) < self.keep_per_client:
                sample.append((answer, meta))
            else:   # reservoir: every answer equally likely to be kept
                j = int(keep_rng.integers(0, seen))
                if j < self.keep_per_client:
                    sample[j] = (answer, meta)
        with self._lock:
            self.kept.extend(sample)

    def join(self, timeout: float = DRAIN_S) -> None:
        deadline = time.perf_counter() + timeout
        for th in self._threads:
            th.join(max(0.0, deadline - time.perf_counter()))

    def at_close(self) -> dict:
        return {}

    def stop(self) -> None:
        pass

    def close(self) -> None:
        """Drop every reference into the program's state."""
        self.dep = None


def view_of(state: dict, keys, fields):
    ids = np.nonzero(state["alive"])[0]
    return SimpleNamespace(row_idx=ids, keys=[keys[i] for i in ids],
                           values={f: state[f][ids] for f in fields})
