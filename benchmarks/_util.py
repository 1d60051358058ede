"""Benchmark helpers: timing + synthetic UniProt-like releases."""
from __future__ import annotations

import time

import numpy as np


def timeit(fn, *, reps: int = 3, warmup: int = 0):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.mean(ts)), float(np.std(ts))


def frac(x: float | None) -> str:
    """A roofline fraction for a derived column; ``none`` where the device
    has no peaks to divide by (``launch/roofline.PEAKS``)."""
    return "none" if x is None else f"{x:.4f}"


def synth_release(n_entries: int, seq_w: int = 64, *, seed: int = 0,
                  base=None, frac_updated: float = 0.0, n_new: int = 0,
                  n_deleted: int = 0):
    """Synthetic parsed UniProtKB-like release: (keys, table).

    With `base`, derives the next release: `frac_updated` of entries get new
    sequences (significant churn), everyone gets fresh annotation (the
    annotation-churn regime of real UniProt releases), `n_new` appended,
    `n_deleted` dropped."""
    rng = np.random.default_rng(seed)
    if base is None:
        keys = [f"P{i:08d}" for i in range(n_entries)]
        table = {
            "sequence": rng.integers(0, 25, (n_entries, seq_w)).astype(np.int32),
            "length": rng.integers(50, 400, (n_entries, 1)).astype(np.int32),
            "annotation": rng.integers(0, 100, (n_entries, 8)).astype(np.int32),
        }
        return keys, table
    keys0, tbl0 = base
    keep = len(keys0) - n_deleted
    keys = list(keys0[:keep])
    table = {k: v[:keep].copy() for k, v in tbl0.items()}
    n_upd = int(frac_updated * keep)
    upd = rng.choice(keep, size=n_upd, replace=False)
    table["sequence"][upd] = rng.integers(0, 25, (n_upd, table["sequence"].shape[1]))
    table["annotation"] = rng.integers(0, 100, table["annotation"].shape).astype(np.int32)
    start = int(keys0[-1][1:]) + 1
    for i in range(n_new):
        keys.append(f"P{start + i:08d}")
    if n_new:
        rngn = np.random.default_rng(seed + 1)
        for name, v in list(table.items()):
            roww = v.shape[1]
            newv = (rngn.integers(0, 25, (n_new, roww)).astype(np.int32)
                    if name != "length" else
                    rngn.integers(50, 400, (n_new, 1)).astype(np.int32))
            table[name] = np.concatenate([v, newv])
    return keys, table
