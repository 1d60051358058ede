"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and merges the same rows into
``BENCH_results.json`` (the CI artifact) *per table*: a run replaces only
the tables it attempted, so a partial or BENCH_TABLES-filtered run no
longer clobbers earlier results. Set BENCH_N / BENCH_APP_N / BENCH_BATCH_N
/ BENCH_STORE_N / BENCH_SHARD_N / BENCH_SHARDS / BENCH_SERVE_* /
BENCH_INGEST_* to scale (defaults sized for a single CPU core), and
BENCH_TABLES to a comma-separated list of table keys (e.g.
``table5,table7``) to run a subset. Compiled programs persist in
``JAX_COMPILATION_CACHE_DIR`` when it is set, else in ``.jax_cache/``
at the repository root (``kernels/launch.use_compile_cache``).
"""
from __future__ import annotations

import json
import os
import sys
import traceback

# BENCH_DEVICES=N forces N host CPU devices (the device-parallel sharded
# rows in table7 need a real mesh). The count is locked at first jax init,
# so this must run at module top — before the benchmark modules import.
_DEV = os.environ.get("BENCH_DEVICES", "").strip()
if _DEV and _DEV != "0":
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={int(_DEV)}").strip()

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_RESULTS = os.path.join(_ROOT, "BENCH_results.json")


def _table_key(mod) -> str:
    """'benchmarks.table5_batched' -> 'table5' (matches its row prefixes)."""
    return mod.__name__.split(".")[-1].split("_")[0]


def _merge(path: str, attempted: set[str], results: list[dict],
           failures: list[str]) -> dict:
    """Per-table merge: rows and failures of tables NOT attempted by this
    run survive; attempted tables are replaced wholesale."""
    old_results: list[dict] = []
    old_failures: list[str] = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
            old_results = old.get("results", [])
            old_failures = old.get("failures", [])
        except (json.JSONDecodeError, OSError):
            pass  # unreadable history: start fresh
    keep = [r for r in old_results
            if r.get("name", "").split(".")[0] not in attempted]
    keep_fail = [f for f in old_failures
                 if f.split(".")[-1].split("_")[0] not in attempted]
    return {"results": keep + results, "failures": keep_fail + failures}


def main() -> None:
    # robust to both `python benchmarks/run.py` and `python -m benchmarks.run`
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    sys.path.insert(0, _ROOT)
    from repro.kernels.launch import use_compile_cache
    use_compile_cache(_ROOT)
    from benchmarks import (fig1_growth, roofline_table, table1_lifecycle,
                            table2_incremental, table3_split,
                            table4_application, table5_batched,
                            table6_storage, table7_sharding, table9_serving,
                            table10_observability, table11_kernels,
                            table12_ingest)
    mods = [table1_lifecycle, table2_incremental, table3_split,
            table4_application, table5_batched, table6_storage,
            table7_sharding, table9_serving, table10_observability,
            table11_kernels, table12_ingest, fig1_growth, roofline_table]
    only = {w.strip() for w in os.environ.get("BENCH_TABLES", "").split(",")
            if w.strip()}
    if only:
        unknown = only - {_table_key(m) for m in mods}
        if unknown:
            print(f"BENCH_TABLES names unknown tables: {sorted(unknown)}",
                  file=sys.stderr)
            sys.exit(2)
        mods = [m for m in mods if _table_key(m) in only]
    print("name,us_per_call,derived")
    results = []
    failures = []
    for mod in mods:
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.3f},{derived}")
                results.append({"name": name, "us_per_call": us,
                                "derived": derived})
        except Exception:
            failures.append(mod.__name__)
            print(f"{mod.__name__},NaN,FAILED", file=sys.stderr)
            traceback.print_exc()
    merged = _merge(_RESULTS, {_table_key(m) for m in mods}, results,
                    failures)
    with open(_RESULTS, "w") as f:
        json.dump(merged, f, indent=2)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
