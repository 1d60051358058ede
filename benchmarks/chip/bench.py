#!/usr/bin/env python3
"""The chip benchmark of GeStore: one run of one cell.

    python benchmarks/chip/bench.py --workload swissprot.versions \\
        --seed 123 --seconds 30 --trace 0

Run from the root of a checkout, on a machine whose JAX sees a TPU. The
cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (``chipbench/spec.py``). The run builds its data from
``--seed``, warms every shape the traffic uses (``setup_s``), measures
for ``--seconds``, and then compares a sample of the answers with a plain
numpy reference. ``--trace 1`` runs the window under the profiler and
reports the per-layer metrics instead of the end-to-end ones.

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace
1``), then ``checks``: each number compared with its limit, which are
also the last lines on stderr. With no TPU, fewer chips than the cell
asks for, or no program beside the benchmark, it exits non-zero and
prints no result.

The compile cache is ``<checkout>/.jax_cache``; data, stores and traces
go to ``<checkout>/.bench_work`` and are removed at exit. Every
``GESTORE_*`` variable is dropped before the program is imported, so
the program's built-in settings decide what runs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    from chipbench import run, spec
    run.own_environment(ROOT)

    try:
        cell = spec.load_cell(ROOT, args.workload)
    except spec.CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program beside the benchmark ({ROOT}/src/repro)",
              file=sys.stderr)
        return 2
    try:
        devices = run.require_chips(cell.chips)
    except run.NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(ROOT, "src"))
    d0 = devices[0]
    print(f"device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    result = run.run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START,
                          work_root=os.path.join(ROOT, ".bench_work"),
                          devices=devices)
    print(run.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
