#!/usr/bin/env python3
"""Readings of a cell's correctness check, for setting its limits: the
program's and the control's, over several seeds in one process.

    python benchmarks/chip/control.py --workload swissprot.versions \\
        --seeds 11,12,13 --seconds 20

Each seed is one whole run of the cell at its own size (set-up, a window
at the cell's load, the comparison with the plain reference). The
control is the reference put in the program's place with one guarantee
of the configuration broken (see ``check(control=True)`` of the cell's
op in ``ops/``), judged by the same limits as the program; it has to
come out not correct. One JSON line per seed, then one with the lowest
control reading, the highest program reading, and whether the control
was ever ruled correct. Needs the chips the cell asks for, like ``bench.py``; the
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    from chipbench import run, spec
    run.own_environment(ROOT)

    cell = spec.load_cell(ROOT, args.workload)
    try:
        devices = run.require_chips(cell.chips)
    except run.NoChip as e:
        print(f"control: {e}; refusing to run", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(ROOT, "src"))
    compiles = run.Compiles().install()
    program, control, verdicts = [], [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                           t_start=time.perf_counter(),
                           work_root=os.path.join(ROOT, ".bench_work"),
                           devices=devices, control=True, compiles=compiles)
        program.append(res["checks"]["wrong_rows"]["value"])
        control.append(res["control"]["checks"]["wrong_rows"]["value"])
        verdicts.append(res["control"]["correct"])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"],
                          "control": res["control"],
                          "metrics": res["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "program_wrong_rows_max": max(program),
                      "control_wrong_rows_min": min(control),
                      "control_ever_correct": any(verdicts)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
