#!/usr/bin/env python3
"""The control of a cell's ``correct``: the plain reference put in the
program's place with its guarantee broken, which the check has to fail.

    python3 perfbench/control.py --workload <cell> --seeds 1 2 3

For each seed it asks the configuration's search (``harness.load_search``)
for its control at the cell's size, from a start drawn from the seed, and
compares the control's level counts with the reference's as a run does.
What the control breaks is the search file's to say (its ``control``).
It prints, per seed, each number compared beside its limit and whether
the run would have been correct.  The benchmark's own runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_readings(spec, cell_name: str, seeds, bench_dir=None):
    """[(seed, numbers, correct)] of the control in the program's place."""
    from perfbench import harness
    bench_dir = bench_dir or harness.BENCH_DIR
    cell = harness.find_cell(spec, cell_name)
    config = harness.load_config(cell["config"], bench_dir)
    traffic = harness.load_traffic(cell_name, bench_dir)
    graph = harness.load_search(config, bench_dir)
    if graph.control is None:
        raise ValueError(f"configuration {cell['config']!r} has no control")
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        got, want = graph.control(np.random.default_rng(seed))
        search = harness.Search(0, got, 0, time.perf_counter() - t0)
        verdict = harness.check([search], [want], traffic)
        out.append((seed, verdict["numbers"], verdict["correct"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT]
    from perfbench import harness
    spec = harness.load_spec(ROOT)
    for seed, numbers, correct in control_readings(spec, args.workload,
                                                   args.seeds):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": correct, "checks": numbers}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
