#!/usr/bin/env python3
"""Where a cell's search time goes, by the program's own spans.

    python3 perfbench/spanreport.py --workload pancake-10.search --seed 7 \\
        --seconds 51

from the root of a checkout, on a machine whose JAX finds the chips the
cell asks for.  It makes the run that ``run.py --trace 1`` makes, with the
program's obs spans on for the window and each span also a profiler
annotation (``obs.enable(annotate=jax.profiler.TraceAnnotation)``), and
reduces the trace with ``spanfold``.  Standard error gets the run's own lines, then
one line per search of the seconds under each span name and the
device-idle seconds under each, on the profiler's clock, and the levels of
the first search.  The last line of standard output is one JSON object:
the run's result line under ``run``; under ``program`` the numbers of
``spanfold.metrics``, the window's ``implicit`` counters, and the tables
``idle_by_span``, ``device_by_scope`` and ``levels``.  Without a TPU, or
with fewer chips than the cell asks for, it exits 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ranked(seconds: Dict[str, float]) -> List:
    return [[k, v] for k, v in sorted(seconds.items(), key=lambda kv: -kv[1])]


def print_program(program: Dict, log) -> None:
    """One line per search: seconds under each span name and the
    device-idle seconds under each; then the levels of the first search."""
    for i, s in enumerate(program["per_search"]):
        spans = ", ".join(f"{k} {v:.4f}"
                          for k, v in sorted(s["span_s"].items()))
        idle = ", ".join(f"{k} {v:.4f}" for k, v in _ranked(s["idle_s"]))
        print(f"search {i} ({s['seconds']:.4f} s): {spans}; "
              f"device idle under {idle}", file=log)
    print("levels of the first search (level, frontier, device s, host s): "
          f"{program['levels']}", file=log)


def report(spec: Dict, cell_name: str, seed: int, seconds: float, *,
           log=sys.stderr, **run_kw) -> Dict:
    """One traced run of one cell with the program's spans on for its
    window; returns ``{"run": <result line>, "program": ...}``.  ``run_kw``
    goes to ``harness.run_cell``."""
    import jax
    from perfbench import harness, spanfold, tracefold
    from repro.core import obs
    trace_dir = run_kw.setdefault("trace_dir", harness.TRACE_DIR)
    counted = []
    searches = harness._searches

    # The spans and the counters cover the window alone, not the set-up
    # search; ``run_cell`` has no hook around its window, so this wraps
    # the function that runs it.
    def window(*args):
        obs.enable(annotate=jax.profiler.TraceAnnotation)
        try:
            with obs.scope() as scope:
                counted.append(scope)
                return searches(*args)
        finally:
            obs.disable()

    harness._searches = window
    try:
        result = harness.run_cell(spec, cell_name, seed, seconds, True,
                                  log=log, **run_kw)
    finally:
        harness._searches = searches
    path = tracefold.find_xplane(trace_dir)
    trace = tracefold.load_xplane(path)
    trace.update(spanfold.load_program(path))
    program = spanfold.fold(trace)
    counters = counted[0].delta().get("implicit", {})
    print_program(program, log)
    return {"run": result, "program": {
        "metrics": spanfold.metrics(program, counters, result["attempted"]),
        "counters": counters,
        "idle_by_span": _ranked(program["idle_by_span"]),
        "device_by_scope": _ranked(program["device_by_scope"]),
        "levels": program["levels"]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perfbench import harness
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    try:
        out = report(harness.load_spec(ROOT), args.workload, args.seed,
                     args.seconds, t_start=T_START)
    except harness.NoChip as e:
        print(f"spanreport: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
