#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 perfbench/run.py --workload pancake-10.search --seed 7 \\
        --seconds 40 --trace 0

from the root of a checkout, on a machine whose JAX finds the TPU chips
the cell asks for.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the same
window.  The last line of standard output is one JSON object; the numbers
that decide ``correct`` are the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, it exits 1 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # The compile cache lives at one fixed path inside the checkout, so
    # that only a cell's first run there compiles; set before JAX loads.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        from perfbench import harness
        from repro.launch.compile_cache import enable_compile_cache
        spec = harness.load_spec(ROOT)
        print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
        result = harness.run_cell(spec, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_start=T_START)
    except (ImportError, OSError, KeyError) as e:
        print(f"perfbench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    except harness.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
