"""Benchmark harness — one family per paper construct/claim.

Prints ``name,us_per_call,derived`` CSV (the harness contract). Sections:
  constructs   paper §3 programming constructs on Tier J
  pancake      the paper's flagship BFS app, tier J vs real-disk vs oracle
  disk         Tier-D streaming primitives (external sort, merge, reduce)
  moe          Roomy dispatch vs einsum baseline (8 fake devices)
  lm           per-family train/decode step wall times (smoke configs)
  serve        distance-oracle serving tier: QPS + p50/p99 under
               concurrent closed-loop clients at a starved LRU budget

A section that raises prints ``<section>_FAILED`` and the others still
run, but the exit code is then non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=("constructs", "pancake", "bfs",
                                       "disk", "moe", "lm", "serve"))
    ap.add_argument("--pancake-n", type=int, default=7)
    ap.add_argument("--shards", type=int, default=0,
                    help="also benchmark the sharded Tier D runtime with "
                         "N shards (bfs section; 0 = skip)")
    ap.add_argument("--compress", action="store_true",
                    help="also benchmark compressed runs (bfs section; the "
                         "rows report stored bytes/level + raw/stored ratio "
                         "from the codec ledger and surface as unchecked "
                         "NOTEs in benchmarks/compare.py until folded into "
                         "the baseline)")
    ap.add_argument("--json", metavar="PATH",
                    help="also dump results as JSON (the BENCH trajectory "
                         "record: {section: [{name, us_per_call, derived}]})")
    args = ap.parse_args()

    from repro.launch import compile_cache

    from . import constructs, disk_tier, lm_step, moe_dispatch, pancake
    compile_cache.enable_compile_cache()

    def bench_bfs_section():
        # Imported lazily: bfs pulls in examples/cayley_bfs.py via a path
        # hack, and an import failure there must not take down the other
        # sections (the try/except below only guards section execution).
        from . import bfs
        return bfs.bench_bfs(args.pancake_n, shards=args.shards,
                             compress=args.compress)

    def bench_serve_section():
        # Lazy for the same examples path hack; its own section keeps the
        # CI gate (--section bfs) and BENCH_baseline.json untouched.
        from . import serve
        return serve.bench_serve(args.pancake_n)

    sections = {
        "constructs": lambda: constructs.bench_constructs(),
        "pancake": lambda: pancake.bench_pancake(args.pancake_n),
        "bfs": bench_bfs_section,
        "disk": lambda: disk_tier.bench_disk(),
        "moe": lambda: moe_dispatch.bench_moe_dispatch(),
        "lm": lambda: lm_step.bench_lm_steps(),
        "serve": bench_serve_section,
    }
    # Schema: sections always maps to a LIST of row dicts (empty on
    # failure); errors live in a separate map so consumers can iterate
    # sections uniformly.
    record = {"timestamp": time.time(), "sections": {}, "errors": {}}
    print("name,us_per_call,derived")
    for name, fn in sections.items():
        if args.only and name != args.only:
            continue
        try:
            rows = list(fn())
            for row in rows:
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
                sys.stdout.flush()
            record["sections"][name] = [
                {"name": r[0], "us_per_call": r[1], "derived": r[2]}
                for r in rows]
        except Exception as e:                # a failed section must not
            print(f"{name}_FAILED,0,{e!r}")   # hide the others
            record["sections"][name] = []
            record["errors"][name] = repr(e)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
    return 1 if record["errors"] else 0


if __name__ == "__main__":
    sys.exit(main())
