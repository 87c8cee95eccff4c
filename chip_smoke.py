#!/usr/bin/env python3
"""Smoke run of the Tier J implicit BFS on one TPU chip.

Searches the pancake graph at n=11 (39,916,800 states, a 10 MB packed
2-bit array) through ``repro.core.constructs.implicit_bfs`` with the
Pallas kernels, and checks its level counts three ways:

  (a) the same search with ``impl="ref"`` on the same chip gives the same
      counts;
  (b) the counts sum to n! over P(n) + 1 levels, P(n) the pancake number;
  (c) at n=9 the Tier J Pallas counts equal those of Tier D's numpy
      ``disk.implicit_bfs``, run in this process.

Usage, from the repository root on a machine with one TPU chip:

    python chip_smoke.py            # pancake n=11
    python chip_smoke.py --n 10

Everything runs in this one process.  It prints the device, the level
counts, the seconds each level took and the device's peak memory; its last
line is one JSON object naming the device.  A failed check, an error, or
the lack of a TPU exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import statistics
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
PANCAKE_NUMBER = {4: 4, 5: 5, 6: 7, 7: 8, 8: 9, 9: 10, 10: 11, 11: 13,
                  12: 14}
CHECK_N = 9                 # size of the Tier J vs Tier D comparison
WORK_DIR = os.path.join(REPO, ".chip_smoke_work")   # Tier D's files


def _start(n: int) -> int:
    import numpy as np
    from repro.core import ranking as R
    return int(R.rank_np(np.arange(n)[None, :])[0])


def search(n: int, impl: str):
    """Tier J pancake search; returns (level counts, seconds per level).

    The seconds come from the search's own ``bfs.level`` spans; a level's
    span ends when its count reaches the host, so it covers the device
    work.  The first level's span also covers compilation."""
    from pancake_bits import neighbor_jnp
    from repro.core import constructs as C
    from repro.core import obs
    obs.enable()
    try:
        sizes, _ = C.implicit_bfs(math.factorial(n), [_start(n)],
                                  neighbor_jnp(n), impl=impl)
        secs = [s["dur_us"] / 1e6 for s in obs.drain_spans()
                if s["sid"] == "bfs.level"]
    finally:
        obs.disable()
    return sizes, secs


def tier_d(n: int):
    """Tier D's implicit BFS over the same graph, single process, numpy."""
    from pancake_bits import neighbors_np
    from repro.core.disk import implicit_bfs as disk_implicit_bfs
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    try:
        sizes, _ = disk_implicit_bfs(WORK_DIR, math.factorial(n),
                                     [_start(n)], neighbors_np(n))
    finally:
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    return sizes


def report(label: str, sizes, secs) -> None:
    print(f"{label} level counts: {sizes}")
    print(f"{label} seconds per level: {secs}")
    steady = statistics.median(secs[1:]) if len(secs) > 1 else float("nan")
    print(f"{label} level 1 seconds (compile + run): {secs[0]}")
    print(f"{label} steady seconds per level (median of levels 2+): "
          f"{steady}")
    print(f"{label} compile seconds (level 1 less steady median): "
          f"{secs[0] - steady}")


def run_checks(n: int, check_n: int, impl: str):
    """Run the searches and the three checks; returns the failed checks."""
    sizes, secs = search(n, impl)
    report(f"pancake n={n} impl={impl}", sizes, secs)
    ref_sizes, ref_secs = search(n, "ref")
    report(f"pancake n={n} impl=ref", ref_sizes, ref_secs)
    small, _ = search(check_n, impl)
    disk = tier_d(check_n)
    print(f"pancake n={check_n} impl={impl} level counts: {small}")
    print(f"pancake n={check_n} tier D level counts: {disk}")
    checks = {
        f"(a) n={n} {impl} == ref": sizes == ref_sizes,
        f"(b) n={n} sum == n! over P(n)+1 levels":
            sum(sizes) == math.factorial(n)
            and len(sizes) == PANCAKE_NUMBER[n] + 1,
        f"(c) n={check_n} {impl} == tier D": small == disk,
    }
    for name, ok in checks.items():
        print(f"check {name}: {'ok' if ok else 'FAILED'}")
    return [name for name, ok in checks.items() if not ok]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=11, choices=(10, 11, 12),
                    help="pancake size of the timed search (default 11)")
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(REPO, "src"), os.path.join(REPO, "examples")]
    try:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import this repository's code ({e}); "
              "run it from the repository root", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this check runs only on a TPU chip",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}")
    print(f"compile cache: {enable_compile_cache()}")
    failed = run_checks(args.n, CHECK_N, "pallas")
    print(f"peak_bytes_in_use: "
          f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
    if failed:
        print(f"chip_smoke: failed checks: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
