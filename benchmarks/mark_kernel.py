#!/usr/bin/env python3
"""Kernel-only timing of the Tier J mark kernels on one TPU chip.

Runs ``bitpack_scatter_mark`` and ``bitpack_mark_rotate_count`` over the
pancake-10 table (10! states, 226,800 packed words) with one level block's
batch of marks (907,200 states x 9 generators = 8,164,800 ops), for batches
whose share of dead ops (aimed at the trash word) differs:

  dead_0          every op live
  dead_50         half the ops dead, scattered one by one
  dead_91.7       11 of 12 ops dead, scattered one by one (a frontier share
                  of 1/12, as a pancake-10 search sends on average)
  dead_91.7_blocks  the same share, but whole op blocks of DEFAULT_BM are
                  live or dead, so the dead ops fill whole blocks
  dead_100        every op dead

Each call's device time is read from a profiler trace (the durations of the
``roomy_bitpack_*`` ops), ``--calls`` calls a case after one warm-up call;
the median is reported, with ns per op sent, and the derived ns per live op
and per dead op.  Usage, from the repository root, on a machine with a TPU:

    PYTHONPATH=src python benchmarks/mark_kernel.py [--calls 5] [--seed 0] \\
        [--json out.json]

Without a TPU it exits 1.  The last line of standard output is one JSON
object with every reading.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import statistics
import sys
import tempfile

N_STATES = math.factorial(10)
N_WORDS = N_STATES // 16
N_OPS = 907_200 * 9
KERNEL_TAG = "roomy_bitpack_"
CASES = ("dead_0", "dead_50", "dead_91.7", "dead_91.7_blocks", "dead_100")


def batch(case: str, rng, block_m: int):
    """The op indices of a case: live ones uniform over the table."""
    import numpy as np
    cap = N_WORDS * 16
    idx = rng.integers(0, N_STATES, N_OPS, dtype=np.int64).astype(np.int32)
    if case == "dead_50":
        dead = rng.random(N_OPS) < 0.5
    elif case == "dead_91.7":
        dead = rng.random(N_OPS) < 11 / 12
    elif case == "dead_91.7_blocks":
        nb = -(-N_OPS // block_m)
        live_blocks = rng.permutation(nb)[:round(nb / 12)]
        dead = np.ones(nb * block_m, bool)
        for b in live_blocks:
            dead[b * block_m:(b + 1) * block_m] = False
        dead = dead[:N_OPS]
    elif case == "dead_100":
        dead = np.ones(N_OPS, bool)
    else:
        dead = np.zeros(N_OPS, bool)
    idx[dead] = cap
    return idx, int((~dead).sum())


def kernel_ns(trace_dir: str):
    """Durations in ns of the mark kernels' device ops in a trace."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    data = ProfileData.from_file(path)
    planes = sorted(p.name for p in data.planes
                    if p.name.startswith("/device:TPU:"))
    out = []
    for plane in data.planes:
        if plane.name != planes[0]:
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                out += [int(e.duration_ns) for e in line.events
                        if KERNEL_TAG in e.name]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("mark_kernel: no TPU found", file=sys.stderr)
        return 1
    from repro.kernels import bitpack as bp

    lut = bp.make_lut([0, 3, 1, 3])
    kernels = {
        "scatter_mark": jax.jit(lambda t, i: bp.bitpack_scatter_mark(t, i)),
        "mark_rotate_count": jax.jit(
            lambda t, i: bp.bitpack_mark_rotate_count(t, i, lut, 1)[0]),
    }
    rng = np.random.default_rng(args.seed)
    table = jnp.asarray(rng.integers(0, 1 << 32, N_WORDS, dtype=np.uint64)
                        .astype(np.uint32))
    dev = jax.devices()[0]
    result = {"device": dev.device_kind, "n_ops": N_OPS, "n_words": N_WORDS,
              "calls": args.calls, "cases": {}}
    for case in CASES:
        idx_np, n_live = batch(case, rng, bp.DEFAULT_BM)
        idx = jnp.asarray(idx_np)
        row = {"live_ops": n_live}
        for name, fn in kernels.items():
            jax.block_until_ready(fn(table, idx))        # compile, warm up
            with tempfile.TemporaryDirectory() as d:
                with jax.profiler.trace(d):
                    t = table
                    for _ in range(args.calls):
                        t = fn(t, idx)
                    jax.block_until_ready(t)
                times = kernel_ns(d)
            med = statistics.median(times)
            row[name] = {"calls_ns": times, "median_ns": med,
                         "ns_per_op": med / N_OPS}
        result["cases"][case] = row
        print(case, json.dumps(row), file=sys.stderr)

    for name in kernels:
        t = {c: result["cases"][c][name]["median_ns"] for c in CASES}
        live = t["dead_0"] / N_OPS
        derived = {"ns_per_live_op": live,
                   "ns_per_dead_op_in_dead_blocks": t["dead_100"] / N_OPS}
        for c in ("dead_50", "dead_91.7", "dead_91.7_blocks"):
            n_live = result["cases"][c]["live_ops"]
            derived[f"ns_per_dead_op.{c}"] = (
                (t[c] - n_live * live) / (N_OPS - n_live))
        result[name] = derived
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
