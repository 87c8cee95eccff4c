"""Roomy MoE dispatch vs einsum baseline — wall time on a host mesh and
the FLOP argument (the einsum path burns O(T·E·C·d) in one-hot matmuls;
the Roomy path doesn't). The production-scale collective comparison lives
in the dry-run (§Perf); this is the runnable small-scale twin.

The child process runs on 8 fake CPU devices (``JAX_PLATFORMS=cpu``), so
it never competes with its parent for an accelerator; its rows time the
CPU backend.  A failed child raises.
"""
from __future__ import annotations

import subprocess
import sys
import time
from typing import List, Tuple


def bench_moe_dispatch() -> List[Tuple[str, float, str]]:
    # run in a subprocess with 8 fake devices so the Roomy path has a mesh
    code = """
import time, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.moe import init_moe, moe_einsum, moe_roomy
cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True).replace(
    kernels="ref", dtype="float32", n_experts=8, top_k=2,
    d_model=128, d_ff=256, capacity_factor=2.0)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
p = init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (16, 64, cfg.d_model))
f_e = jax.jit(lambda p, x: moe_einsum(p, x, cfg))
f_r = jax.jit(lambda p, x: moe_roomy(p, x, cfg, mesh))
for name, f in (("einsum", f_e), ("roomy", f_r)):
    f(p, x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        f(p, x).block_until_ready()
    us = (time.perf_counter() - t0) / 5 * 1e6
    print(f"RESULT {name} {us:.1f}")
"""
    import os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        raise RuntimeError(f"moe dispatch child failed: {proc.stderr[-2000:]}")
    rows = []
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT"):
            _, name, us = line.split()
            rows.append((f"moe_dispatch_{name}", float(us),
                         "tokens=1024 experts=8 top2 (8 fake CPU devices)"))
    return rows
