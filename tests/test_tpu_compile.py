"""Compiles of the Tier J implicit-BFS path for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse — more scoped VMEM than a kernel may use, more HBM than the chip
holds.  The shapes are those of pancake n=11 (39,916,800 states), the size
chip_smoke.py runs, plus the n=8 scatter that the earlier one-lane table
layout could not fit.

The topology is described only inside the module fixture, so that test
collection never loads the TPU library; the persistent compilation cache
is off around these compiles (an entry written for a described chip
cannot be read back without one).
"""
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import constructs as C
from repro.kernels import bitpack as bp

sys.path.append(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "examples"))
from pancake_bits import neighbor_jnp  # noqa: E402

N11_STATES = math.factorial(11)
N11_WORDS = -(-N11_STATES // 16)
N11_BLOCK_OPS = C.IMPLICIT_BLOCK * 10    # one level block's marks at n=11
V5E_HBM_BYTES = 16 * 10**9
ROTATE_LUT = bp.make_lut([0, 3, 1, 3])


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"    # else the compiler logs to /tmp

    def restore_log_dir():
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        restore_log_dir()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()
    restore_log_dir()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _shapes(one_chip, n_words, n_ops):
    return (jax.ShapeDtypeStruct((n_words,), jnp.uint32, sharding=one_chip),
            jax.ShapeDtypeStruct((n_ops,), jnp.int32, sharding=one_chip))


KERNELS = {
    "lut_count": lambda p, i: bp.bitpack_lut_count(p, ROTATE_LUT, 1),
    "scatter_mark": lambda p, i: bp.bitpack_scatter_mark(p, i),
    "mark_rotate_count": lambda p, i: bp.bitpack_mark_rotate_count(
        p, i, ROTATE_LUT, 1),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_at_n11(one_chip, name):
    packed, idx = _shapes(one_chip, N11_WORDS, N11_BLOCK_OPS)
    compiled = _compile(KERNELS[name], packed, idx)
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    # The op indices reach the kernel flat: no 128-lane padding per op.
    unpadded = 4 * (N11_WORDS + (N11_BLOCK_OPS if name != "lut_count" else 0))
    assert ma.argument_size_in_bytes < 1.01 * unpadded


def test_scatter_mark_compiles_at_n8(one_chip):
    # Pancake n=8: 2,520 words and 282,240 marks a level.  With the table
    # and the indices one lane wide this was refused for 21.02M of scoped
    # VMEM, and the indices alone took 144 MB of padding.
    n_ops = math.factorial(8) * 7
    packed, idx = _shapes(one_chip, math.factorial(8) // 16, n_ops)
    for name in ("scatter_mark", "mark_rotate_count"):
        ma = _compile(KERNELS[name], packed, idx).memory_analysis()
        assert ma.temp_size_in_bytes < 4 * 4 * n_ops


def test_implicit_level_fits_hbm_at_n11(one_chip):
    level = functools.partial(C._implicit_level, n_states=N11_STATES,
                              neighbor_fn=neighbor_jnp(11), impl="pallas")
    packed = jax.ShapeDtypeStruct((N11_WORDS,), jnp.uint32, sharding=one_chip)
    ma = _compile(level, packed).memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < V5E_HBM_BYTES
