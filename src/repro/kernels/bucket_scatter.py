"""Pallas TPU segment scatter-add — the apply phase of a Roomy sync.

After the bucket exchange (core/delayed.py) every shard holds a batch of
(index, payload) update ops destined for its local table slice (embedding
gradients, hashtable values, KV pages). The sync sorts ops by index, so the
kernel sees *runs* of equal indices and can accumulate each run in VMEM,
touching the table once per run instead of once per op — the random-write →
streaming-write conversion that is the heart of the paper.

Correctness does not depend on sortedness (every index change just flushes
the run accumulator through a read-modify-write), so the oracle can be
plain segment_sum; sorted input is purely a performance property.

Mechanics: one sequential grid axis over op blocks; scratch carries the
current run (index in SMEM, (1, D) accumulator in VMEM) across blocks. The
table block must fit VMEM — callers tile big tables into bucket slices
first (which the Roomy layout already provides). Masked flushes go to a
trash row appended at table index N, avoiding data-dependent control flow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256


def _scatter_kernel(idx_ref, pay_ref, tab_ref, out_ref, cur_ref, acc_ref, *,
                    bm: int, n_rows: int):
    blk = pl.program_id(0)
    nblk = pl.num_programs(0)

    @pl.when(blk == 0)
    def _init():
        out_ref[...] = tab_ref[...]
        cur_ref[0] = n_rows                      # trash row
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(i, _):
        row_idx = idx_ref[i, 0]
        cur = cur_ref[0]
        boundary = row_idx != cur
        # Flush the finished run to its row (or to trash if mid-run).
        tgt = jnp.where(boundary, jnp.minimum(cur, n_rows), n_rows)
        out_ref[pl.ds(tgt, 1), :] = out_ref[pl.ds(tgt, 1), :] + acc_ref[...]
        pay = pay_ref[i].astype(jnp.float32)[None, :]
        acc_ref[...] = jnp.where(boundary, pay, acc_ref[...] + pay)
        cur_ref[0] = row_idx
        return 0

    jax.lax.fori_loop(0, bm, body, 0)

    @pl.when(blk == nblk - 1)
    def _final_flush():
        tgt = jnp.minimum(cur_ref[0], n_rows)
        out_ref[pl.ds(tgt, 1), :] = out_ref[pl.ds(tgt, 1), :] + acc_ref[...]


def bucket_scatter_add(
    table: jax.Array,    # (N, D) f32 — the owner's table slice
    idx: jax.Array,      # (M,) int32; idx >= N (or == N) means "drop"
    payload: jax.Array,  # (M, D)
    *,
    block_m: int = DEFAULT_BM,
    interpret: bool = False,
) -> jax.Array:
    """table[idx[i]] += payload[i] for all i; out-of-range indices dropped.

    Returns the updated (N, D) table. Sorted idx is faster (fewer RMWs) but
    not required.
    """
    n, d = table.shape
    m = idx.shape[0]
    bm = min(block_m, m)
    m_pad = -(-m // bm) * bm
    if m_pad != m:
        idx = jnp.pad(idx, (0, m_pad - m), constant_values=n)
        payload = jnp.pad(payload, ((0, m_pad - m), (0, 0)))
    idx = jnp.minimum(idx.astype(jnp.int32), n).reshape(m_pad, 1)
    tab_p = jnp.concatenate([table.astype(jnp.float32),
                             jnp.zeros((1, d), jnp.float32)], axis=0)

    kernel = functools.partial(_scatter_kernel, bm=bm, n_rows=n)
    out = pl.pallas_call(
        kernel,
        grid=(m_pad // bm,),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),          # idx
            pl.BlockSpec((bm, d), lambda i: (i, 0)),          # payload
            pl.BlockSpec((n + 1, d), lambda i: (0, 0)),       # table
        ],
        out_specs=pl.BlockSpec((n + 1, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n + 1, d), jnp.float32),
        scratch_shapes=[
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="roomy_bucket_scatter",
    )(idx, payload, tab_p)
    return out[:n].astype(table.dtype)
