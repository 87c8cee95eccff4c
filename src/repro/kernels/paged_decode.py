"""Pallas TPU paged-decode attention — flash-decoding over the Roomy pages.

The serving hot loop: one query token per sequence attends over a paged KV
cache WITHOUT materializing the contiguous (B, S, kvh, hd) gather that the
jnp path builds (paged.gather). The page table is a *scalar-prefetch*
operand, so each grid step's BlockSpec index_map dereferences the table and
DMAs exactly one physical page — random page placement costs nothing (the
Roomy access pattern, resolved at the DMA level).

Grid: (batch, kv_heads, pages_per_seq←sequential). Per step: one (ps, hd)
K/V page against the query group's (g, hd) rows, online-softmax merged in
VMEM scratch. HBM traffic = the live cache bytes, once.

GQA: the q heads of one kv head's group ride along in the block (g = Hq/Hkv
rows) — one MXU matmul per page per group.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, ps: int, softcap, scale: float):
    b = pl.program_id(0)
    pi = pl.program_id(2)
    npages = pl.num_programs(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = len_ref[b]
    page_start = pi * ps

    @pl.when(page_start < seq_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (g, hd)
        k = k_ref[0, :, 0].astype(jnp.float32)         # (ps, hd)
        v = v_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < seq_len
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_cur

    @pl.when(pi == npages - 1)
    def _finish():
        l = l_ref[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,           # (B, Hq, hd)
    k_pages: jax.Array,     # (num_pages, ps, kvh, hd)
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, pps) int32 physical page ids
    lengths: jax.Array,     # (B,) int32
    *,
    softcap: float | None = None,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, Hq, hd) in q.dtype."""
    b, hq, hd = q.shape
    _, ps, kvh, _ = k_pages.shape
    pps = page_table.shape[1]
    g = hq // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, kvh, g, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, pps),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda bb, h, pi, tbl, ln: (bb, h, 0, 0)),
            pl.BlockSpec((1, ps, 1, hd),
                         lambda bb, h, pi, tbl, ln: (tbl[bb, pi], 0, h, 0)),
            pl.BlockSpec((1, ps, 1, hd),
                         lambda bb, h, pi, tbl, ln: (tbl[bb, pi], 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bb, h, pi, tbl, ln: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ps=ps, softcap=softcap, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="roomy_paged_decode",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(b, hq, hd)
