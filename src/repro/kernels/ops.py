"""Public jit'd entry points for the Pallas kernels.

``impl`` picks the implementation:
  * "pallas"    the compiled Mosaic kernel (needs a TPU);
  * "interpret" the same kernel in Pallas interpret mode (any backend;
                how the CPU tests validate the kernels);
  * "ref"       the pure-jnp oracle in ref.py;
  * "auto"      "pallas" when JAX's default backend is a TPU, else "ref".
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import bitpack as _bp
from . import bucket_scatter as _bs
from . import flash_attention as _fa
from . import mamba_scan as _ms
from . import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if _on_tpu() else "ref"


# ------------------------------------------------------------- attention

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_kernel_vjp(q, k, v, causal, window, softcap, scale, block_q,
                      block_k, interpret):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)


def _flash_fwd(q, k, v, causal, window, softcap, scale, block_q, block_k,
               interpret):
    o, lse = _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        return_lse=True)
    return o, (q, k, v, o, lse)


def _flash_bwd_impl(causal, window, softcap, scale, block_q, block_k,
                    interpret, res, do):
    from .flash_attention_bwd import flash_attention_bwd
    q, k, v, o, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    if g > 1:                                 # GQA: sum the query group
        skv = k.shape[2]
        dk = dk.reshape(b, hkv, g, skv, d).sum(2)
        dv = dv.reshape(b, hkv, g, skv, d).sum(2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_kernel_vjp.defvjp(_flash_fwd, _flash_bwd_impl)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "impl", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, impl="auto", block_q=128, block_k=128):
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        return _flash_kernel_vjp(q, k, v, causal, window, softcap, scale,
                                 block_q, block_k, mode == "interpret")
    return _ref.attention_ref(q, k, v, causal=causal, window=window,
                              softcap=softcap, scale=scale, block_k=block_k)


# ------------------------------------------------------------ mamba scan

@functools.partial(jax.jit, static_argnames=("impl", "block_d", "block_t"))
def mamba_scan(x, dt, a, b, c, d, *, impl="auto", block_d=256, block_t=128):
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        bsz, seq, di = x.shape
        bt = min(block_t, seq)
        pad = (-seq) % bt
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
            c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        bd = block_d
        while di % bd:
            bd //= 2
        y = _ms.mamba_scan(x, dt, a, b, c, d, block_d=bd, block_t=bt,
                           interpret=(mode == "interpret"))
        return y[:, :seq]
    # ref path: the associative form materializes (B, L, Di, N) — fine for
    # tests, ruinous at dry-run scale. Long sequences use the sequential
    # scan, whose live state matches the Pallas kernel's VMEM footprint.
    if x.shape[1] > 512:
        return _ref.mamba_scan_seq_ref(x, dt, a, b, c, d)
    return _ref.mamba_scan_ref(x, dt, a, b, c, d)


# --------------------------------------------------------- bucket scatter

@functools.partial(jax.jit, static_argnames=("impl", "block_m"))
def bucket_scatter_add(table, idx, payload, *, impl="auto", block_m=256):
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        return _bs.bucket_scatter_add(table, idx, payload, block_m=block_m,
                                      interpret=(mode == "interpret"))
    return _ref.bucket_scatter_add_ref(table, idx, payload)


# --------------------------------------------------------------- bitpack

@functools.partial(jax.jit, static_argnames=("lut", "count_val", "impl",
                                             "block_w"))
def bitpack_lut_count(packed, lut, count_val, *, impl="auto",
                      block_w=_bp.ROW_BLOCK):
    """Map each 2-bit field of the packed words through the 4-entry LUT and
    count fields that map to ``count_val`` (over ALL W·16 fields — callers
    with fewer logical elements correct for their padding fields)."""
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        return _bp.bitpack_lut_count(packed, lut, count_val, block_w=block_w,
                                     interpret=(mode == "interpret"))
    return _ref.bitpack_lut_count_ref(packed, lut, count_val)


@functools.partial(jax.jit, static_argnames=("mark", "only_if", "impl",
                                             "block_m"))
def bitpack_scatter_mark(packed, idx, *, mark=2, only_if=0, impl="auto",
                         block_m=_bp.DEFAULT_BM):
    """packed[idx]'s 2-bit field ← mark where it currently holds only_if;
    out-of-range indices dropped, duplicates safe (first mark wins)."""
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        return _bp.bitpack_scatter_mark(packed, idx, mark=mark,
                                        only_if=only_if, block_m=block_m,
                                        interpret=(mode == "interpret"))
    return _ref.bitpack_scatter_mark_ref(packed, idx, mark, only_if)


@functools.partial(jax.jit, static_argnames=("lut", "count_val", "mark",
                                             "only_if", "impl", "block_m"))
def bitpack_mark_rotate_count(packed, idx, lut, count_val, *, mark=2,
                              only_if=0, impl="auto",
                              block_m=_bp.DEFAULT_BM):
    """Fused scatter-mark + lut-rotate + count — the implicit BFS's whole
    per-level array pass in one kernel (one HBM traversal of the packed
    words instead of two).  Semantics are exactly bitpack_scatter_mark
    followed by bitpack_lut_count; the count covers ALL W·16 fields."""
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        return _bp.bitpack_mark_rotate_count(
            packed, idx, lut, count_val, mark=mark, only_if=only_if,
            block_m=block_m, interpret=(mode == "interpret"))
    return _ref.bitpack_mark_rotate_count_ref(packed, idx, lut, count_val,
                                              mark, only_if)


def bitpack_gather2(packed, idx, *, impl="auto", page_words=512,
                    block_m=256):
    """Gather the 2-bit field for each element index (OOB/negative → 0) —
    the serving tier's Tier J batched-lookup path.  NOT jit-wrapped as a
    whole: the kernel path bins queries to pages host-side (numpy in
    bitpack.gather2_plan, data-dependent shapes), exactly like the oracle
    server bins queries to chunks; the pallas_call itself compiles."""
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        return _bp.bitpack_gather2(packed, idx, page_words=page_words,
                                   block_m=block_m,
                                   interpret=(mode == "interpret"))
    return _ref.bitpack_gather2_ref(packed, idx)
