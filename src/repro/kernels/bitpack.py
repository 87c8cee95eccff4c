"""Pallas TPU kernels for the 2-bit packed arrays (core/bitarray.py).

The implicit BFS engine stores 16 two-bit elements per uint32 word; its two
per-level hot paths are pure bit manipulation over the packed words, which
is exactly VPU-shaped work:

  bitpack_lut_count     the fused rotate+count pass: unpack each word's 16
                        fields, map them through a 4-entry LUT (encoded in
                        one uint32 scalar), repack, and count fields that
                        map to a target value — one streaming read-write
                        pass over the packed array, no unpacked (8× larger)
                        intermediate ever hits HBM.

  bitpack_scatter_mark  the sync apply phase: a batch of element indices
                        whose 2-bit field must become ``mark`` iff it
                        currently holds ``only_if`` (the OR-style visited
                        test of the BFS — marks on non-UNSEEN states are
                        absorbed).  Sequential read-modify-write per live
                        op on a VMEM-resident table; dropped ops are aimed
                        at a trash word and do no work: an op block with
                        no live op is skipped whole, and a block with some
                        first packs its live ops together, so a dead op
                        costs a scalar load and store.  The table must
                        fit VMEM: on v5e (128 MiB) one of up to 120 MiB
                        compiles, pancake n=12.

  bitpack_mark_rotate_count
                        the two fused into ONE kernel — the whole per-level
                        array pass of the implicit BFS: scatter the marks,
                        then LUT-rotate and count in the same VMEM
                        residency, so the packed table crosses HBM once per
                        level instead of twice (the Tier J twin of the disk
                        pass planner's fused read-write pass).

  bitpack_gather2       the serving tier's Tier J lookup path: gather the
                        2-bit fields for a vector of element indices out of
                        page-resident packed words.  Queries are binned to
                        pages HOST-side (gather2_plan — the oracle server's
                        chunk binning, numpy) and the kernel walks a
                        scalar-prefetched page table (the paged.py /
                        paged_decode.py idiom) so each grid step streams
                        exactly one page of packed words into VMEM.

The first three share one table layout (table_layout): the packed words
lane-dense as (rows, 128) uint32, so a word costs 4 bytes of VMEM and not
a 512-byte row.  All have pure-jnp oracles in ref.py and interpret-mode
CPU validation in tests/test_kernels.py; ops.py hosts the dispatching
wrappers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FIELDS_PER_WORD = 16
LANES = 128
ROW_BLOCK = 512          # table rows per block (256 KiB of uint32)
DEFAULT_BM = 2048        # scatter ops per SMEM block
PACK_UNROLL = 16         # ops per loop step when packing live ops
VMEM_HEADROOM = 16 << 20  # scoped VMEM beyond the table: rotate temporaries


def make_lut(table) -> int:
    """Encode a 4-entry value map [new0, new1, new2, new3] into one uint32
    scalar: entry v occupies bits [2v, 2v+2)."""
    assert len(table) == 4 and all(0 <= v <= 3 for v in table)
    return sum(int(v) << (2 * i) for i, v in enumerate(table))


# ------------------------------------------------------- table layout

def table_layout(n_words: int, row_block: int = ROW_BLOCK):
    """``(rows, rb)`` of the lane-dense ``(rows, 128)`` uint32 table all
    three array-pass kernels share.  It holds ``n_words + 1`` words — word
    ``n_words`` is the trash word that dropped marks are aimed at (the
    mark kernels apply none of them), so it lives in the
    padding — rounded up to whole blocks of ``rb`` rows, ``rb`` a multiple
    of the (8, 128) uint32 tile."""
    need = -(-(n_words + 1) // LANES)
    rb = min(row_block, -(-need // 8) * 8)
    return -(-need // rb) * rb, rb


@jax.named_scope("to_table")
def _to_table(packed: jax.Array, rows: int) -> jax.Array:
    pad = rows * LANES - packed.shape[0]
    return jnp.pad(packed.astype(jnp.uint32), (0, pad)).reshape(rows, LANES)


@jax.named_scope("to_table")
def _from_table(table: jax.Array, n_words: int) -> jax.Array:
    """The first ``n_words`` words of a ``(rows, 128)`` table, flat."""
    return table.reshape(-1)[:n_words]


def _lut_rotate(w, first_word, n_words: int, lut: int, count_val: int):
    """Map the 16 fields of every word of an ``(r, 128)`` block through the
    LUT; count mapped fields equal to ``count_val`` among the words whose
    flat index (``first_word`` + position) is below ``n_words``, so table
    padding — the trash word included — never counts."""
    acc = jnp.zeros_like(w)
    hits = jnp.zeros(w.shape, jnp.int32)
    for j in range(FIELDS_PER_WORD):
        nf = (jnp.uint32(lut) >> (2 * ((w >> (2 * j)) & 3))) & 3
        acc = acc | (nf << (2 * j))
        hits = hits + (nf == count_val).astype(jnp.int32)
    flat = (first_word
            + jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, w.shape, 1))
    return acc, jnp.sum(jnp.where(flat < n_words, hits, 0))


# ---------------------------------------------------------- lut + count

def _lut_count_kernel(p_ref, o_ref, cnt_ref, *, lut: int, count_val: int,
                      n_words: int):
    blk = pl.program_id(0)
    new, total = _lut_rotate(p_ref[...], blk * (p_ref.shape[0] * LANES),
                             n_words, lut, count_val)
    o_ref[...] = new

    @pl.when(blk == 0)
    def _init():
        cnt_ref[0, 0] = jnp.int32(0)

    cnt_ref[0, 0] = cnt_ref[0, 0] + total


def bitpack_lut_count(
    packed: jax.Array,       # (W,) uint32
    lut: int,                # make_lut(...) scalar (static)
    count_val: int,          # field value to count after mapping (static)
    *,
    block_w: int = ROW_BLOCK,
    interpret: bool = False,
):
    """Map every 2-bit field through ``lut`` and count resulting fields ==
    ``count_val``.  Returns (new_packed (W,) uint32, count () int32).

    The count covers exactly the W·16 fields of the input words (table
    padding is masked out in the kernel).  Callers owning fewer than W·16
    logical elements correct for THEIR tail fields themselves (see
    core/bitarray.py rotate_count).
    """
    w = packed.shape[0]
    rows, rb = table_layout(w, block_w)
    kernel = functools.partial(_lut_count_kernel, lut=lut,
                               count_val=count_val, n_words=w)
    out, cnt = pl.pallas_call(
        kernel,
        grid=(rows // rb,),
        in_specs=[pl.BlockSpec((rb, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rb, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="roomy_bitpack_lut_count",
    )(_to_table(packed, rows))
    return _from_table(out, w), cnt[0, 0]


# ------------------------------------- scatter mark (+ rotate + count)

def _mark_ops(ops_ref, n, tab, *, mark: int, only_if: int):
    """Apply the first ``n`` ops of an SMEM ref in order, one masked
    read-modify-write of a table row each."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(i, carry):
        elt = ops_ref[i]
        word = elt // FIELDS_PER_WORD
        row = word // LANES
        sh = (2 * (elt % FIELDS_PER_WORD)).astype(jnp.uint32)
        w = tab[pl.ds(row, 1), :]
        hit = (lane == word % LANES) & (((w >> sh) & 3) == only_if)
        tab[pl.ds(row, 1), :] = jnp.where(
            hit, (w & ~(jnp.uint32(3) << sh)) | (jnp.uint32(mark) << sh), w)
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _pack_live(idx_ref, live_ref, *, cap: int):
    """Copy the live ops of an SMEM block, in order, to the front of
    ``live_ref``: each op is stored at the live count so far, which only a
    live op advances, so no op branches.  The loop body takes PACK_UNROLL
    ops (or what divides the block), to spread the loop's own cost."""
    bm = idx_ref.shape[0]
    u = math.gcd(bm, PACK_UNROLL)

    def body(g, n):
        for j in range(u):
            elt = idx_ref[g * u + j]
            live_ref[n] = elt
            n = n + (elt != cap).astype(jnp.int32)
        return n

    jax.lax.fori_loop(0, bm // u, body, jnp.int32(0))


def _apply_marks(idx_ref, idx_vec, live_ref, tab, *, cap: int, mark: int,
                 only_if: int):
    """Apply one block of ops in order.  Every index is in [0, cap]; an op
    aimed at cap (field 0 of the trash word) is dead and is not applied.
    The block's live count comes from its vector copy ``idx_vec`` in one
    compare and sum.  A block with no live op does nothing; any other
    first packs its live ops into ``live_ref`` (_pack_live), so a dead op
    costs a scalar load and store and no read-modify-write."""
    live = jnp.sum((idx_vec[...] != cap).astype(jnp.int32))

    @pl.when(live > 0)
    def _mark_live():
        _pack_live(idx_ref, live_ref, cap=cap)
        _mark_ops(live_ref, live, tab, mark=mark, only_if=only_if)


def _scatter_kernel(idx_ref, idx_vec, tab_hbm, out_hbm, *rest, cap: int,
                    mark: int, only_if: int, rotate):
    """Grid over op blocks, each in SMEM (``idx_ref``) and in VMEM
    (``idx_vec``, for the live count).  The table is copied into VMEM once,
    before the first block, and back to HBM (the same buffer: input and
    output are aliased) after the last, whether or not those blocks hold a
    live op.  With ``rotate = (lut, count_val, n_words, rb)`` the last
    block also LUT-rotates and counts the resident table, ``rb`` rows at a
    time, before the copy back."""
    if rotate is None:
        live_ref, tab, sem = rest
    else:
        cnt_ref, live_ref, tab, sem = rest
    blk = pl.program_id(0)

    @pl.when(blk == 0)
    def _load():
        copy = pltpu.make_async_copy(tab_hbm, tab, sem)
        copy.start()
        copy.wait()

    _apply_marks(idx_ref, idx_vec, live_ref, tab, cap=cap, mark=mark,
                 only_if=only_if)

    @pl.when(blk == pl.num_programs(0) - 1)
    def _store():
        if rotate is not None:
            lut, count_val, n_words, rb = rotate

            def rot(b, total):
                r0 = pl.multiple_of(b * rb, rb)
                new, c = _lut_rotate(tab[pl.ds(r0, rb), :], r0 * LANES,
                                     n_words, lut, count_val)
                tab[pl.ds(r0, rb), :] = new
                return total + c

            cnt_ref[0, 0] = jax.lax.fori_loop(0, tab.shape[0] // rb, rot,
                                              jnp.int32(0))
        copy = pltpu.make_async_copy(tab, out_hbm, sem)
        copy.start()
        copy.wait()


def _stage_ops(idx, cap: int, bm: int):
    """The op indices as the mark kernel reads them: out-of-range and
    negative indices, and the padding to whole blocks of ``bm``, retarget
    ``cap``, field 0 of the trash word, so an op is dead exactly when its
    index is ``cap``, and a block's live count is its ops below ``cap``."""
    m = idx.shape[0]
    m_pad = -(-max(m, 1) // bm) * bm
    idx = idx.astype(jnp.int32)
    idx = jnp.where((idx >= 0) & (idx < cap), idx, cap)
    return jnp.pad(idx, (0, m_pad - m), constant_values=cap)


def _scatter_call(packed, idx, *, mark, only_if, block_m, interpret,
                  rotate_lut=None, count_val=None):
    """Stage the table and the op indices (_stage_ops) and run
    _scatter_kernel over the op blocks.  Dead ops do no work and are not
    applied.  The indices stay a flat int32 array, read one block at a
    time into SMEM and VMEM."""
    n_words = packed.shape[0]
    rows, rb = table_layout(n_words)
    cap = n_words * FIELDS_PER_WORD
    bm = min(block_m, max(idx.shape[0], 1))
    idx = _stage_ops(idx, cap, bm)

    rotate = (None if rotate_lut is None
              else (rotate_lut, count_val, n_words, rb))
    out_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    out_shape = [jax.ShapeDtypeStruct((rows, LANES), jnp.uint32)]
    if rotate is not None:
        out_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0),
                                      memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((1, 1), jnp.int32))
    table_bytes = rows * LANES * 4
    res = pl.pallas_call(
        functools.partial(_scatter_kernel, cap=cap, mark=mark,
                          only_if=only_if, rotate=rotate),
        grid=(idx.shape[0] // bm,),
        in_specs=[
            pl.BlockSpec((bm,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((bm,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.SMEM((bm,), jnp.int32),
                        pltpu.VMEM((rows, LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA(())],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=table_bytes + VMEM_HEADROOM,
        ),
        interpret=interpret,
        name=("roomy_bitpack_scatter_mark" if rotate is None
              else "roomy_bitpack_mark_rotate_count"),
    )(idx, idx, _to_table(packed, rows))
    return (_from_table(res[0], n_words),
            res[1][0, 0] if rotate else None)


def bitpack_scatter_mark(
    packed: jax.Array,       # (W,) uint32 — must fit VMEM as a (rows, 128) table
    idx: jax.Array,          # (M,) int32 element indices; OOB/negative drop
    *,
    mark: int = 2,           # value to write (static)
    only_if: int = 0,        # write only where the field currently == this
    block_m: int = DEFAULT_BM,
    interpret: bool = False,
) -> jax.Array:
    """packed[idx] ← mark where the 2-bit field holds ``only_if`` (the
    delayed-mark apply of the implicit BFS).  Duplicate indices are safe —
    the first mark wins and later ones see ``mark`` ≠ ``only_if``.  Out of
    range and negative indices are aimed at the trash word: they are not
    applied and cost no read-modify-write, and an op block of nothing else
    is skipped."""
    out, _ = _scatter_call(packed, idx, mark=mark, only_if=only_if,
                           block_m=block_m, interpret=interpret)
    return out


def bitpack_mark_rotate_count(
    packed: jax.Array,       # (W,) uint32 — must fit VMEM as a (rows, 128) table
    idx: jax.Array,          # (M,) int32 element indices; OOB/negative drop
    lut: int,                # make_lut(...) scalar (static)
    count_val: int,          # field value to count after mapping (static)
    *,
    mark: int = 2,
    only_if: int = 0,
    block_m: int = DEFAULT_BM,
    interpret: bool = False,
):
    """The implicit BFS's whole per-level array pass as ONE kernel:
    ``packed[idx] ← mark`` where the field holds ``only_if`` (delayed-mark
    apply, duplicates/OOB safe as in bitpack_scatter_mark), then every
    field maps through ``lut`` and fields mapping to ``count_val`` are
    counted — over ALL W·16 fields; callers owning fewer logical elements
    correct for their tail fields (core/bitarray.py mark_rotate_count).
    Returns (new_packed (W,) uint32, count () int32).

    Equivalent to bitpack_scatter_mark followed by bitpack_lut_count, but
    the packed table crosses HBM once instead of twice per level.  Dropped
    ops cost no read-modify-write, as there; the rotate and count run
    whatever the ops.
    """
    return _scatter_call(packed, idx, mark=mark, only_if=only_if,
                         block_m=block_m, interpret=interpret,
                         rotate_lut=lut, count_val=count_val)


# ------------------------------------------------- paged gather (serving)

DEFAULT_PAGE_WORDS = 512     # packed words per page block (2 KiB / page)


def _gather2_kernel(tbl_ref, idx_ref, page_ref, out_ref, *, bm: int):
    """One grid step = one block of ``bm`` page-LOCAL element indices
    against the one page the scalar-prefetched table routed in.  Negative
    indices are padding → 0 (same convention as the ref oracle's OOB)."""
    def body(i, _):
        elt = idx_ref[i, 0]
        ok = elt >= 0
        ee = jnp.maximum(elt, 0)
        word = ee // FIELDS_PER_WORD
        sh = (2 * (ee % FIELDS_PER_WORD)).astype(jnp.uint32)
        w = page_ref[pl.ds(word, 1), :]
        f = ((w >> sh) & jnp.uint32(3)).astype(jnp.int32)
        out_ref[pl.ds(i, 1), :] = jnp.where(ok, f, 0)
        return 0

    jax.lax.fori_loop(0, bm, body, 0)


def gather2_plan(idx, n_words: int, *,
                 page_words: int = DEFAULT_PAGE_WORDS,
                 block_m: int = DEFAULT_BM):
    """Host-side (numpy) page binning for :func:`bitpack_gather2`.

    Bins the element indices by owning page (stable argsort + contiguous
    slices — the disk tier's bin-by-dest idiom), pads each page's run to
    whole ``block_m`` blocks with -1, and returns

        (local (n_blocks·bm,) int32 page-LOCAL indices,
         page_table (n_blocks,) int32,
         out_pos (n_blocks·bm,) int64 original query position, -1 = pad)

    OOB/negative queries are excluded here (they never reach the kernel)
    and read back as 0 through ``out_pos``.  Binning is data-dependent
    host work — the same reason the oracle server bins by chunk outside
    any jit.
    """
    idx = np.asarray(idx).astype(np.int64).reshape(-1)
    cap = n_words * FIELDS_PER_WORD
    fpp = page_words * FIELDS_PER_WORD
    (pos,) = np.nonzero((idx >= 0) & (idx < cap))
    page_of = idx[pos] // fpp
    order = pos[np.argsort(page_of, kind="stable")]
    pages, starts = np.unique(idx[order] // fpp, return_index=True)
    bounds = np.append(starts, order.size)
    locs, outpos, tbl = [], [], []
    for pi, page in enumerate(pages):
        sel = order[bounds[pi]:bounds[pi + 1]]
        pad = -(-sel.size // block_m) * block_m - sel.size
        locs.append(np.concatenate(
            [(idx[sel] - page * fpp).astype(np.int32),
             np.full(pad, -1, np.int32)]))
        outpos.append(np.concatenate([sel, np.full(pad, -1, np.int64)]))
        tbl.extend([int(page)] * ((sel.size + pad) // block_m))
    if not tbl:                 # no valid query: one dummy all-pad block
        locs = [np.full(block_m, -1, np.int32)]
        outpos = [np.full(block_m, -1, np.int64)]
        tbl = [0]
    return (np.concatenate(locs), np.asarray(tbl, np.int32),
            np.concatenate(outpos))


def bitpack_gather2(
    packed: jax.Array,       # (W,) uint32 packed 2-bit fields
    idx,                     # (M,) int element indices; OOB/negative → 0
    *,
    page_words: int = DEFAULT_PAGE_WORDS,
    block_m: int = DEFAULT_BM,
    interpret: bool = False,
) -> jax.Array:
    """Gather the 2-bit field for each element index: (M,) int32 in 0..3.

    The packed words are padded to whole pages of ``page_words`` and the
    grid runs one step per query block; a PrefetchScalarGridSpec page
    table (built by :func:`gather2_plan`) picks which page each block's
    BlockSpec streams into VMEM — so a batch touching k pages moves
    k·page_words·4 bytes regardless of W, the serving tier's cache-miss
    cost model on device.
    """
    n_words = packed.shape[0]
    m = int(np.asarray(idx).reshape(-1).shape[0])
    n_pages = max(1, -(-n_words // page_words))
    local, tbl, out_pos = gather2_plan(idx, n_words,
                                       page_words=page_words,
                                       block_m=block_m)
    bm = min(block_m, local.shape[0])
    paged = (jnp.zeros((n_pages * page_words,), jnp.uint32)
             .at[:n_words].set(packed.astype(jnp.uint32))
             .reshape(n_pages * page_words, 1))
    n_blocks = tbl.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, tbl: (i, 0)),
            pl.BlockSpec((page_words, 1), lambda i, tbl: (tbl[i], 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, tbl: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_gather2_kernel, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks * bm, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="roomy_bitpack_gather2",
    )(jnp.asarray(tbl), jnp.asarray(local).reshape(-1, 1), paged)
    flat = np.asarray(out).reshape(-1)
    res = np.zeros(m, np.int32)
    (live,) = np.nonzero(out_pos >= 0)
    res[out_pos[live]] = flat[live]
    return jnp.asarray(res)
