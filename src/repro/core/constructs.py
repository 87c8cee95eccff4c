"""The paper's §3 programming constructs, built on the Roomy primitives.

map / reduce are primitives (rlist.py, array.py); here we provide:

  set operations    union / difference / intersection (paper's recipes,
                    including the 3-temporary intersection)
  chain reduction   a[i] = f(a[i], a[i-1]) via delayed updates — reads all
                    old values before any write (deterministic, §3)
  parallel prefix   log-round chain reductions with stride doubling
  pair reduction    blocked streaming over all N² pairs
  BFS               level-synchronous frontier expansion with the paper's
                    exact dedup loop, plus Python-level capacity growth
                    (the static-shape adaptation of "dynamically sized")
  implicit BFS      the paper's second engine: rank-indexed 2-bit array
                    with delayed marks — no frontier lists, no sorting
                    (bitarray.py + ranking.py; the pancake construction)

Everything below is jit-compatible except the BFS driver loop, which is a
Python loop over levels (level count is data-dependent) — the same
structure as the paper's ``while (RoomyList_size(cur))``.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp

from . import array as RA
from . import bitarray as BA
from . import obs
from . import rlist as RL
from . import types as T


# ---------------------------------------------------------------- set ops

def set_union(a: RL.RoomyList, b: RL.RoomyList) -> RL.RoomyList:
    """A = A ∪ B   (paper: addAll + removeDupes)."""
    out, _ = RL.add_all(a, b)
    return RL.remove_dupes(out)


def set_difference(a: RL.RoomyList, b: RL.RoomyList) -> RL.RoomyList:
    """A = A − B   (paper: removeAll; assumes a, b are sets)."""
    return RL.remove_all(a, b)


def set_intersection(a: RL.RoomyList, b: RL.RoomyList,
                     capacity: int | None = None) -> RL.RoomyList:
    """C = A ∩ B via the paper's recipe: (A+B) − (A−B) − (B−A)."""
    cap = capacity or (a.capacity + b.capacity)
    a_and_b = RL.make(cap, a.width)
    a_and_b, _ = RL.add_all(a_and_b, a)
    a_and_b, _ = RL.add_all(a_and_b, b)
    a_and_b = RL.remove_dupes(a_and_b)
    a_minus_b = RL.remove_all(a, b)
    b_minus_a = RL.remove_all(b, a)
    c = RL.make(cap, a.width)
    c, _ = RL.add_all(c, a_and_b)
    c = RL.remove_all(c, a_minus_b)
    c = RL.remove_all(c, b_minus_a)
    return c


# ------------------------------------------------------- chain reduction

def chain_reduce(ra: RA.RoomyArray, combine: Callable) -> RA.RoomyArray:
    """a[i] = combine(a[i], a[i-1]) for i in 1..N-1, old values throughout.

    Paper §3: map over the array issues update(i+1, val_i); sync applies
    them against the old state (scatter-gather).
    """
    n = ra.size
    idx = jnp.arange(n, dtype=jnp.int32) + 1          # i-1 → i
    valid = idx < n
    ra, _ = RA.update(ra, idx, ra.data, valid)
    return RA.sync(ra, combine=lambda p, q: p, apply=lambda old, pay: combine(old, pay))


def parallel_prefix(ra: RA.RoomyArray, combine: Callable) -> RA.RoomyArray:
    """Inclusive scan via log₂N chain reductions with stride doubling."""
    n = ra.size
    k = 1
    while k < n:
        idx = jnp.arange(n, dtype=jnp.int32) + k
        valid = idx < n
        ra, _ = RA.update(ra, idx, ra.data, valid)
        ra = RA.sync(ra, combine=lambda p, q: p,
                     apply=lambda old, pay: combine(old, pay))
        k *= 2
    return ra


# -------------------------------------------------------- pair reduction

def pair_reduce(ra: RA.RoomyArray, pair_fn: Callable, merge_fn: Callable,
                identity, block: int = 256):
    """Fold pair_fn(a[i], a[j]) over all N² ordered pairs.

    Streaming block×block evaluation — the batched form of the paper's
    map-issuing-accesses pattern (each outer block's delayed accesses to the
    whole array are served one inner block at a time).
    """
    n = ra.size
    nblocks = -(-n // block)
    pad = nblocks * block - n
    data = jnp.concatenate([ra.data, jnp.zeros((pad,) + ra.data.shape[1:],
                                               ra.data.dtype)], axis=0)
    valid = jnp.arange(nblocks * block) < n
    data_b = data.reshape((nblocks, block) + ra.data.shape[1:])
    valid_b = valid.reshape(nblocks, block)

    def outer(acc, ob):
        o_dat, o_val = ob

        def inner(acc2, ib):
            i_dat, i_val = ib
            vals = jax.vmap(lambda x: jax.vmap(lambda y: pair_fn(x, y))(i_dat))(o_dat)
            mask = (o_val[:, None] & i_val[None, :])
            mask = mask.reshape(mask.shape + (1,) * (vals.ndim - 2))
            vals = jnp.where(mask, vals, jnp.asarray(identity, vals.dtype))
            flat = vals.reshape((-1,) + vals.shape[2:])
            return merge_fn(acc2, T.tree_reduce(flat, merge_fn, identity)), None

        acc, _ = jax.lax.scan(inner, acc, (data_b, valid_b))
        return acc, None

    init = jnp.asarray(identity)
    acc, _ = jax.lax.scan(outer, init, (data_b, valid_b))
    return acc


# ------------------------------------------------------------------- BFS

class BFSResult:
    def __init__(self):
        self.level_sizes: List[int] = []
        self.all: RL.RoomyList | None = None
        self.levels_run: int = 0


def dedupe_subtract_fold(nxt_rows: jax.Array, nxt_valid: jax.Array,
                         all_lst: RL.RoomyList, next_cap: int):
    """Fused removeDupes ∘ removeAll ∘ addAll — ONE lexsort, ONE scatter
    (sort-once, Tier J).

    One lexsort over the tagged concatenation ``[nxt_raw; all]`` decides all
    three at once: within an equal-run, any member tagged "old" kills the run
    (visited-set subtraction), otherwise the first member survives
    (intra-level dedup); survivors — already in sorted order — are compacted
    with a boolean argsort and folded into ``all`` with one scatter.

    ``nxt_rows`` may be the RAW expansion (invalid slots included): invalid
    rows are masked to sentinel and sort last, so the same lexsort also does
    the staging compaction that used to cost a separate ``RL.add`` scatter
    before the fold (_bfs_level) — the whole level is 1 lexsort + 1 scatter.

    The reference composition (staged add → remove_dupes → remove_all →
    add_all) costs 2 lexsorts + 2 scatters over the same data; property
    tests assert element-wise equivalence (tests/test_sort_once.py).

    Returns (nxt, all2, overflow) like the composition it replaces.
    """
    m, w = nxt_rows.shape
    na = all_lst.capacity
    all_valid = RL.valid_mask(all_lst)
    # Mask BOTH sides to sentinel outside their valid ranges: append_block's
    # contract allows garbage (not just sentinel) beyond count, and unmasked
    # garbage rows would be resurrected as phantom frontier states.
    rows = jnp.concatenate(
        [jnp.where(nxt_valid[:, None], nxt_rows.astype(jnp.uint32),
                   T.sentinel_rows(m, w)),
         jnp.where(all_valid[:, None], all_lst.data,
                   T.sentinel_rows(na, w))], axis=0)
    is_old = jnp.concatenate([jnp.zeros((m,), bool), all_valid])
    perm = T.lexsort_rows(rows)
    rows_s = rows[perm]
    old_s = is_old[perm]
    rid = T.run_ids(rows_s)
    run_has_old = jax.ops.segment_max(old_s.astype(jnp.int32), rid,
                                      num_segments=m + na)
    keep = (T.first_of_run(rows_s) & T.rows_valid(rows_s)
            & (run_has_old[rid] == 0))
    rows_c, count = T.compact_valid_first(rows_s, keep)   # stays sorted
    if next_cap <= m + na:
        nxt_data = rows_c[:next_cap]
    else:
        nxt_data = jnp.concatenate(
            [rows_c, T.sentinel_rows(next_cap - (m + na), w)], axis=0)
    nxt = RL.RoomyList(nxt_data, jnp.minimum(count, next_cap))
    all2, ov2 = RL.add(all_lst, nxt_data, jnp.arange(next_cap) < count)
    return nxt, all2, (count > next_cap) | ov2


def _bfs_level(cur: RL.RoomyList, all_lst: RL.RoomyList, gen_next: Callable,
               fanout: int, next_cap: int):
    """One level: expand cur, then one fused dedupe/subtract/fold pass.

    gen_next(row) -> (rows (fanout, w), valid (fanout,)). Jitted per shape.

    The raw expansion feeds dedupe_subtract_fold directly: its lexsort
    masks invalid slots to sentinel (they sort last and drop), so the
    staging scatter that used to compact the expansion into a next_cap
    buffer first is folded into the sort the level already pays — one
    lexsort + one scatter per level, asserted by the SORT_STATS trace
    tests.  (The lexsort covers capacity·fanout + all_cap rows instead of
    next_cap + all_cap; sorting the dead slots is cheaper than the extra
    full-width scatter pass they used to cost.)
    """
    nbr_rows, nbr_valid = jax.vmap(gen_next)(cur.data)
    nbr_valid = nbr_valid & RL.valid_mask(cur)[:, None]
    return dedupe_subtract_fold(nbr_rows.reshape(-1, cur.width),
                                nbr_valid.reshape(-1), all_lst, next_cap)


def _bfs_level_reference(cur: RL.RoomyList, all_lst: RL.RoomyList,
                         gen_next: Callable, fanout: int, next_cap: int):
    """Unfused reference level (2 lexsorts + 2 boolean compactions) — kept
    for equivalence tests and the sorts-per-level benchmark; semantics
    identical to _bfs_level."""
    nbr_rows, nbr_valid = jax.vmap(gen_next)(cur.data)
    nbr_valid = nbr_valid & RL.valid_mask(cur)[:, None]
    nxt = RL.make(next_cap, cur.width)
    nxt, overflow = RL.add(nxt, nbr_rows.reshape(-1, cur.width),
                           nbr_valid.reshape(-1))
    nxt = RL.remove_dupes(nxt)                 # dedup within level
    nxt = RL.remove_all(nxt, all_lst)          # dedup against previous levels
    all2, ov2 = RL.add_all(all_lst, nxt)       # record new elements
    return nxt, all2, overflow | ov2


IMPLICIT_BLOCK = 1 << 20     # states expanded per block of an implicit level

# Tier J implicit search, booked on the host whether or not tracing is on:
# a level call expands ``states_expanded`` (padded) states, of which
# ``frontier_states`` are CUR, the live work; a search finds its jitted
# level step kept from an earlier search (``step_hits``) or builds it
# (``step_misses``).
IMPLICIT_STATS = obs.counters("implicit", {
    "searches": 0, "level_calls": 0, "states_expanded": 0,
    "frontier_states": 0, "step_hits": 0, "step_misses": 0})


def _implicit_blocks(n_states: int, block: int):
    """``(nblk, bs)``: the blocks an implicit level expands, and the states
    in each, a whole number of packed words."""
    f = BA.FIELDS_PER_WORD
    nblk = -(-n_states // block)
    return nblk, -(-n_states // (nblk * f)) * f


def _implicit_level(data, *, n_states: int, neighbor_fn: Callable,
                    impl: str, fused: bool = True,
                    block: int = IMPLICIT_BLOCK):
    """One implicit-BFS level over the packed 2-bit array: mark every
    neighbor of a CUR state NEXT-if-UNSEEN (the delayed-update batch — a
    masked scatter, duplicates and visited states absorb silently), then
    rotate CUR→DONE / NEXT→CUR and count the new frontier.  No sort of any
    kind.

    The index space is expanded in blocks of at most ``block`` states, so
    the level's peak memory is O(block), not O(n_states); each block's
    marks are applied before the next block expands.  Marks never touch a
    CUR field, so every block reads CUR from the level's input.  With
    ``fused=True`` the last block's marks and the LUT rotate+count run as
    ONE kernel over the packed words (kernels/bitpack.py
    bitpack_mark_rotate_count), the Tier J twin of the disk pass
    planner's fused level; ``fused=False`` marks every block, then
    rotates and counts in a pass of its own."""
    f = BA.FIELDS_PER_WORD
    cap = data.shape[0] * f
    nblk, bs = _implicit_blocks(n_states, block)
    with jax.named_scope("block_pad"):
        src = jnp.pad(data, (0, nblk * bs // f - data.shape[0]))
    shifts = (2 * jnp.arange(f, dtype=jnp.uint32))[:, None]
    # Block-local state ids in field-major order: row j holds field j of
    # every word, so the CUR test reads the words without an unpack.
    local = (f * jnp.arange(bs // f, dtype=jnp.int32)[None, :]
             + jnp.arange(f, dtype=jnp.int32)[:, None]).reshape(-1)

    @jax.named_scope("expand")
    def targets(b):
        words = jax.lax.dynamic_slice(src, (b * (bs // f),), (bs // f,))
        cur = (((words[None, :] >> shifts) & 3) == BA.CUR).reshape(-1)
        ids = b * bs + local
        nbr = jax.vmap(neighbor_fn, out_axes=1)(jnp.minimum(ids, n_states - 1))
        live = cur & (ids < n_states)
        return jnp.where(live[None, :], nbr.astype(jnp.int32), cap).reshape(-1)

    def mark(b, d):
        return BA.mark_packed(d, targets(b), impl=impl)

    if not fused:
        data = jax.lax.fori_loop(0, nblk, mark, data)
        return BA.rotate_count(data, n_states, impl=impl)
    data = jax.lax.fori_loop(0, nblk - 1, mark, data)
    return BA.mark_rotate_count(data, targets(nblk - 1), n_states, impl=impl)


@functools.partial(jax.jit, static_argnames="n_states")
def _count_cur(data, n_states: int):
    """The CUR states among the first ``n_states`` of a packed array, in
    one program.  Run as separate ops, the unpacked values (4 bytes a
    state) sit in device memory beside what the kept level step holds
    between searches (_implicit_step), and raise the search's peak."""
    return jnp.sum((BA.unpack_values(data)[:n_states] == BA.CUR)
                   .astype(jnp.int32))


@functools.lru_cache(maxsize=8)
def _implicit_step(n_states: int, neighbor_fn: Callable, impl: str,
                   fused: bool, block: int):
    """The jitted ``_implicit_level`` for these inputs, kept across
    searches (the rule by identity, as a function hashes) and built anew
    only for inputs not among the 8 used last: ``jax.jit`` keeps compiled
    code per function object, so a step built for every search would
    trace and lower the level again each time.

    A kept step holds its compiled program in device memory, and no
    arrays: on a v5e the program is 10.2 MB for bubble-sort n=9 and
    21-23 MB for the pancake and bubble-sort rules at n=10-12 with the
    default block.  It is code, which grows far slower than the block
    (pancake n=10: 14.2 MB at 65,536 states a block, 21.4 MB at 2**20),
    so the 8 kept steps hold about 180 MB at most for such rules."""
    IMPLICIT_STATS["step_misses"] += 1
    return jax.jit(functools.partial(_implicit_level, n_states=n_states,
                                     neighbor_fn=neighbor_fn, impl=impl,
                                     fused=fused, block=block))


def implicit_bfs(
    n_states: int,
    start_idx,
    neighbor_fn: Callable,
    max_levels: int = 1_000,
    impl: str = "auto",
    fused: bool = True,
):
    """The paper's *second* BFS engine on Tier J: implicit search over a
    2-bit RoomyBitArray indexed by state rank (ranking.py), the device twin
    of ``disk.implicit_bfs``.

    neighbor_fn(i int32) -> (fanout,) int32 neighbor indices; it is vmapped
    over the whole index space each level, one block of states at a time
    (_implicit_level) — the static-shape adaptation of "expand the CUR
    states" (non-CUR rows are masked out of the mark), so a level costs
    O(n_states) regardless of frontier size but needs no frontier list, no
    sorting and no duplicate elimination.

    Returns (level_sizes, bits: RoomyBitArray) — all reached states end
    DONE in ``bits``.  ``fused=False`` keeps the two-kernel reference
    composition (mark scatter, then rotate+count) for equivalence tests.

    The jitted level step is kept across calls per ``(n_states, rule
    object, impl, fused, IMPLICIT_BLOCK)`` (_implicit_step), so a search
    repeated with the same rule object traces, lowers and compiles
    nothing.  As for any jitted function, the rule must be pure: it is
    traced once and its Python body never runs again.  A rule rebuilt on
    every call is a new object and gets a new step.
    """
    IMPLICIT_STATS["searches"] += 1
    nblk, bs = _implicit_blocks(n_states, IMPLICIT_BLOCK)
    with obs.span("bfs.search", n_states=n_states, tier="j",
                  engine="implicit"):
        with obs.span("bfs.init"):
            ba = BA.make(n_states)
            start = jnp.asarray(start_idx, jnp.int32).reshape(-1)
            data = BA.mark_packed(ba.data, start, mark=BA.CUR,
                                  only_if=BA.UNSEEN, impl=impl)
            level_sizes: List[int] = [int(_count_cur(data, n_states))]
        misses = IMPLICIT_STATS["step_misses"]
        step = _implicit_step(n_states, neighbor_fn, impl, fused,
                              IMPLICIT_BLOCK)
        if IMPLICIT_STATS["step_misses"] == misses:
            IMPLICIT_STATS["step_hits"] += 1
        for _ in range(max_levels):
            frontier = level_sizes[-1]
            with obs.span("bfs.level", level=len(level_sizes), tier="j",
                          engine="implicit", frontier=frontier):
                IMPLICIT_STATS["level_calls"] += 1
                IMPLICIT_STATS["states_expanded"] += nblk * bs
                IMPLICIT_STATS["frontier_states"] += frontier
                with obs.span("bfs.dispatch"):
                    data, cnt = step(data)
                with obs.span("bfs.sync"):
                    c = int(cnt)
            if c == 0:
                break
            level_sizes.append(c)
    return level_sizes, ba._replace(data=data)


def breadth_first_search(
    start_rows,
    gen_next: Callable,
    fanout: int,
    width: int,
    all_capacity: int,
    level_capacity: int,
    max_levels: int = 1_000,
    fused: bool = True,
) -> BFSResult:
    """Paper §3 BFS over an implicit graph, with capacity growth on overflow.

    The per-level step is jitted; capacities double (Python level) whenever
    a level overflows — the static-shape equivalent of Roomy's dynamically
    sized lists. fused=True (default) runs the one-lexsort
    dedupe_subtract_fold level; fused=False the 3-lexsort reference
    composition (for equivalence tests and benchmarks).
    """
    start_rows = jnp.asarray(start_rows, jnp.uint32).reshape(-1, width)
    all_lst = RL.make(all_capacity, width)
    all_lst, _ = RL.add(all_lst, start_rows)
    cur = RL.make(level_capacity, width)
    cur, _ = RL.add(cur, start_rows)

    level_fn = _bfs_level if fused else _bfs_level_reference
    step = jax.jit(functools.partial(level_fn, gen_next=gen_next,
                                     fanout=fanout),
                   static_argnames=("next_cap",))

    res = BFSResult()
    res.level_sizes.append(int(cur.count))
    for _ in range(max_levels):
        if int(cur.count) == 0:
            res.level_sizes.pop()              # last level was empty
            break
        with obs.span("bfs.level", level=res.levels_run + 1, tier="j",
                      engine="sorted", frontier=int(cur.count)):
            next_cap = max(level_capacity, int(cur.count) * fanout)
            nxt, all2, overflow = step(cur, all_lst, next_cap=next_cap)
            if bool(overflow):
                # Grow the 'all' list and redo this level (pure functional
                # state means the failed attempt had no side effects).
                all_capacity *= 2
                grown = RL.make(all_capacity, width)
                grown, _ = RL.add_all(grown, all_lst)
                all_lst = grown
                nxt, all2, overflow = step(cur, all_lst, next_cap=next_cap)
                if bool(overflow):
                    raise MemoryError("BFS capacity growth failed twice")
            cur, all_lst = nxt, all2
            res.levels_run += 1
            res.level_sizes.append(int(cur.count))
        if int(cur.count) == 0:
            res.level_sizes.pop()
            break
    res.all = all_lst
    return res
