"""Permutation rank/unrank — the index space of the implicit bit-array BFS.

Invariant: ``rank`` and ``unrank`` are exact inverses forming a bijection
{permutations of n} ↔ [0, n!), identical bit-for-bit between the numpy
(Tier D) and jax (Tier J) implementations, and rank *rows* sort
lexicographically in rank order (word 0 most significant).  The implicit
BFS engines index 2-bit state arrays with these ranks, so any deviation
silently conflates distinct states.

The paper's pancake computation never stores permutations as row keys: a
permutation IS its index into a RoomyArray of 2-bit elements, via a
rank/unrank bijection {permutations of n} ↔ [0, n!).  We use the
Myrvold–Ruskey ordering (linear-time, non-lexicographic — the ordering is
irrelevant, only bijectivity matters), which vectorizes over batches as n
rounds of fancy-indexed swaps:

    unrank(r):  pi = identity; for i = n..1: swap(pi[i-1], pi[r % i]); r //= i
    rank(pi):   for i = n..2: emit s = pi[i-1]; swap pi so value i-1 lands at
                slot i-1 (and fix pi⁻¹); fold r = r·i + s  (i ascending)

Two parallel implementations share that algorithm:

  *_np    NumPy, uint64 ranks (Tier D — disk BFS drives millions of states
          through these per level; every step is a batched gather/scatter)
  *_jnp   jax.numpy, and — because JAX runs with x64 disabled — ranks are
          **two-word (hi, lo) uint32 pairs** with schoolbook base-2¹⁶
          multiply-add / long division by the (≤ n) loop constant.  Word 0
          is the high word, so rank rows sort lexicographically in rank
          order under the repo's word-0-most-significant row convention.

One uint32 word holds n ≤ 12 (12! < 2³²); two words hold n ≤ 20
(20! < 2⁶⁴).  ``RANK_WIDTH[n]`` gives the row width the BFS encodings use.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MAX_N = 20          # 20! < 2^64: two uint32 words per rank
MAX_N_1WORD = 12    # 12! < 2^32: single-word ranks


def rank_width(n: int) -> int:
    """Row width (uint32 words) needed to hold ranks in [0, n!)."""
    assert 1 <= n <= MAX_N, f"rank/unrank supports n <= {MAX_N}"
    return 1 if n <= MAX_N_1WORD else 2


# ======================================================================
# NumPy (Tier D)
# ======================================================================

def unrank_np(n: int, ranks: np.ndarray) -> np.ndarray:
    """Myrvold–Ruskey unrank, batched: (m,) uint64 → (m, n) int64 perms."""
    assert 1 <= n <= MAX_N
    r = np.asarray(ranks, np.uint64).copy().reshape(-1)
    m = r.shape[0]
    pi = np.broadcast_to(np.arange(n, dtype=np.int64), (m, n)).copy()
    rows = np.arange(m)
    for i in range(n, 0, -1):
        s = (r % np.uint64(i)).astype(np.int64)
        r //= np.uint64(i)
        a = pi[rows, i - 1].copy()
        pi[rows, i - 1] = pi[rows, s]
        pi[rows, s] = a
    return pi


def rank_np(perms: np.ndarray) -> np.ndarray:
    """Myrvold–Ruskey rank, batched: (m, n) perms → (m,) uint64 ranks."""
    pi = np.array(perms, np.int64, copy=True)
    m, n = pi.shape
    assert 1 <= n <= MAX_N
    pinv = np.argsort(pi, axis=1)
    rows = np.arange(m)
    s_seq = []
    for i in range(n, 1, -1):
        s = pi[rows, i - 1].copy()
        j = pinv[rows, i - 1].copy()
        # swap pi[i-1] ↔ pi[j] (value i-1 moves to its home slot) …
        pi[rows, i - 1] = pi[rows, j]
        pi[rows, j] = s
        # … and the matching swap in the inverse.
        t = pinv[rows, s].copy()
        pinv[rows, s] = pinv[rows, i - 1]
        pinv[rows, i - 1] = t
        s_seq.append(s)
    r = np.zeros(m, np.uint64)
    for i, s in zip(range(2, n + 1), reversed(s_seq)):
        r = r * np.uint64(i) + s.astype(np.uint64)
    return r


def ranks_to_rows(ranks: np.ndarray, n: int) -> np.ndarray:
    """uint64 ranks → (m, rank_width(n)) uint32 rows, word 0 most significant
    (so lexicographic row order == numeric rank order)."""
    r = np.asarray(ranks, np.uint64).reshape(-1)
    if rank_width(n) == 1:
        return r.astype(np.uint32)[:, None]
    hi = (r >> np.uint64(32)).astype(np.uint32)
    lo = (r & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return np.stack([hi, lo], axis=1)


def rows_to_ranks(rows: np.ndarray) -> np.ndarray:
    """(m, 1|2) uint32 rows → (m,) uint64 ranks (inverse of ranks_to_rows)."""
    rows = np.asarray(rows, np.uint32)
    if rows.shape[1] == 1:
        return rows[:, 0].astype(np.uint64)
    return (rows[:, 0].astype(np.uint64) << np.uint64(32)) | rows[:, 1].astype(np.uint64)


# ======================================================================
# jax.numpy (Tier J) — double-word uint32 arithmetic (x64 is disabled)
# ======================================================================

def _muladd_u64(hi: jax.Array, lo: jax.Array, i: int, s: jax.Array):
    """(hi, lo)·i + s for small i ≤ MAX_N, s < i.  Base-2¹⁶ carries keep
    every intermediate under 32 bits."""
    s = s.astype(jnp.uint32)
    t0 = (lo & 0xFFFF) * i + s
    t1 = (lo >> 16) * i + (t0 >> 16)
    new_lo = (t0 & 0xFFFF) | ((t1 & 0xFFFF) << 16)
    new_hi = hi * i + (t1 >> 16)
    return new_hi.astype(jnp.uint32), new_lo.astype(jnp.uint32)


def _divmod_u64(hi: jax.Array, lo: jax.Array, i: int):
    """(hi, lo) divmod small i: schoolbook base-2¹⁶ long division.
    Returns (q_hi, q_lo, rem); rem < i fits one word trivially."""
    digits = (hi >> 16, hi & 0xFFFF, lo >> 16, lo & 0xFFFF)
    rem = jnp.zeros_like(hi)
    q = []
    for d in digits:
        cur = (rem << 16) | d          # rem < i ≤ 20 → cur < 2²¹
        q.append(cur // i)
        rem = cur % i
    q_hi = ((q[0] << 16) | q[1]).astype(jnp.uint32)
    q_lo = ((q[2] << 16) | q[3]).astype(jnp.uint32)
    return q_hi, q_lo, rem.astype(jnp.uint32)


def _pick(cols, s):
    """cols[s] per row, for a traced index ``s`` — a chain of selects over
    the columns instead of a gather."""
    out = cols[0]
    for k in range(1, len(cols)):
        out = jnp.where(s == k, cols[k], out)
    return out


def unrank_jnp(n: int, rank_rows: jax.Array) -> jax.Array:
    """Batched unrank: (m, rank_width(n)) uint32 rows → (m, n) int32 perms.

    Accepts width-1 rows for n ≤ 12 and width-2 (hi, lo) rows for any n.
    The permutation is held as n columns of shape (m,), and each swap is a
    select per column, so the batch axis stays the minor (lane) axis and
    no gather or scatter is emitted.
    """
    assert 1 <= n <= MAX_N
    rank_rows = rank_rows.astype(jnp.uint32)
    if rank_rows.shape[1] == 1:
        hi = jnp.zeros_like(rank_rows[:, 0])
        lo = rank_rows[:, 0]
    else:
        hi, lo = rank_rows[:, 0], rank_rows[:, 1]
    cols = [jnp.full(lo.shape, k, jnp.int32) for k in range(n)]
    for i in range(n, 0, -1):
        hi, lo, s = _divmod_u64(hi, lo, i)
        s = s.astype(jnp.int32)
        a, b = cols[i - 1], _pick(cols[:i], s)      # swap pi[i-1] ↔ pi[s]
        cols = ([jnp.where(s == k, a, c) for k, c in enumerate(cols[:i - 1])]
                + [b] + cols[i:])
    return jnp.stack(cols, axis=1)


def rank_jnp(perms: jax.Array, width: int | None = None) -> jax.Array:
    """Batched rank: (m, n) perms → (m, width) uint32 rank rows.

    width defaults to rank_width(n); word 0 is the high word.  Columns and
    selects as in unrank_jnp; the inverse permutation is built by
    comparison instead of an argsort.
    """
    pi = perms.astype(jnp.int32)
    m, n = pi.shape
    assert 1 <= n <= MAX_N
    width = width or rank_width(n)
    cols = [pi[:, k] for k in range(n)]
    inv = [sum(jnp.where(c == v, k, 0) for k, c in enumerate(cols))
           for v in range(n)]
    s_seq = []
    for i in range(n, 1, -1):
        # Value i-1 moves to its home slot i-1 and the value it displaces,
        # s, to slot j; only slots below i-1 are read again.
        s, j = cols[i - 1], inv[i - 1]
        cols = [jnp.where(j == k, s, c) for k, c in enumerate(cols[:i - 1])]
        inv = [jnp.where(s == v, j, c) for v, c in enumerate(inv[:i - 1])]
        s_seq.append(s)
    hi = jnp.zeros((m,), jnp.uint32)
    lo = jnp.zeros((m,), jnp.uint32)
    for i, s in zip(range(2, n + 1), reversed(s_seq)):
        hi, lo = _muladd_u64(hi, lo, i, s)
    if width == 1:
        return lo[:, None]
    return jnp.stack([hi, lo], axis=1)


def n_states(n: int) -> int:
    return math.factorial(n)
