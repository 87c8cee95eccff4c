"""Plain reference for the level counts of a breadth-first search over S_n.

The graph is a Cayley graph of the symmetric group: a state is a
permutation ``p`` of ``range(n)`` and its neighbours are ``p[g]`` for each
generator ``g`` (a permutation of positions) that the configuration lists.
A Cayley graph looks the same from every vertex, so the count of states at
each distance is the same from every start, and the reference searches
from the identity.

This module is deliberately independent of the system under test: it
imports nothing of ``repro``, keeps permutations as rows of bytes, ranks
them lexicographically (a different bijection from the one the program
uses), and keeps one plain boolean array of visited states.
"""
from __future__ import annotations

import math
from typing import List, Sequence

import numpy as np

CHUNK = 1 << 18        # frontier rows expanded at a time (bounds memory)


def lex_rank(perms: np.ndarray) -> np.ndarray:
    """(m, n) permutations -> (m,) int64 lexicographic ranks (Lehmer code)."""
    m, n = perms.shape
    rank = np.zeros(m, np.int64)
    for i in range(n - 1):
        smaller = np.zeros(m, np.int64)
        for j in range(i + 1, n):
            smaller += perms[:, j] < perms[:, i]
        rank += smaller * math.factorial(n - 1 - i)
    return rank


def _expand(frontier: np.ndarray, generators: np.ndarray):
    """Every neighbour of every frontier row: (ranks, rows)."""
    ranks, rows = [], []
    for lo in range(0, frontier.shape[0], CHUNK):
        part = frontier[lo:lo + CHUNK]
        for g in generators:
            nb = part[:, g]
            ranks.append(lex_rank(nb))
            rows.append(nb)
    return np.concatenate(ranks), np.concatenate(rows)


def level_counts(n: int, generators: Sequence[Sequence[int]],
                 lost_updates: bool = False,
                 start: Sequence[int] | None = None) -> List[int]:
    """States at each distance from ``start`` (the identity by default),
    level 0 first.

    ``lost_updates=True`` is the control: it breaks the guarantee that
    every state is marked once at its distance.  Visited states are then
    kept two bits each, sixteen to a 32-bit word, and a level's marks are
    written as whole words read before the level, so where two new states
    share a word only the last write survives (a packed scatter without
    combining).  Lost states are found again later, at a wrong distance.
    """
    gens = np.asarray(generators, np.intp)
    if gens.ndim != 2 or gens.shape[1] != n or sorted(gens[0]) != list(range(n)):
        raise ValueError(f"generators must be permutations of range({n})")
    total = math.factorial(n)
    seen = np.zeros(total, bool)
    words = np.zeros(-(-total // 16), np.uint32)
    frontier = np.asarray(range(n) if start is None else start,
                          np.int8)[None, :]
    if sorted(frontier[0]) != list(range(n)):
        raise ValueError(f"start must be a permutation of range({n})")
    r0 = int(lex_rank(frontier)[0])
    seen[r0] = True
    words[r0 // 16] |= np.uint32(1) << np.uint32(2 * (r0 % 16))
    counts = [1]
    while True:
        ranks, rows = _expand(frontier, gens)
        if lost_updates:
            w, sh = ranks // 16, (2 * (ranks % 16)).astype(np.uint32)
            fresh = ((words[w] >> sh) & 1) == 0
            ranks, rows, w, sh = ranks[fresh], rows[fresh], w[fresh], sh[fresh]
            words[w] = words[w] | (np.uint32(1) << sh)     # last writer wins
            kept = ((words[w] >> sh) & 1) == 1
            ranks, rows = ranks[kept], rows[kept]
        else:
            fresh = ~seen[ranks]
            ranks, rows = ranks[fresh], rows[fresh]
        ranks, first = np.unique(ranks, return_index=True)
        if ranks.size == 0:
            return counts
        seen[ranks] = True
        frontier = rows[first]
        counts.append(int(ranks.size))


def prefix_reversals(n: int) -> List[List[int]]:
    """The pancake graph's generators: reverse the first k, k = 2..n."""
    return [list(range(k))[::-1] + list(range(k, n)) for k in range(2, n + 1)]


def adjacent_transpositions(n: int) -> List[List[int]]:
    """The bubble-sort graph's generators: swap positions i and i+1."""
    out = []
    for i in range(n - 1):
        g = list(range(n))
        g[i], g[i + 1] = g[i + 1], g[i]
        out.append(g)
    return out
