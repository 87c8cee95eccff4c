"""How the harness searches an explicit graph with the RoomyList engine,
and its plain reference: a fixture of the tests, which shows that a
configuration that is not a Cayley graph runs through the harness.

The configuration lists ``n_vertices`` and its undirected ``edges``.  The
program searches with ``repro.core.constructs.breadth_first_search`` (Tier
J: rows of one 32-bit word, one lexsort and one scatter a level) from a
table of each vertex's neighbours; the reference is a plain
breadth-first search over adjacency lists built from the same edges.  A
search covers the vertices it reaches, which depends on the start.  Each
search books its levels in the ``explicit`` counters, which a per-layer
reader of the fixture reads.

As every configuration does, it states for the tests its ``"cpu"`` size
(none to cut: 64 vertices), and its faults sit beside it in
``explicit.faults.py``, whose ``FAULTS`` maps a fault's name to
``(module, attribute, wrapper)``.  The tests replace ``module.attribute``
(here ``constructs._bfs_level``, one RoomyList level) with ``wrapper(the
original)`` for a whole run, which must then come out not correct: a
wrapper takes the level's own arguments and breaks the frontier, the
visited set or a count it returns, not only its timing.  See
``cayley.search.py`` for the faults every such file names.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from repro.core import obs

STATS = obs.counters("explicit", {"searches": 0, "levels": 0})


def level_counts(n_vertices: int, edges, start: int,
                 lost_updates: bool = False) -> List[int]:
    """Vertices at each distance from ``start``, level 0 first.

    ``lost_updates=True`` is the control: visited vertices are kept one
    bit each, 32 to a word, and a level's marks are written as whole words
    read before the level, so where two new vertices share a word only the
    last write survives.  Lost vertices are found again later, at a wrong
    distance."""
    adj: List[List[int]] = [[] for _ in range(n_vertices)]
    for u, v in edges:
        adj[u].append(v)
        adj[v].append(u)
    words = [0] * (-(-n_vertices // 32))
    words[start // 32] |= 1 << (start % 32)
    frontier, counts = [start], [1]
    while True:
        found = {}
        for u in frontier:
            for v in adj[u]:
                if not words[v // 32] >> (v % 32) & 1:
                    found[v] = words[v // 32]     # the word as read
        if not found:
            return counts
        for v, read in found.items():
            words[v // 32] = ((read if lost_updates else words[v // 32])
                              | 1 << (v % 32))
        frontier = [v for v in found if words[v // 32] >> (v % 32) & 1]
        counts.append(len(frontier))


class Explicit:
    """The calls the harness makes for one explicit-graph configuration."""

    scopes = ()

    def __init__(self, config, root: str):
        self.n_states = config["n_vertices"]
        self.edges = [tuple(e) for e in config["edges"]]
        degree = np.zeros(self.n_states, np.int64)
        for u, v in self.edges:
            degree[u] += 1
            degree[v] += 1
        self.fanout = int(degree.max())
        self._gen_next = None

    def gen_next(self):
        """Row -> (its neighbours as rows, which are real), built once."""
        if self._gen_next is None:
            import jax.numpy as jnp
            table = np.zeros((self.n_states, self.fanout), np.uint32)
            fill = np.zeros(self.n_states, np.int64)
            for u, v in self.edges:
                for a, b in ((u, v), (v, u)):
                    table[a, fill[a]] = b
                    fill[a] += 1
            valid = np.arange(self.fanout)[None, :] < fill[:, None]
            table, valid = jnp.asarray(table), jnp.asarray(valid)

            def gen_next(row):
                v = row[0].astype(jnp.int32)
                return table[v][:, None], valid[v]
            self._gen_next = gen_next
        return self._gen_next

    def draw_start(self, rng) -> int:
        return int(rng.integers(0, self.n_states))

    def run(self, start: int, max_levels: Optional[int] = None):
        from repro.core import constructs as C
        kw = {} if max_levels is None else {"max_levels": max_levels}
        res = C.breadth_first_search(
            np.array([[start]], np.uint32), self.gen_next(), self.fanout, 1,
            all_capacity=self.n_states, level_capacity=self.n_states, **kw)
        STATS["searches"] += 1
        STATS["levels"] += len(res.level_sizes)
        return res.level_sizes, res.all.data

    def states(self, sizes: List[int]) -> int:
        return sum(sizes)

    def reference(self, start: int) -> List[int]:
        return level_counts(self.n_states, self.edges, start)

    def control(self, rng):
        start = int(rng.integers(0, self.n_states))
        return (level_counts(self.n_states, self.edges, start,
                             lost_updates=True),
                self.reference(start))


class DropsTheLastLevel(Explicit):
    """A search file that is wrong: it reports one level too few."""

    def run(self, start: int, max_levels: Optional[int] = None):
        sizes, data = super().run(start, max_levels)
        return sizes[:-1], data
