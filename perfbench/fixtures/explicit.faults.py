"""Faults planted in the timed path of ``explicit.search.py``, the RoomyList
engine's level: a fixture of the tests, beside that search file.  Each must
turn a run's ``correct`` false; see ``explicit.search.py`` for the
contract.
"""
from repro.core import constructs as C


def _level_unchanged(level):
    """A RoomyList level that returns its frontier and visited set as it
    found them."""
    def step(cur, all_lst, gen_next, fanout, next_cap):
        _, _, overflow = level(cur, all_lst, gen_next, fanout, next_cap)
        return cur, all_lst, overflow
    return step


def _half_the_frontier(level):
    """A RoomyList level that expands the first half of its frontier and
    leaves out the rest."""
    def step(cur, all_lst, gen_next, fanout, next_cap):
        half = cur._replace(count=(cur.count + 1) // 2)
        return level(half, all_lst, gen_next, fanout, next_cap)
    return step


def _frontier_miscounted(level):
    """A RoomyList level whose new frontier's count is altered where it is
    produced."""
    def step(cur, all_lst, gen_next, fanout, next_cap):
        nxt, all2, overflow = level(cur, all_lst, gen_next, fanout, next_cap)
        more = (nxt.count > 0).astype(nxt.count.dtype)
        return nxt._replace(count=nxt.count + more), all2, overflow
    return step


FAULTS = {
    "unchanged_state": (C, "_bfs_level", _level_unchanged),
    "half_the_batch": (C, "_bfs_level", _half_the_frontier),
    "answer_altered": (C, "_bfs_level", _frontier_miscounted),
}
