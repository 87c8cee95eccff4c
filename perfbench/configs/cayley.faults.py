"""Faults planted in the timed path of ``cayley.search.py``: each must turn
a run's ``correct`` false.  The tests plant them, one at a time, under a
whole run at the configuration's CPU size; the benchmark's own runs never
load this file.  See ``cayley.search.py`` for the contract.
"""
from repro.core import bitarray as BA
from repro.core import constructs as C


def _unchanged_state(level):
    """A level step that returns its state unchanged (its count as found)."""
    def step(data, **kw):
        _, cnt = level(data, **kw)
        return data, cnt
    return step


def _half_the_marks(mark_rotate_count):
    """The last block's marks with the second half of the batch left out."""
    def fused(data, idx, n, **kw):
        half = idx.shape[0] // 2
        cap = data.shape[0] * BA.FIELDS_PER_WORD
        return mark_rotate_count(data, idx.at[half:].set(cap), n, **kw)
    return fused


def _count_off_by_one(level):
    """A level's count altered where it is produced."""
    def step(data, **kw):
        data, cnt = level(data, **kw)
        return data, cnt + (cnt > 0).astype(cnt.dtype)
    return step


FAULTS = {
    "unchanged_state": (C, "_implicit_level", _unchanged_state),
    "half_the_batch": (BA, "mark_rotate_count", _half_the_marks),
    "answer_altered": (C, "_implicit_level", _count_off_by_one),
}
