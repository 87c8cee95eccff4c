"""``correct`` has to come out false when the timed path is broken.

Each fault is planted in the program underneath a whole run (the harness's
look for a chip aside), at n=5 on the CPU; and the control, the plain
reference with lost updates put in the program's place, has to fail the
same check.  The benchmark's own runs do neither.  The same holds for a
configuration that is not a Cayley graph: the explicit graph of
``test_perfbench_searches``, searched by the RoomyList engine, whose
search file provides a control of its own.
"""
from __future__ import annotations

import pytest

from perfbench.control import control_readings
from perfbench.test_perfbench_harness import run, small_bench  # noqa: F401
from perfbench.test_perfbench_searches import CELL, explicit_bench  # noqa: F401
from perfbench.test_perfbench_searches import run as run_explicit
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


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(small_bench, monkeypatch,
                                            fault):
    spec, bench = small_bench
    module, attr, wrap = FAULTS[fault]
    monkeypatch.setattr(module, attr, wrap(getattr(module, attr)))
    for cell in spec["workloads"]:
        result = run(spec, bench, cell["name"])
        assert result["correct"] is False, (fault, cell["name"])
        assert result["failed"] == result["attempted"] >= 1
        gap = result["checks"]["worst_level_gap"]
        assert gap["value"] > gap["limit"] == 0


def test_the_sound_path_is_correct(small_bench):
    spec, bench = small_bench
    for cell in spec["workloads"]:
        assert run(spec, bench, cell["name"])["correct"] is True


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_control_is_not_correct(small_bench, seed):
    spec, bench = small_bench
    for cell in spec["workloads"]:
        for _, numbers, correct in control_readings(spec, cell["name"],
                                                    [seed], bench):
            assert correct is False
            assert numbers["worst_level_gap"]["value"] > 0


# ------------------------------------- a configuration that is no Cayley graph

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


EXPLICIT_FAULTS = {
    "unchanged_state": _level_unchanged,
    "half_the_batch": _half_the_frontier,
    "answer_altered": _frontier_miscounted,
}


@pytest.mark.parametrize("fault", sorted(EXPLICIT_FAULTS))
def test_a_broken_timed_path_off_cayley_is_not_correct(explicit_bench,
                                                       monkeypatch, fault):
    spec, bench = explicit_bench
    monkeypatch.setattr(C, "_bfs_level",
                        EXPLICIT_FAULTS[fault](C._bfs_level))
    result = run_explicit(spec, bench)
    assert result["correct"] is False, fault
    assert result["failed"] == result["attempted"] >= 1
    gap = result["checks"]["worst_level_gap"]
    assert gap["value"] > gap["limit"] == 0


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_control_off_cayley_is_not_correct(explicit_bench, seed):
    spec, bench = explicit_bench
    (_, numbers, correct), = control_readings(spec, CELL, [seed], bench)
    assert correct is False
    assert numbers["worst_level_gap"]["value"] > 0
