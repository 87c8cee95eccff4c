"""``correct`` has to come out false when the timed path is broken.

Each fault is planted in the program underneath a whole run (the harness's
look for a chip aside), at n=5 on the CPU; and the control, the plain
reference with lost updates put in the program's place, has to fail the
same check.  The benchmark's own runs do neither.
"""
from __future__ import annotations

import pytest

from perfbench import harness
from perfbench.control import control_readings
from perfbench.test_perfbench_harness import run, small_bench  # noqa: F401
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
