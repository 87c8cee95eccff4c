"""``correct`` has to come out false when the timed path is broken.

For each cell of ``BENCHMARK.json``, each fault in the faults file beside
its configuration's search file is planted in the program underneath a
whole run (the harness's look for a chip aside), at the configuration's
CPU size; and the control, the plain reference with its guarantee broken
put in the program's place, has to fail the same check.  The benchmark's
own runs do neither.  The explicit graph of ``test_perfbench_searches``,
searched by the RoomyList engine, is one more case of both tests, with
its own faults file and control.
"""
from __future__ import annotations

import os

import pytest

from perfbench import harness
from perfbench.control import control_readings
from perfbench.test_perfbench_harness import (CELLS, cell_config,  # noqa: F401
                                              load_faults, run, small_bench)
from perfbench.test_perfbench_searches import (CELL, FIXTURES,  # noqa: F401
                                               explicit_bench)

# Each cell of BENCHMARK.json, and the fixture's where it is not one of them.
TESTED_CELLS = CELLS + [CELL] * (CELL not in CELLS)


def fault_names(cell):
    if cell in CELLS:
        return sorted(load_faults(cell_config(cell)))
    return sorted(harness.load_file(
        os.path.join(FIXTURES, "explicit.faults.py"), "FAULTS"))


FAULT_CASES = [(cell, fault) for cell in TESTED_CELLS
               for fault in fault_names(cell)]


@pytest.fixture
def bench(request, cell):
    """(spec, bench dir) in which ``cell`` runs at its CPU size."""
    return request.getfixturevalue(
        "small_bench" if cell in CELLS else "explicit_bench")


@pytest.mark.parametrize("cell, fault", FAULT_CASES)
def test_a_broken_timed_path_is_not_correct(bench, monkeypatch, cell,
                                            fault):
    spec, bench_dir = bench
    config = harness.load_config(harness.find_cell(spec, cell)["config"],
                                 bench_dir)
    module, attr, wrap = load_faults(config, os.path.dirname(bench_dir))[fault]
    monkeypatch.setattr(module, attr, wrap(getattr(module, attr)))
    result = run(spec, bench_dir, cell)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    gap = result["checks"]["worst_level_gap"]
    assert gap["value"] > gap["limit"] == 0


# The fixture's sound path is test_an_explicit_graph_runs_through_the_harness.
@pytest.mark.parametrize("cell", CELLS)
def test_the_sound_path_is_correct(small_bench, cell):
    spec, bench = small_bench
    assert run(spec, bench, cell)["correct"] is True


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("cell", TESTED_CELLS)
def test_the_control_is_not_correct(bench, cell, seed):
    spec, bench_dir = bench
    (_, numbers, correct), = control_readings(spec, cell, [seed], bench_dir)
    assert correct is False
    assert numbers["worst_level_gap"]["value"] > 0
