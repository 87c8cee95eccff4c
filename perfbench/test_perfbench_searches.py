"""Tests of the configurations' search files, on the CPU.

``cayley.search.py`` serves both cells; its starts are pinned to those the
harness drew before the file existed.  ``explicit_bench`` builds a
configuration that is not a Cayley graph (``fixtures/explicit.search.py``:
an explicit graph searched by the RoomyList engine) with a per-layer
reader of its own, as files beside the benchmark's, and runs it through
the harness unchanged.
"""
from __future__ import annotations

import io
import json
import os
import shutil

import jax
import numpy as np
import pytest

from perfbench import harness, reference
from perfbench.test_perfbench_harness import cpu_device, small_bench  # noqa: F401

FIXTURES = os.path.join(harness.BENCH_DIR, "fixtures")
CELL = "explicit-64.search"
COUNTER_METRIC = "explicit.levels_per_search"


def explicit_graph(seed=5):
    """64 vertices in two components: 48 joined by a random spanning tree
    and 40 more random edges, and a cycle through the other 16."""
    rng = np.random.default_rng(seed)
    edges = {(int(rng.integers(0, v)), v) for v in range(1, 48)}
    while len(edges) < 47 + 40:
        u, v = sorted(int(x) for x in rng.choice(48, 2, replace=False))
        edges.add((u, v))
    edges |= {(v, v + 1) for v in range(48, 63)} | {(48, 63)}
    return {"name": "explicit-64", "n_vertices": 64,
            "edges": sorted([u, v] for u, v in edges),
            "search": {"file": "perfbench/configs/explicit.search.py",
                       "function": "Explicit"}}


@pytest.fixture
def explicit_bench(tmp_path):
    """(spec, bench dir) of one cell over ``explicit_graph``: the
    benchmark's files, with the fixture's search file, its counter reader,
    configuration and traffic added beside them."""
    bench = tmp_path / "perfbench"
    shutil.copytree(harness.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    shutil.copy(os.path.join(FIXTURES, "explicit.search.py"),
                bench / "configs")
    shutil.copy(os.path.join(FIXTURES, f"{COUNTER_METRIC}.py"),
                bench / "metrics")
    (bench / "configs" / "explicit-64.json").write_text(
        json.dumps(explicit_graph()))
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(
        {"traffic": "search", "why": "whole searches from random starts",
         "limits": {"worst_level_gap": 0}}))
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["devices"][jax.devices()[0].device_kind] = {"hbm_bytes_per_s": 1e9}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    spec = {
        "configs": [{"name": "explicit-64"}],
        "workloads": [{"name": CELL, "config": "explicit-64",
                       "traffic": "search", "chips": 1}],
        "end_to_end": [{"name": "states_per_s", "unit": "states/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": COUNTER_METRIC, "unit": "1",
                       "workloads": [CELL]},
                      {"name": "device.idle_share", "unit": "%"}],
    }
    return spec, str(bench)


def run(spec, bench, traced=False, seed=3):
    return harness.run_cell(spec, CELL, seed, 0.01, traced, t_start=0.0,
                            bench_dir=bench, log=io.StringIO(),
                            trace_dir=os.path.join(bench, "trace"),
                            chip_check=cpu_device)


def reached(graph, start):
    return sum(graph.reference(start))


@pytest.fixture
def timed(monkeypatch):
    """The window's searches of the next run, as the harness timed them."""
    real = harness._searches
    kept = []

    def keep(*args):
        kept.extend(real(*args))
        return kept

    monkeypatch.setattr(harness, "_searches", keep)
    return kept


def test_an_explicit_graph_runs_through_the_harness(explicit_bench, timed):
    spec, bench = explicit_bench
    result = run(spec, bench)
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"] == {"worst_level_gap": {"value": 0, "limit": 0}}
    graph = harness.load_search(harness.load_config("explicit-64", bench),
                                bench)
    assert [s.states for s in timed] == [reached(graph, s.start)
                                         for s in timed]
    assert {reached(graph, s) for s in (0, 63)} == {48, 16}
    # The window runs from the first search's start to the last one's end.
    rate = result["metrics"]["states_per_s"]["value"]
    assert rate <= sum(s.states for s in timed) / sum(s.seconds
                                                      for s in timed)
    assert rate == pytest.approx(sum(s.states for s in timed)
                                 / sum(s.seconds for s in timed), rel=0.5)


def test_a_wrong_search_file_fails_the_check(explicit_bench):
    spec, bench = explicit_bench
    path = os.path.join(bench, "configs", "explicit-64.json")
    with open(path) as fh:
        config = json.load(fh)
    config["search"]["function"] = "DropsTheLastLevel"
    with open(path, "w") as fh:
        json.dump(config, fh)
    result = run(spec, bench)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1


def test_a_reader_of_the_fixture_reads_its_counter(explicit_bench, timed):
    spec, bench = explicit_bench
    result = run(spec, bench, traced=True)
    assert result["correct"] is True
    # The window's counters alone: the set-up search is not among them.
    assert result["metrics"][COUNTER_METRIC] == {
        "value": sum(len(s.sizes) for s in timed) / len(timed), "unit": "1"}


def test_states_per_s_sums_each_search_states():
    searches = [harness.Search(0, [1, 4], 5, 0.5),
                harness.Search(1, [1, 6], 7, 0.5)]
    ctx = harness.Context({}, {}, None, searches, 2.0, 0.0, None, {})
    assert harness.load_metric("states_per_s")(ctx) == 6.0


def test_cayley_starts_are_the_ones_drawn_before(small_bench):
    """The set-up start, then the window's, for one seed, as the harness
    drew them with ``rng.integers(0, n!)`` before configurations named
    their search; and the control's start, ``rng.permutation(n)``."""
    seed = 4300001413
    want = {"pancake-10": [2744738, 858449, 779731, 1131435, 2098017,
                           1104468],
            "bubblesort-9": [274473, 85844, 77973, 113143, 209801, 110446]}
    for name, starts in want.items():
        graph = harness.load_search(harness.load_config(name))
        rng = np.random.default_rng(seed)
        assert [graph.draw_start(rng) for _ in starts] == starts
        assert graph.states([1]) == graph.n_states
    spec, bench = small_bench
    for c in spec["configs"]:
        graph = harness.load_search(harness.load_config(c["name"], bench),
                                    bench)
        got, want_levels = graph.control(np.random.default_rng(11))
        start = np.random.default_rng(11).permutation(5)
        assert got == reference.level_counts(5, graph.generators,
                                             lost_updates=True, start=start)
        assert want_levels == graph.reference(None) == (
            reference.level_counts(5, graph.generators))
