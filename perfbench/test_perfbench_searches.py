"""Tests of the configurations' search files, on the CPU.

``cayley.search.py`` serves both cells; its starts are pinned to those the
harness drew before the file existed.  ``add_explicit`` adds a
configuration that is not a Cayley graph (``fixtures/explicit.search.py``:
an explicit graph searched by the RoomyList engine), with its faults file
and a per-layer reader of its own, as new files beside the benchmark's;
``explicit_bench`` runs it through the harness unchanged, and
``test_a_configuration_joins_as_new_files`` shows that the per-cell tests
take it as they find it.
"""
from __future__ import annotations

import io
import json
import os
import shutil
import subprocess
import sys
import xml.etree.ElementTree as ET

import jax
import numpy as np
import pytest

from perfbench import harness, reference
from perfbench.test_perfbench_harness import (CAYLEY_CONFIGS,  # noqa: F401
                                              cpu_device, small_bench)

FIXTURES = os.path.join(harness.BENCH_DIR, "fixtures")
CELL = "explicit-64.search"
COUNTER_METRIC = "explicit.levels_per_search"
PER_CELL_TESTS = ("test_discovery_finds_every_named_file",
                  "test_a_broken_timed_path_is_not_correct",
                  "test_the_sound_path_is_correct",
                  "test_the_control_is_not_correct")


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
                       "function": "Explicit"},
            "cpu": {}}


def add_explicit(bench: str):
    """Add the fixture's configuration and cell to the benchmark directory
    ``bench`` by new files alone: the configuration, its search and faults
    files, its traffic and its counter reader.  Returns the entries that
    ``BENCHMARK.json`` gains under ``configs``, ``workloads`` and
    ``per_layer``."""
    for name in ("explicit.search.py", "explicit.faults.py"):
        shutil.copy(os.path.join(FIXTURES, name),
                    os.path.join(bench, "configs"))
    shutil.copy(os.path.join(FIXTURES, f"{COUNTER_METRIC}.py"),
                os.path.join(bench, "metrics"))
    with open(os.path.join(bench, "configs", "explicit-64.json"), "w") as fh:
        json.dump(explicit_graph(), fh)
    with open(os.path.join(bench, "workloads", f"{CELL}.json"), "w") as fh:
        json.dump({"traffic": "search",
                   "why": "whole searches from random starts",
                   "limits": {"worst_level_gap": 0}}, fh)
    config = {"name": "explicit-64", "source": "a fixture of the tests",
              "file": "perfbench/configs/explicit-64.json", "reduced": [],
              "why": "an explicit graph searched by the RoomyList engine"}
    cell = {"name": CELL, "config": "explicit-64", "traffic": "search",
            "chips": 1, "why": "whole searches from random starts"}
    metric = {"name": COUNTER_METRIC, "unit": "1", "better": "lower",
              "source": "program_counter", "layer": "search driver",
              "moves": "states_per_s", "workloads": [CELL]}
    return config, cell, metric


@pytest.fixture
def explicit_bench(tmp_path):
    """(spec, bench dir) of one cell over ``explicit_graph``: the
    benchmark's files, with the fixture's added beside them."""
    bench = tmp_path / "perfbench"
    shutil.copytree(harness.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    config, cell, metric = add_explicit(str(bench))
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["devices"][jax.devices()[0].device_kind] = {"hbm_bytes_per_s": 1e9}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    spec = {
        "configs": [config],
        "workloads": [cell],
        "end_to_end": [{"name": "states_per_s", "unit": "states/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [metric, {"name": "device.idle_share", "unit": "%"}],
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
    for name in CAYLEY_CONFIGS:
        graph = harness.load_search(harness.load_config(name, bench), bench)
        got, want_levels = graph.control(np.random.default_rng(11))
        start = np.random.default_rng(11).permutation(graph.n)
        assert got == reference.level_counts(graph.n, graph.generators,
                                             lost_updates=True, start=start)
        assert want_levels == graph.reference(None) == (
            reference.level_counts(graph.n, graph.generators))


def test_a_configuration_joins_as_new_files(tmp_path):
    """The fixture joins a copy of the benchmark as one more configuration
    and cell by new files and new entries alone; the copy's per-cell tests
    then take it, as a subprocess on the CPU, and pass."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH_DIR, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    spec = harness.load_spec(harness.ROOT)
    config, cell, metric = add_explicit(str(root / "perfbench"))
    assert all(p.read_bytes() == b for p, b in before.items())
    spec["configs"].append(config)
    spec["workloads"].append(cell)
    spec["per_layer"].append(metric)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    report = tmp_path / "report.xml"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(root), os.path.join(harness.ROOT, "src")]))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-k", "explicit-64 and not test_a_configuration_joins_as_new_files",
         f"--junitxml={report}", "perfbench"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-6000:] + proc.stderr[-2000:]
    passed = [case.get("name") for case in ET.parse(report).iter("testcase")
              if not any(case.find(k) is not None
                         for k in ("failure", "error", "skipped"))]
    for test in PER_CELL_TESTS:
        assert any(name.startswith(f"{test}[") and CELL in name
                   for name in passed), (test, passed)
