"""Tests of the chip benchmark's harness, on the CPU at small sizes.

The harness's look for a chip is replaced by one that hands it JAX's CPU
device; everything else of a run is the harness's own.  ``small_bench``
copies the benchmark's files with each configuration cut to the size its
``"cpu"`` key states.  The tests of a cell are cases of each cell that
``BENCHMARK.json`` lists, each with its own configuration's files; what
holds only of a Cayley graph is tested for the configurations that name
``cayley.search.py``.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness, reference, tracefold
from perfbench.control import control_readings

ROOT = harness.ROOT
FIXTURE = os.path.join(harness.BENCH_DIR, "fixtures", "trace_pancake8.json")
SPEC = harness.load_spec(ROOT)
CELLS = [c["name"] for c in SPEC["workloads"]]
CPU_STATES = 10_000     # the most states a configuration's CPU size may have
CAYLEY = "perfbench/configs/cayley.search.py"
CAYLEY_CONFIGS = [c["name"] for c in SPEC["configs"]
                  if harness.load_config(c["name"])["search"]["file"] == CAYLEY]
# The members of a search object, as harness.py's docstring lists them.
SEARCH_MEMBERS = ("n_states", "fanout", "scopes", "draw_start", "run",
                  "states", "reference", "control")
# The faults every faults file names; on more than one chip, also the
# exchange between chips left out.
FAULT_NAMES = {"unchanged_state", "half_the_batch", "answer_altered"}
MULTICHIP_FAULT = "exchange_left_out"
GENERATORS = {"pancake": reference.prefix_reversals,
              "bubblesort": reference.adjacent_transpositions}
KNOWN_PANCAKE = {   # OEIS A067607 rows; n=9 equals Tier D's counts
    6: [1, 5, 20, 79, 199, 281, 133, 2],
    7: [1, 6, 30, 149, 543, 1357, 1903, 1016, 35],
    9: [1, 8, 56, 391, 2278, 10666, 38015, 93585, 132697, 79379, 5804],
}


def mahonian(n):
    """T(n, k), k = 0..n(n-1)/2: permutations of n with k inversions."""
    t = [1]
    for m in range(2, n + 1):
        new = [0] * (len(t) + m - 1)
        for k, v in enumerate(t):
            for j in range(m):
                new[k + j] += v
        t = new
    return t


def cpu_device(chips):
    return jax.devices()[0]


def cell_config(cell: str):
    """The configuration that a cell of ``BENCHMARK.json`` runs."""
    return harness.load_config(harness.find_cell(SPEC, cell)["config"])


def cpu_size(config):
    """The configuration cut to the size its ``"cpu"`` key states."""
    return {**config, **config["cpu"]}


def load_faults(config, root=ROOT):
    """A configuration's faults, from the file beside its search file
    (``<stem>.faults.py`` for ``<stem>.search.py``): name -> (module,
    attribute, wrapper), planted as ``module.attribute = wrapper(the
    original)``."""
    search = config["search"]["file"]
    assert search.endswith(".search.py"), search
    path = os.path.join(root, search[:-len(".search.py")] + ".faults.py")
    return harness.load_file(path, "FAULTS")


@pytest.fixture
def small_bench(tmp_path):
    """The benchmark's files with each configuration cut to its CPU size,
    as (spec, dir)."""
    bench = tmp_path / "perfbench"
    shutil.copytree(harness.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    spec = harness.load_spec(ROOT)
    for c in spec["configs"]:
        path = tmp_path / c["file"]
        path.write_text(json.dumps(cpu_size(json.loads(path.read_text()))))
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["devices"][jax.devices()[0].device_kind] = {"hbm_bytes_per_s": 1e9}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    return spec, str(bench)


def run(spec, bench, cell, traced=False, seed=3):
    return harness.run_cell(spec, cell, seed, 0.01, traced, t_start=0.0,
                            bench_dir=bench,
                            trace_dir=os.path.join(bench, "trace"),
                            chip_check=cpu_device)


# ------------------------------------------------------------ discovery

@pytest.mark.parametrize("cell", CELLS)
def test_discovery_finds_every_named_file(cell):
    entry = harness.find_cell(SPEC, cell)
    (c,) = [c for c in SPEC["configs"] if c["name"] == entry["config"]]
    assert os.path.join(ROOT, c["file"]) == os.path.join(
        harness.BENCH_DIR, "configs", f"{c['name']}.json")
    cfg = harness.load_config(c["name"])
    assert set(cfg["search"]) == {"file", "function"}
    graph = harness.load_search(cfg)
    small = harness.load_search(cpu_size(cfg))
    for member in SEARCH_MEMBERS:
        assert hasattr(graph, member) and hasattr(small, member), member
    assert small.n_states <= min(graph.n_states, CPU_STATES)
    faults = load_faults(cfg)
    assert FAULT_NAMES <= set(faults)
    assert entry["chips"] == 1 or MULTICHIP_FAULT in faults
    for module, attr, wrap in faults.values():
        assert callable(getattr(module, attr)) and callable(wrap)
    assert harness.load_traffic(cell)["traffic"] == entry["traffic"]
    for traced in (False, True):
        metrics = harness.cell_metrics(SPEC, cell, traced)
        assert metrics
        for m in metrics:
            assert callable(harness.load_metric(m["name"]))


@pytest.mark.parametrize("name", CAYLEY_CONFIGS)
def test_a_cayley_configuration_is_its_graph_at_both_sizes(name):
    """Its generators are the family's, its CPU size the n=5 graph that
    the tests' pinned numbers were taken at."""
    cfg = harness.load_config(name)
    family = GENERATORS[name.split("-")[0]]
    assert callable(harness.load_file(os.path.join(
        ROOT, cfg["rule"]["file"]), cfg["rule"]["function"]))
    assert cfg["search"] == {"file": CAYLEY, "function": "Cayley"}
    assert cfg["cpu"] == {"n": 5, "generators": family(5)}
    for size in (cfg, cpu_size(cfg)):
        assert size["generators"] == family(size["n"])
        graph = harness.load_search(size)
        assert graph.n_states == math.factorial(size["n"])
        assert graph.fanout == size["n"] - 1


def test_discovery_refuses_unknown_names():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.load_peaks("TPU v0")
    with pytest.raises(KeyError, match="no workload"):
        harness.find_cell(SPEC, "no-such.cell")


def test_cell_metrics_follow_the_workloads_key():
    spec = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["y"]}],
            "per_layer": [{"name": "c", "workloads": ["x"]}]}
    assert [m["name"] for m in harness.cell_metrics(spec, "x", False)] == ["a"]
    assert [m["name"] for m in harness.cell_metrics(spec, "y", False)] == [
        "a", "b"]
    assert [m["name"] for m in harness.cell_metrics(spec, "x", True)] == ["c"]
    assert harness.cell_metrics(spec, "y", True) == []


# ------------------------------------------------------- roofline bytes

def test_roofline_bytes_from_n_fanout_and_counts():
    f = harness.load_file(os.path.join(harness.BENCH_DIR, "metrics",
                                       "bitpack_roofline.py"), "search_bytes")
    # pancake-10: 12 level calls over 226,800 words (907,200 bytes read and
    # written each), and 9 targets of 4 bytes for each of the 10! states.
    sizes = [1, 9] + [0] * 9 + [math.factorial(10) - 10]
    assert f(math.factorial(10), 9, sizes) == (
        12 * 2 * 4 * 226_800 + 4 * 9 * math.factorial(10)) == 152_409_600
    # bubblesort-9: 37 level calls over 22,680 words, 8 targets a state.
    sizes = reference.level_counts(9, reference.adjacent_transpositions(9))
    assert len(sizes) == 37
    assert f(math.factorial(9), 8, sizes) == (
        37 * 2 * 4 * 22_680 + 4 * 8 * math.factorial(9)) == 18_325_440


# ------------------------------------------------------ trace reduction

def test_fold_of_recorded_trace():
    with open(FIXTURE) as fh:
        trace = json.load(fh)
    got = tracefold.fold(trace, harness.KERNEL_TAG)
    searches = [e for e in trace["host"] if e[0] == "perfbench.search"]
    lo = min(s for _, s, _ in searches)
    hi = max(s + d for _, s, d in searches)
    assert got["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["n_kernel_events"] > 0
    kernels = sum(d for n, s, d in trace["device"]
                  if harness.KERNEL_TAG in n and s >= lo and s + d <= hi)
    assert got["kernel_s"] == pytest.approx(kernels / 1e9)
    # The pancake-8 search: the start's mark, then 10 level calls of one
    # fused kernel each.
    assert got["n_kernel_events"] == 11 and got["kernel_s"] > 0.9 * got["busy_s"]
    assert got["device_ops"][0][0] == "roomy_bitpack_mark_rotate_count.1"
    assert got["kernel_s"] + got["other_ops_s"] <= got["busy_s"] * (1 + 1e-9)
    idle = sum(v for _, v in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_fold_nesting_gaps_and_labels():
    trace = {
        "device": [["while.1", 100, 50], ["roomy_bitpack_mark", 110, 20],
                   ["fusion.2", 130, 10], ["fusion.3", 200, 10]],
        "host": [["perfbench.search", 0, 300],
                 ["PjitFunction(level)", 0, 290],
                 ["trace_to_jaxpr_dynamic", 0, 60],
                 ["backend_compile_and_load", 150, 40]],
    }
    got = tracefold.fold(trace, "roomy_bitpack_")
    assert got["window_s"] == 300e-9
    assert got["busy_s"] == 60e-9
    assert got["kernel_s"] == 20e-9
    assert got["other_ops_s"] == 40e-9          # while's self time 20 + 10 + 10
    # Idle: [0, 100), [150, 200), [210, 300); each stretch goes to the most
    # specific activity over it.
    assert dict(got["idle_gaps"]) == pytest.approx({
        "trace_to_jaxpr": 60e-9, "jit_call": 40e-9 + 10e-9 + 80e-9,
        "compile_or_cache_load": 40e-9, "host_other": 10e-9})
    assert dict(got["device_ops"]) == pytest.approx({
        "while.1": 20e-9, "roomy_bitpack_mark": 20e-9, "fusion.2": 10e-9,
        "fusion.3": 10e-9})


# ------------------------------------------------------------- a whole run

@pytest.mark.parametrize("traced", [False, True])
def test_last_line_schema(small_bench, traced):
    spec, bench = small_bench
    cell = "pancake-10.search"
    result = run(spec, bench, cell, traced)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["checks"] == {"worst_level_gap": {"value": 0, "limit": 0}}
    dev = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    wanted = {m["name"] for m in harness.cell_metrics(spec, cell, traced)}
    assert set(result["metrics"]) <= wanted
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
    if traced:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps",
                                            "idle_by_span", "device_by_scope"}
        assert all(len(v) <= 10 for v in result["breakdown"].values())
        assert "driver.compile_s_per_search" in result["metrics"]
    else:
        assert {"states_per_s", "setup_s"} <= set(result["metrics"])
        assert "breakdown" not in result
    json.dumps(result)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join("perfbench", "run.py"), "--workload",
         "bubblesort-9.search", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 1
    assert "no TPU found" in proc.stderr
    assert proc.stdout.strip() == ""


def test_refuses_to_run_short_of_chips(monkeypatch):
    class OneTpu:
        platform = "tpu"
    monkeypatch.setattr(jax, "devices", lambda: [OneTpu()])
    assert isinstance(harness.require_chips(1), OneTpu)
    with pytest.raises(harness.NoChip, match="asks for 4 chips"):
        harness.require_chips(4)


# ------------------------------------------------------ the graphs' counts

@pytest.mark.parametrize("n", [4, 5, 6, 7])
def test_bubblesort_rule_counts_are_mahonian(n):
    from repro.core import constructs as C
    rule = harness.load_file(os.path.join(harness.BENCH_DIR, "configs",
                                          "bubblesort.rule.py"),
                             "neighbor_jnp")(n)
    sizes, _ = C.implicit_bfs(math.factorial(n), [5 % math.factorial(n)],
                              rule, impl="interpret")
    assert sizes == mahonian(n)


@pytest.mark.parametrize("n", [6, 7])
def test_pancake_rule_counts_match_oeis(n):
    from repro.core import constructs as C
    rule = harness.load_file(os.path.join(harness.BENCH_DIR, "configs",
                                          "pancake.rule.py"),
                             "neighbor_jnp")(n)
    sizes, _ = C.implicit_bfs(math.factorial(n), [11], rule,
                              impl="interpret")
    assert sizes == KNOWN_PANCAKE[n]


@pytest.mark.parametrize("n", [4, 5, 6, 7, 8])
def test_reference_counts(n):
    assert reference.level_counts(
        n, reference.adjacent_transpositions(n)) == mahonian(n)
    pancake = reference.level_counts(n, reference.prefix_reversals(n))
    assert sum(pancake) == math.factorial(n)
    if n in KNOWN_PANCAKE:
        assert pancake == KNOWN_PANCAKE[n]


def test_reference_is_the_same_from_every_start():
    gens = reference.prefix_reversals(6)
    assert reference.level_counts(6, gens, start=[3, 1, 5, 0, 2, 4]) == \
        KNOWN_PANCAKE[6]


def test_lex_rank_is_a_bijection():
    import itertools
    import numpy as np
    perms = np.array(list(itertools.permutations(range(5))), np.int8)
    assert reference.lex_rank(perms).tolist() == list(range(120))
