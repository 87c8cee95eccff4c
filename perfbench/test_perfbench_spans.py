"""Tests of the reduction of the program's spans and op scopes
(``spanfold.py``) and of what a traced run reports from them, on the CPU.

The traced runs use ``small_bench`` of the harness's tests: the
benchmark's files with each configuration cut to its CPU size (n=5 for
both Cayley graphs), and JAX's CPU device in place of the chip.
"""
from __future__ import annotations

import ast
import io
import json
import os
import subprocess
import sys

import pytest

from perfbench import harness, spanfold, tracefold
from perfbench.test_perfbench_harness import (FIXTURE, cpu_device,  # noqa: F401
                                              small_bench)
from repro.core import constructs as C
from repro.core import obs

# fold's output on the recorded trace, as the benchmark first computed it.
FOLDED = os.path.join(harness.BENCH_DIR, "fixtures", "fold_pancake8.json")
PROGRAM_METRICS = ("driver.round_trip_idle_s_per_search",
                   "driver.init_s_per_search", "level.frontier_share",
                   "level.expand_s_per_search", "level.copy_s_per_search")


def test_fold_output_is_pinned_and_ignores_the_program_lists():
    with open(FIXTURE) as fh:
        trace = json.load(fh)
    with open(FOLDED) as fh:
        pinned = fh.read().strip()
    got = tracefold.fold(trace, harness.KERNEL_TAG)
    assert json.dumps(got, sort_keys=True) == pinned
    trace["program"] = [["bfs.search", 0, 10 ** 12, {}]]
    trace["scoped"] = [["expand", s, d] for _, s, d in trace["device"]]
    assert json.dumps(tracefold.fold(trace, harness.KERNEL_TAG),
                      sort_keys=True) == pinned


@pytest.mark.parametrize("stack, scope", [
    ("jit(_implicit_level)/while/body/expand/vmap()/rev", "expand"),
    ("jit(<unknown>)/expand/and:", "expand"),        # as a TPU trace has it
    ("jit(_implicit_level)/block_pad/jit(_pad)/pad", "block_pad"),
    ("jit(f)/jit(bitpack_mark_rotate_count)/to_table/slice", "to_table"),
    ("jit(f)/expand/jit(g)/to_table/pad", "to_table"),
    ("jit(_implicit_level)/while/body/expanded/add", ""),
    ("", ""),
])
def test_program_scope_is_the_innermost_named_scope(stack, scope):
    assert spanfold.program_scope(stack, spanfold.PROGRAM_SCOPES) == scope


def test_scoped_ops_read_each_op_scope_from_its_metadata(tmp_path):
    space = spanfold._xplane_schema()()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata.add(key=7).value.name = spanfold.SCOPE_STAT
    plane.stat_metadata.add(key=8).value.name = "jit(f)/body/expand/rev"
    plane.stat_metadata.add(key=9).value.name = "flops"
    pad = plane.event_metadata.add(key=1).value
    pad.stats.add(metadata_id=7, str_value="jit(f)/to_table/jit(_pad)/pad")
    rev = plane.event_metadata.add(key=2).value
    rev.stats.add(metadata_id=7, ref_value=8)      # an interned string
    other = plane.event_metadata.add(key=3).value
    other.stats.add(metadata_id=9, str_value="jit(f)/expand")
    line = plane.lines.add(name="XLA Modules", timestamp_ns=0)
    line.events.add(metadata_id=2, offset_ps=1000, duration_ps=9000)
    line = plane.lines.add(name=tracefold.DEVICE_LINE, timestamp_ns=1000)
    for mid, off, dur in [(1, 5000, 2000), (2, 9000, 1000), (3, 12000, 500),
                          (4, 20000, 3000)]:
        line.events.add(metadata_id=mid, offset_ps=off, duration_ps=dur)
    space.planes.add(name="/device:TPU:1").lines.add(
        name=tracefold.DEVICE_LINE).events.add(metadata_id=2)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert spanfold.scoped_ops(str(path), "/device:TPU:0",
                               spanfold.PROGRAM_SCOPES) == [
        ["to_table", 1005, 2], ["expand", 1009, 1], ["", 1012, 0],
        ["", 1020, 3]]


def synthetic_program_trace():
    """One search: an init with the first count's op, a first level whose
    dispatch is long (the re-trace) and whose sync waits on the device,
    and a second level."""
    return {
        "host": [["perfbench.search", 0, 1000]],
        "device": [["count", 50, 30], ["mark", 420, 160],
                   ["mark", 630, 270]],
        "scoped": [["", 50, 30], ["expand", 420, 100], ["to_table", 520, 60],
                   ["expand", 630, 270]],
        "program": [
            ["bfs.search", 10, 980, {"n_states": 720}],
            ["bfs.init", 10, 90, {}],
            ["bfs.level", 100, 500, {"level": 1, "frontier": 1}],
            ["bfs.dispatch", 110, 290, {}],
            ["bfs.sync", 400, 190, {}],
            ["bfs.level", 600, 380, {"level": 2, "frontier": 5}],
            ["bfs.dispatch", 600, 20, {}],
            ["bfs.sync", 620, 355, {}],
        ],
    }


def test_spanfold_puts_idle_under_the_innermost_span():
    got = spanfold.fold(synthetic_program_trace())
    # Idle: [0, 50), [80, 420), [580, 630), [900, 1000).
    assert got["idle_by_span"] == pytest.approx({
        "outside_program": 20e-9, "bfs.init": 60e-9, "bfs.level": 25e-9,
        "bfs.dispatch": 310e-9, "bfs.sync": 115e-9, "bfs.search": 10e-9})
    assert sum(got["idle_by_span"].values()) == pytest.approx(540e-9)
    assert got["span_s"] == pytest.approx({
        "bfs.search": 980e-9, "bfs.init": 90e-9, "bfs.level": 880e-9,
        "bfs.dispatch": 310e-9, "bfs.sync": 545e-9})
    assert got["span_busy_s"] == pytest.approx({
        "bfs.search": 460e-9, "bfs.init": 30e-9, "bfs.level": 430e-9,
        "bfs.sync": 430e-9})
    want = [[1, 1, 160e-9, 500e-9], [2, 5, 270e-9, 380e-9]]
    assert len(got["levels"]) == 2
    for row, expect in zip(got["levels"], want):
        assert row == pytest.approx(expect)
    assert got["device_by_scope"] == pytest.approx({
        "unscoped": 30e-9, "expand": 370e-9, "to_table": 60e-9})
    (one,) = got["per_search"]
    assert one["seconds"] == pytest.approx(980e-9)
    assert one["span_s"]["bfs.dispatch"] == pytest.approx(310e-9)
    assert one["idle_s"]["bfs.dispatch"] == pytest.approx(310e-9)
    assert "outside_program" not in one["idle_s"]


def test_spanfold_without_program_spans_puts_all_idle_outside():
    trace = synthetic_program_trace()
    del trace["program"], trace["scoped"]
    got = spanfold.fold(trace)
    assert got["idle_by_span"] == pytest.approx({"outside_program": 540e-9})
    assert got["span_s"] == {} and got["per_search"] == []
    assert got["levels"] == [] and got["device_by_scope"] == {}


def test_metrics_read_the_program_spans_and_counters():
    program = spanfold.fold(synthetic_program_trace())
    counters = {"searches": 2, "level_calls": 24,
                "states_expanded": 24 * 3_628_800,
                "frontier_states": 2 * 3_628_800}
    assert spanfold.metrics(program, counters, 2) == pytest.approx({
        # The one search's idle under bfs.level, dispatch, sync, search.
        "driver.round_trip_idle_s_per_search": 460e-9,
        "driver.init_s_per_search": 45e-9,
        "level.frontier_share": 100 / 12,
        "level.expand_s_per_search": 185e-9,
        "level.copy_s_per_search": 30e-9})


def _searches_idle(*idle_s):
    """A fold of searches whose device-idle seconds by span are given."""
    return {"span_s": {"bfs.level": 1.0}, "idle_by_span": {},
            "device_by_scope": {},
            "per_search": [{"seconds": 3.0, "span_s": {}, "idle_s": i}
                           for i in idle_s]}


@pytest.mark.parametrize("idle_s, want", [
    # The same round trips, put under dispatch or under sync by the clocks.
    ([{"bfs.dispatch": 0.01, "bfs.sync": 0.05, "bfs.init": 0.004}] * 3,
     0.06),
    ([{"bfs.sync": 0.06, "bfs.init": 0.004}] * 3, 0.06),
    # One search with a stall in its sync is not the median one.
    ([{"bfs.sync": 0.05, "bfs.level": 0.01}, {"bfs.sync": 0.65},
      {"bfs.sync": 0.048, "bfs.search": 0.013}], 0.061),
])
def test_round_trip_idle_is_the_median_search_over_every_level_span(
        idle_s, want):
    got = spanfold.metrics(_searches_idle(*idle_s), {}, len(idle_s))
    assert got == pytest.approx(
        {"driver.round_trip_idle_s_per_search": want})


def test_metrics_leave_out_what_the_trace_lacks():
    no_spans = spanfold.fold({"host": [["perfbench.search", 0, 10]],
                              "device": []})
    assert spanfold.metrics(no_spans, {}, 1) == {}
    assert spanfold.metrics(no_spans, {"states_expanded": 0}, 1) == {}


# ------------------------------------------------------------ whole runs

def report(spec, bench, cell, seed=3, log=None):
    """One traced run of a cell at n=5 on the CPU."""
    return harness.run_cell(spec, cell, seed, 0.01, True, t_start=0.0,
                            log=log or io.StringIO(), bench_dir=bench,
                            trace_dir=os.path.join(bench, "trace"),
                            chip_check=cpu_device)


@pytest.mark.parametrize("cell, n_calls", [("pancake-10.search", 6),
                                           ("bubblesort-9.search", 11)])
def test_report_reads_the_program_spans(small_bench, cell, n_calls):
    spec, bench = small_bench
    log = io.StringIO()
    with obs.scope() as counted:
        out = report(spec, bench, cell, log=log)
    assert out["correct"] is True
    assert set(PROGRAM_METRICS) <= {
        m["name"] for m in harness.cell_metrics(spec, cell, True)}
    got = {k: m["value"] for k, m in out["metrics"].items()}
    # n=5: each level call expands 120 states padded to 128; a search's
    # frontiers sum to 5! over its level calls.
    assert got["level.frontier_share"] == pytest.approx(
        100 * 120 / (n_calls * 128))
    # The set-up search, cut to one level call, then the window's.
    counters = counted.delta()["implicit"]
    assert counters["searches"] == out["attempted"] + 1
    assert counters["level_calls"] == n_calls * out["attempted"] + 1
    assert got["driver.init_s_per_search"] > 0
    assert got["driver.round_trip_idle_s_per_search"] > 0
    idle = dict(out["breakdown"]["idle_by_span"])
    assert {"bfs.init", "bfs.dispatch", "bfs.sync"} <= set(idle)
    levels = ast.literal_eval(log.getvalue().split(
        "levels of the first search (level, frontier, device s, host s): "
    )[1].splitlines()[0])
    assert [row[:2] for row in levels][:2] == [[1, 1], [2, 4]]
    assert obs.ACTIVE is False
    assert log.getvalue().count("device idle under") == out["attempted"]
    json.dumps(out)


def test_report_turns_the_spans_on_for_the_window_only(small_bench,
                                                       monkeypatch):
    spec, bench = small_bench
    real = C.implicit_bfs
    seen = []

    def watched(*args, **kw):
        seen.append((kw.get("max_levels"), obs.ACTIVE))
        return real(*args, **kw)

    monkeypatch.setattr(C, "implicit_bfs", watched)
    out = report(spec, bench, "bubblesort-9.search")
    # The set-up search, cut to one level, then the window's searches.
    assert seen == [(1, False)] + [(None, True)] * out["attempted"]
    assert obs.ACTIVE is False


def test_report_turns_obs_off_when_the_window_raises(small_bench,
                                                     monkeypatch):
    spec, bench = small_bench

    def broken(*args):
        assert obs.ACTIVE is True and obs._ANNOTATE is not None
        raise RuntimeError("the window failed")

    monkeypatch.setattr(harness, "_searches", broken)
    with pytest.raises(RuntimeError, match="the window failed"):
        report(spec, bench, "pancake-10.search")
    assert obs.ACTIVE is False and obs._ANNOTATE is None


@pytest.mark.parametrize("traced", [False, True])
def test_benchmark_run_turns_obs_on_only_in_a_traced_window(
        small_bench, monkeypatch, traced):
    spec, bench = small_bench
    real = harness._searches
    seen = []

    def watched(*args):
        seen.append(obs.ACTIVE)
        return real(*args)

    monkeypatch.setattr(harness, "_searches", watched)
    result = harness.run_cell(spec, "bubblesort-9.search", 3, 0.01,
                              traced, t_start=0.0, bench_dir=bench,
                              log=io.StringIO(),
                              trace_dir=os.path.join(bench, "trace"),
                              chip_check=cpu_device)
    assert seen == [traced] and result["correct"] is True
    assert obs.ACTIVE is False


def test_report_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join("perfbench", "run.py"), "--workload",
         "pancake-10.search", "--seed", "1", "--seconds", "1", "--trace",
         "1"], cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 1
    assert "no TPU found" in proc.stderr
    assert proc.stdout.strip() == ""
