"""Spans and counters of the Tier J implicit search (core/constructs.py).

``implicit_bfs`` opens ``bfs.search`` > ``bfs.init`` and ``bfs.level`` >
``bfs.dispatch``, ``bfs.sync`` while tracing is on, and books the
``implicit`` counter namespace whether or not it is: one search, one level
call per level, the padded states each call expands and the frontier
states entering it.
"""
import math
import os
import sys

import numpy as np
import pytest

from repro.core import constructs as C
from repro.core import obs
from repro.core import ranking as R
from repro.core.disk import trace

sys.path.append(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "examples"))
from pancake_bits import neighbor_jnp                 # noqa: E402

N = 6
PANCAKE6 = [1, 5, 20, 79, 199, 281, 133, 2]


@pytest.fixture(autouse=True)
def _clean_obs():
    yield
    if trace._SESSION is not None:
        trace.stop()
    obs.disable()


def _search(n=N, **kw):
    start = int(R.rank_np(np.arange(n)[None, :])[0])
    sizes, _ = C.implicit_bfs(math.factorial(n), [start], neighbor_jnp(n),
                              impl="interpret", **kw)
    return sizes


def _padded(n_states):
    nblk, bs = C._implicit_blocks(n_states, C.IMPLICIT_BLOCK)
    return nblk * bs


@pytest.mark.parametrize("n_states, padded", [
    (math.factorial(10), 3_628_800),      # 4 blocks of 907,200 states
    (math.factorial(9), 362_880),         # one block
    (math.factorial(6), 720),
    (1_000, 1_008),                       # a whole number of packed words
])
def test_padded_states_per_level_call(n_states, padded):
    assert _padded(n_states) == padded


def test_traced_search_records_the_span_tree():
    recs = []
    obs.enable(sink=recs.append)
    with obs.scope() as sc:
        sizes = _search()
    assert sizes == PANCAKE6
    by_sid = {}
    for r in recs:
        by_sid.setdefault(r["sid"], []).append(r)
    assert set(by_sid) == {"bfs.search", "bfs.init", "bfs.level",
                           "bfs.dispatch", "bfs.sync"}
    (search,) = by_sid["bfs.search"]
    assert search["parent"] is None and search["depth"] == 0
    assert search["attrs"] == {"n_states": 720, "tier": "j",
                               "engine": "implicit"}
    (init,) = by_sid["bfs.init"]
    assert init["parent"] == "bfs.search" and init["depth"] == 1
    levels = by_sid["bfs.level"]
    # One level call per entry of the level sizes: the last finds nothing.
    assert len(levels) == len(sizes)
    assert [lv["attrs"]["level"] for lv in levels] == list(
        range(1, len(sizes) + 1))
    assert [lv["attrs"]["frontier"] for lv in levels] == sizes
    assert all(lv["parent"] == "bfs.search" and lv["depth"] == 1
               and lv["attrs"]["tier"] == "j"
               and lv["attrs"]["engine"] == "implicit" for lv in levels)
    for sid in ("bfs.dispatch", "bfs.sync"):
        assert len(by_sid[sid]) == len(sizes)
        assert all(r["parent"] == "bfs.level" and r["depth"] == 2
                   for r in by_sid[sid])
    # Children close inside their parents, in order.
    assert init["ts_us"] >= search["ts_us"]
    for lv, d, s in zip(levels, by_sid["bfs.dispatch"], by_sid["bfs.sync"]):
        assert lv["ts_us"] <= d["ts_us"] <= s["ts_us"]
        assert d["dur_us"] + s["dur_us"] <= lv["dur_us"] + 2
    # A level span's counter metrics are that call's own bookings.
    assert levels[2]["metrics"] == {
        "implicit.level_calls": 1, "implicit.states_expanded": 720,
        "implicit.frontier_states": sizes[2]}
    got = sc.delta()["implicit"]
    assert got == {"searches": 1, "level_calls": len(sizes),
                   "states_expanded": len(sizes) * _padded(720),
                   "frontier_states": math.factorial(N)}


def test_untraced_search_books_counters_and_no_spans():
    assert obs.ACTIVE is False
    with obs.scope() as sc:
        sizes = _search()
    assert sizes == PANCAKE6
    assert obs.drain_spans() == [] and obs._STACK == []
    assert sc.delta()["implicit"] == {
        "searches": 1, "level_calls": len(sizes),
        "states_expanded": len(sizes) * 720,
        "frontier_states": math.factorial(N)}


def test_level_cap_books_only_the_calls_made():
    with obs.scope() as sc:
        sizes = _search(max_levels=2)
    assert sizes == PANCAKE6[:3]
    got = sc.delta()["implicit"]
    assert got["level_calls"] == 2
    assert got["frontier_states"] == sum(PANCAKE6[:2])


def test_annotation_hook_sees_every_span_with_its_level():
    seen = []

    class Ann:
        def __init__(self, sid, **attrs):
            seen.append((sid, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    obs.enable(sink=lambda r: None, annotate=Ann)
    sizes = _search()
    levels = [a for sid, a in seen if sid == "bfs.level"]
    assert [a["level"] for a in levels] == list(range(1, len(sizes) + 1))
    assert [a["frontier"] for a in levels] == sizes
    assert seen[0] == ("bfs.search", {"n_states": 720, "tier": "j",
                                      "engine": "implicit"})
    assert seen[1] == ("bfs.init", {})


def test_jsonl_level_rows_ignore_the_child_spans(tmp_path):
    p = str(tmp_path / "tierj.jsonl")
    trace.start(p, meta={"example": "tierj"})
    sizes = _search()
    trace.stop()
    _, spans, summary = trace.read(p)
    assert {s["sid"] for s in spans} >= {"bfs.dispatch", "bfs.sync"}
    rows = trace.level_rows(spans)
    assert [r["level"] for r in rows] == list(range(1, len(sizes) + 1))
    level_us = [s["dur_us"] for s in spans if s["sid"] == "bfs.level"]
    assert [r["wall_us"] for r in rows] == level_us
    assert set(summary) == {"type", "counters"}
    assert summary["counters"]["implicit"]["level_calls"] >= len(sizes)
