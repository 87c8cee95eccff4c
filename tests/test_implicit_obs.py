"""Spans and counters of the Tier J implicit search (core/constructs.py).

``implicit_bfs`` opens ``bfs.search`` > ``bfs.init`` and ``bfs.level`` >
``bfs.dispatch``, ``bfs.sync`` while tracing is on, and books the
``implicit`` counter namespace whether or not it is: one search, one level
call per level, the padded states each call expands and the frontier
states entering it, and per search one step hit or miss: the jitted level
step is kept across searches per (n_states, rule object, impl, fused,
block).
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
PANCAKE5 = [1, 4, 12, 35, 48, 20]
PANCAKE6 = [1, 5, 20, 79, 199, 281, 133, 2]


@pytest.fixture(autouse=True)
def _clean_obs():
    # Every test starts with no level step kept, whatever ran before it.
    C._implicit_step.cache_clear()
    yield
    if trace._SESSION is not None:
        trace.stop()
    obs.disable()


def _counted(n=N):
    """The pancake rule, and the list its Python body appends to on each
    call: it runs only while the level step is traced."""
    calls = []
    nf = neighbor_jnp(n)

    def rule(i):
        calls.append(i)
        return nf(i)
    return rule, calls


def _run(rule, n_states=math.factorial(N), n=N, impl="ref", **kw):
    start = int(R.rank_np(np.arange(n)[None, :])[0])
    sizes, bits = C.implicit_bfs(n_states, [start], rule, impl=impl, **kw)
    return sizes, np.asarray(bits.data)


def _search(n=N, **kw):
    return _run(neighbor_jnp(n), math.factorial(n), n, impl="interpret",
                **kw)[0]


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
    # The step is looked up once, inside the search and outside its levels.
    assert search["metrics"]["implicit.step_misses"] == 1
    assert not any(k.startswith("implicit.step_")
                   for lv in levels for k in lv["metrics"])
    got = sc.delta()["implicit"]
    assert got == {"searches": 1, "level_calls": len(sizes),
                   "states_expanded": len(sizes) * _padded(720),
                   "frontier_states": math.factorial(N),
                   "step_hits": 0, "step_misses": 1}


def test_untraced_search_books_counters_and_no_spans():
    assert obs.ACTIVE is False
    with obs.scope() as sc:
        sizes = _search()
    assert sizes == PANCAKE6
    assert obs.drain_spans() == [] and obs._STACK == []
    assert sc.delta()["implicit"] == {
        "searches": 1, "level_calls": len(sizes),
        "states_expanded": len(sizes) * 720,
        "frontier_states": math.factorial(N),
        "step_hits": 0, "step_misses": 1}


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


# --------------------------------------------- the level step kept across searches

def test_a_repeated_search_keeps_its_step():
    rule, calls = _counted()
    with obs.scope() as sc:
        sizes1, bits1 = _run(rule)
        traced = len(calls)
        sizes2, bits2 = _run(rule)
    assert traced > 0 and len(calls) == traced      # not traced again
    assert sizes1 == sizes2 == PANCAKE6
    assert np.array_equal(bits1, bits2)
    got = sc.delta()["implicit"]
    assert (got["searches"], got["step_misses"], got["step_hits"]) == (2, 1, 1)


@pytest.mark.parametrize("change", ["n_states", "rule", "impl", "fused",
                                    "block"])
def test_a_changed_input_gets_a_fresh_step(change, monkeypatch):
    rule, calls = _counted(5)
    first = _run(rule, n_states=120, n=5)
    traced = len(calls)
    kw = {}
    if change == "n_states":
        # The n=5 rule over a larger space: no state past 5! is reached,
        # so the levels are pancake-5's.
        kw["n_states"] = 200
    elif change == "rule":
        rule, calls = _counted(5)
        traced = 0
    elif change == "impl":
        kw["impl"] = "interpret"
    elif change == "fused":
        kw["fused"] = False
    else:
        monkeypatch.setattr(C, "IMPLICIT_BLOCK", 64)     # 2 blocks of 64
    kw = {"n_states": 120, "n": 5, **kw}
    with obs.scope() as sc:
        sizes, bits = _run(rule, **kw)
        retraced = len(calls)
        assert _run(rule, **kw)[0] == sizes              # then kept
    assert retraced > traced and len(calls) == retraced
    assert sizes == first[0] == PANCAKE5
    if change != "n_states":
        assert np.array_equal(bits, first[1])
    got = sc.delta()["implicit"]
    assert (got["searches"], got["step_misses"], got["step_hits"]) == (2, 1, 1)


def test_each_search_books_one_hit_or_one_miss():
    rule, _ = _counted(4)
    other, _ = _counted(4)
    with obs.scope() as sc:
        for r in (rule, rule, other, rule, other):
            _run(r, n_states=24, n=4)
    got = sc.delta()["implicit"]
    assert got["searches"] == 5
    assert (got["step_misses"], got["step_hits"]) == (2, 3)


def test_the_kept_steps_are_bounded():
    kept = C._implicit_step.cache_info().maxsize
    rules = [_counted(4) for _ in range(kept + 1)]
    for rule, _ in rules:
        _run(rule, n_states=24, n=4)
    assert C._implicit_step.cache_info().currsize == kept
    (oldest, calls), (newest, _) = rules[0], rules[-1]
    with obs.scope() as sc:
        _run(newest, n_states=24, n=4)
        traced = len(calls)
        _run(oldest, n_states=24, n=4)                   # was dropped
    assert len(calls) > traced
    got = sc.delta()["implicit"]
    assert (got["step_misses"], got["step_hits"]) == (1, 1)


def test_a_warmed_up_window_only_hits():
    # A one-level warm-up with the rule object, then whole searches from
    # other starts with the same object: only the warm-up builds a step.
    rule, calls = _counted(5)
    starts = [0, 7, 42, 119]
    with obs.scope() as sc:
        C.implicit_bfs(120, [3], rule, max_levels=1, impl="ref")
        traced = len(calls)
        for start in starts:
            sizes, _ = C.implicit_bfs(120, [start], rule, impl="ref")
            assert sizes == PANCAKE5          # a Cayley graph: any start
    assert traced > 0 and len(calls) == traced
    got = sc.delta()["implicit"]
    assert got["searches"] == 1 + len(starts)
    assert (got["step_misses"], got["step_hits"]) == (1, len(starts))
