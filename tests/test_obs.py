"""Observability layer (core/obs.py + disk/trace.py).

Covers the PR-7 contracts end to end:

  * zero cost when disabled: ``obs.ACTIVE`` is False by default, every
    ``span()`` call returns the shared no-op, an untraced run writes no
    trace file and mutates no tracing state,
  * span mechanics: nesting (parent/depth), wall-time monotonicity,
    counter-delta metrics, shard tagging, out-of-LIFO close tolerance,
  * the registry absorbing the legacy STATS dicts (same live objects),
    snapshot/merge associativity (hypothesis property) with the empty
    snapshot as identity,
  * ``obs.scope()`` delta windows — live while open, frozen at close,
    never resetting the module globals (the bench best-of fix),
  * JSONL trace round-trip + per-level report + Chrome export schema,
  * the sharded-totals contract (ISSUE-7 satellite): spawn == inline ==
    single-process byte counters on pancake n=5, even with tracing off,
  * the acceptance pin: a traced spawn run's per-shard ``pass.rw`` byte
    metrics sum EXACTLY to the single-process run's byte counters,
  * recovery tracing: a killed-and-recovered run books one
    ``recovery.rollback`` span and tags the replayed level.

Module-level imports stay numpy-only (the test_cluster.py convention):
spawn workers re-import this module's generator imports.
"""
import json
import math
import os
import sys

import numpy as np
import pytest

from repro.core import obs
from repro.core.disk import extsort, faults, trace
from repro.core.disk import implicit_bfs

from _hypothesis_compat import given, settings, st

sys.path.append(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "examples"))
from pancake_bits import NeighborsNp                  # noqa: E402

# Fault-free pancake-5 flip-distance histogram (pinned by test_cluster).
PANCAKE5 = [1, 4, 12, 35, 48, 20]


@pytest.fixture(autouse=True)
def _clean_obs():
    """Tracing is off on entry and exit; a failing test can't leak an
    open session, the env hook, or buffered spans into its neighbours."""
    assert trace._SESSION is None, "a previous test leaked a trace session"
    yield
    if trace._SESSION is not None:
        trace.stop()
    os.environ.pop(obs.ENV_VAR, None)
    obs.disable()


def _implicit_levels(wd, n=5, nshards=1, mode="spawn", **kw):
    """Pancake-n implicit (2-bit array) BFS; returns level sizes.

    chunk_elems=20 (a multiple of the 4 packed values per byte) divides
    both the single-process array (120 elements, n=5) and the 60-element
    shard blocks, so chunk boundaries — and therefore partial-pass byte
    counts — line up exactly across layouts (what the byte-total
    equality tests below compare)."""
    from repro.core import ranking as R
    total = math.factorial(n)
    start = int(R.rank_np(np.arange(n)[None, :])[0])
    sizes, bits = implicit_bfs(
        os.path.join(wd, "b"), total, [start], NeighborsNp(n),
        chunk_elems=20, nshards=nshards, shard_mode=mode, **kw)
    bits.destroy()
    return sizes


# ----------------------------------------------------------- zero-cost off

class TestZeroCost:

    def test_off_by_default(self):
        assert obs.ACTIVE is False
        assert obs.ENV_VAR not in os.environ
        s = obs.span("bfs.level", level=1)
        assert s is obs._NULL                 # the shared no-op, no alloc
        with s:
            s.set(extra=1)
        assert obs.drain_spans() == []

    def test_untraced_run_books_nothing(self, tmp_path):
        sizes = _implicit_levels(str(tmp_path), n=4, nshards=1)
        assert sum(sizes) == 24 and len(sizes) - 1 == 4
        assert obs.ACTIVE is False
        assert obs.drain_spans() == [] and obs._STACK == []
        assert obs._ANNOTATE is None
        assert obs.ENV_VAR not in os.environ
        assert not [p for p in tmp_path.rglob("*.jsonl")]


# ------------------------------------------------------------- percentile

class TestHistogramPercentile:
    """Histogram.percentile(q) — the serve bench's p50/p99 columns."""

    def test_single_bucket_interpolates(self):
        h = obs.Histogram()
        for _ in range(10):
            h.observe(3)                      # all land in (2, 4]
        assert h.percentile(0) == pytest.approx(2.0)
        assert h.percentile(50) == pytest.approx(3.0)
        assert h.percentile(100) == pytest.approx(4.0)

    def test_multi_bucket_walk(self):
        h = obs.Histogram()
        for v in (1, 1, 1, 10, 100):          # buckets 0 (x3), 4, 7
            h.observe(v)
        assert h.percentile(50) <= 1.0        # rank 2.5 inside bucket 0
        assert 8 < h.percentile(75) <= 16     # rank 3.75 → bucket 4
        assert 64 < h.percentile(100) <= 128  # top of bucket 7

    def test_bucket_edge_exact(self):
        # q at a bucket boundary must return that bucket's upper edge
        h = obs.Histogram()
        for v in (1, 4):
            h.observe(v)
        assert h.percentile(50) == pytest.approx(1.0)
        assert h.percentile(100) == pytest.approx(4.0)

    def test_monotone_in_q(self):
        h = obs.Histogram()
        rng = np.random.default_rng(0)
        for v in rng.uniform(0.5, 5000.0, 300):
            h.observe(v)
        qs = [0, 1, 10, 25, 50, 75, 90, 99, 100]
        ps = [h.percentile(q) for q in qs]
        assert ps == sorted(ps)

    def test_bounded_by_bucket_resolution(self):
        # the estimate never strays beyond the covering power-of-2 bucket
        h = obs.Histogram()
        for _ in range(1000):
            h.observe(777)                    # bucket (512, 1024]
        for q in (1, 50, 99):
            assert 512 < h.percentile(q) <= 1024

    def test_empty_and_bad_q(self):
        h = obs.Histogram()
        assert h.percentile(50) == 0.0
        h.observe(2)
        with pytest.raises(ValueError):
            h.percentile(-1)
        with pytest.raises(ValueError):
            h.percentile(101)


# ---------------------------------------------------------- span mechanics

class TestSpanMechanics:

    def test_nesting_parent_depth_and_timing(self):
        obs.enable()
        with obs.span("outer", level=1):
            with obs.span("inner"):
                pass
        inner, outer = obs.drain_spans()      # inner closes (emits) first
        assert inner["sid"] == "inner" and outer["sid"] == "outer"
        assert inner["parent"] == "outer" and inner["depth"] == 1
        assert outer["parent"] is None and outer["depth"] == 0
        assert inner["ts_us"] >= outer["ts_us"]
        assert 0 <= inner["dur_us"] <= outer["dur_us"]
        assert outer["attrs"] == {"level": 1}

    def test_sequential_spans_monotonic(self):
        obs.enable()
        for i in range(5):
            with obs.span("step", i=i):
                pass
        recs = obs.drain_spans()
        ts = [r["ts_us"] for r in recs]
        assert ts == sorted(ts)
        assert [r["attrs"]["i"] for r in recs] == list(range(5))

    def test_metric_deltas(self):
        d = obs.counters("obstest", {"x": 0})
        obs.enable()
        with obs.span("work"):
            d["x"] += 3
        with obs.span("idle"):
            pass
        work, idle = obs.drain_spans()
        assert work["metrics"] == {"obstest.x": 3}
        assert "metrics" not in idle           # zero deltas are omitted

    def test_shard_tagging(self):
        obs.enable(shard=7)
        with obs.span("a"):
            pass
        with obs.span("b", shard=2):          # explicit tag wins
            pass
        a, b = obs.drain_spans()
        assert a["shard"] == 7 and b["shard"] == 2
        assert "attrs" not in b               # shard= is split out

    def test_out_of_lifo_close_is_tolerated(self):
        obs.enable()
        s1 = obs.span("gen_held").__enter__()
        s2 = obs.span("other").__enter__()
        s1.__exit__(None, None, None)         # generator-held span first
        s2.__exit__(None, None, None)
        recs = obs.drain_spans()
        assert [r["sid"] for r in recs] == ["gen_held", "other"]
        assert obs._STACK == []

    def test_histogram_pow2_buckets(self):
        h = obs.Histogram()
        for v in (0, 1, 2, 3, 4, 5, 1024):
            h.observe(v)
        assert h.buckets == {0: 2, 1: 1, 2: 2, 3: 1, 10: 1}
        assert h.count == 7 and h.total == 1039.0


# ---------------------------------------------------------- annotate hook

class _Recorder:
    """An ``annotate`` hook that logs what it is handed and when."""

    def __init__(self):
        self.log = []

    def __call__(self, sid, **attrs):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.log.append(("enter", sid, attrs,
                                [s.sid for s in obs._STACK]))
                return self

            def __exit__(self, *exc):
                rec.log.append(("exit", sid, exc[0]))
                return False

        return _Ann()


class TestAnnotateHook:

    def test_entered_and_left_around_the_span(self):
        hook = _Recorder()
        emitted = []
        obs.enable(sink=lambda r: emitted.append(r["sid"]), annotate=hook)
        with obs.span("outer", level=3):
            with obs.span("inner"):
                assert [e[:2] for e in hook.log] == [("enter", "outer"),
                                                     ("enter", "inner")]
        assert [e[:2] for e in hook.log[2:]] == [("exit", "inner"),
                                                 ("exit", "outer")]
        # Entered once the span is open (on the stack, its clock read) ...
        assert hook.log[0][3] == ["outer"]
        assert hook.log[1][3] == ["outer", "inner"]
        # ... and left before its record is emitted.
        assert emitted == ["inner", "outer"]

    def test_gets_the_sid_and_scalar_attrs_only(self):
        hook = _Recorder()
        obs.enable(annotate=hook)
        with obs.span("bfs.level", level=3, frontier=9, tier="j", ok=True,
                      share=0.5, sizes=[1, 2], meta={"a": 1}, shard=4):
            pass
        (_, sid, attrs, _), _ = hook.log
        assert sid == "bfs.level"
        assert attrs == {"level": 3, "frontier": 9, "tier": "j", "ok": True,
                         "share": 0.5}
        (rec,) = obs.drain_spans()          # the record keeps every attr
        assert rec["attrs"]["sizes"] == [1, 2] and rec["shard"] == 4

    def test_left_with_the_exception_that_ends_the_span(self):
        hook = _Recorder()
        obs.enable(annotate=hook)
        with pytest.raises(KeyError):
            with obs.span("boom"):
                raise KeyError("x")
        assert hook.log[-1] == ("exit", "boom", KeyError)
        assert obs._STACK == []

    def test_never_called_while_off(self):
        hook = _Recorder()
        obs.enable(annotate=hook)
        obs.disable()
        assert obs._ANNOTATE is None
        s = obs.span("bfs.level", level=1)
        assert s is obs._NULL
        with s:
            pass
        assert hook.log == []

    def test_enable_without_the_hook_calls_nothing(self):
        hook = _Recorder()
        obs.enable(annotate=hook)
        obs.enable()                          # a later enable drops it
        with obs.span("plain"):
            pass
        assert hook.log == [] and len(obs.drain_spans()) == 1

    def test_obs_imports_no_jax(self):
        import subprocess
        code = ("import sys; import repro.core.obs; "
                "sys.exit('jax' in sys.modules)")
        proc = subprocess.run([sys.executable, "-c", code],
                              env=dict(os.environ), capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr


# ------------------------------------------------------- registry + merge

_INTS = st.integers(min_value=0, max_value=1 << 40)
_SNAP = st.fixed_dictionaries({
    "counters": st.dictionaries(
        st.sampled_from(["extsort", "bits", "tierj"]),
        st.dictionaries(st.sampled_from(["x", "y", "z"]), _INTS, max_size=3),
        max_size=3),
})


class TestRegistryMerge:

    def test_absorbs_legacy_stats_dicts(self):
        """The compatibility keystone: the legacy module dicts ARE the
        registry namespaces — the very same mutable objects."""
        from repro.core.disk import bitarray as DBA
        assert obs.counters("extsort", {}) is extsort.STATS
        assert obs.counters("bits", {}) is DBA.STATS

    def test_counters_live_dict_visible_in_snapshot(self):
        d = obs.counters("obstest2", {"n": 0})
        d["n"] += 5
        assert obs.snapshot()["counters"]["obstest2"]["n"] == d["n"]

    def test_merge_empty_identity(self):
        a = {"counters": {"ns": {"k": 3}}}
        empty = {"counters": {}}
        assert obs.merge(a, empty) == obs.merge(empty, a) == a

    @settings(max_examples=60, deadline=None)
    @given(_SNAP, _SNAP, _SNAP)
    def test_merge_associative(self, a, b, c):
        # Integer counters add exactly: fold order can't matter.
        assert obs.merge(obs.merge(a, b), c) == obs.merge(a, obs.merge(b, c))

    def test_snapshot_holds_counters_only(self):
        obs.counters("obstest3", {"n": 1})
        snap = obs.snapshot()
        assert list(snap) == ["counters"]
        assert snap["counters"]["obstest3"] == {"n": 1}

    def test_counter_deltas_flat_nonzero(self):
        before = {"counters": {"ns": {"a": 1, "b": 2}}}
        after = {"counters": {"ns": {"a": 4, "b": 2}, "new": {"c": 7}}}
        assert obs.counter_deltas(after, before) == {"ns.a": 3, "new.c": 7}


class TestScope:

    def test_live_then_frozen(self):
        d = obs.counters("scopetest", {"n": 0})
        with obs.scope() as sc:
            d["n"] += 2
            assert sc.delta()["scopetest"]["n"] == 2    # live while open
            d["n"] += 3
        frozen = sc.delta()["scopetest"]["n"]
        assert frozen == 5
        d["n"] += 10
        assert sc.delta()["scopetest"]["n"] == 5        # frozen at close

    def test_overlapping_scopes_independent(self):
        """No global reset: two observers each get their own window —
        exactly what reset_stats() between bench repeats broke."""
        d = obs.counters("scopetest2", {"n": 0})
        s1 = obs.Scope()
        d["n"] += 1
        s2 = obs.Scope()
        d["n"] += 1
        assert s1.delta()["scopetest2"]["n"] == 2
        assert s2.delta()["scopetest2"]["n"] == 1


# ------------------------------------------------------- trace round-trip

class TestTraceRoundTrip:

    def _traced_run(self, tmp_path):
        p = str(tmp_path / "run.jsonl")
        trace.start(p, meta={"example": "unit", "n": 4})
        assert os.environ[obs.ENV_VAR] == "1"
        sizes = _implicit_levels(str(tmp_path), n=4, nshards=1)
        assert trace.stop() == p
        return p, sizes

    def test_round_trip_and_report(self, tmp_path, capsys):
        p, sizes = self._traced_run(tmp_path)
        assert obs.ACTIVE is False and obs.ENV_VAR not in os.environ
        meta, spans, summary = trace.read(p)
        assert meta["example"] == "unit" and meta["version"] == 1
        sids = {s["sid"] for s in spans}
        assert "bfs.level" in sids and "pass.rw" in sids
        assert "bits" in summary["counters"]
        rows = trace.report(p)
        out = capsys.readouterr().out
        assert "level" in out and "skew%" in out and "total" in out
        assert rows and sum(r["passes"] for r in rows) > 0
        assert sum(r["bytes"] for r in rows) > 0
        assert not any(r["replay"] for r in rows)       # fault-free run

    def test_chrome_export_schema(self, tmp_path):
        p, _ = self._traced_run(tmp_path)
        out = trace.export_chrome(p)
        assert out == str(tmp_path / "run.chrome.json")
        cj = json.load(open(out))
        evs = cj["traceEvents"]
        assert evs
        for e in evs:
            assert e["ph"] in ("X", "M")
            assert {"name", "ts", "pid", "tid"} <= set(e)
        xs = [e for e in evs if e["ph"] == "X"]
        assert xs
        assert all(e["ts"] >= 0 and e["dur"] >= 0 and e["cat"] == "roomy"
                   for e in xs)
        assert any(e["ph"] == "M" and e["args"]["name"] == "coordinator"
                   for e in evs)
        assert cj["otherData"]["example"] == "unit"

    def test_cli(self, tmp_path, capsys):
        p, _ = self._traced_run(tmp_path)
        assert trace.main(["report", p]) == 0
        out2 = str(tmp_path / "alt.json")
        assert trace.main(["export-chrome", p, "-o", out2]) == 0
        assert json.load(open(out2))["traceEvents"]

    def test_start_twice_raises_stop_idempotent(self, tmp_path):
        assert trace.stop() is None            # nothing active: a no-op
        trace.start(str(tmp_path / "a.jsonl"))
        with pytest.raises(RuntimeError, match="already active"):
            trace.start(str(tmp_path / "b.jsonl"))
        trace.stop()
        assert trace.stop() is None


# ------------------------------------------ sharded totals + acceptance

def _bits_delta(wd, **kw):
    with obs.scope() as sc:
        sizes = _implicit_levels(wd, n=5, **kw)
    assert sizes == PANCAKE5
    return sc.delta()["bits"]


class TestShardedTotals:

    def test_spawn_inline_single_totals_agree(self, tmp_path):
        """The satellite-2 contract: spawn workers' counters are folded
        back to the coordinator at every level barrier even with tracing
        OFF, so the three execution modes book identical byte totals."""
        assert obs.ACTIVE is False
        single = _bits_delta(str(tmp_path / "s1"), nshards=1)
        inline = _bits_delta(str(tmp_path / "s2"), nshards=2, mode="inline")
        spawn = _bits_delta(str(tmp_path / "s3"), nshards=2, mode="spawn")
        for k in ("bytes_read", "bytes_written"):
            assert single[k] == inline[k] == spawn[k] > 0, k
        # Per-shard pass counters agree between the two sharded modes.
        assert inline["sync_passes"] == spawn["sync_passes"] > 0

    def test_spawn_trace_per_shard_bytes_sum_to_single_process(self,
                                                               tmp_path):
        """The PR acceptance pin: the merged trace's per-shard pass.rw
        byte metrics sum EXACTLY to the single-process byte counters."""
        with obs.scope() as sc:
            assert _implicit_levels(str(tmp_path / "ref"),
                                    n=5, nshards=1) == PANCAKE5
        ref = sc.delta()["bits"]
        ref_bytes = ref["bytes_read"] + ref["bytes_written"]

        p = str(tmp_path / "run.jsonl")
        trace.start(p, meta={"example": "unit-sharded"})
        assert _implicit_levels(str(tmp_path / "sh"), n=5, nshards=2,
                                mode="spawn") == PANCAKE5
        trace.stop()

        _, spans, _ = trace.read(p)
        per_shard = {}
        for s in spans:
            if s["sid"] == "pass.rw" and s.get("shard") is not None:
                m = s.get("metrics") or {}
                per_shard[s["shard"]] = (per_shard.get(s["shard"], 0)
                                         + m.get("bits.bytes_read", 0)
                                         + m.get("bits.bytes_written", 0))
        assert set(per_shard) == {0, 1}
        assert all(v > 0 for v in per_shard.values())
        assert sum(per_shard.values()) == ref_bytes


# ------------------------------------------------------ recovery tracing

class TestRecoveryTrace:

    def test_rollback_span_and_replay_tags(self, tmp_path):
        """Kill shard 1 mid-search (spawn mode): the merged trace books
        exactly one recovery.rollback span and the replayed coordinator
        level carries replay=True — what the report marks with ``*``."""
        saved = os.environ.pop(faults.ENV_VAR, None)
        faults.uninstall()
        extsort.reset_stats()
        os.environ[faults.ENV_VAR] = "worker_level:kill:shard=1:level=2"
        p = str(tmp_path / "chaos.jsonl")
        trace.start(p, meta={"example": "unit-chaos"})
        try:
            sizes = _implicit_levels(str(tmp_path), n=5, nshards=2,
                                     mode="spawn",
                                     checkpoint_dir=str(tmp_path / "ck"),
                                     max_recoveries=2)
        finally:
            trace.stop()
            os.environ.pop(faults.ENV_VAR, None)
            faults.uninstall()
            if saved is not None:
                os.environ[faults.ENV_VAR] = saved
        assert sizes == PANCAKE5
        assert extsort.STATS["recoveries"] == 1

        _, spans, _ = trace.read(p)
        rollbacks = [s for s in spans if s["sid"] == "recovery.rollback"]
        assert len(rollbacks) == 1
        assert rollbacks[0]["attrs"]["shard_lost"] == 1
        assert rollbacks[0]["shard"] is None       # coordinator-side span
        replayed = [s for s in spans if s["sid"] == "bfs.level"
                    and (s.get("attrs") or {}).get("replay")]
        assert replayed
        assert all(s["shard"] is None for s in replayed)
        rows = trace.level_rows(spans)
        assert any(r["replay"] for r in rows)
        assert sum(r["recoveries"] for r in rows) == 1
