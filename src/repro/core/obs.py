"""Process-local metrics registry and structured span tracer.

Roomy's performance argument is that disk-based computation is priced
in a handful of countable quantities — passes over data, bytes
streamed, exchange volume (paper §2–3).  This module is the one home
for those counts plus wall-time:

* a **registry** of counter namespaces that ABSORBS the legacy
  module dicts (``extsort.STATS``, ``bitarray.STATS``,
  ``types.SORT_STATS`` stay the very same mutable dict objects —
  every existing ``STATS[k] += n`` keeps working unchanged and is
  automatically visible to snapshots/scopes/spans), and
* a **span tracer**: nested, wall-clock-timed phases with stable ids
  (``bfs.level``, ``pass.rw``, ``sort.run_build``, ``merge``,
  ``bucket.seal``/``bucket.apply``, ``ckpt.snapshot``/``ckpt.restore``,
  ``recovery.rollback``) that record the counter deltas which occurred
  inside them.  Finished spans go to a sink (disk/trace.py's JSONL
  writer) or, in shard workers, to a buffer drained over the result
  queue at each level barrier.  An optional ``annotate`` hook also
  opens each span as a profiler annotation (the caller passes
  ``jax.profiler.TraceAnnotation``), which puts the span on the device
  trace's clock.

``Histogram`` is an exact power-of-two-bucket histogram for callers
that keep their own (the serve bench's latency percentiles).

Zero-cost contract (same standard as disk/faults.py): ``ACTIVE`` is
False by default, every tracing hook starts with that single attribute
test (``span()`` returns a shared no-op immediately), counters behave
exactly as before, and the committed bench baseline stays
byte-identical with tracing off — CI enforces it.

stdlib-only on purpose: spawn-mode shard workers import this module
and must never import jax (see repro/core/__init__'s lazy-import
contract).
"""
from __future__ import annotations

import contextlib
import math
import time
from typing import Any, Callable, ContextManager, Dict, List, Optional

ACTIVE = False

#: Presence of this env var in a freshly spawned (or recovery-respawned)
#: shard worker turns on buffered tracing there — disk/trace.py sets it.
ENV_VAR = "ROOMY_TRACE"

# ----------------------------------------------------------------- registry

_COUNTERS: Dict[str, Dict[str, int]] = {}


def counters(namespace: str, defaults: Dict[str, int]) -> Dict[str, int]:
    """Register (or re-attach to) a counter namespace.

    Returns the LIVE dict: callers keep mutating it with plain
    ``d[k] += n`` and the registry holds the same object, so snapshots
    and scopes see every update with zero per-increment overhead.  This
    is how the legacy ``STATS`` dicts are absorbed backward-compatibly.
    """
    d = _COUNTERS.setdefault(namespace, {})
    for k, v in defaults.items():
        d.setdefault(k, v)
    return d


class Histogram:
    """Exact-count histogram with power-of-two buckets.

    Bucket ``b`` counts observations ``v`` with ``2**(b-1) < v <= 2**b``
    (bucket 0 counts ``v <= 1``).  Counts are exact, not sampled;
    merging two histograms is elementwise addition, hence associative.
    """

    __slots__ = ("buckets", "count", "total")

    def __init__(self):
        self.buckets: Dict[int, int] = {}
        self.count = 0
        self.total = 0.0

    def observe(self, v) -> None:
        v = float(v)
        if v <= 1.0:
            b = 0
        else:
            m, e = math.frexp(v)            # v = m * 2**e, 0.5 <= m < 1
            b = e - 1 if m == 0.5 else e    # ceil(log2(v))
        self.buckets[b] = self.buckets.get(b, 0) + 1
        self.count += 1
        self.total += v

    def percentile(self, q: float) -> float:
        """Estimate the ``q``-th percentile (0 <= q <= 100) from buckets.

        The target rank is walked through the sorted bucket keys; inside
        the covering bucket ``(2**(b-1), 2**b]`` the value is linearly
        interpolated by the rank's fractional position among that
        bucket's observations, so the estimate is exact at bucket edges
        and never off by more than one power-of-two bucket's width — the
        resolution p50/p99 latency columns need.  Empty histogram → 0.0.
        """
        if not 0.0 <= q <= 100.0:
            raise ValueError(f"percentile q={q} outside [0, 100]")
        if self.count == 0:
            return 0.0
        target = q / 100.0 * self.count
        seen = 0
        for b in sorted(self.buckets):
            n = self.buckets[b]
            if seen + n >= target:
                lo = 0.0 if b == 0 else float(2 ** (b - 1))
                hi = float(2 ** b)
                frac = (target - seen) / n
                return lo + frac * (hi - lo)
            seen += n
        return float(2 ** max(self.buckets))


def snapshot() -> dict:
    """Picklable point-in-time copy of the counters — what spawn workers
    ship to the coordinator at each level barrier."""
    return {"counters": {ns: dict(d) for ns, d in _COUNTERS.items()}}


def merge(a: dict, b: dict) -> dict:
    """Combine two snapshots: counters add.  Associative with the empty
    snapshot as identity — the property the coordinator relies on when
    folding per-shard snapshots in whatever order the result queue
    delivers them."""
    out: Dict[str, Dict[str, Dict[str, int]]] = {"counters": {}}
    for src in (a, b):
        for ns, d in src.get("counters", {}).items():
            od = out["counters"].setdefault(ns, {})
            for k, v in d.items():
                od[k] = od.get(k, 0) + v
    return out


def counter_deltas(after: dict, before: dict) -> Dict[str, int]:
    """Flat non-zero counter deltas between two snapshots, keyed
    ``namespace.counter`` — the span metric format."""
    out: Dict[str, int] = {}
    for ns, d in after.get("counters", {}).items():
        base = before.get("counters", {}).get(ns, {})
        for k, v in d.items():
            dv = v - base.get(k, 0)
            if dv:
                out[ns + "." + k] = dv
    return out


# ------------------------------------------------------------------- scopes

class Scope:
    """Counter snapshot/delta window — per-block deltas WITHOUT resetting
    the module globals (a mid-run ``reset_stats()`` corrupts every other
    observer, which is exactly the bench best-of bug this fixes)."""

    __slots__ = ("_begin", "_end")

    def __init__(self):
        self._begin = {ns: dict(d) for ns, d in _COUNTERS.items()}
        self._end = None

    def delta(self) -> Dict[str, Dict[str, int]]:
        """Per-namespace counter deltas since the scope opened (live
        while the scope is open, frozen at its close)."""
        cur = self._end or {ns: dict(d) for ns, d in _COUNTERS.items()}
        out: Dict[str, Dict[str, int]] = {}
        for ns, d in cur.items():
            base = self._begin.get(ns, {})
            out[ns] = {k: v - base.get(k, 0) for k, v in d.items()}
        return out


@contextlib.contextmanager
def scope():
    s = Scope()
    try:
        yield s
    finally:
        s._end = {ns: dict(d) for ns, d in _COUNTERS.items()}


# -------------------------------------------------------------------- spans

_SHARD: Optional[int] = None          # default shard tag for new spans
_STACK: List["Span"] = []             # open spans (runtime is 1 thread/proc)
_SPANS: List[dict] = []               # finished spans awaiting drain/sink
_SINK: Optional[Callable[[dict], None]] = None
# Opens a span's profiler annotation: annotate(sid, **scalar_attrs).
_ANNOTATE: Optional[Callable[..., ContextManager[Any]]] = None
_SCALARS = (bool, int, float, str)


class _NullSpan:
    """Shared no-op for the ACTIVE=False fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NULL = _NullSpan()


class Span:
    __slots__ = ("sid", "attrs", "shard", "ts_us", "parent", "depth",
                 "_t0", "_base", "_ann")

    def __init__(self, sid: str, attrs: dict):
        self.sid = sid
        self.shard = attrs.pop("shard", _SHARD)
        self.attrs = attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        self.parent = _STACK[-1].sid if _STACK else None
        self.depth = len(_STACK)
        _STACK.append(self)
        self._base = {ns: dict(d) for ns, d in _COUNTERS.items()}
        self.ts_us = int(time.time() * 1e6)   # epoch µs: cross-process order
        self._t0 = time.perf_counter()
        self._ann = None
        if _ANNOTATE is not None:
            self._ann = _ANNOTATE(self.sid, **{
                k: v for k, v in self.attrs.items()
                if isinstance(v, _SCALARS)})
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
        dur_us = int((time.perf_counter() - self._t0) * 1e6)
        # Generator-held spans (merge streams, bucket application) can
        # close out of LIFO order — remove by identity, top down.
        for i in range(len(_STACK) - 1, -1, -1):
            if _STACK[i] is self:
                del _STACK[i]
                break
        metrics: Dict[str, int] = {}
        for ns, d in _COUNTERS.items():
            base = self._base.get(ns, {})
            for k, v in d.items():
                dv = v - base.get(k, 0)
                if dv:
                    metrics[ns + "." + k] = dv
        rec = {"type": "span", "sid": self.sid, "ts_us": self.ts_us,
               "dur_us": dur_us, "shard": self.shard,
               "parent": self.parent, "depth": self.depth}
        if self.attrs:
            rec["attrs"] = self.attrs
        if metrics:
            rec["metrics"] = metrics
        _emit(rec)
        return False


def span(sid: str, **attrs):
    """Open a traced span (context manager).  The hook cost when tracing
    is off is this single attribute test.  ``shard=`` is split out as
    the span's shard tag (inline-mode worker fns pass it explicitly;
    spawn workers inherit it from ``enable(shard=...)``)."""
    if not ACTIVE:
        return _NULL
    return Span(sid, attrs)


def _emit(rec: dict) -> None:
    if _SINK is not None:
        _SINK(rec)
    else:
        _SPANS.append(rec)


def drain_spans() -> List[dict]:
    """Pop and return buffered finished spans (plain picklable dicts) —
    what a spawn worker returns over the result queue at a barrier."""
    out = _SPANS[:]
    del _SPANS[:]
    return out


def ingest(spans: List[dict], shard: Optional[int] = None) -> None:
    """Coordinator side: file spans collected from a worker, tagging
    untagged ones with that worker's shard id."""
    for rec in spans:
        if shard is not None and rec.get("shard") is None:
            rec["shard"] = shard
        _emit(rec)


def enable(shard: Optional[int] = None,
           sink: Optional[Callable[[dict], None]] = None,
           annotate: Optional[Callable[..., ContextManager[Any]]] = None
           ) -> None:
    """Turn tracing on.  ``sink`` (the coordinator's JSONL writer)
    receives finished spans immediately; without one (shard workers)
    spans buffer for ``drain_spans()``.  ``annotate(sid, **attrs)``, if
    given, returns a context manager that every span enters after its
    own clock readings and leaves before its end reading, with the
    span's scalar attrs: pass ``jax.profiler.TraceAnnotation`` to put
    the spans on the profiler's clock (this module never imports jax)."""
    global ACTIVE, _SHARD, _SINK, _ANNOTATE
    _SHARD = shard
    _SINK = sink
    _ANNOTATE = annotate
    ACTIVE = True


def disable() -> None:
    """Turn tracing off and drop all tracing state.  Counters are NOT
    touched — they belong to their owning modules (``reset_stats()``)."""
    global ACTIVE, _SHARD, _SINK, _ANNOTATE
    ACTIVE = False
    _SHARD = None
    _SINK = None
    _ANNOTATE = None
    del _STACK[:]
    del _SPANS[:]
