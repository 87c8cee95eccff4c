"""Reduction of a profiler trace to device busy time, op times and idle gaps.

The JAX profiler writes an ``.xplane.pb``.  ``load_xplane`` keeps the two
parts the benchmark reads, as plain lists of ``[name, start_ns, dur_ns]``:

  device  the ops of the first TPU plane's ``XLA Ops`` line;
  host    the host plane's events whose names say what the host was doing
          (``activity`` below), and the benchmark's own annotations, which
          start with ``perfbench.``.

``fold`` works on that plain form alone, so it is tested on a small trace
kept beside the tests.  Events of one line nest (a loop holds the ops of
its body), so an op's time is its self time: its duration less that of
the events nested inside it.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_LINE = "XLA Ops"
ANNOTATION_PREFIX = "perfbench."
SEARCH_ANNOTATION = "perfbench.search"

# Host events that say what the host was doing, most specific first.  The
# names are the TraceMe labels that JAX and XLA put into the trace; the
# last, ``jit_call``, is any other part of a jitted call (``PjitFunction(
# ...)``: tracing the jnp functions inside it, the cache, the dispatch).
HOST_ACTIVITY: Sequence[Tuple[str, Tuple[str, ...]]] = (
    ("trace_to_jaxpr", ("trace_to_jaxpr_dynamic",)),
    ("lower_to_mlir", ("lower_sharding_computation",)),
    ("compile_or_cache_load", ("backend_compile_and_load",)),
    ("count_to_host", ("np.asarray(jax.Array)",)),
    ("jit_call", ()),
)
_ACTIVITY_OF = {name: label for label, names in HOST_ACTIVITY
                for name in names}
JIT_CALL_PREFIX = "PjitFunction("


def activity(name: str):
    """The host activity an event's name stands for, or None."""
    if name in _ACTIVITY_OF:
        return _ACTIVITY_OF[name]
    return "jit_call" if name.startswith(JIT_CALL_PREFIX) else None

Event = List  # [name, start_ns, dur_ns]


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(text: str) -> str:
    """An op's name from the HLO text a TPU trace gives as its name:
    ``%roomy_bitpack_scatter_mark.12 = u32[...] custom-call(...)`` gives
    ``roomy_bitpack_scatter_mark.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> Dict[str, List[Event]]:
    """The device ops and the host activity of one trace, in plain lists."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: List[Event] = []
    host: List[Event] = []
    device_planes = sorted(p.name for p in data.planes
                           if p.name.startswith("/device:TPU:"))
    for plane in data.planes:
        if device_planes and plane.name == device_planes[0]:
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    device.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                                  for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if activity(e.name)
                            or e.name.startswith(ANNOTATION_PREFIX))
    return {"device": device, "host": host}


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: Sequence[Event]) -> Dict[str, int]:
    """Nanoseconds of self time per op name, events of one nesting line."""
    out: Dict[str, int] = defaultdict(int)
    stack: List[List] = []          # [end_ns, name, child_ns, dur_ns]

    def close(top):
        out[top[1]] += top[3] - top[2]

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][2] += dur
        stack.append([start + dur, name, 0, dur])
    while stack:
        close(stack.pop())
    return dict(out)


def _clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def _activity_spans(host: Sequence[Event]) -> Dict[str, List[Tuple[int, int]]]:
    """Merged host intervals of each activity, sorted by start."""
    return {label: _union((s, s + d) for n, s, d in host
                          if activity(n) == label)
            for label, _ in HOST_ACTIVITY}


def _covers(spans: List[Tuple[int, int]], t: float) -> bool:
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i >= 0 and spans[i][0] <= t < spans[i][1]


def _split_gap(lo: int, hi: int, spans: Dict[str, List[Tuple[int, int]]],
               idle: Dict[str, int]) -> None:
    """Add an idle gap's time to ``idle`` by what the host was doing: each
    stretch goes to the most specific activity that covers it, the rest to
    ``host_other``."""
    cuts = {lo, hi}
    for label, _ in HOST_ACTIVITY:
        s = spans[label]
        i = max(bisect.bisect_right(s, (lo,)) - 1, 0)
        while i < len(s) and s[i][0] < hi:
            cuts.update(t for t in s[i] if lo < t < hi)
            i += 1
    edges = sorted(cuts)
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        label = next((lab for lab, _ in HOST_ACTIVITY
                      if _covers(spans[lab], mid)), "host_other")
        idle[label] += b - a


def fold(trace: Dict[str, List[Event]], kernel_tag: str) -> Dict:
    """Reduce a trace to what the per-layer metrics read.

    The window runs from the start of the first search annotation to the
    end of the last.  Returns window and busy seconds, the self seconds of
    the kernels whose names hold ``kernel_tag`` and of all other
    ops, the self seconds of every op (``op_s``) and the top ones, and the
    idle seconds by host activity."""
    searches = [e for e in trace["host"] if e[0] == SEARCH_ANNOTATION]
    if not searches:
        raise ValueError("the trace holds no search annotation")
    lo = min(s for _, s, _ in searches)
    hi = max(s + d for _, s, d in searches)
    ops = [[op_name(n), s, d] for n, s, d in _clip(trace["device"], lo, hi)]
    busy = _union((s, s + d) for _, s, d in ops)
    per_op = self_times(ops)
    kernel_ns = sum(v for k, v in per_op.items() if kernel_tag in k)
    gaps = []
    edge = lo
    for s, e in busy + [(hi, hi)]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    spans = _activity_spans(trace["host"])
    idle: Dict[str, int] = defaultdict(int)
    for a, b in gaps:
        _split_gap(a, b, spans, idle)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "kernel_s": kernel_ns / 1e9,
        "other_ops_s": (sum(per_op.values()) - kernel_ns) / 1e9,
        "n_kernel_events": sum(1 for e in ops if kernel_tag in e[0]),
        "op_s": {k: v / 1e9 for k, v in per_op.items()},
        "device_ops": [[k, v / 1e9] for k, v in top],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
    }
