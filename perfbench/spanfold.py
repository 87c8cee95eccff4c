"""The program's own spans and op scopes in a profiler trace.

The Tier J search driver (``repro.core.constructs.implicit_bfs``) opens
``repro.core.obs`` spans; with ``obs.enable(annotate=
jax.profiler.TraceAnnotation)`` each is also a profiler annotation, on the
device trace's clock.  ``load_program`` reads, from an ``.xplane.pb``, the
two parts of a trace that ``tracefold.load_xplane`` leaves out:

  program  the program's spans of the names given (the harness gives
           those the window opened) as ``[name, start_ns, dur_ns, stats]``;
  scoped   the device ops of the first TPU plane's ``XLA Ops`` line, each
           named by its program scope among those given (the harness gives
           the configuration's search's, such as ``PROGRAM_SCOPES``, each a
           ``jax.named_scope``) or ``""``: the ``SCOPE_STAT`` of the op's
           event metadata, which ``ProfileData`` does not expose, so these
           are read from the protobuf itself.

``fold`` reads them with ``tracefold.load_xplane``'s ``device`` and
``host`` lists inside the window of the search annotations and returns:

  span_s          wall seconds under each span name (the union of its spans);
  span_busy_s     device-busy seconds inside each span name;
  idle_by_span    each device-idle stretch, put down to the innermost span
                  open over it, or to ``outside_program``;
  per_search      for each ``bfs.search``, the seconds under each span name
                  and the idle seconds under each, inside it;
  levels          for each ``bfs.level`` of the first search: its level,
                  frontier, device-busy seconds and host seconds;
  device_by_scope device self seconds by the op's program scope
                  (``unscoped`` for the rest).

A trace without program spans (a program that has no annotation hook)
gives empty tables and every idle stretch under ``outside_program``.
``metrics`` turns a fold and the window's ``implicit`` counters into the
search driver's and the level's numbers; ``reading`` takes one of them
from a run's ``harness.Context``, for the per-layer metrics' readers.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

from . import tracefold

# The named scopes of the Tier J implicit level's ops (core/constructs.py
# _implicit_level, kernels/bitpack.py); an op takes the innermost one of
# its name stack.
PROGRAM_SCOPES = ("expand", "block_pad", "to_table")
# The stat of an op's event metadata that holds its name stack on a TPU.
SCOPE_STAT = "tf_op"
# The spans of a search in which the device waits on the host's round trip
# of a level: the level calls' spans and the search's own stretches between
# them.  A misaligned host clock moves an idle stretch among these, not out.
ROUND_TRIP_SPANS = ("bfs.level", "bfs.dispatch", "bfs.sync", "bfs.search")
# The scopes of the level's copies: the block pad and the kernels' table.
COPY_SCOPES = ("block_pad", "to_table")

OUTSIDE = "outside_program"
UNSCOPED = "unscoped"

Interval = Tuple[int, int]


def program_scope(name_stack: str, scopes: Sequence[str]) -> str:
    """The innermost of ``scopes`` in an op's name stack
    (``jit(f)/while/body/expand/vmap()/rev`` gives ``expand``), or ``""``."""
    for part in reversed(name_stack.split("/")):
        if part in scopes:
            return part
    return ""


def _xplane_schema():
    """Message classes for the parts of the profiler's ``XSpace`` protobuf
    that ``scoped_ops`` reads: each plane's lines of events, and its event
    and stat metadata.  Fields left out here are skipped when parsing."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="perfbench_xplane.proto",
                                           package="perfbench_xplane")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            fd = m.field.add(name=fname, number=number, type=ftype,
                             label=(F.LABEL_REPEATED if repeated
                                    else F.LABEL_OPTIONAL))
            if type_name:
                fd.type_name = ".perfbench_xplane." + type_name

    msg("XStat", ("metadata_id", 1, F.TYPE_INT64, False, None),
        ("str_value", 5, F.TYPE_STRING, False, None),
        ("ref_value", 7, F.TYPE_UINT64, False, None))
    msg("XEventMetadata", ("id", 1, F.TYPE_INT64, False, None),
        ("name", 2, F.TYPE_STRING, False, None),
        ("display_name", 4, F.TYPE_STRING, False, None),
        ("stats", 5, F.TYPE_MESSAGE, True, "XStat"))
    msg("XStatMetadata", ("id", 1, F.TYPE_INT64, False, None),
        ("name", 2, F.TYPE_STRING, False, None))
    # The two maps of XPlane, read as the repeated entries they are encoded as.
    msg("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False, None),
        ("value", 2, F.TYPE_MESSAGE, False, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False, None),
        ("value", 2, F.TYPE_MESSAGE, False, "XStatMetadata"))
    msg("XEvent", ("metadata_id", 1, F.TYPE_INT64, False, None),
        ("offset_ps", 2, F.TYPE_INT64, False, None),
        ("duration_ps", 3, F.TYPE_INT64, False, None))
    msg("XLine", ("name", 2, F.TYPE_STRING, False, None),
        ("timestamp_ns", 3, F.TYPE_INT64, False, None),
        ("events", 4, F.TYPE_MESSAGE, True, "XEvent"))
    msg("XPlane", ("name", 2, F.TYPE_STRING, False, None),
        ("lines", 3, F.TYPE_MESSAGE, True, "XLine"),
        ("event_metadata", 4, F.TYPE_MESSAGE, True, "EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, True, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, F.TYPE_MESSAGE, True, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("perfbench_xplane.XSpace"))


def scoped_ops(path: str, plane_name: str,
               scopes: Sequence[str]) -> List[List]:
    """``[scope, start_ns, dur_ns]`` for each op of one plane's ``XLA Ops``
    line: its program scope among ``scopes``, from the ``SCOPE_STAT`` of
    its metadata."""
    space = _xplane_schema()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out: List[List] = []
    for plane in space.planes:
        if plane.name != plane_name:
            continue
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}
        scope_of: Dict[int, str] = {}
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if stat_name.get(st.metadata_id) == SCOPE_STAT:
                    scope_of[entry.key] = program_scope(
                        st.str_value if st.HasField("str_value")
                        else stat_name.get(st.ref_value, ""), scopes)
        for line in plane.lines:
            if line.name == tracefold.DEVICE_LINE:
                out.extend([scope_of.get(e.metadata_id, ""),
                            int(line.timestamp_ns + e.offset_ps / 1000),
                            int(e.duration_ps / 1000)] for e in line.events)
    return out


def load_program(path: str, spans: Iterable[str],
                 scopes: Sequence[str]) -> Dict[str, List[List]]:
    """The program's spans named in ``spans`` and the device ops of one
    trace, each with its scope among ``scopes``."""
    spans = set(spans)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    program: List[List] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                program.extend([e.name, int(e.start_ns), int(e.duration_ns),
                                dict(e.stats)] for e in line.events
                               if e.name in spans)
    device_planes = sorted(p.name for p in data.planes
                           if p.name.startswith("/device:TPU:"))
    scoped = (scoped_ops(path, device_planes[0], scopes) if device_planes
              else [])
    return {"program": program, "scoped": scoped}


def search_window(host: Sequence[List]) -> Interval:
    """From the start of the first search annotation to the end of the
    last, as ``tracefold.fold`` takes it."""
    searches = [e for e in host if e[0] == tracefold.SEARCH_ANNOTATION]
    if not searches:
        raise ValueError("the trace holds no search annotation")
    return (min(s for _, s, _ in searches),
            max(s + d for _, s, d in searches))


def idle_gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of ``[lo, hi)`` that no busy interval covers."""
    gaps = []
    edge = lo
    for s, e in busy + [(hi, hi)]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    return gaps


def innermost(program: Sequence[List], lo: int,
              hi: int) -> List[Tuple[int, int, str]]:
    """``[lo, hi)`` cut into stretches, each named by the innermost program
    span open over it (the one that opened last), or ``OUTSIDE``."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []          # (end_ns, name), open spans
    t = lo

    def emit(upto: int) -> None:
        nonlocal t
        upto = min(upto, hi)
        if upto > t:
            out.append((t, upto, stack[-1][1] if stack else OUTSIDE))
            t = upto

    def close() -> None:
        emit(stack[-1][0])
        stack.pop()

    for name, start, dur, *_ in sorted(program, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            close()
        emit(start)
        stack.append((start + dur, name))
    while stack:
        close()
    emit(hi)
    return out


def overlap(intervals: List[Interval], lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` covered by sorted disjoint intervals."""
    i = max(bisect.bisect_right(intervals, (lo,)) - 1, 0)
    total = 0
    while i < len(intervals) and intervals[i][0] < hi:
        a, b = max(intervals[i][0], lo), min(intervals[i][1], hi)
        if b > a:
            total += b - a
        i += 1
    return total


def _by_name(program: Sequence[List], lo: int,
             hi: int) -> Dict[str, List[Interval]]:
    spans: Dict[str, List] = defaultdict(list)
    for name, s, d, *_ in program:
        if s < hi and s + d > lo:
            spans[name].append((max(s, lo), min(s + d, hi)))
    return {name: tracefold._union(iv) for name, iv in spans.items()}


def _seconds(named: Dict[str, List[Interval]], of, lo: int,
             hi: int) -> Dict[str, float]:
    """``of(a, b)`` in seconds, summed over each name's intervals clipped
    to ``[lo, hi)``; names with nothing there are left out."""
    out = {}
    for name, iv in named.items():
        ns = sum(of(max(a, lo), min(b, hi)) for a, b in iv
                 if b > lo and a < hi)
        if ns:
            out[name] = ns / 1e9
    return out


def fold(trace: Dict[str, List]) -> Dict:
    """The tables of the module's docstring, from ``tracefold.load_xplane``'s
    lists and ``load_program``'s, inside the window of the searches."""
    lo, hi = search_window(trace["host"])
    program = [e for e in trace.get("program", [])
               if e[1] < hi and e[1] + e[2] > lo]
    busy = tracefold._union((s, s + d) for _, s, d in
                             tracefold._clip(trace["device"], lo, hi))
    named = _by_name(program, lo, hi)

    stretches = innermost(program, lo, hi)
    starts = [a for a, _, _ in stretches]
    idle_named: Dict[str, List[Interval]] = defaultdict(list)
    for g0, g1 in idle_gaps(busy, lo, hi):
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(stretches) and stretches[i][0] < g1:
            a, b = max(stretches[i][0], g0), min(stretches[i][1], g1)
            if b > a:
                idle_named[stretches[i][2]].append((a, b))
            i += 1

    def wall(a, b):
        return b - a

    def busy_in(a, b):
        return overlap(busy, a, b)

    per_search = []
    searches = sorted((e for e in program if e[0] == "bfs.search"),
                      key=lambda e: e[1])
    for _, s, d, *_ in searches:
        per_search.append({
            "seconds": d / 1e9,
            "span_s": _seconds(named, wall, s, s + d),
            "idle_s": _seconds(idle_named, wall, s, s + d)})

    levels = []
    if searches:
        f0, f1 = searches[0][1], searches[0][1] + searches[0][2]
        for _, s, d, stats in sorted((e for e in program
                                      if e[0] == "bfs.level"),
                                     key=lambda e: e[1]):
            if f0 <= s < f1:
                levels.append([stats.get("level"), stats.get("frontier"),
                               overlap(busy, s, s + d) / 1e9, d / 1e9])

    scoped = tracefold._clip(trace.get("scoped", []), lo, hi)
    by_scope = {(k or UNSCOPED): v / 1e9
                for k, v in tracefold.self_times(scoped).items()}
    return {
        "span_s": _seconds(named, wall, lo, hi),
        "span_busy_s": _seconds(named, busy_in, lo, hi),
        "idle_by_span": {k: sum(b - a for a, b in iv) / 1e9
                         for k, iv in idle_named.items()},
        "per_search": per_search,
        "levels": levels,
        "device_by_scope": by_scope,
    }


def metrics(program: Dict, counters: Dict[str, int],
            n_searches: int) -> Dict[str, float]:
    """The search driver's and the level's numbers, per search where they
    are times; each left out where the trace or the counters lack what it
    reads.

      driver.round_trip_idle_s_per_search
                                         the median search's device-idle
                                         seconds under ``ROUND_TRIP_SPANS``
      driver.init_s_per_search           wall seconds of ``bfs.init``
      level.frontier_share               100 x ``implicit.frontier_states``
                                         over ``implicit.states_expanded``
      level.expand_s_per_search          device self seconds in ``expand``
      level.copy_s_per_search            device self seconds in
                                         ``block_pad`` and ``to_table``
    """
    out: Dict[str, float] = {}
    if "bfs.level" in program["span_s"] and program["per_search"]:
        out["driver.round_trip_idle_s_per_search"] = statistics.median(
            sum(s["idle_s"].get(k, 0.0) for k in ROUND_TRIP_SPANS)
            for s in program["per_search"])
    if "bfs.init" in program["span_s"]:
        out["driver.init_s_per_search"] = (program["span_s"]["bfs.init"]
                                           / n_searches)
    if counters.get("states_expanded"):
        out["level.frontier_share"] = (100.0 * counters["frontier_states"]
                                       / counters["states_expanded"])
    scopes = program["device_by_scope"]
    if "expand" in scopes:
        out["level.expand_s_per_search"] = scopes["expand"] / n_searches
    copies = [scopes[k] for k in COPY_SCOPES if k in scopes]
    if copies:
        out["level.copy_s_per_search"] = sum(copies) / n_searches
    return out


def reading(ctx, name: str):
    """``metrics``' number ``name`` for a run's ``harness.Context``: None
    for an untraced run, and where the run lacks what the number reads."""
    if ctx.program is None or not ctx.searches:
        return None
    return metrics(ctx.program, ctx.counters.get("implicit", {}),
                   len(ctx.searches)).get(name)
