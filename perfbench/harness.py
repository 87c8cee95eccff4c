"""The chip benchmark, driven by data.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
harness finds everything by those names:

  configs/<config>.json      the configuration: its sizes and, under
                             ``"search"``, the file and function that turn
                             it into the calls below (``load_search``)
  workloads/<cell>.json      the traffic: its description and the limit
                             of each number that decides ``correct``
  metrics/<metric>.py        one reader per metric, ``read(ctx)``
  peaks.json                 the chip's peaks, keyed by ``device_kind``

The object a configuration's search function returns, given the
configuration and the root its paths are relative to, provides:

  n_states, fanout           the size of the state space and the
                             neighbours of a state, for the roofline
  scopes                     the program's named scopes that its device
                             ops carry, for ``Context.program``
  draw_start(rng)            one search's start, drawn from the run's rng
  run(start, max_levels=None)
                             one search through the program, whole or cut
                             to ``max_levels`` levels: (level counts, the
                             device arrays to wait for)
  states(sizes)              the states one whole search covers
  reference(start)           the plain reference's level counts from start
  control(rng)               the control's level counts from a start drawn
                             from rng, and the reference's; ``control`` is
                             None where the configuration has none

One run: set-up (imports, the compile cache, one search cut to one level
so that its programs compile or load), then whole searches back to back
for about ``--seconds``, then the plain reference and the check of every
timed search's level counts against the reference's from its own start.
A traced run also turns the program's obs spans on for the window, each a
profiler annotation.  ``run.py`` is the command.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import spanfold
from . import tracefold

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(ROOT, ".perfbench", "trace")
KERNEL_TAG = "roomy_bitpack_"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")
TOP = 10          # entries of each list of the breakdown


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- discovery

def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(spec: Dict, name: str) -> Dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[c['name'] for c in spec['workloads']]}")


def load_config(name: str, bench_dir: str = BENCH_DIR) -> Dict:
    return load_json(os.path.join(bench_dir, "configs", f"{name}.json"))


def load_traffic(cell: str, bench_dir: str = BENCH_DIR) -> Dict:
    return load_json(os.path.join(bench_dir, "workloads", f"{cell}.json"))


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> Dict:
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       f"it has {sorted(table['devices'])}")
    return table["devices"][device_kind]


def load_file(path: str, attr: str):
    """``attr`` of the Python file at ``path``, loaded under its own name."""
    mod_name = "perfbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    mod = sys.modules.get(mod_name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
    return getattr(mod, attr)


def load_search(config: Dict, bench_dir: str = BENCH_DIR):
    """The object of the module's docstring for a configuration: its
    ``"search"`` function, called with the configuration and the root that
    the configuration's paths are relative to (the bench directory's
    parent)."""
    root = os.path.dirname(os.path.abspath(bench_dir))
    make = load_file(os.path.join(root, config["search"]["file"]),
                     config["search"]["function"])
    return make(config, root)


def load_metric(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    return load_file(os.path.join(bench_dir, "metrics", f"{name}.py"), "read")


def cell_metrics(spec: Dict, cell: str, traced: bool) -> List[Dict]:
    """The metrics a cell reports: its per-layer ones when traced, else its
    end-to-end ones; an entry with a ``workloads`` key only in those."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ----------------------------------------------------------- the check

def level_gap(got: List[int], want: List[int]) -> int:
    """Largest difference between two level-count lists, a level that only
    one of them has counting in full."""
    k = max(len(got), len(want))
    a = list(got) + [0] * (k - len(got))
    b = list(want) + [0] * (k - len(want))
    return max(abs(x - y) for x, y in zip(a, b))


def check(searches: List["Search"], refs: List[List[int]],
          traffic: Dict) -> Dict:
    """The numbers compared, each with its limit; and the failed searches.
    ``refs`` holds the reference's level counts for each search's start."""
    gaps = [level_gap(s.sizes, ref) for s, ref in zip(searches, refs)]
    limit = traffic["limits"]["worst_level_gap"]
    return {"numbers": {"worst_level_gap": {"value": max(gaps, default=None),
                                            "limit": limit}},
            "failed": sum(g > limit for g in gaps),
            "correct": bool(searches) and max(gaps) <= limit}


# ------------------------------------------------------------- the run

@dataclass
class Search:
    start: Any
    sizes: List[int]
    states: int                           # the states the search covers
    seconds: float
    peak_bytes: Optional[int] = None      # the device's peak after it


@dataclass
class Context:
    """What the metric readers read."""
    config: Dict
    traffic: Dict
    graph: Any                            # load_search's object
    searches: List[Search]
    window_s: float
    setup_s: float
    memory_peak_bytes: Optional[int]
    peaks: Dict
    compile_spans: List = field(default_factory=list)   # (event, t0, t1)
    counters: Dict = field(default_factory=dict)  # the window's obs deltas
    trace: Optional[Dict] = None                  # tracefold.fold
    program: Optional[Dict] = None                # spanfold.fold


class CompileRecorder:
    """JAX's own compile events (trace, lowering, backend compile or cache
    load) and cache hits and misses, while registered."""

    def __init__(self):
        self.spans: List = []
        self.counts: Dict[str, int] = {e: 0 for e in CACHE_EVENTS}

    def _span(self, event, t0, t1, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((event, t0, t1))

    def _event(self, event, **_):
        if event in self.counts:
            self.counts[event] += 1

    @contextlib.contextmanager
    def recording(self):
        import jax.monitoring as mon
        mon.register_event_time_span_listener(self._span)
        mon.register_event_listener(self._event)
        try:
            yield self
        finally:
            mon.unregister_event_time_span_listener(self._span)
            mon.unregister_event_listener(self._event)


def require_chips(chips: int):
    """The device to run on; raises NoChip off a TPU or short of chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX's first device is "
                     f"{devs[0].platform!r}; this benchmark runs on a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds "
                     f"{len(devs)}")
    return devs[0]


def peak_bytes(dev) -> Optional[int]:
    """The device allocator's ``peak_bytes_in_use`` so far, if it has one."""
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def _searches(graph, rng, seconds: float, dev) -> List[Search]:
    """Whole single-source searches back to back, as many as bring the end
    of the window nearest to ``seconds``: another search starts while the
    window, with half a search of the mean length so far, is shorter than
    ``seconds``.  At least one runs."""
    import jax
    done: List[Search] = []
    t_window = time.perf_counter()
    while True:
        start = graph.draw_start(rng)
        with jax.profiler.TraceAnnotation(tracefold.SEARCH_ANNOTATION):
            t0 = time.perf_counter()
            sizes, arrays = graph.run(start)
            jax.block_until_ready(arrays)
            done.append(Search(start, sizes, graph.states(sizes),
                               time.perf_counter() - t0, peak_bytes(dev)))
        del arrays
        elapsed = time.perf_counter() - t_window
        mean = statistics.fmean(s.seconds for s in done)
        if elapsed + mean / 2 >= seconds:
            return done


def _ranked(seconds: Dict[str, float]) -> List:
    return [[k, v] for k, v in sorted(seconds.items(), key=lambda kv: -kv[1])]


def _print_program(program: Dict, log) -> None:
    """One line per search: seconds under each span name and the
    device-idle seconds under each; then the levels of the first search."""
    for i, s in enumerate(program["per_search"]):
        spans = ", ".join(f"{k} {v:.4f}"
                          for k, v in sorted(s["span_s"].items()))
        idle = ", ".join(f"{k} {v:.4f}" for k, v in _ranked(s["idle_s"]))
        print(f"search {i} ({s['seconds']:.4f} s): {spans}; "
              f"device idle under {idle}", file=log)
    print("levels of the first search (level, frontier, device s, host s): "
          f"{program['levels']}", file=log)


def run_cell(spec: Dict, cell_name: str, seed: int, seconds: float,
             traced: bool, *, t_start: float, bench_dir: str = BENCH_DIR,
             trace_dir: str = TRACE_DIR, chip_check: Callable = require_chips,
             log=sys.stderr) -> Dict:
    """One run of one cell; returns the result line as a dict."""
    import jax
    from repro.core import obs
    cell = find_cell(spec, cell_name)
    dev = chip_check(cell["chips"])
    peaks = load_peaks(dev.device_kind, bench_dir)
    config = load_config(cell["config"], bench_dir)
    traffic = load_traffic(cell_name, bench_dir)
    graph = load_search(config, bench_dir)

    # Set-up: the programs of this cell's shapes compile or load.
    rng = np.random.default_rng(seed)
    jax.block_until_ready(graph.run(graph.draw_start(rng), max_levels=1)[1])
    setup_s = time.perf_counter() - t_start
    setup_peak = peak_bytes(dev)

    recorder = CompileRecorder()
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        if traced:
            obs.enable(annotate=jax.profiler.TraceAnnotation)
        with recorder.recording(), obs.scope() as counted:
            t0 = time.perf_counter()
            searches = _searches(graph, rng, seconds, dev)
            window_s = time.perf_counter() - t0
    finally:
        if traced:
            # The names of the spans the window opened, which the trace
            # is read for; obs holds them until it is turned off.
            span_names = {rec["sid"] for rec in obs.drain_spans()}
            obs.disable()
            jax.profiler.stop_trace()
    peak = peak_bytes(dev)
    gc.collect()

    refs = [graph.reference(s.start) for s in searches]
    verdict = check(searches, refs, traffic)
    folded = program = None
    if traced:
        path = tracefold.find_xplane(trace_dir)
        raw = tracefold.load_xplane(path)
        raw.update(spanfold.load_program(path, span_names, graph.scopes))
        folded = tracefold.fold(raw, KERNEL_TAG)
        program = spanfold.fold(raw)
    ctx = Context(config, traffic, graph, searches, window_s, setup_s, peak,
                  peaks, recorder.spans, counted.delta(), folded, program)
    metrics = {}
    for m in cell_metrics(spec, cell_name, traced):
        value = load_metric(m["name"], bench_dir)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": verdict["correct"], "attempted": len(searches),
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if traced:
        device["busy_s"] = folded["busy_s"]
        device["window_s"] = folded["window_s"]
        result["breakdown"] = {
            "device_ops": folded["device_ops"],
            "idle_gaps": folded["idle_gaps"],
            "idle_by_span": _ranked(program["idle_by_span"])[:TOP],
            "device_by_scope": _ranked(program["device_by_scope"])[:TOP]}
    print(f"searches (start, states, seconds): "
          f"{[(s.start, s.states, s.seconds) for s in searches]}", file=log)
    print(f"device peak bytes after set-up {setup_peak}, after each search "
          f"{[s.peak_bytes for s in searches]}", file=log)
    moved = {ns: d for ns, d in ctx.counters.items() if any(d.values())}
    print(f"program counters in the window: {moved}", file=log)
    if traced:
        _print_program(program, log)
    print(f"level counts of the first search: {searches[0].sizes}", file=log)
    print(f"reference level counts from its start: {refs[0]}", file=log)
    print(f"compile cache in the window: {recorder.counts}", file=log)
    for name, num in verdict["numbers"].items():
        print(f"check {name}: {num['value']} (limit {num['limit']})", file=log)
    result["checks"] = verdict["numbers"]
    return result
