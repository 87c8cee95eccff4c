"""The chip benchmark of the Tier J implicit BFS, driven by data.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
harness finds everything by those names:

  configs/<config>.json      the graph: n, its generators (the plain
                             reference's definition) and the program's
                             neighbour rule, as a file and a function
  workloads/<cell>.json      the traffic: its description and the limit
                             of each number that decides ``correct``
  metrics/<metric>.py        one reader per metric, ``read(ctx)``
  peaks.json                 the chip's peaks, keyed by ``device_kind``

One run: set-up (imports, the compile cache, one search cut to one level
so that the level step compiles or loads), then whole searches through
``repro.core.constructs.implicit_bfs`` back to back for about
``--seconds``, then the plain reference (``reference.py``) and the check of
every timed search's level counts against it.  ``run.py`` is the command.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from . import reference
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


def check(searches: List["Search"], ref: List[int], traffic: Dict) -> Dict:
    """The numbers compared, each with its limit; and the failed searches."""
    gaps = [level_gap(s.sizes, ref) for s in searches]
    limit = traffic["limits"]["worst_level_gap"]
    return {"numbers": {"worst_level_gap": {"value": max(gaps, default=None),
                                            "limit": limit}},
            "failed": sum(g > limit for g in gaps),
            "correct": bool(searches) and max(gaps) <= limit}


# ------------------------------------------------------------- the run

@dataclass
class Search:
    start: int
    sizes: List[int]
    seconds: float
    peak_bytes: Optional[int] = None      # the device's peak after it


@dataclass
class Context:
    """What the metric readers read."""
    config: Dict
    traffic: Dict
    searches: List[Search]
    window_s: float
    setup_s: float
    memory_peak_bytes: Optional[int]
    peaks: Dict
    compile_spans: List = field(default_factory=list)   # (event, t0, t1)
    trace: Optional[Dict] = None                         # tracefold.fold

    @property
    def n_states(self) -> int:
        return math.factorial(self.config["n"])

    @property
    def fanout(self) -> int:
        return len(self.config["generators"])


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


def _searches(total: int, rule, rng, seconds: float, dev) -> List[Search]:
    """Whole single-source searches back to back, as many as bring the end
    of the window nearest to ``seconds``: another search starts while the
    window, with half a search of the mean length so far, is shorter than
    ``seconds``.  At least one runs."""
    import jax
    from repro.core import constructs as C
    done: List[Search] = []
    t_window = time.perf_counter()
    while True:
        start = int(rng.integers(0, total))
        with jax.profiler.TraceAnnotation(tracefold.SEARCH_ANNOTATION):
            t0 = time.perf_counter()
            sizes, bits = C.implicit_bfs(total, [start], rule, impl="auto")
            jax.block_until_ready(bits.data)
            done.append(Search(start, sizes, time.perf_counter() - t0,
                               peak_bytes(dev)))
        del bits
        elapsed = time.perf_counter() - t_window
        mean = statistics.fmean(s.seconds for s in done)
        if elapsed + mean / 2 >= seconds:
            return done


def run_cell(spec: Dict, cell_name: str, seed: int, seconds: float,
             traced: bool, *, t_start: float, bench_dir: str = BENCH_DIR,
             trace_dir: str = TRACE_DIR, chip_check: Callable = require_chips,
             log=sys.stderr) -> Dict:
    """One run of one cell; returns the result line as a dict."""
    import jax
    cell = find_cell(spec, cell_name)
    dev = chip_check(cell["chips"])
    peaks = load_peaks(dev.device_kind, bench_dir)
    config = load_config(cell["config"], bench_dir)
    traffic = load_traffic(cell_name, bench_dir)
    rule = load_file(os.path.join(ROOT, config["rule"]["file"]),
                     config["rule"]["function"])(config["n"])
    total = math.factorial(config["n"])

    # Set-up: the level step of this cell's shapes compiles or loads.
    from repro.core import constructs as C
    rng = np.random.default_rng(seed)
    C.implicit_bfs(total, [int(rng.integers(0, total))], rule, max_levels=1,
                   impl="auto")
    setup_s = time.perf_counter() - t_start
    setup_peak = peak_bytes(dev)

    recorder = CompileRecorder()
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with recorder.recording():
            t0 = time.perf_counter()
            searches = _searches(total, rule, rng, seconds, dev)
            window_s = time.perf_counter() - t0
    finally:
        if traced:
            jax.profiler.stop_trace()
    peak = peak_bytes(dev)
    gc.collect()

    ref = reference.level_counts(config["n"], config["generators"])
    verdict = check(searches, ref, traffic)
    folded = None
    if traced:
        folded = tracefold.fold(
            tracefold.load_xplane(tracefold.find_xplane(trace_dir)),
            KERNEL_TAG)
    ctx = Context(config, traffic, searches, window_s, setup_s, peak, peaks,
                  recorder.spans, folded)
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
        result["breakdown"] = {"device_ops": folded["device_ops"],
                               "idle_gaps": folded["idle_gaps"]}
    print(f"searches (start, seconds): "
          f"{[(s.start, s.seconds) for s in searches]}", file=log)
    print(f"device peak bytes after set-up {setup_peak}, after each search "
          f"{[s.peak_bytes for s in searches]}", file=log)
    print(f"level counts of the first search: {searches[0].sizes}", file=log)
    print(f"reference level counts: {ref}", file=log)
    print(f"compile cache in the window: {recorder.counts}", file=log)
    for name, num in verdict["numbers"].items():
        print(f"check {name}: {num['value']} (limit {num['limit']})", file=log)
    result["checks"] = verdict["numbers"]
    return result
