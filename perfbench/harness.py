"""What every cell shares: the run's context, set-up accounting, the
compile cache, the traced slice, and the reduction of what a driver
gathered to the one result line.  Nothing here knows a configuration, a
traffic mix or a metric by name: those are files found by the names in
``BENCHMARK.json``.
"""
from __future__ import annotations

import functools
import importlib
import json
import os
import shutil
import time
from dataclasses import dataclass, field

from perfbench import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "perfbench")


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class CompileClock:
    """Seconds inside XLA's backend compile (or its persistent-cache
    read) and the persistent cache's hits and misses, from jax's own
    monitoring events (copied from chip_smoke.py's ``_Clock``)."""

    totals = {"compile_s": 0.0, "hits": 0, "misses": 0, "compiles": 0}
    _installed = False

    @classmethod
    def install(cls):
        if cls._installed:
            return
        import jax.monitoring as mon

        tot = cls.totals

        def on_duration(event, secs, **_):
            if event.endswith("backend_compile_duration"):
                tot["compile_s"] += secs
                tot["compiles"] += 1

        def on_event(event, **_):
            if event.endswith("compilation_cache/cache_hits"):
                tot["hits"] += 1
            elif event.endswith("compilation_cache/cache_misses"):
                tot["misses"] += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        cls._installed = True

    @classmethod
    def snapshot(cls):
        return dict(cls.totals)


def enable_compile_cache():
    """JAX's persistent cache without a size cap and for every program,
    where ``JAX_COMPILATION_CACHE_DIR`` says or else at the fixed path
    ``<checkout>/.jax_cache``.  Called before anything compiles."""
    import jax

    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclass
class Context:
    """One run: the cell, its files, and what the command line gave."""
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_proc0: float
    require_tpu: bool = True      # False only from the CPU rehearsal tests
    hooks: dict = field(default_factory=dict)   # tests break the path here

    marks: list = field(default_factory=list)   # [(what, s since start)]

    @property
    def name(self):
        return self.workload["name"]

    def mark(self, what):
        """Stamp a set-up phase; printed on the ``setup:`` line."""
        self.marks.append((what, round(time.perf_counter() - self.t_proc0,
                                       2)))

    def rng(self, stream=0):
        import numpy as np

        return np.random.default_rng([int(self.seed), int(stream)])


def make_context(workload_name, seed, seconds, trace, t_proc0, **kw):
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload_name not in cells:
        raise SystemExit(f"perfbench: no workload {workload_name!r} in "
                         f"BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload_name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    return Context(cell, config, traffic, int(seed), float(seconds),
                   bool(trace), t_proc0, **kw)


def check_devices(ctx):
    """No chip, no result: never a CPU fallback."""
    import jax

    devs = jax.devices()
    want = int(ctx.workload.get("chips", 1))
    if ctx.require_tpu:
        if devs[0].platform != "tpu":
            raise SystemExit(f"perfbench: jax found no accelerator (first "
                             f"device: {devs[0].platform}); nothing was run")
        if len(devs) < want:
            raise SystemExit(f"perfbench: cell {ctx.name} needs {want} "
                             f"chips, jax sees {len(devs)}")
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    return devs


def memory_peak_bytes(devs):
    """Peak bytes on the fullest chip, as JAX reports them: the
    allocator's ``peak_bytes_in_use`` (arrays) plus ``peak_bytes_reserved``
    (what the runtime sets aside for the programs' temporaries, which the
    first does not count: 1.0 GB beside 7.3 GB for the ResNet-50 step)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    print(f"memory: {devs[0].memory_stats()}", flush=True)
    return peak


class TraceSlice:
    """The traced slice of a ``--trace 1`` run: jax's profiler (Python
    tracer off: it slows the host by half and fills the trace with frames)
    with ``mx.profiler`` running inside it, so that the program's spans
    land in the same trace as ``TraceAnnotation``s, and the window span
    that bounds the reduction."""

    def __init__(self, ctx):
        self.dir = os.path.join(ROOT, ".perfbench_trace", ctx.name)
        self.t0 = self.t1 = None
        self._span = None

    def start(self):
        import jax
        import mxnet_tpu as mx

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        os.environ.pop("TPUMX_JAX_TRACE_DIR", None)  # one trace: ours
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        mx.profiler.set_state("run")
        self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        import mxnet_tpu as mx

        self.t1 = time.perf_counter()
        import jax

        self._span.__exit__(None, None, None)
        mx.profiler.set_state("stop")
        jax.profiler.stop_trace()

    @property
    def running(self):
        return self.t0 is not None and self.t1 is None

    def load(self, device_prefix="/device:TPU:"):
        trace = trace_reduce.load(trace_reduce.find_xplane(self.dir),
                                  device_prefix)
        if not os.environ.get("PERFBENCH_TRACE_KEEP"):  # for a look by hand
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace


@dataclass
class Sources:
    """What the reducers read.  ``counters``: numbers over the measured
    window (and, prefixed ``slice.``, over the traced slice); ``timers``:
    lists of seconds; ``events``: the program's wide events, one per
    request; ``trace``: the clipped trace of the slice, or None."""
    counters: dict = field(default_factory=dict)
    timers: dict = field(default_factory=dict)
    events: list = field(default_factory=list)
    trace: object = None
    summary: dict = None
    config: dict = None
    traffic: dict = None
    peaks: dict = None


def percentile(values, q):
    """The ``q``-th percentile by linear interpolation (numpy's default),
    in plain Python so that reducers need nothing else."""
    vs = sorted(values)
    if not vs:
        return None
    k = (len(vs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (k - lo)


def per_layer_metrics(ctx, src):
    """Every per-layer metric of ``BENCHMARK.json`` that lists this cell:
    its file names the reducer, the reducer reads ``src``.  One that finds
    nothing to read returns None and is left out."""
    out = {}
    for m in benchmark()["per_layer"]:
        if ctx.name not in m.get("workloads", [ctx.name]):
            continue
        spec = load_json("metrics", m["name"] + ".json")
        reducer = importlib.import_module(
            "perfbench.reducers." + spec["reducer"])
        value = reducer.reduce(spec.get("params", {}), src)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end_metrics(ctx, values):
    out = {}
    for m in benchmark()["end_to_end"]:
        if ctx.name not in m.get("workloads", [ctx.name]):
            continue
        if m["name"] not in values:
            raise RuntimeError(f"driver did not measure {m['name']} in "
                               f"cell {ctx.name}")
        out[m["name"]] = {"value": float(values[m["name"]]),
                          "unit": m["unit"]}
    return out


def print_checks(checks):
    """Each number compared, beside its limit."""
    ok = True
    for name, value, limit, passed in checks:
        ok = ok and bool(passed)
        print(f"check {name}: value={value!r} limit={limit!r} "
              f"{'ok' if passed else 'FAILED'}", flush=True)
    return ok


def result_line(ctx, devs, outcome):
    """The contract's one JSON object from a driver's ``outcome``:
    ``e2e`` values, ``sources``, ``checks``, ``attempted``, ``failed``,
    ``memory_peak_bytes``."""
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": outcome["memory_peak_bytes"]}
    line = {"correct": print_checks(outcome["checks"]),
            "attempted": int(outcome["attempted"]),
            "failed": int(outcome["failed"])}
    if ctx.trace:
        src = outcome["sources"]
        if src.trace is not None:
            src.summary = trace_reduce.summarize(src.trace)
            device["busy_s"] = src.summary["busy_s"]
            device["window_s"] = src.summary["window_s"]
            line["breakdown"] = {"device_ops": src.summary["device_ops"],
                                 "idle_gaps": src.summary["idle_gaps"]}
            print(f"trace: devices={src.summary['n_devices']} "
                  f"events={src.summary['n_events']} spans="
                  f"{trace_reduce.span_counts(src.trace)}", flush=True)
        line["metrics"] = per_layer_metrics(ctx, src)
    else:
        line["metrics"] = end_to_end_metrics(ctx, outcome["e2e"])
    line["device"] = device
    return line
