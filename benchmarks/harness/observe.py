"""What a run observes, and how the per-layer metric files read it.

`obs` (one dict a run) holds
  values    numbers the driver worked out itself (rates, counts of steps)
  spans     {name: [seconds, ...]} of the driver's own calls in the window
  counters  {"window": deltas over the window, "process": totals at exit}
            of the program's counters, as nested dicts
  trace     reduce_trace.reduce()'s result for the traced slice, or None
  checks    {name: (number compared, its limit)}: what decided `correct`
  memory_peak_bytes  read at the window's close, before the reference runs
  cfg, cell the configuration's and the cell's files; device, chips

A metric is a file `metrics/<name>.json`: unit, layer, moves, the driver
kinds (`drivers`) or cells (`workloads`) that report it, a `reader` and
its `args`. A reader is `readers/<reader>.py` with `read(obs, args)`; it
returns None where it finds nothing to read, and the metric is left out.
"""
from __future__ import annotations

import contextlib
import glob
import importlib
import os
import shutil
import time

from .common import BENCH_DIR, load_json


class Spans:
    """Host spans around the driver's own calls: durations by name on the
    host clock always, and `jax.profiler.TraceAnnotation`s on the
    profiler's clock while a trace is being taken."""

    def __init__(self):
        self.durations: dict = {}
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.durations.setdefault(name, []).append(
                time.perf_counter() - t0)
            if ann is not None:
                ann.__exit__(None, None, None)

    def reset(self):
        self.durations = {}


class Tracer:
    """The profiler around a slice of the window. `warm()` takes a
    throw-away trace during set-up so that the start inside the window
    does not pay the profiler's own start-up."""

    def __init__(self, out_dir: str, spans: Spans):
        shutil.rmtree(out_dir, ignore_errors=True)   # only this run's trace
        self.dir, self.spans = out_dir, spans
        self.active = False
        self._slice = None

    def _options(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        return opts

    def warm(self):
        import jax
        import jax.numpy as jnp
        scratch = os.path.join(self.dir, "warm")
        jax.profiler.start_trace(scratch, profiler_options=self._options())
        jnp.zeros(8).block_until_ready()
        jax.profiler.stop_trace()

    def start(self):
        import jax
        jax.profiler.start_trace(os.path.join(self.dir, "slice"),
                                 profiler_options=self._options())
        self.active = self.spans.tracing = True
        self._slice = jax.profiler.TraceAnnotation("bench.slice")
        self._slice.__enter__()

    def stop(self):
        import jax
        self._slice.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = self.spans.tracing = False

    def reduce(self):
        from . import reduce_trace
        path = reduce_trace.find_xplane(os.path.join(self.dir, "slice"))
        if path is None:
            return None, None
        rows = reduce_trace.load_rows(path)
        return reduce_trace.reduce(rows), rows


def delta(after, before):
    """after - before over nested dicts of numbers."""
    if isinstance(after, dict):
        before = before if isinstance(before, dict) else {}
        return {k: delta(v, before.get(k)) for k, v in after.items()}
    if isinstance(after, (int, float)) and not isinstance(after, bool):
        return after - (before if isinstance(before, (int, float)) else 0)
    return after


def metric_files(bench_dir: str = BENCH_DIR):
    return [load_json(p) for p in sorted(
        glob.glob(os.path.join(bench_dir, "metrics", "*.json")))]


def metrics_of(cell: dict, bench_dir: str = BENCH_DIR):
    """The per-layer metric files that this cell reports: those that list
    the cell under `workloads`, or its driver kind under `drivers`."""
    return [m for m in metric_files(bench_dir)
            if cell["name"] in m.get("workloads", ())
            or cell["driver"] in m.get("drivers", ())]


def read_metrics(obs: dict, bench_dir: str = BENCH_DIR) -> dict:
    """{name: {"value", "unit"}} of the cell's per-layer metrics. A reader
    that finds nothing returns None and its metric is left out."""
    out = {}
    for m in metrics_of(obs["cell"], bench_dir):
        reader = importlib.import_module(
            f"benchmarks.readers.{m['reader']}")
        value = reader.read(obs, m.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
