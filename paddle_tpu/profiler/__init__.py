"""Profiler subsystem.

Parity: reference unified profiler (`paddle/fluid/platform/profiler/
profiler.h:47`, python `python/paddle/profiler/profiler.py:358`):
  * `RecordEvent` — instrumented host spans (reference
    `phi/api/profiler/event_tracing.h:32`), here also emitted as
    jax.profiler TraceAnnotations so they appear on the device timeline;
  * `Profiler` with `make_scheduler(closed/ready/record, repeat)` state
    machine, start/stop/step, chrome-trace export and `summary()` tables
    (reference `profiler_statistic.py`);
  * `benchmark()` step timer with ips/latency stats (reference
    `python/paddle/profiler/timer.py`).

TPU-native: the device side is jax.profiler (XLA/TPU trace -> perfetto/
tensorboard); the host side is a lightweight span recorder. Chrome-trace
export writes the host spans; the device trace directory sits next to it.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import ContextDecorator
from enum import Enum
from typing import Callable, Iterable, Optional

import jax

__all__ = ["ProfilerState", "ProfilerTarget", "TracerEventType",
           "RecordEvent", "Profiler", "make_scheduler", "benchmark",
           "export_chrome_tracing", "load_profiler_result",
           "register_counter_provider", "unregister_counter_provider",
           "counters", "default_log_dir", "host_events",
           "PROFILER_LOG_DIR_ENV"]

# Where chrome-trace exports land when no explicit log_dir is given:
# the env var overrides, the default keeps everything in one gitignored
# directory instead of littering the repo root / CWD.
PROFILER_LOG_DIR_ENV = "PADDLE_TPU_PROFILER_DIR"


def default_log_dir() -> str:
    """The profiler's export directory: `Profiler(log_dir=...)` wins,
    then $PADDLE_TPU_PROFILER_DIR, then ./profiler_log (gitignored)."""
    return os.environ.get(PROFILER_LOG_DIR_ENV) or "./profiler_log"


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1      # accepted for API compat; maps to the device target
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class TracerEventType(Enum):
    Operator = 0
    Dataloader = 1
    ProfileStep = 2
    Forward = 3
    Backward = 4
    Optimization = 5
    Communication = 6
    PythonOp = 7
    UserDefined = 8


class _HostTracer:
    """Collects RecordEvent spans (thread-safe, per-thread nesting)."""

    def __init__(self):
        self.events = []
        self._lock = threading.Lock()
        self.enabled = False

    def add(self, name, etype, start_ns, end_ns, tid):
        if not self.enabled:
            return
        with self._lock:
            self.events.append({"name": name, "type": etype.name,
                                "ts": start_ns, "dur": end_ns - start_ns,
                                "tid": tid})


_tracer = _HostTracer()


def host_events() -> list:
    """The recorded RecordEvent host spans (a copy) — the accessor the
    serving RequestTracer merges into its chrome-trace export so host
    work and request lifecycles share one timeline."""
    return list(_tracer.events)

# Counter providers: subsystems (e.g. serving.metrics) register a zero-arg
# callable returning {counter: value}; Profiler.summary() appends the live
# values and counters() exposes them programmatically.
_counter_providers: dict = {}


def register_counter_provider(name: str, fn):
    _counter_providers[name] = fn


def unregister_counter_provider(name: str):
    _counter_providers.pop(name, None)


def counters() -> dict:
    """{provider: {counter: value}} from every registered provider."""
    out = {}
    for name, fn in list(_counter_providers.items()):
        try:
            out[name] = fn()
        except Exception as e:        # a dead provider must not sink summary()
            out[name] = {"error": repr(e)}
    return out


class RecordEvent(ContextDecorator):
    """Host span: a `jax.profiler.TraceAnnotation` in ANY trace that is
    being taken (paddle's `Profiler`, a bare `jax.profiler.start_trace`,
    TensorBoard's capture), so it lands on the profiler's clock beside
    the device ops. Outside a profiling session it is the annotation's
    own flag check (`is_enabled()`) and nothing is built. Keyword
    metadata (`step=`, `bucket=`, `fn=`) rides on the annotation: nesting
    on one thread gives a span its parent, the metadata gives it its
    identifier. The host clock is read, and paddle's own host-span list
    appended to, only behind an active `Profiler`.

    Parity: paddle.profiler.RecordEvent (event_tracing.h:32 emission
    points are the generated ad_funcs; here ops.dispatch hooks this when
    FLAGS_benchmark or an active profiler asks for op spans)."""

    _ann = None                        # the open annotation, in a trace
    _t0 = 0                            # the start, behind a Profiler

    def __init__(self, name: str,
                 event_type: TracerEventType = TracerEventType.UserDefined,
                 **meta):
        self.name = name
        self.event_type = event_type
        self.meta = meta

    def __enter__(self):
        annotation = jax.profiler.TraceAnnotation
        if annotation.is_enabled():
            self._ann = annotation(self.name, **self.meta)
            self._ann.__enter__()
        if _tracer.enabled:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._t0:
            if _tracer.enabled:
                _tracer.add(self.name, self.event_type, self._t0,
                            time.perf_counter_ns(), threading.get_ident())
            self._t0 = 0
        return False

    def begin(self):
        self.__enter__()

    def end(self):
        self.__exit__()


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Parity: paddle.profiler.make_scheduler — step-indexed state fn."""
    period = closed + ready + record

    def fn(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return fn


def _default_on_ready(prof):
    path = prof.log_dir or default_log_dir()
    os.makedirs(path, exist_ok=True)
    out = os.path.join(path, f"paddle_tpu_trace_{int(time.time())}.json")
    prof.export(out)


class Profiler:
    """Parity: paddle.profiler.Profiler (profiler.py:358).

    with Profiler(scheduler=make_scheduler(...)) as p:
        for batch in loader:
            train_step(batch)
            p.step()
    p.summary()
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready=None, log_dir=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self.targets = list(targets) if targets else [ProfilerTarget.CPU,
                                                      ProfilerTarget.TPU]
        if scheduler is None:
            self.scheduler = lambda step: ProfilerState.RECORD
        elif callable(scheduler):
            self.scheduler = scheduler
        else:  # (start, end) tuple form
            lo, hi = scheduler
            self.scheduler = make_scheduler(closed=max(lo, 0), ready=0,
                                            record=hi - lo, repeat=1)
        self.on_trace_ready = on_trace_ready or _default_on_ready
        self.log_dir = log_dir
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._device_tracing = False
        self._step_records = []
        self._last_step_t = None

    # -- lifecycle --------------------------------------------------------
    def start(self):
        benchmark().begin()
        if self.timer_only:
            return
        self.current_state = self.scheduler(self.step_num)
        self._transition(ProfilerState.CLOSED, self.current_state)
        return self

    def stop(self):
        benchmark().end()
        if self.timer_only:
            return
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            self._stop_tracing()
            self.on_trace_ready(self)
        self.current_state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[int] = None):
        benchmark().step(num_samples)
        now = time.perf_counter_ns()
        if self._last_step_t is not None:
            self._step_records.append(now - self._last_step_t)
        self._last_step_t = now
        if self.timer_only:
            self.step_num += 1
            return
        prev = self.current_state
        self.step_num += 1
        self.current_state = self.scheduler(self.step_num)
        self._transition(prev, self.current_state)

    def _transition(self, prev, new):
        recording = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if prev not in recording and new in recording:
            try:
                self._start_tracing()
            except Exception:
                # roll back so this profiler's stop()/__exit__ cannot tear
                # down the OTHER profiler's active recording
                self.current_state = ProfilerState.CLOSED
                raise
        elif prev in recording and new not in recording:
            self._stop_tracing()
            self.on_trace_ready(self)

    def _start_tracing(self):
        if _tracer.enabled:
            # the module-global tracer supports ONE active profiler; a
            # silent second start would clear the first profiler's spans
            raise RuntimeError(
                "another Profiler is already recording; stop it first "
                "(only one active Profiler is supported)")
        _tracer.enabled = True
        _tracer.events = []
        if any(t in (ProfilerTarget.TPU, ProfilerTarget.GPU)
               for t in self.targets):
            d = self.log_dir or default_log_dir()
            os.makedirs(d, exist_ok=True)
            try:
                jax.profiler.start_trace(d)
            except Exception:
                # a device trace that was asked for and did not start is
                # an error, not a host-only recording in silence
                _tracer.enabled = False
                raise
            self._device_tracing = True

    def _stop_tracing(self):
        _tracer.enabled = False
        if self._device_tracing:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_tracing = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- results ----------------------------------------------------------
    def export(self, path: str, format: str = "json"):
        """Chrome-trace JSON of the host spans (device trace lives in the
        jax trace dir). Parity: export_chrome_tracing."""
        events = [{"name": e["name"], "ph": "X", "cat": e["type"],
                   "ts": e["ts"] / 1e3, "dur": e["dur"] / 1e3,
                   "pid": os.getpid(), "tid": e["tid"]}
                  for e in _tracer.events]
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregated host-span table (name, calls, total/avg/max).
        Parity: profiler_statistic.py summary tables."""
        div = {"s": 1e9, "ms": 1e6, "us": 1e3, "ns": 1.0}[time_unit]
        agg = {}
        for e in _tracer.events:
            a = agg.setdefault(e["name"], {"calls": 0, "total": 0,
                                           "max": 0, "type": e["type"]})
            a["calls"] += 1
            a["total"] += e["dur"]
            a["max"] = max(a["max"], e["dur"])
        rows = sorted(agg.items(), key=lambda kv: -kv[1]["total"])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(' + time_unit + ')':>14}"
                 f"{'Avg(' + time_unit + ')':>12}{'Max(' + time_unit + ')':>12}"]
        lines.append("-" * len(lines[0]))
        for name, a in rows:
            lines.append(
                f"{name[:39]:<40}{a['calls']:>8}"
                f"{a['total'] / div:>14.4f}"
                f"{a['total'] / a['calls'] / div:>12.4f}"
                f"{a['max'] / div:>12.4f}")
        if self._step_records:
            import statistics
            sr = [x / 1e6 for x in self._step_records]
            lines.append("")
            lines.append(
                f"steps: {len(sr)}  avg {statistics.mean(sr):.3f} ms  "
                f"p50 {statistics.median(sr):.3f} ms  "
                f"max {max(sr):.3f} ms")
        ctrs = counters()
        if ctrs:
            lines.append("")
            for prov, vals in sorted(ctrs.items()):
                pairs = "  ".join(f"{k}={v}" for k, v in vals.items())
                lines.append(f"[{prov}] {pairs}")
        table = "\n".join(lines)
        print(table)
        return table

    @property
    def events(self):
        return list(_tracer.events)


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """Parity: paddle.profiler.export_chrome_tracing — on_trace_ready
    factory writing into dir_name."""
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        prof.export(os.path.join(
            dir_name, f"{name}_{int(time.time() * 1000)}.json"))
    return handler


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# benchmark timer (parity: python/paddle/profiler/timer.py)
# ---------------------------------------------------------------------------

class _Benchmark:
    def __init__(self):
        self.reset()

    def reset(self):
        self._steps = []
        self._samples = []
        self._t0 = None
        self._running = False

    def begin(self):
        self.reset()
        self._running = True
        self._t0 = time.perf_counter()

    def step(self, num_samples=None):
        if not self._running:
            return
        now = time.perf_counter()
        self._steps.append(now - self._t0)
        self._samples.append(num_samples)
        self._t0 = now

    def step_info(self, unit="samples"):
        if not self._steps:
            return "no steps recorded"
        import statistics
        avg = statistics.mean(self._steps)
        line = (f"avg_batch_cost: {avg * 1000:.3f} ms, "
                f"p50: {statistics.median(self._steps) * 1000:.3f} ms")
        vals = [s for s in self._samples if s]
        if vals:
            total = sum(vals)
            ips = total / sum(self._steps)
            line += f", ips: {ips:.2f} {unit}/s"
        return line

    def end(self):
        self._running = False

    @property
    def num_steps(self):
        return len(self._steps)


_benchmark = _Benchmark()


def benchmark() -> _Benchmark:
    """Parity: paddle.profiler.utils.benchmark() global step timer."""
    return _benchmark


import enum as _enum


class SortedKeys(_enum.Enum):
    """Summary sort keys (parity: profiler.SortedKeys)."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(_enum.Enum):
    """Summary table views (parity: profiler.SummaryView)."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


# ---------------------------------------------------------------------------
# training observability (ISSUE 11): cost accounting, compile-event log,
# and the TrainingMonitor — submodules kept import-light (no jax at
# module level) so loading the profiler never touches a backend.
# ---------------------------------------------------------------------------
from . import compile_log            # noqa: E402
from . import cost                   # noqa: E402

# Set-up spans (compile_log): each enters a RecordEvent of its
# name, and JAX's compile stages are attributed to the innermost open one.
# JAX reports a stage's start (a scalar at entry) and its span at exit,
# on one thread and on one clock, nested as they ran.
compile_log.set_annotation(RecordEvent)
_STAGE_OF = {"/jax/core/compile/jaxpr_trace_duration": "trace",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
             "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


def _stage_began(event, value, **_):
    stage = _STAGE_OF.get(event)
    if stage is not None:
        compile_log.stage_began(stage, value)


def _stage_ended(event, start, end, **_):
    stage = _STAGE_OF.get(event)
    if stage is not None:
        compile_log.stage_ended(stage, start, end)


def _stage_duration(event, seconds, **_):
    if event == _CACHE_LOAD:          # inside a backend compile
        compile_log.stage_nested("cache_load", seconds)


jax.monitoring.register_scalar_listener(_stage_began)
jax.monitoring.register_event_time_span_listener(_stage_ended)
jax.monitoring.register_event_duration_secs_listener(_stage_duration)
from . import exposition             # noqa: E402
from .monitor import (TrainingMonitor, active_monitor,  # noqa: E402
                      grad_global_norm)

__all__ += ["TrainingMonitor", "active_monitor", "grad_global_norm",
            "compile_log", "cost", "exposition"]


def export_protobuf(profiler_result, path):
    """Serialize a profiler result (parity: profiler.export_protobuf —
    the reference dumps its own proto; this build writes the same JSON
    span list load_profiler_result reads back)."""
    import json
    data = profiler_result.events if hasattr(profiler_result, "events") \
        else profiler_result
    with open(path, "w") as f:
        json.dump(data, f)
    return path
