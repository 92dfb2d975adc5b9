"""TrainingMonitor: the serving FlightRecorder's training counterpart
(ISSUE 11).

An always-on-when-attached bounded per-step ring over a training loop:
each `monitor.step(loss)` records fetch-synced step latency, the loss,
the gradient global norm, the learning rate, dispatch NaN-hook hits and
compile-event deltas (trace/retrace/eager-fallback/program-compile) —
so a NaN'd or slowed run carries its own postmortem, the way an engine
failure snapshot ships the flight recorder.

Timing contract (do not regress): a host fetch synchronizes on every
access path, so `step(loss)` fetches the loss scalar FIRST and stamps
the clock AFTER the fetch returns: the recorded latency spans
the device work, not the async dispatch. A monitor-less loop pays
nothing: the only hook in the hot path (`Optimizer.step`) is one
module-global truthiness check, asserted allocation-free by
tests/test_training_monitor.py.

Three output surfaces, all derived from the same ring/counters:

* `snapshot()` — flat dict (counters + gauges + step-latency
  percentiles via the bounded-reservoir registry), rendered to
  Prometheus text by the SHARED exposition module
  (`profiler.exposition`, prefix `paddle_training`) under the same
  no-hand-maintained-name-list drift contract as serving;
* `export(path)` — a chrome-trace JSON (detailed mode adds one span
  per step on the `perf_counter_ns` clock `RecordEvent` uses, so the
  export merges with profiler host spans on ONE timeline) carrying the
  ring + compile-event log for `tools/train_report.py`;
* `Profiler.summary()` — `register()` adds the snapshot as a counter
  provider, like `ServingMetrics.register`.

Detailed mode (default OFF) is the only per-step allocation beyond the
ring dict: a chrome event per step. Everything recorded is JSON-safe.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from . import compile_log

__all__ = ["TrainingMonitor", "active_monitor", "grad_global_norm",
           "TRAIN_PID", "PERCENTILE_WINDOW"]

# chrome-trace pid for training-step rows (serving request rows use 1,
# profiler host spans use os.getpid())
TRAIN_PID = 2

PERCENTILE_WINDOW = 1024

# the active-monitor stack: Optimizer.step's hook is `if _ACTIVE:` —
# one module-global truthiness check when no monitor is attached
_ACTIVE: List["TrainingMonitor"] = []


def active_monitor() -> Optional["TrainingMonitor"]:
    return _ACTIVE[-1] if _ACTIVE else None


# the shared nearest-rank percentile rule — one implementation for
# both observability stacks (serving reservoirs import it too)
from .exposition import percentile as _percentile  # noqa: E402


def _fetch_scalar(v) -> Optional[float]:
    """Host-fetch a scalar (Tensor / jax array / float) — the fetch IS
    the device sync (see module docstring). None-safe; a non-scalar or
    failed fetch records None rather than raising mid-train-loop."""
    if v is None:
        return None
    if isinstance(v, (int, float)):
        return float(v)
    d = getattr(v, "_data", v)
    try:
        return float(np.asarray(d))
    except Exception:
        return None


def grad_global_norm(parameters) -> Optional[object]:
    """sqrt(sum ||g||^2) over parameters' live grad buffers as a LAZY
    jax scalar (fetch it to sync), fp32 accumulation. None when no
    concrete grads exist (e.g. inside a to_static trace, where grads
    are tracers and the python hook must not leak them)."""
    import jax
    import jax.numpy as jnp
    total = None
    for p in parameters:
        g = getattr(p, "_grad_buffer", None)
        if g is None:
            continue
        if isinstance(g, jax.core.Tracer):
            return None
        sq = jnp.sum(jnp.square(jnp.asarray(g).astype(jnp.float32)))
        total = sq if total is None else total + sq
    if total is None:
        return None
    return jnp.sqrt(total)


class TrainingMonitor:
    """Bounded per-step telemetry ring for a training loop.

    with TrainingMonitor(optimizer=opt).watch(step_fn) as mon:
        for batch in loader:
            loss = step_fn(*batch)
            mon.step(loss, tokens=batch_tokens)
    mon.snapshot(); mon.export("train_trace.json")
    """

    def __init__(self, max_steps: int = 512, optimizer=None,
                 detailed: bool = False, name: str = "training",
                 track_grad_norm: bool = True):
        self.name = name
        self.detailed = bool(detailed)
        self.track_grad_norm = bool(track_grad_norm)
        self._optimizer = optimizer
        self._traced = None
        self._ring: deque = deque(maxlen=int(max_steps))
        self._chrome: deque = deque(maxlen=int(max_steps))
        self.counters: Dict[str, int] = {
            "steps": 0,
            "tokens": 0,
            "nan_checks": 0,       # dispatch NaN-hook evaluations seen
            "nan_hits": 0,         # NaN/Inf detections (the alert)
            "traces": 0,           # to_static first compiles
            "retraces": 0,         # guard misses on a warm cache
            "ast_converts": 0,     # dy2static rescues
            "eager_fallbacks": 0,  # graph breaks -> eager
            "program_compiles": 0,  # serving ProgramCache compiles
        }
        self._latency = deque(maxlen=PERCENTILE_WINDOW)   # seconds
        self._t_last: Optional[int] = None
        self.last_loss: Optional[float] = None
        self.last_grad_norm: Optional[float] = None
        self.last_lr: Optional[float] = None
        # pending per-step context pushed by hooks (Optimizer.step)
        self._pending: Dict[str, object] = {}
        self._last_compile = compile_log.counters()
        self._last_compile_gen = compile_log.generation()
        self._last_nan = self._nan_stats()
        self._last_nan_gen = self._nan_gen()
        self._registered = False

    # ---- lifecycle -------------------------------------------------------
    def start(self) -> "TrainingMonitor":
        if self not in _ACTIVE:
            _ACTIVE.append(self)
        self._t_last = None
        return self

    def stop(self) -> "TrainingMonitor":
        if self in _ACTIVE:
            _ACTIVE.remove(self)
        return self

    def __enter__(self) -> "TrainingMonitor":
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def watch(self, traced) -> "TrainingMonitor":
        """Attach the TracedFunction driving the loop: its donation
        mode and fallback/program counts become snapshot gauges and the
        per-step `retraced` flag."""
        self._traced = traced
        return self

    # ---- hooks (called by Optimizer.step when this monitor is active) ----
    def note(self, **kw):
        """Stash per-step context (lr, grad_norm — possibly a LAZY jax
        scalar) for the next `step()` call to fetch and record."""
        self._pending.update(kw)

    # ---- the per-step record ---------------------------------------------
    @staticmethod
    def _nan_stats() -> Dict[str, int]:
        try:
            from ..utils import nan_inf
            return nan_inf.nan_stats()
        except Exception:
            return {"checks": 0, "hits": 0}

    @staticmethod
    def _nan_gen() -> int:
        try:
            from ..utils import nan_inf
            return nan_inf.nan_stats_generation()
        except Exception:
            return 0

    def step(self, loss=None, *, grad_norm=None, lr=None, tokens=None):
        """Record one training step (call once per iteration, after the
        step ran). Fetches the loss (and any pending grad norm) BEFORE
        stamping the clock — the fetch is the sync."""
        loss_v = _fetch_scalar(loss)
        if grad_norm is None:
            grad_norm = self._pending.pop("grad_norm", None)
        gn_v = _fetch_scalar(grad_norm)
        if lr is None:
            lr = self._pending.pop("lr", None)
            if lr is None and self._optimizer is not None:
                try:
                    lr = self._optimizer.get_lr()
                except Exception:
                    lr = None
        now = time.perf_counter_ns()
        dur_ns = None if self._t_last is None else now - self._t_last
        self._t_last = now
        n = self.counters["steps"]
        self.counters["steps"] += 1
        if tokens:
            self.counters["tokens"] += int(tokens)
        # compile-event + NaN-hook deltas since the previous step. The
        # shared sources can be RESET mid-run (to_static_report(
        # reset=True) clears the compile log, reset_nan_stats() the NaN
        # counters): their reset GENERATION re-baselines the deltas to
        # zero, and a residual total-below-baseline also counts from
        # zero — a Prometheus counter must never go backwards.
        gen = compile_log.generation()
        if gen != self._last_compile_gen:
            self._last_compile = {}
            self._last_compile_gen = gen
        comp = compile_log.counters()
        comp_delta = {}
        for k, v in comp.items():
            prev = self._last_compile.get(k, 0)
            d = v - prev if v >= prev else v
            if d:
                comp_delta[k] = d
        self._last_compile = comp
        for kind, d in comp_delta.items():
            key = {"trace": "traces", "retrace": "retraces",
                   "ast_convert": "ast_converts",
                   "eager_fallback": "eager_fallbacks",
                   "program_compile": "program_compiles"}.get(kind)
            if key is not None:
                self.counters[key] += d
        nan_gen = self._nan_gen()
        if nan_gen != self._last_nan_gen:
            self._last_nan = {"checks": 0, "hits": 0}
            self._last_nan_gen = nan_gen
        nan = self._nan_stats()

        def _delta(cur, prev):          # reset-proof (see above)
            return cur - prev if cur >= prev else cur
        nan_checks = _delta(nan.get("checks", 0),
                            self._last_nan.get("checks", 0))
        nan_hits = _delta(nan.get("hits", 0), self._last_nan.get("hits", 0))
        self._last_nan = nan
        self.counters["nan_checks"] += nan_checks
        self.counters["nan_hits"] += nan_hits

        rec = {"step": n, "t1_ns": now,
               "dur_ms": None if dur_ns is None else round(dur_ns / 1e6, 4),
               "loss": loss_v, "grad_norm": gn_v,
               "lr": None if lr is None else float(lr),
               "tokens": None if tokens is None else int(tokens)}
        if nan_hits:
            rec["nan_hits"] = nan_hits
        if comp_delta:
            rec["compile_events"] = comp_delta
            rec["retraced"] = bool(comp_delta.get("trace")
                                   or comp_delta.get("retrace"))
        self._ring.append(rec)
        if dur_ns is not None:
            self._latency.append(dur_ns / 1e9)
        self.last_loss = loss_v
        self.last_grad_norm = gn_v
        self.last_lr = rec["lr"]
        self._pending.clear()
        if self.detailed and dur_ns is not None:
            ev = {"name": "train_step", "ph": "X", "cat": "training",
                  "ts": (now - dur_ns) / 1e3, "dur": dur_ns / 1e3,
                  "pid": TRAIN_PID, "tid": 0,
                  "args": {"step": n, "loss": loss_v}}
            self._chrome.append(ev)
        return rec

    # ---- views -----------------------------------------------------------
    def records(self) -> List[dict]:
        """The retained step records, oldest first (copies)."""
        return [dict(r) for r in self._ring]

    def latency_percentiles(self) -> Dict[str, Optional[float]]:
        return {f"p{q}": _percentile(self._latency, q)
                for q in (50, 90, 99)}

    def snapshot(self) -> dict:
        """Flat counters+gauges dict — the Prometheus/summary surface.
        None-valued gauges are omitted (the exposition rule: no honest
        value, no sample)."""
        snap = dict(self.counters)
        snap["ring_steps"] = len(self._ring)
        snap["detailed"] = self.detailed
        snap["compile_events_dropped"] = compile_log.dropped()
        if self.last_loss is not None:
            snap["last_loss"] = self.last_loss
        if self.last_grad_norm is not None:
            snap["last_grad_norm"] = self.last_grad_norm
        if self.last_lr is not None:
            snap["last_lr"] = self.last_lr
        tr = self._traced
        if tr is not None:
            snap["watched_donate"] = bool(getattr(tr, "_donate", False))
            snap["watched_programs"] = len(getattr(tr, "_cache", ()))
            snap["watched_fallbacks"] = int(
                getattr(tr, "_fallback_count", 0))
        for q, v in self.latency_percentiles().items():
            if v is not None:
                snap[f"step_latency_{q}_ms"] = round(v * 1e3, 3)
        return snap

    summary = snapshot

    def prometheus_text(self, *, prefix: str = "paddle_training",
                        labels: Optional[dict] = None,
                        emit_type: bool = True) -> str:
        """snapshot() through the SHARED exposition renderer — keys in
        the counters dict are typed counter, everything else gauge; the
        drift test asserts the bijection both ways."""
        from .exposition import prometheus_lines
        lines = prometheus_lines(self.snapshot(),
                                 counter_keys=set(self.counters),
                                 prefix=prefix, labels=labels,
                                 emit_type=emit_type)
        return "\n".join(lines) + "\n" if lines else ""

    # ---- export ----------------------------------------------------------
    def chrome_events(self) -> List[dict]:
        events: List[dict] = []
        if self._chrome:
            events.append({"name": "process_name", "ph": "M",
                           "pid": TRAIN_PID,
                           "args": {"name": "training steps"}})
            events.extend(dict(e) for e in self._chrome)
        return events

    def export(self, path: Optional[str] = None,
               include_profiler: bool = True) -> dict:
        """One document for tools/train_report.py: chrome spans
        (detailed mode; merged with profiler RecordEvent host spans on
        the shared perf_counter clock) + the step ring + the
        compile-event log + the snapshot."""
        events = self.chrome_events()
        if include_profiler:
            import os
            from . import host_events
            host = host_events()
            if host:
                events.append({"name": "process_name", "ph": "M",
                               "pid": os.getpid(),
                               "args": {"name": "host spans"}})
            for e in host:
                events.append({"name": e["name"], "ph": "X",
                               "cat": e["type"], "ts": e["ts"] / 1e3,
                               "dur": e["dur"] / 1e3,
                               "pid": os.getpid(), "tid": e["tid"]})
        doc = {"displayTimeUnit": "ms", "traceEvents": events,
               "trainingMonitor": {
                   "snapshot": self.snapshot(),
                   "records": self.records(),
                   "compile_events": compile_log.events(),
                   "compile_counters": compile_log.counters(),
               }}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc

    # ---- profiler integration -------------------------------------------
    def register(self) -> "TrainingMonitor":
        """Expose the snapshot through Profiler.summary() (the
        ServingMetrics.register pattern)."""
        from . import register_counter_provider
        register_counter_provider(self.name, self.snapshot)
        self._registered = True
        return self

    def unregister(self):
        if self._registered:
            from . import unregister_counter_provider
            unregister_counter_provider(self.name)
            self._registered = False
