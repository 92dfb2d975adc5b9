"""Compile-event log: every compilation-shaped event, timestamped (ISSUE 11).

The training stack compiles in four places — `to_static` guard misses
(trace/retrace), dy2static AST rescues, eager-fallback guards, and the
serving `ProgramCache` — and until this module the only way to see a
compile storm was to diff `to_static_report()` between two points in
time. Here every such event lands in ONE bounded, stdlib-only log:

* `log_event(kind, name, duration_s, detail)` — called by jit/api.py
  (kinds `trace` / `retrace` / `ast_convert` / `eager_fallback`) and
  serving/program_cache.py (kind `program_compile`); `duration_s` is
  the wall time the event cost (for a trace: the first call's
  trace+compile+execute wall).
* the ring is bounded (`MAX_EVENTS`, oldest dropped and counted) and
  per-kind counters + duration totals are unbounded, so a long-lived
  process keeps an exact *rate* signal even after the window rolls —
  the alertable "compile storm" number is the counter delta per step,
  which `TrainingMonitor` records.

Consumers: `jit.to_static_report()` (the SOT-gap inventory gains the
compile timeline), `profiler.TrainingMonitor` (per-step event deltas +
Prometheus counters), `tools/train_report.py` (offline timeline).

Deliberately stdlib-only and jax-free: importing this module must never
initialize a jax backend (one process per chip), and the serving ProgramCache logs
through it from inside engine hot paths.
"""
from __future__ import annotations

import threading
import time
from collections import Counter, deque
from typing import Dict, List, Optional

__all__ = ["log_event", "events", "counters", "duration_totals_s",
           "dropped", "reset", "generation", "KINDS", "MAX_EVENTS"]

# the closed vocabulary — consumers (train_report, monitor) render any
# kind they meet, but these are the ones the tree emits
KINDS = ("trace", "retrace", "ast_convert", "eager_fallback",
         "program_compile")

MAX_EVENTS = 512

_lock = threading.Lock()
_events: deque = deque(maxlen=MAX_EVENTS)
_counts: Counter = Counter()
_dur_totals: Dict[str, float] = {}
_dropped = [0]
_generation = [0]


def log_event(kind: str, name: str = "", duration_s: Optional[float] = None,
              detail: Optional[dict] = None):
    """Record one compile-shaped event. `name` identifies the function /
    program family; `detail` must be a small JSON-safe dict (guard-cache
    size, program key, error class — NOT tensors or tracebacks)."""
    rec = {"kind": str(kind), "name": str(name),
           # wall-clock epoch for cross-process correlation AND the
           # perf_counter ns the profiler/tracer clocks use, so the
           # event can be placed on a merged chrome trace
           "t_wall": time.time(),
           "ts_ns": time.perf_counter_ns()}
    if duration_s is not None:
        rec["duration_ms"] = round(float(duration_s) * 1e3, 3)
    if detail:
        rec["detail"] = dict(detail)
    with _lock:
        if len(_events) == _events.maxlen:
            _dropped[0] += 1
        _events.append(rec)
        _counts[rec["kind"]] += 1
        if duration_s is not None:
            _dur_totals[rec["kind"]] = (
                _dur_totals.get(rec["kind"], 0.0) + float(duration_s))
    return rec


def events() -> List[dict]:
    """The retained events, oldest first (copies — safe to mutate)."""
    with _lock:
        return [dict(r) for r in _events]


def counters() -> Dict[str, int]:
    """{kind: total events ever logged} — exact even after the ring
    rolled; the monitor's per-step deltas come from here."""
    with _lock:
        return dict(_counts)


def duration_totals_s() -> Dict[str, float]:
    """{kind: total seconds spent} over events that carried a duration."""
    with _lock:
        return dict(_dur_totals)


def dropped() -> int:
    """Events aged out of the bounded window."""
    return _dropped[0]


def generation() -> int:
    """Bumped by every reset() — delta consumers (TrainingMonitor)
    re-baseline on a generation change, so a mid-run
    `to_static_report(reset=True)` can never produce negative or
    silently-swallowed per-step deltas."""
    return _generation[0]


def reset():
    with _lock:
        _events.clear()
        _counts.clear()
        _dur_totals.clear()
        _dropped[0] = 0
        _generation[0] += 1
