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

Set-up spans: the program's own set-up, recorded where the
work happens, with exact totals by kind (`setup_totals()`):

* `setup.import` — `import paddle_tpu`, its first statement to its last
  (stamped at the top, recorded at the bottom: the profiler cannot be
  imported before the package, so this one span has no annotation);
* `setup.param_init` — each parameter draw (`nn.initializer._init_tensor`):
  host time only (the draw is dispatched, never synced), with exact
  `leaves` and `bytes` counters;
* `setup.program_build` — one program's first call: a `ProgramCache`
  program's (metadata `family`, `key`) and a `to_static` entry's traced
  call or retrace (`fn`).

Every span but the import enters a `profiler.RecordEvent` of its name,
so a trace taken over set-up places it beside the device ops. The
compile stages JAX reports (`profiler/__init__.py` registers the
listeners and calls `stage_began` / `stage_ended` / `stage_nested`) are
attributed to the innermost open set-up span of their thread, each
counted by its SELF time, so that nested stages (an inner jit traced
inside its caller's trace, a cache load inside a backend compile) are
not counted twice; stages under no set-up span go to `outside`. Per-leaf
spans never enter the event ring: a program build adds its stage split to
the `detail` of the one event it logged before. `counters()` and
`duration_totals_s()` keep the event kinds alone, and `reset()` leaves
the set-up totals (the import they start with happens once a process).

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
           "dropped", "reset", "generation", "KINDS", "MAX_EVENTS",
           "setup_span", "record_span", "setup_totals", "SETUP_KINDS",
           "STAGES"]

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


# ------------------------------------------------------------ set-up spans
SETUP_KINDS = ("setup.import", "setup.param_init", "setup.program_build")
STAGES = ("trace", "lower", "compile", "cache_load")

_local = threading.local()         # .spans: open set-up spans, innermost
#                                    last; .stages: open compile stages
_setup: Dict[str, dict] = {}       # kind -> exact totals
_outside = dict.fromkeys(STAGES, 0.0)
_annotation = [None]               # RecordEvent, once the profiler is in


def _open(attr: str) -> list:
    stack = getattr(_local, attr, None)
    if stack is None:
        stack = []
        setattr(_local, attr, stack)
    return stack


class SetupSpan:
    """One open set-up span (`setup_span()`); a context manager. `counts`
    holds numbers summed into its kind's totals; `stages` the compile
    stages' seconds attributed to it so far."""

    __slots__ = ("kind", "counts", "stages", "n_stages", "child_s", "t0",
                 "_ann")

    def __init__(self, kind: str, meta: dict):
        self.kind = kind
        self.counts: Dict[str, float] = {}
        self.stages = dict.fromkeys(STAGES, 0.0)
        self.n_stages = 0              # stage events attributed to it
        self.child_s = 0.0             # seconds of set-up spans inside it
        self._ann = None
        if _annotation[0] is not None:
            self._ann = _annotation[0](kind, **meta)
            self._ann.__enter__()
        _open("spans").append(self)
        self.t0 = time.perf_counter()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def close(self, keep: bool = True):
        """Ends the span. `keep=False` records nothing of it: its stages
        and inner spans are handed to the span it was opened under."""
        t1 = time.perf_counter()
        spans = _open("spans")
        if self in spans:
            spans.remove(self)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        parent = spans[-1] if spans else None
        if not keep:
            for stage, s in self.stages.items():
                if s:
                    _add_stage(parent, stage, s)
            if parent is not None:
                parent.child_s += self.child_s
            return
        seconds = t1 - self.t0
        if parent is not None:
            parent.child_s += seconds
        with _lock:
            tot = _total(self.kind, seconds, max(seconds - self.child_s, 0.0))
            for stage, s in self.stages.items():
                tot["stages"][stage] += s
            for k, v in self.counts.items():
                tot[k] = tot.get(k, 0) + v


def setup_span(kind: str, **meta) -> SetupSpan:
    """Opens a set-up span of `kind` (one of SETUP_KINDS) on this thread;
    `meta` (small str/int values) rides on its annotation."""
    return SetupSpan(kind, meta)


def record_span(kind: str, t0: float, t1: float):
    """Records a set-up span that has already ended, from its
    `time.perf_counter()` stamps: the import's, which starts before this
    module can be imported."""
    with _lock:
        _total(kind, t1 - t0, t1 - t0)


def _total(kind: str, seconds: float, self_seconds: float) -> dict:
    """Adds one span to its kind's totals (under _lock); returns them."""
    tot = _setup.get(kind)
    if tot is None:
        tot = _setup[kind] = {"count": 0, "seconds": 0.0,
                              "self_seconds": 0.0,
                              "stages": dict.fromkeys(STAGES, 0.0)}
    tot["count"] += 1
    tot["seconds"] += seconds
    tot["self_seconds"] += self_seconds
    return tot


def set_annotation(factory):
    """`factory(name, **meta)` gives the context manager each span enters
    (the profiler registers its `RecordEvent`)."""
    _annotation[0] = factory


def _add_stage(span, stage: str, seconds: float):
    if span is not None:
        span.stages[stage] += seconds
        span.n_stages += 1
    else:
        with _lock:
            _outside[stage] += seconds


def _here():
    spans = getattr(_local, "spans", None)
    return spans[-1] if spans else None


def stage_began(stage: str, start: float):
    """A compile stage began on this thread at `start` (the clock of the
    caller's choice, the same for its `stage_ended`)."""
    _open("stages").append([stage, start, 0.0])


def stage_ended(stage: str, start: float, end: float):
    """A compile stage ended: its SELF time (less the stages that ran
    inside it) is attributed to the innermost open set-up span."""
    open_stages = _open("stages")
    inner = 0.0
    for i in range(len(open_stages) - 1, -1, -1):
        if open_stages[i][0] == stage and open_stages[i][1] == start:
            inner = open_stages[i][2]
            del open_stages[i:]
            break
    elapsed = max(end - start, 0.0)
    if open_stages:
        open_stages[-1][2] += elapsed
    _add_stage(_here(), stage, max(elapsed - inner, 0.0))


def stage_nested(stage: str, seconds: float):
    """A stage reported by its duration alone, inside the open stage (a
    cache load inside a backend compile): taken out of that one."""
    open_stages = _open("stages")
    if open_stages:
        open_stages[-1][2] += seconds
    _add_stage(_here(), stage, seconds)


def setup_totals() -> Dict[str, dict]:
    """{kind: {"count", "seconds", "self_seconds", "stages": {stage:
    seconds}, and the kind's own counts}} over the process, exact, plus
    "outside": {"stages": ...} for the stages under no set-up span."""
    with _lock:
        out = {k: dict(v, stages=dict(v["stages"])) for k, v in _setup.items()}
        out["outside"] = {"stages": dict(_outside)}
    return out
