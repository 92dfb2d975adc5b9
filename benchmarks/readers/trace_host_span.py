"""The program's own spans in the traced slice: `profiler.RecordEvent`
annotations (`serving.*` of the engine's step, `to_static.*` of a compiled
call), which the profiler writes on the clock of the device ops. Two
statistics (`stat`):

  ms_per    total seconds of the span(s) named in `span` (a name or a
            list) over the count of the span named in `per`, in ms: host
            time a step or a call
  idle_pct  the seconds the device was idle WHILE `span` (a name or a
            list) was the innermost program span on the driving thread,
            over the slice, in %. Idle is the complement of the union of
            the device ops, as `reduce_trace.reduce` takes it, so the
            shares of all labels add up to `trace_idle`'s. A gap is split
            among the spans by overlap. `span: null` reads the idle time
            outside every program span (the driver's own admitting and
            stamping).

The slice is the driver's `bench.slice` span; its `.xplane.pb` is found
where `observe.Tracer` put it and loaded once a run. A trace may give an
annotation's metadata inside its name (`serving.step#step=12#`): the part
before the `#` is the span's name. One thread drives the program in every
driver; rows of several threads would have to be told apart before nesting
means anything. None without a trace, without any program span in it (a
program that has none), without the named span, or (idle_pct) without a
device plane.
"""
from __future__ import annotations

import os

from benchmarks.harness import reduce_trace
from benchmarks.harness.common import REPO

PROGRAM = ("serving.", "to_static.")
_SUMMARIES: dict = {}          # xplane path -> summarize(rows), once a run


def base_name(event_name: str) -> str:
    return event_name.split("#", 1)[0]


def innermost(spans, lo: float, hi: float):
    """[(t0, t1, name)] covering [lo, hi) in order: `name` is the innermost
    of the properly nested `spans` [(name, start, dur)] there, else None."""
    out, stack, t = [], [], lo

    def upto(x):
        nonlocal t
        x = min(max(x, lo), hi)
        if x > t:
            out.append((t, x, stack[-1][1] if stack else None))
            t = x

    for name, s, d in sorted(spans, key=lambda r: (r[1], -r[2])) \
            + [(None, hi, 0.0)]:
        while stack and stack[-1][0] <= s:
            upto(stack[-1][0])             # the innermost one ends
            stack.pop()
        upto(s)
        stack.append((s + d, name))
    return out


def _gaps(events, lo, hi):
    """The idle intervals of one device plane inside [lo, hi)."""
    busy = reduce_trace._clip(
        reduce_trace.union((s, s + d) for _, s, d in events), lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def summarize(rows: dict) -> dict:
    """{"window_s", "total": {span: seconds inside the slice}, "count":
    {span: spans that start inside it}, "idle": {span or None: idle
    seconds a chip} or None without a device plane}; {} without the
    slice's span or without a program span in it."""
    devs = {k: v for k, v in rows["devices"].items() if v}
    host = [(base_name(n), s, d) for n, s, d in rows["host"]]
    sl = [r for r in host if r[0] == reduce_trace.SLICE_SPAN]
    if not sl:
        return {}
    lo, hi = sl[0][1], sl[0][1] + sl[0][2]
    spans = [r for r in set(host)
             if r[0].startswith(PROGRAM) and r[1] < hi and r[1] + r[2] > lo]
    if not spans:
        return {}
    total, count = {}, {}
    for name, s, d in spans:
        total[name] = total.get(name, 0.0) + min(s + d, hi) - max(s, lo)
        count[name] = count.get(name, 0) + (lo <= s < hi)
    idle = None
    if devs:
        idle, segs = {}, innermost(spans, lo, hi)
        for events in devs.values():
            i = 0
            for g0, g1 in _gaps(events, lo, hi):
                while segs[i][1] <= g0:
                    i += 1
                j = i
                while j < len(segs) and segs[j][0] < g1:
                    t0, t1, name = segs[j]
                    idle[name] = idle.get(name, 0.0) \
                        + min(t1, g1) - max(t0, g0)
                    j += 1
        idle = {k: v / len(devs) for k, v in idle.items()}
    return {"window_s": hi - lo, "total": total, "count": count,
            "idle": idle}


def slice_summary(obs) -> dict:
    path = reduce_trace.find_xplane(os.path.join(
        REPO, ".bench_trace", obs["cell"]["name"], "slice"))
    if path is None:
        return {}
    if path not in _SUMMARIES:
        _SUMMARIES[path] = summarize(reduce_trace.load_rows(
            path, host_prefix=("bench.",) + PROGRAM))
    return _SUMMARIES[path]


def stat(summary: dict, args: dict):
    if not summary:
        return None
    span = args["span"]
    names = [span] if isinstance(span, str) or span is None else span
    if span is not None and not any(x in summary["total"] for x in names):
        return None
    if args["stat"] == "ms_per":
        n = summary["count"].get(args["per"], 0)
        if not n:
            return None
        return 1e3 * sum(summary["total"].get(x, 0.0) for x in names) / n
    if args["stat"] == "idle_pct":
        idle = summary["idle"]
        if idle is None:
            return None
        return 100.0 * sum(idle.get(x, 0.0) for x in names) \
            / summary["window_s"]
    raise ValueError(f"trace_host_span: unknown stat {args['stat']!r}")


def read(obs, args):
    if not obs.get("trace"):
        return None
    return stat(slice_summary(obs), args)
