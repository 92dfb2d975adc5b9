"""From the profiler's trace (`*.xplane.pb`) to numbers. Two stages, so
that the second can be checked on a small recorded table:

  load_rows(path)  planes -> {"devices": {plane: [(name, start_s, dur_s)]},
                              "host": [(name, start_s, dur_s)]}
  reduce(rows)     -> window, busy seconds, time by op, idle gaps by the
                      host span that covered them

A device plane is one named `/device:TPU:<n>` (or any `/device:` plane
that is not a host); its `XLA Ops` line holds one event per executed HLO
op, named by the instruction's text in the compiled program. Host rows are the
driver's own `bench.*` TraceAnnotations, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
SLICE_SPAN = "bench.slice"


def find_xplane(trace_dir: str):
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


_OP = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")


def op_name(event_name: str) -> str:
    """The trace names a device op by its whole HLO instruction, operands
    and all (kilobytes for a kernel call). Kept: the instruction's name and
    the shape of its (first) result, e.g. `fusion.351 bf16[14336,4096]`."""
    m = _OP.match(event_name)
    if not m:
        return event_name[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def load_rows(path: str, host_prefix: str = "bench.") -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host, lines_seen = {}, [], {}
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            lines_seen.setdefault(plane.name, []).append(line.name)
            if is_dev and line.name == OPS_LINE:
                devices[plane.name] = [
                    (op_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events]
            elif not is_dev:
                host += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                         for e in line.events
                         if e.name.startswith(host_prefix)]
    return {"devices": devices, "host": sorted(host, key=lambda r: r[1]),
            "lines": lines_seen}


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce(rows: dict, top: int = 10) -> dict:
    """Busy and idle over the traced slice. The slice is the host span
    `bench.slice` where the trace has it, else the extent of the device
    ops. Busy is the union of the device-op intervals inside it, averaged
    over the device planes; time by op sums durations by name (an op that
    encloses others, such as a `while`, is counted beside them: read the
    list as "where the time is", not as a partition). Every idle gap goes
    to the shortest `bench.*` host span that covers its middle."""
    devs = {k: v for k, v in rows["devices"].items() if v}
    if not devs:
        return {}
    host = [r for r in rows["host"] if r[0] != SLICE_SPAN]
    sl = [r for r in rows["host"] if r[0] == SLICE_SPAN]
    if sl:
        lo, hi = sl[0][1], sl[0][1] + sl[0][2]
    else:
        lo = min(r[1] for v in devs.values() for r in v)
        hi = max(r[1] + r[2] for v in devs.values() for r in v)
    busy, ops, gaps = 0.0, {}, {}
    for events in devs.values():
        merged = _clip(union((s, s + d) for _, s, d in events), lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, s, d in events:
            if lo <= s < hi:
                ops[name] = ops.get(name, 0.0) + d
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            cover = [r for r in host if r[1] <= mid < r[1] + r[2]]
            name = (min(cover, key=lambda r: r[2])[0] if cover
                    else "(no bench span)")
            gaps[name] = gaps.get(name, 0.0) + (g1 - g0)
    n = len(devs)
    rank = lambda d: sorted(([k, v / n] for k, v in d.items()),
                            key=lambda kv: -kv[1])
    return {"window_s": hi - lo, "busy_s": busy / n, "n_devices": n,
            "ops": {k: v / n for k, v in ops.items()},
            "device_ops": rank(ops)[:top], "idle_gaps": rank(gaps)[:top],
            "slice_from_host_span": bool(sl)}


def op_time(reduced: dict, pattern: str) -> float:
    """Seconds (a chip) of the device ops whose name matches `pattern`."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced.get("ops", {}).items() if rx.search(k))
