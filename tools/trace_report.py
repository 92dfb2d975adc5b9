"""Offline report over an exported serving trace / flight recorder.

Reads the JSON `RequestTracer.export()` writes (chrome `traceEvents`
plus the `requestTraces` / `flightRecorder` side-channels) — or a bare
engine snapshot / `engine.timeline()` dump carrying only a flight
recorder — and prints:

* a per-phase latency table (queue_wait / prefill_chunk / decode_step /
  verify_step / migration park->adopt / total request lifetime, with
  count, p50, p99, total);
* the slowest requests' span-by-span breakdown;
* a flight-recorder digest (step latency percentiles, occupancy range,
  program-launch counts per family, fault/retry totals).

Deliberately stdlib-only: loading this module must never import jax
(a process that starts a jax backend may take the chip), so the
report runs anywhere, including while an engine holds the chip.

Usage:  python tools/trace_report.py TRACE.json [--slowest 3]
(`make soak` runs it over the soak's exported trace as a smoke.)
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional

# phases reported in table order; "migration" and "total" are derived
PHASES = ("queue_wait", "prefill_chunk", "decode_step", "verify_step")


def _percentile(samples, q):
    """Nearest-rank percentile (the serving.metrics rule, duplicated so
    this tool stays import-free)."""
    if not samples:
        return None
    s = sorted(samples)
    idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return s[idx]


def load(path: str) -> dict:
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, list):
        # a bare engine.timeline() dump
        return {"flightRecorder": data}
    if "flight_recorder" in data and "requestTraces" not in data:
        # an engine snapshot: the recorder rides under its snapshot key
        return {"flightRecorder": data["flight_recorder"]}
    return data


# --------------------------------------------------------------- phases
def phase_durations_ms(traces: List[dict]) -> Dict[str, List[float]]:
    """{phase: [durations ms]} over every request trace, including the
    derived `migration` (park -> adopt gap) and `total` phases."""
    out: Dict[str, List[float]] = {p: [] for p in PHASES}
    out["migration"] = []
    out["total"] = []
    for tr in traces:
        for s in tr.get("spans", ()):
            out.setdefault(s["name"], []).append(
                (s["t1"] - s["t0"]) / 1e6)
        park = None
        for m in tr.get("marks", ()):
            if m["name"] == "park":
                park = m["t"]
            elif m["name"] == "adopt" and park is not None:
                out["migration"].append((m["t"] - park) / 1e6)
                park = None
        if tr.get("t_end") is not None:
            out["total"].append((tr["t_end"] - tr["t_begin"]) / 1e6)
    return out


def format_phase_table(traces: List[dict]) -> str:
    durs = phase_durations_ms(traces)
    lines = [f"{'phase':<16}{'count':>8}{'p50(ms)':>12}{'p99(ms)':>12}"
             f"{'total(ms)':>12}"]
    lines.append("-" * len(lines[0]))
    order = list(PHASES) + ["migration", "total"]
    order += sorted(k for k in durs if k not in order)
    for phase in order:
        samples = durs.get(phase, ())
        if not samples:
            continue
        lines.append(
            f"{phase:<16}{len(samples):>8}"
            f"{_percentile(samples, 50):>12.3f}"
            f"{_percentile(samples, 99):>12.3f}"
            f"{sum(samples):>12.3f}")
    return "\n".join(lines)


def format_slowest(traces: List[dict], n: int = 3) -> str:
    done = [t for t in traces if t.get("t_end") is not None]
    done.sort(key=lambda t: t["t_end"] - t["t_begin"], reverse=True)
    lines = []
    for tr in done[:n]:
        total = (tr["t_end"] - tr["t_begin"]) / 1e6
        lines.append(f"request {tr['request_id']} "
                     f"({tr.get('finish_reason')}): {total:.3f} ms, "
                     f"{len(tr.get('spans', ()))} spans")
        by_name: Dict[str, List[float]] = {}
        for s in tr.get("spans", ()):
            by_name.setdefault(s["name"], []).append(
                (s["t1"] - s["t0"]) / 1e6)
        for name, ds in sorted(by_name.items(),
                               key=lambda kv: -sum(kv[1])):
            lines.append(f"    {name:<16} x{len(ds):<4} "
                         f"total {sum(ds):10.3f} ms  "
                         f"max {max(ds):8.3f} ms")
        marks = [m["name"] for m in tr.get("marks", ())]
        if marks:
            lines.append(f"    marks: {' '.join(marks)}")
    return "\n".join(lines) if lines else "(no completed traces)"


# ------------------------------------------------------ flight recorder
def format_flight_recorder(records: List[dict]) -> str:
    if not records:
        return "(empty flight recorder)"
    lat = [r["t_wall_ms"] for r in records
           if isinstance(r.get("t_wall_ms"), (int, float))]
    occ = [r["kv_occupancy"] for r in records if "kv_occupancy" in r]
    fams: Dict[str, int] = {}
    for r in records:
        for p in r.get("programs", ()):
            fam = str(p).split(":", 1)[0]
            fams[fam] = fams.get(fam, 0) + 1
    totals = {k: sum(int(r.get(k, 0) or 0) for r in records)
              for k in ("tokens_out", "prefill_tokens", "retries",
                        "quarantined", "preempted", "prefix_hits",
                        "spec_drafted", "spec_accepted")}
    lines = [f"flight recorder: {len(records)} steps "
             f"(#{records[0].get('step')}..#{records[-1].get('step')})"]
    if lat:
        lines.append(
            f"  step latency ms: p50 {_percentile(lat, 50):.3f}  "
            f"p99 {_percentile(lat, 99):.3f}  max {max(lat):.3f}")
    if occ:
        lines.append(f"  kv occupancy: min {min(occ):.4f}  "
                     f"max {max(occ):.4f}")
    lines.append("  launches: " + (" ".join(
        f"{k}={v}" for k, v in sorted(fams.items())) or "(none)"))
    lines.append("  totals:   " + " ".join(
        f"{k}={v}" for k, v in totals.items() if v))
    failed = [r for r in records if r.get("failed")]
    for r in failed:
        lines.append(f"  FAILED step #{r.get('step')}: {r['failed']}")
    return "\n".join(lines)


def report(data: dict, slowest: int = 3) -> str:
    """Compose every section the document carries."""
    parts = []
    traces = data.get("requestTraces")
    if traces:
        parts.append("== per-phase latency ==")
        parts.append(format_phase_table(traces))
        parts.append("")
        parts.append(f"== slowest {slowest} requests ==")
        parts.append(format_slowest(traces, slowest))
    recs = data.get("flightRecorder")
    if recs:
        if parts:
            parts.append("")
        parts.append("== engine flight recorder ==")
        parts.append(format_flight_recorder(recs))
    if not parts:
        parts.append("(no requestTraces or flightRecorder in input)")
    return "\n".join(parts)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="exported trace / flight recorder JSON")
    ap.add_argument("--slowest", type=int, default=3,
                    help="how many slowest requests to break down")
    args = ap.parse_args(argv)
    print(report(load(args.path), slowest=args.slowest))
    return 0


if __name__ == "__main__":
    sys.exit(main())
