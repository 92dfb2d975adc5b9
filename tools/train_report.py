"""Offline report over an exported training-monitor document.

Reads the JSON `TrainingMonitor.export()` writes (chrome `traceEvents`
plus the `trainingMonitor` side-channel: step ring, snapshot,
compile-event log) and prints:

* a per-step latency digest (count, p50/p90/p99/max, throughput from
  the token counter);
* a loss / grad-norm trajectory digest (first/last/min/max, NaN'd and
  retraced steps called out — the postmortem view of the ring);
* the compile-event timeline (every trace/retrace/AST rescue/eager
  fallback/program compile with its duration, plus per-kind totals —
  a compile storm reads as a table, not a debugger hunt).

Deliberately stdlib-only: loading this module must never import jax
(a process that starts a jax backend may take the chip), so the
report runs anywhere, including while a trainer holds the chip. The
`--demo` flag is the one exception: it lazily imports paddle_tpu to run
a tiny monitored CPU training loop and write the artifact it then
reports on (`make train-report` smokes exactly that under the
CPU-pinned test env).

Usage:  python tools/train_report.py TRACE.json [--worst 3]
        python tools/train_report.py --demo TRACE.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional


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
    if isinstance(data, dict) and "trainingMonitor" in data:
        return data["trainingMonitor"]
    # a bare snapshot/records dump is accepted too
    return data if isinstance(data, dict) else {"records": data}


# ------------------------------------------------------------- latency
def format_latency(records: List[dict], snapshot: dict) -> str:
    lat = [r["dur_ms"] for r in records
           if isinstance(r.get("dur_ms"), (int, float))]
    lines = [f"steps recorded: {len(records)} "
             f"(#{records[0]['step']}..#{records[-1]['step']}, "
             f"{snapshot.get('steps', '?')} total)"] if records else \
        ["(empty step ring)"]
    if lat:
        lines.append(
            f"  step latency ms: p50 {_percentile(lat, 50):.3f}  "
            f"p90 {_percentile(lat, 90):.3f}  "
            f"p99 {_percentile(lat, 99):.3f}  max {max(lat):.3f}")
        tokens = [r["tokens"] for r in records
                  if isinstance(r.get("tokens"), int) and r.get("dur_ms")]
        if tokens and sum(lat) > 0:
            tps = sum(tokens) / (sum(lat) / 1e3)
            lines.append(f"  throughput: {tps:.1f} tokens/s over the ring")
    return "\n".join(lines)


def format_worst_steps(records: List[dict], n: int = 3) -> str:
    timed = [r for r in records
             if isinstance(r.get("dur_ms"), (int, float))]
    timed.sort(key=lambda r: r["dur_ms"], reverse=True)
    lines = []
    for r in timed[:n]:
        extra = ""
        if r.get("compile_events"):
            extra += "  compile=" + ",".join(
                f"{k}x{v}" for k, v in sorted(r["compile_events"].items()))
        if r.get("nan_hits"):
            extra += f"  NAN_HITS={r['nan_hits']}"
        lines.append(f"  step #{r['step']:<6} {r['dur_ms']:10.3f} ms  "
                     f"loss={_fmt(r.get('loss'))}{extra}")
    return "\n".join(lines) if lines else "  (no timed steps)"


# ---------------------------------------------------------- trajectory
def _fmt(v) -> str:
    if v is None:
        return "-"
    if v != v:                          # NaN
        return "NaN"
    return f"{v:.6g}"


def format_trajectory(records: List[dict], snapshot: dict) -> str:
    lines = []
    for key in ("loss", "grad_norm"):
        vals = [(r["step"], r[key]) for r in records
                if isinstance(r.get(key), (int, float))]
        finite = [(s, v) for s, v in vals if v == v]
        if not vals:
            continue
        row = (f"  {key:<10} first {_fmt(vals[0][1]):>12}  "
               f"last {_fmt(vals[-1][1]):>12}")
        if finite:
            row += (f"  min {_fmt(min(v for _, v in finite)):>12}"
                    f"  max {_fmt(max(v for _, v in finite)):>12}")
        lines.append(row)
        nan_steps = [s for s, v in vals if v != v]
        if nan_steps:
            lines.append(f"      NaN at steps: "
                         f"{' '.join(str(s) for s in nan_steps[:10])}"
                         + (" ..." if len(nan_steps) > 10 else ""))
    retraced = [r["step"] for r in records if r.get("retraced")]
    if retraced:
        lines.append(f"  retraced steps: "
                     f"{' '.join(str(s) for s in retraced[:10])}"
                     + (" ..." if len(retraced) > 10 else ""))
    for k in ("nan_hits", "eager_fallbacks", "retraces"):
        if snapshot.get(k):
            lines.append(f"  ALERT {k} = {snapshot[k]}")
    return "\n".join(lines) if lines else "  (no loss/grad-norm samples)"


# ------------------------------------------------------- compile events
def format_compile_timeline(events: List[dict],
                            counters: Dict[str, int],
                            dropped: int = 0) -> str:
    if not events and not counters:
        return "(no compile events)"
    lines = []
    per_kind: Dict[str, List[float]] = {}
    for e in events:
        per_kind.setdefault(e["kind"], []).append(
            float(e.get("duration_ms") or 0.0))
    lines.append(f"{'kind':<18}{'count':>8}{'logged':>8}{'total(ms)':>12}")
    lines.append("-" * len(lines[0]))
    for kind in sorted(set(counters) | set(per_kind)):
        durs = per_kind.get(kind, [])
        lines.append(f"{kind:<18}{counters.get(kind, 0):>8}"
                     f"{len(durs):>8}{sum(durs):>12.3f}")
    if dropped:
        lines.append(f"(+{dropped} events aged out of the window)")
    t0 = events[0]["t_wall"] if events else 0.0
    for e in events[-20:]:
        dur = (f" {e['duration_ms']:.1f} ms"
               if e.get("duration_ms") is not None else "")
        det = e.get("detail") or {}
        det_s = " ".join(f"{k}={v}" for k, v in det.items())
        lines.append(f"  +{e['t_wall'] - t0:9.3f}s {e['kind']:<16} "
                     f"{e['name']}{dur}  {det_s}".rstrip())
    if len(events) > 20:
        lines.insert(len(lines) - 20,
                     f"  (last 20 of {len(events)} retained events)")
    return "\n".join(lines)


def report(data: dict, worst: int = 3) -> str:
    records = data.get("records") or []
    snapshot = data.get("snapshot") or {}
    parts = ["== step latency ==", format_latency(records, snapshot)]
    parts += [f"== worst {worst} steps ==", format_worst_steps(records, worst)]
    parts += ["== trajectory ==", format_trajectory(records, snapshot)]
    parts += ["== compile events ==",
              format_compile_timeline(
                  data.get("compile_events") or [],
                  data.get("compile_counters") or {},
                  snapshot.get("compile_events_dropped", 0))]
    return "\n".join(parts)


# ------------------------------------------------------------------ demo
def run_demo(path: str) -> None:
    """Tiny monitored CPU training loop -> export artifact at `path`.
    The ONLY jax-importing entry point of this file (opt-in via --demo;
    the make target runs it under the CPU-pinned env)."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.profiler import TrainingMonitor

    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(64, 64), paddle.nn.ReLU(),
                               paddle.nn.Linear(64, 8))
    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=1e-3)

    def train_step(x):
        y = net(x)
        loss = (y * y).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step, state_objects=[net, opt])
    rng = np.random.RandomState(0)
    with TrainingMonitor(optimizer=opt, detailed=True).watch(step) as mon:
        for i in range(12):
            # vary the batch once mid-run so the demo shows a retrace
            b = 8 if i < 8 else 16
            x = paddle.to_tensor(rng.rand(b, 64).astype("f"))
            mon.step(step(x), tokens=b)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    mon.export(path)
    print(f"demo training trace written to {path}")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="exported TrainingMonitor JSON")
    ap.add_argument("--worst", type=int, default=3,
                    help="how many slowest steps to break down")
    ap.add_argument("--demo", action="store_true",
                    help="run a tiny monitored training loop first and "
                         "write the artifact to PATH (imports paddle_tpu)")
    args = ap.parse_args(argv)
    if args.demo:
        run_demo(args.path)
    print(report(load(args.path), worst=args.worst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
