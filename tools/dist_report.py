"""Merge per-rank pipeline timeline exports into one distributed report.

Cross-process launched jobs (distributed.launch) each write their own
view of the run under $PADDLE_TPU_PROFILER_DIR —
`ThreadedFleetExecutor.export_rank_timelines()` /
`ThreadedZBVExecutor.export_rank_timelines()` produce one
`pipeline_rank<N>.json` chrome-trace per rank, carrying the F/B/W job
spans, the measured-vs-simulated bubble digest, and (optionally) the
program's collective accounting (`TracedFunction.comm_report()`). This
tool merges them into ONE rank-labelled chrome trace (load it in
Perfetto / chrome://tracing) and prints the digest:

* per-rank span counts, busy time and per-kind durations;
* the pipeline bubble table (measured vs `simulate_pipeline_makespan`
  fractions, straight from each export's `pipeline` section);
* the collective-traffic digest (payload bytes per mesh axis; ranks of
  one SPMD program account identical bytes — the digest reports the
  per-rank value and flags disagreement instead of summing it 8x).

Deliberately stdlib-only: loading this module must never import jax
(a process that starts a jax backend may take the chip), so the
report runs anywhere, including while a launched fleet holds the chip.
`--demo` is the one exception: it lazily imports paddle_tpu to run a
tiny threaded ZB pipeline and write real per-rank exports first.

Usage:  python tools/dist_report.py [DIR] [--out MERGED.json]
        python tools/dist_report.py --demo [DIR]
(`make dist-report` runs the demo + merge as a smoke.)
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional


def rank_files(log_dir: str) -> List[str]:
    """The per-rank exports under `log_dir`, rank-sorted."""
    paths = glob.glob(os.path.join(log_dir, "pipeline_rank*.json"))

    def rank_of(p):
        stem = os.path.basename(p)[len("pipeline_rank"):-len(".json")]
        return int(stem) if stem.isdigit() else 1 << 30
    return sorted(paths, key=rank_of)


def load_docs(paths: List[str]) -> List[dict]:
    docs = []
    for p in paths:
        with open(p) as f:
            doc = json.load(f)
        doc.setdefault("rank", len(docs))
        docs.append(doc)
    return docs


def merge_trace(docs: List[dict]) -> dict:
    """One chrome-trace document over every rank's export: span events
    re-labelled tid=GLOBAL rank (the per-rank files of one process
    carry local tids), one thread_name row per rank. Spans were stamped
    on each host's perf_counter — within one host they share a base and
    the merged view is exact; exports carrying more than one distinct
    `host` stamp get a `hosts` list here and a WARNING in the digest
    (per-host clock bases differ; alignment would be fiction)."""
    events: List[dict] = []
    pids = set()
    for doc in docs:
        rank = int(doc.get("rank", 0))
        for e in doc.get("traceEvents", ()):
            if e.get("ph") != "X":
                pids.add(e.get("pid"))
                continue
            ev = dict(e)
            ev["tid"] = rank
            events.append(ev)
    pid = next((p for p in pids if p is not None), 3)
    meta = [{"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": "pipeline ranks (merged)"}}]
    for doc in docs:
        rank = int(doc.get("rank", 0))
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": rank, "args": {"name": f"rank {rank}"}})
    for ev in events:
        ev["pid"] = pid
    merged = {"displayTimeUnit": "ms",
              "traceEvents": meta + sorted(events,
                                           key=lambda e: e["ts"]),
              "ranks": [int(d.get("rank", 0)) for d in docs]}
    hosts = sorted({str(d["host"]) for d in docs if d.get("host")})
    if hosts:
        merged["hosts"] = hosts
    pipelines = [d["pipeline"] for d in docs if "pipeline" in d]
    if pipelines:
        merged["pipeline"] = pipelines[0]
    comms = [d["comm"] for d in docs if "comm" in d]
    if comms:
        merged["comm"] = comms[0]
    return merged


# ---------------------------------------------------------------- digest
def format_rank_table(docs: List[dict]) -> str:
    lines = [f"{'rank':>4}{'spans':>8}{'busy(ms)':>12}{'F':>6}{'B':>6}"
             f"{'W':>6}"]
    lines.append("-" * len(lines[0]))
    for doc in docs:
        spans = [e for e in doc.get("traceEvents", ())
                 if e.get("ph") == "X"]
        busy = sum(e["dur"] for e in spans) / 1e3
        kinds = {"F": 0, "B": 0, "W": 0}
        for e in spans:
            k = e.get("args", {}).get("kind", e.get("name", "?")[:1])
            if k in kinds:
                kinds[k] += 1
        lines.append(f"{doc.get('rank', '?'):>4}{len(spans):>8}"
                     f"{busy:>12.3f}{kinds['F']:>6}{kinds['B']:>6}"
                     f"{kinds['W']:>6}")
    return "\n".join(lines)


def format_bubble(docs: List[dict]) -> str:
    pipes = [d["pipeline"] for d in docs if "pipeline" in d]
    if not pipes:
        return "(no pipeline digest in exports)"
    p = pipes[0]   # every rank file of one run carries the same digest
    lines = [f"schedule {p.get('schedule')}: workers={p.get('workers')} "
             f"jobs={p.get('jobs')}"]
    mk, sim = p.get("makespan_s"), p.get("sim_makespan_s")
    if mk is not None:
        lines.append(f"  measured makespan {mk * 1e3:10.3f} ms   "
                     f"bubble {p.get('bubble_fraction'):.4f}"
                     if p.get("bubble_fraction") is not None
                     else f"  measured makespan {mk * 1e3:10.3f} ms")
    if sim is not None:
        lines.append(f"  modeled  makespan {sim * 1e3:10.3f} ms   "
                     f"bubble {p.get('sim_bubble_fraction'):.4f}  "
                     f"(simulate_pipeline_makespan on measured "
                     f"durations)")
    return "\n".join(lines)


def format_comm(docs: List[dict]) -> str:
    comms = [(int(d.get("rank", 0)), d["comm"]) for d in docs
             if isinstance(d.get("comm"), dict)]
    if not comms:
        return "(no comm accounting in exports)"
    lines = []
    # one SPMD program: every rank should account the SAME bytes
    base = json.dumps(comms[0][1].get("bytes_per_axis"), sort_keys=True)
    agree = all(json.dumps(c.get("bytes_per_axis"), sort_keys=True)
                == base for _, c in comms)
    rank, c = comms[0]
    lines.append(f"payload bytes {c.get('payload_bytes')} "
                 f"per axis {c.get('bytes_per_axis')} "
                 f"ops {c.get('op_counts')}")
    if agree:
        lines.append(f"  ({len(comms)} rank exports agree — one SPMD "
                     f"program, bytes reported once, not summed)")
    else:
        lines.append("  WARNING: rank exports DISAGREE on bytes_per_axis"
                     " (heterogeneous programs?):")
        for rank, c in comms:
            lines.append(f"    rank {rank}: {c.get('bytes_per_axis')}")
    return "\n".join(lines)


def report(docs: List[dict]) -> str:
    parts = []
    hosts = sorted({str(d["host"]) for d in docs if d.get("host")})
    if len(hosts) > 1:
        parts += [f"WARNING: exports span {len(hosts)} hosts "
                  f"({', '.join(hosts)}) — perf_counter bases are "
                  f"per-host, cross-host span alignment in the merged "
                  f"trace is not meaningful", ""]
    parts += ["== per-rank spans ==", format_rank_table(docs), "",
              "== pipeline bubbles ==", format_bubble(docs), "",
              "== collective traffic ==", format_comm(docs)]
    return "\n".join(parts)


# ------------------------------------------------------------------ demo
def run_demo(log_dir: str) -> None:
    """Run a tiny threaded ZB-H1 pipeline and write real per-rank
    exports (with a live comm_report) under `log_dir`. The ONLY
    jax-importing entry point of this file (opt-in via --demo; the
    reporting paths above stay stdlib-only by contract). Stale
    pipeline_rank*.json from earlier runs are cleared first — merging
    exports from two different runs (different clock epochs, possibly
    different rank counts) would produce a chimera digest."""
    import time

    for stale in rank_files(log_dir):
        try:
            os.remove(stale)
        except OSError:
            pass

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # the comm side of the demo needs a multi-device mesh: force the
    # 8-device virtual CPU platform BEFORE jax initializes (the tests'
    # conftest rule) — on one device the honest accounting is 0 bytes
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            flags + " --xla_force_host_platform_device_count=8"
    import numpy as np

    from paddle_tpu.distributed.fleet_executor import ThreadedFleetExecutor

    def fwd(r, m, x):
        time.sleep(0.002)
        return x

    def bwd(r, m, g):
        time.sleep(0.002)
        return g

    def w(r, m):
        time.sleep(0.001)

    ex = ThreadedFleetExecutor(2, 4, "ZB-H1", fwd, bwd, w)
    ex.run(list(range(4)), list(range(4)))

    # a real compiled-program comm accounting to ride the export: the
    # demo matmul psums its loss over the full 8-device mesh
    comm = None
    try:
        import jax
        from paddle_tpu.profiler import comm as _comm
        from paddle_tpu.distributed.fleet import fleet, DistributedStrategy
        st = DistributedStrategy()
        st.hybrid_configs = {"dp_degree": max(len(jax.devices()) // 2, 1),
                             "mp_degree": 2 if len(jax.devices()) >= 2
                             else 1, "pp_degree": 1, "sharding_degree": 1,
                             "sep_degree": 1}
        fleet._hcg = None
        fleet.init(is_collective=True, strategy=st)
        mesh = fleet.get_hybrid_communicate_group().mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        def loss(a):
            a = jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P("data", "model")))
            return a.sum()

        comm = _comm.jit_comm(
            loss, jax.ShapeDtypeStruct((8, 16), np.float32),
            mesh=mesh).to_dict()
    except Exception as e:                                 # noqa: BLE001
        print(f"(demo comm accounting unavailable: {e})")
    paths = ex.export_rank_timelines(log_dir, comm=comm)
    print(f"demo pipeline exports written: {paths}")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir", nargs="?", default=None,
                    help="directory of pipeline_rank*.json exports "
                         "(default: $PADDLE_TPU_PROFILER_DIR or "
                         "./profiler_log)")
    ap.add_argument("--out", default=None,
                    help="write the merged chrome trace here")
    ap.add_argument("--demo", action="store_true",
                    help="first run a tiny threaded pipeline and write "
                         "per-rank exports (imports paddle_tpu)")
    args = ap.parse_args(argv)
    log_dir = args.dir or os.environ.get("PADDLE_TPU_PROFILER_DIR") \
        or "./profiler_log"
    if args.demo:
        run_demo(log_dir)
    paths = rank_files(log_dir)
    if not paths:
        print(f"no pipeline_rank*.json exports under {log_dir}")
        return 1
    docs = load_docs(paths)
    print(f"merging {len(paths)} rank exports from {log_dir}")
    print(report(docs))
    if args.out:
        merged = merge_trace(docs)
        with open(args.out, "w") as f:
            json.dump(merged, f)
        print(f"merged chrome trace written to {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
