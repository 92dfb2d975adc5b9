"""Pipeline timeline export + cross-rank merge report (ISSUE 12).

The threaded executors already MEASURE makespans (VERDICT r3); these
tests pin the export contract on top: chrome-trace spans must reproduce
the executor's reported makespan exactly (one track per rank, F/B/W
spans on the shared perf_counter clock), the measured bubble fraction
must agree with `simulate_pipeline_makespan` fed the measured durations
(the BENCH_PIPELINE methodology), per-rank export files must carry only
their own rank's spans plus the shared digests, and the stdlib-only
`tools/dist_report.py` must merge them back into one rank-labelled
trace — flagging (not summing) per-rank comm disagreement.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from paddle_tpu.distributed.fleet_executor import (
    PIPE_PID, ThreadedFleetExecutor, ThreadedZBVExecutor,
    build_zbv_rank_schedules, per_rank_schedule,
    simulate_pipeline_makespan)

import tools.dist_report as dist_report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sleep_jobs(t_f=0.004, t_b=0.004, t_w=0.002):
    def fwd(r, m, x):
        time.sleep(t_f)
        return x

    def bwd(r, m, g):
        time.sleep(t_b)
        return g

    def w(r, m):
        time.sleep(t_w)

    return fwd, bwd, w


def _run_zb(n_stages=2, n_micro=6):
    fwd, bwd, w = _sleep_jobs()
    ex = ThreadedFleetExecutor(n_stages, n_micro, "ZB-H1", fwd, bwd, w)
    mk = ex.run(list(range(n_micro)), list(range(n_micro)))
    assert not ex.errors
    return ex, mk


# -------------------------------------------------------- chrome export
def test_chrome_events_reproduce_makespan_one_track_per_rank():
    n_stages, n_micro = 2, 6
    ex, mk = _run_zb(n_stages, n_micro)
    evs = ex.chrome_events()
    spans = [e for e in evs if e.get("ph") == "X"]
    # every scheduled job exported, one span each
    expected_jobs = sum(len(per_rank_schedule(r, n_stages, n_micro,
                                              "ZB-H1"))
                        for r in range(n_stages))
    assert len(spans) == expected_jobs
    # span extents reproduce the executor's reported makespan (the
    # acceptance criterion; 1e-6 absorbs the us round-trip only)
    lo = min(e["ts"] for e in spans)
    hi = max(e["ts"] + e["dur"] for e in spans)
    assert abs((hi - lo) / 1e6 - mk) < 1e-6
    assert ex.last_makespan == mk
    # one track per rank on the pipeline pid, named
    assert {e["tid"] for e in spans} == set(range(n_stages))
    assert all(e["pid"] == PIPE_PID for e in spans)
    names = [e for e in evs if e.get("ph") == "M"
             and e["name"] == "thread_name"]
    assert {e["tid"] for e in names} == set(range(n_stages))
    # F/B/W all present with micro/stage args
    kinds = {e["args"]["kind"] for e in spans}
    assert kinds == {"F", "B", "W"}
    assert all({"kind", "micro", "stage"} <= set(e["args"]) for e in spans)


def test_bubble_fraction_agrees_with_makespan_model():
    """Measured bubble fraction vs the dependency model fed the
    MEASURED durations (the BENCH_PIPELINE methodology). Sleep-based
    jobs on a loaded host jitter, so the agreement band is generous —
    the point is that both sit in the same regime, not timer parity."""
    n_stages, n_micro = 2, 6
    ex, mk = _run_zb(n_stages, n_micro)
    rep = ex.bubble_report()
    assert rep["workers"] == n_stages
    assert rep["jobs"] == {"F": n_stages * n_micro,
                           "B": n_stages * n_micro,
                           "W": n_stages * n_micro}
    assert rep["makespan_s"] == mk
    assert 0.0 <= rep["busy_s"] <= rep["workers"] * rep["makespan_s"]
    assert 0.0 <= rep["bubble_fraction"] < 1.0
    assert rep["sim_makespan_s"] is not None
    assert 0.0 <= rep["sim_bubble_fraction"] < 1.0
    assert abs(rep["bubble_fraction"] - rep["sim_bubble_fraction"]) \
        < 0.15, rep
    # the sim really is simulate_pipeline_makespan on measured durations
    durs = rep["measured_durations_s"]
    assert rep["sim_makespan_s"] == simulate_pipeline_makespan(
        n_stages, n_micro, "ZB-H1", t_f=durs["F"], t_b=durs["B"],
        t_w=durs["W"])


def test_zbv_executor_exports_and_reports():
    fwd, bwd, w = _sleep_jobs()
    n_ranks, n_micro = 2, 4
    ex = ThreadedZBVExecutor(n_ranks, n_micro, fwd, bwd, w, split_w=True)
    mk = ex.run(list(range(n_micro)), list(range(n_micro)))
    assert not ex.errors
    doc = ex.export_timeline()
    assert doc["pipeline"]["schedule"] == "ZB-V"
    assert doc["pipeline"]["makespan_s"] == mk
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["tid"] for e in spans} == set(range(n_ranks))
    rep = ex.bubble_report()
    durs = rep["measured_durations_s"]
    assert rep["sim_makespan_s"] == build_zbv_rank_schedules(
        n_ranks, n_micro, t_f=durs["F"], t_b=durs["B"], t_w=durs["W"],
        split_w=True)[1]
    assert abs(rep["bubble_fraction"] - rep["sim_bubble_fraction"]) \
        < 0.2, rep


# ------------------------------------------------- per-rank files, merge
def test_export_rank_timelines_and_dist_report_merge(tmp_path, capsys):
    ex, mk = _run_zb()
    comm = {"payload_bytes": 512, "bytes_per_axis": {"x": 512},
            "op_counts": {"all-reduce": 1}}
    paths = ex.export_rank_timelines(str(tmp_path), comm=comm)
    assert [os.path.basename(p) for p in paths] \
        == ["pipeline_rank0.json", "pipeline_rank1.json"]
    total_spans = 0
    for r, p in enumerate(paths):
        with open(p) as f:
            doc = json.load(f)
        assert doc["rank"] == r
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert spans and all(e["tid"] == r for e in spans)
        total_spans += len(spans)
        # the shared digests ride every rank file
        assert doc["pipeline"]["schedule"] == "ZB-H1"
        assert doc["comm"] == comm
    assert total_spans == len(ex.timeline)

    # merge via the stdlib reporter API (what `make dist-report` runs)
    docs = dist_report.load_docs(dist_report.rank_files(str(tmp_path)))
    merged = dist_report.merge_trace(docs)
    mspans = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert len(mspans) == total_spans
    assert {e["tid"] for e in mspans} == {0, 1}
    assert merged["ranks"] == [0, 1]
    # merged span extents still reproduce the measured makespan
    lo = min(e["ts"] for e in mspans)
    hi = max(e["ts"] + e["dur"] for e in mspans)
    assert abs((hi - lo) / 1e6 - mk) < 1e-6
    text = dist_report.report(docs)
    assert "rank exports agree" in text
    assert "bubble" in text
    # ranks of one SPMD program: bytes reported once, never summed
    assert "payload bytes 512" in text

    # a disagreeing rank is FLAGGED, not averaged away
    docs[1]["comm"] = dict(comm, bytes_per_axis={"x": 99})
    assert "DISAGREE" in dist_report.report(docs)


def test_export_rank_timelines_disjoint_across_processes(tmp_path,
                                                         monkeypatch):
    """A launched process at rank k exporting an n-worker view writes
    ranks k*n..k*n+n-1 — two processes sharing PADDLE_TPU_PROFILER_DIR
    never clobber each other's files."""
    import paddle_tpu.distributed.env as dist_env
    ex, _ = _run_zb(n_stages=2, n_micro=4)
    monkeypatch.setattr(dist_env, "get_rank", lambda: 1)
    paths = ex.export_rank_timelines(str(tmp_path))
    assert [os.path.basename(p) for p in paths] \
        == ["pipeline_rank2.json", "pipeline_rank3.json"]
    with open(paths[0]) as f:
        assert json.load(f)["rank"] == 2


def test_cross_host_merge_is_flagged(tmp_path):
    """Exports stamped with different hosts: the merged doc carries the
    host list and the digest WARNS instead of pretending one clock."""
    ex, _ = _run_zb()
    paths = ex.export_rank_timelines(str(tmp_path))
    docs = dist_report.load_docs(paths)
    assert all("host" in d for d in docs)
    assert "WARNING" not in dist_report.report(docs)    # one host: quiet
    docs[1]["host"] = "other-host"
    text = dist_report.report(docs)
    assert "WARNING" in text and "other-host" in text
    merged = dist_report.merge_trace(docs)
    assert len(merged["hosts"]) == 2


def test_dist_report_is_stdlib_only():
    """Importing the reporter must not drag in jax (a process that
    starts a jax backend may take the chip — the tool must run while a
    fleet holds it). The --demo path is the documented exception."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, 'tools'); import dist_report; "
         "assert 'jax' not in sys.modules; "
         "assert 'paddle_tpu' not in sys.modules; print('STDLIB_OK')"],
        cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "STDLIB_OK" in out.stdout


def test_rank_files_sorted_and_missing_dir(tmp_path):
    for r in (10, 2, 0):
        with open(tmp_path / f"pipeline_rank{r}.json", "w") as f:
            json.dump({"rank": r, "traceEvents": []}, f)
    paths = dist_report.rank_files(str(tmp_path))
    assert [os.path.basename(p) for p in paths] == [
        "pipeline_rank0.json", "pipeline_rank2.json",
        "pipeline_rank10.json"]
    assert dist_report.rank_files(str(tmp_path / "nope")) == []
    # empty-dir CLI exit is the documented non-zero
    assert dist_report.main([str(tmp_path / "nope")]) == 1
