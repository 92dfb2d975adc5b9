"""Every driver rehearsed on the CPU at a toy width, through the drivers'
own functions (the command's TPU guard has no switch). The toy
configuration, cell and metric files under data/ are taken by the harness
as they are: adding one needed no edit to a file that was there."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks.harness import common, observe

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REPO = common.REPO


def run_cell(name, seconds=1.5, seed=3000000019):
    """What run_cell.py does after its guard, on the toy files."""
    cell, cfg = common.load_cell(name, DATA)
    cache, spans = common.CacheCounter(), observe.Spans()
    phases = common.Phases(time.time())
    mod = "train_loop" if cell["driver"] == "train_loop" else "serve_loop"
    run = __import__(f"benchmarks.harness.{mod}", fromlist=["run"]).run
    e2e, obs, correct, attempted, failed = run(
        cfg, cell, seed=seed, seconds=seconds, cache=cache, phases=phases,
        spans=spans)
    obs.update(cfg=cfg, cell=cell, device={"kind": "TPU v5 lite"},
               chips=cell["chips"], trace=None)
    return e2e, obs, correct, attempted, failed, phases


@pytest.mark.parametrize("name", ["toy-train", "toy-train4"])
def test_train_loop(name):
    e2e, obs, correct, attempted, failed, phases = run_cell(name)
    assert correct and failed == 0 and attempted > 0
    assert e2e["train_tokens_per_s_chip"][0] > 0
    assert set(phases.parts) >= {"model_build", "program_build",
                                 "warm_traffic", "check"}
    got = observe.read_metrics(obs)          # the real metric files
    assert {"train_enqueue_ms", "train_compile_s", "train_mfu"} <= set(got)
    # nothing compiled inside the window
    assert obs["counters"]["window"]["to_static"]["compile_events"] == 0


@pytest.mark.parametrize("name", ["toy-closed", "toy-open"])
def test_serve_loop(name):
    e2e, obs, correct, attempted, failed, _ = run_cell(name)
    assert correct and failed == 0 and attempted > 0
    assert e2e["serve_tokens_per_s"][0] > 0 and e2e["itl_p95_ms"][0] > 0
    assert ("ttft_p95_ms" in e2e) == (name == "toy-open")
    got = observe.read_metrics(obs)
    assert {"serve_step_ms", "batch_occupancy", "preemptions",
            "prefill_step_share", "serve_program_build_s"} <= set(got)
    assert obs["counters"]["window"]["programs"]["count"] == 0
    assert obs["values"]["admit_late_p95_ms"] >= 0.0
    # a metric over a counter is data: the toy file is found by name
    toy = observe.read_metrics(obs, DATA)
    assert ("toy_requests_added" in toy) == (name == "toy-open")
    if name == "toy-open":
        # admitted in the window ~ due in the window (a late admission at
        # either edge moves one or two across it)
        assert abs(toy["toy_requests_added"]["value"] - attempted) <= 5


def test_trace_metrics_are_left_out_without_a_trace():
    cell, cfg = common.load_cell("toy-train", DATA)
    obs = {"values": {}, "spans": {}, "counters": {"window": {}, "process": {}},
           "cfg": cfg, "cell": cell, "device": {"kind": "TPU v5 lite"},
           "chips": 1, "trace": None}
    assert observe.read_metrics(obs) == {}


def test_command_refuses_without_a_tpu():
    """On the CPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run_cell.py"),
         "--workload", "train-pretrain-2k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, cwd=REPO)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    for line in p.stdout.splitlines():
        assert not line.startswith("{"), line
