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


def run_cell(name, seconds=1.5, seed=3000000019, data=DATA):
    """What run_cell.py does after its look for a chip, on the toy files:
    the command's own `measure`, driver found by name."""
    from benchmarks import run_cell as command
    cell, cfg = common.load_cell(name, data)
    t0 = time.time()
    line, obs = command.measure(cell, cfg, common.CPU_AS, seed=seed,
                                seconds=seconds, trace=0, t_start=t0)
    assert line["correct"] == all(c["value"] <= c["limit"]
                                  for c in line["checks"].values())
    assert list(line)[-1] == "checks" and len(line["checks"]) >= 5
    e2e = {k: (m["value"], m["unit"]) for k, m in line["metrics"].items()}
    return e2e, obs, line["correct"], line["attempted"], line["failed"], \
        line["metrics"]["setup_s"]["value"]


@pytest.mark.parametrize("name", ["toy-train", "toy-train4"])
def test_train_loop(name):
    e2e, obs, correct, attempted, failed, setup_s = run_cell(name)
    assert correct and failed == 0 and attempted > 0
    assert e2e["train_tokens_per_s_chip"][0] > 0
    # set-up is split into its parts, and the reference's seconds are none
    parts = obs["phases"]
    assert set(parts) >= {"import", "model_build", "program_build",
                          "warm_traffic", "check"}
    assert parts["check"] > 0 and setup_s == pytest.approx(
        sum(v for k, v in parts.items() if k != "check"))
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


def test_a_token_altered_where_it_is_produced_comes_out_not_correct(
        monkeypatch):
    """The rest of a run driven with the timed path broken underneath: the
    engine alters the third token of every request as it returns it, and
    the comparison with the plain reference says so."""
    from paddle_tpu.serving import ServingEngine
    real, seen = ServingEngine.step, {}

    def step(self):
        out = []
        for rid, tok in real(self):
            seen[rid] = seen.get(rid, 0) + 1
            out.append((rid, (tok + 1) % 512 if seen[rid] == 3 else tok))
        return out

    monkeypatch.setattr(ServingEngine, "step", step)
    _, obs, correct, attempted, _, _ = run_cell("toy-closed")
    assert attempted > 0 and not correct
    wrong = {k for k, (v, lim) in obs["checks"].items() if not v <= lim}
    assert wrong == {"logit_gap_mean", "logit_gap_widest"}


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
