"""The three lookups by name (harness/lookup.py), and the seam they make:
a second family, a new driver kind, a configuration, a cell and two
metrics, one with its own work function, laid into a COPY of the
benchmark's tree as new files only (what a later PR does) and run there
through the command's own dispatch on the CPU, in a process of its own."""
import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import common, lookup, observe, work

BENCH = common.BENCH_DIR
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

FAMILY = '''
"""A second family for the test: the Llama toy underneath (the lookup is
what is tested, not a model), its own reference, limits and work count
under its own file, and NO serve_flops_per_token."""
from benchmarks.families import llama

CALLS = []
config, build_model = llama.config, llama.build_model
loss, loss_tolerance, logits = llama.loss, llama.loss_tolerance, llama.logits
load_weights = llama.load_weights


def reference_weights(*a):
    CALLS.append("reference_weights")
    return llama.reference_weights(*a)


def token_gaps(*a, **kw):
    CALLS.append("token_gaps")
    return llama.token_gaps(*a, **kw)


def gap_limits(cfg):
    CALLS.append("gap_limits")
    return {k: 2.0 * v for k, v in llama.gap_limits(cfg).items()}


def toy_keys_per_token(cfg, cell, values):
    CALLS.append("toy_keys_per_token")
    return 1e9 * values["mean_context_tokens"]
'''

DRIVER = '''
"""A new driver kind for the test, over the serving driver."""
from benchmarks.harness import serve_loop

CALLS = []


def run(cfg, cell, **kw):
    CALLS.append(cell["name"])
    return serve_loop.run(cfg, cell, **kw)
'''

# what the copy's own process does: the command's `measure` on the new
# cell, then the look-ups with the second family beside the first
SCRIPT = '''
import json, sys, time
from benchmarks import run_cell as command
from benchmarks.families import llama
from benchmarks.harness import common, lookup, observe, work

data = sys.argv[1]
cell, cfg = common.load_cell("toy-second", data)
line, obs = command.measure(cell, cfg, common.CPU_AS, seed=2147483659,
                            seconds=1.5, trace=0, t_start=time.time())
fam, drv = lookup.family(cfg), lookup.driver(cell)
got = observe.read_metrics(obs, data)
fixed = dict(obs, trace=None, values={
    "processed_tokens_per_s": 100.0, "mean_context_tokens": 50.0,
    "tokens_per_s": 10.0, "head_tokens_per_processed": 0.2})
cell2, cfg2 = common.load_cell("toy-open", data)
toy = {"family": "toyfam"}
work.host_bytes = lambda c, w, v: 1.0     # what no model shapes
print(json.dumps({
    "line": line, "family": fam.__name__, "family_calls": fam.CALLS,
    "driver": drv.__name__, "driver_calls": drv.CALLS,
    "metrics": sorted(got),
    "family_lacks": not hasattr(fam, "serve_flops_per_token")
    and hasattr(llama, "serve_flops_per_token"),
    "fixed": {k: v["value"] for k, v in
              observe.read_metrics(fixed, data).items()},
    "fixed_default": sorted(observe.read_metrics(
        dict(fixed, cfg=cfg2, cell=cell2))),
    "work": {
        "own": lookup.work(toy, "toy_keys_per_token") is fam.toy_keys_per_token,
        "lacks": lookup.work(toy, "train_flops_per_token") is None,
        "not_the_defaults": lookup.work({}, "toy_keys_per_token") is None,
        "shared": lookup.work(toy, "host_bytes") is work.host_bytes
        and lookup.work({}, "host_bytes") is work.host_bytes}}))
'''


def write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text if isinstance(text, str) else json.dumps(text))


def same_but_for(a, b):
    """Files of tree `b` that `a` lacks, as paths under `b`; every file
    of `a` is in `b` with the same bytes."""
    cmp = filecmp.dircmp(a, b, ignore=["__pycache__"])
    assert not cmp.diff_files and not cmp.left_only and not cmp.funny_files
    added = list(cmp.right_only)
    for name, sub in cmp.subdirs.items():
        added += [f"{name}/{x}" for x in same_but_for(sub.left, sub.right)]
    return sorted(added)


@pytest.fixture(scope="module")
def second(tmp_path_factory):
    """A copy of the benchmark's tree with the second family's NEW files,
    and what a run of the new cell in it reported."""
    root = tmp_path_factory.mktemp("copy")
    bench = str(root / "benchmarks")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(common.REPO, "BENCHMARK.json"), root)
    data = f"{bench}/tests/data"
    write(f"{bench}/families/toyfam.py", FAMILY)
    write(f"{bench}/drivers/toy_kind.py", DRIVER)
    write(f"{data}/configs/toy-second.json",
          dict(common.load_json(f"{DATA}/configs/toy-serve.json"),
               name="toy-second", family="toyfam"))
    write(f"{data}/workloads/toy-second.json",
          dict(common.load_json(f"{DATA}/workloads/toy-open.json"),
               name="toy-second", config="toy-second", driver="toy_kind"))
    metric = {"unit": "%", "better": "higher", "source": "host_clock",
              "layer": "model", "moves": "serve_tokens_per_s",
              "workloads": ["toy-second"], "reader": "work_rate"}
    for name, fn in (("toy_family_share", "toy_keys_per_token"),
                     ("toy_other_familys", "serve_flops_per_token")):
        write(f"{data}/metrics/{name}.json",
              dict(metric, name=name, args={
                  "rate": "values.processed_tokens_per_s", "work": fn,
                  "peak": "flops"}))
    # nothing that was there was edited
    assert same_but_for(BENCH, bench) == [
        "drivers/toy_kind.py", "families/toyfam.py",
        "tests/data/configs/toy-second.json",
        "tests/data/metrics/toy_family_share.json",
        "tests/data/metrics/toy_other_familys.json",
        "tests/data/workloads/toy-second.json"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), common.REPO]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, data], cwd=str(root),
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_second_family_runs_with_new_files_only(second):
    line = second["line"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert second["family"] == "benchmarks.families.toyfam"
    assert second["driver"] == "benchmarks.drivers.toy_kind"
    assert second["driver_calls"] == ["toy-second"]
    # the family's reference and limits decided `correct`
    calls = second["family_calls"]
    assert calls.count("token_gaps") == 4
    assert {"gap_limits", "reference_weights"} <= set(calls)
    from benchmarks.families import llama
    limit = line["checks"]["logit_gap_widest"]["limit"]
    assert limit == pytest.approx(2.0 * llama.gap_limits(common.load_json(
        f"{DATA}/configs/toy-serve.json"))["widest"])
    # its own work function is read; another family's count never is
    assert "toy_family_share" in second["metrics"]
    assert "toy_keys_per_token" in calls and second["family_lacks"]
    assert "toy_other_familys" not in second["metrics"]
    # the toy metric files of the data directory still report
    assert "toy_requests_added" not in second["metrics"]  # toy-open's only


def test_a_metric_whose_count_the_family_lacks_is_left_out(second):
    assert second["fixed"]["toy_family_share"] \
        == pytest.approx(100.0 * 100.0 * 1e9 * 50.0 / 197e12)
    assert "toy_other_familys" not in second["fixed"]
    # the same values under the default family: serve_mfu is Llama's count
    assert "serve_mfu" in second["fixed_default"]
    assert "train_mfu" not in second["fixed_default"]
    assert all(second["work"].values()), second["work"]


def test_lookups_by_name_without_building_a_model():
    # a file without `family` is of the default family, the one place
    # its name is written
    assert lookup.family({"name": "x"}).__name__ \
        == f"benchmarks.families.{lookup.DEFAULT_FAMILY}"
    for kind in ("train_loop", "closed_loop", "open_loop"):
        assert lookup.driver({"driver": kind}).__name__ \
            == f"benchmarks.drivers.{kind}"
        assert callable(lookup.driver({"driver": kind}).run)
    for bad in ({"driver": "no_such_kind"}, {"driver": "../run_cell"},
                {"driver": None}, {"driver": "__init__"}):
        with pytest.raises(LookupError, match="benchmarks/drivers/"):
            lookup.driver(bad)
    with pytest.raises(LookupError, match="benchmarks/families/.*llama"):
        lookup.family({"family": "no_such_family"})


def test_a_work_count_is_the_familys_first_then_the_shared(monkeypatch):
    from benchmarks.families import llama
    assert lookup.work({}, "train_flops_per_token") \
        is llama.train_flops_per_token
    assert lookup.work({}, "no_such_count") is None
    # what no model shapes lives in harness/work.py
    monkeypatch.setattr(work, "host_bytes", lambda c, w, v: 1.0, raising=False)
    assert lookup.work({}, "host_bytes") is work.host_bytes
    # a name that is no function is no work count
    assert lookup.work({}, "MODEL_KEYS") is None
    # the default family's serve cell reports its own count's metric
    cell, cfg = common.load_cell("toy-open", DATA)
    obs = {"values": {"processed_tokens_per_s": 100.0,
                      "mean_context_tokens": 50.0,
                      "head_tokens_per_processed": 0.2},
           "spans": {}, "counters": {"window": {}, "process": {}},
           "cfg": cfg, "cell": cell, "device": {"kind": "TPU v5 lite"},
           "chips": 1, "trace": None}
    got = observe.read_metrics(obs)
    assert 0 < got["serve_mfu"]["value"] < 100 and "train_mfu" not in got
