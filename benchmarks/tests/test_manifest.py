"""BENCHMARK.json against the benchmark's files and the contract's limits
that can be checked here."""
import json
import os
import re

import pytest

from benchmarks.harness import common, observe

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return common.load_json(os.path.join(common.REPO, "BENCHMARK.json"))


def test_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["benchmarks"] and 1 <= man["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(common.REPO, "BENCHMARK.json")) < 65536
    names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(names) == len(set(names))
    for m in man["end_to_end"] + man["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in man["end_to_end"])
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 4)


def test_cells_and_configs_have_their_files(man):
    cfgs = {c["name"]: c for c in man["configs"]}
    assert len({c["file"] for c in man["configs"]}) == len(cfgs)
    used = set()
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell, cfg = common.load_cell(w["name"])
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["why"] == w["why"] and cell["traffic_name"] == w["traffic"]
        used.add(w["config"])
    assert used == set(cfgs)
    for name, c in cfgs.items():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        f = common.load_json(os.path.join(common.REPO, c["file"]))
        assert f["source"] == c["source"] and len(c["why"]) <= 200
        assert sorted(f["reduced"]) == c["reduced"] == ["num_hidden_layers"]
        assert f["published"]["num_hidden_layers"] == 32
        # no width is cut: Mistral-7B-v0.3's published sizes
        assert (f["hidden_size"], f["intermediate_size"], f["head_dim"],
                f["num_attention_heads"], f["num_key_value_heads"],
                f["vocab_size"]) == (4096, 14336, 128, 32, 8, 32768)


def test_every_metric_of_the_manifest_is_a_file_and_every_cell_reports(man):
    files = {m["name"]: m for m in observe.metric_files()}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = [w["name"] for w in man["workloads"]]
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        f = files[m["name"]]
        for k in ("unit", "better", "source", "layer", "moves"):
            assert f[k] == m[k], (m["name"], k)
        assert os.path.exists(os.path.join(
            common.BENCH_DIR, "readers", f["reader"] + ".py"))
        # the metric it moves is reported wherever this one is
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in moved.get("workloads", cells), (m["name"], c)
            cell, _ = common.load_cell(c)
            assert m["name"] in [x["name"] for x in observe.metrics_of(cell)]
    for c in cells:
        assert sum(c in m.get("workloads", cells) for m in man["end_to_end"]) >= 2
        assert any(c in m.get("workloads", cells) for m in man["per_layer"])
