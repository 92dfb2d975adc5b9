"""The six `setup_*` metrics: the program's own record of its set-up
(`readers/program_setup.py`), read from the toy train and serve cells as
a traced run reads them. The record is by span, so work after the driver,
a plain `jax.jit` here, does not move them; a program without the record
gives none of them."""
import jax
import numpy as np
import pytest

from benchmarks.harness import observe
from benchmarks.readers import program_setup
from test_drivers import run_cell

SIX = {"setup_import_s", "setup_param_init_s", "setup_program_trace_s",
       "setup_program_lower_s", "setup_program_compile_s",
       "setup_program_cache_load_s"}


@pytest.mark.parametrize("name,build", [("toy-train", "train_compile_s"),
                                        ("toy-closed",
                                         "serve_program_build_s")])
def test_the_toy_cells_yield_the_six(name, build):
    # the record is the process's, and this process runs more than one
    # cell: the builds' stages are compared over this cell's run
    stages = [m for m in SIX if m.startswith("setup_program_")]
    args = {m["name"]: m["args"] for m in observe.metric_files()}
    traced = {"trace": {"busy_s": 1.0}}
    before = {m: program_setup.read(traced, args[m]) for m in stages}
    _, obs, correct, _, _, _ = run_cell(name)
    assert correct
    assert not SIX & set(observe.read_metrics(obs))     # no trace, none
    obs["trace"] = {"busy_s": 1.0}                       # a traced run's obs
    got = observe.read_metrics(obs)
    assert SIX <= set(got)
    assert all(got[m]["unit"] == "s" and got[m]["value"] >= 0 for m in SIX)
    assert got["setup_import_s"]["value"] > 0
    assert got["setup_param_init_s"]["value"] > 0
    built = sum(got[m]["value"] - before[m] for m in stages)
    assert 0 < built <= got[build]["value"]
    jax.jit(lambda a: a * 5.0 + 3.0)(np.arange(7.0)).block_until_ready()
    again = observe.read_metrics(obs)
    assert {m: again[m] for m in SIX} == {m: got[m] for m in SIX}


def test_a_program_without_the_record_gives_none(monkeypatch):
    from paddle_tpu.profiler import compile_log
    monkeypatch.delattr(compile_log, "setup_totals")
    obs = {"trace": {"busy_s": 1.0}}
    assert program_setup.read(obs, {"span": "setup.import"}) is None
