"""The benchmark's three look-ups by name (`benchmarks/harness/lookup.py`),
kept under tests/ too so that they count in tier 1 (PERF.md section 7
asked for it; `benchmarks/tests/test_lookup.py` holds the whole seam): the
default family, the second family found by a configuration's `family`,
the error for an unknown name, a work count that is the family's own or
none, and no model built by any of it."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import common, lookup  # noqa: E402


def test_the_default_family_and_the_second_by_name():
    assert lookup.DEFAULT_FAMILY == "llama"
    assert lookup.family({"name": "x"}).__name__ \
        == "benchmarks.families.llama"
    cell, cfg = common.load_cell("serve-reasoning-decode")
    assert cfg["family"] == "kimi_k2"
    assert lookup.family(cfg).__name__ == "benchmarks.families.kimi_k2"
    assert lookup.driver(cell).__name__ == "benchmarks.drivers.closed_loop"
    for name in ("train-pretrain-2k", "serve-offline-decode",
                 "serve-chat-steady"):
        _, other = common.load_cell(name)
        assert lookup.family(other).__name__ == "benchmarks.families.llama"


@pytest.mark.parametrize("kind", ["train_loop", "closed_loop", "open_loop"])
def test_a_driver_kind_is_found_by_name(kind):
    assert lookup.driver({"driver": kind}).__name__ \
        == f"benchmarks.drivers.{kind}"
    assert callable(lookup.driver({"driver": kind}).run)


@pytest.mark.parametrize("bad", ["no_such_kind", "../run_cell", None,
                                 "__init__"])
def test_an_unknown_name_fails_with_the_directory_and_what_it_holds(bad):
    with pytest.raises(LookupError, match="benchmarks/drivers/.*closed_loop"):
        lookup.driver({"driver": bad})
    with pytest.raises(LookupError, match="benchmarks/families/.*kimi_k2"):
        lookup.family({"family": bad or "no_such_family"})


def test_a_work_count_is_the_familys_own_or_none():
    from benchmarks.families import kimi_k2, llama
    kimi = {"family": "kimi_k2"}
    assert lookup.work({}, "serve_flops_per_token") \
        is llama.serve_flops_per_token
    assert lookup.work(kimi, "serve_flops_per_token") \
        is kimi_k2.serve_flops_per_token
    # never another family's count: the metric is left out instead
    assert lookup.work(kimi, "paged_decode_kv") is None
    assert lookup.work({}, "mla_decode_latent") is None
    assert lookup.work(kimi, "train_flops_per_token") is None
    assert lookup.work(kimi, "MODEL_KEYS") is None     # no function


def test_the_lookups_build_no_model():
    """Finding a family, a driver and a work count imports their files
    and nothing of the program."""
    import subprocess
    code = ("import sys; from benchmarks.harness import lookup; "
            "f = lookup.family({'family': 'kimi_k2'}); "
            "lookup.driver({'driver': 'closed_loop'}); "
            "lookup.work({'family': 'kimi_k2'}, 'moe_held_experts'); "
            "assert 'paddle_tpu' not in sys.modules, 'the program'; "
            "print('ok')")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=300)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr
