"""The program's set-up spans (`profiler.compile_log`):
`setup.import`, `setup.param_init` and `setup.program_build`, with JAX's
compile stages (trace, lower, compile, cache_load) attributed to the
innermost open one by their self time, and stages under no span kept
apart as `outside`. `jax.profiler.TraceAnnotation` is patched with a
recorder as in test_spans.py: what is asserted of the annotations is
which are entered, with which metadata, under which parent.
"""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import compile_log
from paddle_tpu.serving import ServingEngine

from test_spans import Recorder

BUILD = "setup.program_build"
INIT = "setup.param_init"


@pytest.fixture()
def rec(monkeypatch):
    r = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", r.cls)
    return r


def _totals():
    return compile_log.setup_totals()


def _stages(totals, kind):
    return totals.get(kind, {}).get("stages", dict.fromkeys(
        compile_log.STAGES, 0.0))


def _stage_delta(after, before, kind):
    a, b = _stages(after, kind), _stages(before, kind)
    return {s: a[s] - b[s] for s in compile_log.STAGES}


def _count(totals, kind, key="count"):
    return totals.get(kind, {}).get(key, 0)


def _toy_step():
    net = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())

    def toy_train_step(x):
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return paddle.jit.to_static(toy_train_step, state_objects=[net, opt])


def test_a_first_call_attributes_trace_lower_and_compile(rec):
    step = _toy_step()
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    n_events = len(compile_log.events())
    before = _totals()
    step(x)
    after = _totals()
    # the build is annotated inside the call's dispatch, so the call's own
    # phases stay as they were
    (i,) = [j for j, s in enumerate(rec.spans) if s[0] == BUILD]
    assert rec.parent_name(i) == "to_static.dispatch"
    assert rec.spans[i][1]["fn"].endswith("toy_train_step")
    got = _stage_delta(after, before, BUILD)
    assert got["trace"] > 0 and got["lower"] > 0 and got["compile"] > 0
    assert _count(after, BUILD) == _count(before, BUILD) + 1
    # the one event the call logs carries the same split, and the stages
    # fit inside the wall time it gives the call
    (ev,) = compile_log.events()[n_events:]
    assert ev["kind"] == "trace"
    assert ev["detail"]["stages"] == pytest.approx(got)
    assert sum(got.values()) <= ev["duration_ms"] / 1e3
    # and inside the span's own seconds
    seconds = _count(after, BUILD, "seconds") - _count(before, BUILD,
                                                       "seconds")
    assert sum(got.values()) <= seconds


def test_a_second_call_adds_nothing_and_a_retrace_adds_again():
    step = _toy_step()
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    step(x)
    before = _totals()
    for _ in range(3):
        step(x)
    same = _totals()
    assert same[BUILD] == before[BUILD]
    step(paddle.to_tensor(np.ones((3, 4), np.float32)))   # a new guard key
    after = _totals()
    assert _count(after, BUILD) == _count(before, BUILD) + 1
    got = _stage_delta(after, before, BUILD)
    assert got["trace"] > 0 and got["compile"] > 0


def test_an_inner_jit_is_not_counted_twice():
    inner = jax.jit(lambda a: jnp.sin(a) * 2.0 + jnp.cos(a))

    def calls_an_inner_jit(x):
        return paddle.Tensor(inner((x * 3.0)._data)).sum()

    step = paddle.jit.to_static(calls_an_inner_jit)
    x = paddle.to_tensor(np.ones((2, 5), np.float32))
    raw = []

    def listen(event, start, end, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            raw.append((start, end))

    before = _totals()
    jax.monitoring.register_event_time_span_listener(listen)
    try:
        step(x)
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    after = _totals()
    union, hi = 0.0, None
    for start, end in sorted(raw):
        if hi is None or start >= hi:
            union += end - start
            hi = end
        elif end > hi:
            union += end - hi
            hi = end
    naive = sum(end - start for start, end in raw)
    assert len(raw) >= 2 and naive > union      # the inner trace nests
    counted = sum(_stage_delta(after, before, k)["trace"]
                  for k in compile_log.SETUP_KINDS + ("outside",))
    assert counted == pytest.approx(union, rel=1e-6, abs=1e-9)
    assert _stage_delta(after, before, BUILD)["trace"] > 0


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    paddle.seed(0)
    return LlamaForCausalLM(cfg)


def test_a_program_cache_build_records_its_key(model, rec):
    eng = ServingEngine(model, num_pages=64, page_size=8, token_budget=64,
                        batch_buckets=[8], prefill_buckets=[32],
                        pages_buckets=[8], temperature=0.0)
    n_events = len(compile_log.events())
    before = _totals()
    eng.add_request([1, 2, 3, 4, 5], max_new_tokens=3)
    eng.run()
    after = _totals()
    built = [k for k, ms in eng.programs.compile_times_ms().items()
             if ms is not None]
    assert built
    assert _count(after, BUILD) - _count(before, BUILD) == len(built)
    got = _stage_delta(after, before, BUILD)
    assert got["trace"] > 0 and got["lower"] > 0 and got["compile"] > 0
    spans = [s for s in rec.spans if s[0] == BUILD]
    assert sorted(s[1]["key"] for s in spans) == \
        sorted(repr(k)[:120] for k in built)
    assert {s[1]["family"] for s in spans} == {str(k[0]) for k in built}
    events = [e for e in compile_log.events()[n_events:]
              if e["kind"] == "program_compile"]
    assert len(events) == len(built)
    assert sum(e["detail"]["stages"]["compile"] for e in events) == \
        pytest.approx(got["compile"])


def test_a_bare_jit_lands_outside_only():
    before = _totals()
    jax.jit(lambda a: a * 7.0 - 2.0)(np.arange(6.0)).block_until_ready()
    after = _totals()
    out = _stage_delta(after, before, "outside")
    assert out["trace"] > 0 and out["lower"] > 0 and out["compile"] > 0
    for kind in compile_log.SETUP_KINDS:
        assert after.get(kind) == before.get(kind)


def test_param_init_counts_the_leaves_and_bytes_of_a_layer(rec):
    before = _totals()
    net = paddle.nn.Sequential(paddle.nn.Linear(3, 5),
                               paddle.nn.LayerNorm(5),
                               paddle.nn.Linear(5, 2, bias_attr=False))
    after = _totals()
    params = net.parameters()
    assert _count(after, INIT, "leaves") - _count(before, INIT, "leaves") \
        == len(params) == 5
    assert _count(after, INIT, "bytes") - _count(before, INIT, "bytes") \
        == sum(int(np.prod(p.shape)) * 4 for p in params) == 4 * 40
    assert _count(after, INIT) - _count(before, INIT) == len(params)
    assert rec.names().count(INIT) == len(params)


def test_the_import_span_is_recorded_once():
    imp = _totals()["setup.import"]
    assert imp["count"] == 1
    assert imp["seconds"] > 0 and imp["self_seconds"] == imp["seconds"]
    assert set(imp["stages"]) == set(compile_log.STAGES)


def test_the_event_kinds_and_compile_seconds_keep_their_kinds(model):
    _toy_step()(paddle.to_tensor(np.ones((2, 4), np.float32)))
    eng = ServingEngine(model, num_pages=64, page_size=8, token_budget=64,
                        batch_buckets=[8], prefill_buckets=[32],
                        pages_buckets=[8], temperature=0.0)
    eng.add_request([3, 1, 4], max_new_tokens=2)
    eng.run()
    kinds = set(compile_log.KINDS)
    assert set(compile_log.counters()) <= kinds
    assert set(compile_log.duration_totals_s()) <= kinds
    rep = paddle.jit.to_static_report()
    assert set(rep["compile_seconds"]) <= kinds
    assert set(rep["compile_counters"]) <= kinds
    assert set(rep["setup"]) >= {"setup.import", INIT, BUILD, "outside"}


def test_reset_leaves_the_setup_totals():
    _toy_step()(paddle.to_tensor(np.ones((2, 4), np.float32)))
    before = _totals()
    compile_log.reset()
    assert compile_log.counters() == {}
    assert _totals() == before


def test_stages_are_counted_by_their_self_time():
    """A cache load inside a backend compile inside a trace: each second
    counted once, in the innermost stage, under the innermost span."""
    before = _totals()
    with compile_log.setup_span(BUILD, family="unit") as span:
        compile_log.stage_began("trace", 100.0)
        compile_log.stage_began("trace", 101.0)          # a nested trace
        compile_log.stage_ended("trace", 101.0, 103.0)
        compile_log.stage_began("compile", 104.0)
        compile_log.stage_nested("cache_load", 1.5)
        compile_log.stage_ended("compile", 104.0, 108.0)
        compile_log.stage_ended("trace", 100.0, 110.0)
        # a stage whose start was never seen counts whole
        compile_log.stage_ended("lower", 200.0, 200.25)
    assert span.stages == {"trace": 6.0, "lower": 0.25, "compile": 2.5,
                           "cache_load": 1.5}
    got = _stage_delta(_totals(), before, BUILD)
    assert got == pytest.approx(span.stages)


def test_nested_spans_and_a_span_that_is_not_kept():
    before = _totals()
    with compile_log.setup_span(BUILD, fn="outer") as outer:
        with compile_log.setup_span(INIT) as inner:
            inner.counts["leaves"] = 1
            compile_log.stage_ended("compile", 0.0, 0.5)
        dropped = compile_log.setup_span(BUILD, fn="warm call")
        compile_log.stage_ended("trace", 0.0, 0.125)
        dropped.close(keep=False)         # its stage goes to the outer
    after = _totals()
    assert inner.stages["compile"] == 0.5 and outer.stages["compile"] == 0
    assert outer.stages["trace"] == 0.125
    assert _count(after, BUILD) == _count(before, BUILD) + 1
    assert _count(after, INIT) == _count(before, INIT) + 1
    build_s = _count(after, BUILD, "seconds") - _count(before, BUILD,
                                                       "seconds")
    build_self = _count(after, BUILD, "self_seconds") - \
        _count(before, BUILD, "self_seconds")
    init_s = _count(after, INIT, "seconds") - _count(before, INIT, "seconds")
    assert build_self == pytest.approx(build_s - init_s)


def test_another_threads_stages_are_not_taken_by_this_threads_span():
    before = _totals()
    with compile_log.setup_span(BUILD) as span:
        t = threading.Thread(
            target=compile_log.stage_ended, args=("compile", 0.0, 0.75))
        t.start()
        t.join()
    after = _totals()
    assert span.stages["compile"] == 0
    assert _stage_delta(after, before, "outside")["compile"] == 0.75


def test_compile_log_imports_no_jax():
    path = os.path.join(os.path.dirname(compile_log.__file__),
                        "compile_log.py")
    code = ("import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('cl', {path!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "with m.setup_span('setup.param_init'):\n"
            "    pass\n"
            "assert m.setup_totals()['setup.param_init']['count'] == 1\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    assert importlib.util.find_spec("jax") is not None
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_totals_lose_no_update_under_many_threads():
    before = _totals()
    n_threads, n_each = 16, 200

    def work():
        for _ in range(n_each):
            compile_log.stage_ended("compile", 0.0, 0.5)      # outside
            with compile_log.setup_span(INIT) as span:
                span.counts["leaves"] = 1

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    after = _totals()
    n = n_threads * n_each
    assert _stage_delta(after, before, "outside")["compile"] == 0.5 * n
    assert _count(after, INIT, "leaves") - _count(before, INIT, "leaves") == n
    assert _count(after, INIT) - _count(before, INIT) == n
