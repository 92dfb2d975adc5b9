"""The program's own spans (ISSUE 27): `profiler.RecordEvent` enters a
`jax.profiler.TraceAnnotation` whether or not paddle's `Profiler` is
recording, so the engine's step phases and `to_static`'s call phases
land in ANY trace taken over them. `jax.profiler.TraceAnnotation` is
patched with a recorder here: what is asserted is which annotations are
entered, with which metadata, nested how. PERF.md lists the names.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import NgramProposer, ServingEngine

LAUNCHES = {"serving.prefill_chunk", "serving.decode_step",
            "serving.multi_decode_step", "serving.verify_step"}
PHASES = {"serving.schedule", "serving.build_inputs", "serving.fetch",
          "serving.emit", "serving.bookkeeping"} | LAUNCHES


class Recorder:
    """Stands in for `jax.profiler.TraceAnnotation`: every span entered,
    as (name, metadata, parent's index or None, index), in entry order."""

    def __init__(self):
        self.spans, self.stack = [], []
        outer = self

        class Annotation:
            is_enabled = staticmethod(lambda: True)   # a trace is on

            def __init__(self, name, **meta):
                self.name, self.meta = name, meta

            def __enter__(self):
                parent = outer.stack[-1] if outer.stack else None
                outer.stack.append(len(outer.spans))
                outer.spans.append((self.name, self.meta, parent))
                return self

            def __exit__(self, *exc):
                outer.stack.pop()
                return False

        self.cls = Annotation

    def names(self):
        return [s[0] for s in self.spans]

    def parent_name(self, i):
        p = self.spans[i][2]
        return None if p is None else self.spans[p][0]

    def children(self, i):
        return [s[0] for s in self.spans if s[2] == i]


@pytest.fixture()
def rec(monkeypatch):
    r = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", r.cls)
    return r


def test_record_event_reaches_a_trace_without_paddles_profiler(rec):
    assert not profiler._tracer.enabled
    before = len(profiler.host_events())
    with profiler.RecordEvent("outer", step=12, bucket=[64, 64]):
        with profiler.RecordEvent("inner"):
            pass
    assert rec.spans == [("outer", {"step": 12, "bucket": [64, 64]}, None),
                         ("inner", {}, 0)]
    assert rec.stack == []
    # paddle's own host-span list stays behind an active Profiler
    assert len(profiler.host_events()) == before


def test_record_event_reads_the_host_clock_only_for_paddles_profiler(
        rec, monkeypatch, tmp_path):
    reads = []
    real = profiler.time

    class Clock:
        @staticmethod
        def perf_counter_ns():
            reads.append(1)
            return real.perf_counter_ns()

        def __getattr__(self, name):
            return getattr(real, name)

    monkeypatch.setattr(profiler, "time", Clock())
    with profiler.RecordEvent("off"):
        pass
    assert reads == [] and rec.names() == ["off"]
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                           log_dir=str(tmp_path),
                           on_trace_ready=lambda prof: None):
        n = len(reads)
        with profiler.RecordEvent("on"):
            pass
        assert len(reads) == n + 2
        assert [e["name"] for e in profiler.host_events()
                if e["name"] in ("off", "on")] == ["on"]
    # a span that was open when the Profiler started is not half-recorded
    ev = profiler.RecordEvent("straddles")
    ev.begin()
    profiler._tracer.enabled = True
    try:
        ev.end()
    finally:
        profiler._tracer.enabled = False
    assert "straddles" not in [e["name"] for e in profiler.host_events()]


def test_record_event_decorator_and_unbalanced_end(rec):
    @profiler.RecordEvent("decorated", fn="f")
    def f():
        return 3

    assert f() == 3 and f() == 3
    ev = profiler.RecordEvent("never_begun")
    ev.end()                               # no begin: nothing to leave
    assert rec.names() == ["decorated", "decorated"] and rec.stack == []


def test_profiler_raises_when_the_device_trace_does_not_start(
        monkeypatch, tmp_path):
    def boom(*a, **k):
        raise RuntimeError("no trace for you")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    p = profiler.Profiler(log_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="no trace for you"):
        p.start()
    assert not profiler._tracer.enabled
    assert p.current_state is profiler.ProfilerState.CLOSED
    # a host-only profiler still starts after the failure
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                           log_dir=str(tmp_path),
                           on_trace_ready=lambda prof: None):
        assert profiler._tracer.enabled
    assert not profiler._tracer.enabled


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    paddle.seed(0)
    return LlamaForCausalLM(cfg)


KW = dict(num_pages=64, page_size=8, token_budget=64, batch_buckets=[8],
          prefill_buckets=[32], pages_buckets=[8], temperature=0.0)


def _run_engine(model, rec, **kw):
    if "proposer" in kw:
        kw = dict(kw, proposer=kw["proposer"]())
    eng = ServingEngine(model, trace=True, **KW, **kw)
    rng = np.random.RandomState(0)
    for n, m in ((5, 4), (40, 3), (9, 5)):     # the 40 takes two chunks
        eng.add_request(rng.randint(0, 128, (n,)).tolist(), max_new_tokens=m)
    out = eng.run()
    assert sum(len(v) for v in out.values()) == 12
    return eng


@pytest.mark.parametrize("kw,launch", [
    ({}, "serving.decode_step"),
    ({"decode_steps": 4}, "serving.multi_decode_step"),
    ({"spec_k": 2, "proposer": NgramProposer}, "serving.verify_step")],
    ids=["plain", "multi", "spec"])
def test_engine_step_spans_nest_and_carry_the_step_number(model, rec, kw,
                                                          launch):
    eng = _run_engine(model, rec, **kw)
    steps = [i for i, s in enumerate(rec.spans) if s[0] == "serving.step"]
    assert steps and all(rec.spans[i][2] is None for i in steps)
    # every other engine span is a phase, directly under a step
    for i, (name, meta, parent) in enumerate(rec.spans):
        if name.startswith("serving.") and name != "serving.step":
            assert name in PHASES, name
            assert rec.parent_name(i) == "serving.step", name
        if name in LAUNCHES:
            assert all(isinstance(b, int) for b in meta["bucket"])
    seen = set(rec.names())
    assert PHASES - LAUNCHES <= seen
    assert {"serving.prefill_chunk", launch} <= seen
    # one root a step, numbered as the flight recorder numbers its records
    numbers = [rec.spans[i][1]["step"] for i in steps]
    assert numbers == list(range(1, len(steps) + 1))
    records = eng.timeline()
    assert records and {r["step"] for r in records} <= set(numbers)
    # a step's phases come in the order of the work: schedule first, the
    # step's own bookkeeping last, and each launch of the one launch path
    # as its family's five phases, whole and in the family's order
    # (`serve_idle_in_*` attribute the device's gaps by them). A
    # multi-decode launch is waited for at once (the TPOT sample ends
    # with the tokens on the host) and keeps its books after; a chunk and
    # a verify launch store the caches and close the request's launch
    # span first, as they did before they had spans
    fetch_first = ["serving.fetch", "serving.bookkeeping"]
    plain = "serving.decode_step"
    in_flight = ran_ahead = 0
    for i in steps:
        kids = rec.children(i)
        assert kids[0] == "serving.schedule"
        assert kids[-1] == "serving.bookkeeping"
        assert kids.count("serving.build_inputs") == \
            sum(k in LAUNCHES for k in kids)
        for j, k in enumerate(kids):
            if k in LAUNCHES and k != plain:
                after = fetch_first if "decode" in k else fetch_first[::-1]
                assert kids[j - 1:j + 4] == \
                    ["serving.build_inputs", k] + after + ["serving.emit"], k
        # the plain decode family (ISSUE 34) fetches ONE launch a step,
        # after its launches and before its books: the step's own launch
        # (enqueued here only when the step before left none in flight)
        # and then the NEXT step's, both in front of the fetch, so that
        # fetch, bookkeeping and emit run under the device's work
        if launch == plain and (plain in kids or in_flight):
            tail = kids[1:-1]
            while tail[:2] == ["serving.build_inputs",
                               "serving.prefill_chunk"]:
                tail = tail[5:]
            n = tail.count(plain)
            assert tail == ["serving.build_inputs", plain] * n + \
                fetch_first + ["serving.emit"], tail
            assert n in ((0, 1) if in_flight else (1, 2)), (n, in_flight)
            ran_ahead += in_flight
            in_flight = in_flight + n - 1
    if launch == plain:
        assert ran_ahead and not in_flight
    # the join to the host-clock recorders: every launch span of the
    # RequestTracer carries a step number the profiler's trace has, and
    # the flight recorder's record of that step names the same program
    by_step = {r["step"]: r for r in records}
    n_launch_spans = 0
    for tr in eng.tracer.traces():
        for ev in tr.spans:
            if ev["name"] in ("prefill_chunk", "decode_step",
                              "multi_decode_step", "verify_step"):
                n_launch_spans += 1
                step = ev["args"]["step"]
                assert step in by_step
                family = ev["name"].replace("prefill_chunk", "chunk") \
                    .replace("_step", "")
                assert any(p.startswith(family + ":")
                           for p in by_step[step]["programs"])
    assert n_launch_spans


def test_engine_spans_with_the_request_tracer_off(model, rec):
    eng = ServingEngine(model, **KW)
    eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.run()
    assert eng.tracer is None
    assert {"serving.step", "serving.decode_step"} <= set(rec.names())


def test_to_static_call_spans(rec):
    net = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())

    def train_step(x):
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step, state_objects=[net, opt])
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    losses = [float(step(x)) for _ in range(3)]
    assert losses[2] < losses[0]
    calls = [i for i, s in enumerate(rec.spans) if s[0] == "to_static.call"]
    assert len(calls) == 3
    for i in calls:
        assert rec.spans[i][2] is None
        assert rec.spans[i][1]["fn"].endswith("train_step")
        assert rec.children(i) == [
            "to_static.guard", "to_static.collect_state",
            "to_static.dispatch", "to_static.load_state"]
