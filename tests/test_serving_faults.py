"""Fault-tolerant serving (ISSUE 3): request lifecycle (deadlines,
abort, admission control), the step supervisor (transient retry, NaN
quarantine, snapshot/resume), and the fault-injection registry.

CPU-only, greedy, pinned single-bucket grids (the SERVING.md
determinism contract: bit-identity claims hold within one program
shape). Every test leaves the fault registry clean — `faults.injected`
disarms on exit and the autouse fixture asserts it.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (EngineFailure, EngineOverloaded,
                                RequestState, RetryPolicy, ServingEngine,
                                TransientDeviceError, classify_failure)
from paddle_tpu.serving.supervisor import FATAL, POISON, TRANSIENT
from paddle_tpu.utils import faults

from _engine_steps import drain


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    paddle.seed(0)
    return LlamaForCausalLM(cfg)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_counts()
    yield
    assert not faults.active(), "test leaked an armed fault spec"
    faults.clear()


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


# single-bucket grid: identical program shapes across every run in this
# file, so greedy outputs are comparable bit-for-bit
KW = dict(num_pages=64, page_size=8, token_budget=64,
          batch_buckets=[8], prefill_buckets=[32], pages_buckets=[8],
          temperature=0.0)

NOSLEEP = RetryPolicy(max_retries=3, base_s=0.0, sleep=lambda s: None)


def _reqs(n, seed=42, plen=(4, 20), mnew=(3, 9)):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, 128, (rng.randint(*plen),)).tolist(),
             int(rng.randint(*mnew))) for _ in range(n)]


def _baseline(model, prompts, **kw):
    eng = ServingEngine(model, **{**KW, **kw})
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    out = eng.run()
    eng.shutdown()
    return {i: out[r] for i, r in enumerate(rids)}


# ---------------------------------------------------------------- registry
def test_fault_registry_triggers_and_counts():
    pt = faults.register_point("test.point")
    assert pt in faults.points()
    with pytest.raises(KeyError):
        faults.inject("no.such.point", payload=1)
    # after/times windowing
    with faults.injected(pt, payload="x", after=2, times=2) as spec:
        assert [faults.fire(pt) for _ in range(5)] == \
            [None, None, "x", "x", None]
        assert spec.fired == 2
    assert faults.fire(pt) is None          # disarmed on exit
    # seeded probability stream is reproducible
    def schedule():
        with faults.injected(pt, payload=1, prob=0.5, times=-1, seed=7):
            return [faults.fire(pt) is not None for _ in range(32)]
    assert schedule() == schedule()
    # exception action + firing counts
    faults.reset_counts()
    with faults.injected(pt, exc=RuntimeError("boom")):
        with pytest.raises(RuntimeError):
            faults.fire(pt)
    assert faults.fired_counts() == {pt: 1}


def test_classify_failure():
    assert classify_failure(TransientDeviceError("x")) == TRANSIENT
    assert classify_failure(RuntimeError("UNAVAILABLE: device gone")) \
        == TRANSIENT
    assert classify_failure(FloatingPointError("nan")) == POISON
    assert classify_failure(RuntimeError("RESOURCE_EXHAUSTED: OOM")) == FATAL
    assert classify_failure(ValueError("whatever")) == FATAL
    # a program the chip's compiler refuses must surface, never retry
    assert classify_failure(NotImplementedError(
        "Mosaic kernels cannot be automatically partitioned. Please wrap "
        "the call in a shard_map.")) == FATAL
    assert classify_failure(RuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel")) == FATAL


# ---------------------------------------------------------- lifecycle
def test_deadline_expiry_in_every_state(model):
    """TTL cancels at the next boundary whether the request is queued,
    mid-prefill (chunked), decoding, or preempted-to-waiting."""
    clock = FakeClock()
    eng = ServingEngine(model, clock=clock, **KW)
    # decoding request: generous prompt, many tokens
    r_dec = eng.add_request([1] * 10, max_new_tokens=30, ttl_s=5.0)
    eng.step()                       # prefill + first token
    eng.step()                       # decoding now
    assert eng.requests[r_dec].state is RequestState.DECODE
    # queued request behind it with a short TTL
    r_q = eng.add_request([2] * 10, max_new_tokens=4, ttl_s=1.0)
    clock.advance(2.0)               # expires r_q only
    eng.step()
    assert eng.requests[r_q].finish_reason == "expired"
    assert eng.requests[r_dec].state is RequestState.DECODE
    clock.advance(10.0)              # now r_dec expires mid-decode
    eng.step()
    assert eng.requests[r_dec].finish_reason == "expired"
    snap = eng.metrics.snapshot()
    assert snap["deadline_expired"] == 2
    # expired requests donated their valid KV: tree holds pages, and
    # dropping it returns the pool to zero
    assert eng.allocator.num_used == eng.radix.num_cached_pages
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown()


def test_abort_in_every_state_and_donation(model):
    eng = ServingEngine(model, **KW)
    prompts = _reqs(3, seed=1, plen=(16, 17), mnew=(8, 9))
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    assert eng.abort(rids[0])        # queued: never ran
    eng.step()
    assert eng.requests[rids[0]].finish_reason == "abort"
    # now abort one decoding request; the other must be unaffected
    eng.step()
    assert eng.abort(rids[1])
    solo = _baseline(model, prompts[2:3])
    out = eng.run()
    assert eng.requests[rids[1]].finish_reason == "abort"
    assert len(out[rids[1]]) < prompts[1][1]   # stopped early
    assert out[rids[2]] == solo[0]             # survivor bit-identical
    assert eng.metrics.counters["requests_aborted"] == 2
    # aborted decoding request donated its computed full pages
    assert eng.radix.num_cached_pages > 0
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    # unknown / finished ids
    assert not eng.abort(99999)
    assert not eng.abort(rids[2])
    eng.shutdown()


def test_admission_control_sheds_with_typed_error(model):
    eng = ServingEngine(model, max_queue_len=2, **KW)
    eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.add_request([1, 2, 4], max_new_tokens=2)
    with pytest.raises(EngineOverloaded) as ei:
        eng.add_request([1, 2, 5], max_new_tokens=2)
    assert ei.value.max_queue_len == 2
    assert ei.value.queue_depth == 2
    assert eng.metrics.counters["requests_shed"] == 1
    # shed request is not tracked anywhere
    assert len(eng.requests) == 2
    # queue drains -> admission reopens
    eng.run()
    rid = eng.add_request([1, 2, 5], max_new_tokens=2)
    assert len(eng.run()[rid]) == 2
    eng.shutdown()


def test_preemption_requeue_bypasses_admission_bound(model):
    """A preempted request re-enters the head of the queue even when
    the queue is at its admission bound: it was admitted once, and
    shedding accepted work would break FCFS completion."""
    eng = ServingEngine(model, num_pages=9, page_size=8,
                        token_budget=64, batch_buckets=[4],
                        prefill_buckets=[16, 32], pages_buckets=[2, 4],
                        temperature=0.0, enable_prefix_cache=False,
                        max_queue_len=4)
    rng = np.random.RandomState(9)
    rids = [eng.add_request(rng.randint(0, 128, (14,)).tolist(),
                            max_new_tokens=12) for _ in range(4)]
    out = eng.run()
    assert eng.scheduler.num_preemptions >= 1
    assert all(len(out[r]) == 12 for r in rids)
    assert eng.allocator.num_used == 0
    eng.shutdown()


# ------------------------------------------------------------ supervisor
def test_transient_step_failures_retry_bit_identical(model):
    prompts = _reqs(6, seed=3)
    want = _baseline(model, prompts)
    eng = ServingEngine(model, retry_policy=NOSLEEP, **KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    with faults.injected("serving.engine.decode_step",
                         exc=TransientDeviceError("UNAVAILABLE: injected"),
                         times=3, after=2), \
         faults.injected("serving.engine.prefill_chunk",
                         exc=TransientDeviceError("injected connection loss"),
                         times=2, after=1):
        out = eng.run()
    got = {i: out[r] for i, r in enumerate(rids)}
    assert got == want                       # retries are invisible
    assert eng.metrics.counters["step_retries"] == 5
    assert eng.metrics.counters["requests_quarantined"] == 0
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.shutdown()


def test_retry_backoff_is_capped_exponential():
    sleeps = []
    pol = RetryPolicy(max_retries=5, base_s=0.1, factor=2.0, cap_s=0.35,
                      sleep=sleeps.append)
    from paddle_tpu.serving import StepSupervisor
    sup = StepSupervisor(policy=pol)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 5:
            raise TransientDeviceError("UNAVAILABLE")
        return "ok"

    assert sup.run(flaky) == "ok"
    assert sup.num_retries == 4
    np.testing.assert_allclose(sleeps, [0.1, 0.2, 0.35, 0.35])


def test_exhausted_retries_drain_to_snapshot(model):
    eng = ServingEngine(model, retry_policy=NOSLEEP, **KW)
    rid = eng.add_request([1] * 8, max_new_tokens=4)
    with faults.injected("serving.engine.prefill_chunk",
                         exc=TransientDeviceError("UNAVAILABLE: down"),
                         times=-1):
        with pytest.raises(EngineFailure) as ei:
            eng.run()
    assert ei.value.snapshot is not None
    assert [r["request_id"] for r in ei.value.snapshot["requests"]] == [rid]
    assert eng.failed
    assert eng.metrics.counters["engine_failures"] == 1
    assert eng.metrics.counters["step_retries"] == NOSLEEP.max_retries
    # a failed engine refuses further work
    with pytest.raises(EngineFailure):
        eng.add_request([1, 2], max_new_tokens=1)
    with pytest.raises(EngineFailure):
        eng.step()
    eng.shutdown()


def test_retry_gate_refuses_when_donated_buffers_deleted(model):
    """TPU donation hazard: when a failed launch has already consumed
    the donated K/V caches, the supervisor must NOT re-pass the deleted
    arrays — it fails over to the snapshot path instead of retrying.
    (CPU never donates, so the hazard is simulated via the engine's
    `_caches_alive` gate.)"""
    eng = ServingEngine(model, retry_policy=NOSLEEP, **KW)
    rid = eng.add_request([1] * 8, max_new_tokens=4)
    eng._caches_alive = lambda: False        # as after a consumed donation
    eng.supervisor.retryable = eng._caches_alive
    with faults.injected("serving.engine.prefill_chunk",
                         exc=TransientDeviceError("UNAVAILABLE: mid-run"),
                         times=1):
        with pytest.raises(EngineFailure) as ei:
            eng.run()
    # zero retries happened: the transient went straight to the snapshot
    assert eng.metrics.counters["step_retries"] == 0
    assert [r["request_id"] for r in ei.value.snapshot["requests"]] == [rid]
    eng.shutdown()


# ----------------------------------------------------------- quarantine
def test_injected_nan_quarantines_one_request(model):
    prompts = _reqs(6, seed=5, mnew=(6, 7))
    want = _baseline(model, prompts)
    eng = ServingEngine(model, **KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    # poison row 1 of the first decode batch
    with faults.injected("serving.engine.nan_logits", payload=[1]):
        out = eng.run()
    bad = [r for r in rids if eng.requests[r].finish_reason
           == "quarantined"]
    assert len(bad) == 1
    assert eng.metrics.counters["requests_quarantined"] == 1
    # every other request is bit-identical to the no-fault run
    for i, r in enumerate(rids):
        if r not in bad:
            assert out[r] == want[i], f"survivor {r} diverged"
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown()


def test_genuine_nan_weight_quarantines_via_in_graph_check():
    """A NaN that really flows through the network trips the in-graph
    finiteness flags (no injection): the request is quarantined at its
    first chunk and its pages are NOT donated to the radix tree."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    paddle.seed(1)
    bad_model = LlamaForCausalLM(cfg)
    w = next(iter(bad_model.parameters()))
    w._data = w._data * np.float32("nan")
    eng = ServingEngine(bad_model, num_pages=32, page_size=8,
                        token_budget=32, batch_buckets=[4],
                        prefill_buckets=[16], pages_buckets=[4],
                        temperature=0.0)
    rid = eng.add_request([1] * 10, max_new_tokens=4)
    eng.run()
    assert eng.requests[rid].finish_reason == "quarantined"
    assert eng.requests[rid].output_ids == []
    assert eng.radix.num_cached_pages == 0    # poisoned KV never donated
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown()


# --------------------------------------------------- allocator OOM fault
def test_injected_allocator_oom_degrades_via_preemption(model):
    prompts = _reqs(5, seed=11, mnew=(5, 8))
    want = _baseline(model, prompts)
    eng = ServingEngine(model, **KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    with faults.injected("serving.kv.alloc_page", payload=True,
                         prob=0.2, times=8, seed=13):
        out = eng.run()
    assert faults.fired_counts().get("serving.kv.alloc_page", 0) > 0
    # OOM faults cause preemption/retry churn, never failure: everything
    # completes bit-identically (greedy + pinned buckets)
    for i, r in enumerate(rids):
        assert out[r] == want[i]
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown()


def test_radix_donation_fault_never_leaks(model):
    prompts = _reqs(5, seed=17)
    eng = ServingEngine(model, **KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    with faults.injected("serving.radix.insert",
                         exc=RuntimeError("injected donation failure"),
                         times=-1):
        out = eng.run()
    assert all(len(out[r]) == prompts[i][1] for i, r in enumerate(rids))
    # nothing was donated, so the pool is empty with NO tree reset
    assert eng.radix.num_cached_pages == 0
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown()


# -------------------------------------------------------- deadline storm
def test_deadline_storm_fault_expires_and_reclaims(model):
    clock = FakeClock()
    eng = ServingEngine(model, clock=clock, default_ttl_s=100.0, **KW)
    prompts = _reqs(6, seed=19)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    eng.step(); eng.step()
    # the storm jumps the engine clock past every deadline
    with faults.injected("serving.engine.deadline_storm", payload=1000.0):
        out = eng.run()
    assert all(eng.requests[r].finish_reason == "expired" for r in rids
               if eng.requests[r].finish_reason != "length")
    assert eng.metrics.counters["deadline_expired"] >= 1
    assert eng.metrics.counters["deadline_expired"] + \
        eng.metrics.counters["requests_finished"] == len(rids)
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown()


# ------------------------------------------------------- snapshot/resume
def test_kill_and_resume_completes_with_correct_outputs(model):
    """Acceptance: an engine forced into an unrecoverable step error
    snapshots; a fresh engine resumed from the (JSON-round-tripped)
    snapshot completes every request with outputs bit-identical to an
    uninterrupted run."""
    prompts = _reqs(8, seed=23, mnew=(5, 10))
    want = _baseline(model, prompts)

    eng = ServingEngine(model, **KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    for _ in range(4):               # mixed states: some decode, some wait
        eng.step()
    with faults.injected("serving.engine.decode_step",
                         exc=RuntimeError("INTERNAL: device wedged"),
                         times=-1):
        with pytest.raises(EngineFailure) as ei:
            drain(eng)
    snap = json.loads(json.dumps(ei.value.snapshot))   # serializable
    # nothing finished in 4 steps (min max_new_tokens is 5). The failing
    # step's own launch had been enqueued by the fourth step, before the
    # fault was armed: its tokens are emitted before the engine drains
    # (ISSUE 34), so a request of 5 tokens ends there, whole, and
    # everything else is in the snapshot, mid-flight tokens included
    done = {r: eng.requests[r].output_ids for r in rids
            if eng.requests[r].state is RequestState.FINISHED}
    assert all(eng.requests[r].finish_reason == "length" for r in done)
    eng.shutdown()

    eng2 = ServingEngine.from_snapshot(model, snap, **KW)
    assert set(eng2.requests) == set(rids) - set(done)
    out2 = eng2.run()    # run() folds restored output_ids into its result
    out2.update(done)
    for i, r in enumerate(rids):
        assert out2[r] == want[i], f"request {r} diverged across resume"
    eng2.reset_prefix_cache()
    assert eng2.allocator.num_used == 0
    eng2.allocator.check_invariants()
    # restored ids never collide with new ones
    fresh = eng2.add_request([1, 2, 3], max_new_tokens=1)
    assert fresh > max(rids)
    eng2.shutdown()


def test_snapshot_preserves_deadlines_and_aborts(model):
    clock = FakeClock()
    eng = ServingEngine(model, clock=clock, **KW)
    r1 = eng.add_request([1] * 8, max_new_tokens=6, ttl_s=50.0)
    r2 = eng.add_request([2] * 8, max_new_tokens=6)
    eng.step()
    clock.advance(10.0)
    eng.abort(r2)
    snap = eng.snapshot(reason="test")
    recs = {r["request_id"]: r for r in snap["requests"]}
    assert recs[r1]["deadline_remaining_s"] == pytest.approx(40.0)
    assert recs[r2]["aborted"] is True
    clock2 = FakeClock()
    eng2 = ServingEngine.from_snapshot(model, snap, clock=clock2, **KW)
    clock2.advance(45.0)             # past r1's restored deadline
    eng2.run()
    assert eng2.requests[r1].finish_reason == "expired"
    assert eng2.requests[r2].finish_reason == "abort"
    eng.shutdown(); eng2.shutdown()


# ------------------------------------------------- preemption storm (SAT)
def test_preemption_storm_terminates_and_preserves_fcfs(model):
    """Satellite: repeated preempt-by-eviction under near-full KV with
    the radix cache ENABLED terminates (no admission/eviction livelock)
    and surviving requests complete in FCFS order."""
    eng = ServingEngine(model, num_pages=9, page_size=8,   # 8 usable
                        token_budget=64, batch_buckets=[4],
                        prefill_buckets=[16, 32], pages_buckets=[2, 4],
                        temperature=0.0)
    rng = np.random.RandomState(29)
    rids = [eng.add_request(rng.randint(0, 128, (14,)).tolist(),
                            max_new_tokens=12) for _ in range(6)]
    out = eng.run()                  # run() raises on failure to drain
    assert eng.scheduler.num_preemptions >= 2
    assert all(len(out[r]) == 12 for r in rids)
    # FCFS: completion order == arrival order (equal token budgets)
    assert eng._finished_order == sorted(rids)
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown()
