"""The plain decode loop runs one launch ahead (ISSUE 34): `ServingEngine`
enqueues decode launch n+1 before it fetches launch n's tokens, taking
the next input ids from launch n's tokens on the device. What these
tests hold is the contract of `ServingEngine.step()`'s docstring: the
same tokens as the serial order, every token returned once, nothing of a
row that ended while a launch was in flight, nothing half-made seen from
outside `step()`, the serial order wherever a step is not quiet.

CPU-only, pinned single-bucket grids (the SERVING.md determinism
contract: bit-identity claims hold within one program shape).
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (EngineFailure, NgramProposer, RequestState,
                                RetryPolicy, ServingEngine,
                                TransientDeviceError)
from paddle_tpu.serving.kv_cache import BlockAllocator
from paddle_tpu.serving.radix_cache import RadixCache
from paddle_tpu.serving.scheduler import Request, Scheduler
from paddle_tpu.utils import faults


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


KW = dict(num_pages=96, page_size=8, token_budget=64, batch_buckets=[8],
          prefill_buckets=[32], pages_buckets=[8], temperature=0.0)
NOSLEEP = RetryPolicy(max_retries=3, base_s=0.0, sleep=lambda s: None)


def _reqs(n, seed, plen=(4, 24), mnew=(6, 14)):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, 128, (rng.randint(*plen),)).tolist(),
             int(rng.randint(*mnew))) for _ in range(n)]


class Served:
    """One engine and what its `step()`s returned, request by request."""

    def __init__(self, model, **kw):
        self.eng = ServingEngine(model, **{**KW, **kw})
        self.rids, self.got = [], {}

    def add(self, reqs, eos=None):
        for p, m in reqs:
            e = None if eos is None else eos.get(len(self.rids))
            rid = self.eng.add_request(p, max_new_tokens=m, eos_token_id=e)
            self.rids.append(rid)
            self.got[rid] = []
        return self

    def step(self):
        out = self.eng.step()
        for rid, tok in out:
            self.got[rid].append(tok)
        return out

    def until(self, done):
        steps = 0
        while not done():
            assert steps < 500
            self.step()
            steps += 1
        return self

    def until_in_flight(self, n_decoding=1):
        """Stop BETWEEN two steps with a decode launch enqueued ahead."""
        eng = self.eng
        return self.until(lambda: eng._flight is not None and
                          len(eng.scheduler.running) >= n_decoding)

    def drain(self):
        return self.until(lambda: not self.eng.has_work())

    def tokens(self):
        """By arrival; what was streamed is what the requests hold."""
        for rid in self.rids:
            req = self.eng.requests.get(rid)
            if req is not None:
                assert req.output_ids == self.got[rid], rid
        return [self.got[rid] for rid in self.rids]

    def empty(self):
        eng = self.eng
        assert eng._flight is None and not eng.has_work()
        eng.reset_prefix_cache()
        assert eng.allocator.num_used == 0
        eng.allocator.check_invariants()
        eng.shutdown()


def _staggered(model, reqs, eos=None, **kw):
    """Four requests up front, the rest once decoding is under way."""
    s = Served(model, **kw).add(reqs[:4], eos)
    for _ in range(3):
        s.step()
    return s.add(reqs[4:], eos).drain()


def _first_fresh(toks, lo=2):
    """Index of the first token from `lo` on that no earlier token
    equals: an `eos_token_id` set to it stops the request exactly there."""
    return next(j for j in range(lo, len(toks)) if toks[j] not in toks[:j])


# ------------------------------------------------------- (a) the same tokens
def test_mixed_batch_is_the_dense_path_and_the_solo_order(model):
    reqs = _reqs(7, seed=11)
    plain = _staggered(model, reqs)
    free = plain.tokens()
    assert [len(t) for t in free] == [m for _, m in reqs]
    plain.empty()
    # two requests end on an `eos_token_id` in the middle of the batch,
    # which only the token itself can tell: each rides one launch more
    eos = {i: free[i][_first_fresh(free[i])] for i in (1, 4)}
    batch = _staggered(model, reqs, eos)
    got = batch.tokens()
    c = batch.eng.metrics.counters
    for i, (p, m) in enumerate(reqs):
        want = free[i][:_first_fresh(free[i]) + 1] if i in eos else free[i]
        assert got[i] == want, i
        # the dense-cache path of the model itself
        ref = model.generate(paddle.to_tensor(np.asarray([p])),
                             max_new_tokens=len(want), temperature=0.0)
        assert np.asarray(ref._data)[0, len(p):].tolist() == want, i
        # and the same request served alone: a batch of one goes on or
        # ends by what the host already knows, which is the serial order
        solo = Served(model).add([(p, m)], {0: eos.get(i)}).drain()
        assert solo.tokens() == [want], i
        solo.empty()
    assert {batch.eng.requests[batch.rids[i]].finish_reason
            for i in eos} == {"stop"}
    # the loop did run ahead, and no token of a dropped row was counted:
    # all but each request's first token came from decode launches
    assert c["decode_launches_ahead"] > c["decode_launches"] // 2
    assert c["decode_tokens"] == sum(len(t) - 1 for t in got)
    batch.empty()


def test_sampling_is_reproducible_from_the_seed(model):
    reqs = _reqs(6, seed=13)
    kw = dict(temperature=0.8, top_k=20, seed=7)
    runs = []
    for _ in range(2):
        s = _staggered(model, reqs, **kw)
        runs.append(s.tokens())
        assert s.eng.metrics.counters["decode_launches_ahead"] > 0
        s.empty()
    assert runs[0] == runs[1]
    assert runs[0] != _staggered(model, reqs, **{**kw, "seed": 8}).tokens()


# -------------------------------- (b) a row that ends with a launch in flight
@pytest.mark.parametrize("how", ["eos", "abort", "expired", "quarantined"])
def test_a_row_that_ends_in_flight_emits_nothing_more(model, how):
    reqs = _reqs(4, seed=17, mnew=(10, 14))
    base = Served(model).add(reqs).drain()
    free = base.tokens()
    base.empty()

    clock = FakeClock()
    cut = _first_fresh(free[1], lo=4)
    s = Served(model, clock=clock)
    s.add(reqs, {1: free[1][cut]} if how == "eos" else None)
    victim = s.rids[1]
    req = s.eng.requests[victim]
    if how == "expired":
        req.deadline = 100.0
    s.until_in_flight(n_decoding=4)
    s.until(lambda: len(s.got[victim]) >= 3)
    assert s.eng._flight is not None and req in s.eng._flight.reqs
    if how == "eos":
        s.until(lambda: req.state is RequestState.FINISHED)
        want = free[1][:cut + 1]
        assert req.finish_reason == "stop"
    else:
        want = list(s.got[victim])
        if how == "abort":
            assert s.eng.abort(victim)
        elif how == "expired":
            clock.t = 200.0
        if how == "quarantined":
            with faults.injected("serving.engine.nan_logits",
                                 payload=lambda rows: [rows.index(req)]):
                s.step()
        else:
            s.step()
        assert req.state is RequestState.FINISHED
        assert req.finish_reason == how
    # the launch in flight when it ended still carried it, for nothing
    assert s.got[victim] == want
    valid = list(reqs[1][0]) + want
    computed = len(valid) - 1      # the last token's K/V was never kept
    s.drain()
    got = s.tokens()
    assert got[1] == want
    assert [got[i] for i in (0, 2, 3)] == [free[i] for i in (0, 2, 3)]
    c = s.eng.metrics.counters
    # `decode_tokens` counts a launch's live rows, so the launch that
    # found the row not finite counted it, as ever; the launch in flight
    # then, which carried it for nothing, counted nothing
    assert c["decode_tokens"] == sum(len(t) - 1 for t in got) + \
        (how == "quarantined")
    # the radix tree holds nothing of it beyond what was computed
    # (nothing at all of a quarantined request) ...
    _, m = s.eng.radix.match(valid + [1, 2, 3], promote_budget=0)
    assert m <= (0 if how == "quarantined" else computed)
    if how != "quarantined":
        # ... and what it holds is sound: served again from the cached
        # prefix, the request goes on as the undisturbed run did
        again = free[1][len(want):]
        rid = s.eng.add_request(valid, max_new_tokens=len(again))
        s.rids.append(rid)
        s.got[rid] = []
        s.drain()
        assert s.got[rid] == again
        assert c["prefix_hits"] >= 1
    s.empty()


# ------------------------------ (c) nothing half-made outside of `step()`
def test_snapshot_with_a_launch_in_flight_loses_and_repeats_nothing(model):
    reqs = _reqs(5, seed=19, mnew=(8, 14))
    base = Served(model).add(reqs).drain()
    free = base.tokens()
    base.empty()

    s = Served(model).add(reqs).until_in_flight(n_decoding=5)
    so_far = [list(s.got[r]) for r in s.rids]
    snap = json.loads(json.dumps(s.eng.snapshot()))
    # the snapshot holds what `step()` has returned, no more and no less
    # (the launch in flight has returned nothing): it took nothing back
    assert [r["output_ids"] for r in snap["requests"]] == so_far
    assert s.eng._flight is not None
    # whoever resumes it computes the token in flight again
    eng2 = ServingEngine.from_snapshot(model, snap, **KW)
    out2 = eng2.run()
    assert [out2[r] for r in s.rids] == free
    eng2.reset_prefix_cache()
    assert eng2.allocator.num_used == 0
    eng2.shutdown()
    # and the engine that was asked goes on as if it had not been
    assert s.drain().tokens() == free
    c = s.eng.metrics.counters
    assert c["decode_launches_ahead"] == c["decode_launches"] - 1
    s.empty()


def test_vacate_with_a_launch_in_flight(model):
    reqs = _reqs(5, seed=23, mnew=(8, 14))
    base = Served(model).add(reqs).drain()
    free = base.tokens()
    base.empty()

    s = Served(model).add(reqs).until_in_flight(n_decoding=5)
    so_far = [list(s.got[r]) for r in s.rids]
    snap = s.eng.snapshot()
    s.eng.vacate()
    eng = s.eng
    assert eng._flight is None and not eng.has_work()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    assert s.step() == []                    # nothing is held back
    other = ServingEngine(model, **KW)
    other.adopt_requests(snap["requests"])
    rest = {rid: [] for rid in s.rids}
    while other.has_work():
        for rid, tok in other.step():
            rest[rid].append(tok)
    assert [so_far[i] + rest[r] for i, r in enumerate(s.rids)] == free
    other.reset_prefix_cache()
    assert other.allocator.num_used == 0
    other.shutdown()
    eng.shutdown()


def _export(eng, first):
    n, payloads = eng.export_prefix(first)
    assert n >= eng.page_size and payloads


def _adopt(eng, first):
    n, payloads = eng.export_prefix(first)
    eng.release_prefix(first, drop=True)
    assert eng.adopt_prefix(first[:n], payloads) == len(payloads)


@pytest.mark.parametrize("call", [
    _export, _adopt,
    lambda eng, first: eng.release_prefix(first),
    lambda eng, first: eng.reset_prefix_cache()],
    ids=["export_prefix", "adopt_prefix", "release_prefix",
         "reset_prefix_cache"])
def test_prefix_calls_with_a_launch_in_flight(model, call):
    reqs = _reqs(5, seed=29, mnew=(8, 14))
    reqs[0] = (reqs[0][0] + [5] * 16, 3)     # ends early, donates 2 pages
    base = Served(model).add(reqs).drain()
    free = base.tokens()
    base.empty()

    s = Served(model).add(reqs)
    first = s.eng.requests[s.rids[0]]
    s.until(lambda: first.state is RequestState.FINISHED)
    s.until_in_flight(n_decoding=4)
    call(s.eng, reqs[0][0])
    # the launch in flight was taken back: the engine is where the serial
    # order stands after the last token it returned
    assert s.eng._flight is None
    assert not any(r.reserved_ahead for r in s.eng.scheduler.running)
    assert all(r.seq.num_tokens == len(r.prompt_ids) + len(r.output_ids) - 1
               for r in s.eng.scheduler.running)
    s.eng.allocator.check_invariants()
    assert s.drain().tokens() == free
    s.empty()


def test_shutdown_with_a_launch_in_flight(model):
    reqs = _reqs(3, seed=31, mnew=(8, 14))
    base = Served(model).add(reqs).drain()
    free = base.tokens()
    base.empty()

    s = Served(model).add(reqs).until_in_flight(n_decoding=3)
    so_far = [list(s.got[r]) for r in s.rids]
    s.eng.shutdown()
    assert s.eng._flight is None
    assert all(not r.reserved_ahead for r in s.eng.requests.values())
    snap = s.eng.snapshot()
    assert [r["output_ids"] for r in snap["requests"]] == so_far
    eng2 = ServingEngine.from_snapshot(model, snap, **KW)
    out2 = eng2.run()
    assert [out2[r] for r in s.rids] == free
    eng2.shutdown()


# --------------------------------------- (d) faults at the launch ahead
def test_transient_fault_at_the_launch_ahead_is_retried_unseen(model):
    reqs = _reqs(5, seed=37)
    base = Served(model).add(reqs).drain()
    free = base.tokens()
    base.empty()

    s = Served(model, retry_policy=NOSLEEP).add(reqs)
    s.until_in_flight(n_decoding=5)
    # this step's own launch is in flight already: the next launch the
    # fault point sees is the one enqueued ahead
    with faults.injected("serving.engine.decode_step",
                         exc=TransientDeviceError("UNAVAILABLE: injected"),
                         times=2):
        s.step()
    assert faults.fired_counts()["serving.engine.decode_step"] == 2
    assert s.eng._flight is not None and s.eng._flight.ahead
    assert s.drain().tokens() == free
    c = s.eng.metrics.counters
    assert c["step_retries"] == 2 and c["requests_quarantined"] == 0
    assert c["decode_launches_ahead"] == c["decode_launches"] - 1
    s.empty()


@pytest.mark.parametrize("times", [1, -1], ids=["once", "lasting"])
def test_poison_fault_at_the_launch_ahead_is_isolated(model, times):
    reqs = _reqs(5, seed=41, mnew=(9, 14))
    base = Served(model).add(reqs).drain()
    free = base.tokens()
    base.empty()

    s = Served(model).add(reqs).until_in_flight(n_decoding=5)
    with faults.injected("serving.engine.decode_step",
                         exc=FloatingPointError("injected nan"),
                         times=times):
        before = [len(s.got[r]) for r in s.rids]
        s.step()
        # the step's own tokens came out, the launch ahead was taken back
        assert [len(s.got[r]) for r in s.rids] == [n + 1 for n in before]
        assert s.eng._flight is None and not s.eng.failed
        # and the next step stands in the serial order, where a launch
        # that fails again is isolated row by row as ever
        s.step()
        assert s.eng.timeline()[-1]["decode_ahead"] is False
    got = s.drain().tokens()
    c = s.eng.metrics.counters
    if times == 1:
        assert got == free and c["requests_quarantined"] == 0
    else:
        assert c["requests_quarantined"] == 5
        assert got == [t[:n + 1] for t, n in zip(free, before)]
    s.empty()


def test_fatal_fault_at_the_launch_ahead_drains_after_the_emission(model):
    reqs = _reqs(4, seed=43, mnew=(9, 14))
    base = Served(model).add(reqs).drain()
    free = base.tokens()
    base.empty()

    s = Served(model).add(reqs).until_in_flight(n_decoding=4)
    before = [len(s.got[r]) for r in s.rids]
    with faults.injected("serving.engine.decode_step",
                         exc=RuntimeError("INTERNAL: device wedged"),
                         times=-1):
        with pytest.raises(EngineFailure) as ei:
            s.step()
    snap = json.loads(json.dumps(ei.value.snapshot))
    # the tokens already computed are in the drained state
    assert [len(r["output_ids"]) for r in snap["requests"]] == \
        [n + 1 for n in before]
    assert s.eng._flight is None
    s.eng.vacate()
    assert s.eng.allocator.num_used == 0
    s.eng.shutdown()
    eng2 = ServingEngine.from_snapshot(model, snap, **KW)
    out2 = eng2.run()
    assert [out2[r] for r in s.rids] == free
    eng2.shutdown()


# ---------------------------------------------- (e) when it runs ahead
def test_a_quiet_run_is_ahead_in_all_but_its_first_launch(model):
    s = Served(model).add(_reqs(1, seed=47, mnew=(12, 13))).drain()
    c = s.eng.metrics.counters
    assert c["decode_launches"] == 11      # the prefill gave the first token
    assert c["decode_launches_ahead"] == 10
    ahead = [r["decode_ahead"] for r in s.eng.timeline() if r["decode_batch"]]
    assert ahead == [False] + [True] * 10
    # a step's record names the launch whose tokens it returned
    assert all(r["programs"] == ["decode:B8:P8"] and r["tokens_out"] == 1
               for r in s.eng.timeline() if r["decode_batch"])
    s.empty()


@pytest.mark.parametrize("kw", [
    {"decode_steps": 4}, {"proposer": NgramProposer, "spec_k": 2}],
    ids=["multi", "spec"])
def test_the_other_decode_families_keep_the_serial_order(model, kw):
    if "proposer" in kw:
        kw = dict(kw, proposer=kw["proposer"]())
    s = Served(model, **kw).add(_reqs(4, seed=53))
    while s.eng.has_work():
        s.step()
        assert s.eng._flight is None
    c = s.eng.metrics.counters
    assert c["decode_launches_ahead"] == 0
    assert not any(r["decode_ahead"] for r in s.eng.timeline())
    s.empty()


def test_a_starved_pool_takes_the_serial_order_and_the_same_tokens(model):
    """A slot that needs a preemption is not reserved ahead: that step
    is the scheduler's, in its own order."""
    rng = np.random.RandomState(9)
    reqs = [(rng.randint(0, 128, (14,)).tolist(), 12) for _ in range(4)]
    kw = dict(batch_buckets=[4], prefill_buckets=[32], pages_buckets=[4],
              enable_prefix_cache=False)
    roomy = Served(model, **kw).add(reqs).drain()
    free = roomy.tokens()
    c = roomy.eng.metrics.counters
    assert c["requests_preempted"] == 0
    assert c["decode_launches_ahead"] == c["decode_launches"] - 1
    roomy.empty()
    tight = Served(model, num_pages=9, **kw).add(reqs).drain()
    c = tight.eng.metrics.counters
    assert tight.tokens() == free
    assert c["requests_preempted"] >= 1
    assert 0 < c["decode_launches_ahead"] < c["decode_launches"] - 1
    tight.empty()


def test_a_pool_full_of_donated_prefixes_still_runs_ahead(model):
    """A server's steady state: the free list is dry and every new page
    comes out of the radix tree's unused prefixes. That is the ladder's
    first rung and decides nothing about live work, so it is taken ahead
    (ISSUE 34's first chip run read 88.6 % ahead while it was not)."""
    rng = np.random.RandomState(59)
    s = Served(model, num_pages=20)
    for _ in range(6):                      # waves of two, distinct prompts
        s.add([(rng.randint(0, 128, (17,)).tolist(), 14) for _ in range(2)])
        s.drain()
    c = s.eng.metrics.counters
    assert s.eng.radix.num_evicted_pages > 0
    assert c["requests_preempted"] == 0
    # each wave's first launch stands in the serial order, no other
    assert c["decode_launches"] - c["decode_launches_ahead"] == 6
    s.empty()


def test_reserve_ahead_is_quiet_or_not_at_all():
    """`Scheduler.reserve_ahead` takes every slot or none; for a slot it
    drops a cached prefix nobody uses, and nothing else: nobody is
    preempted, no page is copied. `schedule()` appends nothing more for
    a request it served."""
    alloc = BlockAllocator(num_pages=10, page_size=8)    # 9 to hand out
    tree = RadixCache(alloc)
    sched = Scheduler(alloc, max_batch_size=4, prefix_cache=tree)

    def decoding(tok):
        req = Request([tok] * 16, max_new_tokens=8)
        req.seq = alloc.alloc_sequence(16)
        req.output_ids, req.state = [7], RequestState.DECODE
        req.num_computed = 16
        sched.running.append(req)
        return req

    rows = [decoding(1 + i) for i in range(3)]
    sched.finish(decoding(9), "length")       # its two pages are donated
    lens = lambda: [r.seq.num_tokens for r in rows]
    # every row's next slot opens a page: one is free, and the other two
    # come from the cached prefix that no request uses
    assert alloc.num_free == 1 and tree.num_cached_pages == 2
    assert sched.reserve_ahead(rows)
    assert lens() == [17] * 3 and all(r.reserved_ahead for r in rows)
    assert tree.num_cached_pages == 0 and alloc.num_free == 0
    sched.release_ahead(rows)
    assert lens() == [16] * 3 and alloc.num_free == 3
    assert not any(r.reserved_ahead for r in rows)
    # a pool that is dry with nothing cached: nobody is preempted for a
    # slot ahead, and what was reserved before the dry one is given back
    held = alloc.alloc_sequence(16)
    assert alloc.num_free == 1
    assert not sched.reserve_ahead(rows)
    assert lens() == [16] * 3 and alloc.num_free == 1
    assert not any(r.reserved_ahead for r in rows) and len(sched.running) == 3
    assert sched.reserve_ahead(rows[:1])
    assert rows[0].reserved_ahead and lens() == [17, 16, 16]
    sched.release_ahead(rows)
    alloc.free_sequence(held)
    # a slot in a page shared with a fork would have to be copied
    alloc.truncate_sequence(rows[1].seq, 12)
    fork = alloc.fork_sequence(rows[1].seq)
    assert alloc.append_copies(rows[1].seq)
    assert not sched.reserve_ahead(rows[1:2]) and lens()[1] == 12
    alloc.free_sequence(fork)
    assert sched.reserve_ahead(rows[1:2]) and lens()[1] == 13
    # `schedule()` appends for the rows that hold no slot yet, and only
    # for them; a request that ends gives its slot back with its pages
    alloc.truncate_sequence(rows[2].seq, 10)
    step = sched.schedule()
    assert step.decodes == rows and not step.preempted
    assert lens() == [17, 13, 11]
    assert not any(r.reserved_ahead or r.pending_copies for r in rows)
    assert sched.reserve_ahead(rows[2:])
    sched.finish(rows[2], "abort")
    assert not rows[2].reserved_ahead
    for req in rows[:2]:
        sched.finish(req, "length")
    tree.clear()
    assert alloc.num_used == 0
    alloc.check_invariants()
