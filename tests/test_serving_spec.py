"""Speculative decoding acceptance (ISSUE 5): NgramProposer and
DraftModelProposer over the bucketed ("verify", B, K, P) program, with
KV rollback through `BlockAllocator.truncate_sequence`.

The bar (ISSUE acceptance criteria): greedy spec-decode output is
bit-identical to plain decode for a >= 16-request mixed-prompt workload
while acceptance > 0 and mean emitted tokens/verify-step > 1 on a
repetitive workload; rollback leaks zero pages after a forced
all-reject step and across mid-flight abort / snapshot-resume with
drafts in flight. Single-bucket grids are pinned where cross-run
identity is asserted (SERVING.md determinism contract); spec-vs-plain
greedy identity is an argmax-stability property across program shapes,
the same property test_engine_matches_eager_generate_greedy already
pins for the paged-vs-dense pair.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (BlockAllocator, DraftModelProposer,
                                NgramProposer, ServingEngine)
from paddle_tpu.utils import faults

from _engine_steps import step_until


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    paddle.seed(0)
    return LlamaForCausalLM(cfg)


ENGINE_KW = dict(num_pages=96, page_size=8, token_budget=96,
                 batch_buckets=[16], prefill_buckets=[8, 16, 32, 64],
                 pages_buckets=[2, 4, 8], temperature=0.0)


def _mixed_prompts(n=16, seed=42):
    """Mixed lengths, half of them repetitive (the ngram-friendly
    regime), half random."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        if i % 2 == 0:
            cycle = rng.randint(0, 128, (rng.randint(2, 5),)).tolist()
            p = (cycle * 8)[:rng.randint(8, 24)]
        else:
            p = rng.randint(0, 128, (rng.randint(2, 25),)).tolist()
        out.append((p, int(rng.randint(4, 14))))
    return out


# --------------------------------------------------------------- proposers
def test_ngram_proposer_prompt_lookup():
    p = NgramProposer(max_ngram=3, min_ngram=1)
    # longest suffix n-gram wins; continuation follows the match
    assert p.propose_for([1, 2, 3, 9, 1, 2, 3], k=2) == [9, 1]
    # most recent occurrence preferred
    assert p.propose_for([5, 7, 5, 8, 5], k=1) == [8]
    # cyclic self-overlap drafts the cycle forward (up to the history
    # end — the continuation never wraps past what was actually seen)
    assert p.propose_for([1, 2, 1, 2, 1], k=4) == [2, 1]
    assert p.propose_for([1, 2, 1, 2, 1, 2, 1], k=4) == [2, 1, 2, 1]
    # no recurrence -> no draft; k bounds the draft
    assert p.propose_for([1, 2, 3, 4], k=4) == []
    assert len(p.propose_for([1, 2] * 10, k=3)) == 3
    with pytest.raises(ValueError):
        NgramProposer(max_ngram=0)


# ------------------------------------------------------- truncate_sequence
def test_truncate_sequence_releases_only_dead_pages():
    a = BlockAllocator(num_pages=16, page_size=8)
    seq = a.alloc_sequence(20)                 # 3 pages
    used = a.num_used
    a.truncate_sequence(seq, 17)               # still 3 pages
    assert a.num_used == used and seq.num_tokens == 17
    a.truncate_sequence(seq, 16)               # exactly 2 pages
    assert a.num_used == used - 1 and len(seq.pages) == 2
    a.truncate_sequence(seq, 3)
    assert a.num_used == used - 2 and len(seq.pages) == 1
    a.truncate_sequence(seq, 0)                # legal, non-terminal
    assert a.num_used == 0 and not seq.freed
    copies = a.append_token(seq)               # still usable
    assert copies == [] and seq.num_tokens == 1
    a.check_invariants()
    with pytest.raises(ValueError):
        a.truncate_sequence(seq, 2)            # beyond current length
    a.free_sequence(seq)
    with pytest.raises(RuntimeError):
        a.truncate_sequence(seq, 0)            # freed is terminal


def test_truncate_sequence_respects_shared_refs():
    """Truncating a sequence that shares pages with a fork only drops
    this sequence's refs — the fork keeps the pages alive (the CoW /
    radix-donation invariant the spec rollback relies on)."""
    a = BlockAllocator(num_pages=16, page_size=8)
    seq = a.alloc_sequence(16)                 # 2 pages
    fork = a.fork_sequence(seq)
    used = a.num_used
    a.truncate_sequence(seq, 0)
    assert a.num_used == used                  # fork still holds both
    a.free_sequence(fork)
    assert a.num_used == 0
    a.check_invariants()


def test_draft_extension_oom_rolls_back_all_or_nothing(model):
    """The rollback-under-OOM fault point: injected allocator OOM mid
    draft-extension must degrade (shorter/zero draft), never leak, and
    never change greedy output."""
    kw = dict(ENGINE_KW, num_pages=24)         # tight pool
    plain = ServingEngine(model, **kw)
    rid = plain.add_request([9, 9, 9, 9] * 4, max_new_tokens=12)
    ref = plain.run()[rid]

    eng = ServingEngine(model, proposer=NgramProposer(), spec_k=4, **kw)
    # the draft storm makes every verify step carry a full-K draft, so
    # extensions cross page boundaries whatever tokens this jax's
    # numerics make the model emit (the ngram drafts alone stopped
    # reaching a boundary, and the OOM path went unexercised)
    with faults.injected("serving.spec.draft_storm", payload=True,
                         times=-1), \
            faults.injected("serving.kv.alloc_page", payload=True,
                            prob=0.5, times=40, seed=3):
        rid = eng.add_request([9, 9, 9, 9] * 4, max_new_tokens=12)
        out = eng.run()[rid]
    assert out == ref
    assert eng.metrics.counters["spec_draft_oom_drops"] >= 1
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown(), plain.shutdown()


# ------------------------------------------------- the acceptance criteria
def test_spec_greedy_identity_16_requests_mixed(model):
    """>= 16 mixed-prompt requests: spec-decode (NgramProposer, K=4)
    emits bit-identical token streams to plain decode, acceptance > 0,
    mean emitted tokens per verify step > 1, full reclamation."""
    prompts = _mixed_prompts(16)

    plain = ServingEngine(model, **ENGINE_KW)
    rids = [plain.add_request(p, max_new_tokens=m) for p, m in prompts]
    ref = plain.run()
    ref = {i: ref[r] for i, r in enumerate(rids)}

    eng = ServingEngine(model, proposer=NgramProposer(), spec_k=4,
                        **ENGINE_KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    out = eng.run()
    out = {i: out[r] for i, r in enumerate(rids)}
    assert out == ref, "spec decode changed greedy tokens"

    snap = eng.metrics.summary()
    assert snap["spec_steps"] > 0
    assert snap["spec_accepted_tokens"] > 0
    assert snap["spec_acceptance_rate"] > 0
    assert snap["spec_tokens_per_step"] > 1.0
    # emitted = every decode-side token; the savings are real launches
    assert snap["spec_steps"] < sum(len(v) for v in out.values())

    # bucket-grid compile bound (verify grid included); the per-family
    # ProgramCache view (ISSUE 8) shows verify programs actually
    # compiled and bounded by their own grid
    assert eng.num_compiled_programs <= eng.max_program_count()
    assert eng.metrics.counters["recompiles"] == eng.num_compiled_programs
    counts = eng.program_counts()
    assert counts["verify"] >= 1
    assert counts["verify"] <= eng.max_program_count("verify")
    assert sum(counts.values()) == eng.num_compiled_programs

    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown(), plain.shutdown()


def test_spec_draft_model_proposer_identity_and_win(model):
    """DraftModelProposer with the TARGET as its own draft: acceptance
    must be ~perfect (the strongest identity cross-check: every draft
    position's verify logits reproduce the decode path's argmax), and
    output stays bit-identical to plain decode."""
    prompts = _mixed_prompts(8, seed=11)
    plain = ServingEngine(model, **ENGINE_KW)
    rids = [plain.add_request(p, max_new_tokens=m) for p, m in prompts]
    ref = plain.run()
    ref = {i: ref[r] for i, r in enumerate(rids)}

    dp = DraftModelProposer(model, num_pages=96, page_size=8,
                            prefill_buckets=[8, 16, 32, 64],
                            batch_buckets=[16], pages_buckets=[2, 4, 8])
    eng = ServingEngine(model, proposer=dp, spec_k=4, **ENGINE_KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    out = eng.run()
    out = {i: out[r] for i, r in enumerate(rids)}
    assert out == ref
    snap = eng.metrics.summary()
    # the draft IS the target: every scored draft token must accept
    assert snap["spec_acceptance_rate"] == 1.0
    assert snap["spec_tokens_per_step"] > 2.0
    assert dp.num_compiled_programs <= dp.max_program_count()
    # terminal requests released their draft-pool state
    assert not dp._states and dp.allocator.num_used == 0
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.shutdown()
    plain.shutdown()


def test_spec_forced_all_reject_rolls_back_zero_leaks(model):
    """A draft-mismatch storm (every draft garbage) forces all-reject
    verify steps: output must stay bit-identical, every rejected
    draft's pages reclaim, invariants hold mid-flight and at drain."""
    plain = ServingEngine(model, **ENGINE_KW)
    rp = plain.add_request([5, 6, 7, 8] * 4, max_new_tokens=10)
    ref = plain.run()[rp]

    eng = ServingEngine(model, proposer=NgramProposer(), spec_k=4,
                        **ENGINE_KW)
    with faults.injected("serving.spec.draft_storm", payload=True,
                         times=-1):
        rid = eng.add_request([5, 6, 7, 8] * 4, max_new_tokens=10)
        steps = 0
        while eng.has_work():
            eng.step()
            eng.allocator.check_invariants()     # invariants EVERY step
            steps += 1
            assert steps < 200
    assert eng.requests[rid].output_ids == ref
    snap = eng.metrics.summary()
    assert snap["spec_accepted_tokens"] == 0     # storm rejected all
    assert snap["spec_rollback_tokens"] > 0
    assert snap["spec_tokens_per_step"] == 1.0
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.shutdown(), plain.shutdown()


def test_spec_abort_and_snapshot_resume_with_drafts_in_flight(model):
    """Mid-flight abort and kill-and-resume while speculation is
    active: the aborted request cancels cleanly at a boundary, the
    snapshot round-trips, the resumed engine completes every request
    with greedy outputs bit-identical to an uninterrupted plain run,
    and zero pages leak anywhere."""
    # long generations so every request is still mid-decode (with
    # drafts in flight) when the abort + snapshot land
    prompts = [(p, 20) for p, _ in _mixed_prompts(6, seed=5)]
    plain = ServingEngine(model, **ENGINE_KW)
    rids = [plain.add_request(p, max_new_tokens=m) for p, m in prompts]
    ref = plain.run()
    ref = {i: ref[r] for i, r in enumerate(rids)}

    eng = ServingEngine(model, proposer=NgramProposer(), spec_k=4,
                        **ENGINE_KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts]
    idx_of = {r: i for i, r in enumerate(rids)}
    out = {i: [] for i in range(len(prompts))}
    # a few steps with drafts in flight, then abort one decoding
    # request and snapshot the rest
    for _ in range(3):
        for r, t in eng.step():
            out[idx_of[r]].append(t)
    assert eng.metrics.counters["spec_steps"] > 0   # drafts were in flight
    aborted = rids[2]
    from paddle_tpu.serving import RequestState
    assert eng.requests[aborted].state is not RequestState.FINISHED
    assert eng.abort(aborted)
    eng.step()
    assert eng.requests[aborted].finish_reason == "abort"
    snap = eng.snapshot(reason="test kill")
    import json
    snap = json.loads(json.dumps(snap))             # JSON round-trip

    eng2 = ServingEngine.from_snapshot(
        model, snap, proposer=NgramProposer(), spec_k=4, **ENGINE_KW)
    res = eng2.run()
    for rid_, toks in res.items():
        if rid_ in idx_of:
            out[idx_of[rid_]] = toks
    for i in range(len(prompts)):
        if rids[i] == aborted:
            continue
        assert out[i] == ref[i], f"request {i} diverged across resume"
    # full reclamation on BOTH engines. The killed engine still holds
    # its in-flight sequences; an abort-all sweep (drafts in flight)
    # must cancel every state cleanly before the pool can drain.
    for r in list(eng.requests):
        eng.abort(r)
    eng.step()
    for e in (eng, eng2):
        e.reset_prefix_cache()
        assert e.allocator.num_used == 0
        e.allocator.check_invariants()
        e.shutdown()


def test_spec_budget_accounting_and_program_grid(model):
    """The scheduler charges 1 + spec_k tokens per decoding request, so
    verify tokens compete with prefill admission under the same budget;
    the verify program count is bounded by the K-bucket grid."""
    eng = ServingEngine(model, proposer=NgramProposer(), spec_k=4,
                        **ENGINE_KW)
    assert eng.scheduler.decode_token_cost == 5
    assert eng.spec_buckets == [1, 2, 4]
    base = ((len(eng.prefill_buckets) + len(eng.batch_buckets))
            * len(eng.pages_buckets))
    assert eng.max_program_count() == base + 1 * 3 * 3
    plain = ServingEngine(model, **ENGINE_KW)
    assert plain.scheduler.decode_token_cost == 1
    assert plain.max_program_count() == base
    with pytest.raises(ValueError):
        ServingEngine(model, proposer=NgramProposer(), spec_k=4,
                      spec_buckets=[2], **ENGINE_KW)
    eng.shutdown(), plain.shutdown()

    # budget actually bites: a decode batch of 4 at cost 5 under a
    # 24-token budget leaves 4 tokens for prefill chunks
    kw = dict(num_pages=96, page_size=8, token_budget=24,
              batch_buckets=[4], prefill_buckets=[16],
              pages_buckets=[4], temperature=0.0)
    eng = ServingEngine(model, proposer=NgramProposer(), spec_k=4, **kw)
    # 16 new tokens each: the first admitted must still be decoding
    # when the fourth joins (at 8 they finished before it did)
    for _ in range(4):
        eng.add_request([1, 2] * 4, max_new_tokens=16)
    step_until(eng, lambda: len(eng.scheduler.running) == 4, cap=50)
    eng.add_request([3, 4] * 6, max_new_tokens=4)
    eng.run()
    # the late prompt (12 tokens) needed more than one chunk under the
    # squeezed budget; with cost 1 it would have fit in one
    assert eng.metrics.counters["prefill_chunks"] >= 4 + 2
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.shutdown()


def test_spec_sampled_reproducible_and_unbiased_mechanics(model):
    """temperature > 0 with a proposer: same seed reproduces the same
    stream; the stream genuinely samples (diverges from greedy); all
    randomness is pre-drawn per launch (retry bit-identity is covered
    by the transient-injection test below)."""
    kw = dict(ENGINE_KW)
    kw.pop("temperature")
    outs = []
    for _ in range(2):
        eng = ServingEngine(model, proposer=NgramProposer(), spec_k=4,
                            temperature=0.8, top_p=0.9, seed=7, **kw)
        rid = eng.add_request([1, 2, 3, 4] * 5, max_new_tokens=12)
        outs.append(eng.run()[rid])
        eng.reset_prefix_cache()
        assert eng.allocator.num_used == 0
        eng.shutdown()
    assert outs[0] == outs[1]
    greedy = ServingEngine(model, **ENGINE_KW)
    rid = greedy.add_request([1, 2, 3, 4] * 5, max_new_tokens=12)
    assert outs[0] != greedy.run()[rid]
    greedy.shutdown()


def test_spec_transient_retry_is_bit_identical(model):
    """An injected transient on the verify launch retries the identical
    program (key pre-drawn): outputs match the fault-free run exactly,
    and the retry counter records it."""
    from paddle_tpu.serving import RetryPolicy, TransientDeviceError
    kw = dict(ENGINE_KW)
    outs = {}
    for inject in (False, True):
        eng = ServingEngine(
            model, proposer=NgramProposer(), spec_k=4,
            retry_policy=RetryPolicy(max_retries=3, base_s=0.0,
                                     sleep=lambda s: None), **kw)
        rid = eng.add_request([1, 2] * 8, max_new_tokens=10)
        if inject:
            with faults.injected("serving.engine.verify_step",
                                 exc=TransientDeviceError("UNAVAILABLE"),
                                 after=2, times=2):
                outs[inject] = eng.run()[rid]
            assert eng.metrics.counters["step_retries"] >= 1
        else:
            outs[inject] = eng.run()[rid]
        eng.reset_prefix_cache()
        assert eng.allocator.num_used == 0
        eng.shutdown()
    assert outs[True] == outs[False]


def test_spec_nan_quarantine_isolates_one_request(model):
    """NaN-poisoned verify flags quarantine exactly the offending
    request; batchmates keep their greedy streams (rows independent)."""
    plain = ServingEngine(model, **ENGINE_KW)
    keep_p = plain.add_request([11, 12] * 6, max_new_tokens=8)
    ref = plain.run()[keep_p]

    eng = ServingEngine(model, proposer=NgramProposer(), spec_k=4,
                        **ENGINE_KW)
    victim = eng.add_request([21, 22] * 6, max_new_tokens=8)
    keep = eng.add_request([11, 12] * 6, max_new_tokens=8)
    # poison row 0 (the victim) on one mid-decode verify launch
    with faults.injected("serving.engine.nan_logits", payload=[0],
                         after=2, times=1):
        eng.run()
    assert eng.requests[victim].finish_reason == "quarantined"
    assert eng.requests[keep].output_ids == ref
    assert eng.metrics.counters["requests_quarantined"] == 1
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown(), plain.shutdown()


def test_spec_drafting_survives_full_radix_pool(model):
    """Long-running-server steady state: the pool fills with donated
    radix prefixes. Draft extension must reclaim via radix LRU eviction
    (rung 1 of the ladder — never preemption) instead of dropping every
    draft, or the spec-decode win silently disappears exactly where the
    feature targets."""
    # small pool: after a few requests drain, donations own ~all pages
    kw = dict(num_pages=20, page_size=8, token_budget=64,
              batch_buckets=[4], prefill_buckets=[32], pages_buckets=[4],
              temperature=0.0)
    eng = ServingEngine(model, proposer=NgramProposer(), spec_k=4, **kw)
    # fill the tree: distinct prompts run to completion and donate
    # (16 prompt + 8 generated -> 2 full computed pages donated each)
    rng = np.random.RandomState(17)
    for _ in range(12):
        eng.add_request(rng.randint(0, 128, (16,)).tolist(),
                        max_new_tokens=8)
        eng.run()
        if eng.allocator.num_free <= 3:
            break
    assert eng.allocator.num_free <= 3      # pool is donation-saturated
    evicted_before = eng.radix.num_evicted_pages
    # a repetitive request now needs draft pages: eviction must free them
    rid = eng.add_request([1, 2, 3] * 6, max_new_tokens=12)
    out = eng.run()[rid]
    snap = eng.metrics.summary()
    assert snap["spec_drafted_tokens"] > 0, \
        "full radix pool starved drafting entirely"
    assert eng.radix.num_evicted_pages > evicted_before
    plain = ServingEngine(model, **kw)
    rp = plain.add_request([1, 2, 3] * 6, max_new_tokens=12)
    assert plain.run()[rp] == out
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown(), plain.shutdown()


def test_draft_proposer_disable_is_observable(model):
    """A proposer that keeps failing host-side retires after 3
    consecutive failures with a recorded reason and a RuntimeWarning —
    never a silent missing speedup; the engine keeps decoding plainly
    with identical output."""
    import warnings as _w
    dp = DraftModelProposer(model, num_pages=64, page_size=8,
                            prefill_buckets=[32], batch_buckets=[4],
                            pages_buckets=[4])
    dp._propose = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("host-side draft bug"))
    eng = ServingEngine(model, proposer=dp, spec_k=4, num_pages=64,
                        page_size=8, token_budget=64, batch_buckets=[4],
                        prefill_buckets=[32], pages_buckets=[4],
                        temperature=0.0)
    rid = eng.add_request([1, 2] * 6, max_new_tokens=8)
    with _w.catch_warnings(record=True) as caught:
        _w.simplefilter("always")
        out = eng.run()[rid]
    assert dp.disabled and "3 consecutive" in dp.disabled_reason
    assert dp.num_propose_failures == 3
    assert any("DraftModelProposer disabled" in str(w.message)
               for w in caught)
    plain = ServingEngine(model, num_pages=64, page_size=8,
                          token_budget=64, batch_buckets=[4],
                          prefill_buckets=[32], pages_buckets=[4],
                          temperature=0.0)
    rp = plain.add_request([1, 2] * 6, max_new_tokens=8)
    assert plain.run()[rp] == out
    eng.shutdown(), plain.shutdown()


def test_metrics_reservoirs_auto_exposed():
    """The satellite contract: registering a reservoir (or a counter)
    is all it takes to surface it in snapshot()/summary() — no
    hand-maintained key list."""
    from paddle_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics(name="spec-test")
    r = m.add_reservoir("custom_depth")
    r.extend([1, 2, 3, 4, 5])
    m.counters["custom_counter"] = 7
    snap = m.summary()
    assert snap["custom_depth_p50"] == 3
    assert snap["custom_depth_p99"] == 5
    assert snap["custom_counter"] == 7
    # spec counters + the accepted-per-step reservoir ride the same path
    m.on_spec_step(drafted=4, accepted=2, emitted=3, rolled_back=2,
                   rows=1)
    snap = m.summary()
    assert snap["spec_accepted_p50"] == 2
    assert snap["spec_acceptance_rate"] == 0.5
    assert snap["spec_tokens_per_step"] == 3.0
    assert m.summary == m.snapshot
