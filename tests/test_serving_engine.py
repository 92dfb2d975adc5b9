"""End-to-end ServingEngine acceptance (ISSUE 1 + ISSUE 2): overlapping
requests of mixed prompt lengths run to completion under continuous
batching with chunked prefill and the radix prefix cache; outputs
exactly match solo runs; the jit recompile counter stays within the
shape bucket grid; KV occupancy returns to zero once the prefix cache
is released. CPU-only (paged Pallas kernel in interpret mode), greedy.

Determinism note (SERVING.md): exact cross-run matches require the same
program shapes in both runs — XLA does not promise identical rounding
across different program shapes, but rows within one program shape are
independent of batch occupancy and of the chunk offset (cache_len rides
as data, not shape). Hence the pinned single-bucket grids below.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ServingEngine


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    paddle.seed(0)
    return LlamaForCausalLM(cfg)


ENGINE_KW = dict(num_pages=64, page_size=8, token_budget=48,
                 batch_buckets=[16], prefill_buckets=[8, 16, 32, 64],
                 pages_buckets=[2, 4, 8], temperature=0.0)


def _prompts(n=16):
    rng = np.random.RandomState(42)
    lens = rng.randint(2, 25, size=n)           # mixed 2..24 tokens
    news = rng.randint(3, 13, size=n)           # 3..12 new tokens
    return [(rng.randint(0, 128, (l,)).tolist(), int(m))
            for l, m in zip(lens, news)]


def test_serving_engine_continuous_batching_acceptance(model):
    prompts = _prompts(16)
    eng = ServingEngine(model, **ENGINE_KW)

    # stagger arrivals: 10 up front, 6 more once decoding is underway
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in prompts[:10]]
    saw_multi_decode = 0
    steps = 0
    late_added = False
    while eng.has_work():
        if steps == 3 and not late_added:
            rids += [eng.add_request(p, max_new_tokens=m)
                     for p, m in prompts[10:]]
            late_added = True
        batch = len(eng.scheduler.running)
        eng.step()
        saw_multi_decode = max(saw_multi_decode, batch)
        steps += 1
        assert steps < 500
    out = {rid: eng.requests[rid].output_ids for rid in rids}

    # continuous batching actually batched: many requests decoded in one
    # program launch at peak
    assert saw_multi_decode >= 8

    # every request completed with exactly max_new_tokens (no eos set)
    for (p, m), rid in zip(prompts, rids):
        assert len(out[rid]) == m

    # KV fully reclaimed once the donated prefixes are released: live
    # sequences hold nothing, only the radix tree does
    assert eng.allocator.num_used == eng.radix.num_cached_pages
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    assert eng.allocator.occupancy() == 0

    # recompiles bounded by the bucket grid — flat count and the
    # per-family view through the unified ProgramCache (ISSUE 8) agree
    assert eng.metrics.counters["recompiles"] == eng.num_compiled_programs
    assert eng.num_compiled_programs <= eng.max_program_count()
    counts = eng.program_counts()
    assert set(counts) == {"chunk", "decode", "verify", "multi_decode"}
    assert sum(counts.values()) == eng.num_compiled_programs
    assert counts["verify"] == 0                  # no proposer configured
    assert counts["multi_decode"] == 0            # decode_steps=1
    for fam, n in counts.items():
        assert n <= eng.max_program_count(fam)

    # ---- exact match vs one-request-at-a-time ---------------------------
    single = ServingEngine(model, **ENGINE_KW)
    for (p, m), rid in zip(prompts, rids):
        srid = single.add_request(p, max_new_tokens=m)
        single.run()
        assert single.requests[srid].output_ids == out[rid], \
            f"request {rid} diverged between batched and solo runs"
    single.reset_prefix_cache()
    assert single.allocator.num_used == 0
    assert single.num_compiled_programs <= single.max_program_count()


def test_shared_prefix_radix_acceptance(model):
    """ISSUE 2 acceptance: a 16-request shared-prefix workload produces
    token-for-token identical outputs with the prefix cache on vs off,
    while the counters prove >= 50% of prefill tokens were served from
    cache and every block is reclaimed at drain."""
    rng = np.random.RandomState(7)
    shared = rng.randint(0, 128, (24,)).tolist()      # 3 full pages
    tails = [rng.randint(0, 128, (8,)).tolist() for _ in range(16)]
    # single prefill bucket + single pages bucket: cache hits change
    # cache_len (data), never the program shape
    kw = dict(num_pages=128, page_size=8, token_budget=64,
              batch_buckets=[16], prefill_buckets=[32], pages_buckets=[8],
              temperature=0.0)

    outs = {}
    for cache_on in (True, False):
        eng = ServingEngine(model, enable_prefix_cache=cache_on, **kw)
        # warm the tree: the first request runs to completion before the
        # other 15 arrive, so its donated prefix serves all of them
        first = eng.add_request(shared + tails[0], max_new_tokens=4)
        eng.run()
        rest = [eng.add_request(shared + t, max_new_tokens=4)
                for t in tails[1:]]
        res = eng.run()
        outs[cache_on] = [eng.requests[first].output_ids] + \
            [res[r] for r in rest]

        snap = eng.metrics.snapshot()
        total_prompt = 16 * 32
        if cache_on:
            # every follower matched the 24-token shared prefix
            assert snap["prefix_hits"] == 15
            assert snap["prefix_hit_rate"] == round(15 / 16, 4)
            skipped = snap["prefill_tokens_skipped"]
            assert skipped == snap["cached_tokens_served"] == 15 * 24
            assert skipped / total_prompt >= 0.5
            assert snap["prefill_tokens"] == total_prompt - skipped
            assert snap["cached_pages"] > 0
        else:
            assert snap["prefix_hits"] == 0
            assert snap["prefill_tokens"] == total_prompt
        # percentile plumbing produced numbers
        assert snap["ttft_p50_ms"] >= 0
        assert snap["queue_wait_p99_ms"] >= 0

        # all blocks reclaimed at drain: live sequences hold zero pages;
        # releasing the tree returns the pool to empty with refcounts
        # consistent
        freed = eng.reset_prefix_cache()
        assert eng.allocator.num_used == 0
        eng.allocator.check_invariants()
        assert (freed > 0) == cache_on
        eng.shutdown()

    assert outs[True] == outs[False], "prefix cache changed tokens"


def test_chunked_prefill_identity_and_recompile_bound(model):
    """ISSUE 2 acceptance: a prompt larger than the token budget is
    admitted in chunks interleaved with decodes, with outputs identical
    to unchunked execution and no recompiles beyond the bucket grid."""
    kw = dict(num_pages=64, page_size=8, batch_buckets=[4],
              prefill_buckets=[16], pages_buckets=[4], temperature=0.0)
    prompt = list(range(1, 21))                        # 20 tokens

    big = ServingEngine(model, token_budget=32, **kw)  # 2 chunks of 16/4
    r_big = big.add_request(prompt, max_new_tokens=5)
    out_big = big.run()[r_big]

    small = ServingEngine(model, token_budget=6, **kw)  # 4 chunks
    # an ongoing decode the chunks must interleave with
    warm = small.add_request([5, 6, 7], max_new_tokens=12)
    small.step()
    r_small = small.add_request(prompt, max_new_tokens=5)
    interleaved = 0
    steps = 0
    while small.has_work():
        st_running = [r for r in small.scheduler.prefilling]
        if st_running and small.scheduler.running:
            interleaved += 1
        small.step()
        steps += 1
        assert steps < 500
    assert interleaved >= 2          # chunks really rode along decodes
    out_small = small.requests[r_small].output_ids
    assert out_small == out_big
    assert len(small.requests[warm].output_ids) == 12
    for e in (big, small):
        assert e.num_compiled_programs <= e.max_program_count()
        e.reset_prefix_cache()
        assert e.allocator.num_used == 0
        e.shutdown()
    big_chunks = big.metrics.counters["prefill_chunks"]
    small_chunks = small.metrics.counters["prefill_chunks"]
    assert small_chunks > big_chunks >= 2


def test_engine_matches_eager_generate_greedy(model):
    """The paged chunk-prefill + decode path reproduces the model's own
    dense-cache greedy generate token-for-token (cross-validates
    paged_cache_write_range/the prefill span/paged_attention_decode
    against the concat-cache forward)."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 128, (1, 9))
    ref = model.generate(paddle.to_tensor(prompt), max_new_tokens=8,
                         temperature=0.0)
    ref_new = np.asarray(ref._data)[0, 9:].tolist()
    eng = ServingEngine(model, **ENGINE_KW)
    rid = eng.add_request(prompt[0].tolist(), max_new_tokens=8)
    assert eng.run()[rid] == ref_new


def test_engine_eos_and_streaming(model):
    """eos stops a request early; stream() yields (rid, token) in
    emission order; finished requests free their pages immediately
    (modulo the donated prefix the radix tree retains)."""
    eng = ServingEngine(model, **ENGINE_KW)
    rng = np.random.RandomState(5)
    p1 = rng.randint(0, 128, (6,)).tolist()
    # run once to learn the first two tokens, then replay with eos set
    # to the second token: generation must stop after it
    rid0 = eng.add_request(p1, max_new_tokens=4)
    toks = eng.run()[rid0]
    eng2 = ServingEngine(model, **ENGINE_KW)
    rid = eng2.add_request(p1, max_new_tokens=10, eos_token_id=toks[1])
    seen = list(eng2.stream())
    assert [t for r, t in seen if r == rid] == toks[:2]
    assert eng2.requests[rid].finish_reason == "stop"
    eng2.reset_prefix_cache()
    assert eng2.allocator.num_used == 0


def test_engine_preemption_end_to_end(model):
    """Starved KV pool: requests preempt mid-decode, resume by
    re-prefill, and still all run to completion with pages reclaimed.
    Prefix cache off: this pins the PR-1 recompute-preemption behavior
    (with the cache on, donated prefixes turn most resumes into hits —
    covered by test_preemption_resume_hits_cache)."""
    eng = ServingEngine(model, num_pages=9, page_size=8,  # 8 usable pages
                        token_budget=64, batch_buckets=[4],
                        prefill_buckets=[16, 32], pages_buckets=[2, 4],
                        temperature=0.0, enable_prefix_cache=False)
    rng = np.random.RandomState(9)
    rids = [eng.add_request(rng.randint(0, 128, (14,)).tolist(),
                            max_new_tokens=12) for _ in range(4)]
    out = eng.run()
    assert all(len(out[r]) == 12 for r in rids)
    assert eng.scheduler.num_preemptions >= 1
    assert eng.metrics.counters["requests_preempted"] >= 1
    assert eng.allocator.num_used == 0


def test_preemption_resume_hits_cache(model):
    """With the radix tree on, a preempted request's donated pages turn
    its recompute-resume into a prefix hit."""
    eng = ServingEngine(model, num_pages=11, page_size=8,  # 10 usable
                        token_budget=64, batch_buckets=[4],
                        prefill_buckets=[16, 32], pages_buckets=[2, 4],
                        temperature=0.0)
    rng = np.random.RandomState(9)
    rids = [eng.add_request(rng.randint(0, 128, (14,)).tolist(),
                            max_new_tokens=12) for _ in range(4)]
    out = eng.run()
    assert all(len(out[r]) == 12 for r in rids)
    assert eng.scheduler.num_preemptions >= 1
    # at least one resume was served from the tree
    assert eng.metrics.counters["cached_tokens_served"] > 0
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()


def test_engine_metrics_and_profiler_counters(model):
    from paddle_tpu import profiler
    eng = ServingEngine(model, **ENGINE_KW)
    rng = np.random.RandomState(11)
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                           on_trace_ready=lambda p: None) as prof:
        eng.add_request(rng.randint(0, 128, (5,)).tolist(),
                        max_new_tokens=4)
        eng.run()
        table = prof.summary()
    # engine spans appear among the profiled host events
    names = {e["name"] for e in prof.events}
    assert "serving.prefill_chunk" in names and "serving.decode_step" in names
    # the engine's counters ride Profiler.summary() via the provider hook
    # (provider names are per-engine so concurrent engines don't shadow)
    assert f"[{eng.metrics.name}]" in table and "decode_tokens=3" in table
    snap = eng.metrics.snapshot()
    assert snap["requests_finished"] == 1
    assert snap["prefill_tokens"] == 5
    assert snap["decode_tokens"] == 3        # 1 of 4 tokens from prefill
    assert snap["prefill_chunks"] == 1
    assert snap["admissions"] == 1
    assert snap["mean_ttft_ms"] >= 0
    assert snap["ttft_p90_ms"] >= snap["ttft_p50_ms"] >= 0
    assert snap["tokens_per_second"] > 0
    eng.shutdown()
    assert eng.metrics.name not in profiler.counters()


def test_two_engines_have_distinct_counter_providers(model):
    from paddle_tpu import profiler
    a = ServingEngine(model, **ENGINE_KW)
    b = ServingEngine(model, **ENGINE_KW)
    assert a.metrics.name != b.metrics.name
    assert {a.metrics.name, b.metrics.name} <= set(profiler.counters())
    a.shutdown()                     # must not tear down b's provider
    assert b.metrics.name in profiler.counters()
    b.shutdown()


def test_finished_request_retention_is_bounded(model):
    """A long-lived server keeps only the most recent finished requests
    readable (same unbounded-growth class the jit fallback registry cap
    addresses); older ones are evicted and counted."""
    eng = ServingEngine(model, max_retained_finished=2, **ENGINE_KW)
    rng = np.random.RandomState(13)
    rids = [eng.add_request(rng.randint(0, 128, (4,)).tolist(),
                            max_new_tokens=2) for _ in range(5)]
    eng.run()
    assert eng.num_evicted_finished == 3
    kept = [r for r in rids if r in eng.requests]
    assert kept == rids[-2:]
    assert eng.metrics.counters["requests_finished"] == 5


def test_engine_request_validation(model):
    eng = ServingEngine(model, **ENGINE_KW)
    with pytest.raises(ValueError):
        eng.add_request([1] * 70, max_new_tokens=1)         # prompt too long
    with pytest.raises(ValueError):
        eng.add_request([1, 2], max_new_tokens=64)          # over max_seq_len
    # PR 1 rejected requests whose post-preemption resume outsized the
    # largest prefill bucket; chunked prefill REMOVED that failure mode
    # — any resume within max_seq_len re-prefills in chunks
    narrow = ServingEngine(model, num_pages=64, page_size=8,
                           batch_buckets=[4], prefill_buckets=[16],
                           pages_buckets=[4], temperature=0.0)
    rid = narrow.add_request([1] * 10, max_new_tokens=10)   # resume -> 19 ok
    out = narrow.run()
    assert len(out[rid]) == 10


def test_oversized_prompt_vs_token_budget_does_not_livelock(model):
    """A prompt longer than token_budget prefills in budget-sized
    chunks (the PR-1 'admitted alone' special case is gone)."""
    eng = ServingEngine(model, num_pages=64, page_size=8, token_budget=4,
                        batch_buckets=[4], prefill_buckets=[16],
                        pages_buckets=[4], temperature=0.0)
    rid = eng.add_request(list(range(1, 11)), max_new_tokens=3)  # 10 > 4
    out = eng.run()
    assert len(out[rid]) == 3
    assert eng.metrics.counters["prefill_chunks"] >= 3  # 4+4+2
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
