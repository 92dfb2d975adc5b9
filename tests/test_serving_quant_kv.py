"""Quantized KV cache (ISSUE 6) acceptance: int8 pages with per-slot
scales behind the SAME page machinery as bf16.

The load-bearing claims, each pinned here:
* capacity — the page payload halves exactly and the page count at a
  fixed pool-byte budget grows by 2D/(D+4) (~2x; `paged_page_bytes` is
  the single math source);
* accuracy — quantize->dequantize error is bounded by scale/2
  (absmax/254 per element), end-to-end greedy decode matches
  full-precision within the documented token-flip budget;
* paging bit-exactness — the allocator/radix/CoW/truncate/snapshot
  machinery is host-side and byte-level, so an int8 engine's page and
  refcount state is IDENTICAL to the bf16 engine's on the same
  workload (token values only enter through radix content keys, which
  the shared-prefix workload keeps identical);
* determinism — prefix cache on/off is bit-identical at fixed
  kv_dtype (quantize-on-write is content-deterministic: cached pages
  hold exactly the bytes the request would have written), spec-decode
  greedy output is token-identical to plain decode under int8, and
  snapshot/resume reproduces the uninterrupted int8 run;
* compile discipline — quantized engines ride the same bucket-grid
  program-cache bound, with the quant config in the key.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels.paged_attention import paged_page_bytes, quantize_kv
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ServingEngine

CFG = dict(vocab_size=128, hidden_size=128, intermediate_size=256,
           num_hidden_layers=2, num_attention_heads=2,
           num_key_value_heads=1, max_position_embeddings=128)

# single-bucket grid: identical program shapes across engines, so
# cross-engine token comparisons are exact (SERVING.md determinism
# contract — same rationale as the soak's pinned grid)
ENGINE_KW = dict(num_pages=64, page_size=8, token_budget=48,
                 batch_buckets=[8], prefill_buckets=[32],
                 pages_buckets=[8], temperature=0.0)


def _model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(**CFG))


@pytest.fixture(scope="module")
def model():
    return _model()


def _workload(n=8, seed=1, shared=10):
    """Mixed prompts over a shared prefix (radix exercise). The shared
    head is prompt content, identical across kv_dtypes by construction
    — generated tokens only ever land in per-request tail pages, so
    radix MATCH lengths (and with them the whole scheduling trace)
    cannot depend on the attention arithmetic."""
    rng = np.random.RandomState(seed)
    head = rng.randint(0, 128, (shared,)).tolist()
    out = []
    for i in range(n):
        tail = rng.randint(0, 128, (int(rng.randint(2, 12)),)).tolist()
        out.append(((head + tail) if i % 2 == 0 else tail,
                    int(rng.randint(3, 10))))
    return out


def _drain(eng, work):
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in work]
    out = eng.run()
    return [out[r] for r in rids]


# ---------------------------------------------------------- capacity
def test_int8_page_payload_halves_and_capacity_nearly_doubles():
    KVH, PS = 8, 16
    for D in (64, 128, 256):
        bf16 = paged_page_bytes(KVH, PS, D)
        int8 = paged_page_bytes(KVH, PS, D, "int8")
        payload_bf16 = 2 * KVH * PS * D * 2
        payload_int8 = 2 * KVH * PS * D
        scales = 2 * KVH * PS * 4
        assert bf16 == payload_bf16
        assert int8 == payload_int8 + scales      # payload halves exactly
        # page count at fixed pool bytes: 2D/(D+4) — 1.88x at D=64,
        # 1.94x at D=128, 1.97x at D=256
        ratio = bf16 / int8
        assert ratio == pytest.approx(2 * D / (D + 4))
        assert ratio >= 1.85
        pool = 256 * bf16                          # fits 256 bf16 pages
        assert pool // int8 >= int(1.85 * (pool // bf16))


def test_engine_kv_pool_bytes_sizing(model):
    kw = {k: v for k, v in ENGINE_KW.items() if k != "num_pages"}
    pool = 1 << 20
    full = ServingEngine(model, kv_pool_bytes=pool, **kw)
    quant = ServingEngine(model, kv_pool_bytes=pool, kv_dtype="int8", **kw)
    assert full.num_pages == pool // full.kv_page_bytes
    assert quant.num_pages == pool // quant.kv_page_bytes
    # the CPU model is fp32, so the measured ratio exceeds even the
    # bf16 2x target; the bf16 ratio is pinned analytically above
    assert quant.num_pages >= 1.85 * full.num_pages
    snap = quant.metrics.snapshot()
    assert snap["kv_dtype"] == "int8"
    assert snap["kv_pool_bytes"] == quant.kv_page_bytes * quant.num_pages
    for e in (full, quant):
        e.shutdown()


# ---------------------------------------------------------- accuracy
def test_quantize_dequantize_rel_err_bound():
    rng = np.random.RandomState(0)
    x = (rng.randn(64, 4, 128) * rng.lognormal(0, 2, (64, 4, 1))) \
        .astype(np.float32)
    q, s = quantize_kv(x)
    q, s = np.asarray(q, np.float32), np.asarray(s)
    deq = q * s[..., None]
    # round-to-nearest: |err| <= scale/2 = absmax/254 per element
    bound = np.abs(x).max(-1, keepdims=True) / 254.0
    assert (np.abs(deq - x) <= bound * (1 + 1e-5) + 1e-12).all()
    # and the relative error vs the per-token absmax is <= ~0.4%
    rel = np.abs(deq - x) / np.abs(x).max(-1, keepdims=True)
    assert rel.max() <= 0.5 / 127 + 1e-6


def test_int8_greedy_matches_full_precision_within_budget(model):
    """End-to-end greedy decode under int8 KV vs full precision: the
    DOCUMENTED budget is >= 90% token match on this fixed workload
    (SERVING.md "Quantized KV & weights"; measured 100% at this seed —
    the floor leaves room for platform rounding differences)."""
    work = _workload(8)
    full = _drain(ServingEngine(model, **ENGINE_KW), work)
    quant = _drain(ServingEngine(model, kv_dtype="int8", **ENGINE_KW),
                   work)
    total = sum(len(t) for t in full)
    match = sum(a == b for fa, qa in zip(full, quant)
                for a, b in zip(fa, qa))
    assert match / total >= 0.9, f"{match}/{total} tokens matched"


# ------------------------------------------- paging bit-exactness
def _paging_trace(model, work, kv_dtype):
    eng = ServingEngine(model, kv_dtype=kv_dtype, **ENGINE_KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in work]
    trace = []
    page_maps = {}
    while eng.has_work():
        assert len(trace) < 500
        eng.step()
        trace.append((eng.allocator.num_used, eng.allocator.num_free))
        for i, rid in enumerate(rids):   # keyed by workload index: the
            req = eng.requests[rid]      # global request-id counter
            if req.seq is not None and not req.seq.freed:   # differs
                page_maps[i] = (list(req.seq.pages), req.seq.num_tokens)
    state = dict(
        trace=trace,
        page_maps=page_maps,
        refs=dict(eng.allocator._refs),
        free=list(eng.allocator._free),
        radix=(eng.radix.num_cached_pages, eng.radix.num_nodes),
        outputs=[eng.requests[r].output_ids for r in rids],
    )
    eng.radix.check_invariants()
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown()
    return state


def test_paging_state_bit_identical_to_bf16(model):
    """CoW fork, radix donation/match, page assignment order, refcounts
    and the free list evolve IDENTICALLY under kv_dtype=int8 — paging
    is byte-level and dtype-agnostic (the ISSUE 6 invariant). The
    shared-prefix workload keeps radix content keys equal across
    dtypes, so any divergence here would be a real machinery leak."""
    work = _workload(8)
    full = _paging_trace(model, work, None)
    quant = _paging_trace(model, work, "int8")
    assert full["trace"] == quant["trace"]
    assert full["page_maps"] == quant["page_maps"]
    assert full["refs"] == quant["refs"]
    assert full["free"] == quant["free"]
    assert full["radix"] == quant["radix"]
    # same workload produced the same tokens too (not required for the
    # paging claim, but true at this seed and a stronger signal)
    assert full["outputs"] == quant["outputs"]


def test_cow_copy_carries_scale_rows(model):
    """A CoW page copy under int8 must copy the per-slot scale rows
    with the values: a fork that kept stale scales would dequantize
    the copied page wrongly. Drive _apply_copies directly."""
    import jax.numpy as jnp
    eng = ServingEngine(model, kv_dtype="int8", **ENGINE_KW)
    src, dst = 3, 5
    for l in range(eng.num_layers):
        eng._k_caches[l] = eng._k_caches[l].at[src].set(l + 1)
        eng._k_scales[l] = eng._k_scales[l].at[src].set(0.5 * (l + 1))
        eng._v_scales[l] = eng._v_scales[l].at[src].set(0.25 * (l + 1))
    eng._apply_copies([(src, dst)])
    for l in range(eng.num_layers):
        assert (np.asarray(eng._k_caches[l][dst]) == l + 1).all()
        assert (np.asarray(eng._k_scales[l][dst]) == 0.5 * (l + 1)).all()
        assert (np.asarray(eng._v_scales[l][dst]) == 0.25 * (l + 1)).all()
    eng.shutdown()


# ----------------------------------------------------- determinism
def test_prefix_cache_on_off_bit_identical_at_int8(model):
    """Cache on/off must stay bit-identical at kv_dtype=int8: a radix
    hit reuses pages holding EXACTLY the quantized bytes the request's
    own prefill would have written (quantize-on-write is a pure
    function of the token content)."""
    rng = np.random.RandomState(7)
    shared = rng.randint(0, 128, (24,)).tolist()
    tails = [rng.randint(0, 128, (8,)).tolist() for _ in range(8)]
    outs = {}
    for cache_on in (True, False):
        eng = ServingEngine(_model(), kv_dtype="int8",
                            enable_prefix_cache=cache_on, **ENGINE_KW)
        first = eng.add_request(shared + tails[0], max_new_tokens=4)
        eng.run()                    # warm request donates the prefix
        rest = [eng.add_request(shared + t, max_new_tokens=4)
                for t in tails[1:]]
        res = eng.run()
        outs[cache_on] = [eng.requests[first].output_ids] + \
            [res[r] for r in rest]
        if cache_on:
            assert eng.metrics.counters["prefix_hits"] >= 7
        eng.reset_prefix_cache()
        assert eng.allocator.num_used == 0
        eng.shutdown()
    assert outs[True] == outs[False], "prefix cache changed int8 tokens"


class _WrongProposer:
    """Drafts that are always wrong: every draft is rejected, so the
    verify step exercises truncate_sequence rollback maximally while
    greedy output must stay bit-identical to plain decode."""

    def propose(self, reqs, k):
        return [[(r.output_ids[-1] + 1) % 128] * k for r in reqs]

    def on_finished(self, req):
        pass

    def reset(self):
        pass


def test_spec_rollback_under_int8_is_exact(model):
    work = _workload(6, seed=3)
    plain = _drain(ServingEngine(model, kv_dtype="int8", **ENGINE_KW),
                   work)
    eng = ServingEngine(model, kv_dtype="int8", proposer=_WrongProposer(),
                        spec_k=2, spec_buckets=[2], **ENGINE_KW)
    spec = _drain(eng, work)
    assert spec == plain, "rejected drafts changed int8 greedy tokens"
    assert eng.metrics.counters["spec_rollback_tokens"] >= 1
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()
    eng.shutdown()


def test_snapshot_resume_under_int8(model):
    """Drain-to-snapshot mid-flight, resume in a FRESH int8 engine:
    greedy outputs complete bit-identically to the uninterrupted int8
    run (re-prefill quantizes the same tokens to the same bytes)."""
    work = _workload(4, seed=5)
    ref = _drain(ServingEngine(model, kv_dtype="int8", **ENGINE_KW), work)
    eng = ServingEngine(model, kv_dtype="int8", **ENGINE_KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in work]
    for _ in range(3):
        eng.step()
    snap = eng.snapshot(reason="test")
    eng.shutdown()
    res = ServingEngine.from_snapshot(model, snap, kv_dtype="int8",
                                      **ENGINE_KW)
    out = res.run()
    got = [res.requests[r].output_ids for r in rids]
    assert got == ref
    res.reset_prefix_cache()
    assert res.allocator.num_used == 0
    res.shutdown()


# -------------------------------------------- programs + weight quant
def test_quant_configs_ride_program_keys_and_stay_bounded(model):
    eng = ServingEngine(model, kv_dtype="int8", **ENGINE_KW)
    _drain(eng, _workload(6, seed=9))
    assert eng.num_compiled_programs <= eng.max_program_count()
    # per-family counts through the unified ProgramCache (ISSUE 8)
    counts = eng.program_counts()
    assert sum(counts.values()) == eng.num_compiled_programs
    for fam, n in counts.items():
        assert n <= eng.max_program_count(fam)
    # quant config + mesh shape ride every key, before the sampling tuple
    assert all(key[-4:-1] == ("int8", "w_full", ("tp", 1))
               for key in eng.programs.keys())
    eng.shutdown()


def test_wq_int8_engine_decodes_and_stays_bounded():
    """wq="int8" converts MLP + LM head in place (fresh model — the
    conversion mutates it) and serves through the fused dequant-matmul;
    outputs keep their lengths, programs stay bounded, and the
    full quantized config (int8 KV + int8 weights) drains clean."""
    model = _model()
    work = _workload(6, seed=11)
    eng = ServingEngine(model, wq="int8", kv_dtype="int8", **ENGINE_KW)
    assert eng.num_wq_layers == 2 * 3 + 1     # gate/up/down x L + head
    sd = model.state_dict()
    assert "lm_head.qweight" in sd and "lm_head.weight" not in sd
    outs = _drain(eng, work)
    assert [len(t) for t in outs] == [m for _, m in work]
    assert eng.num_compiled_programs <= eng.max_program_count()
    assert all(key[-4:-2] == ("int8", "int8")
               for key in eng.programs.keys())
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.shutdown()


def test_kv_bytes_counters_track_tokens(model):
    eng = ServingEngine(model, kv_dtype="int8", **ENGINE_KW)
    rid = eng.add_request(list(range(1, 9)), max_new_tokens=4)
    eng.run()
    snap = eng.metrics.snapshot()
    bpt = eng.kv_bytes_per_token
    assert snap["kv_bytes_per_token"] == bpt
    # prefill wrote 8 tokens, the 3 decode steps one each
    assert snap["kv_bytes_written"] == (8 + 3) * bpt
    # the prefill chunk gathered its own 8 tokens; each decode read the
    # whole live sequence (9, 10, 11 tokens)
    assert snap["kv_bytes_read"] == (8 + 9 + 10 + 11) * bpt
    # int8 bytes/token is ~half the fp32 engine's
    full = ServingEngine(model, **ENGINE_KW)
    assert bpt < 0.6 * full.kv_bytes_per_token
    for e in (eng, full):
        e.shutdown()


def test_invalid_quant_configs_raise(model):
    with pytest.raises(ValueError):
        ServingEngine(model, kv_dtype="int4", **ENGINE_KW)
    with pytest.raises(ValueError):
        ServingEngine(model, wq="fp8", **ENGINE_KW)
