"""On-chip serving-path validation: compiled decode loop + int8 parity.

1. LlamaForCausalLM.generate(use_jit=True) — prefill + whole decode
   loop + sampling as ONE XLA program — on the real chip, checked
   against the eager decode loop token-for-token (greedy).
2. weight_only_linear int8 vs the bf16 matmul it approximates.
"""
import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

print("devices:", jax.devices())

cfg = LlamaConfig(vocab_size=1024, hidden_size=256, intermediate_size=512,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=4, max_position_embeddings=256)
paddle.seed(0)
model = LlamaForCausalLM(cfg)
model.bfloat16()
rng = np.random.RandomState(0)
ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 16)))

# the greedy eager-vs-jit gate is a CHIP gate: on CPU the two paths
# compile to different XLA programs whose rounding legitimately
# diverges at near-tie logits (0.79 match measured at the PR-5 HEAD),
# so off-chip this reports instead of hard-asserting (ISSUE 6
# satellite — the pre-existing CPU failure mode)
ON_TPU = jax.default_backend() == "tpu"

out_eager = model.generate(ids, max_new_tokens=24, temperature=0.0)
out_jit = model.generate(ids, max_new_tokens=24, temperature=0.0,
                         use_jit=True)
a = np.asarray(out_eager._data if hasattr(out_eager, "_data") else out_eager)
b = np.asarray(out_jit._data if hasattr(out_jit, "_data") else out_jit)
match = (a == b).mean()
print(f"decode greedy eager-vs-jit token match: {match:.3f}")
if ON_TPU:
    # greedy at temperature 0 must agree EXACTLY on chip — one flipped
    # token cascades, so anything < 1.0 is a real regression
    assert match == 1.0, (a, b)
    print("SERVING_JIT_CHIP_OK", a.shape)
else:
    print(f"SERVING_JIT_CPU_REPORT_ONLY match={match:.3f} "
          "(hard gate runs on TPU)")

# sampled path executes (no parity claim — different RNG streams ok)
out_s = model.generate(ids, max_new_tokens=8, temperature=0.8, top_p=0.9,
                       use_jit=True, seed=7)
print("SERVING_SAMPLED_CHIP_OK",
      np.asarray(out_s._data if hasattr(out_s, "_data") else out_s).shape)

# --- int8 weight-only parity -----------------------------------------
from paddle_tpu.nn.quant import weight_quantize, weight_only_linear
K, N, M = 1024, 1024, 64
w = paddle.to_tensor((rng.randn(K, N) * 0.02).astype(np.float32))
x = paddle.to_tensor(rng.randn(M, K).astype(np.float32))
qw, scale = weight_quantize(w, algo="weight_only_int8")
y_q = np.asarray(weight_only_linear(
    x, qw, weight_scale=scale, weight_dtype="int8")._data, np.float32)
y_f = np.asarray((x._data @ w._data), np.float32)
rel = np.abs(y_q - y_f).max() / (np.abs(y_f).max() + 1e-9)
print(f"int8 weight-only rel_err {rel:.4f}")
assert rel < 2e-2, rel
print("INT8_CHIP_OK")

# --- ServingEngine continuous-batching decode throughput --------------
# VERDICT open item #9 ("measure serving decode"): 8 requests decode in
# ONE batched program over the real Pallas paged kernel. Each step()
# host-fetches the sampled tokens, which synchronizes, so wall-clock
# across steps is a true step time.
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.scheduler import RequestState

eng = ServingEngine(model, num_pages=128, page_size=16,
                    batch_buckets=[8], prefill_buckets=[16, 128],
                    pages_buckets=[8], temperature=0.0)
for _ in range(8):
    eng.add_request(rng.randint(0, cfg.vocab_size, (12,)).tolist(),
                    max_new_tokens=100)
# warm: prefills + first decode launch (compiles both programs)
while not all(r.state is RequestState.DECODE
              for r in eng.requests.values()):
    eng.step()
eng.step()
import time
N_STEPS = 64
t0 = time.perf_counter()
for _ in range(N_STEPS):
    eng.step()
dt = time.perf_counter() - t0
tps = 8 * N_STEPS / dt
print(f"serving engine: batch=8 decode {dt / N_STEPS * 1e3:.2f} ms/step "
      f"SERVING_ENGINE_TOKS_PER_S {tps:.1f}")
# the engine report goes out through the observability paths (ISSUE 10)
# — the Prometheus exposition and the flight-recorder digest — so the
# chip probe exercises the same renderers production scrapes use
# (host-side only: needs no chip)
import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.abspath(__file__)))
import trace_report
print("serving engine exposition:")
print(eng.metrics.prometheus_text(), end="")
print(trace_report.format_flight_recorder(eng.timeline()))
assert eng.num_compiled_programs <= eng.max_program_count()

# --- failure-mode probe (ISSUE 3): abort + TTL on the real chip -------
# Two of the decoding requests are aborted mid-flight and two more are
# added with a microscopic TTL; the engine must drain cleanly, donate
# the aborted KV to the radix tree, and report the failure counters.
live = [r for r in eng.requests.values()
        if r.state is RequestState.DECODE][:2]
for r in live:
    assert eng.abort(r.request_id)
for _ in range(2):
    eng.add_request(rng.randint(0, cfg.vocab_size, (12,)).tolist(),
                    max_new_tokens=50, ttl_s=1e-6)
eng.run()
snap = eng.metrics.snapshot()
fail_keys = ("requests_aborted", "deadline_expired", "requests_shed",
             "step_retries", "requests_quarantined", "engine_failures")
print("serving failure counters:",
      {k: snap[k] for k in fail_keys})
print(trace_report.format_flight_recorder(eng.timeline()))
assert snap["requests_aborted"] == 2 and snap["deadline_expired"] == 2
assert snap["requests_quarantined"] == 0 and snap["engine_failures"] == 0
eng.reset_prefix_cache()
assert eng.allocator.num_used == 0
eng.shutdown()
print("SERVING_ENGINE_CHIP_OK SERVING_FAULTS_CHIP_OK")

# --- shared-prefix throughput probe (ISSUE 2) --------------------------
# 8 requests sharing a 96-token system-prompt-style prefix, radix cache
# on vs off. The first request warms the tree; the other 7 should serve
# the shared pages straight from cache. TTFT and total wall-clock are
# printed (not asserted — chip variance stays out of the gate); the
# counter assertions ARE the gate: the hit accounting must be exact.
shared = rng.randint(0, cfg.vocab_size, (96,)).tolist()
tails = [rng.randint(0, cfg.vocab_size, (8,)).tolist() for _ in range(8)]
for cache_on in (True, False):
    eng = ServingEngine(model, num_pages=256, page_size=16,
                        batch_buckets=[8], prefill_buckets=[128],
                        pages_buckets=[8], temperature=0.0,
                        enable_prefix_cache=cache_on)
    t0 = time.perf_counter()
    first = eng.add_request(shared + tails[0], max_new_tokens=16)
    eng.run()                       # warm request donates the prefix
    rest = [eng.add_request(shared + t, max_new_tokens=16)
            for t in tails[1:]]
    eng.run()
    wall = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    label = "on" if cache_on else "off"
    print(f"shared-prefix cache={label}: wall {wall:.3f}s "
          f"prefill_tokens {snap['prefill_tokens']} "
          f"skipped {snap['prefill_tokens_skipped']} "
          f"hit_rate {snap.get('prefix_hit_rate', 0)} "
          f"ttft_p50_ms {snap.get('ttft_p50_ms')}")
    if cache_on:
        assert snap["prefix_hits"] == 7, snap
        assert snap["prefill_tokens_skipped"] >= 7 * 96, snap
        print(f"SERVING_PREFIX_CACHE_CHIP_OK skipped="
              f"{snap['prefill_tokens_skipped']}")
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.shutdown()

# --- speculative-decoding probe (ISSUE 5) ------------------------------
# NgramProposer over a repetitive (summarization-shaped) workload:
# tok/s at batch {1, 8} x K in {2, 4, 8} against the plain-decode
# baseline, plus acceptance rate and the per-sequence tokens-per-step
# multiplier. Timing is fetch-synced by construction: every step()
# host-fetches the emitted tokens (a sync on any access path), so
# wall-clock across a drain
# is a true serving time. Throughput is printed, not asserted (chip
# variance stays out of the gate); the gates are greedy bit-identity
# vs plain decode and exact reclamation. Written without a chip: CPU runs of
# the same code path are pinned by tests/test_serving_spec.py.
from paddle_tpu.serving import NgramProposer

spec_rng = np.random.RandomState(3)
cycle = spec_rng.randint(0, cfg.vocab_size, (6,)).tolist()
SPEC_PROMPT = (cycle * 12)[:64]          # repetitive: ngram-friendly
SPEC_NEW = 48


def run_spec_probe(batch, k, proposer):
    eng = ServingEngine(model, num_pages=256, page_size=16,
                        batch_buckets=[8], prefill_buckets=[64],
                        pages_buckets=[8], temperature=0.0,
                        proposer=proposer,
                        spec_k=(k or 1), spec_buckets=[k] if k else None)
    t0 = time.perf_counter()
    rids = [eng.add_request(SPEC_PROMPT, max_new_tokens=SPEC_NEW)
            for _ in range(batch)]
    out = eng.run()
    wall = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    assert eng.num_compiled_programs <= eng.max_program_count()
    eng.shutdown()
    toks = sum(len(out[r]) for r in rids)
    return {i: out[r] for i, r in enumerate(rids)}, toks / wall, snap


for batch in (1, 8):
    base_out, base_tps, _ = run_spec_probe(batch, None, None)
    print(f"spec-decode baseline: batch={batch} plain decode "
          f"{base_tps:.1f} tok/s")
    for k in (2, 4, 8):
        out, tps, snap = run_spec_probe(batch, k, NgramProposer())
        # greedy identity is a CHIP gate for the same reason as the
        # eager-vs-jit one above: this probe's model is bf16, and on
        # CPU the decode and verify programs (different shapes) round
        # near-tie bf16 logits differently — pre-existing at the PR-5
        # HEAD (16/48 match at batch=1 K=2), report-only off chip.
        # The f32 CPU identity contract stays pinned by
        # tests/test_serving_spec.py.
        if ON_TPU:
            assert out == base_out, f"spec K={k} changed greedy tokens"
        elif out != base_out:
            m = sum(a == b for bo, so in zip(base_out.values(),
                                             out.values())
                    for a, b in zip(bo, so))
            t = sum(len(v) for v in base_out.values())
            print(f"SPEC_CPU_REPORT_ONLY batch={batch} K={k} "
                  f"match={m}/{t} (hard gate runs on TPU)")
        print(f"SPEC_DECODE_CHIP batch={batch} K={k} "
              f"tok_s={tps:.1f} speedup={tps / base_tps:.2f}x "
              f"accept_rate={snap.get('spec_acceptance_rate')} "
              f"tokens_per_step={snap.get('spec_tokens_per_step')}")
        assert snap["spec_accepted_tokens"] > 0
print("SPEC_DECODE_CHIP_OK")

# --- quantized decode path probe (ISSUE 6) -----------------------------
# int8 KV pages + weight-only int8: decode throughput at batch 8 vs the
# full-precision engine, greedy token match fraction, and the doubled
# page capacity at fixed pool bytes. The rel-err budget asserted on
# chip: >= 90% token match (the per-step attention error is ~0.007 —
# chip_parity pins the kernel-level number; token flips only happen at
# near-tie logits). Throughput is printed, not asserted (chip variance
# stays out of the gate).
QPROMPTS = [rng.randint(0, cfg.vocab_size, (12,)).tolist()
            for _ in range(8)]


def run_quant_probe(kv_dtype=None, wq=None):
    import paddle_tpu as _p
    _p.seed(0)
    qmodel = LlamaForCausalLM(cfg)
    qmodel.bfloat16()
    eng = ServingEngine(qmodel, num_pages=128, page_size=16,
                        batch_buckets=[8], prefill_buckets=[16, 128],
                        pages_buckets=[8], temperature=0.0,
                        kv_dtype=kv_dtype, wq=wq)
    t0 = time.perf_counter()
    rids = [eng.add_request(p, max_new_tokens=32) for p in QPROMPTS]
    out = eng.run()
    wall = time.perf_counter() - t0
    toks = [out[r] for r in rids]
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    assert eng.num_compiled_programs <= eng.max_program_count()
    snap = eng.metrics.snapshot()
    eng.shutdown()
    return toks, sum(len(t) for t in toks) / wall, snap


full_toks, full_tps, full_snap = run_quant_probe()
for label, kvd, wq in (("int8kv", "int8", None),
                       ("int8kv+wq", "int8", "int8")):
    q_toks, q_tps, q_snap = run_quant_probe(kvd, wq)
    total = sum(len(t) for t in full_toks)
    match = sum(a == b for ft, qt in zip(full_toks, q_toks)
                for a, b in zip(ft, qt)) / total
    print(f"QUANT_DECODE_CHIP {label}: tok_s={q_tps:.1f} "
          f"(full {full_tps:.1f}, {q_tps / full_tps:.2f}x) "
          f"token_match={match:.3f} "
          f"bytes/token {q_snap['kv_bytes_per_token']} vs "
          f"{full_snap['kv_bytes_per_token']}")
    if ON_TPU:
        assert match >= 0.9, f"{label} token match {match}"
    assert q_snap["kv_bytes_per_token"] * 1.7 <= \
        full_snap["kv_bytes_per_token"]

# page capacity at fixed pool bytes (pure geometry, asserted anywhere)
from paddle_tpu.kernels.paged_attention import paged_page_bytes
pb_full = paged_page_bytes(cfg.num_key_value_heads, 16,
                           cfg.hidden_size // cfg.num_attention_heads)
pb_int8 = paged_page_bytes(cfg.num_key_value_heads, 16,
                           cfg.hidden_size // cfg.num_attention_heads,
                           "int8")
POOL = 64 << 20
print(f"page capacity at {POOL >> 20} MB: bf16 {POOL // pb_full} "
      f"int8 {POOL // pb_int8} ({POOL // pb_int8 / (POOL // pb_full):.2f}x)")
assert POOL // pb_int8 >= 1.85 * (POOL // pb_full)
print("QUANT_DECODE_CHIP_OK")

# --- multi-step decode probe (ISSUE 13) --------------------------------
# K decode iterations per compiled launch vs the K=1 baseline: tok/s at
# K in {1, 4, 8, 16} over the same 8-request workload. Every step()
# host-fetches the launch's tokens (a sync on any access path), so
# wall-clock across a drain is a true serving time; wherever the host
# round trip per launch dominates, K
# amortizes the dominant decode cost and the tok/s ladder IS the
# measured win. Greedy bit-identity vs K=1 is a CHIP gate (ON_TPU —
# this probe's model is bf16 and CPU rounds near-tie logits
# differently across program shapes; the f32 CPU identity contract is
# pinned by tests/test_serving_multi.py); tokens-per-launch >= 0.9 K
# at full batch is host bookkeeping and asserts anywhere.
MD_PROMPTS = [rng.randint(0, cfg.vocab_size, (12,)).tolist()
              for _ in range(8)]
MD_NEW = 48


def run_multi_probe(k):
    import paddle_tpu as _p
    _p.seed(0)
    mmodel = LlamaForCausalLM(cfg)
    mmodel.bfloat16()
    eng = ServingEngine(mmodel, num_pages=256, page_size=16,
                        batch_buckets=[8], prefill_buckets=[16, 128],
                        pages_buckets=[8], temperature=0.0,
                        decode_steps=k, multi_buckets=[k] if k > 1
                        else None)
    t0 = time.perf_counter()
    rids = [eng.add_request(p, max_new_tokens=MD_NEW)
            for p in MD_PROMPTS]
    out = eng.run()
    wall = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    assert eng.num_compiled_programs <= eng.max_program_count()
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.shutdown()
    toks = [out[r] for r in rids]
    return toks, sum(len(t) for t in toks) / wall, snap


md_base, md_base_tps, _ = run_multi_probe(1)
print(f"multi-decode baseline: K=1 {md_base_tps:.1f} tok/s")
for K in (4, 8, 16):
    md_toks, md_tps, md_snap = run_multi_probe(K)
    tpl = md_snap.get("decode_tokens_per_launch", 0)
    print(f"MULTI_DECODE_CHIP K={K} tok_s={md_tps:.1f} "
          f"speedup={md_tps / md_base_tps:.2f}x "
          f"tokens_per_launch={tpl} "
          f"tpot_p50_ms={md_snap.get('tpot_p50_ms')} "
          f"launches={md_snap.get('decode_launches')}")
    # full batch, uniform lengths, no EOS: every row emits its cap
    # each launch — the >= 0.9 K acceptance number is host-exact
    assert tpl >= 0.9 * K, (K, tpl)
    if ON_TPU:
        assert md_toks == md_base, f"K={K} changed greedy tokens"
    elif md_toks != md_base:
        m = sum(a == b for bo, so in zip(md_base, md_toks)
                for a, b in zip(bo, so))
        t = sum(len(v) for v in md_base)
        print(f"MULTI_DECODE_CPU_REPORT_ONLY K={K} match={m}/{t} "
              "(hard gate runs on TPU)")
print("MULTI_DECODE_CHIP_OK")

# --- tensor-parallel serving probe (ISSUE 8) ---------------------------
# TP in {1, 2, 4} engines over the hybrid mesh's 'model' axis at FIXED
# model size: tok/s and per-chip KV GB/s (global engine-accounted bytes
# / tp / wall — bytes-true through paged_page_bytes), plus the page-
# capacity multiplier at a fixed per-chip pool budget. Timing is
# fetch-synced by construction (every step() host-fetches the sampled
# tokens — a sync on any access path). Degrees are clamped to the devices actually present —
# one chip probes TP=1 only and says so. Greedy token
# identity across degrees is a CHIP gate (ON_TPU, same rationale as
# the eager-vs-jit gate above: TP changes reduction layouts, and CPU
# near-tie bf16 rounding is report-only off chip); written without a chip —
# the CPU contract is pinned by tests/test_serving_tp.py in f32.
from paddle_tpu.serving import tp_serving_mesh

TP_PROMPTS = [rng.randint(0, cfg.vocab_size, (12,)).tolist()
              for _ in range(8)]
tp_degrees = [t for t in (1, 2, 4)
              if t <= len(jax.devices())
              and cfg.num_key_value_heads % t == 0]
if tp_degrees[1:]:
    tp_outs = {}
    for tp in tp_degrees:
        import paddle_tpu as _p
        _p.seed(0)
        tmodel = LlamaForCausalLM(cfg)
        tmodel.bfloat16()
        eng = ServingEngine(tmodel, num_pages=128, page_size=16,
                            batch_buckets=[8], prefill_buckets=[16, 128],
                            pages_buckets=[8], temperature=0.0,
                            mesh=tp_serving_mesh(tp) if tp > 1 else None)
        t0 = time.perf_counter()
        rids = [eng.add_request(p, max_new_tokens=32) for p in TP_PROMPTS]
        out = eng.run()
        wall = time.perf_counter() - t0
        snap = eng.metrics.snapshot()
        tp_outs[tp] = [out[r] for r in rids]
        toks = sum(len(t) for t in tp_outs[tp])
        kv_gb = (snap["kv_bytes_read"] + snap["kv_bytes_written"]) / 1e9
        print(f"TP_SERVING_CHIP tp={tp} tok_s={toks / wall:.1f} "
              f"per_chip_kv_gbps={kv_gb / tp / wall:.2f} "
              f"page_bytes_shard={snap['kv_page_bytes_shard']}")
        assert eng.num_compiled_programs <= eng.max_program_count()
        eng.reset_prefix_cache()
        assert eng.allocator.num_used == 0
        eng.shutdown()
        if tp > 1:
            if ON_TPU:
                assert tp_outs[tp] == tp_outs[1], \
                    f"TP={tp} changed greedy tokens"
            elif tp_outs[tp] != tp_outs[1]:
                m = sum(a == b for bo, so in zip(tp_outs[1], tp_outs[tp])
                        for a, b in zip(bo, so))
                t = sum(len(v) for v in tp_outs[1])
                print(f"TP_CPU_REPORT_ONLY tp={tp} match={m}/{t} "
                      "(hard gate runs on TPU)")
    # per-chip capacity multiplier at a fixed pool budget (pure
    # geometry through paged_page_bytes — asserted anywhere)
    pb1 = paged_page_bytes(cfg.num_key_value_heads, 16,
                           cfg.hidden_size // cfg.num_attention_heads,
                           "bfloat16")
    tp_hi = tp_degrees[-1]
    pb_shard = paged_page_bytes(cfg.num_key_value_heads // tp_hi, 16,
                                cfg.hidden_size // cfg.num_attention_heads,
                                "bfloat16")
    POOL = 64 << 20
    print(f"TP page capacity at {POOL >> 20} MB/chip: tp1 {POOL // pb1} "
          f"tp{tp_hi} {POOL // pb_shard} "
          f"({(POOL // pb_shard) / (POOL // pb1):.2f}x)")
    assert POOL // pb_shard >= tp_hi * (POOL // pb1)
    print("TP_SERVING_CHIP_OK")
else:
    print(f"TP_SERVING_CHIP_SKIPPED: {len(jax.devices())} device(s) — "
          "one chip; the TP probe needs more")

# --- multi-LoRA serving probe (ISSUE 15) -------------------------------
# N-adapter tok/s vs the single-adapter baseline over the same
# 8-request workload: every decode launch mixes adapters (the masked
# segment-bmm streams each loaded adapter's A/B once per launch), so
# the ladder measures what serving N adapters costs over serving one —
# the >= 0.7x acceptance bar. Timing is fetch-synced by construction
# (step() host-fetches tokens). Per-adapter identity vs a solo engine
# is a CHIP gate (ON_TPU — this probe's model is bf16 and CPU rounds
# near-tie logits differently; the f32 CPU identity contract is pinned
# by tests/test_serving_lora.py).
from paddle_tpu.serving import AdapterRegistry, LoRAAdapter
from paddle_tpu.serving.lora.store import llama_lora_dims

LORA_DIMS = llama_lora_dims(cfg)
LORA_PROMPTS = [rng.randint(0, cfg.vocab_size, (12,)).tolist()
                for _ in range(8)]


def _lora_adapter(i):
    return LoRAAdapter.random(f"ad{i}", 8, LORA_DIMS, seed=500 + i)


def run_lora_probe(n_adapters):
    import paddle_tpu as _p
    _p.seed(0)
    lmodel = LlamaForCausalLM(cfg)
    lmodel.bfloat16()
    reg = AdapterRegistry(LORA_DIMS, rank_buckets=(8,),
                          slots=max(2, n_adapters + 1))
    for i in range(n_adapters):
        reg.load(_lora_adapter(i))
    eng = ServingEngine(lmodel, lora=reg, num_pages=256, page_size=16,
                        batch_buckets=[8], prefill_buckets=[16, 128],
                        pages_buckets=[8], temperature=0.0)
    t0 = time.perf_counter()
    rids = [eng.add_request(p, max_new_tokens=32,
                            adapter=f"ad{j % n_adapters}")
            for j, p in enumerate(LORA_PROMPTS)]
    out = eng.run()
    wall = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    assert eng.num_compiled_programs <= eng.max_program_count()
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    reg.check_invariants()
    eng.shutdown()
    toks = {j: out[r] for j, r in enumerate(rids)}
    return toks, sum(len(t) for t in toks.values()) / wall, snap


lora_outs, lora_base_tps, _ = run_lora_probe(1)
print(f"lora baseline: 1 adapter {lora_base_tps:.1f} tok/s")
lora_na_outs = {}
for NA in (4, 8):
    la_outs, la_tps, la_snap = run_lora_probe(NA)
    lora_na_outs[NA] = la_outs
    print(f"LORA_CHIP n_adapters={NA} tok_s={la_tps:.1f} "
          f"vs_solo={100 * la_tps / lora_base_tps:.1f}% "
          f"adapter_mix_p50={la_snap.get('adapter_mix_p50')} "
          f"loaded={la_snap.get('adapters_loaded')}")
    if ON_TPU:
        # the >= 0.7x acceptance bar is a CHIP number (CPU
        # wall times are harness evidence only)
        assert la_tps >= 0.7 * lora_base_tps, (la_tps, lora_base_tps)

# per-adapter identity: mixed engine rows == a solo engine running the
# SAME rows with only that adapter loaded (hard gate ON_TPU only)
import paddle_tpu as _p
_p.seed(0)
_solo_model = LlamaForCausalLM(cfg)
_solo_model.bfloat16()
_solo_reg = AdapterRegistry(LORA_DIMS, rank_buckets=(8,), slots=2)
_solo_reg.load(_lora_adapter(0))
_solo_eng = ServingEngine(_solo_model, lora=_solo_reg, num_pages=256,
                          page_size=16, batch_buckets=[8],
                          prefill_buckets=[16, 128], pages_buckets=[8],
                          temperature=0.0)
_mix4 = lora_na_outs[4]
_solo_rids = [_solo_eng.add_request(p, max_new_tokens=32, adapter="ad0")
              for j, p in enumerate(LORA_PROMPTS) if j % 4 == 0]
_solo_out = _solo_eng.run()
_solo_eng.shutdown()
solo_toks = [_solo_out[r] for r in _solo_rids]
mix_toks = [_mix4[j] for j in range(len(LORA_PROMPTS)) if j % 4 == 0]
if ON_TPU:
    assert solo_toks == mix_toks, "mixed engine changed adapter-0 tokens"
    print("LORA_IDENTITY_CHIP_OK")
elif solo_toks != mix_toks:
    m = sum(a == b for so, mo in zip(solo_toks, mix_toks)
            for a, b in zip(so, mo))
    t = sum(len(v) for v in solo_toks)
    print(f"LORA_CPU_REPORT_ONLY match={m}/{t} (hard gate runs on TPU)")
print("LORA_CHIP_OK")

# --- tiered-KV spill probe (ISSUE 17) ----------------------------------
# Cached-token rate at a tiny FORCED-SPILL device pool vs the same pool
# HBM-only: 24 queued requests round-robin 4 distinct 64-token (4-page)
# prefixes against a 22-page device pool, so the radix tree cannot hold
# all 16 prefix pages on device alongside the live batch — HBM-only
# drops the LRU prefix and recomputes it, the spill tier demotes it to
# host RAM and promotes it back on the next hit (promotion needs free
# device pages AT match time, which is why the requests run as one
# continuously-batched queue: duplicate-span donations from completing
# cache-hit rows return their shared pages to the free list mid-run —
# the sequential one-at-a-time shape starves promotion by design).
# Bit-identity spill-on vs spill-off is a HARD gate everywhere (not
# just ON_TPU): promotion restores the exact bytes the prefill wrote,
# and spill on/off cannot change program shapes, so there is no
# legitimate divergence source on any backend (the CPU contract is
# pinned by tests/test_serving_spill.py). The cached-token counters
# are host-exact bookkeeping and assert anywhere; wall-clock is
# printed, not asserted (chip variance stays out of the gate). On chip
# this is the first time the promotion host->device copy runs against
# a real device.
from paddle_tpu.utils import faults

spill_rng = np.random.RandomState(17)
SPILL_SHARED = [spill_rng.randint(0, cfg.vocab_size, (64,)).tolist()
                for _ in range(4)]
SPILL_TAILS = [spill_rng.randint(0, cfg.vocab_size, (8,)).tolist()
               for _ in range(24)]


def run_spill_probe(host_pages):
    eng = ServingEngine(model, num_pages=22, page_size=16,
                        batch_buckets=[4], prefill_buckets=[128],
                        pages_buckets=[8], temperature=0.0,
                        host_spill_pages=host_pages)
    t0 = time.perf_counter()
    rids = [eng.add_request(SPILL_SHARED[i % 4] + tail,
                            max_new_tokens=16)
            for i, tail in enumerate(SPILL_TAILS)]
    out = eng.run()
    outs = [out[r] for r in rids]
    wall = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    if eng.host_store is not None:
        assert eng.host_store.num_used == 0          # both pools reclaim
        eng.host_store.check_invariants()
    assert eng.num_compiled_programs <= eng.max_program_count()
    eng.shutdown()
    return outs, wall, snap


sp_off, sp_off_wall, sp_off_snap = run_spill_probe(0)
sp_on, sp_on_wall, sp_on_snap = run_spill_probe(32)
print(f"TIERED_KV_CHIP off: wall {sp_off_wall:.3f}s "
      f"cached_tokens {sp_off_snap['cached_tokens_served']} "
      f"| on: wall {sp_on_wall:.3f}s "
      f"cached_tokens {sp_on_snap['cached_tokens_served']} "
      f"demoted {sp_on_snap['kv_pages_demoted']} "
      f"promoted {sp_on_snap['kv_pages_promoted']} "
      f"host_hits {sp_on_snap['host_prefix_hits']}")
assert sp_on == sp_off, "spill tier changed greedy tokens"
assert sp_on_snap["kv_pages_demoted"] > 0
assert sp_on_snap["kv_pages_promoted"] > 0
assert sp_on_snap["host_prefix_hits"] >= 1
# the acceptance number: cached-token rate ABOVE the HBM-only ceiling
# at FIXED device-pool bytes
assert sp_on_snap["cached_tokens_served"] > \
    sp_off_snap["cached_tokens_served"], (sp_on_snap, sp_off_snap)

# fault degrade on the real promotion path: one corrupt host page must
# fall back to recompute-from-radix-prefix with identical tokens
faults.inject("host_spill.corrupt", payload=True, after=1, times=1)
try:
    sp_chaos, _, sp_chaos_snap = run_spill_probe(32)
    assert faults.fired_counts().get("host_spill.corrupt", 0) >= 1
finally:
    faults.clear()
    faults.reset_counts()
assert sp_chaos == sp_off, "corrupt-page recompute changed greedy tokens"
assert sp_chaos_snap["host_spill_corrupt"] >= 1
print(f"TIERED_KV_CHIP_OK cached_on={sp_on_snap['cached_tokens_served']} "
      f"cached_off={sp_off_snap['cached_tokens_served']} "
      f"corrupt_recomputes={sp_chaos_snap['host_spill_corrupt']}")

# --- disaggregated prefill/decode probe (ISSUE 18) ---------------------
# The handoff round trip ON the real chip, in ONE process (the chip's
# single-process rule forbids spawning role workers here, so this
# drives the same engine-level machinery the fleet supervisor
# orchestrates): a prefill-role engine runs admission + chunked
# prefill + first token and finishes "handoff"; the donated prefix
# exports, rides the real chunk/join payload codec (FRAME_CAP
# chunking, CRC per page), and a SECOND engine adopts the pages and
# streams the rest. Bit-identity vs the co-located engine is a HARD
# gate everywhere (single-bucket grid + greedy: the adopted
# continuation replays the preemption-resume path); on chip this is
# the first time the exported page bytes round-trip through device
# fetch + host re-upload against a real device.
from paddle_tpu.serving.fleet.transport import (chunk_payloads,
                                                join_payloads)

DG_KW = dict(num_pages=48, page_size=16, token_budget=64,
             batch_buckets=[8], prefill_buckets=[64], pages_buckets=[8],
             temperature=0.0)
dg_rng = np.random.RandomState(18)
DG_WORK = [(dg_rng.randint(0, cfg.vocab_size, (dg_rng.randint(32, 48),))
            .tolist(), 12) for _ in range(8)]

dg_ref_eng = ServingEngine(model, **DG_KW)
dg_ref_rids = [dg_ref_eng.add_request(p, max_new_tokens=m)
               for p, m in DG_WORK]
dg_t0 = time.perf_counter()
dg_ref = dg_ref_eng.run()
dg_coloc_wall = time.perf_counter() - dg_t0
dg_ref_eng.shutdown()

dg_pre = ServingEngine(model, role="prefill", **DG_KW)
dg_dec = ServingEngine(model, **DG_KW)
dg_t0 = time.perf_counter()
dg_rids = [dg_pre.add_request(p, max_new_tokens=m) for p, m in DG_WORK]
while dg_pre.has_work():
    dg_pre.step()
dg_shipped = 0
dg_recs = []
for (p, m), rid in zip(DG_WORK, dg_rids):
    req = dg_pre.requests[rid]
    assert req.finish_reason == "handoff", req.finish_reason
    toks = (p + list(req.output_ids))[:req.handoff_prefix_len]
    n, payloads = dg_pre.export_prefix(toks)
    assert n == req.handoff_prefix_len, (n, req.handoff_prefix_len)
    adopted = dg_dec.adopt_prefix(
        toks[:n], join_payloads(chunk_payloads(payloads)))
    assert adopted == len(payloads), (adopted, len(payloads))
    dg_shipped += adopted
    dg_pre.release_prefix(toks[:n])
    dg_recs.append({"request_id": rid, "prompt_ids": p,
                    "output_ids": list(req.output_ids),
                    "max_new_tokens": m, "eos_token_id": None,
                    "num_preemptions": 0, "aborted": False,
                    "adapter": None, "colocate": False,
                    "deadline_remaining_s": None})
dg_dec.adopt_requests(dg_recs)
dg_out = dg_dec.run()
dg_wall = time.perf_counter() - dg_t0
# adopted records fold the pre-handoff tokens back in, so the decode
# engine's output IS the full stream
assert [dg_out[r] for r in dg_rids] == \
    [dg_ref[r] for r in dg_ref_rids], \
    "disaggregated handoff changed greedy tokens"
assert dg_pre.metrics.counters["prefill_handoffs"] == len(DG_WORK)
assert dg_dec.metrics.counters["kv_pages_adopted"] == dg_shipped
for e in (dg_pre, dg_dec):
    e.reset_prefix_cache()
    assert e.allocator.num_used == 0
    e.shutdown()
print(f"DISAGG_CHIP_OK pages_shipped={dg_shipped} "
      f"handoffs={len(DG_WORK)} coloc_wall={dg_coloc_wall:.3f}s "
      f"disagg_wall={dg_wall:.3f}s")

print("CHIP_SERVING_ALL_OK")
