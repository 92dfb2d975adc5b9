"""Multi-step device-side decode (ISSUE 13): K decode iterations per
compiled launch through the ("multi_decode", B, K, P) program family.

The contracts pinned here, CPU/f32 (the chip probe in
tools/chip_serving.py re-asserts the bf16 identity gate ON_TPU):

* greedy output bit-identical to K=1 for a 16-request mixed workload —
  prefix hits, int8 KV, and abort/TTL mid-launch each exercised;
* tokens/launch >= 0.9 K at full batch; emitted slots past a row's
  finish masked to the -1 sentinel in-graph;
* EOS freezes a row mid-launch at exactly the K=1 stopping point;
* abort()/TTL take effect at the next K-boundary with the launch's
  tokens delivered (injectable clock — no token loss, no emission
  beyond the in-graph cap);
* NaN quarantine applies per LAUNCH (poisoned row delivers none of the
  failing launch's tokens; the rest of the batch is unaffected);
* snapshot/resume at a K-boundary completes bit-identically on both a
  K engine and a K=1 engine;
* ProgramCache: K rides the key, the per-family bound holds;
* TPOT reservoir divides launch latency by tokens emitted, so the
  per-token percentiles stay comparable across K (drift test vs K=1);
* decode_steps x proposer mutual exclusion and the MAX_DECODE_STEPS
  ceiling fail loud at construction.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import MAX_DECODE_STEPS
from paddle_tpu.utils import faults

from _engine_steps import drain, step_until


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    paddle.seed(0)
    return LlamaForCausalLM(cfg)


ENGINE_KW = dict(num_pages=64, page_size=8, token_budget=48,
                 batch_buckets=[16], prefill_buckets=[8, 16, 32],
                 pages_buckets=[2, 4, 8], temperature=0.0)


def _prompts(n=16, shared=6, eos_every=0):
    """Mixed workload with a shared prefix block (radix hits);
    `eos_every` > 0 gives every that-many-th request an eos_token_id
    (random — with the 128-token vocab some fire mid-stream, which the
    identity test asserts for its fixed seed)."""
    rng = np.random.RandomState(7)
    head = rng.randint(0, 128, (16,)).tolist()
    out = []
    for i in range(n):
        if i < shared:
            p = head + rng.randint(0, 128, (rng.randint(1, 6),)).tolist()
        else:
            p = rng.randint(0, 128, (rng.randint(2, 24),)).tolist()
        eos = int(rng.randint(0, 128)) \
            if eos_every and i % eos_every == 0 else None
        out.append((p, int(rng.randint(3, 13)), eos))
    return out


def _run_all(eng, prompts):
    rids = [eng.add_request(p, max_new_tokens=m, eos_token_id=e)
            for p, m, e in prompts]
    out = eng.run()
    return [out[r] for r in rids]


def test_greedy_identity_vs_k1_mixed_workload(model):
    """16 mixed requests (prefix hits and mid-stream EOS stops
    included): K=4 engine tokens == K=1 engine tokens, and the program
    keys/bounds hold."""
    base = _prompts()
    clean = _run_all(ServingEngine(model, **ENGINE_KW), base)
    # every 3rd request gets an eos it is GUARANTEED to emit
    # mid-stream (its own 2nd clean token), so the in-graph EOS freeze
    # is exercised inside the identity contract
    prompts = [(p, m, clean[i][1] if i % 3 == 0 and m > 2 else e)
               for i, (p, m, e) in enumerate(base)]
    out1 = _run_all(ServingEngine(model, **ENGINE_KW), prompts)
    eng = ServingEngine(model, decode_steps=4, **ENGINE_KW)
    out4 = _run_all(eng, prompts)
    assert out4 == out1
    assert eng.metrics.counters["prefix_hits"] > 0
    assert any(r.finish_reason == "stop" for r in eng.requests.values())
    # K rides every multi_decode key; the per-family bound holds
    mkeys = [k for k in eng.programs.keys() if k[0] == "multi_decode"]
    assert mkeys and all(k[2] in (1, 2, 4) for k in mkeys)
    counts = eng.program_counts()
    assert counts["decode"] == 0          # the K=1 family never compiled
    for fam, n in counts.items():
        assert n <= eng.max_program_count(fam)
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.allocator.check_invariants()


@pytest.mark.slow
def test_greedy_identity_vs_k1_int8_kv():
    """Slow-marked like the PR-8 TP identity VARIANTS: tier-1 keeps
    the core mixed-workload identity; `make test` runs this int8
    variant explicitly."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    prompts = _prompts(8)
    paddle.seed(0)
    out1 = _run_all(ServingEngine(LlamaForCausalLM(cfg), kv_dtype="int8",
                                  **ENGINE_KW), prompts)
    paddle.seed(0)
    eng = ServingEngine(LlamaForCausalLM(cfg), kv_dtype="int8",
                        decode_steps=4, **ENGINE_KW)
    assert _run_all(eng, prompts) == out1
    assert any(k[0] == "multi_decode" and "int8" in k
               for k in eng.programs.keys())


@pytest.mark.slow   # tier-1 870s budget (PR 14): joins this module's make-test slow set
def test_tokens_per_launch_at_full_batch(model):
    """Full batch, uniform lengths, no EOS: every row emits its cap
    each launch, so tokens per row-launch >= 0.9 K."""
    eng = ServingEngine(model, decode_steps=4,
                        num_pages=128, page_size=8, token_budget=128,
                        batch_buckets=[8], prefill_buckets=[16],
                        pages_buckets=[8], temperature=0.0)
    rng = np.random.RandomState(0)
    for _ in range(8):
        eng.add_request(rng.randint(0, 128, (10,)).tolist(),
                        max_new_tokens=16)
    eng.run()
    snap = eng.metrics.snapshot()
    assert snap["decode_tokens_per_launch"] >= 0.9 * 4
    assert snap["decode_launch_steps"] >= snap["decode_launches"] * 4
    # decode_k rides the flight-recorder step records (ISSUE 13
    # observability satellite)
    ks = [r["decode_k"] for r in eng.timeline() if r["decode_batch"]]
    assert ks and all(k == 4 for k in ks)


@pytest.mark.slow
def test_eos_freezes_row_mid_launch_and_sentinel(model):
    """A row whose EOS lands mid-launch stops exactly where K=1 stops,
    and the in-graph sentinel masks the slots past the freeze.
    Slow-marked (four engine drains); `make test` runs it explicitly —
    the EOS path itself is also exercised tier-1 through the mixed
    identity workload's "stop"-finishing rows."""
    prompt = list(range(3, 13))
    ref = ServingEngine(model, **ENGINE_KW)
    rid = ref.add_request(prompt, max_new_tokens=10)
    full = ref.run()[rid]
    # an eos value whose FIRST occurrence lands mid-launch (index >= 2)
    stop_at = next(j for j in range(2, len(full))
                   if full[j] not in full[:j])
    eos = full[stop_at]
    e1 = ServingEngine(model, **ENGINE_KW)
    r1 = e1.add_request(prompt, max_new_tokens=10, eos_token_id=eos)
    out1 = e1.run()[r1]
    e4 = ServingEngine(model, decode_steps=4, **ENGINE_KW)
    r4 = e4.add_request(prompt, max_new_tokens=10, eos_token_id=eos)
    out4 = e4.run()[r4]
    assert out4 == out1 == full[:stop_at + 1]
    assert e4.requests[r4].finish_reason == "stop"
    # sentinel: drive one raw launch and look past the freeze point
    e = ServingEngine(model, decode_steps=4, **ENGINE_KW)
    r = e.add_request(prompt, max_new_tokens=10, eos_token_id=eos)
    e.step()                            # prefill + first token
    req = e.requests[r]
    cap = min(4, req.remaining_new_tokens())
    # mimic the scheduler's per-launch slot reservation (schedule()
    # step 1 appends the input token's slot before the engine extends)
    assert not e.allocator.append_token(req.seq)
    granted, _copies = e._extend_slots(req, cap - 1)
    assert granted == cap - 1
    toks, n_emit, oks, _dt = e._run_multi_decode([req], [1 + granted], 4)
    exp = min(stop_at, 4)       # launch emits global tokens 1..stop_at
    assert int(n_emit[0]) == exp
    assert all(int(t) == -1 for t in toks[0, exp:])
    assert bool(oks[0])


def test_abort_and_ttl_at_k_boundary(model):
    """Expiry/abort take effect at the NEXT K-boundary: the launch
    that straddles the deadline still delivers its tokens (no token
    loss), nothing is emitted after the boundary, and the KV is
    donated. Injectable clock — the deadline passes mid-launch."""
    clock = {"t": 0.0}
    eng = ServingEngine(model, decode_steps=4, clock=lambda: clock["t"],
                        **ENGINE_KW)
    prompt = list(range(2, 14))
    rid = eng.add_request(prompt, max_new_tokens=12, ttl_s=1.0)
    emitted = []
    emitted += [t for _, t in eng.step()]       # prefill + token 1
    emitted += [t for _, t in eng.step()]       # K-launch: tokens 2-5
    n_before = len(emitted)
    assert n_before == 5
    clock["t"] = 2.0            # deadline passed DURING that launch
    emitted += [t for _, t in eng.step()]       # boundary: cancel
    req = eng.requests[rid]
    assert req.finish_reason == "expired"
    assert len(emitted) == n_before             # delivered, then cut
    assert req.output_ids == emitted            # no token lost
    assert eng.radix.num_cached_pages > 0       # valid KV donated
    # the delivered prefix is bit-identical to the K=1 stream
    ref = ServingEngine(model, **ENGINE_KW)
    rref = ref.add_request(prompt, max_new_tokens=12)
    assert ref.run()[rref][:len(emitted)] == emitted
    # abort: same boundary semantics
    eng2 = ServingEngine(model, decode_steps=4, **ENGINE_KW)
    rid2 = eng2.add_request(prompt, max_new_tokens=12)
    eng2.step()
    eng2.step()
    got = len(eng2.requests[rid2].output_ids)
    assert got == 5
    assert eng2.abort(rid2)
    out = eng2.step()
    assert out == [] and \
        eng2.requests[rid2].finish_reason == "abort"
    assert len(eng2.requests[rid2].output_ids) == got
    for e in (eng, eng2):
        e.reset_prefix_cache()
        assert e.allocator.num_used == 0


@pytest.mark.slow
def test_quarantine_per_launch(model):
    """nan_logits on one row of a multi launch: that request is
    quarantined alone with NO tokens from the failing launch; the
    others complete identically to an unfaulted run. Slow-marked (two
    full drains); `make test` runs it explicitly."""
    rng = np.random.RandomState(11)
    prompts = [(rng.randint(0, 128, (10,)).tolist(), 8, None)
               for _ in range(4)]
    clean = _run_all(ServingEngine(model, decode_steps=4,
                                   enable_prefix_cache=False,
                                   **ENGINE_KW), prompts)
    eng = ServingEngine(model, decode_steps=4, enable_prefix_cache=False,
                        **ENGINE_KW)
    rids = [eng.add_request(p, max_new_tokens=m)
            for p, m, _e in prompts]
    from paddle_tpu.serving import RequestState
    # chunked prefills may straddle steps
    step_until(eng, lambda: all(
        eng.requests[r].state is RequestState.DECODE for r in rids))
    pre = len(eng.requests[rids[1]].output_ids)
    assert pre >= 1
    # armed only once every row decodes: the next launch is a 4-row
    # multi decode launch, and row 1 is the poisoned one
    with faults.injected("serving.engine.nan_logits", payload=[1],
                         times=1):
        eng.step()
    out = eng.run()
    snap = eng.metrics.snapshot()
    assert snap["requests_quarantined"] == 1
    bad = eng.requests[rids[1]]
    assert bad.finish_reason == "quarantined"
    # per-LAUNCH granularity: nothing from the poisoned launch landed
    assert len(bad.output_ids) == pre
    for i in (0, 2, 3):
        assert eng.requests[rids[i]].output_ids == clean[i]
    assert eng.allocator.num_used == 0          # quarantine freed all


@pytest.mark.slow
def test_snapshot_resume_at_k_boundary(model):
    """A fatal mid-drain failure drains to a snapshot; resuming on a
    K=4 engine AND a K=1 engine both complete bit-identically to the
    uninterrupted run (K-boundary recompute resume). Slow-marked
    (three full drains); `make test` runs it explicitly."""
    prompts = _prompts(4, shared=0)
    clean = _run_all(ServingEngine(model, decode_steps=4,
                                   enable_prefix_cache=False,
                                   **ENGINE_KW), prompts)
    from paddle_tpu.serving import EngineFailure
    eng = ServingEngine(model, decode_steps=4, enable_prefix_cache=False,
                        **ENGINE_KW)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m, _e in prompts]
    eng.step()
    eng.step()
    with faults.injected("serving.engine.multi_decode_step",
                         exc=RuntimeError("INVALID_ARGUMENT: boom"),
                         times=1):
        with pytest.raises(EngineFailure):
            drain(eng)
    snap = eng.last_snapshot
    assert snap is not None and snap["requests"]
    for k in (4, 1):
        res = ServingEngine.from_snapshot(
            model, snap, decode_steps=k, enable_prefix_cache=False,
            **ENGINE_KW)
        out = res.run()
        for i, rid in enumerate(rids):
            if rid in res.requests:
                assert res.requests[rid].output_ids == clean[i]
            else:               # finished before the failure
                assert out.get(rid, clean[i]) == clean[i]


def test_tpot_reservoir_per_token_across_k(model, monkeypatch):
    """The TPOT sample is launch seconds / tokens emitted: with a
    pinned launch duration, a K=4 launch emitting 4 tokens and a K=1
    launch emitting 1 must sample THE SAME per-token number — the
    PR-10 p99s stay comparable across K."""
    from paddle_tpu.serving import engine as engine_mod
    tick = {"t": 0.0}

    def fake_perf():
        tick["t"] += 0.005          # every timer read advances 5 ms
        return tick["t"]

    monkeypatch.setattr(engine_mod, "_perf_counter", fake_perf)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 128, (10,)).tolist()
    samples = {}
    for k in (1, 4):
        eng = ServingEngine(model, decode_steps=k, num_pages=64,
                            page_size=8, token_budget=32,
                            batch_buckets=[1], prefill_buckets=[16],
                            pages_buckets=[4], temperature=0.0)
        eng.add_request(prompt, max_new_tokens=9)
        eng.run()
        res = list(eng.metrics._reservoirs["tpot"])
        assert len(res) == eng.metrics.counters["decode_launches"]
        samples[k] = res
        assert eng.metrics.snapshot()["tpot_p50_ms"] > 0
    # one timer delta per launch = 0.005 s; K=1 divides by 1 token,
    # K=4 by 4 tokens on the full launches — per-token equality
    assert samples[1][0] == pytest.approx(0.005)
    assert samples[4][0] == pytest.approx(0.005 / 4)


def test_construction_validation(model):
    from paddle_tpu.serving import NgramProposer
    with pytest.raises(ValueError, match="mutually exclusive"):
        ServingEngine(model, decode_steps=4, proposer=NgramProposer(),
                      **ENGINE_KW)
    with pytest.raises(ValueError, match="MAX_DECODE_STEPS"):
        ServingEngine(model, decode_steps=MAX_DECODE_STEPS + 1,
                      **ENGINE_KW)
    with pytest.raises(ValueError, match="decode_steps"):
        ServingEngine(model, decode_steps=0, **ENGINE_KW)
    with pytest.raises(ValueError, match="multi bucket"):
        ServingEngine(model, decode_steps=8, multi_buckets=[2, 4],
                      **ENGINE_KW)


def test_program_cache_bound_enforced(model):
    """The multi_decode family bound is the B x K x P grid — a leaked
    key axis fails loud."""
    eng = ServingEngine(model, decode_steps=4, **ENGINE_KW)
    bound = eng.max_program_count("multi_decode")
    assert bound == (len(eng.batch_buckets) * len(eng.multi_buckets)
                     * len(eng.pages_buckets))
    for i in range(bound):
        eng.programs.get(("multi_decode", "fake", i), lambda: object())
    with pytest.raises(RuntimeError, match="compile bound"):
        eng.programs.get(("multi_decode", "fake", bound),
                         lambda: object())
