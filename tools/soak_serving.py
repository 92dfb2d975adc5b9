"""Randomized fault-injection soak for the serving engine (ISSUE 3 + 5).

Runs the SAME seeded mixed workload four times on CPU — plain-decode
clean and chaos, then SPECULATIVE-decode (NgramProposer, K=4) clean and
chaos — and asserts the resilience acceptance criteria on each pair:

* zero engine crashes (injected transients can never exhaust the retry
  budget by construction: times <= max_retries);
* every KV page reclaimed and allocator/radix ref-counts consistent at
  drain;
* greedy outputs of UNAFFECTED requests bit-identical to the clean run
  (affected = quarantined / expired / aborted / shed);
* every fault point ARMED IN THAT PASS actually fired (a soak that
  injected nothing proves nothing);
* spec-decode extras (ISSUE 5): the spec-clean pass emits streams
  bit-identical to the plain clean pass (speculation only changes how
  many launches, never which tokens) with acceptance > 0, and the
  spec-chaos pass layers a draft-mismatch STORM (garbage drafts — all
  rejected, output-invariant by the acceptance rule), injected
  rollback-OOM during draft extension, and NaN in verify logits on top
  of the ISSUE-3 chaos;
* int8-KV extras (ISSUE 6): the same workload runs a clean + chaos
  pair under kv_dtype="int8" (quantized pages + per-slot scales) —
  unaffected requests must stay bit-identical WITHIN the int8 pair
  (quantize-on-write is deterministic, so chaos may only change
  affected requests, exactly like the full-precision pair), and every
  page/refcount reclamation check holds on the quantized pool.

Deterministic end to end: workload, fault schedule, aborts and the
deadline clock all derive from --seed; wall-clock never enters the
engine (FakeClock + storm skew only). Bounded runtime: the engine's own
drain guard plus a hard step ceiling.

* tiered-KV extras (ISSUE 17, `--spill`): a spill-pressure workload
  (six shared prefixes thrashing a shrunken device pool) runs three
  ways — host tier off, on, and on with every `host_spill.*` read
  fault armed. The tier must be token-invisible both times (spill
  on == off for EVERY request; faults degrade to recompute with NO
  affected requests), both pools must reclaim to zero at drain, every
  armed fault point must fire, and the clean spill pass must serve
  MORE cached tokens than the HBM-only ceiling at the same device
  pool (the perf_opt acceptance).

* multi-LoRA extras (ISSUE 15, `--lora`): the workload spread over 3
  resident adapters + base rows runs a clean/chaos pair — a 4th "hot"
  adapter's MID-STREAM load fails typed under chaos (its tail of the
  workload sheds `AdapterNotLoaded` at the door, never serves wrong
  weights), the `serving.lora.evict_race` guard refuses evicting a
  pinned adapter, and every co-batched row of the OTHER adapters stays
  bit-identical to the clean lora pass.

Usage:  JAX_PLATFORMS=cpu \
            python tools/soak_serving.py [--requests 200] [--seed 0]
(or `make soak`; --no-spec skips the two spec passes, --lora adds the
multi-LoRA pair, --spill the tiered-KV triple). Exits 0 on
success, 1 with a report on violation — this is a test harness, not
bench.py; it is allowed to fail loudly.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# CPU pin BEFORE jax initializes (mirror tests/conftest.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                   # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np                                           # noqa: E402

import paddle_tpu as paddle                                  # noqa: E402
from paddle_tpu.models.llama import (LlamaConfig,            # noqa: E402
                                     LlamaForCausalLM)
from paddle_tpu.serving import (EngineOverloaded,            # noqa: E402
                                NgramProposer, RetryPolicy,
                                ServingEngine, TransientDeviceError)
from paddle_tpu.utils import faults                          # noqa: E402

# single-bucket grid: every run hits identical program shapes, so the
# bit-identity comparison is exact (SERVING.md determinism contract).
# The spec passes pin a single K bucket too — a chaos-perturbed draft
# length then changes dl DATA, never the verify program shape.
ENGINE_KW = dict(num_pages=40, page_size=8, token_budget=48,
                 batch_buckets=[8], prefill_buckets=[32], pages_buckets=[8],
                 temperature=0.0, max_queue_len=32)
SPEC_KW = dict(spec_k=4, spec_buckets=[4])
TTL_S = 1000.0          # generous; only storm skew can expire anything
ABORT_FRACTION = 0.04
MAX_STEPS_FACTOR = 400  # hard ceiling: steps <= factor * num_requests


class FakeClock:
    """Engine deadline clock: advances a fixed tick per call, so expiry
    is a function of step count + injected storm skew, never host
    wall-clock."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def make_workload(n, seed):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, 128, (16,)).tolist()    # 2 full pages
    work = []
    for i in range(n):
        u = rng.random()
        if u < 0.3:                                 # radix exercise
            p = shared + rng.randint(0, 128, (rng.randint(2, 8),)).tolist()
        elif u < 0.55:                              # ngram exercise:
            cyc = rng.randint(0, 128, (rng.randint(2, 4),)).tolist()
            p = (cyc * 10)[:rng.randint(8, 24)]     # repetitive prompt
        else:
            p = rng.randint(0, 128, (rng.randint(4, 24),)).tolist()
        work.append((p, int(rng.randint(3, 10))))
    return work


def make_spill_workload(n, seed):
    """Spill-pressure variant (ISSUE 17): six shared 24-token (3-page)
    prefixes revisited round-robin with short random tails. The spill
    passes' shrunken device pool cannot hold all six prefixes plus the
    running tails at once, so the revisits force demote -> host ->
    promote cycles on every lap — exactly the traffic the host tier
    exists for, and a steady stream of store reads for the armed
    host_spill.* specs to hit."""
    rng = np.random.RandomState(seed + 17)
    prefixes = [rng.randint(0, 128, (24,)).tolist() for _ in range(6)]
    work = []
    for i in range(n):
        p = prefixes[i % len(prefixes)] + \
            rng.randint(0, 128, (rng.randint(2, 8),)).tolist()
        work.append((p, int(rng.randint(3, 8))))
    return work


def run_workload(model, work, *, chaos, seed, report, spec=False,
                 kv_dtype=None, trace=None, label=None, keep=None,
                 extra_kw=None, spill_chaos=False):
    """One full soak pass; returns ({idx: tokens}, affected_idx_set).
    `trace` (a RequestTracer) turns per-request tracing on for the
    pass (ISSUE 10 — the overhead measurement and the exported trace
    the `make soak` trace-report smoke reads); `keep` (a dict) receives
    the engine's flight-recorder timeline + Prometheus exposition
    before shutdown so the final report prints through the
    observability paths instead of an ad-hoc dict dump. `extra_kw`
    overrides engine kwargs (the spill passes shrink the device pool
    and attach the host tier); `spill_chaos` arms the three
    `host_spill.*` read-path faults INSTEAD of the engine chaos set —
    they must degrade to recompute with NO affected requests, so they
    get their own switch rather than riding `chaos`."""
    rng = np.random.RandomState(seed + 1)
    abort_at = {i for i in range(len(work))
                if rng.random() < ABORT_FRACTION} if chaos else set()

    kw = dict(ENGINE_KW, kv_dtype=kv_dtype)
    if extra_kw:
        kw.update(extra_kw)
    if spec:
        kw.update(SPEC_KW, proposer=NgramProposer())
    eng = ServingEngine(
        model, clock=FakeClock(), default_ttl_s=TTL_S,
        retry_policy=RetryPolicy(max_retries=12, base_s=0.0,
                                 sleep=lambda s: None),
        trace=trace, **kw)
    armed = set()

    def arm(name, **kwargs):
        faults.inject(name, **kwargs)
        armed.add(name)

    if chaos and spec:
        # ISSUE 5 chaos: draft-mismatch storm (garbage drafts — the
        # acceptance rule makes them output-invariant), rollback-OOM
        # during draft extension (the alloc point fires inside
        # append_token there too), NaN in verify logits, transient
        # verify-step exceptions. decode_step is NOT armed: the spec
        # engine replaces the decode launch with verify.
        arm("serving.spec.draft_storm", payload=True, after=2, times=2)
        arm("serving.spec.draft_storm", payload=True, prob=0.05,
            times=10, seed=seed + 9)
        arm("serving.engine.verify_step",
            exc=TransientDeviceError("soak: UNAVAILABLE"),
            after=4, times=1)
        arm("serving.engine.verify_step",
            exc=TransientDeviceError("soak: connection loss"),
            prob=0.03, times=9, seed=seed + 10)
    if chaos:
        # Every point gets one DETERMINISTIC early spec (the "every
        # armed point fired" assertion must not ride on a seeded coin)
        # plus a seeded probabilistic spec for spread. Transient totals
        # stay < max_retries(12), so retry exhaustion (and thus
        # EngineFailure) is impossible by construction.
        arm("serving.engine.prefill_chunk",
            exc=TransientDeviceError("soak: UNAVAILABLE"),
            after=3, times=1)
        arm("serving.engine.prefill_chunk",
            exc=TransientDeviceError("soak: UNAVAILABLE"),
            prob=0.03, times=9, seed=seed + 2)
        if not spec:
            arm("serving.engine.decode_step",
                exc=TransientDeviceError("soak: connection loss"),
                after=4, times=1)
            arm("serving.engine.decode_step",
                exc=TransientDeviceError("soak: connection loss"),
                prob=0.03, times=9, seed=seed + 3)
        arm("serving.kv.alloc_page", payload=True,
            after=5, times=2)
        arm("serving.kv.alloc_page", payload=True,
            prob=0.05, times=20, seed=seed + 4)
        nan_rng = np.random.RandomState(seed + 5)
        arm("serving.engine.nan_logits",
            payload=lambda reqs: [nan_rng.randint(len(reqs))],
            after=6, times=1)
        arm("serving.engine.nan_logits",
            payload=lambda reqs: [nan_rng.randint(len(reqs))],
            prob=0.02, times=3, seed=seed + 6)
        # the storm fires at boundary hits 11-12, whose combined 1200 s
        # of skew blows every pre-storm deadline (TTL 1000) — a burst
        # expiry wave mid-traffic
        arm("serving.engine.deadline_storm", payload=600.0,
            after=10, times=2)
        arm("serving.radix.insert",
            exc=RuntimeError("soak: donation failed"),
            after=2, times=1)
        arm("serving.radix.insert",
            exc=RuntimeError("soak: donation failed"),
            prob=0.05, times=7, seed=seed + 8)
    if spill_chaos:
        # ISSUE 17 chaos: every host-tier read-path fault. corrupt =
        # CRC reject at decode (node dropped, recompute); slow =
        # deadline miss (node kept on host, recompute now, retry
        # later); lost = backing buffer gone (slot forgotten under its
        # holders, node dropped, recompute). One deterministic early
        # spec per point + a seeded coin for spread, same convention
        # as the engine chaos set.
        arm("host_spill.corrupt", payload=True, after=1, times=1)
        arm("host_spill.corrupt", payload=True,
            prob=0.04, times=6, seed=seed + 11)
        arm("host_spill.slow", payload=True, after=3, times=1)
        arm("host_spill.slow", payload=True,
            prob=0.04, times=6, seed=seed + 12)
        arm("host_spill.lost", payload=True, after=5, times=1)
        arm("host_spill.lost", payload=True,
            prob=0.03, times=4, seed=seed + 13)

    idx_of = {}
    pending = list(enumerate(work))
    sheds = 0
    steps = 0
    max_steps = MAX_STEPS_FACTOR * max(1, len(work))
    out = {}
    try:
        while pending or eng.has_work():
            # arrival waves: up to 4 per step; shed -> retry next step
            admitted_this_step = 0
            while pending and admitted_this_step < 4:
                i, (p, m) = pending[0]
                try:
                    rid = eng.add_request(p, max_new_tokens=m)
                except EngineOverloaded:
                    sheds += 1
                    break
                idx_of[rid] = i
                pending.pop(0)
                admitted_this_step += 1
            for rid, tok in eng.step():
                i = idx_of[rid]
                out.setdefault(i, []).append(tok)
                if i in abort_at and len(out[i]) == 1:
                    eng.abort(rid)
            steps += 1
            if steps > max_steps:
                raise AssertionError(
                    f"soak failed to drain after {steps} steps")

        affected = set()
        reasons = {}
        for rid, i in idx_of.items():
            req = eng.requests.get(rid)
            assert req is not None, f"request {rid} evicted mid-soak"
            reasons[req.finish_reason] = reasons.get(
                req.finish_reason, 0) + 1
            if req.finish_reason in ("quarantined", "expired", "abort"):
                affected.add(i)
            out[i] = list(req.output_ids)

        # ---- reclamation + ref-count consistency at drain -----------
        if eng.radix is not None:
            eng.radix.check_invariants()
            assert eng.allocator.num_used == eng.radix.num_cached_pages
        eng.reset_prefix_cache()
        assert eng.allocator.num_used == 0, "KV pages leaked"
        eng.allocator.check_invariants()
        if getattr(eng, "host_store", None) is not None:
            # BOTH pools must come back empty (ISSUE 17 reclamation):
            # radix.clear() released every host tree ref too
            assert eng.host_store.num_used == 0, "host pages leaked"
            eng.host_store.check_invariants()

        snap = eng.metrics.snapshot()
        if label is None:
            label = ("int8_" if kv_dtype == "int8" else "") \
                + ("spec_" if spec else "") \
                + ("chaos" if chaos else "clean")
        rep = {
            "steps": steps, "sheds": sheds,
            "finish_reasons": reasons,
            "affected": len(affected),
            "preemptions": snap["requests_preempted"],
            "step_retries": snap["step_retries"],
            "quarantined": snap["requests_quarantined"],
            "expired": snap["deadline_expired"],
            "aborted": snap["requests_aborted"],
            "prefix_hits": snap["prefix_hits"],
        }
        if spec:
            rep.update({
                "spec_steps": snap["spec_steps"],
                "spec_drafted": snap["spec_drafted_tokens"],
                "spec_accepted": snap["spec_accepted_tokens"],
                "spec_rollback": snap["spec_rollback_tokens"],
                "spec_oom_drops": snap["spec_draft_oom_drops"],
                "spec_tokens_per_step": snap.get("spec_tokens_per_step"),
            })
        if getattr(eng, "host_store", None) is not None:
            rep.update({
                "cached_tokens": snap["cached_tokens_served"],
                "kv_pages_demoted": snap["kv_pages_demoted"],
                "kv_pages_promoted": snap["kv_pages_promoted"],
                "host_prefix_hits": snap["host_prefix_hits"],
                "host_pages_dropped": snap["host_pages_dropped"],
                "spill_faults": [snap["host_spill_corrupt"],
                                 snap["host_spill_slow"],
                                 snap["host_spill_lost"]],
            })
        elif extra_kw is not None:
            rep["cached_tokens"] = snap["cached_tokens_served"]
        report[label] = rep
        if chaos or spill_chaos:
            fired = faults.fired_counts()
            report[f"fired_{label}"] = fired
            for pt in sorted(armed):
                assert fired.get(pt, 0) >= 1, \
                    f"armed fault point {pt} never fired"
        if keep is not None:
            keep["timeline"] = eng.timeline()
            keep["prometheus"] = eng.metrics.prometheus_text()
        return out, affected
    finally:
        faults.clear()
        faults.reset_counts()
        eng.shutdown()


def run_lora_pass(model, work, *, chaos, seed, report):
    """Multi-LoRA pass (ISSUE 15): the same seeded workload spread over
    3 resident adapters (+ base rows), with a 4th "hot" adapter loaded
    MID-STREAM and the tail of the workload targeted at it.

    Chaos layer: `serving.lora.load_fail` makes the mid-stream load
    fail typed — every hot-adapter request then sheds typed
    (AdapterNotLoaded) at the door, and the co-batched rows of the
    OTHER adapters must stay bit-identical to the clean lora pass;
    `serving.lora.evict_race` is armed across a forced slot-pressure
    load while the resident adapters are pinned by live requests — the
    refcount guard must refuse (counted), never evict live weights.
    Plus the usual transient/NaN chaos so adapter'd rows exercise
    retry and per-row quarantine. Returns ({idx: tokens}, affected)."""
    from paddle_tpu.serving import (AdapterLoadError, AdapterNotLoaded,
                                    AdapterRegistry, LoRAAdapter)
    from paddle_tpu.serving.lora.store import llama_lora_dims
    dims = llama_lora_dims(model.cfg)

    def mk_adapter(name, seed_off):
        return LoRAAdapter.random(name, 4, dims, seed=700 + seed_off)

    # slots=5 -> 4 usable: ad0..ad2 + hot fill the bucket, so the
    # evict-race load below MUST attempt an eviction
    reg = AdapterRegistry(dims, rank_buckets=(8,), slots=5)
    for i in range(3):
        reg.load(mk_adapter(f"ad{i}", i))
    adapters = [None if i % 5 == 4 else f"ad{i % 3}"
                for i in range(len(work))]
    hot_from = max(1, len(work) - max(4, len(work) // 8))
    for i in range(hot_from, len(work)):
        adapters[i] = "hot"

    eng = ServingEngine(
        model, clock=FakeClock(), default_ttl_s=TTL_S,
        retry_policy=RetryPolicy(max_retries=12, base_s=0.0,
                                 sleep=lambda s: None),
        lora=reg, **ENGINE_KW)
    armed = set()

    def arm(name, **kwargs):
        faults.inject(name, **kwargs)
        armed.add(name)

    if chaos:
        # the lora points are armed IN the loop, immediately before
        # the load they target — arming order, not luck, decides which
        # load fails
        arm("serving.engine.decode_step",
            exc=TransientDeviceError("soak: connection loss"),
            after=4, times=1)
        nan_rng = np.random.RandomState(seed + 5)
        arm("serving.engine.nan_logits",
            payload=lambda reqs: [nan_rng.randint(len(reqs))],
            after=6, times=1)

    idx_of = {}
    pending = list(enumerate(work))
    out = {}
    affected = set()
    steps = 0
    hot_loaded = False
    hot_attempted = False
    evict_race_done = False
    max_steps = MAX_STEPS_FACTOR * max(1, len(work))
    try:
        while pending or eng.has_work():
            admitted = 0
            while pending and admitted < 4:
                i, (p, m) = pending[0]
                if i >= hot_from and not hot_attempted:
                    break            # hot tail waits for the load
                try:
                    rid = eng.add_request(p, max_new_tokens=m,
                                          adapter=adapters[i])
                except EngineOverloaded:
                    break
                except AdapterNotLoaded:
                    # typed shed at the door (hot load failed): the
                    # request is affected; co-batched rows must not be
                    affected.add(i)
                    out[i] = []
                    pending.pop(0)
                    continue
                idx_of[rid] = i
                pending.pop(0)
                admitted += 1
            if pending and pending[0][0] >= hot_from and \
                    not hot_attempted:
                # mid-stream: the hot adapter loads only once its tail
                # of the workload reaches the head of the queue; under
                # chaos the load fails typed and the tail sheds typed
                hot_attempted = True
                if chaos:
                    arm("serving.lora.load_fail", payload=True, times=1)
                try:
                    eng.load_adapter(mk_adapter("hot", 9))
                    hot_loaded = True
                except AdapterLoadError:
                    hot_loaded = False
            if chaos and not evict_race_done and \
                    len(eng.scheduler.running) >= 2:
                # forced slot pressure while the residents are pinned
                # by live requests: "spare" fills the bucket's last
                # slot, "spare2" then needs an eviction — the armed
                # race makes the evictor ATTEMPT a pinned victim; the
                # refcount guard must refuse it (counted) and take the
                # idle "spare" instead
                evict_race_done = True
                try:
                    eng.load_adapter(mk_adapter("spare", 11))
                except AdapterLoadError:
                    pass
                arm("serving.lora.evict_race", payload=True, times=1)
                try:
                    eng.load_adapter(mk_adapter("spare2", 12))
                except AdapterLoadError:
                    pass
            for rid, tok in eng.step():
                out.setdefault(idx_of[rid], []).append(tok)
            steps += 1
            if steps > max_steps:
                raise AssertionError(
                    f"lora soak failed to drain after {steps} steps")

        reasons = {}
        for rid, i in idx_of.items():
            req = eng.requests.get(rid)
            assert req is not None, f"request {rid} evicted mid-soak"
            reasons[req.finish_reason] = reasons.get(
                req.finish_reason, 0) + 1
            if req.finish_reason in ("quarantined", "expired", "abort"):
                affected.add(i)
            out[i] = list(req.output_ids)

        # every adapter unpinned at drain; reclamation exact
        for name in reg.adapter_names():
            assert reg.refs_of(name) == 0, (name, reg.refs_of(name))
        reg.check_invariants()
        eng.reset_prefix_cache()
        assert eng.allocator.num_used == 0, "KV pages leaked"
        eng.allocator.check_invariants()

        snap = eng.metrics.snapshot()
        label = "lora_chaos" if chaos else "lora_clean"
        report[label] = {
            "steps": steps, "hot_loaded": hot_loaded,
            "finish_reasons": reasons, "affected": len(affected),
            "adapters_loaded": snap["adapters_loaded"],
            "adapters_evicted": snap["adapters_evicted"],
            "adapter_rejects": snap["adapter_rejects"],
            "adapter_load_failures": snap["adapter_load_failures"],
            "lora_evict_refusals": snap["lora_evict_refusals"],
            "step_retries": snap["step_retries"],
            "quarantined": snap["requests_quarantined"],
            "prefix_hits": snap["prefix_hits"],
            "adapter_mix_p50": snap.get("adapter_mix_p50"),
        }
        if chaos:
            fired = faults.fired_counts()
            report[f"fired_{label}"] = fired
            for pt in sorted(armed):
                assert fired.get(pt, 0) >= 1, \
                    f"armed fault point {pt} never fired"
        return out, affected
    finally:
        faults.clear()
        faults.reset_counts()
        eng.shutdown()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-spec", action="store_true",
                    help="skip the two speculative-decoding passes")
    ap.add_argument("--lora", action="store_true",
                    help="add the multi-LoRA clean + chaos passes "
                         "(ISSUE 15: mid-stream adapter load failure "
                         "sheds typed, evict-race guard, co-batched "
                         "bit-identity)")
    ap.add_argument("--no-int8", action="store_true",
                    help="skip the two int8-KV passes")
    ap.add_argument("--spill", action="store_true",
                    help="add the tiered-KV passes (ISSUE 17: spill "
                         "off/clean/chaos on a spill-pressure workload "
                         "— host_spill.* faults degrade to recompute "
                         "bit-identically, both pools reclaim, cached-"
                         "token rate beats the HBM-only ceiling)")
    ap.add_argument("--trace-out",
                    default=os.path.join("profiler_log",
                                         "soak_trace.json"),
                    help="where the traced pass exports its merged "
                         "chrome-trace JSON (ISSUE 10)")
    args = ap.parse_args(argv)

    cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    work = make_workload(args.requests, args.seed)

    report = {"requests": args.requests, "seed": args.seed}
    t0 = time.perf_counter()
    clean, _ = run_workload(model, work, chaos=False, seed=args.seed,
                            report=report)
    chaotic, affected = run_workload(model, work, chaos=True,
                                     seed=args.seed, report=report)

    # ---- bit-identity of unaffected requests ------------------------
    diverged = [i for i in range(len(work))
                if i not in affected and chaotic.get(i) != clean.get(i)]
    assert not diverged, \
        f"unaffected requests diverged from the clean run: {diverged[:10]}"
    # the chaos run must actually have exercised the failure paths
    ch = report["chaos"]
    assert ch["step_retries"] >= 1 and ch["quarantined"] >= 1, ch
    report["unaffected_bit_identical"] = args.requests - len(affected)

    # ---- tracing overhead + trace export (ISSUE 10) ------------------
    # the SAME clean workload with per-request tracing ON: tokens must
    # be bit-identical (observation must not perturb), and the step-
    # loop time delta vs an untraced re-run IS the measured tracing
    # cost (tracing off is the default — nothing to measure there).
    # Methodology: every pass recompiles its programs (fresh engine ⇒
    # fresh jit closures), and XLA compile variance on a shared CPU box
    # (~±0.2 s) swamps the tracing signal in raw wall clock; single
    # 40 ms GC/dispatch spikes likewise dominate a window SUM. So the
    # arms are compared on the flight recorder's own per-step t_wall_ms
    # over the steady-state window (the bounded ring drops the early
    # compile-heavy steps), PAIRED by step number — both passes run the
    # identical schedule — and the estimator is the median paired delta
    # over the median untraced step: robust to load spikes in either
    # arm. Three interleaved reps, deltas POOLED across reps before the
    # median so slow load drift between passes cancels; per-rep medians
    # are printed alongside as the spread evidence.
    from paddle_tpu.serving import RequestTracer
    estimates = []
    all_deltas = []
    all_base = []
    tracer = None
    keep = {}

    def _step_ms(kp):
        return {r["step"]: r["t_wall_ms"] for r in kp["timeline"]}

    for rep in range(3):
        kp_u = {}
        warm, _ = run_workload(model, work, chaos=False, seed=args.seed,
                               report=report, label=f"warm_clean_{rep}",
                               keep=kp_u)
        assert warm == clean, "untraced re-run must be bit-identical"
        tracer = RequestTracer(max_completed=4 * max(1, args.requests))
        keep = {}
        traced, _ = run_workload(model, work, chaos=False,
                                 seed=args.seed, report=report,
                                 trace=tracer, label=f"traced_{rep}",
                                 keep=keep)
        div = [i for i in range(len(work))
               if traced.get(i) != clean.get(i)]
        assert not div, f"tracing changed greedy tokens: {div[:10]}"
        by_u, by_t = _step_ms(kp_u), _step_ms(keep)
        assert set(by_u) == set(by_t), "step windows diverged"
        deltas = sorted(by_t[s] - by_u[s] for s in by_u)
        base = sorted(by_u.values())
        med_delta = deltas[len(deltas) // 2]
        med_base = base[len(base) // 2]
        estimates.append(med_delta / max(med_base, 1e-9))
        all_deltas.extend(deltas)
        all_base.extend(base)
    all_deltas.sort()
    all_base.sort()
    med_base_ms = max(all_base[len(all_base) // 2], 1e-9)
    overhead = all_deltas[len(all_deltas) // 2] / med_base_ms
    report["trace_overhead"] = round(overhead, 4)
    report["traced_requests"] = tracer.num_completed
    # generous sanity bound only — wall-clock noise on a shared CPU box
    # must not flake the soak; the measured number is the evidence
    assert overhead < 0.5, \
        f"tracing overhead {overhead:.1%} is far beyond budget"

    # deterministic per-step cost bound: time EXACTLY what a traced
    # decode step adds (2 now_ns + the shared batched `span_many`, the
    # decode_step arg shape) against the median untraced step — the
    # precise ≤5% gate the wall-clock estimate above corroborates but,
    # on a shared box, cannot enforce without flaking
    mb = RequestTracer()
    rids = tuple(range(8))
    for rid in rids:
        mb.begin(rid, engine="microbench", prompt_len=16,
                 max_new_tokens=8)
    n_iter = 2000
    t1 = time.perf_counter()
    for _ in range(n_iter):
        t_tr = mb.now_ns()
        mb.span_many(rids, "decode_step", t_tr, mb.now_ns(),
                     engine="microbench", batch=8, bucket=[8, 8])
    per_step_ms = (time.perf_counter() - t1) * 1e3 / n_iter
    for rid in range(8):       # keep the microbench traces bounded
        mb.finish(rid, "stop")
    overhead_step = per_step_ms / med_base_ms
    report["trace_overhead_per_step"] = round(overhead_step, 4)
    assert overhead_step < 0.05, \
        f"per-step tracing cost {overhead_step:.2%} breaks the 5% budget"
    os.makedirs(os.path.dirname(args.trace_out) or ".", exist_ok=True)
    tracer.export(args.trace_out, flight_recorder=keep.get("timeline"))
    report["trace_out"] = args.trace_out

    if not args.no_spec:
        # ---- speculative-decoding passes (ISSUE 5) -------------------
        spec_clean, _ = run_workload(model, work, chaos=False,
                                     seed=args.seed, report=report,
                                     spec=True)
        # speculation must not change ANY greedy token vs plain decode
        # (same workload, same clock, no faults in either pass)
        spec_div = [i for i in range(len(work))
                    if spec_clean.get(i) != clean.get(i)]
        assert not spec_div, \
            f"spec decode changed greedy tokens: {spec_div[:10]}"
        sc = report["spec_clean"]
        assert sc["spec_accepted"] > 0 and sc["spec_steps"] > 0, sc
        # ... and fewer decode-side launches did the same work
        assert sc["spec_tokens_per_step"] > 1.0, sc

        spec_chaos, spec_aff = run_workload(model, work, chaos=True,
                                            seed=args.seed,
                                            report=report, spec=True)
        spec_div = [i for i in range(len(work))
                    if i not in spec_aff
                    and spec_chaos.get(i) != spec_clean.get(i)]
        assert not spec_div, ("unaffected requests diverged under spec "
                              f"chaos: {spec_div[:10]}")
        sx = report["spec_chaos"]
        assert sx["step_retries"] >= 1 and sx["quarantined"] >= 1, sx
        assert sx["spec_rollback"] >= 1, sx
        report["spec_unaffected_bit_identical"] = \
            args.requests - len(spec_aff)

    if not args.no_int8:
        # ---- int8-KV passes (ISSUE 6) --------------------------------
        # quantize-on-write is deterministic, so the int8 pair carries
        # the SAME bit-identity contract as the full-precision pair:
        # chaos may only change affected (quarantined/expired/aborted)
        # requests. Cross-dtype token equality is NOT asserted — int8
        # attention is allowed its documented rel-err budget.
        i8_clean, _ = run_workload(model, work, chaos=False,
                                   seed=args.seed, report=report,
                                   kv_dtype="int8")
        i8_chaos, i8_aff = run_workload(model, work, chaos=True,
                                        seed=args.seed, report=report,
                                        kv_dtype="int8")
        i8_div = [i for i in range(len(work))
                  if i not in i8_aff
                  and i8_chaos.get(i) != i8_clean.get(i)]
        assert not i8_div, ("unaffected requests diverged under int8 "
                            f"chaos: {i8_div[:10]}")
        ic = report["int8_chaos"]
        assert ic["step_retries"] >= 1 and ic["quarantined"] >= 1, ic
        report["int8_unaffected_bit_identical"] = \
            args.requests - len(i8_aff)

    if args.lora:
        # ---- multi-LoRA passes (ISSUE 15) ----------------------------
        lora_clean, lc_aff = run_lora_pass(model, work, chaos=False,
                                           seed=args.seed, report=report)
        assert not lc_aff and report["lora_clean"]["hot_loaded"], \
            report["lora_clean"]
        assert report["lora_clean"]["prefix_hits"] >= 1
        lora_chaos, lora_aff = run_lora_pass(model, work, chaos=True,
                                             seed=args.seed,
                                             report=report)
        lx = report["lora_chaos"]
        # the mid-stream load failure really shed the hot tail typed...
        assert not lx["hot_loaded"] and lx["adapter_load_failures"] >= 1
        assert lx["adapter_rejects"] >= 1 and len(lora_aff) >= 1, lx
        # ...the evict-race guard refused the pinned victim...
        assert lx["lora_evict_refusals"] >= 1, lx
        # ...and no co-batched row of any OTHER adapter moved a bit
        lora_div = [i for i in range(len(work))
                    if i not in lora_aff
                    and lora_chaos.get(i) != lora_clean.get(i)]
        assert not lora_div, ("unaffected requests diverged under lora "
                              f"chaos: {lora_div[:10]}")
        report["lora_unaffected_bit_identical"] = \
            args.requests - len(lora_aff)

    if args.spill:
        # ---- tiered-KV spill passes (ISSUE 17) -----------------------
        # a spill-pressure workload on a shrunken device pool, three
        # ways: host tier off (the HBM-only ceiling), on (clean), and
        # on with every host_spill.* read fault armed
        swork = make_spill_workload(args.requests, args.seed)
        off_kw = dict(num_pages=24)
        on_kw = dict(num_pages=24, host_spill_pages=32)
        s_off, _ = run_workload(model, swork, chaos=False,
                                seed=args.seed, report=report,
                                extra_kw=off_kw, label="spill_off")
        s_clean, _ = run_workload(model, swork, chaos=False,
                                  seed=args.seed, report=report,
                                  extra_kw=on_kw, label="spill_clean")
        # the tier is invisible in the tokens (EVERY request — no
        # faults in either pass) ...
        s_div = [i for i in range(len(swork))
                 if s_clean.get(i) != s_off.get(i)]
        assert not s_div, \
            f"spill tier changed greedy tokens: {s_div[:10]}"
        sc = report["spill_clean"]
        assert sc["kv_pages_demoted"] > 0 and \
            sc["kv_pages_promoted"] > 0 and \
            sc["host_prefix_hits"] >= 1, sc
        # ... while serving MORE cached tokens at the same device pool
        # (the perf_opt acceptance: host capacity raises the hit rate
        # above the HBM-only ceiling)
        assert sc["cached_tokens"] > \
            report["spill_off"]["cached_tokens"], \
            (sc["cached_tokens"], report["spill_off"]["cached_tokens"])
        s_chaos, s_aff = run_workload(model, swork, chaos=False,
                                      seed=args.seed, report=report,
                                      extra_kw=on_kw, spill_chaos=True,
                                      label="spill_chaos")
        # all three read faults degrade to recompute: NOTHING is
        # affected and EVERY token matches the clean spill pass
        assert not s_aff, s_aff
        s_div = [i for i in range(len(swork))
                 if s_chaos.get(i) != s_clean.get(i)]
        assert not s_div, \
            f"spill faults changed greedy tokens: {s_div[:10]}"
        sx = report["spill_chaos"]
        assert all(c >= 1 for c in sx["spill_faults"]), sx
        report["spill_bit_identical"] = args.requests

    report["wall_s"] = round(time.perf_counter() - t0, 2)
    print(json.dumps(report))
    # ---- final report through the observability paths (ISSUE 10) -----
    # per-phase latency + flight-recorder digest from the traced pass,
    # and the engine's Prometheus exposition — the same renderers
    # production scrapes/postmortems use, exercised on every soak
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_report
    print(trace_report.report(trace_report.load(args.trace_out)))
    print("== metrics exposition (traced clean pass) ==")
    print(keep.get("prometheus", ""), end="")
    print(f"trace_overhead={report['trace_overhead']:+.2%} "
          f"(median paired per-step delta over the steady-state "
          f"window; reps {['%+.2f%%' % (100 * e) for e in estimates]}) "
          f"per_step_bound={report['trace_overhead_per_step']:.2%}")
    print("SOAK_SERVING_OK")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except AssertionError as e:
        print(f"SOAK_SERVING_FAILED: {e}", file=sys.stderr)
        sys.exit(1)
