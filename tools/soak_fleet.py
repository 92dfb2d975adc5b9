"""Multi-replica chaos soak for the fleet front-end (ISSUE 7).

Runs the SAME seeded shared-prefix-heavy workload four times on CPU:

* `single`  — one replica, prefix-affinity router, no faults: the
  PR-2-style single-replica radix baseline the routing criterion is
  measured against;
* `clean`   — three replicas, prefix-affinity router, no faults: the
  reference token streams;
* `chaos`   — three replicas, prefix-affinity router, with a seeded
  KILL of replica-0 mid-stream (`fleet.replica_crash`), a permanent
  STALL of replica-1 (`fleet.stream_stall` -> stall detector), routing
  races, injected allocator OOM, and transient step errors;
* `random`  — three replicas, seeded RandomRouter, no faults: the
  routing-criterion strawman.

Acceptance assertions (ISSUE 7):

* zero-loss failover: EVERY accepted request completes in the chaos
  pass, with its token stream BIT-IDENTICAL to the clean pass (zero
  lost requests, zero duplicated or reordered tokens — migration
  preserves tokens-so-far and greedy continuation is deterministic
  under the pinned bucket grid);
* full page/refcount reclamation on every replica's pool — including
  the killed and the stalled one (vacate at evacuation);
* prefix-affinity routing measurably works: fleet-level radix hits in
  `clean` >= the `single` baseline, and strictly > `random`;
* every fault point armed in the chaos pass actually fired.

Deterministic end to end: workload, fault schedule, stepping order and
the shared engine/fleet clock all derive from --seed; wall-clock never
enters any engine. Bounded runtime: hard step ceiling.

Usage:  JAX_PLATFORMS=cpu \
            python tools/soak_fleet.py [--requests 120] [--seed 0]
(or `make soak-fleet`). Exits 0 on success, 1 with a report on
violation — a test harness like soak_serving.py, allowed to fail loud.

`--procs` (ISSUE 14, `make soak-fleet-proc`) runs the CROSS-PROCESS
chaos ladder instead: real worker processes behind the TCPStore
mailbox —

* in-process reference pass (also warms the shared compile cache) and
  the cold-vs-warm compile-cache bench (warm cold-start-to-first-token
  must be >= 5x faster than cold compile; a corrupted entry degrades
  to a counted recompile mid-bench);
* clean 3-worker cross-process pass — streams BIT-IDENTICAL to the
  in-process reference;
* chaos pass: seeded kill -9 of w0 mid-stream (worker.kill9, proven
  by the -SIGKILL returncode), a PERMANENTLY wedged w1
  (transport.stall times=-1 worker-side: no heartbeats out, no
  commands in -> the hard-stall ladder kills + adopts), w2 a
  slow-heartbeat worker under load (visible SUSPECT gaps, survives)
  that also absorbs a finite transport.stall (reported via heartbeat
  fired counts), with transport.drop / transport.duplicate armed
  host-side on the event streams. All requests complete bit-identical,
  zero lost, zero funnel conflicts, full reclamation on survivors;
* rolling restart: drain -> respawn -> adopt with exactly-once
  delivery, the successor warm-starting from the disk cache (zero
  recompiles), heartbeat gaps visible in the Prometheus text.

`--disagg` (ISSUE 18, `make soak-disagg`) runs the DISAGGREGATED
prefill/decode ladder: 2 prefill-role + 2 decode-role workers with
mid-flight KV handoff —

* clean pass: a 16-request prefill-heavy mixed load (shared-prefix
  hits included) streams BIT-IDENTICAL to the in-process co-located
  reference, with real KV pages shipped (handoffs_completed >= 1);
* decode-TPOT comparison: the same load on an all-"both" fleet of the
  SAME size; steady-state decode inter-token-gap p99 (per-token host
  stamps, first post-handoff gap excluded) must be LOWER on the
  disaggregated fleet — prefill chunks no longer interleave with
  decode steps;
* 3-seed chaos: kill -9 of a prefill worker MID-HANDOFF
  (fleet.handoff_partial: dies with only part of the kv_page stream
  shipped), kill -9 of a decode worker mid-decode (its adopted work
  re-lands on the surviving decode worker), host-armed
  fleet.handoff_stall (relay frames eaten -> phase timeout -> capped
  backoff -> re-pull), and a decode_reject refusal — every pass
  bit-identical, zero lost, zero funnel conflicts, full reclamation
  on survivors;
* role-starved fallback: a prefill-only fleet degrades every handoff
  to co-located execution (handoffs_colocated == streams) instead of
  shedding;
* int8-KV variant: the handoff ships quantized pages + scales,
  bit-identical to the int8 in-process reference.
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
                                Fleet, PrefixAffinityRouter,
                                RandomRouter, RetryPolicy,
                                ServingEngine, TransientDeviceError)
from paddle_tpu.utils import faults                          # noqa: E402

# single-bucket grid: every pass hits identical program shapes, so the
# bit-identity comparison across clean/chaos is exact (SERVING.md
# determinism contract) — same discipline as soak_serving.py.
ENGINE_KW = dict(num_pages=40, page_size=8, token_budget=48,
                 batch_buckets=[8], prefill_buckets=[32], pages_buckets=[8],
                 temperature=0.0, max_queue_len=32)
STALL_TIMEOUT_S = 0.2   # ~200 clock ticks; detection within tens of steps
MAX_STEPS_FACTOR = 400  # hard ceiling: steps <= factor * num_requests
MAX_LIVE = 8            # client-side concurrency cap (see run_pass)
WARMUP = 2              # bare-prefix warmup requests (make_workload)


class FakeClock:
    """Shared engine+fleet clock: a fixed tick per observation, so
    heartbeat ages and deadlines are functions of call counts, never
    host wall-clock."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def make_workload(n, seed):
    """Shared-prefix-heavy mix: two 2-page shared prefixes (the
    affinity router should pin each to one replica) + random fill.
    The first WARMUP requests carry each bare prefix — run_pass drains
    them before the main traffic so the hit-rate comparison measures
    ROUTING, not the admission race of a cold cache (two cold replicas
    can each admit a shared-prefix request before either donates, a
    concurrency artifact every router suffers equally)."""
    rng = np.random.RandomState(seed)
    prefix_a = rng.randint(0, 128, (16,)).tolist()
    prefix_b = rng.randint(0, 128, (16,)).tolist()
    work = [(list(prefix_a), 4), (list(prefix_b), 4)]
    for _ in range(n):
        u = rng.random()
        if u < 0.30:
            p = prefix_a + rng.randint(0, 128, (rng.randint(2, 8),)).tolist()
        elif u < 0.55:
            p = prefix_b + rng.randint(0, 128, (rng.randint(2, 8),)).tolist()
        else:
            p = rng.randint(0, 128, (rng.randint(4, 24),)).tolist()
        work.append((p, int(rng.randint(3, 10))))
    return work


def run_pass(model, work, *, n_replicas, router, chaos, seed, report,
             label, trace=None, keep=None):
    """One full soak pass; returns {workload idx: token stream}.
    `trace` (one RequestTracer SHARED by every replica — the migration
    contract) turns request tracing on; `keep` (a dict) receives the
    per-replica flight-recorder timelines and the fleet's Prometheus
    exposition before shutdown (ISSUE 10)."""
    clock = FakeClock()
    engines = [ServingEngine(
        model, clock=clock,
        retry_policy=RetryPolicy(max_retries=12, base_s=0.0,
                                 sleep=lambda s: None),
        trace=trace, **ENGINE_KW) for _ in range(n_replicas)]
    fleet = Fleet(engines, router=router, clock=clock,
                  stall_timeout_s=STALL_TIMEOUT_S)
    armed = set()

    def arm(name, **kwargs):
        faults.inject(name, **kwargs)
        armed.add(name)

    if chaos:
        # THE kill: replica-0 dies at its first step past the warmup
        # window — mid-stream, with requests in every state. times=-1 +
        # a name: other replicas consume firings and ignore them, the
        # victim cannot miss.
        arm("fleet.replica_crash", payload="replica-0", after=20,
            times=-1)
        # permanent stall of replica-1 a little later (hits accrue ~2
        # per fleet step once replica-0 is dead): the heartbeat stops,
        # the stall detector drains it around the wedge
        arm("fleet.stream_stall", payload="replica-1", after=60,
            times=-1)
        # routing races: the chosen replica "goes unhealthy between
        # scoring and submission"
        arm("fleet.route_race", payload=True, after=5, times=3)
        # engine-level noise underneath the fleet faults: transient
        # launch errors (retried in place; totals < max_retries by
        # construction) and allocator OOM (reclamation ladder)
        arm("serving.engine.prefill_chunk",
            exc=TransientDeviceError("soak: UNAVAILABLE"),
            after=3, times=1)
        arm("serving.engine.prefill_chunk",
            exc=TransientDeviceError("soak: UNAVAILABLE"),
            prob=0.02, times=9, seed=seed + 2)
        arm("serving.engine.decode_step",
            exc=TransientDeviceError("soak: connection loss"),
            after=4, times=1)
        arm("serving.engine.decode_step",
            exc=TransientDeviceError("soak: connection loss"),
            prob=0.02, times=9, seed=seed + 3)
        arm("serving.kv.alloc_page", payload=True, after=5, times=2)
        arm("serving.kv.alloc_page", payload=True,
            prob=0.03, times=12, seed=seed + 4)

    idx_of = {}
    handles = []
    pending = list(enumerate(work))
    sheds = 0
    steps = 0
    max_steps = MAX_STEPS_FACTOR * max(1, len(work))
    try:
        # warmup wave: the bare-prefix requests drain first (and donate
        # each prefix into exactly one replica's radix tree)
        for _ in range(WARMUP):
            i, (p, m) = pending.pop(0)
            h = fleet.submit(p, max_new_tokens=m)
            idx_of[h.request_id] = i
            handles.append(h)
        while fleet.has_work():
            fleet.step_all()
            steps += 1
        while pending or fleet.has_work():
            # fixed client-side concurrency (same offered load in every
            # pass, whatever the replica count): the routing criterion
            # compares hit rates, so the single-replica baseline and
            # the fleet must see the same admission dynamics — without
            # the cap the 3-replica fleet admits 3x faster and more
            # shared-prefix requests arrive before the first donation
            # (a cold-start artifact, not a routing property)
            admitted = 0
            while pending and admitted < 4 and \
                    sum(1 for h in handles if not h.finished) < MAX_LIVE:
                i, (p, m) = pending[0]
                try:
                    h = fleet.submit(p, max_new_tokens=m)
                except EngineOverloaded:
                    sheds += 1
                    break
                idx_of[h.request_id] = i
                handles.append(h)
                pending.pop(0)
                admitted += 1
            fleet.step_all()
            steps += 1
            if steps > max_steps:
                raise AssertionError(
                    f"[{label}] failed to drain after {steps} steps")

        out = {}
        reasons = {}
        for rid, i in idx_of.items():
            h = fleet.handle(rid)
            assert h.finished, f"[{label}] request {i} never finished"
            reasons[h.finish_reason] = reasons.get(h.finish_reason, 0) + 1
            out[i] = list(h.tokens)

        # ---- reclamation on EVERY pool (killed/stalled included) ----
        for r in fleet.replicas:
            if r.engine.radix is not None:
                r.engine.radix.check_invariants()
            r.engine.reset_prefix_cache()
            assert r.engine.allocator.num_used == 0, \
                f"[{label}] {r.name} leaked KV pages"
            r.engine.allocator.check_invariants()

        snap = fleet.merged_metrics().snapshot()
        report[label] = {
            "steps": steps, "sheds": sheds,
            "finish_reasons": reasons,
            "replica_states": {r.name: r.state.value
                               for r in fleet.replicas},
            "prefix_hits": snap["prefix_hits"],
            "cached_tokens_served": snap["cached_tokens_served"],
            "preemptions": snap["requests_preempted"],
            "step_retries": snap["step_retries"],
            "migrated": fleet.counters["requests_migrated"],
            "catchup_tokens": fleet.counters["catchup_tokens"],
            "lost": fleet.counters["requests_lost"],
            "deaths": fleet.counters["replica_deaths"],
            "stalls": fleet.counters["replica_stalls"],
            "route_races": fleet.counters["route_races"],
        }
        if chaos:
            fired = faults.fired_counts()
            report[f"fired_{label}"] = fired
            for pt in sorted(armed):
                assert fired.get(pt, 0) >= 1, \
                    f"[{label}] armed fault point {pt} never fired"
        if keep is not None:
            keep["timelines"] = [
                dict(rec, replica=r.name)
                for r in fleet.replicas for rec in r.engine.timeline()]
            keep["prometheus"] = fleet.prometheus_text()
            keep["migrated"] = fleet.counters["requests_migrated"]
        return out
    finally:
        faults.clear()
        faults.reset_counts()
        fleet.shutdown()


# ===================== cross-process ladder (ISSUE 14) =====================

CFG_DICT = dict(vocab_size=128, hidden_size=128, intermediate_size=256,
                num_hidden_layers=2, num_attention_heads=2,
                num_key_value_heads=1, max_position_embeddings=128)
PROC_SUSPECT_S = 0.5
PROC_DEAD_S = 6.0


def _drive_engine(eng, work):
    """Drain `work` through one in-process engine with client-side
    pacing (the queue is bounded); returns {workload idx: stream}."""
    from paddle_tpu.serving import EngineOverloaded as _EO
    out, rid_of = {}, {}
    pending = list(enumerate(work))
    while pending or eng.has_work():
        while pending:
            i, (p, m) = pending[0]
            try:
                rid_of[eng.add_request(p, max_new_tokens=m)] = i
            except _EO:
                break
            pending.pop(0)
        for rid, tok in eng.step():
            out.setdefault(rid_of[rid], []).append(int(tok))
    return out


def _first_token_s(model, cache_dir):
    """Cold-start-to-first-token: fresh engine on `cache_dir`, one
    request, stepped to its first emission. Returns (seconds, the
    engine's CompileCache counters); the engine itself is drained and
    shut down here."""
    from paddle_tpu.serving import ServingEngine
    t0 = time.perf_counter()
    eng = ServingEngine(model, compile_cache=cache_dir, **ENGINE_KW)
    eng.add_request(list(range(1, 9)), max_new_tokens=2)
    emitted = []
    while not emitted:
        emitted = eng.step()
    dt = time.perf_counter() - t0
    eng.run()    # drain the tail so the engine ends clean
    cc = dict(eng.compile_cache.counters)
    eng.shutdown()
    eng.metrics.unregister()
    return dt, cc


def run_proc_pass(work, ref, ccdir, *, chaos, seed, report, label):
    """One cross-process pass over `work`; asserts bit-identity against
    the in-process reference streams and (chaos) the full fault
    ladder."""
    from paddle_tpu.serving import EngineOverloaded, ProcessFleet
    from paddle_tpu.serving.fleet.errors import NoHealthyReplica
    from paddle_tpu.serving.fleet.procfleet import WorkerState

    base = {"model": {"kind": "llama", "config": CFG_DICT, "seed": 0},
            "engine": ENGINE_KW, "heartbeat_interval_s": 0.05,
            "compile_cache_dir": ccdir}
    specs = {f"w{i}": dict(base) for i in range(3)}
    if chaos:
        # w0: seeded kill -9 mid-stream (proven by returncode -9)
        specs["w0"]["faults"] = [
            {"point": "worker.kill9", "after": 25, "times": 1}]
        # w1: permanently wedged transport — no heartbeats out, no
        # commands in; the hard-stall ladder must kill + adopt
        specs["w1"]["faults"] = [
            {"point": "transport.stall", "after": 40, "times": -1}]
        # w2: slow heartbeats under load (SUSPECT gaps, survives) + a
        # finite stall it recovers from and REPORTS (fired counts ride
        # its later heartbeats — the in-soak firing proof)
        specs["w2"]["heartbeat_interval_s"] = 1.0
        specs["w2"]["faults"] = [
            {"point": "transport.stall", "after": 60, "times": 3}]
    pf = ProcessFleet(specs, suspect_after_s=PROC_SUSPECT_S,
                      dead_after_s=PROC_DEAD_S,
                      max_inflight_per_worker=8,
                      stderr_dir=os.path.join("profiler_log",
                                              "soak_proc_workers"))
    armed_host = set()
    try:
        t0 = time.monotonic()
        while not all(w.ready for w in pf.workers.values()):
            pf.pump()
            if time.monotonic() - t0 > 120:
                raise AssertionError(f"[{label}] workers never ready")
            time.sleep(0.01)
        if chaos:
            # host-side wire damage on the worker->host streams: drops
            # heal through heartbeat snapshots, duplicates must die in
            # the exactly-once funnel
            faults.inject("transport.drop", payload=True, prob=0.02,
                          times=8, seed=seed + 11)
            faults.inject("transport.duplicate", payload=True,
                          prob=0.03, times=10, seed=seed + 12)
            armed_host |= {"transport.drop", "transport.duplicate"}

        idx_of = {}
        pending = list(enumerate(work))
        max_gap = {n: 0.0 for n in pf.workers}
        t0 = time.monotonic()
        while pending or pf.has_work():
            submitted = 0
            while pending and submitted < 4:
                i, (p, m) = pending[0]
                try:
                    h = pf.submit(p, max_new_tokens=m)
                except (EngineOverloaded, NoHealthyReplica):
                    break   # backpressure / mid-failover: retry later
                idx_of[h.request_id] = i
                pending.pop(0)
                submitted += 1
            pf.pump()
            for n in pf.workers:
                g = pf.heartbeat_gap_s(n)
                if g is not None and \
                        pf.workers[n].state not in (WorkerState.DEAD,
                                                    WorkerState.STOPPED):
                    max_gap[n] = max(max_gap[n], g)
            if time.monotonic() - t0 > 600:
                raise AssertionError(
                    f"[{label}] failed to drain after 600s; "
                    f"{pf.summary()}")
            time.sleep(2e-3)

        streams = {}
        for rid, i in idx_of.items():
            h = pf.handles[rid]
            assert h.finished, f"[{label}] request {i} never finished"
            assert h.finish_reason in ("stop", "length"), \
                f"[{label}] request {i} ended {h.finish_reason!r}"
            streams[i] = list(h.tokens)
        diverged = [i for i in streams if streams[i] != ref.get(i)]
        assert not diverged, \
            f"[{label}] cross-process streams diverged from the " \
            f"in-process reference: {diverged[:10]}"
        assert pf.counters["requests_lost"] == 0, pf.summary()
        assert pf.counters["funnel_conflicts"] == 0, pf.summary()

        # let the suspicion ladder RESOLVE every suspect (a wedged
        # worker must reach DEAD via the hard-stall timeout before the
        # reclamation sweep asks it anything)
        t0 = time.monotonic()
        while any(w.state is WorkerState.SUSPECT
                  for w in pf.workers.values()):
            pf.pump()
            if time.monotonic() - t0 > PROC_DEAD_S * 3:
                break
            time.sleep(0.01)

        # ---- full reclamation on every SURVIVING worker --------------
        for name, w in pf.workers.items():
            if w.state is not WorkerState.HEALTHY:
                continue
            st = pf.request_stats(name, reset_prefix_cache=True)
            assert st is not None, f"[{label}] no stats from {name}"
            assert st.get("radix_ok", True) and st["allocator_ok"], st
            assert st["kv_used_pages"] == 0, \
                f"[{label}] {name} leaked KV pages: {st}"

        report[label] = {
            "streams": len(streams),
            "max_heartbeat_gap_s": {n: round(g, 3)
                                    for n, g in max_gap.items()},
            "worker_states": {n: w.state.value
                              for n, w in pf.workers.items()},
            **{k: v for k, v in pf.counters.items() if v},
        }
        if chaos:
            host_fired = faults.fired_counts()
            worker_fired = pf.fired_counts()
            report[f"fired_{label}"] = {"host": host_fired,
                                        "worker": worker_fired}
            # every armed fault PROVEN fired:
            for pt in sorted(armed_host):
                assert host_fired.get(pt, 0) >= 1, \
                    f"[{label}] host-armed {pt} never fired"
            # kill9: the process really died by SIGKILL, mid-workload
            assert pf.workers["w0"].poll() == -9, \
                f"[{label}] w0 rc {pf.workers['w0'].poll()}"
            assert pf.counters["worker_kill9_observed"] >= 1
            # the wedged worker was hard-stalled out and its work moved
            assert pf.counters["worker_hard_stalls"] >= 1, pf.summary()
            assert pf.workers["w1"].state is WorkerState.DEAD
            assert pf.counters["requests_migrated"] >= 1, pf.summary()
            # w2 recovered from its finite stall and REPORTED it
            assert worker_fired.get("transport.stall", 0) >= 1, \
                f"[{label}] worker-side transport.stall unreported: " \
                f"{worker_fired}"
            # slow-heartbeat worker: visible gaps, still alive
            assert max_gap["w2"] > PROC_SUSPECT_S, max_gap
            assert pf.workers["w2"].state not in (WorkerState.DEAD,
                                                  WorkerState.STOPPED)
            # duplicates died in the funnel (asserted zero-conflict
            # above); count what the funnel absorbed
            report[label]["funnel_duplicates"] = \
                pf.counters["funnel_duplicates"]
        # heartbeat-gap visibility in the Prometheus text
        text = pf.prometheus_text()
        assert "worker_heartbeat_gap_seconds" in text
        report[f"prometheus_{label}_lines"] = text.count("\n")
        return streams, pf
    finally:
        faults.clear()
        faults.reset_counts()
        pf.shutdown()


def run_proc_ladder(args):
    """The --procs entry: reference + bench + clean + chaos + rolling
    restart. Returns the report dict (raises AssertionError on any
    violation)."""
    import shutil
    import tempfile

    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.fleet.procfleet import WorkerState

    report = {"requests": args.requests, "seed": args.seed,
              "mode": "procs"}
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**CFG_DICT))
    work = make_workload(args.requests, args.seed)
    ccdir = tempfile.mkdtemp(prefix="soak_ptcc_")
    try:
        # ---- in-process reference (warms the shared cache) -----------
        ref_eng = ServingEngine(model, compile_cache=ccdir, **ENGINE_KW)
        try:
            ref = _drive_engine(ref_eng, work)
            saved = ref_eng.save_compile_cache()
        finally:
            ref_eng.shutdown()
        assert saved >= 2, f"compile cache saved only {saved} entries"
        report["cache_entries_saved"] = saved

        # ---- cold-vs-warm compile-cache bench ------------------------
        cold_dir = tempfile.mkdtemp(prefix="soak_ptcc_cold_")
        try:
            t_cold, _ = _first_token_s(model, cold_dir)
        finally:
            shutil.rmtree(cold_dir, ignore_errors=True)
        # a corrupted entry must degrade to a counted recompile,
        # mid-bench, without crashing the engine
        faults.inject("cache.corrupt_entry", payload=True, times=1)
        t_warm, warm_cc = _first_token_s(model, ccdir)
        corrupt_fired = faults.fired_counts().get("cache.corrupt_entry",
                                                  0)
        faults.clear()
        faults.reset_counts()
        assert corrupt_fired >= 1, "cache.corrupt_entry never fired"
        assert warm_cc["rejects"] >= 1
        # second warm engine, undamaged: the actual warm number
        t_warm2, _ = _first_token_s(model, ccdir)
        t_warm = min(t_warm, t_warm2)
        speedup = t_cold / t_warm
        report["compile_cache_bench"] = {
            "cold_first_token_s": round(t_cold, 3),
            "warm_first_token_s": round(t_warm, 3),
            "speedup": round(speedup, 2),
            "corrupt_entry_rejects": warm_cc["rejects"]}
        assert speedup >= 5.0, \
            f"warm cold-start-to-first-token only {speedup:.1f}x " \
            f"faster than cold compile (need >= 5x)"

        # ---- clean + chaos cross-process passes ----------------------
        run_proc_pass(work, ref, ccdir, chaos=False, seed=args.seed,
                      report=report, label="proc_clean")
        run_proc_pass(work, ref, ccdir, chaos=True, seed=args.seed,
                      report=report, label="proc_chaos")

        # ---- rolling restart: drain -> respawn -> adopt --------------
        from paddle_tpu.serving import ProcessFleet
        base = {"model": {"kind": "llama", "config": CFG_DICT,
                          "seed": 0},
                "engine": ENGINE_KW, "heartbeat_interval_s": 0.05,
                "compile_cache_dir": ccdir}
        pf = ProcessFleet({"w0": dict(base), "w1": dict(base)},
                          suspect_after_s=PROC_SUSPECT_S,
                          dead_after_s=30.0,
                          stderr_dir=os.path.join(
                              "profiler_log", "soak_proc_workers"))
        try:
            t0 = time.monotonic()
            while not all(w.ready for w in pf.workers.values()):
                pf.pump()
                assert time.monotonic() - t0 < 120
                time.sleep(0.01)
            long_work = [(p, 24) for p, _ in work[:8]]
            handles = []
            for p, m in long_work:
                handles.append(pf.submit(p, max_new_tokens=m))
            # first tokens, then restart w0 under load
            t0 = time.monotonic()
            while not all(h.tokens for h in handles):
                pf.pump()
                assert time.monotonic() - t0 < 120
                time.sleep(5e-3)
            pf.rolling_restart("w0")
            res = pf.run(timeout_s=300)
            # per-request streams are batch-invariant (the SERVING.md
            # determinism contract), so ONE warm reference engine
            # serves all 8 expected streams
            solo = ServingEngine(model, compile_cache=ccdir,
                                 **ENGINE_KW)
            try:
                rids = [solo.add_request(p, max_new_tokens=m)
                        for p, m in long_work]
                solo_out = solo.run()
            finally:
                solo.shutdown()
            for i, h in enumerate(handles):
                assert res[h.request_id] == solo_out[rids[i]], \
                    f"rolling restart diverged request {i}"
            assert pf.counters["requests_lost"] == 0
            assert pf.counters["funnel_conflicts"] == 0
            assert pf.counters["worker_drains"] == 1
            assert pf.counters["worker_restarts"] == 1
            # successor warm-starts from disk: route it fresh traffic
            # (the migrated work may have landed on the other worker),
            # then its heartbeat counters must show disk hits and ZERO
            # XLA compiles — the no-compile-storm restart criterion
            t0 = time.monotonic()
            while not pf.workers["w0"].ready:
                pf.pump()
                assert time.monotonic() - t0 < 120, \
                    "respawned successor never became ready"
                time.sleep(0.01)
            for p, _ in work[8:12]:
                pf.submit(p, max_new_tokens=6)
            pf.run(timeout_s=120)
            t0 = time.monotonic()
            while (pf.workers["w0"].last_beat is None or
                   pf.workers["w0"].last_beat["counters"]
                   ["engine_steps"] == 0):
                pf.pump()
                assert time.monotonic() - t0 < 60, \
                    "successor never stepped"
                time.sleep(5e-3)
            wc = pf.workers["w0"].last_beat["counters"]
            assert wc["recompiles"] == 0, wc
            assert wc["compile_cache_hits"] >= 1, wc
            assert pf.counters["requests_lost"] == 0
            text = pf.prometheus_text()
            assert 'worker_heartbeat_gap_seconds{worker="w0"}' in text
            assert 'paddle_serving_worker_generation{worker="w0"} 1' \
                in text
            report["rolling_restart"] = {
                "streams": len(handles),
                "migrated": pf.counters["requests_migrated"],
                "successor_cache_hits": wc["compile_cache_hits"],
            }
        finally:
            pf.shutdown()
        return report
    finally:
        shutil.rmtree(ccdir, ignore_errors=True)


# ============== disaggregated prefill/decode ladder (ISSUE 18) =============

def make_disagg_workload(n, seed):
    """Prefill-heavy mixed load: long prompts (2-4 pages, so every
    handoff has real KV to ship) with the two shared prefixes still in
    the mix — the bit-identity pass exercises prefix-cache hits ACROSS
    the handoff, not just cold pulls."""
    rng = np.random.RandomState(seed + 1000)
    prefix_a = rng.randint(0, 128, (16,)).tolist()
    prefix_b = rng.randint(0, 128, (16,)).tolist()
    work = [(list(prefix_a), 4), (list(prefix_b), 4)]
    for _ in range(n - 2):
        u = rng.random()
        if u < 0.25:
            p = prefix_a + rng.randint(0, 128,
                                       (rng.randint(4, 12),)).tolist()
        elif u < 0.50:
            p = prefix_b + rng.randint(0, 128,
                                       (rng.randint(4, 12),)).tolist()
        else:
            p = rng.randint(0, 128, (rng.randint(16, 28),)).tolist()
        work.append((p, int(rng.randint(6, 12))))
    return work


def _decode_tpot_gaps(handles):
    """Steady-state decode inter-token gaps (seconds) from the per-
    token host stamps. The FIRST gap is excluded on purpose: in the
    disaggregated fleet it contains the handoff itself (pull + adopt),
    in the co-located fleet the post-prefill scheduling seam — TPOT is
    the steady decode cadence, not the transition. Catch-up bursts
    (many tokens on one stamp) only happen in chaos passes, so callers
    measure CLEAN passes only."""
    gaps = []
    for h in handles:
        ts = h.token_ts
        gaps.extend(b - a for a, b in zip(ts[1:], ts[2:]))
    return gaps


def run_disagg_pass(work, ref, ccdir, *, label, report, roles,
                    engine_kw=None, worker_faults=None, host_faults=None,
                    expect=None):
    """One cross-process pass with role-tagged workers; asserts
    bit-identity against `ref`, zero loss, zero funnel conflicts and
    full reclamation on every surviving worker. `roles` maps worker
    name -> role; `worker_faults` maps worker name -> spec fault list;
    `host_faults` arms supervisor-side points once workers are ready;
    `expect(pf)` runs scenario-specific assertions before shutdown.
    Returns the decode-TPOT gap samples."""
    from paddle_tpu.serving import EngineOverloaded, ProcessFleet
    from paddle_tpu.serving.fleet.errors import NoHealthyReplica
    from paddle_tpu.serving.fleet.procfleet import WorkerState

    kw = dict(engine_kw or ENGINE_KW)
    specs = {}
    for name, role in roles.items():
        specs[name] = {"model": {"kind": "llama", "config": CFG_DICT,
                                 "seed": 0},
                       "engine": kw, "heartbeat_interval_s": 0.05,
                       "compile_cache_dir": ccdir, "role": role}
        if worker_faults and name in worker_faults:
            specs[name]["faults"] = worker_faults[name]
    pf = ProcessFleet(specs, suspect_after_s=PROC_SUSPECT_S,
                      dead_after_s=PROC_DEAD_S,
                      handoff_timeout_s=1.0, handoff_backoff_s=0.1,
                      max_inflight_per_worker=8,
                      stderr_dir=os.path.join("profiler_log",
                                              "soak_disagg_workers"))
    try:
        t0 = time.monotonic()
        while not all(w.ready for w in pf.workers.values()):
            pf.pump()
            if time.monotonic() - t0 > 120:
                raise AssertionError(f"[{label}] workers never ready")
            time.sleep(0.01)
        for name, kws in (host_faults or {}).items():
            faults.inject(name, **kws)

        idx_of = {}
        pending = list(enumerate(work))
        t0 = time.monotonic()
        while pending or pf.has_work():
            submitted = 0
            while pending and submitted < 4:
                i, (p, m) = pending[0]
                try:
                    h = pf.submit(p, max_new_tokens=m)
                except (EngineOverloaded, NoHealthyReplica):
                    break
                idx_of[h.request_id] = i
                pending.pop(0)
                submitted += 1
            pf.pump()
            if time.monotonic() - t0 > 600:
                raise AssertionError(
                    f"[{label}] failed to drain after 600s; "
                    f"{pf.summary()}")
            time.sleep(2e-3)

        handles = [pf.handles[rid] for rid in idx_of]
        streams = {}
        for rid, i in idx_of.items():
            h = pf.handles[rid]
            assert h.finished, f"[{label}] request {i} never finished"
            streams[i] = list(h.tokens)
        diverged = [i for i in streams if streams[i] != ref.get(i)]
        assert not diverged, \
            f"[{label}] disaggregated streams diverged from the " \
            f"co-located reference: {diverged[:10]}"
        assert pf.counters["requests_lost"] == 0, pf.summary()
        assert pf.counters["funnel_conflicts"] == 0, pf.summary()

        # every handoff entry resolved — nothing mid-flight at drain
        assert not pf._handoffs, pf.summary()
        # let the suspicion ladder resolve before the reclamation sweep
        t0 = time.monotonic()
        while any(w.state is WorkerState.SUSPECT
                  for w in pf.workers.values()):
            pf.pump()
            if time.monotonic() - t0 > PROC_DEAD_S * 3:
                break
            time.sleep(0.01)
        for name, w in pf.workers.items():
            if w.state is not WorkerState.HEALTHY:
                continue
            st = pf.request_stats(name, reset_prefix_cache=True)
            assert st is not None, f"[{label}] no stats from {name}"
            assert st.get("radix_ok", True) and st["allocator_ok"], st
            assert st["kv_used_pages"] == 0, \
                f"[{label}] {name} leaked KV pages: {st}"

        if expect is not None:
            expect(pf)
        report[label] = {
            "streams": len(streams),
            "worker_states": {n: w.state.value
                              for n, w in pf.workers.items()},
            **{k: v for k, v in pf.counters.items() if v},
        }
        return _decode_tpot_gaps(handles)
    finally:
        faults.clear()
        faults.reset_counts()
        pf.shutdown()


def run_disagg_ladder(args):
    """The --disagg entry: co-located reference + TPOT strawman, clean
    disaggregated pass, 3-seed chaos, role-starved fallback, int8-KV
    variant. Returns the report dict (AssertionError on violation)."""
    import shutil
    import tempfile

    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.fleet.procfleet import WorkerState

    report = {"requests": args.requests, "seed": args.seed,
              "mode": "disagg"}
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**CFG_DICT))
    n = max(16, args.requests // 4)   # per-pass size; chaos runs 3 seeds
    ccdir = tempfile.mkdtemp(prefix="soak_dgcc_")
    try:
        def reference(work, **ekw):
            eng = ServingEngine(model, compile_cache=ccdir,
                                **dict(ENGINE_KW, **ekw))
            try:
                out = _drive_engine(eng, work)
                eng.save_compile_cache()
            finally:
                eng.shutdown()
            return out

        work = make_disagg_workload(n, args.seed)
        ref = reference(work)

        # ---- co-located strawman (same worker count, all "both"):
        # the chunked-prefill interference baseline the decode-TPOT
        # criterion is measured against
        coloc_roles = {f"w{i}": "both" for i in range(4)}
        coloc_gaps = run_disagg_pass(
            work, ref, ccdir, label="coloc", report=report,
            roles=coloc_roles)

        # ---- clean disaggregated pass: 2 prefill + 2 decode ----------
        roles = {"p0": "prefill", "p1": "prefill",
                 "d0": "decode", "d1": "decode"}

        def expect_clean(pf):
            assert pf.counters["handoffs_started"] >= len(work) - 2, \
                pf.summary()
            assert pf.counters["handoffs_completed"] >= 1, pf.summary()
            assert pf.counters["kv_pages_shipped"] >= 2, pf.summary()
            assert pf.counters["handoffs_colocated"] == 0, pf.summary()
            text = pf.prometheus_text()
            assert 'role="prefill"' in text and 'role="decode"' in text
            assert "fleet_kv_pages_shipped" in text

        disagg_gaps = run_disagg_pass(
            work, ref, ccdir, label="disagg_clean", report=report,
            roles=roles, expect=expect_clean)

        # ---- decode-TPOT criterion -----------------------------------
        p99 = lambda g: float(np.percentile(np.asarray(g), 99))  # noqa: E731
        tpot = {"coloc_p99_ms": round(p99(coloc_gaps) * 1e3, 3),
                "disagg_p99_ms": round(p99(disagg_gaps) * 1e3, 3),
                "coloc_samples": len(coloc_gaps),
                "disagg_samples": len(disagg_gaps)}
        tpot["ratio"] = round(tpot["coloc_p99_ms"]
                              / max(tpot["disagg_p99_ms"], 1e-9), 2)
        report["decode_tpot"] = tpot
        assert tpot["disagg_p99_ms"] < tpot["coloc_p99_ms"], \
            f"decode TPOT p99 not improved by disaggregation: {tpot}"

        # ---- 3-seed chaos ladder -------------------------------------
        for k in range(3):
            seed = args.seed + k
            cwork = make_disagg_workload(n, seed)
            cref = reference(cwork)

            def expect_chaos(pf):
                # the prefill worker really died -9 MID-HANDOFF...
                assert pf.workers["p0"].poll() == -9, \
                    pf.workers["p0"].poll()
                assert pf.workers["p0"].state is WorkerState.DEAD
                # ... and the decode worker mid-decode
                assert pf.workers["d0"].poll() == -9, \
                    pf.workers["d0"].poll()
                # interrupted handoffs degraded instead of wedging:
                # re-prefilled (refetched / migrated) or re-pulled
                assert (pf.counters["handoffs_refetched"]
                        + pf.counters["requests_migrated"]) >= 1, \
                    pf.summary()
                # the host-armed stall fired and the state machine
                # noticed (phase deadline -> backoff -> re-pull)
                assert faults.fired_counts().get(
                    "fleet.handoff_stall", 0) >= 1
                assert pf.counters["handoff_stalls"] >= 1, pf.summary()

            run_disagg_pass(
                cwork, cref, ccdir, label=f"disagg_chaos_s{seed}",
                report=report, roles=roles,
                worker_faults={
                    # p0: SIGKILL itself with only part of the kv_page
                    # stream shipped (the mid-flight death)
                    "p0": [{"point": "fleet.handoff_partial",
                            "after": k, "times": 1}],
                    # d0: die mid-decode a little into the run, adopted
                    # work re-lands on d1
                    "d0": [{"point": "worker.kill9",
                            "after": 80 + 40 * k, "times": 1}],
                    # d1: refuse its first adopt batch (typed reject ->
                    # supervisor re-routes)
                    "d1": [{"point": "fleet.decode_reject",
                            "after": k, "times": 1}],
                },
                host_faults={
                    # eat kv_page frames at the supervisor relay: the
                    # phase deadline must fire and the pull re-issue.
                    # after= skips the EARLY relays — those pulls tend
                    # to resolve through the p0/d0 death branches
                    # (donor-evacuation / target-reroute), which would
                    # mask the deadline path this scenario is proving
                    "fleet.handoff_stall": dict(payload=True,
                                                after=6 + 2 * k,
                                                times=2),
                },
                expect=expect_chaos)

        # ---- role-starved fallback: prefill-only fleet ---------------
        def expect_starved(pf):
            assert pf.counters["handoffs_colocated"] >= len(work) - 2, \
                pf.summary()
            assert pf.counters["handoffs_completed"] == 0, pf.summary()

        run_disagg_pass(
            work, ref, ccdir, label="role_starved", report=report,
            roles={"p0": "prefill", "p1": "prefill"},
            expect=expect_starved)

        # ---- int8-KV variant: quantized pages + scales ship ----------
        i8work = make_disagg_workload(8, args.seed + 7)
        i8ref = reference(i8work, kv_dtype="int8")

        def expect_int8(pf):
            assert pf.counters["handoffs_completed"] >= 1, pf.summary()
            assert pf.counters["kv_pages_shipped"] >= 2, pf.summary()

        run_disagg_pass(
            i8work, i8ref, ccdir, label="disagg_int8", report=report,
            roles={"p0": "prefill", "d0": "decode"},
            engine_kw=dict(ENGINE_KW, kv_dtype="int8"),
            expect=expect_int8)
        return report
    finally:
        shutil.rmtree(ccdir, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=120)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--procs", action="store_true",
                    help="run the cross-process chaos ladder "
                         "(ISSUE 14) instead of the in-process soak")
    ap.add_argument("--disagg", action="store_true",
                    help="run the disaggregated prefill/decode ladder "
                         "(ISSUE 18): role-split fleet, mid-flight KV "
                         "handoff chaos, decode-TPOT comparison")
    ap.add_argument("--trace-out",
                    default=os.path.join("profiler_log",
                                         "soak_fleet_trace.json"),
                    help="where the traced chaos pass exports the "
                         "MERGED chrome-trace JSON (profiler host "
                         "spans + request lifecycles, ISSUE 10)")
    args = ap.parse_args(argv)

    if args.procs:
        t0 = time.perf_counter()
        report = run_proc_ladder(args)
        report["wall_s"] = round(time.perf_counter() - t0, 2)
        print(json.dumps(report))
        print("SOAK_FLEET_PROC_OK")
        return 0

    if args.disagg:
        t0 = time.perf_counter()
        report = run_disagg_ladder(args)
        report["wall_s"] = round(time.perf_counter() - t0, 2)
        print(json.dumps(report))
        print("SOAK_FLEET_DISAGG_OK")
        return 0

    cfg = LlamaConfig(**CFG_DICT)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    work = make_workload(args.requests, args.seed)

    report = {"requests": args.requests, "seed": args.seed}
    t0 = time.perf_counter()
    single = run_pass(model, work, n_replicas=1,
                      router=PrefixAffinityRouter(), chaos=False,
                      seed=args.seed, report=report, label="single")
    clean = run_pass(model, work, n_replicas=3,
                     router=PrefixAffinityRouter(), chaos=False,
                     seed=args.seed, report=report, label="clean")
    chaos = run_pass(model, work, n_replicas=3,
                     router=PrefixAffinityRouter(), chaos=True,
                     seed=args.seed, report=report, label="chaos")
    rand = run_pass(model, work, n_replicas=3,
                    router=RandomRouter(seed=args.seed + 7), chaos=False,
                    seed=args.seed, report=report, label="random")

    # ---- traced chaos pass (ISSUE 10): the SAME kill/stall chaos with
    # one fleet-shared RequestTracer + an active Profiler, exported as
    # ONE merged chrome-trace JSON — profiler host spans and request
    # lifecycle rows on the shared perf_counter clock (the acceptance
    # artifact); migration park/adopt marks come from the kill.
    from paddle_tpu import profiler
    from paddle_tpu.serving import RequestTracer
    tracer = RequestTracer(max_completed=4 * max(1, args.requests))
    keep = {}
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                             on_trace_ready=lambda p: None)
    prof.start()
    try:
        traced = run_pass(model, work, n_replicas=3,
                          router=PrefixAffinityRouter(), chaos=True,
                          seed=args.seed, report=report, label="traced",
                          trace=tracer, keep=keep)
    finally:
        prof.stop()
    tdiv = [i for i in range(len(work))
            if traced.get(i) != clean.get(i)]
    assert not tdiv, f"tracing perturbed chaos streams: {tdiv[:10]}"
    migrated_traces = [t for t in tracer.traces()
                       if "park" in t.mark_names()
                       and "adopt" in t.mark_names()]
    assert keep["migrated"] == 0 or migrated_traces, \
        "migrations happened but no trace carries park+adopt marks"
    os.makedirs(os.path.dirname(args.trace_out) or ".", exist_ok=True)
    doc = tracer.export(args.trace_out, include_profiler=True,
                        flight_recorder=keep["timelines"])
    cats = {e.get("cat") for e in doc["traceEvents"]}
    assert "request" in cats and len(cats - {"request", None}) >= 1, \
        f"merged export missing host or request spans: {cats}"
    report["trace_out"] = args.trace_out
    report["traced_migration_traces"] = len(migrated_traces)

    # ---- zero-loss failover: EVERY request bit-identical -------------
    diverged = [i for i in range(len(work)) if chaos.get(i) != clean.get(i)]
    assert not diverged, \
        f"chaos streams diverged from the clean run: {diverged[:10]}"
    assert report["chaos"]["lost"] == 0, report["chaos"]
    assert report["chaos"]["deaths"] == 1, report["chaos"]
    assert report["chaos"]["stalls"] == 1, report["chaos"]
    assert report["chaos"]["migrated"] >= 1, report["chaos"]
    report["bit_identical_requests"] = len(work)

    # single-replica sanity: affinity fleet = single replica tokens too
    # (the routing layer must never change WHAT is generated)
    div1 = [i for i in range(len(work)) if single.get(i) != clean.get(i)]
    assert not div1, f"fleet changed tokens vs single replica: {div1[:10]}"

    # ---- the routing criterion ---------------------------------------
    hits_single = report["single"]["prefix_hits"]
    hits_aff = report["clean"]["prefix_hits"]
    hits_rand = report["random"]["prefix_hits"]
    assert hits_single > 0, report["single"]
    assert hits_aff >= hits_single, \
        f"affinity fleet hit rate fell below the single-replica " \
        f"baseline: {hits_aff} < {hits_single}"
    assert hits_aff > hits_rand, \
        f"affinity routing did not beat random spray: " \
        f"{hits_aff} <= {hits_rand}"

    report["wall_s"] = round(time.perf_counter() - t0, 2)
    print(json.dumps(report))
    # ---- final report through the observability paths (ISSUE 10) -----
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_report
    print(trace_report.report(trace_report.load(args.trace_out)))
    print("== fleet metrics exposition (traced chaos pass) ==")
    print(keep.get("prometheus", ""), end="")
    print("SOAK_FLEET_OK")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except AssertionError as e:
        print(f"SOAK_FLEET_FAILED: {e}", file=sys.stderr)
        sys.exit(1)
