"""Serving observability: counters + gauges wired into the profiler.

Two integration points with the existing profiler subsystem:
  * the engine wraps prefill/decode program launches in
    `profiler.RecordEvent` spans, so they land on the host timeline and
    in `Profiler.summary()` like any other op;
  * a ServingMetrics registers itself as a profiler counter provider
    (`profiler.register_counter_provider`), so `Profiler.summary()`
    appends the live serving counters to its table.

Prefix-cache / chunked-prefill observability (ISSUE 2): prefix hit
rate, cached-tokens-served, prefill-tokens-skipped, radix evictions,
prefill chunks, and per-request queue-wait / TTFT percentiles (bounded
reservoirs — a long-lived server keeps the last `PERCENTILE_WINDOW`
samples, not one entry per request ever served).
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, Optional

__all__ = ["ServingMetrics"]

PERCENTILE_WINDOW = 1024


# the shared nearest-rank percentile rule (profiler/exposition.py) —
# serving reservoirs and the TrainingMonitor latency ring must agree
from ..profiler.exposition import percentile as _percentile  # noqa: E402


class ServingMetrics:
    """Counters/gauges for one ServingEngine."""

    def __init__(self, name: str = "serving"):
        self.name = name
        self.counters: Dict[str, int] = {
            "requests_added": 0,
            # snapshot-restored intake (fleet migration / from_snapshot)
            # — kept separate from requests_added so fleet-merged
            # counters (dead replicas included) don't double-count a
            # migrated request as two arrivals
            "requests_adopted": 0,
            "requests_finished": 0,
            "requests_preempted": 0,
            "prefill_tokens": 0,
            "decode_tokens": 0,
            "engine_steps": 0,
            "recompiles": 0,
            # --- prefix cache / chunked prefill (ISSUE 2) ---
            "prefill_chunks": 0,           # chunk launches (incl. final)
            "admissions": 0,               # first-chunk admissions
            "prefix_hits": 0,              # admissions with a cache match
            "cached_tokens_served": 0,     # matched tokens reused from cache
            "prefill_tokens_skipped": 0,   # prefill work those tokens saved
            "radix_evicted_pages": 0,
            # --- failure modes (ISSUE 3) ---
            "requests_aborted": 0,         # client abort() honored
            "deadline_expired": 0,         # TTL/deadline cancellations
            "requests_shed": 0,            # EngineOverloaded rejections
            "step_retries": 0,             # transient-failure re-launches
            "requests_quarantined": 0,     # poisoned (NaN) requests failed
            "engine_failures": 0,          # unrecoverable -> snapshot
            # --- quantized KV / weights (ISSUE 6) ---
            # device bytes the KV writes landed / the attention reads
            # streamed (host-computed from token counts x bytes-per-
            # token, scales included) — the capacity-per-chip evidence:
            # at kv_dtype=int8 both drop ~2x for the same token traffic
            "kv_bytes_written": 0,
            "kv_bytes_read": 0,
            # --- speculative decoding (ISSUE 5) ---
            "spec_steps": 0,               # verify launches
            "spec_verified_rows": 0,       # sequence-steps verified
            "spec_drafted_tokens": 0,      # draft tokens scored
            "spec_accepted_tokens": 0,     # drafts that survived verify
            "spec_emitted_tokens": 0,      # tokens emitted by verify steps
            "spec_rollback_tokens": 0,     # rejected-draft KV truncated
            "spec_draft_oom_drops": 0,     # drafts dropped: pool pressure
            # --- multi-step decode (ISSUE 13) ---
            "decode_launches": 0,          # decode-side program launches
            "decode_launch_steps": 0,      # K summed over those launches
            "decode_launch_rows": 0,       # live rows summed over them
            # plain decode launches enqueued BEFORE the launch before
            # them was fetched (ISSUE 34): over decode_launches, the
            # share of launches whose host work ran under the device's
            "decode_launches_ahead": 0,
            "multi_decode_slot_shortfall": 0,  # K-1 slots the pool denied
            # --- multi-LoRA serving (ISSUE 15) ---
            # registry lifecycle (AdapterRegistry.bind_counters homes
            # them here): loads, explicit unloads, LRU evictions of
            # idle adapters, typed load failures (incl. the injected
            # serving.lora.load_fail fault), evict-race guard refusals
            # (a busy adapter picked for eviction and refused), and
            # requests rejected at the door for naming an unloaded
            # adapter
            "adapters_loaded": 0,
            "adapters_unloaded": 0,
            "adapters_evicted": 0,
            "adapter_load_failures": 0,
            "lora_evict_refusals": 0,
            "adapter_rejects": 0,
            # --- tiered KV: host-RAM spill (ISSUE 17) ---
            # demotion/promotion traffic (radix-synced at the gauge
            # sites, like radix_evicted_pages), the eviction rung taken
            # (demote-to-host vs drop — the spill tier's auditability
            # counters), host-tier hits/drops, fleet prefix pulls, and
            # the three host_spill fault outcomes (bridge-incremented
            # where the degradation happens)
            "kv_pages_demoted": 0,         # device pages spilled to host
            "kv_pages_promoted": 0,        # host pages copied back
            "host_prefix_hits": 0,         # matches that promoted a span
            "host_pages_dropped": 0,       # host-tier LRU/cascade drops
            "radix_evict_demoted": 0,      # eviction rung: demoted
            "radix_evict_dropped": 0,      # eviction rung: dropped
            "kv_pages_exported": 0,        # fleet pull, donor side
            "kv_pages_adopted": 0,         # fleet pull, receiver side
            # --- disaggregated prefill/decode (ISSUE 18) ---
            # prefill-role engines: requests finished "handoff" (pages
            # donated for the fleet's kv_pull) and pages released
            # (demoted-to-coldest or dropped) after the decode side
            # confirmed adoption
            "prefill_handoffs": 0,
            "kv_pages_released": 0,
            "host_spill_corrupt": 0,       # CRC reject -> recompute
            "host_spill_slow": 0,          # deadline miss -> retry later
            "host_spill_lost": 0,          # buffer gone -> recompute
            # --- persistent compile cache (ISSUE 14) ---
            # mirrors of the engine's CompileCache counters (zero with
            # the cache off): hits skipped a trace+compile entirely;
            # rejects are corrupt/stale/mismatched entries that
            # degraded to recompile (counted, never crashing)
            "compile_cache_hits": 0,
            "compile_cache_misses": 0,
            "compile_cache_rejects": 0,
        }
        self._registered = False
        self._t_start = time.perf_counter()
        self._arrive_t: Dict[int, float] = {}   # in-flight only (popped
        # on finish) — aggregates + bounded reservoirs, so a long-lived
        # server doesn't keep a per-request entry forever
        self._ttft_sum = 0.0
        self._ttft_count = 0
        # Named bounded reservoirs, AUTO-exposed by snapshot()/summary()
        # as {name}_p50/_p90/_p99{suffix} — registering one here is all
        # it takes to surface its percentiles, the same no-hand-
        # maintained-key-list contract the counters dict gives new
        # counters (a PR-3 lesson: drift between the metric store and
        # the reporting path is a silent observability bug).
        self._reservoirs: Dict[str, deque] = {}
        self._reservoir_fmt: Dict[str, tuple] = {}   # name -> (scale,
        #                                         suffix, round digits)
        self._ttft_samples = self.add_reservoir("ttft", scale=1e3,
                                                suffix="_ms")
        self._queue_wait_samples = self.add_reservoir("queue_wait",
                                                      scale=1e3,
                                                      suffix="_ms")
        # accepted tokens per verify step (the spec-decode win, per
        # step): mean > 1 is the "speculation pays" signal
        self._accepted_samples = self.add_reservoir("spec_accepted")
        # TPOT: launch wall seconds / tokens emitted by the launch, so
        # the per-token percentiles stay comparable whether a launch
        # emits 1 token (K=1) or K (multi-step decode, ISSUE 13) —
        # coarser launches must not silently inflate the p99s
        self._tpot_samples = self.add_reservoir("tpot", scale=1e3,
                                                suffix="_ms")
        # distinct adapters per decode-side launch (ISSUE 15): the
        # per-launch adapter-mix histogram — p50 > 1 means launches
        # really are heterogeneous (the segment kernel's whole point)
        self._adapter_mix_samples = self.add_reservoir("adapter_mix",
                                                       digits=2)
        # gauges updated by the engine each step
        self.queue_depth = 0
        self.running = 0
        self.kv_used_pages = 0
        self.kv_occupancy = 0.0
        self.cached_pages = 0
        self.radix_nodes = 0
        # used pages of each windowed layer group (None: the model has
        # one group, and the snapshot shows nothing new)
        self.kv_window_used_pages = None
        # static KV-geometry gauges (set once at engine construction)
        self.kv_dtype = None
        self.kv_page_bytes = 0
        self.kv_pool_bytes = 0
        self.kv_bytes_per_token = 0
        # per-shard geometry (ISSUE 8): with a TP mesh the page
        # contents are head-sharded, so one chip pays page_bytes/tp
        # per page; at tp=1 shard == global
        self.kv_tp_degree = 0
        self.kv_page_bytes_shard = 0
        self.kv_pool_bytes_shard = 0
        # host spill tier (ISSUE 17): pool geometry set once at engine
        # construction (set_host_info), occupancy updated per step.
        # host_pool_pages == 0 means no spill tier — the snapshot block
        # is gated on it, so spill-off engines expose nothing new.
        self.host_pool_pages = 0
        self.host_page_bytes = 0
        self.host_pool_bytes = 0
        self.host_pages_used = 0
        self.host_occupancy = 0.0

    # ---- reservoir registry ---------------------------------------------
    def add_reservoir(self, name: str, scale: float = 1.0,
                      suffix: str = "", digits: int = 3) -> deque:
        """Register a bounded percentile reservoir. Returns the deque to
        append raw samples to; snapshot() exposes
        `{name}_p50{suffix}` / p90 / p99 (sample * scale) automatically."""
        d = self._reservoirs.setdefault(
            name, deque(maxlen=PERCENTILE_WINDOW))
        self._reservoir_fmt[name] = (float(scale), suffix, int(digits))
        return d

    def reservoir_percentiles(self, name):
        """{p50, p90, p99} raw-valued over one registered reservoir."""
        return {f"p{q}": _percentile(self._reservoirs.get(name, ()), q)
                for q in (50, 90, 99)}

    # ---- event hooks -----------------------------------------------------
    def on_add(self, request_id: int):
        self.counters["requests_added"] += 1
        self._arrive_t[request_id] = time.perf_counter()

    def on_adopt(self, request_id: int):
        """Snapshot-restored request entering this engine: counted as
        adopted, not added, and with NO arrival stamp — its queue-wait/
        TTFT windows belong to its original admission, not the
        migration."""
        self.counters["requests_adopted"] += 1

    def on_admission(self, request_id: int, cached_tokens: int,
                     resumed: bool = False):
        """First chunk of an admission scheduled. `admissions` and the
        hit accounting count RE-admissions after preemption too (a
        donated prefix turning a resume into a hit is the point);
        the queue-wait sample is taken only for the ORIGINAL admission —
        on a resume the arrival-to-now span includes time already spent
        running, which is not queue wait."""
        self.counters["admissions"] += 1
        if cached_tokens > 0:
            self.counters["prefix_hits"] += 1
            self.counters["cached_tokens_served"] += cached_tokens
            self.counters["prefill_tokens_skipped"] += cached_tokens
        if not resumed:
            t0 = self._arrive_t.get(request_id)
            if t0 is not None:
                self._queue_wait_samples.append(time.perf_counter() - t0)

    def on_first_token(self, request_id: int):
        # called once per request (the engine guards on num_generated==0)
        t0 = self._arrive_t.get(request_id)
        if t0 is not None:
            dt = time.perf_counter() - t0
            self._ttft_sum += dt
            self._ttft_count += 1
            self._ttft_samples.append(dt)

    def on_prefill(self, num_tokens: int):
        self.counters["prefill_tokens"] += num_tokens
        self.counters["prefill_chunks"] += 1

    def on_decode(self, num_tokens: int):
        self.counters["decode_tokens"] += num_tokens

    def on_decode_launch(self, k: int, rows: int, tokens: int,
                         seconds: Optional[float] = None,
                         ahead: bool = False):
        """One decode-side program launch (plain K=1 or multi-step K)
        over `rows` live rows: `tokens` tokens were emitted in
        `seconds` of launch wall time. The TPOT sample divides the
        launch latency by the tokens it emitted — the per-token number
        that stays comparable across K. `ahead`: the launch was
        enqueued before the launch before it was fetched."""
        self.counters["decode_launches"] += 1
        self.counters["decode_launches_ahead"] += int(ahead)
        self.counters["decode_launch_steps"] += int(k)
        self.counters["decode_launch_rows"] += int(rows)
        if seconds is not None and seconds > 0 and tokens > 0:
            self._tpot_samples.append(seconds / tokens)

    def on_adapter_mix(self, distinct: int):
        """Distinct adapters (null/base excluded) in one decode-side
        launch — the mixed-batch heterogeneity histogram (ISSUE 15)."""
        self._adapter_mix_samples.append(int(distinct))

    def tokens_per_launch(self) -> Optional[float]:
        """Mean decode tokens emitted per ROW per decode-side launch
        (None before any launch) — 1.0 for plain decode, approaching K
        for multi-step decode at full batch (the >= 0.9 K acceptance
        number; the tail of a draining workload pulls it down when
        rows run out of remaining tokens mid-grid)."""
        if not self.counters["decode_launch_rows"]:
            return None
        return (self.counters["decode_tokens"]
                / self.counters["decode_launch_rows"])

    # ---- quantized KV / weights (ISSUE 6) --------------------------------
    def set_kv_info(self, *, kv_dtype, page_bytes, pool_bytes,
                    bytes_per_token, tp_degree=1, page_bytes_shard=None,
                    pool_bytes_shard=None):
        """Static KV-pool geometry: dtype, bytes/page (scales included),
        total pool bytes, and one token's all-layer K+V footprint —
        page capacity at fixed HBM is pool_bytes / page_bytes, the
        number kv_dtype=int8 roughly doubles. page/pool bytes are
        GLOBAL (summed over TP shards); the per-shard gauges (ISSUE 8)
        record what ONE chip pays — pool_bytes_shard is the per-chip
        `kv_pool_bytes` budget's echo, the number head-sharding holds
        fixed while page capacity scales ~x tp."""
        self.kv_dtype = str(kv_dtype)
        self.kv_page_bytes = int(page_bytes)
        self.kv_pool_bytes = int(pool_bytes)
        self.kv_bytes_per_token = int(bytes_per_token)
        self.kv_tp_degree = int(tp_degree)
        self.kv_page_bytes_shard = int(
            page_bytes if page_bytes_shard is None else page_bytes_shard)
        self.kv_pool_bytes_shard = int(
            pool_bytes if pool_bytes_shard is None else pool_bytes_shard)

    def set_host_info(self, *, pool_pages, page_bytes):
        """Static host-spill-pool geometry (ISSUE 17): slot count and
        the bytes ONE host page carries — a radix page's K+V across
        every layer, scale rows included (num_layers x kv_page_bytes),
        because the demote unit is the whole per-layer stack for one
        device page. pool_pages > 0 is also the snapshot gate for the
        host block, the same role kv_pool_bytes plays for the KV
        geometry block."""
        self.host_pool_pages = int(pool_pages)
        self.host_page_bytes = int(page_bytes)
        self.host_pool_bytes = int(pool_pages) * int(page_bytes)

    def on_kv_bytes(self, written: int = 0, read: int = 0):
        self.counters["kv_bytes_written"] += int(written)
        self.counters["kv_bytes_read"] += int(read)

    def on_finish(self, request_id: int):
        self.counters["requests_finished"] += 1
        self._arrive_t.pop(request_id, None)

    def on_preempt(self):
        self.counters["requests_preempted"] += 1

    # ---- failure-mode hooks (ISSUE 3) -----------------------------------
    def on_abort(self, request_id: int):
        self.counters["requests_aborted"] += 1
        self._arrive_t.pop(request_id, None)

    def on_expire(self, request_id: int):
        self.counters["deadline_expired"] += 1
        self._arrive_t.pop(request_id, None)

    def on_shed(self):
        self.counters["requests_shed"] += 1

    def on_step_retry(self):
        self.counters["step_retries"] += 1

    def on_quarantine(self, request_id: int):
        self.counters["requests_quarantined"] += 1
        self._arrive_t.pop(request_id, None)

    def on_engine_failure(self):
        self.counters["engine_failures"] += 1

    # ---- speculative decoding hooks (ISSUE 5) ---------------------------
    def on_spec_step(self, drafted: int, accepted: int, emitted: int,
                     rolled_back: int, rows: int):
        """One verify launch: `drafted` draft tokens scored, `accepted`
        of them kept, `emitted` tokens emitted in total (accepted + the
        correction/bonus tokens), `rolled_back` rejected-draft tokens
        truncated out of the paged cache, over `rows` verified
        sequences (emitting rows — quarantined rows excluded), so the
        tokens-per-step multiplier normalizes per SEQUENCE, not per
        launch (a full batch would otherwise look like speculation)."""
        self.counters["spec_steps"] += 1
        self.counters["spec_verified_rows"] += rows
        self.counters["spec_drafted_tokens"] += drafted
        self.counters["spec_accepted_tokens"] += accepted
        self.counters["spec_emitted_tokens"] += emitted
        self.counters["spec_rollback_tokens"] += rolled_back
        self._accepted_samples.append(accepted)

    def on_spec_draft_oom(self, dropped: int):
        self.counters["spec_draft_oom_drops"] += dropped

    def on_step(self):
        self.counters["engine_steps"] += 1

    def on_recompile(self):
        self.counters["recompiles"] += 1

    def update_gauges(self, *, queue_depth, running, kv_used_pages,
                      kv_occupancy, cached_pages=0, radix_nodes=0,
                      radix_evicted_pages=None,
                      host_pages_used=None, host_occupancy=None,
                      radix_evict_demoted=None, radix_evict_dropped=None,
                      kv_pages_demoted=None, kv_pages_promoted=None,
                      host_prefix_hits=None, host_pages_dropped=None,
                      kv_window_used_pages=None):
        """None for an optional field means "leave it untouched" — the
        engine passes its radix/spill sync kwargs only when the
        corresponding subsystem exists, so a cache-off or spill-off
        engine can never zero a counter it does not own."""
        self.queue_depth = queue_depth
        self.running = running
        self.kv_used_pages = kv_used_pages
        self.kv_occupancy = kv_occupancy
        self.cached_pages = cached_pages
        self.radix_nodes = radix_nodes
        if radix_evicted_pages is not None:
            self.counters["radix_evicted_pages"] = radix_evicted_pages
        if host_pages_used is not None:
            self.host_pages_used = host_pages_used
        if host_occupancy is not None:
            self.host_occupancy = host_occupancy
        if kv_window_used_pages is not None:
            self.kv_window_used_pages = list(kv_window_used_pages)
        # radix-owned counters synced by assignment (idempotent), the
        # radix_evicted_pages pattern
        for key, val in (("radix_evict_demoted", radix_evict_demoted),
                         ("radix_evict_dropped", radix_evict_dropped),
                         ("kv_pages_demoted", kv_pages_demoted),
                         ("kv_pages_promoted", kv_pages_promoted),
                         ("host_prefix_hits", host_prefix_hits),
                         ("host_pages_dropped", host_pages_dropped)):
            if val is not None:
                self.counters[key] = val

    # ---- derived ---------------------------------------------------------
    def tokens_per_second(self) -> float:
        dt = time.perf_counter() - self._t_start
        total = self.counters["prefill_tokens"] + self.counters["decode_tokens"]
        return total / dt if dt > 0 else 0.0

    def mean_ttft(self) -> Optional[float]:
        if not self._ttft_count:
            return None
        return self._ttft_sum / self._ttft_count

    def prefix_hit_rate(self) -> Optional[float]:
        if not self.counters["admissions"]:
            return None
        return self.counters["prefix_hits"] / self.counters["admissions"]

    def spec_acceptance_rate(self) -> Optional[float]:
        """accepted / drafted over the engine's life (None before any
        draft was scored)."""
        if not self.counters["spec_drafted_tokens"]:
            return None
        return (self.counters["spec_accepted_tokens"]
                / self.counters["spec_drafted_tokens"])

    def spec_tokens_per_step(self) -> Optional[float]:
        """Mean tokens emitted per SEQUENCE per verify launch — the
        spec-decode throughput multiplier (1.0 = speculation never
        paid; the paged-attention launch amortizes over this many
        tokens per sequence)."""
        if not self.counters["spec_verified_rows"]:
            return None
        return (self.counters["spec_emitted_tokens"]
                / self.counters["spec_verified_rows"])

    def ttft_percentiles(self):
        """{p50, p90, p99} seconds over the bounded TTFT window —
        a view over the registered reservoir, so this method and
        snapshot() can never disagree."""
        return self.reservoir_percentiles("ttft")

    def queue_wait_percentiles(self):
        return self.reservoir_percentiles("queue_wait")

    def snapshot(self) -> dict:
        snap = dict(self.counters)
        snap.update({
            "queue_depth": self.queue_depth,
            "running": self.running,
            "kv_used_pages": self.kv_used_pages,
            "kv_occupancy": round(self.kv_occupancy, 4),
            "cached_pages": self.cached_pages,
            "radix_nodes": self.radix_nodes,
            "tokens_per_second": round(self.tokens_per_second(), 2),
        })
        if self.kv_window_used_pages is not None:
            snap["kv_window_used_pages"] = list(self.kv_window_used_pages)
        # pool bytes gate the block (not page bytes): a heterogeneous
        # fleet merge zeroes the per-page gauges as sentinels while the
        # pooled bytes stay exact — they must still surface
        if self.kv_page_bytes or self.kv_pool_bytes:
            snap.update({
                "kv_dtype": self.kv_dtype,
                "kv_page_bytes": self.kv_page_bytes,
                "kv_pool_bytes": self.kv_pool_bytes,
                "kv_bytes_per_token": self.kv_bytes_per_token,
                "kv_tp_degree": self.kv_tp_degree,
                "kv_page_bytes_shard": self.kv_page_bytes_shard,
                "kv_pool_bytes_shard": self.kv_pool_bytes_shard,
            })
        # host spill tier (ISSUE 17): gated on a configured pool —
        # merged summaries keep the block when ANY replica spills
        # (pool pages sum; page bytes may sentinel to 0 when mixed)
        if self.host_pool_pages:
            snap.update({
                "host_pool_pages": self.host_pool_pages,
                "host_page_bytes": self.host_page_bytes,
                "host_pool_bytes": self.host_pool_bytes,
                "host_pages_used": self.host_pages_used,
                "host_occupancy": round(self.host_occupancy, 4),
            })
        hr = self.prefix_hit_rate()
        if hr is not None:
            snap["prefix_hit_rate"] = round(hr, 4)
        ar = self.spec_acceptance_rate()
        if ar is not None:
            snap["spec_acceptance_rate"] = round(ar, 4)
        tps = self.spec_tokens_per_step()
        if tps is not None:
            snap["spec_tokens_per_step"] = round(tps, 4)
        tpl = self.tokens_per_launch()
        if tpl is not None:
            snap["decode_tokens_per_launch"] = round(tpl, 4)
        ttft = self.mean_ttft()
        if ttft is not None:
            snap["mean_ttft_ms"] = round(ttft * 1e3, 3)
        # every registered reservoir surfaces its percentiles here — no
        # hand-maintained key list to drift from the registry
        for name, (scale, suffix, digits) in self._reservoir_fmt.items():
            for q, v in self.reservoir_percentiles(name).items():
                if v is not None:
                    snap[f"{name}_{q}{suffix}"] = round(v * scale, digits)
        return snap

    # the reference's Metric objects expose `summary()`; ours is the
    # same auto-exposing view (counters dict + registered reservoirs)
    summary = snapshot

    # ---- Prometheus exposition (ISSUE 10) --------------------------------
    def prometheus_text(self, *, prefix: str = "paddle_serving",
                        labels: Optional[dict] = None,
                        emit_type: bool = True) -> str:
        """This metrics object as Prometheus exposition text — DERIVED
        from `snapshot()` (the renderer walks the live snapshot dict),
        so the scrape can never disagree with it: every counter, gauge
        and registered-reservoir percentile surfaces with no
        hand-maintained name list. Keys in the counters dict are typed
        `counter`, everything else `gauge`."""
        from ..profiler.exposition import prometheus_lines
        lines = prometheus_lines(self.snapshot(),
                                 counter_keys=set(self.counters),
                                 prefix=prefix, labels=labels,
                                 emit_type=emit_type)
        return "\n".join(lines) + "\n" if lines else ""

    # ---- cross-replica aggregation (fleet, ISSUE 7) ----------------------
    @classmethod
    def merge(cls, *metrics: "ServingMetrics",
              name: str = "fleet") -> "ServingMetrics":
        """Combine per-replica metrics into ONE summary: counters and
        TTFT aggregates sum, every registered percentile reservoir
        merges via a balanced NEWEST-first draw across replicas (still
        bounded by the window — an overflowing union keeps each
        replica's freshest samples instead of letting the last-merged
        replica's window win), count-like gauges sum, and
        kv_occupancy becomes the pooled used/total ratio. The result is
        a live view's worth of state in a fresh UNREGISTERED instance
        (register() it only if it should shadow a real engine in
        Profiler.summary(), which a fleet summary should not).
        tokens_per_second spans the earliest source's start time, so
        the merged rate is fleet throughput, not a division by the
        merge call's age."""
        out = cls(name=name)
        total_pages_used = 0
        total_pages = 0.0
        for m in metrics:
            for k, v in m.counters.items():
                out.counters[k] = out.counters.get(k, 0) + v
            out._ttft_sum += m._ttft_sum
            out._ttft_count += m._ttft_count
            out._t_start = min(out._t_start, m._t_start)
            out.queue_depth += m.queue_depth
            out.running += m.running
            out.kv_used_pages += m.kv_used_pages
            out.cached_pages += m.cached_pages
            out.radix_nodes += m.radix_nodes
            out.kv_pool_bytes += m.kv_pool_bytes
            # pool-weighted occupancy: per-replica page counts recovered
            # from the byte geometry (pool / page bytes)
            if m.kv_page_bytes:
                pages = m.kv_pool_bytes / m.kv_page_bytes
                total_pages += pages
                total_pages_used += m.kv_used_pages
        if total_pages:
            out.kv_occupancy = total_pages_used / total_pages
        # per-page geometry gauges are only meaningful when every
        # source agrees — a heterogeneous fleet gets explicit sentinels
        # instead of whichever replica happened to merge last (pooled
        # kv_pool_bytes / occupancy above stay exact either way)
        pbs = {m.kv_page_bytes for m in metrics if m.kv_page_bytes}
        dts = {m.kv_dtype for m in metrics if m.kv_page_bytes}
        bpts = {m.kv_bytes_per_token for m in metrics if m.kv_page_bytes}
        out.kv_page_bytes = pbs.pop() if len(pbs) == 1 else 0
        out.kv_dtype = dts.pop() if len(dts) == 1 \
            else ("mixed" if dts else None)
        out.kv_bytes_per_token = bpts.pop() if len(bpts) == 1 else 0
        # per-shard geometry (ISSUE 8): same singleton-or-sentinel rule
        # — a fleet mixing TP degrees zeroes the per-shard gauges (and
        # tp_degree) instead of letting the last-merged replica win,
        # while the pooled kv_pool_bytes / occupancy above stay EXACT
        # (both are computed from each replica's own GLOBAL geometry
        # before the sentinel collapse, so mixed-TP pools sum true)
        tps = {m.kv_tp_degree for m in metrics if m.kv_page_bytes}
        pbss = {m.kv_page_bytes_shard for m in metrics if m.kv_page_bytes}
        plss = {m.kv_pool_bytes_shard for m in metrics if m.kv_page_bytes}
        out.kv_tp_degree = tps.pop() if len(tps) == 1 else 0
        out.kv_page_bytes_shard = pbss.pop() if len(pbss) == 1 else 0
        out.kv_pool_bytes_shard = plss.pop() if len(plss) == 1 else 0
        # host spill tier (ISSUE 17): pooled slots/bytes/usage sum EXACT
        # across the replicas that spill (spill-off replicas contribute
        # zeros); occupancy is the pooled used/total ratio; per-page
        # bytes follow the singleton-or-sentinel rule — a heterogeneous
        # fleet (mixed layer counts or kv dtypes) zeroes the gauge
        # instead of letting the last-merged replica win
        out.host_pool_pages = sum(m.host_pool_pages for m in metrics)
        out.host_pool_bytes = sum(m.host_pool_bytes for m in metrics)
        out.host_pages_used = sum(m.host_pages_used for m in metrics)
        if out.host_pool_pages:
            out.host_occupancy = (out.host_pages_used
                                  / out.host_pool_pages)
        hpbs = {m.host_page_bytes for m in metrics if m.host_pool_pages}
        out.host_page_bytes = hpbs.pop() if len(hpbs) == 1 else 0
        # reservoirs: per-name balanced newest-first draw — walk every
        # source from its freshest sample backwards, round-robin, until
        # the window fills; reversed so the merged deque stays
        # oldest->newest like any live reservoir
        fmts = {}
        for m in metrics:
            for rname in m._reservoirs:
                fmts.setdefault(rname, m._reservoir_fmt[rname])
        for rname, (scale, suffix, digits) in fmts.items():
            srcs = [list(m._reservoirs[rname]) for m in metrics
                    if rname in m._reservoirs]
            picked = []
            depth = 1
            while len(picked) < PERCENTILE_WINDOW and \
                    any(depth <= len(s) for s in srcs):
                for s in srcs:
                    if depth <= len(s) and len(picked) < PERCENTILE_WINDOW:
                        picked.append(s[-depth])
                depth += 1
            out.add_reservoir(rname, scale=scale, suffix=suffix,
                              digits=digits).extend(reversed(picked))
        return out

    # ---- profiler integration -------------------------------------------
    def register(self):
        """Expose this engine's counters through Profiler.summary()."""
        from .. import profiler
        profiler.register_counter_provider(self.name, self.snapshot)
        self._registered = True
        return self

    def unregister(self):
        if self._registered:
            from .. import profiler
            profiler.unregister_counter_provider(self.name)
            self._registered = False
