"""Prometheus exposition of serving metrics (ISSUE 10).

The load-bearing test is the DRIFT test: the exposition is derived from
`ServingMetrics.snapshot()` with one rendering rule per VALUE type and
no hand-maintained name lists, so every snapshot key must appear in the
scrape and every scrape metric must map back to a snapshot key — in
both directions, including the reservoir percentiles and the PR-8
merge/mixed-TP sentinel gauges.
"""
from __future__ import annotations

import re

import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (Fleet, PrefixAffinityRouter,
                                ServingEngine, ServingMetrics)
from paddle_tpu.profiler.exposition import (metric_name,
                                           parse_exposition_names,
                                           prometheus_lines,
                                           render_prometheus)

PREFIX = "paddle_serving"


def expected_names(snap: dict, prefix: str = PREFIX) -> set:
    """What the rendering rules say the exposition must contain —
    computed from the snapshot alone (the drift test's forward
    direction)."""
    out = set()
    for k, v in snap.items():
        if v is None:
            continue
        name = metric_name(prefix, k)
        if isinstance(v, str):
            name += "_info"
        elif not isinstance(v, (int, float, bool)):
            name += "" if isinstance(v, dict) else "_info"
        out.add(name)
    return out


def populated_metrics(tp_degree=1) -> ServingMetrics:
    m = ServingMetrics(name="t")
    m.on_add(1)
    m.on_admission(1, cached_tokens=3)
    m.on_first_token(1)
    m.on_prefill(10)
    m.on_decode(4)
    m.on_finish(1)
    m.on_spec_step(4, 2, 3, 2, 1)
    m.on_adapter_mix(2)
    m.set_kv_info(kv_dtype="int8", page_bytes=1024, pool_bytes=65536,
                  bytes_per_token=128, tp_degree=tp_degree,
                  page_bytes_shard=1024 // tp_degree,
                  pool_bytes_shard=65536 // tp_degree)
    # tiered-KV host spill (ISSUE 17): geometry + the full sync-kwarg
    # set, so the drift bijection covers every new host/rung name
    m.set_host_info(pool_pages=8, page_bytes=2048)
    m.update_gauges(queue_depth=2, running=1, kv_used_pages=5,
                    kv_occupancy=0.25, cached_pages=3, radix_nodes=2,
                    radix_evicted_pages=1,
                    host_pages_used=3, host_occupancy=0.375,
                    radix_evict_demoted=4, radix_evict_dropped=1,
                    kv_pages_demoted=6, kv_pages_promoted=5,
                    host_prefix_hits=2, host_pages_dropped=1)
    return m


# ---------------------------------------------------------------- drift
def test_snapshot_exposition_bijection():
    m = populated_metrics()
    snap = m.snapshot()
    # reservoirs actually surfaced (percentile keys present)
    assert any(k.startswith("ttft_p") for k in snap)
    assert any(k.startswith("spec_accepted_p") for k in snap)
    # multi-LoRA additions (ISSUE 15) ride the same registries in both
    # directions: the adapter counters land in the counters dict (typed
    # counter in the scrape) and the per-launch mix histogram is a
    # registered reservoir (percentiles in snapshot AND scrape)
    for key in ("adapters_loaded", "adapters_evicted",
                "adapter_load_failures", "lora_evict_refusals",
                "adapter_rejects"):
        assert key in m.counters and key in snap
    assert snap["adapter_mix_p50"] == 2
    text = m.prometheus_text()
    assert parse_exposition_names(text) == expected_names(snap)
    assert f"# TYPE {PREFIX}_adapters_loaded counter" in text
    assert f"{PREFIX}_adapter_mix_p50 2" in text
    # tiered-KV (ISSUE 17) names ride the same registries: the host
    # pool block is snapshot-gated on set_host_info, the rung/traffic
    # counters live in the counters dict (typed counter in the scrape)
    for key in ("host_pool_pages", "host_page_bytes", "host_pool_bytes",
                "host_pages_used", "host_occupancy"):
        assert key in snap
    for key in ("kv_pages_demoted", "kv_pages_promoted",
                "host_prefix_hits", "host_pages_dropped",
                "radix_evict_demoted", "radix_evict_dropped",
                "kv_pages_exported", "kv_pages_adopted",
                "host_spill_corrupt", "host_spill_slow",
                "host_spill_lost"):
        assert key in m.counters and key in snap
    assert f"# TYPE {PREFIX}_kv_pages_demoted counter" in text
    assert f"{PREFIX}_host_pool_pages 8" in text
    # spill-off engines expose NO host block (the pool_pages gate)
    off = ServingMetrics(name="off")
    off_snap = off.snapshot()
    assert not any(k.startswith("host_") for k in off_snap
                   if k not in off.counters)
    assert parse_exposition_names(off.prometheus_text()) \
        == expected_names(off_snap)


def test_drift_new_counter_and_reservoir_auto_surface():
    """The registry contract: adding a counter key or a reservoir is
    ALL it takes for the scrape to carry it."""
    m = populated_metrics()
    m.counters["totally_new_counter"] = 7
    m.add_reservoir("new_latency", scale=1e3, suffix="_ms").extend(
        [0.001, 0.002])
    snap = m.snapshot()
    assert "new_latency_p50_ms" in snap
    text = m.prometheus_text()
    names = parse_exposition_names(text)
    assert names == expected_names(snap)
    assert f"{PREFIX}_totally_new_counter" in names
    assert f"{PREFIX}_new_latency_p50_ms" in names
    # counters typed counter, derived/gauge keys typed gauge
    assert f"# TYPE {PREFIX}_totally_new_counter counter" in text
    assert f"# TYPE {PREFIX}_new_latency_p50_ms gauge" in text


def test_mixed_tp_merge_sentinels_round_trip():
    """The PR-8 singleton-or-sentinel gauges survive the exposition:
    a mixed-TP merge zeroes the per-shard gauges and flags kv_dtype
    'mixed' — all of it must round-trip the scrape."""
    a = populated_metrics(tp_degree=1)
    b = populated_metrics(tp_degree=4)
    b.kv_dtype = "bfloat16"                # heterogeneous dtype too
    m = ServingMetrics.merge(a, b)
    snap = m.snapshot()
    assert snap["kv_tp_degree"] == 0       # the sentinel
    assert snap["kv_page_bytes_shard"] == 0
    assert snap["kv_dtype"] == "mixed"
    text = m.prometheus_text()
    names = parse_exposition_names(text)
    assert names == expected_names(snap)
    assert f'{PREFIX}_kv_dtype_info{{kv_dtype="mixed"}} 1' in text
    assert f"{PREFIX}_kv_tp_degree 0" in text


def test_mixed_host_merge_pools_and_sentinels():
    """ISSUE 17 merge rules for a heterogeneous fleet: pooled host
    slots/bytes/usage sum exactly (spill-off replicas contribute
    zeros), occupancy re-derives from the pooled ratio, and the
    per-page gauge follows the PR-8 singleton-or-sentinel rule —
    all of it must survive the scrape."""
    a = populated_metrics()                # 8 pages x 2048 B, 3 used
    b = populated_metrics()
    b.set_host_info(pool_pages=4, page_bytes=4096)   # different geometry
    b.update_gauges(queue_depth=0, running=0, kv_used_pages=0,
                    kv_occupancy=0.0, host_pages_used=1,
                    host_occupancy=0.25)
    off = ServingMetrics(name="off")       # spill-off replica
    m = ServingMetrics.merge(a, b, off)
    snap = m.snapshot()
    assert snap["host_pool_pages"] == 12
    assert snap["host_pool_bytes"] == 8 * 2048 + 4 * 4096
    assert snap["host_pages_used"] == 4
    assert snap["host_occupancy"] == round(4 / 12, 4)
    assert snap["host_page_bytes"] == 0    # mixed geometry -> sentinel
    text = m.prometheus_text()
    assert parse_exposition_names(text) == expected_names(snap)
    assert f"{PREFIX}_host_page_bytes 0" in text
    assert f"{PREFIX}_host_pool_pages 12" in text
    # homogeneous-geometry merge keeps the singleton (off replicas are
    # excluded from the set, so they cannot force the sentinel)
    h = ServingMetrics.merge(a, populated_metrics(), off)
    assert h.snapshot()["host_page_bytes"] == 2048


# ------------------------------------------------------------- format
def test_exposition_format_and_labels():
    lines = prometheus_lines({"a_count": 3, "rate": 0.5, "kind": "x y"},
                             counter_keys={"a_count"}, prefix="p",
                             labels={"replica": "r-0"})
    text = "\n".join(lines)
    assert '# TYPE p_a_count counter' in text
    assert 'p_a_count{replica="r-0"} 3' in text
    assert 'p_rate{replica="r-0"} 0.5' in text
    assert 'p_kind_info{kind="x y",replica="r-0"} 1' in text
    # every sample line parses
    parse_exposition_names(text)
    # None values are omitted, not rendered as "None"
    assert prometheus_lines({"x": None}) == []
    # malformed lines raise in the parser (the format sanity net)
    with pytest.raises(ValueError):
        parse_exposition_names("not a metric line")


def test_render_prometheus_dict_values():
    text = render_prometheus(
        {"replica_states": {"r-0": "healthy", "r-1": "dead"}},
        prefix="p")
    assert 'p_replica_states{replica_state="r-0",value="healthy"} 1' \
        in text
    assert 'p_replica_states{replica_state="r-1",value="dead"} 1' in text


# ----------------------------------------------------- fleet exposition
@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    paddle.seed(0)
    return LlamaForCausalLM(cfg)


KW = dict(num_pages=40, page_size=8, token_budget=48,
          batch_buckets=[8], prefill_buckets=[32], pages_buckets=[8],
          temperature=0.0)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def test_fleet_exposition_per_replica_labels_and_slo_burn(model):
    clock = FakeClock()
    engines = [ServingEngine(model, clock=clock, **KW) for _ in range(2)]
    fleet = Fleet(engines, router=PrefixAffinityRouter(), clock=clock)
    # the FakeClock advances 1ms per observation, so a 1µs TTFT target
    # is guaranteed-violated while a generous TPOT target is met
    for i in range(3):
        fleet.submit([1 + i, 2, 3, 4], max_new_tokens=4,
                     ttft_slo_s=1e-6, tpot_slo_s=100.0)
    fleet.run()
    assert fleet.counters["slo_ttft_violations"] == 3
    assert fleet.counters["slo_tpot_violations"] == 0
    text = fleet.prometheus_text()
    parse_exposition_names(text)           # every line parses
    # fleet counters surface (typed counter) with the merged block
    assert f"# TYPE {PREFIX}_fleet_slo_ttft_violations counter" in text
    assert f"{PREFIX}_fleet_slo_ttft_violations 3" in text
    # per-replica labeled series for BOTH replicas + liveness gauges
    for name in ("replica-0", "replica-1"):
        assert f'{PREFIX}_replica_up{{replica="{name}"}} 1' in text
        assert f'{PREFIX}_engine_steps{{replica="{name}"}} ' in text
    # replica states render as labeled info lines via summary()
    assert f'{PREFIX}_replica_states' in text
    # exposition derives from snapshot(): merged sample == snapshot value
    snap = fleet.summary()
    assert f"{PREFIX}_requests_added {snap['requests_added']}" in text
    fleet.shutdown()


def test_server_metrics_text_hook(model):
    """FleetServer.metrics_text — the scrape body the future HTTP
    transport mounts; callable without an event loop."""
    from paddle_tpu.serving import FleetServer
    eng = ServingEngine(model, **KW)
    fleet = Fleet([eng])
    server = FleetServer(fleet)
    text = server.metrics_text()
    assert text == fleet.prometheus_text()
    parse_exposition_names(text)
    fleet.shutdown()
