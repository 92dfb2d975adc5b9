"""bench_ops.py timing-harness hardening (VERDICT r5 #7, the off-chip
half): median-of-k with a spread column, auto-rerun on noisy samples,
the int8-vs-bf16 decision sweep rows, and the --help contract — all
with the device timing backend MOCKED so the logic is provable on CPU
without a chip."""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest


def _load_bench_ops():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_ops", os.path.join(root, "bench_ops.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def bench_ops():
    mod = _load_bench_ops()
    mod.RESULTS.clear()
    mod.TIMING.update(k=3, spread_pct=20.0, max_reruns=2)
    # the harness runs with a mocked timer on the "cpu" shape set, so it
    # mocks the peaks too: the real table has no "cpu" row and raises
    mod._peaks = lambda device_kind: (1e12, 100e9)
    return mod


def _feed(bench_ops, samples):
    it = iter(samples)
    bench_ops._device_time = lambda fn, *a, **k: next(it)
    return it


def test_median_of_k_and_spread(bench_ops):
    _feed(bench_ops, [1.0, 1.1, 0.95])
    med, spread = bench_ops._time_stats(lambda: None)
    assert med == 1.0
    assert spread == pytest.approx(0.15)     # (1.1-0.95)/1.0, no rerun


def test_auto_rerun_clears_a_one_shot_hiccup(bench_ops):
    # round 1 wildly noisy (host hiccup), round 2 re-draws tight: the
    # median is over ALL collected samples, but the spread that decides
    # rerun/noisy is over the FRESHEST k — a single hiccup must be
    # clearable, or the threshold would be unsatisfiable forever
    calls = []

    def fake(fn, *a, **k):
        calls.append(1)
        return [1.0, 5.0, 1.02, 1.01, 1.0, 0.99][len(calls) - 1]

    bench_ops._device_time = fake
    med, spread = bench_ops._time_stats(lambda: None)
    assert len(calls) == 6                   # one rerun round triggered
    assert med == pytest.approx(np.median([1.0, 5.0, 1.02, 1.01, 1.0, 0.99]))
    rec = bench_ops._record("b", "v", "s", (med, spread), device_kind="cpu")
    assert "noisy" not in rec and rec["spread_pct"] < 20


def test_rerun_budget_is_bounded(bench_ops):
    _feed(bench_ops, [1.0, 9.0] * 100)       # never converges
    med, spread = bench_ops._time_stats(lambda: None)
    # k=3 initial + 2 rerun rounds of 3 = 9 draws, then give up
    assert med > 0 and spread > 0.2


def test_nan_sentinel_poisons_sample(bench_ops):
    _feed(bench_ops, [1.0, float("nan"), 1.0])
    med, spread = bench_ops._time_stats(lambda: None)
    assert med != med                        # NaN
    rec = bench_ops._record("b", "v", "s", (med, spread), device_kind="cpu")
    assert rec["ms"] is None and "unresolved" in rec["note"]


def test_record_spread_column_and_stable_row(bench_ops):
    rec = bench_ops._record("b", "v", "s", (1e-3, 0.05),
                            bytes_moved=1e6, device_kind="cpu")
    assert rec["spread_pct"] == 5.0 and "noisy" not in rec
    assert rec["gbps"] == 1.0


def test_int8_decision_sweep_rows(bench_ops):
    """The M in {1, 32, 256} sweep emits int8+bf16+speedup rows per M
    (timing mocked: int8 'faster' at M=1, slower at M=256)."""
    times = {1: {"int8": 1e-3, "bf16": 2e-3},
             32: {"int8": 1.5e-3, "bf16": 1.6e-3},
             256: {"int8": 4e-3, "bf16": 3e-3}}
    state = {"m": None, "which": None}

    def fake_stats(fn, *args, iters=10):
        m = args[0].shape[0]
        state["which"] = "bf16" if state["which"] == "int8" else "int8"
        return times[m][state["which"]], 0.01

    bench_ops._time_stats = fake_stats
    bench_ops.bench_int8_matmul("cpu", quick=True)
    rows = [r for r in bench_ops.RESULTS
            if r["bench"] == "weight_only_matmul"]
    shapes = [r.get("shape") for r in rows if "shape" in r]
    assert {"1x256x256", "32x256x256", "256x256x256"} <= set(shapes)
    decisions = {r["variant"]: r["value"] for r in rows if "value" in r}
    assert decisions["int8_speedup_pct_m1"] == 50.0
    assert decisions["int8_speedup_pct_m256"] < 0      # bf16 wins big-M


def test_int8_kv_paged_rows(bench_ops):
    """The paged-decode bench emits a bf16 row, an int8 row and the
    bytes-ratio decision row per page size (ISSUE 6); the static ratio
    must clear the >= ~1.7x acceptance bar (exactly 2D/(D+4) — the
    fp32 scale rows are the gap to 2.0). Timing mocked; the kernels
    themselves run for real in interpret mode."""
    bench_ops._time_stats = lambda fn, *a, iters=10: (1e-3, 0.01)
    bench_ops.bench_paged_decode("cpu", quick=True)
    rows = [r for r in bench_ops.RESULTS if r["bench"] == "paged_decode"]
    variants = {r["variant"] for r in rows}
    assert {"pallas_page16", "pallas_int8_page16",
            "int8_kv_bytes_ratio_page16",
            "int8_decode_speedup_pct_page16"} <= variants
    ratio = next(r["value"] for r in rows
                 if r["variant"] == "int8_kv_bytes_ratio_page16")
    D = 64                                   # the CPU bench's head_dim
    assert ratio == pytest.approx(2 * D / (D + 4), abs=5e-3)
    assert ratio >= 1.7
    bf16 = next(r for r in rows if r["variant"] == "pallas_page16")
    int8 = next(r for r in rows if r["variant"] == "pallas_int8_page16")
    # same mocked time, int8 moves fewer bytes -> lower reported GB/s
    assert int8["gbps"] < bf16["gbps"]


def test_multi_decode_rows_and_default_k(bench_ops):
    """The multi-step decode bench (ISSUE 13) emits a bytes-true row,
    a tok/s row and an amortization row per K in {1, 4, 8, 16}, plus
    the default_k decision row. Timing mocked with a fixed per-launch
    overhead + per-step cost, so amortization and the K choice are
    deterministic: overhead 1 ms / step 1 ms -> K=16 wins."""
    times = {K: 1e-3 + K * 1e-3 for K in (1, 4, 8, 16)}
    seen = []

    def fake_stats(fn, *args, iters=10):
        K = (1, 4, 8, 16)[len(seen)]
        seen.append(K)
        return times[K], 0.01

    bench_ops._time_stats = fake_stats
    bench_ops.bench_multi_decode("cpu", quick=True)
    rows = [r for r in bench_ops.RESULTS if r["bench"] == "multi_decode"]
    variants = {r["variant"] for r in rows}
    assert {"k1", "k4", "k8", "k16", "tok_s_k1", "tok_s_k16",
            "amortization_pct_k4", "amortization_pct_k16",
            "default_k"} <= variants
    vals = {r["variant"]: r.get("value") for r in rows if "value" in r}
    # overhead 1 ms amortized: 4 launches @2ms -> one 5ms launch
    assert vals["amortization_pct_k4"] == pytest.approx(
        100 * (4 * 2e-3 - 5e-3) / (4 * 2e-3))
    assert vals["default_k"] == 16           # best tok/s under the mock
    # tok/s = B * K / dt with the CPU bench's B=2
    assert vals["tok_s_k1"] == pytest.approx(2 * 1 / 2e-3, rel=1e-3)
    # bytes-true: the K row's bytes grow superlinearly in K (prefix
    # grows per step), so bandwidth at equal per-step time grows with
    # K (hbm_frac carries 4 decimals; gbps rounds to 1)
    k1 = next(r for r in rows if r["variant"] == "k1")
    k16 = next(r for r in rows if r["variant"] == "k16")
    assert k16["hbm_frac"] > k1["hbm_frac"]


def test_lora_matmul_rows_and_decision(bench_ops):
    """The ISSUE-15 bench: one bytes-true row per (N_adapters, rank)
    in {1,4,16} x {8,16,64} plus an `n_adapter_vs_solo_pct` decision
    row per rank. Timing mocked with a mild per-adapter slope so the
    decision value is deterministic: t(N) = 1 + 0.02*N ms ->
    100 * 1.02/1.32 = 77.27 (clears the >= 70 acceptance bar). The
    kernels themselves execute for real in interpret mode underneath
    the jit the bench builds."""
    import jax

    def fake_stats(fn, *args, iters=10):
        # mocked TIME, real EXECUTION: the jitted masked kernel runs
        # once per variant so a broken lowering cannot hide behind the
        # mock (the bench_paged_decode_tp convention)
        out = jax.block_until_ready(fn(*args))
        assert out.shape == (args[0].shape[0], args[3].shape[2])
        na = args[2].shape[0] - 1      # slot-stack size minus null slot
        return (1e-3 + na * 2e-5, 0.01)

    bench_ops._time_stats = fake_stats
    bench_ops.bench_lora_matmul("cpu", quick=True)
    rows = [r for r in bench_ops.RESULTS if r["bench"] == "lora_matmul"]
    variants = {r["variant"] for r in rows}
    for R in (8, 16, 64):
        for NA in (1, 4, 16):
            assert f"pallas_n{NA}_r{R}" in variants, variants
    decisions = {r["variant"]: r["value"] for r in rows if "value" in r}
    for R in (8, 16, 64):
        assert decisions[f"n_adapter_vs_solo_pct_r{R}"] == \
            pytest.approx(100 * 1.02 / 1.32, abs=0.01)
        assert decisions[f"n_adapter_vs_solo_pct_r{R}"] >= 70
    # bytes-true: at equal mocked N_adapters, the rank-64 row moves
    # more weight bytes than rank-8 -> higher reported GB/s
    r8 = next(r for r in rows if r["variant"] == "pallas_n16_r8")
    r64 = next(r for r in rows if r["variant"] == "pallas_n16_r64")
    assert r64["gbps"] > r8["gbps"]


def test_lora_matmul_nan_sentinel_skips_decision(bench_ops):
    bench_ops._time_stats = \
        lambda fn, *a, iters=10: (float("nan"), float("nan"))
    bench_ops.bench_lora_matmul("cpu", quick=True)
    rows = [r for r in bench_ops.RESULTS if r["bench"] == "lora_matmul"]
    assert rows and not any("value" in r for r in rows)


def test_tp_paged_rows_bytes_per_chip(bench_ops):
    """The sharded paged-decode bench (ISSUE 8) emits one row per TP
    degree with BYTES-TRUE per-chip traffic — global KV bytes / tp
    through the paged_page_bytes source — so at a mocked equal step
    time the reported per-chip GB/s halves from tp1 to tp2 and
    quarters at tp4. Runs on the 8-virtual-device conftest mesh; the
    GSPMD lowering itself is exercised for real (timing mocked)."""
    import jax
    from paddle_tpu.kernels.paged_attention import paged_page_bytes
    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-device test mesh")

    # mocked step TIME (small enough that the 1-decimal GB/s rounding
    # in _record cannot mask the per-chip ratio) — but execute each
    # jitted candidate ONCE so the GSPMD TP lowering really runs; a
    # broken mesh/in-spec would otherwise only surface on chip
    def fake_stats(fn, *a, iters=10):
        out = jax.block_until_ready(fn(*a))
        assert out.shape == (2, 8, 64)       # (B, H, D), CPU geometry
        return (1e-5, 0.01)

    bench_ops._time_stats = fake_stats
    bench_ops.bench_paged_decode_tp("cpu", quick=True)
    rows = [r for r in bench_ops.RESULTS
            if r["bench"] == "paged_decode_tp"]
    variants = {r["variant"] for r in rows}
    assert {"tp1_page8", "tp2_page8", "tp4_page8"} <= variants
    by_tp = {t: next(r for r in rows if r["variant"] == f"tp{t}_page8")
             for t in (1, 2, 4)}
    # CPU bench geometry: B=2, S=64, KVH=4, D=64
    global_bytes = 2 * 64 * paged_page_bytes(4, 1, 64)
    per_chip = {r["variant"]: r["value"] for r in rows if "value" in r}
    assert per_chip["tp1_bytes_per_chip"] == global_bytes
    assert per_chip["tp2_bytes_per_chip"] == global_bytes // 2
    assert per_chip["tp4_bytes_per_chip"] == global_bytes // 4
    assert by_tp[2]["gbps"] == pytest.approx(by_tp[1]["gbps"] / 2,
                                             abs=0.11)
    assert by_tp[4]["gbps"] == pytest.approx(by_tp[1]["gbps"] / 4,
                                             abs=0.11)


def test_tp_paged_rows_skip_without_devices(bench_ops):
    """Degrees beyond the device count emit an explicit skip row, not
    silent absence."""
    import jax
    real = jax.devices
    jax.devices = lambda: real()[:1]
    try:
        bench_ops._time_stats = lambda fn, *a, iters=10: (1e-3, 0.01)
        bench_ops.bench_paged_decode_tp("cpu", quick=True)
    finally:
        jax.devices = real
    rows = [r for r in bench_ops.RESULTS
            if r["bench"] == "paged_decode_tp"]
    notes = [r for r in rows if "note" in r]
    assert {r["variant"] for r in notes} == {"tp2", "tp4"}
    assert all("skipped" in r["note"] for r in notes)


def test_help_documents_median_spread_mode():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "bench_ops.py"), "--help"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0
    help_text = out.stdout
    assert "median" in help_text and "--spread-pct" in help_text
    assert "--max-reruns" in help_text and "-k" in help_text


def test_optimizer_update_rows_and_decisions(bench_ops):
    """The ISSUE-9 optimizer bench: one bytes-true row per state recipe
    (fp32 moments / bf16 moments / fused pallas), a projected-608M row
    each, the static bf16 bytes ratio, and the fused-vs-XLA decision
    row. Timing mocked so the contract is provable on CPU: with the
    fused path measured faster, its GB/s must come out >= the unfused
    row's (the acceptance bar for the chip window)."""
    times = iter([3e-3,     # xla_fp32_moments
                  2.2e-3,   # xla_bf16_moments
                  2.0e-3])  # fused_pallas_bf16_moments

    bench_ops._time_stats = lambda fn, *a, iters=10: (next(times), 0.01)
    bench_ops.bench_optimizer_update("cpu", quick=True)
    rows = [r for r in bench_ops.RESULTS if r["bench"] == "optimizer_update"]
    timed = {r["variant"]: r for r in rows if "ms" in r}
    assert set(timed) == {"xla_fp32_moments", "xla_bf16_moments",
                          "fused_pallas_bf16_moments"}
    decisions = {r["variant"]: r["value"] for r in rows if "value" in r}
    # bytes-true: bf16 moments move 20 B/elem vs 28 B/elem fp32 (master
    # recipe) -> static ratio 1.4 exactly
    assert decisions["bf16_state_bytes_ratio"] == 1.4
    # measured decision row: (2.2 - 2.0) / 2.2
    assert decisions["fused_vs_xla_speedup_pct"] == pytest.approx(9.09,
                                                                  abs=0.01)
    # the fused row must report >= the unfused GB/s (same bytes, less
    # time) — the bench_ops acceptance contract for this PR
    assert timed["fused_pallas_bf16_moments"]["gbps"] >= \
        timed["xla_bf16_moments"]["gbps"]
    # projected flagship rows exist for every recipe and scale with GB/s
    proj = {k: v for k, v in decisions.items()
            if k.startswith("projected_608M_ms_")}
    assert len(proj) == 3
    assert proj["projected_608M_ms_fused_pallas_bf16_moments"] < \
        proj["projected_608M_ms_xla_fp32_moments"]


def test_kv_spill_rows_and_promote_decision(bench_ops):
    """The ISSUE-17 promotion bench: one bytes-true host->device row
    per page in {64, 128} x {bf16, int8} (int8 rides its fp32 scale
    rows, so its payload is smaller but not half) plus the
    promote_vs_recompute projection row. Timing mocked at a fixed
    0.1 ms (coarse enough that the 1-decimal GB/s rounding keeps the
    payload-size ordering visible) — but each promote closure executes
    ONCE inside the mock so the codec round trip and the .at[].set
    commit really run (the bench_paged_decode_tp convention): the
    fetched element must be nonzero (the page landed) and the decode
    must not raise."""
    def fake_stats(fn, *args, iters=10, timer=None):
        assert timer is bench_ops._host_time     # transfer-path timer
        val = fn()                               # real execution
        assert float(val) != 0.0
        return (1e-4, 0.01)

    bench_ops._time_stats = fake_stats
    bench_ops.bench_kv_spill("cpu", quick=True)
    rows = [r for r in bench_ops.RESULTS if r["bench"] == "kv_spill"]
    variants = {r["variant"] for r in rows}
    for page in (64, 128):
        for dtype in ("bf16", "int8"):
            assert f"promote_{dtype}_page{page}" in variants, variants
    by = {r["variant"]: r for r in rows if "ms" in r}
    # bytes-true: CPU geometry L=2, KVH=2, D=64; bf16 payload =
    # 2L * page*KVH*D * 2B, int8 adds (page, KVH) fp32 scales per array
    bf = by["promote_bf16_page128"]
    i8 = by["promote_int8_page128"]
    assert bf["gbps"] == pytest.approx(
        4 * 128 * 2 * 64 * 2 / 1e-4 / 1e9, abs=0.06)
    assert i8["gbps"] < bf["gbps"]               # int8 moves fewer bytes
    assert by["promote_bf16_page64"]["gbps"] < bf["gbps"]  # same mock dt
    # decision row: 7B page bytes / measured rate vs 40%-MFU recompute
    # of 128 tokens on the mocked 1 TFLOP peak — 4.48 s / 12.8 ms = 350.0
    dec = next(r for r in rows if r["variant"] == "promote_vs_recompute")
    assert dec["value"] == pytest.approx(350.0, abs=0.01)


def test_kv_spill_nan_sentinel_skips_decision(bench_ops):
    bench_ops._time_stats = \
        lambda fn, *a, iters=10, timer=None: (float("nan"), float("nan"))
    bench_ops.bench_kv_spill("cpu", quick=True)
    rows = [r for r in bench_ops.RESULTS if r["bench"] == "kv_spill"]
    assert rows and not any("value" in r for r in rows)


def test_optimizer_update_nan_sentinel_skips_decisions(bench_ops):
    """A NaN draw must not fabricate speedup/projection rows."""
    bench_ops._time_stats = \
        lambda fn, *a, iters=10: (float("nan"), float("nan"))
    bench_ops.bench_optimizer_update("cpu", quick=True)
    rows = [r for r in bench_ops.RESULTS if r["bench"] == "optimizer_update"]
    variants = {r["variant"] for r in rows}
    assert "fused_vs_xla_speedup_pct" not in variants
    assert not any(v.startswith("projected_608M") for v in variants)
    # the static bytes ratio is timing-independent and stays
    assert "bf16_state_bytes_ratio" in variants
