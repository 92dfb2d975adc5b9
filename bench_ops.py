"""Op-level microbenchmarks: prove (or disprove) the XLA-fusion story.

VERDICT r2 #2: the A.2 fused-kernel backlog (fused_rope, rms_norm,
swiglu, fused_dropout_add, gemm epilogue — reference
`paddle/phi/kernels/fusion/gpu/`) was covered by "XLA will fuse it" with
zero measurements. This harness measures, on the live chip:

  - Pallas flash attention vs an XLA-composed SDPA (fwd and fwd+bwd)
  - the elementwise/fusion pack (rms_norm[+residual], rope, swiglu,
    fused_dropout_add, bias+gelu epilogue) as achieved HBM bandwidth vs
    the device roofline — a memory-bound op whose XLA composition runs
    near the roofline needs no hand-written kernel (>10% gap = Pallas
    candidate, per the round-3 plan)
  - paged-KV decode attention GB/s vs HBM peak
  - int8 weight-only dequant matmul vs bf16 matmul in the decode regime

Usage: python bench_ops.py [--write-md] [--quick] [-k N] [--spread-pct P]
Prints one JSON line per benchmark; --write-md also rewrites
BENCH_OPS.md. Needs a TPU: without one it exits non-zero and measures
nothing (the bench functions keep a tiny "cpu" shape set only so the
harness tests can drive them with the timer and the peaks mocked).

Timing robustness (VERDICT r5 #7): every number is the MEDIAN of k
(default 3) independent device_time measurements, reported with a
`spread_pct` column ((max-min)/median over the freshest k draws); when
the spread exceeds --spread-pct (default 20%), the sample is
automatically re-measured with k more draws (up to --max-reruns extra
rounds) — the median is then over everything collected, while the
spread tracks the freshest round so a single host hiccup is clearable
and can no longer masquerade as a kernel regression. Rows whose final
spread still exceeds the threshold carry "noisy": true so the table
regeneration can flag them (the rope-row contradiction in BENCH_OPS.md
was exactly such a one-shot artifact).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

RESULTS = []
# timing policy (overridden by CLI flags in main())
TIMING = {"k": 3, "spread_pct": 20.0, "max_reruns": 2}

def _peaks(device_kind):
    """(bf16 FLOP/s, HBM bytes/s) from the tree's one peak table; an
    unknown device raises. Indirection point: the CPU harness tests
    mock THIS name along with the timer."""
    from paddle_tpu.profiler.cost import chip_peaks
    return chip_peaks(device_kind)


def _emit_all(error=None):
    for r in RESULTS:
        print(json.dumps(r), flush=True)
    if error:
        print(json.dumps({"bench": "__status__", "error": error}), flush=True)


def _device_time(fn, *args, iters=10):
    """Dispatch-proof device-side timing; see kernels/timing.py for the
    full methodology (fori_loop chaining, fetch sync, 2N-N
    differencing, NaN sentinel for unresolvably fast ops). Indirection
    point: the CPU harness test monkeypatches THIS name."""
    from paddle_tpu.kernels.timing import device_time
    return device_time(fn, *args, iters=iters)


def _host_time(fn, *args, iters=10):
    """Wall-clock timing for host<->device transfer paths (the tiered-KV
    promote copy), which cannot ride the fori_loop device chain. fn MUST
    end with a host fetch (np.asarray of an element that depends on the
    transfer) — the fetch is the synchronization. Indirection
    point: the CPU harness test monkeypatches THIS name."""
    fn(*args)                                # warm-up (first-touch paths)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args)
    return (time.perf_counter() - t0) / iters


def _time_stats(fn, *args, iters=10, timer=None):
    """Median-of-k timing with spread + auto-rerun (module docstring).

    The median is over EVERY draw collected, but the rerun exit spread
    is over the freshest k only — a single host hiccup in round 1 must
    not make the threshold unsatisfiable (the whole point of rerunning
    is to let tight re-draws clear it). Returns (median_seconds,
    spread_fraction of the freshest k). NaN sentinels from any draw
    poison the whole sample to NaN (an op that sometimes fails to
    resolve is not trustworthy at all). `timer` defaults to the
    device-side chain; transfer benches pass _host_time."""
    samples = []
    rounds = 0
    while True:
        for _ in range(TIMING["k"]):
            dt = (timer or _device_time)(fn, *args, iters=iters)
            if not (dt > 0):
                return float("nan"), float("nan")
            samples.append(dt)
        med = float(np.median(samples))
        fresh = samples[-TIMING["k"]:]
        spread = (max(fresh) - min(fresh)) / med if med > 0 else 0.0
        if spread * 100.0 <= TIMING["spread_pct"] or \
                rounds >= TIMING["max_reruns"]:
            return med, spread
        rounds += 1


def _record(name, variant, shape, dt, flops=None, bytes_moved=None,
            device_kind="?", spread=None):
    fpeak, bpeak = _peaks(device_kind)
    if isinstance(dt, tuple):       # (median, spread) from _time_stats
        dt, spread = dt
    if not (dt > 0):        # NaN sentinel from _time_stats
        rec = {"bench": name, "variant": variant, "shape": shape,
               "ms": None, "device": device_kind,
               "note": "unresolved: 2N-N delta <= 0 at the loop cap"}
        RESULTS.append(rec)
        return rec
    rec = {"bench": name, "variant": variant, "shape": shape,
           "ms": round(dt * 1e3, 4), "device": device_kind}
    if spread is not None and spread == spread:
        rec["spread_pct"] = round(spread * 100.0, 1)
        if spread * 100.0 > TIMING["spread_pct"]:
            rec["noisy"] = True     # still unstable after the reruns
    if flops:
        rec["tflops"] = round(flops / dt / 1e12, 2)
        rec["mfu"] = round(flops / dt / fpeak, 4)
    if bytes_moved:
        rec["gbps"] = round(bytes_moved / dt / 1e9, 1)
        rec["hbm_frac"] = round(bytes_moved / dt / bpeak, 4)
    RESULTS.append(rec)
    return rec


# ---------------------------------------------------------------- benches
def bench_flash_vs_sdpa(dev, quick):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import flash_attention_bshd

    def xla_sdpa(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, v.dtype.type(1) * k) \
            * (1.0 / np.sqrt(q.shape[-1]))
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask, s, -1e9)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    if dev == "cpu":          # interpret-mode Pallas: harness check only
        shapes = [(1, 256, 2, 64)]
    elif quick:
        shapes = [(4, 2048, 16, 64)]
    else:
        shapes = [(4, 2048, 16, 64), (1, 8192, 16, 64)]
    for B, S, H, D in shapes:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
        flops_fwd = 4.0 * B * H * S * S * D * 0.5  # causal halves the work
        flash = jax.jit(lambda q, k, v: flash_attention_bshd(
            q, k, v, causal=True))
        sdpa = jax.jit(xla_sdpa)
        for variant, fn in [("pallas_flash", flash), ("xla_sdpa", sdpa)]:
            dt = _time_stats(fn, q, k, v)
            _record("attention_fwd", variant, f"b{B}s{S}h{H}d{D}", dt,
                    flops=flops_fwd, device_kind=dev)
        # fwd+bwd
        for variant, fn in [("pallas_flash", flash), ("xla_sdpa", sdpa)]:
            g = jax.jit(jax.grad(lambda q, k, v: fn(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)))
            dt = _time_stats(g, q, k, v)
            _record("attention_fwdbwd", variant, f"b{B}s{S}h{H}d{D}", dt,
                    flops=flops_fwd * 3.5, device_kind=dev)


def bench_fusion_pack(dev, quick):
    """The A.2 backlog as roofline fractions: each op is memory-bound;
    bytes = reads + writes of the major arrays (bf16)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn.functional import (
        fused_rms_norm, fused_rotary_position_embedding, swiglu,
        fused_dropout_add)

    if dev == "cpu":
        B, S, Hd = (1, 256, 512)
    else:
        B, S, Hd = (4, 2048, 4096) if quick else (8, 4096, 4096)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S, Hd), jnp.bfloat16)
    res = jnp.asarray(rng.randn(B, S, Hd), jnp.bfloat16)
    w = jnp.asarray(rng.randn(Hd), jnp.bfloat16)
    nbytes = x.size * 2

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    def t(a):
        return Tensor(a)

    # no-residual fused_rms_norm returns a single Tensor (no [0]!
    # after the arity fix a [0] would batch-slice and let XLA DCE
    # 7/8 of the work)
    rms = jax.jit(lambda a: fused_rms_norm(t(a), t(w))._data)
    _record("rms_norm", "xla_fused", f"{B}x{S}x{Hd}",
            _time_stats(rms, x), bytes_moved=2 * nbytes, device_kind=dev)
    # the Pallas counterpart (kernels/fused_norm.py), same wall-clock
    # harness as the xla_fused row above so the two are comparable —
    # kept so every table regeneration re-checks the A.2 call (on-chip
    # verdict: XLA at least matches Pallas for rms_norm at every shape
    # tried, so the model keeps the XLA composition)
    from paddle_tpu.kernels.fused_norm import rms_norm_rows
    rms_pl = jax.jit(lambda a: rms_norm_rows(
        a.reshape(-1, Hd), w.astype(a.dtype)).reshape(a.shape))
    _record("rms_norm", "pallas", f"{B}x{S}x{Hd}",
            _time_stats(rms_pl, x), bytes_moved=2 * nbytes, device_kind=dev)

    rms_res = jax.jit(
        lambda a, r: fused_rms_norm(t(a), t(w), residual=t(r))[0]._data)
    _record("rms_norm_residual", "xla_fused", f"{B}x{S}x{Hd}",
            _time_stats(rms_res, x, res), bytes_moved=3 * nbytes,
            device_kind=dev)

    # rope on (B, S, H, D)
    H, D = (4, 64) if dev == "cpu" else (32, 128)
    qk = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
    pos = jnp.arange(S)
    inv = 1.0 / (10000 ** (jnp.arange(0, D, 2) / D))
    ang = pos[:, None] * inv[None, :]
    sin = jnp.sin(ang).astype(jnp.bfloat16)[None, :, None, :]
    cos = jnp.cos(ang).astype(jnp.bfloat16)[None, :, None, :]
    def _rope_call(a):
        out = fused_rotary_position_embedding(t(a), sin=t(sin), cos=t(cos))
        return (out[0] if isinstance(out, (tuple, list)) else out)._data

    rope = jax.jit(_rope_call)
    _record("rope", "xla_fused", f"{B}x{S}x{H}x{D}",
            _time_stats(rope, qk), bytes_moved=2 * qk.size * 2,
            device_kind=dev)

    inter = 512 if dev == "cpu" else (11008 if not quick else 4096)
    g1 = jnp.asarray(rng.randn(B * S // 4, inter), jnp.bfloat16)
    g2 = jnp.asarray(rng.randn(B * S // 4, inter), jnp.bfloat16)
    sw = jax.jit(lambda a, b: swiglu(t(a), t(b))._data)
    _record("swiglu", "xla_fused", f"{B * S // 4}x{inter}",
            _time_stats(sw, g1, g2), bytes_moved=3 * g1.size * 2,
            device_kind=dev)

    da = jax.jit(lambda a, b: fused_dropout_add(t(a), t(b), p=0.0,
                                                training=False)._data)
    _record("dropout_add", "xla_fused", f"{B}x{S}x{Hd}",
            _time_stats(da, x, res), bytes_moved=3 * nbytes, device_kind=dev)

    # gemm epilogue: matmul + bias + gelu fused by XLA — compute-bound
    if dev == "cpu":
        M, K, N = (256, 256, 256)
    else:
        M, K, N = (4096, 4096, 4096) if not quick else (2048, 2048, 2048)
    a = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
    wt = jnp.asarray(rng.randn(K, N), jnp.bfloat16)
    bias = jnp.asarray(rng.randn(N), jnp.bfloat16)
    ep = jax.jit(lambda a, w_, b_: jax.nn.gelu(a @ w_ + b_))
    plain = jax.jit(lambda a, w_: a @ w_)
    dt_ep, sp_ep = _time_stats(ep, a, wt, bias)
    dt_pl, sp_pl = _time_stats(plain, a, wt)
    _record("gemm_epilogue", "matmul_bias_gelu", f"{M}x{K}x{N}", dt_ep,
            flops=2.0 * M * K * N, device_kind=dev, spread=sp_ep)
    _record("gemm_epilogue", "matmul_only", f"{M}x{K}x{N}", dt_pl,
            flops=2.0 * M * K * N, device_kind=dev, spread=sp_pl)
    if dt_ep > 0 and dt_pl > 0:     # NaN sentinel would poison the JSON
        RESULTS.append({"bench": "gemm_epilogue", "variant": "overhead_pct",
                        "value": round(100 * (dt_ep - dt_pl) / dt_pl, 2),
                        "device": dev})


def bench_paged_decode(dev, quick):
    """bf16 vs int8 KV pages (ISSUE 6): the decode kernel is
    bandwidth-bound at the HBM roofline, so bytes/token IS tokens/s at
    fixed HBM. Each page size gets a bf16 row, an int8 row (quantized
    caches + per-slot scale pages, dequantize-in-kernel), a static
    `int8_kv_bytes_ratio` decision row (bf16/int8 bytes per token —
    the >= ~1.7x acceptance number; < 2.0 exactly because the fp32
    scales ride along), and a measured `int8_decode_speedup_pct` row."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import (
        alloc_paged_cache, paged_attention_decode, paged_page_bytes,
        quantize_kv)

    if dev == "cpu":
        B, KVH, H, D = 2, 2, 4, 64
        pages, S = (16,), 64
    else:
        B, KVH, H, D = 16, 8, 32, 128
        # 16 = vLLM-style small pages (DMA-latency-bound even folded),
        # 128 = TPU-preferred page size (near the big-page roofline)
        pages, S = (16, 128), 1024 if quick else 2048
    rng = np.random.RandomState(0)
    for page in pages:
        pages_per_seq = S // page
        num_pages = B * pages_per_seq
        k_cache, v_cache = alloc_paged_cache(KVH, num_pages, page, D,
                                             dtype=jnp.bfloat16)
        k_cache = jnp.asarray(rng.randn(*k_cache.shape), jnp.bfloat16)
        v_cache = jnp.asarray(rng.randn(*v_cache.shape), jnp.bfloat16)
        bt = jnp.arange(num_pages, dtype=jnp.int32).reshape(
            B, pages_per_seq)
        sl = jnp.full((B,), S, jnp.int32)
        q = jnp.asarray(rng.randn(B, H, D), jnp.bfloat16)
        fn = jax.jit(lambda q, kc, vc, bt=bt, sl=sl: paged_attention_decode(
            q, kc, vc, bt, sl))
        dt_bf = _time_stats(fn, q, k_cache, v_cache)
        # bytes via the capacity math's single source (page_size=1 ==
        # per-token bytes), so the bench can never drift from the
        # engine's accounting if the scale layout changes
        kv_bytes = B * S * paged_page_bytes(KVH, 1, D)        # bf16 K+V
        _record("paged_decode", f"pallas_page{page}",
                f"b{B}s{S}kvh{KVH}h{H}d{D}", dt_bf,
                bytes_moved=kv_bytes, device_kind=dev)

        # int8 image of the SAME cache contents (per-slot quantization)
        kq, ks = quantize_kv(k_cache)
        vq, vs = quantize_kv(v_cache)
        fn_q = jax.jit(
            lambda q, kc, vc, kss, vss, bt=bt, sl=sl:
            paged_attention_decode(q, kc, vc, bt, sl,
                                   k_scale=kss, v_scale=vss))
        dt_i8 = _time_stats(fn_q, q, kq, vq, ks, vs)
        kv_bytes_i8 = B * S * paged_page_bytes(KVH, 1, D, "int8")
        _record("paged_decode", f"pallas_int8_page{page}",
                f"b{B}s{S}kvh{KVH}h{H}d{D}", dt_i8,
                bytes_moved=kv_bytes_i8, device_kind=dev)
        RESULTS.append({
            "bench": "paged_decode",
            "variant": f"int8_kv_bytes_ratio_page{page}",
            "value": round(kv_bytes / kv_bytes_i8, 3),
            "device": dev})
        dt_bf, dt_i8 = dt_bf[0], dt_i8[0]
        if dt_bf > 0 and dt_i8 > 0:
            RESULTS.append({
                "bench": "paged_decode",
                "variant": f"int8_decode_speedup_pct_page{page}",
                "value": round(100 * (dt_bf - dt_i8) / dt_bf, 2),
                "device": dev})


def bench_paged_decode_tp(dev, quick):
    """Sharded paged-decode bandwidth (ISSUE 8): the decode kernel at
    TP in {1, 2, 4} over the hybrid mesh's 'model' axis, reported as
    BYTES-TRUE per-chip GB/s — one step still reads every live token's
    K/V, but the pages are head-sharded so each chip moves
    global_bytes / tp (paged_page_bytes is the bytes source, same as
    the engine's accounting). Degrees beyond the device count (or not
    dividing KVH) emit an explicit skip row instead of silently
    shrinking coverage. On CPU the GSPMD path partitions the
    interpret-mode kernel (the virtual-mesh validation); on TPU the
    shard_map manual path runs the real kernel per shard."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.kernels.paged_attention import (
        paged_attention_decode, paged_attention_decode_tp,
        paged_page_bytes)

    if dev == "cpu":
        B, KVH, H, D, page, S = 2, 4, 8, 64, 8, 64
    else:
        B, KVH, H, D, page, S = 16, 8, 32, 128, 128, 1024 if quick else 2048
    rng = np.random.RandomState(0)
    pages_per_seq = S // page
    num_pages = B * pages_per_seq
    devs = jax.devices()
    kv_bytes_global = B * S * paged_page_bytes(KVH, 1, D)
    for tp in (1, 2, 4):
        if tp > len(devs) or KVH % tp:
            RESULTS.append({
                "bench": "paged_decode_tp", "variant": f"tp{tp}",
                "device": dev,
                "note": f"skipped: {len(devs)} device(s), KVH={KVH}"})
            continue
        k_cache = jnp.asarray(
            rng.randn(num_pages, KVH, page, D), jnp.bfloat16)
        v_cache = jnp.asarray(
            rng.randn(num_pages, KVH, page, D), jnp.bfloat16)
        q = jnp.asarray(rng.randn(B, H, D), jnp.bfloat16)
        bt = jnp.arange(num_pages, dtype=jnp.int32).reshape(
            B, pages_per_seq)
        sl = jnp.full((B,), S, jnp.int32)
        if tp == 1:
            fn = jax.jit(lambda q, kc, vc, bt=bt, sl=sl:
                         paged_attention_decode(q, kc, vc, bt, sl))
        else:
            mesh = Mesh(np.asarray(devs[:tp], dtype=object).reshape(
                1, 1, 1, 1, tp),
                ("data", "pipe", "sharding", "sep", "model"))
            shard = NamedSharding(mesh, P(None, "model", None, None))
            k_cache = jax.device_put(k_cache, shard)
            v_cache = jax.device_put(v_cache, shard)
            q = jax.device_put(
                q, NamedSharding(mesh, P(None, "model", None)))
            fn = jax.jit(lambda q, kc, vc, bt=bt, sl=sl, mesh=mesh:
                         paged_attention_decode_tp(q, kc, vc, bt, sl,
                                                   mesh))
        dt = _time_stats(fn, q, k_cache, v_cache)
        # bytes-true per-chip traffic: head-sharded pages split the
        # global K/V read exactly by tp
        per_chip = kv_bytes_global // tp
        _record("paged_decode_tp", f"tp{tp}_page{page}",
                f"b{B}s{S}kvh{KVH}h{H}d{D}", dt,
                bytes_moved=per_chip, device_kind=dev)
        RESULTS.append({
            "bench": "paged_decode_tp",
            "variant": f"tp{tp}_bytes_per_chip",
            "value": per_chip, "device": dev})


def bench_multi_decode(dev, quick):
    """Multi-step device-side decode (ISSUE 13): K decode iterations of
    a small Llama inside ONE compiled launch (`models/paged.py`
    `decode_multi` — in-graph sampling, per-step paged cache writes through the scan
    carry) vs K single-step launches. Rows per K in {1, 4, 8, 16}:
    wall ms, BYTES-TRUE KV GB/s (each step reads the then-current
    prefix and writes one token — paged_page_bytes is the accounting
    source, same as the engine's), derived tokens/s, and an
    `amortization_pct` row = how much of K single-step launches the
    K-step launch saves (host launch overhead + per-launch readback
    amortized xK). A `default_k` decision row picks the measured-best
    K as the candidate engine default."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.api import functional_call
    from paddle_tpu.kernels.paged_attention import (alloc_paged_cache,
                                                    paged_page_bytes)
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.paged import decode_multi

    if dev == "cpu":
        B, S, page = 2, 48, 8
        cfg = LlamaConfig(vocab_size=128, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=128)
    else:
        # quick halves the model depth and prefix length like the
        # sibling benches — 4 multi-decode jit compiles are the cost
        B, S, page = 8, (512 if quick else 1024), 128
        cfg = LlamaConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=2816,
                          num_hidden_layers=4 if quick else 8,
                          num_attention_heads=16, num_key_value_heads=8,
                          max_position_embeddings=4096)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if dev != "cpu":
        model.bfloat16()
    state = {k: t._data for k, t in model.state_dict().items()}
    wdtype = next(a.dtype for a in state.values()
                  if jnp.issubdtype(a.dtype, jnp.floating))
    D = cfg.hidden_size // cfg.num_attention_heads
    KVH = cfg.num_key_value_heads
    ks = (1, 4, 8, 16)
    # room for S prefix tokens + the largest K per row, plus pad page 0
    pages_per_seq = -(-(S + max(ks)) // page)
    num_pages = B * pages_per_seq + 1
    rng = np.random.RandomState(0)
    caches = [tuple(jnp.asarray(rng.randn(*a.shape) * 0.1, a.dtype)
                    for a in alloc_paged_cache(KVH, num_pages, page, D,
                                               dtype=wdtype))
              for _ in range(cfg.num_hidden_layers)]
    flat0 = [a for kv in caches for a in kv]
    arity = len(caches[0])
    bt = jnp.asarray(
        1 + np.arange(B * pages_per_seq, dtype=np.int32).reshape(
            B, pages_per_seq))
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B,)), jnp.int32)
    sl = jnp.full((B,), S, jnp.int32)
    eos = jnp.full((B,), -1, jnp.int32)
    key = jax.random.PRNGKey(0)
    kv_tok = paged_page_bytes(KVH, 1, D, str(wdtype)) \
        * cfg.num_hidden_layers

    # device_time spreads *args as plain arrays: state and caches ride
    # flattened positionally (a closure would bake ~100 MB of weights
    # into the program as literals)
    state_keys = sorted(state)
    sargs = [state[k] for k in state_keys]

    def make(K):
        caps = jnp.full((B,), K, jnp.int32)

        def prog(ids_a, sl_a, key_a, *rest):
            sv, flat = rest[:len(state_keys)], rest[len(state_keys):]
            st = {k: Tensor(v) for k, v in zip(state_keys, sv)}
            pc = [tuple(Tensor(a)
                        for a in flat[i * arity:(i + 1) * arity])
                  for i in range(cfg.num_hidden_layers)]
            with no_grad():
                toks, n_emit, ok, _, _ = functional_call(
                    model, st, Tensor(ids_a), pc, Tensor(bt),
                    Tensor(sl_a), Tensor(caps), Tensor(eos), key_a,
                    method=decode_multi, k_steps=K)
            return toks._data, n_emit._data, ok._data

        return jax.jit(prog)

    shape = (f"b{B}s{S}l{cfg.num_hidden_layers}h{cfg.hidden_size}"
             f"page{page}")
    times = {}
    for K in ks:
        fn = make(K)
        dt = _time_stats(fn, ids, sl, key, *sargs, *flat0)
        # bytes-true per launch: step j reads B rows' (S + j)-token
        # prefix and writes one token per row, scales included
        nbytes = sum(B * (S + j) * kv_tok + B * kv_tok
                     for j in range(K))
        rec = _record("multi_decode", f"k{K}", shape, dt,
                      bytes_moved=nbytes, device_kind=dev)
        times[K] = dt[0]
        if dt[0] > 0:
            RESULTS.append({
                "bench": "multi_decode", "variant": f"tok_s_k{K}",
                "value": round(B * K / dt[0], 1), "device": dev})
    if times.get(1, 0) > 0:
        for K in ks[1:]:
            if times.get(K, 0) > 0:
                # launch-overhead amortization: K single-step launches
                # vs one K-step launch
                save = 100 * (K * times[1] - times[K]) / (K * times[1])
                RESULTS.append({
                    "bench": "multi_decode",
                    "variant": f"amortization_pct_k{K}",
                    "value": round(save, 2), "device": dev})
        best = max((K for K in ks if times.get(K, 0) > 0),
                   key=lambda K: B * K / times[K])
        RESULTS.append({"bench": "multi_decode", "variant": "default_k",
                        "value": best, "device": dev})


def bench_lora_matmul(dev, quick):
    """Multi-LoRA segment-bmm (ISSUE 15): the per-launch adapter-delta
    GEMM at N_adapters in {1, 4, 16} x rank in {8, 16, 64}. Each row's
    slot stack holds the N loaded adapters (+ the null slot), rows
    spread round-robin across them — the masked kernel streams every
    loaded adapter's A/B once per launch, so the N sweep measures
    exactly what serving N adapters costs over serving one. Bytes-true
    via `lora_delta_bytes` (active adapters' weights + x + delta). The
    `n_adapter_vs_solo_pct` decision row per rank = 100 x t(N=1) /
    t(N=16): the ISSUE-15 acceptance bar is >= 70 (the N-adapter step
    at >= 0.7x the single-adapter step). That bar is a CHIP number:
    on CPU the kernel runs in interpret mode, where every extra slot
    adds python-loop grid steps, so the CPU row wildly understates the
    ratio (the engine-level CPU probe in tools/chip_serving.py, which
    measures whole serving steps, lands at ~solo parity) — same
    harness-evidence-only caveat as bench_multi_decode's CPU rows."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.lora_matmul import (lora_delta_bytes,
                                                lora_matmul,
                                                lora_matmul_xla,
                                                pick_lora_blocks)

    B, H, N = (8, 256, 256) if dev == "cpu" else (16, 4096, 4096)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, H), jnp.float32)
    n_adapters = (1, 4, 16)
    ranks = (8, 16, 64)
    times = {}
    for R in ranks:
        for NA in n_adapters:
            S = NA + 1                       # + the null slot
            a = jnp.asarray(rng.randn(S, H, R) * 0.02, jnp.float32)
            b = jnp.asarray(rng.randn(S, R, N) * 0.02, jnp.float32)
            # slot 0 is the all-zero null adapter (the engine contract)
            a = a.at[0].set(0.0)
            b = b.at[0].set(0.0)
            ids = jnp.asarray(1 + np.arange(B) % NA, jnp.int32)
            blocks = pick_lora_blocks(B, H, R, N)
            if blocks is not None:
                fn = jax.jit(lambda xx, ii, aa, bb, _blk=blocks:
                             lora_matmul(xx, ii, aa, bb, blocks=_blk))
                variant = f"pallas_n{NA}_r{R}"
            else:                            # fallback shapes still row
                fn = jax.jit(lora_matmul_xla)
                variant = f"xla_n{NA}_r{R}"
            dt = _time_stats(fn, x, ids, a, b)
            # bytes-true: the masked kernel streams EVERY slot in the
            # stack (null slot included), re-streaming A/x once per
            # output block column — the accounting follows the grid
            bn = blocks[1] if blocks is not None else None
            nbytes = lora_delta_bytes(B, H, R, N, S, bn=bn)
            _record("lora_matmul", variant, f"b{B}x{H}x{N}", dt,
                    bytes_moved=nbytes, device_kind=dev)
            times[(NA, R)] = dt[0]
        t1, t16 = times.get((1, R), 0), times.get((16, R), 0)
        if t1 > 0 and t16 > 0:
            RESULTS.append({
                "bench": "lora_matmul",
                "variant": f"n_adapter_vs_solo_pct_r{R}",
                "value": round(100 * t1 / t16, 2), "device": dev})


def bench_int8_matmul(dev, quick):
    """The int8-vs-bf16 DECISION sweep (VERDICT r5 #7): weight-only
    int8 halves the weight traffic but pays a dequant; whether that
    wins depends on the batch M (decode M=1 is pure weight-bound,
    prefill-sized M amortizes the weights). One row per M plus a
    speedup_pct decision row, so the first live window settles which
    serving regimes should quantize."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.quant import weight_quantize, weight_only_linear
    import paddle_tpu as paddle

    K, N = (256, 256) if dev == "cpu" else (4096, 4096)
    rng = np.random.RandomState(0)
    w = paddle.to_tensor(rng.randn(K, N).astype(np.float32) * 0.02)
    qw, scale = weight_quantize(w, algo="weight_only_int8")
    w_bf = w._data.astype(jnp.bfloat16)

    int8 = jax.jit(lambda xa: weight_only_linear(
        paddle.Tensor(xa), qw, weight_scale=scale,
        weight_dtype="int8")._data)
    bf16 = jax.jit(lambda xa: xa @ w_bf)
    for M in (1, 32, 256):
        x_bf = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
        dt_i8, sp_i8 = _time_stats(int8, x_bf)
        dt_bf, sp_bf = _time_stats(bf16, x_bf)
        _record("weight_only_matmul", "int8", f"{M}x{K}x{N}", dt_i8,
                bytes_moved=K * N, device_kind=dev, spread=sp_i8)
        _record("weight_only_matmul", "bf16", f"{M}x{K}x{N}", dt_bf,
                bytes_moved=K * N * 2, device_kind=dev, spread=sp_bf)
        if dt_i8 > 0 and dt_bf > 0:
            RESULTS.append({
                "bench": "weight_only_matmul",
                "variant": f"int8_speedup_pct_m{M}",
                "value": round(100 * (dt_bf - dt_i8) / dt_bf, 2),
                "device": dev})


def bench_optimizer_update(dev, quick):
    """Bytes-true AdamW update rows (ISSUE 9): the round-4 chip point
    is ~21 ms for 608M fp32 states == the HBM roofline, so the update
    is pure bytes and GB/s IS the metric. One row per state recipe —
    fp32 moments (the round-4 configuration), bf16 moments through the
    per-leaf XLA path, and the fused bucketed Pallas kernel — each
    with bytes from kernels.fused_optimizer.adamw_update_bytes (the
    engine's single accounting source), plus decision rows: the static
    bf16 bytes ratio, the measured fused-vs-XLA speedup, and each
    recipe's projected ms for the 608M-param flagship state at the
    measured GB/s (directly comparable to the 21 ms chip point)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.fused_optimizer import (
        LANES, adamw_scalars, adamw_update_bytes, fused_adamw_bucket)

    rows = 256 if dev == "cpu" else (32768 if quick else 131072)
    E = rows * LANES
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(rows, LANES), jnp.bfloat16)
    w = jnp.asarray(rng.randn(rows, LANES), jnp.float32)   # fp32 master
    scalars = adamw_scalars(3e-4, 0.9, 0.999, 1e-8, 0.01, 100)

    def make(mdtype, use_pallas):
        m = jnp.zeros((rows, LANES), mdtype)
        v = jnp.zeros((rows, LANES), mdtype)
        fn = jax.jit(lambda g, w, m, v: fused_adamw_bucket(
            g, w, m, v, scalars, param_dtype=jnp.bfloat16,
            use_pallas=use_pallas))
        return fn, m, v

    variants = [
        ("xla_fp32_moments", jnp.float32, False),
        ("xla_bf16_moments", jnp.bfloat16, False),
        ("fused_pallas_bf16_moments", jnp.bfloat16, True),
    ]
    times = {}
    for name, mdtype, use_pallas in variants:
        fn, m, v = make(mdtype, use_pallas)
        nbytes = adamw_update_bytes(
            E, param_width=2, moment_width=jnp.dtype(mdtype).itemsize,
            has_master=True)
        dt = _time_stats(fn, g, w, m, v)
        times[name] = (dt[0], nbytes)
        _record("optimizer_update", name, f"{E}elems", dt,
                bytes_moved=nbytes, device_kind=dev)
        if dt[0] > 0:
            # projected flagship time: the 608M-param AdamW state at
            # this recipe's measured GB/s (round-4 chip point: ~21 ms)
            flag_bytes = adamw_update_bytes(
                608_000_000, param_width=2,
                moment_width=jnp.dtype(mdtype).itemsize, has_master=True)
            RESULTS.append({
                "bench": "optimizer_update",
                "variant": f"projected_608M_ms_{name}",
                "value": round(flag_bytes / (nbytes / dt[0]) * 1e3, 2),
                "device": dev})
    b32 = adamw_update_bytes(E, param_width=2, moment_width=4,
                             has_master=True)
    b16 = adamw_update_bytes(E, param_width=2, moment_width=2,
                             has_master=True)
    RESULTS.append({"bench": "optimizer_update",
                    "variant": "bf16_state_bytes_ratio",
                    "value": round(b32 / b16, 3), "device": dev})
    dt_xla = times["xla_bf16_moments"][0]
    dt_fused = times["fused_pallas_bf16_moments"][0]
    if dt_xla > 0 and dt_fused > 0:
        RESULTS.append({"bench": "optimizer_update",
                        "variant": "fused_vs_xla_speedup_pct",
                        "value": round(100 * (dt_xla - dt_fused) / dt_xla, 2),
                        "device": dev})


def bench_kv_spill(dev, quick):
    """Tiered-KV promotion path (ISSUE 17): wall-clock host->device rate
    of the engine's promote copy — CRC-checked payload decode plus one
    `.at[pid].set(jnp.asarray(...))` commit per layer array, ending in
    the single-element fetch that synchronizes — for ONE radix page's full K/V stack at page in {64, 128} x
    {bf16, int8} (int8 rows carry their fp32 scale rows, the engine's
    payload layout). The `promote_vs_recompute` decision row projects
    the measured bf16 page-128 rate onto a 7B-class stack
    (L=32, KVH=8, D=128) against recomputing those 128 tokens of
    prefill at 40% MFU on this chip's peak: value = t_recompute /
    t_promote, > 1 means promotion wins and the spill tier pays."""
    import jax.numpy as jnp
    from paddle_tpu.serving.kv_cache import (decode_page_payload,
                                             encode_page_payload)

    rng = np.random.RandomState(0)
    L, KVH, D = (2, 2, 64) if dev == "cpu" else (4, 8, 128)
    NUM_PAGES = 4
    rates = {}
    for page in (64, 128):
        for dtype in ("bf16", "int8"):
            kvs, scales = [], []
            for _ in range(L):
                if dtype == "int8":
                    kvs.append(rng.randint(
                        -127, 128, (page, KVH, D)).astype(np.int8))
                    kvs.append(rng.randint(
                        -127, 128, (page, KVH, D)).astype(np.int8))
                    scales.append(rng.rand(page, KVH).astype(np.float32))
                    scales.append(rng.rand(page, KVH).astype(np.float32))
                else:
                    kvs.append(rng.randn(page, KVH, D)
                               .astype(jnp.bfloat16))
                    kvs.append(rng.randn(page, KVH, D)
                               .astype(jnp.bfloat16))
            arrays = kvs + scales
            payload = encode_page_payload(arrays)
            nbytes = sum(a.nbytes for a in arrays)
            caches = [jnp.zeros((NUM_PAGES,) + a.shape, a.dtype)
                      for a in arrays]

            def promote(payload=payload, caches=caches):
                arrs = decode_page_payload(payload)
                out = None
                for c, a in zip(caches, arrs):
                    out = c.at[1].set(jnp.asarray(a))
                return np.asarray(out[1].ravel()[0])   # fetch sync

            med, sp = _time_stats(promote, timer=_host_time)
            _record("kv_spill", f"promote_{dtype}_page{page}",
                    f"L{L}x{page}x{KVH}x{D}", (med, sp),
                    bytes_moved=nbytes, device_kind=dev)
            if med > 0:
                rates[(page, dtype)] = nbytes / med
    if (128, "bf16") in rates:
        page_bytes_7b = 32 * 2 * 128 * 8 * 128 * 2     # L*2*P*KVH*D*2B
        t_promote = page_bytes_7b / rates[(128, "bf16")]
        fpeak, _ = _peaks(dev)
        t_recompute = 2 * 7e9 * 128 / (0.4 * fpeak)
        RESULTS.append({"bench": "kv_spill",
                        "variant": "promote_vs_recompute",
                        "value": round(t_recompute / t_promote, 2),
                        "device": dev})


BENCHES = [bench_flash_vs_sdpa, bench_fusion_pack, bench_paged_decode,
           bench_paged_decode_tp, bench_multi_decode, bench_lora_matmul,
           bench_int8_matmul, bench_optimizer_update, bench_kv_spill]


def write_md(path="BENCH_OPS.md"):
    dev = next((r.get("device") for r in RESULTS if r.get("device")), "?")
    lines = [
        "# Op microbenchmarks (bench_ops.py)", "",
        f"Device: **{dev}**. Roofline fractions use bf16 peak FLOP/s and "
        "HBM peak bytes/s for the chip; `hbm_frac` near 1.0 means the "
        "XLA-fused composition saturates memory bandwidth and needs no "
        "hand-written kernel (>10% gap = Pallas candidate).", "",
        f"Timing: median of k={TIMING['k']} device_time draws; "
        "`spread%` = (max-min)/median, auto-rerun above "
        f"{TIMING['spread_pct']}% (bench_ops.py docstring); rows still "
        "noisy after the reruns are marked `!`.", "",
        "| bench | variant | shape | ms | spread% | TFLOP/s | MFU "
        "| GB/s | HBM frac |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in RESULTS:
        if r.get("bench") == "__status__" or "ms" not in r:
            continue
        ms = "unresolved" if r["ms"] is None else r["ms"]
        sp = r.get("spread_pct", "")
        if r.get("noisy"):
            sp = f"{sp} !"
        lines.append(
            f"| {r['bench']} | {r['variant']} | {r.get('shape','')} "
            f"| {ms} | {sp} | {r.get('tflops','')} | {r.get('mfu','')} "
            f"| {r.get('gbps','')} | {r.get('hbm_frac','')} |")
    # decision rows AND skip notes: a degree skipped for lack of
    # devices must be visible in the table regeneration, not silently
    # absent (the bench_paged_decode_tp coverage contract)
    extra = [r for r in RESULTS
             if "value" in r or ("note" in r and "ms" not in r)]
    if extra:
        lines.append("")
        for r in extra:
            lines.append(f"- {r['bench']}/{r['variant']}: "
                         f"{r.get('value', r.get('note'))}")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def _build_parser():
    ap = argparse.ArgumentParser(
        prog="bench_ops.py",
        description="Op-level TPU microbenchmarks. Every number is the "
                    "median of k independent device-side timings with a "
                    "spread percentage column; samples whose spread exceeds "
                    "--spread-pct are automatically re-measured (k more "
                    "draws, up to --max-reruns rounds) before the median "
                    "is taken — see the module docstring.")
    ap.add_argument("--quick", action="store_true",
                    help="smaller shapes / fewer configs")
    ap.add_argument("--write-md", action="store_true",
                    help="rewrite BENCH_OPS.md from the results")
    ap.add_argument("-k", type=int, default=TIMING["k"],
                    help="timing samples per measurement (median-of-k, "
                         "default %(default)s)")
    ap.add_argument("--spread-pct", type=float,
                    default=TIMING["spread_pct"],
                    help="(max-min)/median spread above which a sample "
                         "is re-measured (default %(default)s%%)")
    ap.add_argument("--max-reruns", type=int, default=TIMING["max_reruns"],
                    help="extra measurement rounds before accepting a "
                         "noisy sample (default %(default)s)")
    return ap


def main() -> int:
    args = _build_parser().parse_args()
    TIMING["k"] = max(1, args.k)
    TIMING["spread_pct"] = args.spread_pct
    TIMING["max_reruns"] = max(0, args.max_reruns)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench_ops.py: needs a TPU, JAX found {dev.platform!r}; "
              f"nothing was measured", file=sys.stderr)
        return 1
    failed = False
    for bench in BENCHES:
        try:
            bench(dev.device_kind, args.quick)
        except Exception as e:      # one bench must not hide the others
            failed = True
            RESULTS.append({"bench": bench.__name__,
                            "error": repr(e)[:300]})
    _emit_all()
    if args.write_md and not failed:
        write_md()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
