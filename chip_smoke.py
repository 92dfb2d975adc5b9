"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two main paths once, through the entry points a
user calls, at the full width of models the repo supports (depth is the
only cut; weights and prompts are random, from a seed):

  train  `paddle.jit.to_static(step, state_objects=[model, opt])` over
         `LlamaForCausalLM` at the `LlamaConfig()` default (Llama-2-7B)
         widths, bf16 parameters, AdamW with fp32 master and moments;
  serve  `ServingEngine(model)` at the `llama_3_8b()` widths, a handful
         of requests of mixed prompt length through add_request/step.

and checks what comes out by the repo's own means (see the phase
functions). `--chips 4` runs ONLY the cross-chip phase and what it is
compared with: a TP-4 serving engine against the one-chip engine, and a
dp2 x mp2 hybrid train step against the one-device step.

Contract with the driver: exits non-zero and prints no result when JAX
finds no TPU (nothing runs on the CPU), or when any phase fails; prints
as the LAST line of stdout one JSON object
`{"ok": true, "device": {"platform", "kind", "count"}}`; starts no child
process. The phases are plain functions of their sizes so the CPU
rehearsal (tests/test_chip_smoke.py) calls them at toy width.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time

import numpy as np

# What a Pallas kernel compiled by Mosaic leaves in the optimized HLO. An
# interpret-mode kernel (any backend but tpu) or an XLA stand-in leaves
# none, so a phase that requires it cannot pass off the chip.
KERNEL_MARKER = "tpu_custom_call"

SEED = 0
# Depth cuts, sized from compiled.memory_analysis() of the whole programs
# compiled for a described v5e (16 GB) in the rehearsal — see CHANGES.md.
TRAIN_LAYERS = 2          # of 32: 16 B/param of params+grads+AdamW state
SERVE_LAYERS = 16         # of 32: 9.1 GB of bf16 weights beside the pool
SERVE_POOL_PAGES = 1024   # x page_size 16 = a 16,384-token KV pool


def say(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


# ------------------------------------------------------------------ helpers
def build_model(cfg, dtype):
    """LlamaForCausalLM with parameters CREATED in `dtype` (building in
    float32 and casting after does not fit at these widths)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        return LlamaForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(prev)


def logit_tolerance(dtype, layers: int) -> float:
    """Relative slack (x max |logit|) between two correct evaluations of
    ONE logit of a `layers`-deep decoder in `dtype` that differ only in
    kernel and reduction order: one rounding (eps) per layer-level
    accumulation, adding as a random walk over depth, doubled. The floor
    keeps float32 from demanding bit-identity across differently tiled
    programs."""
    import jax.numpy as jnp
    return 2.0 * max(float(jnp.finfo(dtype).eps), 4e-5) * math.sqrt(layers)


def loss_tolerance(dtype) -> float:
    """Relative slack on a LOSS: 1/32 of one rounding step of `dtype`
    (2.4e-4 for bfloat16), floored at 1e-5. Per-position errors are
    zero-mean and average over batch x seq positions, so a correct
    evaluation lands far inside it — the first chip run read 3.6e-7 and
    1.8e-6 in bf16 at 4096 wide — while a wrong mask or kernel moves a
    trained loss by whole percents."""
    import jax.numpy as jnp
    return max(float(jnp.finfo(dtype).eps) / 32.0, 1e-5)


def reference_loss(weights, cfg, ids, labels):
    """Next-token loss of a Llama decoder in plain jax.numpy, float32
    throughout — the script's own reference, independent of the
    package's layers, kernels and dispatch. `weights` is the model's
    state_dict as arrays (bf16 weights are upcast, not re-rounded)."""
    import jax
    import jax.numpy as jnp

    def f32(name):
        return weights[name].astype(jnp.float32)

    def norm(x, w):
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + cfg.rms_norm_eps) * w

    hi = jax.lax.Precision.HIGHEST
    h_dim, n_q, n_kv = (cfg.hidden_size, cfg.num_attention_heads,
                        cfg.num_key_value_heads)
    d = h_dim // n_q
    b, s = ids.shape
    inv = 1.0 / (cfg.rope_theta
                 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)      # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]

    def rope(x):                    # (B, S, H, D), interleaved pairs
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)

    x = jnp.take(f32("model.embed_tokens.weight"), ids, axis=0)
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        h = norm(x, f32(p + "input_layernorm.weight"))
        q = jnp.dot(h, f32(p + "self_attn.q_proj.weight"), precision=hi)
        k = jnp.dot(h, f32(p + "self_attn.k_proj.weight"), precision=hi)
        v = jnp.dot(h, f32(p + "self_attn.v_proj.weight"), precision=hi)
        q = rope(q.reshape(b, s, n_q, d))
        k = rope(k.reshape(b, s, n_kv, d))
        v = v.reshape(b, s, n_kv, d)
        k = jnp.repeat(k, n_q // n_kv, axis=2)
        v = jnp.repeat(v, n_q // n_kv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / math.sqrt(d)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                       precision=hi).reshape(b, s, h_dim)
        x = x + jnp.dot(a, f32(p + "self_attn.o_proj.weight"), precision=hi)
        h = norm(x, f32(p + "post_attention_layernorm.weight"))
        g = jnp.dot(h, f32(p + "mlp.gate_proj.weight"), precision=hi)
        u = jnp.dot(h, f32(p + "mlp.up_proj.weight"), precision=hi)
        x = x + jnp.dot(jax.nn.silu(g) * u, f32(p + "mlp.down_proj.weight"),
                        precision=hi)
    x = norm(x, f32("model.norm.weight"))
    logits = jnp.dot(x[:, :-1], f32("lm_head.weight"), precision=hi)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def device_bytes(arrays) -> dict:
    """{device id: bytes} actually resident, from addressable_shards."""
    out: dict = {}
    for a in arrays:
        for sh in getattr(a, "addressable_shards", ()):
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return dict(sorted(out.items()))


def memory_stats_by_device() -> dict:
    import jax
    out = {}
    for dev in jax.devices():
        st = dev.memory_stats() or {}
        out[dev.id] = {k: st.get(k) for k in
                       ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
    return out


def spread_over_devices(per_dev: dict, n: int) -> bool:
    """True when every one of `n` devices holds something and device 0
    holds less than half of it — i.e. not everything sits on device 0."""
    total = sum(per_dev.values())
    return (len(per_dev) == n and min(per_dev.values()) > 0
            and per_dev[min(per_dev)] < 0.5 * total)


class CacheCounter:
    """Counts JAX persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ------------------------------------------------------------- train phase
def run_train_steps(cfg, *, batch, seq, steps, dtype, lr=1e-3, seed=SEED,
                    data_sharding=None, ref_at=()):
    """Build the model + AdamW under whatever mesh is ambient, wrap the
    step with to_static exactly as bench.py does, take `steps` steps on
    one fixed random batch. Before each 1-based step in `ref_at`, the
    plain float32 reference is evaluated on the weights that step will
    see. Returns a dict of everything observed."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle

    paddle.seed(seed)
    t0 = time.perf_counter()
    model = build_model(cfg, dtype)
    opt = paddle.optimizer.AdamW(lr, parameters=model.parameters(),
                                 multi_precision=True)
    jax.block_until_ready([p._data for p in model.parameters()])
    build_s = time.perf_counter() - t0

    def train_step(ids, labels):
        loss = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step, state_objects=[model, opt])

    rng = np.random.RandomState(seed)
    ids_np = rng.randint(0, cfg.vocab_size, (batch, seq))
    ids_a = jnp.asarray(ids_np)
    if data_sharding is not None:
        ids_a = jax.device_put(ids_a, data_sharding)
    ids = paddle.Tensor(ids_a)
    # labels = ids: the head shifts them, so the objective is next-token
    # prediction over one fixed batch — a loss that must fall
    labels = ids

    ref = jax.jit(lambda w, i: reference_loss(w, cfg, i, i))
    losses, refs, enqueue_s, block_s, fetch_s = [], {}, [], [], []
    for n in range(1, steps + 1):
        if n in ref_at:
            w = {k: t._data for k, t in model.state_dict().items()}
            refs[n] = float(ref(w, jnp.asarray(ids_np, jnp.int32)))
            del w
        t0 = time.perf_counter()
        loss = step(ids, labels)
        t1 = time.perf_counter()
        loss._data.block_until_ready()
        t2 = time.perf_counter()
        losses.append(float(np.asarray(loss._data)))
        t3 = time.perf_counter()
        enqueue_s.append(t1 - t0)
        block_s.append(t2 - t1)
        fetch_s.append(t3 - t2)
    return {"model": model, "opt": opt, "step": step, "losses": losses,
            "refs": refs, "build_s": build_s, "enqueue_s": enqueue_s,
            "block_s": block_s, "fetch_s": fetch_s,
            "n_params": sum(int(np.prod(p.shape))
                            for p in model.parameters())}


def train_phase(cfg, *, batch, seq, steps, dtype="bfloat16", seed=SEED):
    """Passes when: losses are finite and fall; to_static recorded zero
    eager fallbacks; the attention calls took the Pallas kernel (no
    refusal at this shape) and the compiled step's text carries its
    custom call; the first and the last step's loss agree with the plain
    float32 reference on the same weights within `loss_tolerance`."""
    import paddle_tpu as paddle
    from paddle_tpu.nn.functional.flash_attention import pallas_refusals

    paddle.jit.to_static_report(reset=True)
    pallas_refusals(reset=True)
    say(f"train: hidden {cfg.hidden_size} inter {cfg.intermediate_size} "
        f"heads {cfg.num_attention_heads}/{cfg.num_key_value_heads} vocab "
        f"{cfg.vocab_size}, layers CUT to {cfg.num_hidden_layers}, {dtype} "
        f"params, AdamW fp32 master+moments, batch {batch} x seq {seq}, "
        f"{steps} steps")
    assert steps >= 3, "steps 1 and 2 each compile; a steady step follows"
    r = run_train_steps(cfg, batch=batch, seq=seq, steps=steps, dtype=dtype,
                        seed=seed, ref_at=(1, steps))
    losses, refs, step = r["losses"], r["refs"], r["step"]
    rep = paddle.jit.to_static_report()
    texts = step.compiled_texts()
    kernel_calls = [t.count(KERNEL_MARKER) for t in texts]
    refused = [k for k in pallas_refusals() if k[1][1] == seq]
    tol = loss_tolerance(dtype)
    ref_err = {n: abs(losses[n - 1] - v) / abs(v) for n, v in refs.items()}

    # medians over the steady steps (1 and 2 compile)
    walls = [e + b for e, b in zip(r["enqueue_s"], r["block_s"])]
    step_s, enqueue, block, fetch = (
        float(np.median(xs[2:]))
        for xs in (walls, r["enqueue_s"], r["block_s"], r["fetch_s"]))
    # does block_until_ready wait for the device? If it does, the wait
    # carries the step and the host fetch after it finds the value ready.
    waits = block > 10 * fetch and block > 0.5 * step_s
    say(f"train: {r['n_params'] / 1e6:.0f}M params, model build "
        f"{r['build_s']:.1f}s; step wall (enqueue+wait) "
        f"{[round(w, 3) for w in walls]} s — steps 1 and 2 compile (AdamW "
        f"state is created in step 1)")
    say(f"train: steady step {step_s:.4f}s = {batch * seq / step_s:.0f} "
        f"tokens/s (smoke reading); enqueue {enqueue:.4f}s, "
        f"block_until_ready {block:.4f}s, host fetch after it {fetch:.5f}s "
        f"-> block_until_ready waits: {waits}")
    say(f"train: losses {[round(x, 4) for x in losses]}; float32 reference "
        f"{ {n: round(v, 4) for n, v in refs.items()} }, rel err "
        f"{ {n: f'{e:.2e}' for n, e in ref_err.items()} } (tolerance "
        f"{tol:.2e})")
    say(f"train: {len(texts)} compiled programs, {KERNEL_MARKER} count "
        f"{kernel_calls}; eager fallbacks "
        f"{len(rep['eager_fallbacks'])}; compile seconds "
        f"{ {k: round(v, 1) for k, v in rep['compile_seconds'].items()} }")
    checks = {
        "losses_finite": bool(np.all(np.isfinite(losses))),
        "losses_fall": losses[-1] < losses[0],
        "no_eager_fallback": (not rep["eager_fallbacks"]
                              and rep["eager_fallbacks_dropped"] == 0),
        "flash_kernel_taken": not refused,
        "flash_kernel_in_step": bool(texts) and min(kernel_calls) > 0,
        "loss_matches_reference": all(e <= tol for e in ref_err.values()),
    }
    return {"name": "train", "checks": checks, "losses": losses,
            "refs": refs, "step_s": step_s, "block_until_ready_waits": waits}


# ------------------------------------------------------------- serve phase
def make_requests(vocab: int, lens, shared_prefix: int, seed=SEED):
    """Random prompts of the given lengths; the LAST TWO share their
    first `shared_prefix` tokens (a radix-cache hit for the second,
    which `drain` sends once the first has finished)."""
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, vocab, (n,)).tolist() for n in lens]
    prompts[-1][:shared_prefix] = prompts[-2][:shared_prefix]
    return prompts


def drain(eng, prompts, max_new_tokens, step_cap=4000):
    """add_request all but the last prompt and step until drained, then
    the last one (whose shared prefix the radix cache holds only once
    its sibling has finished and donated its pages) and drain again.
    Returns (tokens per request, per-step records). A step that
    compiled is marked: steady-rate readings leave it out."""
    rids, steps = [], []
    for wave in (prompts[:-1], prompts[-1:]):
        rids += [eng.add_request(p, max_new_tokens=max_new_tokens)
                 for p in wave]
        while eng.has_work():
            if len(steps) >= step_cap:
                raise RuntimeError(f"engine not drained in {step_cap} steps")
            n_prog = eng.num_compiled_programs
            done = sum(len(eng.requests[r].output_ids) for r in rids)
            t0 = time.perf_counter()
            eng.step()
            steps.append({
                "s": time.perf_counter() - t0,
                "compiled": eng.num_compiled_programs - n_prog,
                "tokens": sum(len(eng.requests[r].output_ids)
                              for r in rids) - done})
    return [list(eng.requests[r].output_ids) for r in rids], steps


def decode_program_texts(eng) -> dict:
    """{(family, B, P): optimized HLO text} of the engine's decode
    programs. One re-lowering each — a decode program costs ~1 s of host
    tracing per layer to lower again, so callers read everything they
    need off the one text."""
    return {k[:3]: eng.programs.compiled_text(k)
            for k in eng.programs.keys() if k[0] == "decode"}


def greedy_gaps(model, prompt, tokens):
    """Teacher-forced check of a greedy continuation against the DENSE
    forward: feed prompt+tokens through `model.forward` once and, at
    every position whose dense argmax is not the token given, report
    how far below the dense maximum that token's logit sits. Returns
    (rows, max |logit|); rows are (position, token, dense argmax, gap)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle

    seq = np.asarray([list(prompt) + list(tokens)], np.int32)
    state = {k: t._data for k, t in model.state_dict().items()}

    def dense(st, ids):
        out = paddle.jit.functional_call(
            model, {k: paddle.Tensor(v) for k, v in st.items()},
            paddle.Tensor(ids))
        return out._data[0, len(prompt) - 1:-1].astype(jnp.float32)

    with paddle.no_grad():
        logits = np.asarray(jax.jit(dense)(state, jnp.asarray(seq)))
    rows = []
    for j, tok in enumerate(tokens):
        top = int(np.argmax(logits[j]))
        if top != tok:
            rows.append((j, int(tok), top,
                         float(logits[j, top] - logits[j, tok])))
    return rows, float(np.max(np.abs(logits)))


def explain_tokens(model, prompt, tokens, dtype, label) -> bool:
    """True when every token that is not the dense argmax sits within
    `logit_tolerance` x max|logit| of it — a near-tie two correct
    evaluations may break differently. Prints each such position."""
    rows, scale = greedy_gaps(model, prompt, tokens)
    tol = logit_tolerance(dtype, model.cfg.num_hidden_layers) * scale
    for j, tok, top, gap in rows:
        say(f"{label}: new token {j}: got {tok}, dense argmax {top}, logit "
            f"gap {gap:.5f} ({'within' if gap <= tol else 'EXCEEDS'} "
            f"{tol:.5f})")
    say(f"{label}: {len(rows)} of {len(tokens)} tokens differ from the dense "
        f"argmax; max |logit| {scale:.3f}, tolerance {tol:.5f}")
    return all(gap <= tol for _, _, _, gap in rows)


def serve_phase(cfg, *, prompt_lens, shared_prefix, max_new_tokens,
                num_pages, dtype="bfloat16", seed=SEED):
    """Passes when: every request finishes with the asked number of
    tokens; the decode program's text carries the paged-attention custom
    call; the allocator is empty after reset_prefix_cache(); the first
    request's greedy tokens equal the dense-cache `model.generate()`
    path, or every difference is a near-tie the script prints."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine

    paddle.seed(seed)
    t0 = time.perf_counter()
    model = build_model(cfg, dtype)
    jax.block_until_ready([p._data for p in model.parameters()])
    build_s = time.perf_counter() - t0
    eng = ServingEngine(model, num_pages=num_pages)
    pool_tokens = eng.num_pages * eng.page_size
    say(f"serve: hidden {cfg.hidden_size} inter {cfg.intermediate_size} "
        f"heads {cfg.num_attention_heads}/{cfg.num_key_value_heads} vocab "
        f"{cfg.vocab_size}, layers CUT to {cfg.num_hidden_layers} {dtype}; "
        f"KV pool {eng.num_pages} pages x {eng.page_size} = {pool_tokens} "
        f"tokens ({eng.kv_page_bytes * eng.num_layers * eng.num_pages / 2**30:.2f} GiB); "
        f"model build {build_s:.1f}s")
    prompts = make_requests(cfg.vocab_size, prompt_lens, shared_prefix, seed)
    chunk = eng.scheduler.token_budget
    say(f"serve: {len(prompts)} requests, prompt lengths {prompt_lens} "
        f"(prefill chunk {chunk}; the last two share {shared_prefix} "
        f"tokens), {max_new_tokens} new tokens each, greedy")

    t0 = time.perf_counter()
    outs, steps = drain(eng, prompts, max_new_tokens)
    wall = time.perf_counter() - t0
    steady = [s for s in steps if not s["compiled"]]
    steady_tok = sum(s["tokens"] for s in steady)
    steady_s = sum(s["s"] for s in steady)
    counters = dict(eng.metrics.counters)
    say(f"serve: drained in {len(steps)} steps, {wall:.1f}s wall, "
        f"{sum(s['compiled'] for s in steps)} programs compiled "
        f"{eng.program_counts()}; first-launch seconds per program "
        f"{ {str(k[:3]): round(ms / 1e3, 1) for k, ms in eng.programs.compile_times_ms().items()} }")
    say(f"serve: steady steps (no compile) {len(steady)}: {steady_tok} new "
        f"tokens in {steady_s:.2f}s = "
        f"{steady_tok / max(steady_s, 1e-9):.1f} tokens/s (smoke reading); "
        f"prefix_hits {counters['prefix_hits']}, prefill_chunks "
        f"{counters['prefill_chunks']}")

    decode_calls = {k: t.count(KERNEL_MARKER)
                    for k, t in decode_program_texts(eng).items()}
    chunk_key = next(k for k in eng.programs.keys() if k[0] == "chunk")
    chunk_calls = eng.programs.compiled_text(chunk_key).count(KERNEL_MARKER)
    say(f"serve: {KERNEL_MARKER} per decode program {decode_calls}; chunk "
        f"program {chunk_key[:3]} has {chunk_calls} — prefill chunks attend "
        f"through the masked XLA composition over gathered pages "
        f"(models/llama.py LlamaAttention.paged, a prefill span), a lead for later")

    # dense-cache reference for the first request
    ref_ids = model.generate(
        paddle.to_tensor(np.asarray([prompts[0]])),
        max_new_tokens=max_new_tokens, use_jit=True)
    ref = np.asarray(ref_ids._data)[0, len(prompts[0]):].tolist()
    same = ref == outs[0]
    say(f"serve: request 0 vs dense-cache generate(): "
        f"{'identical' if same else 'DIFFERENT'} over {len(ref)} tokens")
    explained = same or explain_tokens(model, prompts[0], outs[0], dtype,
                                       "serve: request 0")

    eng.reset_prefix_cache()
    used = eng.allocator.num_used
    eng.shutdown()
    checks = {
        "all_requests_finished": all(len(o) == max_new_tokens for o in outs),
        "multi_chunk_prefill": max(prompt_lens) > chunk,
        "prefix_cache_hit": counters["prefix_hits"] > 0,
        "paged_kernel_in_decode": (bool(decode_calls)
                                   and min(decode_calls.values()) > 0),
        "allocator_empty": used == 0,
        "tokens_match_dense": bool(explained),
    }
    return {"name": "serve", "checks": checks, "tokens": outs,
            "steady_tokens_per_s": steady_tok / max(steady_s, 1e-9)}


# -------------------------------------------------------- cross-chip phase
def tp_serve_phase(cfg, *, tp, prompt_lens, shared_prefix, max_new_tokens,
                   num_pages, dtype="bfloat16", seed=SEED):
    """A TP-`tp` ServingEngine against the one-chip engine on device 0,
    same model, same requests. Passes when every request's greedy tokens
    are equal (or each side's differences from the dense argmax are
    near-ties), the TP engine's weights and KV pool are spread over the
    `tp` devices, its decode programs all-reduce over 'model' and carry
    the paged-attention custom call (the manual shard_map lowering)."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine, tp_serving_mesh

    paddle.seed(seed)
    model = build_model(cfg, dtype)
    prompts = make_requests(cfg.vocab_size, prompt_lens, shared_prefix, seed)
    say(f"tp-serve: llama widths hidden {cfg.hidden_size}, layers CUT to "
        f"{cfg.num_hidden_layers} {dtype}; {len(prompts)} requests "
        f"{prompt_lens}, {max_new_tokens} new tokens, pool {num_pages} pages")

    one = ServingEngine(model, num_pages=num_pages)
    t0 = time.perf_counter()
    base, _ = drain(one, prompts, max_new_tokens)
    say(f"tp-serve: one-chip engine drained in {time.perf_counter() - t0:.1f}s")
    one.shutdown()
    del one
    gc.collect()

    eng = ServingEngine(model, mesh=tp_serving_mesh(tp), num_pages=num_pages)
    t0 = time.perf_counter()
    outs, _ = drain(eng, prompts, max_new_tokens)
    say(f"tp-serve: TP-{tp} engine drained in {time.perf_counter() - t0:.1f}s")
    per_dev = device_bytes(list(eng._state.values()) + eng._k_caches
                           + eng._v_caches)
    say(f"tp-serve: TP engine weights+KV bytes per device {per_dev}; "
        f"memory_stats {memory_stats_by_device()}")

    from paddle_tpu.profiler import comm
    texts = decode_program_texts(eng)
    decode = {k: comm.CommReport(comm.parse_hlo_collectives(t),
                                 mesh=eng.mesh).to_dict()
              for k, t in texts.items()}
    kernel = {k: t.count(KERNEL_MARKER) for k, t in texts.items()}
    say(f"tp-serve: collectives per decode program "
        f"{ {k: (r['op_counts'], r['bytes_per_axis']) for k, r in decode.items()} }")
    say(f"tp-serve: {KERNEL_MARKER} per TP decode program {kernel}")

    equal = [a == b for a, b in zip(base, outs)]
    say(f"tp-serve: greedy tokens equal per request: {equal}")
    ok_tokens = True
    for i, same in enumerate(equal):
        if not same:
            ok_tokens &= explain_tokens(model, prompts[i], base[i], dtype,
                                        f"tp-serve: request {i} one-chip")
            ok_tokens &= explain_tokens(model, prompts[i], outs[i], dtype,
                                        f"tp-serve: request {i} TP-{tp}")
    eng.reset_prefix_cache()
    used = eng.allocator.num_used
    eng.shutdown()
    checks = {
        "all_requests_finished": all(len(o) == max_new_tokens for o in outs),
        "tokens_match_one_chip": bool(ok_tokens),
        "state_spread_over_devices": spread_over_devices(per_dev, tp),
        "decode_all_reduces_on_model": bool(decode) and all(
            r["op_counts"].get("all-reduce", 0) >= 1
            and set(r["bytes_per_axis"]) <= {"model"}
            for r in decode.values()),
        "paged_kernel_in_tp_decode": bool(kernel) and min(kernel.values()) > 0,
        "allocator_empty": used == 0,
    }
    return {"name": "tp_serve", "checks": checks}


def hybrid_train_phase(cfg, *, dp, mp, batch, seq, dtype="bfloat16",
                       seed=SEED):
    """One to_static train step on a dp x mp hybrid mesh, set up through
    fleet.init as __graft_entry__.py does, against the same step on one
    device. Passes when the step-1 losses agree within `loss_tolerance`,
    parameters and optimizer state are spread over the devices, the
    compiled step has collectives on both axes and the flash kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed.fleet import DistributedStrategy, fleet

    say(f"hybrid-train: llama widths hidden {cfg.hidden_size}, layers CUT "
        f"to {cfg.num_hidden_layers} {dtype}, batch {batch} x seq {seq}; "
        f"one device, then dp{dp} x mp{mp}")
    one = run_train_steps(cfg, batch=batch, seq=seq, steps=2, dtype=dtype,
                          seed=seed)
    base = one["losses"]
    del one
    gc.collect()
    say(f"hybrid-train: one-device losses {[round(x, 4) for x in base]}")

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    try:
        mesh = fleet.get_hybrid_communicate_group().mesh
        r = run_train_steps(
            cfg, batch=batch, seq=seq, steps=2, dtype=dtype, seed=seed,
            data_sharding=NamedSharding(mesh, P("data", None)))
        losses, step = r["losses"], r["step"]
        per_dev = device_bytes([p._data for p in r["model"].parameters()]
                               + list(r["opt"].raw_state().values()))
        crep = step.comm_report()
        kernel_calls = [t.count(KERNEL_MARKER) for t in step.compiled_texts()]
    finally:
        fleet._hcg = None
    tol = loss_tolerance(dtype)
    err = abs(losses[0] - base[0]) / abs(base[0])
    say(f"hybrid-train: dp{dp} x mp{mp} losses "
        f"{[round(x, 4) for x in losses]}; step-1 rel diff to one device "
        f"{err:.2e} (tolerance {tol:.2e})")
    say(f"hybrid-train: params+optimizer bytes per device {per_dev}; "
        f"memory_stats {memory_stats_by_device()}")
    say(f"hybrid-train: collectives {crep['op_counts']}, bytes per axis "
        f"{crep['bytes_per_axis']}; {KERNEL_MARKER} count {kernel_calls}")
    checks = {
        "losses_finite": bool(np.all(np.isfinite(losses))),
        "loss_matches_one_device": err <= tol,
        "state_spread_over_devices": spread_over_devices(per_dev, dp * mp),
        "collectives_on_both_axes": all(
            any(ax in key.split("+") for key in crep["bytes_per_axis"])
            for ax in ("data", "model")),
        "flash_kernel_in_step": bool(kernel_calls) and min(kernel_calls) > 0,
    }
    return {"name": "hybrid_train", "checks": checks}


# --------------------------------------------------------------------- main
def report(device: dict, phases) -> tuple:
    """(ok, the contract's last line) from the phases' checks."""
    ok = True
    for ph in phases:
        failed = [k for k, v in ph["checks"].items() if not v]
        say(f"phase {ph['name']}: {'PASS' if not failed else 'FAIL'} "
            f"{ph['checks']}")
        ok &= not failed
    return ok, json.dumps({"ok": bool(ok), "device": device})


def real_sizes():
    """The sizes main() runs: published widths, depth cut to the chip."""
    from paddle_tpu.models.llama import LlamaConfig, llama_3_8b
    train = dict(cfg=LlamaConfig(num_hidden_layers=TRAIN_LAYERS),
                 batch=2, seq=2048)
    serve = dict(cfg=llama_3_8b(num_hidden_layers=SERVE_LAYERS),
                 prompt_lens=[24, 200, 1100, 600, 640], shared_prefix=512,
                 max_new_tokens=64, num_pages=SERVE_POOL_PAGES)
    return train, serve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the cross-chip phase (default 1)")
    args = ap.parse_args(argv)

    from paddle_tpu.utils.compile_cache_dir import place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(f"chip_smoke.py: needs {args.chips} TPU chip(s), JAX found "
              f"{device}; nothing was run", file=sys.stderr)
        return 1

    import paddle_tpu._native as native
    from paddle_tpu.kernels.autotune import autotune_enabled
    from paddle_tpu.profiler.cost import chip_peaks
    cache = CacheCounter()
    say(f"device {device}; published peaks (bf16 FLOP/s, HBM B/s) "
        f"{chip_peaks(device['kind'])}; jax {jax.__version__}; compile "
        f"cache {cache_dir}; native extension loaded: "
        f"{native.available()} (not needed); kernel autotune on: "
        f"{autotune_enabled()}")
    if autotune_enabled():
        print("chip_smoke.py: kernel autotune must be off (nothing outside "
              "the checkout may shape a kernel)", file=sys.stderr)
        return 1

    train, serve = real_sizes()
    t0 = time.perf_counter()
    if args.chips == 1:
        phases = [train_phase(steps=4, **train)]
        gc.collect()
        phases.append(serve_phase(**serve))
    else:
        phases = [hybrid_train_phase(dp=2, mp=2, **train)]
        gc.collect()
        phases.append(tp_serve_phase(tp=4, **serve))
    say(f"all phases took {time.perf_counter() - t0:.0f}s; persistent "
        f"compile cache hits {cache.hits} misses {cache.misses}; "
        f"memory_stats {memory_stats_by_device()}")
    ok, last_line = report(device, phases)
    if not ok:
        return 1
    print(last_line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
