"""Llama decoder pretraining step on one TPU chip: one process, one attempt.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
metric = Llama pretraining MFU (the BASELINE.md north star is >= 40% MFU);
vs_baseline = MFU / 0.40; tokens/sec/chip and the accounting fields ride
along, and `device` names what it ran on.

This is a stop-gap until the repository has a benchmark proper (see
ROADMAP.md); meanwhile it must not lie. It exits non-zero, printing no
result line, when JAX finds no TPU or when its one attempt fails: no CPU
config under the device metric's name, no Pallas -> XLA -> smaller-config
degradation, no error record with exit 0.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

ITERS = 6


def llama_step_flops(cfg, batch, seq):
    """Training FLOPs/step: 6*N*tokens (fwd+bwd) + attention 12*L*s^2*h."""
    # The input-embedding lookup performs no matmul FLOPs; only the LM
    # head's vocab matmul counts toward the 6*N model.
    n_matmul = (
        cfg.vocab_size * cfg.hidden_size  # LM head
        + cfg.num_hidden_layers * (
            2 * cfg.hidden_size * cfg.hidden_size  # q,o
            + 2 * cfg.hidden_size * (cfg.num_key_value_heads *
                                     cfg.hidden_size // cfg.num_attention_heads)
            + 3 * cfg.hidden_size * cfg.intermediate_size))
    n_params = n_matmul + (0 if cfg.tie_word_embeddings
                           else cfg.vocab_size * cfg.hidden_size)
    tokens = batch * seq
    dense = 6.0 * n_matmul * tokens
    attn = 12.0 * cfg.num_hidden_layers * batch * seq * seq * cfg.hidden_size
    return dense + attn, n_params, attn


def run(dev):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.profiler.cost import peak_flops_per_chip

    # ~0.8B-param config that fits one v5e chip (16GB HBM) with AdamW
    # fp32 states + bf16 params/activations.
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1536,
                      intermediate_size=4096, num_hidden_layers=18,
                      num_attention_heads=12, num_key_value_heads=12,
                      max_position_embeddings=2048)
    batch, seq = 4, 2048

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters(),
                                 multi_precision=True)

    def train_step(ids, labels):
        loss = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step, state_objects=[model, opt])

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))

    # warmup: call 1 compiles, call 2 recompiles once the lazily created
    # AdamW moments have grown the donated state (jit/api.py _CacheEntry).
    # A host fetch of the loss is the sync; the donated state chains step
    # N+1 on step N, so the timed steps cannot overlap or be elided.
    for _ in range(3):
        loss = step(ids, labels)
    float(np.asarray(loss._data))
    t0 = time.perf_counter()
    for _ in range(ITERS):
        loss = step(ids, labels)
    loss_val = float(np.asarray(loss._data))
    dt = (time.perf_counter() - t0) / ITERS
    if not np.isfinite(loss_val):
        raise RuntimeError(f"non-finite loss {loss_val}")

    flops, n_params, attn_flops = llama_step_flops(cfg, batch, seq)
    peak = peak_flops_per_chip(dev.device_kind)
    mfu = flops / dt / peak

    # XLA's own accounting of the compiled step, AFTER timing. Reading
    # caveat: Pallas custom calls count ZERO flops, so the analytic number
    # undercounts by ~attn_flops_share (profiler/cost.py).
    prog = step.cost_report()["programs"][0]
    if "error" in prog:
        raise RuntimeError(f"cost accounting failed: {prog['error']}")
    crep = step.comm_report()

    return {
        "metric": "llama_pretrain_mfu",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu / 0.40, 4),
        "tokens_per_sec_per_chip": round(batch * seq / dt, 1),
        "step_time_s": round(dt, 4),
        "n_params": int(n_params),
        "loss": loss_val,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "attention": "pallas_flash",
        "optimizer": "adamw",
        "attn_flops_share": round(attn_flops / flops, 4),
        "analytic_flops": float(prog["flops"]),
        "peak_hbm_bytes": int(prog["peak_bytes"]),
        "analytic_mfu": round(float(prog["flops"]) / dt / peak, 4),
        "comm_bytes": int(crep["payload_bytes"]),
        "comm_bytes_per_axis": dict(crep["bytes_per_axis"]),
        "config": {"hidden": cfg.hidden_size, "layers": cfg.num_hidden_layers,
                   "batch": batch, "seq": seq},
    }


def main() -> int:
    from paddle_tpu.utils.compile_cache_dir import place_compile_cache
    place_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py: needs a TPU, JAX found {dev.platform!r}; no "
              f"result", file=sys.stderr)
        return 1
    print(json.dumps(run(dev)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
