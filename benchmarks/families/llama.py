"""Family `llama`: the Llama-shaped dense decoder (Mistral-7B's block)
through `paddle_tpu.models.llama`. What the drivers need of a model and
nothing else (README lists it): the program's config object, the model in
the served type, a served model's weights from the seed, the plain
reference, the limits with their readings or reasons, and the family's
work counts.

`config`, `build_model` and the loss tolerance are COPIES of `chip_smoke.py`'s
(PR 24 proved them on the chip); the benchmark keeps its own so that no
later PR can change the yardstick by editing the program. The reference is
`references/llama.py`, which imports nothing of the program.

Work counts (shared rules: `harness/work.py`): a work function takes the
configuration (the dict of a `configs/*.json`), the cell (the dict of a
`workloads/*.json`) and the run's `values`, and returns a number, a dict of
numbers, or None / {} where the run lacks what it counts from.
"""
from __future__ import annotations


from benchmarks.harness.work import BYTES
from benchmarks.references import llama as reference
from benchmarks.references.llama import (logits, loss,  # noqa: F401
                                         position_logits, token_gaps)

# keys of a configuration file that LlamaConfig takes as they are
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta", "tie_word_embeddings")


def config(cfg: dict):
    """The program's LlamaConfig from a configuration file: every
    published key it has a field for, passed explicitly (its defaults for
    `rms_norm_eps` and `rope_theta` are another model's)."""
    from paddle_tpu.models.llama import LlamaConfig
    kw = {k: cfg[k] for k in MODEL_KEYS}
    if cfg.get("head_dim", kw["hidden_size"] // kw["num_attention_heads"]) \
            != kw["hidden_size"] // kw["num_attention_heads"]:
        raise ValueError("LlamaConfig derives head_dim = hidden / heads; "
                         "this configuration's head_dim differs")
    if cfg.get("sliding_window") is not None:
        raise ValueError("the Llama-shaped path has no sliding window")
    return LlamaConfig(**kw)


def build_model(pcfg, dtype):
    """LlamaForCausalLM with parameters CREATED in `dtype` (building in
    float32 and casting after does not fit at these widths)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        return LlamaForCausalLM(pcfg)
    finally:
        paddle.set_default_dtype(prev)


def load_weights(model, pcfg, cfg: dict, seed: int):
    """The served model's parameters replaced by the benchmark's own,
    drawn from the seed on the device (`references/llama.py`
    `make_weights`, which says why these scales). The arrays the program's
    constructor made are freed first: both do not fit."""
    sd = model.state_dict()
    spec = reference.leaves(pcfg)
    if set(sd) != set(spec) or any(
            tuple(sd[k].shape) != spec[k][0] for k in spec):
        raise ValueError(f"the model's parameters are not the family's: "
                         f"{sorted(set(sd) ^ set(spec))[:6]}")
    for t in sd.values():
        t._data.delete()
    new = reference.make_weights(pcfg, seed, cfg["dtype"])
    for k, t in sd.items():
        t._data = new[k]


def reference_weights(pcfg, cfg: dict, seed: int):
    """What the plain reference reads: the same values drawn again from
    the seed, a leaf at a time; nothing the program holds."""
    return reference.LazyWeights(pcfg, seed, cfg["dtype"])


# What a served request is held to (serve driver's check), for bfloat16:
# over its served tokens, how far each token's reference logit lies under
# the reference's best. Logits are about N(0, 1) by the weights' scales at
# any width, so the limits are in logit units. Set from chip readings
# (PERF.md section 6, PR 29, has them): above the largest a sound run gave
# over a dozen seeds a cell, below the smallest the control gave.
GAP_LIMITS_BF16 = {"mean": 4e-4, "widest": 0.05}


def gap_limits(cfg: dict) -> dict:
    """{"mean", "widest"} for the configuration's type: bfloat16's
    readings, scaled by the type's rounding step (floored, so that
    float32 is not held to bit-identity across differently tiled
    programs). Only bfloat16's were read on the chip; the toy cells of
    the tests are float32."""
    import jax.numpy as jnp
    eps = max(float(jnp.finfo(cfg["dtype"]).eps), 4e-5)
    return {k: v * eps / float(jnp.finfo(jnp.bfloat16).eps)
            for k, v in GAP_LIMITS_BF16.items()}


def loss_tolerance(cfg: dict) -> float:
    """Relative slack on a LOSS: 1/32 of one rounding step of the
    configuration's `dtype` (2.4e-4 for bfloat16), floored at 1e-5.
    Per-position errors are zero-mean and average over batch x seq
    positions, so a correct evaluation lands far inside it (PR 24's chip
    run read 3.6e-7 and 1.8e-6 in bf16 at 4096 wide) while a wrong mask or
    kernel moves a loss by whole percents."""
    import jax.numpy as jnp
    return max(float(jnp.finfo(cfg["dtype"]).eps) / 32.0, 1e-5)


# ------------------------------------------------------------ work counts

def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg: dict) -> int:
    """Matmul parameters of one decoder layer: q, k, v, o, gate, up, down."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = h * cfg["num_attention_heads"] * d
    kv = h * cfg["num_key_value_heads"] * d
    return 2 * q + 2 * kv + 3 * h * cfg["intermediate_size"]


def matmul_params(cfg: dict) -> int:
    """Parameters a token is multiplied by: the layers and the head (the
    embedding is a lookup; a tied head still multiplies)."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    emb = h * cfg["vocab_size"]
    n = matmul_params(cfg) + cfg["num_hidden_layers"] * 2 * h + h
    return n if cfg.get("tie_word_embeddings") else n + emb


def train_flops_per_token(cfg: dict, cell: dict, values=None) -> float:
    """Forward + backward of one token at the cell's sequence length."""
    s = cell["traffic"]["seq"]
    attn = 6 * cfg["num_hidden_layers"] * s * cfg["num_attention_heads"] \
        * head_dim(cfg)
    return 6.0 * matmul_params(cfg) + attn


def train_step_flops(cfg: dict, cell: dict, values=None) -> float:
    t = cell["traffic"]
    return train_flops_per_token(cfg, cell) * t["batch"] * t["seq"]


def serve_flops_per_token(cfg: dict, cell: dict, values):
    """Forward of one token the engine processed (prefilled or decoded):
    2 x the layers' matmul parameters; the head only where a token comes
    out of it (a prompt's last position and every decoded token:
    `values['head_tokens_per_processed']`); and attention over the keys
    the token had to see, QK^T and PV = 2 x 2 x context x h a layer
    (`values['mean_context_tokens']`: itself and what came before it,
    half the prompt on average for a prefilled token). Both are the
    driver's means over the SAME tokens as the rate this multiplies
    (`processed_tokens_per_s`). The whole step's required work: what a
    faster program cannot shrink."""
    ctx = values.get("mean_context_tokens")
    heads = values.get("head_tokens_per_processed")
    if ctx is None or heads is None:
        return None
    attn = 4 * cfg["num_hidden_layers"] * ctx * cfg["num_attention_heads"] \
        * head_dim(cfg)
    return 2.0 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                  + heads * cfg["hidden_size"] * cfg["vocab_size"]) + attn


def flash_attention_train(cfg: dict, cell: dict, values=None) -> dict:
    """Causal flash attention, forward + backward (dq and dkv), over every
    layer of ONE train step: operations and the bytes that must cross HBM
    (q, k, v, o and their gradients once each, in the model's type; the
    backward reads q, k, v, o, do and writes dq, dk, dv)."""
    t = cell["traffic"]
    b, s, L = t["batch"], t["seq"], cfg["num_hidden_layers"]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    # forward 2 matmuls, backward 5 (recomputed scores, dv, dp, dq, dk)
    # on half of the s x s matrix: (2 + 5) * 2 * s^2/2 * d per head
    flops = L * b * hq * 7 * s * s * d
    el = BYTES[cfg.get("torch_dtype", "bfloat16")]
    q_like, kv_like = b * s * hq * d * el, b * s * hkv * d * el
    fwd = 2 * q_like + 2 * kv_like                  # q,o + k,v
    bwd = 4 * q_like + 4 * kv_like                  # q,o,do,dq + k,v,dk,dv
    return {"flops": float(flops), "bytes": float(L * (fwd + bwd))}


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one token over every layer, in the served type."""
    el = BYTES[cfg.get("kv_dtype") or cfg.get("torch_dtype", "bfloat16")]
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * head_dim(cfg) * el


def weight_bytes(cfg: dict) -> int:
    return total_params(cfg) * BYTES[cfg.get("torch_dtype", "bfloat16")]


def paged_decode_kv(cfg: dict, cell: dict, values) -> dict:
    """Bytes of KV the decode steps of the traced slice had to read: every
    decoding row reads its whole context once a step.
    `values['slice_decode_context_tokens']` is the driver's sum, over the
    slice's decode steps and their rows, of the row's context length."""
    n = values.get("slice_decode_context_tokens")
    if n is None:
        return {}
    return {"flops": 0.0, "bytes": float(n) * kv_bytes_per_token(cfg)}
