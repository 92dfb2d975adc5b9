"""What the algorithm REQUIRES of the chip, from shapes alone: operations
and bytes. Kept with the benchmark so that no PR that claims a gain can
change the count. A work function takes the configuration (the dict of a
`configs/*.json`), the cell (the dict of a `workloads/*.json`) and the
run's `values`, and returns a number or a dict of numbers.

Counts: a matmul of an (m, k) by a (k, n) is 2mkn operations. Training
does forward + backward = 3 x the forward's matmul operations (6 per
parameter per token). CAUSAL attention needs half of the s x s score
matrix: forward QK^T and PV are 2 x 2 x (s^2/2) x h = 2 s^2 h a sequence a
layer, so 6 s^2 h with the backward, i.e. 6 s h a token (`bench.py`'s
`llama_step_flops` counts the unmasked 12). The embedding lookup,
norms, RoPE, softmax and any recomputation count nothing.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def chip_peaks(device_kind: str) -> dict:
    """{'flops': bf16 FLOP/s, 'bytes': HBM bytes/s} of one chip; raises
    for a device the table does not hold."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    kind = str(device_kind).lower()
    for row in table:
        if row["match"] in kind:
            return {"flops": row["bf16_flops_per_s"],
                    "bytes": row["hbm_bytes_per_s"]}
    raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                     f"add it to peaks.json with its source")


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
