"""Family `cohere2_moe`: Command A+'s layer (window and full attention
layers interleaved, a parallel attention + expert block under one
LayerNorm, sigmoid-routed experts beside averaged shared experts) through
`paddle_tpu.models.cohere2_moe`, served as ONE chip's share of an
expert-parallel deployment: the configuration says how many routed
experts this chip holds and from which (`experts_held`,
`expert_offset`); the router keeps its published width.

What the drivers need of a model and nothing else (README lists it): the
program's config object, the model in the served type, a served model's
weights from the seed for the program and again for the reference, the
plain reference (`references/cohere2_moe.py`, which imports nothing of
the program), the limits with their readings, and the family's work
counts. This family brings no `paged_decode_kv`: the driver's sum of
contexts cannot clip a row at the window, so `paged_attn_hbm_share` stays
out of its cells' lines; its decode kernel's count is `window_decode_kv`,
from the program's own counter of the keys a step had to read.
"""
from __future__ import annotations

from benchmarks.harness.work import BYTES
from benchmarks.references import cohere2_moe as reference
from benchmarks.references.cohere2_moe import (  # noqa: F401
    ROUTE_TIE, logits, loss, position_logits, position_logits_and_margins,
    token_gaps)

# keys of a configuration file that Cohere2MoeConfig takes as they are
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "num_experts",
              "num_experts_per_tok", "num_shared_experts", "norm_topk_prob",
              "layer_norm_eps", "rope_theta", "sliding_window",
              "layer_types", "layer_switch", "logit_scale",
              "max_position_embeddings", "experts_held", "expert_offset")
# what the program's layer is written for: a configuration that says
# otherwise is another architecture
FIXED = {"expert_selection_fn": "sigmoid", "first_k_dense_replace": 0,
         "use_parallel_block": True, "use_qk_norm": False,
         "attention_bias": False, "rotary_pct": 1,
         "position_embedding_type": "rope_gptj", "hidden_act": "silu",
         "use_gated_activation": True, "tie_word_embeddings": True,
         "rms_norm_eps": None,
         "shared_expert_combination_strategy": "average",
         "order_of_interleaved_layers": "local_attn_first"}
SLIDING = "sliding_attention"


def config(cfg: dict):
    from paddle_tpu.models.cohere2_moe import Cohere2MoeConfig
    for k, v in FIXED.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"family cohere2_moe is written for {k} = "
                             f"{v!r}; this configuration has {cfg[k]!r}")
    kw = {k: cfg[k] for k in MODEL_KEYS}
    # the file keeps the published list of all 32 layers' kinds; the cut
    # runs its first num_hidden_layers
    kw["layer_types"] = tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])
    return Cohere2MoeConfig(**kw)


def build_model(pcfg, dtype):
    """Cohere2MoeForCausalLM with parameters CREATED in `dtype`."""
    import paddle_tpu as paddle
    from paddle_tpu.models.cohere2_moe import Cohere2MoeForCausalLM
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        return Cohere2MoeForCausalLM(pcfg)
    finally:
        paddle.set_default_dtype(prev)


def load_weights(model, pcfg, cfg: dict, seed: int):
    """The served model's parameters replaced by the benchmark's own,
    drawn from the seed on the device (`references/cohere2_moe.py`
    `make_weights`: the held experts stacked and the shared experts side
    by side, as the program keeps them). The arrays the program's
    constructor made are freed first: both do not fit."""
    sd = model.state_dict()
    spec = reference.program_leaves(pcfg)
    if set(sd) != set(spec) or any(
            tuple(sd[k].shape) != spec[k] for k in spec):
        raise ValueError(f"the model's parameters are not the family's: "
                         f"{sorted(set(sd) ^ set(spec))[:6]}")
    for t in sd.values():
        t._data.delete()
    new = reference.make_weights(pcfg, seed, cfg["dtype"])
    for k, t in sd.items():
        t._data = new[k]


def reference_weights(pcfg, cfg: dict, seed: int):
    """What the plain reference reads: the same values drawn again from
    the seed, a leaf at a time, every expert a leaf of its own."""
    return reference.LazyWeights(pcfg, seed, cfg["dtype"])


# What a served request is held to (serve driver's check), for bfloat16:
# over its served tokens, how far each token's reference logit lies under
# the reference's best, in logit units, over the positions the reference
# keeps (it leaves out those within `ROUTE_TIE` of a routing tie, a little
# over half of them); logits are about N(0, 1 / 16) by the weights' scales
# (the tied embedding's, `references/cohere2_moe.py` EMBED_GAIN), a
# quarter of the other families' spread. Set from chip readings at the
# cell's own size and load (PERF.md section 6, PR 35, has every reading):
# `control_gap_fresh.py` on three seeds with the int8 control on each,
# every position kept and held against `ROUTE_TIE` 2^-7 afterwards, and
# the cell's own 45 s runs. MEAN: the program 9.8e-5 to 1.56e-4, the int8
# control 1.50e-3 to 1.79e-3: the geometric middle, three times of room on
# both sides. WIDEST: the program 0.0080 to 0.0107 on the three seeds (and
# up to 0.0165 in fifteen runs read at a tie of 2^-8, where one more read
# 0.088: a routing flip 4.4e-3 from the cut, which 2^-7 leaves out); the
# control 0.0415, 0.0466, 0.177. The limit lies between, nearer the
# control: 1.8 times the program's largest at either tie and 1.4 times
# under the control's smallest, because one run over it refuses a PR
# that did nothing, and the mean refuses the control by itself.
GAP_LIMITS_BF16 = {"mean": 4.8e-4, "widest": 0.03}


def gap_limits(cfg: dict) -> dict:
    """{"mean", "widest"} for the configuration's type: bfloat16's
    readings, scaled by the type's rounding step (floored, so that
    float32 is not held to bit-identity across differently tiled
    programs). Only bfloat16's were read on the chip."""
    import jax.numpy as jnp
    eps = max(float(jnp.finfo(cfg["dtype"]).eps), 4e-5)
    return {k: v * eps / float(jnp.finfo(jnp.bfloat16).eps)
            for k, v in GAP_LIMITS_BF16.items()}


def loss_tolerance(cfg: dict) -> float:
    """Relative slack on a LOSS, as family `llama`'s (no cell of this
    family trains): 1/32 of one rounding step of `dtype`, floored."""
    import jax.numpy as jnp
    return max(float(jnp.finfo(cfg["dtype"]).eps) / 32.0, 1e-5)


# ------------------------------------------------------------ work counts

def _el(cfg: dict) -> int:
    return BYTES[cfg.get("torch_dtype", cfg.get("dtype", "bfloat16"))]


def attention_params(cfg: dict) -> int:
    """q, k, v, o of one layer."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nh * d + 2 * h * nkv * d + nh * d * h


def expert_params(cfg: dict) -> int:
    """One expert, routed or shared: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def nonrouted_matmul_params(cfg: dict) -> int:
    """What every token is multiplied by on this chip, the head aside:
    every layer's attention, shared experts and router."""
    per_layer = (attention_params(cfg)
                 + cfg["num_shared_experts"] * expert_params(cfg)
                 + cfg["hidden_size"] * cfg["num_experts"])
    return cfg["num_hidden_layers"] * per_layer


def held_pairs_per_token(cfg: dict) -> float:
    """Routed (token, expert) pairs a layer of this chip computes a token,
    in expectation under even routing: k x held / routed."""
    return cfg["num_experts_per_tok"] * cfg["experts_held"] \
        / cfg["num_experts"]


def layer_windows(cfg: dict) -> list:
    """Each run layer's window, None for a full layer."""
    return [cfg["sliding_window"] if t == SLIDING else None
            for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def kv_bytes_per_key(cfg: dict) -> int:
    """One key's K and V in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * _el(cfg)


def window_clip_share(cfg: dict, cell: dict) -> float:
    """Of the keys the cell's tokens see in a full layer, the share they
    see in a window layer: the sum of min(context, window) over the sum of
    the contexts, over every position of the cell's own cycle of
    (prompt, output) lengths (`harness/loadgen.py` `draw_sizes`; an open
    loop's cycle depends on the window, so 1,024 draws of its law stand
    for it). The driver keeps the MEAN context only, and clipping the
    mean at the window counts a window layer a tenth too high."""
    from benchmarks.harness.loadgen import draw_sizes
    traffic, w = cell["traffic"], cfg["sliding_window"]
    p, o = draw_sizes(traffic, int(traffic.get("pool", 1024)),
                      cfg["max_position_embeddings"])
    n = (p + o - 1).astype(float)          # contexts 1 .. n of a request
    m = n.clip(max=w)
    return float((m * (m + 1) / 2 + (n - m) * w).sum()
                 / (n * (n + 1) / 2).sum())


def serve_flops_per_token(cfg: dict, cell: dict, values):
    """Forward of one token the engine processed, THIS CHIP's required
    work: 2 x (the non-routed matmul parameters + the routed pairs this
    chip holds, in expectation, x an expert's parameters); the head's
    slice only where a token comes out of it
    (`values['head_tokens_per_processed']`); and attention, QK^T and PV
    over head_dim a head a layer, over the keys the token had to see: the
    mean context (`values['mean_context_tokens']`) in a full layer, and
    `window_clip_share` of it in a window layer."""
    ctx = values.get("mean_context_tokens")
    heads = values.get("head_tokens_per_processed")
    if ctx is None or heads is None:
        return None
    clip = window_clip_share(cfg, cell)
    keys = sum(ctx if w is None else ctx * clip for w in layer_windows(cfg))
    attn = 4 * keys * cfg["num_attention_heads"] * cfg["head_dim"]
    routed = cfg["num_hidden_layers"] * held_pairs_per_token(cfg) \
        * expert_params(cfg)
    return 2.0 * (nonrouted_matmul_params(cfg) + routed
                  + heads * cfg["hidden_size"] * cfg["vocab_size"]) + attn


def window_decode_kv(cfg: dict, cell: dict, values) -> dict:
    """Bytes of K and V the decode steps of the traced slice had to read,
    from the program's own counter (`attn_decode_keys`, summed over the
    slice's decode launches by `readers/trace_op_counters.py`): a
    decoding row's context in a full layer and at most the window in a
    window layer, whatever implements it."""
    keys = (values.get("slice_counters") or {}).get("attn_decode_keys")
    if keys is None:
        return {}
    return {"flops": 0.0, "bytes": float(keys) * kv_bytes_per_key(cfg)}


def window_chunk_attention(cfg: dict, cell: dict, values) -> dict:
    """What the prefill chunks' attention of the traced slice REQUIRED,
    from the program's own counter (`attn_chunk_pairs`, summed over the
    slice's chunk launches): QK^T and PV over head_dim for every head
    and every (query, key) pair a chunk had to score, a query's whole
    context in a full layer and at most the window in a window layer.
    Operations only: a chunk reads each key once a KV head and is bound
    by its products."""
    pairs = (values.get("slice_counters") or {}).get("attn_chunk_pairs")
    if pairs is None:
        return {}
    return {"flops": 4.0 * pairs * cfg["num_attention_heads"]
            * cfg["head_dim"], "bytes": 0.0}


def moe_held_experts(cfg: dict, cell: dict, values) -> dict:
    """What the held experts' products of the traced slice REQUIRED,
    whatever implements them, from the program's counters of the slice:
    the weights of every held expert that received a token, streamed once
    a launch a layer (`moe_experts_touched`), and 2 x an expert's
    parameters a routed pair held (`moe_pairs_held`): 6 x hidden x
    intermediate_size FLOPs."""
    c = values.get("slice_counters") or {}
    touched, pairs = c.get("moe_experts_touched"), c.get("moe_pairs_held")
    if touched is None or pairs is None:
        return {}
    return {"flops": 2.0 * pairs * expert_params(cfg),
            "bytes": float(touched) * expert_params(cfg) * _el(cfg)}


def weight_bytes(cfg: dict) -> int:
    """The weights this chip holds (norms aside), in the served type; the
    embedding is the head too, one matrix."""
    held = cfg["num_hidden_layers"] * cfg["experts_held"] \
        * expert_params(cfg)
    emb = cfg["hidden_size"] * cfg["vocab_size"]
    return (nonrouted_matmul_params(cfg) + held + emb) * _el(cfg)
