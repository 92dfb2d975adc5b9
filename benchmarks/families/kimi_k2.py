"""Family `kimi_k2`: Kimi-K2's layer (DeepSeek-V3's: latent attention,
sigmoid-routed experts, a shared expert) through
`paddle_tpu.models.kimi_k2`, served as ONE chip's share of an
expert-parallel deployment: the configuration says how many routed
experts this chip holds and from which (`experts_held`,
`expert_offset`); the router keeps its published width.

What the drivers need of a model and nothing else (README lists it): the
program's config object, the model in the served type, a served model's
weights from the seed for the program and again for the reference, the
plain reference (`references/kimi_k2.py`, which imports nothing of the
program), the limits with their readings, and the family's work counts.
This family brings no `paged_decode_kv` (its cache is not K and V pages),
so `paged_attn_hbm_share` stays out of its cells' lines; its decode
kernel's count is `mla_decode_latent`.
"""
from __future__ import annotations

from benchmarks.harness.work import BYTES
from benchmarks.references import kimi_k2 as reference
from benchmarks.references.kimi_k2 import (  # noqa: F401
    ROUTE_TIE, logits, loss, position_logits, position_logits_and_margins,
    token_gaps)

# keys of a configuration file that KimiK2Config takes as they are
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
              "first_k_dense_replace", "routed_scaling_factor",
              "norm_topk_prob", "rms_norm_eps", "rope_theta", "rope_scaling",
              "max_position_embeddings", "experts_held", "expert_offset")
# what the program's layer is written for: a configuration that says
# otherwise is another architecture
FIXED = {"n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "moe_layer_freq": 1,
         "num_nextn_predict_layers": 0, "attention_bias": False,
         "hidden_act": "silu", "tie_word_embeddings": False}


def config(cfg: dict):
    from paddle_tpu.models.kimi_k2 import KimiK2Config
    for k, v in FIXED.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"family kimi_k2 is written for {k} = {v!r}; "
                             f"this configuration has {cfg[k]!r}")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("latent attention has one latent for all heads; "
                         "num_key_value_heads is num_attention_heads")
    return KimiK2Config(**{k: cfg[k] for k in MODEL_KEYS})


def build_model(pcfg, dtype):
    """KimiK2ForCausalLM with parameters CREATED in `dtype`."""
    import paddle_tpu as paddle
    from paddle_tpu.models.kimi_k2 import KimiK2ForCausalLM
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        return KimiK2ForCausalLM(pcfg)
    finally:
        paddle.set_default_dtype(prev)


def load_weights(model, pcfg, cfg: dict, seed: int):
    """The served model's parameters replaced by the benchmark's own,
    drawn from the seed on the device (`references/kimi_k2.py`
    `make_weights`: the held experts stacked as the program keeps them).
    The arrays the program's constructor made are freed first: both do
    not fit."""
    sd = {k: t for k, t in model.state_dict().items()
          if not k.endswith(("rope_cos", "rope_sin"))}   # tables, not drawn
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
    the seed, a leaf at a time, under the published names."""
    return reference.LazyWeights(pcfg, seed, cfg["dtype"])


# What a served request is held to (serve driver's check), for bfloat16:
# over its served tokens, how far each token's reference logit lies under
# the reference's best, in logit units (logits are about N(0, 1) by the
# weights' scales), over the positions the reference keeps: it leaves out
# those where a held expert stands at a routing tie (`ROUTE_TIE`, about a
# fifth of them), which read the rounding of the router's input and lie a
# tenth or two under the best in the program and in a control alike. Set
# from chip readings at the cell's own size and load (PERF.md section 6,
# PR 31, has them): `control_gap_routed.py` on eight seeds with the int8
# control on each, and five 45 s runs of the cell. MEAN: program 1.68e-4
# to 2.62e-4 on twelve readings and 3.62e-4 on one (a sample is four
# requests, and one request's mean ranges from 1e-4 to 5.8e-4), the int8
# control 8.79e-4 to 1.23e-3: the limit is the geometric middle of 3.62e-4
# and 8.79e-4. WIDEST: program 0.027 to 0.053, control 0.101 to 0.236; the
# limit lies between, nearer the control's, because the largest of 2,000
# gaps has a long tail and one sound run over it refuses a PR, while the
# control has to come out not correct by one limit, which the mean sees to
# (it fails both on all eight seeds).
GAP_LIMITS_BF16 = {"mean": 5.6e-4, "widest": 0.085}


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
    """q_a, q_b, kv_a, kv_b, o of one layer."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * nh * dq
            + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * nh * (cfg["qk_nope_head_dim"]
                                          + cfg["v_head_dim"])
            + nh * cfg["v_head_dim"] * h)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def nonrouted_matmul_params(cfg: dict) -> int:
    """What every token is multiplied by on this chip, the head aside:
    attention of every layer, the dense layers' MLP, and in an expert
    layer the shared expert and the router."""
    h = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"] * 3 * h * cfg["intermediate_size"]
    sparse = sparse_layers(cfg) * (
        cfg["n_shared_experts"] * expert_params(cfg)
        + h * cfg["n_routed_experts"])
    return cfg["num_hidden_layers"] * attention_params(cfg) + dense + sparse


def held_pairs_per_token(cfg: dict) -> float:
    """Routed (token, expert) pairs an expert layer of this chip computes
    a token, in expectation under even routing: k x held / routed."""
    return cfg["num_experts_per_tok"] * cfg["experts_held"] \
        / cfg["n_routed_experts"]


def serve_flops_per_token(cfg: dict, cell: dict, values):
    """Forward of one token the engine processed, THIS CHIP's required
    work: 2 x (the non-routed matmul parameters + the routed pairs this
    chip holds, in expectation, x an expert's parameters); the head's
    slice only where a token comes out of it
    (`values['head_tokens_per_processed']`); and attention over the keys
    the token had to see in the EXPANDED form's count, QK^T over
    d_nope + d_rope and PV over d_v a head a layer
    (`values['mean_context_tokens']`). The absorbed form's extra
    operations (it attends in the latent's width) and the latent's
    expansion count nothing: they are the program's choice."""
    ctx = values.get("mean_context_tokens")
    heads = values.get("head_tokens_per_processed")
    if ctx is None or heads is None:
        return None
    attn = 2 * cfg["num_hidden_layers"] * ctx * cfg["num_attention_heads"] \
        * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
           + cfg["v_head_dim"])
    routed = sparse_layers(cfg) * held_pairs_per_token(cfg) \
        * expert_params(cfg)
    return 2.0 * (nonrouted_matmul_params(cfg) + routed
                  + heads * cfg["hidden_size"] * cfg["vocab_size"]) + attn


def latent_bytes_per_token(cfg: dict) -> int:
    """One token's cache entries over every layer: kv_lora_rank +
    qk_rope_head_dim values each, K and V being views of them."""
    return cfg["num_hidden_layers"] * _el(cfg) \
        * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def mla_decode_latent(cfg: dict, cell: dict, values) -> dict:
    """Bytes of latent cache the decode steps of the traced slice had to
    read: every decoding row reads its whole context once a step
    (`values['slice_decode_context_tokens']`, the driver's sum over the
    slice's decode steps and their rows of the row's context length)."""
    n = values.get("slice_decode_context_tokens")
    if n is None:
        return {}
    return {"flops": 0.0, "bytes": float(n) * latent_bytes_per_token(cfg)}


def moe_held_experts(cfg: dict, cell: dict, values) -> dict:
    """What the held experts' products of the traced slice REQUIRED,
    whatever implements them, from the engine's counters
    (`readers/trace_op_counters.py` hands the window's, scaled to the
    slice): the weights of every held expert that received a token,
    streamed once a launch a layer (`moe_experts_touched`), and 2 x an
    expert's parameters a routed pair held (`moe_pairs_held`): 6 x hidden
    x moe_intermediate_size FLOPs."""
    c = values.get("slice_counters") or {}
    touched, pairs = c.get("moe_experts_touched"), c.get("moe_pairs_held")
    if touched is None or pairs is None:
        return {}
    return {"flops": 2.0 * pairs * expert_params(cfg),
            "bytes": float(touched) * expert_params(cfg) * _el(cfg)}


def weight_bytes(cfg: dict) -> int:
    """The weights this chip holds (norms aside), in the served type."""
    held = sparse_layers(cfg) * cfg["experts_held"] * expert_params(cfg)
    emb = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return (nonrouted_matmul_params(cfg) + held + emb) * _el(cfg)
