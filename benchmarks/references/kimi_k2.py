"""The plain reference of Kimi-K2's layer (DeepSeek-V3's, key for key):
jax.numpy, float32 throughout, Precision.HIGHEST, EXPANDED attention only
(keys and values of every head made from the latent, no absorbed form),
no kernels, no cache, no sorting of tokens by expert; independent of the
package's layers. It is given the same SHARE as the program: it routes
over all `n_routed_experts`, adds the part of the experts
`expert_offset .. expert_offset + experts_held` and the shared expert, and
what the absent experts would have added is left out.

It runs LAYER BY LAYER, a sequence at a time, and inside a layer a group
of heads and an expert at a time, so that only a few matrices are upcast
to float32 at once: at 64 heads and 5,120 positions the float32 scores of
a whole layer are 6.7 GB. `cfg` is any object with the published keys as
attributes. `weights` maps the PUBLISHED parameter names to arrays, every
expert's three matrices leaves of their own (so that a control lowers
each a column at a time).

The weights are the benchmark's own: `leaves` gives every parameter's
name, shape and scale from the configuration alone, `make_weights` draws
them from `--seed` for the program (an expert layer's held experts
stacked, as the program keeps them) and `LazyWeights` draws the same
values again a leaf at a time for the reference.

One departure from the published code, which the program shares: RoPE
pairs lanes interleaved (2i, 2i + 1) where the published code re-lays the
pairs out in halves before rotating; every q_pe . k_pe is the same.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.references.llama import (HI, _embed, _head, _leaf,
                                         _leaves_from, _norm, _seed_words)

BRANCH_GAIN = 0.5          # as references/llama.py: the stream keeps the
#                            embedding's share of its variance
BIAS_STD = 0.05            # e_score_correction_bias: a quarter of the
#                            scores' spread (0.21), so some selections change
HEAD_GROUP = 8             # heads scored at once
# A position is left out of `token_gaps` where, in any expert layer, a held
# expert's score + bias lies closer than this to the selection's cut (above
# the first score left out if it was chosen, under the last score chosen if
# it was not): there the program's choice of that expert is decided by the
# rounding of the router's input and not by its precision as a whole, and
# the token served after a flipped expert lies a tenth or two of a logit
# under the reference's best, in the program and in an int8 control alike.
# bfloat16's own step at the scores' size (2^-8 in [0.5, 1), where the top
# scores lie). On the chip (PERF.md section 6, PR 31: 14,763 positions of
# six seeds) a served token over 0.05 under the best was seen at distances
# up to 1.74e-3 and at none beyond; the rate falls like a normal tail of
# sigma 9e-4, so this is over four of them. Only the REFERENCE's own
# float32 scores decide it: nothing of the program is read.
ROUTE_TIE = 2.0 ** -8
ATTN_LEAVES = ("input_layernorm.weight", "self_attn.q_a_proj.weight",
               "self_attn.q_a_layernorm.weight", "self_attn.q_b_proj.weight",
               "self_attn.kv_a_proj_with_mqa.weight",
               "self_attn.kv_a_layernorm.weight",
               "self_attn.kv_b_proj.weight", "self_attn.o_proj.weight",
               "post_attention_layernorm.weight")
MLP_LEAVES = ("gate_proj.weight", "up_proj.weight", "down_proj.weight")


def held_range(cfg):
    n = cfg.n_routed_experts if cfg.experts_held is None else cfg.experts_held
    return range(cfg.expert_offset, cfg.expert_offset + n)


def layer_leaf_names(cfg, n: int):
    """The short names of layer n's leaves, in drawing order."""
    names = list(ATTN_LEAVES)
    if n < cfg.first_k_dense_replace:
        return names + [f"mlp.{k}" for k in MLP_LEAVES]
    names += ["mlp.gate.weight", "mlp.gate.e_score_correction_bias"]
    names += [f"mlp.experts.{e}.{k}" for e in held_range(cfg)
              for k in MLP_LEAVES]
    return names + [f"mlp.shared_experts.{k}" for k in MLP_LEAVES]


def leaves(cfg) -> dict:
    """{name: (shape, std)} of every parameter, matrices as (in, out);
    std 0 marks a norm's weight, which is 1. Every matrix maps unit
    variance to unit variance (std 1 / sqrt(in)) but the branches' last
    matrices (BRANCH_GAIN) and the router's bias (BIAS_STD): q and k then
    have unit entries, and scores of (q . k) / sqrt(192) x m^2 a spread
    of about 2: attention that looks at what the keys hold."""
    h, v = cfg.hidden_size, cfg.vocab_size
    nh, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    unit = lambda n: 1.0 / math.sqrt(n)

    def mlp(prefix, i):
        return {f"{prefix}gate_proj.weight": ((h, i), unit(h)),
                f"{prefix}up_proj.weight": ((h, i), unit(h)),
                f"{prefix}down_proj.weight": ((i, h), BRANCH_GAIN * unit(i))}

    attn = {"input_layernorm.weight": ((h,), 0.0),
            "self_attn.q_a_proj.weight": ((h, cfg.q_lora_rank), unit(h)),
            "self_attn.q_a_layernorm.weight": ((cfg.q_lora_rank,), 0.0),
            "self_attn.q_b_proj.weight":
                ((cfg.q_lora_rank, nh * dq), unit(cfg.q_lora_rank)),
            "self_attn.kv_a_proj_with_mqa.weight":
                ((h, rank + cfg.qk_rope_head_dim), unit(h)),
            "self_attn.kv_a_layernorm.weight": ((rank,), 0.0),
            "self_attn.kv_b_proj.weight":
                ((rank, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                 unit(rank)),
            "self_attn.o_proj.weight":
                ((nh * cfg.v_head_dim, h),
                 BRANCH_GAIN * unit(nh * cfg.v_head_dim)),
            "post_attention_layernorm.weight": ((h,), 0.0)}
    sparse = {"mlp.gate.weight": ((h, cfg.n_routed_experts), unit(h)),
              "mlp.gate.e_score_correction_bias":
                  ((cfg.n_routed_experts,), BIAS_STD)}
    for e in held_range(cfg):
        sparse.update(mlp(f"mlp.experts.{e}.", cfg.moe_intermediate_size))
    sparse.update(mlp("mlp.shared_experts.",
                      cfg.moe_intermediate_size * cfg.n_shared_experts))
    dense = mlp("mlp.", cfg.intermediate_size)
    out = {"model.embed_tokens.weight": ((v, h), 1.0)}
    for n in range(cfg.num_hidden_layers):
        kind = {**attn, **(dense if n < cfg.first_k_dense_replace
                           else sparse)}
        out.update({f"model.layers.{n}.{k}": kind[k]
                    for k in layer_leaf_names(cfg, n)})
    out["model.norm.weight"] = ((h,), 0.0)
    out["lm_head.weight"] = ((h, v), unit(h))
    return out


def program_leaves(cfg) -> dict:
    """{name: shape} under the PROGRAM's names: as `leaves`, but an
    expert layer's held experts stacked by kind."""
    n_held, out = len(held_range(cfg)), {}
    for name, (shape, _) in leaves(cfg).items():
        pre, _, rest = name.partition(".mlp.experts.")
        if rest:
            out[f"{pre}.mlp.experts.{rest.split('.')[1]}"] = \
                (n_held,) + shape
        else:
            out[name] = shape
    return out


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _stacked_from(lo, hi, k0, spec, dtype, n_stack):
    """`n_stack` consecutive groups of three equal-shaped leaves (one
    expert's gate, up, down each) from leaf k0 on, stacked by kind:
    three arrays (n_stack, ...)."""
    got = [_leaf(lo, hi, k0 + j, shape, std, dtype)
           for j, (shape, std) in enumerate(spec * n_stack)]
    return [jnp.stack(got[i::3]) for i in range(3)]


def make_weights(cfg, seed: int, dtype) -> dict:
    """Every parameter from the seed, made on the device in the type it is
    served in, under the PROGRAM's names: as `leaves` names them, but an
    expert layer's held experts stacked into `mlp.experts.gate_proj`,
    `.up_proj` (E_held, hidden, I) and `.down_proj` (E_held, I, hidden).
    A few jitted calls a layer (`k0` is traced, so the expert layers
    share their compiled programs)."""
    spec = leaves(cfg)
    index = {n: k for k, n in enumerate(spec)}
    lo_hi, dtype = _seed_words(seed), jnp.dtype(dtype)
    n_held = len(held_range(cfg))

    def run(names):
        got = _leaves_from(*lo_hi, index[names[0]],
                           tuple(spec[x] for x in names), dtype)
        return dict(zip(names, got))

    out = run(["model.embed_tokens.weight"])
    for n in range(cfg.num_hidden_layers):
        pre = f"model.layers.{n}."
        names = [pre + k for k in layer_leaf_names(cfg, n)]
        first = pre + f"mlp.experts.{cfg.expert_offset}.gate_proj.weight"
        if first not in index:
            out.update(run(names))
            continue
        a = names.index(first)
        b = a + 3 * n_held
        out.update(run(names[:a]))
        stacked = _stacked_from(*lo_hi, index[first],
                                tuple(spec[x] for x in names[a:a + 3]),
                                dtype, n_held)
        out.update(zip((pre + "mlp.experts.gate_proj",
                        pre + "mlp.experts.up_proj",
                        pre + "mlp.experts.down_proj"), stacked))
        out.update(run(names[b:]))
    out.update(run(["model.norm.weight"]))
    out.update(run(["lm_head.weight"]))
    return out


class LazyWeights:
    """The same values under the published names, each leaf drawn when it
    is asked for (one small compiled program a shape)."""

    def __init__(self, cfg, seed: int, dtype):
        self.spec = leaves(cfg)
        self.index = {n: k for k, n in enumerate(self.spec)}
        self.words, self.dtype = _seed_words(seed), jnp.dtype(dtype)
        self.draw = jax.jit(_leaf, static_argnums=(3, 4, 5))

    def __getitem__(self, name):
        shape, std = self.spec[name]
        return self.draw(*self.words, self.index[name], shape, std,
                         self.dtype)


# ---------------------------------------------------------------- forward
def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn(cfg, s):
    """cos, sin (S, d_rope / 2) of positions 0 .. S - 1 and the factor m^2
    on the scores, after the published YaRN: frequencies that turn fewer
    than `beta_slow` times over the original context are divided by
    `factor`, those that turn more than `beta_fast` times kept, a linear
    ramp between."""
    d, base, rs = cfg.qk_rope_head_dim, float(cfg.rope_theta), \
        cfg.rope_scaling
    factor = float(rs["factor"])
    orig = rs["original_max_position_embeddings"]
    pos_freq = base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def dim_of(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    high = high + 0.001 if low == high else high
    keep = 1.0 - jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                          / (high - low), 0, 1)
    inv = (1.0 / (factor * pos_freq)) * (1 - keep) + (1.0 / pos_freq) * keep
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    table = _mscale(factor, rs["mscale"]) \
        / _mscale(factor, rs["mscale_all_dim"])
    m = _mscale(factor, rs["mscale_all_dim"])
    return jnp.cos(ang) * table, jnp.sin(ang) * table, m * m


def _rope(t, cos, sin):
    """t (S, ..., D) rotated as interleaved pairs at positions 0 .. S-1."""
    shape = (t.shape[0],) + (1,) * (t.ndim - 2) + (cos.shape[-1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    t1, t2 = t[..., 0::2], t[..., 1::2]
    return jnp.stack([t1 * c - t2 * s, t2 * c + t1 * s],
                     axis=-1).reshape(t.shape)


class _Static:
    """The configuration as a hashable static argument of a jitted layer."""

    def __init__(self, cfg):
        self.cfg = cfg
        rs = cfg.rope_scaling
        self.key = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                    cfg.qk_rope_head_dim, cfg.v_head_dim,
                    cfg.n_routed_experts, cfg.num_experts_per_tok,
                    cfg.routed_scaling_factor, cfg.norm_topk_prob,
                    cfg.rms_norm_eps, cfg.rope_theta,
                    tuple(sorted(rs.items())), cfg.expert_offset,
                    cfg.experts_held)

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return self.key == other.key


@functools.partial(jax.jit, static_argnums=(2,))
def _attention(x, w, st):
    """x + attention(norm(x)) for ONE sequence x (S, H) in float32."""
    cfg = st.cfg
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    s = x.shape[0]
    nh, dn, dr, dv, rank = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                            cfg.qk_rope_head_dim, cfg.v_head_dim,
                            cfg.kv_lora_rank)
    eps = float(cfg.rms_norm_eps)
    cos, sin, m2 = _yarn(cfg, s)
    h = _norm(x, w["input_layernorm.weight"], eps)
    c_q = _norm(jnp.dot(h, w["self_attn.q_a_proj.weight"], precision=HI),
                w["self_attn.q_a_layernorm.weight"], eps)
    q = jnp.dot(c_q, w["self_attn.q_b_proj.weight"],
                precision=HI).reshape(s, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], cos, sin)
    kv = jnp.dot(h, w["self_attn.kv_a_proj_with_mqa.weight"], precision=HI)
    c_kv = _norm(kv[:, :rank], w["self_attn.kv_a_layernorm.weight"], eps)
    k_pe = _rope(kv[:, rank:], cos, sin)               # one for all heads
    kvb = jnp.dot(c_kv, w["self_attn.kv_b_proj.weight"],
                  precision=HI).reshape(s, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    scale = (dn + dr) ** -0.5 * m2
    causal = jnp.tril(jnp.ones((s, s), bool))[None]

    def heads(args):                                   # a group of heads
        qn, qp, kn, vv = args                          # (S, g, d)
        sc = jnp.einsum("qhd,khd->hqk", qn, kn, precision=HI) \
            + jnp.einsum("qhd,kd->hqk", qp, k_pe, precision=HI)
        p = jax.nn.softmax(jnp.where(causal, sc * scale, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vv, precision=HI)

    g = math.gcd(nh, HEAD_GROUP)
    split = lambda t: jnp.moveaxis(
        t.reshape(s, nh // g, g, t.shape[-1]), 1, 0)
    o = jax.lax.map(heads, (split(q_nope), split(q_pe), split(k_nope),
                            split(v)))                 # (nh/g, S, g, dv)
    o = jnp.moveaxis(o, 0, 1).reshape(s, nh * dv)
    return x + jnp.dot(o, w["self_attn.o_proj.weight"], precision=HI)


@functools.partial(jax.jit, static_argnums=(2,))
def _swiglu(x, w, eps):
    """(norm(x), its SwiGLU) under the post-attention norm: the dense
    layer, and an expert layer's shared expert."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _norm(x, w["norm"], eps)
    g = jnp.dot(h, w["gate_proj.weight"], precision=HI)
    u = jnp.dot(h, w["up_proj.weight"], precision=HI)
    return h, jnp.dot(jax.nn.silu(g) * u, w["down_proj.weight"],
                      precision=HI)


@functools.partial(jax.jit, static_argnums=(3,))
def _route(h, w_gate, bias, st):
    """Sigmoid scores over all experts; the top k of score + bias; the
    weights the scores at the chosen, over their sum, times the factor.
    Returns (T, n_routed_experts) float32: a token's weight on each
    expert, 0 where it was not chosen; and (T,) float32: how far the
    nearest HELD expert's score + bias lies from the selection's cut
    (a chosen one above the first score left out, another under the last
    score chosen). Experts this chip does not hold change nothing of its
    share when they flip, so they are not looked at."""
    cfg = st.cfg
    k = cfg.num_experts_per_tok
    s = jax.nn.sigmoid(jnp.dot(h, w_gate.astype(jnp.float32), precision=HI))
    sb = s + bias.astype(jnp.float32)
    top, idx = jax.lax.top_k(sb, min(k + 1, sb.shape[-1]))
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(True)
    wt = jnp.where(chosen, s, 0.0)
    if cfg.norm_topk_prob:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    held = slice(held_range(cfg).start, held_range(cfg).stop)
    last_in, first_out = top[:, k - 1:k], top[:, -1:]
    near = jnp.where(chosen[:, held], sb[:, held] - first_out,
                     last_in - sb[:, held])
    return wt * cfg.routed_scaling_factor, jnp.min(near, axis=-1)


@jax.jit
def _expert(h, wt, gate, up, down):
    """One expert's weighted part: wt (T,) is 0 where it was not chosen."""
    f32 = lambda a: a.astype(jnp.float32)
    g = jnp.dot(h, f32(gate), precision=HI)
    u = jnp.dot(h, f32(up), precision=HI)
    return wt[:, None] * jnp.dot(jax.nn.silu(g) * u, f32(down), precision=HI)


def _layer(x, weights, cfg, st, n):
    """(x after layer n, each position's distance from a routing tie
    (T,), +inf in a dense layer)."""
    pre = f"model.layers.{n}."
    x = _attention(x, {k: weights[pre + k] for k in ATTN_LEAVES}, st)
    eps = float(cfg.rms_norm_eps)
    norm = weights[pre + "post_attention_layernorm.weight"]
    if n < cfg.first_k_dense_replace:
        _, y = _swiglu(x, {"norm": norm, **{
            k: weights[pre + "mlp." + k] for k in MLP_LEAVES}}, eps)
        return x + y, jnp.full(x.shape[:1], jnp.inf)
    h, y = _swiglu(x, {"norm": norm, **{
        k: weights[pre + "mlp.shared_experts." + k] for k in MLP_LEAVES}},
        eps)
    wt, near = _route(h, weights[pre + "mlp.gate.weight"],
                      weights[pre + "mlp.gate.e_score_correction_bias"], st)
    for e in held_range(cfg):
        y = y + _expert(h, wt[:, e], *(
            weights[pre + f"mlp.experts.{e}.{k}"] for k in MLP_LEAVES))
    return x + y, near


def _sequence(weights, cfg, st, ids):
    """Float32 logits (S, V) of ONE sequence's full causal forward, and
    (S,) the least distance from a routing tie over its expert layers."""
    x = _embed(weights["model.embed_tokens.weight"], ids)
    margin = jnp.full(ids.shape, jnp.inf)
    for n in range(cfg.num_hidden_layers):
        x, near = _layer(x, weights, cfg, st, n)
        margin = jnp.minimum(margin, near)
    return _head(x, weights["model.norm.weight"], weights["lm_head.weight"],
                 float(cfg.rms_norm_eps)), margin


def logits(weights, cfg, ids):
    """Float32 logits (B, S, V) of the full causal forward over `ids`, a
    sequence at a time."""
    ids = jnp.asarray(ids, jnp.int32)
    st = _Static(cfg)
    return jnp.stack([_sequence(weights, cfg, st, row)[0] for row in ids])


@jax.jit
def _next_token_loss(lg, labels):
    logp = jax.nn.log_softmax(lg[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1))


def loss(weights, cfg, ids) -> float:
    """Next-token loss of a batch with labels = ids (mean of the
    sequences' means: they are of one length)."""
    ids = jnp.asarray(ids, jnp.int32)
    rows = [float(_next_token_loss(logits(weights, cfg, ids[i:i + 1]),
                                   ids[i:i + 1]))
            for i in range(ids.shape[0])]
    return sum(rows) / len(rows)


def position_logits_and_margins(weights, cfg, prompt, output, pad_to=None):
    """Float32 logits (len(output), V) at the positions that predict each
    token of `output` after `prompt`, and those positions' distances from
    a routing tie (`_route`); `pad_to` pads on the right (causal: earlier
    positions do not see it) so that requests of many lengths share one
    compiled forward."""
    import numpy as np
    seq = list(prompt) + list(output)
    n = len(seq)
    if pad_to is not None and pad_to > n:
        seq = seq + [0] * (pad_to - n)
    lg, margin = _sequence(weights, cfg, _Static(cfg),
                           jnp.asarray(seq, jnp.int32))
    at = slice(len(prompt) - 1, n - 1)
    return np.asarray(lg[at]), np.asarray(margin[at])


def position_logits(weights, cfg, prompt, output, pad_to=None):
    return position_logits_and_margins(weights, cfg, prompt, output,
                                       pad_to)[0]


def token_gaps(weights, cfg, prompt, output, pad_to=None):
    """For a request served greedily: how far each emitted token's
    reference logit lies under the reference maximum at its position,
    over the positions that stand clear of a routing tie (`ROUTE_TIE`).
    Returns (gaps, max |logit|) over the emitted positions."""
    import numpy as np
    lg, margin = position_logits_and_margins(weights, cfg, prompt, output,
                                             pad_to)
    out = np.asarray(output)
    gaps = lg.max(axis=-1) - lg[np.arange(len(out)), out]
    return gaps[margin >= ROUTE_TIE], float(np.abs(lg).max())
