"""The plain reference of Cohere2-MoE's layer (`model_type` cohere2_moe,
Command A+), key for key: jax.numpy, float32 throughout,
Precision.HIGHEST, no kernels, no cache, no pages, no sorting of tokens
by expert; independent of the package's layers. It is given the same
SHARE as the program: it routes over all `num_experts`, adds the part of
the experts `expert_offset .. expert_offset + experts_held` and the mean
of the shared experts, and what the absent experts would have added is
left out.

One layer on ONE sequence x (S, hidden):

  h       = (x - mean) / sqrt(var + layer_norm_eps) * w        one norm
  q, k, v = h W_q (heads x head_dim), h W_k, h W_v (KV heads x head_dim)
  window layer (`layer_types[n]` sliding_attention): q, k rotated as
            interleaved pairs (2i, 2i + 1) at their positions with
            rope_theta; query i sees key j iff i - sliding_window < j <= i
  full layer: no position encoding; query i sees key j iff j <= i
  a       = softmax(q k^T head_dim^-0.5) v, heads concatenated, W_o
  s       = sigmoid(h W_g); the top k; weights s at the chosen over their
            sum; routed = sum over the HELD chosen experts of w_e E_e(h)
  shared  = (1 / num_shared_experts) sum_s S_s(h), each S_s a SwiGLU MLP
            of an expert's width, kept as leaves of their own
  x_out   = x + a + routed + shared                      the parallel block

and logits = LayerNorm(x) E^T x logit_scale with E the embedding (tied).

It runs LAYER BY LAYER, inside a layer ONE HEAD and one expert at a time,
so that 13,056 positions fit: one head's float32 scores are 0.68 GB.
`cfg` is any object with the published keys as attributes (and
`experts_held`, `expert_offset`). `weights` maps parameter names to
arrays, every expert's and every shared expert's three matrices leaves of
their own (so that a control lowers each a column at a time).

Departures from the published model, each shared with the program:
  * `shared_expert_combination_strategy` "average" is READ as the mean of
    the shared experts' outputs, added to the routed sum (the published
    code is not to hand; the configuration lists it under `assumed`);
  * `intermediate_size` is read as ONE expert's width (the catalog's
    inference), for routed and shared experts alike;
  * the weights are the benchmark's own, random from `--seed`: `leaves`
    gives every parameter's name, shape and scale from the configuration
    alone (the constants below say why each scale: the tied embedding's
    makes logits about N(0, 1 / 16), a quarter of the other families'
    spread).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.references.llama import (HI, _embed, _leaf, _leaves_from,
                                         _next_token_loss, _seed_words)

BRANCH_GAIN = 0.5          # as references/llama.py: a branch's last matrix
# q and k entries have variance 2, so that scores (q . k) / sqrt(head_dim)
# spread by about 2 (as `references/kimi_k2.py`'s): attention that looks at
# what the keys hold. At a spread of 1 a query weighs thousands of keys
# almost evenly and the layer adds their mean: next to nothing at 4,096
# keys, and at toy width a slowly moving mean under which the served
# token stood still for dozens of steps (PR 35, the first toy runs).
QK_GAIN = 2.0 ** 0.5
# the mean of four shared experts at gain 1 has the variance of ONE
# branch at BRANCH_GAIN: the shared experts keep a branch's share of the
# stream, which is the part that follows the CURRENT token
SHARED_GAIN = 1.0
# the tied embedding's entries, x 1 / sqrt(hidden): logits are then about
# N(0, 1 / 16). It is the head too, and the residual stream carries a
# token's own embedding to it: with unit entries that token stood 40
# logit spreads above the rest and every request repeated its last prompt
# token for ever; at 1 / sqrt(hidden) still about two spreads; at a
# quarter of that, half of one
EMBED_GAIN = 0.25
# A position is left out of `token_gaps` where, in any layer, a held
# expert's score lies closer than this to the selection's cut: there the
# program's choice of that expert is decided by the rounding of the
# router's input. Only the REFERENCE's own float32 scores decide it. TWO
# of bfloat16's steps at the scores' size (a sigmoid's, in [0.5, 1)):
# at one step, `references/kimi_k2.py`'s value, the chip served a token
# 0.088 under the best through an expert chosen at a distance of 4.4e-3
# from the cut (PERF.md section 6, PR 35), as far as the int8 control's
# flips reach, and no limit on the widest gap lay between the two; at
# two, every reading kept (`chiprun_out/control_gap_routed.npz`) parts
# them: the program 0.008 to 0.011, the control 0.042 to 0.177. 45 % of
# the positions stay.
ROUTE_TIE = 2.0 ** -7
SLIDING = "sliding_attention"
ATTN_LEAVES = ("input_layernorm.weight", "self_attn.q_proj.weight",
               "self_attn.k_proj.weight", "self_attn.v_proj.weight",
               "self_attn.o_proj.weight")
MLP_LEAVES = ("gate_proj.weight", "up_proj.weight", "down_proj.weight")


def held_range(cfg):
    n = cfg.num_experts if cfg.experts_held is None else cfg.experts_held
    return range(cfg.expert_offset, cfg.expert_offset + n)


def layer_leaf_names(cfg):
    """The short names of a layer's leaves, in drawing order."""
    return (list(ATTN_LEAVES) + ["mlp.gate.weight"]
            + [f"mlp.experts.{e}.{k}" for e in held_range(cfg)
               for k in MLP_LEAVES]
            + [f"mlp.shared_experts.{s}.{k}"
               for s in range(cfg.num_shared_experts) for k in MLP_LEAVES])


def leaves(cfg) -> dict:
    """{name: (shape, std)} of every parameter, matrices as (in, out);
    std 0 marks a norm's weight, which is 1. Every matrix maps unit
    variance to unit variance (std 1 / sqrt(in)) but the branches' last
    matrices (BRANCH_GAIN; the shared experts' SHARED_GAIN), the q and k
    projections (QK_GAIN) and the tied embedding (EMBED_GAIN)."""
    h, v, i = cfg.hidden_size, cfg.vocab_size, cfg.intermediate_size
    nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    unit = lambda n: 1.0 / math.sqrt(n)
    layer = {"input_layernorm.weight": ((h,), 0.0),
             "self_attn.q_proj.weight": ((h, nh * d), QK_GAIN * unit(h)),
             "self_attn.k_proj.weight": ((h, nkv * d), QK_GAIN * unit(h)),
             "self_attn.v_proj.weight": ((h, nkv * d), unit(h)),
             "self_attn.o_proj.weight":
                 ((nh * d, h), BRANCH_GAIN * unit(nh * d)),
             "mlp.gate.weight": ((h, cfg.num_experts), unit(h))}
    mlp = {"gate_proj.weight": ((h, i), unit(h)),
           "up_proj.weight": ((h, i), unit(h)),
           "down_proj.weight": ((i, h), BRANCH_GAIN * unit(i))}
    shared = dict(mlp, **{"down_proj.weight": (
        (i, h), SHARED_GAIN * unit(i))})
    for name in layer_leaf_names(cfg)[len(layer):]:
        kind = shared if ".shared_experts." in name else mlp
        layer[name] = kind[name.split(".", 3)[3]]
    out = {"model.embed_tokens.weight": ((v, h), EMBED_GAIN * unit(h))}
    for n in range(cfg.num_hidden_layers):
        out.update({f"model.layers.{n}.{k}": layer[k]
                    for k in layer_leaf_names(cfg)})
    out["model.norm.weight"] = ((h,), 0.0)
    return out


def program_leaves(cfg) -> dict:
    """{name: shape} under the PROGRAM's names: as `leaves`, but a layer's
    held experts stacked by kind (E_held, ...), and its shared experts
    side by side in ONE MLP: gate and up joined along the width, down
    along its rows."""
    n_held, n_sh, out = len(held_range(cfg)), cfg.num_shared_experts, {}
    for name, (shape, _) in leaves(cfg).items():
        pre, _, rest = name.partition(".mlp.experts.")
        if rest:
            out[f"{pre}.mlp.experts.{rest.split('.')[1]}"] = \
                (n_held,) + shape
            continue
        pre, _, rest = name.partition(".mlp.shared_experts.")
        if rest:
            kind = rest.split(".", 1)[1]
            wide = 0 if kind.startswith("down") else 1
            out[f"{pre}.mlp.shared_experts.{kind}"] = tuple(
                n * n_sh if a == wide else n for a, n in enumerate(shape))
        else:
            out[name] = shape
    return out


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _grouped_from(lo, hi, k0, spec, dtype, n_group, how):
    """`n_group` consecutive groups of three equal-shaped leaves (one
    expert's gate, up, down each) from leaf k0 on, joined by kind:
    stacked on a new axis (`how` "stack": the held experts) or side by
    side ("wide": the shared experts as one MLP)."""
    got = [_leaf(lo, hi, k0 + j, shape, std, dtype)
           for j, (shape, std) in enumerate(spec * n_group)]
    if how == "stack":
        return [jnp.stack(got[i::3]) for i in range(3)]
    return [jnp.concatenate(got[i::3], axis=0 if i == 2 else 1)
            for i in range(3)]


def make_weights(cfg, seed: int, dtype) -> dict:
    """Every parameter from the seed, made on the device in the type it is
    served in, under the PROGRAM's names (`program_leaves`). A few jitted
    calls a layer (`k0` is traced, so the layers share their compiled
    programs)."""
    spec = leaves(cfg)
    index = {n: k for k, n in enumerate(spec)}
    lo_hi, dtype = _seed_words(seed), jnp.dtype(dtype)
    n_held, n_sh = len(held_range(cfg)), cfg.num_shared_experts

    def run(names):
        got = _leaves_from(*lo_hi, index[names[0]],
                           tuple(spec[x] for x in names), dtype)
        return dict(zip(names, got))

    out = run(["model.embed_tokens.weight"])
    for n in range(cfg.num_hidden_layers):
        pre = f"model.layers.{n}."
        names = [pre + k for k in layer_leaf_names(cfg)]
        a = names.index(pre + f"mlp.experts.{cfg.expert_offset}."
                        + MLP_LEAVES[0])
        b = a + 3 * n_held
        out.update(run(names[:a]))
        three = lambda at: tuple(spec[x] for x in names[at:at + 3])
        out.update(zip(
            (pre + "mlp.experts." + k.split(".")[0] for k in MLP_LEAVES),
            _grouped_from(*lo_hi, index[names[a]], three(a), dtype, n_held,
                          "stack")))
        out.update(zip(
            (pre + "mlp.shared_experts." + k for k in MLP_LEAVES),
            _grouped_from(*lo_hi, index[names[b]], three(b), dtype, n_sh,
                          "wide")))
    out.update(run(["model.norm.weight"]))
    return out


class LazyWeights:
    """The same values under `leaves`' names, each leaf drawn when it is
    asked for (one small compiled program a shape)."""

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
def _layer_norm(x, w, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + eps) * w


def _rope(t, theta):
    """t (S, heads, D) rotated as interleaved pairs at positions 0..S-1."""
    s, _, d = t.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    t1, t2 = t[..., 0::2], t[..., 1::2]
    return jnp.stack([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                     axis=-1).reshape(t.shape)


class _Static:
    """The configuration as a hashable static argument of a jitted layer."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.key = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim, cfg.num_experts,
                    cfg.num_experts_per_tok, cfg.num_shared_experts,
                    cfg.norm_topk_prob, cfg.layer_norm_eps, cfg.rope_theta,
                    cfg.sliding_window, cfg.expert_offset, cfg.experts_held)

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return self.key == other.key


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention(x, w, st, sliding):
    """(h, attention(h)) for ONE sequence x (S, H) in float32, h the
    layer's normed input, which the experts read too."""
    cfg = st.cfg
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    s = x.shape[0]
    nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    h = _layer_norm(x, w["input_layernorm.weight"], float(cfg.layer_norm_eps))
    q = jnp.dot(h, w["self_attn.q_proj.weight"],
                precision=HI).reshape(s, nh, d)
    k = jnp.dot(h, w["self_attn.k_proj.weight"],
                precision=HI).reshape(s, nkv, d)
    v = jnp.dot(h, w["self_attn.v_proj.weight"],
                precision=HI).reshape(s, nkv, d)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = j <= i
    if sliding:
        q, k = _rope(q, float(cfg.rope_theta)), _rope(k, float(cfg.rope_theta))
        seen = seen & (j > i - cfg.sliding_window)
    k_heads, v_heads = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)

    def head(args):                                    # one query head
        qh, kv = args                                  # (S, d), its KV head
        sc = jnp.dot(qh, k_heads[kv].T, precision=HI) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.dot(p, v_heads[kv], precision=HI)

    o = jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                           jnp.arange(nh) // (nh // nkv)))   # (nh, S, d)
    o = jnp.moveaxis(o, 0, 1).reshape(s, nh * d)
    return h, jnp.dot(o, w["self_attn.o_proj.weight"], precision=HI)


@functools.partial(jax.jit, static_argnums=(2,))
def _route(h, w_gate, st):
    """Sigmoid scores over all experts; the top k; the weights the scores
    at the chosen over their sum. Returns (T, num_experts) float32: a
    token's weight on each expert, 0 where it was not chosen; and (T,)
    float32: how far the nearest HELD expert's score lies from the
    selection's cut (a chosen one above the first score left out,
    another under the last score chosen)."""
    cfg = st.cfg
    k = cfg.num_experts_per_tok
    s = jax.nn.sigmoid(jnp.dot(h, w_gate.astype(jnp.float32), precision=HI))
    top, idx = jax.lax.top_k(s, min(k + 1, s.shape[-1]))
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(True)
    wt = jnp.where(chosen, s, 0.0)
    if cfg.norm_topk_prob:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    held = slice(held_range(cfg).start, held_range(cfg).stop)
    last_in, first_out = top[:, k - 1:k], top[:, -1:]
    near = jnp.where(chosen[:, held], s[:, held] - first_out,
                     last_in - s[:, held])
    return wt, jnp.min(near, axis=-1)


@jax.jit
def _expert(h, wt, gate, up, down):
    """One expert's weighted part: wt (T,) is 0 where it was not chosen
    (a shared expert's is 1 / num_shared_experts everywhere)."""
    f32 = lambda a: a.astype(jnp.float32)
    g = jnp.dot(h, f32(gate), precision=HI)
    u = jnp.dot(h, f32(up), precision=HI)
    return wt[:, None] * jnp.dot(jax.nn.silu(g) * u, f32(down), precision=HI)


def _layer(x, weights, cfg, st, n):
    """(x after layer n, each position's distance from a routing tie)."""
    pre = f"model.layers.{n}."
    h, y = _attention(x, {k: weights[pre + k] for k in ATTN_LEAVES}, st,
                      cfg.layer_types[n] == SLIDING)
    wt, near = _route(h, weights[pre + "mlp.gate.weight"], st)
    for e in held_range(cfg):
        y = y + _expert(h, wt[:, e], *(
            weights[pre + f"mlp.experts.{e}.{k}"] for k in MLP_LEAVES))
    mean = jnp.full(x.shape[:1], 1.0 / cfg.num_shared_experts)
    for s in range(cfg.num_shared_experts):
        y = y + _expert(h, mean, *(
            weights[pre + f"mlp.shared_experts.{s}.{k}"]
            for k in MLP_LEAVES))
    return x + y, near


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, norm_w, table, eps, scale):
    x = _layer_norm(x, norm_w.astype(jnp.float32), eps)
    return jnp.dot(x, table.astype(jnp.float32).T, precision=HI) * scale


def _sequence(weights, cfg, st, ids):
    """Float32 logits (S, V) of ONE sequence's full causal forward, and
    (S,) the least distance from a routing tie over its layers."""
    x = _embed(weights["model.embed_tokens.weight"], ids)
    margin = jnp.full(ids.shape, jnp.inf)
    for n in range(cfg.num_hidden_layers):
        x, near = _layer(x, weights, cfg, st, n)
        margin = jnp.minimum(margin, near)
    return _head(x, weights["model.norm.weight"],
                 weights["model.embed_tokens.weight"],
                 float(cfg.layer_norm_eps), float(cfg.logit_scale)), margin


def logits(weights, cfg, ids):
    """Float32 logits (B, S, V) of the full causal forward over `ids`, a
    sequence at a time."""
    ids = jnp.asarray(ids, jnp.int32)
    st = _Static(cfg)
    return jnp.stack([_sequence(weights, cfg, st, row)[0] for row in ids])


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
