"""The plain reference of a Llama-shaped dense decoder (Mistral-7B's
block): jax.numpy, float32 throughout, Precision.HIGHEST, no kernels, no
cache, no batching tricks, independent of the package's layers.

It runs LAYER BY LAYER (one jitted function a layer, called in a Python
loop) so that only one layer's weights are upcast to float32 at a time:
the float32 copy of a whole 16-layer stack does not fit beside the
engine's state. `cfg` is any object with the published keys as
attributes (`vocab_size`, `hidden_size`, `num_attention_heads`,
`num_key_value_heads`, `num_hidden_layers`, `rms_norm_eps`, `rope_theta`).
`weights` maps the published parameter names to arrays (bf16 weights are
upcast, not re-rounded).

The weights are the benchmark's own too (PR 29): `leaves` gives every
parameter's name, shape and scale from the configuration alone, and
`make_weights` / `LazyWeights` draw them from `--seed`, leaf k from the
key folded with k, so that the program is loaded with them in a jitted
call a layer and the reference, after the window, reads the same values a
leaf at a time without holding a second copy. The reference takes nothing the
program has made. The scales (why these: `leaves`) keep the served model
from collapsing to one token, which the program's Xavier initialisation
did: the comparison of served tokens then says nothing about precision.

One departure from the published code, which the program shares: RoPE
pairs lanes interleaved (2i, 2i+1) where Mistral's reference splits the
head in halves (i, i + d/2). With seeded random weights that is a fixed
permutation of the columns of q_proj and k_proj, not another function.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

# gain of a residual branch's last matrix (o_proj, down_proj) against a
# unit-variance map: 2 x 16 branches then add about as much variance to
# the stream as the embedding (rows of unit variance) brings, so the next
# token depends on the last one AND on what the layers computed from the
# context. At gain 1 (and Xavier's near-zero embedding) the stream is the
# branches' sum alone and greedy decoding falls into one repeated token.
BRANCH_GAIN = 0.5
LAYER_LEAVES = ("input_layernorm.weight", "self_attn.q_proj.weight",
                "self_attn.k_proj.weight", "self_attn.v_proj.weight",
                "self_attn.o_proj.weight", "post_attention_layernorm.weight",
                "mlp.gate_proj.weight", "mlp.up_proj.weight",
                "mlp.down_proj.weight")


def leaves(cfg) -> dict:
    """{name: (shape, std)} of every parameter, matrices as (in, out);
    std 0 marks a norm's weight, which is 1. Every matrix maps unit
    variance to unit variance (std 1 / sqrt(in)), so the logits are
    about N(0, 1) at any width, but for the branches' last matrices
    (BRANCH_GAIN) and q/k, whose 1.2 gives attention scores a spread of
    about 1.4: attention that looks at what the keys hold."""
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    d = h // cfg.num_attention_heads
    kv = cfg.num_key_value_heads * d
    unit = 1.0 / math.sqrt(h)
    layer = {"input_layernorm.weight": ((h,), 0.0),
             "self_attn.q_proj.weight": ((h, h), 1.2 * unit),
             "self_attn.k_proj.weight": ((h, kv), 1.2 * unit),
             "self_attn.v_proj.weight": ((h, kv), unit),
             "self_attn.o_proj.weight": ((h, h), BRANCH_GAIN * unit),
             "post_attention_layernorm.weight": ((h,), 0.0),
             "mlp.gate_proj.weight": ((h, i), unit),
             "mlp.up_proj.weight": ((h, i), unit),
             "mlp.down_proj.weight": ((i, h), BRANCH_GAIN / math.sqrt(i))}
    out = {"model.embed_tokens.weight": ((v, h), 1.0)}
    for n in range(cfg.num_hidden_layers):
        out.update({f"model.layers.{n}.{k}": layer[k] for k in LAYER_LEAVES})
    out["model.norm.weight"] = ((h,), 0.0)
    if not getattr(cfg, "tie_word_embeddings", False):
        out["lm_head.weight"] = ((h, v), unit)
    return out


def _seed_words(seed: int):
    """`--seed` passes 2**31: two 32-bit words, traced, so that one
    compiled program serves every seed."""
    import numpy as np
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def _leaf(lo, hi, k, shape, std, dtype):
    if std == 0.0:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.key(29), lo), hi), k)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _leaves_from(lo, hi, k0, spec, dtype):
    """Leaves k0, k0 + 1, ... of shapes and scales `spec`: `k0` is traced,
    so the sixteen layers share one compiled program."""
    return [_leaf(lo, hi, k0 + j, shape, std, dtype)
            for j, (shape, std) in enumerate(spec)]


def make_weights(cfg, seed: int, dtype) -> dict:
    """Every parameter from the seed, made on the device in the type it is
    served in: one jitted call a layer (all of one compiled program) and
    one each for what stands before and after them. One call for the
    whole model would be 146 random draws unrolled into one program, 21 s
    to compile (rehearsal, PR 29) where these take 3."""
    spec = leaves(cfg)
    names, lo_hi, dtype = list(spec), _seed_words(seed), jnp.dtype(dtype)
    n, out, k = len(LAYER_LEAVES), {}, 0
    while k < len(names):
        run = n if names[k].endswith("." + LAYER_LEAVES[0]) else 1
        got = _leaves_from(*lo_hi, k, tuple(spec[x] for x in names[k:k + run]),
                           dtype)
        out.update(zip(names[k:k + run], got))
        k += run
    return out


class LazyWeights:
    """The same values, each leaf drawn when it is asked for (one small
    compiled program a shape): the reference walks the layers one at a
    time and never holds the whole model."""

    def __init__(self, cfg, seed: int, dtype):
        self.spec = leaves(cfg)
        self.index = {n: k for k, n in enumerate(self.spec)}
        self.words, self.dtype = _seed_words(seed), jnp.dtype(dtype)
        self.draw = jax.jit(_leaf, static_argnums=(3, 4, 5))

    def __getitem__(self, name):
        shape, std = self.spec[name]
        return self.draw(*self.words, self.index[name], shape, std,
                         self.dtype)


def _norm(x, w, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, w, n_q, n_kv, eps, theta):
    """One decoder layer on (B, S, H) float32 activations; `w` is the
    layer's nine weights by their short names, in any float type."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, s, h_dim = x.shape
    d = h_dim // n_q
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)      # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]

    def rope(t):                    # (B, S, heads, D), interleaved pairs
        t1, t2 = t[..., 0::2], t[..., 1::2]
        return jnp.stack([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                         axis=-1).reshape(t.shape)

    h = _norm(x, w["input_layernorm.weight"], eps)
    q = jnp.dot(h, w["self_attn.q_proj.weight"], precision=HI)
    k = jnp.dot(h, w["self_attn.k_proj.weight"], precision=HI)
    v = jnp.dot(h, w["self_attn.v_proj.weight"], precision=HI)
    q = rope(q.reshape(b, s, n_q, d))
    k = rope(k.reshape(b, s, n_kv, d))
    v = v.reshape(b, s, n_kv, d)
    k = jnp.repeat(k, n_q // n_kv, axis=2)
    v = jnp.repeat(v, n_q // n_kv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(d)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc,
                   -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=HI).reshape(b, s, h_dim)
    x = x + jnp.dot(a, w["self_attn.o_proj.weight"], precision=HI)
    h = _norm(x, w["post_attention_layernorm.weight"], eps)
    g = jnp.dot(h, w["mlp.gate_proj.weight"], precision=HI)
    u = jnp.dot(h, w["mlp.up_proj.weight"], precision=HI)
    return x + jnp.dot(jax.nn.silu(g) * u, w["mlp.down_proj.weight"],
                       precision=HI)


@jax.jit
def _embed(table, ids):
    return jnp.take(table, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(x, norm_w, head_w, eps):
    x = _norm(x, norm_w.astype(jnp.float32), eps)
    return jnp.dot(x, head_w.astype(jnp.float32), precision=HI)


@jax.jit
def _next_token_loss(logits, labels):
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def logits(weights, cfg, ids):
    """Float32 logits (B, S, V) of the full causal forward over `ids`."""
    x = _embed(weights["model.embed_tokens.weight"], ids)
    for i in range(cfg.num_hidden_layers):
        w = {k: weights[f"model.layers.{i}.{k}"] for k in LAYER_LEAVES}
        x = _layer(x, w, cfg.num_attention_heads, cfg.num_key_value_heads,
                   float(cfg.rms_norm_eps), float(cfg.rope_theta))
    head = (weights["model.embed_tokens.weight"].T
            if getattr(cfg, "tie_word_embeddings", False)
            else weights["lm_head.weight"])
    return _head(x, weights["model.norm.weight"], head,
                 float(cfg.rms_norm_eps))


def loss(weights, cfg, ids) -> float:
    """Next-token loss of a batch with labels = ids: the mean over its
    sequences, each passed through the forward alone (every sequence has
    the same length, so the mean of their means is the batch's mean). One
    sequence at a time because the float32 logits of a whole batch do not
    fit beside a train cell's optimizer state."""
    ids = jnp.asarray(ids, jnp.int32)
    rows = [float(_next_token_loss(logits(weights, cfg, ids[i:i + 1]),
                                   ids[i:i + 1]))
            for i in range(ids.shape[0])]
    return sum(rows) / len(rows)


def position_logits(weights, cfg, prompt, output, pad_to=None):
    """Float32 logits (len(output), V) at the positions that predict each
    token of `output` after `prompt`. `pad_to` pads the sequence on the
    right (causal, so earlier positions do not see it) so that requests of
    many lengths share one compiled forward."""
    import numpy as np
    seq = list(prompt) + list(output)
    n = len(seq)
    if pad_to is not None and pad_to > n:
        seq = seq + [0] * (pad_to - n)
    lg = logits(weights, cfg, jnp.asarray([seq], jnp.int32))[0]
    return np.asarray(lg[len(prompt) - 1:n - 1])      # predicts output[j]


def token_gaps(weights, cfg, prompt, output, pad_to=None):
    """For a request served greedily: how far each emitted token's
    reference logit lies under the reference maximum at its position.
    Returns (gaps, max |logit|) over the emitted positions."""
    import numpy as np
    lg = position_logits(weights, cfg, prompt, output, pad_to)
    out = np.asarray(output)
    gaps = lg.max(axis=-1) - lg[np.arange(len(out)), out]
    return gaps, float(np.abs(lg).max())
