"""The plain reference of a Llama-shaped dense decoder (Mistral-7B's
block): jax.numpy, float32 throughout, Precision.HIGHEST, no kernels, no
cache, no batching tricks, independent of the package's layers.

It runs LAYER BY LAYER (one jitted function a layer, called in a Python
loop) so that only one layer's weights are upcast to float32 at a time:
the float32 copy of a whole 16-layer stack does not fit beside the
engine's state. `cfg` is any object with the published keys as
attributes (`vocab_size`, `hidden_size`, `num_attention_heads`,
`num_key_value_heads`, `num_hidden_layers`, `rms_norm_eps`, `rope_theta`).
`weights` is the model's state_dict as arrays (bf16 weights are upcast,
not re-rounded).

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
        p = f"model.layers.{i}."
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)
             and k.endswith(".weight")}
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


def token_gaps(weights, cfg, prompt, output, pad_to=None):
    """For a request served greedily: how far each emitted token's
    reference logit lies under the reference maximum at its position.
    Returns (gaps, max |logit|) over the emitted positions. `pad_to` pads
    the sequence on the right (causal, so earlier positions do not see
    it) so that requests of many lengths share one compiled forward."""
    import numpy as np
    seq = list(prompt) + list(output)
    n = len(seq)
    if pad_to is not None and pad_to > n:
        seq = seq + [0] * (pad_to - n)
    lg = logits(weights, cfg, jnp.asarray([seq], jnp.int32))[0]
    lg = np.asarray(lg[len(prompt) - 1:n - 1])        # predicts output[j]
    out = np.asarray(output)
    gaps = lg.max(axis=-1) - lg[np.arange(len(out)), out]
    return gaps, float(np.abs(lg).max())
