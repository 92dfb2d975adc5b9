"""Ring / Ulysses context-parallel attention vs single-device reference.

Capability-parity-plus (SURVEY.md §5): the reference has no in-core ring
attention; these tests check our first-class implementation bitwise-close
against the plain fp32 attention composition, fwd + grads, on the 8-device
virtual CPU mesh."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.kernels.ring_attention import (ring_flash_attention,
                                               ulysses_attention)

rng = np.random.RandomState(7)


def _mesh(n=4):
    devs = np.array(jax.devices()[:n])
    return Mesh(devs, ("sep",))


def _ref_attention(q, k, v, causal):
    B, S, H, D = q.shape
    rep = H // k.shape[2]
    k = np.repeat(k, rep, axis=2)
    v = np.repeat(v, rep, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q.astype(np.float64),
                  k.astype(np.float64)) / math.sqrt(D)
    if causal:
        mask = np.tril(np.ones((S, S), bool))
        s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v.astype(np.float64))


def _make_qkv(B=2, S=64, H=4, Hkv=None, D=16):
    Hkv = Hkv or H
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, Hkv, D).astype(np.float32)
    v = rng.randn(B, S, Hkv, D).astype(np.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("gqa", [False, True])
def test_ring_forward(causal, gqa):
    q, k, v = _make_qkv(H=4, Hkv=2 if gqa else 4)
    mesh = _mesh(4)
    fn = shard_map(
        lambda a, b, c: ring_flash_attention(a, b, c, "sep", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
        out_specs=P(None, "sep"), check_vma=False)
    out = np.asarray(jax.jit(fn)(q, k, v))
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_grads(causal):
    q, k, v = _make_qkv(B=1, S=32, H=2, D=8)
    mesh = _mesh(4)

    def loss_ring(q, k, v):
        inner = shard_map(
            lambda a, b, c: ring_flash_attention(a, b, c, "sep",
                                                 causal=causal),
            mesh=mesh,
            in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
            out_specs=P(None, "sep"), check_vma=False)
        return jnp.sum(jnp.sin(inner(q, k, v)))

    def loss_ref(q, k, v):
        D = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
        if causal:
            S = q.shape[1]
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.sum(jnp.sin(o))

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   atol=2e-3, rtol=2e-3)


def test_ring_gqa_grads():
    q, k, v = _make_qkv(B=1, S=32, H=4, Hkv=2, D=8)
    mesh = _mesh(4)

    def loss(fn_inner, q, k, v):
        return jnp.sum(shard_map(
            fn_inner, mesh=mesh,
            in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
            out_specs=P(None, "sep"), check_vma=False)(q, k, v) ** 2)

    def ring(a, b, c):
        return ring_flash_attention(a, b, c, "sep", causal=True)

    g = jax.jit(jax.grad(lambda q, k, v: loss(ring, q, k, v),
                         argnums=(0, 1, 2)))(q, k, v)

    def loss_ref(q, k, v):
        kr = jnp.repeat(k, 2, axis=2)
        vr = jnp.repeat(v, 2, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / math.sqrt(q.shape[-1])
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vr)
        return jnp.sum(o ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_forward(causal):
    q, k, v = _make_qkv(B=2, S=64, H=8, D=16)
    mesh = _mesh(4)
    fn = shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, "sep", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
        out_specs=P(None, "sep"), check_vma=False)
    out = np.asarray(jax.jit(fn)(q, k, v))
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


def test_ulysses_gqa_repeat_heads():
    # Hkv=2 < sep=4: heads get repeated so the a2a can split them
    q, k, v = _make_qkv(B=1, S=64, H=8, Hkv=2, D=16)
    mesh = _mesh(4)
    fn = shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, "sep", causal=True),
        mesh=mesh,
        in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
        out_specs=P(None, "sep"), check_vma=False)
    out = np.asarray(jax.jit(fn)(q, k, v))
    ref = _ref_attention(q, k, v, True)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


def test_ulysses_grads():
    q, k, v = _make_qkv(B=1, S=32, H=4, D=8)
    mesh = _mesh(4)

    def loss(q, k, v):
        inner = shard_map(
            lambda a, b, c: ulysses_attention(a, b, c, "sep", causal=True),
            mesh=mesh,
            in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
            out_specs=P(None, "sep"), check_vma=False)
        return jnp.sum(inner(q, k, v) ** 2)

    def loss_ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return jnp.sum(o ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("mode", ["ring", "ulysses"])
def test_context_parallel_attention_wrapper(mode):
    import paddle_tpu.distributed as dist
    from jax.sharding import NamedSharding
    q, k, v = _make_qkv(B=1, S=64, H=8, D=16)
    mesh = _mesh(4)
    sharding = NamedSharding(mesh, P(None, "sep"))
    qj = jax.device_put(q, sharding)
    kj = jax.device_put(k, sharding)
    vj = jax.device_put(v, sharding)
    out = dist.context_parallel_attention(qj, kj, vj, causal=True, mode=mode)
    assert out.sharding.spec == P(None, "sep")
    ref = _ref_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3, rtol=2e-3)


# ----------------------------------------------------- Megatron-SP layers
def test_megatron_sp_linears_match_plain_math():
    """ColumnSequenceParallelLinear / RowSequenceParallelLinear (parity:
    sequence_parallel_utils.py:427,562): sequence-sharded activations in
    and out of the TP pair reproduce the unsharded math, with the output
    actually sharded over ('data','sep')."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet import fleet, DistributedStrategy
    from paddle_tpu.distributed.fleet.sequence_parallel_utils import (
        ColumnSequenceParallelLinear, RowSequenceParallelLinear, all_gather,
        scatter)

    st = DistributedStrategy()
    st.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                         "sharding_degree": 1, "sep_degree": 2}
    fleet.init(is_collective=True, strategy=st)
    paddle.seed(0)

    class Block(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.c = ColumnSequenceParallelLinear(16, 32, has_bias=True,
                                                  gather_output=False)
            self.r = RowSequenceParallelLinear(32, 16, has_bias=True,
                                               input_is_parallel=True)

        def forward(self, x):
            return self.r(paddle.nn.functional.relu(self.c(x)))

    blk = Block()
    rng_ = np.random.RandomState(0)
    x = paddle.to_tensor(rng_.randn(4, 8, 16).astype(np.float32),
                         stop_gradient=False)
    out = blk(scatter(x))
    ref = np.maximum(np.asarray(x._data) @ np.asarray(blk.c.weight._data)
                     + np.asarray(blk.c.bias._data), 0) \
        @ np.asarray(blk.r.weight._data) + np.asarray(blk.r.bias._data)
    np.testing.assert_allclose(np.asarray(out._data), ref, atol=1e-5)
    assert "sep" in str(out._data.sharding.spec)
    out.sum().backward()
    assert blk.c.weight.grad is not None
    np.testing.assert_allclose(np.asarray(all_gather(out)._data), ref,
                               atol=1e-5)
    fleet._hcg = None
