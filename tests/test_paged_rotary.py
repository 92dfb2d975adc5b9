"""The paged spans' rotation (`apply_rotary_paged`, PR 32) against
`apply_rotary`: the same interleaved rotation bit for bit on the three
spans, and the same KV pages after the engine's chunk and decode
programs have written them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama, llama_tiny
from paddle_tpu.models.llama import (_rope_cache, apply_rotary,
                                     apply_rotary_paged)
from paddle_tpu.serving import ServingEngine

H, D, MAX_POS = 4, 64, 96
# span -> (B, S) of x, the shape of the positions gathered for it, and
# the axes those rope rows lack of (B, S, 1, D/2) (`LlamaAttention._paged_qk`)
SPANS = {"decode": ((5, 1), (5,), (1, 2)),
         "chunk": ((1, 24), (24,), (0, 2)),
         "verify": ((3, 4), (3, 4), (2,))}


def through_apply_rotary(x, cos, sin):
    """`apply_rotary` itself in the paged rotation's place: one call a
    batch row, each with that row's own (S, D/2) rope rows."""
    shape = x.shape[:2] + (1, x.shape[-1] // 2)
    cos, sin = jnp.broadcast_to(cos, shape), jnp.broadcast_to(sin, shape)
    return jax.vmap(lambda xb, cb, sb: apply_rotary(
        xb[None], cb[:, 0], sb[:, 0])[0])(x, cos, sin)


def bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("span", sorted(SPANS))
def test_paged_rotation_is_apply_rotary_bit_for_bit(span, dtype):
    (b, s), pos_shape, expand = SPANS[span]
    rng = np.random.default_rng(32)
    x = jnp.asarray(rng.standard_normal((b, s, H, D)), dtype)
    pos = jnp.asarray(rng.integers(0, MAX_POS, pos_shape))
    cos, sin = (jnp.expand_dims(jnp.take(t, pos, axis=0), expand)
                for t in _rope_cache(D, MAX_POS, 1e6, dtype))
    want = through_apply_rotary(x, cos, sin)
    got = apply_rotary_paged(x, cos, sin)
    assert got.dtype == want.dtype and got.shape == want.shape
    # op by op nothing can fuse: a*c - b*s and a*c + b*(-s) are one float
    assert np.array_equal(bits(got), bits(want))
    # and compiled, where the CPU backend may fuse a multiply into the
    # add: it does so alike in both forms here (held exactly, not to an
    # ulp; if a later backend fuses them differently this is the line
    # that says so)
    assert np.array_equal(bits(jax.jit(apply_rotary_paged)(x, cos, sin)),
                          bits(jax.jit(through_apply_rotary)(x, cos, sin)))
    # a rotation, not a copy: the test has something to tell apart
    assert not np.array_equal(bits(got), bits(x))


def served_pages(kv_dtype, lengths, new_tokens):
    """Tokens and the whole KV pool after the engine has served prompts
    of `lengths` tokens in 16-token chunks and `new_tokens` each."""
    paddle.seed(0)
    # head_dim 64: the least the paged decode kernel takes
    model = LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=128, intermediate_size=256,
        num_attention_heads=2, num_key_value_heads=1))
    eng = ServingEngine(model, num_pages=16, page_size=16, max_batch_size=2,
                        prefill_buckets=[16], token_budget=16,
                        kv_dtype=kv_dtype)
    rng = np.random.default_rng(7)
    rids = [eng.add_request([int(t) for t in rng.integers(1, 128, n)],
                            max_new_tokens=new_tokens) for n in lengths]
    outs = eng.run()
    pools = [np.asarray(a) for lst in eng._cache_lists() for a in lst]
    programs = eng.program_counts()
    eng.shutdown()
    assert programs["chunk"] >= 1 and programs["decode"] >= 1
    assert all(p.any() for p in pools)      # every pool was written
    return [outs[r] for r in rids], pools


def both_rotations(monkeypatch, *args):
    now = served_pages(*args)
    monkeypatch.setattr(llama, "apply_rotary_paged", through_apply_rotary)
    return now, served_pages(*args)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_kv_pages_are_those_apply_rotary_writes(monkeypatch, kv_dtype):
    """Prefill spans (two chunks) and decode spans (`paged_forward`)
    write the same roped K, lane for lane and bit for bit, as with
    `apply_rotary` in the rotation's place: what the radix cache, page
    transport and the int8 quantise-on-write see has not changed. Run op
    by op (`disable_jit`), where no backend can fuse a multiply into an
    add; the compiled programs are the next test's."""
    with jax.disable_jit():
        (toks, pools), (toks_ref, pools_ref) = both_rotations(
            monkeypatch, kv_dtype, (21,), 3)
    assert toks == toks_ref
    assert len(pools) == len(pools_ref) == (8 if kv_dtype else 4)
    for got, want in zip(pools, pools_ref):
        assert got.dtype == want.dtype
        assert np.array_equal(got.view(np.uint8), want.view(np.uint8))


def test_compiled_programs_write_those_pages_to_an_ulp(monkeypatch):
    """The same through the engine's COMPILED chunk and decode programs.
    There the CPU backend contracts one of a sum's two products into a
    fused multiply-add, and which one depends on the form: a*c - b*s
    read 1 float32 ulp (of the pair's length, which bounds each product)
    from a*c + b*(-s) in a third of the first layer's K lanes here. So
    the first layer's K is held to that ulp and its V, which no rotation
    touches, exactly; deeper layers see the ulp through the attention
    before them and are held by the tokens."""
    (toks, pools), (toks_ref, pools_ref) = both_rotations(
        monkeypatch, None, (37, 21), 6)
    assert toks == toks_ref
    layers = len(pools) // 2
    k, k_ref, v, v_ref = (pools[0], pools_ref[0],
                          pools[layers], pools_ref[layers])
    assert np.array_equal(v, v_ref)
    pair = k_ref.reshape(*k_ref.shape[:-1], -1, 2)
    ulp = np.spacing(np.sqrt((pair ** 2).sum(-1, keepdims=True)))
    assert (np.abs(k - k_ref).reshape(pair.shape) <= ulp).all()
