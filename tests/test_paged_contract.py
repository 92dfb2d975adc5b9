"""The engine's model-side contract (`models/paged.py`): Llama's programs
are what their families' programs written out by hand lower to, and
neither the engine's four builders nor the draft model's two name a
model's method."""
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit.api import functional_call
from paddle_tpu.kernels.paged_attention import paged_page_bytes
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.generation import _sample_arr
from paddle_tpu.models.paged import PAGED_ENTRY, PagedSpan, decode_multi
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving.spec import draft_model as draft_mod

B, P, S, K = 2, 4, 16, 2


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    # head_dim 64: the least the paged decode kernel takes
    return LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=128, intermediate_size=256,
        num_attention_heads=2, num_key_value_heads=1))


def by_hand(eng, kind):
    """The family's program written out by hand, frame and all: what the
    engine's one wrapper around the family's body has to lower to. It
    calls the model as the contract says (PAGED_ENTRY over a span, or
    `decode_multi`), greedy."""
    model, views, split = eng.model, eng._paged_views, eng._split_views

    def st_of(state):
        return {k: Tensor(v) for k, v in state.items()}

    def chunk(state, kcs, vcs, kss, vss, ids, cache_len, live, bt, key):
        logits, caches, counts = functional_call(
            model, st_of(state), Tensor(ids), views(kcs, vcs, kss, vss),
            Tensor(bt), PagedSpan("prefill", Tensor(cache_len), Tensor(live)),
            method=PAGED_ENTRY)
        last = logits._data[0, 0]
        ok = jnp.all(jnp.isfinite(last))
        tok = _sample_arr(last[None], key, 0.0, 0, 1.0)[0]
        return (tok, ok, counts) + split(caches)

    def decode(state, kcs, vcs, kss, vss, ids, bt, sl, key):
        logits, caches, counts = functional_call(
            model, st_of(state), Tensor(ids), views(kcs, vcs, kss, vss),
            Tensor(bt), PagedSpan("decode", Tensor(sl)), method=PAGED_ENTRY)
        rows = logits._data[:, 0, :]
        ok = jnp.all(jnp.isfinite(rows), axis=-1)
        toks = _sample_arr(rows, key, 0.0, 0, 1.0)
        return (toks, ok, counts) + split(caches)

    def multi(state, kcs, vcs, kss, vss, ids, bt, sl, caps, eos, key):
        toks, n_emit, ok, caches, counts = functional_call(
            model, st_of(state), Tensor(ids), views(kcs, vcs, kss, vss),
            Tensor(bt), Tensor(sl), Tensor(caps), Tensor(eos), key,
            method=decode_multi, k_steps=K, temperature=0.0, top_k=0,
            top_p=1.0)
        return (toks._data, n_emit._data, ok._data, counts) + split(caches)

    def verify(state, kcs, vcs, kss, vss, ids, bt, sl, dl, key):
        logits, caches, counts = functional_call(
            model, st_of(state), Tensor(ids), views(kcs, vcs, kss, vss),
            Tensor(bt), PagedSpan("verify", Tensor(sl), Tensor(dl)),
            method=PAGED_ENTRY)
        lg = logits._data
        jpos = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
        live = jpos <= dl[:, None]
        fin = jnp.all(jnp.isfinite(lg), axis=-1)
        ok = jnp.all(jnp.where(live, fin, True), axis=-1)
        drafts = ids[:, 1:]
        has_draft = jpos[:, :K] < dl[:, None]
        idsn = jnp.concatenate([drafts, jnp.zeros((B, 1), ids.dtype)],
                               axis=1)
        # greedy: the longest prefix of drafts the argmaxes agree with,
        # then the argmax as correction or bonus
        pred = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        acc = jnp.logical_and(pred[:, :K] == drafts, has_draft)
        n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
        toks = jnp.where(jpos < n_acc[:, None], idsn, pred)
        return (toks, n_acc, ok, counts) + split(caches)

    program = {"chunk": chunk, "decode": decode, "multi_decode": multi,
               "verify": verify}[kind]
    program.__name__ = "program"      # the wrapper's name, in the module's
    return program


def arguments(eng, kind):
    i32 = jnp.int32
    base = (eng._state,) + tuple(eng._cache_lists())
    key = eng._null_key
    if kind == "chunk":
        return base + (jnp.zeros((1, S), i32), i32(0), i32(S),
                       jnp.zeros((P,), i32), key)
    if kind == "decode":
        return base + (jnp.zeros((B, 1), i32), jnp.zeros((B, P), i32),
                       jnp.ones((B,), i32), key)
    if kind == "verify":
        return base + (jnp.zeros((B, K + 1), i32), jnp.zeros((B, P), i32),
                       jnp.ones((B,), i32), jnp.ones((B,), i32), key)
    return base + (jnp.zeros((B,), i32), jnp.zeros((B, P), i32),
                   jnp.ones((B,), i32), jnp.ones((B,), i32),
                   jnp.full((B,), -1, i32), key)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("kind", ["chunk", "decode", "multi_decode",
                                  "verify"])
def test_llama_programs_through_the_contract_are_the_parents(model, kind,
                                                             kv_dtype):
    """The lowered program (StableHLO text) of each builder is, letter for
    letter, what the family's program written out by hand lowers to: the
    engine's one wrapper and Llama's one span-shaped method a level add
    no operation, no operand and no output. (Against the parent commit's
    tree the same texts were compared when the wrapper came, CHANGES.md
    PR 33.)"""
    eng = ServingEngine(model, num_pages=16, page_size=16, max_batch_size=B,
                        decode_steps=K if kind == "multi_decode" else 1,
                        kv_dtype=kv_dtype)
    build = {"chunk": lambda: eng._build_chunk(S, P),
             "decode": lambda: eng._build_decode(B, P),
             "multi_decode": lambda: eng._build_multi_decode(B, K, P),
             "verify": lambda: eng._build_verify(B, K, P)}[kind]
    args = arguments(eng, kind)
    with paddle.no_grad():
        now = build().lower(*args).as_text()
        then = jax.jit(by_hand(eng, kind)).lower(*args).as_text()
    assert now == then
    eng.shutdown()


def test_verify_program_serves_the_same_tokens(model):
    """The verify builder through the contract: speculative decoding
    serves exactly the plain engine's greedy tokens (the identity suite
    of test_serving_spec.py holds it at length; this is its guard here)."""
    from paddle_tpu.serving.spec import NgramProposer
    prompts = [[1, 2, 3, 1, 2, 3, 1, 2], [5, 6, 5, 6, 5, 6]]
    outs = []
    for proposer in (None, NgramProposer()):
        eng = ServingEngine(model, num_pages=32, page_size=16,
                            max_batch_size=2, proposer=proposer, spec_k=K)
        rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        res = eng.run()
        outs.append([res[r] for r in rids])
        if proposer is not None:
            assert eng.program_counts()["verify"] >= 1
        eng.shutdown()
    assert outs[0] == outs[1]


def test_the_builders_name_no_model_method():
    from paddle_tpu.serving.spec import DraftModelProposer
    for cls, names in ((ServingEngine, ("_build_chunk", "_build_decode",
                                        "_build_multi_decode",
                                        "_build_verify")),
                       (DraftModelProposer, ("_build_chunk",
                                             "_build_decode"))):
        for name in names:
            src = inspect.getsource(getattr(cls, name))
            assert "PAGED_ENTRY" in src or "decode_multi" in src, name
    # nowhere in either module is a model's method passed by name
    for mod in (engine_mod, draft_mod):
        src = inspect.getsource(mod)
        assert "forward_paged" not in src, mod.__name__
        assert not re.search(r"method=[\"']", src), mod.__name__
    assert engine_mod.PAGED_ENTRY == "paged_forward"


@pytest.mark.parametrize("kv_dtype,name", [(None, "float32"),
                                           ("int8", "int8")])
def test_llamas_page_and_bytes_do_not_change(model, kv_dtype, name):
    cfg = model.cfg
    hd = cfg.hidden_size // cfg.num_attention_heads
    spec = model.paged_cache_spec(16, jnp.float32, kv_dtype=kv_dtype)
    want = paged_page_bytes(cfg.num_key_value_heads, 16, hd, name)
    assert spec.page_bytes == spec.page_bytes_shard == want
    shapes = [e[0] for e in spec.entries]
    page = (cfg.num_key_value_heads, 16, hd)
    assert shapes == [page, page] + ([page[:2]] * 2 if kv_dtype else [])
    eng = ServingEngine(model, num_pages=16, page_size=16, kv_dtype=kv_dtype)
    assert eng.kv_page_bytes == want
    assert eng.kv_bytes_per_token == cfg.num_hidden_layers * want // 16
    assert [len(c) for c in eng._cache_lists()] == \
        [cfg.num_hidden_layers] * len(shapes) + [0] * (4 - len(shapes))
    assert model.paged_counters == () and eng._model_counters == ()
    eng.shutdown()


def test_a_page_payload_round_trips_whatever_the_entry(model):
    """The tiered-KV page I/O works on page ids: k row, v row a layer,
    then the scale rows, as before; a latent cache's one array a layer."""
    from paddle_tpu.models.kimi_k2 import KimiK2ForCausalLM, kimi_k2_tiny
    from paddle_tpu.serving.kv_cache import decode_page_payload
    for m, kv, n in ((model, "int8", 4 * 2), (model, None, 2 * 2),
                     (KimiK2ForCausalLM(kimi_k2_tiny(experts_held=4)), None,
                      3)):
        eng = ServingEngine(m, num_pages=8, page_size=16, kv_dtype=kv)
        pools = eng._cache_lists()
        for c, pool in enumerate(pools):
            for l in range(len(pool)):
                pool[l] = pool[l].at[3].set(
                    jnp.asarray(1 + c + 4 * l).astype(pool[l].dtype))
        arrays = decode_page_payload(eng._gather_page_payload(3))
        assert len(arrays) == n
        eng._scatter_page_payload(5, arrays)
        eng._apply_copies([(3, 6)])
        for pool in eng._cache_lists():
            for a in pool:
                assert np.array_equal(np.asarray(a[5]), np.asarray(a[3]))
                assert np.array_equal(np.asarray(a[6]), np.asarray(a[3]))
        eng.shutdown()
