"""The third served family (`models/cohere2_moe.py`: window and full
attention layers over a cache of two layer groups, a parallel attention +
expert block, sigmoid-routed experts beside averaged shared experts) at
toy width on the CPU: window 32, page 8, contexts of 20-200, seeded
weights; the program against the benchmark's plain reference, the
window's edge, the layer groups' page bounds, and what the engine refuses."""
import ast
import functools
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit.api import functional_call
from paddle_tpu.models.cohere2_moe import (FULL, SLIDING,
                                           Cohere2MoeAttention,
                                           Cohere2MoeExperts,
                                           Cohere2MoeForCausalLM,
                                           cohere2_moe_tiny)
from paddle_tpu.models.paged import PAGED_ENTRY, PagedSpan
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.errors import UnsupportedFeature

from benchmarks.references import cohere2_moe as reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3500000031
WINDOW, PAGE = 32, 8


def build(dtype="float32", **kw):
    """The toy model loaded with the reference's seeded weights (the held
    experts stacked, the shared experts side by side), and the same
    weights as the reference reads them."""
    cfg = cohere2_moe_tiny(**{"experts_held": 8, "expert_offset": 4, **kw})
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        model = Cohere2MoeForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(prev)
    new = reference.make_weights(cfg, SEED, dtype)
    assert set(new) == set(model.state_dict())
    for k, t in model.state_dict().items():
        assert tuple(t.shape) == tuple(new[k].shape), k
        t._data = new[k]
    return cfg, model, reference.LazyWeights(cfg, SEED, dtype)


def engine(model, **kw):
    return ServingEngine(model, **{
        "num_pages": 128, "page_size": PAGE, "max_batch_size": 4,
        "token_budget": 32, "prefill_buckets": [16, 32],
        "pages_buckets": [32], **kw})


def paged_logits(model, ids, n_prompt, chunk=32):
    """Logits at the positions that predict ids[n_prompt:], through the
    engine's own pools, allocator and block tables, a table a layer
    group: the prompt prefilled in chunks (the windowed group's pages
    taken a chunk at a time and given back behind it, as the scheduler
    does), then one decode span a token, teacher-forced."""
    eng = engine(model, max_batch_size=2, token_budget=chunk)
    alloc, pages = eng.allocator, eng.pages_buckets[-1]
    seq = alloc.alloc_sequence(n_prompt)
    pools = eng._cache_lists()
    out = []

    @functools.partial(jax.jit, static_argnums=(0,))
    def program(kind, state, pools, tokens, bt, start, live):
        st = {k: paddle.Tensor(v) for k, v in state.items()}
        span = PagedSpan(kind, paddle.Tensor(start),
                         None if live is None else paddle.Tensor(live))
        lg, caches, counts = functional_call(
            model, st, paddle.Tensor(tokens), eng._paged_views(*pools),
            paddle.Tensor(bt), span, method=PAGED_ENTRY)
        return lg._data, eng._split_views(caches), counts

    def call(tokens, bt, kind, start, live=None):
        nonlocal pools
        with paddle.no_grad():
            lg, pools, counts = program(
                kind, eng._state, pools, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(bt), jnp.asarray(start, jnp.int32),
                None if live is None else jnp.int32(live))
        assert counts.shape == (6,)
        return np.asarray(lg, np.float32), np.asarray(counts)

    def tables(rows):
        return np.stack([
            np.concatenate([alloc.block_table([seq], pages, g),
                            np.zeros((rows - 1, pages), np.int32)])
            for g in range(2)])

    done = 0
    while done < n_prompt:
        n = min(chunk, n_prompt - done)
        alloc.advance_windows(seq, done, done + n)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = ids[done:done + n]
        lg, counts = call(padded, tables(1)[:, 0], "prefill", done, n)
        # a chunk counts no decode keys; its live queries at positions
        # done .. done + n - 1 see position + 1 keys in the full layer and
        # at most the window in each of the three window layers
        seen = np.arange(done, done + n) + 1
        assert counts[4] == 0 and counts[5] == \
            seen.sum() + 3 * np.minimum(seen, WINDOW).sum()
        done += n
        alloc.advance_windows(seq, done, done)
    out.append(lg[0, 0])
    for j in range(n_prompt, len(ids) - 1):
        alloc.append_token(seq)
        assert seq.window_held(0) <= WINDOW // PAGE + 1
        # row 1 is a padded row (length 0): it must neither write nor count
        lg, counts = call([[ids[j]], [0]], tables(2), "decode", [j + 1, 0])
        # ... and the live row counts its context THROUGH its input token
        assert counts[4] == (j + 1) + 3 * min(j + 1, WINDOW) \
            and counts[5] == 0
        out.append(lg[0, 0])
    alloc.free_sequence(seq)
    alloc.check_invariants()
    assert alloc.num_used == 0 and alloc.windows[0].pool.num_used == 0
    eng.shutdown()
    return np.stack(out)


IDS = np.random.default_rng(35).integers(0, 256, 150).tolist()
N_PROMPT = 118     # four chunks of 32: three whole and one of 22


@pytest.fixture(scope="module")
def reference_logits():
    cfg, _, w = build("float32")
    lg = reference.logits(w, cfg, jnp.asarray([IDS], jnp.int32))[0]
    return np.asarray(lg[N_PROMPT - 1:len(IDS) - 1])


# The mean |difference| over the logits (about N(0, 1/16)) of 32
# positions whose contexts run from 118 to 149 tokens, all past the
# 32-token window. float32: the program and the reference differ in the
# ORDER of float32 sums only (pages and tiles split the softmax, the
# experts' rows are summed by slot, the shared experts are one matmul): a
# few float32 steps through 4 layers. bfloat16: every matmul input is
# rounded to 8 bits (a relative step of 7.8e-3) through 4 layers of the
# stream; routing flips at ties move single logits further, so the mean
# is held, and the widest only in float32.
F32_MEAN, F32_WIDEST, BF16_MEAN = 2e-6, 5e-5, 2e-2


def test_prefill_then_decode_against_the_reference_in_float32(
        reference_logits):
    _, model, _ = build("float32")
    got = paged_logits(model, IDS, N_PROMPT)
    diff = np.abs(got - reference_logits)
    assert diff.mean() < F32_MEAN and diff.max() < F32_WIDEST, (
        diff.mean(), diff.max())
    assert (got.argmax(-1) == reference_logits.argmax(-1)).all()


def test_bfloat16_is_close_and_fails_float32s_limit(reference_logits):
    _, model, _ = build("bfloat16")
    got = paged_logits(model, IDS, N_PROMPT)
    mean = np.abs(got - reference_logits).mean()
    assert F32_MEAN < mean < BF16_MEAN, mean


def test_rms_norm_in_place_of_layer_norm_fails(reference_logits,
                                               monkeypatch):
    """The family's norm subtracts the mean: a reference that only divides
    by the root mean square is another model."""
    def rms(x, w, eps):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w

    cfg, _, w = build("float32")
    monkeypatch.setattr(reference, "_layer_norm", rms)
    reference._attention.clear_cache()
    reference._head.clear_cache()
    try:
        lg = reference.logits(w, cfg, jnp.asarray([IDS], jnp.int32))[0]
    finally:
        monkeypatch.undo()
        reference._attention.clear_cache()
        reference._head.clear_cache()
    other = np.asarray(lg[N_PROMPT - 1:len(IDS) - 1])
    assert np.abs(other - reference_logits).mean() > 100 * F32_MEAN


# ------------------------------------------------------- the window's edge
def _decode_kernel_case(window):
    """One row of 90 tokens over 12 pages: `run(k, v, table)` is the
    kernel's output, `moved(pos)` whether a change of the key and value
    at `pos` changes it."""
    from paddle_tpu.kernels.paged_attention import paged_attention_decode
    rng = np.random.default_rng(5)
    kvh, d, pages, length = 2, 64, 12, 90
    kc = rng.normal(size=(1 + pages, kvh, PAGE, d)).astype(np.float32)
    vc = rng.normal(size=(1 + pages, kvh, PAGE, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(1, 8, d)), jnp.float32)
    bt = np.arange(1, 1 + pages, dtype=np.int32)[None]

    def run(k=kc, v=vc, table=bt):
        return np.asarray(paged_attention_decode(
            q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(table),
            jnp.asarray([length], jnp.int32), window=window))

    def moved(pos):
        k2, v2 = kc.copy(), vc.copy()
        k2[1 + pos // PAGE, :, pos % PAGE] += 1.0
        v2[1 + pos // PAGE, :, pos % PAGE] += 1.0
        return not np.array_equal(run(k2, v2), run())
    return run, moved, bt, length


def test_the_decode_kernel_sees_window_minus_one_back_and_not_window():
    run, moved, bt, length = _decode_kernel_case(WINDOW)
    q_pos = length - 1
    assert moved(q_pos) and moved(q_pos - (WINDOW - 1))
    assert not moved(q_pos - WINDOW) and not moved(0)
    # the pages before the first visible key are never read: the pad page
    # in their place changes nothing (a windowed group gives them back)
    holes = bt.copy()
    holes[0, :(length - WINDOW) // PAGE] = 0
    assert np.array_equal(run(), run(table=holes))


def test_without_a_window_the_decode_kernel_sees_everything():
    _, moved, _, length = _decode_kernel_case(None)
    assert moved(0) and moved(length - 1 - WINDOW)


@pytest.mark.parametrize("window", [None, WINDOW])
def test_the_chunk_kernel_against_the_composition_at_the_edge(window):
    from paddle_tpu.kernels.flash_attention import flash_attention_chunk_gqa
    from paddle_tpu.models.cohere2_moe import _dense_attention
    rng = np.random.default_rng(7)
    s, t, h, kvh, d = 16, 64, 8, 2, 64
    q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    k = rng.normal(size=(t, kvh, d)).astype(np.float32)
    v = rng.normal(size=(t, kvh, d)).astype(np.float32)
    qpos, kpos = jnp.arange(40, 40 + s), jnp.arange(8, 8 + t)

    def run(k, v):
        return np.asarray(flash_attention_chunk_gqa(
            q, jnp.asarray(k), jnp.asarray(v), qpos, kpos, window=window))

    want = np.asarray(_dense_attention(q, jnp.asarray(k), jnp.asarray(v),
                                       qpos, kpos, d ** -0.5, window))
    base = run(k, v)
    np.testing.assert_allclose(base, want, atol=2e-6)

    def rows_moved(pos):
        k2, v2 = k.copy(), v.copy()
        k2[pos - 8] += 1.0
        v2[pos - 8] += 1.0
        return np.abs(run(k2, v2) - base).max(axis=(1, 2)) > 0

    # the key at position 20: queries sit at 40..55
    seen = rows_moved(20)
    if window is None:
        assert seen.all()
    else:                       # query i sees it iff i - 32 < 20: i <= 51
        assert seen[:12].all() and not seen[12:].any()


def test_the_reference_sees_window_minus_one_back_and_not_window():
    """On a model of window layers only (the reference takes any
    `layer_types`; the program refuses a model without a full layer)."""
    cfg = cohere2_moe_tiny(num_hidden_layers=1, layer_types=(SLIDING,),
                           experts_held=8, expert_offset=4)
    w = reference.LazyWeights(cfg, SEED, "float32")
    ids = np.asarray(IDS[:80], np.int32)
    base = np.asarray(reference.logits(w, cfg, ids[None])[0, -1])

    def moved(pos):
        other = ids.copy()
        other[pos] = (other[pos] + 1) % 256
        return not np.array_equal(
            np.asarray(reference.logits(w, cfg, other[None])[0, -1]), base)
    assert moved(79 - (WINDOW - 1)) and not moved(79 - WINDOW)
    model = Cohere2MoeForCausalLM(cfg)
    with pytest.raises(ValueError, match="window layers only"):
        model.paged_cache_spec(PAGE, jnp.float32)


def test_full_layers_are_unmoved_by_a_shift_of_positions():
    cfg = cohere2_moe_tiny()
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 6, 64)),
                    jnp.float32)
    pos = jnp.arange(6, dtype=jnp.int32)[None]
    for window, same in ((None, True), (WINDOW, False)):
        attn = Cohere2MoeAttention(cfg, window)
        w = [t._data for t in attn._weights()[:3]]
        a, b = attn._qkv(x, pos, *w), attn._qkv(x, pos + 17, *w)
        assert np.array_equal(a[2], b[2])            # v is never rotated
        assert np.array_equal(a[0], b[0]) == same
        assert np.array_equal(a[1], b[1]) == same


# ------------------------------------------------------------- the experts
def test_four_averaged_shared_experts_equal_the_fused_mlp_over_four():
    cfg, model, w = build("float32")
    mlp = model.model.layers[1].mlp
    x = np.random.default_rng(9).normal(size=(1, 10, 64)).astype(np.float32)
    got = np.asarray(mlp.shared(paddle.Tensor(jnp.asarray(x)))._data)[0]
    pre = "model.layers.1.mlp.shared_experts."
    want = sum(np.asarray(reference._expert(
        jnp.asarray(x[0]), jnp.full((10,), 0.25),
        *(w[f"{pre}{s}.{k}"] for k in reference.MLP_LEAVES)))
        for s in range(4))
    np.testing.assert_allclose(got, want, atol=1e-6)
    # ... which is the 128-wide MLP's output over four, not each expert's
    fused = np.asarray(mlp.shared_experts(
        paddle.Tensor(jnp.asarray(x)))._data)[0]
    np.testing.assert_allclose(got, fused * 0.25, atol=1e-7)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Routed parts of 8 chips holding 2 of 16 experts each, plus the
    shared experts ONCE, are the layer that holds all 16."""
    whole_cfg = cohere2_moe_tiny()
    paddle.seed(11)
    whole = Cohere2MoeExperts(whole_cfg)
    x = paddle.Tensor(jnp.asarray(
        np.random.default_rng(13).normal(size=(2, 9, 64)), jnp.float32))
    with paddle.no_grad():       # the grouped kernels have no gradient
        _shares_add_up(whole, x)


def _shares_add_up(whole, x):
    want, counts = whole(x)
    assert int(counts._data[1]) == int(counts._data[0]) == 2 * 9 * 4
    total = np.asarray(whole.shared(x)._data)
    held = 0
    for chip in range(8):
        part = Cohere2MoeExperts(cohere2_moe_tiny(experts_held=2,
                                                  expert_offset=2 * chip))
        part.gate.weight._data = whole.gate.weight._data
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(part.experts, name)._data = \
                getattr(whole.experts, name)._data[2 * chip:2 * chip + 2]
        y, c = part.routed(x)
        total = total + np.asarray(y._data)
        held += int(c._data[1])
    assert held == 2 * 9 * 4                 # every pair on exactly one chip
    np.testing.assert_allclose(total, np.asarray(want._data), atol=1e-5)


# --------------------------------------------------- the cache's two groups
def _prompts(lengths, seed=17):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 255, n).tolist() for n in lengths]


def _serve(eng, prompts, new=24, watch=None):
    rids = [eng.add_request(p, max_new_tokens=new) for p in prompts]
    out = {}
    while eng.has_work():
        for rid, tok in eng.step():
            out.setdefault(rid, []).append(tok)
        if watch is not None:
            watch(eng)
    return [out[r] for r in rids]


@pytest.fixture(scope="module")
def served():
    """Four requests on both sides of the window through a roomy engine:
    the tokens every other engine must return. The engine stays (it is
    empty between two tests, and building one compiles three programs)."""
    _, model, _ = build("float32")
    prompts = _prompts((20, 70, 130, 200 - 24))
    eng = engine(model)
    toks = _serve(eng, prompts)
    yield model, prompts, toks, eng
    eng.shutdown()


def test_the_spec_names_two_groups_and_the_engine_sizes_the_windowed(served):
    model = served[0]
    spec = model.paged_cache_spec(PAGE, jnp.float32)
    assert spec.windows == (None, WINDOW)
    assert spec.layer_groups == (1, 1, 1, 0)
    eng = served[3]
    [group] = eng.allocator.windows
    # rows x (window / page + 2) + the chunk budget's pages + the pad page
    assert group.pool.num_pages == 4 * (4 + 2) + 32 // PAGE + 1
    assert [a.shape[0] for a in eng._k_caches] == [29, 29, 29, 128]
    assert eng.radix is None             # such a model donates nothing
    assert "kv_window_pages_held" in eng.metrics.counters
    # a model of one group keeps one table and no windowed pool
    plain = Cohere2MoeForCausalLM(cohere2_moe_tiny(
        layer_types=(FULL,) * 4, experts_held=8, expert_offset=4))
    assert plain.paged_cache_spec(PAGE, jnp.float32).windows == (None,)
    eng = engine(plain)
    assert eng.allocator.windows == [] and eng.radix is not None
    assert "kv_window_pages_held" not in eng.metrics.counters
    eng.shutdown()


def test_the_engines_tokens_are_the_references_greedy_tokens(served):
    model, prompts, toks, _ = served
    cfg, _, w = build("float32")
    for p, t in zip(prompts, toks):
        lg = np.asarray(reference.position_logits(w, cfg, p, t, pad_to=200))
        assert (lg.argmax(-1) == np.asarray(t)).all()
        assert len(set(t)) > 4           # no collapse onto one token


def test_a_windowed_group_keeps_its_bound_and_both_groups_end_empty(served):
    model, prompts, toks, eng = served
    [group] = eng.allocator.windows
    seen = {"decoding": 0, "prefilling": 0, "used": 0}
    before = dict(eng.metrics.counters)

    def watch(e):
        for r in e.scheduler.running:
            seen["decoding"] = max(seen["decoding"], r.seq.window_held(0))
        for r in e.scheduler.prefilling:
            seen["prefilling"] = max(seen["prefilling"],
                                     r.seq.window_held(0))
        seen["used"] = max(seen["used"], group.pool.num_used)
        e.allocator.check_invariants()

    assert _serve(eng, prompts, watch=watch) == toks
    assert 0 < seen["decoding"] <= WINDOW // PAGE + 1
    assert seen["prefilling"] <= (WINDOW + 32) // PAGE + 1
    assert seen["used"] <= group.pool.num_pages - 1
    assert eng.allocator.num_used == 0 and group.pool.num_used == 0
    c = {k: v - before.get(k, 0) for k, v in eng.metrics.counters.items()
         if isinstance(v, (int, float))}
    assert c["kv_window_pages_released"] > 0
    assert 0 < c["kv_window_pages_held"] < c["kv_window_pages_full"]
    assert eng.metrics.snapshot()["kv_window_used_pages"] == [0]
    # the launch ahead was taken on every quiet step: giving pages back
    # is no pressure
    assert c["decode_launches_ahead"] > 0.8 * c["decode_launches"]


def test_a_batch_runs_that_an_all_layers_table_could_not_hold(served):
    """The pools' page-layers (the unbounded group's x 1 layer + the
    windowed group's x 3) are fewer than an all-layers table needs for
    this batch at its longest, and nobody is preempted."""
    model, prompts, toks, _ = served
    eng = engine(model, num_pages=1 + 4 * 25)
    [group] = eng.allocator.windows
    have = (eng.num_pages - 1) * 1 + (group.pool.num_pages - 1) * 3
    need = 4 * sum(-(-(len(p) + 24) // PAGE) for p in prompts)
    assert have < need, (have, need)
    assert _serve(eng, prompts) == toks
    assert eng.metrics.counters["requests_preempted"] == 0
    eng.shutdown()


def test_preemption_under_pool_pressure_and_resume_return_the_same_tokens(
        served):
    model, roomy = served[0], served[3]
    # four rows of 60 tokens are admitted into 40 pages (8 each) and grow
    # to 84 (11 each): the newest gives way and resumes
    prompts = _prompts((60, 60, 60, 60), seed=23)
    toks = _serve(roomy, prompts)
    assert roomy.metrics.counters["requests_preempted"] == 0
    eng = engine(model, num_pages=1 + 40)
    assert _serve(eng, prompts) == toks
    assert eng.metrics.counters["requests_preempted"] > 0
    eng.allocator.check_invariants()
    assert eng.allocator.num_used == 0
    assert eng.allocator.windows[0].pool.num_used == 0
    eng.shutdown()


def test_a_dry_windowed_pool_delays_a_chunk_and_preempts_nobody_wrongly(
        served):
    """The windowed pool is sized never to run dry; an injected dry page
    walks the same ladder as the unbounded group's: the tokens stand."""
    from paddle_tpu.serving.kv_cache import FAULT_ALLOC
    from paddle_tpu.utils import faults
    model, prompts, toks, eng = served
    with faults.injected(FAULT_ALLOC, payload=True, after=40, times=3):
        assert _serve(eng, prompts) == toks
    assert faults.fired_counts()[FAULT_ALLOC] >= 3
    eng.allocator.check_invariants()
    assert eng.allocator.windows[0].pool.num_used == 0


def test_a_request_sharing_a_prefix_returns_what_a_cold_one_returns(served):
    """No request attends through a page that was given back: a model
    with a windowed group donates no prefix, so the second request
    computes its own."""
    model, prompts, toks, eng = served
    shared = prompts[2][:96]
    second = shared + _prompts((30,), seed=19)[0]
    cold = engine(model, max_batch_size=1, batch_buckets=[1])
    [want] = _serve(cold, [second])
    cold.shutdown()
    [first] = _serve(eng, [prompts[2]])
    assert first == toks[2]
    [got] = _serve(eng, [second])
    assert got == want
    assert eng.metrics.counters["cached_tokens_served"] == 0
    assert not eng.export_prefix(shared)[0]


@pytest.mark.parametrize("kw, features", [
    (dict(decode_steps=2), ("multi_step_decode", "windowed_cache")),
    (dict(host_spill_pages=8), ("host_spill", "windowed_cache")),
    (dict(role="prefill"), ("prefill_role", "windowed_cache")),
    (dict(proposer=object()), ("proposer", "windowed_cache")),
])
def test_what_is_not_written_for_a_windowed_group_is_refused(served, kw,
                                                             features):
    with pytest.raises(UnsupportedFeature) as e:
        engine(served[0], **kw)
    assert e.value.features == features
    assert "not supported yet" in str(e.value)


def test_the_model_refuses_int8_pages_a_model_axis_and_a_verify_span(served):
    model = served[0]
    with pytest.raises(ValueError, match="kv_dtype"):
        engine(model, kv_dtype="int8")
    with pytest.raises(ValueError, match="model' axis"):
        model.paged_cache_spec(PAGE, jnp.float32, tp=2)
    with pytest.raises(ValueError, match="verify"):
        model.paged_forward(paddle.Tensor(jnp.zeros((1, 2), jnp.int32)), [],
                            None, PagedSpan("verify", None, None))
    # ... and adapters: its projections carry no hooks, and an engine with
    # a registry would serve the base model under an adapter's name
    from paddle_tpu.serving.lora.runtime import lora_scope
    with lora_scope(object()), pytest.raises(ValueError, match="adapter"):
        model.paged_forward(paddle.Tensor(jnp.zeros((1, 2), jnp.int32)), [],
                            None, PagedSpan("decode", None))


def test_a_chunk_bucket_the_flash_kernel_refuses_is_refused_not_composed(
        served):
    """A prefill chunk attends through the flash kernel or not at all: a
    bucket its tiling rule refuses (15 queries x 4 heads a KV head are not
    whole sublanes) raises when its program is built, with the rule, and
    no float32 score matrix of the chunk is composed in its place."""
    eng = engine(served[0], prefill_buckets=[15], token_budget=15)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            with pytest.raises(ValueError, match="multiple of 8"):
                with paddle.no_grad():
                    eng._build_chunk(15, 32).lower(
                        eng._state, *eng._cache_lists(),
                        jnp.zeros((1, 15), jnp.int32), jnp.int32(0),
                        jnp.int32(15), jnp.zeros((2, 32), jnp.int32),
                        eng._null_key)
    finally:
        eng.shutdown()


# ------------------------------------------------- the benchmark's look-ups
def test_the_third_family_is_found_by_name_with_its_own_counts():
    """The tier-1 copy of `benchmarks/tests`' look-up check for this
    family: found by the configuration's `family`, its work counts its
    own, never another family's."""
    from benchmarks.families import cohere2_moe, kimi_k2
    from benchmarks.harness import common, lookup
    cell, cfg = common.load_cell("serve-mixed-context")
    assert cfg["family"] == "cohere2_moe"
    assert lookup.family(cfg) is cohere2_moe
    assert lookup.driver(cell).__name__ == "benchmarks.drivers.closed_loop"
    assert lookup.work(cfg, "window_decode_kv") is cohere2_moe.window_decode_kv
    assert lookup.work(cfg, "moe_held_experts") \
        is cohere2_moe.moe_held_experts is not kimi_k2.moe_held_experts
    assert lookup.work(cfg, "paged_decode_kv") is None
    assert lookup.work(cfg, "mla_decode_latent") is None
    pcfg = cohere2_moe.config(cfg)
    assert pcfg.layer_types == (SLIDING,) * 3 + (FULL,)
    assert (pcfg.held, pcfg.num_experts, pcfg.sliding_window) \
        == (16, 128, 4096)
    with pytest.raises(ValueError, match="use_parallel_block"):
        cohere2_moe.config(dict(cfg, use_parallel_block=False))


NEW_MODULES = ("paddle_tpu/models/cohere2_moe.py",
               "benchmarks/families/cohere2_moe.py",
               "benchmarks/references/cohere2_moe.py",
               "benchmarks/rehearse_groups.py")


@pytest.mark.parametrize("path", NEW_MODULES)
def test_nothing_of_jax_is_called_at_import(path):
    """No statement that runs at import (module level, class bodies,
    decorators, default arguments) calls into jax or jax.numpy: no array,
    no backend call, no compile. `jax.jit` as a decorator wraps and does
    not trace."""
    tree = ast.parse(open(os.path.join(REPO, path)).read())

    def at_import(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from child.decorator_list
                yield from child.args.defaults
                yield from (d for d in child.args.kw_defaults if d)
            elif isinstance(child, ast.ClassDef):
                yield from child.decorator_list
                yield from child.bases
                yield from at_import(child)
            else:
                yield child

    def dotted(f):
        parts = []
        while isinstance(f, ast.Attribute):
            parts.append(f.attr)
            f = f.value
        return ".".join([f.id] + parts[::-1]) if isinstance(f, ast.Name) \
            else ""

    calls = [dotted(n.func) for top in at_import(tree)
             for n in ast.walk(top) if isinstance(n, ast.Call)]
    bad = [c for c in calls if c.split(".")[0] in ("jax", "jnp", "pl",
                                                   "pltpu")]
    assert bad == [], bad


def test_importing_the_new_modules_touches_no_backend():
    """In a fresh process: `import paddle_tpu` does not import the model,
    and importing it and the benchmark's new modules leaves no live array
    and compiles nothing."""
    code = """
import jax, sys
import paddle_tpu
lazy = ["paddle_tpu.models.cohere2_moe", "paddle_tpu.models.kimi_k2",
        "paddle_tpu.kernels.grouped_matmul"]
assert not [m for m in lazy if m in sys.modules], "imported with the package"
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, *a, **k: compiles.append(name) if "compile" in name else None)
before = len(jax.live_arrays())
import importlib
for m in lazy + ["benchmarks.families.cohere2_moe",
                 "benchmarks.references.cohere2_moe"]:
    importlib.import_module(m)
assert len(jax.live_arrays()) == before, (before, len(jax.live_arrays()))
assert compiles == [], compiles
print("ok")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=300)
    assert p.returncode == 0 and p.stdout.strip().endswith("ok"), p.stderr
