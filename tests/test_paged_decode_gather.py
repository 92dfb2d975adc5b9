"""`paged_attention_decode` gathers each token tile's live pages with its
own copies, the next step's while this one computes (interpret mode on the
CPU).

Every pool page that no block table names holds NaN, the pad page 0
included, so a copy aimed at the wrong page, a dead slot copied, or a
buffer row that no copy of this step wrote and that reaches the `p @ V`
product, turns the row's output to NaN. The lengths end on both sides of
a tile's edge and inside pages, rows follow one another so that the copies
started one step ahead cross into the next row, and the table width is no
multiple of the pages a tile gathers. Each gather form of `_gather_plan`
is driven: pages joined along the token axis, pages laid whole, and pages
the pipeline fetches; int8 pages with their scale pages; windows whose
first visible key lies inside a tile, beside rows shorter than the window.
"""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.paged_attention import (_fold_pages, _gather_plan,
                                                _live_steps,
                                                paged_attention_decode,
                                                paged_decode_page_counts)

KVH, SCALE = 2, 0.3


def _case(layout, fold, page):
    """(table width, row lengths) of each layout, in tiles of the kernel's
    own size."""
    T = fold * page
    if layout == "ragged":          # three tiles and five pages
        P = 3 * fold + 5
        return P, [1, T - 1, T, T + 1, P * page, 2 * T + 37, 3 * T + 1,
                   page + 3]
    if layout == "narrow":          # a table narrower than a tile
        return 5, [1, 2 * page, 5 * page, 37]
    return 2 * fold + 1, [(2 * fold + 1) * page]        # "one-row"


def _inputs(layout, page, D, G, kv, seed=0):
    rng = np.random.default_rng(seed)
    fold = _fold_pages(page, 1 << 20, KVH, D, jnp.int8 if kv == "int8"
                       else jnp.bfloat16)
    P, lens = _case(layout, fold, page)
    lens = np.asarray(lens, np.int32)
    B = len(lens)
    live = np.arange(P)[None, :] * page < lens[:, None]
    n_pages = 1 + int(live.sum()) + 7
    ids = 1 + rng.permutation(n_pages - 1)
    bt = np.zeros((B, P), np.int32)
    bt[live] = ids[:int(live.sum())]
    unnamed = np.concatenate([[0], ids[int(live.sum()):]])
    shape = (n_pages, KVH, page, D)
    q = rng.normal(size=(B, KVH * G, D)).astype(np.float32)
    if kv == "int8":
        k, v = (rng.integers(-127, 128, size=shape).astype(np.int8)
                for _ in range(2))
        ks, vs = (rng.uniform(0.002, 0.02, size=shape[:3]).astype(np.float32)
                  for _ in range(2))
        ks[unnamed] = np.nan
        vs[unnamed] = np.nan
        pools = [jnp.asarray(a) for a in (k, v, ks, vs)]
    else:
        k, v = (rng.normal(size=shape).astype(np.float32) for _ in range(2))
        k[unnamed] = np.nan
        v[unnamed] = np.nan
        pools = [jnp.asarray(a, kv) for a in (k, v)]
    return (jnp.asarray(q), pools, jnp.asarray(bt), jnp.asarray(lens))


def _reference(q, pools, bt, lens, window=None):
    """A plain softmax over each row's visible keys, in float32."""
    pools = [np.asarray(a.astype(jnp.float32)) for a in pools]
    if len(pools) == 4:
        k = pools[0] * pools[2][..., None]
        v = pools[1] * pools[3][..., None]
    else:
        k, v = pools
    B, H, D = q.shape
    out = []
    for b in range(B):
        n = int(lens[b])
        lo = 0 if window is None else max(n - window, 0)
        keys = k[np.asarray(bt[b])].transpose(1, 0, 2, 3).reshape(KVH, -1, D)
        vals = v[np.asarray(bt[b])].transpose(1, 0, 2, 3).reshape(KVH, -1, D)
        keys, vals = keys[:, lo:n], vals[:, lo:n]
        qb = np.asarray(q[b]).reshape(KVH, H // KVH, D)
        s = np.einsum("kgd,ktd->kgt", qb, keys) * SCALE
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        out.append(np.einsum("kgt,ktd->kgd", p, vals).reshape(H, D))
    return np.stack(out)


# (page, head dim, kv) -> the gather form it takes on the chip
FORMS = {
    "joined-f32": (16, 128, jnp.float32),       # pages along the token axis
    "joined-bf16": (16, 128, jnp.bfloat16),
    "whole-pages-bf16": (8, 128, jnp.bfloat16),  # a page half a bf16 tile
    "pipelined-d64": (16, 64, jnp.float32),     # the pipeline's BlockSpecs
    "int8-p16": (16, 128, "int8"),              # scale pages pipelined
    "int8-p128": (128, 128, "int8"),            # scale pages copied too
}
TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 1e-2, "int8": 2e-5}


def _run(form, layout, G, window=None):
    page, D, kv = FORMS[form]
    q, pools, bt, lens = _inputs(layout, page, D, G, kv)
    scales = dict(k_scale=pools[2], v_scale=pools[3]) if kv == "int8" \
        else {}
    got = np.asarray(paged_attention_decode(
        q, pools[0], pools[1], bt, lens, sm_scale=SCALE, window=window,
        **scales))
    assert got.shape == q.shape
    assert np.isfinite(got).all()
    want = _reference(q, pools, bt, lens, window)
    tol = TOLS[kv]
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_every_form_is_one_the_plan_names():
    plans = {form: _gather_plan(page, D, jnp.int8 if kv == "int8" else kv,
                                kv == "int8")
             for form, (page, D, kv) in FORMS.items()}
    assert plans["joined-bf16"] == (True, True, False)
    assert plans["whole-pages-bf16"] == (True, False, False)
    assert plans["pipelined-d64"] == (False, False, False)
    assert plans["int8-p16"] == (True, False, False)
    assert plans["int8-p128"] == (True, True, True)


@pytest.mark.parametrize("layout", ["ragged", "narrow", "one-row"])
@pytest.mark.parametrize("form", list(FORMS))
def test_gathered_tiles_match_a_plain_softmax(form, layout):
    _run(form, layout, G=4)


@pytest.mark.parametrize("form", ["joined-f32", "int8-p16"])
def test_sixteen_query_heads_a_kv_head(form):
    _run(form, "ragged", G=16)


@pytest.mark.parametrize("form", ["joined-f32", "whole-pages-bf16",
                                  "pipelined-d64"])
@pytest.mark.parametrize("window", [40, 300])
def test_a_window_starts_inside_a_tile(form, window):
    """Window 40 starts inside a page of every long row's last tile; 300
    leaves some rows shorter than the window and starts the others'
    inside an earlier tile."""
    _run(form, "ragged", G=4, window=window)


@pytest.mark.parametrize("window", [None, 40, 300])
@pytest.mark.parametrize("page,P", [(16, 53), (16, 5), (16, 9), (128, 16)])
def test_page_counts_match_a_brute_force_count(page, P, window):
    """`paged_decode_page_counts` against the grid the kernel runs
    (`_live_steps`) and the pages whose keys a row's query sees, counted
    a position at a time."""
    D = 128
    fold = _fold_pages(page, P, 8, D, jnp.bfloat16)
    lens = np.asarray([0, 1, page - 1, page, page + 1, 255, 256, 257, 300,
                       333, 412, P * page - 1, P * page, P * page + 40],
                      np.int32)
    tiles, copied = paged_decode_page_counts(lens, page, P, 8, D,
                                             jnp.bfloat16, window)
    steps_per_row = -(-P // fold)
    sl = np.minimum(lens, P * page)
    total, *_ = _live_steps(jnp.asarray(sl), fold * page, steps_per_row,
                            fold, window=window)
    assert tiles == int(total) * fold
    want = 0
    for n in sl:
        lo = 0 if window is None else max(int(n) - window, 0)
        want += len({j // page for j in range(lo, int(n))})
    assert copied == want
    assert copied <= tiles


def test_page_counts_add_over_rows():
    rows = [[3, 200], [700, 0, 45]]
    parts = [paged_decode_page_counts(np.asarray(r), 16, 64, 8, 128,
                                      jnp.bfloat16, 4096) for r in rows]
    whole = paged_decode_page_counts(
        np.asarray(list(itertools.chain(*rows))), 16, 64, 8, 128,
        jnp.bfloat16, 4096)
    assert whole == tuple(map(sum, zip(*parts)))


def test_the_engine_counts_each_plain_decode_launch_by_the_kernels_rules():
    """One row of 5 prompt tokens and 4 new ones decodes in a B 1 x P 4
    bucket at page 8: each launch's row runs one step of 4 page slots
    (the table clamps the tile) and copies the one page its <= 8 tokens
    fill, in each of the model's 2 layers."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine
    from tests._engine_steps import drain
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=128))
    eng = ServingEngine(model, num_pages=32, page_size=8, token_budget=64,
                        batch_buckets=[1], prefill_buckets=[8],
                        pages_buckets=[4], temperature=0.0)
    try:
        eng.add_request([3, 1, 4, 1, 5], max_new_tokens=4)
        drain(eng)
        c = eng.metrics.counters
        launches = c["decode_launches"]
        assert launches >= 3
        assert c["decode_kv_tile_pages"] == 2 * 4 * launches
        assert c["decode_kv_pages_copied"] == 2 * 1 * launches
    finally:
        eng.shutdown()
