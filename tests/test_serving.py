"""Serving/decode path: paged-KV Pallas attention, contiguous-cache decode
MHA, top-p sampling. Parity targets: reference block_multi_head_attention /
masked_multihead_attention (`phi/kernels/fusion/gpu/`) and
`paddle.tensor.top_p_sampling` (`python/paddle/tensor/search.py:1363`)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels.paged_attention import (alloc_paged_cache,
                                                paged_attention_decode,
                                                paged_cache_write)
from paddle_tpu.incubate.nn.functional import (block_multihead_attention,
                                               masked_multihead_attention)

rng = np.random.RandomState(0)


def _dense_decode_ref(q, kd, vd, seq_lens):
    """q (B,H,D); kd/vd dense (B, KVH, S, D); mask by seq_lens."""
    B, H, D = q.shape
    KVH = kd.shape[1]
    G = H // KVH
    qg = q.reshape(B, KVH, G, D).astype(np.float32)
    s = np.einsum("bhgd,bhsd->bhgs", qg, kd.astype(np.float32)) / np.sqrt(D)
    pos = np.arange(kd.shape[2])[None, None, None, :]
    s = np.where(pos < seq_lens[:, None, None, None], s, -1e30)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(-1, keepdims=True)
    o = np.einsum("bhgs,bhsd->bhgd", p, vd.astype(np.float32))
    return o.reshape(B, H, D)


def _build_paged(B, KVH, D, page, max_pages, seq_lens):
    """Random dense KV + its paged image with a shuffled page assignment."""
    S = page * max_pages
    kd = rng.randn(B, KVH, S, D).astype(np.float32)
    vd = rng.randn(B, KVH, S, D).astype(np.float32)
    num_pages = B * max_pages + 3
    kc = np.zeros((num_pages, KVH, page, D), np.float32)
    vc = np.zeros((num_pages, KVH, page, D), np.float32)
    perm = rng.permutation(num_pages - 1) + 1  # keep page 0 as the pad page
    bt = np.zeros((B, max_pages), np.int32)
    n = 0
    for b in range(B):
        for j in range(max_pages):
            if j * page >= seq_lens[b]:
                continue  # unused slots stay 0 (pad page)
            pid = int(perm[n]); n += 1
            bt[b, j] = pid
            kc[pid] = kd[b, :, j * page:(j + 1) * page]
            vc[pid] = vd[b, :, j * page:(j + 1) * page]
    return kd, vd, kc, vc, bt


@pytest.mark.parametrize("G", [1, 4])
def test_paged_attention_decode_matches_dense(G):
    B, KVH, D, page, max_pages = 3, 2, 128, 16, 4
    H = KVH * G
    seq_lens = np.array([5, 37, 64], np.int32)
    q = rng.randn(B, H, D).astype(np.float32)
    kd, vd, kc, vc, bt = _build_paged(B, KVH, D, page, max_pages, seq_lens)
    out = paged_attention_decode(jnp.asarray(q), jnp.asarray(kc),
                                 jnp.asarray(vc), jnp.asarray(bt),
                                 jnp.asarray(seq_lens))
    ref = _dense_decode_ref(q, kd, vd, seq_lens)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def _assert_matches_dense(out, q, kd, vd, seq_lens):
    live = seq_lens > 0
    ref = _dense_decode_ref(q, kd, vd, seq_lens)
    np.testing.assert_allclose(np.asarray(out)[live], ref[live],
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out)[~live].any()


def _tile_edge_case(page, G, kv_dtype, KVH=2):
    """Ragged rows on every edge of the kernel's token tiling, over a
    table one page wider than two folds (so the block table is padded
    and the last grid step holds one live page)."""
    from paddle_tpu.kernels.paged_attention import _fold_pages, quantize_kv
    D = 64
    cache_dtype = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    fold = _fold_pages(page, 10 ** 6, 2, D, cache_dtype)
    T = fold * page
    max_pages = 2 * fold + 1
    # the empty row is a bucket-padded batch row: its output is zeros;
    # the last row's length overshoots its table and reads all of it
    seq_lens = np.array([0, 1, page - 1, page, page + 1, T - 1, T, T + 1,
                         2 * T, 2 * T + 1, max_pages * page,
                         max_pages * page + 7], np.int32)
    B = len(seq_lens)
    kd, vd, kc, vc, bt = _build_paged(B, KVH, D, page, max_pages, seq_lens)
    q = rng.randn(B, KVH * G, D).astype(np.float32)
    if kv_dtype == "int8":
        kq, ks = quantize_kv(jnp.asarray(kc))
        vq, vs = quantize_kv(jnp.asarray(vc))
        kq_d, ks_d = quantize_kv(jnp.asarray(kd))      # per-slot: same values
        vq_d, vs_d = quantize_kv(jnp.asarray(vd))
        kd = np.asarray(kq_d, np.float32) * np.asarray(ks_d)[..., None]
        vd = np.asarray(vq_d, np.float32) * np.asarray(vs_d)[..., None]
        return q, (kq, vq), dict(k_scale=ks, v_scale=vs), bt, seq_lens, kd, vd
    kc, vc = jnp.asarray(kc, jnp.bfloat16), jnp.asarray(vc, jnp.bfloat16)
    kd = np.asarray(jnp.asarray(kd, jnp.bfloat16).astype(jnp.float32))
    vd = np.asarray(jnp.asarray(vd, jnp.bfloat16).astype(jnp.float32))
    return q, (kc, vc), {}, bt, seq_lens, kd, vd


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("G", [1, 4, 8])
@pytest.mark.parametrize("page", [8, 16, 32, 128])
def test_paged_attention_decode_tile_edges(page, G, kv_dtype):
    """The joined-tile kernel against dense float32 attention over the
    values the cache holds: a page is only the unit of the gather, so
    no page size, group size or storage dtype may change the answer."""
    q, caches, scales, bt, seq_lens, kd, vd = _tile_edge_case(page, G,
                                                              kv_dtype)
    out = paged_attention_decode(jnp.asarray(q), *caches, jnp.asarray(bt),
                                 jnp.asarray(seq_lens), **scales)
    _assert_matches_dense(out, q, kd, vd, seq_lens)


@pytest.mark.parametrize("tp,kv_dtype", [(2, "bf16"), (4, "bf16"),
                                         (4, "int8")])
def test_paged_attention_decode_tp_tile_edges(tp, kv_dtype):
    """The TP wrapper runs the same kernel on KVH/tp = 2 heads a shard:
    the same tile edges, through a `shard_map` over `model`."""
    from paddle_tpu.kernels.paged_attention import paged_attention_decode_tp
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:tp]), ("model",))
    q, caches, scales, bt, seq_lens, kd, vd = _tile_edge_case(
        16, 4, kv_dtype, KVH=2 * tp)
    out = paged_attention_decode_tp(jnp.asarray(q), *caches, jnp.asarray(bt),
                                    jnp.asarray(seq_lens), mesh, **scales)
    _assert_matches_dense(out, q, kd, vd, seq_lens)


@pytest.mark.parametrize("page", [16, 128])
def test_paged_attention_decode_rows_stay_independent(page):
    """A row's steps gather its own table's slots only (its pages, and
    past them the pad page), never another sequence's: NaN in every
    page one row owns changes that row's answer and no other's — what
    the engine's per-row quarantine rests on."""
    q, (kc, vc), _, bt, seq_lens, _, _ = _tile_edge_case(page, 4, "bf16")
    args = (jnp.asarray(bt), jnp.asarray(seq_lens))
    clean = np.asarray(paged_attention_decode(jnp.asarray(q), kc, vc, *args))
    victim = 7                                           # the T + 1 row
    owned = bt[victim][:-(-int(seq_lens[victim]) // page)]
    kc = kc.at[owned].set(jnp.nan)
    vc = vc.at[owned].set(jnp.nan)
    out = np.asarray(paged_attention_decode(jnp.asarray(q), kc, vc, *args))
    others = np.arange(len(seq_lens)) != victim
    assert np.isnan(out[victim]).all()
    np.testing.assert_array_equal(out[others], clean[others])


def test_paged_cache_write_roundtrip():
    B, KVH, D, page, max_pages = 2, 2, 128, 16, 3
    seq_lens = np.array([page * max_pages, page * max_pages], np.int32)
    _, _, kc, vc, bt = _build_paged(B, KVH, D, page, max_pages, seq_lens)
    knew = rng.randn(B, KVH, D).astype(np.float32)
    vnew = rng.randn(B, KVH, D).astype(np.float32)
    pos = np.array([17, 40], np.int32)  # page 1 slot 1 / page 2 slot 8
    kc2, vc2 = paged_cache_write(jnp.asarray(kc), jnp.asarray(vc),
                                 jnp.asarray(knew), jnp.asarray(vnew),
                                 jnp.asarray(bt), jnp.asarray(pos))
    kc2, vc2 = np.asarray(kc2), np.asarray(vc2)
    for b in range(B):
        pid = bt[b, pos[b] // page]
        off = pos[b] % page
        np.testing.assert_allclose(kc2[pid, :, off], knew[b], rtol=1e-6)
        np.testing.assert_allclose(vc2[pid, :, off], vnew[b], rtol=1e-6)
    # everything else untouched
    mask = np.ones(kc.shape, bool)
    for b in range(B):
        mask[bt[b, pos[b] // page], :, pos[b] % page] = False
    np.testing.assert_allclose(kc2[mask], kc[mask], rtol=1e-6)


def test_block_multihead_attention_decode_steps():
    """A few decode steps through the paged path match the dense cache."""
    B, KVH, G, D, page, max_pages = 2, 2, 2, 128, 8, 4
    H = KVH * G
    kc, vc = alloc_paged_cache(KVH, B * max_pages + 1, page, D, jnp.float32)
    bt = jnp.asarray(
        1 + np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages))
    S = page * max_pages
    kd = np.zeros((B, KVH, S, D), np.float32)
    vd = np.zeros((B, KVH, S, D), np.float32)
    for t in range(3):
        qkv = rng.randn(B, (H + 2 * KVH) * D).astype(np.float32)
        lens = np.full((B,), t, np.int32)
        out, kc, vc = block_multihead_attention(
            paddle.to_tensor(qkv), paddle.to_tensor(kc),
            paddle.to_tensor(vc), paddle.to_tensor(lens),
            paddle.to_tensor(bt))
        out, kc, vc = out._data, kc._data, vc._data
        parts = qkv.reshape(B, H + 2 * KVH, D)
        kd[:, :, t] = parts[:, H:H + KVH]
        vd[:, :, t] = parts[:, H + KVH:]
        ref = _dense_decode_ref(parts[:, :H], kd, vd,
                                np.full((B,), t + 1, np.int32))
        np.testing.assert_allclose(np.asarray(out).reshape(B, H, D), ref,
                                   rtol=2e-5, atol=2e-5)


def test_masked_multihead_attention_matches_dense():
    B, KVH, G, D, S = 2, 2, 3, 64, 32
    H = KVH * G
    cache = np.zeros((2, B, KVH, S, D), np.float32)
    kd = np.zeros((B, KVH, S, D), np.float32)
    vd = np.zeros((B, KVH, S, D), np.float32)
    for t in range(4):
        x = rng.randn(B, (H + 2 * KVH) * D).astype(np.float32)
        lens = np.full((B,), t, np.int32)
        out, cache_t = masked_multihead_attention(
            paddle.to_tensor(x), paddle.to_tensor(cache),
            sequence_lengths=paddle.to_tensor(lens))
        cache = np.asarray(cache_t._data)
        parts = x.reshape(B, H + 2 * KVH, D)
        kd[:, :, t] = parts[:, H:H + KVH]
        vd[:, :, t] = parts[:, H + KVH:]
        ref = _dense_decode_ref(parts[:, :H], kd, vd,
                                np.full((B,), t + 1, np.int32))
        np.testing.assert_allclose(
            np.asarray(out._data).reshape(B, H, D), ref,
            rtol=2e-5, atol=2e-5)
    # cache holds exactly the appended keys/values
    np.testing.assert_allclose(cache[0][:, :, :4], kd[:, :, :4], rtol=1e-6)


def test_top_p_sampling_nucleus_membership():
    paddle.seed(7)
    B, V = 4, 50
    logits = rng.randn(B, V).astype(np.float32) * 3
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    ps = np.array([0.1, 0.5, 0.9, 0.99], np.float32)
    for _ in range(5):
        vals, ids = paddle.tensor.top_p_sampling(
            paddle.to_tensor(probs), paddle.to_tensor(ps))
        ids_np = np.asarray(ids._data).reshape(B)
        vals_np = np.asarray(vals._data).reshape(B)
        for b in range(B):
            order = np.argsort(-probs[b])
            rank = int(np.where(order == ids_np[b])[0][0])
            mass_before = probs[b][order][:rank].sum()
            assert mass_before < ps[b] or rank == 0
            np.testing.assert_allclose(vals_np[b], probs[b, ids_np[b]],
                                       rtol=1e-5)


def test_top_p_sampling_greedy_and_topk():
    B, V = 3, 20
    logits = rng.randn(B, V).astype(np.float32)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    tiny = np.full((B,), 1e-6, np.float32)
    vals, ids, tv, ti = paddle.tensor.top_p_sampling(
        paddle.to_tensor(probs), paddle.to_tensor(tiny), seed=3, k=5,
        return_top=True)
    np.testing.assert_array_equal(np.asarray(ids._data).reshape(B),
                                  probs.argmax(-1))
    np.testing.assert_array_equal(np.asarray(ti._data),
                                  np.argsort(-probs, -1)[:, :5])
    assert np.asarray(tv._data).shape == (B, 5)


def test_top_p_sampling_fixed_seed_deterministic():
    B, V = 2, 30
    probs = np.full((B, V), 1.0 / V, np.float32)
    ps = np.full((B,), 0.8, np.float32)
    r1 = paddle.tensor.top_p_sampling(paddle.to_tensor(probs),
                                      paddle.to_tensor(ps), seed=11)
    r2 = paddle.tensor.top_p_sampling(paddle.to_tensor(probs),
                                      paddle.to_tensor(ps), seed=11)
    np.testing.assert_array_equal(np.asarray(r1[1]._data),
                                  np.asarray(r2[1]._data))


def test_decode_rope_styles():
    """neox=True rotates halves; neox=False rotates (even, odd) pairs —
    matching models/llama.py's pair convention at position p."""
    from paddle_tpu.incubate.nn.functional import _apply_decode_rope
    B, D = 2, 8
    t = rng.randn(B, 3, D).astype(np.float32)
    theta = rng.rand(D // 2).astype(np.float32)
    cos = np.repeat(np.cos(theta)[None, None, :], 2, axis=-1)  # half layout
    sin = np.repeat(np.sin(theta)[None, None, :], 2, axis=-1)
    out_neox = np.asarray(_apply_decode_rope(
        jnp.asarray(t), jnp.asarray(cos), jnp.asarray(sin), True))
    h1, h2 = t[..., :D // 2], t[..., D // 2:]
    ref = np.concatenate([h1 * cos[..., :D // 2] - h2 * sin[..., :D // 2],
                          h2 * cos[..., D // 2:] + h1 * sin[..., D // 2:]],
                         axis=-1)
    np.testing.assert_allclose(out_neox, ref, rtol=1e-6)

    # interleaved layout: cos/sin repeat per (even, odd) pair
    cos_i = np.asarray(np.stack([np.cos(theta), np.cos(theta)], -1)).reshape(-1)[None, None]
    sin_i = np.asarray(np.stack([np.sin(theta), np.sin(theta)], -1)).reshape(-1)[None, None]
    out_pair = np.asarray(_apply_decode_rope(
        jnp.asarray(t), jnp.asarray(cos_i), jnp.asarray(sin_i), False))
    even, odd = t[..., 0::2], t[..., 1::2]
    c, s = np.cos(theta), np.sin(theta)
    ref_e = even * c - odd * s
    ref_o = odd * c + even * s
    ref_pair = np.stack([ref_e, ref_o], axis=-1).reshape(t.shape)
    np.testing.assert_allclose(out_pair, ref_pair, rtol=1e-6)


def test_top_p_threshold_respected_in_both_modes():
    B, V = 2, 16
    probs = np.full((B, V), 1.0 / V, np.float32)
    probs[:, 0] = 0.4
    probs = probs / probs.sum(-1, keepdims=True)
    th = np.full((B,), 0.3, np.float32)  # only token 0 passes
    ps = np.full((B,), 0.99, np.float32)
    for mode in ("truncated", "non-truncated"):
        _, ids = paddle.tensor.top_p_sampling(
            paddle.to_tensor(probs), paddle.to_tensor(ps),
            threshold=paddle.to_tensor(th), seed=5, mode=mode)
        np.testing.assert_array_equal(np.asarray(ids._data).reshape(B), 0)
