"""The flash kernel's forward where positions are data (every serving
chunk: `flash_attention_chunk_gqa`, `flash_attention_varlen_bshd`): which
tiles it computes is read from one table, the tiles it skips are not
fetched, and both products take their operands as stored.

Interpret mode on the CPU, tiles cut to 128 so that one small chunk holds
blocks of every kind at once; each case checks that it does. The reference
is the float32 `jax.numpy` composition over the dense mask."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import flash_attention as fa

TILE = 128
BLOCK_CAP = fa._block_cap


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(fa, "_block_cap", lambda d: TILE)


def _dense(q, k, v, allow, scale):
    """q (S, H, D), k and v (T, H, D), allow (S, T): float32 throughout."""
    q, k, v = (np.asarray(x.astype(jnp.float32)) for x in (q, k, v))
    s = np.einsum("qhd,khd->hqk", q, k) * scale
    s = np.where(allow[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)


def _allow(segq, posq, segk, posk, causal, window):
    allow = segq[:, None] == segk[None, :]
    if causal:
        allow &= posk[None, :] <= posq[:, None]
    if window is not None:
        allow &= posk[None, :] > posq[:, None] - window
    return allow


def _block_kinds(allow, bq, bk):
    """Per (q block, k block): 'visible' (every score allowed), 'edge' (some)
    or 'none', from the dense mask."""
    nq, nk = allow.shape[0] // bq, allow.shape[1] // bk
    tiles = allow.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    return np.where(tiles.all((2, 3)), "visible",
                    np.where(tiles.any((2, 3)), "edge", "none"))


def _rows_blind_in_first_block(allow, bq, bk):
    """Whether some query row sees no key in the first block its q block
    computes (and some key later)."""
    for i in range(allow.shape[0] // bq):
        rows = allow[i * bq:(i + 1) * bq]
        seen = rows.reshape(bq, -1, bk).any(-1)           # (bq, nk)
        first = np.argmax(seen.any(0))
        if (~seen[:, first] & seen.any(1)).any():
            return True
    return False


def _inputs(s, t, h, kvh, d, dtype, seed):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.5, dtype)
    return mk(s, h, d), mk(t, kvh, d), mk(t, kvh, d)


def _tolerance(dtype):
    # bfloat16: the output's own rounding and p's, 2^-9 of values near 1
    return 2e-5 if dtype == jnp.float32 else 2e-2


CHUNK_CASES = [(dtype, window) for dtype in (jnp.float32, jnp.bfloat16)
               for window in (None, 400)]


def _chunk_case(window):
    """A 256-token chunk at positions 600.. of a sequence whose gathered
    table runs from 0 to 1,023: keys past the sequence's end (856 on), a
    causal edge, and with the window blocks behind it and at its edge."""
    s, t = 256, 1024
    qpos, kpos = 600 + np.arange(s), np.arange(t)
    ones = lambda n: np.ones(n, np.int32)
    return qpos, kpos, _allow(ones(s), qpos, ones(t), kpos, True, window)


@pytest.mark.parametrize("dtype,window", CHUNK_CASES,
                         ids=[f"{np.dtype(d).name}-window{w}"
                              for d, w in CHUNK_CASES])
def test_chunk_gqa_forward_over_blocks_of_every_kind(dtype, window):
    h, kvh, d = 4, 2, 64
    qpos, kpos, allow = _chunk_case(window)
    kinds = _block_kinds(allow, TILE, TILE)
    assert {"visible", "edge", "none"} <= set(kinds.ravel())
    # wholly in the future (and past the end), and wholly behind the window
    assert (kinds[:, -1] == "none").all()
    assert window is None or (kinds[:, 0] == "none").all()
    assert window is None or _rows_blind_in_first_block(allow, TILE, TILE)
    q, k, v = _inputs(len(qpos), len(kpos), h, kvh, d, dtype, 11)
    out = fa.flash_attention_chunk_gqa(q, k, v, jnp.asarray(qpos),
                                       jnp.asarray(kpos), window=window)
    assert out.dtype == dtype
    want = _dense(q, jnp.repeat(k, h // kvh, 1), jnp.repeat(v, h // kvh, 1),
                  allow, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), want,
                               atol=_tolerance(dtype))


def _varlen_case(form):
    """(segq, posq, segk, posk) of one row of `flash_attention_varlen_bshd`.
    'packed': three sequences packed along 1,024 tokens, boundaries inside
    blocks, positions from the segments. 'chunk': Kimi's call, one segment,
    256 queries at positions 500.. over a table of 1,024 keys."""
    if form == "packed":
        seg = np.zeros(1024, np.int32)
        seg[300:700], seg[700:] = 1, 2
        pos = np.asarray(fa._positions_in_segments(jnp.asarray(seg[None])))[0]
        return seg, pos, seg, pos
    return (np.ones(256, np.int32), 500 + np.arange(256),
            np.ones(1024, np.int32), np.arange(1024))


VARLEN_CASES = [(form, dtype, causal) for form in ("packed", "chunk")
                for dtype in (jnp.float32, jnp.bfloat16)
                for causal in (True, False) if causal or form == "packed"]


@pytest.mark.parametrize("form,dtype,causal", VARLEN_CASES,
                         ids=[f"{f}-{np.dtype(d).name}-"
                              f"{'causal' if c else 'full'}"
                              for f, d, c in VARLEN_CASES])
def test_varlen_forward_over_blocks_of_every_kind(form, dtype, causal):
    h, d = 2, 64
    segq, posq, segk, posk = _varlen_case(form)
    allow = _allow(segq, posq, segk, posk, causal, None)
    kinds = _block_kinds(allow, TILE, TILE)
    assert {"visible", "edge", "none"} <= set(kinds.ravel())
    if form == "packed":
        # a q block across a boundary: its later rows see nothing in the
        # first block it computes
        assert _rows_blind_in_first_block(allow, TILE, TILE)
    q, k, v = _inputs(len(segq), len(segk), h, h, d, dtype, 13)
    explicit = dict(q_positions=jnp.asarray(posq)[None],
                    kv_positions=jnp.asarray(posk)[None]) \
        if form == "chunk" else {}
    out = fa.flash_attention_varlen_bshd(
        q[None], k[None], v[None], jnp.asarray(segq)[None],
        jnp.asarray(segk)[None], causal=causal, **explicit)[0]
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               _dense(q, k, v, allow, d ** -0.5),
                               atol=_tolerance(dtype))


@pytest.mark.parametrize("window", [None, 400], ids=["full", "window"])
def test_the_table_skips_no_tile_that_holds_a_visible_score(window):
    """`_seg_block_table` over a chunk's rows against the dense mask: the
    rule is a bound on min and max, so it may compute a tile that holds no
    visible score, never skip one that does; and it does skip."""
    g = 2
    qpos, kpos, allow = _chunk_case(window)
    rows = lambda pos: jnp.stack([jnp.ones_like(pos), pos])[None]
    computed = np.asarray(fa._seg_block_table(
        rows(jnp.repeat(jnp.asarray(qpos), g)), rows(jnp.asarray(kpos)),
        TILE, TILE, True, window)[0])
    # a position's g query heads lie side by side on the query axis
    kinds = _block_kinds(np.repeat(allow, g, axis=0), TILE, TILE)
    assert computed[kinds != "none"].all()
    assert (kinds != "none").sum() <= computed.sum() < kinds.size
    # the index maps clamp a q block's fetches to the tiles it computes
    assert (~computed[:, -1]).all() and (window is None
                                         or (~computed[:, 0]).all())


def test_the_table_is_the_rule_a_block_at_a_time():
    """`_seg_block_table` over packed segments equals the backward
    kernels' reading of each block's own vectors."""
    seg, pos, _, _ = _varlen_case("packed")
    rows = jnp.stack([jnp.asarray(seg), jnp.asarray(pos)])[None]
    for causal in (True, False):
        contributes = np.asarray(fa._seg_block_table(
            rows, rows, TILE, TILE, causal, None)[0])
        for i in range(len(seg) // TILE):
            for j in range(len(seg) // TILE):
                qs, ks = (slice(n * TILE, (n + 1) * TILE) for n in (i, j))
                qb = fa._block_bounds(rows[0, 0, qs], rows[0, 1, qs])
                kb = fa._block_bounds(rows[0, 0, ks], rows[0, 1, ks])
                assert contributes[i, j] == bool(
                    fa._seg_block_contributes(qb, kb, causal, None))


@pytest.mark.parametrize("chunk", [16, 256, 512, 1024, 2048])
def test_a_count_of_keys_that_tiles_badly_is_padded(chunk, monkeypatch):
    """At `serve-mixed-context`'s sizes (window 4,096, page 16: a window
    layer gathers the window's and the chunk's pages and one more, in
    whole eights): the kernel runs key tiles of 512 or more, over fewer
    than 512 keys more than it was given (the 512- and 1,024-token chunks
    gather 37 and 41 x 128 keys: 128-key tiles)."""
    monkeypatch.setattr(fa, "_block_cap", BLOCK_CAP)
    t = -(-((4096 + chunk) // 16 + 1) // 8) * 8 * 16
    keys = fa._chunk_gqa_keys(t, 128)
    assert keys % 128 == 0 and 0 <= keys - t < 512
    assert fa._pick_block_k(keys, 128) >= 512
    assert keys == {512: 5120, 1024: 5376, 2048: t}.get(chunk, keys)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_chunk_gqa_forward_over_padded_keys(dtype, monkeypatch):
    """640 keys divide into 128-key tiles only where the largest is 512:
    the call runs 768 in tiles of 384, the padding in every query's
    future, and gives what the dense composition gives over the 640."""
    monkeypatch.setattr(fa, "_block_cap", lambda d: 512)
    h, kvh, d, s, t = 4, 2, 64, 128, 640
    assert fa._chunk_gqa_keys(t, d) == 768 and fa._pick_block_k(768, d) == 384
    qpos, kpos = 480 + np.arange(s), np.arange(t)
    ones = lambda n: np.ones(n, np.int32)
    allow = _allow(ones(s), qpos, ones(t), kpos, True, 300)
    q, k, v = _inputs(s, t, h, kvh, d, dtype, 19)
    out = fa.flash_attention_chunk_gqa(q, k, v, jnp.asarray(qpos),
                                       jnp.asarray(kpos), window=300)
    want = _dense(q, jnp.repeat(k, h // kvh, 1), jnp.repeat(v, h // kvh, 1),
                  allow, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), want,
                               atol=_tolerance(dtype))


def test_a_block_table_past_scalar_memory_is_refused():
    """The table rides in scalar memory, a word a tile over the batch
    rows: past its room the call raises (and `nn.functional`'s varlen
    attention falls back) before the compiler refuses it."""
    q = jax.ShapeDtypeStruct((fa._TABLE_TILES // 4 + 1, 256, 1, 64),
                             jnp.bfloat16)
    seg = jax.ShapeDtypeStruct(q.shape[:2], jnp.int32)
    with pytest.raises(ValueError, match="block table"):
        jax.eval_shape(fa.flash_attention_varlen_bshd, q, q, q, seg, seg)
