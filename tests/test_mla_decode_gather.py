"""`mla_paged_decode` gathers each token tile's pages with its own copies,
the next step's while this one computes (interpret mode on the CPU).

Every pool page that no block table names holds NaN, so a copy aimed at
the wrong page, or a buffer row that no copy of this step wrote, turns the
row's output to NaN. The lengths end on both sides of a tile's edge and
inside tiles, rows follow one another so that the copies started one step
ahead cross into the next row, and the table width is no multiple of the
pages a tile gathers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.mla_attention import mla_fold_pages, mla_paged_decode

PAGE, H, RANK, W = 16, 8, 128, 256     # an entry of 128 + 16 values, padded
SCALE = 0.3


def _case(layout):
    """(table width, row lengths) of each layout, in tiles of the kernel's
    own size."""
    fold = mla_fold_pages(PAGE, 1 << 20)
    T = fold * PAGE
    if layout == "ragged":          # three tiles and five pages
        P = 3 * fold + 5
        return P, [1, T - 1, T, T + 1, P * PAGE, 2 * T + 37, 3 * T + 1,
                   PAGE + 3]
    if layout == "narrow":          # a table narrower than a tile
        return 5, [1, 2 * PAGE, 5 * PAGE, 37]
    return 2 * fold + 1, [(2 * fold + 1) * PAGE]        # "one-row"


def _inputs(layout, dtype, seed=0):
    rng = np.random.default_rng(seed)
    P, lens = _case(layout)
    lens = np.asarray(lens, np.int32)
    B = len(lens)
    live = np.arange(P)[None, :] * PAGE < lens[:, None]
    n_pages = 1 + int(live.sum()) + 7
    pool = rng.normal(size=(n_pages, PAGE, W)).astype(np.float32)
    pool[:, :, RANK + 16:] = 0
    pool[0] = 0                         # the pad page that dead slots name
    ids = 1 + rng.permutation(n_pages - 1)
    bt = np.zeros((B, P), np.int32)
    bt[live] = ids[:int(live.sum())]
    unnamed = ids[int(live.sum()):]
    pool[unnamed] = np.nan
    q = rng.normal(size=(B, H, W)).astype(np.float32)
    q[:, :, RANK + 16:] = 0
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(bt), jnp.asarray(lens))


def _reference(q, pool, bt, lens):
    """A plain softmax over each row's gathered entries, in float32."""
    out = []
    for b in range(bt.shape[0]):
        n = int(lens[b])
        ent = np.asarray(pool.astype(jnp.float32))[np.asarray(bt[b])]
        ent = ent.reshape(-1, W)[:n]
        s = np.asarray(q[b].astype(jnp.float32)) @ ent.T * SCALE
        p = np.exp(s - s.max(axis=1, keepdims=True))
        out.append((p / p.sum(axis=1, keepdims=True)) @ ent[:, :RANK])
    return np.stack(out)


@pytest.mark.parametrize("layout", ["ragged", "narrow", "one-row"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
def test_gathered_tiles_match_a_plain_softmax(layout, dtype, tol):
    q, pool, bt, lens = _inputs(layout, dtype)
    got = np.asarray(mla_paged_decode(q, pool, bt, lens, rank=RANK,
                                      sm_scale=SCALE))
    assert got.shape == (bt.shape[0], H, RANK)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _reference(q, pool, bt, lens),
                               atol=tol, rtol=tol)
