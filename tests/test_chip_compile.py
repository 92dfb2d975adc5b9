"""The main path's Pallas kernels compiled for a DESCRIBED TPU v5e, at the
widths chip_smoke.py runs (Llama-2-7B train, Llama-3-8B serve).

Interpret mode on the CPU hides what Mosaic refuses (unaligned slices,
VMEM budgets, i64 under the package's x64 mode, kernels GSPMD would have
to partition). The TPU compiler is installed here and compiles for a
topology that is described, not attached — so these cases guard every
later PR at no chip time. Nothing runs: a passing compile is not a chip
run.

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture that skips when it
cannot be — never at import, never in conftest.py, never autouse; every
compile happens in the test's own process (the worker that loaded
libtpu keeps its lock); all cases live in this ONE file so one xdist
worker owns them; JAX's persistent cache is off around them (an entry
compiled for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from paddle_tpu.kernels import flash_attention as fa

MARKER = "tpu_custom_call"
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_mesh(topo):
    """mesh(dp, mp) over the four described chips, on the hybrid axes."""
    from paddle_tpu.distributed.fleet.topology import build_mesh

    def mesh(dp, mp):
        return build_mesh(dp=dp, mp=mp, devices=list(topo.devices))
    return mesh


@pytest.fixture()
def for_chip():
    """Steer the kernels to the Mosaic lowering (off-TPU they default to
    interpret mode, cached for the process) and keep the persistent
    compilation cache out of it; both restored after the test."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev_interpret = fa._INTERPRET_CACHE[0]
    prev_cache = jax.config.jax_enable_compilation_cache
    fa._INTERPRET_CACHE[0] = False
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        fa._INTERPRET_CACHE[0] = prev_interpret
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# a `tpu_custom_call`'s configuration in a program's lowered text
KERNEL_CONFIG = r'backend_config = "((?:[^"\\]|\\.)*)"'


def _kernel_bodies(lowered_text):
    """The Mosaic module of every `tpu_custom_call` in a program's LOWERED
    text, as MLIR text without debug locations. The lowered text carries
    each kernel as serialized bytecode whose locations are the kernel
    file's line numbers: decoded and printed without them, an edit
    elsewhere in the file leaves the text as it was."""
    import base64
    import json
    import re
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir
    bodies = []
    for found in re.finditer(KERNEL_CONFIG, lowered_text):
        config = json.loads(found.group(1).replace("\\22", '"')
                            .replace("\\5C", "\\"))
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True   # `stable_mosaic`'s ops
        with ctx:
            module = ir.Module.parse(base64.b64decode(
                config["custom_call_config"]["body"]))
            bodies.append(module.operation.get_asm(enable_debug_info=False))
    return bodies


def _matmul_operands(body):
    """[(lhs element type, rhs element type)] of a kernel's products."""
    import re
    return re.findall(
        r'tpu\.matmul"\(.*?: \(vector<[0-9x]+x(\w+)>, vector<[0-9x]+x(\w+)>',
        body)


# Train phase: batch 2 x seq 2048, 32 heads x 128 (LlamaConfig() widths).
QKV = (2, 2048, 32, 128)


def test_flash_forward(for_chip, one_chip):
    q = _sds(QKV, BF16, one_chip)
    text = _compiled_text(
        lambda q, k, v: fa.flash_attention_bshd(q, k, v, causal=True),
        q, q, q)
    assert MARKER in text


def test_flash_backward(for_chip, one_chip):
    q = _sds(QKV, BF16, one_chip)

    def loss(q, k, v):
        out = fa.flash_attention_bshd(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert text.count(MARKER) >= 2          # forward + backward kernels


TRAIN_FORM_TEXTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "data", "flash_train_form_texts.json")


def _train_form_digests(one_chip):
    """SHA-256 of the text lowered for the described chip of
    `flash_attention_bshd` at the train cell's shape, forward and
    forward + backward, each kernel's module in `_kernel_bodies`' form
    (the serialized form carries the kernel file's line numbers)."""
    import hashlib
    import re
    q = _sds(QKV, BF16, one_chip)

    def loss(q, k, v):
        out = fa.flash_attention_bshd(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    digests = {}
    for name, fn in (
            ("forward", lambda q, k, v: fa.flash_attention_bshd(
                q, k, v, causal=True)),
            ("forward_backward", jax.grad(loss, argnums=(0, 1, 2)))):
        text = jax.jit(fn).lower(q, q, q).as_text()
        bodies = _kernel_bodies(text)
        assert len(bodies) == {"forward": 1, "forward_backward": 3}[name]
        outer = re.sub(KERNEL_CONFIG, "backend_config = <kernel>", text)
        digests[name] = hashlib.sha256(
            "\n".join([outer] + bodies).encode()).hexdigest()
    return digests


def test_the_train_forms_lower_to_the_text_they_had(for_chip, one_chip):
    """The plain-causal forward and the two backward kernels, which the
    train step runs, are the kernels of the commit the data file names
    (PR 37 changed the forward where positions are data, and only there:
    PR 36 was refused for what a change to all forms cost the train cell's
    set-up). Written by `python tests/test_chip_compile.py` from a
    `git archive` of that commit; skips under another jax."""
    import json
    with open(TRAIN_FORM_TEXTS) as f:
        kept = json.load(f)
    if kept["jax"] != jax.__version__:
        pytest.skip(f"the texts were lowered under jax {kept['jax']}")
    assert _train_form_digests(one_chip) == kept["sha256"], (
        f"a train form no longer lowers to the text of {kept['commit']}")


@pytest.mark.parametrize("case", ["d128", "d128-positions", "kimi-d192",
                                  "table-full"])
def test_flash_varlen(for_chip, one_chip, case):
    """`d128`: positions from the segment ids, `nn.functional`'s default.
    `kimi-d192`: 2,048 queries over 5,120 keys with explicit positions,
    `serve-reasoning-decode`'s chunk (tiles of 512). `table-full`: two
    packed rows of 131,072 tokens, the most tiles the block table takes in
    scalar memory (`_TABLE_TILES`)."""
    B, Sq, Sk, H, D = {"d128": (1, 4096, 4096, 32, 128),
                       "d128-positions": (1, 4096, 4096, 32, 128),
                       "kimi-d192": (1, 2048, 5120, 64, 192),
                       "table-full": (2, 131072, 131072, 1, 192)}[case]
    assert B * (Sq // fa._pick_block_q(Sq, D)) * (
        Sk // fa._pick_block_k(Sk, D)) <= fa._TABLE_TILES
    q, k = (_sds((B, n, H, D), BF16, one_chip) for n in (Sq, Sk))
    segq, segk = (_sds((B, n), jnp.int32, one_chip) for n in (Sq, Sk))
    positions = (segq, segk) if case in ("d128-positions", "kimi-d192") \
        else (None, None)
    lowered = jax.jit(
        lambda q, k, v, sq, sk, pq, pk: fa.flash_attention_varlen_bshd(
            q, k, v, sq, sk, causal=True, q_positions=pq, kv_positions=pk)
    ).lower(q, k, k, segq, segk, *positions)
    assert MARKER in lowered.compile().as_text()
    body, = _kernel_bodies(lowered.as_text())
    # both products take their operands as stored
    assert _matmul_operands(body) == [("bf16", "bf16")] * 2


def test_flashmask(for_chip, one_chip):
    q = _sds(QKV, BF16, one_chip)
    idx = _sds((2, 1, 2048, 1), jnp.int32, one_chip)
    text = _compiled_text(
        lambda q, k, v, i: fa.flashmask_attention_bshd(q, k, v, i,
                                                       causal=True),
        q, q, q, idx)
    assert MARKER in text


def test_flash_per_shard_under_hybrid_mesh(for_chip, chip_mesh):
    """The sdpa functional under a live dp2 x mp2 mesh: GSPMD cannot
    partition a Mosaic kernel, so the call must arrive wrapped in a
    shard_map (batch over 'data', heads over 'model')."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.mpu import mesh_scope
    from paddle_tpu.nn.functional.flash_attention import (
        pallas_refusals, scaled_dot_product_attention)
    mesh = chip_mesh(2, 2)
    q = _sds(QKV, BF16, NamedSharding(mesh, P("data", None, "model", None)))

    def attend(q, k, v):
        with paddle.no_grad():
            return scaled_dot_product_attention(
                paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
                is_causal=True)._data

    before = sum(pallas_refusals().values())
    with mesh_scope(mesh):
        text = _compiled_text(attend, q, q, q)
    assert MARKER in text
    assert sum(pallas_refusals().values()) == before    # kernel was taken


# Serve phase: llama_3_8b() widths, batch bucket 8, the 1,024-page pool.
@pytest.mark.parametrize("page,kv_dtype", [(16, None), (128, "int8")])
def test_paged_attention_decode(for_chip, one_chip, page, kv_dtype):
    from paddle_tpu.kernels.paged_attention import paged_attention_decode
    B, H, KVH, D, pages = 8, 32, 8, 128, 1024
    q = _sds((B, H, D), BF16, one_chip)
    cache = _sds((pages, KVH, page, D),
                 jnp.int8 if kv_dtype else BF16, one_chip)
    bt = _sds((B, 2048 // page), jnp.int32, one_chip)
    sl = _sds((B,), jnp.int32, one_chip)
    if kv_dtype:
        scale = _sds((pages, KVH, page), jnp.float32, one_chip)
        text = _compiled_text(
            lambda q, k, v, bt, sl, ks, vs: paged_attention_decode(
                q, k, v, bt, sl, k_scale=ks, v_scale=vs),
            q, cache, cache, bt, sl, scale, scale)
    else:
        text = _compiled_text(paged_attention_decode, q, cache, cache, bt, sl)
    assert MARKER in text


def test_paged_attention_decode_tp4(for_chip, chip_mesh):
    """The TP lowering: a fully manual shard_map over the four chips
    (Mosaic refuses the kernel under a partial-manual map)."""
    from paddle_tpu.kernels.paged_attention import paged_attention_decode_tp
    mesh = chip_mesh(1, 4)

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    B, H, KVH, D, pages, page = 8, 32, 8, 128, 1024, 16
    q = _sds((B, H, D), BF16, ns(None, "model", None))
    cache = _sds((pages, KVH, page, D), BF16, ns(None, "model", None, None))
    bt = _sds((B, 128), jnp.int32, ns())
    sl = _sds((B,), jnp.int32, ns())
    text = _compiled_text(
        lambda q, k, v, bt, sl: paged_attention_decode_tp(q, k, v, bt, sl,
                                                          mesh),
        q, cache, cache, bt, sl)
    assert MARKER in text


# The serving cells' own calls (benchmarks/: `serve-offline-decode`'s B 64
# x a 64-page table at page 16, and the engine sends q as f32; the same on
# the KVH 8/4 = 2 heads of a TP-4 shard; `serve-chat-steady`'s B 32 x 160).
@pytest.mark.parametrize("B,H,KVH,table", [(64, 32, 8, 64), (64, 8, 2, 64),
                                           (32, 32, 8, 160)],
                         ids=["cell", "kvh2-shard", "chat-steady"])
def test_paged_attention_decode_serving_cell(for_chip, one_chip, B, H, KVH,
                                             table):
    from paddle_tpu.kernels.paged_attention import paged_attention_decode
    D, pages, page = 128, 4096, 16
    cache = _sds((pages, KVH, page, D), BF16, one_chip)
    text = _compiled_text(
        paged_attention_decode, _sds((B, H, D), jnp.float32, one_chip),
        cache, cache, _sds((B, table), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip))
    assert MARKER in text


# `serve-mixed-context`'s calls (PR 35): B 48 rows of 16 query heads a KV
# head over an 816-page table (39,168 table words beside the four step
# arrays in scalar-prefetch memory), a full layer's and a window layer's;
# and a 2,048-token chunk's attention through the flash kernel with the
# group's heads laid along the query axis, over the window's 392 pages
# and over the whole table.
@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_paged_attention_decode_mixed_context_cell(for_chip, one_chip,
                                                   window):
    from paddle_tpu.kernels.paged_attention import paged_attention_decode
    B, H, KVH, D, pages, page, table = 48, 128, 8, 128, 12513, 16, 816
    cache = _sds((pages, KVH, page, D), BF16, one_chip)
    text = _compiled_text(
        lambda q, k, v, bt, sl: paged_attention_decode(
            q, k, v, bt, sl, window=window),
        _sds((B, H, D), BF16, one_chip), cache, cache,
        _sds((B, table), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip))
    assert "paged_attention_decode" in text and MARKER in text


# The paged decode kernel at the engine's small buckets (B 1, 2, 8 x tables
# of 1, 4, 8, 16 pages), where its BlockSpec form first halted a chip by
# reading past its scalar-prefetch arrays: a tile here is the whole table,
# and the copies a step starts for the next one read the step arrays one
# entry on.
@pytest.mark.parametrize("table", [1, 4, 8, 16])
@pytest.mark.parametrize("B", [1, 2, 8])
def test_paged_decode_small_buckets(for_chip, one_chip, B, table):
    from paddle_tpu.kernels.paged_attention import paged_attention_decode
    H, KVH, D, pages, page = 32, 8, 128, 1024, 16
    cache = _sds((pages, KVH, page, D), BF16, one_chip)
    text = _compiled_text(
        paged_attention_decode, _sds((B, H, D), jnp.float32, one_chip),
        cache, cache, _sds((B, table), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip))
    assert "paged_attention_decode" in text and MARKER in text


# Each way `_gather_plan` gathers a kind of page, where Mosaic takes a
# copy of one page and where it does not: pages laid whole (bf16 page 8),
# value pages fetched by the pipeline (head dim 64), int8 value pages
# copied beside scale pages the pipeline fetches (page 16, 32).
@pytest.mark.parametrize("page,D,kv_dtype", [(8, 128, None), (16, 64, None),
                                             (16, 128, "int8"),
                                             (32, 128, "int8")],
                         ids=["whole-pages", "pipelined-d64", "int8-p16",
                              "int8-p32"])
def test_paged_decode_gather_forms(for_chip, one_chip, page, D, kv_dtype):
    from paddle_tpu.kernels.paged_attention import paged_attention_decode
    B, H, KVH, pages = 8, 32, 8, 1024
    cache = _sds((pages, KVH, page, D), jnp.int8 if kv_dtype else BF16,
                 one_chip)
    scale = _sds((pages, KVH, page), jnp.float32, one_chip)
    text = _compiled_text(
        lambda q, k, v, bt, sl, ks, vs: paged_attention_decode(
            q, k, v, bt, sl, k_scale=ks if kv_dtype else None,
            v_scale=vs if kv_dtype else None),
        _sds((B, H, D), BF16, one_chip), cache, cache,
        _sds((B, 2048 // page), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip), scale, scale)
    assert "paged_attention_decode" in text and MARKER in text


# The latent decode kernel at the engine's small buckets (B 1-8, tables of
# 1-16 pages), where the paged kernel's BlockSpec form first halted a chip by
# reading past its scalar-prefetch arrays: a tile here is the whole table, and the copies a
# step starts for the next one read the step arrays one entry on.
@pytest.mark.parametrize("B,table", [(1, 1), (2, 4), (2, 8), (8, 16)],
                         ids=["b1-p1", "b2-p4", "b2-p8", "b8-p16"])
def test_mla_decode_small_buckets(for_chip, one_chip, B, table):
    from paddle_tpu.kernels.mla_attention import mla_paged_decode
    H, W, pages, page = 64, 640, 1024, 16
    text = _compiled_text(
        lambda q, c, bt, sl: mla_paged_decode(q, c, bt, sl, rank=512,
                                              sm_scale=0.1),
        _sds((B, H, W), BF16, one_chip),
        _sds((pages, page, W), BF16, one_chip),
        _sds((B, table), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip))
    assert "mla_paged_decode" in text and MARKER in text


@pytest.mark.parametrize(
    "S,keys,window", [(2048, 392 * 16, 4096), (2048, 816 * 16, None),
                      (1024, 328 * 16, 4096), (512, 296 * 16, 4096)],
    ids=["window", "full", "window-s1024", "window-s512"])
def test_flash_chunk_gqa_mixed_context_cell(for_chip, one_chip, S, keys,
                                            window):
    """The cell's chunk buckets over what `Cohere2MoeAttention._chunk_keys`
    gathers for them in a window layer (the two smaller ones' counts are
    padded by the kernel), and the table in the full one."""
    H, KVH, D = 128, 8, 128
    assert fa.chunk_gqa_unsupported_reason(S, keys, H, KVH, D, BF16) is None
    kv = _sds((keys, KVH, D), BF16, one_chip)
    lowered = jax.jit(
        lambda q, k, v, qp, kp: fa.flash_attention_chunk_gqa(
            q, k, v, qp, kp, window=window)).lower(
        _sds((S, H, D), BF16, one_chip), kv, kv,
        _sds((S,), jnp.int32, one_chip), _sds((keys,), jnp.int32, one_chip))
    text = lowered.compile().as_text()
    assert "flash_attention_fwd" in text and MARKER in text
    body, = _kernel_bodies(lowered.as_text())
    # both products take their operands as stored
    assert _matmul_operands(body) == [("bf16", "bf16")] * 2


def test_quant_matmul(for_chip, one_chip):
    from paddle_tpu.kernels.quant_matmul import quant_matmul
    M, K, N = 32, 4096, 14336
    text = _compiled_text(
        quant_matmul, _sds((M, K), BF16, one_chip),
        _sds((K, N), jnp.int8, one_chip), _sds((N,), BF16, one_chip))
    assert MARKER in text


def test_lora_matmul(for_chip, one_chip):
    from paddle_tpu.kernels.lora_matmul import lora_matmul
    B, H, R, N, S = 8, 4096, 16, 4096, 4
    text = _compiled_text(
        lora_matmul, _sds((B, H), BF16, one_chip),
        _sds((B,), jnp.int32, one_chip),
        _sds((S, H, R), jnp.float32, one_chip),
        _sds((S, R, N), jnp.float32, one_chip))
    assert MARKER in text


@pytest.mark.parametrize("moment_dtype", [jnp.float32, BF16])
def test_fused_adamw_bucket(for_chip, one_chip, moment_dtype):
    from paddle_tpu.kernels.fused_optimizer import (LANES, adamw_scalars,
                                                    fused_adamw_bucket)
    rows = 1 << 20
    scalars = adamw_scalars(1e-3, 0.9, 0.999, 1e-8, 0.01, 1)
    bucket = _sds((rows, LANES), jnp.float32, one_chip)
    grads = _sds((rows, LANES), BF16, one_chip)
    moment = _sds((rows, LANES), moment_dtype, one_chip)
    text = _compiled_text(
        lambda g, w, m, v, s: fused_adamw_bucket(g, w, m, v, s,
                                                 param_dtype=BF16),
        grads, bucket, moment, moment,
        _sds(np.shape(scalars), jnp.float32, one_chip))
    assert MARKER in text


def test_rms_norm_rows(for_chip, one_chip):
    from paddle_tpu.kernels.fused_norm import rms_norm_rows
    text = _compiled_text(rms_norm_rows, _sds((2048, 4096), BF16, one_chip),
                          _sds((4096,), BF16, one_chip))
    assert MARKER in text


# The names the benchmark's kernel metrics match (PERF.md lists them once).
# Each case compiles inside a function called `program`, as the engine's
# decode programs are: unnamed, the custom call would be `program.N`.
def _flash_args(one_chip):
    return (_sds(QKV, BF16, one_chip),) * 3


def _flash_fwd(one_chip):
    return (lambda q, k, v: fa.flash_attention_bshd(q, k, v, causal=True),
            _flash_args(one_chip))


def _flash_bwd(one_chip):
    def loss(q, k, v):
        out = fa.flash_attention_bshd(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2)), _flash_args(one_chip)


def _paged16(one_chip):
    from paddle_tpu.kernels.paged_attention import paged_attention_decode
    B, H, KVH, D, pages, page = 8, 32, 8, 128, 1024, 16
    cache = _sds((pages, KVH, page, D), BF16, one_chip)
    return paged_attention_decode, (
        _sds((B, H, D), BF16, one_chip), cache, cache,
        _sds((B, 2048 // page), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip))


def _rms_norm(one_chip):
    from paddle_tpu.kernels.fused_norm import rms_norm_rows
    return rms_norm_rows, (_sds((2048, 4096), BF16, one_chip),
                           _sds((4096,), BF16, one_chip))


def _fused_adamw(one_chip):
    from paddle_tpu.kernels.fused_optimizer import (LANES, adamw_scalars,
                                                    fused_adamw_bucket)
    rows = 1 << 16
    scalars = adamw_scalars(1e-3, 0.9, 0.999, 1e-8, 0.01, 1)
    f32 = _sds((rows, LANES), jnp.float32, one_chip)
    return (lambda g, w, m, v, s: fused_adamw_bucket(g, w, m, v, s,
                                                     param_dtype=BF16),
            (_sds((rows, LANES), BF16, one_chip), f32, f32, f32,
             _sds(np.shape(scalars), jnp.float32, one_chip)))


def _lora(one_chip):
    from paddle_tpu.kernels.lora_matmul import lora_matmul
    B, H, R, N, S = 8, 4096, 16, 4096, 4
    return lora_matmul, (_sds((B, H), BF16, one_chip),
                         _sds((B,), jnp.int32, one_chip),
                         _sds((S, H, R), jnp.float32, one_chip),
                         _sds((S, R, N), jnp.float32, one_chip))


def _quant(one_chip):
    from paddle_tpu.kernels.quant_matmul import quant_matmul
    M, K, N = 32, 4096, 14336
    return quant_matmul, (_sds((M, K), BF16, one_chip),
                          _sds((K, N), jnp.int8, one_chip),
                          _sds((N,), BF16, one_chip))


def _mla_decode(one_chip):
    """The latent decode kernel at the serving cell's call: B 64, 64 heads
    over one 640-lane entry (512 + 64 in whole lane tiles), a 320-page
    table at page 16."""
    from paddle_tpu.kernels.mla_attention import mla_paged_decode
    B, H, W, pages, page, table = 64, 64, 640, 16384, 16, 320
    return (lambda q, c, bt, sl: mla_paged_decode(q, c, bt, sl, rank=512,
                                                  sm_scale=0.1),
            (_sds((B, H, W), BF16, one_chip),
             _sds((pages, page, W), BF16, one_chip),
             _sds((B, table), jnp.int32, one_chip),
             _sds((B,), jnp.int32, one_chip)))


def _held_experts(tokens):
    """12 held experts at the published widths over `tokens` x 8 pairs: a
    decode step's 64 tokens (16-row tiles) and a chunk's 2,048 (128)."""
    def case(one_chip):
        from paddle_tpu.models.kimi_k2 import held_experts
        H, I, E, k = 7168, 2048, 12, 8
        return (lambda x, live, idx, w, eg, eu, ed: held_experts(
            x, live, idx, w, eg, eu, ed, offset=0),
            (_sds((tokens, H), BF16, one_chip),
             _sds((tokens,), jnp.bool_, one_chip),
             _sds((tokens, k), jnp.int32, one_chip),
             _sds((tokens, k), jnp.float32, one_chip),
             _sds((E, H, I), BF16, one_chip), _sds((E, H, I), BF16, one_chip),
             _sds((E, I, H), BF16, one_chip)))
    return case


KERNEL_NAMES = [("mla_paged_decode", _mla_decode),
                ("moe_grouped_matmul_gate_up", _held_experts(64)),
                ("moe_grouped_matmul_down", _held_experts(64)),
                ("moe_grouped_matmul_gate_up", _held_experts(2048)),
                ("flash_attention_fwd", _flash_fwd),
                ("flash_attention_bwd_dq", _flash_bwd),
                ("flash_attention_bwd_dkv", _flash_bwd),
                ("paged_attention_decode", _paged16),
                ("rms_norm", _rms_norm), ("fused_adamw", _fused_adamw),
                ("lora_matmul", _lora), ("quant_matmul", _quant)]


def _custom_call_names(text):
    import re
    return re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*"
                      r'custom_call_target="tpu_custom_call"', text, re.M)


@pytest.mark.parametrize("name,case", KERNEL_NAMES,
                         ids=[f"{i}-{n}" for i, (n, _) in
                              enumerate(KERNEL_NAMES)])
def test_kernel_name_in_compiled_text(for_chip, one_chip, name, case):
    """The `name=` of each `pallas_call` is the compiled instruction's
    name (through jit, jvp and transpose as a substring): what a device
    trace, and with it the benchmark's kernel metrics, can match."""
    fn, args = case(one_chip)

    def program(*a):
        return fn(*a)

    calls = _custom_call_names(_compiled_text(program, *args))
    assert any(name in c for c in calls), calls
    assert not any(c.startswith("program") for c in calls), calls


# The engine's paged programs at Mistral-7B-v0.3's widths (the serving
# cells' configuration, ONE layer): no projection weight is re-laid-out
# inside a launch (PR 32). At the parent each q_proj and k_proj went
# through a transposing copy, a physical reshape to RoPE's pair view and
# a third copy before the dot read it: 12 weight-sized instructions a
# program at two layers, 4.0 GB of traffic a launch at sixteen.
WEIGHT_ELEMENTS = 4096 * 1024            # k_proj, the smallest projection
RELAYOUTS = ("copy", "reshape", "transpose")
# kinds that hand an array on as it is stored: views, and the compiler's
# asynchronous prefetches into fast memory
PASS_ON = ("bitcast", "get-tuple-element", "copy-start", "copy-done",
           "slice-start", "slice-done")


@pytest.fixture(scope="module")
def mistral_engine():
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype("bfloat16")
    try:
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=32768, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=1, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=32768,
            rms_norm_eps=1e-5, rope_theta=1e6, dtype="bfloat16"))
    finally:
        paddle.set_default_dtype(prev)
    eng = ServingEngine(model, num_pages=256, page_size=16,
                        max_batch_size=64, temperature=0.0)
    eng._donate = (1, 2, 3, 4)           # as on the chip: caches donated
    yield eng
    eng.shutdown()


def _hlo_computations(text):
    """{computation: [(name, elements, kind, rest of the line)]} of a
    compiled module's text; `elements` is None for a tuple result."""
    import re
    head = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
    instr = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.+?) ([a-z][\w\-]*)\((.*)$")
    comps, cur = {}, None
    for line in text.splitlines():
        m = head.match(line)
        if m:
            cur = comps.setdefault("ENTRY" if line.startswith("ENTRY")
                                   else m.group(1), [])
            continue
        m = instr.match(line) if cur is not None else None
        if m:
            name, typ, kind, rest = m.groups()
            dims = re.match(r"\w+\[([\d,]*)\]", typ)
            n = None if dims is None else int(np.prod(
                [int(d) for d in dims.group(1).split(",") if d] or [1]))
            cur.append((name, n, kind, rest))
    return comps


def _weight_relayouts(text):
    return [f"{kind} {name}" for body in _hlo_computations(text).values()
            for name, n, kind, _ in body
            if kind in RELAYOUTS and n is not None and n >= WEIGHT_ELEMENTS]


def _stray_weight_readers(text):
    """Readers of a `*_proj_weight` parameter, followed through PASS_ON,
    that are not a fusion holding the matmul."""
    import re
    comps = _hlo_computations(text)
    entry = comps["ENTRY"]
    reads = lambda rest, name: re.search(
        r"[(, ]%" + re.escape(name) + r"[,)]", "(" + rest) is not None
    stray, seen = [], 0
    todo = [name for name, _, kind, _ in entry
            if kind == "parameter" and "_proj_weight" in name]
    assert todo, "no projection weight among the program's parameters"
    while todo:
        src = todo.pop()
        for name, _, kind, rest in entry:
            if kind == "parameter" or not reads(rest, src):
                continue
            seen += 1
            if kind in PASS_ON:
                todo.append(name)
                continue
            called = re.search(r"calls=%([\w.\-]+)", rest)
            if kind != "fusion" or not any(
                    k in ("convolution", "dot")
                    for _, _, k, _ in comps[called.group(1)]):
                stray.append(f"{kind} {name} <- {src}")
    assert seen, "no reader of a projection weight was found"
    return stray


def _paged_program(eng, kind):
    i32 = jnp.int32
    if kind == "decode":                 # serve-offline-decode's bucket
        B, P = 64, 64
        return eng._build_decode(B, P), [((B, 1), i32), ((B, P), i32),
                                         ((B,), i32)]
    if kind == "chunk":
        S, P = 512, 64
        return eng._build_chunk(S, P), [((1, S), i32), ((), i32), ((), i32),
                                        ((P,), i32)]
    B, K, P = 1, 1, 16                   # verify at its smallest bucket
    return eng._build_verify(B, K, P), [((B, K + 1), i32), ((B, P), i32),
                                        ((B,), i32), ((B,), i32)]


@pytest.mark.parametrize("kind", ["decode", "chunk", "verify"])
def test_paged_programs_leave_the_projection_weights_where_they_are(
        for_chip, one_chip, mistral_engine, kind):
    import paddle_tpu as paddle
    eng = mistral_engine
    placed = lambda tree: jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), tree)
    program, shapes = _paged_program(eng, kind)
    with paddle.no_grad():               # as the engine launches it
        text = program.lower(
            placed(eng._state), *placed(tuple(eng._cache_lists())),
            *[_sds(s, dt, one_chip) for s, dt in shapes],
            placed(eng._null_key)).compile().as_text()
    if kind == "decode":
        assert "paged_attention_decode" in text
    assert _weight_relayouts(text) == []
    assert _stray_weight_readers(text) == []


def test_the_ids_of_a_launch_ahead_are_built_from_rows_alone(for_chip,
                                                             one_chip):
    """ISSUE 34: the decode launch enqueued before the last one is fetched
    takes its input ids from that launch's tokens on the device, in a
    program of its own BESIDE the decode program (which the case above
    holds as it was: the same builder, the same inputs). That program
    sees one value a row (the gather pads its indices to one tile of
    1,024): no weight, no pool, nothing 64 bits wide (an index computed
    with `jnp` under the package's x64 mode is emulated on the device)."""
    from paddle_tpu.serving.engine import _ids_after
    B = 64                               # serve-offline-decode's bucket
    row = _sds((B,), jnp.int32, one_chip)
    compiled = _ids_after.lower(row, row, row).compile()
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert (out.shape, out.dtype) == ((B, 1), jnp.int32)
    text = compiled.as_text()
    assert "s64[" not in text and "u64[" not in text
    sizes = [n for body in _hlo_computations(text).values()
             for _, n, _, _ in body if n is not None]
    assert sizes and max(sizes) <= 1024


if __name__ == "__main__":
    # writes tests/data/flash_train_form_texts.json from the checkout on
    # PYTHONPATH (a `git archive` of the commit the train forms are held to):
    #   JAX_PLATFORMS=cpu PYTHONPATH=<that checkout> TEXTS_OF=<commit> \
    #       python tests/test_chip_compile.py > tests/data/flash_train_form_texts.json
    import json
    import sys
    from jax.experimental import topologies
    fa._INTERPRET_CACHE[0] = False
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    print("kernels of", fa.__file__, file=sys.stderr)
    print(json.dumps({"commit": os.environ["TEXTS_OF"],
                      "jax": jax.__version__,
                      "sha256": _train_form_digests(chip)}, indent=1))
