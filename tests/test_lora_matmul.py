"""kernels/lora_matmul.py — the batched heterogeneous-adapter LoRA
delta kernel (ISSUE 15).

Pinned here, CPU (interpret mode runs the same kernel body the chip
compiles; the BlockSpec sweep proves Mosaic tiling legality
statically):

* Pallas masked segment-bmm == XLA gathered bmv numerically (tight
  f32 tolerance; the two routes may order the H reduction differently,
  so CROSS-route bitwise equality is not claimed — the engine uses one
  route per program shape, and the solo-vs-mixed identity rests on the
  WITHIN-route bit-independence from other slots, via exact-0.0
  masking, which IS asserted bitwise);
* a row's delta is independent of every OTHER slot's contents;
* slot 0 (the null adapter) yields an exact zero delta;
* every pick `pick_lora_blocks` returns fits the A3 VMEM estimator,
  and every enumerated (block, array) pair is Mosaic-legal;
* ranks past MAX_KERNEL_RANK / untileable dims report unsupported
  (the XLA fallback route), never an illegal pallas_call.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis.vmem import VMEM_BUDGET_BYTES, estimate_vmem_bytes
from paddle_tpu.kernels.lora_matmul import (MAX_KERNEL_RANK, _blocks,
                                            lora_blockspecs, lora_matmul,
                                            lora_matmul_supported,
                                            lora_matmul_xla,
                                            pick_lora_blocks)
from tests.test_flash_blockspec_legality import mosaic_legal


def _mats(B, H, R, N, S, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, H), jnp.float32)
    a = jnp.asarray(rng.randn(S, H, R) * 0.02, jnp.float32)
    b = jnp.asarray(rng.randn(S, R, N) * 0.02, jnp.float32)
    # slot 0 is the null adapter by contract
    a = a.at[0].set(0.0)
    b = b.at[0].set(0.0)
    ids = jnp.asarray(rng.randint(0, S, (B,)), jnp.int32)
    return x, ids, a, b


@pytest.mark.parametrize("B,H,R,N,S", [
    (8, 256, 8, 128, 4),
    (16, 512, 16, 256, 8),
    (1, 128, 64, 128, 2),
    (8, 384, 8, 128, 3),          # H tiles at 128, not a pow2
])
def test_pallas_matches_xla(B, H, R, N, S):
    x, ids, a, b = _mats(B, H, R, N, S)
    assert lora_matmul_supported(B, H, R, N)
    d_pal = np.asarray(lora_matmul(x, ids, a, b))
    d_xla = np.asarray(lora_matmul_xla(x, ids, a, b))
    assert np.allclose(d_pal, d_xla, atol=2e-6), \
        np.abs(d_pal - d_xla).max()


def test_row_delta_independent_of_other_slots():
    """The acceptance backbone: change every OTHER slot's weights and a
    row's delta must not move a single bit (masked contributions are
    exact 0.0)."""
    B, H, R, N, S = 8, 256, 8, 128, 4
    x, _, a, b = _mats(B, H, R, N, S)
    ids = jnp.full((B,), 2, jnp.int32)
    base = np.asarray(lora_matmul(x, ids, a, b))
    xla_base = np.asarray(lora_matmul_xla(x, ids, a, b))
    rng = np.random.RandomState(9)
    for s in (1, 3):
        a = a.at[s].set(jnp.asarray(rng.randn(H, R) * 5.0, jnp.float32))
        b = b.at[s].set(jnp.asarray(rng.randn(R, N) * 5.0, jnp.float32))
    again = np.asarray(lora_matmul(x, ids, a, b))
    assert (base == again).all()
    # and the XLA route agrees with ITSELF the same way (across the two
    # routes only test_pallas_matches_xla's tolerance holds: their
    # reduction orders differ)
    assert (np.asarray(lora_matmul_xla(x, ids, a, b)) == xla_base).all()


def test_null_slot_is_exact_zero():
    B, H, R, N, S = 4, 256, 8, 128, 4
    x, _, a, b = _mats(B, H, R, N, S)
    ids = jnp.zeros((B,), jnp.int32)
    assert np.abs(np.asarray(lora_matmul(x, ids, a, b))).max() == 0.0
    assert np.abs(np.asarray(lora_matmul_xla(x, ids, a, b))).max() == 0.0


def test_inside_jit_and_mixed_dtype_x():
    B, H, R, N, S = 8, 256, 8, 128, 4
    x, ids, a, b = _mats(B, H, R, N, S)
    xb = x.astype(jnp.bfloat16)
    d = jax.jit(lambda *t: lora_matmul(*t))(xb, ids, a, b)
    assert d.dtype == jnp.float32 and d.shape == (B, N)


# ------------------------------------------------------- picks / legality
@pytest.mark.parametrize("B,H,R,N", [
    (8, 4096, 8, 4096),           # llama-7B-ish decode
    (16, 4096, 64, 11008),        # MLP up at rank 64
    (64, 8192, 16, 8192),         # big batch, big model
    (8, 128, 8, 128),             # tiny test geometry
])
def test_picks_fit_estimator_and_specs_legal(B, H, R, N):
    picked = pick_lora_blocks(B, H, R, N)
    assert picked is not None
    bk, bn = picked
    assert H % bk == 0 and N % bn == 0
    ib, ob, sc = _blocks(B, bk, R, bn, jnp.float32)
    assert estimate_vmem_bytes(ib, ob, sc) <= VMEM_BUDGET_BYTES
    for block, array in lora_blockspecs(B, 8, H, R, N):
        assert mosaic_legal(block, array), (block, array)


def test_unsupported_routes_to_fallback():
    # rank past the kernel ceiling
    assert not lora_matmul_supported(8, 4096, MAX_KERNEL_RANK * 2, 4096)
    assert lora_blockspecs(8, 4, 4096, MAX_KERNEL_RANK * 2, 4096) is None
    # un-tileable N (prime, > cap, no 128-divisor)
    assert not lora_matmul_supported(8, 4096, 8, 2051 * 128 + 1)
    with pytest.raises(ValueError):
        x, ids, a, b = _mats(8, 4096, MAX_KERNEL_RANK * 2, 128, 2)
        lora_matmul(x, ids, a, b)
    # the fallback itself still computes
    x, ids, a, b = _mats(2, 64, MAX_KERNEL_RANK * 2, 96, 2)
    d = lora_matmul_xla(x, ids, a, b)
    assert d.shape == (2, 96)


def test_scaled_b_stack_formula():
    """Callers fold alpha/rank into B before the call; both routes must
    then agree with the explicit x @ A @ (B*s) reference."""
    B, H, R, N, S = 4, 256, 8, 128, 3
    x, ids, a, b = _mats(B, H, R, N, S)
    scaling = jnp.asarray([0.0, 2.0, 0.5], jnp.float32)
    b_scaled = b * scaling[:, None, None]
    ref = np.stack([
        np.asarray(x[i] @ a[int(ids[i])] @ b_scaled[int(ids[i])])
        for i in range(B)])
    got = np.asarray(lora_matmul(x, ids, a, b_scaled))
    assert np.allclose(got, ref, atol=1e-5)
