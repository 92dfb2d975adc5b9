"""On-chip NUMERIC parity for the Pallas pack (interpret=False).

Execution alone proves Mosaic compiles the
kernels; this asserts the numbers match an XLA reference computed on
the same chip, closing the interpret-mode-only validation gap
(ADVICE r3 medium finding).
"""
import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import (
    flash_attention_bshd, flash_attention_varlen_bshd,
    flashmask_attention_bshd)
from paddle_tpu.kernels.paged_attention import paged_attention_decode
print("devices:", jax.devices())


def sdpa_ref(q, k, v, mask=None, causal=True):
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (1.0 / np.sqrt(q.shape[-1]))
    S, Sk = q.shape[1], k.shape[1]
    if causal:
        cm = jnp.tril(jnp.ones((S, Sk), bool))
        s = jnp.where(cm[None, None], s, -1e30)
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def relerr(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-9))


# ---- flash fwd + bwd vs SDPA, S=2048 --------------------------------
B, S, H, D = 2, 2048, 4, 128
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
k = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
v = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
out = flash_attention_bshd(q, k, v, causal=True)
ref = sdpa_ref(q, k, v)
e = relerr(out, ref)
assert e < 3e-2, f"flash fwd parity {e}"
print(f"PARITY flash fwd rel_err={e:.4f} OK")

dq, dk, dv = jax.grad(
    lambda q, k, v: flash_attention_bshd(q, k, v, causal=True)
    .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
rq, rk, rv = jax.grad(
    lambda q, k, v: sdpa_ref(q, k, v).sum(), argnums=(0, 1, 2))(q, k, v)
for name, a, b in [("dq", dq, rq), ("dk", dk, rk), ("dv", dv, rv)]:
    e = relerr(a, b)
    assert e < 5e-2, f"flash bwd {name} parity {e}"
    print(f"PARITY flash bwd {name} rel_err={e:.4f} OK")

# ---- varlen (two packed sequences) vs block-diagonal SDPA -----------
seg = jnp.concatenate([jnp.zeros((B, S // 2), jnp.int32),
                       jnp.ones((B, S // 2), jnp.int32)], axis=1)
out = flash_attention_varlen_bshd(q, k, v, seg, seg, causal=True)
mask = (seg[:, None, :, None] == seg[:, None, None, :])
ref = sdpa_ref(q, k, v, mask=mask)
e = relerr(out, ref)
assert e < 3e-2, f"varlen parity {e}"
print(f"PARITY varlen rel_err={e:.4f} OK")

# ---- flashmask (causal C=1: rows >= start[k] masked) vs dense mask --
start = jnp.asarray(
    rng.randint(1, S + 1, (B, 1, S, 1)).astype(np.int32)
    .clip(min=np.arange(S).reshape(1, 1, S, 1) + 1))
out = flashmask_attention_bshd(q, k, v, start, causal=True)
rows = jnp.arange(S)[:, None]
keep = rows < start[:, 0, :, 0][:, None, :]      # (B, Sq, Sk)
ref = sdpa_ref(q, k, v, mask=keep[:, None], causal=True)
e = relerr(out, ref)
assert e < 3e-2, f"flashmask parity {e}"
print(f"PARITY flashmask rel_err={e:.4f} OK")

# ---- paged decode vs gathered dense attention -----------------------
B2, H2, KVH, D2, page, pps = 4, 8, 8, 128, 16, 8
num_pages = B2 * pps
q1 = jnp.asarray(rng.randn(B2, H2, D2), jnp.bfloat16)
kc = jnp.asarray(rng.randn(num_pages, KVH, page, D2), jnp.bfloat16)
vc = jnp.asarray(rng.randn(num_pages, KVH, page, D2), jnp.bfloat16)
tables = jnp.arange(num_pages, dtype=jnp.int32).reshape(B2, pps)
lens = jnp.full((B2,), page * pps, jnp.int32)
out = paged_attention_decode(q1, kc, vc, tables, lens)
# dense ref: gather pages -> (B, S, KVH, D), single-query attention
kd = kc[tables].transpose(0, 2, 1, 3, 4).reshape(B2, KVH, pps * page, D2)
vd = vc[tables].transpose(0, 2, 1, 3, 4).reshape(B2, KVH, pps * page, D2)
g = H2 // KVH
qf = q1.astype(jnp.float32).reshape(B2, KVH, g, D2)
sc = jnp.einsum("bkgd,bkSd->bkgS", qf, kd.astype(jnp.float32))
sc = sc * (1.0 / np.sqrt(D2))
p = jax.nn.softmax(sc, axis=-1)
ref = jnp.einsum("bkgS,bkSd->bkgd", p, vd.astype(jnp.float32)).reshape(
    B2, H2, D2)
e = relerr(out, ref)
assert e < 3e-2, f"paged parity {e}"
print(f"PARITY paged decode rel_err={e:.4f} OK")

# ---- int8-KV paged decode vs the SAME dense reference (ISSUE 6) -----
# quantize the bf16 cache per (slot, head), run the quantized kernel
# (int8 value pages + fp32 scale pages, dequantize-in-kernel), and
# hold it to the int8 rel-err budget vs the full-precision reference —
# wired without a chip and not yet run on one; the CPU interpret
# run of the same code path is pinned by tests/test_serving_quant_kv.
from paddle_tpu.kernels.paged_attention import quantize_kv
kq, ks = quantize_kv(kc)
vq, vs = quantize_kv(vc)
out_q = paged_attention_decode(q1, kq, vq, tables, lens,
                               k_scale=ks, v_scale=vs)
e = relerr(out_q, ref)
assert e < 3e-2, f"int8-KV paged parity {e}"
print(f"PARITY paged decode int8-KV rel_err={e:.4f} OK")

# ---- fused int8 dequant-matmul vs its XLA composition ----------------
# same numerics by construction (fp32 accumulate, per-out-channel
# scale at the flush) — on chip this catches Mosaic lowering bugs the
# interpret-mode CPU tests cannot see; also budgeted against the
# full-precision matmul it approximates (chip_serving measured 0.0065
# for the old route; the fused kernel must hold the same 2e-2 budget).
from paddle_tpu.kernels.quant_matmul import (dequant_matmul_xla,
                                             quant_matmul)
M, K, N = 64, 1024, 1024
w = (rng.randn(K, N) * 0.02).astype(np.float32)
absmax = np.maximum(np.abs(w).max(0), 1e-10)
scale = jnp.asarray((absmax / 127.0).astype(np.float32))
qw = jnp.asarray(np.clip(np.round(w / (absmax / 127.0)[None, :]),
                         -127, 127).astype(np.int8))
x = jnp.asarray(rng.randn(M, K).astype(np.float32))
out_pl = quant_matmul(x, qw, scale)
out_xla = dequant_matmul_xla(x, qw, scale)
e = relerr(out_pl, out_xla)
assert e < 1e-4, f"quant_matmul vs XLA composition {e}"
e_full = relerr(out_pl, np.asarray(x) @ w)
assert e_full < 2e-2, f"quant_matmul vs full precision {e_full}"
print(f"PARITY quant_matmul xla={e:.6f} full={e_full:.4f} OK")

# ---- fused AdamW bucket kernel vs the jnp reference update (ISSUE 9) -
# the flagship recipe: bf16 grads/params, fp32 master, bf16 moments.
# Two checks on chip: (a) the Pallas kernel vs the identical XLA
# composition (same _adamw_math expression — catches Mosaic lowering
# bugs, moments must match bitwise, master within fp32 fusion noise),
# (b) the kernel vs a hand-written jnp AdamW step (independent
# expression, loose fp32 budget).
from paddle_tpu.kernels.fused_optimizer import (adamw_scalars,
                                                fused_adamw_bucket)
rows = 4096
gf = jnp.asarray(rng.randn(rows, 128), jnp.bfloat16)
wf = jnp.asarray(rng.randn(rows, 128), jnp.float32)
sc = adamw_scalars(3e-4, 0.9, 0.999, 1e-8, 0.01, 1)
# bitwise moment check from ZERO-seeded moments (the step-1 shape):
# with m = v = 0 there is no FMA-contraction ambiguity in the moment
# chain, so Mosaic and XLA:TPU must agree bit-for-bit; from nonzero
# moments a contracted `b1*m + omb1*g` can legally differ by 1 fp32
# ulp and flip a bf16 storage bit — that case gets a tolerance below
mz = jnp.zeros((rows, 128), jnp.bfloat16)
vz = jnp.zeros((rows, 128), jnp.bfloat16)
p_pl, w_pl, m_pl, v_pl = fused_adamw_bucket(
    gf, wf, mz, vz, sc, param_dtype=jnp.bfloat16, use_pallas=True)
p_x, w_x, m_x, v_x = fused_adamw_bucket(
    gf, wf, mz, vz, sc, param_dtype=jnp.bfloat16, use_pallas=False)
assert bool(jnp.all(m_pl == m_x)) and bool(jnp.all(v_pl == v_x)), \
    "fused AdamW step-1 moment storage differs from the XLA composition"
e = relerr(w_pl, w_x)
assert e < 1e-5, f"fused AdamW master vs XLA composition {e}"
# steady-state (nonzero moments): FMA-tolerant budgets, plus an
# independent hand-written fp32 reference
mf = jnp.asarray(rng.randn(rows, 128), jnp.bfloat16) * 0.01
vf = jnp.abs(jnp.asarray(rng.randn(rows, 128), jnp.bfloat16)) * 0.01
sc7 = adamw_scalars(3e-4, 0.9, 0.999, 1e-8, 0.01, 7)
p_pl, w_pl, m_pl, v_pl = fused_adamw_bucket(
    gf, wf, mf, vf, sc7, param_dtype=jnp.bfloat16, use_pallas=True)
p_x, w_x, m_x, v_x = fused_adamw_bucket(
    gf, wf, mf, vf, sc7, param_dtype=jnp.bfloat16, use_pallas=False)
for nm, a, b, budget in [("m", m_pl, m_x, 1e-2), ("v", v_pl, v_x, 1e-2),
                         ("w", w_pl, w_x, 1e-5)]:
    es = relerr(a, b)
    assert es < budget, f"fused AdamW steady-state {nm} parity {es}"
g32 = gf.astype(jnp.float32)
m32 = 0.9 * mf.astype(jnp.float32) + 0.1 * g32
v32 = 0.999 * vf.astype(jnp.float32) + 0.001 * g32 * g32
wd = wf * (1.0 - 3e-4 * 0.01)
ref_w = wd - 3e-4 * (m32 / (1 - 0.9 ** 7)) / (
    jnp.sqrt(v32 / (1 - 0.999 ** 7)) + 1e-8)
e2 = relerr(w_pl, ref_w)
assert e2 < 1e-4, f"fused AdamW vs hand reference {e2}"
print(f"PARITY fused_adamw xla={e:.2e} ref={e2:.2e} OK")

print("CHIP_PARITY_ALL_OK")
