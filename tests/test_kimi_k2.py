"""Kimi-K2's layer (latent attention over a one-pool paged cache, the held
experts' share) at toy width on the CPU, against the benchmark's plain
reference (`benchmarks/references/kimi_k2.py`: float32, expanded attention
only, no cache, no sorting) on seeded weights."""
import ast
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit.api import functional_call
from paddle_tpu.models.kimi_k2 import (KimiK2ForCausalLM, KimiK2MoE,
                                       kimi_k2_tiny)
from paddle_tpu.models.paged import PAGED_ENTRY, PagedSpan
from paddle_tpu.serving import ServingEngine

from benchmarks.references import kimi_k2 as reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3100000031


def build(dtype="float32", **kw):
    """The toy model loaded with the reference's seeded weights (the held
    experts stacked), and the same weights as the reference reads them."""
    cfg = kimi_k2_tiny(**{"experts_held": 8, "expert_offset": 8, **kw})
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        model = KimiK2ForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(prev)
    new = reference.make_weights(cfg, SEED, dtype)
    for k, t in model.state_dict().items():
        if k in new:
            t._data = new[k]
    assert set(new) == {k for k in model.state_dict() if "rope_" not in k}
    return cfg, model, reference.LazyWeights(cfg, SEED, dtype)


def paged_logits(model, ids, n_prompt, chunk=32, page=16, pages=16):
    """Logits at the positions that predict ids[n_prompt:], through the
    engine's own pool, allocator and block tables: the prompt prefilled in
    chunks (the last position of the last chunk predicts the first
    output), then one decode span a token, teacher-forced."""
    eng = ServingEngine(model, num_pages=pages, page_size=page,
                        max_batch_size=2, token_budget=chunk)
    seq = eng.allocator.alloc_sequence(0)
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
        assert counts.shape == (4,)
        return np.asarray(lg, np.float32)

    def table():
        bt = np.zeros((pages,), np.int32)
        bt[:len(seq.pages)] = seq.pages
        return bt

    done = 0
    while done < n_prompt:
        n = min(chunk, n_prompt - done)
        for _ in range(n):
            eng.allocator.append_token(seq)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = ids[done:done + n]
        lg = call(padded, table(), "prefill", done, n)
        done += n
    out.append(lg[0, 0])
    for j in range(n_prompt, len(ids) - 1):
        eng.allocator.append_token(seq)
        # row 1 is a padded row (length 0): it must neither write nor count
        bt = np.stack([table(), np.zeros((pages,), np.int32)])
        lg = call([[ids[j]], [0]], bt, "decode", [j + 1, 0])
        out.append(lg[0, 0])
    eng.shutdown()
    return np.stack(out)


IDS = np.random.default_rng(31).integers(0, 256, 90).tolist()
N_PROMPT = 70      # three chunks of 32: two whole and one of 6


@pytest.fixture(scope="module")
def reference_logits():
    cfg, _, w = build("float32")
    lg = reference.logits(w, cfg, jnp.asarray([IDS], jnp.int32))[0]
    return np.asarray(lg[N_PROMPT - 1:len(IDS) - 1])


# The mean |difference| over the logits (about N(0, 1)) of 20 positions.
# float32: the program and the reference differ in the ORDER of float32
# sums only (the absorbed form multiplies W_kvb into the query before the
# keys, the chunks split the softmax, the experts' rows are summed by
# slot): a few float32 steps (1.2e-7), through 3 layers; the widest
# single logit is held too. bfloat16: every matmul input is rounded to 8
# bits (a relative step of 7.8e-3) through 3 layers of the stream:
# hundredths of a logit on average; a token whose k-th and (k+1)-th
# experts stand closer than that picks the other one, which moves its
# logits by tenths, so only the mean is held there.
TOL = {"float32": 2e-5, "bfloat16": 0.06}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_logits_against_the_reference(
        dtype, reference_logits):
    _, model, _ = build(dtype)
    got = paged_logits(model, IDS, N_PROMPT)
    diff = np.abs(got - reference_logits)
    assert diff.mean() < TOL[dtype], (diff.mean(), diff.max())
    if dtype == "float32":
        assert diff.max() < 2e-4, diff.max()
    else:
        # the float32 limit is tight enough that a bfloat16 program
        # fails it: the comparison sees a loss of precision
        assert diff.mean() > 10 * TOL["float32"], diff.mean()


def test_greedy_tokens_through_add_request_and_step():
    cfg, model, w = build("float32")
    eng = ServingEngine(model, num_pages=64, page_size=16, max_batch_size=4,
                        token_budget=32)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).tolist() for n in (5, 40, 70, 17)]
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    out = eng.run()
    for p, r in zip(prompts, rids):
        gaps, _ = reference.token_gaps(w, cfg, p, out[r])
        assert gaps.max() < 1e-3, gaps
    c = eng.metrics.counters
    # routing over all 32, 8 held: about a quarter of the pairs, never all
    assert 0 < c["moe_pairs_held"] < c["moe_pairs_routed"]
    assert c["moe_pairs_routed"] % cfg.num_experts_per_tok == 0
    assert 0 < c["moe_max_expert_pairs"] <= c["moe_pairs_held"]
    assert 0 < c["moe_experts_touched"]
    # one array a layer: 128 + 16 values an entry, in whole lane tiles
    assert eng.kv_page_bytes == 16 * 256 * 4
    assert eng._v_caches == [] and len(eng._k_caches) == 3
    assert tuple(eng._k_caches[0].shape) == (64, 16, 256)
    eng.reset_prefix_cache()
    assert eng.allocator.num_used == 0
    eng.shutdown()


def test_each_launch_writes_its_counters_into_a_trace(monkeypatch):
    """In a trace every launch's own counts ride on a span under
    `serving.fetch` (where they arrive with the tokens), and add up to the
    engine's counters: a reader sums a traced slice's without the
    window's. Outside a trace nothing is built."""
    spans, stack = [], []

    class Annotation:
        is_enabled = staticmethod(lambda: True)

        def __init__(self, name, **meta):
            self.name, self.meta = name, meta

        def __enter__(self):
            spans.append((self.name, self.meta, stack[-1] if stack else None))
            stack.append(self.name)

        def __exit__(self, *exc):
            stack.pop()

    _, model, _ = build("float32")
    eng = ServingEngine(model, num_pages=64, page_size=16, max_batch_size=4,
                        token_budget=32)
    eng.add_request(list(range(40)), max_new_tokens=4)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    eng.run()
    got = [(m, parent) for n, m, parent in spans
           if n == "serving.model_counters"]
    launches = sum(n in ("serving.prefill_chunk", "serving.decode_step")
                   for n, _, _ in spans)
    assert len(got) == launches >= 4
    assert all(parent == "serving.fetch" for _, parent in got)
    for name in model.paged_counters:
        assert sum(m[name] for m, _ in got) == eng.metrics.counters[name] > 0
    eng.shutdown()


def test_absorbed_attention_agrees_with_expanded():
    """One decode step over a cache that a prefill wrote: the absorbed
    form over the latent pool (the Pallas kernel) against the expanded
    form over the gathered entries."""
    from paddle_tpu.kernels.mla_attention import mla_paged_decode
    from paddle_tpu.models.kimi_k2 import yarn_softmax_scale
    cfg, model, _ = build("float32")
    attn = model.model.layers[1].self_attn
    rng = np.random.default_rng(7)
    B, P, page, W = 3, 4, 16, 256     # 128 + 16 values, whole lane tiles
    lens = np.asarray([50, 17, 64], np.int32)
    pool = jnp.asarray(rng.normal(size=(1 + B * P, page, W)), jnp.float32)
    bt = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
    qn = jnp.asarray(rng.normal(size=(B, 8, cfg.qk_nope_head_dim)),
                     jnp.float32)
    qp = jnp.asarray(rng.normal(size=(B, 8, cfg.qk_rope_head_dim)),
                     jnp.float32)
    wkvb = attn.kv_b_proj.weight._data
    q_abs, w_v = attn._absorbed_query(qn, qp, wkvb)
    q_abs = jnp.pad(q_abs, ((0, 0), (0, 0), (0, W - q_abs.shape[-1])))
    o_lat = mla_paged_decode(q_abs, pool, jnp.asarray(bt), jnp.asarray(lens),
                             rank=cfg.kv_lora_rank,
                             sm_scale=yarn_softmax_scale(cfg))
    absorbed = jnp.einsum("bhr,rhd->bhd", o_lat, w_v)
    for b in range(B):
        lat = pool[bt[b]].reshape(P * page, W)
        exp = attn._expand(qn[b:b + 1], qp[b:b + 1], lat, wkvb,
                           jnp.asarray([lens[b] - 1], jnp.int32))
        np.testing.assert_allclose(np.asarray(absorbed[b]),
                                   np.asarray(exp[0]), atol=2e-5, rtol=2e-5)


def _moe(offset, held, n=32, seed=3):
    cfg = kimi_k2_tiny(n_routed_experts=n, experts_held=held,
                       expert_offset=offset)
    layer = KimiK2MoE(cfg)
    key = jax.random.PRNGKey(seed)
    h, i = cfg.hidden_size, cfg.moe_intermediate_size
    full = {k: jax.random.normal(jax.random.fold_in(key, j), shape,
                                 jnp.float32) * np.float32(s)
            for j, (k, shape, s) in enumerate([
                ("gate", (h, n), h ** -0.5), ("bias", (n,), 0.05),
                ("eg", (n, h, i), h ** -0.5), ("eu", (n, h, i), h ** -0.5),
                ("ed", (n, i, h), i ** -0.5)])}
    layer.gate.weight._data = full["gate"]
    layer.gate.e_score_correction_bias._data = full["bias"]
    sl = slice(offset, offset + held)
    layer.experts.gate_proj._data = full["eg"][sl]
    layer.experts.up_proj._data = full["eu"][sl]
    layer.experts.down_proj._data = full["ed"][sl]
    return layer


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """Over all 32 offsets (32 experts, one held a chip), each share's
    routed part, and the shared expert counted ONCE, against the layer
    that holds every expert."""
    x = paddle.Tensor(jnp.asarray(
        np.random.default_rng(1).normal(size=(2, 9, 64)), jnp.float32))
    with paddle.no_grad():
        whole = _moe(0, 32)
        uncut, counts = whole(x)
        total = whole.shared_experts(x)._data
        pairs = 0
        for off in range(32):
            part, c = _moe(off, 1).routed(x)
            total = total + part._data
            pairs += int(c._data[1])
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut._data),
                               atol=2e-5, rtol=2e-5)
    # every routed pair was computed by exactly one share
    assert pairs == int(counts._data[0]) == int(counts._data[1]) == 2 * 9 * 4


def test_no_token_is_dropped_when_every_token_picks_one_expert():
    """A bias that makes expert 3 every token's first choice: 200 tokens
    on one expert (no capacity, nothing dropped), against that expert's
    plain product on every token."""
    layer = _moe(0, 8)
    bias = np.zeros((32,), np.float32)
    bias[3] = 10.0
    layer.gate.e_score_correction_bias._data = jnp.asarray(bias)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 200, 64)),
                    jnp.float32)
    with paddle.no_grad():
        y, counts = layer.routed(paddle.Tensor(x))
    from paddle_tpu.models.kimi_k2 import route
    cfg = layer.cfg
    idx, w = route(x[0], layer.gate.weight._data, jnp.asarray(bias),
                   top_k=4, scale=cfg.routed_scaling_factor, norm=True)
    assert bool((idx == 3).any(axis=1).all())
    want = np.zeros((200, 64), np.float32)
    for e in range(8):
        g = x[0] @ layer.experts.gate_proj._data[e]
        u = x[0] @ layer.experts.up_proj._data[e]
        part = (jax.nn.silu(g) * u) @ layer.experts.down_proj._data[e]
        wt = jnp.where(idx == e, w, 0.0).sum(axis=1)
        want = want + np.asarray(wt[:, None] * part)
    np.testing.assert_allclose(np.asarray(y._data[0]), want, atol=2e-5,
                               rtol=2e-5)
    assert int(counts._data[3]) == 200      # the busiest expert's pairs
    assert int(counts._data[1]) == int((np.asarray(idx) < 8).sum())


def _route_case(scores, held=(8, 16), k=4):
    """`reference._route` on hand-made scores over 32 experts: h is the
    identity's rows, so a token's router logits are a row of the gate."""
    from types import SimpleNamespace
    cfg = SimpleNamespace(num_experts_per_tok=k, norm_topk_prob=True,
                          routed_scaling_factor=1.0, n_routed_experts=32,
                          expert_offset=held[0],
                          experts_held=held[1] - held[0])
    st = SimpleNamespace(cfg=cfg, __hash__=None)
    s = np.asarray(scores, np.float64)
    logit = np.log(s / (1 - s)).astype(np.float32)
    h = jnp.eye(len(s), dtype=jnp.float32)
    return reference._route.__wrapped__(h, jnp.asarray(logit),
                                        jnp.zeros(32), st)


@pytest.mark.parametrize("case", ["held_in", "held_out", "held_clear",
                                  "others_tie"])
def test_the_reference_marks_a_held_expert_at_a_routing_tie(case):
    """A position is left out where a HELD expert (8..15 here) stands
    closer to the selection's cut than ROUTE_TIE, chosen or not; a tie
    among experts this chip does not hold changes nothing of its share
    and marks nothing."""
    base = np.full((32,), 0.2)
    base[[0, 1, 2]] = (0.9, 0.8, 0.7)          # three clear choices
    tie = reference.ROUTE_TIE
    row = base.copy()
    if case == "held_in":                      # a held expert just inside
        row[9], row[20] = 0.6, 0.6 - tie / 2
    elif case == "held_out":                   # ... and just outside
        row[20], row[9] = 0.6, 0.6 - tie / 2
    elif case == "held_clear":                 # inside by four ties
        row[9], row[20] = 0.6, 0.6 - 4 * tie
    else:                                      # the tie is between others
        row[20], row[21] = 0.6, 0.6 - tie / 2
    wt, near = _route_case(row[None])
    marked = float(near[0]) < tie
    assert marked == (case in ("held_in", "held_out")), (case, float(near[0]))
    assert int((np.asarray(wt[0]) > 0).sum()) == 4
    if case == "held_clear":
        assert float(near[0]) == pytest.approx(4 * tie, rel=1e-3)


def test_token_gaps_leaves_out_the_positions_at_a_tie(monkeypatch):
    """`token_gaps` drops exactly the positions whose distance from a
    tie is under ROUTE_TIE, and `position_logits` is unchanged by it."""
    lg = np.zeros((5, 7), np.float32)
    lg[np.arange(5), [1, 2, 3, 4, 5]] = 1.0           # the reference's best
    margin = np.asarray([1.0, reference.ROUTE_TIE / 2, 1.0,
                         reference.ROUTE_TIE, 0.0], np.float32)
    monkeypatch.setattr(reference, "position_logits_and_margins",
                        lambda *a, **k: (lg, margin))
    served = [1, 0, 0, 4, 0]                  # wrong at 1 (tie), 2, 4 (tie)
    gaps, top = reference.token_gaps(None, None, [9], served)
    assert gaps.tolist() == [0.0, 1.0, 0.0] and top == 1.0
    assert reference.position_logits(None, None, [9], served) is lg


def test_the_margins_of_a_full_forward_are_the_layers_least():
    """Through the toy model: every position has a finite distance (two
    expert layers), most stand clear of a tie, and the logits beside them
    are `logits`' own."""
    cfg, _, w = build()
    ids = np.random.default_rng(7).integers(0, 256, 48).tolist()
    lg, margin = reference.position_logits_and_margins(w, cfg, ids[:8],
                                                       ids[8:])
    full = reference.logits(w, cfg, jnp.asarray([ids]))[0]
    np.testing.assert_array_equal(lg, np.asarray(full[7:47]))
    assert margin.shape == (40,) and np.isfinite(margin).all()
    assert (margin >= 0).all() and (margin >= reference.ROUTE_TIE).mean() > 0.5


def test_a_chunk_attends_through_the_flash_kernel_at_the_cells_buckets():
    """The path a prefill chunk takes is a rule of its shapes stated once
    (`unsupported_reason`), not a caught exception: every pinned bucket of
    `kimi-k2.5-serve1` takes the flash kernel, and a shape the kernel's
    tiling refuses takes the XLA composition and says so."""
    import json
    from paddle_tpu.kernels import flash_attention as fa
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kimi-k2.5-serve1.json")) as f:
        cell_cfg = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "workloads",
                           "serve-reasoning-decode.json")) as f:
        cell = json.load(f)
    attn = build()[1].model.layers[0].self_attn
    page = cell_cfg["engine"]["page_size"]
    for s in cell_cfg["engine"]["prefill_buckets"]:
        for p in cell["engine"]["pages_buckets"]:
            assert attn.chunk_path(s, p * page, jnp.bfloat16) == "flash"
    with pytest.warns(UserWarning, match="XLA composition"):
        assert attn.chunk_path(1500, 5120, jnp.bfloat16) == "xla"
    # the tile is the kernel's own decision, from the head width
    assert fa._pick_block_q(2048, 128) == fa._pick_block_q(2048) == 1024
    assert fa._pick_block_q(2048, 192) == fa._pick_block_k(5120, 192) == 512
    assert fa._pick_block_k(5120, 128) == 1024


NEW_MODULES = ("paddle_tpu/models/kimi_k2.py", "paddle_tpu/models/paged.py",
               "paddle_tpu/kernels/mla_attention.py",
               "paddle_tpu/kernels/grouped_matmul.py",
               "benchmarks/families/kimi_k2.py",
               "benchmarks/references/kimi_k2.py",
               "benchmarks/readers/trace_op_counters.py")


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


def test_importing_the_new_modules_builds_no_array_and_compiles_nothing():
    """In a fresh process: `import paddle_tpu` does not import the model or
    its kernels, and importing them leaves no live array and compiles
    nothing."""
    code = """
import jax, sys
import paddle_tpu
lazy = ["paddle_tpu.models.kimi_k2", "paddle_tpu.kernels.mla_attention",
        "paddle_tpu.kernels.grouped_matmul"]
assert not [m for m in lazy if m in sys.modules], "imported with the package"
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, *a, **k: compiles.append(name) if "compile" in name else None)
before = len(jax.live_arrays())
import importlib
for m in lazy + ["paddle_tpu.models.paged", "benchmarks.families.kimi_k2",
                 "benchmarks.references.kimi_k2",
                 "benchmarks.readers.trace_op_counters"]:
    importlib.import_module(m)
assert len(jax.live_arrays()) == before, (before, len(jax.live_arrays()))
assert compiles == [], compiles
print("ok")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=300)
    assert p.returncode == 0 and p.stdout.strip().endswith("ok"), p.stderr
