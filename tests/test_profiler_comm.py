"""Collective-traffic accounting + runtime comm counters (ISSUE 12).

Three legs, each pinned the way its PR-10/11 sibling is:

* the IR walk (`profiler/comm.py`): byte counts for psum / all-gather /
  reduce-scatter / collective-permute on the 8-virtual-CPU mesh must
  match HAND-COMPUTED payload bytes exactly, and per-axis attribution
  must be correct for the hybrid-mesh programs (ZeRO-1 fused AdamW ->
  the param-bucket all-gather on 'sharding'; the TP=2 decode program ->
  the row-parallel psum on 'model', gated on the gspmd_tp_mesh probe);
* the runtime counters (`distributed/collective.py`): calls/bytes/
  group-size per primitive, booby-trapped OFF path (the recorder is
  never invoked when disabled) and counters-on-vs-off bit-identity;
* the shared exposition: comm counters and SPMD `rule_stats()` render
  through `profiler/exposition.py` with the name bijection asserted in
  BOTH directions (the drift-test contract of ISSUE 10/11), and
  `FLAGS_spmd_debug` rule failures land as shared Diagnostics in
  `to_static_report()["purity_diagnostics"]`, not on stdout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import collective as C
from paddle_tpu.distributed.fleet import fleet, DistributedStrategy
from paddle_tpu.profiler import comm as pcomm
from paddle_tpu.profiler.exposition import parse_exposition_names

from _env_probes import gspmd_tp_mesh, skip_unless

from jax import shard_map


# ------------------------------------------------------------ HLO parse
def test_parse_replica_groups_forms():
    # explicit
    assert pcomm.parse_replica_groups("replica_groups={{0,1},{2,3}}") \
        == [(0, 1), (2, 3)]
    # empty = every participant
    assert pcomm.parse_replica_groups("replica_groups={}") is None
    # iota v2
    assert pcomm.parse_replica_groups("replica_groups=[2,4]<=[8]") \
        == [(0, 1, 2, 3), (4, 5, 6, 7)]
    # iota with transpose: iota([4,2]) transposed by (1,0) -> strided
    assert pcomm.parse_replica_groups("replica_groups=[2,4]<=[4,2]T(1,0)") \
        == [(0, 2, 4, 6), (1, 3, 5, 7)]


SYNTHETIC_HLO = """\
ENTRY %main {
  %p0 = f32[8,16]{1,0} parameter(0)
  %ar = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %p0), replica_groups={{0,1},{2,3}}, to_apply=%add
  %ags = (f32[64]{0}, f32[256]{0}) all-gather-start(f32[64]{0} %p1), replica_groups=[2,4]<=[8], dimensions={0}
  %agd = f32[256]{0} all-gather-done((f32[64]{0}, f32[256]{0}) %ags)
  %cp = f32[64]{0} collective-permute(f32[64]{0} %p1), source_target_pairs={{0,1},{1,0}}
}
"""


def test_parse_hlo_collectives_synthetic():
    ops = pcomm.parse_hlo_collectives(SYNTHETIC_HLO)
    kinds = [op.kind for op in ops]
    # the -done half of the async pair is NOT a second op
    assert kinds == ["all-reduce", "all-gather", "collective-permute"]
    ar, ag, cp = ops
    assert ar.payload_bytes == 8 * 16 * 4       # operand buffer
    assert ar.group_size == 2
    # all-gather accounted at the RESULT it materializes: operand x
    # group size (robust to the async tuple result double-listing)
    assert ag.payload_bytes == 64 * 4 * 4
    assert ag.group_size == 4
    assert cp.payload_bytes == 64 * 4
    assert cp.group_size == 2


def test_axis_attribution_and_unattributed():
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    ops = pcomm.parse_hlo_collectives(SYNTHETIC_HLO)
    ar = ops[0]
    # groups {0,1},{2,3}: coords vary only in the trailing 'model' dim
    assert pcomm.attribute_axes(ar, mesh) == ("model",)
    # rows of 4 on a 2x4 mesh: still 'model' only
    ag = ops[1]
    assert pcomm.attribute_axes(ag, mesh) == ("model",)
    # a single group over all 8 devices spans both axes -> compound
    fused = pcomm.CollectiveOp("all-reduce", 64, 64,
                               pcomm.parse_replica_groups("[1,8]<=[8]"), 8)
    assert pcomm.attribute_axes(fused, mesh) == ("data", "model")
    rep = pcomm.CommReport([ar, fused], mesh=mesh)
    assert rep.bytes_per_axis() == {"model": ar.payload_bytes,
                                    "data+model": fused.payload_bytes}
    # an entry outside the mesh -> UNATTRIBUTED, never dropped
    bad = pcomm.CollectiveOp("all-reduce", 4, 4, [(0, 9)], 2)
    rep2 = pcomm.CommReport([bad], mesh=mesh)
    assert rep2.bytes_per_axis() == {pcomm.UNATTRIBUTED: 4}
    assert rep2.payload_bytes == 4


# -------------------------------------------- exact bytes, 8-device mesh
def _flat_mesh():
    return Mesh(np.array(jax.devices()), ("x",))


def _shmap(body, mesh, out_specs=P("x")):
    return jax.jit(shard_map(body, mesh=mesh, in_specs=P("x"),
                             out_specs=out_specs, check_vma=False))


def test_collective_bytes_match_hand_computed_exactly():
    """The acceptance-criteria table: each primitive on a known-size
    f32[1024] over the flat 8-device axis accounts exactly the payload
    rule's bytes (per-shard operand for psum/reduce-scatter/ppermute,
    the materialized full array for all-gather), all on axis 'x'."""
    mesh = _flat_mesh()
    n_dev = len(jax.devices())
    assert n_dev == 8, "tests run on the 8-virtual-CPU-device platform"
    N = 1024
    x = jax.ShapeDtypeStruct((N,), np.float32)
    full = N * 4
    shard = full // n_dev
    cases = {
        "psum": (_shmap(lambda a: jax.lax.psum(a, "x"), mesh),
                 "all-reduce", shard),
        "all_gather": (_shmap(lambda a: jax.lax.all_gather(
            a, "x", tiled=True), mesh, P(None)), "all-gather", full),
        "reduce_scatter": (_shmap(lambda a: jax.lax.psum_scatter(
            a, "x", tiled=True), mesh), "reduce-scatter", shard),
        "ppermute": (_shmap(lambda a: jax.lax.ppermute(
            a, "x", [(i, (i + 1) % n_dev) for i in range(n_dev)]), mesh),
            "collective-permute", shard),
    }
    for name, (fn, kind, want) in cases.items():
        rep = pcomm.lowered_comm(fn.lower(x), mesh=mesh)
        assert rep.payload_bytes == want, (name, rep.to_dict())
        assert rep.op_counts() == {kind: 1}, (name, rep.to_dict())
        assert rep.bytes_per_axis() == {"x": want}, (name, rep.to_dict())


def test_gspmd_sum_attributes_each_axis():
    """A GSPMD (constraint-driven) reduction over a 2x4 mesh emits one
    all-reduce per axis; each is attributed to ITS axis with the
    per-shard payload."""
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))

    def f(a):
        a = jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P("data", "model")))
        return a.sum()

    rep = pcomm.jit_comm(f, jax.ShapeDtypeStruct((8, 16), np.float32),
                         mesh=mesh)
    assert rep.op_counts() == {"all-reduce": 2}
    assert rep.bytes_per_axis() == {"model": 4, "data": 4}
    d = rep.to_dict()
    assert d["mesh_axes"] == ["data", "model"]
    assert d["payload_bytes"] == 8


# -------------------------------------------------- hybrid-mesh programs
def _hybrid_mesh(**degrees):
    st = DistributedStrategy()
    cfg = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
           "sharding_degree": 1, "sep_degree": 1}
    cfg.update(degrees)
    st.hybrid_configs = cfg
    fleet.init(is_collective=True, strategy=st)


def test_zero1_fused_adamw_param_all_gather_on_sharding():
    """The ZeRO-1 compiled step's traffic lands ENTIRELY on 'sharding'
    (the only >1 axis), and the param-bucket all-gather is visible at
    exactly the bucket's bytes (per-shard operand x degree 8 = the
    gathered bucket every rank ends up holding)."""
    try:
        _hybrid_mesh(sharding_degree=8)
        h = 48
        paddle.seed(0)
        net = paddle.nn.Sequential(paddle.nn.Linear(h, h),
                                   paddle.nn.GELU(),
                                   paddle.nn.Linear(h, h))
        opt = paddle.optimizer.AdamW(1e-2, parameters=net.parameters(),
                                     fused=True)
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(8, h).astype(np.float32))
        y = paddle.to_tensor(rng.randn(8, h).astype(np.float32))

        def step(a, b):
            loss = paddle.nn.functional.mse_loss(net(a), b)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        sstep = paddle.jit.to_static(step, state_objects=[net, opt])
        for _ in range(3):
            sstep(x, y)
        rep = sstep.comm_report()
        assert rep["payload_bytes"] > 0
        assert set(rep["bytes_per_axis"]) == {"sharding"}, rep
        assert rep["op_counts"].get("all-gather", 0) >= 1
        bucket = opt._accumulators["fused_m"][0]
        bucket_bytes = int(np.prod(bucket.shape)) * 4
        prog = rep["programs"][-1]
        assert any(op["kind"] == "all-gather"
                   and op["payload_bytes"] == bucket_bytes
                   and op["group_size"] == 8
                   for op in prog["ops"]), prog["ops"]
    finally:
        fleet._hcg = None


@skip_unless(gspmd_tp_mesh)
def test_tp2_decode_row_parallel_psum_on_model():
    """The TP=2 serving programs' collectives all attribute to 'model';
    the decode family carries the row-parallel psum (all-reduce)."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine, tp_serving_mesh
    cfg = LlamaConfig(vocab_size=128, hidden_size=256,
                      intermediate_size=256, num_hidden_layers=1,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=128)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    eng = ServingEngine(model, mesh=tp_serving_mesh(2), num_pages=64,
                        page_size=8, token_budget=32, batch_buckets=[8],
                        prefill_buckets=[32], pages_buckets=[8],
                        temperature=0.0)
    try:
        eng.add_request([1, 2, 3, 4], max_new_tokens=4)
        guard = 0
        while eng.has_work():
            eng.step()
            guard += 1
            assert guard < 100
        table = eng.comm_table()
        decode_rows = {k: v for k, v in table.items() if k[0] == "decode"}
        assert decode_rows
        for k, rec in table.items():
            assert rec is not None and "error" not in rec, (k, rec)
            assert set(rec["bytes_per_axis"]) <= {"model"}, (k, rec)
        for k, rec in decode_rows.items():
            assert rec["op_counts"].get("all-reduce", 0) >= 1, (k, rec)
            assert rec["bytes_per_axis"].get("model", 0) > 0
    finally:
        eng.shutdown()


def test_meshless_program_accounts_zero():
    """No mesh, no sharding: the honest accounting is zero bytes — and
    comm_report still returns the full structure (bench.py's single-chip
    answer)."""
    paddle.seed(0)
    net = paddle.nn.Linear(8, 8)

    def f(a):
        return net(a).sum()

    sf = paddle.jit.to_static(f, state_objects=[net])
    sf(paddle.to_tensor(np.ones((2, 8), np.float32)))
    rep = sf.comm_report()
    assert rep["payload_bytes"] == 0
    assert rep["bytes_per_axis"] == {}
    assert rep["op_counts"] == {}
    assert all("error" not in p for p in rep["programs"])


# ------------------------------------------------------ runtime counters
@pytest.fixture
def fresh_comm_stats():
    C.reset_comm_stats()
    prev = C.set_comm_stats_enabled(True)
    yield
    C.set_comm_stats_enabled(prev)
    C.reset_comm_stats()


def test_comm_counters_calls_bytes_group(fresh_comm_stats):
    t = paddle.to_tensor(np.ones((4, 8), np.float32))
    dist.all_reduce(t)
    dist.all_reduce(t)
    dist.broadcast(t)
    dist.barrier()
    dist.all_gather_object([], {"some": "object"})
    s = C.comm_stats()
    assert s["all_reduce_calls"] == 2
    assert s["all_reduce_bytes"] == 2 * 4 * 8 * 4     # shape x itemsize
    assert s["all_reduce_group_size"] == 1            # world-1 group
    assert s["broadcast_calls"] == 1
    assert s["broadcast_bytes"] == 128
    assert s["barrier_calls"] == 1 and s["barrier_bytes"] == 0
    assert s["all_gather_object_calls"] == 1
    # reduce() delegates to all_reduce and must be counted ONCE
    dist.reduce(t)
    s = C.comm_stats()
    assert s["all_reduce_calls"] == 3
    assert "reduce_calls" not in s
    # counters joined the shared profiler registry
    import paddle_tpu.profiler as prof
    assert prof.counters().get("distributed_comm", {}) == s


def test_comm_counters_off_never_invokes_recorder(fresh_comm_stats,
                                                  monkeypatch):
    """Booby trap (the PR-10/11 pattern): with counting disabled the
    payload reader must never run — and either way the collective's
    NUMERIC result is untouched (the counters read shapes only, so
    on-vs-off is bit-identical by construction; asserted anyway)."""
    t = paddle.to_tensor(np.arange(32, dtype=np.float32).reshape(4, 8))
    dist.all_reduce(t)                      # in-place on tensor
    on = np.asarray(t._data).copy()

    def boom(*a, **k):
        raise AssertionError("payload reader ran with counters off")

    C.set_comm_stats_enabled(False)
    monkeypatch.setattr(C, "_tensor_payload_bytes", boom)
    before = C.comm_stats()
    t2 = paddle.to_tensor(np.arange(32, dtype=np.float32).reshape(4, 8))
    dist.all_reduce(t2)
    off = np.asarray(t2._data).copy()
    dist.broadcast(t2)
    dist.reduce_scatter(t2, [t2])
    assert C.comm_stats() == before       # nothing recorded
    assert (on == off).all()              # trajectory bit-identical
    # re-enabling routes through the (trapped) reader again — the off
    # path really was the only thing keeping it quiet
    C.set_comm_stats_enabled(True)
    with pytest.raises(AssertionError, match="counters off"):
        dist.all_reduce(t2)


def test_comm_counters_on_vs_off_training_bit_identical(fresh_comm_stats):
    """The DP eager pattern (all_reduce on grads between steps) trains
    bit-identically with counters on vs off."""
    def run(enabled):
        prev = C.set_comm_stats_enabled(enabled)
        try:
            paddle.seed(11)
            net = paddle.nn.Linear(16, 16)
            opt = paddle.optimizer.AdamW(1e-2,
                                         parameters=net.parameters())
            x = paddle.to_tensor(np.ones((4, 16), np.float32))
            for _ in range(3):
                loss = (net(x) ** 2).mean()
                loss.backward()
                for p in net.parameters():
                    dist.all_reduce(p.grad)
                opt.step()
                opt.clear_grad()
            return {k: np.asarray(v._data).copy()
                    for k, v in net.state_dict().items()}
        finally:
            C.set_comm_stats_enabled(prev)

    off = run(False)
    on = run(True)
    assert C.comm_stats()["all_reduce_calls"] > 0    # on-run did count
    for k in off:
        assert (off[k] == on[k]).all(), k


# ------------------------------------------------------ exposition drift
def _expected_flat_names(snap, prefix):
    return {f"{prefix}_{k}" for k, v in snap.items() if v is not None}


def test_comm_exposition_drift_bijection(fresh_comm_stats):
    """Both directions: every comm_stats key appears in the scrape,
    every scrape name maps back — and a NEW primitive surfaces with no
    hand-maintained list (the registry contract of ISSUE 10/11)."""
    t = paddle.to_tensor(np.ones((4, 8), np.float32))
    dist.all_reduce(t)
    dist.barrier()
    C._COMM_STATS["totally_new_prim_calls"] = 7       # the drift probe
    C._COMM_STATS["totally_new_prim_bytes"] = 11
    text = C.comm_prometheus_text()
    names = parse_exposition_names(text)
    assert names == _expected_flat_names(C.comm_stats(), "paddle_comm")
    assert "paddle_comm_totally_new_prim_calls" in names
    # typing: _calls/_bytes counter, _group_size gauge
    assert "# TYPE paddle_comm_all_reduce_calls counter" in text
    assert "# TYPE paddle_comm_all_reduce_bytes counter" in text
    assert "# TYPE paddle_comm_all_reduce_group_size gauge" in text
    assert "# TYPE paddle_comm_totally_new_prim_calls counter" in text
    # empty stats -> empty scrape, not a parse error
    C.reset_comm_stats()
    assert C.comm_prometheus_text() == ""


def test_rule_stats_exposition_drift_bijection():
    """rule_stats() renders through the shared renderer: one labelled
    line per op under each nested dict, names bijective with the
    non-empty snapshot entries; the provider joins profiler.counters()
    when propagation activates."""
    from paddle_tpu.distributed.auto_parallel import propagation as prop
    from paddle_tpu.distributed.auto_parallel import spmd_propagation
    from paddle_tpu.distributed.auto_parallel.spmd_rules import (
        _RULES, SpmdResult, register_spmd_rule)
    from paddle_tpu.ops.dispatch import apply_op
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))

    @register_spmd_rule("spmd_expo_ok")
    def _ok(x_spec, **attrs):
        return SpmdResult([x_spec], x_spec)

    @register_spmd_rule("spmd_expo_bad")
    def _bad(x_spec, **attrs):
        raise RuntimeError("exposition probe")

    try:
        x = paddle.Tensor(jax.device_put(
            jnp.ones((8, 16)), NamedSharding(mesh, P("data", None))))
        prop.reset_rule_stats()
        with spmd_propagation(mesh):
            apply_op("spmd_expo_ok", lambda a: a + 1.0, x)
            apply_op("spmd_expo_bad", lambda a: a + 1.0, x)
        stats = prop.rule_stats()
        assert stats["hits"].get("spmd_expo_ok") == 1
        assert stats["errors"].get("spmd_expo_bad") == 1
        text = prop.rules_prometheus_text()
        names = parse_exposition_names(text)
        # nested dicts render as one labelled series per metric name:
        # names biject with the NON-EMPTY snapshot entries (an empty
        # dict emits its TYPE header only — no samples to map back)
        assert names == {f"paddle_spmd_{k}" for k, v in stats.items()
                         if v}
        assert 'paddle_spmd_hits{hit="spmd_expo_ok"} 1' in text
        assert 'paddle_spmd_errors{error="spmd_expo_bad"} 1' in text
        # last_error values are strings -> labelled info-style lines
        assert "paddle_spmd_last_error" in names
        # the provider joined the shared registry on activation
        import paddle_tpu.profiler as prof
        assert prof.counters().get("spmd_rules") == stats
    finally:
        _RULES.pop("spmd_expo_ok", None)
        _RULES.pop("spmd_expo_bad", None)
        prop.reset_rule_stats()


def test_spmd_debug_failure_routed_to_diagnostics(capsys):
    """FLAGS_spmd_debug failures land machine-readable in the shared
    purity Diagnostics (to_static_report()["purity_diagnostics"]), not
    as a bare print on stdout (the PR-4 diagnostics path)."""
    from paddle_tpu.analysis import purity
    from paddle_tpu.distributed.auto_parallel import spmd_propagation
    from paddle_tpu.distributed.auto_parallel.spmd_rules import (
        _RULES, register_spmd_rule)
    from paddle_tpu.jit.api import to_static_report
    from paddle_tpu.ops.dispatch import apply_op
    from paddle_tpu.utils.flags import set_flags, get_flags
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))

    @register_spmd_rule("spmd_diag_op")
    def _broken(x_spec, **attrs):
        raise RuntimeError("diagnostics probe failure")

    prev = get_flags("spmd_debug")["FLAGS_spmd_debug"]
    purity.reset()
    try:
        set_flags({"spmd_debug": True})
        x = paddle.Tensor(jax.device_put(
            jnp.ones((8, 16)), NamedSharding(mesh, P("data", None))))
        with spmd_propagation(mesh):
            out = apply_op("spmd_diag_op", lambda a: a + 1.0, x)
        np.testing.assert_allclose(np.asarray(out._data), 2.0)
        diags = [d for d in to_static_report()["purity_diagnostics"]
                 if d.get("slug") == "spmd-rule"]
        assert diags, "rule failure did not reach purity diagnostics"
        assert "spmd_diag_op" in diags[0]["message"]
        assert "diagnostics probe failure" in diags[0]["message"]
        assert capsys.readouterr().out == ""      # nothing on stdout
        # flag OFF: counted (unconditional) but NOT recorded
        purity.reset()
        set_flags({"spmd_debug": False})
        with spmd_propagation(mesh):
            apply_op("spmd_diag_op", lambda a: a + 1.0, x)
        assert not [d for d in purity.snapshot()
                    if d.slug == "spmd-rule"]
    finally:
        set_flags({"spmd_debug": prev})
        _RULES.pop("spmd_diag_op", None)
        purity.reset()


# ------------------------------------------------- serving program cache
def test_program_cache_comm_table_meshless_unattributed():
    """ProgramCache.comm_table without a mesh still accounts (ops land
    unattributed); programs never launched return None, errors never
    raise (the cost_table contract)."""
    from paddle_tpu.serving.program_cache import ProgramCache
    mesh = _flat_mesh()
    pc = ProgramCache().register_family("probe", lambda: 4)
    fn = _shmap(lambda a: jax.lax.psum(a, "x"), mesh)
    prog = pc.get(("probe", "psum"), lambda: fn)
    x = jax.device_put(jnp.ones((1024,), np.float32),
                       NamedSharding(mesh, P("x")))
    prog(x)
    rec_meshless = pc.comm_table()[("probe", "psum")]
    assert rec_meshless["payload_bytes"] == 512
    assert rec_meshless["bytes_per_axis"] == {pcomm.UNATTRIBUTED: 512}
    rec = pc.comm_table(mesh=mesh)[("probe", "psum")]
    assert rec["bytes_per_axis"] == {"x": 512}


def test_program_cache_meshless_resolves_ambient_mesh():
    """A meshless comm_table under an ACTIVE fleet mesh attributes over
    that ambient mesh and caches under its axes signature — the cache
    key always matches the attribution performed (a later fleet
    re-init must not be answered from a stale 'no mesh' entry)."""
    from paddle_tpu.serving.program_cache import ProgramCache
    mesh = _flat_mesh()
    pc = ProgramCache().register_family("probe", lambda: 4)
    fn = _shmap(lambda a: jax.lax.psum(a, "x"), mesh)
    prog = pc.get(("probe", "psum"), lambda: fn)
    x = jax.device_put(jnp.ones((1024,), np.float32),
                       NamedSharding(mesh, P("x")))
    prog(x)
    try:
        _hybrid_mesh(sharding_degree=8)
        rec = pc.comm_table()[("probe", "psum")]
        # the program's own axis 'x' is not an ambient-mesh axis: the
        # replica groups span several hybrid axes -> compound label,
        # NOT the unattributed bucket a truly meshless call produces
        assert set(rec["bytes_per_axis"]) != {pcomm.UNATTRIBUTED}
        cached = prog._comm
        ambient_axes = ("data", "pipe", "sharding", "sep", "model")
        assert ambient_axes in cached and None not in cached
    finally:
        fleet._hcg = None
    # with the fleet gone, meshless now truly means unattributed —
    # answered fresh, not from the ambient-mesh cache entry
    rec2 = pc.comm_table()[("probe", "psum")]
    assert rec2["bytes_per_axis"] == {pcomm.UNATTRIBUTED: 512}
