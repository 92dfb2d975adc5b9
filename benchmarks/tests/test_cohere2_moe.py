"""The third family (`families/cohere2_moe.py`, `references/cohere2_moe.py`)
and its cell: the toy cell through run_cell.py's own dispatch on the CPU,
the family's work counts at the published widths against ISSUE 35's
arithmetic, its metrics' readers, the control at toy width, and the
configuration file against the catalog."""
import json
import os
import time

import numpy as np
import pytest

from benchmarks import control_gap
from benchmarks.harness import common, lookup, observe
from benchmarks.readers import ratio, trace_op_counters

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def toy_run():
    from benchmarks import run_cell as command
    cell, cfg = common.load_cell("toy-cohere-closed", DATA)
    line, obs = command.measure(cell, cfg, common.CPU_AS, seed=3500000033,
                                seconds=1.5, trace=0, t_start=time.time())
    return line, obs


def metric(name):
    return common.load_json(os.path.join(common.BENCH_DIR, "metrics",
                                         f"{name}.json"))


def test_the_toy_cell_through_the_commands_own_dispatch(toy_run):
    line, obs = toy_run
    assert lookup.family(obs["cfg"]).__name__ \
        == "benchmarks.families.cohere2_moe"
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert line["checks"]["logit_gap_widest"]["value"] \
        <= line["checks"]["logit_gap_widest"]["limit"]
    assert line["checks"]["pages_in_use_after_reset"]["value"] == 0
    c = obs["counters"]["window"]["serving"]
    # 8 of 16 experts held, 4 a token: about half of the pairs, 4 layers
    assert 0.3 < c["moe_pairs_held"] / c["moe_pairs_routed"] < 0.7
    assert c["moe_pairs_routed"] == 4 * (c["prefill_tokens"]
                                         + c["decode_tokens"]) * 4
    # a decode step's keys: at most the context in the full layer and the
    # 32-token window in each of the three window layers
    assert 0 < c["attn_decode_keys"] \
        <= c["decode_tokens"] * (256 + 3 * 32)
    assert 0 < c["attn_chunk_pairs"] \
        <= c["prefill_tokens"] * (256 + 3 * 32)
    assert 0 < c["kv_window_pages_held"] < c["kv_window_pages_full"]
    got = observe.read_metrics(obs)
    assert {"serve_step_ms", "batch_occupancy", "serve_mfu"} <= set(got)
    # (the family's own metrics name their cell: the readers are held below)
    # the family brings no count of K and V pages by the driver's contexts
    assert lookup.work(obs["cfg"], "paged_decode_kv") is None
    assert lookup.work(obs["cfg"], "window_decode_kv") is not None


def test_the_new_metrics_readers_on_the_toy_run(toy_run, monkeypatch):
    _, obs = toy_run
    c = obs["counters"]["window"]["serving"]
    args = dict(metric("cohere2_moe_load_imbalance")["args"], scale=8.0)
    got = ratio.read(obs, args)                      # 8 held at toy width
    assert got == pytest.approx(8.0 * c["moe_max_expert_pairs"]
                                / c["moe_pairs_held"])
    assert 1.0 <= got <= 8.0
    share = ratio.read(obs, metric("window_kv_held_share")["args"])
    assert share == pytest.approx(100.0 * c["kv_window_pages_held"]
                                  / c["kv_window_pages_full"])
    assert 20.0 < share < 100.0
    # the roofline shares: nothing without a trace; with one, the work of
    # the slice by the slice's OWN counters over the ops' time
    m_moe = metric("cohere2_moe_expert_roofline")
    m_attn = metric("window_attn_hbm_share")
    m_chunk = metric("flash_attn_chunk_roofline")
    for m in (m_moe, m_attn, m_chunk):
        assert trace_op_counters.read(obs, m["args"]) is None
    fam = lookup.family(obs["cfg"])
    traced = dict(obs, trace={"window_s": 0.5, "ops": {
        "moe_grouped_matmul_gate_up.3": 0.002, "fusion.1": 1.0,
        "moe_grouped_matmul_down.4": 0.001,
        "paged_attention_decode.7": 0.003,
        "paged_attention_decode.9": 0.001,
        "flash_attention_fwd.2": 0.002, "flash_attention_fwd.5": 0.003}})
    in_slice = {"moe_pairs_held": 40.0, "moe_experts_touched": 12.0,
                "attn_decode_keys": 5000.0, "attn_chunk_pairs": 6.0e6}
    monkeypatch.setattr(trace_op_counters, "slice_counters",
                        lambda o: in_slice)
    need = fam.moe_held_experts(obs["cfg"], obs["cell"],
                                {"slice_counters": in_slice})
    assert need["flops"] == pytest.approx(2 * 40 * 3 * 64 * 32)
    assert need["bytes"] == pytest.approx(12 * 3 * 64 * 32 * 4)
    assert trace_op_counters.read(traced, m_moe["args"]) == pytest.approx(
        100 * max(need["flops"] / 197e12, need["bytes"] / 819e9) / 0.003)
    # K and V of 2 KV heads x 64 in float32: 1,024 B a key a layer
    assert trace_op_counters.read(traced, m_attn["args"]) == pytest.approx(
        100 * 5000 * 2 * 2 * 64 * 4 / 819e9 / 0.004)
    # the chunks' pairs: QK^T and PV over 8 heads x 64, by operations
    assert trace_op_counters.read(traced, m_chunk["args"]) == pytest.approx(
        100 * 6.0e6 * 4 * 8 * 64 / 197e12 / 0.005)
    # a program older than the counters writes no such span: nothing, and
    # no error; a slice without the attention counters likewise
    monkeypatch.setattr(trace_op_counters, "slice_counters", lambda o: None)
    assert trace_op_counters.read(traced, m_attn["args"]) is None
    monkeypatch.setattr(trace_op_counters, "slice_counters",
                        lambda o: {"moe_pairs_held": 1.0})
    assert trace_op_counters.read(traced, m_attn["args"]) is None
    assert trace_op_counters.read(traced, m_chunk["args"]) is None


def test_work_counts_at_the_published_widths():
    """ISSUE 35's arithmetic: 142.6 M of attention a layer, experts of
    50.33 M, 344.5 M non-routed a layer, 9.47 GB of weights, 4,096 B a
    key a layer."""
    cell, cfg = common.load_cell("serve-mixed-context")
    fam = lookup.family(cfg)
    assert fam.attention_params(cfg) == 142_606_336
    assert fam.expert_params(cfg) == 50_331_648
    assert fam.nonrouted_matmul_params(cfg) == 4 * 344_457_216
    assert fam.kv_bytes_per_key(cfg) == 4_096
    assert fam.weight_bytes(cfg) / 1e9 == pytest.approx(9.47, abs=0.01)
    assert fam.held_pairs_per_token(cfg) == 1.0
    assert fam.layer_windows(cfg) == [4096, 4096, 4096, None]
    # of the keys the cell's tokens see in a full layer, a window layer
    # sees the cycle's own share: every position of every request, by hand
    from benchmarks.harness.loadgen import draw_sizes
    p, o = draw_sizes(cell["traffic"], 48, 13_056)
    ctx = np.concatenate([np.arange(1, n + 1) for n in p + o - 1])
    clip = np.minimum(ctx, 4096).sum() / ctx.sum()
    assert fam.window_clip_share(cfg, cell) == pytest.approx(clip)
    assert 0.70 < clip < 0.82
    # a token at a mean of 5,170 keys: 2 x (non-routed + 1 pair x 4
    # layers + the head's slice) + attention over 5,170 x (1 + 3 x clip)
    got = fam.serve_flops_per_token(cfg, cell, {
        "mean_context_tokens": 5170.0, "head_tokens_per_processed": 1.0})
    want = 2 * (4 * 344_457_216 + 4 * 50_331_648 + 4096 * 32768) \
        + 4 * 5170 * (1 + 3 * clip) * 128 * 128
    assert got == pytest.approx(want)
    # a cell of prompts under the window clips nothing
    short = dict(cell, traffic=dict(cell["traffic"], prompt={
        "dist": "constant", "value": 1000}))
    assert fam.window_clip_share(cfg, short) == 1.0
    assert fam.serve_flops_per_token(cfg, short, {
        "mean_context_tokens": 1000.0, "head_tokens_per_processed": 0.0}) \
        == pytest.approx(2 * (4 * 344_457_216 + 4 * 50_331_648)
                         + 4 * 4000 * 128 * 128)
    assert fam.serve_flops_per_token(cfg, cell, {}) is None
    # a chunk's attention: 4 x head_dim operations a pair a head
    assert fam.window_chunk_attention(cfg, cell, {}) == {}
    assert fam.window_chunk_attention(cfg, cell, {"slice_counters": {
        "attn_chunk_pairs": 10.0}}) == {"flops": 10 * 4 * 128 * 128.0,
                                        "bytes": 0.0}
    assert fam.window_decode_kv(cfg, cell, {}) == {}
    assert fam.moe_held_experts(cfg, cell, {}) == {}
    assert fam.window_decode_kv(cfg, cell, {"slice_counters": {
        "attn_decode_keys": 10.0}}) == {"flops": 0.0, "bytes": 40_960.0}
    # the cell's pools: 24,576 pages and 48 x 258 + 128 + 1 windowed
    eng = dict(cfg["engine"], **cell["engine"])
    from paddle_tpu.serving.kv_cache import WindowGroup
    assert WindowGroup.pages_for(4096, 16, eng["max_batch_size"],
                                 eng["token_budget"]) == 12_513
    assert eng["pages_buckets"] == [816] and 816 * 16 == 13_056


def test_the_control_comes_out_not_correct_at_toy_width():
    """As tests/test_control.py for family llama: the plain reference from
    weights a precision below puts other tokens first and comes out over a
    limit; the tokens a correct program serves read 0."""
    import jax.numpy as jnp
    cfg = common.load_json(os.path.join(DATA, "configs", "toy-cohere.json"))
    fam = lookup.family(cfg)
    pcfg = fam.config(cfg)
    w = fam.reference_weights(pcfg, cfg, 3500000037)
    prompt = np.random.default_rng(31).integers(0, 256, 40).tolist()
    seq, pad = list(prompt), 256
    for _ in range(200):            # what a correct greedy program serves
        ids = jnp.asarray([seq + [0] * (pad - len(seq))], jnp.int32)
        seq.append(int(fam.logits(w, pcfg, ids)[0, len(seq) - 1].argmax()))
    assert len(set(seq[40:])) > 40              # no collapse
    records = []
    gaps, _ = control_gap.control_of(fam, ("bfloat16",), records)(
        w, pcfg, prompt, seq[len(prompt):], pad_to=pad)
    assert gaps.max() == 0.0
    control = control_gap.summary(records, "bfloat16")
    limits = fam.gap_limits(cfg)
    assert control["under_the_best"] >= 1
    # not correct: by one of the limits, as the contract asks
    assert control["mean"] > limits["mean"] \
        or control["widest"] > limits["widest"], (control, limits)
    # every expert's and every shared expert's matrices are leaves of
    # their own: the control lowers each a column at a time
    for name, shape in (
            ("model.layers.1.mlp.experts.9.down_proj.weight", (32, 64)),
            ("model.layers.3.mlp.shared_experts.2.gate_proj.weight",
             (64, 32))):
        low = np.asarray(control_gap.Lowered(w, "bfloat16")[name])
        assert low.shape == shape
        assert not np.array_equal(low, np.asarray(w[name]))


def test_the_control_as_a_run_comes_out_not_correct(capsys, monkeypatch):
    """`control_as_run.py`: the toy cell through `measure` with a
    control's tokens in the served ones' place is refused by the run's own
    comparison at the family's limits, by a gap limit and by nothing else;
    the family's `token_gaps` is its own again afterwards. (The int8
    control: the toy cell's sample is some forty tokens, too few for
    float32's own control, bfloat16, to put another token first.)"""
    from benchmarks import control_as_run
    monkeypatch.setattr(control_gap, "controls_for", lambda dtype: ("int8",))
    fam = lookup.family(common.load_json(os.path.join(
        DATA, "configs", "toy-cohere.json")))
    real = fam.token_gaps
    assert control_as_run.main(["--workload", "toy-cohere-closed", "--seed",
                                "3500000039", "--seconds", "1.5", "--cpu",
                                DATA]) == 0
    assert fam.token_gaps is real
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    over = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert not line["correct"] and over \
        and over <= {"logit_gap_mean", "logit_gap_widest"}


def test_the_configuration_file_against_the_catalog_and_the_manifest():
    man = common.load_json(os.path.join(common.REPO, "BENCHMARK.json"))
    entry = next(c for c in man["configs"]
                 if c["name"] == "command-a-plus-serve1")
    f = common.load_json(os.path.join(common.REPO, entry["file"]))
    assert f["source"] == entry["source"] and f["family"] == "cohere2_moe"
    assert sorted(f["reduced"]) == sorted(entry["reduced"]) \
        == ["experts_held", "num_hidden_layers", "vocab_size"]
    assert f["published"] == {"num_hidden_layers": 32, "vocab_size": 262144,
                              "experts_held": 128}
    assert (f["num_hidden_layers"], f["vocab_size"], f["experts_held"],
            f["expert_offset"]) == (4, 32768, 16, 0)
    # no width is cut, and the router keeps its width and its k
    assert (f["hidden_size"], f["intermediate_size"], f["head_dim"],
            f["num_attention_heads"], f["num_key_value_heads"],
            f["num_experts"], f["num_experts_per_tok"],
            f["num_shared_experts"], f["sliding_window"]) \
        == (4096, 4096, 128, 128, 8, 128, 8, 4, 4096)
    cell = next(w for w in man["workloads"]
                if w["name"] == "serve-mixed-context")
    assert cell == {"name": "serve-mixed-context",
                    "config": "command-a-plus-serve1",
                    "traffic": "mixed-context", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    t = common.load_json(os.path.join(
        common.BENCH_DIR, "workloads", "serve-mixed-context.json"))
    assert t["traffic_name"] == "mixed-context" \
        and t["driver"] == "closed_loop"
    assert (t["traffic"]["clients"], t["traffic"]["pool"],
            t["engine"]["max_batch_size"], t["engine"]["batch_buckets"],
            t["engine"]["pages_buckets"]) == (96, 48, 48, [48], [816])
    assert t["traffic"]["prompt"] == {"dist": "lognormal", "median": 4096,
                                      "sigma": 0.7, "min": 512, "max": 12288}
    assert t["traffic"]["output"] == {"dist": "lognormal", "median": 384,
                                      "sigma": 0.4, "min": 96, "max": 768}
    assert (t["traffic"]["warm_seconds"], t["traffic"]["trace_seconds"]) \
        == (30.0, 3.0)
    # the cell is on the list of both end-to-end metrics and of every
    # serve per-layer metric it reports; its own four name it alone
    lists = {m["name"]: m.get("workloads")
             for m in man["end_to_end"] + man["per_layer"]}
    for name in ("serve_tokens_per_s", "itl_p95_ms", "batch_occupancy",
                 "device_idle.serve", "serve_mfu", "decode_runahead_share",
                 "serve_idle_in_fetch", "serve_step_ms"):
        assert lists[name][-1] == "serve-mixed-context", name
    for name in ("window_attn_hbm_share", "cohere2_moe_expert_roofline",
                 "cohere2_moe_load_imbalance", "window_kv_held_share"):
        assert lists[name] == ["serve-mixed-context"], name
        assert metric(name)["workloads"] == ["serve-mixed-context"]
    for name in ("paged_attn_hbm_share", "moe_expert_roofline",
                 "mla_decode_hbm_share"):
        assert "serve-mixed-context" not in lists[name], name
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "command-a-plus-05-2026")
    assert f["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in f["reduced"]:
            assert f[key] != value and f["published"][key] == value
        else:                    # numbers, strings and nested groups alike
            assert f[key] == value, key
