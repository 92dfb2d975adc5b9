"""The second family (`families/kimi_k2.py`, `references/kimi_k2.py`) and
its cell: the toy cell through run_cell.py's own dispatch on the CPU, the
family's work counts at the published widths, its metrics' readers, the
control at toy width, and the configuration file against the catalog."""
import json
import os
import time

import numpy as np
import pytest

from benchmarks import control_gap
from benchmarks.harness import common, lookup, observe
from benchmarks.readers import ratio, trace_op_counters, trace_op_time

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def toy_run():
    from benchmarks import run_cell as command
    cell, cfg = common.load_cell("toy-kimi-closed", DATA)
    line, obs = command.measure(cell, cfg, common.CPU_AS, seed=3100000033,
                                seconds=1.5, trace=0, t_start=time.time())
    return line, obs


def metric(name):
    return common.load_json(os.path.join(common.BENCH_DIR, "metrics",
                                         f"{name}.json"))


def test_the_toy_cell_through_the_commands_own_dispatch(toy_run):
    line, obs = toy_run
    assert lookup.family(obs["cfg"]).__name__ == "benchmarks.families.kimi_k2"
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert line["checks"]["logit_gap_widest"]["value"] \
        <= line["checks"]["logit_gap_widest"]["limit"]
    c = obs["counters"]["window"]["serving"]
    # 8 of 32 experts held, 4 a token: about a quarter of the pairs
    assert 0.1 < c["moe_pairs_held"] / c["moe_pairs_routed"] < 0.45
    assert c["moe_pairs_routed"] == 4 * (c["prefill_tokens"]
                                         + c["decode_tokens"]) * 2
    got = observe.read_metrics(obs)
    assert {"serve_step_ms", "batch_occupancy", "serve_mfu"} <= set(got)
    # the family brings no count of K and V pages: that metric is left out
    assert lookup.work(obs["cfg"], "paged_decode_kv") is None
    assert lookup.work(obs["cfg"], "mla_decode_latent") is not None


def test_the_new_metrics_readers_on_the_toy_run(toy_run, monkeypatch):
    _, obs = toy_run
    c = obs["counters"]["window"]["serving"]
    args = dict(metric("moe_load_imbalance")["args"], scale=8.0)  # 8 held
    got = ratio.read(obs, args)
    assert got == pytest.approx(8.0 * c["moe_max_expert_pairs"]
                                / c["moe_pairs_held"])
    assert 1.0 <= got <= 8.0
    # the roofline shares: nothing without a trace; with one, the work of
    # the slice by the slice's OWN counters (the launches' spans in the
    # trace) over the ops' time
    m = metric("moe_expert_roofline")
    assert trace_op_counters.read(obs, m["args"]) is None
    fam = lookup.family(obs["cfg"])
    w = obs["values"]["window_s"]
    traced = dict(obs, trace={"window_s": w / 4, "ops": {
        "moe_grouped_matmul_gate_up.3": 0.002, "fusion.1": 1.0,
        "moe_grouped_matmul_down.4": 0.001, "mla_paged_decode.7": 0.004}})
    in_slice = {"moe_pairs_held": 40.0, "moe_experts_touched": 12.0}
    monkeypatch.setattr(trace_op_counters, "slice_counters",
                        lambda o: in_slice)
    need = fam.moe_held_experts(obs["cfg"], obs["cell"],
                                {"slice_counters": in_slice})
    assert need["flops"] == pytest.approx(2 * 40 * 3 * 64 * 32)
    assert need["bytes"] == pytest.approx(12 * 3 * 64 * 32 * 4)
    share = trace_op_counters.read(traced, m["args"])
    assert share == pytest.approx(100 * max(
        need["flops"] / 197e12, need["bytes"] / 819e9) / 0.003)
    # a program older than the counters writes no such span: nothing, and
    # no error
    monkeypatch.setattr(trace_op_counters, "slice_counters", lambda o: None)
    assert trace_op_counters.read(traced, m["args"]) is None
    traced["values"] = dict(obs["values"], slice_decode_context_tokens=1000)
    got = trace_op_time.read(traced, metric("mla_decode_hbm_share")["args"])
    assert got == pytest.approx(
        100 * 1000 * 3 * (128 + 16) * 4 / 819e9 / 0.004)


def test_the_slices_own_counters_from_a_trace_on_disk(tmp_path, monkeypatch):
    """`observe.Tracer` writes the slice; the reader sums the metadata of
    the `serving.model_counters` spans that start inside `bench.slice`
    and of no other; a trace without one reads None."""
    import jax.numpy as jnp
    import paddle_tpu  # noqa: F401
    from paddle_tpu import profiler
    monkeypatch.setattr(trace_op_counters, "REPO", str(tmp_path))
    obs = {"trace": {"busy_s": 1.0}, "cell": {"name": "toy-cell"}}

    def traced(with_counters):
        trace_op_counters._SUMS.clear()
        tracer = observe.Tracer(
            os.path.join(str(tmp_path), ".bench_trace", "toy-cell"),
            observe.Spans())
        tracer.warm()               # a span outside the slice's trace
        tracer.start()
        for i in range(3):
            with profiler.RecordEvent("serving.fetch"):
                jnp.ones(8).block_until_ready()
                if with_counters:
                    with profiler.RecordEvent(
                            "serving.model_counters", moe_pairs_held=10 + i,
                            moe_experts_touched=3):
                        pass
        tracer.stop()
        return trace_op_counters.slice_counters(obs)

    assert traced(True) == {"moe_pairs_held": 33.0,
                            "moe_experts_touched": 9.0}
    assert traced(False) is None


def test_work_counts_at_the_published_widths():
    cell, cfg = common.load_cell("serve-reasoning-decode")
    fam = lookup.family(cfg)
    assert fam.attention_params(cfg) == 101_122_048          # ISSUE 31
    assert fam.expert_params(cfg) == 44_040_192
    assert fam.latent_bytes_per_token(cfg) == 8_064
    assert fam.weight_bytes(cfg) / 1e9 == pytest.approx(9.70, abs=0.02)
    assert fam.held_pairs_per_token(cfg) == 0.25
    # a decoded token at 2,500 keys: 2 x (non-routed + 0.25 pairs x 6
    # layers + the head's slice) + attention
    got = fam.serve_flops_per_token(cfg, cell, {
        "mean_context_tokens": 2500.0, "head_tokens_per_processed": 1.0})
    non_routed = 7 * 101_122_048 + 3 * 7168 * 18432 \
        + 6 * (44_040_192 + 7168 * 384)
    want = 2 * (non_routed + 6 * 0.25 * 44_040_192 + 7168 * 20480) \
        + 2 * 7 * 2500 * 64 * (192 + 128)
    assert got == pytest.approx(want)
    assert fam.serve_flops_per_token(cfg, cell, {}) is None
    assert fam.mla_decode_latent(cfg, cell, {}) == {}
    assert fam.moe_held_experts(cfg, cell, {}) == {}


def test_the_control_comes_out_not_correct_at_toy_width():
    """As tests/test_control.py for family llama: the plain reference from
    weights a precision below puts other tokens first and comes out over a
    limit; the tokens a correct program serves read 0."""
    import jax.numpy as jnp
    cfg = common.load_json(os.path.join(DATA, "configs", "toy-kimi.json"))
    fam = lookup.family(cfg)
    pcfg = fam.config(cfg)
    w = fam.reference_weights(pcfg, cfg, 3100000037)
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
    # every expert's matrices are leaves of their own: the control lowers
    # each a column at a time
    name = "model.layers.1.mlp.experts.9.down_proj.weight"
    assert np.asarray(control_gap.Lowered(w, "bfloat16")[name]).shape \
        == (32, 64)
    assert not np.array_equal(
        np.asarray(control_gap.Lowered(w, "bfloat16")[name]),
        np.asarray(w[name]))


def test_the_configuration_file_against_the_catalog_and_the_manifest():
    man = common.load_json(os.path.join(common.REPO, "BENCHMARK.json"))
    entry = next(c for c in man["configs"] if c["name"] == "kimi-k2.5-serve1")
    f = common.load_json(os.path.join(common.REPO, entry["file"]))
    assert f["source"] == entry["source"] and f["family"] == "kimi_k2"
    assert sorted(f["reduced"]) == sorted(entry["reduced"]) \
        == ["experts_held", "num_hidden_layers", "vocab_size"]
    assert f["published"] == {"num_hidden_layers": 61, "vocab_size": 163840,
                              "experts_held": 384}
    assert (f["num_hidden_layers"], f["vocab_size"], f["experts_held"],
            f["expert_offset"]) == (7, 20480, 12, 0)
    # no width is cut, and the router keeps its width and its k
    assert (f["hidden_size"], f["intermediate_size"],
            f["moe_intermediate_size"], f["q_lora_rank"], f["kv_lora_rank"],
            f["qk_nope_head_dim"], f["qk_rope_head_dim"], f["v_head_dim"],
            f["num_attention_heads"], f["n_routed_experts"],
            f["num_experts_per_tok"]) \
        == (7168, 18432, 2048, 1536, 512, 128, 64, 128, 64, 384, 8)
    cell = next(w for w in man["workloads"]
                if w["name"] == "serve-reasoning-decode")
    assert cell["config"] == "kimi-k2.5-serve1" and cell["chips"] == 1
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Kimi-K2.5")
    assert f["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if f.get(k) != v)
    assert differ == ["num_hidden_layers", "vocab_size"]
