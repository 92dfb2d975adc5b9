"""readers/trace_host_span.py: the statistics on a small hand-made table of
rows with a chip trace's shape (one device plane; the engine's step spans
nested on one thread), and the way from a trace on disk to a metric on a
trace taken here on the CPU backend (host spans only: no device plane)."""
import os

import pytest

from benchmarks.harness import observe
from benchmarks.readers import trace_host_span as ths

# Two engine steps inside a 10 s slice. The device runs [1,4) and [7,9.5):
# idle are [0,1), [4,7) and [9.5,10). The gap [4,7) crosses fetch's tail,
# emit, bookkeeping, the first step's own tail, the driver between the
# steps, and the second step's schedule and build_inputs.
ROWS = {
    "devices": {"/device:TPU:0": [
        ["paged_attention_decode.1 f32[8]", 1.0, 2.0], ["fusion.2", 3.0, 1.0],
        ["paged_attention_decode.1 f32[8]", 7.0, 2.5],
        ["fusion.2", 20.0, 1.0]]},                     # outside the slice
    "host": [
        ["bench.slice", 0.0, 10.0],
        ["bench.engine_step", 0.2, 5.2],
        ["serving.step#step=7#", 0.25, 5.05],
        ["serving.schedule", 0.3, 0.2],
        ["serving.build_inputs", 0.5, 0.3],
        ["serving.decode_step#bucket=[8, 8]#", 0.8, 0.4],
        ["serving.fetch", 1.2, 3.0],                   # until 4.2
        ["serving.emit", 4.2, 0.5],                    # until 4.7
        ["serving.bookkeeping", 4.7, 0.5],             # until 5.2
        ["bench.admit", 5.5, 0.1],
        ["bench.engine_step", 5.9, 4.0],
        ["serving.step#step=8#", 6.0, 3.8],
        ["serving.schedule", 6.0, 0.4],                # until 6.4
        ["serving.build_inputs", 6.4, 0.4],            # until 6.8
        ["serving.decode_step#bucket=[8, 8]#", 6.8, 0.4],
        ["serving.fetch", 7.2, 2.4],                   # until 9.6
        ["serving.bookkeeping", 9.6, 0.2],
        ["serving.step#step=9#", 9.9, 0.5],            # leaves the slice
    ],
}
LAUNCH = ["serving.prefill_chunk", "serving.decode_step"]


def stat(**args):
    return ths.stat(ths.summarize(ROWS), args)


def test_ms_per_a_step():
    s = ths.summarize(ROWS)
    assert s["window_s"] == pytest.approx(10.0)
    assert s["count"]["serving.step"] == 3
    # the third step is cut at the slice's end: 0.1 s of it is inside
    assert s["total"]["serving.step"] == pytest.approx(5.05 + 3.8 + 0.1)
    assert stat(stat="ms_per", span="serving.schedule",
                per="serving.step") == pytest.approx(1e3 * 0.6 / 3)
    assert stat(stat="ms_per", span=LAUNCH,
                per="serving.step") == pytest.approx(1e3 * 0.8 / 3)
    assert stat(stat="ms_per", span="serving.verify_step",
                per="serving.step") is None            # no such span
    assert stat(stat="ms_per", span="serving.schedule",
                per="to_static.call") is None          # nothing to count


def test_a_gap_that_crosses_spans_is_split_by_overlap():
    idle = lambda span: stat(stat="idle_pct", span=span)
    # [0,1): 0.25 outside, .05 step, .2 schedule, .3 build, .2 of the launch
    # [4,7): .2 fetch, .5 emit, .5 bookkeeping, .1 step, .7 outside,
    #        .4 schedule, .4 build_inputs, .2 launch
    # [9.5,10): .1 fetch, .2 bookkeeping, .1 outside, .1 of the third step
    assert idle("serving.fetch") == pytest.approx(10 * (0.2 + 0.1))
    assert idle("serving.emit") == pytest.approx(10 * 0.5)
    assert idle("serving.bookkeeping") == pytest.approx(10 * (0.5 + 0.2))
    assert idle("serving.schedule") == pytest.approx(10 * (0.2 + 0.4))
    assert idle("serving.build_inputs") == pytest.approx(10 * (0.3 + 0.4))
    assert idle(LAUNCH) == pytest.approx(10 * (0.2 + 0.2))
    assert idle("serving.step") == pytest.approx(10 * (0.05 + 0.1 + 0.1))
    assert idle(None) == pytest.approx(10 * (0.25 + 0.7 + 0.1))  # span: null
    assert idle("serving.verify_step") is None
    # every label together is the device's idle share, as trace_idle has it
    s = ths.summarize(ROWS)
    assert sum(s["idle"].values()) == pytest.approx(1.0 + 3.0 + 0.5)


def _two_chips():
    """ROWS with a second chip whose ops straddle both ends of the slice,
    overlap each other and leave a gap no host span covers."""
    rows = {"host": ROWS["host"], "devices": dict(ROWS["devices"])}
    rows["devices"]["/device:TPU:1"] = [
        ["fusion.9", -0.5, 1.0], ["fusion.9", 0.2, 0.6],     # [-.5,.8)
        ["paged_attention_decode.1 f32[8]", 5.3, 0.1],       # in the driver
        ["fusion.9", 9.8, 0.7]]                              # past the end
    return rows


@pytest.mark.parametrize("rows", [ROWS, _two_chips()],
                         ids=["one_chip", "two_chips"])
def test_the_labels_add_up_to_reduce_traces_idle(rows):
    """The reader takes the gaps itself (the harness gives a reader the
    reduced trace, not its rows): the idle seconds over ALL labels are
    `reduce()`'s `window_s - busy_s` on the same rows, so the seven
    `serve_idle_*` and what lies under `serving.step` itself add up to
    `device_idle.serve`."""
    from benchmarks.harness import reduce_trace
    s, r = ths.summarize(rows), reduce_trace.reduce(rows)
    assert s["window_s"] == pytest.approx(r["window_s"])
    assert sum(s["idle"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert sum(s["idle"].values()) == pytest.approx(
        sum(v for _, v in r["idle_gaps"]))


def test_nothing_to_read_gives_none():
    args = {"stat": "idle_pct", "span": None}
    bare = {"devices": ROWS["devices"],
            "host": [r for r in ROWS["host"] if r[0].startswith("bench.")]}
    assert ths.summarize(bare) == {}       # a program without the spans
    assert ths.stat({}, args) is None
    cell = {"name": "no-such-cell"}
    assert ths.read({"trace": None, "cell": cell}, args) is None   # no trace
    assert ths.read({"trace": {"busy_s": 1.0}, "cell": cell}, args) is None
    host_only = {"devices": {}, "host": ROWS["host"]}
    assert ths.stat(ths.summarize(host_only), args) is None
    assert ths.stat(ths.summarize(host_only),
                    {"stat": "ms_per", "span": "serving.fetch",
                     "per": "serving.step"}) == pytest.approx(1e3 * 5.4 / 3)
    with pytest.raises(ValueError):
        stat(stat="median", span="serving.fetch")


def test_from_a_trace_on_disk_to_a_metric(tmp_path, monkeypatch):
    """`observe.Tracer` writes the slice, the reader finds it by the cell's
    name and keeps the program's spans; metadata does not change a name."""
    import jax.numpy as jnp
    import paddle_tpu  # noqa: F401
    from paddle_tpu import profiler
    monkeypatch.setattr(ths, "REPO", str(tmp_path))
    tracer = observe.Tracer(
        os.path.join(str(tmp_path), ".bench_trace", "toy-cell"),
        observe.Spans())
    tracer.start()
    for i in range(3):
        with profiler.RecordEvent("to_static.call", fn="train_step"):
            with profiler.RecordEvent("to_static.guard"):
                jnp.ones(8).block_until_ready()
            with profiler.RecordEvent("to_static.dispatch", step=i):
                jnp.ones(8).block_until_ready()
    tracer.stop()
    obs = {"trace": {"busy_s": 1.0}, "cell": {"name": "toy-cell"}}
    s = ths.slice_summary(obs)
    assert s["count"] == {"to_static.call": 3, "to_static.guard": 3,
                          "to_static.dispatch": 3} and s["idle"] is None
    guard = ths.read(obs, {"stat": "ms_per", "span": "to_static.guard",
                           "per": "to_static.call"})
    both = ths.read(obs, {"stat": "ms_per", "per": "to_static.call", "span": [
        "to_static.guard", "to_static.dispatch"]})
    whole = ths.read(obs, {"stat": "ms_per", "span": "to_static.call",
                           "per": "to_static.call"})
    assert 0 < guard < both <= whole
    assert ths.read(obs, {"stat": "idle_pct", "span": None}) is None


KERNEL_SHARES = {"paged_attn_hbm_share": "paged_attention_decode",
                 "flash_attn_roofline": "flash_attention_bwd_dkv"}
MS_PER = ["serve_schedule_ms", "serve_dispatch_ms", "train_guard_ms",
          "train_state_sync_ms", "train_dispatch_ms"]
IDLE = ["serve_idle_in_schedule", "serve_idle_in_build_inputs",
        "serve_idle_in_dispatch", "serve_idle_in_fetch",
        "serve_idle_in_emit", "serve_idle_in_bookkeeping",
        "serve_idle_outside_step"]


def test_the_fourteen_metrics_of_issue_27():
    import re
    from benchmarks.harness import common
    man = common.load_json(os.path.join(common.REPO, "BENCHMARK.json"))
    listed = {m["name"]: m for m in man["per_layer"]}
    files = {m["name"]: m for m in observe.metric_files()}
    for name in list(KERNEL_SHARES) + MS_PER + IDLE:
        f, m = files[name], listed[name]
        train = m["moves"] == "train_tokens_per_s_chip"
        assert f["drivers"] == (["train_loop"] if train
                                else ["closed_loop", "open_loop"])
        assert m["workloads"] == (
            ["train-pretrain-2k"] if train
            else ["serve-offline-decode", "serve-chat-steady"])
    for name, kernel in KERNEL_SHARES.items():
        f = files[name]
        assert (f["reader"], f["unit"], f["better"], f["source"]) == (
            "trace_op_time", "%", "higher", "device_trace")
        # the pattern finds the kernel's `name=` as the compiled program
        # has it, through jit, jvp and transpose
        assert re.search(f["args"]["pattern"], f"transpose_jvp_{kernel}__.5")
    for name in MS_PER:
        f = files[name]
        assert (f["reader"], f["unit"], f["better"], f["source"]) == (
            "trace_host_span", "ms", "lower", "program_span")
        assert f["args"]["stat"] == "ms_per"
        assert f["args"]["per"] in ("serving.step", "to_static.call")
    for name in IDLE:
        f = files[name]
        assert (f["reader"], f["unit"], f["better"], f["source"]) == (
            "trace_host_span", "%", "lower", "device_trace")
        assert f["args"]["stat"] == "idle_pct"
    # the seven labels are disjoint, and one of them is "outside"
    spans = [files[n]["args"]["span"] for n in IDLE]
    flat = [x for s in spans if s for x in ([s] if isinstance(s, str) else s)]
    assert spans.count(None) == 1 and len(flat) == len(set(flat)) == 9
