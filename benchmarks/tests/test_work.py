"""The Llama family's work counts (families/llama.py) against counts made
by hand for Mistral-7B-v0.3's widths, and the shared table of peaks."""
import pytest

from benchmarks.families import llama
from benchmarks.harness import common, work

CELL = {"traffic": {"batch": 2, "seq": 2048}}


def cfg(name):
    return common.load_json(f"{common.BENCH_DIR}/configs/{name}.json")


def test_a_layer_is_218_1_million_matmul_parameters():
    c = cfg("mistral-7b-v0.3-train1")
    by_hand = (4096 * 4096 * 2            # q_proj, o_proj
               + 4096 * 1024 * 2          # k_proj, v_proj (8 heads x 128)
               + 4096 * 14336 * 3)        # gate, up, down
    assert by_hand == 218_103_808 == llama.layer_matmul_params(c)
    full = dict(c, num_hidden_layers=32)
    assert llama.total_params(full) == 32 * (by_hand + 2 * 4096) + 4096 \
        + 2 * 4096 * 32768
    assert round(llama.total_params(full) / 1e9, 2) == 7.25


def test_cell_1_step_is_14_4_tflop():
    c = cfg("mistral-7b-v0.3-train1")
    matmul = 2 * 218_103_808 + 4096 * 32768          # 2 layers + the head
    causal_attention = 6 * 2 * 2048 * 4096           # 6 * L * s * h a token
    assert llama.train_flops_per_token(c, CELL) == 6 * matmul + causal_attention
    assert llama.train_step_flops(c, CELL) / 1e12 == pytest.approx(14.43, abs=0.01)
    # the head's share of the matmul operations, as the config file says
    assert 4096 * 32768 / matmul == pytest.approx(0.235, abs=0.001)


def test_serving_state():
    c = cfg("mistral-7b-v0.3-serve1")
    assert llama.kv_bytes_per_token(c) == 2 * 16 * 8 * 128 * 2 == 65536
    assert llama.weight_bytes(c) / 1e9 == pytest.approx(7.52, abs=0.01)
    kv = llama.paged_decode_kv(c, {}, {"slice_decode_context_tokens": 1000})
    assert kv == {"flops": 0.0, "bytes": 65536000.0}
    assert llama.paged_decode_kv(c, {}, {}) == {}


def test_flash_attention_counts():
    c = cfg("mistral-7b-v0.3-train1")
    w = llama.flash_attention_train(c, CELL)
    assert w["flops"] == 2 * 2 * 32 * 7 * 2048 * 2048 * 128
    q, kv = 2 * 2048 * 32 * 128 * 2, 2 * 2048 * 8 * 128 * 2
    assert w["bytes"] == 2 * (6 * q + 6 * kv)


def test_peaks_raise_on_an_unknown_device():
    assert work.chip_peaks("TPU v5 lite") == {"flops": 197e12, "bytes": 819e9}
    with pytest.raises(ValueError):
        work.chip_peaks("cpu")


def test_a_served_token_is_2_flop_a_parameter_and_attention_over_its_keys():
    c = cfg("mistral-7b-v0.3-serve1")
    layers, head = 16 * 218_103_808, 4096 * 32768
    attention = 4 * 16 * 500 * 4096        # QK^T and PV over 500 keys
    # the head where a token comes out of it: here a quarter of the tokens
    v = {"mean_context_tokens": 500, "head_tokens_per_processed": 0.25}
    assert llama.serve_flops_per_token(c, {}, v) \
        == 2 * (layers + 0.25 * head) + attention
    assert llama.serve_flops_per_token(c, {}, {}) is None
    assert llama.serve_flops_per_token(
        c, {}, {"mean_context_tokens": 500}) is None
