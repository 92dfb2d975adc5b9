"""chip_smoke.py rehearsed on the CPU: its phases at toy width, in-process.

The script itself refuses any platform but tpu, so what can be held here
is everything but the device: the phases' control flow and their checks
(the kernel-presence checks are the ones that must FAIL off the chip —
interpret-mode kernels leave no custom call — and each test says so),
the last-line format, the refusal without a chip, the peak table's
unknown-device error and the compile-cache placement.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:          # chip_smoke.py lives at the repo root
    sys.path.insert(0, REPO)

import chip_smoke
from paddle_tpu.models.llama import LlamaConfig

# what can only hold on the chip, per phase
CHIP_ONLY = {"train": {"flash_kernel_in_step"},
             "serve": {"paged_kernel_in_decode"},
             "hybrid_train": {"flash_kernel_in_step"},
             "tp_serve": {"paged_kernel_in_tp_decode"}}


def _toy(**kw):
    cfg = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               num_hidden_layers=2, num_attention_heads=2,
               num_key_value_heads=1, max_position_embeddings=1024)
    cfg.update(kw)
    return LlamaConfig(**cfg)


def _assert_phase(result):
    """Every check passes except the chip-only ones, which must not."""
    chip_only = CHIP_ONLY[result["name"]]
    for name, ok in result["checks"].items():
        assert ok == (name not in chip_only), (name, result["checks"])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_train_phase_toy(dtype):
    res = chip_smoke.train_phase(
        _toy(num_attention_heads=4, num_key_value_heads=4),
        batch=2, seq=128, steps=4, dtype=dtype)
    _assert_phase(res)
    assert len(res["losses"]) == 4 and set(res["refs"]) == {1, 4}


def test_serve_phase_toy():
    res = chip_smoke.serve_phase(
        _toy(), prompt_lens=[12, 40, 600, 80, 90], shared_prefix=64,
        max_new_tokens=16, num_pages=128, dtype="float32")
    _assert_phase(res)
    assert [len(t) for t in res["tokens"]] == [16] * 5


def test_cross_chip_phases_toy():
    """The --chips 4 path on virtual CPU devices (all 8 for the hybrid
    mesh fleet.init builds, the first 4 for the TP engine)."""
    cfg = _toy(hidden_size=256, num_attention_heads=4,
               num_key_value_heads=4)
    _assert_phase(chip_smoke.hybrid_train_phase(
        cfg, dp=4, mp=2, batch=4, seq=128, dtype="float32"))
    _assert_phase(chip_smoke.tp_serve_phase(
        cfg, tp=4, prompt_lens=[12, 90, 100], shared_prefix=64,
        max_new_tokens=8, num_pages=64, dtype="float32"))


def test_reference_loss_catches_a_wrong_model():
    """The script's own float32 forward is a real reference: it agrees
    with the package's loss on the same weights, and stops agreeing when
    one weight is disturbed."""
    import paddle_tpu as paddle
    cfg = _toy()
    paddle.seed(3)
    model = chip_smoke.build_model(cfg, "float32")
    ids = np.random.RandomState(3).randint(0, cfg.vocab_size, (2, 64))
    with paddle.no_grad():
        loss = float(model(paddle.to_tensor(ids),
                           labels=paddle.to_tensor(ids)))
    w = {k: t._data for k, t in model.state_dict().items()}
    ref = float(chip_smoke.reference_loss(w, cfg, ids, ids))
    tol = chip_smoke.loss_tolerance("float32")
    assert abs(loss - ref) / ref <= tol
    w["model.layers.1.mlp.down_proj.weight"] = \
        w["model.layers.1.mlp.down_proj.weight"] * 3.0
    bad = float(chip_smoke.reference_loss(w, cfg, ids, ids))
    assert abs(loss - bad) / ref > tol


def test_token_explanation_accepts_ties_only():
    """explain_tokens: the dense greedy continuation explains itself; a
    token that is not a near-tie of the dense argmax does not."""
    import paddle_tpu as paddle
    cfg = _toy()
    paddle.seed(4)
    model = chip_smoke.build_model(cfg, "float32")
    prompt = np.random.RandomState(4).randint(0, cfg.vocab_size, 16).tolist()
    out = model.generate(paddle.to_tensor(np.asarray([prompt])),
                         max_new_tokens=8, use_jit=True)
    tokens = np.asarray(out._data)[0, len(prompt):].tolist()
    assert chip_smoke.explain_tokens(model, prompt, tokens, "float32", "t")
    rows, _ = chip_smoke.greedy_gaps(model, prompt, tokens)
    assert rows == []
    wrong = tokens[:-1] + [(tokens[-1] + 1) % cfg.vocab_size]
    assert not chip_smoke.explain_tokens(model, prompt, wrong, "float32", "t")
    (row,), _ = chip_smoke.greedy_gaps(model, prompt, wrong)
    assert row[0] == 7 and row[2] == tokens[-1] and row[3] > 0


def test_last_line_is_the_contracts_and_any_failed_check_fails(capsys):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    good = {"name": "train", "checks": {"a": True, "b": True}}
    bad = {"name": "serve", "checks": {"a": True, "b": False}}
    ok, line = chip_smoke.report(device, [good])
    assert ok and json.loads(line) == {"ok": True, "device": device}
    assert line == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
    ok, line = chip_smoke.report(device, [good, bad])
    assert not ok and json.loads(line)["ok"] is False
    assert "phase serve: FAIL" in capsys.readouterr().out


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_without_a_chip(argv):
    """On a machine with no TPU the script exits non-zero and prints no
    result — nothing runs on the CPU under the device's name."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode not in (0, 2), proc.stderr[-500:]   # 2 = usage
    assert proc.stdout.strip() == ""
    assert "nothing was run" in proc.stderr


def test_main_takes_no_option_but_chips():
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(["--layers", "1"])
    assert e.value.code == 2


def test_unknown_device_kind_is_an_error():
    from paddle_tpu.profiler.cost import chip_peaks
    assert chip_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks(jax.devices()[0].device_kind)       # "cpu": no such row


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code."""
    from paddle_tpu.utils import compile_cache_dir as ccd
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert ccd.place_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    """Unset: <checkout>/.jax_cache — a fixed path, no tempfile, pid or
    clock in it (the path is part of every entry's key)."""
    from paddle_tpu.utils import compile_cache_dir as ccd
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert ccd.place_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
