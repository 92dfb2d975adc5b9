"""The control of the serving cells' comparison (control_gap.py) at a
size a test run can hold: the plain reference computed from weights in a
precision below the configuration's puts other tokens first, and the
numbers a run compares come out over their limits; the tokens a correct
program serves read 0."""
import os

import numpy as np

from benchmarks import control_gap
from benchmarks.harness import common, lookup

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_the_control_comes_out_not_correct_at_toy_width():
    import jax.numpy as jnp
    cfg = common.load_json(os.path.join(DATA, "configs", "toy-serve.json"))
    fam = lookup.family(cfg)
    pcfg = fam.config(cfg)
    w = fam.reference_weights(pcfg, cfg, 2900000029)
    prompt = np.random.default_rng(29).integers(0, 512, 48).tolist()
    seq, pad = list(prompt), 256
    for _ in range(200):          # what a correct greedy program serves
        ids = jnp.asarray([seq + [0] * (pad - len(seq))], jnp.int32)
        seq.append(int(fam.logits(w, pcfg, ids)[0, len(seq) - 1].argmax()))
    assert len(set(seq[48:])) > 40        # no collapse (vocabulary 512)
    kinds = control_gap.controls_for(cfg["dtype"])
    assert kinds == ("bfloat16",)                 # the toy is float32
    assert control_gap.controls_for("bfloat16") == ("int8",)
    records = []
    gaps, _ = control_gap.control_of(fam, kinds, records)(
        w, pcfg, prompt, seq[len(prompt):], pad_to=pad)
    assert gaps.max() == 0.0
    program = control_gap.summary(records, "program")
    control = control_gap.summary(records, "bfloat16")
    limits = fam.gap_limits(cfg)
    assert program["mean"] == program["widest"] == 0.0
    assert control["under_the_best"] >= 1
    assert control["mean"] > 3 * limits["mean"], (control, limits)
    assert control["widest"] > 3 * limits["widest"], (control, limits)
    # each lowering moves the weights, the coarser the further
    err = {k: float(np.abs(np.asarray(control_gap.Lowered(w, k)[
        "lm_head.weight"]) - np.asarray(w["lm_head.weight"])).mean())
        for k in ("bfloat16", "int8")}
    assert 0 < err["bfloat16"] < err["int8"]
