"""Publish the SOT-gap inventory (VERDICT r5 #5): run every ladder-model
train step through jit.to_static and commit what fell back to eager and
why (FALLBACKS.md).

`jit.to_static_report()` already collects the data (function-level eager
fallbacks with the breaking error + dy2static's per-reason counters);
this script drives the five BASELINE ladder families through two
compiled steps each — CPU-sized configs, the same model classes the
chip ladder trains — and renders the per-model inventory. An empty
fallback list for a model is the claim "this train step runs as ONE
compiled program"; a populated one is the measured cost of not having a
bytecode tracer, which is exactly the evidence the
build-jit/sot-or-not decision needs (to_static_report docstring).

Usage: JAX_PLATFORMS=cpu \
           python tools/fallback_report.py [--out FALLBACKS.md]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import jit  # noqa: E402

rng = np.random.RandomState(0)
REPORTS = {}


def run_step(name, model, make_batch, loss_fn, steps=2):
    jit.to_static_report(reset=True)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    def step(*batch):
        loss = loss_fn(model, *batch)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = paddle.jit.to_static(step, state_objects=[model, opt])
    t0 = time.perf_counter()
    losses = []
    for _ in range(steps):
        losses.append(float(np.asarray(compiled(*make_batch())._data)))
    dt = time.perf_counter() - t0
    assert all(np.isfinite(l) for l in losses), (name, losses)
    rep = jit.to_static_report(reset=True)
    REPORTS[name] = {"report": rep, "losses": losses, "seconds": dt}
    print(f"{name}: losses {losses} ({dt:.1f}s) "
          f"fallbacks={len(rep['eager_fallbacks'])} "
          f"breaks={rep['break_counters']}", flush=True)


def build_all():
    ce = paddle.nn.CrossEntropyLoss()

    # ladder 1: ResNet-50
    from paddle_tpu.vision.models import resnet50
    m = resnet50(num_classes=10)
    run_step(
        "resnet50", m,
        lambda: (paddle.to_tensor(rng.randn(2, 3, 32, 32).astype(np.float32)),
                 paddle.to_tensor(rng.randint(0, 10, (2,)))),
        lambda mm, x, y: ce(mm(x), y))

    # ladder 2: ERNIE masked-LM
    from paddle_tpu.models.ernie import ernie_tiny, ErnieForMaskedLM
    ecfg = ernie_tiny()
    em = ErnieForMaskedLM(ecfg)
    EV = ecfg.vocab_size

    def ernie_loss(mm, ids, labels):
        out = mm(ids)
        logits = out[0] if isinstance(out, (tuple, list)) else out
        return ce(logits.reshape([-1, logits.shape[-1]]),
                  labels.reshape([-1]))

    run_step(
        "ernie_mlm", em,
        lambda: (paddle.to_tensor(rng.randint(1, EV, (2, 32))),
                 paddle.to_tensor(rng.randint(1, EV, (2, 32)))),
        ernie_loss)

    # ladder 3: Llama causal LM (the flagship bench family)
    from paddle_tpu.models.llama import llama_tiny, LlamaForCausalLM
    lm = LlamaForCausalLM(llama_tiny())

    def lm_loss(mm, ids, labels):
        return mm(ids, labels=labels)

    LV = lm.cfg.vocab_size
    run_step(
        "llama", lm,
        lambda: (paddle.to_tensor(rng.randint(0, LV, (2, 32))),
                 paddle.to_tensor(rng.randint(0, LV, (2, 32)))),
        lm_loss)

    # ladder 4: DiT (conv+attn mixed)
    from paddle_tpu.models.dit import DiT, dit_tiny
    dcfg = dit_tiny()
    dm = DiT(dcfg)

    def dit_loss(mm, x, t, y):
        out = mm(x, t, y)
        return ((out.astype("float32") - x.astype("float32")) ** 2).mean()

    run_step(
        "dit", dm,
        lambda: (paddle.to_tensor(
            rng.randn(2, dcfg.in_channels, dcfg.image_size,
                      dcfg.image_size).astype(np.float32)),
                 paddle.to_tensor(rng.randint(0, 1000, (2,))),
                 paddle.to_tensor(rng.randint(0, dcfg.num_classes, (2,)))),
        dit_loss)

    # ladder 5: Qwen2-MoE (expert routing + aux loss)
    from paddle_tpu.models.qwen2_moe import qwen2_moe_tiny, Qwen2MoeForCausalLM
    qcfg = qwen2_moe_tiny()
    qm = Qwen2MoeForCausalLM(qcfg)
    QV = qcfg.vocab_size

    def moe_loss(mm, ids, labels):
        out = mm(ids, labels=labels)
        return out[0] if isinstance(out, (tuple, list)) else out

    run_step(
        "qwen2_moe", qm,
        lambda: (paddle.to_tensor(rng.randint(0, QV, (2, 32))),
                 paddle.to_tensor(rng.randint(0, QV, (2, 32)))),
        moe_loss)


def _lint_section():
    """FALLBACKS.md section for the dy2static purity diagnostics
    (tpu-lint rule A5, shared Diagnostic type from paddle_tpu.analysis):
    scan/while-lowered bodies that printed at trace time, loops kept
    eager because their bodies mutate non-carried python state, and
    out-of-trace collective rejections — recorded at runtime while the
    ladder steps above compiled, reported next to the eager-fallback
    counts they explain. See ANALYSIS.md for the rule catalog."""
    lines = ["", "## dy2static purity diagnostics (tpu-lint A5, `--lint`)",
             "",
             "Runtime promotions of the purity checks: recorded while "
             "the ladder train steps compiled (shared `Diagnostic` type "
             "with `tools/tpu_lint.py`; catalog in ANALYSIS.md).", ""]
    any_diag = False
    for name, d in REPORTS.items():
        diags = d["report"].get("purity_diagnostics", [])
        if not diags:
            continue
        any_diag = True
        lines.append(f"### {name}")
        for dg in diags:
            lines.append(
                f"- `{dg['rule']}[{dg['slug']}]` {dg['path']}:{dg['line']} "
                f"— {dg['message']}")
        lines.append("")
    if not any_diag:
        lines.append("No purity diagnostics: every compiled ladder step "
                     "ran without trace-time side effects, eager-kept "
                     "mutating loops, or out-of-trace collectives.")
    return lines


def write_md(path, lint=False):
    lines = [
        "# FALLBACKS.md — the eager-fallback inventory "
        "(jit.to_static_report)", "",
        "Two compiled train steps per BASELINE ladder model on the "
        "8-virtual-CPU test platform; for each, every function-level "
        "eager fallback `to_static` recorded (with the error that broke "
        "it) plus dy2static's per-reason break/decline counters. "
        "Regenerate with `tools/fallback_report.py` (VERDICT r5 #5).", "",
        "An empty row = the whole step (fwd+bwd+AdamW) ran as one "
        "compiled program. `break_counters` counts CONVERSION decisions "
        "(e.g. a scan decline that still compiled via while_loop or "
        "unrolling is a counter, not a fallback).", "",
        "| ladder model | step losses | eager fallbacks | break counters |",
        "|---|---|---|---|",
    ]
    detail = []
    for name, d in REPORTS.items():
        rep = d["report"]
        fbs = rep["eager_fallbacks"]
        losses = ", ".join(f"{l:.4f}" for l in d["losses"])
        bc = ", ".join(f"{k}={v}" for k, v in
                       sorted(rep["break_counters"].items())) or "—"
        lines.append(f"| {name} | {losses} | {len(fbs)} | {bc} |")
        if fbs:
            detail.append(f"## {name}")
            for fb in fbs:
                detail.append(f"- `{fb.get('function', '?')}`: "
                              f"{fb.get('reason', fb)}")
            detail.append("")
    if detail:
        lines += ["", "## Per-function fallback reasons", ""] + detail
    else:
        lines += ["", "No ladder-model train step produced a "
                  "function-level eager fallback: the five families "
                  "compile end-to-end. The break counters above are the "
                  "only dy2static activity (conversions that still "
                  "landed in a compiled form)."]
    if lint:
        lines += _lint_section()
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "FALLBACKS.md"))
    ap.add_argument("--lint", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="include the dy2static purity-diagnostic section "
                         "(tpu-lint A5 runtime promotions; on by default "
                         "so a plain regeneration keeps the committed "
                         "FALLBACKS.md section — --no-lint to drop it)")
    args = ap.parse_args()
    build_all()
    write_md(args.out, lint=args.lint)
