"""Measured pipeline-schedule comparison: 1F1B vs ZB-H1 wall-clock.

VERDICT r3 item 3: "measure ZB-H1 for real and close the makespan loop".
Runs the ThreadedFleetExecutor (per-rank threads, jitted stage jobs, each
stage's params pinned to its own virtual CPU device so compute genuinely
overlaps) at pp∈{2,4} × micro∈{4,8} under both schedules, and reports:

  - measured wall-clock makespan (first job start -> last job end)
  - the dependency-simulator makespan fed with the MEASURED mean job
    durations (so the model and the wall clock are directly comparable)
  - the unit-time simulator's predicted bubble reduction

Usage:
  JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tools/bench_pipeline.py [--write-md]
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def build_stage_jobs(n_stages, hidden=512, layers_per_stage=3, batch=64,
                     seed=0, device_of=None):
    """Per-stage MLP jobs with a HAND-SPLIT backward, the way the
    reference ZB pass splits each matmul_grad into independent dx / dw
    ops sharing saved inputs (pipeline_zero_bubble.py) — no forward
    recompute in either half, so 1F1B and ZB-H1 run identical total
    FLOPs and the measured difference is pure scheduling.

      forward: saves (layer input, layer output) residuals
      B (dx):  per layer g_z = g * (1 - out^2); g = g_z @ W.T  — saves g_z
      W (dw):  per layer dW = x_in.T @ g_z                     — deferred

    Each stage's params are committed to its own virtual CPU device so
    rank threads genuinely overlap."""
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    rng = np.random.RandomState(seed)

    def stage_fn(params, x):
        for W in params:
            x = jnp.tanh(x @ W)
        return x

    def fwd_resid(params, x):
        resid = []
        for W in params:
            out = jnp.tanh(x @ W)
            resid.append((x, out))
            x = out
        return x, resid

    def bwd_dx(params, resid, g):
        gzs = []
        for W, (xin, out) in zip(reversed(params), reversed(resid)):
            gz = g * (1.0 - out * out)
            gzs.append(gz)
            g = gz @ W.T
        return g, gzs[::-1]

    def bwd_dw(resid, gzs):
        return [xin.T @ gz for (xin, _), gz in zip(resid, gzs)]

    def bwd_full(params, resid, g):
        gx, gzs = bwd_dx(params, resid, g)
        return gx, bwd_dw(resid, gzs)

    # device_of maps stage index -> device slot (ZB-V pins both of a
    # rank's chunks to that rank's device); default = stage index
    dev_of = device_of or (lambda s: s)
    stage_params = []
    for r in range(n_stages):
        Ws = [jnp.asarray(rng.randn(hidden, hidden).astype(np.float32)
                          * (1.0 / np.sqrt(hidden)))
              for _ in range(layers_per_stage)]
        stage_params.append(jax.device_put(Ws, devs[dev_of(r) % len(devs)]))

    def loss_fn(pred, label):
        return ((pred - label) ** 2).mean()

    fwd_jit = jax.jit(fwd_resid)
    dx_jit = jax.jit(bwd_dx)
    dw_jit = jax.jit(bwd_dw)
    full_jit = jax.jit(bwd_full)

    def loss_grad(y, label):
        loss, pull = jax.vjp(lambda yy: loss_fn(yy, label), y)
        (g,) = pull(jnp.ones_like(loss))
        return loss, g
    loss_grad_jit = jax.jit(loss_grad)

    state = {"resid": {}, "gzs": {}, "preds": {},
             "grads": [None] * n_stages, "losses": []}

    def to_dev(v, r):
        return jax.device_put(v, devs[dev_of(r) % len(devs)])

    def fwd(r, m, x):
        out, resid = fwd_jit(stage_params[r], to_dev(x, r))
        state["resid"][(m, r)] = resid
        if r == n_stages - 1:
            state["preds"][m] = out
        out.block_until_ready()
        return out

    def _accum(r, dW):
        g = state["grads"][r]
        state["grads"][r] = dW if g is None else \
            [a + b for a, b in zip(g, dW)]

    def _incoming_cot(r, m, g_or_label):
        if r == n_stages - 1:
            loss, g = loss_grad_jit(state["preds"][m],
                                    to_dev(g_or_label, r))
            state["losses"].append(loss)
            return g
        return to_dev(g_or_label, r)

    def bwd_b_split(r, m, g_or_label):
        g = _incoming_cot(r, m, g_or_label)
        gx, gzs = dx_jit(stage_params[r], state["resid"][(m, r)], g)
        state["gzs"][(m, r)] = gzs
        gx.block_until_ready()
        return gx

    def bwd_w(r, m):
        dW = dw_jit(state["resid"][(m, r)], state["gzs"][(m, r)])
        jax.block_until_ready(dW)
        _accum(r, dW)
        del state["resid"][(m, r)], state["gzs"][(m, r)]

    def bwd_fused(r, m, g_or_label):
        g = _incoming_cot(r, m, g_or_label)
        gx, dW = full_jit(stage_params[r], state["resid"][(m, r)], g)
        gx.block_until_ready()
        _accum(r, dW)
        del state["resid"][(m, r)]
        return gx

    def reset():
        """Clear per-run state so jitted jobs (and their compile caches)
        are reused across repeats — only the first run pays compilation."""
        state["resid"].clear()
        state["gzs"].clear()
        state["preds"].clear()
        state["losses"].clear()
        state["grads"] = [None] * n_stages

    return dict(stage_fn=stage_fn, stage_params=stage_params,
                loss_fn=loss_fn, fwd=fwd, bwd_b_split=bwd_b_split,
                bwd_w=bwd_w, bwd_fused=bwd_fused, state=state,
                reset=reset, hidden=hidden, batch=batch)


def measure(n_stages, n_micro, hidden=1024, layers_per_stage=2, batch=128,
            repeats=2):
    """Wall-clock both schedules; returns a row dict."""
    from paddle_tpu.distributed.fleet_executor import (
        ThreadedFleetExecutor, simulate_pipeline_makespan)

    rng = np.random.RandomState(1)
    xs = [rng.randn(batch, hidden).astype(np.float32)
          for _ in range(n_micro)]
    ys = [rng.randn(batch, hidden).astype(np.float32)
          for _ in range(n_micro)]

    repeats = max(repeats, 1)   # iteration 0 is always jit warmup
    row = {"pp": n_stages, "micro": n_micro}
    for sched, label in (("1F1B", "1f1b"), ("ZB-H1", "zb")):
        best_wall, durs = None, None
        jobs = build_stage_jobs(n_stages, hidden, layers_per_stage, batch)
        for it in range(repeats + 1):
            jobs["reset"]()  # jits persist: only iteration 0 compiles
            if sched in ("ZB-H1",):
                ex = ThreadedFleetExecutor(
                    n_stages, n_micro, sched, jobs["fwd"],
                    jobs["bwd_b_split"], jobs["bwd_w"])
            else:
                ex = ThreadedFleetExecutor(
                    n_stages, n_micro, sched, jobs["fwd"],
                    jobs["bwd_fused"])
            wall = ex.run(xs, ys)
            if it > 0 and (best_wall is None or wall < best_wall):
                best_wall, durs = wall, ex.measured_durations()
        row[f"wall_{label}_ms"] = best_wall * 1e3
        row[f"durs_{label}"] = {k: v * 1e3 for k, v in durs.items()}
        t_f = durs.get("F", 1.0)
        t_b = durs.get("B", 1.0)
        t_w = durs.get("W", max(t_b * 0.5, 1e-9)) if sched == "ZB-H1" \
            else t_b * 0.5  # fused B includes W work; split it nominally
        if sched == "ZB-H1":
            sim = simulate_pipeline_makespan(
                n_stages, n_micro, sched, t_f=t_f, t_b=t_b, t_w=t_w)
        else:
            # fused backward: simulator folds W into B (t_b covers both)
            sim = simulate_pipeline_makespan(
                n_stages, n_micro, sched, t_f=t_f, t_b=t_b * 0.5,
                t_w=t_b * 0.5)
        row[f"sim_{label}_ms"] = sim * 1e3
    row["measured_reduction_pct"] = 100.0 * (
        1.0 - row["wall_zb_ms"] / row["wall_1f1b_ms"])
    u_zb = simulate_pipeline_makespan(n_stages, n_micro, "ZB-H1")
    u_1f = simulate_pipeline_makespan(n_stages, n_micro, "1F1B")
    row["predicted_reduction_pct"] = 100.0 * (1.0 - u_zb / u_1f)
    return row


def measure_zbv(n_ranks, n_micro, hidden=1024, layers_per_stage=1,
                batch=128, repeats=2):
    """ZB-V (2 chunks/rank, V placement) vs the same placement with a
    fused backward — both EXECUTED on the ThreadedZBVExecutor."""
    from paddle_tpu.distributed.fleet_executor import (
        ThreadedZBVExecutor, zbv_stage_of)

    n_stages = 2 * n_ranks
    rank_of = {}
    for r in range(n_ranks):
        for c in (0, 1):
            rank_of[zbv_stage_of(r, c, n_ranks)] = r

    rng = np.random.RandomState(1)
    xs = [rng.randn(batch, hidden).astype(np.float32)
          for _ in range(n_micro)]
    ys = [rng.randn(batch, hidden).astype(np.float32)
          for _ in range(n_micro)]

    from paddle_tpu.distributed.fleet_executor import \
        build_zbv_rank_schedules

    repeats = max(repeats, 1)   # iteration 0 is always jit warmup
    row = {"ranks": n_ranks, "micro": n_micro}
    for split_w, label in ((False, "fused"), (True, "zbv")):
        best_wall, durs, sim = None, None, None
        jobs = build_stage_jobs(n_stages, hidden, layers_per_stage,
                                batch, device_of=lambda s: rank_of[s])
        for it in range(repeats + 1):
            jobs["reset"]()
            ex = ThreadedZBVExecutor(
                n_ranks, n_micro, jobs["fwd"],
                jobs["bwd_b_split"] if split_w else jobs["bwd_fused"],
                jobs["bwd_w"] if split_w else None, split_w=split_w)
            wall = ex.run(xs, ys)
            if it > 0 and (best_wall is None or wall < best_wall):
                best_wall, durs = wall, ex.measured_durations()
                sim = ex.sim_makespan
        row[f"wall_{label}_ms"] = best_wall * 1e3
        row[f"durs_{label}"] = {k: v * 1e3 for k, v in durs.items()}
        row[f"unitsim_{label}"] = sim
        # the dependency model fed with the MEASURED durations — the
        # makespan these jobs imply with true per-rank parallelism (the
        # honest column on a serializing 1-core host)
        if split_w:
            _, msim = build_zbv_rank_schedules(
                n_ranks, n_micro, t_f=durs.get("F", 1.0),
                t_b=durs.get("B", 1.0), t_w=durs.get("W", 1.0))
        else:
            fb = durs.get("B", 1.0)
            _, msim = build_zbv_rank_schedules(
                n_ranks, n_micro, t_f=durs.get("F", 1.0),
                t_b=fb * 0.5, t_w=fb * 0.5, split_w=False)
        row[f"sim_{label}_ms"] = msim * 1e3
    row["measured_reduction_pct"] = 100.0 * (
        1.0 - row["wall_zbv_ms"] / row["wall_fused_ms"])
    row["sim_reduction_pct"] = 100.0 * (
        1.0 - row["sim_zbv_ms"] / row["sim_fused_ms"])
    row["predicted_reduction_pct"] = 100.0 * (
        1.0 - row["unitsim_zbv"] / row["unitsim_fused"])
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--write-md", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="single config (pp=2, micro=4)")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_num_cpu_devices", 8)

    configs = [(2, 4)] if args.quick else [(2, 4), (2, 8), (4, 4), (4, 8)]
    rows = [measure(pp, mi) for pp, mi in configs]
    zbv_configs = [(2, 4)] if args.quick else [(2, 4), (2, 8), (4, 8)]
    zbv_rows = [measure_zbv(p, mi) for p, mi in zbv_configs]
    hdr = ("| pp | micro | wall 1F1B (ms) | wall ZB-H1 (ms) | measured "
           "t_f/t_b/t_w (ms) | sim(measured t) 1F1B | sim(measured t) "
           "ZB-H1 | sim reduction | unit-sim predicted |")
    sep = "|" + "---|" * 9
    lines = [hdr, sep]
    for r in rows:
        d = r["durs_zb"]
        sim_red = 100.0 * (1.0 - r["sim_zb_ms"] / r["sim_1f1b_ms"])
        lines.append(
            f"| {r['pp']} | {r['micro']} | {r['wall_1f1b_ms']:.1f} | "
            f"{r['wall_zb_ms']:.1f} | "
            f"{d.get('F', 0):.1f}/{d.get('B', 0):.1f}/{d.get('W', 0):.1f} | "
            f"{r['sim_1f1b_ms']:.1f} | {r['sim_zb_ms']:.1f} | "
            f"{sim_red:+.1f}% | {r['predicted_reduction_pct']:+.1f}% |")
    table = "\n".join(lines)
    zlines = ["", "ZB-V (2 chunks/rank, V placement) vs fused backward "
              "on the same placement — EXECUTED (ThreadedZBVExecutor):",
              "",
              "| ranks | micro | wall fused (ms) | wall ZB-V (ms) | "
              "wall reduction | sim(measured t) fused | sim(measured t) "
              "ZB-V | sim reduction | unit-sim predicted |",
              "|" + "---|" * 9]
    for r in zbv_rows:
        zlines.append(
            f"| {r['ranks']} | {r['micro']} | {r['wall_fused_ms']:.1f} | "
            f"{r['wall_zbv_ms']:.1f} | {r['measured_reduction_pct']:+.1f}% "
            f"| {r['sim_fused_ms']:.1f} | {r['sim_zbv_ms']:.1f} | "
            f"{r['sim_reduction_pct']:+.1f}% | "
            f"{r['predicted_reduction_pct']:+.1f}% |")
    table = table + "\n" + "\n".join(zlines)
    print(table)
    if args.write_md:
        import os
        ncores = os.cpu_count() or 1
        doc = (
            "# Measured pipeline schedules — 1F1B vs ZB-H1\n\n"
            "Harness: `tools/bench_pipeline.py` — ThreadedFleetExecutor\n"
            "(one thread per pipeline rank, jitted stage jobs, params\n"
            "pinned per virtual CPU device), 2-layer MLP per stage,\n"
            "hidden=1024, batch=128, split backward shares residuals\n"
            "(no recompute) so both schedules run identical total FLOPs.\n\n"
            "Columns: wall = measured first-start..last-end makespan;\n"
            "t_f/t_b/t_w = measured mean job durations (ZB split);\n"
            "sim(measured t) = the dependency-model makespan fed with\n"
            "those measured durations — i.e. what the measured jobs\n"
            "imply when each rank genuinely runs on its own device;\n"
            "unit-sim = the shape-only prediction.\n\n"
            f"HOST CAVEAT: this machine has {ncores} physical core(s).\n"
            "With 1 core, rank threads serialize, so the wall column\n"
            "cannot show bubble overlap (it degenerates to total work,\n"
            "where ZB pays its ~10% two-dispatch split tax). The\n"
            "sim-with-measured-durations column is the makespan evidence\n"
            "those same measured jobs give on parallel hardware; the\n"
            "driver's TPU bench is the real-chip path.\n\n" + table + "\n")
        Path(__file__).resolve().parent.parent.joinpath(
            "BENCH_PIPELINE.md").write_text(doc)
        print("\nwrote BENCH_PIPELINE.md")


if __name__ == "__main__":
    sys.exit(main())
