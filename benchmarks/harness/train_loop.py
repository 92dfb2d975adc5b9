"""Driver `train_loop`: `paddle.jit.to_static(step, state_objects=[model,
opt])` over the family's model, as a trainer calls it (run_train_steps of
chip_smoke.py, re-arranged around a window). A fresh Zipf batch every
step, labels = ids, the loss fetched every `sync_every`-th step.
"""
from __future__ import annotations

import time

import numpy as np

from . import loadgen, lookup
from .common import (CacheCounter, device_bytes, memory_peak_bytes, say,
                     within)
from .observe import Spans, delta


def setup(cfg: dict, cell: dict, seed: int):
    """Model, optimizer and the to_static step, under the configuration's
    mesh (fleet.init hybrid dp x mp) where it has one."""
    import jax
    import paddle_tpu as paddle

    fam = lookup.family(cfg)
    pcfg = fam.config(cfg)
    sharding = None
    mesh = cfg.get("mesh")
    if mesh:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed.fleet import DistributedStrategy, fleet
        st = DistributedStrategy()
        st.hybrid_configs = {"dp_degree": mesh["dp"], "mp_degree": mesh["mp"],
                             "pp_degree": 1, "sharding_degree": 1,
                             "sep_degree": 1}
        fleet.init(is_collective=True, strategy=st)
        sharding = NamedSharding(fleet.get_hybrid_communicate_group().mesh,
                                 P("data", None))
    paddle.seed(seed % (2 ** 31 - 1))
    model = fam.build_model(pcfg, cfg["dtype"])
    o = cfg["optimizer"]
    opt = getattr(paddle.optimizer, o["name"])(
        o["lr"], parameters=model.parameters(),
        multi_precision=o["multi_precision"])
    jax.block_until_ready([p._data for p in model.parameters()])

    def train_step(ids, labels):
        loss = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step, state_objects=[model, opt])
    say(f"train: parameter bytes per device "
        f"{device_bytes(p._data for p in model.parameters())}")
    return {"family": fam, "pcfg": pcfg, "model": model, "opt": opt,
            "step": step, "sharding": sharding}


def counters(cache: CacheCounter) -> dict:
    import paddle_tpu as paddle
    rep = paddle.jit.to_static_report()
    return {"to_static": {
                "compile_seconds": dict(rep["compile_seconds"]),
                "compile_events": len(rep["compile_events"])
                + rep["compile_events_dropped"],
                "eager_fallbacks": len(rep["eager_fallbacks"])
                + rep["eager_fallbacks_dropped"]},
            "jax_cache": {"hits": cache.hits, "misses": cache.misses}}


def run(cfg, cell, *, seed, seconds, cache, phases, tracer=None,
        spans=None):
    """Warm up, check, measure for `seconds`, check again. Returns
    (end_to_end values, obs, correct, attempted, failed)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle

    t = cell["traffic"]
    chips = cell["chips"]
    spans = spans or Spans()
    built = setup(cfg, cell, seed)
    model, step = built["model"], built["step"]
    phases.mark("model_build")
    batches = loadgen.ZipfBatches(cfg["vocab_size"], t["zipf"], t["batch"],
                                  t["seq"], seed)
    tokens_a_step = t["batch"] * t["seq"]
    sync_every = int(t["sync_every"])

    def put(ids_np):
        a = jnp.asarray(ids_np)
        if built["sharding"] is not None:
            a = jax.device_put(a, built["sharding"])
        return paddle.Tensor(a)

    def one_step():
        ids = put(batches.next())
        with spans.span("bench.train_step"):
            return step(ids, ids)

    def fetch(loss) -> float:
        with spans.span("bench.sync"):
            return float(np.asarray(loss._data))

    def checked_step():
        """One step whose loss is compared with the plain reference on
        the weights the step sees. Returns the relative error."""
        ids_np = batches.next()
        w = {k: v._data for k, v in model.state_dict().items()}
        ref = built["family"].loss(w, built["pcfg"], ids_np)
        del w
        ids = put(ids_np)
        got = float(np.asarray(step(ids, ids)._data))
        return got, ref, abs(got - ref) / abs(ref)

    # steps 1 and 2 compile (AdamW state is created in step 1)
    losses = [fetch(one_step()) for _ in range(2)]
    phases.mark("program_build")
    for _ in range(int(t.get("warm_steps", 6))):
        loss = one_step()
    losses.append(fetch(loss))
    if tracer is not None:
        tracer.warm()
    phases.mark("warm_traffic")
    steps_checked = [checked_step()]
    phases.mark("check")

    # ---------------------------------------------------------- the window
    spans.reset()
    before = counters(cache)
    # a traced run traces the LAST `trace_seconds` of the window, so that
    # stopping the profiler (seconds of writing) falls outside it
    trace_s = float(t.get("trace_seconds", 3.0))
    sync_t, sync_steps, n, traced_from = [], [], 0, None
    t0 = time.perf_counter()
    while True:
        for _ in range(sync_every):
            loss = one_step()
        n += sync_every
        losses.append(fetch(loss))
        now = time.perf_counter()
        if traced_from is None:       # the rate leaves the traced slice out
            sync_t.append(now)
            sync_steps.append(n)
        if now - t0 >= seconds:
            break
        if tracer is not None and traced_from is None \
                and now - t0 >= seconds - trace_s:
            tracer.start()
            traced_from = n
    slice_steps = None
    if traced_from is not None:
        tracer.stop()
        slice_steps = n - traced_from
    after = counters(cache)
    peak = memory_peak_bytes()
    phases.skip()
    # between the first and the last sync inside the window
    steps = sync_steps[-1] - sync_steps[0]
    span_s = sync_t[-1] - sync_t[0]
    tokens_per_s = steps * tokens_a_step / span_s
    steps_checked.append(checked_step())
    phases.mark("check")

    tol = built["family"].loss_tolerance(cfg)
    in_window = delta(after, before)
    finite = [bool(np.isfinite(x)) for x in losses]
    checks = {"loss_rel_err.before": (steps_checked[0][2], tol),
              "loss_rel_err.after": (steps_checked[1][2], tol),
              "losses_not_finite": (finite.count(False), 0),
              "eager_fallbacks": (after["to_static"]["eager_fallbacks"], 0),
              "compiles_in_window": (
                  in_window["to_static"]["compile_events"]
                  + in_window["jax_cache"]["hits"]
                  + in_window["jax_cache"]["misses"], 0)}
    gaps = np.diff(sync_t)
    say(f"train: seconds from sync to sync ({sync_every} steps): min "
        f"{gaps.min():.4f} median {np.median(gaps):.4f} max {gaps.max():.4f}")
    say(f"train: {n} steps in the window, {steps} between its first and "
        f"last sync over {span_s:.3f}s; losses first/last "
        f"{losses[0]:.4f}/{losses[-1]:.4f}; checked steps (loss, float32 "
        f"reference, rel err) {[(round(g, 5), round(r, 5), f'{e:.2e}') for g, r, e in steps_checked]} "
        f"tolerance {tol:.2e}")
    values = {"tokens_per_s": tokens_per_s, "steps": steps,
              "window_s": span_s, "slice_steps": slice_steps}
    e2e = {"train_tokens_per_s_chip": (tokens_per_s / chips, "tokens/s/chip")}
    obs = {"values": values, "spans": spans.durations, "checks": checks,
           "memory_peak_bytes": peak,
           "counters": {"window": in_window, "process": after}}
    return e2e, obs, within(checks), n, finite[3:].count(False)
