"""The program's own record of its set-up, read from the program when
the line is made: `paddle_tpu.profiler.compile_log.setup_totals()`. The
record is by span, so the reference's compiles after the window, and any
plain `jax.jit` outside the program's set-up, never enter it.

  span   the span kind: `setup.import`, `setup.param_init`,
         `setup.program_build`
  stage  one of the compile stages attributed to that kind (`trace`,
         `lower`, `compile`, `cache_load`), summed over its spans; without
         it, the seconds of the kind's spans

Seconds, times `scale`. Read in a traced run, as every per-layer metric
is: None without a trace, and None where the program keeps no such record
(a program without `setup_totals`); 0 where it keeps one and no span of
the kind was opened.
"""
from __future__ import annotations


def read(obs, args):
    if obs.get("trace") is None:
        return None
    try:
        from paddle_tpu.profiler import compile_log
    except ImportError:
        return None
    totals = getattr(compile_log, "setup_totals", None)
    if totals is None:
        return None
    rec = totals().get(args["span"])
    if rec is None:
        return 0.0
    v = rec["stages"][args["stage"]] if "stage" in args else rec["seconds"]
    return v * args.get("scale", 1.0)
