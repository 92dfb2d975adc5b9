"""Kernel autotune: block-size search + persistent cache.

Parity: reference `paddle/phi/kernels/autotune/` — `AutoTuneCache`
(cache.h: per-algo hashmaps keyed by shapes), `SwitchAutoTune`
(switch_autotune.h: tune for N steps then freeze), used for conv algos /
transpose tiling.

TPU-native: the tunable is the Pallas block geometry (block_q/block_k for
the attention kernels, block m/k/n for matmuls). `autotune()` times each
candidate on the live device, keeps the winner in a process cache, and
persists it as JSON keyed by (kernel, shape-signature, device kind) so
later processes skip the search. Off-TPU (interpret mode) the search is
skipped and heuristics stand."""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

__all__ = ["AutoTuneCache", "autotune", "lookup", "set_autotune_enabled",
           "autotune_enabled", "attention_block_candidates"]

from ..utils.flags import define_flag, flags

define_flag("use_autotune", False,
            "search Pallas block geometries at first use and cache winners")


def set_autotune_enabled(on: bool):
    """Parity: FLAGS_use_autotune / SwitchAutoTune (also settable via
    paddle.set_flags({'FLAGS_use_autotune': True}))."""
    from ..utils.flags import set_flags
    set_flags({"FLAGS_use_autotune": bool(on)})


def autotune_enabled() -> bool:
    return bool(flags("use_autotune", False))


class AutoTuneCache:
    """Process-wide winner cache with optional JSON persistence
    (parity: autotune/cache.h AutoTuneCache singleton)."""

    _instance = None
    _lock = threading.Lock()

    def __init__(self, path: Optional[str] = None):
        self._path = path or os.environ.get(
            "PADDLE_AUTOTUNE_CACHE", os.path.expanduser(
                "~/.cache/paddle_tpu_autotune.json"))
        self._mem: Dict[str, dict] = {}
        self._loaded = False
        self.hits = 0
        self.misses = 0

    @classmethod
    def instance(cls) -> "AutoTuneCache":
        with cls._lock:
            if cls._instance is None:
                cls._instance = AutoTuneCache()
            return cls._instance

    def _load(self):
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self._path) as f:
                self._mem.update(json.load(f))
        except Exception:
            pass

    def _save(self):
        try:
            os.makedirs(os.path.dirname(self._path), exist_ok=True)
            with open(self._path, "w") as f:
                json.dump(self._mem, f)
        except Exception:
            pass

    def get(self, key: str):
        self._load()
        got = self._mem.get(key)
        if got is None:
            self.misses += 1
        else:
            self.hits += 1
        return got

    def put(self, key: str, value: dict, persist=True):
        self._load()
        self._mem[key] = value
        if persist:
            self._save()

    def clear(self):
        self._mem.clear()
        self.hits = self.misses = 0


def _device_kind():
    import jax
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "cpu"


def lookup(kernel_name: str, shape_sig: Tuple) -> Optional[dict]:
    """Cached winner for (kernel, shape, device) or None.

    Pure host logic on static shapes — safe to call at TRACE time, so
    jitted models pick up winners a previous eager search persisted
    (the search itself cannot run under tracing)."""
    cache = AutoTuneCache.instance()
    key = json.dumps([kernel_name, list(shape_sig), _device_kind()])
    return cache.get(key)


def autotune(kernel_name: str, shape_sig: Tuple, candidates: List[dict],
             run_fn: Callable[[dict], Callable], warmup: int = 1,
             iters: int = 8, default: Optional[dict] = None):
    """Pick the fastest candidate config.

    run_fn(cfg) returns either a zero-arg callable (legacy; timed with
    host-fetch sync per call — coarse: it times dispatch too; runs
    max(1, warmup) un-timed calls first) or an (fn, args) tuple, timed
    with kernels/timing.py::device_time (the dispatch-proof path:
    device-side loop, fetch sync, 2N-N differencing; compiles are its
    warmup). Returns the best cfg, cached by (kernel, shape, device
    kind); if every candidate fails/can't be resolved, returns
    `default` when given (NOT cached) instead of raising."""
    cache = AutoTuneCache.instance()
    key = json.dumps([kernel_name, list(shape_sig), _device_kind()])
    hit = cache.get(key)
    if hit is not None:
        return hit
    if not candidates:
        raise ValueError("no candidates")
    from .timing import device_time
    import numpy as _np
    best_cfg, best_t = None, float("inf")
    for cfg in candidates:
        try:
            timed = run_fn(cfg)
            if isinstance(timed, tuple):
                fn, args = timed
                dt = device_time(fn, *args, iters=iters)
                if dt != dt:        # NaN: unresolvable — skip honestly
                    continue
            else:
                # legacy zero-arg form: fetch-sync each call
                for _ in range(max(1, warmup)):
                    _np.asarray(timed()).ravel()[:1]
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = timed()
                _np.asarray(out).ravel()[:1]
                dt = (time.perf_counter() - t0) / iters
        except Exception:
            continue  # illegal tiling for this shape: skip the candidate
        if dt < best_t:
            best_cfg, best_t = cfg, dt
    if best_cfg is None:
        if default is not None:
            return dict(default)     # not cached: a later window can tune
        raise RuntimeError(f"all {len(candidates)} candidates failed for "
                           f"{kernel_name} {shape_sig}")
    best = dict(best_cfg)
    best["_time_s"] = best_t
    cache.put(key, best)
    return best


def attention_block_candidates(sq: int, sk: int) -> List[dict]:
    """Legal (block_q, block_k) grid for the flash kernels: full axis or a
    128-multiple divisor (the Mosaic tiling rule _pick_block enforces)."""
    def options(n):
        opts = {n}
        if n % 128 == 0:
            for b in (128, 256, 512, 1024):
                if b <= n and n % b == 0:
                    opts.add(b)
        return sorted(opts)

    return [{"block_q": bq, "block_k": bk}
            for bq in options(sq) for bk in options(sk)]
