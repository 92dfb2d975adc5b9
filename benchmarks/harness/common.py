"""Helpers shared by the drivers, none of them a model's (those are the
family's, `families/<family>.py`). `CacheCounter`, `device_bytes` and the
TPU-or-exit guard are COPIES of `chip_smoke.py`'s (PR 24 proved them on
the chip); the benchmark keeps its own so that no later PR can change the
yardstick by editing the program.
"""
from __future__ import annotations

import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
# what a rehearsal of a toy cell on the CPU gives as its device (the work
# counts need a kind that the table of peaks holds)
CPU_AS = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def say(msg: str):
    print(f"[bench] {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_dir: str = BENCH_DIR):
    """(cell, config) dicts found by name under `bench_dir`, which the
    cell keeps: its metric files are read from there (the tests' data
    directory for a toy cell)."""
    cell = load_json(os.path.join(bench_dir, "workloads", f"{name}.json"))
    cfg = load_json(os.path.join(bench_dir, "configs",
                                 f"{cell['config']}.json"))
    cell.setdefault("name", name)
    cell["bench_dir"] = bench_dir
    return cell, cfg


def place_compile_cache() -> str:
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says, else
    the fixed git-ignored `.jax_cache` of the checkout (the path is part
    of the key, so it never moves). As paddle_tpu/utils/compile_cache_dir.py."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or os.path.join(REPO, ".jax_cache")
    if not env:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def cache_state(path: str) -> dict:
    """What bounds the persistent cache and how full it is: a cache that
    evicts (a `max_size` from the environment) makes every run compile."""
    import jax
    files = [os.path.join(path, f) for f in os.listdir(path)] \
        if os.path.isdir(path) else []
    sizes = sorted((os.path.getsize(f) for f in files if os.path.isfile(f)),
                   reverse=True)
    return {"dir": path, "entries": len(sizes),
            "MiB": round(sum(sizes) / 2**20, 1),
            "largest_MiB": [round(x / 2**20, 1) for x in sizes[:6]],
            "max_size": jax.config.jax_compilation_cache_max_size,
            "env": {k: v for k, v in os.environ.items()
                    if "COMPILATION_CACHE" in k or "PERSISTENT_CACHE" in k}}


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it, or exit non-zero with no result: a
    measurement path that finds no chip fails, it never falls back."""
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or device["count"] < chips:
        print(f"run_cell.py: needs {chips} TPU chip(s), JAX found {device}; "
              f"nothing was run", file=sys.stderr)
        sys.exit(1)
    return device


def device_bytes(arrays) -> dict:
    """{device id: bytes} actually resident, from addressable_shards."""
    out: dict = {}
    for a in arrays:
        for sh in getattr(a, "addressable_shards", ()):
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return dict(sorted(out.items()))


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, from memory_stats()."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class CacheCounter:
    """Counts JAX persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @property
    def total(self) -> int:
        return self.hits + self.misses


class Phases:
    """Splits what a run does outside its window into named parts on the
    host clock, from the process's start. Set-up is their sum but for
    `check`: the comparison with the plain reference is paid by every run
    and is no part of `setup_s`."""
    NOT_SETUP = ("check",)

    def __init__(self, t_start: float):
        self.t_start = self._last = t_start
        self.parts: dict = {}

    def mark(self, name: str):
        now = time.time()
        self.parts[name] = self.parts.get(name, 0.0) + now - self._last
        self._last = now

    def skip(self):
        """Time since the last mark belongs to no part (the window)."""
        self._last = time.time()

    def total(self) -> float:
        return sum(v for k, v in self.parts.items()
                   if k not in self.NOT_SETUP)


def within(checks: dict) -> bool:
    """`checks` is {name: (number compared, its limit)}: correct where
    every number is at most its limit (a NaN is not)."""
    return all(v <= lim for v, lim in checks.values())


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation (numpy's default),
    over ALL values given."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def resolve(tree, dotted: str):
    """Value at a dotted path of nested dicts, or None. The longest key
    that matches is taken first, so keys that hold dots themselves work."""
    if dotted == "":
        return tree
    if not isinstance(tree, dict):
        return None
    parts = dotted.split(".")
    for n in range(len(parts), 0, -1):
        head = ".".join(parts[:n])
        if head in tree:
            return resolve(tree[head], ".".join(parts[n:]))
    return None


def total(value):
    """A number, or the sum of a (nested) dict's numbers."""
    if isinstance(value, dict):
        return sum(total(v) for v in value.values() if v is not None)
    return value
