"""XLA cost/memory accounting per compiled program (ISSUE 11).

Until this PR every FLOPs / HBM-bytes / MFU claim in the tree was a
hand-maintained formula (`bench.py::llama_step_flops`, BASELINE.md's
`adamw_update_bytes` sizing tables) — honest the day it was written,
unverifiable after. XLA already computes the ground truth at compile
time: `lowered.compile().cost_analysis()` (flops, transcendentals,
per-operand bytes accessed) and `.memory_analysis()`
(argument/output/temp/alias buffer sizes). This module turns those into
one structured `ProgramCost`, and the hand formulas become
CROSS-CHECKED claims (tests/test_profiler_cost.py fails on drift).

Reading the numbers honestly:

* `flops` counts the HLO module's arithmetic. While/scan BODIES ARE
  COUNTED ONCE, not per trip — so programs that hide matmuls inside
  `lax.scan`/Pallas-interpret kernels (the CPU flash-attention path)
  UNDERCOUNT, and custom-call kernels (real Pallas on TPU) count zero.
  Analytic MFU is therefore a LOWER bound whenever custom kernels are
  in the program; the FLOPs cross-check pins the pure-XLA sdpa path
  where the count is exact (measured 1.003x of the hand formula on the
  flagship config).
* `bytes_accessed` is XLA's per-op operand+result sum — it counts
  intermediate fusion traffic and overlaps, NOT minimal HBM traffic
  (measured 1.5x the roofline bytes on the AdamW update). For
  roofline/bytes claims use `io_bytes` (argument + output buffer
  sizes from memory_analysis): for a bytes-bound program that reads
  every input once and writes every output once it IS the roofline
  number — it reproduces `adamw_update_bytes` exactly.
* `peak_bytes` = arguments + outputs + temps - donation aliases: the
  live-buffer bound XLA budgeted, the "does this config fit HBM"
  number (`bench.py` reports it as `peak_hbm_bytes`).

Consumers: `TracedFunction.cost_report()` (jit/api.py), the serving
`ProgramCache.cost_table()` and `bench.py`'s JSON line.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

__all__ = ["ProgramCost", "compiled_cost", "lowered_cost", "jit_cost",
           "shape_structs", "chip_peaks", "peak_flops_per_chip",
           "analytic_mfu", "CHIP_PEAKS"]

# Published per-chip peaks: (bf16 FLOP/s, HBM bytes/s), matched as a
# substring of the lower-cased `device_kind` JAX reports ("TPU v5 lite"
# is a v5e), most specific first. Source: Google Cloud TPU documentation,
# the "TPU v5e" / "TPU v5p" / "TPU v4" / "TPU v6e" system-architecture
# pages. The ONE table of the tree: a device that is not in it is an
# error, never a default — a share of an invented peak is not a number.
CHIP_PEAKS = {
    "v5 lite": (197e12, 819e9), "v5e": (197e12, 819e9),
    "v5litepod": (197e12, 819e9),
    "v5p": (459e12, 2765e9), "v5": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6 lite": (918e12, 1640e9), "v6e": (918e12, 1640e9),
    "trillium": (918e12, 1640e9),
}


def chip_peaks(device_kind: str):
    """(bf16 peak FLOP/s, HBM peak bytes/s) of one chip of `device_kind`;
    raises ValueError for a device the table does not hold."""
    kind = str(device_kind).lower()
    for k, v in CHIP_PEAKS.items():
        if k in kind:
            return v
    raise ValueError(
        f"no published peaks for device kind {device_kind!r} (known: "
        f"{sorted(CHIP_PEAKS)}); add it to profiler/cost.py CHIP_PEAKS "
        f"with its source before quoting a utilization")


def peak_flops_per_chip(device_kind: str) -> float:
    return chip_peaks(device_kind)[0]


class ProgramCost:
    """Structured cost/memory accounting of ONE compiled program."""

    __slots__ = ("flops", "transcendentals", "bytes_accessed",
                 "argument_bytes", "output_bytes", "temp_bytes",
                 "alias_bytes", "generated_code_bytes")

    def __init__(self, *, flops=0.0, transcendentals=0.0,
                 bytes_accessed=0.0, argument_bytes=0, output_bytes=0,
                 temp_bytes=0, alias_bytes=0, generated_code_bytes=0):
        self.flops = float(flops)
        self.transcendentals = float(transcendentals)
        self.bytes_accessed = float(bytes_accessed)
        self.argument_bytes = int(argument_bytes)
        self.output_bytes = int(output_bytes)
        self.temp_bytes = int(temp_bytes)
        self.alias_bytes = int(alias_bytes)
        self.generated_code_bytes = int(generated_code_bytes)

    # ---- derived ---------------------------------------------------------
    @property
    def io_bytes(self) -> int:
        """Read-every-input-once + write-every-output-once traffic — the
        roofline bytes for a bandwidth-bound program (matches
        `adamw_update_bytes` on the optimizer step)."""
        return self.argument_bytes + self.output_bytes

    @property
    def peak_bytes(self) -> int:
        """Live-buffer bound: args + outputs + temps - donation aliases."""
        return (self.argument_bytes + self.output_bytes
                + self.temp_bytes - self.alias_bytes)

    def mfu(self, dt_s: float, peak_flops: Optional[float] = None,
            device_kind: Optional[str] = None) -> Optional[float]:
        """Analytic MFU of one execution taking `dt_s` seconds."""
        if peak_flops is None:
            peak_flops = peak_flops_per_chip(
                device_kind if device_kind is not None
                else _default_device_kind())
        if dt_s <= 0 or peak_flops <= 0:
            return None
        return self.flops / dt_s / peak_flops

    def hbm_gbps(self, dt_s: float) -> Optional[float]:
        """io_bytes / time — the achieved roofline GB/s."""
        if dt_s <= 0:
            return None
        return self.io_bytes / dt_s / 1e9

    def to_dict(self) -> dict:
        return {"flops": self.flops,
                "transcendentals": self.transcendentals,
                "bytes_accessed": self.bytes_accessed,
                "io_bytes": self.io_bytes,
                "peak_bytes": self.peak_bytes,
                "argument_bytes": self.argument_bytes,
                "output_bytes": self.output_bytes,
                "temp_bytes": self.temp_bytes,
                "alias_bytes": self.alias_bytes,
                "generated_code_bytes": self.generated_code_bytes}

    def __repr__(self):
        return (f"ProgramCost(flops={self.flops:.4g}, "
                f"io_bytes={self.io_bytes}, peak_bytes={self.peak_bytes})")


def _default_device_kind() -> str:
    import jax
    dev = jax.devices()[0]
    return getattr(dev, "device_kind", dev.platform)


def analytic_mfu(flops: float, dt_s: float,
                 peak_flops: Optional[float] = None,
                 device_kind: Optional[str] = None) -> Optional[float]:
    """MFU from already-known flops (e.g. a hand formula) — same peak
    table as ProgramCost.mfu so the two are directly comparable."""
    if peak_flops is None:
        peak_flops = peak_flops_per_chip(
            device_kind if device_kind is not None
            else _default_device_kind())
    if dt_s <= 0 or peak_flops <= 0:
        return None
    return float(flops) / dt_s / peak_flops


def compiled_cost(compiled) -> ProgramCost:
    """ProgramCost of a `jax.stages.Compiled` (or anything exposing
    cost_analysis()/memory_analysis()). Absent analyses (some backends
    return None) degrade to zeros rather than raising — a cost report
    must never take down the program it describes."""
    ca: Dict[str, Any] = {}
    try:
        raw = compiled.cost_analysis()
        # jax 0.4.x returns [dict] (one per partition), newer a dict
        if isinstance(raw, (list, tuple)):
            raw = raw[0] if raw else {}
        ca = dict(raw or {})
    except Exception:
        pass
    kw = {"flops": ca.get("flops", 0.0) or 0.0,
          "transcendentals": ca.get("transcendentals", 0.0) or 0.0,
          "bytes_accessed": ca.get("bytes accessed", 0.0) or 0.0}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        kw.update(
            argument_bytes=getattr(ma, "argument_size_in_bytes", 0),
            output_bytes=getattr(ma, "output_size_in_bytes", 0),
            temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
            alias_bytes=getattr(ma, "alias_size_in_bytes", 0),
            generated_code_bytes=getattr(
                ma, "generated_code_size_in_bytes", 0))
    return ProgramCost(**kw)


def lowered_cost(lowered) -> ProgramCost:
    """Compile a `jax.stages.Lowered` and account it. With the
    persistent compilation cache on (bench.py enables it), re-compiling
    an already-seen program is a disk hit."""
    return compiled_cost(lowered.compile())


def shape_structs(tree):
    """Abstract a pytree of arrays to ShapeDtypeStructs (non-array
    leaves pass through), so a program can be re-lowered for accounting
    without holding or moving any data. An array that was placed keeps
    its sharding, so a mesh-sharded program re-lowers as the program
    that ran and not as its one-device twin."""
    import jax

    def _abs(leaf):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            return leaf
        placed = isinstance(leaf, jax.Array) and leaf.committed
        return jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=leaf.sharding if placed else None)
    return jax.tree_util.tree_map(_abs, tree)


def jit_cost(fn, *args, static_argnums=(), donate_argnums=(),
             **kwargs) -> ProgramCost:
    """Account an arbitrary function: jit -> lower(*args) -> compile ->
    ProgramCost. `args` may be concrete arrays or ShapeDtypeStructs
    (pass through `shape_structs` to avoid materializing inputs)."""
    import jax
    jitted = jax.jit(fn, static_argnums=static_argnums,
                     donate_argnums=donate_argnums)
    return lowered_cost(jitted.lower(*args, **kwargs))
