"""ProgramCache: the ONE owner of every bucketed compiled program.

Until ISSUE 8 the prefill/chunk, decode, verify and draft-model program
buckets lived in engine-local dicts with hand-maintained count bounds —
tolerable for a (family, B, P) key space, but TP serving multiplies
every key by the mesh shape and quantized serving already multiplied it
by (kv_dtype, wq). This module centralizes the store so the
TP x quant x spec key space has one owner:

* keys are tuples whose FIRST element names the program family
  ("chunk", "decode", "verify", ... — families are registered up front
  with their bucket-grid bound);
* `get(key, builder)` compiles on miss, reports the compile through the
  `on_compile` hook (the engine wires it to
  `ServingMetrics.on_recompile`), and ENFORCES the registered family
  bound — exceeding it raises instead of silently recompiling forever,
  because an unbounded program cache is exactly the bug the bucket grid
  exists to prevent;
* per-family counts (`counts()`) and bounds (`max_count(family)`)
  replace the single flat number, so "which family is compiling?" is
  answerable from metrics instead of a debugger.

The bound callables are evaluated lazily (engines finalize their bucket
lists after construction-time clamping), and the bound is the grid for
ONE mesh shape — an engine owns one mesh, so its key space is
`bucket grid x {its mesh shape}`; processes mixing TP degrees get one
cache per engine and the global compile count stays the sum of the
per-engine grids (the "mesh shapes actually used" bound in ISSUE 8).

Observability (ISSUE 11): every stored program rides in a thin
`_TrackedProgram` wrapper — its FIRST launch (the jit trace+compile)
is timed and logged to the shared compile-event ring
(`profiler.compile_log`, kind `program_compile`), and the launch args'
ShapeDtypeStructs are recorded so `cost_table()` can re-lower each
program for XLA cost/memory accounting (`profiler.cost`) without
holding tensor data. Steady-state launches pay one attribute check.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

__all__ = ["ProgramCache"]


class _TrackedProgram:
    """Callable wrapper over one compiled-program builder result: times
    the first launch (= jit compile) and keeps abstract arg shapes for
    later cost accounting. Transparent to call sites — engines only
    ever `prog(*args)`.

    Disk-loaded programs (ISSUE 14) ride the same wrapper with
    `from_disk=True` and a `fallback` builder: a deserialized
    executable that fails its FIRST call (a stale entry whose damage
    the checksums could not see — e.g. an aval-shape drift) is
    replaced by a fresh build in place, counted as a cache reject —
    the persistent cache degrades to recompile, never to a crashed
    worker."""

    __slots__ = ("fn", "key", "first_call_ms", "arg_avals", "_cost",
                 "_comm", "from_disk", "fallback", "on_reject")

    def __init__(self, fn, key, *, from_disk=False, fallback=None,
                 on_reject=None):
        self.fn = fn
        self.key = key
        self.first_call_ms = None
        self.arg_avals = None
        self._cost = None
        self._comm = {}
        self.from_disk = from_disk
        self.fallback = fallback
        self.on_reject = on_reject

    def __call__(self, *args):
        if self.first_call_ms is None:
            from ..profiler import compile_log
            t0 = time.perf_counter()
            with compile_log.setup_span(
                    "setup.program_build", family=str(self.key[0]),
                    key=repr(self.key)[:120]) as build:
                out = self._first_call(args)
            dt = time.perf_counter() - t0
            self.first_call_ms = round(dt * 1e3, 3)
            try:
                from ..profiler.cost import shape_structs
                self.arg_avals = shape_structs(list(args))
            except Exception:
                self.arg_avals = None
            compile_log.log_event(
                "program_compile", name=str(self.key[0]), duration_s=dt,
                detail={"key": repr(self.key)[:120],
                        "from_disk": self.from_disk,
                        "stages": dict(build.stages)})
            return out
        return self.fn(*args)

    def _first_call(self, args):
        if not (self.from_disk and self.fallback is not None):
            return self.fn(*args)
        try:
            return self.fn(*args)
        except Exception as exc:                  # noqa: BLE001
            # Only a FATAL failure indicts the ENTRY (stale avals,
            # foreign executable). Transient device errors and poison
            # must propagate to the engine's supervisor — its retry path
            # owns the donated-buffer hazard, and a perfectly good entry
            # must not be rejected for the device's flakiness.
            from .supervisor import FATAL, classify_failure
            if classify_failure(exc) != FATAL:
                raise
            self.fn = self.fallback()
            self.from_disk = False
            if self.on_reject is not None:
                self.on_reject()
            return self.fn(*args)

    def lower(self):
        """Re-lower this program from the recorded arg avals. Under
        no_grad like every launch site: a retrace in grad mode would
        send the paged-attention kernel through jax.vjp, which Pallas
        refuses for scalar-prefetch grids."""
        from ..core.autograd import no_grad
        with no_grad():
            return self.fn.lower(*self.arg_avals)

    def cost_report(self) -> Optional[dict]:
        """XLA cost/memory accounting of this program (lazy, cached):
        re-lowers from the recorded arg avals — only possible for
        jax.jit-built programs that have launched at least once."""
        if self._cost is not None:
            return self._cost
        if self.arg_avals is None or not hasattr(self.fn, "lower"):
            return None
        try:
            from ..profiler import cost as _cost
            rec = _cost.lowered_cost(self.lower()).to_dict()
        except Exception as e:   # accounting must never break serving
            # transient failures are NOT cached — the next call retries
            rec = {"error": f"{type(e).__name__}: {e}"[:200]}
            rec["compile_ms"] = self.first_call_ms
            return rec
        rec["compile_ms"] = self.first_call_ms
        self._cost = rec
        return rec

    def comm_report(self, mesh=None) -> Optional[dict]:
        """Collective-traffic accounting of this program (ISSUE 12):
        op counts + payload bytes per mesh axis from the compiled HLO
        (`profiler.comm`). A meshless call resolves the ambient hybrid
        mesh FIRST — `lowered_comm` would fall back to it anyway, so
        resolving up front keeps the cache key (the mesh-axes
        signature) matched to the attribution actually performed; with
        no mesh anywhere, ops stay unattributed under the None key."""
        from ..profiler import comm as _comm
        if mesh is None:
            mesh = _comm._default_mesh()
        try:
            axes = tuple(getattr(mesh, "jax_mesh", mesh).axis_names) \
                if mesh is not None else None
        except Exception:
            axes = None
        if axes in self._comm:
            return self._comm[axes]
        if self.arg_avals is None or not hasattr(self.fn, "lower"):
            return None
        try:
            rec = _comm.lowered_comm(self.lower(), mesh=mesh).to_dict()
        except Exception as e:   # accounting must never break serving
            # transient failures are NOT cached — the next call retries
            return {"error": f"{type(e).__name__}: {e}"[:200]}
        self._comm[axes] = rec
        return rec


class ProgramCache:
    """Keyed store of compiled programs with per-family compile bounds.

    on_compile: optional zero-arg hook fired once per compilation (cache
    miss) — the engine's recompile counter.
    """

    def __init__(self, on_compile: Optional[Callable[[], None]] = None,
                 disk=None):
        self._programs: Dict[tuple, object] = {}
        self._bounds: Dict[str, Callable[[], int]] = {}
        self._counts: Dict[str, int] = {}
        self._on_compile = on_compile
        # optional persistent CompileCache (ISSUE 14): consulted on
        # every miss BEFORE the builder; set post-construction by the
        # engine (`self.programs.disk = CompileCache(...)`)
        self.disk = disk

    def register_family(self, family: str, bound: Callable[[], int]):
        """Declare a program family and its (lazily evaluated) compile
        bound — the bucket-grid size for this family."""
        self._bounds[family] = bound
        self._counts.setdefault(family, 0)
        return self

    # ------------------------------------------------------------- access
    def get(self, key: tuple, builder: Callable[[], object]):
        """The program for `key` (key[0] = family), compiling via
        `builder` on miss. Raises KeyError for an unregistered family
        and RuntimeError when a compile would exceed the family bound —
        a blown bound means a key axis leaked out of the bucket grid
        (the unbounded-recompilation bug class), which must fail loud,
        not page the on-call about mystery latency."""
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        family = key[0]
        if family not in self._bounds:
            raise KeyError(f"unregistered program family {family!r} "
                           f"(known: {sorted(self._bounds)})")
        bound = int(self._bounds[family]())
        if self._counts[family] + 1 > bound:
            raise RuntimeError(
                f"program family {family!r} would exceed its compile "
                f"bound {bound} with key {key!r} — a key axis is not "
                f"riding the bucket grid")
        loaded = self.disk.load(key) if self.disk is not None else None
        if loaded is not None:
            # disk hit: the deserialized executable skips trace AND
            # compile; `builder` stays attached as the first-call
            # fallback, and a fallback rebuild counts a disk reject.
            # NOT a compile for on_compile/metrics purposes — the
            # recompiles counter keeps meaning "XLA compiled here".
            def _reject():
                # hits stays MONOTONIC (it is exposed as a Prometheus
                # counter; a decrement would read as a counter reset):
                # net useful hits = hits - rejects
                self.disk.counters["rejects"] += 1
                # a checksummed-but-unrunnable entry: mark it so the
                # next save_all REWRITES it from the fresh build
                self.disk.rejected_keys.add(key)
                if self._on_compile is not None:
                    self._on_compile()   # the fallback IS a compile
            prog = _TrackedProgram(loaded, key, from_disk=True,
                                   fallback=builder, on_reject=_reject)
        else:
            prog = _TrackedProgram(builder(), key)
            if self._on_compile is not None:
                self._on_compile()
        self._programs[key] = prog
        self._counts[family] += 1
        return prog

    # ------------------------------------------------------------ counts
    @property
    def num_programs(self) -> int:
        return len(self._programs)

    def counts(self) -> Dict[str, int]:
        """{family: programs compiled} — every registered family
        appears, compiled or not."""
        return dict(self._counts)

    def max_count(self, family: Optional[str] = None) -> int:
        """The compile bound: one family's grid, or (default) the sum
        over every registered family."""
        if family is not None:
            return int(self._bounds[family]())
        return sum(int(b()) for b in self._bounds.values())

    def keys(self):
        """The live program keys (tests assert the key-suffix axes —
        quant config, mesh shape — actually ride them)."""
        return list(self._programs.keys())

    # ------------------------------------------------------- accounting
    def compile_times_ms(self) -> Dict[tuple, Optional[float]]:
        """{key: first-launch wall ms} — None for programs never
        launched (built but not yet called)."""
        return {k: p.first_call_ms for k, p in self._programs.items()}

    def cost_table(self) -> Dict[tuple, Optional[dict]]:
        """{key: XLA cost/memory dict} over every launched program
        (ISSUE 11) — flops, bytes, peak_bytes per bucketed program, so
        "which bucket family is paying for its HBM" is answerable from
        metrics. Lazy: each program's accounting is computed once, on
        the first cost_table() call after its first launch."""
        return {k: p.cost_report() for k, p in self._programs.items()}

    def comm_table(self, mesh=None) -> Dict[tuple, Optional[dict]]:
        """{key: collective-traffic dict} over every launched program
        (ISSUE 12) — op counts and payload bytes per mesh axis, so
        "which bucketed program moves how much over 'model'" is
        answerable from metrics (the TP row-parallel psum shows up on
        the decode family's rows). Pass the engine's mesh for axis
        attribution; `ServingEngine.comm_table()` does."""
        return {k: p.comm_report(mesh=mesh)
                for k, p in self._programs.items()}

    def compiled_text(self, key: tuple) -> str:
        """Optimized HLO text of one launched program — where a chip
        check looks for the paged-attention `tpu_custom_call`."""
        return self._programs[key].lower().compile().as_text()

    def family_costs(self) -> Dict[str, dict]:
        """Per-family aggregate of cost_table(): program count, summed
        flops, max peak_bytes — the capacity-planning view."""
        out: Dict[str, dict] = {}
        for key, rec in self.cost_table().items():
            fam = out.setdefault(str(key[0]), {
                "programs": 0, "accounted": 0, "flops": 0.0,
                "max_peak_bytes": 0})
            fam["programs"] += 1
            if not rec or "error" in rec:
                continue
            fam["accounted"] += 1
            fam["flops"] += rec.get("flops", 0.0)
            fam["max_peak_bytes"] = max(fam["max_peak_bytes"],
                                        rec.get("peak_bytes", 0))
        return out

    def __len__(self):
        return len(self._programs)

    def __contains__(self, key):
        return key in self._programs
