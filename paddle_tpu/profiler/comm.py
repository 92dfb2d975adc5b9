"""Collective-traffic accounting per compiled program (ISSUE 12).

`profiler/cost.py` (ISSUE 11) made FLOPs/HBM-bytes claims derivable from
the compiled program; this module does the same for COMMUNICATION. It
walks the post-SPMD-partitioning HLO text of a compiled jit program
(`compiled.as_text()` — the same `lowered.compile()` access path
`cost.py` uses) for the five collective families XLA emits

    all-reduce, all-gather, reduce-scatter, all-to-all,
    collective-permute  (async `-start` forms counted, `-done` skipped)

and turns operand shapes + replica groups into per-op records and a
per-MESH-AXIS attribution of op counts and payload bytes — "how many
bytes does this step move over which axis" becomes a dict, not an HLO
reading session. Parity: the reference pairs its executors with a comm
cost model (`paddle/fluid/distributed/fleet_executor/` +
`paddle/phi/api/profiler/`); here XLA already placed the collectives,
so the honest model is to read them back out.

Reading the numbers honestly:

* **payload bytes, not wire bytes.** Each op is accounted at its
  LOGICAL payload: operand buffer bytes for all-reduce /
  reduce-scatter / all-to-all / collective-permute, RESULT buffer
  bytes for all-gather (the gathered buffer every participant ends up
  holding). Algorithm traffic (ring all-reduce moves ~2(n-1)/n x
  payload per link) is a backend scheduling detail; divide yourself if
  you need link-level numbers.
* **per-executed-program, counted once.** Like `cost.py` flops,
  while/scan bodies count ONCE, and collectives issued inside Pallas
  custom calls (manual-collective shard_map kernels) count ZERO — the
  IR walk is a LOWER bound under custom comm kernels.
* **axis attribution** maps each replica group's device entries to
  coordinates in the mesh's device array (entries are flat indices in
  row-major mesh order — the device-assignment order XLA uses for a
  mesh-sharded jit) and names the axes whose coordinate varies within
  a group. A fused collective spanning several axes reports a compound
  label ("data+model"); entries that don't fit the mesh land under
  "unattributed" rather than being dropped.

Consumers: `TracedFunction.comm_report()` (jit/api.py, beside
`cost_report()`), the serving `ProgramCache.comm_table()`, `bench.py`'s
`comm_bytes`/`comm_bytes_per_axis` JSON fields, the
`dryrun_multichip` evidence line, and tools/chip_comm.py. All analysis
failures degrade to an error record — accounting must never take down
the program it describes.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["CollectiveOp", "CommReport", "parse_hlo_collectives",
           "parse_replica_groups", "compiled_comm", "lowered_comm",
           "jit_comm", "COLLECTIVE_KINDS", "UNATTRIBUTED"]

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

# axis label for replica groups whose entries don't map onto the mesh
UNATTRIBUTED = "unattributed"

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(
    r"\b(" + "|".join(sorted(_DTYPE_BYTES, key=len, reverse=True))
    + r")\[([0-9,]*)\]")
# the instruction head: "%name = <result shapes> <kind>[-start](..."
_INSTR_RE = re.compile(
    r"=\s*(?P<result>[^=]*?)\s(?P<kind>"
    + "|".join(COLLECTIVE_KINDS) + r")(?P<async>-start)?\(")
# any instruction's definition head: "[ROOT] %name = " (shape follows)
_DEF_RE = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_EXPLICIT_GROUPS_RE = re.compile(r"\{\{[0-9,{} ]*\}\}|\{\}")
_IOTA_GROUPS_RE = re.compile(
    r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def _shape_bytes(text: str) -> int:
    """Total buffer bytes of every dtype[dims] shape token in `text`
    (a tuple shape simply contributes each element)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_replica_groups(attr_text: str) -> Optional[List[Tuple[int, ...]]]:
    """Replica groups from an HLO attribute string. Handles the explicit
    form `{{0,1},{2,3}}`, the empty form `{}` (all participants in one
    group -> None, meaning "everyone"), and the iota form
    `[g,s]<=[dims]` / `[g,s]<=[dims]T(perm)` (v2 iota group lists:
    transpose iota(dims) by perm, reshape to g groups of s)."""
    m = _IOTA_GROUPS_RE.search(attr_text)
    if m is not None:
        out_dims = [int(x) for x in m.group(1).split(",")]
        reshape = [int(x) for x in m.group(2).split(",")]
        total = 1
        for d in reshape:
            total *= d
        flat = list(range(total))
        # build the transposed iota without numpy (stdlib-safe parse)
        if m.group(3):
            perm = [int(x) for x in m.group(3).split(",")]
            strides = [1] * len(reshape)
            for i in range(len(reshape) - 2, -1, -1):
                strides[i] = strides[i + 1] * reshape[i + 1]
            tdims = [reshape[p] for p in perm]
            tstrides = [strides[p] for p in perm]
            flat = []
            idx = [0] * len(tdims)
            for _ in range(total):
                flat.append(sum(i * s for i, s in zip(idx, tstrides)))
                for ax in range(len(tdims) - 1, -1, -1):
                    idx[ax] += 1
                    if idx[ax] < tdims[ax]:
                        break
                    idx[ax] = 0
        n_groups, group_size = out_dims[0], out_dims[-1]
        if len(out_dims) == 1:
            n_groups, group_size = 1, out_dims[0]
        return [tuple(flat[g * group_size:(g + 1) * group_size])
                for g in range(n_groups)]
    m = _EXPLICIT_GROUPS_RE.search(attr_text)
    if m is None:
        return None
    body = m.group(0)
    if body == "{}":
        return None
    groups = []
    for grp in re.findall(r"\{([0-9, ]+)\}", body):
        groups.append(tuple(int(x) for x in grp.replace(" ", "").split(",")
                            if x))
    return groups or None


class CollectiveOp:
    """One collective instruction found in the compiled HLO."""

    __slots__ = ("kind", "operand_bytes", "result_bytes", "groups",
                 "group_size", "axes")

    def __init__(self, kind, operand_bytes, result_bytes, groups,
                 group_size, axes=None):
        self.kind = kind
        self.operand_bytes = int(operand_bytes)
        self.result_bytes = int(result_bytes)
        self.groups = groups
        self.group_size = int(group_size)
        self.axes = axes        # tuple of mesh axis names, or None

    @property
    def payload_bytes(self) -> int:
        """The logical payload (module docstring): all-gather is
        accounted at the RESULT it materializes everywhere (operand x
        group size — computed that way so async `-start` tuple results
        don't double-count; the sync result equals it exactly), the
        rest at the operand buffer entering the collective."""
        if self.kind == "all-gather":
            if self.group_size > 0:
                return self.operand_bytes * self.group_size
            return self.result_bytes
        return self.operand_bytes

    @property
    def axis_label(self) -> str:
        if not self.axes:
            return UNATTRIBUTED
        return "+".join(self.axes)

    def to_dict(self) -> dict:
        return {"kind": self.kind, "payload_bytes": self.payload_bytes,
                "operand_bytes": self.operand_bytes,
                "result_bytes": self.result_bytes,
                "group_size": self.group_size,
                "axis": self.axis_label}

    def __repr__(self):
        return (f"CollectiveOp({self.kind}, payload={self.payload_bytes}, "
                f"axis={self.axis_label}, groups of {self.group_size})")


def _close_paren(text: str, start: int) -> int:
    """Index just past the ')' closing the '(' whose inside starts at
    `start` (len(text) when it never closes)."""
    depth, end = 1, start
    while end < len(text) and depth:
        depth += (text[end] == "(") - (text[end] == ")")
        end += 1
    return end


def _result_shapes(lines: Sequence[str]) -> Dict[str, str]:
    """{instruction name: its result-shape text} for every `%name = shape
    op(...)` line. The installed jax prints operands by name only
    (`all-reduce(%param.1)`), so an operand's shape is its definition's."""
    shapes: Dict[str, str] = {}
    for line in lines:
        m = _DEF_RE.match(line)
        if m is None:
            continue
        rest = line[m.end():]
        if rest.startswith("("):        # tuple shape: up to its closing paren
            shapes[m.group(1)] = rest[:_close_paren(rest, 1)]
        else:
            shapes[m.group(1)] = rest.split(" ", 1)[0]
    return shapes


def parse_hlo_collectives(hlo_text: str) -> List[CollectiveOp]:
    """Every collective instruction in an HLO module text. `-done` halves
    of async pairs carry no shape/group info of their own and are
    skipped (the `-start` is the accounted op)."""
    ops: List[CollectiveOp] = []
    lines = hlo_text.splitlines()
    shapes = None
    for line in lines:
        m = _INSTR_RE.search(line)
        if m is None:
            continue
        kind = m.group("kind")
        # operand text: between the op's '(' and its matching ')'
        start = m.end()
        end = _close_paren(line, start)
        operand_text = line[start:end - 1]
        attr_text = line[end:]
        # metadata repeats the source op name; groups/pairs live in the
        # attribute tail only
        attr_text = attr_text.split("metadata=")[0]
        if kind == "collective-permute":
            pairs = parse_replica_groups(
                "".join(re.findall(r"source_target_pairs=(\{\{[0-9,{} ]*\}\})",
                                   attr_text)) or "{}")
            groups, group_size = pairs, 2
        else:
            groups = parse_replica_groups(attr_text)
            group_size = len(groups[0]) if groups else 0
        operand_bytes = _shape_bytes(operand_text)
        if not operand_bytes:
            # operands printed by name only: resolve each definition
            if shapes is None:
                shapes = _result_shapes(lines)
            operand_bytes = sum(
                _shape_bytes(shapes.get(name, ""))
                for name in _OPERAND_NAME_RE.findall(operand_text))
        ops.append(CollectiveOp(
            kind=kind,
            operand_bytes=operand_bytes,
            result_bytes=_shape_bytes(m.group("result")),
            groups=groups, group_size=group_size))
    return ops


def _mesh_axis_attribution(mesh):
    """(axis_names, shape, id->coords fn) for a jax Mesh / ProcessMesh.
    Replica-group entries are flat indices in row-major mesh-device
    order (the device assignment of a mesh-sharded jit)."""
    jmesh = getattr(mesh, "jax_mesh", mesh)
    names = tuple(jmesh.axis_names)
    shape = tuple(jmesh.devices.shape)
    total = 1
    for d in shape:
        total *= d

    def coords(flat: int):
        if flat < 0 or flat >= total:
            return None
        c = []
        for d in reversed(shape):
            c.append(flat % d)
            flat //= d
        return tuple(reversed(c))

    return names, shape, coords


def attribute_axes(op: CollectiveOp, mesh) -> Optional[Tuple[str, ...]]:
    """The mesh axes a collective spans: axes whose coordinate varies
    within at least one replica group. None (unattributable) when any
    entry falls outside the mesh. groups=None means "every participant"
    -> every axis of size > 1."""
    names, shape, coords = _mesh_axis_attribution(mesh)
    if op.groups is None:
        return tuple(n for n, d in zip(names, shape) if d > 1) or None
    varying = set()
    for grp in op.groups:
        cs = []
        for entry in grp:
            c = coords(entry)
            if c is None:
                return None
            cs.append(c)
        for i in range(len(names)):
            if len({c[i] for c in cs}) > 1:
                varying.add(i)
    if not varying:
        return None
    return tuple(names[i] for i in sorted(varying))


class CommReport:
    """Collective traffic of ONE compiled program, attributed to mesh
    axes when a mesh is supplied."""

    def __init__(self, ops: Sequence[CollectiveOp], mesh=None):
        self.ops = list(ops)
        self.mesh_axes: Optional[Tuple[str, ...]] = None
        if mesh is not None:
            try:
                self.mesh_axes = tuple(
                    getattr(mesh, "jax_mesh", mesh).axis_names)
                for op in self.ops:
                    op.axes = attribute_axes(op, mesh)
            except Exception:
                self.mesh_axes = None

    # ---- aggregates ------------------------------------------------------
    @property
    def payload_bytes(self) -> int:
        return sum(op.payload_bytes for op in self.ops)

    def op_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for op in self.ops:
            out[op.kind] = out.get(op.kind, 0) + 1
        return out

    def bytes_per_axis(self) -> Dict[str, int]:
        """{axis label: payload bytes} — compound labels ("data+model")
        for fused multi-axis collectives, UNATTRIBUTED for groups that
        don't fit the mesh (or when no mesh was given)."""
        out: Dict[str, int] = {}
        for op in self.ops:
            k = op.axis_label
            out[k] = out.get(k, 0) + op.payload_bytes
        return out

    def counts_per_axis(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for op in self.ops:
            k = op.axis_label
            out[k] = out.get(k, 0) + 1
        return out

    def to_dict(self) -> dict:
        return {"payload_bytes": self.payload_bytes,
                "op_counts": self.op_counts(),
                "bytes_per_axis": self.bytes_per_axis(),
                "counts_per_axis": self.counts_per_axis(),
                "mesh_axes": list(self.mesh_axes) if self.mesh_axes else None,
                "ops": [op.to_dict() for op in self.ops]}

    def __repr__(self):
        return (f"CommReport(payload_bytes={self.payload_bytes}, "
                f"per_axis={self.bytes_per_axis()})")


def _default_mesh():
    """The ambient hybrid mesh (mesh_scope override, else the fleet.init
    singleton) — the mesh whose axes the program was sharded over in
    every in-tree path."""
    try:
        from ..distributed.fleet.mpu import current_mesh
        return current_mesh()
    except Exception:
        return None


def compiled_comm(compiled, mesh=None) -> CommReport:
    """CommReport of a `jax.stages.Compiled`. Failures degrade to an
    empty report (accounting must never break the program)."""
    if mesh is None:
        mesh = _default_mesh()
    try:
        text = compiled.as_text()
    except Exception:
        return CommReport([], mesh=None)
    try:
        return CommReport(parse_hlo_collectives(text), mesh=mesh)
    except Exception:
        return CommReport([], mesh=None)


def lowered_comm(lowered, mesh=None) -> CommReport:
    """Compile a `jax.stages.Lowered` and account its collectives (a
    disk hit with the persistent compilation cache on)."""
    return compiled_comm(lowered.compile(), mesh=mesh)


def jit_comm(fn, *args, mesh=None, static_argnums=(), donate_argnums=(),
             **kwargs) -> CommReport:
    """Account an arbitrary function: jit -> lower -> compile ->
    CommReport. `args` may be ShapeDtypeStructs (`cost.shape_structs`)."""
    import jax
    jitted = jax.jit(fn, static_argnums=static_argnums,
                     donate_argnums=donate_argnums)
    return lowered_comm(jitted.lower(*args, **kwargs), mesh=mesh)
