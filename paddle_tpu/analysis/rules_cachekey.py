"""Rule B1 — ProgramCache key completeness.

Serving history (PRs 5/6/13/15): every config axis the engine bakes
into a compiled program as a Python constant had to be hand-added to
the program-cache key after the aliasing bit — quant config
(`kv_dtype`/`wq`), the `("tp", tp)` mesh shape, the spec-decode `K`,
the LoRA layout signature. Each omission is silent: two engines (or
one engine and the persistent CompileCache of a previous process)
share a program whose closed-over constants differ.

The rule runs per class: every `self._get_program(key, builder)` /
`self.programs.get(key, builder)` call is paired with its builder
FunctionDef (direct `self._build_x` reference or
`lambda: self._build_x(...)`; where the call sits in a method that takes
the builder as a PARAMETER — the engine's one launch path — every
builder the class hands that method), and every `self.<attr>` READ
inside the builder must ride the key. "Rides the key" is transitive
through plain `self.X = <expr>` assignments anywhere in the class — the
engine's `self._qkey` aggregate keys `kv_dtype`/`wq`/`tp`/`lora`
without naming them at the call site. Methods/properties defined in
the class body are exempt (they are code, not config), and
`# tpu-lint: cache-key-ok` acknowledges an attr that genuinely cannot
alias (e.g. `self.model` under a per-engine cache whose disk tier
fingerprints the model geometry separately).
"""
from __future__ import annotations

import ast

from . import astutil
from .diagnostics import Diagnostic, Severity
from .registry import register_rule


def _self_attrs(node):
    """Names X for every `self.X` attribute access anywhere in node."""
    out = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) \
                and isinstance(n.value, ast.Name) and n.value.id == "self":
            out.add(n.attr)
    return out


def _attr_dependencies(cls):
    """attr -> set of self-attrs its assignment(s) read, over every
    `self.X = <expr>` / `self.X += <expr>` in the class body. Feeding
    `self._qkey = (self.kv_dtype, ..., ("tp", self.tp))` through this
    map is what lets a call-site key of `(...) + self._qkey` count
    kv_dtype/wq/tp as keyed."""
    deps = {}
    for n in ast.walk(cls):
        if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = n.targets if isinstance(n, ast.Assign) \
                else [n.target]
            value = n.value
            if value is None:
                continue
            read = _self_attrs(value)
            for t in targets:
                if isinstance(t, ast.Attribute) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id == "self":
                    deps.setdefault(t.attr, set()).update(read)
    return deps


def _expand_keyed(keyed, deps):
    """Transitive closure of `keyed` through the assignment-dependency
    map (fixpoint; the map is tiny)."""
    out = set(keyed)
    changed = True
    while changed:
        changed = False
        for a in list(out):
            extra = deps.get(a, ())
            if not out.issuperset(extra):
                out.update(extra)
                changed = True
    return out


def _resolve_builder(expr, class_defs):
    """The builder FunctionDef a cache-get call will invoke, or None.
    Handles the two idioms in the tree: `lambda: self._build_x(S, P)`
    and a bare `self._build_x` reference."""
    if isinstance(expr, ast.Lambda) and isinstance(expr.body, ast.Call):
        expr = expr.body.func
    if isinstance(expr, ast.Attribute) \
            and isinstance(expr.value, ast.Name) and expr.value.id == "self":
        return class_defs.get(expr.attr)
    return None


def _builders_of(expr, fn, cls, class_defs):
    """The builder FunctionDefs a cache-get call inside method `fn` can
    invoke: the one `expr` names, or, where `expr` is a parameter of
    `fn`, what every `self.<fn>(...)` call in the class passes for it."""
    params = [a.arg for a in fn.args.args]
    if not (isinstance(expr, ast.Name) and expr.id in params):
        found = _resolve_builder(expr, class_defs)
        return [] if found is None else [found]
    pos = params.index(expr.id) - 1                          # less self
    out = []
    for n in ast.walk(cls):
        if isinstance(n, ast.Call) \
                and astutil.dotted_name(n.func) == f"self.{fn.name}":
            passed = n.args[pos] if pos < len(n.args) else next(
                (k.value for k in n.keywords if k.arg == expr.id), None)
            found = _resolve_builder(passed, class_defs)
            if found is not None:
                out.append(found)
    return out


def _cache_get_calls(cls):
    """(call, key_expr, builder_expr) for every program-cache get in
    the class: `self._get_program(key, builder)` or
    `self.programs.get(key, builder)` (the draft model's per-proposer
    cache uses the latter through its own _get_program)."""
    for n in ast.walk(cls):
        if not isinstance(n, ast.Call) or len(n.args) < 2:
            continue
        name = astutil.dotted_name(n.func) or ""
        if name.endswith("._get_program") or name.endswith(".programs.get"):
            yield n, n.args[0], n.args[1]


@register_rule(
    "B1", ("cache-key",), Severity.ERROR,
    "self.<config> read inside a program builder but absent from its "
    "ProgramCache key")
def check_cache_key(ctx):
    if ctx.is_test:
        return []
    out = []
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        class_defs = {n.name: n for n in cls.body
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef))}
        deps = None
        flagged = set()
        pairs = [(key_expr, builder)
                 for fn in class_defs.values()
                 for _, key_expr, builder_expr in _cache_get_calls(fn)
                 # a key made elsewhere marks a forwarding shim
                 # (_get_program itself): checked where the key is made
                 if not isinstance(key_expr, ast.Name)
                 for builder in _builders_of(builder_expr, fn, cls,
                                             class_defs)]
        for key_expr, builder in pairs:
            if deps is None:
                deps = _attr_dependencies(cls)
            keyed = _expand_keyed(_self_attrs(key_expr), deps)
            for node in ast.walk(builder):
                if not (isinstance(node, ast.Attribute)
                        and isinstance(node.value, ast.Name)
                        and node.value.id == "self"
                        and isinstance(node.ctx, ast.Load)):
                    continue
                attr = node.attr
                if attr in keyed or attr in class_defs \
                        or (builder.name, attr) in flagged:
                    continue
                flagged.add((builder.name, attr))
                out.append(Diagnostic(
                    rule="B1", slug="cache-key", severity=Severity.ERROR,
                    path=ctx.path, line=node.lineno, col=node.col_offset,
                    message=(f"self.{attr} is read inside program builder "
                             f"{builder.name}() but does not ride its "
                             "cache key: two engines (or a restarted "
                             "process via the persistent CompileCache) "
                             "with different values would share one "
                             "compiled program"),
                    hint=f"add self.{attr} (or an aggregate like "
                         "self._qkey that includes it) to the key tuple, "
                         "or annotate `# tpu-lint: cache-key-ok` with why "
                         "it cannot alias"))
        # `flagged`/`deps` are per-class by construction
    return out
