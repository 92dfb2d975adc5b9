"""Shared diagnostic type for tpu-lint (static rules AND runtime
promotions from dy2static / the collective layer).

Deliberately stdlib-only: the linter must run on a cold CPU interpreter
in CI without importing jax (no backend start-up, <60 s budget — see
ANALYSIS.md), and the runtime recorders in `paddle_tpu.jit.dy2static` /
`paddle_tpu.distributed.collective` import this module from inside the
package, so it must stay dependency-free in both directions.
"""
from __future__ import annotations

from dataclasses import dataclass, field, asdict

__all__ = ["Severity", "Diagnostic", "format_text"]


class Severity:
    """String severities (not an Enum: JSON output stays plain)."""
    ERROR = "error"
    WARNING = "warning"
    _ORDER = {ERROR: 0, WARNING: 1}

    @classmethod
    def rank(cls, sev):
        return cls._ORDER.get(sev, 99)


@dataclass
class Diagnostic:
    """One finding: rule id (A1..A5), slug (the escape-hatch token —
    `# tpu-lint: <slug>-ok` suppresses it), severity, location, message
    and a fix hint. Runtime-recorded diagnostics (dy2static purity
    promotions) use the same type so FALLBACKS.md and the CLI render
    identically."""
    rule: str
    slug: str
    severity: str
    path: str
    line: int
    message: str
    col: int = 0
    hint: str = ""
    source: str = "static"  # "static" (AST rule) | "runtime" (recorder)

    def to_dict(self):
        return asdict(self)

    def format(self):
        loc = f"{self.path}:{self.line}:{self.col}"
        head = f"{loc}: {self.severity} {self.rule}[{self.slug}] {self.message}"
        if self.hint:
            head += f"\n    hint: {self.hint}"
        return head

    def sort_key(self):
        return (self.path, self.line, self.col,
                Severity.rank(self.severity), self.rule)


def format_text(diags):
    """Render a diagnostic list the way the CLI prints it."""
    return "\n".join(d.format() for d in
                     sorted(diags, key=Diagnostic.sort_key))
