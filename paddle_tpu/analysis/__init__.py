"""paddle_tpu.analysis — tpu-lint: static trace-safety analysis.

An AST-based analyzer that turns the round-4 chip-landmine catalog into
enforced invariants runnable in CI on CPU (no jax import, no TPU
grant). Rule pack:

  A1  index-map   bare int literals / python `//` `%` in BlockSpec
                  index maps (i64-under-x64 + Mosaic convert recursion)
  A2  blockspec   (8, 128)-divisibility of statically-known block dims
  A3  vmem        per-pallas_call scoped-VMEM budget estimate
  A4  interpret / timing-cap
                  interpret=True shipping in non-test code; device-side
                  loops past the 512-iteration wedge cap
  A5  purity      side effects in traced cond branches and scan/while
                  bodies (static half) + runtime promotions recorded by
                  dy2static and the collective layer (purity.py)

B-series (ISSUE 19) — serving/fleet protocol & consistency:

  B1  cache-key   self.<config> read inside a ProgramCache builder but
                  absent from the cache-key derivation
  B2  protocol    mailbox message types sent without a receiver
                  dispatch arm (and dead arms), across the
                  worker/procfleet pair via `protocol-peer=` hints
  B3  fault-point fired-but-unregistered fault points; registered
                  points missing from SERVING.md's fault table
  B4  refusal     feature-conflict raises outside serving/errors.py's
                  FEATURE_CONFLICTS table (ROADMAP item 4)
  B5  metric      counters/reservoirs referenced but absent from their
                  exposition registries

CLI: tools/tpu_lint.py (`make lint`). Docs: ANALYSIS.md. Fixture
corpus: tests/lint_fixtures/ via tests/test_tpu_lint.py.

This package is stdlib-only BY CONTRACT — importing jax (or anything
that imports jax) here would start a jax backend from the lint CLI and
blow the <60 s CI budget.
"""
from .diagnostics import Diagnostic, Severity, format_text  # noqa: F401
from .registry import Rule, all_rules, select_rules  # noqa: F401
from . import purity  # noqa: F401
from . import vmem  # noqa: F401
# importing the rule modules registers them
from . import rules_index_map  # noqa: F401
from . import rules_blockspec  # noqa: F401
from . import rules_runtime  # noqa: F401
from . import rules_purity  # noqa: F401
from . import rules_cachekey  # noqa: F401
from . import rules_protocol  # noqa: F401
from . import rules_serving  # noqa: F401
from .driver import (  # noqa: F401
    FileContext, iter_python_files, lint_file, lint_paths, lint_source)

__all__ = [
    "Diagnostic", "Severity", "format_text", "Rule", "all_rules",
    "select_rules", "purity", "vmem", "FileContext", "iter_python_files",
    "lint_file", "lint_paths", "lint_source",
]
