"""Typed failure surface of the serving engine.

Every way the engine can refuse or lose work has a distinct type, so
clients and the soak harness can branch on *what* failed instead of
string-matching messages:

* `EngineOverloaded` — admission control shed the request (bounded
  queue); retry-after semantics belong to the caller.
* `TransientDeviceError` — a device/transport error the supervisor
  believes is retryable (UNAVAILABLE, connection loss). Raised internally
  and by fault injection; callers normally never see it because the
  supervisor retries it away.
* `PoisonedComputation` — a deterministic numeric failure (NaN/Inf)
  attributed to specific request(s); subclasses FloatingPointError so
  the existing `utils.nan_inf` contract (dispatch NaN hooks raise
  FloatingPointError) and the supervisor's classifier agree.
* `EngineFailure` — the engine hit an unrecoverable error and drained
  to `snapshot` (see SERVING.md "Failure semantics"); a fresh engine
  resumes from it via `ServingEngine.from_snapshot`.
* `SnapshotVersionError` — a snapshot's schema `version` stamp does not
  match what this engine build writes. Resume and fleet migration must
  fail LOUD on it: silently reinterpreting an old schema would resume
  garbage (wrong deadlines, dropped tokens) instead of crashing.

* `UnsupportedFeature` — a feature COMBINATION this build refuses by
  policy (see `FEATURE_CONFLICTS`, the central capability table).
  Subclasses ValueError so pre-existing callers catching the untyped
  constructor refusals keep working.

Fleet-level errors (replica supervision, routing, tenant fairness) live
in `serving.fleet.errors` — they are failures of the layer ABOVE the
engine.
"""
from __future__ import annotations

from typing import Optional

__all__ = ["EngineOverloaded", "TransientDeviceError",
           "PoisonedComputation", "EngineFailure",
           "SnapshotVersionError", "UnsupportedFeature",
           "FEATURE_CONFLICTS", "check_feature_conflicts"]


class EngineOverloaded(RuntimeError):
    """Admission refused: the bounded waiting queue is full."""

    def __init__(self, msg: str, queue_depth: int = 0,
                 max_queue_len: int = 0):
        super().__init__(msg)
        self.queue_depth = queue_depth
        self.max_queue_len = max_queue_len


class TransientDeviceError(RuntimeError):
    """A retryable device/transport failure (UNAVAILABLE-class)."""


class PoisonedComputation(FloatingPointError):
    """NaN/Inf attributed to a specific computation; `request_ids`
    carries the quarantine targets when the engine can attribute it."""

    def __init__(self, msg: str, request_ids=()):
        super().__init__(msg)
        self.request_ids = tuple(request_ids)


class SnapshotVersionError(ValueError):
    """Snapshot schema mismatch: refuse to resume/migrate it. Subclasses
    ValueError so pre-existing callers that caught the untyped rejection
    keep working; `found` / `expected` carry the version stamps."""

    def __init__(self, msg: str, found=None, expected=None):
        super().__init__(msg)
        self.found = found
        self.expected = expected


class UnsupportedFeature(ValueError):
    """A feature combination this build refuses (capability table hit).
    `features` carries the conflicting pair so callers/routers can
    branch on WHAT conflicted instead of string-matching the reason."""

    def __init__(self, msg: str, features=()):
        super().__init__(msg)
        self.features = tuple(sorted(features))


# The central capability table (ROADMAP item 4): every pairwise feature
# conflict the engine refuses, in ONE place, as
# {frozenset({feature_a, feature_b}): reason}. Feature names are the
# vocabulary `ServingEngine.__init__` derives from its kwargs:
#
#   proposer          speculative decoding (serving.spec)
#   multi_step_decode decode_steps > 1 (ISSUE 13)
#   lora              multi-LoRA adapter serving (ISSUE 15)
#   tensor_parallel   mesh with model-axis degree > 1 (ISSUE 8)
#   host_spill        host_spill_pages > 0 (ISSUE 17)
#   no_prefix_cache   enable_prefix_cache=False
#   prefill_role      role="prefill" (ISSUE 18 disaggregation)
#   windowed_cache    the model's cache spec has a windowed layer group
#                     (models/paged.py): derived from the model, not a kwarg
#
# Adding a conflict = adding a row; the engine's single
# `check_feature_conflicts(active)` call enforces all of them. Reasons
# keep the historical phrasing ("mutually exclusive", "not supported
# yet") — callers match on those strings.
FEATURE_CONFLICTS = {
    frozenset({"multi_step_decode", "proposer"}):
        "decode_steps > 1 and a proposer are mutually exclusive: "
        "speculative verify and plain multi-step decode both multiply "
        "tokens per launch — pick one per engine",
    frozenset({"lora", "proposer"}):
        "lora and a proposer are mutually exclusive: the verify "
        "program has no adapter path (pick one per engine)",
    frozenset({"lora", "tensor_parallel"}):
        "lora under tensor parallelism is not supported yet: the "
        "adapter pools/stacks carry no sharding specs (run lora "
        "engines at tp=1)",
    frozenset({"host_spill", "tensor_parallel"}):
        "host spill under tensor parallelism is not supported yet: "
        "page gathers would fetch every shard through the host (run "
        "spill engines at tp=1)",
    frozenset({"host_spill", "no_prefix_cache"}):
        "host_spill_pages needs the radix cache: the spill tier lives "
        "UNDER it (enable_prefix_cache=True)",
    frozenset({"prefill_role", "proposer"}):
        "a prefill-role engine and a proposer are mutually exclusive: "
        "speculative decoding only pays on the decode side, which a "
        "prefill-role engine hands off before reaching",
    frozenset({"prefill_role", "multi_step_decode"}):
        "a prefill-role engine and decode_steps > 1 are mutually "
        "exclusive: multi-step decode only pays on the decode side, "
        "which a prefill-role engine hands off before reaching",
    frozenset({"prefill_role", "no_prefix_cache"}):
        "a prefill-role engine needs the radix cache: handoff ships "
        "the prefilled KV out of the donated radix prefix "
        "(enable_prefix_cache=True)",
    # a windowed layer group gives a row's pages back as the row
    # advances (kv_cache.py `WindowGroup`): what needs them back later,
    # or a table of them another program's way, is refused until it is
    # written and tested
    frozenset({"windowed_cache", "proposer"}):
        "a proposer over a model with a windowed layer group is not "
        "supported yet: a rejected draft rolls the row back past pages "
        "the window already gave back",
    frozenset({"windowed_cache", "multi_step_decode"}):
        "decode_steps > 1 over a model with a windowed layer group is "
        "not supported yet: the K-step launch reserves K slots ahead and "
        "its scan has no window-release points",
    frozenset({"windowed_cache", "host_spill"}):
        "host spill over a model with a windowed layer group is not "
        "supported yet: such a model donates no prefix, so the radix "
        "cache the spill tier lives under holds nothing",
    frozenset({"windowed_cache", "prefill_role"}):
        "a prefill-role engine over a model with a windowed layer group "
        "is not supported yet: handoff ships a donated radix prefix, and "
        "such a model donates none",
    frozenset({"windowed_cache", "tensor_parallel"}):
        "tensor parallelism over a model with a windowed layer group is "
        "not supported yet: the windowed decode kernel has no per-shard "
        "wrapper",
}


def check_feature_conflicts(active) -> None:
    """Raise the typed `UnsupportedFeature` for the first capability-
    table row fully contained in `active` (a set of feature names).
    Rows are checked in a deterministic order so the same kwargs always
    produce the same refusal."""
    active = frozenset(active)
    for pair in sorted(FEATURE_CONFLICTS, key=sorted):
        if pair <= active:
            raise UnsupportedFeature(FEATURE_CONFLICTS[pair],
                                     features=pair)


class EngineFailure(RuntimeError):
    """Unrecoverable engine error. `snapshot` is the serializable
    drain state (queued + preempted + in-flight requests)."""

    def __init__(self, msg: str, snapshot: Optional[dict] = None,
                 cause: Optional[BaseException] = None):
        super().__init__(msg)
        self.snapshot = snapshot
        self.cause = cause
