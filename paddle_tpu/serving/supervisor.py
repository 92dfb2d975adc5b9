"""Step supervisor: failure classification + retry policy for compiled
engine launches.

A wedged device returns UNAVAILABLE for minutes and a connection to a
remote device can drop and come back, so the engine treats every
compiled-step launch as fallible and sorts failures into three bins:

* **transient** — UNAVAILABLE / connection-class transport
  errors (and the typed `TransientDeviceError` the fault harness
  raises). Retried in place with capped exponential backoff; the batch
  re-runs bit-identically because launches are idempotent (a chunk or
  decode step rewrites the same K/V at the same positions, and the
  engine draws each launch's RNG key BEFORE the supervised call).
* **poison** — deterministic numeric failure (FloatingPointError, i.e.
  the `utils.nan_inf` dispatch-hook contract, incl. the typed
  `PoisonedComputation`). Retrying cannot help; the engine quarantines
  the offending request(s) and keeps the rest of the batch alive.
* **fatal** — everything else (deterministic OOM/INVALID_ARGUMENT, a
  program the chip's compiler refuses, exhausted retries). The engine drains to a snapshot and raises
  `EngineFailure`.

Classification is by exception type first, then by status-code markers
in the message — the same markers jaxlib's XlaRuntimeError carries, so
no import of jaxlib internals is needed.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

from .errors import PoisonedComputation, TransientDeviceError

__all__ = ["classify_failure", "RetryPolicy", "StepSupervisor",
           "TRANSIENT", "POISON", "FATAL"]

TRANSIENT = "transient"
POISON = "poison"
FATAL = "fatal"

# Status-code markers of retryable transport failures. DEADLINE_EXCEEDED
# and the socket strings cover a connection dying mid-call;
# RESOURCE_EXHAUSTED (device OOM) is deliberately NOT here —
# re-launching the identical program re-OOMs deterministically.
_TRANSIENT_MARKERS = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
                      "connection reset", "connection refused",
                      "socket closed", "Connection reset")


def classify_failure(exc: BaseException) -> str:
    """Sort an exception from a compiled-step launch into
    transient / poison / fatal. An exception that carries its own
    `failure_class` attribute (the fleet transport's typed
    `TransportError`, ISSUE 14) is believed verbatim — the raiser
    knows whether a retry can help better than a message heuristic
    does — as long as it names one of the three bins."""
    own = getattr(exc, "failure_class", None)
    if own in (TRANSIENT, POISON, FATAL):
        return own
    if isinstance(exc, (PoisonedComputation, FloatingPointError)):
        return POISON
    if isinstance(exc, TransientDeviceError):
        return TRANSIENT
    msg = str(exc)
    if any(m in msg for m in _TRANSIENT_MARKERS):
        return TRANSIENT
    return FATAL


class RetryPolicy:
    """Capped exponential backoff: delays base, base*factor, ... capped
    at `cap_s`, at most `max_retries` re-launches. `sleep` is injectable
    so tests and the soak harness never wall-clock-wait."""

    def __init__(self, max_retries: int = 3, base_s: float = 0.05,
                 factor: float = 2.0, cap_s: float = 2.0,
                 sleep: Optional[Callable[[float], None]] = None):
        self.max_retries = int(max_retries)
        self.base_s = float(base_s)
        self.factor = float(factor)
        self.cap_s = float(cap_s)
        self.sleep = sleep if sleep is not None else time.sleep

    def delay_s(self, attempt: int) -> float:
        """Backoff before retry number `attempt` (1-based)."""
        return min(self.cap_s, self.base_s * (self.factor ** (attempt - 1)))


class StepSupervisor:
    """Wraps compiled-step launches; owns the retry loop and counters.

    `run(launch)` returns the launch's result, retrying transients per
    the policy. Poison and fatal failures propagate to the engine (which
    quarantines or snapshots — those decisions need request context the
    supervisor does not have). `on_retry` is the metrics hook.

    `retryable` (optional callable) is consulted before every retry: a
    False return re-raises instead. The engine uses it for the donated-
    buffer hazard: on TPU the K/V caches are donated to the launch, and
    a dispatch that failed AFTER consuming them leaves nothing valid to
    re-pass — retrying would hit 'Array has been deleted'; failing to
    the snapshot path (which recomputes KV on resume) is the only
    correct move."""

    def __init__(self, policy: Optional[RetryPolicy] = None,
                 on_retry: Optional[Callable[[str, int], None]] = None,
                 retryable: Optional[Callable[[], bool]] = None):
        self.policy = policy or RetryPolicy()
        self.on_retry = on_retry
        self.retryable = retryable
        self.num_retries = 0
        self.last_error: Optional[BaseException] = None

    def run(self, launch: Callable, *, label: str = "step"):
        attempt = 0
        while True:
            try:
                return launch()
            except Exception as exc:                # noqa: BLE001
                self.last_error = exc
                kind = classify_failure(exc)
                if kind != TRANSIENT or attempt >= self.policy.max_retries \
                        or (self.retryable is not None
                            and not self.retryable()):
                    raise
                attempt += 1
                self.num_retries += 1
                if self.on_retry is not None:
                    self.on_retry(label, attempt)
                self.policy.sleep(self.policy.delay_s(attempt))
