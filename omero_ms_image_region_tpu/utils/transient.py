"""Fault-tolerance primitives: transient-error classification, request
deadlines, circuit breaking and op-aware retry policies.

A device runtime that sits behind a connection can surface mid-compile
and mid-transfer drops as ``jax.errors.JaxRuntimeError`` with INTERNAL
or UNAVAILABLE status and a transport-level message.  The program being
launched is fine; re-dispatching succeeds.  A locally attached chip
does not produce these in healthy operation, so a single retry is safe
everywhere; every retry is counted
(``imageregion_retries_total{op=...}``), and a run on the current chip
that counts none is the evidence for deleting this path (ROADMAP D2).

Deterministic failures — shape errors, tracer leaks,
RESOURCE_EXHAUSTED (HBM OOM) — carry other statuses/types and are NOT
retried.

The check is name-based so device-free processes (frontend proxies) can
import this module without pulling in jax.

On top of that classification this module carries the serving chain's
shared resilience state (the reference leaned on Vert.x supervisor
restarts and bounded event-loop backpressure; these are the TPU build's
equivalents, used by ``server.sidecar`` / ``server.batcher``):

* **Deadlines** — a per-request budget in a ``contextvars`` context.
  ``server.app`` opens the scope, the sidecar wire carries the
  remaining budget, and queued work whose budget is already spent is
  cancelled cooperatively instead of rendered for nobody.
* **CircuitBreaker** — consecutive-failure breaker with a half-open
  probe, so a dead sidecar fails calls fast instead of each request
  paying the full connect-timeout + retry ladder.
* **RetryPolicy** — capped exponential backoff + jitter, applied ONLY
  to idempotent ops; ``plane_put`` (a state-changing upload) is never
  auto-retried.
"""

from __future__ import annotations

import contextvars
import logging
import random
import threading
import time
from contextlib import contextmanager
from typing import Callable, Optional, TypeVar

logger = logging.getLogger(__name__)

T = TypeVar("T")

# Substrings of transient transport statuses (matched case-insensitively
# — strerror text capitalizes "Connection reset by peer"/"Broken pipe").
# Bare status names are too broad on their own: INTERNAL also tags
# compiler bugs, and UNAVAILABLE also tags a persistently dead/detached
# device ("device unavailable"), which a retry would only delay — and
# double-dispatch against.  Both therefore require a transport-flavored
# detail alongside the status.
_TRANSPORT_DETAILS = (
    "read body",
    "response body closed",
    "connection reset",
    "broken pipe",
    "socket closed",
    "transport closed",
    "connection refused",
    "connection closed",
    # gRPC transient texts that carry no socket-level detail.
    "failed to connect",
    "goaway",
    "keepalive",
)


def is_transient_device_error(exc: BaseException) -> bool:
    """True when ``exc`` is a device-runtime error whose message says
    the TRANSPORT (not the program, and not the device itself)
    failed."""
    if type(exc).__name__ not in ("JaxRuntimeError", "XlaRuntimeError"):
        return False
    msg = str(exc).lower()
    return any(marker in msg for marker in _TRANSPORT_DETAILS)


def retry_transient(fn: Callable[[], T], what: str = "device call",
                    backoff_s: float = 2.0) -> T:
    """Run ``fn``; on a transient transport error, retry ONCE after a
    short backoff.  Anything else (including a second transient
    failure) propagates."""
    try:
        return fn()
    except Exception as exc:
        if not is_transient_device_error(exc):
            raise
        logger.warning("%s hit a transient device transport error; "
                       "retrying once: %s", what, exc)
        # Counted (imageregion_retries_total{op=<what>}) so a run can
        # say from the server's own series whether this ever fired.
        from . import telemetry
        telemetry.RESILIENCE.count_retry(what)
        time.sleep(backoff_s)
        return fn()


# ------------------------------------------------------------- deadlines

class DeadlineExceededError(Exception):
    """The request's time budget is spent (maps to HTTP 504).

    Raised COOPERATIVELY — at pipeline entry, at batcher dispatch pop,
    and on the sidecar wire — never by interrupting running device
    work (a launched XLA program cannot be cancelled anyway)."""


# Absolute time.monotonic() deadline of the current request, or None.
# Set by server.app at request entry; the sidecar wire carries the
# REMAINING budget so the device process re-anchors against its own
# clock (wall clocks never cross the wire).
_DEADLINE: contextvars.ContextVar[Optional[float]] = \
    contextvars.ContextVar("imageregion_deadline", default=None)


@contextmanager
def deadline_scope(budget_ms: Optional[float]):
    """Give the current context ``budget_ms`` of budget from now.
    ``None``/``0`` opens an unbounded scope (explicitly clearing any
    inherited deadline — a detached task must not inherit its spawning
    request's budget)."""
    deadline = (time.monotonic() + budget_ms / 1000.0
                if budget_ms else None)
    token = _DEADLINE.set(deadline)
    try:
        yield deadline
    finally:
        _DEADLINE.reset(token)


def set_task_deadline(budget_ms: Optional[float]) -> None:
    """Give the CURRENT task's context ``budget_ms`` of budget from
    now.  Wire semantics, unlike ``deadline_scope``'s config
    semantics: ``None`` (no header) is unbounded, but ``0`` is a
    budget that is ALREADY SPENT — a request arriving with nothing
    left must 504, not run forever.  No scope token to restore: this
    is for per-request asyncio tasks, whose context dies with them —
    a generator-scope here would only leak "created in a different
    Context" noise when the task is cancelled mid-request."""
    _DEADLINE.set(None if budget_ms is None
                  else time.monotonic() + budget_ms / 1000.0)


def clear_deadline() -> None:
    """Detach the current context from any inherited deadline (for
    long-lived tasks spawned from inside a request that must not run
    on its budget)."""
    _DEADLINE.set(None)


def deadline() -> Optional[float]:
    """The context's absolute monotonic deadline, or None."""
    return _DEADLINE.get()


def remaining_ms() -> Optional[float]:
    """Milliseconds of budget left (may be <= 0), or None (unbounded)."""
    d = _DEADLINE.get()
    if d is None:
        return None
    return (d - time.monotonic()) * 1000.0


def check_deadline(what: str = "request") -> None:
    """Cooperative cancellation point: raise when the budget is spent."""
    d = _DEADLINE.get()
    if d is not None and time.monotonic() >= d:
        raise DeadlineExceededError(f"{what}: deadline exceeded")


# -------------------------------------------------------- circuit breaker

class CircuitBreaker:
    """Consecutive-failure circuit breaker with a half-open probe.

    closed -> (``failure_threshold`` consecutive failures) -> open ->
    (``reset_after_s`` elapses) -> half-open: ONE trial call is
    admitted; its success closes the breaker, its failure re-opens it
    for another ``reset_after_s``.

    Thread-safe; the clock is injectable so tests drive state
    transitions deterministically."""

    CLOSED, HALF_OPEN, OPEN = 0, 1, 2
    _NAMES = {0: "closed", 1: "half-open", 2: "open"}

    def __init__(self, failure_threshold: int = 5,
                 reset_after_s: float = 5.0,
                 clock: Callable[[], float] = time.monotonic):
        if failure_threshold < 1:
            raise ValueError("failure_threshold must be >= 1")
        self.failure_threshold = failure_threshold
        self.reset_after_s = reset_after_s
        self._clock = clock
        self._lock = threading.Lock()
        self._failures = 0
        self._state = self.CLOSED
        self._opened_at = 0.0
        self._probing = False
        self._probe_started_at = 0.0
        self.opens = 0          # /metrics counter: closed/half -> open

    @property
    def state(self) -> int:
        with self._lock:
            return self._effective_state()

    @property
    def state_name(self) -> str:
        return self._NAMES[self.state]

    def _effective_state(self) -> int:
        # Lock held.  OPEN decays to HALF_OPEN by clock, not by a
        # background task — breakers must work in processes with no
        # event loop running.
        if (self._state == self.OPEN
                and self._clock() - self._opened_at >= self.reset_after_s):
            self._state = self.HALF_OPEN
            self._probing = False
        return self._state

    def allow(self) -> bool:
        """Whether a call may proceed now.  In half-open, one caller
        at a time holds the trial slot — but the slot EXPIRES after
        ``reset_after_s``: a probe whose caller never reported an
        outcome (cancelled mid-call, deadline fired between allow()
        and the send) must not wedge the breaker into shedding
        forever."""
        with self._lock:
            state = self._effective_state()
            if state == self.CLOSED:
                return True
            if state == self.HALF_OPEN and (
                    not self._probing
                    or self._clock() - self._probe_started_at
                    >= self.reset_after_s):
                self._probing = True
                self._probe_started_at = self._clock()
                return True
            return False

    def retry_after_s(self) -> float:
        """How long until the breaker will admit a trial call — the
        shed response's Retry-After."""
        with self._lock:
            if self._effective_state() != self.OPEN:
                return 0.0
            return max(0.0, self.reset_after_s
                       - (self._clock() - self._opened_at))

    def record_success(self) -> None:
        with self._lock:
            self._failures = 0
            self._state = self.CLOSED
            self._probing = False

    def record_failure(self) -> None:
        with self._lock:
            state = self._effective_state()
            self._failures += 1
            if state == self.HALF_OPEN or (
                    state == self.CLOSED
                    and self._failures >= self.failure_threshold):
                if self._state != self.OPEN:
                    self.opens += 1
                self._state = self.OPEN
                self._opened_at = self._clock()
                self._probing = False


# ------------------------------------------------------------ retry policy

# Sidecar ops safe to re-issue against a peer that may or may not have
# executed the original: renders and probes are pure reads, ping and
# metrics are trivially repeatable.  plane_put is NOT here — it mutates
# device-cache state and its digest verification makes a duplicate
# upload wasted wire bytes at best, so the caller decides.
IDEMPOTENT_OPS = frozenset({"image", "mask", "ping", "metrics",
                            "plane_probe",
                            # Drain surfaces: the manifest is a pure
                            # read; prestage re-stages through the
                            # digest-deduped path, so a duplicate is a
                            # no-op probe hit, never double state.
                            "shard_manifest", "prestage",
                            # Fleet-global byte tier: presence probe
                            # and byte read are pure reads.  byte_put
                            # (the peer write-back) is NOT here — like
                            # plane_put it mutates cache state, and a
                            # blind re-send is wasted wire bytes at
                            # best; the caller decides.
                            "byte_probe", "byte_fetch",
                            # Cross-host federation: the manifest
                            # exchange and the gossip swap are pure
                            # state reads on both ends (merge is
                            # newest-ts idempotent).  shard_transfer
                            # is NOT here — it ships cache state, the
                            # plane_put posture.
                            "manifest_hello", "member_gossip",
                            # Two-phase epoch rolls are idempotent BY
                            # CONTRACT (a re-propose re-acks the same
                            # pending manifest; a re-commit of the
                            # active epoch answers already-active), so
                            # a coordinator may retry them across a
                            # flaky link without double-rolling.  The
                            # partition op sets/clears absolute rules
                            # — a duplicate is a no-op, and the HEAL
                            # call must survive a lossy drill link.
                            "epoch_propose", "epoch_commit",
                            "partition"})


class RetryPolicy:
    """Capped exponential backoff + jitter for idempotent ops.

    ``rng`` is injectable so tests (and the seeded chaos harness) get
    deterministic backoff sequences."""

    def __init__(self, max_attempts: int = 3,
                 base_backoff_s: float = 0.025,
                 max_backoff_s: float = 1.0,
                 jitter: float = 0.5,
                 rng: Optional[random.Random] = None):
        if max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        self.max_attempts = max_attempts
        self.base_backoff_s = base_backoff_s
        self.max_backoff_s = max_backoff_s
        self.jitter = jitter
        self._rng = rng or random.Random()

    def attempts_for(self, op: str) -> int:
        """How many total attempts ``op`` gets: the full ladder for
        idempotent ops, exactly one for anything state-changing."""
        return self.max_attempts if op in IDEMPOTENT_OPS else 1

    def backoff_s(self, attempt: int) -> float:
        """Sleep before attempt ``attempt + 1`` (attempt is 0-based):
        ``base * 2^attempt`` capped at ``max``, plus up to ``jitter``
        of itself so a burst of failed requests does not retry in
        lockstep."""
        backoff = min(self.base_backoff_s * (2 ** attempt),
                      self.max_backoff_s)
        return backoff * (1.0 + self.jitter * self._rng.random())
