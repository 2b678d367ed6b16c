"""Transient-failure watchdog: bounded retries around long training runs.

The reference gets elastic recovery for free from Spark's cluster manager
(failed tasks re-run on other executors — SURVEY.md §5.3).  A TPU driver
is one process talking to devices over a transport that can drop
(preemption, coordinator restart, network): the idiomatic SPMD recovery is
checkpoint + resume, which both drivers already persist per solved λ /
per CD iteration (io/checkpoint.py).  This module supplies the missing
AUTOMATIC piece: classify an exception as transient, back off, and re-run
the training closure — which reloads the checkpoint and continues where
the crashed attempt stopped, so a retry never repeats finished work.

Classification is by message patterns: the concrete error type for a lost
device is ``XlaRuntimeError`` with a gRPC-style status prefix
("UNAVAILABLE: Socket closed", "DEADLINE_EXCEEDED", ...), and the same type
carries compile failures and out-of-memory, so the type alone decides
nothing.
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Callable, Optional, Sequence, TypeVar

from photon_ml_tpu import telemetry as telemetry_mod

T = TypeVar("T")

# gRPC-ish status markers + transport phrases that indicate the RUN may
# succeed on retry.  Deliberately NOT included: RESOURCE_EXHAUSTED /
# out-of-memory (a retry recomputes the same allocation and dies again)
# and INVALID_ARGUMENT-style programming errors.
_TRANSIENT_PATTERNS = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "CANCELLED",
    "INTERNAL",
    "socket closed",
    "connection reset",
    "connection refused",
    "transport",
    "device lost",
    "heartbeat",
    "preempted",
)

# Markers that mean a retry will deterministically fail again — they VETO
# every transient pattern above.  The last group is compile-shaped: a
# Mosaic or XLA compile failure arrives as ``XlaRuntimeError: INTERNAL:
# Mosaic failed to compile TPU kernel ...``, and recompiling the same
# program fails the same way.  Retrying it as a lost device (training) or
# answering from the host instead (serving) would let a run whose kernels
# do not build look healthy.
_NON_TRANSIENT_PATTERNS = (
    "RESOURCE_EXHAUSTED",
    "out of memory",
    "INVALID_ARGUMENT",
    "FAILED_PRECONDITION",
    "NOT_FOUND",
    "UNIMPLEMENTED",
    "mosaic",
    "compil",  # compile / compiler / compilation
    "lowering",
    "vmem",
)


@dataclasses.dataclass(frozen=True)
class Classification:
    """Why an exception was (or wasn't) judged transient: the verdict plus
    the pattern/type-name that decided it — what the watchdog logs and
    emits as a telemetry event per attempt."""

    transient: bool
    #: the matched message pattern, None when nothing matched
    matched: Optional[str] = None
    #: "interrupt" | "non_transient_pattern" | "transient_pattern" | "none"
    source: str = "none"


@dataclasses.dataclass
class RetryStats:
    """Observable retry behavior of one :func:`run_with_retries` call.

    Tests assert on this instead of timing sleeps; drivers surface it in
    their result JSON.  ``failures`` holds one dict per caught exception
    (attempt, exception type, message head, verdict, matched pattern,
    backoff seconds — backoff is None when the failure propagated)."""

    attempts: int = 0  # fn invocations started
    retries: int = 0  # sleeps taken (= transient failures retried)
    sleep_seconds: float = 0.0  # total backoff requested
    succeeded: bool = False
    gave_up: bool = False  # budget exhausted on a transient failure
    failures: list = dataclasses.field(default_factory=list)

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """How a long run reacts to transient failures.

    ``max_retries=0`` disables the watchdog (failures propagate, exactly
    the pre-watchdog behavior).  Backoff is exponential:
    ``backoff_seconds * multiplier**attempt``, capped at ``max_backoff``.
    """

    max_retries: int = 0
    backoff_seconds: float = 5.0
    backoff_multiplier: float = 2.0
    max_backoff_seconds: float = 300.0
    extra_patterns: Sequence[str] = ()
    #: "none" = the deterministic exponential above; "decorrelated" =
    #: AWS-style decorrelated jitter (sleep ~ U[base, 3·previous sleep],
    #: capped).  Parallel clients sharing one backoff schedule retry in
    #: lockstep and re-overload whatever just failed (the thundering
    #: herd — exactly the tuning orchestrator's W parallel trials after
    #: a coordinator blip); jitter decorrelates them.  The RNG is
    #: injected at run_with_retries (tests pass a seeded random.Random).
    jitter: str = "none"

    def __post_init__(self):
        if self.jitter not in ("none", "decorrelated"):
            raise ValueError(
                f"jitter must be 'none' or 'decorrelated', got "
                f"{self.jitter!r}"
            )

    def classify(self, exc: BaseException) -> Classification:
        """Verdict + the pattern that decided it (see Classification)."""
        if isinstance(exc, (KeyboardInterrupt, SystemExit)):
            # A user interrupt / deliberate exit is NEVER retryable, no
            # matter what its message says (SystemExit("UNAVAILABLE: ..")
            # from a CLI guard must not put the process back to work).
            return Classification(False, type(exc).__name__, "interrupt")
        msg = str(exc).lower()
        # Deterministic-failure markers veto everything: an
        # XlaRuntimeError carrying RESOURCE_EXHAUSTED re-runs the same
        # allocation and dies again.
        for p in _NON_TRANSIENT_PATTERNS:
            if p.lower() in msg:
                return Classification(False, p, "non_transient_pattern")
        patterns = tuple(_TRANSIENT_PATTERNS) + tuple(self.extra_patterns)
        for p in patterns:
            if p.lower() in msg:
                return Classification(True, p, "transient_pattern")
        # No marker, no retry: an XlaRuntimeError is transient only when
        # its status says so, never by its type alone.
        return Classification(False)

    def is_transient(self, exc: BaseException) -> bool:
        return self.classify(exc).transient

    def backoff(
        self,
        attempt: int,
        rng: Optional[random.Random] = None,
        previous: Optional[float] = None,
    ) -> float:
        """Seconds to sleep before retrying after failure ``attempt``.

        With ``jitter="none"`` (or no RNG supplied): the deterministic
        capped exponential.  With ``jitter="decorrelated"`` and an RNG:
        ``min(cap, U[base, 3·previous])`` where ``previous`` is the last
        delay actually slept (``base`` on the first retry) — each
        client's schedule random-walks away from its peers' instead of
        colliding at base·2^k.
        """
        if self.jitter == "decorrelated" and rng is not None:
            prev = self.backoff_seconds if previous is None else previous
            hi = max(self.backoff_seconds, 3.0 * prev)
            return min(
                self.max_backoff_seconds,
                rng.uniform(self.backoff_seconds, hi),
            )
        return min(
            self.backoff_seconds * self.backoff_multiplier**attempt,
            self.max_backoff_seconds,
        )


def run_with_retries(
    fn: Callable[[int], T],
    policy: RetryPolicy,
    logger=None,
    sleep: Callable[[float], None] = time.sleep,
    stats: Optional[RetryStats] = None,
    rng: Optional[random.Random] = None,
) -> T:
    """Run ``fn(attempt)`` until it returns, retrying transient failures.

    ``fn`` receives the attempt number (0 = first try) and MUST re-read
    its checkpoint state each call — that is what makes a retry resume
    instead of restart (the drivers' closures reload the grid / CD
    checkpointers).  Non-transient exceptions and exhausted budgets
    propagate unchanged.

    ``stats`` (a RetryStats, mutated in place) records every attempt's
    classification and backoff — tests assert on it instead of timing
    sleeps.  Each classify/backoff/give-up decision is also emitted as a
    ``watchdog.attempt`` telemetry event and counted on the
    ``watchdog_retries`` metric.

    ``rng`` drives decorrelated-jitter backoff when the policy enables
    it (``jitter="decorrelated"``); pass a seeded ``random.Random`` for
    deterministic tests.  Omitted with jitter enabled, a fresh RNG is
    created — production callers get real decorrelation by default.
    Note ``KeyboardInterrupt``/``SystemExit`` are BaseExceptions: they
    propagate without ever reaching classification, and ``classify``
    refuses them explicitly for callers that classify on their own.
    """
    tel = telemetry_mod.current()
    if stats is None:
        stats = RetryStats()
    if rng is None and policy.jitter != "none":
        rng = random.Random()
    attempt = 0
    prev_delay: Optional[float] = None
    while True:
        stats.attempts += 1
        try:
            result = fn(attempt)
        except Exception as exc:  # noqa: BLE001 — classified below
            verdict = policy.classify(exc)
            retrying = verdict.transient and attempt < policy.max_retries
            delay = (
                policy.backoff(attempt, rng=rng, previous=prev_delay)
                if retrying else None
            )
            stats.gave_up = verdict.transient and not retrying
            stats.failures.append({
                "attempt": attempt,
                "exception": type(exc).__name__,
                "message": str(exc)[:200],
                "transient": verdict.transient,
                "matched": verdict.matched,
                "source": verdict.source,
                "backoff_seconds": delay,
            })
            tel.event(
                "watchdog.attempt",
                attempt=attempt,
                outcome=(
                    "retry" if retrying
                    else "gave_up" if verdict.transient
                    else "non_transient"
                ),
                exception=type(exc).__name__,
                matched=verdict.matched,
                source=verdict.source,
                backoff_seconds=delay,
            )
            if not retrying:
                # Watchdog-fatal: the run is about to die for good —
                # freeze the event window (telemetry/recorder.py; no-op
                # without a recorder-equipped hub).
                telemetry_mod.dump_flight_recorder(
                    reason=(
                        "watchdog-fatal: "
                        f"{type(exc).__name__}: {exc}"
                    )[:300]
                )
                raise
            stats.retries += 1
            stats.sleep_seconds += delay
            prev_delay = delay
            tel.counter("watchdog_retries").inc()
            if logger is not None:
                logger.warning(
                    "transient failure (attempt %d/%d), retrying in %.1fs: "
                    "%s: %s",
                    attempt + 1, policy.max_retries, delay,
                    type(exc).__name__, exc,
                )
            sleep(delay)
            attempt += 1
        else:
            stats.succeeded = True
            if stats.retries or stats.failures:
                tel.event(
                    "watchdog.recovered",
                    attempts=stats.attempts, retries=stats.retries,
                )
            return result
