"""Bounded retry with exponential backoff + jitter — the transient-
failure policy shared by the device dispatch, host-fetch and transport
paths (ISSUE 6).

The reference retries transient infrastructure errors everywhere it
talks to something that can hiccup (ckwriter reconnect+retry,
uniform_sender failover, grpc session redial) and treats everything
else as fatal-but-contained. This module is that policy as one
function: classify, back off exponentially with jitter (decorrelated
retries — N feeders must not re-dial a recovering device in lockstep),
give up after a bounded number of attempts.

Retrying a DEVICE dispatch is only sound when the failure pre-empted
the call: the fused steps donate their accumulator buffers, so an
error thrown mid-execution leaves the donated input consumed. The
transient classification therefore covers admission-time failures —
RESOURCE_EXHAUSTED-style allocator rejections, queue-full, timeouts —
plus the chaos module's injected faults (which always fire before the
real call); a mid-flight device loss is NOT transient and surfaces to
the containment layer (feeder degraded mode) instead. Because the
runtime reports both kinds through message substrings, there are TWO
classifiers: is_transient (fetch/transport — no donation, the broad
marker set applies) and is_dispatch_transient (donated-buffer
dispatch — admission-time codes only).
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import random
import time

_rng_seq = itertools.count()


def decorrelated_rng(tag: int) -> random.Random:
    """Jitter rng for one retrying instance: seeded from a caller tag,
    the pid and a process-wide instance counter, so N managers (or N
    processes) backing off against one recovering device never share a
    jitter stream — identical streams re-dial in lockstep, the exact
    thundering herd the jitter exists to break."""
    return random.Random((tag << 40) ^ (os.getpid() << 20) ^ next(_rng_seq))

# Substrings of runtime error text treated as transient. XLA runtime
# errors carry their absl status code in the message; these are the
# codes that mean "the device may accept the same call shortly".
TRANSIENT_ERROR_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "DEADLINE_EXCEEDED",
    "UNAVAILABLE",
    "ABORTED",
)


class TransientError(Exception):
    """Failures that are retryable by construction (admission-time:
    the operation never started). The chaos module's transient fault
    classes subclass this."""


def is_transient(exc: BaseException) -> bool:
    """The shared retry classification: our TransientError classes,
    plus runtime errors whose status code says try-again. For
    donated-buffer DISPATCH calls use is_dispatch_transient instead."""
    if isinstance(exc, TransientError):
        return True
    if isinstance(exc, Exception):
        msg = str(exc)
        return any(m in msg for m in TRANSIENT_ERROR_MARKERS)
    return False


# Dispatch-only markers: UNAVAILABLE/ABORTED can be a MID-FLIGHT
# device loss, after the step consumed its donated accumulator — a
# retry would then fail on a deleted array and mask the real error.
# Only codes that by construction reject the call at admission time
# (allocator/queue rejections, deadline before launch) are safe.
DISPATCH_TRANSIENT_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "DEADLINE_EXCEEDED",
)


def is_dispatch_transient(exc: BaseException) -> bool:
    """Admission-time-only classification for the donated-buffer
    dispatch paths: our TransientError classes (the chaos seam fires
    before the real call) plus admission-time status codes. The fetch
    path keeps the broader is_transient — a blown fetch deadline
    leaves the device handle valid."""
    if isinstance(exc, TransientError):
        return True
    if isinstance(exc, Exception):
        msg = str(exc)
        return any(m in msg for m in DISPATCH_TRANSIENT_MARKERS)
    return False


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """attempts = TOTAL tries (1 = no retry). Delay for retry k
    (k=1..attempts-1) is min(base * multiplier**(k-1), max) scaled by a
    uniform jitter in [1-jitter, 1]."""

    attempts: int = 4
    base_delay_s: float = 0.005
    max_delay_s: float = 0.5
    multiplier: float = 2.0
    jitter: float = 0.5

    def delay(self, attempt: int, rng: random.Random) -> float:
        # clamp the exponent before exponentiating: callers feed
        # unbounded failstreaks in here (serve()'s crash-loop guard),
        # and float ** raises OverflowError past ~2.0**1024 — the
        # min() with max_delay_s saturates the result long before 64
        # doublings for any sane policy, so the cap never changes it
        d = min(self.base_delay_s * self.multiplier ** min(attempt - 1, 64),
                self.max_delay_s)
        return d * (1.0 - self.jitter * rng.random())


def retry_call(
    fn,
    policy: RetryPolicy = RetryPolicy(),
    *,
    classify=is_transient,
    on_retry=None,
    rng: random.Random | None = None,
    sleep=time.sleep,
):
    """Call `fn()`; on a transient failure, back off and retry up to
    policy.attempts total tries. Non-transient errors (and BaseException
    kill-points from the chaos harness) propagate immediately —
    containment above this layer decides what survives. `on_retry(k,
    exc)` fires before each retry so owners can count them."""
    rng = rng if rng is not None else random
    last = None
    for attempt in range(1, max(1, policy.attempts) + 1):
        try:
            return fn()
        except Exception as exc:
            if attempt > policy.attempts - 1 or not classify(exc):
                raise
            last = exc
            if on_retry is not None:
                on_retry(attempt, exc)
            sleep(policy.delay(attempt, rng))
    raise last  # pragma: no cover - loop always returns or raises
