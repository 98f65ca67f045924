"""Deterministic fault injection for the feeder→device→flush path.

The reference proves its ingest survives agent disconnects, ingester
restarts and backpressure by running them in anger; a reproduction
needs the same proof in CI, which means faults that are *scriptable
per step* and replay identically under a fixed seed. This module is
that harness:

  * a `FaultPlan` holds `FaultRule`s keyed by **site** — the named
    seams the production code already has (device dispatch, host
    fetch, feeder decode, journal/checkpoint I/O, sink writes);
  * production seams call `chaos.maybe_fail(site)`, a no-op (one
    global read) unless a plan is installed, so the fault surface
    costs nothing in steady state;
  * rules fire on exact per-site call indices (`at=(3, 7)`), windows
    (`start/count/every`), or a seeded probability (`p=`), so every
    scenario — "the 4th dispatch throws RESOURCE_EXHAUSTED twice" —
    reproduces bit-for-bit;
  * `KillPoint` derives from BaseException: it models *process death*
    and deliberately pierces every containment layer (retry loops and
    quarantine guards catch Exception only), so recovery tests can
    kill a pipeline mid-flush and rebuild from journal + checkpoint.

Frame-corruption helpers (`truncate_frame` / `bitflip_frame`) cover
the fault class that arrives as bytes rather than exceptions.

THE SEAM LIST (ISSUE 15 satellite — the named seams have grown across
r11/r15/r18 and were only discoverable by grep; this table is the one
place that enumerates them). Every seam is a `chaos.maybe_fail(site)`
call in production code; the "fires in" column is the exact module:

    site              fires in                          covers
    ----------------  --------------------------------  -----------------------------
    device.dispatch   aggregator/window.py,             fused-step dispatch (single-
                      parallel/sharded.py               chip AND sharded)
    host.fetch        aggregator/window.py,             device→host fetch (the
                      parallel/sharded.py               ≤3-fetch budget's seam)
    feeder.decode     feeder/runtime.py                 sink codec decode (poisoned-
                      (FrameCodecBase.decode_frame)     frame quarantine boundary)
    sink.write        storage/writer.py                 TableWriter → store.insert
    checkpoint.io     aggregator/checkpoint.py          window-state snapshot write
    journal.io        feeder/journal.py                 frame-journal append/rotate
    handoff.send      ingest/handoff.py                 misroute-handoff transport
                      (HandoffSender peer loop)         write (ISSUE 15: scripted
                                                        transport loss)
    rebalance.step    parallel/rebalance.py             each protocol step of a
                      (GroupRebalancer release/adopt)   shard-group handover
                                                        (ISSUE 15: mid-protocol
                                                        death via KillPoint)
"""

from __future__ import annotations

import dataclasses
import random
import threading
from contextlib import contextmanager

from ..utils.retry import TransientError

# ---------------------------------------------------------------------------
# fault sites — the seams production code exposes to the plan

SITE_DISPATCH = "device.dispatch"  # fused-step dispatch (window + sharded)
SITE_FETCH = "host.fetch"  # device→host fetch (WindowManager._fetch seam)
SITE_DECODE = "feeder.decode"  # sink codec decode (quarantine boundary)
SITE_SINK_WRITE = "sink.write"  # storage TableWriter → store.insert
SITE_CHECKPOINT_IO = "checkpoint.io"  # window-state snapshot write
SITE_JOURNAL_IO = "journal.io"  # frame-journal append/rotate
SITE_HANDOFF_SEND = "handoff.send"  # misroute-handoff transport write
SITE_REBALANCE_STEP = "rebalance.step"  # shard-group handover protocol step
SITE_WIRE_SEND = "wire.send"  # DFPUSH publisher result/alert upload write

FAULT_SITES = (
    SITE_DISPATCH,
    SITE_FETCH,
    SITE_DECODE,
    SITE_SINK_WRITE,
    SITE_CHECKPOINT_IO,
    SITE_JOURNAL_IO,
    SITE_HANDOFF_SEND,
    SITE_WIRE_SEND,
    SITE_REBALANCE_STEP,
)


# ---------------------------------------------------------------------------
# fault classes

class InjectedFault(Exception):
    """Base marker for every chaos-raised failure."""


class TransientDeviceError(TransientError, InjectedFault):
    """RESOURCE_EXHAUSTED-style admission failure: the dispatch never
    started; the retry policy may re-issue it."""


class FetchTimeout(TransientError, InjectedFault):
    """host_fetch deadline blown (a device→host transfer stalling);
    retryable — the device handle is still valid."""


class DeviceLost(InjectedFault):
    """Non-transient device failure: retrying is unsound (donated
    buffers may be consumed); containment must degrade instead."""


class SinkWriteError(InjectedFault, OSError):
    """Storage/sink write failure — OSError so the TableWriter's
    existing transient-retry loop exercises its real path."""


class CheckpointIOError(InjectedFault, OSError):
    """Checkpoint snapshot I/O failure (disk full, volume gone)."""


class KillPoint(BaseException):
    """Simulated process death. BaseException on purpose: retry and
    quarantine guards catch Exception, so a KillPoint rips straight
    through to the test driver exactly like SIGKILL would — nothing
    in-process may 'handle' its own death."""


class RebalanceAbortError(Exception):
    """A shard-group handover (parallel/rebalance.py) could not
    complete: quiesce never drained, the barrier checkpoint aborted, a
    concurrent rebalance holds the single-flight guard, or a scripted
    fault at the `rebalance.step` seam. Part of the fault classes so
    CI can inject it mid-protocol; also raised by the real protocol —
    the old owner keeps serving the group, nothing has moved."""


# ---------------------------------------------------------------------------
# rules + plan


@dataclasses.dataclass
class FaultRule:
    """Fires `error` at matching per-site call indices (0-based).

    `at`: explicit index tuple (wins over start/count/every).
    `start/count/every`: fire `count` times, at indices start,
    start+every, … . `p`: instead of index matching, fire with
    probability p per call (seeded by the plan — deterministic),
    still bounded by `count`.
    """

    site: str
    error: type | BaseException = TransientDeviceError
    at: tuple[int, ...] | None = None
    start: int = 0
    count: int = 1
    every: int = 1
    p: float | None = None

    def _matches(self, n: int, fired: int, rng: random.Random) -> bool:
        if fired >= self.count and self.at is None:
            return False
        if self.at is not None:
            return n in self.at
        if self.p is not None:
            return n >= self.start and rng.random() < self.p
        return n >= self.start and (n - self.start) % max(1, self.every) == 0

    def _make(self) -> BaseException:
        if isinstance(self.error, BaseException):
            return self.error
        return self.error(f"injected fault at {self.site}")


class FaultPlan:
    """A seeded, scriptable fault schedule over the named sites.

    Thread-safe (the feeder pump, writer flusher and collector tick all
    cross seams concurrently). Per-site call counts and injection
    counts are exposed for test assertions; `calls`/`injected` survive
    uninstall so a finished scenario can still be audited.
    """

    def __init__(self, seed: int = 0, rules: list[FaultRule] | None = None):
        self.seed = seed
        self.rules = list(rules or ())
        self.calls: dict[str, int] = {}
        self.injected: dict[str, int] = {}
        self._rng = random.Random(seed)
        self._fired: dict[int, int] = {}  # id(rule) → times fired
        self._lock = threading.Lock()

    def add(self, *rules: FaultRule) -> "FaultPlan":
        self.rules.extend(rules)
        return self

    def fire(self, site: str) -> None:
        """Count one call at `site`; raise if a rule matches."""
        with self._lock:
            n = self.calls.get(site, 0)
            self.calls[site] = n + 1
            for rule in self.rules:
                if rule.site != site:
                    continue
                fired = self._fired.get(id(rule), 0)
                if rule._matches(n, fired, self._rng):
                    self._fired[id(rule)] = fired + 1
                    self.injected[site] = self.injected.get(site, 0) + 1
                    raise rule._make()


# ---------------------------------------------------------------------------
# the global hook production seams consult

_active: FaultPlan | None = None


def install(plan: FaultPlan) -> FaultPlan:
    global _active
    _active = plan
    return plan


def uninstall() -> None:
    global _active
    _active = None


@contextmanager
def active(plan: FaultPlan):
    install(plan)
    try:
        yield plan
    finally:
        uninstall()


def maybe_fail(site: str) -> None:
    """THE seam: free when no plan is installed."""
    plan = _active
    if plan is not None:
        plan.fire(site)


# ---------------------------------------------------------------------------
# byte-level corruption (the decode fault class arrives as data)


def truncate_frame(raw: bytes, rng: random.Random) -> bytes:
    """Cut a frame at a random interior point (1 ≤ cut < len)."""
    if len(raw) < 2:
        return raw[:0]
    return raw[: rng.randrange(1, len(raw))]


def bitflip_frame(raw: bytes, rng: random.Random, flips: int = 4) -> bytes:
    """Flip `flips` random bits anywhere in the frame."""
    buf = bytearray(raw)
    if not buf:
        return bytes(buf)
    for _ in range(flips):
        i = rng.randrange(len(buf))
        buf[i] ^= 1 << rng.randrange(8)
    return bytes(buf)
