"""Host stage-span tracer for the pipeline's self-telemetry plane.

The reference attributes latency per pipeline stage by shipping every
component's counters through its own stats pipeline (stats.go:89-202);
what it cannot see — and what the TPU build critically needs — is where
a *host-driven* batch spends its wall time. This module is that seam: a
monotonic-clock span recorder with a fixed vocabulary of stage names,
cheap enough to stay always-on, exposing three faces:

  * `summary()` — per-stage count / total / self / cpu / max / last
    aggregates and the compile lanes, for bench JSON snapshots;
  * `get_counters()` — a flat Countable field map so the tracer
    registers on `utils/stats.StatsCollector` like any component and
    its aggregates dogfood into the `deepflow_system` table;
  * `export_otlp(exporter)` — drains the recent-span ring through the
    EXISTING OTLP exporter path (server/exporters.OtlpExporter's
    l7_flow_log traces lane), parent ids included.

**Nesting.** Every tracer in the process pushes its open spans onto ONE
per-thread stack, so the feeder's spans and the pipeline's nest across
the two tracers. A span's record carries its own `span_id` and the
`parent_span_id` of the span open beneath it on that thread (a root
span opens a trace; its descendants share the `trace_id`); on exit it
adds its duration to its parent's child time, and the aggregate keeps
`self_us` = duration less what child records covered. `record()` (work
measured by the caller, split over non-contiguous sections) nests the
same way under whatever span is open on the calling thread.

**The CPU lane.** A span also reads its thread's CPU clock
(`time.thread_time_ns()`) where it reads the wall's, and the aggregate
keeps `cpu_us`: the CPU time of the span's thread between enter and
exit, children included, as `total_us` is. `total_us` − `cpu_us` is the
time the thread was not running: blocked on the device, on a lock, on
the GIL, or descheduled. Kernel time on the thread (a first-touch page
fault) is CPU: work. It is ONE thread's clock: where a pass is divided
over `utils/hostpool.py`'s workers, the span's lane holds the calling
thread's own share only (the workers open no span). `record()` takes the
lane from its caller, who measured the wall too; `cpu_us(names)` is the
one reader for whoever republishes lanes as counters.

The served path's vocabulary, as a tree (`f` = the FeederRuntime's
tracer, `p` = the pipeline's / WindowManager's; a name lives on one):

    feeder.pump                  f  one pump that drained or emitted
      feeder.drain               f  a round's queue gets
      feeder.coalesce            f  a round's journal + decode + admit
        feeder.decode            f  the round's sink.decode_frame calls,
                                    summed, ONE record a round
        feeder.dispatch          f  sink.emit of one bucket batch
          feeder.assemble        f  the chunks' writes into the staging buffer
            feeder.staging_wait  f  only when it blocked: the wait for the
                                    device to have read that buffer
          ingest.stage           p  three uploads of the staging buffer
          window.fold            p  fold dispatch when the ring is full
          ingest.dispatch        p  the fused step's dispatch
          stats.fetch            p  the per-batch counter-block sync
          window.advance         p  a close: fold + range-flush dispatch
            window.fold          p
          flush.drain            p  every ready flush entry
            flush.wait           p  scalar fetch: waits for the fold
                                    and range flush queued ahead
              flush.reserve      p  the join's destination made and
                                    touched while the device works (a
                                    drain with history and over a page)
            flush.rows           p  page dispatches and the row fetch
              flush.fetch        p  the one device_get of the pages
              flush.join         p  the host's cut, written into the
                                    reserve (else concatenated)
            flush.split          p  unpack, per-window split, sketch
                                    and tier marrying
              flush.sketch       p  sketch plane on: the drain's packed
                                    block rows unpacked and held
                flush.sketch_merge  p  sharded only: the host's merge of
                                    the devices' blocks into one a window
      feeder.dispatch            f  the pump's sub-bucket tail emit
    checkpoint.save, query.snapshot, query.cache   p  roots
    xla.compile                  a ring record (no aggregate of its
                                    own) under whichever span compiled

The sharded manager (parallel/sharded.py) records the same tree under
`feeder.dispatch`, with three differences: `window.close_collective`
(the dispatch of the cross-mesh merge of the open sketch ring, once an
advance, ahead of `ingest.stage`) is its own; its one counter sync is the
close's bundled scalar fetch, so its `stats.fetch` is a child of
`flush.wait`, once a drain and not once a batch; and `flush.sketch`
holds `flush.sketch_merge`.

A pump that drains nothing and emits nothing records no span (it counts
itself and its wall on the feeder: `idle_pumps`, `idle_pump_us`): a
starved feeder pumps ~2,000 times a second, which would turn the
4,096-record ring over in two.

**Compiles.** One process-wide listener on JAX's
`/jax/core/compile/backend_compile_duration` event (registered the
first time a span opens with `jax` imported) charges every backend
compile, persistent-cache reads included, to the innermost span open on
the compiling thread: an `xla.compile` record in that span's tracer's
ring (parent = that span, start = arrival less the duration), the
`compiles` / `compile_us` lanes of that span name's aggregate, and the
span's child time (so `self_us` excludes it). A compile with no span
open lands in the module's `unspanned_compiles()` lanes.

**Profiles.** While a span is open it holds a
`jax.profiler.TraceAnnotation(name)` (only where `jax` is already in
`sys.modules`: the tracer itself imports no JAX), so a profile an
operator takes (`jax.profiler.start_trace(dir)` … `stop_trace()`, or
`chipbench/tests/dump_trace.py`) shows these spans on the trace's own
clock, on the host thread that ran them, beside the device's lines.

`JitCacheMonitor` rides along: retrace/compile counters for one jitted
callable, read from the pjit cache size — the CI gate asserts ZERO
retraces across steady-state same-shape ingest so a shape leak (the
silent compile-per-batch failure mode) trips loudly.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import sys
import threading
import time
from collections import deque

import numpy as np

# The pipeline stage vocabulary (explicit names, ISSUE 3). Everything
# the window managers emit uses these; ad-hoc names are allowed but the
# docs/tests pin this set.
SPAN_INGEST_STAGE = "ingest.stage"  # the three uploads of a staging buffer (a FlowBatch is first written into one)
SPAN_INGEST_DISPATCH = "ingest.dispatch"  # fused jit step dispatch (async — host-side cost)
SPAN_STATS_FETCH = "stats.fetch"  # the ONE per-batch device→host stats sync
SPAN_WINDOW_ADVANCE = "window.advance"  # fold + flush_range dispatch on window close
# fold dispatch alone (capacity-triggered AND the advance's span fold) —
# nested inside window.advance when the advance fires it, so the
# fold-dominated share of drain_ms is attributable on its own (ISSUE 5;
# this is the lane the merge-fold exists to shrink)
SPAN_WINDOW_FOLD = "window.fold"
SPAN_FLUSH_DRAIN = "flush.drain"  # packed flush fetch + per-window split
# flush.drain's three phases per flush entry (ISSUE 26): the scalar
# fetch blocks until the fold and range flush queued ahead have run;
# the rows phase is where a per-document-count slice/reshape compiles
SPAN_FLUSH_WAIT = "flush.wait"
SPAN_FLUSH_ROWS = "flush.rows"
SPAN_FLUSH_SPLIT = "flush.split"
# flush.rows' two host-visible halves (ISSUE 28), where a close is
# hundreds of megabytes: the ONE device_get of a drain's pages, and the
# host's cut and concatenate of them. Neither can compile, so they stay
# out of FLUSH_SPAN_NAMES (the page dispatches are flush.rows' own).
SPAN_FLUSH_FETCH = "flush.fetch"
SPAN_FLUSH_JOIN = "flush.join"
# flush.wait's host work (PR 34): the array a drain's exact rows will be
# joined into, allocated and touched before the blocking scalar fetch,
# so its page faults are taken while the device runs the fold. Host
# NumPy only (not in FLUSH_SPAN_NAMES); absent from a drain that has no
# history to size it from or expects under one page.
SPAN_FLUSH_RESERVE = "flush.reserve"
# flush.split's sketch half (PR 33), where a closed block is tens of
# megabytes: `unpack_drained` + `_hold_sketch_blocks` of one drain. Host
# NumPy only, so it cannot compile and stays out of FLUSH_SPAN_NAMES;
# absent from a manager without the plane.
SPAN_FLUSH_SKETCH = "flush.sketch"
# what only the sharded close does (PR 36): the host's share of the
# D devices' blocks' merge into one a window, a child of flush.sketch
# (host NumPy only: since PR 39 with the pool off the candidate union
# over the devices' gathered top-K lanes, the rest merges on the
# devices; with it on `WindowSketchBlock.merge`); and the dispatch of the
# collective that merges the open sketch ring across the mesh on every
# advance (`ShardedPipeline.window_close`: lax.pmax / lax.psum). Neither
# is in PIPELINE_SPAN_NAMES: a one-chip manager has no such work.
SPAN_FLUSH_SKETCH_MERGE = "flush.sketch_merge"
SPAN_WINDOW_CLOSE_COLLECTIVE = "window.close_collective"
FLUSH_SPAN_NAMES = (
    SPAN_FLUSH_DRAIN, SPAN_FLUSH_WAIT, SPAN_FLUSH_ROWS, SPAN_FLUSH_SPLIT
)
SPAN_CHECKPOINT_SAVE = "checkpoint.save"  # window-state snapshot to .npz
# live read plane (ISSUE 10): pull-only open-window snapshot reads and
# result-cache lookups — separate names so a live dashboard's read
# latency is attributable on its own instead of hiding in flush.drain
SPAN_QUERY_SNAPSHOT = "query.snapshot"  # snapshot_open: fold + 2-fetch read
SPAN_QUERY_CACHE = "query.cache"  # result-cache lookup (hit or miss)
# a backend compile seen by the process-wide listener: a ring record
# under the span that compiled, never an aggregate of its own
SPAN_XLA_COMPILE = "xla.compile"

# Feeder-runtime stages (ISSUE 4) — emitted by feeder/runtime.py on its
# own tracer; NOT in PIPELINE_SPAN_NAMES (a pipeline can run feederless,
# and the pinned vocabulary must stay satisfiable by a bare pipeline).
SPAN_FEEDER_PUMP = "feeder.pump"  # one pump that drained or emitted (root)
SPAN_FEEDER_DRAIN = "feeder.drain"  # a round's queue gets
SPAN_FEEDER_COALESCE = "feeder.coalesce"  # journal + decode + bucket assembly
SPAN_FEEDER_DECODE = "feeder.decode"  # a round's decode_frame calls, summed
SPAN_FEEDER_DISPATCH = "feeder.dispatch"  # staged batch → sink ingest
SPAN_FEEDER_ASSEMBLE = "feeder.assemble"  # a batch's chunks written into its staging buffer, the stale tail zeroed
# feeder.assemble's wait for the step that read the buffer it is about to
# write (PR 38): recorded only when it blocked, so its count is the
# feeder's `staging_waits`. On one chip the per-batch stats.fetch syncs
# first and it never blocks; the sharded manager has no per-batch sync,
# so there it is the feed's backpressure.
SPAN_FEEDER_STAGING_WAIT = "feeder.staging_wait"
FEEDER_SPAN_NAMES = (
    SPAN_FEEDER_PUMP,
    SPAN_FEEDER_DRAIN,
    SPAN_FEEDER_COALESCE,
    SPAN_FEEDER_DECODE,
    SPAN_FEEDER_DISPATCH,
    SPAN_FEEDER_ASSEMBLE,
    SPAN_FEEDER_STAGING_WAIT,
)

# Push query plane (ISSUE 11) — emitted by querier/subscribe.py and
# querier/alerts.py on their own tracers; also not pipeline vocabulary
# (a pipeline can run with no standing queries). One span per
# subscription/rule evaluation, so fan-out latency (flush → watcher
# delivery) is attributable separately from the pull path's
# query.snapshot/query.cache lanes.
SPAN_SUBSCRIPTION_EVAL = "subscribe.eval"  # one shared eval serving N watchers
SPAN_ALERT_EVAL = "alert.eval"  # rule query + state-machine step

PIPELINE_SPAN_NAMES = (
    SPAN_INGEST_STAGE,
    SPAN_INGEST_DISPATCH,
    SPAN_STATS_FETCH,
    SPAN_WINDOW_ADVANCE,
    SPAN_WINDOW_FOLD,
    SPAN_FLUSH_DRAIN,
    SPAN_FLUSH_WAIT,
    SPAN_FLUSH_ROWS,
    SPAN_FLUSH_FETCH,
    SPAN_FLUSH_JOIN,
    SPAN_FLUSH_SPLIT,
    SPAN_CHECKPOINT_SAVE,
    SPAN_QUERY_SNAPSHOT,
    SPAN_QUERY_CACHE,
)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    name: str
    start_s: float  # wall-clock epoch seconds (for export timestamps)
    duration_us: int  # monotonic-clock measured
    # window-lineage context (ISSUE 13): when a stage span belongs to a
    # window's lineage trace, these carry the DERIVED ids
    # (tracing/lineage.window_trace_id — the window id IS the context)
    # and export_otlp emits them instead of synthesizing singleton ids;
    # `window` is the per-window correlation key ("<idx>@<interval>s").
    trace_id: str = ""
    span_id: str = ""
    parent_span_id: str = ""
    window: str = ""
    # the recording thread's CPU time inside the span (module docstring);
    # 0 on a pre-measured record whose caller gave none, and on xla.compile
    cpu_us: int = 0


@dataclasses.dataclass(frozen=True)
class SpanHistSpec:
    """Log-binned per-stage latency histogram geometry (ISSUE 12) — the
    numpy twin of ops/histogram.LogHistSpec (same bin(v) =
    floor(log_gamma(v / vmin)) algebra, same (gamma-1)/(gamma+1)
    relative-error bound), kept jax-free so the tracer stays importable
    from host-only components (agent, querier threads). The default
    covers 1 µs .. ~640 s at ≤1% relative error in 1024 i64 bins
    (8 KB per stage)."""

    bins: int = 1024
    vmin: float = 1.0  # µs; durations at/below land in bin 0
    gamma: float = 1.02
    # ln(gamma), computed once: bin() runs on every record
    _log_gamma: float = dataclasses.field(
        init=False, repr=False, compare=False, default=0.0
    )

    def __post_init__(self):
        object.__setattr__(self, "_log_gamma", math.log(self.gamma))

    def bin(self, duration_us: float) -> int:
        v = float(duration_us)
        if v <= self.vmin:
            return 0
        return min(int(math.log(v / self.vmin) / self._log_gamma),
                   self.bins - 1)

    def centers(self) -> np.ndarray:
        return self.vmin * np.power(
            float(self.gamma), np.arange(self.bins, dtype=np.float64) + 0.5
        )


def loghist_quantiles_np(
    hist: np.ndarray, spec: SpanHistSpec, qs: tuple[float, ...]
) -> np.ndarray:
    """Pure-numpy quantiles over one [bins] log-histogram — the same
    cumsum + rank-threshold walk as ops/histogram.loghist_quantiles,
    evaluated host-side so the Countable face never dispatches to a
    device. Returns zeros for an empty histogram (no fake series)."""
    cum = np.cumsum(hist.astype(np.float64))
    total = cum[-1]
    if total <= 0:
        return np.zeros(len(qs))
    centers = spec.centers()
    out = np.empty(len(qs))
    for i, q in enumerate(qs):
        idx = int(np.searchsorted(cum, q * total, side="left"))
        out[i] = centers[min(idx, spec.bins - 1)]
    return out


#: the quantiles the Countable face exports per stage (deepflow_system
#: metric names: <module>_<stage>_p50_us / _p95_us / _p99_us — the lanes
#: span-latency alert rules key on, ISSUE 12)
SPAN_QUANTILES = (0.5, 0.95, 0.99)


class _Agg:
    __slots__ = ("count", "total_us", "self_us", "cpu_us", "max_us", "last_us",
                 "compiles", "compile_us", "hist")

    def __init__(self, bins: int):
        self.count = 0
        self.total_us = 0
        # duration less what child records covered (nested spans,
        # record()s made while the span was open, compiles)
        self.self_us = 0
        # the recording threads' CPU time inside the spans, children included
        self.cpu_us = 0
        self.max_us = 0
        self.last_us = 0
        # backend compiles charged to this span name (innermost rule)
        self.compiles = 0
        self.compile_us = 0
        # per-stage log-histogram (ISSUE 12): updated together with the
        # scalar aggregates — callers hold the tracer lock, so the
        # read-modify-write on the bin counter cannot lose updates under
        # concurrent feeder-pump + query threads
        self.hist = np.zeros(bins, np.int64)

    def add(self, dur_us: int, self_us: int, cpu_us: int, bin_idx: int) -> None:
        self.count += 1
        self.total_us += dur_us
        self.self_us += self_us
        self.cpu_us += cpu_us
        self.last_us = dur_us
        if dur_us > self.max_us:
            self.max_us = dur_us
        self.hist[bin_idx] += 1


# -- the process-wide per-thread span stack, and what hangs off it ------

_tls = threading.local()
_ids = itertools.count(1)  # span ids: one sequence for every tracer
_hooks_lock = threading.Lock()
_annotation = None  # jax.profiler.TraceAnnotation, once jax is imported
_unspanned = {"compiles": 0, "compile_us": 0}


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


def _new_ids(stack: list) -> tuple[str, str, str]:
    """(span_id, parent_span_id, trace_id) for a span opening above
    `stack`: a child joins its parent's trace, a root opens one."""
    n = next(_ids)
    if stack:
        parent = stack[-1]
        return f"{n:016x}", parent.span_id, parent.trace_id
    return f"{n:016x}", "", f"{n:032x}"


def _jax_hooks():
    """TraceAnnotation, or None while nothing has imported jax. The
    first call that finds jax also registers the compile listener:
    once for the process, whatever the number of tracers."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:  # not imported, or still importing
            return None
        with _hooks_lock:
            if _annotation is None:
                import jax.monitoring

                jax.monitoring.register_event_duration_secs_listener(_on_duration)
                _annotation = profiler.TraceAnnotation
    return _annotation


def _on_duration(event: str, secs: float, **_kw) -> None:
    """JAX's duration events arrive on the thread that did the work: a
    backend compile is charged to the innermost span open there."""
    if event != COMPILE_EVENT:
        return
    us = int(secs * 1e6)
    stack = _stack()
    if stack:
        top = stack[-1]
        top.child_us += us
        top.tracer._charge_compile(top, us, time.time() - secs)
    else:
        with _hooks_lock:
            _unspanned["compiles"] += 1
            _unspanned["compile_us"] += us


def unspanned_compiles() -> dict[str, int]:
    """Backend compiles (count, µs) that ran with no span open on their
    thread, since the process started."""
    with _hooks_lock:
        return dict(_unspanned)


class _Span:
    """One open span: the context manager `SpanTracer.span` returns and
    the entry on its thread's stack."""

    __slots__ = ("tracer", "name", "window", "span_id", "trace_id",
                 "parent_id", "child_us", "wall", "t0", "cpu0", "ann", "discarded",
                 "duration_us")

    def __init__(self, tracer: "SpanTracer", name: str, window: str):
        self.tracer = tracer
        self.name = name
        self.window = window
        self.child_us = 0
        self.discarded = False
        self.duration_us = 0  # set on exit, for a caller that counts a discarded span's time

    def discard(self) -> None:
        """Leave no record and no aggregate of this span when it closes
        (an idle pump). Call it only where nothing was recorded under
        the span: a child would keep a parent id that names nothing."""
        self.discarded = True

    def __enter__(self) -> "_Span":
        stack = _stack()
        self.span_id, self.parent_id, self.trace_id = _new_ids(stack)
        stack.append(self)
        ann = _jax_hooks()
        if ann is None:
            self.ann = None
        else:
            self.ann = ann(self.name)
            self.ann.__enter__()
        self.wall = time.time()
        self.t0 = time.perf_counter()
        # the CPU clock is read inside the wall's two reads, so a span's
        # cpu_us cannot pass its duration by more than a clock's tick
        self.cpu0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> None:
        # (a discarded span keeps no lane: it saves the second read, a system call)
        cpu = 0 if self.discarded else (time.thread_time_ns() - self.cpu0) // 1000
        dur = self.duration_us = int((time.perf_counter() - self.t0) * 1e6)
        if self.ann is not None:
            self.ann.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # closed out of order (a generator's span)
            stack.remove(self)
        if self.discarded:
            return
        if stack:
            stack[-1].child_us += dur
        self.tracer._commit(
            SpanRecord(self.name, self.wall, dur, trace_id=self.trace_id,
                       span_id=self.span_id, parent_span_id=self.parent_id,
                       window=self.window, cpu_us=cpu),
            max(dur - self.child_us, 0),
        )


class SpanTracer:
    """Monotonic-clock stage spans: aggregates + per-stage log-histograms
    always, ring for export. Spans nest across tracers (module
    docstring)."""

    # 4,096 records: the served path at capacity records ~25 spans a
    # second on the feeder's tracer and ~10 on the pipeline's (PERF.md
    # §6, PR 26), and whoever lays the ring over a device profile reads it
    # a minute or more after the profiled slice closed
    def __init__(self, service: str = "deepflow_tpu.pipeline", ring_size: int = 4096,
                 hist_spec: SpanHistSpec = SpanHistSpec()):
        self.service = service
        self.hist_spec = hist_spec
        self._ring: deque[SpanRecord] = deque(maxlen=ring_size)
        self._agg: dict[str, _Agg] = {}
        self._lock = threading.Lock()
        self._seq = 0

    def span(self, name: str, *, window: str = "") -> _Span:
        """`with tracer.span(name) [as s]:` — times the block, nested
        under whatever span is open on this thread; `s.discard()` drops
        it. `window` is the per-window correlation key, as in record()."""
        return _Span(self, name, window)

    def record(self, name: str, duration_us: int, start_s: float | None = None,
               *, cpu_us: int = 0, trace_id: str = "", span_id: str = "",
               parent_span_id: str = "", window: str = ""):
        """Record a pre-measured span — for stages whose work is split
        across non-contiguous host sections (e.g. the sharded advance:
        sketch close before the append, fold after; the feeder's
        per-frame decode) that must count as ONE logical span so
        cross-path stage attribution compares. `cpu_us` is the CPU lane
        of those sections, where the caller measured it. With no ids given it
        nests like span(): child of the span open on this thread, whose
        child time it joins. Explicit trace/parent ids + the per-window
        correlation key ride into the export ring untouched (ISSUE 13:
        lineage-context stage spans)."""
        duration_us = int(duration_us)
        if not (trace_id or span_id or parent_span_id):
            stack = _stack()
            span_id, parent_span_id, trace_id = _new_ids(stack)
            if stack:
                stack[-1].child_us += duration_us
        self._commit(
            SpanRecord(name, time.time() if start_s is None else start_s,
                       duration_us, trace_id=trace_id, span_id=span_id,
                       parent_span_id=parent_span_id, window=window,
                       cpu_us=int(cpu_us)),
            duration_us,
        )

    def _agg_of(self, name: str) -> _Agg:
        agg = self._agg.get(name)
        if agg is None:
            agg = self._agg[name] = _Agg(self.hist_spec.bins)
        return agg

    def _commit(self, rec: SpanRecord, self_us: int) -> None:
        # the bin is computed outside the lock (pure math), but EVERY
        # aggregate mutation — scalar lanes and the histogram counter —
        # happens under the tracer lock: spans close concurrently on
        # feeder-pump and query threads, and an unlocked += on the
        # histogram would silently lose samples (ISSUE 12 satellite,
        # pinned by tests/test_profiling.py::test_span_tracer_threaded).
        bin_idx = self.hist_spec.bin(rec.duration_us)
        with self._lock:
            self._ring.append(rec)
            self._agg_of(rec.name).add(rec.duration_us, self_us, rec.cpu_us, bin_idx)

    def _charge_compile(self, span: _Span, us: int, start_s: float) -> None:
        """One backend compile under `span` (still open): the ring gets
        an xla.compile record, so whoever lays the ring over a device
        trace finds the gap under it (innermost = shortest open span),
        and the span name's aggregate its compile lanes."""
        rec = SpanRecord(SPAN_XLA_COMPILE, start_s, us, trace_id=span.trace_id,
                         span_id=f"{next(_ids):016x}",
                         parent_span_id=span.span_id, window=span.window)
        with self._lock:
            self._ring.append(rec)
            agg = self._agg_of(span.name)
            agg.compiles += 1
            agg.compile_us += us

    def compile_lanes(self, names: tuple[str, ...] | None = None) -> tuple[int, int]:
        """(compiles, compile_us) charged to spans of this tracer: all
        of them, or those named."""
        with self._lock:
            aggs = [a for n, a in self._agg.items() if names is None or n in names]
            return sum(a.compiles for a in aggs), sum(a.compile_us for a in aggs)

    def cpu_us(self, names: tuple[str, ...]) -> dict[str, int]:
        """name → the CPU lane of that span name's aggregate, 0 for one
        that never ran: a lock and a look-up a name, for a Countable that
        republishes lanes (FeederRuntime.get_counters)."""
        with self._lock:
            return {n: a.cpu_us if (a := self._agg.get(n)) else 0 for n in names}

    # -- read faces -----------------------------------------------------
    def summary(self) -> dict[str, dict]:
        """Per-stage aggregates, JSON-able (the bench snapshot shape) —
        now with the log-histogram quantiles (ISSUE 12), so BENCH files
        carry p50/p95/p99 stage attribution next to count/avg/max."""
        with self._lock:
            out = {}
            for name, a in sorted(self._agg.items()):
                qv = loghist_quantiles_np(a.hist, self.hist_spec, SPAN_QUANTILES)
                out[name] = {
                    "count": a.count,
                    "total_us": a.total_us,
                    "self_us": a.self_us,
                    "cpu_us": a.cpu_us,
                    "avg_us": round(a.total_us / a.count, 1) if a.count else 0.0,
                    "max_us": a.max_us,
                    "last_us": a.last_us,
                    "compiles": a.compiles,
                    "compile_us": a.compile_us,
                    **{
                        f"p{int(q * 100)}_us": round(float(v), 1)
                        for q, v in zip(SPAN_QUANTILES, qv)
                    },
                }
            return out

    def hist_dump(self) -> dict[str, list[list[int]]]:
        """stage → nonzero (bin, count) pairs — the same compact shape
        `FreshnessTracker.hist_dump` emits, so span latency histograms
        ride the fleet frame and merge bin-for-bin across hosts
        (histograms add; quantile summaries don't)."""
        with self._lock:
            return {
                name: [
                    [int(b), int(a.hist[b])]
                    for b in np.nonzero(a.hist)[0]
                ]
                for name, a in sorted(self._agg.items())
            }

    def quantiles(
        self, name: str, qs: tuple[float, ...] = SPAN_QUANTILES
    ) -> np.ndarray | None:
        """Per-stage latency quantiles (µs) from the log-histogram —
        pure numpy, no device access. None when the stage never ran."""
        with self._lock:
            a = self._agg.get(name)
            hist = None if a is None else a.hist.copy()
        if hist is None:
            return None
        return loghist_quantiles_np(hist, self.hist_spec, qs)

    def get_counters(self) -> dict[str, int | float]:
        """Countable face: flat `<stage>.count/.total_us/.self_us/.cpu_us/
        .max_us` fields, the compile lanes `<stage>.compiles/.compile_us`, plus
        the log-histogram p50/p95/p99 lanes (ISSUE 12) — dogfooded
        via integration/dfstats into deepflow_system, where
        `ingest.dispatch.p99_us` becomes the
        `tpu_pipeline_spans_ingest_dispatch_p99_us` metric a span-latency
        alert rule keys on. Pure numpy, fetch-free, safe from a ticking
        collector thread."""
        with self._lock:
            aggs = [(name, a.count, a.total_us, a.self_us, a.cpu_us, a.max_us,
                     a.compiles, a.compile_us, a.hist.copy())
                    for name, a in sorted(self._agg.items())]
        out: dict[str, int | float] = {}
        for (name, count, total_us, self_us, cpu_us, max_us, compiles,
             compile_us, hist) in aggs:
            out[f"{name}.count"] = count
            out[f"{name}.total_us"] = total_us
            out[f"{name}.self_us"] = self_us
            out[f"{name}.cpu_us"] = cpu_us
            out[f"{name}.max_us"] = max_us
            out[f"{name}.compiles"] = compiles
            out[f"{name}.compile_us"] = compile_us
            qv = loghist_quantiles_np(hist, self.hist_spec, SPAN_QUANTILES)
            for q, v in zip(SPAN_QUANTILES, qv):
                out[f"{name}.p{int(q * 100)}_us"] = round(float(v), 1)
        return out

    def recent(self, name: str | None = None) -> list[SpanRecord]:
        with self._lock:
            recs = list(self._ring)
        if name is not None:
            recs = [r for r in recs if r.name == name]
        return recs

    def drain(self) -> list[SpanRecord]:
        """Pop and return the ring (export-once semantics)."""
        with self._lock:
            recs = list(self._ring)
            self._ring.clear()
        return recs

    # -- OTLP export ------------------------------------------------------
    def export_otlp(self, exporter, *, table: str = "l7_flow_log") -> int:
        """Drain the span ring through an exporter's traces lane.

        Builds l7_flow_log-shaped columns (app_service/endpoint/
        start_time/response_duration + trace ids) and hands them to
        `exporter.export(table, cols)` — the same path every other
        trace row takes (server/exporters.OtlpExporter turns each row
        into an OTel span). Returns the span count exported."""
        recs = self.drain()
        if not recs:
            return 0
        with self._lock:
            seq0 = self._seq
            self._seq += len(recs)
        n = len(recs)
        cols = {
            "time": np.asarray([int(r.start_s) for r in recs], np.uint32),
            "start_time": np.asarray([int(r.start_s) for r in recs], np.uint32),
            "response_duration": np.asarray(
                [r.duration_us for r in recs], np.uint32
            ),
            "app_service": np.asarray([self.service] * n),
            # the window correlation key (when set) suffixes the
            # endpoint so per-window stage spans stay distinguishable
            # in the trace backend
            "endpoint": np.asarray(
                [f"{r.name}:{r.window}" if r.window else r.name for r in recs]
            ),
            # records carrying lineage context keep their DERIVED ids;
            # plain stage spans synthesize singleton ids as before
            "trace_id": np.asarray(
                [r.trace_id or f"{seq0 + i + 1:032x}"
                 for i, r in enumerate(recs)]
            ),
            "span_id": np.asarray(
                [r.span_id or f"{seq0 + i + 1:016x}"
                 for i, r in enumerate(recs)]
            ),
            "parent_span_id": np.asarray([r.parent_span_id for r in recs]),
        }
        exporter.export(table, cols)
        return n


class JitCacheMonitor:
    """Compile/retrace counters for ONE jitted callable.

    Reads the pjit executable-cache size (`fn._cache_size()`): the first
    `expected_compiles` entries are expected compiles (one per declared
    input shape — a shape-bucketed feeder legitimately compiles the
    fused step once per bucket), every further entry is a RETRACE — a
    shape/dtype/static-arg leak recompiling what steady state should
    reuse. `poll()` is cheap (no device sync); call it after each
    dispatch. Degrades to zeros on jax builds without the cache probe.
    """

    def __init__(self, fn=None, expected_compiles: int = 1):
        self._fn = fn
        self._size = 0
        self.expected_compiles = max(1, int(expected_compiles))
        self.compiles = 0
        self.retraces = 0
        # poll() runs from the ingest loop AND a ticking StatsCollector
        # thread (the pipeline registers itself); the read-modify-write
        # on _size must not double-count one cache growth
        self._lock = threading.Lock()

    def attach(self, fn) -> None:
        """Point at a (new) jitted callable; cumulative counts survive."""
        with self._lock:
            self._fn = fn
            self._size = 0

    def poll(self) -> tuple[int, int]:
        """→ (compiles, retraces), updated from the current cache size."""
        with self._lock:
            if self._fn is not None:
                try:
                    size = int(self._fn._cache_size())
                except Exception:  # pragma: no cover - probe-less jax build
                    size = self._size
                grew = size - self._size
                while grew > 0 and self.compiles < self.expected_compiles:
                    self.compiles += 1
                    grew -= 1
                if grew > 0:
                    self.retraces += grew
                self._size = size
            return self.compiles, self.retraces

    def get_counters(self) -> dict[str, int]:
        self.poll()
        return {"jit_compiles": self.compiles, "jit_retraces": self.retraces}
