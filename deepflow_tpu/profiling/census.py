"""XLA step-cost census — per-callable × bucket-shape attribution
(ISSUE 12, layer 2).

Rides `utils/spans.JitCacheMonitor`: the monitor already knows when the
fused step compiled; the census remembers WHAT compiled — the abstract
arg shapes (jax.ShapeDtypeStruct, a few hundred bytes per bucket, never
the live buffers) and the measured first-dispatch wall time — and can
later answer, per (step, bucket):

  * `cost_analysis()`    — flops + bytes accessed per dispatch,
  * `memory_analysis()`  — peak temp / argument / output bytes,
  * compile wall time    — the warmup tax a new bucket shape pays.

Capture is FREE on the hot path: observing a bucket stores shapes only
(no fetch, no compile); the expensive `fn.lower(shapes).compile()`
analysis runs lazily at `snapshot(analyze=True)` — the REST
`/v1/profile/device` pull, `dfctl profile device`, the bench embed —
and is cached per entry. On jax builds whose AOT path cannot analyze a
step (or for a GC'd callable), the entry degrades to shapes + compile
wall time with an `analysis_error` note instead of raising — the
profile surface must never take down the server.

Per-bucket flops/bytes make the fused step's arithmetic intensity (and
therefore which window lever to pull next) a lookup, not a guess.
"""

from __future__ import annotations

import threading
import weakref


def _abstract(tree):
    """Pytree of live args → pytree of ShapeDtypeStructs (metadata
    only: holding the struct keeps no device buffer alive)."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if hasattr(x, "shape") and hasattr(x, "dtype")
        else x,
        tree,
    )


class _Entry:
    __slots__ = ("service", "step", "bucket", "fn_ref", "abstract_args",
                 "compiles", "compile_wall_s", "first_dispatch_s",
                 "analysis", "analysis_error", "sorts", "sorts_error")

    def __init__(self, service, step, bucket, fn, abstract_args):
        self.service = service
        self.step = step
        self.bucket = bucket
        self.fn_ref = weakref.ref(fn) if fn is not None else None
        self.abstract_args = abstract_args
        self.compiles = 0
        self.compile_wall_s = 0.0
        self.first_dispatch_s = 0.0
        self.analysis: dict | None = None
        self.analysis_error: str | None = None
        self.sorts: int | None = None
        self.sorts_error: str | None = None


#: the headline cost_analysis keys (XLA also emits per-operand
#: `bytes_accessed<N>{}` / `utilization<N>{}` rows — noise for a
#: per-step census; the totals are what the census serves)
_COST_KEYS = ("flops", "bytes accessed", "transcendentals",
              "optimal_seconds")


def _flatten_cost(cost: dict) -> dict:
    """XLA cost_analysis → the headline totals only (keys carry spaces:
    'bytes accessed')."""
    out = {}
    for k in _COST_KEYS:
        if k in cost:
            try:
                out[k.replace(" ", "_")] = float(cost[k])
            except (TypeError, ValueError):
                continue
    return out


def _count_sort_eqns(jaxpr) -> int:
    """Recursively count `sort` primitive equations through every
    sub-jaxpr (pjit bodies, cond branches, scan/while bodies, custom
    call wrappers) — the static sorts-per-dispatch attribution of
    ISSUE 17. Conditional branches each count: the census reports the
    sorts a dispatch CAN pay, which is what the one-pass gate bounds."""
    import jax

    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            total += 1
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                total += _count_sort_eqns(sub)
    return total


def _sub_jaxprs(v):
    """Yield every Jaxpr held by one eqn param value (handles Jaxpr,
    ClosedJaxpr, and lists/tuples of either)."""
    from jax.extend.core import Jaxpr

    if isinstance(v, Jaxpr):
        yield v
    elif hasattr(v, "jaxpr") and isinstance(getattr(v, "jaxpr"), Jaxpr):
        yield v.jaxpr  # ClosedJaxpr
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def _trace_sort_count(fn, abstract_args) -> int:
    """Sorts per dispatch of `fn` at the recorded bucket shapes —
    STATIC jaxpr inspection only: `jax.make_jaxpr` re-traces abstractly
    without touching the jit executable cache, so the count can ride
    the steady-state profile pull without tripping the zero-retrace or
    fetch-budget gates."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*abstract_args)
    return _count_sort_eqns(jaxpr.jaxpr)


class StepCostCensus:
    """Per-(service, step, bucket) compiled-step cost registry."""

    def __init__(self):
        self._entries: dict[tuple, _Entry] = {}
        self._lock = threading.Lock()

    # -- capture (hot path: metadata only) ------------------------------
    def seen(self, service: str, step: str, bucket: int) -> bool:
        """True when the bucket is recorded AND its callable is still
        alive — a dead ref (the previous same-shaped pipeline was
        collected) reports unseen so the caller re-observes and the
        entry re-points to the live step (observe() handles it)."""
        e = self._entries.get((service, step, int(bucket)))
        return e is not None and (e.fn_ref is None or e.fn_ref() is not None)

    def observe(self, service: str, step: str, bucket: int, fn, args) -> None:
        """Record one bucket shape the first time it dispatches: the
        callable (weak) + abstract arg shapes. Idempotent; no compile,
        no transfer. A restarted pipeline with the same (service, step,
        bucket) re-points a dead callable ref (compile counts keep
        accumulating — recompiles across restarts are real cost)."""
        key = (service, step, int(bucket))
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                if e.fn_ref is not None and e.fn_ref() is None:
                    e.fn_ref = weakref.ref(fn) if fn is not None else None
                    e.abstract_args = _abstract(args)
                    e.analysis = None
                    e.analysis_error = None
                return
            self._entries[key] = _Entry(service, step, int(bucket), fn,
                                        _abstract(args))

    def note_compile(self, service: str, step: str, bucket: int,
                     wall_s: float) -> None:
        """Attribute a measured compile (the JitCacheMonitor detected
        cache growth on this dispatch) to its bucket. `wall_s` is the
        first-dispatch wall time — compile + first execute, the real
        warmup tax a new shape pays."""
        key = (service, step, int(bucket))
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return
            e.compiles += 1
            e.compile_wall_s += float(wall_s)
            if e.first_dispatch_s == 0.0:
                e.first_dispatch_s = float(wall_s)

    # -- analysis (pull path: may compile) ------------------------------
    def _analyze(self, e: _Entry) -> None:
        if e.analysis is not None or e.analysis_error is not None:
            return
        fn = e.fn_ref() if e.fn_ref is not None else None
        if fn is None:
            e.analysis_error = "callable collected"
            return
        try:
            compiled = fn.lower(*e.abstract_args).compile()
            ana: dict = {}
            try:
                ana.update(_flatten_cost(compiled.cost_analysis()))
            except Exception as err:  # pragma: no cover - backend-dependent
                ana["cost_error"] = repr(err)
            try:
                mem = compiled.memory_analysis()
                for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                             "output_size_in_bytes", "alias_size_in_bytes",
                             "generated_code_size_in_bytes"):
                    v = getattr(mem, attr, None)
                    if v is not None:
                        ana[attr] = int(v)
            except Exception as err:  # pragma: no cover - backend-dependent
                ana["memory_error"] = repr(err)
            e.analysis = ana
        except Exception as err:
            e.analysis_error = repr(err)

    def _count_sorts(self, e: _Entry) -> None:
        """Lazy per-entry sorts/dispatch attribution (ISSUE 17): a pure
        abstract re-trace, cached after the first pull. No compile, no
        fetch — cheap enough for the default (analyze=False) snapshot
        that telemetry()["profile"] and the bench JSON embeds read."""
        if e.sorts is not None or e.sorts_error is not None:
            return
        fn = e.fn_ref() if e.fn_ref is not None else None
        if fn is None:
            e.sorts_error = "callable collected"
            return
        try:
            e.sorts = _trace_sort_count(fn, e.abstract_args)
        except Exception as err:
            e.sorts_error = repr(err)

    def snapshot(self, *, analyze: bool = False) -> list[dict]:
        """One JSON-able row per (service, step, bucket). With
        `analyze=True` each entry's compiled-module analyses are
        computed (cached after the first pull) — this may COMPILE the
        step for its recorded shapes via the AOT path, so it belongs on
        the profile pull, never inside ingest. The `sorts` column
        (sorts per dispatch, static jaxpr count) is computed on every
        pull — trace-only, cached, fetch-free."""
        with self._lock:
            entries = list(self._entries.values())
        rows = []
        for e in sorted(entries, key=lambda e: (e.service, e.step, e.bucket)):
            if analyze:
                self._analyze(e)
            self._count_sorts(e)
            row = {
                "service": e.service,
                "step": e.step,
                "bucket": e.bucket,
                "compiles": e.compiles,
                "compile_wall_s": round(e.compile_wall_s, 4),
                "first_dispatch_s": round(e.first_dispatch_s, 4),
            }
            if e.sorts is not None:
                row["sorts"] = e.sorts
            if e.sorts_error is not None:
                row["sorts_error"] = e.sorts_error
            if e.analysis is not None:
                row.update(e.analysis)
            if e.analysis_error is not None:
                row["analysis_error"] = e.analysis_error
            rows.append(row)
        return rows

    def get_counters(self) -> dict[str, int | float]:
        """Countable face — cheap scalars only (no analysis): entry and
        compile counts plus the cumulative compile wall time, so compile
        pressure is queryable from deepflow_system."""
        with self._lock:
            entries = list(self._entries.values())
        return {
            "entries": len(entries),
            "compiles": sum(e.compiles for e in entries),
            "compile_wall_ms": int(
                sum(e.compile_wall_s for e in entries) * 1e3
            ),
        }


#: process-wide default census (the REST / dfctl surface reads it);
#: registered as a Countable so compile pressure dogfoods too
default_census = StepCostCensus()

from ..utils.stats import register_countable  # noqa: E402

register_countable("tpu_step_census", default_census)
