#!/usr/bin/env python
"""Push query plane A/B (ISSUE 11 acceptance): dashboard-storm fan-out
amplification + flush→watcher invalidation latency.

One JSON line with two measurements:

  * **fanout**: ONE subscribed PromQL query over a live open-window
    overlay (512 flow series), fanned out to W watchers, driven by E
    window-close events. Per watcher count: evaluations (must be E —
    one per event, NEVER per watcher), deliveries (E×W), amplification
    (deliveries/evals == W), evals/sec, deliveries/sec, and the
    flush→delivery latency (publish-to-first-watcher and
    publish-to-last-watcher, ms) — the push plane's answer to "how
    stale is a dashboard after a window closes". The acceptance shape
    is W ≥ 100 from a SINGLE evaluation per event.
  * **pinned**: the last delivered result compared bit-exact against a
    fresh pull evaluation of the same query at the same instant
    (cache=False) — push-invalidated results never serve a stale row.

The alert lane rides along: a threshold rule on the same metric
evaluated on the same events, with its eval latency recorded.

Usage: python bench/pushbench.py [repo_root]
Knobs: PUSHBENCH_WATCHERS (comma list, default "1,10,100"),
PUSHBENCH_EVENTS, PUSHBENCH_FLOWS. CPU-container numbers; on-chip
columns pending per the measurement-debt item (PERF.md §20).
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"

root = sys.argv[1] if len(sys.argv) > 1 else os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))
)
sys.path.insert(0, root)

T0 = 1_700_000_000


def _stack(n_flows):
    import numpy as np

    from deepflow_tpu.aggregator.window import WindowConfig, WindowManager
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
    from deepflow_tpu.integration.dfstats import (
        DEEPFLOW_SYSTEM_DB,
        DEEPFLOW_SYSTEM_TABLE,
        PipelineLiveSource,
        ensure_system_table,
    )
    from deepflow_tpu.querier.events import QueryEventBus
    from deepflow_tpu.querier.live import LiveRegistry, QueryResultCache
    from deepflow_tpu.storage.store import ColumnarStore

    store = ColumnarStore()
    ensure_system_table(store)
    reg = LiveRegistry()
    wm = WindowManager(WindowConfig(capacity=1 << 12, min_snapshot_interval=0.0))
    reg.register(DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE,
                 PipelineLiveSource(wm))
    bus = QueryEventBus(name="pushbench")
    cache = QueryResultCache(max_entries=64)
    cache.attach_bus(bus)

    def ingest(t):
        meters = np.zeros((FLOW_METER.num_fields, n_flows), np.float32)
        meters[FLOW_METER.index("byte_tx")] = 64.0
        wm.ingest(
            np.full(n_flows, t, np.uint32),
            np.arange(n_flows, dtype=np.uint32),
            np.arange(n_flows, dtype=np.uint32),
            np.zeros((TAG_SCHEMA.num_fields, n_flows), np.uint32), meters,
            np.ones(n_flows, bool),
        )
        wm.snapshot_open(force=True)

    return store, reg, wm, bus, cache, ingest


def _run_fanout(watchers, events, n_flows):
    from deepflow_tpu.integration.dfstats import (
        DEEPFLOW_SYSTEM_DB,
        DEEPFLOW_SYSTEM_TABLE,
        LIVE_METRIC_FLOW_BYTES,
    )
    from deepflow_tpu.querier.alerts import AlertEngine, AlertRule
    from deepflow_tpu.querier.events import WindowClosed
    from deepflow_tpu.querier.promql import query_range
    from deepflow_tpu.querier.subscribe import SubscriptionManager

    store, reg, wm, bus, cache, ingest = _stack(n_flows)
    subs = SubscriptionManager(store, live=reg, cache=cache, bus=bus,
                               name=f"pushbench{watchers}")
    SPAN, STEP = 4, 1
    stamp = {"t": 0.0}
    first_lat, last_lat = [], []
    results = []

    def make_cb(i):
        if i == 0:
            def cb(r, s):
                first_lat.append(time.perf_counter() - stamp["t"])
                results.append(r)
            return cb
        if i == watchers - 1:
            return lambda r, s: last_lat.append(
                time.perf_counter() - stamp["t"]
            )
        return lambda r, s: None

    sub = None
    for i in range(watchers):
        sub, _ = subs.subscribe_promql(
            LIVE_METRIC_FLOW_BYTES, span_s=SPAN, step=STEP,
            db=DEEPFLOW_SYSTEM_DB, table=DEEPFLOW_SYSTEM_TABLE,
            callback=make_cb(i),
        )
    alerts = AlertEngine(store, live=reg, bus=bus, name=f"pb{watchers}",
                         log_sink=False)
    alerts.add_rule(AlertRule(
        name="hot", query=LIVE_METRIC_FLOW_BYTES, comparator=">",
        threshold=1.0, for_s=0,
    ))

    # warmup eval (compile nothing, but fault in the code paths)
    ingest(T0)
    bus.publish(WindowClosed(DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE, T0))
    ev0, first_lat[:], last_lat[:], results[:] = sub.evals, [], [], []

    t_start = time.perf_counter()
    for i in range(events):
        t = T0 + 1 + i
        ingest(t)
        stamp["t"] = time.perf_counter()
        bus.publish(WindowClosed(DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE, t))
    elapsed = time.perf_counter() - t_start

    evals = sub.evals - ev0
    sc = subs.get_counters()
    # the bit-exact pin: last delivered == fresh pull at the same now
    fresh = query_range(
        store, LIVE_METRIC_FLOW_BYTES, sub.last_now - SPAN, sub.last_now,
        STEP, db=DEEPFLOW_SYSTEM_DB, table=DEEPFLOW_SYSTEM_TABLE, live=reg,
        cache=False,
    )
    pinned = bool(results) and results[-1] == fresh and bool(fresh)
    lat_ms = lambda xs: round(sum(xs) / max(1, len(xs)) * 1e3, 3)
    return {
        "watchers": watchers,
        "events": events,
        "evals": evals,
        "deliveries": evals * watchers if sc["watcher_errors"] == 0 else None,
        "amplification": round(sc["deliveries"] / max(1, sc["evals"]), 1),
        "evals_per_s": round(evals / elapsed, 1),
        "deliveries_per_s": round(evals * watchers / elapsed, 1),
        "publish_to_first_watcher_ms": lat_ms(first_lat),
        "publish_to_last_watcher_ms": lat_ms(
            last_lat if watchers > 1 else first_lat
        ),
        "series": len(fresh),
        "pinned_bit_exact": pinned,
        "alert_state": alerts.state("hot"),
        "cache": cache.get_counters(),
    }


def main():
    watcher_counts = [
        int(w) for w in os.environ.get("PUSHBENCH_WATCHERS", "1,10,100").split(",")
    ]
    events = int(os.environ.get("PUSHBENCH_EVENTS", 32))
    n_flows = int(os.environ.get("PUSHBENCH_FLOWS", 512))
    try:
        rows = [_run_fanout(w, events, n_flows) for w in watcher_counts]
        rec = {
            "bench": "pushbench",
            "events": events,
            "flows": n_flows,
            "rows": rows,
        }
    except Exception as e:  # parseable partial record, never a traceback
        rec = {"bench": "pushbench", "partial": True, "error": repr(e)}
    from deepflow_tpu.utils.provenance import device_identity

    rec["device"] = device_identity()  # names the platform this ran on
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
