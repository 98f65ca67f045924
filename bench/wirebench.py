#!/usr/bin/env python
"""Wire delivery plane A/B (ISSUE 19 acceptance): push fan-out latency
over REAL sockets, watchers × rules × hosts.

pushbench measures the in-process push plane (ONE eval, N callback
watchers). This bench extends it across the wire: H pipeline-host
stacks (store + bus + SubscriptionManager + `WirePublisher`), each
dialed into ONE `FleetSubscriptionRouter` over TCP, fan merged eval
envelopes out to W wire clients attached through the serving `WireHub`
(`open_stream`, the same face `GET /v1/watch` rides). Per grid cell:

  * **publish → all-W-watchers latency** (mean/p95 ms): host-side
    window-close publish until EVERY wire client's queue holds the
    merged envelope — eval + frame encode + socket + merge + fan-out.
    The acceptance shape: latency FLAT in W (fan-out is W bounded-queue
    appends off one merged eval; the wire/eval cost dominates and is
    paid ONCE), summarized as `latency_ratio_wmax_over_w1` per
    (hosts, rules) group.
  * **one upstream subscription** regardless of W (`upstream_subs`),
    evals == events per host (never × W), deliveries == merged × W,
    zero drops (drains keep up).
  * **rules ride along**: R host-side alert rules firing on the same
    events push `alert` frames up the same lane (`alerts_rx` counted);
    an alerts-topic wire client drains them.
  * **pinned**: the last merged envelope's per-host rows bit-exact vs
    each host's own `last_result` through `result_to_jsonable` — the
    wire never re-evaluates or re-shapes.

Usage: python bench/wirebench.py [repo_root]
Knobs: WIREBENCH_WATCHERS (default "1,10,100"), WIREBENCH_HOSTS
("1,2"), WIREBENCH_RULES ("0,4"), WIREBENCH_EVENTS (16). CPU-container
numbers; on-chip columns pending per the measurement-debt item
(PERF.md §27).
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


class _HostStack:
    """One pipeline host: local store/bus/subs (+R alert rules) and a
    WirePublisher uplink into the bench router."""

    def __init__(self, idx, endpoint, rules):
        import numpy as np

        from deepflow_tpu.integration.dfstats import (
            DEEPFLOW_SYSTEM_DB,
            DEEPFLOW_SYSTEM_TABLE,
            ensure_system_table,
        )
        from deepflow_tpu.querier.events import QueryEventBus, WindowClosed
        from deepflow_tpu.querier.live import LiveRegistry
        from deepflow_tpu.querier.subscribe import SubscriptionManager
        from deepflow_tpu.storage.store import ColumnarStore
        from deepflow_tpu.wire import WirePublisher

        self.np = np
        self.db, self.table = DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE
        self.WindowClosed = WindowClosed
        self.host = f"h{idx}"
        self.store = ColumnarStore()
        ensure_system_table(self.store)
        self.bus = QueryEventBus(name=f"wirebench-{idx}")
        self.subs = SubscriptionManager(
            self.store, live=LiveRegistry(), cache=False, bus=self.bus,
            name=f"wirebench-{idx}",
        )
        self.alerts = None
        if rules:
            from deepflow_tpu.querier.alerts import AlertEngine, AlertRule

            self.alerts = AlertEngine(
                self.store, live=LiveRegistry(), bus=self.bus,
                name=f"wirebench-{idx}", log_sink=False,
            )
            for r in range(rules):
                self.alerts.add_rule(AlertRule(
                    name=f"rule{r}", query="m", comparator=">",
                    threshold=-1.0, for_s=0, lookback_s=2,
                ))
        self.pub = WirePublisher(endpoint, host=self.host,
                                 subscriptions=self.subs,
                                 alerts=self.alerts)

    def wait_subscribed(self, timeout_s=30.0):
        deadline = time.monotonic() + timeout_s
        while not self.pub.active_queries():
            if time.monotonic() > deadline:
                raise TimeoutError(f"{self.host}: no sub from router")
            time.sleep(0.005)
        return self.pub.active_queries()[0][1]

    def publish(self, t, v):
        np = self.np
        self.store.insert(self.db, self.table, {
            "time": np.asarray([t], np.uint32),
            "metric": np.asarray(["m"], object),
            "labels": np.asarray([""], object),
            "value": np.asarray([v], np.float64),
        })
        self.bus.publish(self.WindowClosed(self.db, self.table, t))

    def close(self):
        self.pub.close()
        self.subs.close()


def _run_cell(watchers, hosts, rules, events):
    from deepflow_tpu.querier.live import LiveRegistry
    from deepflow_tpu.querier.subscribe import SubscriptionManager
    from deepflow_tpu.storage.store import ColumnarStore
    from deepflow_tpu.wire import (
        FleetSubscriptionRouter,
        WireHub,
        result_to_jsonable,
    )

    router = FleetSubscriptionRouter(name=f"wb{watchers}x{hosts}").start()
    local = SubscriptionManager(ColumnarStore(), live=LiveRegistry(),
                                cache=False, name="wirebench-agg")
    hub = WireHub(local, router=router, name="wirebench")
    stacks, conns, alert_conn = [], [], None
    try:
        conns = [hub.open_stream(promql="m", span_s=4, maxlen=4 * events)
                 for _ in range(watchers)]
        if rules:
            alert_conn = hub.open_stream(alerts=True,
                                         maxlen=4 * events * rules * hosts)
        stacks = [_HostStack(i, router.endpoint, rules)
                  for i in range(hosts)]
        host_subs = [s.wait_subscribed() for s in stacks]
        assert router.get_counters()["upstream_subs"] == 1

        def wait_all(target, timeout_s=30.0):
            deadline = time.monotonic() + timeout_s
            while any(c.watcher.delivered < target for c in conns):
                if time.monotonic() > deadline:
                    raise TimeoutError("fan-out stalled")
                time.sleep(0)

        # warmup: one event per host faults every path in
        for i, s in enumerate(stacks):
            s.publish(T0 + i, 1.0)
        wait_all(hosts)

        lat = []
        t_start = time.perf_counter()
        for k in range(events):
            s = stacks[k % hosts]
            stamp = time.perf_counter()
            s.publish(T0 + hosts + k, float(k))
            wait_all(hosts + k + 1)
            lat.append(time.perf_counter() - stamp)
        elapsed = time.perf_counter() - t_start

        rc = router.get_counters()
        merged = rc["merged_evals"]
        # pinned: per-host wire rows == that host's own last eval
        env = None
        for c in conns[:1]:
            item = c.poll()
            while item is not None:
                env, item = item, c.poll()
        pinned = bool(env) and all(
            env["hosts"][s.host]["series"] == json.loads(
                json.dumps(result_to_jsonable(hs.last_result), default=str)
            )
            for s, hs in zip(stacks, host_subs)
        )
        alerts_drained = 0
        if alert_conn is not None:
            while alert_conn.poll() is not None:
                alerts_drained += 1
        lat.sort()
        return {
            "watchers": watchers,
            "hosts": hosts,
            "rules": rules,
            "events": events,
            "merged_evals": merged,
            "deliveries": rc["deliveries"],
            "upstream_subs": rc["upstream_subs"],
            "host_evals": [hs.evals for hs in host_subs],
            "drops": rc["drops"],
            "alerts_rx": rc["alerts_rx"],
            "alerts_drained": alerts_drained,
            "publish_to_all_watchers_ms_mean": round(
                sum(lat) / len(lat) * 1e3, 3),
            "publish_to_all_watchers_ms_p95": round(
                lat[int(0.95 * (len(lat) - 1))] * 1e3, 3),
            "deliveries_per_s": round(rc["deliveries"] / elapsed, 1),
            "pinned_bit_exact": pinned,
        }
    finally:
        for s in stacks:
            s.close()
        hub.close()
        local.close()
        router.stop()


def main():
    watcher_counts = [int(w) for w in os.environ.get(
        "WIREBENCH_WATCHERS", "1,10,100").split(",")]
    host_counts = [int(h) for h in os.environ.get(
        "WIREBENCH_HOSTS", "1,2").split(",")]
    rule_counts = [int(r) for r in os.environ.get(
        "WIREBENCH_RULES", "0,4").split(",")]
    events = int(os.environ.get("WIREBENCH_EVENTS", 16))
    try:
        from deepflow_tpu.utils.provenance import bench_provenance

        rows = [
            _run_cell(w, h, r, events)
            for h in host_counts for r in rule_counts
            for w in watcher_counts
        ]
        # the flatness summary the acceptance reads: max-W latency over
        # W=1 latency within each (hosts, rules) group
        ratios = {}
        for h in host_counts:
            for r in rule_counts:
                group = [x for x in rows
                         if x["hosts"] == h and x["rules"] == r]
                lo = min(group, key=lambda x: x["watchers"])
                hi = max(group, key=lambda x: x["watchers"])
                ratios[f"h{h}_r{r}"] = round(
                    hi["publish_to_all_watchers_ms_mean"]
                    / max(1e-9, lo["publish_to_all_watchers_ms_mean"]), 3)
        rec = {
            "bench": "wirebench",
            "events": events,
            "rows": rows,
            "latency_ratio_wmax_over_w1": ratios,
            "provenance": bench_provenance(),
        }
    except Exception as e:  # parseable partial record, never a traceback
        rec = {"bench": "wirebench", "partial": True, "error": repr(e)}
    from deepflow_tpu.utils.provenance import device_identity

    rec["device"] = device_identity()  # names the platform this ran on
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
