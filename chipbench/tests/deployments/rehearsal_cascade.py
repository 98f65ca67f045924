"""Rehearsal builder (b): today's single-chip L4 deployment with the
rollup cascade on (one coarser tier). Throw-away, as rehearsal_sketch.

The program's drain is its shutdown path: it flushes the range up to the
last slot, which moves every tier's watermark there, and no tier window
closes after it. So this warm-up never drains (`end_warm_up_windows`):
its windows close as event time moves on, the last ones under the run's
first records, and `documents` drops whatever is stamped before T0."""

import gen
import sut


class Served(sut.Served):
    guarantee_counters = sut.GUARANTEE_COUNTERS + (
        "pipeline.cascade_shed", "pipeline.tier_windows_dropped")

    def __init__(self, config: dict):
        self.tiers = []  # (interval_s, DocBatch), oldest first
        super().__init__(config)

    def window_config(self, config: dict):
        from deepflow_tpu.aggregator.cascade import CascadeConfig
        from deepflow_tpu.aggregator.window import WindowConfig

        p = config["pipeline"]
        return WindowConfig(
            interval=int(p["interval"]), delay=int(p["delay"]),
            capacity=int(p["stash_rows"]), accum_batches=int(p["accum_batches"]),
            cascade=CascadeConfig(intervals=tuple(p["cascade"]["intervals"]),
                                  capacity=int(p["cascade"]["rows"])))

    def end_warm_up_windows(self) -> list:
        return []

    def documents(self, out: list) -> list:
        self.tiers += self.pipe.pop_tier_docbatches()
        return [db for db in out if int(db.timestamp[0]) >= gen.T0]

    def side_outputs(self) -> dict:
        return {"tier_docbatches": self.tiers}
