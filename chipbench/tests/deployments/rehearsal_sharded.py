"""Rehearsal builder (c): ShardedFeedSink / ShardedWindowManager on
`make_mesh(chips, n_hosts=1)`, the 60 s tier on. Throw-away: it proves the
seam on four forced host devices (test_seams.py) and is what
rehearse_x4.py runs once on four chips at l4_1s_1m's widths; the cell
`l4_1m_x4.saturate` brings a builder of its own under
chipbench/deployments/.

The sharded manager keeps one exact stash per device and hands over their
rows side by side, so one document key can come as up to `chips` partial
rows: `documents` merges them by key (SUM lanes add, MAX lanes take the
maximum) before anything is compared.

Its warm-up never drains, for the reason rehearsal_cascade.py gives, and
`documents` drops what is stamped before T0."""

import dataclasses

import numpy as np

import gen
import reference
import sut


class Served(sut.Served):
    guarantee_counters = sut.GUARANTEE_COUNTERS + (
        "pipeline.cascade_shed", "pipeline.tier_windows_dropped",
        "pipeline.sketch_blocks_dropped")
    stats_module = "tpu_sharded_pipeline"

    def __init__(self, config: dict):
        from deepflow_tpu.feeder import ShardedFeedSink
        from deepflow_tpu.parallel.mesh import make_mesh
        from deepflow_tpu.parallel.sharded import (
            ShardedConfig, ShardedPipeline, ShardedWindowManager,
        )

        p = config["pipeline"]
        self.config = config
        self.chips = int(config["chips"])
        self.interval, self.delay = int(p["interval"]), int(p["delay"])
        self.buckets = tuple(p["buckets"])
        self.swm = ShardedWindowManager(ShardedPipeline(
            make_mesh(self.chips, n_hosts=1),
            ShardedConfig(
                interval=self.interval,
                capacity_per_device=int(p["stash_rows"]),
                accum_batches=int(p["accum_batches"]),
                batch_unique_cap=int(p["batch_unique_cap"]) // self.chips,
                num_services=int(p["sketch"]["num_services"]),
                hll_precision=int(p["sketch"]["hll_precision"]),
                cascade=tuple(p["cascade"]["intervals"]),
                cascade_capacity=int(p["cascade"]["rows"]),
            )), delay=self.delay)
        self.tiers, self.blocks, self.partial_rows = [], [], 0
        self.serve(config, ShardedFeedSink(self.swm, self.buckets))

    def ingest_direct(self, fields: list, tags, meters, stamp: int) -> int:
        """As sut.Served.ingest_direct, into the sharded manager: batches
        padded to a bucket, which the mesh's device count divides."""
        tags[fields.index("timestamp")] = stamp
        n, docs = meters.shape[0], 0
        for lo in range(0, n, self.buckets[-1]):
            rows = min(self.buckets[-1], n - lo)
            bucket = next(b for b in self.buckets if b >= rows)
            t = np.zeros((len(fields), bucket), np.uint32)
            m = np.zeros((bucket, meters.shape[1]), np.float32)
            t[:, :rows], m[:rows] = tags[:, lo:lo + rows], meters[lo:lo + rows]
            out = self.swm.ingest({f: t[j] for j, f in enumerate(fields)}, m,
                                  np.arange(bucket) < rows)
            docs += sum(db.tags.shape[0] for db in out)
        return docs

    def end_warm_up_windows(self) -> list:
        return []

    def block(self) -> None:
        import jax

        jax.block_until_ready((self.swm.stash, self.swm.acc, self.swm.sketches))

    def pipeline_counters(self) -> dict:
        c = self.swm.get_counters()
        # the sharded manager's names for what the guarantees read
        c["stash_evictions"] = int(np.asarray(self.swm.stash.dropped_overflow).sum())
        c["prereduce_shed"] = self.feeder.sink.feeder_shed
        c["jit_retraces"] = 0  # it keeps no such count (PERF.md section 7)
        return c

    def tracers(self) -> list:
        return [self.feeder.tracer, self.swm.tracer]

    def drain(self) -> list:
        return self.swm.drain()

    def documents(self, out: list) -> list:
        self.tiers += [(iv, self.merged(db)) for iv, db in self.swm.pop_tier_docbatches()]
        self.blocks += [b for b in self.swm.pop_closed_sketches() if b.window >= gen.T0]
        return [self.merged(db) for db in out if int(db.timestamp[0]) >= gen.T0]

    def merged(self, db):
        """One window's partial rows, one row a document key."""
        key = np.flatnonzero(db.tag_schema.key_mask)
        sum_mask = np.array([f.op.value == "sum" for f in db.meter_schema.fields])
        first, meters = reference._group_reduce(
            np.ascontiguousarray(db.tags[:, key].T), db.meters, sum_mask)
        self.partial_rows += db.tags.shape[0]
        return dataclasses.replace(
            db, tags=db.tags[first], meters=meters.astype(np.float32),
            timestamp=db.timestamp[first], valid=db.valid[first])

    def side_outputs(self) -> dict:
        return {"tier_docbatches": self.tiers, "sketch_blocks": self.blocks,
                "partial_rows": self.partial_rows}

    def flushed_docs(self) -> int:
        return self.swm.get_counters()["flushed_doc"]

    def close(self) -> None:
        self.receiver.stop()
        self.swm.close()
