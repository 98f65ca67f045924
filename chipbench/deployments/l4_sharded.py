"""The 64-agent pod on one four-chip host (`l4_1s_4m_x4_sketch`): the
system's sharded path, entry point for entry point.

  socket -> Receiver -> `receiver_queues` queues -> FeederRuntime ->
  ShardedFeedSink -> ShardedWindowManager on make_mesh(chips, n_hosts=1)

A batch is dealt over the mesh in contiguous shares; every device runs
the fused step (append + per-window sketch plane) on its share and keeps
its own exact stash, ring and plane. On every window advance the open
sketch ring is merged across the mesh on the devices (the `lax.pmax` /
`lax.psum` of `ShardedPipeline.window_close`); a closed window comes to
the host as each device's partial rows, device-major, and each device's
packed sketch block, which the manager merges into one block a window.

**A document key comes as up to `chips` partial rows a window**, one a
device: `documents` hands back each window as an object whose `tags` and
`meters` are the partial rows merged by key (SUM lanes add, MAX lanes
take the maximum: `reference._group_reduce`). Inside the timed window
run.py reads only `timestamp[0]` of it, so nothing is merged there: the
merges start when `drain()` is called, after the clock has stopped, on a
few threads. Until then a window's partial rows are kept as they were
handed over (views of the manager's one host matrix a close, 1.3 GB);
once merged, what `pod_partials` reads of them is copied out (the tag
columns and the `packet_tx` lane, a third of the bytes) and the matrix
is let go.

`pipeline` keys read here: `interval`, `delay`, `stash_rows` (a device),
`buckets`, `batch_unique_cap` (the feeder's; a device takes its share),
`accum_batches`, `sketch` (`l4_sketch`'s keys; no pool on this path).
The sharded manager has no plane-off mode. `pipeline.cascade` must be
false: the sharded 60 s tier does not compile at these widths (the
configuration's `reduced.tiers`).

This file needs a program whose sharded close opens the spans the cell's
metrics read; on one without them (the parent commit of PR 36) the
import below fails at once, before a port, a thread or device memory is
held.
"""

import concurrent.futures
import dataclasses

import numpy as np

import gen
import reference
import sut

# what the parent commit lacks: the cell's own spans (PR 36)
from deepflow_tpu.utils.spans import (  # noqa: F401
    SPAN_FLUSH_SKETCH_MERGE,
    SPAN_WINDOW_CLOSE_COLLECTIVE,
)

MERGE_THREADS = 4


class MergedLater:
    """One window's partial rows, and on first use of `tags` / `meters`
    its documents: one row a document key."""

    def __init__(self, partial, counts):
        self.partial, self.counts = partial, counts
        self.rows = partial.tags.shape[0]
        self.timestamp, self.valid = partial.timestamp, partial.valid
        self._kept = None  # (partial tags, partial packet_tx) once merged
        self._merged = None

    def start(self, pool) -> None:
        if self._merged is None:
            self._merged = pool.submit(self._merge)

    def _merge(self):
        db = self.partial
        key = np.flatnonzero(db.tag_schema.key_mask)
        sum_mask = np.array([f.op.value == "sum" for f in db.meter_schema.fields])
        first, meters = reference._group_reduce(
            np.ascontiguousarray(db.tags[:, key].T), db.meters, sum_mask)
        merged = db.tags[first], meters.astype(np.float32)
        lane = [f.name for f in db.meter_schema.fields].index("packet_tx")
        self._kept = (np.ascontiguousarray(db.tags), np.array(db.meters[:, lane]))
        self.partial = None
        return merged

    def _result(self):
        if self._merged is None:  # asked before drain() started the threads
            self._merged = concurrent.futures.Future()
            self._merged.set_result(self._merge())
        return self._merged.result()

    def kept(self) -> tuple:
        """What is kept of the partial rows once they are merged: their
        tag columns and their `packet_tx` lane."""
        self._result()
        return self._kept

    @property
    def tags(self):
        return self._result()[0]

    @property
    def meters(self):
        return self._result()[1]


class Served(sut.Served):
    # what the planes shed or drop by count is held to 0 too
    guarantee_counters = sut.GUARANTEE_COUNTERS + (
        "pipeline.sketch_shed", "pipeline.sketch_blocks_dropped")
    stats_module = "tpu_sharded_pipeline"

    def __init__(self, config: dict):
        from deepflow_tpu.feeder import ShardedFeedSink
        from deepflow_tpu.ops.histogram import LogHistSpec
        from deepflow_tpu.parallel.mesh import make_mesh
        from deepflow_tpu.parallel.sharded import (
            ShardedConfig, ShardedPipeline, ShardedWindowManager,
        )

        p = config["pipeline"]
        s = p["sketch"]
        if p.get("cascade") or s.get("pool"):
            raise ValueError("l4_sharded builds the 1 s rollup with the slab "
                             "sketch plane: no cascade, no pool")
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
                num_services=int(s["num_groups"]),
                hll_precision=int(s["hll_precision"]),
                cms_depth=int(s["cms_depth"]), cms_width=int(s["cms_width"]),
                hist=LogHistSpec(bins=int(s["hist_bins"]),
                                 vmin=float(s["hist_vmin"]),
                                 gamma=float(s["hist_gamma"])),
                topk_rows=int(s["topk_rows"]), topk_cols=int(s["topk_cols"]),
                # a slot an open window, as the one-chip plane's ring
                sketch_ring=self.delay // self.interval + 2,
                sketch_pending=int(s["pending"]),
            )), delay=self.delay)
        self.windows, self.blocks = [], []
        self.pool = None  # the merges' threads, from drain() on
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
        out = self.swm.drain()
        # the warm-up's blocks and counts are not the run's
        self.swm.pop_closed_sketches()
        self.swm.pop_partial_row_counts()
        return out

    def block(self) -> None:
        import jax

        jax.block_until_ready((self.swm.stash, self.swm.acc, self.swm.sketches))

    def pipeline_counters(self) -> dict:
        c = self.swm.get_counters()
        # a batch's pre-reduce overflow is counted by the sharded step in
        # the device stash's overflow counter, `stash_evictions` here;
        # what is left under this name is what the feeder shed ahead of it
        c["prereduce_shed"] = self.feeder.sink.feeder_shed
        return c

    def tracers(self) -> list:
        return [self.feeder.tracer, self.swm.tracer]

    def drain(self) -> list:
        out = self.swm.drain()
        self.pool = concurrent.futures.ThreadPoolExecutor(MERGE_THREADS)
        for w in self.windows:
            w.start(self.pool)
        return out

    def documents(self, out: list) -> list:
        """The closed blocks are taken off the manager at every pump
        (narrowed as `l4_sketch` narrows them, ~13 MB a block); a closed
        window is handed back unmerged (`MergedLater`)."""
        for blk in self.swm.pop_closed_sketches():
            if blk.window * self.interval >= gen.T0:
                self.blocks.append(dataclasses.replace(
                    blk, hll=blk.hll.astype(np.int8),
                    cms=blk.cms.astype(np.int32), hist=blk.hist.astype(np.int32)))
        counts = dict(self.swm.pop_partial_row_counts())
        mine = []
        for db in out:
            w = int(db.timestamp[0])
            if w < gen.T0:
                continue
            mine.append(MergedLater(db, counts[w // self.interval]))
            if self.pool is not None:
                mine[-1].start(self.pool)
        self.windows += mine
        return mine

    def side_outputs(self) -> dict:
        return {
            "sketch_blocks": self.blocks,
            # window -> (partial tags, their packet_tx lane, rows a device)
            "pod_partials": {
                int(w.timestamp[0]): (*w.kept(), w.counts) for w in self.windows},
        }

    def flushed_docs(self) -> int:
        return self.swm.get_counters()["flushed_doc"]

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown(wait=False, cancel_futures=True)
        self.receiver.stop()
        self.swm.close()
