"""The single-chip L4 deployment with the per-window sketch plane on, at
the shapes the configuration's `pipeline.sketch` gives (`l4_1s_1m_sketch`).

The served path is `sut.Served`'s, entry point for entry point: Receiver
-> queues -> FeederRuntime -> PipelineFeedSink -> L4Pipeline, with
`WindowConfig.sketch` set, so every record also updates its window's HLL
registers, count-min counters, latency histogram and top-K sketch inside
the same fused step, and a closed window hands over one
`WindowSketchBlock` with its documents. There is no sketch-only mode: the
exact rollup runs beside the plane by design. A program whose plane counts
pre-reduced rows (before PR 33: the plane sat behind the batch pre-reduce)
cannot give the deployment's coverage guarantee and is refused at once,
before anything is started.

`pipeline.sketch` keys read here: `num_groups`, `hll_precision`,
`cms_depth`, `cms_width`, `hist_bins`, `hist_vmin`, `hist_gamma`,
`topk_rows`, `topk_cols`, `pending`, `pool` (null = the slab layout, or
the keys of `PoolConfig`). The limits beside them (`distinct_*`) are the
check's (`checks/sketch_blocks.py`).

**What `documents` keeps.** The closed blocks are taken off the pipeline
at every pump, so `L4Pipeline.closed_sketches` never grows. run.py draws
the windows it compares only after the window, from what closed, so every
block is kept for `side_outputs()`, with its planes in the narrowest
integer type that holds them exactly: HLL registers (0...33) as int8,
count-min counters and histogram bins as int32 (what the device held). At
512 groups, p = 14 and 4 x 2^18 counters that is 8.4 + 4.2 + 0.5 MB =
~13 MB a block where the unpacked block is 43 MB; a 51 s run closes 20-35,
under 0.5 GB. The copies are made here, after `feeder.pump()` has
returned: outside `flush.drain` and its children, inside the timed window
(~10 ms a block of host time, with the device idle or not as the feed
left it).
"""

import dataclasses

import numpy as np

import gen
import sut


class Served(sut.Served):
    # what the plane sheds, drops or spills by count is held to 0 too
    guarantee_counters = sut.GUARANTEE_COUNTERS + (
        "pipeline.sketch_shed", "pipeline.sketch_blocks_dropped",
        "pipeline.sketch_pool_spill")

    def __init__(self, config: dict):
        from deepflow_tpu.aggregator import pipeline

        if not getattr(pipeline, "SKETCH_ROWS_ARE_RECORDS", False):
            # before anything is started: a program of before PR 33 fails
            # here at once, and holds no port, thread or device memory
            raise RuntimeError(
                "this program cannot run l4_sketch: its fused step updates the "
                "sketch plane behind the batch pre-reduce, so a block's n_updates "
                "and histogram count pre-reduced rows and depend on where the "
                "feeder cut its batches; the deployment's coverage guarantee (one "
                "update a record) needs pipeline.SKETCH_ROWS_ARE_RECORDS (PR 33)")
        self.blocks = []
        super().__init__(config)

    def window_config(self, config: dict):
        from deepflow_tpu.aggregator.sketchplane import PoolConfig, SketchConfig
        from deepflow_tpu.aggregator.window import WindowConfig
        from deepflow_tpu.ops.histogram import LogHistSpec

        p = config["pipeline"]
        if p.get("cascade") or int(config.get("chips", 1)) != 1:
            raise ValueError("l4_sketch builds the single-chip plane only: "
                             "no cascade, one chip")
        s = p["sketch"]
        return WindowConfig(
            interval=int(p["interval"]), delay=int(p["delay"]),
            capacity=int(p["stash_rows"]), accum_batches=int(p["accum_batches"]),
            sketch=SketchConfig(
                num_groups=int(s["num_groups"]),
                hll_precision=int(s["hll_precision"]),
                cms_depth=int(s["cms_depth"]), cms_width=int(s["cms_width"]),
                hist=LogHistSpec(bins=int(s["hist_bins"]),
                                 vmin=float(s["hist_vmin"]),
                                 gamma=float(s["hist_gamma"])),
                topk_rows=int(s["topk_rows"]), topk_cols=int(s["topk_cols"]),
                pending=int(s["pending"]),
                pool=PoolConfig(**s["pool"]) if s.get("pool") else None))

    def documents(self, out: list) -> list:
        for blk in self.pipe.pop_closed_sketches():
            # the warm-up's windows are stamped before T0
            if blk.window * self.interval >= gen.T0:
                self.blocks.append(dataclasses.replace(
                    blk, hll=blk.hll.astype(np.int8),
                    cms=blk.cms.astype(np.int32), hist=blk.hist.astype(np.int32)))
        return out

    def side_outputs(self) -> dict:
        return {"sketch_blocks": self.blocks}
