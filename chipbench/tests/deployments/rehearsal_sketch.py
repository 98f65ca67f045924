"""Rehearsal builder (a): today's single-chip L4 deployment with the
per-window sketch plane on. Throw-away: it proves the seam, at tiny.py's
sizes on the CPU; the deployment PERF.md section 7 row 2 asks for brings a
builder of its own under chipbench/deployments/."""

import sut


class Served(sut.Served):
    # the sketch plane sheds and drops by count too: both are held to 0
    guarantee_counters = sut.GUARANTEE_COUNTERS + (
        "pipeline.sketch_shed", "pipeline.sketch_blocks_dropped")

    def __init__(self, config: dict):
        self.blocks = []
        super().__init__(config)

    def window_config(self, config: dict):
        from deepflow_tpu.aggregator.sketchplane import SketchConfig
        from deepflow_tpu.aggregator.window import WindowConfig

        p = config["pipeline"]
        return WindowConfig(
            interval=int(p["interval"]), delay=int(p["delay"]),
            capacity=int(p["stash_rows"]), accum_batches=int(p["accum_batches"]),
            sketch=SketchConfig(hll_precision=int(p["sketch"]["hll_precision"])))

    def documents(self, out: list) -> list:
        self.blocks += self.pipe.pop_closed_sketches()
        return out

    def side_outputs(self) -> dict:
        return {"sketch_blocks": self.blocks}
