"""The system under test, built from a configuration file: everything the
benchmark takes from the program is imported here and in server_check.py.

  Receiver (TCP) -> `receiver_queues` overwrite queues -> FeederRuntime ->
  PipelineFeedSink -> L4Pipeline / WindowManager (fused step, fold, window
  close, flush).

Copied from `chip_smoke.py` (`make_pipeline`, `drive_feeder`'s set-up,
`_feeder_health`, `_pipeline_health`).
"""

from __future__ import annotations

import numpy as np

import gen


class Served:
    """One deployment, started: the pipeline, its receiver and feeder."""

    def __init__(self, config: dict):
        from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
        from deepflow_tpu.aggregator.window import WindowConfig
        from deepflow_tpu.feeder import FeederConfig, FeederRuntime, PipelineFeedSink
        from deepflow_tpu.ingest.framing import MessageType
        from deepflow_tpu.ingest.queues import new_queue
        from deepflow_tpu.ingest.receiver import Receiver

        p = config["pipeline"]
        if p.get("sketch") or p.get("cascade"):
            raise ValueError("sketch and cascade configurations are not built yet")
        buckets = tuple(p["buckets"])
        self.config = config
        self.interval, self.delay = int(p["interval"]), int(p["delay"])
        self.buckets = buckets
        self.pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(interval=self.interval, delay=self.delay,
                                capacity=int(p["stash_rows"]),
                                accum_batches=int(p["accum_batches"])),
            batch_size=buckets[-1], bucket_sizes=buckets,
            batch_unique_cap=int(p["batch_unique_cap"]),
        ))
        self.receiver = Receiver(tcp_port=0, udp_port=0)
        self.queues = [new_queue(int(config["queue_frames"]))
                       for _ in range(int(config["receiver_queues"]))]
        self.queue_kind = type(self.queues[0]).__name__
        self.receiver.register_handler(MessageType.TAGGEDFLOW, self.queues)
        self.receiver.start()
        self.feeder = FeederRuntime(
            self.queues, PipelineFeedSink(self.pipe), FeederConfig(),
            name="chipbench")

    @property
    def port(self) -> int:
        return self.receiver.tcp_port

    def warm_up(self, schema: dict, source, schedule) -> int:
        """Run every program the window will use, once, before it: the
        fused step at each bucket, the fold, the range flush and its
        fetches. Records of the cell's population, stamped well before T0,
        go straight into the pipeline (the host path compiles nothing) and
        are drained again; their flows come from the configuration's
        `population.seed`, so the windows they close hold the same
        document counts in every run. Then the traffic's own
        `warm_up_event_seconds`, each ingested and closed alone: the
        program compiles a slice and a reshape for every document count it
        flushes, and a window of the run that holds the same flows as one
        of these finds its close compiled. Returns the documents flushed."""
        from deepflow_tpu.datamodel.batch import FlowBatch

        fields = schema["flow_record_tag_fields"]
        ts = fields.index("timestamp")

        def ingest(tags, meters, stamp) -> int:
            tags[ts] = stamp
            n, step, docs = meters.shape[0], self.buckets[-1], 0
            for lo in range(0, n, step):
                fb = FlowBatch(
                    tags={f: tags[j, lo:lo + step] for j, f in enumerate(fields)},
                    meters=meters[lo:lo + step],
                    valid=np.ones(min(step, n - lo), bool))
                docs += sum(db.tags.shape[0] for db in self.pipe.ingest(fb))
            return docs

        src = gen.FlowSource(schema, self.config["population"], seed=0)
        stamp = gen.T0 - 64 * self.interval  # warm-up event time ends before T0
        docs = 0
        sizes = list(self.buckets) + [self.buckets[-1]] * 2 + [self.buckets[0]]
        for i, rows in enumerate(sizes):
            # one bucket to an event-second; the last closes the first
            last = i == len(sizes) - 1
            docs += ingest(*src.second(i, rows, stream=2),
                           stamp + (i + (self.delay + 1) * last) * self.interval)
        docs += sum(db.tags.shape[0] for db in self.pipe.drain())
        stamp += 16 * self.interval
        for k in schedule.warm_up_seconds:
            docs += ingest(*source.second(k, schedule.records_in_second(k)), stamp)
            docs += sum(db.tags.shape[0] for db in self.pipe.drain())
            stamp += 4 * self.interval
        self.block()
        return docs

    def block(self) -> None:
        """Wait for everything dispatched to the device."""
        import jax

        jax.block_until_ready((self.pipe.wm.state, self.pipe.wm.acc))

    def counters(self) -> dict:
        """The counters the guarantees and the layer metrics read, under
        `feeder.`, `receiver.` and `pipeline.` prefixes."""
        out = {}
        for prefix, c in (("feeder", self.feeder.get_counters()),
                          ("receiver", self.receiver.get_counters()),
                          ("pipeline", self.pipe.get_counters())):
            out.update({f"{prefix}.{k}": v for k, v in c.items()
                        if isinstance(v, (int, float))})
        return out

    def spans(self) -> dict:
        """name -> {count, total_us} from both always-on tracers."""
        out = {}
        for tracer in (self.feeder.tracer, self.pipe.tracer):
            for name, s in tracer.summary().items():
                out[name] = {"count": s["count"], "total_us": s["total_us"]}
        return out

    def close(self) -> None:
        self.receiver.stop()
        self.pipe.close()


# a record counted in one of these did not reach exactly one window's rows
GUARANTEE_COUNTERS = (
    "feeder.shed_records", "feeder.lost_records", "feeder.bad_frames",
    "feeder.emit_failures", "feeder.degraded_entries",
    "feeder.queue_overwritten", "feeder.decode_errors",
    "receiver.bad_frames", "receiver.no_handler",
    "pipeline.stash_evictions", "pipeline.prereduce_shed",
    "pipeline.drop_before_window", "pipeline.jit_retraces",
    "pipeline.fetch_retries", "pipeline.dispatch_retries",
)
