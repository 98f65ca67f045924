"""The system under test, built from a configuration file: everything the
benchmark takes from the program is imported here, in server_check.py and
in the deployment a configuration names.

**Who builds it.** `build(config)` is what run.py calls. A configuration
that carries `"built_by": "<name>"` is built by `deployments/<name>.py`
(looked for in DEPLOYMENT_DIRS): the file exports `Served`, called as
`Served(config)`. One that says nothing is built by `Served` below,
today's single-chip L4 deployment:

  Receiver (TCP) -> `receiver_queues` overwrite queues -> FeederRuntime ->
  PipelineFeedSink -> L4Pipeline / WindowManager (fused step, fold, window
  close, flush).

(copied from `chip_smoke.py`: `make_pipeline`, `drive_feeder`'s set-up,
`_feeder_health`, `_pipeline_health`). It builds no sketch plane, no
cascade and no second chip; a configuration that asks for one of them and
names no builder is an error that says which builder is missing.

**The protocol.** run.py and server_check.py use a deployment through
these names and no others (nothing reaches through it to a pipeline or a
window manager):

  port            the TCP port the load generator's clients connect to
  queue_kind      the receive queues' type name (printed, not compared)
  feeder          pump() and flush() return what closed; get_counters()
                  carries records_in, records_out, emit_failures,
                  degraded_entries; tracer is its SpanTracer
  warm_up(schema, source, schedule) -> documents flushed: runs every
                  program the window will use, before it
  block()         wait for everything dispatched to the device
  counters()      name -> number under `feeder.`, `receiver.`, `pipeline.`
                  prefixes; every name in `guarantee_counters` is there
  spans()         name -> {count, total_us}
  tracers()       the SpanTracers whose recent() spans the idle-gap
                  attribution of a traced run reads
  drain()         close what is still open after the window; returns as
                  pump() does
  documents(out)  given what pump() / flush() / drain() returned, the
                  base-interval DocBatches among it, one window each
                  (what by_window, the conservation check and the
                  row-by-row comparison read), comparable as they stand:
                  a deployment that flushes partial rows merges them by
                  key. Inside the window run.py reads only
                  `timestamp[0]` of a returned batch; `tags`, `meters`
                  and `valid` after it. Everything else that closed (tier
                  DocBatches with their interval, closed
                  WindowSketchBlocks) the deployment keeps for
  side_outputs()  a dict the configuration's named checks read, asked
                  once, after drain()
  flushed_docs()  the count of documents the program says it flushed,
  stats_module    and the name its telemetry reports `flushed_doc` under
                  (the PromQL check asks the composed Server for that sum)
  guarantee_counters  the counters whose limit is 0; GUARANTEE_COUNTERS
                  below or a longer tuple, never a shorter one
  close()
"""

from __future__ import annotations

import importlib.util
import os
import re

import numpy as np

import gen

HERE = os.path.dirname(os.path.abspath(__file__))
# where `built_by` is looked for; a test appends a directory of its own
DEPLOYMENT_DIRS = [os.path.join(HERE, "deployments")]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

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


def load_named(kind: str, name: str, dirs: list):
    """The module `<name>.py` from the first of `dirs` that holds it: how
    a configuration's `built_by` and `checks` names become code."""
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"{kind} name {name!r} is not a plain file name")
    for d in dirs:
        path = os.path.join(d, f"{name}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module
    raise FileNotFoundError(
        f"no {kind} {name!r}: {name}.py is in none of {dirs}")


def build(config: dict):
    """The configuration's deployment, started."""
    name = config.get("built_by")
    if name is None:
        return Served(config)
    return load_named("deployment", name, DEPLOYMENT_DIRS).Served(config)


class Served:
    """One deployment, started: the pipeline, its receiver and feeder."""

    guarantee_counters = GUARANTEE_COUNTERS
    stats_module = "tpu_pipeline"

    def __init__(self, config: dict):
        from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
        from deepflow_tpu.feeder import PipelineFeedSink

        p = config["pipeline"]
        buckets = tuple(p["buckets"])
        self.config = config
        self.interval, self.delay = int(p["interval"]), int(p["delay"])
        self.buckets = buckets
        self.pipe = L4Pipeline(PipelineConfig(
            window=self.window_config(config),
            batch_size=buckets[-1], bucket_sizes=buckets,
            batch_unique_cap=int(p["batch_unique_cap"]),
        ))
        self.serve(config, PipelineFeedSink(self.pipe))

    def serve(self, config: dict, sink) -> None:
        """Receiver, its queues and the feeder that pumps them into
        `sink`: the front of every deployment."""
        from deepflow_tpu.feeder import FeederConfig, FeederRuntime
        from deepflow_tpu.ingest.framing import MessageType
        from deepflow_tpu.ingest.queues import new_queue
        from deepflow_tpu.ingest.receiver import Receiver

        self.receiver = Receiver(tcp_port=0, udp_port=0)
        self.queues = [new_queue(int(config["queue_frames"]))
                       for _ in range(int(config["receiver_queues"]))]
        self.queue_kind = type(self.queues[0]).__name__
        self.receiver.register_handler(MessageType.TAGGEDFLOW, self.queues)
        self.receiver.start()
        self.feeder = FeederRuntime(self.queues, sink, FeederConfig(),
                                    name="chipbench")

    def window_config(self, config: dict):
        """The pipeline's WindowConfig. A builder that keeps this
        single-chip deployment and switches a plane on (deployments/
        <name>.py: `class Served(sut.Served)`) overrides this, and
        `documents` / `side_outputs` for what the plane closes. One that
        replaces the pipeline keeps `serve`, `warm_up`, `counters` and
        `spans` and overrides what they call: `ingest_direct`,
        `end_warm_up_windows`, `block`, `pipeline_counters`, `tracers`."""
        from deepflow_tpu.aggregator.window import WindowConfig

        p = config["pipeline"]
        beyond = [k for k in ("sketch", "cascade") if p.get(k)] \
            + ["chips"] * (int(config.get("chips", 1)) != 1)
        if beyond:
            raise ValueError(
                f"configuration {config.get('name')!r} sets {beyond} and names no "
                "`built_by` that builds them: sut.Served is the single-chip L4 "
                "deployment only; add chipbench/deployments/<builder>.py and "
                "name it in the configuration")
        return WindowConfig(interval=int(p["interval"]), delay=int(p["delay"]),
                            capacity=int(p["stash_rows"]),
                            accum_batches=int(p["accum_batches"]))

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
        `warm_up_event_seconds`, each ingested and closed alone, at the
        window's own record counts. Since PR 27 the close fetches
        fixed-size pages and no program's shape depends on a document
        count, so these closes warm nothing the buckets above did not;
        they stay as the rehearsal of a full-size close before the clock
        starts. Returns the documents flushed."""
        fields = schema["flow_record_tag_fields"]

        def ingest(tags, meters, stamp) -> int:
            return self.ingest_direct(fields, tags, meters, stamp)

        def close() -> int:
            return sum(db.tags.shape[0] for db in self.end_warm_up_windows())

        src = gen.FlowSource(schema, self.config["population"], seed=0)
        stamp = gen.T0 - 64 * self.interval  # warm-up event time ends before T0
        docs = 0
        sizes = list(self.buckets) + [self.buckets[-1]] * 2 + [self.buckets[0]]
        for i, rows in enumerate(sizes):
            # one bucket to an event-second; the last closes the first
            last = i == len(sizes) - 1
            docs += ingest(*src.second(i, rows, stream=2),
                           stamp + (i + (self.delay + 1) * last) * self.interval)
        docs += close()
        stamp += 16 * self.interval
        for k in schedule.warm_up_seconds:
            docs += ingest(*source.second(k, schedule.records_in_second(k)), stamp)
            docs += close()
            stamp += 4 * self.interval
        self.block()
        return docs

    def ingest_direct(self, fields: list, tags, meters, stamp: int) -> int:
        """Records (`tags` [T, n] in `fields` order, `meters` [n, M]), all
        stamped `stamp`, straight into the pipeline in batches of the
        largest bucket; returns the documents that closed."""
        from deepflow_tpu.datamodel.batch import FlowBatch

        tags[fields.index("timestamp")] = stamp
        n, step, docs = meters.shape[0], self.buckets[-1], 0
        for lo in range(0, n, step):
            fb = FlowBatch(
                tags={f: tags[j, lo:lo + step] for j, f in enumerate(fields)},
                meters=meters[lo:lo + step],
                valid=np.ones(min(step, n - lo), bool))
            docs += sum(db.tags.shape[0] for db in self.pipe.ingest(fb))
        return docs

    def end_warm_up_windows(self) -> list:
        """Close the windows the warm-up has open, by a drain. A builder
        whose drain is final (with a cascade no tier window closes after
        it) returns [] here: its warm-up's windows close as event time
        moves on, the last of them under the run's first records, and its
        `documents` drops what is stamped before T0."""
        return self.drain()

    def block(self) -> None:
        """Wait for everything dispatched to the device."""
        import jax

        jax.block_until_ready((self.pipe.wm.state, self.pipe.wm.acc))

    def pipeline_counters(self) -> dict:
        return self.pipe.get_counters()

    def counters(self) -> dict:
        """The counters the guarantees and the layer metrics read, under
        `feeder.`, `receiver.` and `pipeline.` prefixes."""
        out = {}
        for prefix, c in (("feeder", self.feeder.get_counters()),
                          ("receiver", self.receiver.get_counters()),
                          ("pipeline", self.pipeline_counters())):
            out.update({f"{prefix}.{k}": v for k, v in c.items()
                        if isinstance(v, (int, float))})
        return out

    def spans(self) -> dict:
        """name -> {count, total_us} from the always-on tracers."""
        out = {}
        for tracer in self.tracers():
            for name, s in tracer.summary().items():
                out[name] = {"count": s["count"], "total_us": s["total_us"]}
        return out

    def tracers(self) -> list:
        return [self.feeder.tracer, self.pipe.tracer]

    def drain(self) -> list:
        return self.pipe.drain()

    def documents(self, out: list) -> list:
        """Every batch this deployment flushes is one base-interval
        window's whole rows; it has no tier and no sketch plane."""
        return out

    def side_outputs(self) -> dict:
        return {}

    def flushed_docs(self) -> int:
        return self.pipe.get_counters()["flushed_doc"]

    def close(self) -> None:
        self.receiver.stop()
        self.pipe.close()
