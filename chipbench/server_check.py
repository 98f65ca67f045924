"""Checks that use the program as the second witness, outside the window,
on the small prefix window only: the program's own scalar oracle
(`oracle_l4_rollup`) against the flushed documents, and the documents
through the composed Server (decode -> device enrich -> store) with one
SQL and one PromQL answer.

Copied from `chip_smoke.py` (`flowbatch_records`, `oracle_arrays`,
`through_server`). The program has no wiring from the pipeline's flush to
the store, so the documents are re-encoded as pb here in Python: the
reason this is done for a 4,096-record window and not for a timed one.
"""

from __future__ import annotations

import os
import socket
import sys
import time

import numpy as np

SQL_SUM_RTOL = 2e-5  # the querier adds an f32 column in f32 (pairwise)


def oracle_docs(schema: dict, tags: np.ndarray, meters: np.ndarray):
    """The prefix window's documents by the program's scalar oracle:
    (doc tags [g, D] u32, doc meters [g, M] f64)."""
    from deepflow_tpu.aggregator.fanout import FanoutConfig
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
    from deepflow_tpu.oracle.numpy_oracle import oracle_l4_rollup

    fields = schema["flow_record_tag_fields"]
    names = FLOW_METER.field_names()
    records = []
    for i in range(tags.shape[1]):
        rec = {f: int(tags[j, i]) for j, f in enumerate(fields)}
        rec["meter"] = {n: int(meters[i, j]) for j, n in enumerate(names)
                        if meters[i, j]}
        records.append(rec)
    oracle = oracle_l4_rollup(records, FanoutConfig())
    tn = TAG_SCHEMA.field_names()
    t = np.array([[d.tag[k] for k in tn] for d in oracle.values()], np.uint32)
    m = np.array([[d.meter[k] for k in names] for d in oracle.values()],
                 np.float64)
    return t.reshape(-1, len(tn)), m.reshape(-1, len(names))


def _totals(srv, table: str, want_count: float, timeout_s: float = 20.0) -> dict:
    """Count, Sum(byte_tx) and Max(rtt_max) of one table. `docs_written`
    counts a document when the writer takes it, a moment before its table
    can be read: ask again until the rows are there (late is not wrong)."""
    from deepflow_tpu.querier.sqlparse import SQLError

    deadline = time.monotonic() + timeout_s
    while True:
        srv.doc_writer.flush()
        try:
            res = srv.query.execute(
                f"SELECT Count() AS c, Sum(byte_tx) AS b, Max(rtt_max) AS r "
                f"FROM {table}")
            got = {k: float(res.values[k][0]) for k in ("c", "b", "r")}
        except SQLError:
            got = {"c": -1.0, "b": 0.0, "r": 0.0}
        if got["c"] >= want_count or time.monotonic() > deadline:
            return got
        time.sleep(0.05)


def through_server(doc_tags: np.ndarray, doc_meters: np.ndarray, window: int,
                   store_dir: str, flushed_doc: int,
                   stats_module: str = "tpu_pipeline") -> dict:
    """One window's documents as METRICS frames into the composed Server,
    then SQL totals of both tables and two PromQL sums over the server's
    own telemetry, each beside the number the same documents give in
    NumPy. `flushed_doc` is what the deployment says it flushed and
    `stats_module` the name its telemetry reports that under. Returns the
    gaps (see LIMITS)."""
    from deepflow_tpu.datamodel.batch import DocBatch
    from deepflow_tpu.datamodel.code import CodeId, DocumentFlag
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
    from deepflow_tpu.ingest.codec import encode_docbatch
    from deepflow_tpu.ingest.framing import FlowHeader, MessageType, encode_frame
    from deepflow_tpu.integration.dfstats import (
        DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE, system_metric_name, system_sink,
    )
    from deepflow_tpu.querier.promql import query_instant
    from deepflow_tpu.server.main import Server
    from deepflow_tpu.utils.config import load_config
    from deepflow_tpu.utils.stats import default_collector

    cfg, _ = load_config({
        "receiver": {"tcp_port": 0, "udp_port": 0},
        "ingester": {"n_decoders": 2},
        "storage": {"root": os.path.join(store_dir, "store"), "writer_flush_s": 0.2},
    })
    srv = Server(cfg).start()
    sink = system_sink(srv.store)
    default_collector.add_sink(sink)
    out = {}
    try:
        n = doc_tags.shape[0]
        db = DocBatch(tags=doc_tags, meters=doc_meters,
                      timestamp=np.full(n, window, np.uint32),
                      valid=np.ones(n, bool))
        msgs = encode_docbatch(db, flags=int(DocumentFlag.PER_SECOND_METRICS))
        frames = [
            encode_frame(FlowHeader(msg_type=int(MessageType.METRICS), agent_id=1),
                         msgs[off:off + 1024])
            for off in range(0, len(msgs), 1024)
        ]
        with socket.create_connection(("127.0.0.1", srv.receiver.tcp_port),
                                      timeout=30) as sock:
            for fr in frames:
                sock.sendall(fr)
            deadline = time.monotonic() + 120
            while (srv.flow_metrics.get_counters()["docs_written"] < len(msgs)
                   and time.monotonic() < deadline):
                time.sleep(0.02)
        srv.doc_writer.flush()
        fm = srv.flow_metrics.get_counters()
        out["server_docs_gap"] = abs(fm["docs_written"] - len(msgs)) \
            + fm["decode_errors"] + fm["drop_other_region"]

        code = doc_tags[:, TAG_SCHEMA.index("code_id")]
        is_edge = code == int(CodeId.EDGE_IP_PORT)
        exact_gap, sum_err = 0.0, 0.0
        for table, rows in (("network.1s", ~is_edge), ("network_map.1s", is_edge)):
            got = _totals(srv, table, float(rows.sum()))
            m64 = doc_meters[rows].astype(np.float64)
            want_b = float(m64[:, FLOW_METER.index("byte_tx")].sum())
            exact_gap += abs(got["c"] - float(rows.sum())) + abs(
                got["r"] - float(m64[:, FLOW_METER.index("rtt_max")].max(initial=0.0)))
            sum_err = max(sum_err, abs(got["b"] - want_b) / max(want_b, 1.0))
        out["sql_exact_gap"] = exact_gap
        out["sql_sum_rel_err"] = sum_err

        # PromQL over the server's own telemetry: what the device flushed
        # (the pipeline's counter) and what the store took in. The
        # collector's own thread ticks too; an answer that mixes two ticks
        # is asked again a second later (late is not wrong).
        for _attempt in range(3):
            srv.tick()
            now = int(time.time()) + 1
            gap = 0.0
            for metric, want in (
                (system_metric_name(stats_module, "flushed_doc"),
                 float(flushed_doc)),
                (system_metric_name("flow_metrics_ingester", "docs_written"),
                 float(len(msgs))),
            ):
                res = query_instant(srv.store, f"sum({metric})", now,
                                    db=DEEPFLOW_SYSTEM_DB,
                                    table=DEEPFLOW_SYSTEM_TABLE)
                gap += abs(res[0]["value"] - want) if len(res) == 1 \
                    else 1e18  # no single answer
            if gap == 0.0:
                break
            time.sleep(1.1)
        if gap:
            print("promql sources:", [
                (p.module, p.timestamp, p.fields.get("flushed_doc"),
                 p.fields.get("docs_written"))
                for m in (stats_module, "flow_metrics_ingester")
                for p in default_collector.recent(m)][-12:], file=sys.stderr)
        out["promql_gap"] = gap
        return out
    finally:
        default_collector.remove_sink(sink)
        srv.stop()


LIMITS = {"server_docs_gap": 0, "sql_exact_gap": 0,
          "sql_sum_rel_err": SQL_SUM_RTOL, "promql_gap": 0}
