"""The yardstick's own parts, each against something independent of it:
the frozen schema against the program's, the wire encoder against the
program's decoder, the reference against the program's scalar oracle, the
comparison against planted faults and the low-precision control, the
generator's key draws, the layer reader on empty planes."""

import json
import os

import ml_dtypes
import numpy as np
import pytest

import gen
import layers
import reference
import server_check
import wire

SCHEMA = gen.load_schema()


def test_schema_is_the_programs():
    from deepflow_tpu.aggregator.fanout import FanoutConfig
    from deepflow_tpu.datamodel.batch import FLOW_RECORD_TAG_FIELDS
    from deepflow_tpu.datamodel.code import CodeId, Direction, MeterId, SignalSource
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
    from deepflow_tpu.feeder.flowframe import FLOWFRAME_MAGIC, FLOWFRAME_VERSION
    from deepflow_tpu.ingest.framing import HEADER_LEN, HEADER_VERSION, MessageType

    assert SCHEMA["flow_record_tag_fields"] == list(FLOW_RECORD_TAG_FIELDS)
    assert SCHEMA["flow_meter"] == [
        {"name": f.name, "op": f.op.value, "reverse_with": f.reverse_with,
         "zero_on_reverse": bool(f.zero_on_reverse)} for f in FLOW_METER.fields]
    assert SCHEMA["doc_tags"] == [
        {"name": n, "key": bool(k)}
        for n, k in zip(TAG_SCHEMA.field_names(), TAG_SCHEMA.key_mask)]
    e = SCHEMA["enums"]
    assert (e["signal_source_packet"], e["meter_id_flow"]) == (
        int(SignalSource.PACKET), int(MeterId.FLOW))
    assert (e["code_single_ip_port"], e["code_edge_ip_port"]) == (
        int(CodeId.SINGLE_IP_PORT), int(CodeId.EDGE_IP_PORT))
    assert (e["direction_client_to_server"], e["direction_server_to_client"]) == (
        int(Direction.CLIENT_TO_SERVER), int(Direction.SERVER_TO_CLIENT))
    cfg = FanoutConfig()
    assert SCHEMA["fanout"] == {"global_thread_id": cfg.global_thread_id,
                                "agent_id": cfg.agent_id}
    w = SCHEMA["wire"]
    assert (w["msg_type_taggedflow"], w["header_version"], w["header_len"],
            w["flowframe_magic"], w["flowframe_version"]) == (
        int(MessageType.TAGGEDFLOW), HEADER_VERSION, HEADER_LEN,
        FLOWFRAME_MAGIC, FLOWFRAME_VERSION)


def _records(tuples=150, n=1200, seed=3, k=0):
    return gen.FlowSource(SCHEMA, {"tuples": tuples}, seed).second(k, n)


def test_the_programs_decoder_reads_the_frames():
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.feeder.flowframe import decode_flowframe_body
    from deepflow_tpu.ingest.framing import (
        HEADER_LEN, FlowHeader, FrameReassembler, split_message_spans)

    tags, meters = _records()
    frames = wire.encode_frames(tags, meters, {**SCHEMA["wire"], "rows_per_frame": 500})
    assert len(frames) == 3
    parts = []
    for header, body in FrameReassembler().feed(b"".join(frames)):
        assert header.msg_type == SCHEMA["wire"]["msg_type_taggedflow"]
        assert FlowHeader.parse(header.encode()).frame_size == HEADER_LEN + len(body)
        parts += [decode_flowframe_body(body[o:o + ln])
                  for o, ln in split_message_spans(body)]
    fb = FlowBatch.concat(parts)
    for i, f in enumerate(SCHEMA["flow_record_tag_fields"]):
        assert np.array_equal(fb.tags[f], tags[i]), f
    assert np.array_equal(fb.meters, meters)


def test_seeds_beyond_32_bits_and_regeneration():
    a = gen.FlowSource(SCHEMA, {"tuples": 50}, 2**31 + 12345)
    b = gen.FlowSource(SCHEMA, {"tuples": 50}, 2**31 + 12345)
    for k in (0, 7):
        assert all(np.array_equal(x, y) for x, y in zip(a.second(k, 64), b.second(k, 64)))
    assert not np.array_equal(a.second(1, 64)[1], a.second(2, 64)[1])
    assert not np.array_equal(a.second(1, 64)[1], a.second(1, 64, stream=2)[1])


ZIPF = {"tuples": 2000, "keys": "zipf", "zipf_s": 1.1, "seed": 1}


def test_keys_follow_the_configuration_and_meters_the_seed():
    a, b = (gen.FlowSource(SCHEMA, ZIPF, seed) for seed in (1, 2**31 + 5))
    for k in (0, 3):
        (ta, ma), (tb, mb) = a.second(k, 4096), b.second(k, 4096)
        assert np.array_equal(ta, tb)  # same flows, record by record
        assert not np.array_equal(ma, mb)  # other meters
    other = gen.FlowSource(SCHEMA, {**ZIPF, "seed": 2}, 1)
    assert not np.array_equal(a.second(1, 4096)[0], other.second(1, 4096)[0])


def test_zipf_draw_is_skewed_and_the_active_set_changes_each_second():
    src = gen.FlowSource(SCHEMA, ZIPF, 1)
    counts = np.bincount(src.flows(1, 50_000), minlength=2000)
    assert counts[0] > 20 * np.median(counts) and counts.argmax() == 0
    active = [frozenset(np.unique(src.flows(k, 50_000)).tolist()) for k in range(1, 9)]
    assert len({len(s) for s in active}) > 4 and len(set(active)) == 8
    same = gen.FlowSource(SCHEMA, ZIPF, 1, key_draw="same_every_second")
    assert np.array_equal(same.flows(1, 50_000), same.flows(7, 50_000))
    assert not np.array_equal(same.second(1, 512)[1], same.second(7, 512)[1])
    uniform = gen.FlowSource(SCHEMA, {"tuples": 2000}, 1)
    assert np.bincount(uniform.flows(1, 50_000), minlength=2000).max() < 80


def test_every_full_window_of_saturate_has_another_document_count():
    """What made every close of l4_10k.saturate compile before PR 27 (and
    what the two traffic files still differ in): at the cell's own size,
    the first 12 event-seconds' windows hold 12 different document
    counts; under `steady` they hold one."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "configs", "l4_1s_10k.json")) as f:
        population = json.load(f)["population"]
    counts = {}
    for name in ("saturate", "steady"):
        with open(os.path.join(root, "chipbench", "traffic", name + ".json")) as f:
            sched = gen.Schedule(json.load(f), SCHEMA["wire"]["rows_per_frame"])
        src = gen.FlowSource(SCHEMA, population, 1, sched.key_draw)
        counts[name] = [reference.reference_docs(
            SCHEMA, *src.second(k, sched.records_in_second(k)))[0].shape[0]
            for k in range(1, 13)]
    assert len(set(counts["saturate"])) == 12, counts
    assert len(set(counts["steady"])) == 1, counts


def test_reference_matches_the_programs_oracle():
    tags, meters = _records()
    want = server_check.oracle_docs(SCHEMA, tags, meters)
    got = reference.reference_docs(SCHEMA, tags, meters)
    c = reference.compare_docs(SCHEMA, got[0], got[1].astype(np.float32), *want)
    assert c["docs"] == c["docs_got"] > 0
    assert all(c[k] <= lim for k, lim in reference.LIMITS.items()), c


@pytest.mark.parametrize("fault,number", [
    ("wrong_sum", "sum_rel_err"), ("wrong_max", "max_lanes_differ"),
    ("missing_doc", "unpaired_docs"), ("extra_doc", "unpaired_docs"),
    ("wrong_tag", "tag_rows_differ"),
])
def test_compare_catches(fault, number):
    tags, meters = _records(50, 400, 1)
    want_t, want_m = reference.reference_docs(SCHEMA, tags, meters)
    got_t, got_m = want_t.copy(), want_m.astype(np.float32)
    names = [m["name"] for m in SCHEMA["flow_meter"]]
    doc = [d["name"] for d in SCHEMA["doc_tags"]]
    if fault == "wrong_sum":
        got_m[0, names.index("byte_tx")] *= 1.00001
    elif fault == "wrong_max":
        got_m[0, names.index("rtt_max")] += 1
    elif fault == "missing_doc":
        got_t, got_m = got_t[1:], got_m[1:]
    elif fault == "extra_doc":
        extra = got_t[:1].copy()
        extra[0, doc.index("server_port")] += 1
        got_t, got_m = np.concatenate([got_t, extra]), np.concatenate([got_m, got_m[:1]])
    elif fault == "wrong_tag":
        col = next(i for i, d in enumerate(SCHEMA["doc_tags"]) if not d["key"])
        got_t[0, col] += 1
    c = reference.compare_docs(SCHEMA, got_t, got_m, want_t, want_m)
    assert c[number] > reference.LIMITS[number], c
    clean = reference.compare_docs(SCHEMA, want_t, want_m.astype(np.float32), want_t, want_m)
    assert all(clean[k] <= lim for k, lim in reference.LIMITS.items())


def test_the_low_precision_control_is_not_correct():
    """The reference in the program's place, every sum and maximum held in
    bfloat16 (the step below the float32 the configuration states): the
    SUM lanes leave their limit by orders of magnitude."""
    tags, meters = _records(300, 6000, 5)
    want = reference.reference_docs(SCHEMA, tags, meters)
    ctl = reference.reference_docs(SCHEMA, tags, meters, acc_dtype=ml_dtypes.bfloat16)
    c = reference.compare_docs(SCHEMA, ctl[0], ctl[1].astype(np.float32), *want)
    assert c["unpaired_docs"] == 0
    assert c["sum_rel_err"] > 1000 * reference.SUM_RTOL
    assert c["max_lanes_differ"] > 0


def test_reader_finds_nothing_returns_nothing():
    spec = layers.load_layer("fold.ms_per_window")
    assert layers.read_metric(spec, {"spans": {}, "run": {"windows_closed": 3}}) is None
    assert layers.read_metric(
        spec, {"spans": {"window.fold": {"total_us": 5000}}, "run": {"windows_closed": 0}}) is None
    assert layers.read_metric(
        spec, {"spans": {"window.fold": {"total_us": 5000}}, "run": {"windows_closed": 2}}) == 2.5
    idle = layers.load_layer("device.idle_share")
    assert layers.read_metric(idle, {"spans": {}}) is None  # no trace plane taken


def test_benchmark_json_and_layer_files_agree():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        spec = layers.load_layer(m["name"])
        assert {k: spec[k] for k in m} == m
        assert set(m.get("workloads", cells)) <= set(e2e[m["moves"]].get("workloads", cells))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(root, "chipbench", "traffic", w["traffic"] + ".json"))
        assert any(m["name"] != "setup_s" and w["name"] in m.get("workloads", cells)
                   for m in bench["end_to_end"])
        assert any(w["name"] in m.get("workloads", cells) for m in bench["per_layer"])
    for c in bench["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            assert json.load(f)["source"] == c["source"]


def test_benchmark_json_keeps_the_contract_s_limits():
    import re

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    path = os.path.join(root, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
    assert 1 <= bench["run_seconds"] <= 51 and bench["paths"] == ["chipbench"]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("chipbench/") and len(c["reduced"]) <= 16
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4)
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in layers.SOURCES and line(m["layer"])
    for m in metrics:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
