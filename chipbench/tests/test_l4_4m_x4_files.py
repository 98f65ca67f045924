"""The files of the deployment `l4_1s_4m_x4_sketch` and its cell
`l4_4m_x4_sketch.saturate` (PR 36): the configuration says what its
`BENCHMARK.json` entry says, is `l4_1s_1m_sketch`'s plane and guarantees
word for word on four chips, loads through the runner and names a builder
and checks that are there; each of the five layer files pairs with its
entry, reads the right number from planes made by hand and nothing from a
program without the span or counter; the trace groups load beside the ones
that are there; and a program without the sharded close's spans (the parent)
fails at the builder's import."""

import json
import os

import pytest

import layers
import run as chipbench_run
import sut
import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "l4_4m_x4_sketch.saturate"


@pytest.fixture(scope="module")
def spec():
    return chipbench_run.load_cell(CELL)


def test_configuration_says_what_its_entry_says(spec):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [c for c in bench["configs"] if c["name"] == "l4_1s_4m_x4_sketch"]
    with open(os.path.join(ROOT, "chipbench", "configs", "l4_1s_1m_sketch.json")) as f:
        one_chip = json.load(f)
    cfg = spec["config"]
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert len(cfg["source"]) <= 200 and cfg["source"].startswith("BASELINE.json configs[4]")
    assert cfg["reduced"] == entry["reduced"] == ["flows_sent", "tiers"]
    for cut in cfg["reduced"]:
        assert set(cfg[cut]) == {"source", "here", "why"}
    assert spec["config_path"].endswith(entry["file"])
    assert spec["cell"] == {**spec["cell"], "config": "l4_1s_4m_x4_sketch",
                            "traffic": "saturate_x4", "chips": 4}
    assert [m["name"] for m in spec["end_to_end"]] == ["records_per_s", "setup_s"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # 64 agents at upstream's flow limit, 16 a chip
    assert cfg["population"] == {**cfg["population"], "tuples": 64 * 65535, "keys": "uniform"}
    assert (cfg["chips"], cfg["agents"]) == (4, 64)
    p, q = cfg["pipeline"], one_chip["pipeline"]
    # a device's ring is one chip's: a quarter of the bucket x 4 lanes x 8
    assert 4 * (p["batch_unique_cap"] // 4) * p["accum_batches"] \
        == 4 * q["batch_unique_cap"] * q["accum_batches"] == 1 << 20
    assert p["sketch"] == q["sketch"] and p["cascade"] is False
    for key in ("interval", "delay", "stash_rows", "buckets", "batch_unique_cap"):
        assert p[key] == q[key], key
    for key in ("record", "receiver_queues", "queue_frames"):
        assert cfg[key] == one_chip[key], key
    # the one-chip deployment's guarantees word for word, `merge` beside them
    assert {k: cfg["guarantees"][k] for k in one_chip["guarantees"]} == one_chip["guarantees"]
    assert set(cfg["guarantees"]) - set(one_chip["guarantees"]) == {"merge"}
    assert (cfg["built_by"], cfg["checks"]) == ("l4_sharded", ["sketch_blocks", "pod_partials"])
    for kind, name, dirs in [("deployment", cfg["built_by"], sut.DEPLOYMENT_DIRS)] \
            + [("check", c, chipbench_run.CHECK_DIRS) for c in cfg["checks"]]:
        assert any(os.path.exists(os.path.join(d, f"{name}.py")) for d in dirs), (kind, name)


def test_no_two_flows_of_the_population_share_a_document_key_and_differ_in_pod_id(spec):
    """The rule `population.seed` was chosen by: every document's tag row
    is a function of its key, as the plain reference assumes."""
    import pod_id_pairs

    pop = spec["config"]["population"]
    assert pod_id_pairs.collisions(pop["seed"], pop["tuples"]) == [0, 0, 0]
    assert sum(pod_id_pairs.collisions(1, pop["tuples"])) > 0  # the other files' seed


def test_traffic_has_four_connections_and_the_pods_event_second(spec):
    t = spec["traffic"]
    assert (t["loop"], t["clients"], t["records_per_event_second"]) == ("closed", 4, 1 << 20)
    assert (t["in_flight_event_seconds"], t["key_draw"], t["prefix_records"]) \
        == (1, "each_second", 4096)
    assert t["warm_up_event_seconds"] == [0] and t["trace_slice"]["window_closes"] == 2
    with open(os.path.join(ROOT, "chipbench", "traffic", "saturate.json")) as f:
        one = json.load(f)
    assert t["records_per_event_second"] == 4 * one["records_per_event_second"]


def test_a_program_without_the_sharded_spans_fails_at_the_builders_import(spec, monkeypatch):
    """What the parent commit does with the benchmark's files laid over
    it: the builder's import of the close's new span names raises, before
    a port, a thread or device memory is held."""
    from deepflow_tpu.utils import spans

    monkeypatch.delattr(spans, "SPAN_FLUSH_SKETCH_MERGE")
    with pytest.raises(ImportError, match="SPAN_FLUSH_SKETCH_MERGE"):
        sut.build(spec["config"])
    monkeypatch.undo()
    with pytest.raises(ValueError, match=r"chips.*built_by"):
        sut.build({k: v for k, v in spec["config"].items() if k != "built_by"})


def test_trace_groups_add_and_redefine_none():
    groups = trace_reduce.load_groups()
    assert groups["sharded_window_close"] == ["jit_window_close_sharded"]
    assert groups["fused_step"] == ["jit_step"]
    # the sharded step, fold and range flush fall into the accepted groups
    for module, group in (("jit_step_sharded(7)", "fused_step"),
                          ("jit__fold_sharded(8)", "fold"),
                          ("jit__flush_range_sharded(9)", "flush_range"),
                          ("jit_window_close_sharded(3)", "sharded_window_close"),
                          ("jit__take_page(1)", "take_page")):
        assert trace_reduce.module_group(module, groups) == group


PLANES = {
    "spans": {"window.close_collective": {"count": 15, "total_us": 15 * 2_500},
              "flush.sketch_merge": {"count": 15, "total_us": 15 * 120_000}},
    "counters": {"pipeline.flush_partial_rows": 13_200_000,
                 "feeder.records_in": 12_000_000},
    "run": {"windows_closed": 15},
    "schema": {"record_bytes": 396},
    "trace": {"slice_records": 4_000_000, "busy_s": 3.0,
              "module_s": {"fused_step": 0.5, "sharded_window_close": 0.03}},
    "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}
WANT = {
    "sharded_step_roofline": 100.0 * (4_000_000 * 396 * 0.25 / 819e9) / 0.5,
    "close.collective_share_of_busy": 1.0,
    "close.collective_ms_per_window": 2.5,
    "close.sketch_merge_ms_per_window": 120.0,
    "close.partial_rows_per_record": 1.1,
}
# the parent commit's planes: no such span, counter or module group
PARENT = {"spans": {}, "counters": {"feeder.records_in": 12_000_000},
          "run": {"windows_closed": 15}, "schema": {"record_bytes": 396},
          "trace": {"slice_records": 4_000_000, "busy_s": 3.0, "module_s": {}},
          "peaks": PLANES["peaks"]}


@pytest.mark.parametrize("name", sorted(WANT))
def test_layer_file_pairs_with_its_entry_and_reads_by_hand(name, spec):
    layer = layers.load_layer(name)
    entry, = [m for m in spec["per_layer"] if m["name"] == name]
    assert {k: layer[k] for k in entry} == entry
    assert entry["workloads"] == [CELL] and entry["moves"] == "records_per_s"
    assert layers.read_metric(layer, PLANES) == pytest.approx(WANT[name])
    for planes in ({}, PARENT):
        assert layers.read_metric(layer, planes) is None


def test_the_cell_reports_every_metric_without_a_list_and_its_own_five(spec):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    everyones = [m["name"] for m in per_layer if "workloads" not in m]
    names = [m["name"] for m in spec["per_layer"]]
    assert names == everyones + sorted(WANT, key=names.index) and len(names) == 27 + 5
    for cell in ("l4_10k.saturate", "l4_1m.saturate", "l4_1m_sketch.saturate"):
        assert not set(WANT) & {m["name"] for m in chipbench_run.load_cell(cell)["per_layer"]}
