"""The files of the deployment `l4_1s_1m` and its cell `l4_1m.saturate`
(PR 28): the configuration loads through the runner and says what its
`BENCHMARK.json` entry says, the generator gives the flow count the
configuration's `deployment` text states, and each of the four layer
files the PR brought reads the right number from planes made by hand
and nothing (not 0) from a program that lacks the counter or span."""

import json
import os

import numpy as np
import pytest

import gen
import layers
import run as chipbench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def spec():
    return chipbench_run.load_cell("l4_1m.saturate")


def test_configuration_loads_and_agrees_with_its_entry(spec):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [c for c in bench["configs"] if c["name"] == "l4_1s_1m"]
    cfg = spec["config"]
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert len(cfg["source"]) <= 200 and cfg["reduced"] == entry["reduced"] == []
    assert spec["config_path"].endswith(entry["file"])
    assert spec["cell"] == {**spec["cell"], "config": "l4_1s_1m",
                            "traffic": "saturate", "chips": 1}
    assert [m["name"] for m in spec["end_to_end"]] == ["records_per_s", "setup_s"]
    assert cfg["population"] == {"tuples": 16 * 65535, "keys": "uniform", "seed": 1}
    assert cfg["agents"] == 16 and cfg["chips"] == 1
    # every width as the 10k deployment has it, but the stash
    with open(os.path.join(ROOT, "chipbench", "configs", "l4_1s_10k.json")) as f:
        small = json.load(f)
    assert cfg["pipeline"] == {**small["pipeline"], "stash_rows": 1 << 22}
    assert cfg["record"] == small["record"] and cfg["guarantees"] == small["guarantees"]
    for key in ("receiver_queues", "queue_frames"):
        assert cfg[key] == small[key]


def test_a_second_touches_231912_flows_and_most_are_alone(spec):
    schema = gen.load_schema()
    schedule = gen.Schedule(spec["traffic"], schema["wire"]["rows_per_frame"])
    assert schedule.records_in_second(3) == 262144
    source = gen.FlowSource(schema, spec["config"]["population"], seed=99,
                            key_draw=schedule.key_draw)
    flows, counts = np.unique(source.flows(3, 262144), return_counts=True)
    assert flows.size == 231912
    assert int((counts == 1).sum()) == 204074  # 88.0% of the flows, 77.8% of the records
    # the flows follow the file's population.seed, not --seed
    other = gen.FlowSource(schema, spec["config"]["population"], seed=7,
                           key_draw=schedule.key_draw)
    assert np.array_equal(other.flows(3, 262144), source.flows(3, 262144))


PLANES = {
    "counters": {"pipeline.stash_live_rows_sum": 3 * 1_940_000,
                 "pipeline.stash_capacity_rows_sum": 3 * 4_194_304,
                 "pipeline.doc_in": 812_000, "feeder.records_in": 262_144,
                 "pipeline.flush_rows_live": 20 * 765_487},
    "spans": {"flush.fetch": {"count": 20, "total_us": 20 * 150_000}},
    "run": {"windows_closed": 20},
}
WANT = {
    "stash.live_share": 100 * 1_940_000 / 4_194_304,
    "step.doc_rows_per_record": 812_000 / 262_144,
    "flush.docs_per_window": 765_487.0,
    "flush.fetch_ms_per_window": 150.0,
}
# the parent commit's planes: none of this PR's counters and spans. It has
# `flush_rows_live` (PR 27), so it reports `flush.docs_per_window` too
PARENT = {"counters": {"feeder.records_in": 262_144,
                       "pipeline.flush_rows_live": 20 * 765_487},
          "spans": {}, "run": {"windows_closed": 20}}
NEW_IN_THE_PROGRAM = sorted(set(WANT) - {"flush.docs_per_window"})


@pytest.mark.parametrize("name", sorted(WANT))
def test_layer_file_reads_the_number_by_hand_and_nothing_from_nothing(name, spec):
    layer = layers.load_layer(name)
    entry, = [m for m in spec["per_layer"] if m["name"] == name]
    assert {k: layer[k] for k in entry} == entry and "workloads" not in entry
    assert entry["moves"] == "records_per_s"
    assert layers.read_metric(layer, PLANES) == pytest.approx(WANT[name])
    for planes in ({}, {"counters": {}, "spans": {}, "run": {}},
                   {**PLANES, "run": {"windows_closed": 0}} if "window" in name
                   else {"counters": {"feeder.records_in": 0}}):
        assert layers.read_metric(layer, planes) is None
    want = None if name in NEW_IN_THE_PROGRAM else pytest.approx(WANT[name])
    assert layers.read_metric(layer, PARENT) == want
