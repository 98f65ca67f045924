"""The files of the deployment `l4_1s_1m_sketch` and its cell
`l4_1m_sketch.saturate` (PR 33): the configuration is `l4_1s_1m.json` plus
the plane and nothing else, says what its `BENCHMARK.json` entry says,
loads through the runner and is built by the builder it names; each of the
three layer files pairs with its entry, reads the right number from planes
made by hand and nothing from a program without the plane; and a checkout
without `deployments/l4_sketch.py` (the parent) fails at once, by name."""

import json
import os

import pytest

import layers
import run as chipbench_run
import sut

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "l4_1m_sketch.saturate"
# what the new file may say differently from l4_1s_1m.json, or add to it
ITS_OWN = {"name", "source", "deployment", "reduced", "flows_sent", "assumed",
           "guarantees", "built_by", "checks"}


@pytest.fixture(scope="module")
def spec():
    return chipbench_run.load_cell(CELL)


def test_configuration_is_l4_1s_1m_plus_the_plane_and_nothing_else(spec):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [c for c in bench["configs"] if c["name"] == "l4_1s_1m_sketch"]
    with open(os.path.join(ROOT, "chipbench", "configs", "l4_1s_1m.json")) as f:
        exact = json.load(f)
    cfg = spec["config"]
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert len(cfg["source"]) <= 200 and cfg["source"].startswith("BASELINE.json configs[2]")
    assert cfg["reduced"] == entry["reduced"] == ["flows_sent"]
    assert cfg["flows_sent"]["source"] == 100_000_000
    assert spec["config_path"].endswith(entry["file"])
    assert spec["cell"] == {**spec["cell"], "config": "l4_1s_1m_sketch",
                            "traffic": "saturate", "chips": 1}
    assert [m["name"] for m in spec["end_to_end"]] == ["records_per_s", "setup_s"]
    # key for key, outside what is the plane's
    assert set(cfg) - ITS_OWN == set(exact) - ITS_OWN
    for key in set(exact) - ITS_OWN - {"pipeline"}:
        assert cfg[key] == exact[key], key
    assert {**cfg["pipeline"], "sketch": False} == exact["pipeline"]
    assert cfg["pipeline"]["cascade"] is False
    # the exact side's guarantees word for word, the plane's beside them
    assert {k: cfg["guarantees"][k] for k in exact["guarantees"]} == exact["guarantees"]
    assert set(cfg["guarantees"]) - set(exact["guarantees"]) \
        == {"coverage", "determinism", "error"}
    assert set(exact["assumed"]) <= set(cfg["assumed"])
    assert (cfg["built_by"], cfg["checks"]) == ("l4_sketch", ["sketch_blocks"])
    s = cfg["pipeline"]["sketch"]
    assert (s["num_groups"], s["hll_precision"], s["cms_depth"], s["cms_width"]) \
        == (512, 14, 4, 1 << 18)
    assert s["pool"] is None and s["distinct_mean_rel_err"] == 0.01


def test_the_block_is_38_megabytes_and_the_builder_builds_that_plane(spec):
    served_class = sut.load_named("deployment", "l4_sketch", sut.DEPLOYMENT_DIRS).Served
    wc = served_class.window_config(served_class.__new__(served_class), spec["config"])
    words = wc.sketch.block_width
    assert words == 1 + 512 * 16384 + 4 * (1 << 18) + 512 * 256 + 5 * 2 * 512 == 9_573_377
    assert wc.sketch.pool is None and wc.sketch.pending == spec["config"]["pipeline"]["sketch"]["pending"]
    assert (wc.capacity, wc.ring) == (1 << 22, 4)
    assert served_class.guarantee_counters[:len(sut.GUARANTEE_COUNTERS)] == sut.GUARANTEE_COUNTERS
    assert set(served_class.guarantee_counters[len(sut.GUARANTEE_COUNTERS):]) == {
        "pipeline.sketch_shed", "pipeline.sketch_blocks_dropped",
        "pipeline.sketch_pool_spill"}


def test_a_checkout_without_the_builder_fails_at_once_and_names_it(spec, monkeypatch, tmp_path):
    """What the parent commit does with the new cell: `sut.build` raises
    before anything is started."""
    monkeypatch.setattr(sut, "DEPLOYMENT_DIRS", [str(tmp_path)])
    with pytest.raises(FileNotFoundError, match=r"no deployment 'l4_sketch': l4_sketch\.py"):
        sut.build(spec["config"])
    # with the benchmark's files laid over it the parent has the builder, and
    # the builder refuses the parent's program (its plane counts pre-reduced rows)
    monkeypatch.undo()
    from deepflow_tpu.aggregator import pipeline
    monkeypatch.delattr(pipeline, "SKETCH_ROWS_ARE_RECORDS")
    with pytest.raises(RuntimeError, match="cannot run l4_sketch"):
        sut.build(spec["config"])
    # and a configuration that lost its `built_by` is told which builder is missing
    with pytest.raises(ValueError, match=r"\['sketch'\].*built_by"):
        sut.build({k: v for k, v in spec["config"].items() if k != "built_by"})


PLANES = {
    "spans": {"flush.sketch": {"count": 20, "total_us": 20 * 45_000}},
    "counters": {"pipeline.sketch_bytes_fetched": 16 * 20 * 38_293_508,
                 "pipeline.sketch_bytes_live": 20 * 38_293_508,
                 "pipeline.sketch_rows": 5_000_000, "feeder.records_in": 5_000_000},
    "run": {"windows_closed": 20},
}
WANT = {"sketch.flush_ms_per_window": 45.0,
        "sketch.fetched_bytes_per_block_byte": 16.0,
        "sketch.rows_per_record": 1.0}
# the parent commit's planes in the exact cells: no such span, no such counters
PARENT = {"spans": {}, "counters": {"feeder.records_in": 5_000_000},
          "run": {"windows_closed": 20}}


@pytest.mark.parametrize("name", sorted(WANT))
def test_layer_file_pairs_with_its_entry_and_reads_by_hand(name, spec):
    layer = layers.load_layer(name)
    entry, = [m for m in spec["per_layer"] if m["name"] == name]
    assert {k: layer[k] for k in entry} == entry
    assert entry["workloads"] == [CELL] and entry["moves"] == "records_per_s"
    assert layers.read_metric(layer, PLANES) == pytest.approx(WANT[name])
    for planes in ({}, PARENT):
        assert layers.read_metric(layer, planes) is None


def test_the_other_cells_report_none_of_the_three():
    for cell in ("l4_10k.saturate", "l4_10k.steady", "l4_1m.saturate"):
        names = [m["name"] for m in chipbench_run.load_cell(cell)["per_layer"]]
        assert len(names) == 24 and not set(names) & set(WANT)
    assert len(chipbench_run.load_cell(CELL)["per_layer"]) == 27
