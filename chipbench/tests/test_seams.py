"""The four seams a later PR adds a deployment through, without editing a
file that is there: who builds it (`built_by`), what decides `correct`
besides the base checks (`checks`), what the trace is read into
(`trace_groups/`), a kernel's roofline as data; and that a configuration
which names none of them gets today's deployment and today's checks.

Three rehearsal deployments prove the seam is enough: builders and checks
under tests/deployments and tests/checks, found through the search paths
this file appends; nothing outside chipbench/tests is touched to run
them. Each runs through run_cell, ends correct with its own numbers under
`checks`, and is not correct once its side output is corrupted."""

import json
import os
import subprocess
import sys

import pytest

import layers
import rehearse_x4
import run as chipbench_run
import sut
import tiny
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(autouse=True)
def _rehearsal_search_paths(monkeypatch):
    monkeypatch.setattr(sut, "DEPLOYMENT_DIRS",
                        sut.DEPLOYMENT_DIRS + [os.path.join(HERE, "deployments")])
    monkeypatch.setattr(chipbench_run, "CHECK_DIRS",
                        chipbench_run.CHECK_DIRS + [os.path.join(HERE, "checks")])


def spec_with(tmp_path, traffic: dict, **config) -> dict:
    """tiny.spec with keys of the configuration replaced or added
    (`pipeline` merged key by key)."""
    spec = tiny.spec(tmp_path, traffic)
    cfg = {**spec["config"], **config,
           "pipeline": {**spec["config"]["pipeline"], **config.get("pipeline", {})}}
    with open(spec["config_path"], "w") as f:
        json.dump(cfg, f)
    return {**spec, "config": cfg}


def over_limit(out: dict) -> set:
    return {k for k, c in out["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]}


# ---------------------------------------------------------------------------
# the three rehearsal deployments

SKETCH = {"built_by": "rehearsal_sketch", "checks": ["hll_distinct"],
          "pipeline": {"sketch": {"hll_precision": 14, "distinct_rel_err": 0.05}}}
CASCADE = {"built_by": "rehearsal_cascade", "checks": ["tier_rows"],
           "pipeline": {"cascade": {"intervals": [60], "rows": 8192}}}
# event-seconds small enough that a minute of them passes in seconds
SHORT_SECONDS = {**tiny.SATURATE, "records_per_event_second": 400}


def half_the_hll_registers_cleared(served):
    side_outputs = served.side_outputs

    def broken():
        out = side_outputs()
        for block in out["sketch_blocks"]:
            block.hll[:, ::2] = 0
        return out

    served.side_outputs = broken


@pytest.mark.parametrize("config,traffic,seconds,own,fault,fails", [
    (SKETCH, tiny.SATURATE, 5.0,
     {"sketch.hll_rel_err", "sketch.windows_without_block",
      "pipeline.sketch_shed", "pipeline.sketch_blocks_dropped"},
     half_the_hll_registers_cleared, "sketch.hll_rel_err"),
    (CASCADE, SHORT_SECONDS, 8.0,
     {"tier.sum_rel_err", "tier.unpaired_docs", "tier.windows_missing",
      "pipeline.cascade_shed", "pipeline.tier_windows_dropped"},
     rehearse_x4.corrupt_the_tier_rows, "tier.sum_rel_err"),
], ids=["sketch_on", "tier_60s_on"])
def test_rehearsal_deployment_is_correct_and_its_corruption_is_not(
        tmp_path, config, traffic, seconds, own, fault, fails):
    spec = spec_with(tmp_path, traffic, **config)
    good = chipbench_run.run_cell(spec, seed=2**31 + 21, seconds=seconds,
                                  trace=False, workdir=str(tmp_path), device=CPU)
    assert good["correct"] is True, over_limit(good)
    assert own <= set(good["checks"])
    # the base checks ran beside the named ones
    assert {"edge_packet_tx_gap", "sum_rel_err", "oracle.sum_rel_err",
            "promql_gap", "records_unaccounted"} <= set(good["checks"])
    if config is CASCADE:
        assert good["checks"]["tier.windows_compared"]["value"] >= 1
    bad = chipbench_run.run_cell(spec, seed=2**31 + 21, seconds=seconds,
                                 trace=False, workdir=str(tmp_path), device=CPU,
                                 on_built=fault)
    assert bad["correct"] is False
    assert over_limit(bad) == {fails}


@pytest.mark.parametrize("corrupt", [False, True], ids=["sound", "tier_rows_altered"])
def test_sharded_rehearsal_on_four_forced_host_devices(corrupt):
    """ShardedFeedSink / ShardedWindowManager on make_mesh(4, n_hosts=1),
    four clients: a process of its own, since the device count is read
    when JAX starts (rehearse_x4.py is also the by-hand chip run)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_x4.py"), "--size", "tiny",
         "--seed", str(2**31 + 31), "--seconds", "8"] + ["--corrupt"] * corrupt,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert {"tier.sum_rel_err", "sketch.hll_rel_err", "pipeline.cascade_shed",
            "edge_packet_tx_gap", "promql_gap"} <= set(out["checks"])
    assert out["checks"]["tier.windows_compared"]["value"] >= 1
    if corrupt:
        assert out["correct"] is False and over_limit(out) == {"tier.sum_rel_err"}
    else:
        assert out["correct"] is True, over_limit(out)
        assert out["failed"] == 0 and out["attempted"] > 0


# ---------------------------------------------------------------------------
# who builds it


def test_a_configuration_without_the_new_keys_gets_todays_deployment():
    assert not {"built_by", "checks"} & set(tiny.CONFIG)
    for name in ("l4_1s_10k", "l4_1s_1m"):
        with open(os.path.join(CHIPBENCH, "configs", f"{name}.json")) as f:
            assert not {"built_by", "checks"} & set(json.load(f))
    served = sut.build(tiny.CONFIG)
    try:
        assert type(served) is sut.Served
        assert type(served.pipe).__name__ == "L4Pipeline"
        assert type(served.feeder.sink).__name__ == "PipelineFeedSink"
        assert chipbench_run.guarantees_of(served) == sut.GUARANTEE_COUNTERS
        assert served.stats_module == "tpu_pipeline" and served.side_outputs() == {}
        batch = [object()]
        assert served.documents(batch) is batch
    finally:
        served.close()
    assert sut.GUARANTEE_COUNTERS == (  # as the parent commit had them
        "feeder.shed_records", "feeder.lost_records", "feeder.bad_frames",
        "feeder.emit_failures", "feeder.degraded_entries",
        "feeder.queue_overwritten", "feeder.decode_errors",
        "receiver.bad_frames", "receiver.no_handler",
        "pipeline.stash_evictions", "pipeline.prereduce_shed",
        "pipeline.drop_before_window", "pipeline.jit_retraces",
        "pipeline.fetch_retries", "pipeline.dispatch_retries")


@pytest.mark.parametrize("change,names", [
    ({"pipeline": {**tiny.CONFIG["pipeline"], "sketch": True}}, ["sketch"]),
    ({"pipeline": {**tiny.CONFIG["pipeline"], "cascade": {"intervals": [60]}}},
     ["cascade"]),
    ({"chips": 4}, ["chips"]),
])
def test_a_plane_with_no_builder_is_an_error_that_names_the_builder(change, names):
    with pytest.raises(ValueError) as e:
        sut.build({**tiny.CONFIG, **change})
    assert str(names) in str(e.value)
    assert "built_by" in str(e.value) and "chipbench/deployments/" in str(e.value)


@pytest.mark.parametrize("name,error", [
    ("no_such_builder", FileNotFoundError), ("../tiny", ValueError), (7, ValueError)])
def test_an_unknown_builder_fails_loudly(name, error):
    with pytest.raises(error) as e:
        sut.build({**tiny.CONFIG, "built_by": name})
    assert repr(name) in str(e.value)


def test_guarantee_counters_grow_and_never_shrink():
    class Longer:
        guarantee_counters = sut.GUARANTEE_COUNTERS + ("pipeline.sketch_blocks_dropped",)

    class Shorter:
        guarantee_counters = sut.GUARANTEE_COUNTERS[1:]

    assert chipbench_run.guarantees_of(Longer())[-1] == "pipeline.sketch_blocks_dropped"
    with pytest.raises(chipbench_run.HarnessFailure, match="feeder.shed_records"):
        chipbench_run.guarantees_of(Shorter())


# ---------------------------------------------------------------------------
# what decides `correct`


def test_named_checks_add_numbers_and_replace_none(tmp_path, monkeypatch):
    (tmp_path / "adds.py").write_text(
        "def check(ctx):\n"
        "    return {'mine.gap': (ctx['side_outputs']['gap'], 0),\n"
        "            'mine.seen': (len(ctx['got']), None)}\n")
    (tmp_path / "collides.py").write_text(
        "def check(ctx):\n    return {'sum_rel_err': (0.0, 1.0)}\n")
    monkeypatch.setattr(chipbench_run, "CHECK_DIRS", [str(tmp_path)])
    ctx = {"side_outputs": {"gap": 3}, "got": {1: None, 2: None}}
    numbers = {"sum_rel_err": (0.0, 1e-6)}
    chipbench_run.named_checks(["adds"], ctx, numbers)
    assert numbers == {"sum_rel_err": (0.0, 1e-6), "mine.gap": (3, 0),
                       "mine.seen": (2, None)}
    with pytest.raises(chipbench_run.HarnessFailure, match="sum_rel_err"):
        chipbench_run.named_checks(["collides"], ctx, numbers)
    assert numbers["sum_rel_err"] == (0.0, 1e-6)
    with pytest.raises(FileNotFoundError, match="no_such_check"):
        chipbench_run.named_checks(["no_such_check"], ctx, numbers)


def test_run_py_reaches_through_no_deployment():
    for name in ("run.py", "server_check.py"):
        with open(os.path.join(CHIPBENCH, name)) as f:
            text = f.read()
        assert "served.pipe" not in text and ".pipe." not in text, name
    with open(os.path.join(CHIPBENCH, "run.py")) as f:
        assert "roofline" not in f.read()


# ---------------------------------------------------------------------------
# what the trace is read into


def test_trace_groups_add_up_and_cannot_be_redefined(tmp_path):
    base = os.path.join(CHIPBENCH, "trace_groups.json")
    with open(base) as f:
        assert trace_reduce.load_groups() == json.load(f)["modules"]
    more = tmp_path / "tier.json"
    more.write_text(json.dumps({"modules": {"tier_fold": ["jit__tier_fold"]}}))
    groups = trace_reduce.load_groups([base, str(more)])
    assert groups["tier_fold"] == ["jit__tier_fold"] and "fold" in groups
    assert trace_reduce.module_group("jit__tier_fold_impl(12)", groups) == "tier_fold"
    again = tmp_path / "again.json"
    again.write_text(json.dumps({"modules": {"fold": ["jit__other"]}}))
    with pytest.raises(ValueError, match="'fold' is already defined"):
        trace_reduce.load_groups([base, str(again)])
    steals = tmp_path / "steals.json"
    steals.write_text(json.dumps({"modules": {"mine": ["jit_step"]}}))
    with pytest.raises(ValueError, match="'jit_step' already belongs"):
        trace_reduce.load_groups([base, str(steals)])


# ---------------------------------------------------------------------------
# a kernel's roofline as data

PLANES = {  # a traced run's planes, as recorded from l4_10k.saturate (ledger, PR 31)
    "trace": {"slice_records": 5_324_800, "module_s": {"fused_step": 0.279, "fold": 3.77}},
    "schema": {"record_bytes": 396},
    "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def test_fused_step_roofline_reads_what_run_py_computed():
    spec = layers.load_layer("fused_step_roofline")
    old = trace_reduce.roofline_pct(  # run.py's own lines before this form
        PLANES["trace"]["slice_records"] * PLANES["schema"]["record_bytes"],
        PLANES["trace"]["module_s"].get("fused_step", 0.0),
        PLANES["peaks"]["hbm_bytes_per_s"])
    assert layers.read_metric(spec, PLANES) == old
    assert old == pytest.approx(0.9228, rel=1e-3)
    for missing in ("trace", "schema", "peaks"):
        assert layers.read_metric(
            spec, {k: v for k, v in PLANES.items() if k != missing}) is None
    no_step = {**PLANES, "trace": {"slice_records": 5, "module_s": {"fold": 1.0}}}
    assert layers.read_metric(spec, no_step) is None
    assert layers.read_metric(
        spec, {**PLANES, "trace": {**PLANES["trace"], "slice_records": 0}}) is None


def test_roofline_reader_does_not_clamp_and_takes_the_larger_bound():
    read = lambda r, planes=PLANES: layers.read_metric({"read": {"roofline": r}}, planes)
    records = {"from": "trace", "name": "slice_records"}
    fast = {**PLANES, "trace": {"slice_records": 819_000, "module_s": {"k": 0.0005}}}
    # 819 kB x 1000 in 0.5 ms: twice what the chip can move, printed as it is
    assert read({"group": "k", "bytes": [records], "bytes_scale": 1000}, fast) \
        == pytest.approx(200.0)
    # compute-bound: 197e9 operations in 10 ms is a tenth of the peak ...
    ops = {"group": "k", "ops": [records], "ops_scale": 197e9 / 819_000}
    slow = {**PLANES, "trace": {"slice_records": 819_000, "module_s": {"k": 0.010}}}
    assert read(ops, slow) == pytest.approx(10.0)
    # ... and with bytes that need longer than the operations, the bytes bound it
    assert read({**ops, "bytes": [records], "bytes_scale": 4000}, slow) \
        == pytest.approx(40.0)
    assert read({"group": "absent", "bytes": [records]}, slow) is None


def test_fold_device_ms_per_mrec_is_one_data_file_away():
    """The fold's device time for each record is readable as data today.
    Its file and `per_layer` entry wait for a PR that may edit
    tests/test_stash_window.py and tests/test_span_tree.py, which pin the
    number of per-layer entries and their pairing with layers/*.json."""
    spec = {"read": {
        "num": [{"from": "trace", "name": "module_s", "field": "fold"}],
        "num_scale": 1000.0,
        "den": {"from": "trace", "name": "slice_records"}, "den_scale": 1e-06}}
    assert layers.read_metric(spec, PLANES) == pytest.approx(3770 / 5.3248)
    assert layers.read_metric(spec, {"trace": {"slice_records": 9, "module_s": {}}}) is None
    assert layers.read_metric(spec, {"spans": {}, "counters": {}}) is None
