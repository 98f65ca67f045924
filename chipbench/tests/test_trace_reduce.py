"""The trace reduction on a small recorded trace (data/trace_events.json:
2.2 s of one chip's slice in l4_100k.saturate), and its arithmetic on a
hand-made one."""

import json
import os

import pytest

import gen
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
GROUPS = trace_reduce.load_groups()


def test_recorded_slice():
    with open(os.path.join(HERE, "data", "trace_events.json")) as f:
        events = json.load(f)
    red = trace_reduce.reduce(events, GROUPS)
    assert red["window_s"] == pytest.approx(2.2)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["idle_share_pct"] == pytest.approx(
        100 * (1 - red["busy_s"] / red["window_s"]))
    # the two programs the window drives are told apart by module
    assert {"fused_step", "fold"} <= set(red["module_s"])
    assert red["module_s"]["fold"] > red["module_s"]["fused_step"] > 0
    # ops cannot be busy for longer than their modules are
    assert red["busy_s"] <= sum(red["module_s"].values()) * 1.001
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) >= 1
    assert red["device_ops"][0][0] == "module:fold"
    assert all(name.split("/")[0] in ("fold", "fused_step", "?", "module:fold",
                                      "module:fused_step")
               or name.startswith(("module:", "jit_"))
               for name, _s in red["device_ops"])
    assert red["idle_gaps"][0][0] == "unattributed"  # no host spans were given
    # to the last digit what the parent commit's reduce() read from this
    # file (c200a7a, before per-device numbers were returned beside them)
    assert red["busy_s"] == 1.769335169
    assert red["idle_share_pct"] == 19.57567413636364
    assert red["module_s"] == {"jit_convert_element_type": 8.898e-06,
                               "fused_step": 0.132207289, "fold": 1.637161951}
    assert red["idle_gaps"] == [["unattributed", 0.430664831]]
    assert red["longest_gap_s"] == 0.232240877
    # one device: its own numbers are the means
    assert red["per_device"] == {"plane": ["/device:TPU:0"],
                                 "busy_s": [red["busy_s"]],
                                 "module_s": [red["module_s"]]}


def test_two_devices_read_each_and_the_mean():
    """A synthesised two-device slice: device 1 runs the same step and a
    fold twice as long. busy_s, module_s and idle_share_pct are means over
    the devices; `per_device` gives each in the planes' order."""
    ms = 1e6
    events = {
        "annotations": [["chipbench.anchor", 0.0, 1.0], ["chipbench.end", 100 * ms, 1.0]],
        "devices": [
            {"plane": "/device:TPU:0",
             "modules": [["jit_step(1)", 10 * ms, 10 * ms],
                         ["jit__fold_counted_impl(2)", 40 * ms, 20 * ms]],
             "ops": [["%a = x", 10 * ms, 10 * ms], ["%b = y", 40 * ms, 20 * ms]]},
            {"plane": "/device:TPU:1",
             "modules": [["jit_step(1)", 10 * ms, 10 * ms],
                         ["jit__fold_counted_impl(2)", 40 * ms, 40 * ms]],
             "ops": [["%a = x", 10 * ms, 10 * ms], ["%b = y", 40 * ms, 40 * ms]]},
        ],
    }
    red = trace_reduce.reduce(events, GROUPS)
    assert red["per_device"]["plane"] == ["/device:TPU:0", "/device:TPU:1"]
    assert red["per_device"]["busy_s"] == pytest.approx([0.030, 0.050])
    assert red["per_device"]["module_s"] == [
        {"fused_step": pytest.approx(0.010), "fold": pytest.approx(0.020)},
        {"fused_step": pytest.approx(0.010), "fold": pytest.approx(0.040)}]
    assert red["busy_s"] == pytest.approx(0.040)
    assert red["module_s"] == {"fused_step": pytest.approx(0.010),
                               "fold": pytest.approx(0.030)}
    assert red["idle_share_pct"] == pytest.approx(60.0)
    assert red["window_s"] == pytest.approx(0.100)


def test_union_gaps_and_attribution():
    ms = 1e6
    events = {
        "annotations": [["chipbench.anchor", 0.0, 1.0], ["chipbench.end", 100 * ms, 1.0]],
        "devices": [{
            "plane": "/device:TPU:0",
            "modules": [["jit_step_plain(1)", 10 * ms, 30 * ms],
                        ["jit__fold_counted_impl(2)", 60 * ms, 20 * ms]],
            # two ops overlap 20..30: the union counts it once; one op
            # starts before the slice and is clipped to it
            "ops": [["%a = x", -5 * ms, 10 * ms], ["%b = y", 10 * ms, 20 * ms],
                    ["%c = z", 20 * ms, 20 * ms], ["%d = w", 60 * ms, 20 * ms]],
        }],
    }
    spans = [("flush.drain", 1000.040, 0.020), ("feeder.dispatch", 1000.0, 0.1)]
    red = trace_reduce.reduce(events, GROUPS, spans, anchor_wall_s=1000.0)
    assert red["busy_s"] == pytest.approx(0.005 + 0.030 + 0.020)
    assert red["idle_share_pct"] == pytest.approx(45.0)
    assert red["module_s"] == {"fused_step": pytest.approx(0.030),
                               "fold": pytest.approx(0.020)}
    ops = dict(red["device_ops"])
    assert ops["fused_step/%b"] == pytest.approx(0.020)
    assert ops["fold/%d"] == pytest.approx(0.020) and "?/%a" in ops
    gaps = dict(red["idle_gaps"])
    # 40..60 ms idle: its middle (50 ms) lies in flush.drain, the innermost
    assert gaps["flush.drain"] == pytest.approx(0.020)
    assert gaps["feeder.dispatch"] == pytest.approx(0.005 + 0.020)
    assert red["longest_gap_s"] == pytest.approx(0.020)


def test_roofline_and_peaks():
    schema = gen.load_schema()
    assert trace_reduce.record_bytes(schema) == 4 * (37 + 62)
    peaks = trace_reduce.load_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace_reduce.load_peaks("TPU v9")
    # 819 MB in 10 ms is a tenth of the bandwidth
    assert trace_reduce.roofline_pct(819e6, 0.010, 819e9) == pytest.approx(10.0)
    assert trace_reduce.roofline_pct(0, 0.010, 819e9) is None
    assert trace_reduce.roofline_pct(819e6, 0.0, 819e9) is None
