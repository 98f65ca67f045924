"""run.py's control flow at a tiny size on the CPU: the shape of the last
line, and that the measurement path refuses to report off the chip."""

import json
import os
import subprocess
import sys

import pytest

import run as chipbench_run
import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def over_limit(out: dict) -> dict:
    return {k: c for k, c in out["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]}


def test_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "l4_10k.saturate",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs" in proc.stderr and "TPU" in proc.stderr


@pytest.mark.parametrize("traffic,flows,cell,metrics", [
    (tiny.SATURATE, 1, "l4_10k.saturate", {"setup_s", "records_per_s"}),
    (tiny.STEADY, 1, "l4_10k.steady",
     {"setup_s", "records_per_s", "steady_records_per_s"}),
    # flows this process has not closed yet, drawn anew each second, under
    # the control cell's metric list: since PR 27 a close with a new
    # document count compiles nothing, so the metric that carries the
    # tight bound is reported here too
    (tiny.SATURATE, 903, "l4_10k.steady",
     {"setup_s", "records_per_s", "steady_records_per_s"}),
], ids=["saturate", "steady", "steady_cell_with_new_document_counts"])
def test_last_line_shape(tmp_path, traffic, flows, cell, metrics):
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    out = chipbench_run.run_cell(
        tiny.spec(tmp_path, traffic, flows, cell), seed=2**31 + 11, seconds=6.0,
        trace=False, workdir=str(tmp_path), device=device)
    line = json.loads(json.dumps(out))  # it must be JSON as it stands
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, over_limit(line)
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert line["checks"]["windows_compared"]["value"] >= 2
    assert line["checks"]["oracle.unpaired_docs"]["value"] == 0


def test_traced_run_reports_no_device_metric_off_the_chip(tmp_path):
    """The profiler slice opens and closes on the CPU too, but there is no
    device plane to read: every device_trace metric is left out, and
    `device` carries no busy_s."""
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    out = chipbench_run.run_cell(
        tiny.spec(tmp_path, tiny.SATURATE), seed=7, seconds=4.0, trace=True,
        workdir=str(tmp_path), device=device)
    assert out["correct"] is True, over_limit(out)
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert not [m for m in out["metrics"] if m.startswith(("device.", "fused_step"))]
    assert {"feeder.drain_ms_per_mrec", "fold.ms_per_window",
            "flush.ms_per_window",
            "records_per_s.outside_compile"} <= set(out["metrics"])
    assert "records_per_s" not in out["metrics"]  # --trace 1: per-layer only


def test_steady_traffic_compiles_nothing_in_the_window(tmp_path):
    """Nothing compiles in the window, whether one draw of flows serves
    every second or each second draws its own (a new document count at
    every close): since PR 27 the close fetches fixed-size pages."""
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    per_window = {}
    for flows, traffic in ((901, tiny.STEADY), (902, tiny.SATURATE)):
        out = chipbench_run.run_cell(
            tiny.spec(tmp_path, traffic, flows), seed=9, seconds=5.0, trace=True,
            workdir=str(tmp_path), device=device)
        assert out["correct"] is True, over_limit(out)
        per_window[traffic["name"]] = out["metrics"]["close.compile_ms_per_window"]["value"]
    assert per_window == {"tiny_steady": 0.0, "tiny_saturate": 0.0}
