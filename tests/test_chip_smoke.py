"""chip_smoke.py off the chip: it must refuse to run, and its phases —
imported and run here on the CPU at tiny size, the platform check
bypassed by calling them directly — must match the oracle."""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes.tiny()


def test_refuses_to_run_without_a_tpu():
    """No silent CPU run: non-zero exit and no `ok` line."""
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=_REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_reference_fanout_matches_the_oracle():
    """The smoke's vectorised NumPy reference against the repo's scalar
    oracle on the same records — the reference itself is checked."""
    from deepflow_tpu.aggregator.fanout import FanoutConfig
    from deepflow_tpu.ingest.replay import SyntheticFlowGen
    from deepflow_tpu.oracle.numpy_oracle import oracle_l4_rollup

    fb = SyntheticFlowGen(num_tuples=150, seed=3).flow_batch(1200, chip_smoke.T0)
    tags, meters = chip_smoke.reference_docs(fb)
    oracle = oracle_l4_rollup(chip_smoke.flowbatch_records(fb), FanoutConfig())
    o_tags, o_meters = chip_smoke.oracle_arrays(oracle)
    out = chip_smoke.compare_docs(tags, meters.astype(np.float32), o_tags,
                                  o_meters, what="reference vs oracle")
    assert out["docs"] == len(oracle) > 0


def test_compare_docs_catches_a_wrong_meter():
    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    fb = SyntheticFlowGen(num_tuples=50, seed=1).flow_batch(400, chip_smoke.T0)
    tags, meters = chip_smoke.reference_docs(fb)
    bad = meters.astype(np.float32)
    bad[0, 0] += 1
    with pytest.raises(chip_smoke.SmokeFailure, match="SUM lanes"):
        chip_smoke.compare_docs(tags, bad, tags, meters, what="t")


def test_served_phase_matches_oracle_on_cpu(tmp_path):
    out = chip_smoke.phase_served(TINY, 0, str(tmp_path))
    assert out["closed_before_drain"] >= TINY.windows + 1
    assert out["oracle_prefix_docs"] > 0 and out["server_docs"] > 0
    assert all(v == 0 for v in out["health"].values())


def test_kernel_phase_matches_reference_on_cpu():
    out = chip_smoke.phase_kernel(TINY, 0)
    assert out["docs"] > 0
    assert out["pallas"] is False  # the CPU takes the XLA segment ops


def test_sketch_phase_within_one_percent_on_cpu():
    out = chip_smoke.phase_sketch(TINY, 0)
    assert out["windows"] == 2
    assert all(d["rel_err"] <= 0.01 for d in out["distinct"].values())


def test_sharded_phase_matches_one_chip_on_cpu():
    """`--chips 4`'s phase on four of conftest's virtual CPU devices:
    sharded rows, merged by key, equal the one-chip manager's, and the
    state is spread over the mesh."""
    out = chip_smoke.phase_sharded(TINY, 0, n_devices=4)
    assert out["devices"] == 4 and out["docs"] > 0
    assert out["sharded_partial_rows"] > out["docs"]  # keys live on several chips
    assert out["cascade_rows"] > 0 and out["sketch_blocks"] == out["windows"]


def test_forced_step_failure_fails_the_smoke(tmp_path, monkeypatch):
    """A compile failure in the fused step is swallowed by the feeder
    (emit_failures, degraded mode, shed); the smoke must fail on it."""
    import deepflow_tpu.aggregator.pipeline as pipeline_mod

    def boom(*a, **k):
        raise RuntimeError("forced compile failure")

    monkeypatch.setattr(pipeline_mod, "batch_prereduce", boom)
    with pytest.raises(chip_smoke.SmokeFailure, match="dispatch into the fused step"):
        chip_smoke.phase_served(TINY, 0, str(tmp_path))


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    from deepflow_tpu.utils import compile_cache

    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from deepflow_tpu.utils import compile_cache

    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(_REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == path  # fixed, not pid/time
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
