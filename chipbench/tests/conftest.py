"""chipbench's own tests: run by hand and in the CPU rehearsal
(`JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q`), not tier-1."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(CHIPBENCH)
for p in (ROOT, CHIPBENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import gc  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _collect_the_last_run():
    """A stopped Server's counters stay registered (weakly) until it is
    collected; a test that runs a second cell in this process would find
    them in its PromQL sums. The benchmark itself is one process a run."""
    gc.collect()
    yield
