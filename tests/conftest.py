"""Test harness: every test runs on the CPU backend with eight virtual
devices, which is what the multi-chip sharding tests need. The platform
and device count are set before JAX is imported; the chip is reached
only through `python chip_smoke.py` (see README "Running").
"""

import faulthandler
import os
import sys

# Hung-device forensics (ISSUE 6): a wedged dispatch/fetch used to die
# at the suite timeout with no trace of WHERE it hung. faulthandler
# dumps every thread's stack to stderr shortly before the tier-1
# timeout (ROADMAP: 1500 s) would kill us, without exiting — the test
# then still fails on its own terms, but the log says which seam hung.
faulthandler.enable()
_dump_after = float(os.environ.get("DEEPFLOW_FAULTHANDLER_TIMEOUT_S", "1750"))
if _dump_after > 0:
    faulthandler.dump_traceback_later(_dump_after, exit=False)

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# tests never read or write the persistent compile cache: launchers name
# its directory (utils/compile_cache.py), this keeps it off
jax.config.update("jax_enable_compilation_cache", False)

assert jax.devices()[0].platform == "cpu", jax.devices()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: depth tier excluded from tier-1 (`-m 'not slow'`) to hold "
        "the suite under the 870 s gate — the heaviest fuzz pins for "
        "non-default modes live here; run them with `-m slow` (or no "
        "marker filter) when touching their subsystem",
    )


def pytest_collection_modifyitems(config, items):
    """Start the mesh-harness prewarm at COLLECTION time when any
    harness-consuming test is in the run. The memoized multi-subprocess
    artifacts (oracle/mesh2/mesh2_kill/rebalance/rebalance_kill/
    rb_oracle) cost ~2 min of build wall; started here they overlap
    the first ~40% of the suite instead of serializing into the middle
    of it — the difference between tier-1 fitting the 870 s cap and
    riding it. Gated on the consumers so `pytest -k one_fast_test`
    does not spawn subprocess fleets it will never use."""
    heavy = (
        "test_mesh_multiproc", "test_mesh_rebalance", "test_perf_gate",
        "test_recovery",
    )
    if any(
        any(h in str(item.fspath) for h in heavy) for item in items
    ):
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import mesh_harness

        mesh_harness.prewarm_async()
