"""PR 39: the devices' closed sketch blocks merge on the devices, inside
the sharded sketch drain (`parallel/sharded.py` `_merge_closed_blocks`),
and the host unpacks one block a window. Each case drives two
ShardedWindowManagers over the same batches on four forced host devices
(a 2 x 2 mesh, so the merge spans both mesh axes): the manager as built,
and one whose drain leaves the merge to the host (`_merged_block_slots`
patched to 0: the parent's code path, which the pool still takes), and
holds the first's closed blocks to the second's bit for bit."""

import numpy as np
import pytest

import jax

from deepflow_tpu.aggregator.sketchplane import PoolConfig
from deepflow_tpu.datamodel.batch import FlowBatch
from deepflow_tpu.datamodel.schema import FLOW_METER
from deepflow_tpu.ingest.replay import SyntheticFlowGen
from deepflow_tpu.ops.histogram import LogHistSpec
from deepflow_tpu.parallel import sharded
from deepflow_tpu.parallel.mesh import make_mesh

CHIPS = 4
ROWS = 16  # a device's share of a 64-row batch: device d takes rows 16d..16d+15
T0 = 1_700_000_000
HEAVY = 2.0**30  # bytes of one record: three on a device read 3 * 2^30 as u32

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < CHIPS, reason="needs four (forced host) devices")


def _config(pool=None):
    return sharded.ShardedConfig(
        capacity_per_device=1 << 10, num_services=8, hll_precision=7,
        cms_depth=2, cms_width=256, hist=LogHistSpec(bins=32, vmin=1.0, gamma=1.3),
        topk_cols=64, sketch_ring=4, sketch_pending=3, sketch_pool=pool)


def _batch(gen, t, devices, heavy=False):
    """64 records at `t`, valid only in the shares of `devices`; `heavy`:
    on every device three records of one flow, 2^30 bytes each, so that
    the count-min counters they hit add up past 2^32 over four devices."""
    fb = gen.flow_batch(CHIPS * ROWS, t)
    valid = np.zeros(CHIPS * ROWS, bool)
    for dev in devices:
        valid[dev * ROWS:(dev + 1) * ROWS] = True
    if heavy:
        for dev in range(CHIPS):
            rows = dev * ROWS + np.arange(3)
            for col in fb.tags.values():
                col[rows] = col[0]
            fb.meters[rows, FLOW_METER.index("byte_tx")] = HEAVY
    return FlowBatch(tags=fb.tags, meters=fb.meters, valid=valid)


def _batches():
    gen = SyntheticFlowGen(num_tuples=300, seed=39)
    return [
        _batch(gen, T0, (0, 1)),  # a window only devices 0 and 1 see
        _batch(gen, T0 + 1, range(CHIPS), heavy=True),  # at pend position 1 on
        # devices 0 and 1, 0 on devices 2 and 3
        _batch(gen, T0 + 4, (1, 3)),  # advances: T0 and T0 + 1 close in one drain
    ]


def _run(monkeypatch, *, on_devices: bool, pool=None):
    with monkeypatch.context() as mp:
        if not on_devices:
            mp.setattr(sharded, "_merged_block_slots", lambda config, n: 0)
        pipe = sharded.ShardedPipeline(make_mesh(CHIPS, n_hosts=2), _config(pool))
    wm = sharded.ShardedWindowManager(pipe)
    for fb in _batches():
        wm.ingest(fb.tags, fb.meters, fb.valid)
    wm.drain()
    return wm.pop_closed_sketches(), wm.get_counters()


FIELDS = ("hll", "cms", "hist", "tk_hi", "tk_lo", "tk_ida", "tk_idb", "tk_votes")


def test_device_merged_blocks_are_the_host_merge_bit_for_bit(monkeypatch):
    got, c = _run(monkeypatch, on_devices=True)
    want, c_host = _run(monkeypatch, on_devices=False)
    assert [b.window for b in got] == [b.window for b in want] == [T0, T0 + 1, T0 + 4]
    assert [b.n_updates for b in got] == [b.n_updates for b in want] == [32, 64, 32]
    for a, b in zip(got, want):
        for f in FIELDS:
            x, y = getattr(a, f), getattr(b, f)
            assert x.dtype == y.dtype and x.shape == y.shape, (b.window, f)
            np.testing.assert_array_equal(x, y, err_msg=f"{b.window} {f}")
        # the candidate multiset is the devices' union, in merge()'s order
        assert len(a.tk_hi) > 0
    # four devices' counters of the heavy flow: 12 * 2^30, past 2^32
    heavy = got[1].cms.max()
    assert heavy >= 12 * 2**30 > 2**32
    assert (c["sketch_blocks_device_merged"], c["sketch_blocks_closed"]) == (3, 3)
    assert (c_host["sketch_blocks_device_merged"], c_host["sketch_blocks_closed"]) == (0, 3)
    # what the drains fetched: one merged block a window, no more
    assert c["sketch_bytes_fetched"] == c["sketch_bytes_live"] > 0
    assert c_host["sketch_bytes_fetched"] == c_host["sketch_bytes_live"]
    assert c["sketch_blocks_dropped"] == c["sketch_shed"] == 0
    # a drain's merged slots outnumber its windows (4 against 2 and 1):
    # the empty ones come back as no block
    assert c["sketch_rows"] == sum(b.n_updates for b in got) == 128


def test_with_the_pool_on_the_host_still_merges(monkeypatch):
    """Compact pool rows pack four registers a word: the drain hands each
    device's blocks to the host as before, and they give the windows,
    update counts and registers the device-merged slab blocks give (the
    layouts agree there; count-min and histogram are narrower in a
    compact slot)."""
    pool = PoolConfig(compact_slots=3, wide_slots=1, cms_factor=4,
                      topk_factor=2, hist_factor=4, promote_fill=0.5)
    pooled, c = _run(monkeypatch, on_devices=True, pool=pool)
    slab, _ = _run(monkeypatch, on_devices=True)
    assert c["sketch_blocks_device_merged"] == 0
    assert c["sketch_blocks_closed"] == len(pooled) == 3
    assert [b.window for b in pooled] == [b.window for b in slab]
    for a, b in zip(pooled, slab):
        assert a.n_updates == b.n_updates
        np.testing.assert_array_equal(a.hll, b.hll)


def test_the_merge_engages_only_with_full_width_rows():
    pool = PoolConfig(compact_slots=3, wide_slots=1, cms_factor=4,
                      topk_factor=2, hist_factor=4, promote_fill=0.5)
    assert sharded._merged_block_slots(_config(), CHIPS) == 4  # the ring
    assert sharded._merged_block_slots(_config(), 1) == 3  # one device's pend
    assert sharded._merged_block_slots(_config(pool), CHIPS) == 0
