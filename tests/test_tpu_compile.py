"""The chip's compiler, asked from the sandbox: the kernels of the main
path compile for a DESCRIBED v5e (no chip attached, nothing runs).

Interpret mode cannot show what Mosaic refuses — the r6 in-kernel
gather passed every interpret-mode test and was refused for a slice not
aligned to the 128-lane tiling. These cases replace its interpret-only
tests one for one and guard every later PR at no chip time.

The topology is described inside a module-scoped fixture (never at
import: only one process may load libtpu, and every xdist worker
imports every test file) and the compile runs in the test's own
process. `jax.default_backend()` still says "cpu" here, so the tests
steer the kernels' platform check; the program has no option for it.
Whole programs (minutes each) are compiled by chip_smoke's rehearsal
script, not here — CHANGES.md PR 22 has their result.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from deepflow_tpu.datamodel.schema import APP_METER, FLOW_METER

N = 32768
CAP = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_tpu(monkeypatch):
    """Steer the platform checks (`_interpret`, `_use_pallas_reduce`)
    down their TPU branch, and keep the persistent compile cache out of
    it: an executable for a described chip cannot be read back here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("DEEPFLOW_SEGREDUCE", raising=False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled, compiled.as_text()


def _kernel_shapes(n, m, cap, sharding):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    return s((n, m), jnp.float32), s((n,), jnp.int32), s((cap,), jnp.int32)


# (rows, meter lanes, block): FLOW_METER's 62 lanes and a full 128-lane
# tile at the production block; APP_METER's width; a row count that is
# not a multiple of the block (the pad path); a small block
KERNEL_CASES = [
    (N, FLOW_METER.num_fields, 2048),
    (N, 128, 2048),
    (N, APP_METER.num_fields, 2048),
    (N - 1000, FLOW_METER.num_fields, 2048),
    (N, FLOW_METER.num_fields, 512),
]


@pytest.mark.parametrize("n,m,block", KERNEL_CASES)
def test_default_segreduce_kernel_compiles(topo, one_chip, as_tpu, n, m, block):
    from deepflow_tpu.ops.segreduce_pallas import sorted_segment_sum_max

    def f(rows, seg, first_pos):
        return sorted_segment_sum_max(rows, seg, CAP, first_pos, block=block)

    compiled, hlo = _compile(f, *_kernel_shapes(n, m, CAP, one_chip))
    assert "tpu_custom_call" in hlo  # the Pallas kernel, not a reference
    out_s, out_m = compiled.out_info
    assert out_s.shape == (CAP, m) and out_m.shape == (CAP, m)


@pytest.mark.parametrize(
    "schema", [FLOW_METER, APP_METER], ids=lambda s: s.name
)
def test_groupby_reduce_tpu_branch_compiles(topo, one_chip, as_tpu, schema):
    """The group-by reduce as the TPU takes it: the row gather through
    the sort permutation, the Pallas reduce, the schema's sum/max lane
    split and the head gathers. (The keyed 4-lane `lax.sort` in front of
    it is plain XLA and alone costs ~75 s of compile at any size, so it
    is compiled with the whole programs, not here.)"""
    from deepflow_tpu.ops.segment import _use_pallas_reduce, groupby_reduce_sorted

    assert _use_pallas_reduce()
    sum_cols = np.nonzero(schema.sum_mask)[0].astype(np.int32)
    max_cols = np.nonzero(schema.max_mask)[0].astype(np.int32)
    t, m = 34, schema.num_fields

    def f(slot, hi, lo, perm, tags, meters):
        g = groupby_reduce_sorted(slot, hi, lo, perm, tags, meters,
                                  sum_cols, max_cols, out_capacity=CAP)
        return g.meters, g.tags, g.seg_valid, g.num_segments

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    u32 = jnp.uint32
    compiled, hlo = _compile(
        f, s((N,), u32), s((N,), u32), s((N,), u32), s((N,), jnp.int32),
        s((t, N), u32), s((N, m), jnp.float32),
    )
    assert "tpu_custom_call" in hlo
    assert compiled.out_info[0].shape == (m, CAP)


def test_segreduce_kernel_compiles_inside_shard_map(topo, as_tpu):
    """The four-chip path: the kernel traced under `shard_map`'s
    varying-axes typing on the described 2x2 mesh, one block of rows per
    device."""
    from deepflow_tpu.ops.segreduce_pallas import sorted_segment_sum_max

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("chip",))
    m = FLOW_METER.num_fields

    def per_device(rows, seg, first_pos):
        return sorted_segment_sum_max(rows, seg, CAP, first_pos)

    f = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P("chip"), P("chip"), P("chip")),
        out_specs=(P("chip"), P("chip")),
    )
    sh = NamedSharding(mesh, P("chip"))
    compiled, hlo = _compile(f, *_kernel_shapes(4 * N, m, 4 * CAP, sh))
    assert "tpu_custom_call" in hlo
    assert compiled.out_info[0].shape == (4 * CAP, m)
