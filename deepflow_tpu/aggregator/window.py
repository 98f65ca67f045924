"""Window ring controller — SubQuadGen/Collector window semantics.

Replicates the reference's windowed-stash protocol
(quadruple_generator.rs:275-352, collector.rs:380-430):

  * time is bucketed into fixed `interval` windows (1s or 60s);
  * a window stays open for `delay` seconds after its end to absorb
    out-of-order arrivals, then is flushed;
  * arrivals older than the oldest open window are dropped and counted
    (`drop_before_window`, collector.rs:386-391).

Control flow is host-driven (the reference drives it from queue ticks);
the data path is device-resident. One deliberate difference: the
reference interleaves per-flow inserts with window moves, while we apply
batch-atomic semantics — merge the whole batch, then advance the window
to `max(batch time) - delay`. Within-batch reordering is invisible to the
output because merges are commutative per window.

Host-sync budget (every device→host fetch stalls the host on the
device): steady-state `ingest` performs
AT MOST one tiny fetch per batch — the versioned on-device COUNTER BLOCK
the jitted append step computes (late/valid/shed plus stash occupancy &
evictions, packed-key excess-word hits, ring fill and feeder shed; see
COUNTER_BLOCK_VERSION / CB_* below) — plus two fetches per *window
advance* (row count + the packed flush matrix's pages, one list),
independent of batch size and of how many windows closed. With
`WindowConfig.stats_ring = K` the blocks accumulate in a
device-resident [K, CB_LEN] ring fetched once per K dispatches,
dropping steady-state syncs to 1/K per batch
(ISSUE 4; late gating moves to device state so flushed rows stay
bit-exact vs per-batch fetching). All transfers route through
`host_fetch` so the CI gate (tests/test_perf_gate.py) can count them and
trip on a reintroduced per-row or per-window fetch; the managers also
account fetch count and bytes per direction, and wrap each host stage
(dispatch / stats fetch / advance / drain) in utils/spans tracer spans.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import chaos
from ..datamodel.schema import FLOW_METER, TAG_SCHEMA, MeterSchema, TagSchema
from ..ops.hashing import fingerprint64
from ..ops.segment import _use_shared_sort, out_blocks_total
from .cascade import CascadeConfig, TierCascade, TierFlush
from .sketchplane import (
    SENTINEL_WIN,
    SketchConfig,
    SketchState,
    WindowSketchBlock,
    _flatten_open,
    _pool_mode,
    sketch_drain,
    sketch_init,
    sketch_plane_step,
    unpack_drained,
)
from ..utils import hostpool
from ..utils.retry import (
    RetryPolicy,
    decorrelated_rng,
    is_dispatch_transient,
    retry_call,
)
from ..utils.spans import (
    FLUSH_SPAN_NAMES,
    SPAN_FLUSH_DRAIN,
    SPAN_FLUSH_FETCH,
    SPAN_FLUSH_JOIN,
    SPAN_FLUSH_RESERVE,
    SPAN_FLUSH_ROWS,
    SPAN_FLUSH_SKETCH,
    SPAN_FLUSH_SPLIT,
    SPAN_FLUSH_WAIT,
    SPAN_INGEST_DISPATCH,
    SPAN_QUERY_SNAPSHOT,
    SPAN_STATS_FETCH,
    SPAN_WINDOW_ADVANCE,
    SPAN_WINDOW_FOLD,
    SpanTracer,
)
from .stash import (
    AccumState,
    StashState,
    _append_impl,
    accum_init,
    check_fold_mode,
    plan_append,
    stash_flush_range,
    stash_fold_counted,
    stash_init,
    stash_merge_fold,
    stash_snapshot_range,
    unpack_flush_rows,
)

_U32_MAX = np.uint32(0xFFFFFFFF)


def host_fetch(x):
    """THE device→host fetch boundary for the windowed path.

    Every transfer WindowManager performs goes through here so the
    perf gate can shim it and assert the per-batch budget; keep new
    fetches behind this seam. A list of device arrays is ONE fetch
    (`jax.device_get`: every copy is started, then all are waited for)
    and comes back as a list of host arrays."""
    if isinstance(x, list):
        return jax.device_get(x)
    return np.asarray(x)


# Rows of one page of a drain's row fetch. A close reads the device
# matrices in pages of this many rows at a traced offset and cuts to the
# live count on the host, so no program on the close path has a shape
# that depends on a document count: one `_take_page` program per matrix
# shape for the life of the process, an over-fetch of under one page per
# part. A constant: chosen on the chip (PERF.md §6, PR 27).
PAGE_ROWS = 16384


@partial(jax.jit, static_argnames=("rows", "axis"))
def _take_page(x, start, *, rows: int, axis: int = 0):
    """`rows` rows of `x` along `axis` from the traced offset `start`
    (clamped by XLA so the page stays inside `x`)."""
    return jax.lax.dynamic_slice_in_dim(x, start, rows, axis=axis)


# What a drain reserves for its exact rows beyond the rows it expects
# (the rows per window of the manager's last drain x the windows it
# closes): 1/32 of them. A window's document count moves ~1% from one
# second to the next in both benchmark deployments, and every reserved
# row is touched and, past the live count, wasted; a drain that outgrows
# its reserve joins the old way.
RESERVE_MARGIN_SHIFT = 5


def reserve_rows(expected: int) -> int:
    """Rows a drain that expects `expected` exact rows reserves."""
    return expected + (expected >> RESERVE_MARGIN_SHIFT)


def _memory_order(a: np.ndarray) -> str:
    """"F" for a column-major host array, else "C". A fetched page has
    the order the device program gave its output: the TPU lays a
    `[rows, 99]` u32 matrix out with the rows minor (`{0,1}`) and the
    host array keeps that, the CPU backend hands back row-major."""
    return "F" if a.ndim > 1 and a.strides[0] < a.strides[-1] else "C"


def _fresh_for(cut: list, axis: int = 0) -> np.ndarray:
    """An uninitialised array that holds the cuts joined along `axis`,
    in their memory order."""
    shape = list(cut[0].shape)
    shape[axis] = sum(c.shape[axis] for c in cut)
    return np.empty(shape, cut[0].dtype, order=_memory_order(cut[0]))


class _PagedRows:
    """The first `n` rows of device array `x` along `axis`, as pages of
    min(`page_rows`, rows of x): `pages` are the device handles to fetch
    (none when n == 0; `x` itself when it is under one page) and `join`
    cuts the fetched pages back to exactly those `n` rows. `page_rows`
    is PAGE_ROWS unless the caller's rows are wide: a packed sketch
    block is megabytes a row, so its part pages by the row. `dst`, a
    host array the caller reserved, is where `join` writes the rows if
    it holds them all in the pages' memory order (axis 0 only)."""

    def __init__(self, x, n: int, axis: int = 0, page_rows: int | None = None,
                 dst: np.ndarray | None = None):
        size = x.shape[axis]
        self.n, self.axis = int(n), axis
        self.page = min(page_rows or PAGE_ROWS, size)
        self.row_bytes = x.nbytes // max(size, 1)
        # dynamic_slice moves a start past this back to it
        self.last_start = size - self.page
        self._no_rows = (x.shape[:axis] + (0,) + x.shape[axis + 1:], x.dtype)
        self.dst = dst
        # what the last `join` saw and did: the fetched pages' memory
        # order (None: no pages), whether the rows landed in `dst`, and
        # the bytes of the fresh array it made (0: a view of a fetched
        # page, or rows written into `dst`)
        self.order: str | None = None
        self.landed = False
        self.joined_bytes = 0
        if self.page == size:
            self.pages = [x] if self.n else []
        else:
            self.pages = [
                _take_page(x, np.int32(s), rows=self.page, axis=axis)
                for s in range(0, self.n, self.page)
            ]

    @property
    def rows_fetched(self) -> int:
        return len(self.pages) * self.page

    def join(self, fetched: list) -> np.ndarray:
        """The `n` live rows of the fetched pages as ONE host array the
        caller owns, in the pages' memory order: a view of the page when
        there is one and nothing was reserved; the cuts written into
        `dst[:n]` when the reserved destination holds them and has the
        pages' order (each row is written once, into memory that was
        touched before: the copy alone); else into a fresh array, whose
        first-touch page faults cost several times the copy. A
        destination of the other order would turn the copy into a
        transpose, which costs as much as the faults: it is left. The
        copy is `hostpool.copy_cuts`: over a few threads where it is
        large."""
        cut = []
        lead = (slice(None),) * self.axis
        for s, page in zip(range(0, self.n, self.page), fetched):
            at = min(s, self.last_start)  # where the page really starts
            cut.append(page[lead + (slice(s - at, min(self.n, s + self.page) - at),)])
        self.order, self.landed, self.joined_bytes = None, False, 0
        self.copied_bytes = self.pooled_bytes = 0
        if not cut:
            return np.zeros(*self._no_rows)
        self.order = _memory_order(fetched[0])
        dst = self.dst
        if (dst is not None and self.n <= dst.shape[0]
                and _memory_order(dst) == self.order):
            self.landed = True
            out = dst[: self.n]
        elif len(cut) == 1:
            return cut[0]
        else:
            out = _fresh_for(cut, self.axis)
            self.joined_bytes = out.nbytes
        self.copied_bytes = out.nbytes
        if hostpool.copy_cuts(cut, out, self.axis) > 1:
            self.pooled_bytes = out.nbytes
        return out


# ---------------------------------------------------------------------------
# Versioned on-device counter block (ISSUE 3). The fused jit step's
# per-batch download widened from the 5-scalar stats vector into this
# u32 block — still ONE fetch, same ≤3-fetch budget. Layout is a
# CONTRACT between the device step and `_process_stats`; bump
# COUNTER_BLOCK_VERSION when it changes (element 0 carries the version
# so a stale host parser fails loudly instead of mis-slicing).
# v2 (ISSUE 4): + feeder_shed — records the feeder runtime dropped
# upstream of this batch's assembly, riding the same fetch so queue
# pressure is visible in the device counter plane.
# v3 (ISSUE 5): + fold_rows — rows the LAST fold's keyed sort touched
# (full-sort mode: whole live stash + ring; merge mode: only the acc
# rows that folded, span-bounded on advances), so the merge-fold's row
# savings are visible in deepflow_system without a new fetch.
# v4 (ISSUE 8): + sketch_rows / sketch_shed — cumulative rows the
# per-window sketch plane folded (the lane asserting sketch updates
# actually ran in the fused dispatch) and rows the plane counted-shed
# (mid-gap jumps, pending-buffer overflow); zero with the plane off.
# v5 (ISSUE 9): + cascade_rows / cascade_shed — cumulative rows the
# rollup cascade's tier folds consumed (closed child-window rows merged
# into 1m/1h tier stashes) and cumulative tier-stash overflow sheds;
# zero with the cascade off. Rides the same fetch as every other lane.
# v6 (ISSUE 10): + snapshot_reads / snapshot_bytes — the live read
# plane's cumulative pull-only snapshot count and fetched bytes (host
# scalars riding the upload direction like feeder_shed, cached as one
# device vector so steady state re-sends the same handle), so a live
# dashboard's read pressure is visible in the device counter plane
# without a new fetch. u32 lanes: bytes wrap mod 2^32 like every other
# cumulative lane; the host ints stay authoritative.
# v7 (ISSUE 20): + sketch_pool_spill / sketch_pool_occ /
# sketch_promotions — the pooled sketch memory's cumulative counted
# spills (windows that wanted a compact slot when the pool was full),
# the occupancy gauge (allocated compact slots + closed-pending wide
# slots at dispatch), and cumulative compact→wide promotions. Zero in
# slab mode (the pool lanes are zero-size arrays whose sums are 0).
# v8 (PR 29): + fold_blocks — the trip count of the LAST fold's output
# loop (ops/segment.py: blocks of OUT_BLOCK_ROWS segments that held a
# live one), the second lane of the vector the fold kernels return
# beside fold_rows. Against `out_blocks_total(capacity)` it says how
# much of the stash's capacity the fold's output side paid for.

COUNTER_BLOCK_VERSION = 8
(
    CB_VERSION,  # constant COUNTER_BLOCK_VERSION
    CB_T_MAX,  # max valid timestamp (pre-gate)
    CB_T_MIN,  # min valid timestamp (pre-gate)
    CB_N_VALID,  # valid rows this batch (pre-gate)
    CB_N_LATE,  # rows dropped by the late-arrival gate
    CB_PREREDUCE_SHED,  # unique keys shed by batch_prereduce this batch
    CB_EXCESS_HITS,  # doc rows whose packed-key excess word != 0
    CB_STASH_OCCUPANCY,  # valid stash rows at dispatch (post-fold)
    CB_STASH_EVICTIONS,  # cumulative stash overflow drops at dispatch
    CB_RING_FILL,  # accumulator rows already occupied at dispatch
    CB_FEEDER_SHED,  # records shed by the feeder before this batch
    CB_FOLD_ROWS,  # rows the last fold's keyed sort touched
    CB_SKETCH_ROWS,  # cumulative rows folded into the sketch plane
    CB_SKETCH_SHED,  # cumulative rows the sketch plane counted-shed
    CB_CASCADE_ROWS,  # cumulative rows the cascade's tier folds consumed
    CB_CASCADE_SHED,  # cumulative tier-stash overflow sheds
    CB_SNAPSHOT_READS,  # cumulative live snapshot_open() reads
    CB_SNAPSHOT_BYTES,  # cumulative live snapshot bytes fetched (mod 2^32)
    CB_SKETCH_POOL_SPILL,  # cumulative pool-exhaustion counted spills
    CB_SKETCH_POOL_OCC,  # pool occupancy gauge at dispatch (compact+wide)
    CB_SKETCH_PROMOTIONS,  # cumulative compact→wide slot promotions
    CB_FOLD_BLOCKS,  # output blocks the last fold's loop ran
) = range(22)
CB_LEN = 22
CB_FIELDS = (
    "version", "t_max", "t_min", "n_valid", "n_late", "prereduce_shed",
    "excess_word_hits", "stash_occupancy", "stash_evictions", "ring_fill",
    "feeder_shed", "fold_rows", "sketch_rows", "sketch_shed",
    "cascade_rows", "cascade_shed", "snapshot_reads", "snapshot_bytes",
    "sketch_pool_spill", "sketch_pool_occ", "sketch_promotions",
    "fold_blocks",
)


def batch_stats(timestamp, valid, start_window, interval, aux=None):
    """Per-batch bookkeeping, device-side (traced): returns (gated_valid,
    window, stats[5] u32) where stats = [t_max, t_min, n_valid, n_late,
    aux]. `start_window` is a traced u32 scalar (0 = no gate yet: no row
    can be late). t_max/t_min are over pre-gate valid rows (0 / U32_MAX
    when none). `aux` rides along so callers piggyback one extra counter
    (e.g. pre-reduce shed rows) on the same single fetch."""
    ts = jnp.asarray(timestamp, dtype=jnp.uint32)
    valid = jnp.asarray(valid)
    window = ts // jnp.uint32(interval)
    late = valid & (window < start_window)
    gated = valid & ~late
    stats = jnp.stack(
        [
            jnp.max(jnp.where(valid, ts, jnp.uint32(0))),
            jnp.min(jnp.where(valid, ts, jnp.uint32(_U32_MAX))),
            jnp.sum(valid).astype(jnp.uint32),
            jnp.sum(late).astype(jnp.uint32),
            jnp.uint32(0) if aux is None else jnp.asarray(aux).astype(jnp.uint32),
        ]
    )
    return gated, window, stats


def batch_counter_block(
    timestamp,
    valid,
    start_window,
    interval,
    *,
    aux=None,
    excess_hits=None,
    stash_valid=None,
    stash_evictions=None,
    ring_fill=None,
    feeder_shed=None,
    fold_rows=None,
    sketch_rows=None,
    sketch_shed=None,
    cascade_rows=None,
    cascade_shed=None,
    snapshot_reads=None,
    snapshot_bytes=None,
    sketch_pool_spill=None,
    sketch_pool_occ=None,
    sketch_promotions=None,
    fold_blocks=None,
):
    """`batch_stats` widened into the versioned counter block (traced).

    Extra lanes ride the SAME single per-batch fetch: packed-key
    excess-word hits (the datamodel/code.py contract guard), stash
    occupancy summed from the (device-resident — zero transfer) valid
    plane, cumulative eviction count, the accumulator-ring fill at
    dispatch, the feeder's upstream shed count for this batch, and the
    last fold's touched-row count and output-loop trip count (device
    scalars the fold kernels return — ISSUE 5, PR 29). All optional
    inputs default to zero so every caller of the old 5-vector shape
    can widen incrementally."""
    gated, window, stats = batch_stats(timestamp, valid, start_window, interval, aux=aux)

    def u32(x):
        return jnp.uint32(0) if x is None else jnp.asarray(x).astype(jnp.uint32)

    occ = (
        jnp.uint32(0)
        if stash_valid is None
        else jnp.sum(stash_valid).astype(jnp.uint32)
    )
    block = jnp.concatenate(
        [
            jnp.full((1,), COUNTER_BLOCK_VERSION, dtype=jnp.uint32),
            stats,
            jnp.stack([u32(excess_hits), occ, u32(stash_evictions),
                       u32(ring_fill), u32(feeder_shed), u32(fold_rows),
                       u32(sketch_rows), u32(sketch_shed),
                       u32(cascade_rows), u32(cascade_shed),
                       u32(snapshot_reads), u32(snapshot_bytes),
                       u32(sketch_pool_spill), u32(sketch_pool_occ),
                       u32(sketch_promotions), u32(fold_blocks)]),
        ]
    )
    return gated, window, block


@partial(jax.jit, donate_argnums=(0,), static_argnames=("interval",))
def _raw_append_step(acc, offset, start_window, stash_valid, stash_evict,
                     feeder_shed, fold_lanes, casc_lanes, snap_lanes,
                     timestamp, key_hi, key_lo, tags, meters, valid,
                     *, interval):
    """One jitted call per raw doc batch: late gate + counter block +
    ring append. `stash_valid`/`stash_evict`/`fold_lanes` (the last
    fold's [fold_rows, fold_blocks]) are
    device-resident lanes folded into the block — inputs already on
    device, no transfer. `feeder_shed` is the feeder's upstream drop
    count for this batch (a host scalar riding the upload direction);
    `casc_lanes` the cascade's device [rows, shed] vector (ISSUE 9 —
    zeros when no cascade is configured); `snap_lanes` the live read
    plane's [reads, bytes] vector (ISSUE 10 — a cached device handle
    rebuilt only when a snapshot actually happens)."""
    gated, window, block = batch_counter_block(
        timestamp, valid, start_window, interval,
        stash_valid=stash_valid, stash_evictions=stash_evict, ring_fill=offset,
        feeder_shed=feeder_shed, fold_rows=fold_lanes[0],
        fold_blocks=fold_lanes[1],
        cascade_rows=casc_lanes[0], cascade_shed=casc_lanes[1],
        snapshot_reads=snap_lanes[0], snapshot_bytes=snap_lanes[1],
    )
    acc = _append_impl(acc, window, key_hi, key_lo, tags, meters, gated, offset)
    return acc, block


def sketch_tag_indices(tag_schema: TagSchema, meter_schema: MeterSchema) -> tuple:
    """Static column-index tuple the sketch-enabled fused steps close
    over: ip0/ip1 words (client + flow identity), server_port /
    protocol / l3_epc_id1 (service grouping + id preview), and the
    byte / rtt meter columns. Raises with the missing field name when a
    schema cannot drive the plane (the plane is TAG_SCHEMA-shaped)."""
    try:
        t = tag_schema.index
        m = meter_schema.index
        return (
            tuple(t(f"ip0_w{w}") for w in range(4))
            + tuple(t(f"ip1_w{w}") for w in range(4))
            + (t("server_port"), t("protocol"), t("l3_epc_id1"),
               m("byte_tx"), m("rtt_sum"), m("rtt_count"))
        )
    except KeyError as e:
        raise ValueError(
            f"sketch plane needs tag/meter column {e} which this "
            f"tag schema / {meter_schema.name} meter schema does not declare"
        ) from e


def sketch_plane_inputs(
    num_groups: int, *, ip0, ip1, server_port, protocol, l3_epc_id1,
    byte_w, rtt_sum, rtt_count,
):
    """Traced: derive the plane's per-row inputs from raw columns.

    Shared by every sketch-enabled step (the raw-doc step here, the
    pipeline's flow-row step, the sharded device step) so all entry
    points sketch identical quantities: the HLL distinct entity is the
    client address (ip0 words), the flow key is the 10-column
    (ip0, ip1, server_port, protocol) fingerprint, the service group is
    the (l3_epc_id1, server_port) hash, the heavy-hitter weight is
    byte_tx, and the id preview is (ip0_w3, port<<16|proto)."""
    u = lambda c: jnp.asarray(c, jnp.uint32)
    ip0 = [u(c) for c in ip0]
    ip1 = [u(c) for c in ip1]
    port, proto, epc = u(server_port), u(protocol), u(l3_epc_id1)
    client_hi, client_lo = fingerprint64(jnp.stack(ip0, axis=1))
    key_hi, key_lo = fingerprint64(jnp.stack(ip0 + ip1 + [port, proto], axis=1))
    group = (epc * jnp.uint32(131) + port) % jnp.uint32(num_groups)
    rtt_cnt = rtt_count
    rtt = rtt_sum / jnp.maximum(rtt_cnt, 1.0)
    return dict(
        group=group, client_hi=client_hi, client_lo=client_lo,
        key_hi=key_hi, key_lo=key_lo, weight=byte_w,
        rtt=rtt, rtt_valid=rtt_cnt > 0,
        id_a=ip0[3],
        id_b=(port << jnp.uint32(16)) | (proto & jnp.uint32(0xFFFF)),
    )


def sketch_inputs_from_matrix(tags, meters, num_groups: int, ix: tuple):
    """`sketch_plane_inputs` over column-major [T, N] tags / [M, N]
    meters via the static `ix` tuple (sketch_tag_indices)."""
    (i00, i01, i02, i03, i10, i11, i12, i13,
     ix_port, ix_proto, ix_epc, m_byte, m_rs, m_rc) = ix
    return sketch_plane_inputs(
        num_groups,
        ip0=[tags[i] for i in (i00, i01, i02, i03)],
        ip1=[tags[i] for i in (i10, i11, i12, i13)],
        server_port=tags[ix_port], protocol=tags[ix_proto],
        l3_epc_id1=tags[ix_epc],
        byte_w=meters[m_byte], rtt_sum=meters[m_rs], rtt_count=meters[m_rc],
    )


def sketch_inputs_from_columns(tags: dict, meters, num_groups: int, meter_ix):
    """`sketch_plane_inputs` over a raw flow-column dict + row-major
    [N, M] meters (`meter_ix` = the meter schema's index fn) — the
    shape every flow-row step holds (RollupPipeline, the sharded device
    step, make_ingest_step's sketch append). One call site per step
    keeps the 'all entry points sketch identical quantities' contract
    a single function instead of three copies."""
    return sketch_plane_inputs(
        num_groups,
        ip0=[tags[f"ip0_w{w}"] for w in range(4)],
        ip1=[tags[f"ip1_w{w}"] for w in range(4)],
        server_port=tags["server_port"], protocol=tags["protocol"],
        l3_epc_id1=tags["l3_epc_id1"],
        byte_w=meters[:, meter_ix("byte_tx")],
        rtt_sum=meters[:, meter_ix("rtt_sum")],
        rtt_count=meters[:, meter_ix("rtt_count")],
    )


def sketch_span_bounds(start_window, ts, valid, *, interval: int, delay: int):
    """Traced: (base_w, close_w) for the plane — the pre-/post-batch
    open-span starts, replicating the host rules exactly: close_w is
    `_process_block`'s advance target (max(gate, (t_max-delay)//i), the
    same value `_stats_ring_push` maintains on device) and base_w is
    the opening rule's max(gate, min(t_min, t_max-delay)//i)."""
    has = jnp.any(valid)
    t_max = jnp.max(jnp.where(valid, ts, jnp.uint32(0)))
    t_min = jnp.min(jnp.where(valid, ts, _U32_MAX))
    t_adj = jnp.where(t_max > jnp.uint32(delay), t_max - jnp.uint32(delay),
                      jnp.uint32(0))
    close_w = jnp.maximum(start_window, t_adj // jnp.uint32(interval))
    base_w = jnp.maximum(
        start_window, jnp.minimum(t_min // jnp.uint32(interval), close_w)
    )
    close_w = jnp.where(has, close_w, start_window)
    base_w = jnp.where(has, base_w, start_window)
    return base_w, close_w


@partial(
    jax.jit,
    donate_argnums=(0, 9),
    static_argnames=("interval", "delay", "ix", "spec", "shared_sort"),
)
def _raw_append_step_sk(acc, offset, start_window, stash_valid, stash_evict,
                        feeder_shed, fold_lanes, casc_lanes, snap_lanes, sk,
                        timestamp, key_hi, key_lo, tags, meters, valid,
                        *, interval, delay, ix, spec, shared_sort=True):
    """`_raw_append_step` with the per-window sketch plane fused in
    (ISSUE 8): the SAME jit dispatch updates HLL/CMS/histogram/top-K
    slots for every accepted row — key identity is the caller's doc
    fingerprint (key_hi/key_lo), client identity re-derives from the
    ip0 tag words — and the counter block grows the v4 sketch lanes.
    Zero new fetches: the plane's closed blocks leave the device via
    the advance drain, not here.

    `shared_sort` (ISSUE 17) is STATIC: this step is
    module-level-jitted, so an env flip after the first trace would be
    invisible if the plane read the knob at trace time — the caller
    (WindowManager.merge_batch) reads it per dispatch instead and a
    flip recompiles (counted by the jit monitor like any retrace)."""
    ts = jnp.asarray(timestamp, dtype=jnp.uint32)
    valid_b = jnp.asarray(valid)
    base_w, close_w = sketch_span_bounds(
        start_window, ts, valid_b, interval=interval, delay=delay
    )
    inp = sketch_inputs_from_matrix(tags, meters, sk.hll.shape[1], ix)
    # the caller's fingerprint IS the flow key — sketch estimates then
    # join exactly against flushed exact rows
    inp["key_hi"] = jnp.asarray(key_hi, jnp.uint32)
    inp["key_lo"] = jnp.asarray(key_lo, jnp.uint32)
    sk = sketch_plane_step(
        sk, spec,
        window=ts // jnp.uint32(interval), valid=valid_b,
        base_w=base_w, close_w=close_w,
        shared_sort=shared_sort, **inp,
    )
    # pool lanes (CB v7): occupancy gauges sum zero-size arrays in slab
    # mode, so the lanes are 0 there without a mode branch
    pool_occ = (
        jnp.sum(sk.slot_of != jnp.int32(-1))
        + jnp.sum(sk.wide_close != jnp.uint32(SENTINEL_WIN))
    ).astype(jnp.uint32)
    gated, window, block = batch_counter_block(
        ts, valid_b, start_window, interval,
        stash_valid=stash_valid, stash_evictions=stash_evict, ring_fill=offset,
        feeder_shed=feeder_shed, fold_rows=fold_lanes[0],
        fold_blocks=fold_lanes[1],
        sketch_rows=sk.rows, sketch_shed=sk.shed,
        cascade_rows=casc_lanes[0], cascade_shed=casc_lanes[1],
        snapshot_reads=snap_lanes[0], snapshot_bytes=snap_lanes[1],
        sketch_pool_spill=sk.pool_spill, sketch_pool_occ=pool_occ,
        sketch_promotions=sk.pool_promos,
    )
    acc = _append_impl(acc, window, key_hi, key_lo, tags, meters, gated, offset)
    return acc, block, sk


# READ-ONLY open-slot sketch snapshot (ISSUE 10): the packed [R, WIDE]
# block rows + their window ids, no donation — the plane keeps counting.
_sketch_open_snapshot = jax.jit(lambda sk: (_flatten_open(sk), sk.win))


def attach_open_sketch_blocks(
    windows: "list[FlushedWindow]", merged: dict, *,
    interval: int, num_tags: int, num_meters: int,
) -> "list[FlushedWindow]":
    """THE open-snapshot block-marry rule, shared by the single-chip
    and sharded snapshot paths (ISSUE 10): attach each window's merged
    open sketch block, synthesize a row-less partial FlushedWindow for
    every block whose window has no exact rows (same coverage contract
    as the drain's sketch-only windows), and return the list sorted by
    window. `merged` is consumed."""
    exact = {f.window_idx for f in windows}
    for f in windows:
        f.sketches = merged.pop(f.window_idx, None)
    for w in sorted(merged):
        if w in exact:
            continue
        windows.append(
            FlushedWindow(
                window_idx=w,
                start_time=w * interval,
                key_hi=np.zeros((0,), np.uint32),
                key_lo=np.zeros((0,), np.uint32),
                tags=np.zeros((0, num_tags), np.uint32),
                meters=np.zeros((0, num_meters), np.float32),
                count=0,
                sketches=merged[w],
                partial=True,
            )
        )
    windows.sort(key=lambda f: f.window_idx)
    return windows


@partial(jax.jit, donate_argnums=(0,), static_argnames=("interval", "delay"))
def _stats_ring_push(ring, k, sw_state, block, *, interval, delay):
    """Device side of the K-batch counter ring (ISSUE 4): write one
    batch's counter block into the [K, CB_LEN] ring at row `k` and
    advance the DEVICE-RESIDENT window-gate state — all without a host
    sync, so the host fetches the whole ring once per K dispatches.

    `sw_state` is [start_window, opened] u32. The update replicates
    `_process_block`'s host bookkeeping exactly: after ANY non-empty
    block the host span ends at max(previous, (t_max - delay) //
    interval) — on the opening batch it first opens at
    max(0, min(t_min, t_max - delay)) but then advances to that same
    value within the SAME block (open_w ≤ adv_w always), so adv_w is
    the post-block gate in both cases. The late gate of every deferred
    batch therefore sees the SAME start_window it would have seen
    under per-batch fetching — that invariant is what makes the K-ring
    flush output bit-exact against the per-batch oracle: no row that
    per-batch mode would late-drop can reach a window the deferred
    flush later closes."""
    ring = jax.lax.dynamic_update_slice(
        ring, block[None, :].astype(jnp.uint32), (k, jnp.int32(0))
    )
    t_max = block[CB_T_MAX]
    has = block[CB_N_VALID] > 0
    # u32-safe max(0, t_max - delay)
    t_adj = jnp.where(t_max > jnp.uint32(delay), t_max - jnp.uint32(delay),
                      jnp.uint32(0))
    adv_w = t_adj // jnp.uint32(interval)
    new_sw = jnp.where(has, jnp.maximum(sw_state[0], adv_w), sw_state[0])
    new_opened = ((sw_state[1] > 0) | has).astype(jnp.uint32)
    return ring, jnp.stack([new_sw, new_opened])


@dataclasses.dataclass(frozen=True)
class WindowConfig:
    interval: int = 1  # seconds per window
    delay: int = 2  # seconds a window stays open past its end
    capacity: int = 1 << 14  # stash rows shared by all open windows
    # Batches accumulated between sort+reduce folds. The accumulator ring
    # is sized accum_batches × (rows of the first batch); a fold also
    # fires before any window flush so flushed windows always see every
    # row. 8 amortizes the O((S+A) log(S+A)) sort ~8x while keeping the
    # fold shape small enough for fast (remote) XLA compiles.
    accum_batches: int = 8
    # Double-buffered drain: defer each batch's stats fetch by one
    # ingest call, so the host never blocks on the current batch (JAX
    # async dispatch stays ahead) and a closing window's flush is
    # dispatched before — and its packed output fetched after — the
    # next batch's append dispatch, overlapping transfer with compute.
    # Flushed windows are then RETURNED exactly one ingest call later
    # than in sync mode (content is identical — rows that would race
    # the flush are late-dropped either way), and counters trail by
    # ≤1 batch. flush_all()/drain()/settle() always settles.
    async_drain: bool = False
    # K-batch counter ring (ISSUE 4): accumulate K batches' counter
    # blocks into a device-resident [K, CB_LEN] ring and fetch ONCE per
    # K dispatches — steady-state host syncs drop to 1/K per batch. The
    # late gate moves to device-resident state (_stats_ring_push) so
    # flushed rows stay bit-exact vs per-batch fetching; the cost is
    # window-close latency of up to K-1 batches (drain-on-advance: any
    # advance discovered at ring drain flushes immediately during the
    # replay; drain-on-checkpoint: settle() always drains the partial
    # ring first). 1 = per-batch fetch (today's behavior). Mutually
    # exclusive with async_drain — the ring subsumes its deferral.
    stats_ring: int = 1
    # Fold strategy (ISSUE 5). "full": every fold re-sorts the whole
    # [S+A] stash+accumulator concat (the oracle). "merge": exploit the
    # stash's standing (slot, key) sort — sort only the accumulator and
    # rank-merge it in (stash.stash_merge_fold); window advances fold
    # ONLY the acc rows of the closing span and flushes re-canonicalize
    # via the compacting range flush. Bit-exact vs "full" (flushed rows,
    # drop counters — tests/test_merge_fold.py) whenever the stash
    # capacity holds the live segments; under stash OVERFLOW "merge"
    # may defer shedding (open-window rows still in the ring are not
    # eviction candidates until folded), never shed more. Default stays
    # "full" until on-chip numbers land (PERF.md §15).
    fold_mode: str = "full"
    # Per-window device sketch plane (ISSUE 8): HLL / count-min /
    # latency-histogram / invertible top-K state per open window,
    # updated inside the SAME fused dispatch as the exact append and
    # drained as packed blocks riding the advance's existing fetches —
    # distinct-count / quantile / heavy-hitter answers stop depending
    # on exact-stash capacity (sheds degrade detail, not coverage).
    # None = off (today's exact-only behavior, zero cost).
    sketch: SketchConfig | None = None
    # Multi-resolution rollup cascade (ISSUE 9): fold closed windows of
    # THIS manager into bounded coarser tiers (1m/1h) on device instead
    # of running a second ingest per granularity. Tier closes ride the
    # advance drain's existing fetches (≤3-fetch budget intact); tier
    # windows surface via WindowManager.pop_tier_windows(). None = off.
    cascade: "CascadeConfig | None" = None
    # Live read plane (ISSUE 10): minimum wall-clock seconds between two
    # device snapshot reads — `snapshot_open()` calls inside the window
    # return the cached OpenSnapshot, so a dashboard storm costs at most
    # one 2-fetch snapshot per interval (and the result cache keyed on
    # the snapshot seq stays hot in between). Snapshots are PULL-only:
    # nothing is read until someone asks.
    min_snapshot_interval: float = 0.25

    def __post_init__(self):
        check_fold_mode(self.fold_mode)
        if self.cascade is not None:
            self.cascade.validate_base(self.interval)

    @property
    def ring(self) -> int:
        # number of simultaneously-open windows
        return self.delay // self.interval + 2


@dataclasses.dataclass
class _FlushEntry:
    """One dispatched-but-not-yet-fetched window advance: the packed
    exact flush handles plus (optionally) the sketch plane's pending
    blocks and the cascade's closed tier flushes. `_drain_flush` fetches
    the whole entry in the same two transfers regardless of what rode
    along: the counts, then every part's fixed-size pages as one list."""

    packed: jnp.ndarray  # [S, 3+T+M] u32 device handle
    total: jnp.ndarray  # scalar i32 device handle
    lo: int
    hi: int
    pend: jnp.ndarray | None = None  # [P, WIDE] u32 (sketch plane on)
    pend_win: jnp.ndarray | None = None  # [P] u32
    pend_n: jnp.ndarray | None = None  # scalar i32
    # pooled sketch memory (ISSUE 20): the wide arena's closed slots
    # drain in place — [Pw, WIDE] rows + [Pw] window ids (SENTINEL_WIN
    # where the slot holds an open/free window). Zero-size in slab mode.
    wide_rows: jnp.ndarray | None = None
    wide_wins: jnp.ndarray | None = None
    tiers: list[TierFlush] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class FlushedWindow:
    """One closed window's documents, host-resident and compacted.

    tags/meters are row-major ([n, T] u32 / [n, M] f32) — already
    unpacked from the single flush matrix, so consumers index rows
    directly instead of masking full-capacity device planes. Both, and
    key_hi / key_lo, are VIEWS of the one `[rows, 3+T+M]` u32 matrix the
    drain fetched and joined (`unpack_flush_rows`), in the memory order
    the fetch gave it and never C-contiguous: from the CPU backend
    row-major with the matrix's row stride (396 B for the flow schema),
    from a TPU column-major (XLA lays the matrix out with the rows
    minor: every tag and meter column is contiguous, a row is strided).
    Index rows or columns and assume no strides. The consumer owns
    them: the manager keeps no reference to that matrix, never writes
    to it again and reuses it for no later drain, so a window handed on
    stays as it was; a consumer that wants contiguous columns copies
    (`storage/store.py` does)."""

    window_idx: int  # absolute window index (timestamp // interval)
    start_time: int  # window start in seconds
    key_hi: np.ndarray  # [n] u32
    key_lo: np.ndarray  # [n] u32
    tags: np.ndarray  # [n, T] u32
    meters: np.ndarray  # [n, M] f32
    count: int
    # the window's approximate summary (ISSUE 8) — present when the
    # sketch plane is enabled; count == 0 with a block attached means
    # the exact stash shed every row of this window but the sketch tier
    # still covered it (degradation of detail, not of coverage)
    sketches: WindowSketchBlock | None = None
    # rollup-cascade provenance (ISSUE 9): 0 = the manager's own
    # resolution; N ≥ 1 = the Nth cascade tier, with `interval` that
    # tier's seconds-per-window (window_idx and start_time are already
    # in tier units — consumers never rescale)
    tier: int = 0
    interval: int = 0
    # live read plane (ISSUE 10): True = a snapshot of a still-OPEN
    # window (rows may keep arriving; the later real flush supersedes
    # this view). Flushed windows are always partial=False.
    partial: bool = False


@dataclasses.dataclass
class OpenSnapshot:
    """One pull of the open device-resident window span (ISSUE 10).

    `windows` are partial=True FlushedWindows — same row layout and
    (window, stash position) order as the real flush, with the open
    sketch slots attached as (partial) WindowSketchBlocks where the
    plane is on. `seq` increments per actual device read (rate-limited
    by `min_snapshot_interval`; cached returns keep their seq) — the
    querier's result cache keys its live token on it, so repeated
    dashboards hit the cache until a NEW snapshot is taken. `open_from`
    is the open span's first second (None = nothing ingested yet)."""

    windows: list["FlushedWindow"]
    taken_monotonic: float
    open_from: int | None = None
    seq: int = 0


class WindowManager:
    """Owns one stash + the open-window span for one granularity."""

    def __init__(
        self,
        config: WindowConfig,
        tag_schema: TagSchema = TAG_SCHEMA,
        meter_schema: MeterSchema = FLOW_METER,
        *,
        tracer: SpanTracer | None = None,
    ):
        if config.stats_ring < 1:
            raise ValueError("stats_ring must be >= 1")
        if config.stats_ring > 1 and config.async_drain:
            raise ValueError(
                "stats_ring > 1 already defers stats fetches; combining it "
                "with async_drain would double-defer — pick one"
            )
        self.config = config
        self.tag_schema = tag_schema
        self.meter_schema = meter_schema
        self.state: StashState = stash_init(config.capacity, tag_schema, meter_schema)
        self.acc: AccumState | None = None  # sized on first batch
        self.fill = 0  # host-tracked accumulator rows
        self.start_window: int | None = None  # oldest open window idx
        self.drop_before_window = 0
        self.total_docs_in = 0
        self.total_flushed = 0
        self.aux_count = 0  # caller-defined stats[4] accumulator
        # device counter-block mirror (as of the last stats fetch; the
        # occupancy/eviction lanes snapshot dispatch time — i.e. the
        # post-fold, pre-flush stash of that batch)
        self.excess_word_hits = 0
        self.stash_occupancy = 0
        # the gauge above summed over every processed counter block, and
        # the capacity as often: monotone, so a delta of the two gives
        # the mean live share of the stash over any stretch of blocks
        self.stash_live_rows_sum = 0
        self.stash_capacity_rows_sum = 0
        self.stash_evictions = 0
        self.device_ring_fill = 0
        self.fold_rows = 0  # CB_FOLD_ROWS mirror: last fold's sorted rows
        # the last fold's output-loop trip count (CB_FOLD_BLOCKS) summed
        # over every processed counter block, and the stash's block count
        # as often: monotone like the live-rows pair above, so a delta of
        # the two is the share of the capacity the folds' output side ran
        self.fold_blocks_run_sum = 0
        self.fold_blocks_total_sum = 0
        self._fold_blocks_total = out_blocks_total(config.capacity)
        # [fold_rows, fold_blocks] device vector the fold kernels return;
        # rides into the next dispatch's counter block like the stash
        # lanes (zero transfer)
        self._fold_lanes_dev = jnp.zeros((2,), jnp.uint32)
        # merge mode drains through the compacting range flush so the
        # stash keeps the canonical layout the rank-merge requires
        self._flush_compact = config.fold_mode == "merge"
        # cached zero [rows, shed] lane vector (cascade off)
        self._zero_lanes = jnp.zeros((2,), jnp.uint32)
        # per-window sketch plane (ISSUE 8): device state + the static
        # column-index tuple the fused step closes over; CB-lane mirrors
        self.sk: SketchState | None = None
        self._sketch_ix: tuple | None = None
        self.sketch_rows = 0
        self.sketch_shed = 0
        # pooled sketch memory (ISSUE 20, CB v7): device-lane mirrors —
        # counted spills, the occupancy gauge, and promotions. All zero
        # in slab mode.
        self.sketch_pool_spill = 0
        self.sketch_pool_occ = 0
        self.sketch_promotions = 0
        # closed blocks fetched but whose window has not flushed yet
        # (K-ring replay can drain blocks ahead of their flush range)
        self._sketch_blocks: dict[int, WindowSketchBlock] = {}
        if config.sketch is not None:
            self._sketch_ix = sketch_tag_indices(tag_schema, meter_schema)
            self.sk = sketch_init(config.sketch, config.ring)
        # multi-resolution rollup cascade (ISSUE 9): device tier stashes
        # + host watermarks/pending sketch merges; CB v5 lane mirrors
        self.cascade: TierCascade | None = None
        self.cascade_rows = 0
        self.cascade_shed = 0
        # closed tier windows awaiting a consumer (pop_tier_windows) —
        # bounded drop-oldest-counted like every other held buffer
        self.tier_flushed: list[FlushedWindow] = []
        self.max_held_tier_windows = 4096
        self.tier_windows_dropped = 0
        if config.cascade is not None:
            self.cascade = TierCascade(
                config.cascade, config.interval, tag_schema, meter_schema
            )
        self.n_advances = 0
        # device↔host transfer accounting (the host_fetch seam)
        self.host_fetches = 0
        self.bytes_fetched = 0
        self.bytes_uploaded = 0  # callers add their packed upload sizes
        # how the drains' paged row fetch engages (every part of every
        # drain): pages fetched, the rows they hold, the rows wanted
        self.flush_pages = 0
        self.flush_rows_fetched = 0
        self.flush_rows_live = 0
        # the drains' host half: exact rows per window of the last drain
        # and the memory order of its fetched pages (what the next one
        # sizes and shapes its reserve from; 0 rows = no history), exact
        # rows that were joined into a reserved destination, the bytes of
        # every host array the drains made after the fetch (a reserve,
        # whole; a part's fresh join result), the bytes of their passes
        # over host memory (a reserve's touch, a join's copy) and those
        # of them `hostpool` divided over more than one thread
        self._drain_rows_per_window = 0
        self._drain_order = "C"
        self.flush_rows_reserved = 0
        self.flush_host_write_bytes = 0
        self.flush_host_pass_bytes = 0
        self.flush_pooled_bytes = 0
        # the sketch plane's share of the drains (zero with the plane
        # off): blocks handed over with their windows, the bytes of
        # packed block rows (`pend` pages, closed wide slots) the drains
        # fetched, and the bytes of the blocks they wanted
        self.sketch_blocks_closed = 0
        self.sketch_bytes_fetched = 0
        self.sketch_bytes_live = 0
        self.feeder_shed = 0  # CB_FEEDER_SHED lane mirror
        # live read plane (ISSUE 10): host-authoritative snapshot
        # counters + the cached [reads, bytes] device vector riding into
        # every dispatch's counter block (rebuilt only when a snapshot
        # actually happens — steady state re-sends the same handle, so
        # no per-batch upload), the rate-limit cache, and the lane
        # mirrors the device plane reported at the last fetched block
        # (drift beyond the in-flight dispatch = bookkeeping bug)
        self.snapshot_reads = 0
        self.snapshot_bytes = 0
        self.snapshot_seq = 0
        self._snap_lanes_dev = jnp.zeros((2,), jnp.uint32)
        self._snapshot_cache: OpenSnapshot | None = None
        self.device_snapshot_reads = 0
        self.device_snapshot_bytes = 0
        # transient-failure policy (ISSUE 6): dispatch + fetch are
        # retried with backoff+jitter (per-instance decorrelated rng —
        # fault injection itself stays deterministic via the chaos
        # plan's own seeded rng). Retrying a dispatch is sound only for
        # admission-time failures (utils/retry.py has the donation
        # caveat) — the chaos seam fires BEFORE the jitted call, and
        # RESOURCE_EXHAUSTED-class rejections do too.
        self.retry_policy = RetryPolicy()
        self._retry_rng = decorrelated_rng(0xD15EA5E)
        self.dispatch_retries = 0
        self.fetch_retries = 0
        self.tracer = tracer if tracer is not None else SpanTracer()
        # window lineage plane (ISSUE 13): optional per-window hop
        # recorder (tracing/lineage.LineageTracker). Every hop is a
        # host wall stamp — attaching it never adds a device fetch
        # (CI-gated, test_perf_gate::test_lineage_tracing_budget).
        self.lineage = None
        # device profiling plane (ISSUE 12): every device-resident plane
        # this manager owns is enumerable via device_planes(), and the
        # manager registers WEAKLY on the process-wide HBM ledger (the
        # r13 tier-registry stance — GC removes it, close() eagerly so)
        from ..profiling.ledger import register_profilable

        self._ledger_src = register_profilable(
            "window_manager", self,
            interval=f"{config.interval}s",
            sketch=str(config.sketch is not None),
            cascade=str(config.cascade is not None),
        )
        # async-drain double buffers (device handles, fetched next call)
        self._pending_stats = None
        self._pending_flush: list[tuple] = []
        # K-batch counter ring (stats_ring > 1): device [K, CB_LEN] ring
        # + device-resident [start_window, opened] gate state; the host
        # mirror (start_window above) catches up at every ring drain.
        self._cb_ring = (
            jnp.zeros((config.stats_ring, CB_LEN), jnp.uint32)
            if config.stats_ring > 1 else None
        )
        self._ring_count = 0  # blocks in the ring awaiting the fetch
        self._sw_state = (
            jnp.zeros((2,), jnp.uint32) if config.stats_ring > 1 else None
        )

    def _fetch(self, x):
        """host_fetch + per-manager transfer accounting (count + bytes;
        a list of device arrays is one fetch and returns a list).
        Transient fetch failures (transfer timeouts, injected
        chaos faults) retry with backoff — the device handle stays
        valid across a blown fetch deadline."""

        def once():
            chaos.maybe_fail(chaos.SITE_FETCH)
            return host_fetch(x)

        def on_retry(_attempt, _exc):
            self.fetch_retries += 1

        arr = retry_call(once, self.retry_policy, on_retry=on_retry,
                         rng=self._retry_rng)
        self.host_fetches += 1
        self.bytes_fetched += (
            sum(a.nbytes for a in arr) if isinstance(arr, list) else arr.nbytes
        )
        return arr

    def _fetch_parts(self, parts: "list[_PagedRows]") -> list[np.ndarray]:
        """Every page of every part of a drain in ONE fetch; returns each
        part cut back to its live rows. No pages, no fetch."""
        pages = [pg for part in parts for pg in part.pages]
        got = iter(())
        if pages:
            with self.tracer.span(SPAN_FLUSH_FETCH):
                got = iter(self._fetch(pages))
        with self.tracer.span(SPAN_FLUSH_JOIN):
            return [part.join([next(got) for _ in part.pages]) for part in parts]

    # -- device→host drains ---------------------------------------------
    def _reserve_rows(self, entry: "_FlushEntry", windows: int) -> np.ndarray | None:
        """The host array this drain's exact rows will be joined into,
        made and touched BEFORE the blocking scalar fetch, while the
        device runs the fold and the range flush: sized from what the
        manager knows without a fetch, the rows per window of its last
        drain x the `windows` this entry can hold rows of, plus
        `reserve_rows`' margin, never more than the stash, in the memory
        order that drain's pages came in. None (the join allocates, as
        before) with no history or where the expected rows fit one page,
        which `join` hands on as a view of the fetched page with no
        copy."""
        size = entry.packed.shape[0]
        rows = min(reserve_rows(self._drain_rows_per_window * windows), size)
        if rows <= min(PAGE_ROWS, size):
            return None
        with self.tracer.span(SPAN_FLUSH_RESERVE):
            dst, workers = hostpool.touched_rows(
                rows, entry.packed.shape[1], self._drain_order)
        self.flush_host_write_bytes += dst.nbytes
        self.flush_host_pass_bytes += dst.nbytes
        self.flush_pooled_bytes += dst.nbytes * (workers > 1)
        return dst

    def _drain_flush(self, entry: "_FlushEntry") -> list[FlushedWindow]:
        """Fetch ONE packed flush result and split it into windows.

        Two transfers regardless of row/window count — with the sketch
        plane and/or the rollup cascade enabled the SAME two transfers
        also carry the closed sketch blocks and the closed TIER windows'
        rows: the scalar fetch widens to [row count, pending block
        count, tier row counts…] and the row fetch is one list of
        fixed-size pages (`_PagedRows`: flush rows, packed blocks, block
        window ids, tier rows per tier — each part paged at its own
        size and cut to its live rows on the host), so the ≤3-fetch
        budget is untouched (tests/test_perf_gate.py) and nothing
        dispatched here has a shape that depends on a count.

        Between the fetch and the hand-over a row is written to host
        memory ONCE: the pages' live cuts are joined into a destination
        that `_reserve_rows` made and touched under `flush.wait`, while
        the device still ran the fold (a miss - no history, more rows
        than reserved, pages of another memory order - joins into a
        fresh array as before), and the split hands on views of that one
        matrix. The matrix leaves with the windows: the manager keeps no
        reference and never writes to it again, and no later drain
        reuses it."""
        has_sketch = entry.pend is not None
        # pooled sketch memory (ISSUE 20): closed WIDE slots ride the
        # same two transfers. The scalar vector widens by one lane
        # (closed-wide count, so a drain with none skips the wide bytes
        # entirely); when any closed, all Pw rows + window ids join the
        # row fetch and the host filters on SENTINEL_WIN — Pw is a
        # handful of rows, the filter is cheaper than a device
        # compaction.
        has_wide = entry.wide_rows is not None and entry.wide_rows.size > 0
        # the first fetch of a drain blocks until the fold and the range
        # flush dispatched ahead of it have run on the device
        with self.tracer.span(SPAN_FLUSH_WAIT):
            scalars = [jnp.asarray(entry.total, jnp.int32)]
            if has_sketch:
                scalars.append(jnp.asarray(entry.pend_n, jnp.int32))
            if has_wide:
                scalars.append(
                    jnp.sum(entry.wide_wins != jnp.uint32(SENTINEL_WIN)).astype(
                        jnp.int32
                    )
                )
            scalars += [jnp.asarray(tf.total, jnp.int32) for tf in entry.tiers]
            n_wide = 0
            # windows this entry can hold rows of: an advance's hi - lo,
            # at most the open span (`flush_all` and a jump in time name
            # a wider range)
            windows = min(entry.hi - entry.lo, self.config.ring - 1)
            reserved = self._reserve_rows(entry, windows)
            if len(scalars) == 1:
                total, n_blocks, tier_totals = int(self._fetch(scalars[0])), 0, []
            else:
                vec = self._fetch(jnp.stack(scalars))
                o = 1 + int(has_sketch) + int(has_wide)
                total = int(vec[0])
                n_blocks = int(vec[1]) if has_sketch else 0
                if has_wide:
                    n_wide = int(vec[1 + int(has_sketch)])
                tier_totals = [int(v) for v in vec[o:]]
        # an empty drain still runs the (empty) rows and split phases:
        # previously-held sketch blocks may marry this drain's [lo, hi)
        # range, and a tier window whose exact rows were all shed
        # (sketch-only coverage) still closes there
        with self.tracer.span(SPAN_FLUSH_ROWS):
            # only the exact rows are joined into a reserve: blocks, window
            # ids and tier rows are small or page by the block
            parts = [_PagedRows(entry.packed, total, dst=reserved)]
            sk_parts = []  # (packed block rows of this drain, how many it wants)
            if has_sketch:
                # a page of ONE block: a drain that holds one closed
                # block fetches that block, not all of `pend`
                sk_parts.append(
                    (_PagedRows(entry.pend, n_blocks, page_rows=1), n_blocks))
                parts += [sk_parts[-1][0], _PagedRows(entry.pend_win, n_blocks)]
            if n_wide:
                pw = entry.wide_rows.shape[0]  # every slot: the host filters
                sk_parts.append((_PagedRows(entry.wide_rows, pw), n_wide))
                parts += [sk_parts[-1][0], _PagedRows(entry.wide_wins, pw)]
            parts += [_PagedRows(tf.packed, t)
                      for tf, t in zip(entry.tiers, tier_totals)]
            self.flush_pages += sum(len(p.pages) for p in parts)
            self.flush_rows_fetched += sum(p.rows_fetched for p in parts)
            self.flush_rows_live += sum(p.n for p in parts)
            for part, wanted in sk_parts:
                self.sketch_bytes_fetched += part.rows_fetched * part.row_bytes
                self.sketch_bytes_live += wanted * part.row_bytes
            got = iter(self._fetch_parts(parts))
            self.flush_host_write_bytes += sum(p.joined_bytes for p in parts)
            self.flush_host_pass_bytes += sum(p.copied_bytes for p in parts)
            self.flush_pooled_bytes += sum(p.pooled_bytes for p in parts)
            exact = parts[0]
            self._drain_rows_per_window = -(-total // windows)
            self._drain_order = exact.order or self._drain_order
            if exact.landed:
                self.flush_rows_reserved += total
            rows = next(got)
            blocks = (next(got), next(got)) if has_sketch else None
            wide = (next(got), next(got)) if n_wide else None
            tier_rows = list(got)
        with self.tracer.span(SPAN_FLUSH_SPLIT):
            return self._split_drained(entry, rows, blocks, wide, tier_rows)

    def _hold_sketch_blocks(self, block_rows: np.ndarray, wins: np.ndarray) -> None:
        for blk in unpack_drained(block_rows, wins, self.config.sketch):
            have = self._sketch_blocks.get(blk.window)
            self._sketch_blocks[blk.window] = (
                blk if have is None else have.merge(blk)
            )

    def _split_drained(
        self, entry: "_FlushEntry", rows: np.ndarray,
        blocks: tuple[np.ndarray, np.ndarray] | None,
        wide: tuple[np.ndarray, np.ndarray] | None,
        tier_rows: list[np.ndarray],
    ) -> list[FlushedWindow]:
        """The host half of a drain: split the fetched exact rows into
        windows, unpack the sketch blocks (pending `blocks` and the wide
        arena's slots, each as (rows, window ids)) and marry them to the
        windows, then build the tier windows from `tier_rows`."""
        flushed = []
        if blocks is not None or wide is not None:
            with self.tracer.span(SPAN_FLUSH_SKETCH):
                if blocks is not None:
                    self._hold_sketch_blocks(*blocks)
                if wide is not None:
                    w_rows, w_wins = wide
                    keep = w_wins != np.uint32(SENTINEL_WIN)
                    self._hold_sketch_blocks(w_rows[keep], w_wins[keep])
        if rows.shape[0]:
            flushed = self._split_flushed(rows, rows.shape[0])
        # marry blocks to this drain's window range; blocks whose exact
        # rows were all shed become sketch-only windows (count == 0)
        for f in flushed:
            f.sketches = self._sketch_blocks.pop(f.window_idx, None)
        exact_wins = {f.window_idx for f in flushed}
        lo, hi = entry.lo, entry.hi
        for w in sorted(self._sketch_blocks):
            if lo <= w < hi and w not in exact_wins:
                blk = self._sketch_blocks.pop(w)
                flushed.append(
                    FlushedWindow(
                        window_idx=w,
                        start_time=w * self.config.interval,
                        key_hi=np.zeros((0,), np.uint32),
                        key_lo=np.zeros((0,), np.uint32),
                        tags=np.zeros((0, self.tag_schema.num_fields), np.uint32),
                        meters=np.zeros(
                            (0, self.meter_schema.num_fields), np.float32
                        ),
                        count=0,
                        sketches=blk,
                    )
                )
        flushed.sort(key=lambda f: f.window_idx)
        self.sketch_blocks_closed += sum(f.sketches is not None for f in flushed)
        lin = self.lineage
        if lin is not None and flushed:
            lin.note_flush_windows([(f.window_idx, f.count) for f in flushed])
        if self.cascade is not None:
            # this drain's closed child blocks feed the parent merge
            # BEFORE tier windows are built, so a parent closing in the
            # same drain sees every child (merge order is immaterial —
            # the r12 associativity pins)
            for f in flushed:
                if f.sketches is not None:
                    self.cascade.feed_block(0, f.window_idx, f.sketches)
            tier_wins: list[FlushedWindow] = []
            for tf, t_rows in zip(entry.tiers, tier_rows):
                tier_wins.extend(
                    self.cascade.take_tier_windows(tf, t_rows, t_rows.shape[0])
                )
            if lin is not None and tier_wins:
                lin.note_tier_windows(
                    [(f.interval, f.window_idx, f.count) for f in tier_wins]
                )
            from .sketchplane import hold_blocks

            self.tier_windows_dropped += hold_blocks(
                self.tier_flushed, tier_wins, self.max_held_tier_windows
            )
        return flushed

    def _split_rows(
        self, rows: np.ndarray, total: int, *, partial: bool = False
    ) -> list[FlushedWindow]:
        """Packed (window, stash position)-ordered rows → per-window
        FlushedWindows. Shared by the real flush drain and the live
        snapshot (partial=True) so both split identically."""
        if total == 0:
            return []
        win, key_hi, key_lo, tags, meters = unpack_flush_rows(
            rows, self.tag_schema.num_fields
        )
        flushed = []
        bounds = np.flatnonzero(np.r_[True, win[1:] != win[:-1]]).tolist() + [total]
        for a, b in zip(bounds, bounds[1:]):
            w = int(win[a])
            flushed.append(
                FlushedWindow(
                    window_idx=w,
                    start_time=w * self.config.interval,
                    key_hi=key_hi[a:b],
                    key_lo=key_lo[a:b],
                    tags=tags[a:b],
                    meters=meters[a:b],
                    count=b - a,
                    partial=partial,
                )
            )
        return flushed

    def _split_flushed(self, rows: np.ndarray, total: int) -> list[FlushedWindow]:
        self.total_flushed += total
        return self._split_rows(rows, total)

    def _drain_ready(self, ready) -> list[FlushedWindow]:
        if not ready:
            return []
        with self.tracer.span(SPAN_FLUSH_DRAIN):
            out = []
            for entry in ready:
                out.extend(self._drain_flush(entry))
            return out

    def _fold(self):
        """Full-set fold: every accumulated row reaches the stash and
        the ring resets. fold_mode picks the kernel — the full [S+A]
        re-sort or the rank merge — but both consume the whole ring."""
        if self.fill == 0:
            return
        with self.tracer.span(SPAN_WINDOW_FOLD):
            if self.config.fold_mode == "merge":
                self.state, self.acc, self._fold_lanes_dev = stash_merge_fold(
                    self.state, self.acc, self.meter_schema
                )
            else:
                self.state, self.acc, self._fold_lanes_dev = stash_fold_counted(
                    self.state, self.acc, self.meter_schema
                )
        self.fill = 0

    def _fold_span(self, hi_window: int):
        """Span-bounded advance fold (fold_mode="merge"): merge ONLY the
        acc rows with slot < hi_window — the windows about to flush —
        and leave the rest accumulated. `fill` stays put: consumed rows
        turn sentinel in place and their ring slots are reclaimed by the
        next full fold (plan_append cadence)."""
        if self.fill == 0:
            return
        with self.tracer.span(SPAN_WINDOW_FOLD):
            self.state, self.acc, self._fold_lanes_dev = stash_merge_fold(
                self.state, self.acc, self.meter_schema,
                hi_window=np.uint32(hi_window),
            )

    def window_of(self, timestamp):
        return timestamp // self.config.interval

    def attach_lineage(self, tracker) -> None:
        """Wire a tracing/lineage.LineageTracker: dispatch stamps,
        advance/flush/tier-close hops and the freshness lags all record
        from this manager's existing host seams."""
        self.lineage = tracker

    def _lineage_span_of(self, timestamp, valid) -> tuple[int, int] | None:
        """Host-side window span of one batch — ONLY when the arrays
        are already host-resident (a jnp input would force the transfer
        the zero-fetch contract forbids)."""
        if not isinstance(timestamp, np.ndarray):
            return None
        # the valid mask must be host too — np.asarray on a jnp array
        # would force the very transfer the zero-fetch contract forbids
        v = valid if isinstance(valid, np.ndarray) else None
        ts = timestamp[v.astype(bool)] \
            if (v is not None and v.shape == timestamp.shape) else timestamp
        if ts.size == 0:
            return None
        iv = self.config.interval
        return int(ts.min()) // iv, int(ts.max()) // iv

    def _cascade_lanes(self) -> jnp.ndarray:
        """Device [rows, shed] vector for the counter block's v5 lanes —
        the cascade's when configured, a cached zero vector otherwise
        (same handle every dispatch, so no per-batch upload)."""
        if self.cascade is not None:
            return self.cascade.lanes_dev
        return self._zero_lanes

    def pop_tier_windows(self) -> list[FlushedWindow]:
        """Drain the cascade's closed tier windows (1m/1h…), oldest
        first. Each FlushedWindow carries tier ≥ 1 and its tier
        `interval`; count == 0 with a sketch block attached means the
        exact tier stash shed the window but the merged child sketches
        still cover it."""
        out, self.tier_flushed = self.tier_flushed, []
        return out

    # -- live read plane (ISSUE 10) --------------------------------------
    def _snapshot_lanes(self) -> jnp.ndarray:
        """Device [reads, bytes] vector for the counter block's v6 lanes
        — cached, rebuilt only when a snapshot happens, so steady-state
        dispatches re-send the same handle (no per-batch upload)."""
        return self._snap_lanes_dev

    def snapshot_open(self, *, force: bool = False) -> OpenSnapshot:
        """Pull a read-only snapshot of the OPEN window span: every
        stash row with slot ≥ start_window (the accumulator ring is
        folded in first — a pure device dispatch, zero fetches, the
        same fold the next advance would run) plus the open sketch
        slots, fetched in the flush drain's 2-transfer shape (one
        scalar, one list of fixed-size pages). The stash is untouched
        (stash_snapshot_range does not donate), so the later real flush
        of these windows emits the same rows plus whatever arrived
        after the snapshot — the overlay contract the querier relies
        on: flushed rows SUPERSEDE a window's partial snapshot.

        Rate-limited: within `min_snapshot_interval` seconds the cached
        OpenSnapshot returns (same seq — result caches stay hot);
        `force=True` bypasses. Pull-only: ingest never takes one.
        Caveat: the eager fold means that under stash OVERFLOW a
        snapshot can shed at the pull instead of the next natural fold
        — same counted-shed stance, possibly earlier (fold_mode="merge"
        deferral note in WindowConfig)."""
        now = time.monotonic()
        cached = self._snapshot_cache
        if (
            not force
            and cached is not None
            and now - cached.taken_monotonic < self.config.min_snapshot_interval
        ):
            return cached
        with self.tracer.span(SPAN_QUERY_SNAPSHOT):
            snap = self._read_open_snapshot(now)
        self.snapshot_seq += 1
        snap.seq = self.snapshot_seq
        if self.lineage is not None and snap.windows:
            # a live read served these still-open windows: the DISTINCT
            # partial lane (ISSUE 13 — never confusable with post-flush
            # visibility)
            self.lineage.note_snapshot(
                [(w.window_idx, w.count) for w in snap.windows]
            )
        self._snap_lanes_dev = jnp.asarray(
            [self.snapshot_reads & 0xFFFFFFFF, self.snapshot_bytes & 0xFFFFFFFF],
            dtype=jnp.uint32,
        )
        self._snapshot_cache = snap
        return snap

    def _read_open_snapshot(self, now: float) -> OpenSnapshot:
        if self.start_window is None:
            self.snapshot_reads += 1
            return OpenSnapshot(windows=[], taken_monotonic=now)
        b0, f0 = self.bytes_fetched, self.host_fetches
        self._fold()  # ring rows → stash (exact; zero fetches)
        packed, total = stash_snapshot_range(
            self.state, np.uint32(self.start_window), _U32_MAX
        )
        blocks = wins = None
        if self.sk is not None:
            blocks, wins = _sketch_open_snapshot(self.sk)
        total_i = int(self._fetch(jnp.asarray(total, jnp.int32)))
        parts = [_PagedRows(packed, total_i)]
        if blocks is not None:
            # every open slot: the host filters on SENTINEL_WIN
            r = blocks.shape[0]
            parts += [_PagedRows(blocks, r), _PagedRows(wins, r)]
        rows, *open_slots = self._fetch_parts(parts)
        windows = self._split_rows(rows, total_i, partial=True)
        if open_slots:
            block_rows, win_np = open_slots
            live = win_np != np.uint32(SENTINEL_WIN)
            open_blocks = {
                blk.window: blk
                for blk in unpack_drained(
                    block_rows[live], win_np[live], self.config.sketch
                )
            }
            windows = attach_open_sketch_blocks(
                windows, open_blocks,
                interval=self.config.interval,
                num_tags=self.tag_schema.num_fields,
                num_meters=self.meter_schema.num_fields,
            )
        self.snapshot_reads += 1
        self.snapshot_bytes += self.bytes_fetched - b0
        assert self.host_fetches - f0 <= 2, "snapshot must stay a 2-fetch read"
        return OpenSnapshot(
            windows=windows,
            taken_monotonic=now,
            open_from=self.start_window * self.config.interval,
        )

    # -- stats processing (the ONE per-batch host sync) ------------------
    def _process_stats(self, stats_dev) -> None:
        """Fetch one batch's packed counter block and replay it through
        the host bookkeeping (`_process_block`)."""
        with self.tracer.span(SPAN_STATS_FETCH):
            vec = [int(v) for v in self._fetch(stats_dev)]
        self._process_block(vec)

    def _drain_stats_ring(self) -> None:
        """Fetch the filled prefix of the counter ring in ONE transfer
        and replay every block in dispatch order — window advances land
        exactly where per-batch fetching would have put them, just
        discovered (and flushed) at the drain instead of mid-ring."""
        if self._ring_count == 0:
            return
        with self.tracer.span(SPAN_STATS_FETCH):
            rows = self._fetch(self._cb_ring[: self._ring_count])
        self._ring_count = 0
        for row in rows:
            self._process_block([int(v) for v in row])

    def _sync_device_sw(self) -> None:
        """Reset the device gate state to the host span (checkpoint
        restore / external start_window mutation). Only meaningful with
        stats_ring > 1; requires a drained ring."""
        if self._sw_state is None:
            return
        if self._ring_count:
            raise RuntimeError("cannot resync device gate over a filled ring")
        sw = 0 if self.start_window is None else self.start_window
        opened = 0 if self.start_window is None else 1
        self._sw_state = jnp.asarray([sw, opened], dtype=jnp.uint32)

    def _process_block(self, vec: list[int]) -> None:
        """One batch's counter block → host counters, open-span advance
        and the (dispatched, not fetched) range flush.

        Accepts both the versioned CB_LEN block (element 0 =
        COUNTER_BLOCK_VERSION) and the legacy 5-scalar stats vector, so
        caller-supplied dispatch steps can widen incrementally."""
        lin = self.lineage
        # one block = one dispatch: pop its wall stamp FIRST (whether or
        # not this block advances) so the FIFO pairing stays aligned
        # across K-ring drains and async settles
        lin_stamp = lin.pop_dispatch_stamp() if lin is not None else None
        if len(vec) == CB_LEN:
            if vec[CB_VERSION] != COUNTER_BLOCK_VERSION:
                raise ValueError(
                    f"counter block version {vec[CB_VERSION]} != "
                    f"{COUNTER_BLOCK_VERSION} — device/host layout drift"
                )
            t_max, t_min, n_valid, n_late, aux = vec[CB_T_MAX:CB_PREREDUCE_SHED + 1]
            self.excess_word_hits += vec[CB_EXCESS_HITS]
            self.stash_occupancy = vec[CB_STASH_OCCUPANCY]
            self.stash_live_rows_sum += vec[CB_STASH_OCCUPANCY]
            self.stash_capacity_rows_sum += self.config.capacity
            self.stash_evictions = vec[CB_STASH_EVICTIONS]
            self.device_ring_fill = vec[CB_RING_FILL]
            self.feeder_shed += vec[CB_FEEDER_SHED]
            self.fold_rows = vec[CB_FOLD_ROWS]
            self.fold_blocks_run_sum += vec[CB_FOLD_BLOCKS]
            self.fold_blocks_total_sum += self._fold_blocks_total
            # cumulative device scalars — mirror, don't accumulate
            self.sketch_rows = vec[CB_SKETCH_ROWS]
            self.sketch_shed = vec[CB_SKETCH_SHED]
            self.cascade_rows = vec[CB_CASCADE_ROWS]
            self.cascade_shed = vec[CB_CASCADE_SHED]
            # live-read lanes: the host ints above stay authoritative;
            # these are what the device plane carried at that dispatch
            self.device_snapshot_reads = vec[CB_SNAPSHOT_READS]
            self.device_snapshot_bytes = vec[CB_SNAPSHOT_BYTES]
            # pooled sketch memory (ISSUE 20): spill/promotions are
            # cumulative device scalars (mirror), occupancy is a gauge
            self.sketch_pool_spill = vec[CB_SKETCH_POOL_SPILL]
            self.sketch_pool_occ = vec[CB_SKETCH_POOL_OCC]
            self.sketch_promotions = vec[CB_SKETCH_PROMOTIONS]
        elif len(vec) == 5:  # legacy [t_max, t_min, n_valid, n_late, aux]
            t_max, t_min, n_valid, n_late, aux = vec
        else:
            raise ValueError(
                f"counter block of {len(vec)} lanes is neither the "
                f"v{COUNTER_BLOCK_VERSION} CB_LEN={CB_LEN} block nor the "
                "legacy 5-vector — device/host layout drift"
            )
        self.aux_count += aux
        if n_valid == 0:
            return
        if self.start_window is None:
            # Open the ring far enough back that data older than the first
            # batch but within `delay` is still accepted — the reference
            # starts its window 2min in the past for the same reason
            # (quadruple_generator.rs:782-783). The first batch was gated
            # at window 0, which admits exactly the same rows: this start
            # is ≤ the first batch's oldest valid window.
            self.start_window = self.window_of(
                max(0, min(t_min, t_max - self.config.delay))
            )
        self.drop_before_window += n_late
        self.total_docs_in += n_valid - n_late

        # Advance: every window whose end is more than `delay` behind the
        # newest arrival closes now (move_window, quadruple_generator.rs:339).
        # ALL closed windows flush in ONE fused device call; empty
        # intermediate windows shift silently (the packed matrix simply
        # has no rows for them), so a large timestamp gap costs nothing.
        new_start = self.window_of(max(t_max - self.config.delay, 0))
        if self.start_window < new_start:
            with self.tracer.span(SPAN_WINDOW_ADVANCE):
                # flushed windows must see every accumulated row of the
                # closing span; merge mode folds ONLY that span and
                # leaves open windows' rows in the ring
                if self.config.fold_mode == "merge":
                    self._fold_span(new_start)
                else:
                    self._fold()
                self.state, packed, total = stash_flush_range(
                    self.state,
                    np.uint32(self.start_window),
                    np.uint32(new_start),
                    compact=self._flush_compact,
                )
                self._pending_flush.append(
                    self._make_flush_entry(
                        packed, total, self.start_window, new_start
                    )
                )
                if lin is not None:
                    lin.note_advance(self.start_window, new_start, lin_stamp)
                self.start_window = new_start
                self.n_advances += 1

    def _make_flush_entry(self, packed, total, lo: int, hi: int) -> "_FlushEntry":
        """Build one _pending_flush entry: the exact flush handles,
        widened with the sketch plane's pending-drain handles and the
        cascade's tier fold+flush handles (extra DISPATCHES on the
        advance path only, zero extra fetches — _drain_flush bundles
        everything into the existing two transfers)."""
        entry = _FlushEntry(packed=packed, total=total, lo=int(lo), hi=int(hi))
        if self.sk is not None:
            (self.sk, entry.pend, entry.pend_win, entry.pend_n,
             entry.wide_rows, entry.wide_wins) = sketch_drain(
                self.sk, np.uint32(hi)
            )
        if self.cascade is not None:
            entry.tiers = self.cascade.on_advance(packed, total, int(hi))
        return entry

    # -- ingest ----------------------------------------------------------
    def ingest(
        self,
        timestamp,  # [N] u32 seconds (device or host)
        key_hi,
        key_lo,
        tags,
        meters,
        valid,
        feeder_shed: int = 0,
    ) -> list[FlushedWindow]:
        """Merge a doc batch; advance and flush any windows that closed.

        Returns flushed windows in order (possibly empty). With
        `async_drain`, returns the windows closed by the *previous*
        batch instead (double-buffered — see WindowConfig).
        `feeder_shed` rides into the counter block's CB_FEEDER_SHED
        lane (upstream drop accounting, ISSUE 4)."""
        window_span = (
            self._lineage_span_of(timestamp, valid)
            if self.lineage is not None else None
        )
        timestamp = jnp.asarray(timestamp, dtype=jnp.uint32)
        rows = int(timestamp.shape[0])
        interval = self.config.interval

        if self.sk is not None:
            def dispatch(acc, offset, start_window):
                # sketch-enabled twin: the plane state reads/donates at
                # dispatch time like the stash lanes; the step returns
                # the updated plane as a third output
                st = self.state
                return _raw_append_step_sk(
                    acc, offset, start_window, st.valid, st.dropped_overflow,
                    jnp.uint32(feeder_shed), self._fold_lanes_dev,
                    self._cascade_lanes(), self._snapshot_lanes(), self.sk,
                    timestamp, key_hi, key_lo, tags, meters, valid,
                    interval=interval, delay=self.config.delay,
                    ix=self._sketch_ix, spec=self.config.sketch.hist,
                    # env knob read at DISPATCH time (a static argname —
                    # the step is module-level-jitted, so a flip must
                    # recompile rather than silently keep the old path)
                    shared_sort=_use_shared_sort(),
                )
        else:
            def dispatch(acc, offset, start_window):
                # read the stash AT DISPATCH time (ingest_step may fold
                # first) so the block's occupancy/fold_rows lanes see the
                # post-fold plane; all lanes are device-resident — zero
                # transfer
                st = self.state
                return _raw_append_step(
                    acc, offset, start_window, st.valid, st.dropped_overflow,
                    jnp.uint32(feeder_shed), self._fold_lanes_dev,
                    self._cascade_lanes(), self._snapshot_lanes(),
                    timestamp, key_hi, key_lo, tags, meters, valid,
                    interval=interval,
                )

        return self.ingest_step(dispatch, rows, window_span=window_span)

    def ingest_step(
        self, dispatch, rows: int, ring_rows: int | None = None,
        window_span: tuple[int, int] | None = None,
    ) -> list[FlushedWindow]:
        """Window protocol around a caller-supplied jitted append step.

        `dispatch(acc, offset, start_window)` must return (new_acc,
        stats[5]) with stats as produced by `batch_stats` — pipelines use
        this to fuse fanout/fingerprint/pre-reduce into the same single
        device call (aggregator/pipeline.py). `rows` is the static number
        of accumulator rows the step appends; `ring_rows` (≥ rows) sizes
        the accumulator ring when bucketed callers know a larger batch
        shape is coming, so a small first bucket doesn't build a ring a
        later big bucket immediately replaces. `window_span` (lo, hi —
        host-computed from the batch's own timestamps) binds this
        dispatch to the lineage plane when one is attached (ISSUE 13)."""
        if rows == 0:
            return self._settle_ready()

        ready = self._pending_flush
        self._pending_flush = []

        if self._pending_stats is not None:
            # async: settle the previous batch BEFORE this one's gate —
            # start_window advances exactly as it would have in sync mode.
            stats, self._pending_stats = self._pending_stats, None
            self._process_stats(stats)

        plan = plan_append(self.fill, self.acc.capacity if self.acc else None, rows)
        if plan == "init":
            self._fold()  # pending rows must reach the stash before the ring is replaced
            if self.fill:
                # the plan_append docstring warns that replacing a ring
                # with pending rows silently loses them — make that
                # failure LOUD if a refactor ever bypasses the fold
                # (e.g. wires a span-bounded fold in here)
                raise AssertionError(
                    f"accumulator ring re-init with {self.fill} pending "
                    "rows — they would be silently lost (plan_append "
                    "'init' contract: fold before replacing the ring)"
                )
            base = max(ring_rows or rows, rows)
            self.acc = accum_init(
                max(self.config.accum_batches * base, rows),
                self.tag_schema,
                self.meter_schema,
            )
        elif plan == "fold":
            self._fold()
        K = self.config.stats_ring
        if K > 1:
            # the gate state is DEVICE-resident between ring drains —
            # the host span may lag by up to K-1 batches, but the gate
            # each batch sees matches per-batch mode exactly
            sw_arg = self._sw_state[0]
        else:
            sw_arg = jnp.uint32(
                0 if self.start_window is None else self.start_window
            )
        def dispatch_once():
            # the chaos seam fires BEFORE the jitted call, so a retried
            # injected fault never sees a consumed (donated) accumulator
            chaos.maybe_fail(chaos.SITE_DISPATCH)
            return dispatch(self.acc, jnp.int32(self.fill), sw_arg)

        def on_retry(_attempt, _exc):
            self.dispatch_retries += 1

        lin = self.lineage
        d0 = lin.clock() if lin is not None else 0.0
        with self.tracer.span(SPAN_INGEST_DISPATCH):
            # admission-time-only classification: the step donates its
            # accumulator (and sketch plane), so a mid-flight
            # UNAVAILABLE/ABORTED must NOT retry against consumed buffers
            out = retry_call(
                dispatch_once, self.retry_policy, on_retry=on_retry,
                rng=self._retry_rng, classify=is_dispatch_transient,
            )
            if self.sk is not None:
                self.acc, stats_dev, self.sk = out
            else:
                self.acc, stats_dev = out
        if lin is not None:
            # bind the batch's window span (host timestamps) + push the
            # wall stamp the counter-block replay pops — device-side
            # hop times are DERIVED from this pairing, never fetched
            lin.note_dispatch(window_span, d0)
        self.fill += rows

        if K > 1:
            self._cb_ring, self._sw_state = _stats_ring_push(
                self._cb_ring, jnp.int32(self._ring_count), self._sw_state,
                stats_dev,
                interval=self.config.interval, delay=self.config.delay,
            )
            self._ring_count += 1
            if self._ring_count >= K:
                self._drain_stats_ring()
        elif self.config.async_drain:
            # defer only the STATS fetch: the host returns before this
            # batch's compute finishes, and the previous batch's flush
            # (dispatched above, before this append) is fetched below —
            # its transfer overlaps this batch's in-flight append.
            self._pending_stats = stats_dev
        else:
            self._process_stats(stats_dev)
        ready.extend(self._pending_flush)
        self._pending_flush = []
        return self._drain_ready(ready)

    def _settle_ready(self) -> list[FlushedWindow]:
        """Drain whatever finished without appending anything new."""
        ready = self._pending_flush
        self._pending_flush = []
        return self._drain_ready(ready)

    def settle(self) -> list[FlushedWindow]:
        """Fetch every deferred buffer (counter-ring blocks, pending
        async stats, dispatched flushes) so host counters/span are
        consistent with the device — the drain-on-checkpoint rule.
        Returns the windows that were in flight — callers that snapshot
        state (checkpoint.save_window_state) MUST emit them, since
        their rows have already left the stash."""
        self._drain_stats_ring()
        if self._pending_stats is not None:
            stats, self._pending_stats = self._pending_stats, None
            self._process_stats(stats)
        return self._settle_ready()

    # -- device profiling plane (ISSUE 12) --------------------------------
    def device_planes(self) -> dict:
        """Profilable face: every device-resident plane this manager
        owns, by name — the HBM ledger walks these (metadata-only
        `.nbytes`, zero fetches). The enumeration IS the ownership
        contract: a new device buffer added to the manager without a
        plane entry here fails the ledger reconciliation test."""
        planes: dict[str, object] = {
            "stash": self.state,
            "accumulator": self.acc,  # None until the first batch
            "stats_ring": [self._cb_ring, self._sw_state],
            "lanes": [self._fold_lanes_dev, self._zero_lanes,
                      self._snap_lanes_dev],
            # async-drain holds: the deferred stats vector plus every
            # dispatched-but-unfetched flush's device handles (packed
            # rows, sketch pending, tier flushes) — real HBM between
            # ingest calls, up to a full packed flush block in steady
            # async operation (_FlushEntry/TierFlush are plain
            # dataclasses, not pytrees, so the handles list explicitly)
            "pending_flush": [self._pending_stats] + [
                [e.packed, e.total, e.pend, e.pend_win, e.pend_n,
                 e.wide_rows, e.wide_wins]
                + [[tf.packed, tf.total] for tf in e.tiers]
                for e in self._pending_flush
            ],
        }
        if self.sk is not None:
            if _pool_mode(self.sk):
                # pooled sketch memory (ISSUE 20): split the plane so
                # the ledger's per-pool HBM rows show where the bytes
                # live — the compact hot arena, the wide arena, the
                # pending drain buffer, and the routing/counter meta
                sk = self.sk
                planes["sketch_pool_hot"] = [
                    sk.p_hll, sk.p_cms, sk.p_hist, sk.p_tkv, sk.p_tkh,
                    sk.p_tkl, sk.p_tia, sk.p_tib,
                ]
                planes["sketch_pool_wide"] = [
                    sk.hll, sk.cms, sk.hist, sk.tk_votes, sk.tk_hi,
                    sk.tk_lo, sk.tk_ida, sk.tk_idb,
                ]
                planes["sketch_pending"] = [sk.pend, sk.pend_win]
                planes["sketch_meta"] = [
                    sk.win, sk.count, sk.slot_of, sk.wide_close,
                    sk.wide_count, sk.rows, sk.shed, sk.pend_n,
                    sk.pool_spill, sk.pool_promos, sk.promote_fill,
                ]
            else:
                planes["sketch"] = self.sk
        if self.cascade is not None:
            planes["cascade"] = [
                self.cascade.tiers, self.cascade.accs, self.cascade.fills,
                self.cascade.lanes_dev,
            ]
        return planes

    def close(self) -> None:
        """Eager teardown of the profiling registrations (the weakref
        would get there eventually; close() makes 'this manager's HBM
        left the ledger' a synchronous statement, like the r13 cascade
        tier registry)."""
        from ..profiling.ledger import default_ledger

        default_ledger.deregister(self._ledger_src)

    def make_feeder(self, queues, bucket_sizes, config=None, **kw):
        """Wire this manager behind a feeder runtime: METRICS pb frames
        from `queues` decode via ingest/codec.py and coalesce into
        bucket-shaped doc appends (feeder/runtime.WindowManagerFeedSink)."""
        from ..feeder import FeederConfig, FeederRuntime, WindowManagerFeedSink

        return FeederRuntime(
            queues, WindowManagerFeedSink(self, bucket_sizes),
            config or FeederConfig(), **kw,
        )

    def flush_all(self) -> list[FlushedWindow]:
        """Drain every open window (shutdown path)."""
        flushed = self.settle()
        if self.start_window is None:
            return flushed
        self._fold()
        self.state, packed, total = stash_flush_range(
            self.state, np.uint32(0), _U32_MAX, compact=self._flush_compact
        )
        self._pending_flush.append(
            self._make_flush_entry(packed, total, 0, int(_U32_MAX))
        )
        flushed += self._settle_ready()
        for f in flushed:
            self.start_window = max(self.start_window, f.window_idx + 1)
        # the host span just jumped past every drained window; with a
        # counter ring the DEVICE gate must follow, or a straggler
        # ingest re-admits rows into already-emitted windows (the ring
        # is drained — settle() above — so the resync is legal)
        self._sync_device_sw()
        return flushed

    def get_counters(self) -> dict:
        """Countable face (utils/stats.StatsCollector): host ints and the
        device counter-block cache ONLY — no device access, so a ticking
        collector thread can sample mid-ingest without racing a dispatch
        or burning a host sync. `stash_occupancy`/`stash_evictions` are
        as of the last fused append dispatch; the `counters` property
        below fetches the live values when a probe wants them."""
        xla_compiles, xla_compile_us = self.tracer.compile_lanes()
        flush_compiles, flush_compile_us = self.tracer.compile_lanes(FLUSH_SPAN_NAMES)
        return {
            # backend compiles charged to this manager's spans by the
            # process-wide listener (utils/spans), persistent-cache reads
            # included: all of them, and those under flush.drain — a
            # close that compiles per document count shows here, where
            # jit_compiles / jit_retraces watch the fused step alone
            "xla_compiles": xla_compiles,
            "xla_compile_us": xla_compile_us,
            "flush_compiles": flush_compiles,
            "flush_compile_us": flush_compile_us,
            "doc_in": self.total_docs_in,
            "flushed_doc": self.total_flushed,
            "drop_before_window": self.drop_before_window,
            "prereduce_shed": self.aux_count,
            "excess_word_hits": self.excess_word_hits,
            "stash_occupancy": self.stash_occupancy,
            "stash_live_rows_sum": self.stash_live_rows_sum,
            "stash_capacity_rows_sum": self.stash_capacity_rows_sum,
            "stash_evictions": self.stash_evictions,
            "acc_fill": self.fill,  # rows awaiting the next fold
            # device-reported ring fill at last dispatch — must track
            # acc_fill minus the in-flight batch; drift = host/device
            # bookkeeping bug
            "device_ring_fill": self.device_ring_fill,
            # rows the last fold's keyed sort touched (CB_FOLD_ROWS, as
            # of the last fetched block): full-sort mode counts the
            # whole live stash + ring, merge mode only the folded acc
            # rows — the lane the fold-work perf gate watches (ISSUE 5)
            "fold_rows": self.fold_rows,
            "fold_blocks_run_sum": self.fold_blocks_run_sum,
            "fold_blocks_total_sum": self.fold_blocks_total_sum,
            "window_advances": self.n_advances,
            "host_fetches": self.host_fetches,
            "bytes_fetched": self.bytes_fetched,
            "bytes_uploaded": self.bytes_uploaded,
            # the drains' paged row fetch: fetched − live is the
            # over-fetch, under one page per part per drain
            "flush_pages": self.flush_pages,
            "flush_rows_fetched": self.flush_rows_fetched,
            "flush_rows_live": self.flush_rows_live,
            # the drains' host half: exact rows joined into a destination
            # reserved under flush.wait, and the bytes of every host array
            # the drains made after the fetch (reserves whole, fresh join
            # results); the split copies nothing. Every byte a reserve
            # touched or a join copied, and those of them whose pass was
            # divided over the pool's threads (utils/hostpool.py)
            "flush_rows_reserved": self.flush_rows_reserved,
            "flush_host_write_bytes": self.flush_host_write_bytes,
            "flush_host_pass_bytes": self.flush_host_pass_bytes,
            "flush_pooled_bytes": self.flush_pooled_bytes,
            "sketch_blocks_closed": self.sketch_blocks_closed,
            "sketch_bytes_fetched": self.sketch_bytes_fetched,
            "sketch_bytes_live": self.sketch_bytes_live,
            # transient-failure lanes (ISSUE 6): non-zero means the
            # retry policy absorbed device hiccups
            "dispatch_retries": self.dispatch_retries,
            "fetch_retries": self.fetch_retries,
            # feeder-pressure lane + counter-ring occupancy (ISSUE 4);
            # blocks awaiting the 1/K fetch mean host counters may trail
            # the device by up to stats_ring_pending batches
            "feeder_shed": self.feeder_shed,
            "stats_ring_pending": self._ring_count,
            # sketch-plane lanes (ISSUE 8, CB v4): cumulative rows the
            # plane folded / counted-shed as of the last fetched block —
            # sketch_rows > 0 is the CI assertion that sketch updates
            # actually ran inside the fused dispatch
            "sketch_rows": self.sketch_rows,
            "sketch_shed": self.sketch_shed,
            # pooled sketch memory (ISSUE 20, CB v7): spill > 0 means
            # windows wanted a compact pool slot when none was free —
            # counted, never silent; occupancy is the at-dispatch gauge
            "sketch_pool_spill": self.sketch_pool_spill,
            "sketch_pool_occ": self.sketch_pool_occ,
            "sketch_promotions": self.sketch_promotions,
            # rollup-cascade lanes (ISSUE 9, CB v5): cumulative closed
            # child rows the tier folds consumed / tier-stash overflow
            # sheds, as of the last fetched block; plus the host-side
            # tier-window accounting (held > 0 and rising dropped means
            # nobody drains pop_tier_windows)
            "cascade_rows": self.cascade_rows,
            "cascade_shed": self.cascade_shed,
            "tier_windows_held": len(self.tier_flushed),
            "tier_windows_dropped": self.tier_windows_dropped,
            # live read plane (ISSUE 10, CB v6): host-authoritative
            # snapshot accounting plus the device-plane mirrors (the
            # lanes as of the last fetched block — they trail the host
            # ints by at most the in-flight dispatches)
            "snapshot_reads": self.snapshot_reads,
            "snapshot_bytes": self.snapshot_bytes,
            "device_snapshot_reads": self.device_snapshot_reads,
            "device_snapshot_bytes": self.device_snapshot_bytes,
            **(self.cascade.get_counters() if self.cascade is not None else {}),
        }

    @property
    def counters(self) -> dict:
        out = self.get_counters()
        out.update(
            {
                # scalar device reductions fetched on demand — never the
                # full valid plane; live values, unlike the
                # dispatch-time block cache above. Through _fetch: probe
                # syncs must show up in the transfer accounting too.
                "drop_overflow": int(self._fetch(self.state.dropped_overflow)),
                "occupancy": int(
                    self._fetch(jnp.sum(self.state.valid).astype(jnp.int32))
                ),
            }
        )
        return out
