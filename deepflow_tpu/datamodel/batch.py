"""Struct-of-arrays batch containers — host↔device ABI.

`FlowBatch` is the decoded input: one row per accumulated flow interval
(what the reference calls `FlowMeterWithFlow` entering `Collector::collect_l4`,
collector.rs:380). `DocBatch` is the post-fanout stream of candidate
documents: a u32 tag matrix + f32 meter matrix + timestamp + validity mask,
the shape every device kernel consumes.
"""

from __future__ import annotations

import collections
import dataclasses
import weakref
from typing import Mapping

import numpy as np

from .schema import FLOW_METER, TAG_SCHEMA, MeterSchema, TagSchema

# Input columns of a decoded flow record (pre-fanout). Everything u32
# except meters. direction0/1 use Direction values; is_active_host* are
# 0/1 flags (collector.rs:489-499 activity gating).
FLOW_RECORD_TAG_FIELDS: tuple[str, ...] = (
    "timestamp",  # seconds
    "global_thread_id",
    "agent_id",
    "signal_source",
    "is_ipv6",
    "ip0_w0",
    "ip0_w1",
    "ip0_w2",
    "ip0_w3",
    "ip1_w0",
    "ip1_w1",
    "ip1_w2",
    "ip1_w3",
    "mac0_hi",
    "mac0_lo",
    "mac1_hi",
    "mac1_lo",
    "l3_epc_id",
    "l3_epc_id1",
    "gpid0",
    "gpid1",
    "pod_id",
    "protocol",
    "server_port",
    "tap_port",
    "tap_type",
    "l7_protocol",
    "direction0",
    "direction1",
    "is_active_host0",
    "is_active_host1",
    "is_vip0",
    "is_vip1",
    "is_active_service",
    # L7-only fields (AppMeterWithFlow, collector.rs:101-112); zero for L4
    # records.
    "endpoint_hash",
    "biz_type",
    "time_span",
)

# The raw-tag packing plan (fingerprint hot path) must cover exactly
# these columns — a field added here without a width entry would be
# silently dropped from the group-by key, so fail at import instead.
from .code import RAW_TAG_PACK as _RAW_TAG_PACK  # noqa: E402

assert set(_RAW_TAG_PACK.field_names()) == set(FLOW_RECORD_TAG_FIELDS), (
    "RAW_TAG_WIDTHS (datamodel/code.py) out of sync with FLOW_RECORD_TAG_FIELDS"
)


@dataclasses.dataclass
class FlowBatch:
    """Decoded flow records, columnar. tags: [N] u32 per field; meters:
    [N, FLOW_METER.num_fields] f32; valid: [N] bool (padding mask)."""

    tags: dict[str, np.ndarray]
    meters: np.ndarray
    valid: np.ndarray

    @property
    def size(self) -> int:
        return int(self.meters.shape[0])

    @classmethod
    def from_records(cls, records: list[Mapping], meter_schema: MeterSchema = FLOW_METER) -> "FlowBatch":
        """Build a batch from per-flow dicts (test/replay convenience)."""
        n = len(records)
        tags = {f: np.zeros(n, dtype=np.uint32) for f in FLOW_RECORD_TAG_FIELDS}
        meters = np.zeros((n, meter_schema.num_fields), dtype=np.float32)
        for i, r in enumerate(records):
            for f in FLOW_RECORD_TAG_FIELDS:
                if f in r:
                    tags[f][i] = np.uint32(int(r[f]) & 0xFFFFFFFF)
            m = r.get("meter", {})
            for name, v in m.items():
                meters[i, meter_schema.index(name)] = v
        return cls(tags=tags, meters=meters, valid=np.ones(n, dtype=bool))

    def pad_to(self, n: int) -> "FlowBatch":
        """Pad to a static batch size (XLA wants fixed shapes)."""
        cur = self.size
        if cur == n:
            return self
        if cur > n:
            raise ValueError(f"batch of {cur} cannot pad to {n}")
        pad = n - cur
        tags = {k: np.concatenate([v, np.zeros(pad, dtype=v.dtype)]) for k, v in self.tags.items()}
        meters = np.concatenate([self.meters, np.zeros((pad, self.meters.shape[1]), dtype=self.meters.dtype)])
        valid = np.concatenate([self.valid, np.zeros(pad, dtype=bool)])
        return FlowBatch(tags=tags, meters=meters, valid=valid)

    def slice(self, start: int, stop: int) -> "FlowBatch":
        """Row-range view (the feeder splits decoded chunks across
        bucket boundaries; numpy basic slicing keeps this copy-free)."""
        return FlowBatch(
            tags={k: v[start:stop] for k, v in self.tags.items()},
            meters=self.meters[start:stop],
            valid=self.valid[start:stop],
        )

    @classmethod
    def concat(cls, parts: list["FlowBatch"]) -> "FlowBatch":
        """Row-wise concatenation of same-schema batches."""
        if len(parts) == 1:
            return parts[0]
        keys = parts[0].tags.keys()
        return cls(
            tags={k: np.concatenate([p.tags[k] for p in parts]) for k in keys},
            meters=np.concatenate([p.meters for p in parts]),
            valid=np.concatenate([p.valid for p in parts]),
        )


# ---------------------------------------------------------------------------
# staging buffers — a batch written once, in the upload's layout

# the row order of the packed tag matrix every fused step is built with
STAGED_TAG_ORDER: tuple[str, ...] = tuple(sorted(FLOW_RECORD_TAG_FIELDS))
STAGING_RING_LEN = 3  # the feeder holds one staged batch back; one is being written


def _aligned_zeros(shape, dtype) -> np.ndarray:
    """Zeros that start on a 64-byte line: what the CPU backend asks of
    host memory before it aliases it in place of copying, so the CPU
    tests reuse these buffers under the same hazard the chip's
    asynchronous transfer makes."""
    n = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.zeros(n + 64, dtype=np.uint8)
    off = -raw.ctypes.data % 64
    return raw[off : off + n].view(dtype).reshape(shape)


class StagingBuffer:
    """One batch's host memory as the upload takes it: `tag_mat`
    [T, B] u32 with rows in `names` order, `meters` [B, M] f32, `valid`
    [B] bool. `write` appends a chunk's rows, `finish` zeroes what an
    earlier, longer fill left past them; between the two every byte of a
    record is written once. Reused: see StagingRing.settle for when."""

    def __init__(self, bucket: int, names: tuple[str, ...], n_meters: int):
        self.names = names
        self.tag_mat = _aligned_zeros((len(names), bucket), np.uint32)
        self.meters = _aligned_zeros((bucket, n_meters), np.float32)
        self.valid = _aligned_zeros((bucket,), bool)
        self.rows = 0  # rows of the fill under way
        self.n_valid = 0  # of them valid
        self._dirty = 0  # rows an earlier fill left non-zero
        # wire row i (FLOW_RECORD_TAG_FIELDS order) lands in row _from_wire[i]
        self._from_wire = (
            np.array([names.index(f) for f in FLOW_RECORD_TAG_FIELDS])
            if sorted(names) == sorted(FLOW_RECORD_TAG_FIELDS) else None
        )
        self._owner = None  # weakref: the staged batch uploaded from here, until dispatched
        self._done = None  # a device array that is ready once the device has read this memory

    @property
    def bucket(self) -> int:
        return int(self.valid.shape[0])

    @property
    def row_bytes(self) -> int:
        return 4 * self.tag_mat.shape[0] + 4 * self.meters.shape[1] + 1

    def write(self, tags, meters: np.ndarray, valid: np.ndarray | None = None) -> int:
        """Rows [rows, rows + n) ← one chunk: `tags` the frame's [T, n]
        matrix in FLOW_RECORD_TAG_FIELDS order (one assignment through
        the row permutation) or a mapping name → [n] column; `valid`
        None = all of them. Returns the bytes written."""
        n = int(meters.shape[0])
        s, e = self.rows, self.rows + n
        if e > self.bucket:
            raise ValueError(f"batch of {e} cannot pad to {self.bucket}")
        if isinstance(tags, np.ndarray):
            if self._from_wire is None:
                raise ValueError(
                    "a wire-order tag matrix needs a buffer whose rows are "
                    f"FLOW_RECORD_TAG_FIELDS, not {self.names}")
            self.tag_mat[self._from_wire, s:e] = tags
        else:
            for j, k in enumerate(self.names):
                self.tag_mat[j, s:e] = tags[k]
        self.meters[s:e] = meters
        if valid is None:
            self.valid[s:e] = True
            self.n_valid += n
        else:
            self.valid[s:e] = valid
            self.n_valid += int(np.count_nonzero(valid))
        self.rows = e
        return n * self.row_bytes

    def finish(self) -> int:
        """Zero the tail an earlier fill left behind — rows past
        `_dirty` have been zero since the buffer was made. Returns the
        bytes zeroed."""
        s, e = self.rows, max(self.rows, self._dirty)
        if e > s:
            self.tag_mat[:, s:e] = 0
            self.meters[s:e] = 0
            self.valid[s:e] = False
        self._dirty = self.rows
        return (e - s) * self.row_bytes

    def tag_columns(self) -> dict[str, np.ndarray]:
        """name → row view of `tag_mat` (a FlowBatch's `tags`, no copy)."""
        return {k: self.tag_mat[j] for j, k in enumerate(self.names)}

    # -- when the memory may be written again ---------------------------
    def uploaded(self, staged) -> None:
        """`staged` holds the device arrays made from this memory; until
        it is dispatched (or dropped) the buffer is not offered again."""
        self._owner = weakref.ref(staged)
        self._done = None

    def dispatched(self, done) -> None:
        """The step that reads the staged arrays is on its way; `done`
        (an output of that step) is ready once it has run — and then the
        transfer has finished and, where the device array aliases this
        memory (the CPU backend), nothing reads it any more."""
        self._owner = None
        self._done = done

    def held(self) -> bool:
        return self._owner is not None and self._owner() is not None

    def wait(self) -> bool:
        """Block until the device has read this memory → whether it had
        to (the handle was not ready yet)."""
        done, self._done = self._done, None
        if done is None or done.is_ready():
            return False
        done.block_until_ready()
        return True


class StagingRing:
    """The staging buffers of one consumer, a few per (bucket, tag
    names): made on first use, then reused for the life of the process."""

    def __init__(self, n_meters: int):
        self.n_meters = n_meters
        self._rings: dict = {}  # (bucket, names) → deque of buffers, least recently used first
        self.allocated = 0  # buffers ever made
        self.waits = 0  # acquires that found their buffer still in flight

    def acquire(self, bucket: int, names: tuple[str, ...] = STAGED_TAG_ORDER) -> StagingBuffer:
        """The least recently used buffer of this shape, empty: `offer`,
        then `settle`. A writer that times the wait makes the two calls
        itself (the ring keeps no clock and no tracer)."""
        buf = self.offer(bucket, names)
        self.settle(buf)
        return buf

    def offer(self, bucket: int, names: tuple[str, ...] = STAGED_TAG_ORDER) -> StagingBuffer:
        """The least recently used buffer of this shape, which the device
        may still be reading: `settle` it before the first write. One
        whose staged batch is still held undispatched is passed over, and
        the ring grows only if every buffer is."""
        bufs = self._rings.setdefault((bucket, names), collections.deque())
        buf = None
        if len(bufs) >= STAGING_RING_LEN:
            buf = next((b for b in bufs if not b.held()), None)
        if buf is None:
            buf = StagingBuffer(bucket, names, self.n_meters)
            self.allocated += 1
        else:
            bufs.remove(buf)
        bufs.append(buf)
        return buf

    def settle(self, buf: StagingBuffer) -> bool:
        """Empty an offered buffer for its next fill. A buffer is written
        again only when the batch staged from it has been dispatched and
        the device has read it: `wait`, counted when it blocks → whether
        it did."""
        blocked = buf.wait()
        self.waits += blocked
        buf.rows = buf.n_valid = 0
        return blocked


@dataclasses.dataclass
class DocBatch:
    """Candidate documents after tag fanout.

    tags:      [N, TAG_SCHEMA.num_fields] u32
    meters:    [N, meter_schema.num_fields] f32
    timestamp: [N] u32 (seconds)
    valid:     [N] bool

    A batch built from a flushed window (`L4Pipeline._to_docbatch`)
    holds that window's `tags` and `meters` as they left the drain:
    views of one fetched `[rows, 3+T+M]` u32 matrix in the fetch's
    memory order (row-major with a 396 B row stride from the CPU
    backend, column-major from a TPU), never C-contiguous. Index rows
    or columns and assume no strides. The consumer owns them: the
    window manager never writes to that matrix again.
    """

    tags: np.ndarray
    meters: np.ndarray
    timestamp: np.ndarray
    valid: np.ndarray
    tag_schema: TagSchema = TAG_SCHEMA
    meter_schema: MeterSchema = FLOW_METER

    @property
    def size(self) -> int:
        return int(self.tags.shape[0])

    def tag(self, name: str) -> np.ndarray:
        return self.tags[:, self.tag_schema.index(name)]

    def meter(self, name: str) -> np.ndarray:
        return self.meters[:, self.meter_schema.index(name)]

    def to_dicts(self) -> list[dict]:
        """Expand valid rows to python dicts (tests / JSON export)."""
        out = []
        tag_names = self.tag_schema.field_names()
        meter_names = self.meter_schema.field_names()
        for i in range(self.size):
            if not self.valid[i]:
                continue
            out.append(
                {
                    "timestamp": int(self.timestamp[i]),
                    "tag": {n: int(self.tags[i, j]) for j, n in enumerate(tag_names)},
                    "meter": {n: float(self.meters[i, j]) for j, n in enumerate(meter_names)},
                }
            )
        return out
