"""Per-window device-resident sketch plane — the approximate tier of the
windowed pipeline (ISSUE 8).

The exact stash is capacity-bounded: under high-cardinality traffic
(DDoS, scans, per-user flows) it overflows and sheds, which is both a
correctness cliff and the throughput ceiling. This plane keeps, for
every *open window*, a fixed-size approximate summary on device — HLL
registers (distinct clients per service), a count-min plane (per-flow
frequency/bytes), a log-binned latency histogram (t-digest source), and
an invertible top-K sketch (ops/topk.py — heavy flow keys recoverable
from the sketch itself) — updated from the SAME fused jit dispatch as
the exact append, so the shed path degrades *detail*, never *coverage*.

Ring semantics. Open windows span at most R = delay//interval + 2
consecutive indices, so an [R]-slot ring indexed by `window % R` holds
them without aliasing (consecutive windows are distinct mod R). The
fused step closes slots itself: it derives the post-batch span start
(`close_w`, exactly the host's advance rule) and, between folding the
batch's closing-span rows and its new-span rows, moves every slot with
win < close_w into a flat PENDING buffer of packed u32 block rows. The
host drains pending at each window advance, riding the flush drain's
existing fetches (the scalar fetch widens to [2], the packed-row fetch
becomes one concatenated u32 transfer) — the ≤3-fetch budget is
unchanged, gated in CI.

The one coverage exception is counted, never silent: a single batch
whose accepted rows span more than R windows *below* the close bound
(a giant timestamp jump inside one batch) cannot give each of those
already-closing windows its own slot; such rows are dropped from the
sketch tier only (the exact stash still takes them) and counted in the
`shed` lane, which rides the device counter block (CB_SKETCH_SHED).

Closed blocks are host-side `WindowSketchBlock`s: pure-numpy queries
(the shared xp ops math — ops/cms.row_slots, ops/hll.hll_estimate_np),
mergeable across shards (register max / counter add / MJRTY combine),
t-digest export via the histogram→centroid compressor, and the top-K
inversion (candidates from the invertible sketch, estimates from the
same window's count-min plane).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax

from ..ops.cms import cms_expand, row_slots
from ..ops.hll import (
    clz32,
    hll_estimate_np,
    hll_pack_registers,
    hll_unpack_registers_np,
)
from ..ops.histogram import (
    LogHistSpec,
    loghist_bin,
    loghist_coarsen_bin,
    loghist_expand,
)
from ..ops.segment import _use_shared_sort
from ..ops.tdigest import tdigest_compress, tdigest_quantile
from ..ops.topk import (
    _apply_challengers,
    topk_candidates,
    topk_challengers_presorted,
    topk_select,
    topk_tile,
    topk_update,
)

_U32_MAX = np.uint32(0xFFFFFFFF)
SENTINEL_WIN = _U32_MAX


@dataclasses.dataclass(frozen=True)
class PoolConfig:
    """Disaggregated sketch-memory pool (ISSUE 20).

    Instead of one worst-case-sized slab per ring slot, the plane draws
    from a shared device arena: `compact_slots` narrow sub-sketch slots
    (per lane: full-m int8 HLL registers, CMS width/`cms_factor`,
    top-K cols/`topk_factor`, hist bins/`hist_factor`) plus
    `wide_slots` full-width slots. A window opens compact; when the
    CMS-row-0 fill fraction of its slot reaches `promote_fill` the step
    promotes it to a free wide slot via the r12 merge algebra (HLL
    cast = register max against zero, CMS/hist tile-add, top-K bucket
    tile — ops/{hll,cms,histogram,topk}.py document per-lane
    soundness). Pool exhaustion spills rows from the sketch tier only,
    counted (CB_SKETCH_POOL_SPILL), never silently."""

    compact_slots: int = 3
    wide_slots: int = 1
    cms_factor: int = 8
    topk_factor: int = 4
    hist_factor: int = 8
    promote_fill: float = 0.5

    def meta(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_meta(cls, m: dict) -> "PoolConfig":
        return cls(**m)


def _check_pool(cfg: "SketchConfig") -> None:
    """Pool/ring geometry validation (ISSUE 20 satellite): every way a
    pooled lane could fail to hold — or fail to PROMOTE into — the wide
    lane raises here, naming the lane and both widths, instead of
    surfacing as a shape error inside a jitted step or shard_map body."""
    p = cfg.pool
    if p.compact_slots < 1:
        raise ValueError(
            f"pool compact_slots must be ≥ 1, got {p.compact_slots}"
        )
    if p.wide_slots < 1:
        raise ValueError(
            f"pool wide_slots={p.wide_slots}: the promotion target arena "
            "is empty — a saturated compact slot would have no wide slot "
            "to promote into"
        )
    if cfg.cms_depth < 1:
        raise ValueError(
            "pooled sketch memory requires cms_depth ≥ 1: the promotion "
            "saturation estimator reads the fill of CMS row 0 "
            f"(got cms_depth={cfg.cms_depth})"
        )
    if cfg.hll_m % 4:
        raise ValueError(
            f"pooled HLL packs 4 int8 registers per u32 word; hll_m="
            f"{cfg.hll_m} (precision {cfg.hll_precision}) is not "
            "divisible by 4"
        )
    if not (0.0 < p.promote_fill <= 1.0):
        raise ValueError(
            f"pool promote_fill must be in (0, 1], got {p.promote_fill}"
        )
    lanes = [("cms", p.cms_factor, cfg.cms_width),
             ("hist", p.hist_factor, cfg.hist.bins)]
    if cfg.topk_rows:
        lanes.append(("topk", p.topk_factor, cfg.topk_cols))
    for lane, factor, width in lanes:
        if factor < 1 or (factor & (factor - 1)):
            raise ValueError(
                f"pool {lane}_factor must be a power of two ≥ 1, got "
                f"{factor}"
            )
        if width % factor or width // factor < 1:
            raise ValueError(
                f"pool geometry cannot promote the {lane} lane: factor "
                f"{factor} does not divide the wide width {width} into a "
                f"non-empty compact lane (compact width would be "
                f"{width // factor})"
            )


@dataclasses.dataclass(frozen=True)
class SketchConfig:
    """Shapes and error knobs of the per-window plane.

    hll_precision=14 meets the <1% north-star cardinality bound
    (~0.81% standard error); the defaults here are sized for the
    many-windows-resident case."""

    num_groups: int = 16  # service rows (HLL + histogram group axis)
    hll_precision: int = 12
    cms_depth: int = 4
    cms_width: int = 1 << 12
    hist: LogHistSpec = LogHistSpec(bins=256, vmin=1.0, gamma=1.04)
    topk_rows: int = 2  # 0 disables the top-K lane
    topk_cols: int = 1 << 9
    pending: int = 16  # closed-block rows buffered between host drains
    pool: PoolConfig | None = None  # None → classic per-slot slabs

    def __post_init__(self):
        if self.cms_width & (self.cms_width - 1):
            raise ValueError("cms_width must be a power of two")
        if self.topk_rows and self.topk_cols & (self.topk_cols - 1):
            raise ValueError("topk_cols must be a power of two")
        if self.pool is not None:
            _check_pool(self)

    @property
    def hll_m(self) -> int:
        return 1 << self.hll_precision

    # -- pooled (compact) lane widths (valid only with pool set) --------
    @property
    def pool_cms_width(self) -> int:
        return self.cms_width // self.pool.cms_factor

    @property
    def pool_hist_bins(self) -> int:
        return self.hist.bins // self.pool.hist_factor

    @property
    def pool_topk_cols(self) -> int:
        return self.topk_cols // self.pool.topk_factor if self.topk_rows else 0

    @property
    def block_width(self) -> int:
        """u32 words per packed closed-window block row: the n_updates
        word, then hll / cms / hist / 5 top-K lanes, flattened in that
        order (the layout contract between `_flatten_open`,
        `WindowSketchBlock.from_row` and checkpoint v4)."""
        g = self.num_groups
        return (
            1
            + g * self.hll_m
            + self.cms_depth * self.cms_width
            + g * self.hist.bins
            + 5 * self.topk_rows * self.topk_cols
        )

    @property
    def compact_block_width(self) -> int:
        """u32 words per packed COMPACT pool block row (pool mode only):
        the n_updates word, then packed-i8 hll (4 registers/word), then
        cms / hist / 5 top-K lanes at the pooled widths — same lane
        order as `block_width`. Strictly narrower than `block_width`
        (the HLL lane alone shrinks 4×), which is what lets
        `unpack_drained` dispatch on the row width."""
        g = self.num_groups
        return (
            1
            + g * self.hll_m // 4
            + self.cms_depth * self.pool_cms_width
            + g * self.pool_hist_bins
            + 5 * self.topk_rows * self.pool_topk_cols
        )

    def meta(self) -> dict:
        """JSON-able form for checkpoint meta (v4; "pool" since v6)."""
        return {
            "num_groups": self.num_groups,
            "hll_precision": self.hll_precision,
            "cms_depth": self.cms_depth,
            "cms_width": self.cms_width,
            "hist_bins": self.hist.bins,
            "hist_vmin": self.hist.vmin,
            "hist_gamma": self.hist.gamma,
            "topk_rows": self.topk_rows,
            "topk_cols": self.topk_cols,
            "pending": self.pending,
            "pool": None if self.pool is None else self.pool.meta(),
        }

    @classmethod
    def from_meta(cls, m: dict) -> "SketchConfig":
        # v5 and older meta has no "pool" key → slab plane, so old
        # checkpoints compare equal against slab-configured managers.
        return cls(
            num_groups=m["num_groups"],
            hll_precision=m["hll_precision"],
            cms_depth=m["cms_depth"],
            cms_width=m["cms_width"],
            hist=LogHistSpec(
                bins=m["hist_bins"], vmin=m["hist_vmin"], gamma=m["hist_gamma"]
            ),
            topk_rows=m["topk_rows"],
            topk_cols=m["topk_cols"],
            pending=m["pending"],
            pool=PoolConfig.from_meta(m["pool"]) if m.get("pool") else None,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SketchState:
    """Device-resident plane (leading mesh dim when sharded).

    Open ring: `win[R]` (absolute window per slot, SENTINEL=empty) +
    per-slot planes. Pending: flat packed closed blocks awaiting the
    host drain. `rows`/`shed` are the cumulative counter-block lanes."""

    win: jnp.ndarray  # [R] u32
    count: jnp.ndarray  # [R] u32 rows folded per open slot
    hll: jnp.ndarray  # [R, G, m] i32 (pool mode: [Pw, G, m] wide arena)
    cms: jnp.ndarray  # [R, D, W] i32 (pool mode: [Pw, D, W])
    hist: jnp.ndarray  # [R, G, B] i32 (pool mode: [Pw, G, B])
    tk_votes: jnp.ndarray  # [R, d, C] i32 (pool mode: [Pw, d, C])
    tk_hi: jnp.ndarray  # [R, d, C] u32
    tk_lo: jnp.ndarray  # [R, d, C] u32
    tk_ida: jnp.ndarray  # [R, d, C] u32
    tk_idb: jnp.ndarray  # [R, d, C] u32
    pend: jnp.ndarray  # [P, WIDE] u32 packed closed blocks ([P, CW] pooled)
    pend_win: jnp.ndarray  # [P] u32
    pend_n: jnp.ndarray  # scalar i32
    rows: jnp.ndarray  # scalar u32 — CB_SKETCH_ROWS source
    shed: jnp.ndarray  # scalar u32 — CB_SKETCH_SHED source
    # -- pooled sketch-memory arena (ISSUE 20; all zero-size in slab
    # mode so the slab pytree/step stay bit-identical) ------------------
    slot_of: jnp.ndarray  # [R] i32 pool slot per ring slot: -1 none,
    #                       0..Pc-1 compact arena, Pc+j wide slot j.
    #                       Invariant: slot_of == -1  ⇒  win == SENTINEL
    #                       (spilled rows never claim win or count).
    p_hll: jnp.ndarray  # [Pc, G, m] i8 — full m registers (bit-exact)
    p_cms: jnp.ndarray  # [Pc, D, Wc] i32
    p_hist: jnp.ndarray  # [Pc, G, Bc] i32
    p_tkv: jnp.ndarray  # [Pc, d, Cc] i32
    p_tkh: jnp.ndarray  # [Pc, d, Cc] u32
    p_tkl: jnp.ndarray  # [Pc, d, Cc] u32
    p_tia: jnp.ndarray  # [Pc, d, Cc] u32
    p_tib: jnp.ndarray  # [Pc, d, Cc] u32
    wide_close: jnp.ndarray  # [Pw] u32 closed-awaiting-drain window id
    #                          (SENTINEL = open or free); closed wide
    #                          slots drain IN PLACE — no pend copy.
    wide_count: jnp.ndarray  # [Pw] u32 row count at close
    pool_spill: jnp.ndarray  # scalar u32 — CB_SKETCH_POOL_SPILL source
    pool_promos: jnp.ndarray  # scalar u32 — CB_SKETCH_PROMOTIONS source
    promote_fill: jnp.ndarray  # scalar f32 saturation threshold (from
    #                            PoolConfig at init; 0 in slab mode)

    @property
    def ring(self) -> int:
        return self.win.shape[-1]


def _pool_mode(sk: SketchState) -> bool:
    """Trace-time mode switch: the pool fields are zero-size iff the
    plane was built without a PoolConfig. The trailing dim carries the
    signal so a [D]-leading sharded state answers the same way."""
    return sk.slot_of.shape[-1] > 0


def sketch_init(cfg: SketchConfig, ring: int) -> SketchState:
    g, m = cfg.num_groups, cfg.hll_m
    pool = cfg.pool
    if pool is None:
        pc, pw = 0, 0
        wc, bc, cc = cfg.cms_width, cfg.hist.bins, cfg.topk_cols
        slot_r, arena_rows = 0, ring
        pend_w = cfg.block_width
        fill = 0.0
    else:
        pc, pw = pool.compact_slots, pool.wide_slots
        wc, bc, cc = cfg.pool_cms_width, cfg.pool_hist_bins, cfg.pool_topk_cols
        slot_r, arena_rows = ring, pw
        pend_w = cfg.compact_block_width
        fill = pool.promote_fill
    return SketchState(
        win=jnp.full((ring,), SENTINEL_WIN, dtype=jnp.uint32),
        count=jnp.zeros((ring,), jnp.uint32),
        hll=jnp.zeros((arena_rows, g, m), jnp.int32),
        cms=jnp.zeros((arena_rows, cfg.cms_depth, cfg.cms_width), jnp.int32),
        hist=jnp.zeros((arena_rows, g, cfg.hist.bins), jnp.int32),
        tk_votes=jnp.zeros((arena_rows, cfg.topk_rows, cfg.topk_cols), jnp.int32),
        tk_hi=jnp.zeros((arena_rows, cfg.topk_rows, cfg.topk_cols), jnp.uint32),
        tk_lo=jnp.zeros((arena_rows, cfg.topk_rows, cfg.topk_cols), jnp.uint32),
        tk_ida=jnp.zeros((arena_rows, cfg.topk_rows, cfg.topk_cols), jnp.uint32),
        tk_idb=jnp.zeros((arena_rows, cfg.topk_rows, cfg.topk_cols), jnp.uint32),
        pend=jnp.zeros((cfg.pending, pend_w), jnp.uint32),
        pend_win=jnp.full((cfg.pending,), SENTINEL_WIN, dtype=jnp.uint32),
        pend_n=jnp.zeros((), jnp.int32),
        rows=jnp.zeros((), jnp.uint32),
        shed=jnp.zeros((), jnp.uint32),
        slot_of=jnp.full((slot_r,), -1, dtype=jnp.int32),
        p_hll=jnp.zeros((pc, g, m), jnp.int8),
        p_cms=jnp.zeros((pc, cfg.cms_depth, wc), jnp.int32),
        p_hist=jnp.zeros((pc, g, bc), jnp.int32),
        p_tkv=jnp.zeros((pc, cfg.topk_rows, cc), jnp.int32),
        p_tkh=jnp.zeros((pc, cfg.topk_rows, cc), jnp.uint32),
        p_tkl=jnp.zeros((pc, cfg.topk_rows, cc), jnp.uint32),
        p_tia=jnp.zeros((pc, cfg.topk_rows, cc), jnp.uint32),
        p_tib=jnp.zeros((pc, cfg.topk_rows, cc), jnp.uint32),
        wide_close=jnp.full((pw,), SENTINEL_WIN, dtype=jnp.uint32),
        wide_count=jnp.zeros((pw,), jnp.uint32),
        pool_spill=jnp.zeros((), jnp.uint32),
        pool_promos=jnp.zeros((), jnp.uint32),
        promote_fill=jnp.asarray(fill, jnp.float32),
    )


# ---------------------------------------------------------------------------
# device side (traced helpers — callers fuse these into jitted steps)


def _flatten_compact(sk: SketchState) -> jnp.ndarray:
    """Pool mode: [R, CW] u32 packed compact block rows, layout per
    SketchConfig.compact_block_width. Each ring slot gathers its compact
    arena slot via `slot_of`; slots without a compact allocation (none,
    or promoted wide) come back all-zero."""
    r = sk.ring
    pc = sk.p_hll.shape[0]
    isc = (sk.slot_of >= 0) & (sk.slot_of < pc)
    cp = jnp.clip(sk.slot_of, 0, pc - 1)
    u = lambda x: x[cp].reshape(r, -1).astype(jnp.uint32)
    row = jnp.concatenate(
        [
            jnp.where(isc, sk.count, 0)[:, None].astype(jnp.uint32),
            hll_pack_registers(sk.p_hll[cp]).reshape(r, -1),
            u(sk.p_cms),
            u(sk.p_hist),
            u(sk.p_tkv),
            u(sk.p_tkh),
            u(sk.p_tkl),
            u(sk.p_tia),
            u(sk.p_tib),
        ],
        axis=1,
    )
    return jnp.where(isc[:, None], row, 0)


def _flatten_wide_arena(sk: SketchState, counts) -> jnp.ndarray:
    """[Pw, WIDE] u32 packed rows of the wide arena itself (row j = wide
    slot j), with the given per-slot count word."""
    pw = sk.hll.shape[0]
    u = lambda x: x.reshape(pw, -1).astype(jnp.uint32)
    return jnp.concatenate(
        [
            counts[:, None].astype(jnp.uint32),
            u(sk.hll),
            u(sk.cms),
            u(sk.hist),
            u(sk.tk_votes),
            u(sk.tk_hi),
            u(sk.tk_lo),
            u(sk.tk_ida),
            u(sk.tk_idb),
        ],
        axis=1,
    )


def _flatten_wide_open(sk: SketchState) -> jnp.ndarray:
    """Pool mode: [R, WIDE] u32 — each ring slot's wide-arena view
    (zero unless the slot was promoted)."""
    r = sk.ring
    pc = sk.p_hll.shape[0]
    pw = sk.hll.shape[0]
    isw = sk.slot_of >= pc
    wp = jnp.clip(sk.slot_of - pc, 0, pw - 1)
    packed = _flatten_wide_arena(sk, jnp.zeros((pw,), jnp.uint32))
    row = packed[wp]
    row = row.at[:, 0].set(jnp.where(isw, sk.count, 0).astype(jnp.uint32))
    return jnp.where(isw[:, None], row, 0)


def _flatten_open(sk: SketchState) -> jnp.ndarray:
    """Slab mode: [R, WIDE] u32 packed block rows, layout per
    SketchConfig.block_width. Pool mode (snapshot path): [R, CW + WIDE]
    — compact part ‖ wide part per ring slot; for any live slot exactly
    one part carries a nonzero count word (allocated slots always have
    count ≥ 1), which is how the host picks a side."""
    if _pool_mode(sk):
        return jnp.concatenate(
            [_flatten_compact(sk), _flatten_wide_open(sk)], axis=1
        )
    r = sk.ring
    u = lambda x: x.reshape(r, -1).astype(jnp.uint32)
    return jnp.concatenate(
        [
            sk.count[:, None].astype(jnp.uint32),
            u(sk.hll),
            u(sk.cms),
            u(sk.hist),
            u(sk.tk_votes),
            u(sk.tk_hi),
            u(sk.tk_lo),
            u(sk.tk_ida),
            u(sk.tk_idb),
        ],
        axis=1,
    )


def sketch_close(sk: SketchState, close_w) -> SketchState:
    """Move every open slot with win < close_w out of the ring and reset
    it. Slab mode: the slot's slab flattens into the pending buffer;
    pending overflow drops the block (never corrupts a neighbour) and
    counts the lost rows into `shed`.

    Pool mode: a closing COMPACT slot flattens its (narrow) block into
    the same pending buffer; a closing WIDE slot is merely *marked*
    closed (`wide_close[j] = win`, `wide_count[j] = count`) and drains
    in place at the next `sketch_drain` — the promoted window never
    pays a full-width copy, and a wide slot stays unavailable for
    reallocation until drained. Either way the ring lanes reset and the
    pool slot is freed/zeroed for reuse. Traced; the flatten+scatter
    body runs under a `lax.cond` so the (frequent) no-close batches
    skip the full-plane copy."""
    close_w = jnp.asarray(close_w, jnp.uint32)
    r = sk.ring
    p = sk.pend.shape[0]
    close = (sk.win != jnp.uint32(SENTINEL_WIN)) & (sk.win < close_w)

    def rst(x, fill):
        m = close.reshape((r,) + (1,) * (x.ndim - 1))
        return jnp.where(m, jnp.asarray(fill, x.dtype), x)

    def do_close(sk: SketchState) -> SketchState:
        n_close = jnp.sum(close.astype(jnp.int32))
        pos = sk.pend_n + jnp.cumsum(close.astype(jnp.int32)) - 1
        pos = jnp.where(close, pos, p)  # non-closing rows → dropped
        overflow = close & (pos >= p)
        pos = jnp.minimum(pos, p)
        blocks = _flatten_open(sk)
        pend = sk.pend.at[pos].set(blocks, mode="drop")
        pend_win = sk.pend_win.at[pos].set(sk.win, mode="drop")
        shed = sk.shed + jnp.sum(jnp.where(overflow, sk.count, 0)).astype(
            jnp.uint32
        )

        return dataclasses.replace(
            sk,
            win=rst(sk.win, SENTINEL_WIN),
            count=rst(sk.count, 0),
            hll=rst(sk.hll, 0),
            cms=rst(sk.cms, 0),
            hist=rst(sk.hist, 0),
            tk_votes=rst(sk.tk_votes, 0),
            tk_hi=rst(sk.tk_hi, 0),
            tk_lo=rst(sk.tk_lo, 0),
            tk_ida=rst(sk.tk_ida, 0),
            tk_idb=rst(sk.tk_idb, 0),
            pend=pend,
            pend_win=pend_win,
            pend_n=jnp.minimum(sk.pend_n + n_close, p),
            shed=shed,
        )

    def do_close_pool(sk: SketchState) -> SketchState:
        pc = sk.p_hll.shape[0]
        pw = sk.hll.shape[0]
        isc = (sk.slot_of >= 0) & (sk.slot_of < pc)
        c_close = close & isc
        w_close = close & (sk.slot_of >= pc)
        # compact closes → pending buffer (narrow rows)
        n_close = jnp.sum(c_close.astype(jnp.int32))
        pos = sk.pend_n + jnp.cumsum(c_close.astype(jnp.int32)) - 1
        pos = jnp.where(c_close, pos, p)
        overflow = c_close & (pos >= p)
        pos = jnp.minimum(pos, p)
        blocks = _flatten_compact(sk)
        pend = sk.pend.at[pos].set(blocks, mode="drop")
        pend_win = sk.pend_win.at[pos].set(sk.win, mode="drop")
        shed = sk.shed + jnp.sum(jnp.where(overflow, sk.count, 0)).astype(
            jnp.uint32
        )
        # wide closes → marked in place, drained by sketch_drain
        wix = jnp.where(w_close, sk.slot_of - pc, pw)
        wide_close = sk.wide_close.at[wix].set(sk.win, mode="drop")
        wide_count = sk.wide_count.at[wix].set(sk.count, mode="drop")
        # zero + free the closed compact arena slots (an overflow-shed
        # block is dropped but its arena slot is still reclaimed)
        cz = (
            jnp.zeros((pc,), bool)
            .at[jnp.where(c_close, sk.slot_of, pc)]
            .max(jnp.ones((r,), bool), mode="drop")
        )

        def rstc(x):
            m = cz.reshape((pc,) + (1,) * (x.ndim - 1))
            return jnp.where(m, jnp.asarray(0, x.dtype), x)

        return dataclasses.replace(
            sk,
            win=rst(sk.win, SENTINEL_WIN),
            count=rst(sk.count, 0),
            slot_of=jnp.where(close, jnp.int32(-1), sk.slot_of),
            p_hll=rstc(sk.p_hll),
            p_cms=rstc(sk.p_cms),
            p_hist=rstc(sk.p_hist),
            p_tkv=rstc(sk.p_tkv),
            p_tkh=rstc(sk.p_tkh),
            p_tkl=rstc(sk.p_tkl),
            p_tia=rstc(sk.p_tia),
            p_tib=rstc(sk.p_tib),
            pend=pend,
            pend_win=pend_win,
            pend_n=jnp.minimum(sk.pend_n + n_close, p),
            wide_close=wide_close,
            wide_count=wide_count,
            shed=shed,
        )

    body = do_close_pool if _pool_mode(sk) else do_close
    return lax.cond(jnp.any(close), body, lambda s: s, sk)


def _pool_alloc(sk: SketchState, mask, slot):
    """Claim free COMPACT pool slots for this phase's unallocated ring
    slots (every window opens compact; widening is `_maybe_promote`'s
    job). Fully vectorized rank-matching: the i-th needy ring slot (ring
    order — deterministic) takes the i-th free compact slot; needs past
    the free count stay unallocated, and the caller counts their rows
    into `pool_spill`. Returns (state, alloc_ok[R])."""
    r = sk.ring
    pc = sk.p_hll.shape[0]
    gslot = jnp.where(mask, slot, r)
    touched = (
        jnp.zeros((r,), jnp.int32)
        .at[gslot]
        .max(mask.astype(jnp.int32), mode="drop")
        > 0
    )
    need = touched & (sk.slot_of < 0)
    occ = (
        jnp.zeros((pc,), jnp.int32)
        .at[jnp.where((sk.slot_of >= 0) & (sk.slot_of < pc), sk.slot_of, pc)]
        .max(jnp.ones((r,), jnp.int32), mode="drop")
        > 0
    )
    free = ~occ
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
    n_free = jnp.sum(free.astype(jnp.int32))
    # rank → compact slot id (only the first R free slots can be taken —
    # at most R ring slots exist to take them)
    table = (
        jnp.zeros((r,), jnp.int32)
        .at[jnp.where(free & (free_rank < r), free_rank, r)]
        .set(jnp.arange(pc, dtype=jnp.int32), mode="drop")
    )
    need_rank = jnp.cumsum(need.astype(jnp.int32)) - 1
    got = need & (need_rank < n_free)
    slot_of = jnp.where(got, table[jnp.clip(need_rank, 0, r - 1)], sk.slot_of)
    return dataclasses.replace(sk, slot_of=slot_of), slot_of >= 0


def _scatter_rows_pool(
    sk: SketchState,
    mask,
    win,
    count,
    gid,
    reg,
    rho,
    w,
    b,
    rtt_valid,
    key_hi,
    key_lo,
    weight,
    id_a,
    id_b,
    pslot,
    is_c,
    is_w,
    c_ix,
    w_ix,
    presorted,
) -> SketchState:
    """Pool-mode arena scatters for one phase (`_scatter_rows` computed
    the routing: `pslot` = pool slot per row, `is_c`/`is_w` the arena
    split, `c_ix`/`w_ix` the arena-local indices with OOB sentinels).
    Every lane scatters twice — once per arena — with the other arena's
    rows dropped by out-of-range indices, so each row folds into exactly
    the arena its window lives in. Within a phase a pool slot holds
    exactly one window (slot_of is a per-ring-slot map and the phase
    span is alias-free), so the per-arena folds keep the slab path's
    bit-exactness arguments intact at the pooled widths."""
    pc = sk.p_hll.shape[0]
    pw = sk.hll.shape[0]
    d_cms, w_cms = sk.cms.shape[1], sk.cms.shape[2]
    wc = sk.p_cms.shape[2]
    d_tk = sk.tk_votes.shape[1]

    # HLL — compact keeps the FULL m registers in int8 (rho ≤ 33), so
    # the compact fold is bit-identical to a wide fold of the same rows
    hll = sk.hll.at[w_ix, gid, reg].max(rho, mode="drop")
    p_hll = sk.p_hll.at[c_ix, gid, reg].max(
        rho.astype(jnp.int8), mode="drop"
    )

    # histogram — compact bin derives from the already-computed wide
    # bin by exact integer division (ops/histogram.loghist_coarsen_bin)
    cb = loghist_coarsen_bin(b, sk.hist.shape[2] // sk.p_hist.shape[2])
    hist = sk.hist.at[jnp.where(is_w & rtt_valid, w_ix, pw), gid, b].add(
        1, mode="drop"
    )
    p_hist = sk.p_hist.at[jnp.where(is_c & rtt_valid, c_ix, pc), gid, cb].add(
        1, mode="drop"
    )

    upd = dict(
        win=win, count=count, hll=hll, p_hll=p_hll, hist=hist, p_hist=p_hist
    )

    if presorted is None:
        rs = row_slots(key_hi, key_lo, d_cms, w_cms)  # [D, N]
        flat = w_ix[None, :].astype(jnp.int32) * (d_cms * w_cms) + rs
        upd["cms"] = (
            sk.cms.reshape(-1)
            .at[flat.reshape(-1)]
            .add(jnp.broadcast_to(w[None, :], flat.shape).reshape(-1),
                 mode="drop")
            .reshape(pw, d_cms, w_cms)
        )
        rs_c = row_slots(key_hi, key_lo, d_cms, wc)
        flat_c = c_ix[None, :].astype(jnp.int32) * (d_cms * wc) + rs_c
        upd["p_cms"] = (
            sk.p_cms.reshape(-1)
            .at[flat_c.reshape(-1)]
            .add(jnp.broadcast_to(w[None, :], flat_c.shape).reshape(-1),
                 mode="drop")
            .reshape(pc, d_cms, wc)
        )
        if d_tk:
            lanes = (sk.tk_votes, sk.tk_hi, sk.tk_lo, sk.tk_ida, sk.tk_idb)
            tkv, tkh, tkl, tia, tib = topk_update(
                lanes, jnp.where(is_w, pslot - pc, -1),
                key_hi, key_lo, id_a, id_b, weight, is_w,
            )
            p_lanes = (sk.p_tkv, sk.p_tkh, sk.p_tkl, sk.p_tia, sk.p_tib)
            pv, ph, pl, pa, pb = topk_update(
                p_lanes, jnp.where(is_c, pslot, -1),
                key_hi, key_lo, id_a, id_b, weight, is_c,
            )
            upd.update(
                tk_votes=tkv, tk_hi=tkh, tk_lo=tkl, tk_ida=tia, tk_idb=tib,
                p_tkv=pv, p_tkh=ph, p_tkl=pl, p_tia=pa, p_tib=pb,
            )
        return dataclasses.replace(sk, **upd)

    # -- shared-sort path: route the sorted order through the arenas --
    n = mask.shape[0]
    s_win, s_hi, s_lo, s_pos, head, run_id = presorted
    r = sk.ring
    s_slot = (s_win % jnp.uint32(r)).astype(jnp.int32)
    s_mask = mask[s_pos]
    s_w = w[s_pos]
    run_w = jax.ops.segment_sum(s_w, run_id, num_segments=n)
    rw = run_w[run_id]
    w_head = jnp.where(head, rw, 0)
    s_ia = jnp.asarray(id_a, jnp.uint32)[s_pos]
    s_ib = jnp.asarray(id_b, jnp.uint32)[s_pos]
    # a run is one (window, key): the whole run lives in ONE arena, so
    # arena routing by the run's window keeps head-add dedup intact
    s_pslot = jnp.take(sk.slot_of, s_slot)
    s_isc = s_mask & (s_pslot >= 0) & (s_pslot < pc)
    s_isw = s_mask & (s_pslot >= pc)
    s_cix = jnp.where(s_isc, s_pslot, pc)
    s_wix = jnp.where(s_isw, s_pslot - pc, pw)

    rs = row_slots(s_hi, s_lo, d_cms, w_cms)
    flat = s_wix[None, :].astype(jnp.int32) * (d_cms * w_cms) + rs
    upd["cms"] = (
        sk.cms.reshape(-1)
        .at[flat.reshape(-1)]
        .add(jnp.broadcast_to(w_head[None, :], flat.shape).reshape(-1),
             mode="drop")
        .reshape(pw, d_cms, w_cms)
    )
    rs_c = row_slots(s_hi, s_lo, d_cms, wc)
    flat_c = s_cix[None, :].astype(jnp.int32) * (d_cms * wc) + rs_c
    upd["p_cms"] = (
        sk.p_cms.reshape(-1)
        .at[flat_c.reshape(-1)]
        .add(jnp.broadcast_to(w_head[None, :], flat_c.shape).reshape(-1),
             mode="drop")
        .reshape(pc, d_cms, wc)
    )
    if d_tk:
        lanes = (sk.tk_votes, sk.tk_hi, sk.tk_lo, sk.tk_ida, sk.tk_idb)
        ch_w = topk_challengers_presorted(
            jnp.where(s_isw, s_pslot - pc, 0), s_hi, s_lo, s_ia, s_ib,
            rw, s_isw, pw, d_tk, sk.tk_votes.shape[2],
        )
        tkv, tkh, tkl, tia, tib = _apply_challengers(lanes, ch_w)
        p_lanes = (sk.p_tkv, sk.p_tkh, sk.p_tkl, sk.p_tia, sk.p_tib)
        ch_c = topk_challengers_presorted(
            jnp.where(s_isc, s_pslot, 0), s_hi, s_lo, s_ia, s_ib,
            rw, s_isc, pc, d_tk, sk.p_tkv.shape[2],
        )
        pv, ph, pl, pa, pb = _apply_challengers(p_lanes, ch_c)
        upd.update(
            tk_votes=tkv, tk_hi=tkh, tk_lo=tkl, tk_ida=tia, tk_idb=tib,
            p_tkv=pv, p_tkh=ph, p_tkl=pl, p_tia=pa, p_tib=pb,
        )
    return dataclasses.replace(sk, **upd)


def _scatter_rows(
    sk: SketchState,
    spec: LogHistSpec,
    mask,
    window,
    group,
    client_hi,
    client_lo,
    key_hi,
    key_lo,
    weight,
    rtt,
    rtt_valid,
    id_a,
    id_b,
    presorted=None,
) -> SketchState:
    """Fold one phase's rows into their ring slots (claiming empties).
    Callers guarantee the phase's window span is < R wide, so slots are
    collision-free by construction (consecutive windows ≡ distinct
    mod R).

    With `presorted` (the batch's ONE shared (window, key_hi, key_lo)
    sort from `sketch_plane_step` — ISSUE 17), the count-min and top-K
    lanes consume the shared order instead of sorting again: per-(window,
    key) run weights are summed once and reused as the count-min
    run-dedup weights (one add per run head instead of per row — adds
    commute, totals bit-identical) AND as the top-K challenger weights
    (`topk_challengers_presorted`, zero fresh sorts). The per-row lanes
    whose folds are idempotent or count-shaped (win claim, count, HLL
    register max, histogram) stay on the original row order — a run
    spans one flow key, not one client, so they cannot ride the run
    dedup."""
    r = sk.ring
    g, m = sk.hll.shape[1], sk.hll.shape[2]
    d_cms, w_cms = sk.cms.shape[1], sk.cms.shape[2]
    window = jnp.asarray(window, jnp.uint32)
    slot = (window % jnp.uint32(r)).astype(jnp.int32)
    gid = (jnp.asarray(group).astype(jnp.int32)) % g

    pool = _pool_mode(sk)
    if pool:
        # seat this phase's new windows in the compact arena; rows of
        # windows an exhausted pool cannot seat are masked out HERE, so
        # they never claim win/count (invariant: slot_of == -1 ⇒ win ==
        # SENTINEL) and are counted exactly once into pool_spill.
        sk, alloc_ok = _pool_alloc(sk, mask, slot)
        row_ok = mask & jnp.take(alloc_ok, slot)
        sk = dataclasses.replace(
            sk,
            pool_spill=sk.pool_spill
            + jnp.sum(mask & ~row_ok).astype(jnp.uint32),
        )
        mask = row_ok
        pc = sk.p_hll.shape[0]
        pw = sk.hll.shape[0]
        wc = sk.p_cms.shape[2]
        pslot = jnp.take(sk.slot_of, slot)
        is_c = mask & (pslot >= 0) & (pslot < pc)
        is_w = mask & (pslot >= pc)
        c_ix = jnp.where(is_c, pslot, pc)  # OOB → dropped
        w_ix = jnp.where(is_w, pslot - pc, pw)
    gslot = jnp.where(mask, slot, r)

    win = sk.win.at[gslot].min(window, mode="drop")  # claim (SENTINEL > any)
    count = sk.count.at[gslot].add(1, mode="drop")

    reg = (jnp.asarray(client_lo, jnp.uint32) & jnp.uint32(m - 1)).astype(jnp.int32)
    rho = (clz32(client_hi) + 1).astype(jnp.int32)

    w = jnp.where(mask, jnp.asarray(weight).astype(jnp.int32), 0)

    b = loghist_bin(rtt, spec)

    lanes = (sk.tk_votes, sk.tk_hi, sk.tk_lo, sk.tk_ida, sk.tk_idb)
    d_tk = sk.tk_votes.shape[1]

    if pool:
        return _scatter_rows_pool(
            sk, mask, win, count, gid, reg, rho, w, b, rtt_valid,
            key_hi, key_lo, weight, id_a, id_b,
            pslot, is_c, is_w, c_ix, w_ix, presorted,
        )

    hslot = jnp.where(mask & rtt_valid, slot, r)
    hist = sk.hist.at[hslot, gid, b].add(1, mode="drop")

    if presorted is None:
        # multi-sort oracle: per-row CMS scatter + a fresh 3-key sort
        # per top-K hash row (topk_update)
        hll = sk.hll.at[gslot, gid, reg].max(rho, mode="drop")
        rs = row_slots(key_hi, key_lo, d_cms, w_cms)  # [D, N] in [0, D*W)
        flat = gslot[None, :].astype(jnp.int32) * (d_cms * w_cms) + rs
        cms = (
            sk.cms.reshape(-1)
            .at[flat.reshape(-1)]
            .add(jnp.broadcast_to(w[None, :], flat.shape).reshape(-1), mode="drop")
            .reshape(r, d_cms, w_cms)
        )
        if d_tk:
            tkv, tkh, tkl, tia, tib = topk_update(
                lanes, slot, key_hi, key_lo, id_a, id_b, weight, mask,
            )
        else:
            tkv, tkh, tkl, tia, tib = lanes
        return dataclasses.replace(
            sk, win=win, count=count, hll=hll, cms=cms, hist=hist,
            tk_votes=tkv, tk_hi=tkh, tk_lo=tkl, tk_ida=tia, tk_idb=tib,
        )

    # -- shared-sort path (ISSUE 17) ------------------------------------
    n = window.shape[0]
    s_win, s_hi, s_lo, s_pos, head, run_id = presorted
    s_slot = (s_win % jnp.uint32(r)).astype(jnp.int32)
    s_mask = mask[s_pos]
    s_w = w[s_pos]
    # per-(window, key) run weight under THIS phase's mask — shared by
    # the count-min head adds and every top-K hash row
    run_w = jax.ops.segment_sum(s_w, run_id, num_segments=n)
    rw = run_w[run_id]
    w_head = jnp.where(head, rw, 0)
    s_ia = jnp.asarray(id_a, jnp.uint32)[s_pos]
    s_ib = jnp.asarray(id_b, jnp.uint32)[s_pos]
    rs = row_slots(s_hi, s_lo, d_cms, w_cms)  # [D, N] in [0, D*W)

    hll = sk.hll.at[gslot, gid, reg].max(rho, mode="drop")
    # one add per run HEAD (carrying the run's summed weight) instead of
    # per row: non-head rows add 0 at a live cell — a no-op — so cell
    # totals stay bit-identical to the per-row oracle while the
    # scatter's live writes drop to one per (window, key) run. Head
    # slots are always in-range (window % R), so no index masking is
    # needed: fully-unmasked runs carry w_head == 0.
    flat = s_slot[None, :] * (d_cms * w_cms) + rs
    cms = (
        sk.cms.reshape(-1)
        .at[flat.reshape(-1)]
        .add(
            jnp.broadcast_to(w_head[None, :], flat.shape).reshape(-1),
            mode="drop",
        )
        .reshape(r, d_cms, w_cms)
    )
    challengers = (
        topk_challengers_presorted(
            s_slot, s_hi, s_lo, s_ia, s_ib, rw, s_mask,
            r, d_tk, sk.tk_votes.shape[2],
        )
        if d_tk
        else []
    )
    tkv, tkh, tkl, tia, tib = (
        _apply_challengers(lanes, challengers) if d_tk else lanes
    )
    return dataclasses.replace(
        sk, win=win, count=count, hll=hll, cms=cms, hist=hist,
        tk_votes=tkv, tk_hi=tkh, tk_lo=tkl, tk_ida=tia, tk_idb=tib,
    )


def sketch_plane_step(
    sk: SketchState,
    spec: LogHistSpec,
    *,
    window,
    valid,
    base_w,
    close_w,
    group,
    client_hi,
    client_lo,
    key_hi,
    key_lo,
    weight,
    rtt,
    rtt_valid,
    id_a,
    id_b,
    shared_sort: bool | None = None,
) -> SketchState:
    """One batch through the plane, in window order (traced):

      1. closing-span rows (base_w ≤ window < close_w, within the live
         ring span) fold into their still-open slots;
      2. every slot with win < close_w closes into the pending buffer;
      3. new-span rows (window ≥ close_w) claim the freed slots.

    `base_w`/`close_w` are the pre-/post-batch open-span starts — the
    single-chip fused step derives them on device from the same rule
    the host replays; the sharded step receives them from the host
    (which decides advances before dispatch).

    The closing phase's collision-free span is anchored at the OLDEST
    LIVE RING SLOT (or base_w when the ring is empty), not at base_w:
    when a batch's own t_min jumps ahead of windows still open from
    earlier batches, anchoring at base_w would let a closing row alias
    mod R into an older occupied slot and silently merge two windows'
    sketches. Rows in the mid-gap [anchor + R, close_w) — only
    possible when one batch spans more than R windows below its close
    bound — are counted into `shed` instead (module docstring).

    One-pass fold (ISSUE 17). With `shared_sort` (default: the
    DEEPFLOW_SHARED_SORT knob, ON) and the top-K lane enabled, the
    batch's (window, key_hi, key_lo) stable sort runs ONCE here and
    both phases consume it — the per-hash-row fresh sorts inside
    `topk_update` (2 phases × topk_rows sorts) collapse into this one,
    and the count-min scatter dedups to run heads. Bit-exact vs the
    multi-sort path (tests/test_sketch_onepass.py). The knob resolves
    at TRACE time — callers whose jitted step outlives an env flip must
    thread it as a static argument (aggregator/window.py does)."""
    if shared_sort is None:
        shared_sort = _use_shared_sort()
    r = sk.ring
    window = jnp.asarray(window, jnp.uint32)
    base_w = jnp.asarray(base_w, jnp.uint32)
    close_w = jnp.asarray(close_w, jnp.uint32)
    # oldest live slot bounds the alias-free span; SENTINEL (empty
    # ring) never lowers the min below base_w
    anchor = jnp.minimum(jnp.min(sk.win), base_w)
    hi_a = jnp.minimum(close_w, anchor + jnp.uint32(r))
    in_a = valid & (window >= base_w) & (window < hi_a)
    in_c = valid & (window >= jnp.maximum(close_w, base_w))
    shed = (
        valid
        & (window >= jnp.maximum(anchor + jnp.uint32(r), base_w))
        & (window < close_w)
    )

    presorted = None
    if shared_sort and sk.tk_votes.shape[1]:
        # THE batch sort: stable 3-key over the raw lanes + a position
        # payload. No sentinel rekey is needed — phase masks ride
        # through the permutation, and masked-out rows contribute
        # weight 0 without perturbing the relative order of live rows.
        n = window.shape[0]
        iota = jnp.arange(n, dtype=jnp.int32)
        s_win, s_hi, s_lo, s_pos = jax.lax.sort(
            (window, jnp.asarray(key_hi, jnp.uint32),
             jnp.asarray(key_lo, jnp.uint32), iota),
            num_keys=3,
        )
        head = jnp.concatenate(
            [
                jnp.ones((1,), bool),
                (s_win[1:] != s_win[:-1])
                | (s_hi[1:] != s_hi[:-1])
                | (s_lo[1:] != s_lo[:-1]),
            ]
        )
        run_id = jnp.cumsum(head.astype(jnp.int32)) - 1
        presorted = (s_win, s_hi, s_lo, s_pos, head, run_id)

    args = (group, client_hi, client_lo, key_hi, key_lo, weight, rtt,
            rtt_valid, id_a, id_b)
    sk = _scatter_rows(sk, spec, in_a, window, *args, presorted=presorted)
    sk = sketch_close(sk, close_w)
    sk = _scatter_rows(sk, spec, in_c, window, *args, presorted=presorted)
    if _pool_mode(sk):
        sk = _maybe_promote(sk)
    folded = (jnp.sum(in_a) + jnp.sum(in_c)).astype(jnp.uint32)
    return dataclasses.replace(
        sk,
        rows=sk.rows + folded,
        shed=sk.shed + jnp.sum(shed).astype(jnp.uint32),
    )


def _maybe_promote(sk: SketchState) -> SketchState:
    """End-of-step promotion (pool mode): if the most-saturated occupied
    compact slot has reached the `promote_fill` threshold — saturation =
    CMS row-0 fill fraction, computed from device-resident lanes inside
    the fused step, zero new fetches — move it to a free wide slot.

    Promotion IS a merge into an all-zero wide slot (freed wide slots
    are zeroed at drain), so every lane rides the r12 merge algebra at
    the pooled widths: HLL register max (int8→int32 cast — bit-exact),
    CMS tile-add (`cms_expand` — overestimate preserved), histogram
    center placement (`loghist_expand`), top-K bucket tiling
    (`topk_tile` — a key's own wide bucket always holds its entry;
    spurious tiled copies dedupe at `topk_select`). Closed-block answers
    therefore stay inside the §17 error envelope. At most one promotion
    per batch (`lax.cond`); with no free wide slot the window simply
    stays compact — accuracy degrades toward the compact bound, never
    correctness."""
    pc = sk.p_hll.shape[0]
    pw = sk.hll.shape[0]
    r = sk.ring
    ones_r = jnp.ones((r,), jnp.int32)
    isc = (sk.slot_of >= 0) & (sk.slot_of < pc)
    occ = (
        jnp.zeros((pc,), jnp.int32)
        .at[jnp.where(isc, sk.slot_of, pc)]
        .max(ones_r, mode="drop")
        > 0
    )
    fill = jnp.mean((sk.p_cms[:, 0, :] != 0).astype(jnp.float32), axis=-1)
    cand = occ & (fill >= sk.promote_fill)
    w_occ = (
        jnp.zeros((pw,), jnp.int32)
        .at[jnp.where(sk.slot_of >= pc, sk.slot_of - pc, pw)]
        .max(ones_r, mode="drop")
        > 0
    )
    # a closed-awaiting-drain wide slot is NOT free until drained
    w_free = (~w_occ) & (sk.wide_close == jnp.uint32(SENTINEL_WIN))
    do = jnp.any(cand) & jnp.any(w_free)

    def promote(sk: SketchState) -> SketchState:
        pidx = jnp.argmax(jnp.where(cand, fill, -1.0))
        rstar = jnp.argmax((sk.slot_of == pidx).astype(jnp.int32))
        widx = jnp.argmax(w_free.astype(jnp.int32))
        upd = dict(
            hll=sk.hll.at[widx].set(sk.p_hll[pidx].astype(jnp.int32)),
            cms=sk.cms.at[widx].set(
                cms_expand(sk.p_cms[pidx], sk.cms.shape[2])
            ),
            hist=sk.hist.at[widx].set(
                loghist_expand(sk.p_hist[pidx], sk.hist.shape[2])
            ),
        )
        if sk.tk_votes.shape[1]:
            tkv, tkh, tkl, tia, tib = topk_tile(
                (sk.p_tkv[pidx], sk.p_tkh[pidx], sk.p_tkl[pidx],
                 sk.p_tia[pidx], sk.p_tib[pidx]),
                sk.tk_votes.shape[2],
            )
            upd.update(
                tk_votes=sk.tk_votes.at[widx].set(tkv),
                tk_hi=sk.tk_hi.at[widx].set(tkh),
                tk_lo=sk.tk_lo.at[widx].set(tkl),
                tk_ida=sk.tk_ida.at[widx].set(tia),
                tk_idb=sk.tk_idb.at[widx].set(tib),
            )
        return dataclasses.replace(
            sk,
            slot_of=sk.slot_of.at[rstar].set(
                jnp.int32(pc) + widx.astype(jnp.int32)
            ),
            p_hll=sk.p_hll.at[pidx].set(0),
            p_cms=sk.p_cms.at[pidx].set(0),
            p_hist=sk.p_hist.at[pidx].set(0),
            p_tkv=sk.p_tkv.at[pidx].set(0),
            p_tkh=sk.p_tkh.at[pidx].set(0),
            p_tkl=sk.p_tkl.at[pidx].set(0),
            p_tia=sk.p_tia.at[pidx].set(0),
            p_tib=sk.p_tib.at[pidx].set(0),
            pool_promos=sk.pool_promos + jnp.uint32(1),
            **upd,
        )

    return lax.cond(do, promote, lambda s: s, sk)


def _drain_impl(sk: SketchState, close_w):
    sk = sketch_close(sk, close_w)
    pend, pend_win, n = sk.pend, sk.pend_win, sk.pend_n
    sk = dataclasses.replace(sk, pend_n=jnp.zeros((), jnp.int32))
    if _pool_mode(sk):
        # wide slots drain IN PLACE: pack every closed-awaiting-drain
        # slot as a full-width block row, then zero + free it. Open
        # wide slots ride along as all-SENTINEL rows the host skips.
        pw = sk.hll.shape[0]
        wmask = sk.wide_close != jnp.uint32(SENTINEL_WIN)
        wide_rows = _flatten_wide_arena(sk, sk.wide_count)
        wide_rows = jnp.where(wmask[:, None], wide_rows, 0)
        wide_wins = sk.wide_close

        def rstw(x):
            mm = wmask.reshape((pw,) + (1,) * (x.ndim - 1))
            return jnp.where(mm, jnp.asarray(0, x.dtype), x)

        sk = dataclasses.replace(
            sk,
            hll=rstw(sk.hll),
            cms=rstw(sk.cms),
            hist=rstw(sk.hist),
            tk_votes=rstw(sk.tk_votes),
            tk_hi=rstw(sk.tk_hi),
            tk_lo=rstw(sk.tk_lo),
            tk_ida=rstw(sk.tk_ida),
            tk_idb=rstw(sk.tk_idb),
            wide_close=jnp.full((pw,), SENTINEL_WIN, dtype=jnp.uint32),
            wide_count=jnp.where(wmask, jnp.uint32(0), sk.wide_count),
        )
    else:
        wide_rows = jnp.zeros((0, 0), jnp.uint32)
        wide_wins = jnp.zeros((0,), jnp.uint32)
    return sk, pend, pend_win, n, wide_rows, wide_wins


# donated: the returned state's pending cursor resets while the old
# pend/pend_win buffers come back as outputs — XLA copies whichever
# side cannot alias, so later in-step closes never race the (possibly
# deferred) host fetch of the drained rows.
sketch_drain = jax.jit(_drain_impl, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# host side


@dataclasses.dataclass
class WindowSketchBlock:
    """One closed window's fetched sketch summary (host numpy).

    Mergeable across shards (`merge`). `distinct`/`estimate`/`topk`
    are pure numpy over the fetched arrays (shared-xp ops math — no
    device access); `tdigest`/`quantile` reuse the jitted centroid
    compressor on tiny fixed-size arrays — a device dispatch, but off
    the ingest fetch path and outside the host_fetch budget seam, so
    sink/querier consumers pay it per closed window, never per batch.
    Top-K lanes are kept as flat candidate arrays (bucket layout is
    irrelevant once closed), which is also what makes the cross-shard
    merge a plain concatenation."""

    window: int
    config: SketchConfig
    n_updates: int
    hll: np.ndarray  # [G, m] i32
    cms: np.ndarray  # [D, W] i64 (i64: shard merges must not wrap)
    hist: np.ndarray  # [G, B] i64
    tk_hi: np.ndarray  # [n_cand] u32
    tk_lo: np.ndarray
    tk_ida: np.ndarray
    tk_idb: np.ndarray
    tk_votes: np.ndarray  # [n_cand] i64

    @classmethod
    def from_row(cls, row: np.ndarray, window: int, cfg: SketchConfig):
        """Unpack one [WIDE] u32 packed block row (layout contract:
        SketchConfig.block_width)."""
        g, m = cfg.num_groups, cfg.hll_m
        d, w = cfg.cms_depth, cfg.cms_width
        b = cfg.hist.bins
        tk = cfg.topk_rows * cfg.topk_cols
        o = 0

        def take(n):
            nonlocal o
            out = row[o : o + n]
            o += n
            return out

        n_updates = int(take(1)[0])
        hll = take(g * m).astype(np.int32).reshape(g, m)
        cms = take(d * w).astype(np.int64).reshape(d, w)
        hist = take(g * b).astype(np.int64).reshape(g, b)
        votes = take(tk).astype(np.int32).astype(np.int64)
        hi, lo, ida, idb = (take(tk) for _ in range(4))
        keep = votes > 0
        return cls(
            window=int(window), config=cfg, n_updates=n_updates,
            hll=hll, cms=cms, hist=hist,
            tk_hi=hi[keep].astype(np.uint32), tk_lo=lo[keep].astype(np.uint32),
            tk_ida=ida[keep].astype(np.uint32), tk_idb=idb[keep].astype(np.uint32),
            tk_votes=votes[keep],
        )

    @classmethod
    def from_compact_row(cls, row: np.ndarray, window: int, cfg: SketchConfig):
        """Unpack one [CW] u32 compact pool block row (layout contract:
        SketchConfig.compact_block_width) and up-tile it to the full
        block form — HLL unpacks bit-exactly (full m registers, 4 per
        word), CMS/hist expand via the same congruence/center math the
        device promotion uses, and top-K candidates read directly from
        the flat compact lanes (the block keeps candidates, not
        buckets, so no tiling is needed). Every downstream consumer
        (merge algebra, distinct/estimate/topk/quantile, cascade parent
        feeds) then works unchanged."""
        pool = cfg.pool
        assert pool is not None, "compact row without a pool config"
        g, m = cfg.num_groups, cfg.hll_m
        d, w = cfg.cms_depth, cfg.cms_width
        wc = cfg.pool_cms_width
        bc = cfg.pool_hist_bins
        tk = cfg.topk_rows * cfg.pool_topk_cols
        o = 0

        def take(n):
            nonlocal o
            out = row[o : o + n]
            o += n
            return out

        n_updates = int(take(1)[0])
        hll = hll_unpack_registers_np(
            take(g * m // 4).reshape(g, m // 4), m
        )
        cms = cms_expand(
            take(d * wc).astype(np.int64).reshape(d, wc), w, xp=np
        )
        hist = loghist_expand(
            take(g * bc).astype(np.int64).reshape(g, bc), cfg.hist.bins,
            xp=np,
        )
        votes = take(tk).astype(np.int32).astype(np.int64)
        hi, lo, ida, idb = (take(tk) for _ in range(4))
        keep = votes > 0
        return cls(
            window=int(window), config=cfg, n_updates=n_updates,
            hll=hll, cms=cms, hist=hist,
            tk_hi=hi[keep].astype(np.uint32), tk_lo=lo[keep].astype(np.uint32),
            tk_ida=ida[keep].astype(np.uint32), tk_idb=idb[keep].astype(np.uint32),
            tk_votes=votes[keep],
        )

    def merge(self, other: "WindowSketchBlock") -> "WindowSketchBlock":
        """Cross-shard combine for the same window: register max,
        counter add, candidate union (estimates re-derive from the
        merged count-min at query time)."""
        assert other.window == self.window, (self.window, other.window)
        return WindowSketchBlock(
            window=self.window,
            config=self.config,
            n_updates=self.n_updates + other.n_updates,
            hll=np.maximum(self.hll, other.hll),
            cms=self.cms + other.cms,
            hist=self.hist + other.hist,
            tk_hi=np.concatenate([self.tk_hi, other.tk_hi]),
            tk_lo=np.concatenate([self.tk_lo, other.tk_lo]),
            tk_ida=np.concatenate([self.tk_ida, other.tk_ida]),
            tk_idb=np.concatenate([self.tk_idb, other.tk_idb]),
            tk_votes=np.concatenate([self.tk_votes, other.tk_votes]),
        )

    # -- queries ---------------------------------------------------------
    def distinct(self, group: int | None = None) -> float:
        """HLL distinct-client estimate: one group, or the whole window
        (register-max union over groups — NOT the per-group sum, which
        would double-count clients seen by several services)."""
        if group is None:
            est = hll_estimate_np(self.hll.max(axis=0, keepdims=True))
            return float(est[0])
        return float(hll_estimate_np(self.hll[group : group + 1])[0])

    def distinct_per_group(self) -> np.ndarray:
        return hll_estimate_np(self.hll)

    def estimate(self, key_hi, key_lo) -> np.ndarray:
        """Count-min point estimates (overestimate-only) for flow keys."""
        from ..ops.cms import cms_query_np

        return cms_query_np(self.cms, key_hi, key_lo)

    def tdigest(self, group: int | None = None, compression: int = 64):
        """(means, weights) centroid export of the latency histogram —
        the compact wire form (ops/tdigest.py). group None pools."""
        hist = self.hist.sum(axis=0) if group is None else self.hist[group]
        spec = self.config.hist
        centers = spec.vmin * np.power(
            spec.gamma, np.arange(spec.bins, dtype=np.float64) + 0.5
        )
        m, w = tdigest_compress(
            jnp.asarray(centers, jnp.float32),
            jnp.asarray(hist, jnp.float32),
            compression=compression,
        )
        return np.asarray(m), np.asarray(w)

    def quantile(self, q: float, group: int | None = None) -> float:
        """Latency quantile through the t-digest export path."""
        m, w = self.tdigest(group)
        return float(
            np.asarray(tdigest_quantile(jnp.asarray(m), jnp.asarray(w),
                                        jnp.asarray([q], jnp.float32)))[0]
        )

    def topk(self, k: int) -> list[dict]:
        """Invert the heavy-hitter sketch: candidates from the bucket
        lanes, ranked by the same window's count-min estimate."""
        if len(self.tk_hi) == 0:
            return []
        est = self.estimate(self.tk_hi, self.tk_lo)
        hi, lo, ida, idb, est_k = topk_select(
            self.tk_hi, self.tk_lo, self.tk_ida, self.tk_idb, est, k
        )
        return [
            {
                "key_hi": int(hi[i]), "key_lo": int(lo[i]),
                "id_a": int(ida[i]), "id_b": int(idb[i]),
                "estimate": int(est_k[i]),
            }
            for i in range(len(hi))
        ]


def hold_blocks(held: list, new_blocks, cap: int) -> int:
    """THE closed-block retention policy, shared by RollupPipeline and
    ShardedWindowManager: append, then drop-oldest beyond `cap` (the
    same counted-drop stance as the device pending buffer). Returns the
    number dropped — callers count it so an undrained
    pop_closed_sketches consumer is loud, not a leak."""
    held.extend(new_blocks)
    overflow = len(held) - cap
    if overflow > 0:
        del held[:overflow]
        return overflow
    return 0


def unpack_drained(rows: np.ndarray, wins: np.ndarray, cfg: SketchConfig):
    """Fetched drained/snapshotted rows + [n] window ids →
    WindowSketchBlocks, dispatching on the row width: `block_width` =
    wide rows, `compact_block_width` = pooled pending rows, and their
    sum = open-snapshot combo rows (compact part ‖ wide part — the part
    with a nonzero count word is the live one; allocated slots always
    hold count ≥ 1, so at most one side is nonzero). Blocks that never
    saw a row (possible on the sharded path, where a device closes a
    window its shard had no data for) are dropped here."""
    wide_w = cfg.block_width
    cw = cfg.compact_block_width if cfg.pool is not None else None
    out = []
    for i in range(rows.shape[0]):
        row = rows[i]
        if cw is not None and row.shape[0] == cw:
            blk = WindowSketchBlock.from_compact_row(row, int(wins[i]), cfg)
        elif cw is not None and row.shape[0] == cw + wide_w:
            crow, wrow = row[:cw], row[cw:]
            if int(crow[0]):
                blk = WindowSketchBlock.from_compact_row(crow, int(wins[i]), cfg)
            else:
                blk = WindowSketchBlock.from_row(wrow, int(wins[i]), cfg)
        else:
            blk = WindowSketchBlock.from_row(row, int(wins[i]), cfg)
        if blk.n_updates or len(blk.tk_hi):
            out.append(blk)
    return out


__all__ = [
    "PoolConfig",
    "SketchConfig",
    "SketchState",
    "WindowSketchBlock",
    "sketch_init",
    "sketch_close",
    "sketch_drain",
    "sketch_plane_step",
    "unpack_drained",
    "topk_candidates",
]
