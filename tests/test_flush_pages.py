"""The window close fetches fixed-size pages (ISSUE 27): whatever the
document count of a close, the drained windows equal the sequential
per-window `stash_flush` oracle bit for bit, nothing compiles after the
first closes, the over-fetch stays under one page per part, and a close
is still two fetches. The same for the live read plane's snapshot."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepflow_tpu.aggregator.window as window_mod
from deepflow_tpu.aggregator.cascade import CascadeConfig
from deepflow_tpu.aggregator.sketchplane import SketchConfig
from deepflow_tpu.aggregator.stash import (
    stash_flush,
    stash_snapshot_range,
    unpack_flush_rows,
)
from deepflow_tpu.aggregator.window import WindowConfig, WindowManager, _PagedRows
from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
from deepflow_tpu.ops.histogram import LogHistSpec
from deepflow_tpu.utils import hostpool
from deepflow_tpu.utils.spans import FLUSH_SPAN_NAMES, SPAN_QUERY_SNAPSHOT

PAGE = 12  # does not divide the 128-row stash: the last page is clamped
CAPACITY = 128
WIDTH = 128  # every batch has this many rows; `valid` picks the live ones
# a minute starts 45 s after T0, so the cascade's tier closes mid-stream
T0 = 1_700_000_000 - 1_700_000_000 % 60 + 15
SK = SketchConfig(
    num_groups=4, hll_precision=6, cms_depth=2, cms_width=128,
    hist=LogHistSpec(bins=32, vmin=1.0, gamma=1.3),
    topk_rows=2, topk_cols=64, pending=16,
)
# the tier's stash has another row count than tier 0's: a page program of
# its own, compiled at the first tier close
CASCADE = CascadeConfig(intervals=(60,), capacity=256)
CONFIGS = {
    "plain": {},
    "sketch": {"sketch": SK},
    "cascade": {"sketch": SK, "cascade": CASCADE},
}
# (second after T0, live rows). Every batch closes what came before it: the
# second warm-up batch 4 rows and their minute, then 7, 11, 12, 13, 1, 124
# (its last page starts past row 116 and is clamped), 3, 0 (an empty span),
# 19 (two windows), 6 rows, and `flush_all` the last 25. A window and the
# next together stay under the stash's rows: nothing is shed.
WARM_UP = ((-130, 4), (-60, 7))
STREAM = ((0, 11), (10, 12), (20, 13), (30, 1), (40, 124), (50, 3), (60, 17),
          (61, 2), (70, 6), (80, 25))
CLOSED = [4, 7, 11, 12, 13, 1, 124, 3, 0, 19, 6, 25]
TIER_CLOSED = [4, 7, 11 + 12 + 13 + 1 + 124, 3 + 17 + 2 + 6 + 25]

def _batch(second: int, live: int):
    """WIDTH doc rows stamped T0 + second with keys of that second's own,
    the first `live` of them valid."""
    keys = (np.uint32(1000) * np.uint32(second + 200)
            + np.arange(WIDTH, dtype=np.uint32))
    tags = np.zeros((TAG_SCHEMA.num_fields, WIDTH), np.uint32)
    tags[TAG_SCHEMA.index("ip0_w3")] = keys
    tags[TAG_SCHEMA.index("server_port")] = 443
    tags[TAG_SCHEMA.index("protocol")] = 6
    tags[TAG_SCHEMA.index("l3_epc_id1")] = keys % 5
    meters = np.zeros((FLOW_METER.num_fields, WIDTH), np.float32)
    meters[FLOW_METER.index("byte_tx")] = 0.1 + keys % 7  # uneven bit patterns
    meters[FLOW_METER.index("rtt_sum")] = 10.5
    meters[FLOW_METER.index("rtt_count")] = 1.0
    return (np.full(WIDTH, T0 + second, np.uint32),
            jnp.asarray(keys * np.uint32(2654435761) + np.uint32(1)),
            jnp.asarray(keys ^ np.uint32(0x9E3779B9)),
            jnp.asarray(tags), jnp.asarray(meters),
            jnp.asarray(np.arange(WIDTH) < live))


def _oracle_windows(state, lo: int, hi: int) -> dict:
    """window -> (key_hi, key_lo, tags [n, T], meters [n, M]) from the
    sequential ascending per-window `stash_flush` loop over [lo, hi)."""
    slots, valid = np.asarray(state.slot), np.asarray(state.valid)
    out = {}
    for w in sorted({int(w) for w in slots[valid] if lo <= int(w) < hi}):
        state, got = stash_flush(state, np.uint32(w))
        mask = np.asarray(got["mask"])
        out[w] = (np.asarray(got["key_hi"])[mask], np.asarray(got["key_lo"])[mask],
                  np.asarray(got["tags"]).T[mask], np.asarray(got["meters"]).T[mask])
    return out


def _bits(a: np.ndarray) -> np.ndarray:
    return a.view(np.uint32) if a.dtype == np.float32 else a


def _assert_windows_equal(flushed, oracle: dict) -> None:
    assert [f.window_idx for f in flushed if f.count] == sorted(oracle)
    for f in flushed:
        if f.count:
            for got, want in zip((f.key_hi, f.key_lo, f.tags, f.meters),
                                 oracle[f.window_idx]):
                np.testing.assert_array_equal(_bits(got), _bits(want))


def _block_lanes(blk) -> dict:
    return {k: v for k, v in vars(blk).items() if isinstance(v, np.ndarray)}


def _run_stream(monkeypatch, page_rows: int, config: dict) -> dict:
    """WARM_UP, STREAM and `flush_all` through a WindowManager with pages
    of `page_rows`; every range flush it dispatches is first run through
    the sequential oracle on a copy of the stash."""
    monkeypatch.setattr(window_mod, "PAGE_ROWS", page_rows)
    oracle: list[dict] = []
    real_flush_range = window_mod.stash_flush_range

    def recording_flush_range(state, lo, hi, **kw):
        oracle.append(_oracle_windows(jax.tree.map(jnp.array, state), int(lo), int(hi)))
        return real_flush_range(state, lo, hi, **kw)

    monkeypatch.setattr(window_mod, "stash_flush_range", recording_flush_range)
    wm = WindowManager(WindowConfig(capacity=CAPACITY, **config))
    closes, flushed, tiers = [], [], []

    def close(call):
        c0, n0 = wm.get_counters(), len(oracle)
        out = call()
        c1 = wm.get_counters()
        assert len(oracle) - n0 <= 1  # one range flush, one drain
        if len(oracle) > n0:
            _assert_windows_equal(out, oracle[-1])
            closes.append({
                "total": sum(f.count for f in out),
                "compiles": wm.tracer.compile_lanes(FLUSH_SPAN_NAMES)[0],
                **{k: c1[k] - c0[k] for k in (
                    "host_fetches", "flush_pages", "flush_rows_fetched",
                    "flush_rows_live")},
            })
        flushed.extend(out)
        tiers.extend(wm.pop_tier_windows())

    for second, live in WARM_UP + STREAM:
        close(lambda: wm.ingest(*_batch(second, live)))
    close(wm.flush_all)
    wm.close()
    return {"closes": closes, "flushed": flushed, "tiers": tiers,
            "counters": wm.get_counters()}


@pytest.mark.parametrize("name", list(CONFIGS))
def test_paged_close_equals_oracle_and_compiles_nothing_per_count(monkeypatch, name):
    config = CONFIGS[name]
    run = _run_stream(monkeypatch, PAGE, config)
    closes = run["closes"]
    totals = [c["total"] for c in closes]
    assert totals == CLOSED and len(set(totals)) == len(totals)
    assert {0, 1, PAGE - 1, PAGE, PAGE + 1} <= set(totals) and max(totals) > 3 * PAGE

    # (b) nothing compiles under flush.* after the warm-up's closes (the
    # first close, and for the cascade the first tier close)
    assert [c["compiles"] for c in closes[1:]] == [closes[0]["compiles"]] * (len(closes) - 1)

    # (c) the over-fetch is under one page per part; (d) a close is the
    # counter block's fetch (ingest only), the counts and one row fetch
    parts = 1 + 2 * ("sketch" in config) + ("cascade" in config)
    for i, c in enumerate(closes):
        over = c["flush_rows_fetched"] - c["flush_rows_live"]
        assert 0 <= over < PAGE * parts, c
        assert c["flush_rows_live"] >= c["total"]
        is_ingest = i < len(closes) - 1
        assert c["host_fetches"] <= 2 + is_ingest
        if c["total"]:
            assert c["host_fetches"] == 2 + is_ingest
        if name == "plain":
            assert c["flush_rows_live"] == c["total"] and over < PAGE
            assert c["flush_pages"] == -(-c["total"] // PAGE)
            assert c["flush_rows_fetched"] == c["flush_pages"] * PAGE
    assert run["counters"]["flushed_doc"] == sum(CLOSED)

    # the ride-alongs: blocks and tier windows equal those of a manager
    # that fetches every matrix whole (a page over every matrix's rows)
    whole = _run_stream(monkeypatch, 1 << 20, config)
    assert [c["total"] for c in whole["closes"]] == CLOSED
    for kind in ("flushed", "tiers"):
        assert len(run[kind]) == len(whole[kind])
        for f, g in zip(run[kind], whole[kind]):
            assert (f.window_idx, f.count, f.tier, f.interval) == \
                   (g.window_idx, g.count, g.tier, g.interval)
            for got, want in zip((f.key_hi, f.key_lo, f.tags, f.meters),
                                 (g.key_hi, g.key_lo, g.tags, g.meters)):
                np.testing.assert_array_equal(_bits(got), _bits(want))
            assert (f.sketches is None) == (g.sketches is None) == ("sketch" not in config)
            if f.sketches is not None:
                fb, gb = _block_lanes(f.sketches), _block_lanes(g.sketches)
                assert fb.keys() == gb.keys() and fb
                for k in fb:
                    np.testing.assert_array_equal(fb[k], gb[k])
    if "cascade" in config:
        # the two minutes of the warm-up, the stream's first, its second
        assert [t.count for t in run["tiers"]] == TIER_CLOSED


@pytest.mark.parametrize("name", ["plain", "sketch"])
def test_paged_snapshot_equals_snapshot_range_and_compiles_once(monkeypatch, name):
    monkeypatch.setattr(window_mod, "PAGE_ROWS", PAGE)
    wm = WindowManager(WindowConfig(capacity=CAPACITY, delay=4, **CONFIGS[name]))
    compiles = []
    for second, live in ((0, 5), (1, 20), (2, 30)):  # 5, 25, 55 open rows
        assert wm.ingest(*_batch(second, live)) == []
        f0 = wm.host_fetches
        snap = wm.snapshot_open(force=True)
        assert wm.host_fetches - f0 == 2
        compiles.append(wm.tracer.compile_lanes((SPAN_QUERY_SNAPSHOT,))[0])
        packed, total = stash_snapshot_range(  # the snapshot folded the ring in
            wm.state, np.uint32(wm.start_window), np.uint32(0xFFFFFFFF))
        win, key_hi, key_lo, tags, meters = unpack_flush_rows(
            np.asarray(packed)[: int(total)], TAG_SCHEMA.num_fields)
        rows = [w for w in snap.windows if w.count]
        assert sum(w.count for w in rows) == int(total) == sum(
            live for s, live in ((0, 5), (1, 20), (2, 30)) if s <= second)
        assert all(w.partial for w in snap.windows)
        np.testing.assert_array_equal(
            np.concatenate([np.full(w.count, w.window_idx, np.uint32) for w in rows]), win)
        for got, want in ((np.concatenate([w.key_hi for w in rows]), key_hi),
                          (np.concatenate([w.key_lo for w in rows]), key_lo),
                          (np.concatenate([w.tags for w in rows]), tags),
                          (np.concatenate([w.meters for w in rows]), meters)):
            np.testing.assert_array_equal(_bits(got), _bits(want))
        assert all((w.sketches is not None) == (name == "sketch") for w in snap.windows)
    assert compiles[1:] == compiles[:1] * 2  # nothing after the first snapshot
    # a snapshot is no drain: the drain's counters stand still
    assert wm.get_counters()["flush_pages"] == 0
    _assert_windows_equal(
        wm.flush_all(),
        {f.window_idx: (f.key_hi, f.key_lo, f.tags, f.meters) for f in snap.windows})
    wm.close()


@pytest.mark.parametrize("size,page,axis", [
    (64, 12, 0), (64, 16, 0), (10, 12, 0), (64, 12, 1), (7, 3, 1)])
def test_paged_rows_cut_back_to_the_live_rows(monkeypatch, size, page, axis):
    monkeypatch.setattr(window_mod, "PAGE_ROWS", page)
    shape = (size, 5) if axis == 0 else (3, size)
    host = np.arange(np.prod(shape), dtype=np.uint32).reshape(shape)
    x = jnp.asarray(host)
    for n in sorted({0, 1, page - 1, page, page + 1, 3 * page + 1, size - 1, size}):
        if not 0 <= n <= size:
            continue
        part = _PagedRows(x, n, axis=axis)
        assert len(part.pages) == (-(-n // min(page, size)) if n else 0)
        assert part.rows_fetched - n < page and part.n == n
        assert all(p.shape[axis] == min(page, size) for p in part.pages)
        got = part.join(window_mod.host_fetch(part.pages)) if part.pages else part.join([])
        want = host[:n] if axis == 0 else host[:, :n]
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    vec = _PagedRows(jnp.arange(size, dtype=jnp.uint32), min(size, page + 1))
    np.testing.assert_array_equal(
        vec.join(window_mod.host_fetch(vec.pages)),
        np.arange(min(size, page + 1), dtype=np.uint32))


def test_get_counters_carries_the_page_counters_fetch_free(monkeypatch):
    monkeypatch.setattr(window_mod, "PAGE_ROWS", PAGE)
    wm = WindowManager(WindowConfig(capacity=CAPACITY))
    c = wm.get_counters()
    assert (c["flush_pages"], c["flush_rows_fetched"], c["flush_rows_live"]) == (0, 0, 0)
    wm.ingest(*_batch(0, 30))
    wm.ingest(*_batch(10, 1))  # closes the 30 documents: 3 pages of 12

    def no_fetch(_x):
        raise AssertionError("get_counters must not touch the device")

    monkeypatch.setattr(window_mod, "host_fetch", no_fetch)
    f0 = wm.host_fetches
    c = wm.get_counters()
    assert wm.host_fetches == f0
    assert (c["flush_pages"], c["flush_rows_fetched"], c["flush_rows_live"]) == (3, 36, 30)
    assert all(isinstance(c[k], int) for k in (
        "flush_pages", "flush_rows_fetched", "flush_rows_live"))
    wm.close()


# ---------------------------------------------------------------------------
# PR 34: a close writes its rows to host memory once. The meters leave
# `unpack_flush_rows` as a view of the fetched matrix, and the pages are
# joined into a destination reserved (and touched) under `flush.wait`, in
# the memory order the pages come in (column-major from a TPU).

W = 3 + TAG_SCHEMA.num_fields + FLOW_METER.num_fields  # words of a packed row


def _packed_rows(kind: str) -> np.ndarray:
    """A host `[n, W]` u32 matrix as a drain hands it to the split: the
    join of several pages, one page's cut (a view into the page), or no
    rows. The meter words hold float32 bit patterns of every kind."""
    rng = np.random.default_rng(34)
    page = rng.integers(0, 1 << 32, size=(PAGE, W), dtype=np.uint32)
    page[:, -3:] = np.float32([np.nan, -0.0, 1e-42]).view(np.uint32)
    if kind == "joined":
        return np.concatenate([page, page[::-1], page[:5]])
    if kind == "joined_column_major":  # as the pages of a TPU come and join
        return np.asfortranarray(np.concatenate([page, page[::-1], page[:5]]))
    if kind == "one_page":
        return page[: PAGE - 3]
    return np.zeros((0, W), np.uint32)


@pytest.mark.parametrize("kind", ["joined", "joined_column_major", "one_page", "zero_rows"])
def test_unpacked_meters_are_a_view_equal_to_the_contiguous_copy(kind):
    rows = _packed_rows(kind)
    T = TAG_SCHEMA.num_fields
    win, key_hi, key_lo, tags, meters = unpack_flush_rows(rows, T)
    old = np.ascontiguousarray(rows[:, 3 + T:]).view(np.float32)  # the copy that went
    assert meters.dtype == np.float32 and meters.shape == old.shape == (
        rows.shape[0], FLOW_METER.num_fields)
    np.testing.assert_array_equal(_bits(meters), _bits(old))
    np.testing.assert_array_equal(tags, rows[:, 3:3 + T])
    if rows.shape[0]:
        for out in (win, key_hi, key_lo, tags, meters):
            assert np.shares_memory(out, rows)
        # the matrix's own strides: row-major a row's meters are contiguous,
        # column-major every column is
        assert meters.strides == tags.strides == rows.strides
        assert meters.strides == ((4, 4 * rows.shape[0]) if "column" in kind else (4 * W, 4))
        assert not meters.flags.c_contiguous
        rows[0, -1] ^= np.uint32(1)  # no copy in between: a write shows
        assert _bits(meters)[0, -1] == rows[0, -1] != _bits(old)[0, -1]


# (second after T0, live rows) with the seconds apart or together so that
# each batch closes what the case is about. delay 2, interval 1: a batch
# at second s closes every window before s - 2.
RESERVE_STREAMS = {
    # the first drain with rows has no history (the two before it were empty)
    "first_close": ((0, 30), (1, 5), (2, 5), (3, 5), (4, 5)),
    # 40 rows reserve 41: the next window's 41 land in the reserve
    "fits": ((0, 40), (1, 41), (2, 30), (3, 5), (4, 5), (5, 5)),
    # ... and 42 are one row over it: the reserve is wasted, the join is fresh
    "one_row_over": ((0, 40), (1, 42), (2, 30), (3, 5), (4, 5), (5, 5)),
    # second 7 closes the windows of seconds 2, 3 and 4 in one drain
    "three_windows": ((0, 30), (1, 30), (2, 31), (3, 29), (4, 30), (7, 5), (8, 5)),
    # after a jump the next second's drain closes a window nobody wrote to
    "empty_drain": ((0, 30), (1, 30), (10, 30), (11, 30), (12, 5), (13, 5), (14, 5)),
    "sketch": ((0, 40), (1, 41), (2, 40), (3, 43), (4, 40), (7, 5), (8, 5)),
    "cascade": ((-60, 20), (-59, 20), (0, 40), (1, 41), (2, 40), (3, 43), (4, 40),
                (7, 5), (8, 5)),
}


def _run_reserve_stream(monkeypatch, stream, config: dict, engage: bool,
                        order: str = "C") -> dict:
    """`stream` and `flush_all` through a WindowManager with pages of PAGE
    rows. With `engage` false the manager's history is cleared before
    every drain, so no drain reserves. With `order` "F" every fetched
    matrix comes back column-major, as a TPU's do. Records, a drain: its
    range, its exact rows, and what the three counters moved by."""
    monkeypatch.setattr(window_mod, "PAGE_ROWS", PAGE)
    if order == "F":
        real_fetch = window_mod.host_fetch

        def column_major_fetch(x):
            got = real_fetch(x)
            if isinstance(got, list):
                return [np.asfortranarray(a) if a.ndim == 2 else a for a in got]
            return got

        monkeypatch.setattr(window_mod, "host_fetch", column_major_fetch)
    wm = WindowManager(WindowConfig(capacity=CAPACITY, **config))
    drains, calls, tiers = [], [], []
    real_drain = wm._drain_flush

    def recording_drain(entry):
        if not engage:
            wm._drain_rows_per_window = 0
        c0 = wm.get_counters()
        out = real_drain(entry)
        c1 = wm.get_counters()
        drains.append({
            "windows": min(entry.hi - entry.lo, wm.config.ring - 1),
            "total": sum(f.count for f in out),
            **{k: c1[k] - c0[k] for k in (
                "flush_rows_reserved", "flush_rows_live", "flush_host_write_bytes")},
        })
        return out

    monkeypatch.setattr(wm, "_drain_flush", recording_drain)
    for second, live in stream:
        calls.append(wm.ingest(*_batch(second, live)))
        tiers.extend(wm.pop_tier_windows())
    calls.append(wm.flush_all())
    tiers.extend(wm.pop_tier_windows())
    wm.close()
    return {"calls": calls, "tiers": tiers, "drains": drains,
            "counters": wm.get_counters(), "spans": wm.tracer.summary()}


RESERVE_CASES = [(case, "C") for case in RESERVE_STREAMS] + [
    ("fits", "F"), ("three_windows", "F"), ("sketch", "F")]


def _assert_same_window(f, g) -> None:
    assert (f.window_idx, f.count, f.tier, f.interval) == \
           (g.window_idx, g.count, g.tier, g.interval)
    for got, want in zip((f.key_hi, f.key_lo, f.tags, f.meters),
                         (g.key_hi, g.key_lo, g.tags, g.meters)):
        np.testing.assert_array_equal(_bits(got), _bits(want))
    assert (f.sketches is None) == (g.sketches is None)
    if f.sketches is not None:
        fb, gb = _block_lanes(f.sketches), _block_lanes(g.sketches)
        assert fb.keys() == gb.keys() and fb
        for k in fb:
            np.testing.assert_array_equal(fb[k], gb[k])


def _host_half_model(drains: list) -> list:
    """What the code says a drain of exact rows alone does to the
    counters: (rows that landed in a reserve, bytes of host arrays made,
    whether it reserved)."""
    hint, out = 0, []
    for d in drains:
        reserve = min(window_mod.reserve_rows(hint * d["windows"]), CAPACITY)
        reserves = reserve > PAGE  # under a page the join is a view: nothing to reserve
        made = reserve * W * 4 if reserves else 0
        landed = reserves and 0 < d["total"] <= reserve
        if not landed and d["total"] > PAGE:  # several pages: a fresh concatenate
            made += d["total"] * W * 4
        out.append((d["total"] if landed else 0, made, reserves))
        hint = -(-d["total"] // d["windows"])
    return out


@pytest.mark.parametrize("case,order", RESERVE_CASES)
def test_reserved_join_hands_on_the_same_windows_by_the_same_call(monkeypatch, case, order):
    config = CONFIGS.get(case, {})
    stream = RESERVE_STREAMS[case]
    on = _run_reserve_stream(monkeypatch, stream, config, engage=True, order=order)
    off = _run_reserve_stream(monkeypatch, stream, config, engage=False, order=order)
    # a window over a page is handed on in the order its pages came in
    for f, g in zip((f for c in on["calls"] for f in c), (f for c in off["calls"] for f in c)):
        if f.count > PAGE:
            assert window_mod._memory_order(f.tags) == window_mod._memory_order(g.tags)
            assert (f.tags.strides[0] == 4) == (order == "F")
    # >= 4 closes; every call hands on the windows the other manager's does
    assert len(on["drains"]) == len(off["drains"]) >= 4
    assert [d["total"] for d in on["drains"]] == [d["total"] for d in off["drains"]]
    for kind in ("calls", "tiers"):
        got = on[kind] if kind == "tiers" else [f for c in on[kind] for f in c]
        want = off[kind] if kind == "tiers" else [f for c in off[kind] for f in c]
        assert len(got) == len(want)
        for f, g in zip(got, want):
            _assert_same_window(f, g)
    assert [len(c) for c in on["calls"]] == [len(c) for c in off["calls"]]
    if case == "cascade":
        assert sum(t.count for t in on["tiers"]) > 0

    # the manager that never reserves counts none and opens no span
    assert off["counters"]["flush_rows_reserved"] == 0
    assert "flush.reserve" not in off["spans"]
    for d in on["drains"] + off["drains"]:
        assert 0 <= d["flush_rows_reserved"] <= d["total"] <= d["flush_rows_live"]
    reserved = [d["flush_rows_reserved"] for d in on["drains"]]
    assert on["counters"]["flush_rows_reserved"] == sum(reserved) > 0
    model = _host_half_model(on["drains"])
    assert on["spans"]["flush.reserve"]["count"] == sum(r for _, _, r in model)
    assert reserved == [landed for landed, _, _ in model]
    if not config:
        # the bytes as the code says, drain by drain (blocks, window ids
        # and tier rows join too where a plane is on)
        assert [d["flush_host_write_bytes"] for d in on["drains"]] == [
            made for _, made, _ in model]
        assert [d["flush_host_write_bytes"] for d in off["drains"]] == [
            d["total"] * W * 4 if d["total"] > PAGE else 0 for d in off["drains"]]
    totals = [d["total"] for d in on["drains"]]
    if case == "first_close":
        i = totals.index(30)
        assert totals[:i] == [0] * i and reserved[i] == 0
    elif case == "fits":
        assert reserved[totals.index(41)] == 41 == window_mod.reserve_rows(40)
    elif case == "one_row_over":
        i = totals.index(42)
        assert reserved[i] == 0 and on["drains"][i]["flush_host_write_bytes"] == (
            window_mod.reserve_rows(40) + 42) * W * 4
    elif case == "three_windows":
        i = totals.index(31 + 29 + 30)
        assert on["drains"][i]["windows"] == 3 and reserved[i] == 90
    elif case == "empty_drain":
        i = totals.index(0, 1)  # after the jump's drain, not the first
        assert totals[i - 1] == 60 and on["drains"][i - 1]["windows"] == 3
        assert on["drains"][i]["flush_host_write_bytes"] == \
            window_mod.reserve_rows(20) * W * 4  # made for nothing
        # ... and an empty drain leaves no history: the next rows miss
        j = totals.index(30, i)
        assert reserved[j] == 0 and reserved[j + 1] == 30


@pytest.mark.parametrize("name,order", [("plain", "C"), ("sketch", "C"), ("plain", "F")])
def test_a_window_handed_on_is_views_of_one_matrix_never_written_again(
        monkeypatch, name, order):
    """No reuse: what a drain hands on is the consumer's. A window's keys,
    tags and meters are views of ONE matrix (the drain's joined rows, in
    a reserve from the third close on), every window's bytes, copied when
    it was handed on, are what it holds after every later close, and no
    two drains' windows share memory."""
    stream = tuple((s, 40 + s % 2) for s in range(10))  # 4 pages a close
    run = _run_reserve_stream(monkeypatch, stream, CONFIGS[name], engage=True, order=order)
    assert sum(d["flush_rows_reserved"] > 0 for d in run["drains"]) >= 5
    arrays = lambda f: (f.key_hi, f.key_lo, f.tags, f.meters)
    addr = lambda a: a.__array_interface__["data"][0]
    wm = WindowManager(WindowConfig(capacity=CAPACITY, **CONFIGS[name]))
    held = []  # (window, a copy of its arrays at the hand-over, which call)
    for n, (second, live) in enumerate(stream):
        for f in wm.ingest(*_batch(second, live)):
            held.append((f, [np.array(a) for a in arrays(f)], n))
    for f in wm.flush_all():
        held.append((f, [np.array(a) for a in arrays(f)], len(stream)))
    assert wm.get_counters()["flush_rows_reserved"] > 0
    rowful = [(f, was, n) for f, was, n in held if f.count]
    assert len(rowful) == len(stream) and rowful[-1][2] - rowful[0][2] >= 3
    for f, was, _ in rowful:
        for now, then in zip(arrays(f), was):
            np.testing.assert_array_equal(_bits(now), _bits(then))
        # one matrix: hi, lo, T tags, M meters are columns 1, 2, 3.., 3+T.. of it
        col = f.tags.strides[1]
        assert f.meters.strides == f.tags.strides and f.key_hi.strides == (f.tags.strides[0],)
        assert addr(f.key_lo) - addr(f.key_hi) == col == addr(f.tags) - addr(f.key_lo)
        assert addr(f.meters) - addr(f.tags) == col * TAG_SCHEMA.num_fields
        assert (col == 4) == (order == "C")
        assert f.meters.dtype == np.float32 and not f.meters.flags.c_contiguous
    for (f, _, n), (g, _, m) in zip(rowful, rowful[1:]):
        # the last call's drain hands on several windows of one matrix
        assert n == m or not np.may_share_memory(f.meters, g.meters)
    wm.close()


@pytest.mark.parametrize("pages,dst,lands", [
    ("C", "C", True), ("F", "F", True), ("F", "C", False), ("C", "F", False),
    ("F", None, False), ("F", "short", False)])
def test_join_writes_into_a_destination_of_the_pages_order_only(monkeypatch, pages, dst, lands):
    monkeypatch.setattr(window_mod, "PAGE_ROWS", PAGE)
    host = np.arange(64 * 5, dtype=np.uint32).reshape(64, 5)
    n = 3 * PAGE + 1
    reserve = None if dst is None else (
        np.full((n - 1, 5), 7, np.uint32, order="F") if dst == "short"
        else np.full((n + 2, 5), 7, np.uint32, order=dst))
    part = _PagedRows(jnp.asarray(host), n, dst=reserve)
    fetched = [np.asarray(a, order=pages) for a in window_mod.host_fetch(part.pages)]
    got = part.join(fetched)
    np.testing.assert_array_equal(got, host[:n])
    assert (part.order, part.landed) == (pages, lands)
    assert window_mod._memory_order(got) == pages  # the pages' order either way
    assert np.shares_memory(got, reserve) if lands else part.joined_bytes == got.nbytes
    if lands:  # the tail past the live rows is the reserve's waste, untouched
        assert part.joined_bytes == 0 and (reserve[n:] == 7).all()


# ---------------------------------------------------------------------------
# the close's two host passes over a small pool of threads (PR 37)


def _force_pool(monkeypatch, workers: int, min_bytes: int = 0, cores: int = 64) -> list:
    """The helper's two constants and the core count as this test wants
    them (the tier-1 machine may have one core); returns the list that
    records every divided pass's number of pieces."""
    divided = []
    real_run = hostpool._run

    def recording_run(pieces):
        divided.append(len(pieces))
        real_run(pieces)

    monkeypatch.setattr(hostpool, "WORKERS", workers)
    monkeypatch.setattr(hostpool, "POOL_MIN_BYTES", min_bytes)
    monkeypatch.setattr(hostpool, "_cores", lambda: cores)
    monkeypatch.setattr(hostpool, "_run", recording_run)
    return divided


@pytest.mark.parametrize("workers", [1, 2, 4, 7])
@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("order", ["C", "F"])
def test_pooled_copy_equals_concatenate_bit_for_bit(monkeypatch, order, axis, workers):
    divided = _force_pool(monkeypatch, workers)
    rng = np.random.default_rng(7)
    pages = [np.asarray(rng.integers(0, 1 << 32, (PAGE, 9), dtype=np.uint32), order=order)
             for _ in range(11)]
    short = (slice(None),) * axis + (slice(0, 5),)
    cuts = pages[:-1] + [pages[-1][short]]  # a short last cut
    want = np.concatenate(cuts, axis=axis)
    shape = list(want.shape)
    shape[axis] += 3  # a destination longer than the rows
    dst = np.full(shape, 7, np.uint32, order=order)
    live = (slice(None),) * axis + (slice(0, want.shape[axis]),)
    used = hostpool.copy_cuts(cuts, dst[live], axis)
    assert used == workers and divided == ([workers] if workers > 1 else [])
    np.testing.assert_array_equal(dst[live], want)
    tail = (slice(None),) * axis + (slice(want.shape[axis], None),)
    assert (dst[tail] == 7).all()  # nothing is written past the rows
    # every cut landed at the offset concatenate gives it
    at = 0
    for c in cuts:
        here = (slice(None),) * axis + (slice(at, at + c.shape[axis]),)
        np.testing.assert_array_equal(dst[here], c)
        at += c.shape[axis]


@pytest.mark.parametrize("workers", [1, 2, 4, 7])
@pytest.mark.parametrize("order", ["C", "F"])
def test_pooled_touch_writes_one_word_of_every_page(monkeypatch, order, workers):
    divided = _force_pool(monkeypatch, workers)
    rows, width, sentinel = 1000, 99, 0xA5A5A5A5
    monkeypatch.setattr(  # the helper's allocation, filled so that a write shows
        hostpool.np, "empty", lambda n, dtype: np.full(n, sentinel, dtype))
    got, used = hostpool.touched_rows(rows, width, order)
    monkeypatch.undo()
    want = np.zeros(rows * width, np.uint32).reshape((rows, width), order=order)
    assert (got.shape, got.strides, got.dtype) == (want.shape, want.strides, want.dtype)
    assert used == workers and divided == ([workers] if workers > 1 else [])
    flat = got.reshape(-1, order=order)
    assert np.shares_memory(flat, got)
    written = np.flatnonzero(flat != sentinel)
    # exactly the words `flat[::1024] = 0` writes: one every 4 KiB
    np.testing.assert_array_equal(written, np.arange(0, rows * width, 1024))
    assert (flat[written] == 0).all()


@pytest.mark.parametrize("cores", [1, 64])
def test_a_pass_under_the_threshold_or_on_one_core_is_the_callers(monkeypatch, cores):
    """Small passes stay inline by what the code can see, the bytes of
    the pass; with one core nothing divides and no thread is started."""
    rows = 4 * PAGE
    nbytes = rows * 99 * 4
    divided = _force_pool(monkeypatch, 4, min_bytes=nbytes, cores=cores)
    started = len(hostpool._threads)
    pages = [np.full((PAGE, 99), i, np.uint32) for i in range(4)]
    want = 4 if cores > 1 else 1
    for n, shares in ((rows, want), (rows - 1, 1)):  # at the threshold, one row under it
        cuts = pages[:-1] + [pages[-1][: PAGE - (rows - n)]]
        out = np.empty((n, 99), np.uint32)
        assert hostpool.copy_cuts(cuts, out) == shares
        np.testing.assert_array_equal(out, np.concatenate(cuts))
        assert hostpool.touched_rows(n, 99, "C")[1] == shares
    assert divided == ([4, 4] if cores > 1 else [])
    if cores == 1:
        assert len(hostpool._threads) == started


def test_a_piece_that_raises_fails_the_pass_after_every_piece_has_run(monkeypatch):
    import threading

    ran, me = [], threading.get_ident()

    def piece(i):
        def run():
            ran.append((i, threading.get_ident() == me))
            if i == 2:
                raise RuntimeError("piece 2")
        return run

    with pytest.raises(RuntimeError, match="piece 2"):
        hostpool._run([piece(i) for i in range(5)])
    # the caller ran the first piece itself, pool threads the others, and
    # the failure was raised only once all five had finished
    assert sorted(ran) == [(0, True), (1, False), (2, False), (3, False), (4, False)]
    assert all(t.daemon and t.name.startswith("hostpool-") for t in hostpool._threads)
    assert len(hostpool._threads) >= 4


def _pooled_counters(run: dict) -> tuple[int, int]:
    c = run["counters"]
    return c["flush_host_pass_bytes"], c["flush_pooled_bytes"]


@pytest.mark.parametrize("case,order", [
    ("fits", "C"), ("three_windows", "F"), ("sketch", "F"), ("cascade", "C")])
def test_pooled_closes_hand_on_the_windows_the_unpooled_closes_do(monkeypatch, case, order):
    """The windows `test_reserved_join_hands_on_the_same_windows_by_the_same_call`
    pins, with every pass of the engaged manager divided over four
    threads and none of the other's."""
    config, stream = CONFIGS.get(case, {}), RESERVE_STREAMS[case]
    off = _run_reserve_stream(monkeypatch, stream, config, engage=False, order=order)
    # as the constants stand a test's passes are far under the threshold:
    # the calling thread runs them, the way the 10k deployments' closes go
    passed, pooled = _pooled_counters(off)
    assert passed > 0 and pooled == 0
    divided = _force_pool(monkeypatch, 4)
    on = _run_reserve_stream(monkeypatch, stream, config, engage=True, order=order)
    passed, pooled = _pooled_counters(on)
    # a pass with one cut (a window id vector, one block) stays the caller's
    assert 0 < pooled <= passed and len(divided) >= 4 and set(divided) <= {2, 3, 4}
    assert on["counters"]["flush_rows_reserved"] > 0
    assert [len(c) for c in on["calls"]] == [len(c) for c in off["calls"]]
    for kind in ("calls", "tiers"):
        got = on[kind] if kind == "tiers" else [f for c in on[kind] for f in c]
        want = off[kind] if kind == "tiers" else [f for c in off[kind] for f in c]
        assert len(got) == len(want) > 0 or kind == "tiers"
        for f, g in zip(got, want):
            _assert_same_window(f, g)
            if f.count > PAGE:
                assert window_mod._memory_order(f.tags) == window_mod._memory_order(g.tags)
    # a reserve counts whole, a join the rows it copied
    assert passed >= on["counters"]["flush_host_write_bytes"]


def test_a_worker_that_raises_fails_the_drain_and_hands_nothing_on(monkeypatch):
    _force_pool(monkeypatch, 4)
    real_run, armed = hostpool._run, []

    def run_with_a_failing_worker(pieces):
        def boom():
            raise RuntimeError("worker")
        real_run(pieces[:-1] + [boom] if armed else pieces)

    monkeypatch.setattr(hostpool, "_run", run_with_a_failing_worker)
    monkeypatch.setattr(window_mod, "PAGE_ROWS", PAGE)
    wm = WindowManager(WindowConfig(capacity=CAPACITY))
    handed = []
    for second in range(4):
        handed += wm.ingest(*_batch(second, 40))
    assert [f.count for f in handed] == [40] and wm.get_counters()["flush_pooled_bytes"] > 0
    armed.append(True)
    with pytest.raises(RuntimeError, match="worker"):
        handed += wm.ingest(*_batch(4, 40))
    assert [f.count for f in handed] == [40]
    wm.close()


@pytest.mark.parametrize("dst", [None, "C", "F", "short"])
def test_sharded_join_rows_pooled_equals_unpooled_device_major(monkeypatch, dst):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepflow_tpu.parallel.sharded import _DevicePages

    if len(jax.devices()) < 4:
        pytest.skip("needs four (forced host) devices")
    monkeypatch.setattr(window_mod, "PAGE_ROWS", PAGE)
    host = np.random.default_rng(3).integers(0, 1 << 32, (4, 64, 5), dtype=np.uint32)
    x = jax.device_put(host, NamedSharding(Mesh(np.array(jax.devices()[:4]), ("d",)), P("d")))
    counts = [3 * PAGE, 0, 2 * PAGE + 5, 64]  # whole pages, none, a short tail, all
    want = np.concatenate([host[d, :c] for d, c in enumerate(counts)])
    n = sum(counts)

    def joined(workers):
        divided = _force_pool(monkeypatch, workers)
        part = _DevicePages(x, counts)
        fetched = window_mod.host_fetch(part.handles)
        reserve = None if dst is None else (
            np.full((n - 1, 5), 7, np.uint32) if dst == "short"
            else np.full((n + 2, 5), 7, np.uint32, order=dst))
        return part, part.join_rows(fetched, reserve), reserve, divided

    for workers in (1, 4):
        part, got, reserve, divided = joined(workers)
        np.testing.assert_array_equal(got, want)
        lands = dst == part.order
        assert part.landed == lands and part.copied_bytes == want.nbytes
        assert part.pooled_bytes == (want.nbytes if workers > 1 else 0)
        assert divided == ([4] if workers > 1 else [])
        if lands:
            assert np.shares_memory(got, reserve) and (reserve[n:] == 7).all()
            assert part.joined_bytes == 0
        else:
            assert part.joined_bytes == want.nbytes
        # the other parts' join: each device's rows, pooled the same way
        each = part.join(window_mod.host_fetch(part.handles))
        for d, c in enumerate(counts):
            np.testing.assert_array_equal(each[d], host[d, :c])
