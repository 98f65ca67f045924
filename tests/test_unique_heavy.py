"""A unique-heavy stream at test scale (ISSUE 28, the deployment
`l4_1s_1m`): uniform keys over a population four times an event-second's
records, `delay=2` (three windows open while a fourth closes), a stash
sized by the deployment's own rule — the smallest power of two that ends
with `stash_evictions` 0, so it runs a third to two thirds live — and
pages patched small so that every full close is eight pages with a cut
last one. (A page whose *start* is clamped needs a close that reaches the
stash's last page; `tests/test_flush_pages.py` has that case: 124 rows of
128.)

(a) every closed window equals the scalar oracle row for row; (b) the
guarantee counters read 0 and nothing compiles after the warm-up closes;
(c) the same stream into a stash half that size sheds, and every shed
segment is counted; (d) the counters and spans the deployment brought
(`stash_live_rows_sum`, `stash_capacity_rows_sum`, `flush.fetch`,
`flush.join`) read what the stream gives by hand.
"""

from __future__ import annotations

import numpy as np
import pytest

import deepflow_tpu.aggregator.window as window_mod
from deepflow_tpu.aggregator.fanout import FanoutConfig
from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
from deepflow_tpu.aggregator.window import WindowConfig
from deepflow_tpu.datamodel.batch import FlowBatch
from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
from deepflow_tpu.ingest.replay import SyntheticFlowGen
from deepflow_tpu.oracle.numpy_oracle import oracle_l4_rollup
from deepflow_tpu.utils import spans
from deepflow_tpu.utils.spans import (
    SPAN_FLUSH_FETCH,
    SPAN_FLUSH_JOIN,
    SPAN_FLUSH_ROWS,
)

T0 = 1_700_000_000
BATCH = 128  # the one bucket; two batches make an event-second
SECOND = 2 * BATCH
POPULATION = 4 * SECOND  # a quarter of the flows report in a second
SECONDS = 10  # windows 0..6 close as seconds 3..9 arrive; 7..9 at the drain
PAGE = 96  # ~750 documents a window: eight pages, the last one cut
STASH = 1 << 12  # the smallest power of two without evictions (see (c))
DELAY = 2
SUM_RTOL = 1e-6

KEY_FIELDS = [f.name for f in TAG_SCHEMA.fields if f.key]
KEY_IDX = [TAG_SCHEMA.index(n) for n in KEY_FIELDS]
METERS = FLOW_METER.field_names()
SUM_LANES = np.flatnonzero(FLOW_METER.sum_mask)
MAX_LANES = np.flatnonzero(FLOW_METER.max_mask)


def _doc_keys(records: list[dict]) -> set:
    """(window, key tuple) of every document the records yield."""
    return set(oracle_l4_rollup(records, FanoutConfig()))


@pytest.fixture(scope="module")
def stream():
    """SECONDS event-seconds of two batches each, the oracle's documents
    of the whole stream, and each batch's own document keys."""
    gen = SyntheticFlowGen(num_tuples=POPULATION, seed=28)
    batches = [gen.records(BATCH, T0 + k) for k in range(SECONDS) for _ in range(2)]
    docs = oracle_l4_rollup([r for b in batches for r in b], FanoutConfig())
    return {"batches": batches, "docs": docs,
            "batch_keys": [_doc_keys(b) for b in batches]}


def _run(stream, capacity: int) -> dict:
    """The stream through an L4Pipeline with a stash of `capacity` rows
    and pages of PAGE rows. Per ingest call: what it closed, the counters
    after it, and the compiles so far (under this pipeline's spans and
    under none)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(window_mod, "PAGE_ROWS", PAGE)
        pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(interval=1, delay=DELAY, capacity=capacity,
                                accum_batches=2),
            batch_size=BATCH, bucket_sizes=(BATCH,), batch_unique_cap=BATCH))
        calls = []

        def note(out):
            calls.append({
                "out": out, "counters": pipe.get_counters(),
                "compiles": pipe.tracer.compile_lanes()[0]
                + spans.unspanned_compiles()["compiles"]})

        try:
            for records in stream["batches"]:
                note(pipe.ingest(FlowBatch.from_records(records)))
            note(pipe.drain())
            return {"calls": calls, "summary": pipe.tracer.summary(),
                    "records": pipe.tracer.recent()}
        finally:
            pipe.close()


@pytest.fixture(scope="module")
def held(stream):
    return _run(stream, STASH)


@pytest.fixture(scope="module")
def shed(stream):
    return _run(stream, STASH // 2)


def _emitted(run: dict) -> dict:
    """(window, key tuple) -> meter row of every document a run closed."""
    out = {}
    for call in run["calls"]:
        for db in call["out"]:
            assert bool(db.valid.all())
            for i in range(db.size):
                key = (int(db.timestamp[i]),) + tuple(int(db.tags[i, j]) for j in KEY_IDX)
                assert key not in out, f"a document closed twice: {key}"
                out[key] = db.meters[i]
    return out


# ---------------------------------------------------------------------------
# (a) row for row against the oracle


def test_every_closed_window_equals_the_oracle(stream, held):
    got, want = _emitted(held), stream["docs"]
    assert set(got) == set(want)
    have = np.stack([got[k] for k in want]).astype(np.float64)
    ref = np.array([[d.meter[m] for m in METERS] for d in want.values()], np.float64)
    np.testing.assert_array_equal(have[:, MAX_LANES], ref[:, MAX_LANES])
    np.testing.assert_allclose(have[:, SUM_LANES], ref[:, SUM_LANES],
                               rtol=SUM_RTOL, atol=0)
    assert ref[:, SUM_LANES].sum() > 0 and ref[:, MAX_LANES].sum() > 0


def test_closes_come_in_order_full_and_in_pages(stream, held):
    per_window = {}
    for w, *_ in stream["docs"]:
        per_window[w] = per_window.get(w, 0) + 1
    # second k's first batch closes window k - 3; the drain the last three
    closes = [(i, [int(db.timestamp[0]) for db in c["out"] if db.size],
               sum(db.size for db in c["out"]))
              for i, c in enumerate(held["calls"])]
    nonempty = [(i, ws, n) for i, ws, n in closes if n]
    assert [ws for _, ws, _ in nonempty] == \
        [[T0 + k] for k in range(SECONDS - DELAY - 1)] + \
        [[T0 + k for k in range(SECONDS - DELAY - 1, SECONDS)]]
    assert [i for i, _, _ in nonempty] == \
        [2 * k for k in range(DELAY + 1, SECONDS)] + [2 * SECONDS]
    assert len(nonempty) - 1 >= 6
    for _, ws, n in nonempty:
        assert n == sum(per_window[w] for w in ws)
    # every full close is at least eight pages and its last page is cut
    c = held["calls"]
    for i, ws, n in nonempty:
        pages = c[i]["counters"]["flush_pages"] - c[i - 1]["counters"]["flush_pages"]
        assert pages == -(-n // PAGE) >= 8 and n % PAGE
    last = c[-1]["counters"]
    assert last["flush_rows_live"] == last["flushed_doc"] == len(stream["docs"])


# ---------------------------------------------------------------------------
# (b) the guarantees, and no compile after the warm-up closes


def test_guarantee_counters_read_zero_and_the_stash_runs_half_live(held):
    last = held["calls"][-1]["counters"]
    for name in ("stash_evictions", "prereduce_shed", "drop_before_window",
                 "jit_retraces", "fetch_retries", "dispatch_retries"):
        assert last[name] == 0, name
    assert last["jit_compiles"] == 1  # the one bucket
    # sized by the rule: the gauge reads a third to two thirds of the stash
    # at every dispatch once three windows are open
    full = [c["counters"]["stash_occupancy"] / STASH
            for c in held["calls"][2 * (DELAY + 1):-1]]
    assert 0.35 <= min(full) and max(full) <= 0.65


def test_nothing_compiles_after_the_warm_up_closes(held):
    # windows 0 and 1 close at calls 6 and 8; after them every program of
    # the path (step, fold, range flush, page) has run once
    warm = held["calls"][2 * (DELAY + 2)]["compiles"]
    assert warm > 0
    assert [c["compiles"] for c in held["calls"][2 * (DELAY + 2):]] \
        == [warm] * (len(held["calls"]) - 2 * (DELAY + 2))


# ---------------------------------------------------------------------------
# (c) half the stash: shed, and every shed segment counted


def test_half_the_stash_sheds_and_counts_it(stream, shed):
    got, want = _emitted(shed), stream["docs"]
    evictions = shed["calls"][-1]["counters"]["stash_evictions"]
    assert evictions > 0
    assert set(got) <= set(want)  # nothing invented
    missing = set(want) - set(got)
    assert 0 < len(missing) <= evictions
    # the shed land on the newest windows; what is about to close is kept
    lane = METERS.index("packet_tx")
    for k, row in got.items():
        assert row[lane] <= want[k].meter["packet_tx"]
    for name in ("prereduce_shed", "drop_before_window", "jit_retraces"):
        assert shed["calls"][-1]["counters"][name] == 0, name


# ---------------------------------------------------------------------------
# (d) the new counters and spans, against counts made by hand


def _occupancy_by_hand(stream) -> list[int]:
    """Valid stash rows at each batch's dispatch, from the oracle's keys:
    a fold moves the ring into the stash when the next batch would not fit
    behind it (the ring holds two) and at every advance; a second's first
    batch advances and flushes the windows more than DELAY behind it."""
    stash, ring, seen, newest = set(), [], [], None
    for b, keys in enumerate(stream["batch_keys"]):
        second = b // 2
        if len(ring) == 2:
            stash |= set().union(*ring)
            ring = []
        seen.append(len(stash))
        ring.append(keys)
        if second != newest:
            newest = second
            if second > 0:
                stash |= set().union(*ring)
                ring = []
                stash = {k for k in stash if k[0] >= T0 + second - DELAY}
    return seen


def test_live_and_capacity_sums_read_the_blocks_by_hand(stream, held):
    by_hand = _occupancy_by_hand(stream)
    gauge = [c["counters"]["stash_occupancy"] for c in held["calls"][:-1]]
    assert gauge == by_hand
    last = held["calls"][-1]["counters"]
    blocks = len(stream["batches"])  # one counter block a dispatch
    assert last["stash_capacity_rows_sum"] == blocks * STASH
    assert last["stash_live_rows_sum"] == sum(by_hand)
    # monotone, so a delta over any stretch of blocks is a mean live share
    a, b = held["calls"][7]["counters"], held["calls"][15]["counters"]
    share = (b["stash_live_rows_sum"] - a["stash_live_rows_sum"]) / (
        b["stash_capacity_rows_sum"] - a["stash_capacity_rows_sum"])
    assert share == pytest.approx(sum(by_hand[8:16]) / (8 * STASH))
    assert 0.35 <= share <= 0.65


def test_fetch_and_join_spans_once_a_drain_under_flush_rows(held):
    s = held["summary"]
    drains = s[SPAN_FLUSH_ROWS]["count"]
    # an advance a second from second 1 on, and the drain's one; the
    # first DELAY + 1 advances close nothing, so they fetch nothing
    assert drains == SECONDS - 1 + 1
    assert s[SPAN_FLUSH_JOIN]["count"] == drains
    assert s[SPAN_FLUSH_FETCH]["count"] == drains - (DELAY + 1) + 1
    assert s[SPAN_FLUSH_FETCH]["total_us"] + s[SPAN_FLUSH_JOIN]["total_us"] \
        <= s[SPAN_FLUSH_ROWS]["total_us"]
    by_id = {r.span_id: r for r in held["records"]}
    kids = [r for r in held["records"] if r.name in (SPAN_FLUSH_FETCH, SPAN_FLUSH_JOIN)]
    assert len(kids) == s[SPAN_FLUSH_FETCH]["count"] + s[SPAN_FLUSH_JOIN]["count"]
    assert all(by_id[r.parent_span_id].name == SPAN_FLUSH_ROWS for r in kids)
    # neither can compile: the close's compile lanes need neither name
    assert s[SPAN_FLUSH_FETCH]["compiles"] == s[SPAN_FLUSH_JOIN]["compiles"] == 0
