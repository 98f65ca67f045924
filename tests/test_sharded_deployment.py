"""PR 36: the sharded deployment (`chipbench/deployments/l4_sharded.py`)
on the served path, Receiver -> queues -> FeederRuntime -> ShardedFeedSink
-> ShardedWindowManager on four forced host devices, against the plain
references the benchmark holds it to: the partial rows merged by key are
the NumPy rollup and the one-device deployment's documents; the merged
sketch block is the reference sketch bit for bit, and (PR 39) the block
the devices merged is the one the host merges from their blocks; each
device's rows and blocks alone, merged, give the whole; closes of many document counts
compile the close's programs once; the close's spans add up and its
counters count; a forced retrace is counted; and a bfloat16 control is not
within the SUM limit."""

import copy
import os
import socket
import sys
import time

import numpy as np
import pytest

import jax

from deepflow_tpu.aggregator import window as window_mod
from deepflow_tpu.parallel import sharded
from deepflow_tpu.utils.spans import (
    FLUSH_SPAN_NAMES,
    SPAN_FLUSH_DRAIN,
    SPAN_FLUSH_FETCH,
    SPAN_FLUSH_JOIN,
    SPAN_FLUSH_RESERVE,
    SPAN_FLUSH_ROWS,
    SPAN_FLUSH_SKETCH,
    SPAN_FLUSH_SKETCH_MERGE,
    SPAN_FLUSH_SPLIT,
    SPAN_FLUSH_WAIT,
    SPAN_INGEST_DISPATCH,
    SPAN_INGEST_STAGE,
    SPAN_STATS_FETCH,
    SPAN_WINDOW_ADVANCE,
    SPAN_WINDOW_CLOSE_COLLECTIVE,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(ROOT, "chipbench")
SEED = 2**31 + 36
CHIPS = 4
# records an event-second: a prefix, then eight seconds that close with
# eight different document counts
RECORDS = [256, 5000, 3100, 4600, 2200, 5000, 3900, 2500, 4100, 2800]

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < CHIPS, reason="needs four (forced host) devices")


@pytest.fixture(scope="module")
def m():
    """chipbench's own modules (it is no package: its files import each
    other by bare name) and the cell's two checks, loaded as run.py
    loads them."""
    added = [p for p in (CHIPBENCH, os.path.join(CHIPBENCH, "tests"))
             if p not in sys.path]
    sys.path[:0] = added
    import gen
    import reference
    import sut
    import tiny
    import wire

    checks = os.path.join(CHIPBENCH, "checks")
    yield {"gen": gen, "sut": sut, "tiny": tiny, "wire": wire, "reference": reference,
           "sketch_blocks": sut.load_named("check", "sketch_blocks", [checks]),
           "pod_partials": sut.load_named("check", "pod_partials", [checks])}
    for p in added:
        sys.path.remove(p)


def sharded_config(m) -> dict:
    """tiny.py's deployment on four devices with the cell's builder,
    checks and plane keys."""
    cfg = copy.deepcopy(m["tiny"].CONFIG)
    cfg.update(name="tiny_x4", chips=CHIPS, built_by="l4_sharded",
               checks=["sketch_blocks", "pod_partials"])
    cfg["pipeline"].update(accum_batches=8, sketch={
        "num_groups": 16, "hll_precision": 12, "cms_depth": 4, "cms_width": 1024,
        "hist_bins": 256, "hist_vmin": 1.0, "hist_gamma": 1.04,
        "topk_rows": 2, "topk_cols": 512, "pool": None, "pending": 3,
        "distinct_mean_rel_err": 3.0 / 2 ** 6, "distinct_worst_sigmas": 3.0})
    return cfg


class Seconds:
    """What the checks ask of a schedule."""

    warm_up_seconds = ()  # run.py's traffic names some; the buckets do here

    def __init__(self, records: list):
        self.records = records

    def records_in_second(self, k: int) -> int:
        return self.records[k]


def by_window(docs: list) -> dict:
    return {int(db.timestamp[0]): (db.tags, db.meters) for db in docs}


def served_run(m, config: dict, records: list, on_built=None) -> dict:
    """One event-second after another over TCP into the deployment
    `sut.build` makes of `config`, pumped until taken, then flushed and
    drained: the checks' `ctx`, as run.py would hand it over, and what
    the deployment counted and traced."""
    gen = m["gen"]
    schema = gen.load_schema()
    source = gen.FlowSource(schema, config["population"], SEED)
    served = m["sut"].build(config)
    try:
        if on_built is not None:
            on_built(served)
        # as run.py: every program runs once before anything is counted
        served.warm_up(schema, source, Seconds(records))
        docs, closes, sent = [], [], 0
        base = served.feeder.get_counters()["records_in"]
        c0, s0 = served.counters(), served.spans()
        pages0 = window_mod._take_page._cache_size()
        names = [m_["name"] for m_ in schema["flow_meter"]]
        edge = schema["enums"]["code_edge_ip_port"]
        code = [d["name"] for d in schema["doc_tags"]].index("code_id")
        f = schema["flow_record_tag_fields"].index

        def take(out):
            got = served.documents(out)
            docs.extend(got)
            if got:
                c = served.counters()
                closes.append({"rows": sum(d.rows for d in got)
                               if hasattr(got[0], "rows") else None,
                               "flush_compiles": c["pipeline.flush_compiles"]})

        sent_seconds = []
        with socket.create_connection(("127.0.0.1", served.port), timeout=30) as sock:
            for k, n in enumerate(records):
                tags, meters = source.second(k, n)
                s0_, s1_ = tags[f("direction0")] != 0, tags[f("direction1")] != 0
                mult = s0_.astype(np.int64) + s1_ + (~s0_ & ~s1_)
                sent_seconds.append({
                    "second": k, "records": n,
                    "edge_packet_tx": int((meters[:, names.index("packet_tx")]
                                           .astype(np.int64) * mult).sum())})
                for frame in m["wire"].encode_frames(tags, meters, schema["wire"]):
                    sock.sendall(frame)
                sent += n
                deadline = time.monotonic() + 120
                while served.feeder.get_counters()["records_in"] - base < sent:
                    assert time.monotonic() < deadline, "the feeder took too few records"
                    out = served.feeder.pump()
                    take(out)
                    if not out:
                        time.sleep(0.001)
        take(served.feeder.flush())
        in_window = {int(d.timestamp[0]) for d in docs}
        take(served.drain())
        c1, s1 = served.counters(), served.spans()
        got = by_window(docs)
        assert len(got) == len(docs)
        for w, (tags, meters) in got.items():  # what run.py's base check sums
            have = int(meters[tags[:, code] == edge, names.index("packet_tx")]
                       .astype(np.float64).sum())
            assert have == sent_seconds[w - gen.T0]["edge_packet_tx"], w
        return {
            "ctx": {"schema": schema, "source": source, "schedule": Seconds(records),
                    "sent_seconds": sent_seconds, "got": got,
                    "closed_in_window": in_window, "seed": SEED, "config": config,
                    "side_outputs": served.side_outputs()},
            "counters": {k: c1[k] - c0.get(k, 0) for k in c1},
            "spans": {n: {k: s1[n][k] - s0.get(n, {}).get(k, 0) for k in s1[n]}
                      for n in s1},
            "closes": closes,
            "page_programs": (pages0, window_mod._take_page._cache_size()),
            "records_of": [r for tr in served.tracers() for r in tr.recent()],
            "guarantees_broken": {k for k in served.guarantee_counters
                                  if c1[k] - c0.get(k, 0)},
        }
    finally:
        served.close()


def capture_device_blocks(kept: list):
    """Keep every device's unpacked blocks as the manager sees them,
    ahead of its merge."""
    def on_built(served):
        unpack = sharded.unpack_drained

        def unpack_and_keep(rows, wins, cfg):
            blocks = unpack(rows, wins, cfg)
            kept.append(blocks)
            return blocks

        sharded.unpack_drained = unpack_and_keep
    return on_built


@pytest.fixture(scope="module")
def run(m):
    """The sharded deployment over RECORDS with the page cut small, so
    that a close is several pages and the reserve is used."""
    page = window_mod.PAGE_ROWS
    window_mod.PAGE_ROWS = 256
    try:
        out = served_run(m, sharded_config(m), RECORDS)
    finally:
        window_mod.PAGE_ROWS = page
    assert out["guarantees_broken"] == set()
    return out


@pytest.fixture(scope="module")
def host_merged_run(m):
    """The same run with the closed blocks merged on the host, as before
    PR 39 and as the pool still does (`_merged_block_slots` 0), keeping
    every device's unpacked blocks."""
    slots, unpack, device_blocks = (sharded._merged_block_slots,
                                    sharded.unpack_drained, [])
    sharded._merged_block_slots = lambda config, n_devices: 0
    try:
        out = served_run(m, sharded_config(m), RECORDS,
                         capture_device_blocks(device_blocks))
    finally:
        sharded._merged_block_slots, sharded.unpack_drained = slots, unpack
    assert out["guarantees_broken"] == set()
    out["device_blocks"] = device_blocks
    return out


def over_limit(numbers: dict) -> set:
    return {k for k, (v, lim) in numbers.items() if lim is not None and v > lim}


def window_docs(m, ctx: dict, k: int, acc_dtype=np.float64):
    tags, meters = ctx["source"].second(k, ctx["schedule"].records_in_second(k))
    return m["reference"].reference_docs(ctx["schema"], tags, meters, acc_dtype)


# ---------------------------------------------------------------------------
# the exact rows


@pytest.mark.parametrize("k", range(len(RECORDS)))
def test_partial_rows_merged_by_key_are_the_numpy_rollup(run, m, k):
    ctx, ref = run["ctx"], m["reference"]
    got = ctx["got"][m["gen"].T0 + k]
    r = ref.compare_docs(ctx["schema"], *got, *window_docs(m, ctx, k))
    assert r["docs"] == r["docs_got"] > 0
    assert (r["unpaired_docs"], r["tag_rows_differ"], r["max_lanes_differ"]) == (0, 0, 0)
    assert r["sum_rel_err"] <= ref.SUM_RTOL


def test_merged_documents_are_the_one_device_deployments(run, m):
    cfg = copy.deepcopy(m["tiny"].CONFIG)
    cfg["pipeline"]["accum_batches"] = 8
    one = served_run(m, cfg, RECORDS)
    assert sorted(one["ctx"]["got"]) == sorted(run["ctx"]["got"])
    for w, (tags, meters) in one["ctx"]["got"].items():
        r = m["reference"].compare_docs(run["ctx"]["schema"], *run["ctx"]["got"][w],
                                        tags, np.asarray(meters, np.float64))
        assert r["docs"] == r["docs_got"]
        assert (r["unpaired_docs"], r["tag_rows_differ"], r["max_lanes_differ"]) == (0, 0, 0)
        assert r["sum_rel_err"] <= m["reference"].SUM_RTOL


def test_bfloat16_control_fails_the_sum_limit(run, m):
    """The same comparison against the reference computed in the nearest
    precision below the configuration's float32 is not within the limit."""
    import ml_dtypes

    ctx, k = run["ctx"], 1
    r = m["reference"].compare_docs(
        ctx["schema"], *ctx["got"][m["gen"].T0 + k],
        *window_docs(m, ctx, k, acc_dtype=ml_dtypes.bfloat16))
    assert r["sum_rel_err"] > m["reference"].SUM_RTOL


def test_a_device_hands_over_one_row_a_key_and_the_parts_add_up(run, m):
    numbers = m["pod_partials"].check(run["ctx"])
    assert over_limit(numbers) == set()
    assert numbers["pod.keys_over_one_row_a_device"] == (0, 0)
    assert numbers["pod.partial_packet_tx_differs"] == (0, 0)
    assert numbers["pod.windows_missing_a_device"] == (0, 0)
    per_doc = numbers["pod.partial_rows_per_doc"][0]
    assert 1.0 < per_doc <= CHIPS  # Zipf keys meet several devices a second
    c = run["counters"]
    partials = run["ctx"]["side_outputs"]["pod_partials"]
    rows = sum(t.shape[0] for t, _m, _c in partials.values())
    assert c["pipeline.flush_partial_rows"] == c["pipeline.flushed_doc"] == rows
    for tags, _packet_tx, counts in partials.values():
        assert len(counts) == CHIPS and sum(counts) == tags.shape[0]
    # counted a drain, not a window: the last drain closes three at once
    by_window = sum(sum(n > 0 for n in counts) for _t, _m, counts in partials.values())
    drains = c["pipeline.window_advances"] + 1
    assert by_window - 2 * CHIPS <= c["pipeline.flush_devices_with_rows"] \
        <= min(by_window, CHIPS * drains)


def test_a_device_alone_is_exact_for_the_records_it_was_dealt(run, m):
    """A window's rows of one device, grouped by key, are that device's
    own rollup: keys unique, and summed over the devices every SUM lane
    of a key gives the merged row (checked on the edge documents'
    `packet_tx`, small integers, exactly)."""
    ctx = run["ctx"]
    names = [f["name"] for f in ctx["schema"]["flow_meter"]]
    lane = names.index("packet_tx")
    key = np.flatnonzero([d["key"] for d in ctx["schema"]["doc_tags"]])
    for w, (tags, packet_tx, counts) in ctx["side_outputs"]["pod_partials"].items():
        bounds = np.concatenate([[0], np.cumsum(counts)])
        total = 0.0
        for a, b in zip(bounds, bounds[1:]):
            assert m["pod_partials"].repeated_keys(tags[a:b][:, key]) == 0
            total += float(packet_tx[a:b].astype(np.float64).sum())
        assert total == float(ctx["got"][w][1][:, lane].astype(np.float64).sum())


# ---------------------------------------------------------------------------
# the sketch blocks


def test_merged_block_is_the_reference_sketch_bit_for_bit(run, m):
    numbers = m["sketch_blocks"].check(run["ctx"])
    assert over_limit(numbers) == set(), {k: numbers[k] for k in over_limit(numbers)}
    for k in ("sketch.windows_without_block", "sketch.blocks_without_window",
              "sketch.rows_missing", "sketch.hll_registers_differ",
              "sketch.cms_counters_differ", "sketch.hist_bins_differ"):
        assert numbers[k] == (0, 0), k
    blocks = {int(b.window): b for b in run["ctx"]["side_outputs"]["sketch_blocks"]}
    for k, n in enumerate(RECORDS):
        assert int(blocks[m["gen"].T0 + k].n_updates) == n
    c = run["counters"]
    assert c["pipeline.sketch_rows"] == sum(RECORDS)
    assert c["pipeline.sketch_blocks_closed"] == len(RECORDS)
    assert c["pipeline.sketch_bytes_fetched"] == c["pipeline.sketch_bytes_live"] > 0


def test_each_devices_blocks_alone_merged_give_the_whole(run, host_merged_run):
    by_window: dict = {}
    for blocks in host_merged_run["device_blocks"]:
        for blk in blocks:
            by_window.setdefault(int(blk.window), []).append(blk)
    kept = {int(b.window): b for b in run["ctx"]["side_outputs"]["sketch_blocks"]}
    assert set(kept) <= set(by_window)
    for w, whole in kept.items():
        parts = by_window[w]
        assert 1 <= len(parts) <= CHIPS
        assert sum(int(p.n_updates) for p in parts) == int(whole.n_updates)
        merged = parts[0]
        for p in parts[1:]:
            merged = merged.merge(p)
        np.testing.assert_array_equal(merged.hll, whole.hll)
        np.testing.assert_array_equal(merged.cms, whole.cms)
        np.testing.assert_array_equal(merged.hist, whole.hist)
        # no device's block is the whole: every one lacks another's rows
        if len(parts) > 1:
            assert all(int(p.n_updates) < int(whole.n_updates) for p in parts)


def test_blocks_merged_on_the_devices_are_the_hosts_merge_bit_for_bit(
        run, host_merged_run):
    """PR 39: every window's block as the devices merged it is the one
    the host merged from the devices' blocks, candidates in order too;
    every closed block came off the devices merged, and what the drains
    fetched of them is one block a window."""
    got = run["ctx"]["side_outputs"]["sketch_blocks"]
    want = host_merged_run["ctx"]["side_outputs"]["sketch_blocks"]
    assert [int(b.window) for b in got] == [int(b.window) for b in want]
    for a, b in zip(got, want):
        assert a.n_updates == b.n_updates
        for f in ("hll", "cms", "hist", "tk_hi", "tk_lo", "tk_ida", "tk_idb",
                  "tk_votes"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f), err_msg=f)
    c, c_host = run["counters"], host_merged_run["counters"]
    assert c["pipeline.sketch_blocks_device_merged"] \
        == c["pipeline.sketch_blocks_closed"] == len(RECORDS)
    assert c_host["pipeline.sketch_blocks_device_merged"] == 0
    # each window's block once, where the host fetched each device's
    assert 0 < c["pipeline.sketch_bytes_fetched"] < c_host["pipeline.sketch_bytes_fetched"]


# ---------------------------------------------------------------------------
# the close: compiles, spans, counters


def test_closes_of_eight_document_counts_compile_the_close_once(run):
    closes = [c for c in run["closes"] if c["rows"]]
    assert len({c["rows"] for c in closes}) >= 8
    # everything under flush.* compiled at the warm-up's closes: the count
    # stands still from the run's first close to its last
    assert {c["flush_compiles"] for c in closes} == {closes[0]["flush_compiles"]}
    assert run["counters"]["pipeline.flush_compiles"] == 0
    assert run["counters"]["pipeline.flush_compile_us"] == 0
    # one page program a matrix shape of the close (the exact rows
    # [D, S, C] and the packed blocks [D, P, W]; `pend_win` is under one
    # page), made by the warm-up's closes and by none after them
    before, after = run["page_programs"]
    assert 2 <= before == after


def test_the_closes_span_tree_adds_up(run):
    s, c = run["spans"], run["counters"]
    total = lambda *names: sum(s[n]["total_us"] for n in names)
    drains = s[SPAN_FLUSH_DRAIN]["count"]
    assert drains == c["pipeline.window_advances"] + 1  # + the final drain
    for name in (SPAN_FLUSH_WAIT, SPAN_FLUSH_ROWS, SPAN_FLUSH_SPLIT,
                 SPAN_FLUSH_SKETCH, SPAN_FLUSH_SKETCH_MERGE, SPAN_STATS_FETCH,
                 SPAN_FLUSH_FETCH, SPAN_FLUSH_JOIN):
        assert 0 < s[name]["count"] <= drains, name
    assert s[SPAN_FLUSH_WAIT]["count"] == s[SPAN_STATS_FETCH]["count"] == drains
    assert total(SPAN_FLUSH_WAIT, SPAN_FLUSH_ROWS, SPAN_FLUSH_SPLIT) \
        <= s[SPAN_FLUSH_DRAIN]["total_us"]
    assert total(SPAN_STATS_FETCH, SPAN_FLUSH_RESERVE) <= s[SPAN_FLUSH_WAIT]["total_us"]
    assert total(SPAN_FLUSH_FETCH, SPAN_FLUSH_JOIN) <= s[SPAN_FLUSH_ROWS]["total_us"]
    assert s[SPAN_FLUSH_SKETCH]["total_us"] <= s[SPAN_FLUSH_SPLIT]["total_us"]
    assert s[SPAN_FLUSH_SKETCH_MERGE]["total_us"] <= s[SPAN_FLUSH_SKETCH]["total_us"]
    assert s[SPAN_WINDOW_CLOSE_COLLECTIVE]["count"] == s[SPAN_WINDOW_ADVANCE]["count"] \
        == c["pipeline.window_advances"]
    assert s[SPAN_INGEST_STAGE]["count"] == s[SPAN_INGEST_DISPATCH]["count"] \
        == c["feeder.batches_out"]
    by_id = {r.span_id: r for r in run["records_of"]}
    parents = {SPAN_FLUSH_SKETCH_MERGE: SPAN_FLUSH_SKETCH, SPAN_FLUSH_SKETCH: SPAN_FLUSH_SPLIT,
               SPAN_STATS_FETCH: SPAN_FLUSH_WAIT, SPAN_FLUSH_RESERVE: SPAN_FLUSH_WAIT,
               SPAN_FLUSH_FETCH: SPAN_FLUSH_ROWS, SPAN_FLUSH_JOIN: SPAN_FLUSH_ROWS,
               SPAN_FLUSH_WAIT: SPAN_FLUSH_DRAIN}
    seen = set()
    for r in run["records_of"]:
        if r.name in parents and r.parent_span_id in by_id:
            assert by_id[r.parent_span_id].name == parents[r.name], r
            seen.add(r.name)
    assert seen == set(parents)
    for name in (SPAN_FLUSH_SKETCH_MERGE, SPAN_WINDOW_CLOSE_COLLECTIVE, SPAN_STATS_FETCH):
        assert name not in FLUSH_SPAN_NAMES


def test_the_closes_counters_count(run):
    c = run["counters"]
    live, fetched = c["pipeline.flush_rows_live"], c["pipeline.flush_rows_fetched"]
    assert 0 < live <= fetched and c["pipeline.flush_pages"] > 0
    # over-fetch under one page a device a part a drain
    assert fetched - live < 3 * CHIPS * 256 * (c["pipeline.window_advances"] + 1)
    assert 0 < c["pipeline.flush_rows_reserved"] <= c["pipeline.flush_partial_rows"]
    assert c["pipeline.flush_host_write_bytes"] >= 396 * c["pipeline.flush_rows_reserved"]
    # every reserve and every copied row is a host pass; at these sizes none divides
    assert c["pipeline.flush_host_pass_bytes"] >= c["pipeline.flush_host_write_bytes"]
    assert c["pipeline.flush_pooled_bytes"] == 0
    assert 0 < c["pipeline.stash_live_rows_sum"] <= c["pipeline.stash_capacity_rows_sum"]
    assert 0 < c["pipeline.fold_blocks_run_sum"] <= c["pipeline.fold_blocks_total_sum"]
    assert c["pipeline.doc_in"] >= c["pipeline.flushed_doc"] > 0
    assert c["pipeline.flow_in"] == c["feeder.records_in"] == sum(RECORDS)
    assert (c["pipeline.jit_retraces"], c["pipeline.stash_evictions"]) == (0, 0)


def test_closes_through_the_pool_hand_on_the_same_documents(run, m, monkeypatch):
    """The same deployment with every pass of the close's host half
    divided over four threads (PR 37; as the constants stand these sizes
    stay inline): every window's documents bit for bit, the same rows
    reserved and written."""
    from deepflow_tpu.utils import hostpool

    monkeypatch.setattr(window_mod, "PAGE_ROWS", 256)
    monkeypatch.setattr(hostpool, "WORKERS", 4)
    monkeypatch.setattr(hostpool, "POOL_MIN_BYTES", 0)
    monkeypatch.setattr(hostpool, "_cores", lambda: 64)
    pooled = served_run(m, sharded_config(m), RECORDS)
    assert pooled["guarantees_broken"] == set()
    c, was = pooled["counters"], run["counters"]
    assert 0 < c["pipeline.flush_pooled_bytes"] <= c["pipeline.flush_host_pass_bytes"]
    for k in ("pipeline.flush_rows_reserved", "pipeline.flush_partial_rows",
              "pipeline.flush_host_write_bytes", "pipeline.flush_host_pass_bytes"):
        assert c[k] == was[k], k
    assert sorted(pooled["ctx"]["got"]) == sorted(run["ctx"]["got"])
    for w, (tags, meters) in run["ctx"]["got"].items():
        got_tags, got_meters = pooled["ctx"]["got"][w]
        np.testing.assert_array_equal(got_tags, tags)
        np.testing.assert_array_equal(np.asarray(got_meters).view(np.uint32),
                                      np.asarray(meters).view(np.uint32))
    assert set(pooled["spans"]) == set(run["spans"])


def test_a_forced_retrace_is_counted(m):
    from deepflow_tpu.ingest.replay import SyntheticFlowGen
    from deepflow_tpu.datamodel.batch import FlowBatch

    served = m["sut"].build(sharded_config(m))
    try:
        swm = served.swm
        gen = SyntheticFlowGen(num_tuples=50, seed=3)
        for rows in served.buckets:
            fb = FlowBatch.from_records(gen.records(rows, 1_700_000_000))
            swm.ingest(dict(fb.tags), fb.meters, fb.valid)
        c = swm.get_counters()
        assert (c["jit_compiles"], c["jit_retraces"]) == (len(served.buckets), 0)
        # a batch shape no bucket has: the step compiles again
        fb = FlowBatch.from_records(gen.records(4 * CHIPS, 1_700_000_000))
        swm.ingest(dict(fb.tags), fb.meters, fb.valid)
        assert swm.get_counters()["jit_retraces"] == 1
    finally:
        served.close()
