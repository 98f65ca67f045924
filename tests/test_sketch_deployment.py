"""PR 33: the sketch deployment (`chipbench/deployments/l4_sketch.py`) on
the served path, Receiver -> queues -> FeederRuntime -> PipelineFeedSink ->
L4Pipeline with the plane on, against the plain reference sketch of
`chipbench/checks/sketch_blocks.py`: bit for bit at tiny sizes and at the
cell's own plane shapes, through both buckets; the check passes on a sound
run and does not once a register, a counter, a block or 1% of the rows is
wrong."""

import copy
import dataclasses
import os
import socket
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(ROOT, "chipbench")
SEED = 2**31 + 33

# (num_groups, hll_precision): the default plane, the cell's, a degenerate one
PLANES = [(16, 12), (512, 14), (3, 4)]
# records an event-second: under the small bucket (512 rows), and several
# large ones (2,048 rows) with a tail
BUCKETS = {"small_bucket": [256, 400, 400, 300, 400, 400, 350, 400],
           "both_buckets": [256, 5000, 5000, 4600, 5000, 5000, 4100, 5000]}


@pytest.fixture(scope="module")
def m():
    """chipbench's own modules (it is no package: its files import each
    other by bare name) and the check, loaded as run.py loads it."""
    added = [p for p in (CHIPBENCH, os.path.join(CHIPBENCH, "tests"))
             if p not in sys.path]
    sys.path[:0] = added
    import gen
    import sut
    import tiny
    import wire

    check = sut.load_named("check", "sketch_blocks", [os.path.join(CHIPBENCH, "checks")])
    yield {"gen": gen, "sut": sut, "tiny": tiny, "wire": wire, "check": check}
    for p in added:
        sys.path.remove(p)


def tiny_config(m, groups: int, precision: int) -> dict:
    """tiny.py's deployment with the cell's builder, check and plane keys;
    the count-min is narrow enough to collide, the limits are the
    precision's own (a mean under 1% is p = 14's; 16 registers are no
    estimator, their plane is here for its shapes)."""
    cfg = copy.deepcopy(m["tiny"].CONFIG)
    cfg.update(name="tiny_sketch", built_by="l4_sketch", checks=["sketch_blocks"])
    cfg["pipeline"]["sketch"] = {
        "num_groups": groups, "hll_precision": precision,
        "cms_depth": 4, "cms_width": 1024,
        "hist_bins": 256, "hist_vmin": 1.0, "hist_gamma": 1.04,
        "topk_rows": 2, "topk_cols": 512, "pool": None, "pending": 4,
        "distinct_mean_rel_err": 3.0 / 2 ** (precision / 2),
        "distinct_worst_sigmas": 3.0 if precision >= 8 else 6.0}
    return cfg


class Seconds:
    """What the check asks of a schedule."""

    def __init__(self, records: list):
        self.records = records

    def records_in_second(self, k: int) -> int:
        return self.records[k]


def served_run(m, config: dict, records: list) -> dict:
    """One event-second after another over TCP into the deployment
    `sut.build` makes of `config`, pumped until taken, then flushed and
    drained: the check's `ctx`, as run.py would hand it over."""
    gen = m["gen"]
    schema = gen.load_schema()
    source = gen.FlowSource(schema, config["population"], SEED)
    served = m["sut"].build(config)
    try:
        closed, sent = set(), 0
        base = served.feeder.get_counters()["records_in"]

        def take(out):
            closed.update(int(db.timestamp[0]) for db in served.documents(out))

        with socket.create_connection(("127.0.0.1", served.port), timeout=30) as sock:
            for k, n in enumerate(records):
                tags, meters = source.second(k, n)
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
        in_window = set(closed)
        take(served.drain())
        counters = served.counters()
        return {
            "ctx": {"schema": schema, "source": source, "schedule": Seconds(records),
                    "sent_seconds": [{"second": k, "records": n}
                                     for k, n in enumerate(records)],
                    "got": {}, "closed_in_window": in_window, "seed": SEED,
                    "config": config, "side_outputs": served.side_outputs()},
            "counters": counters, "closed": closed,
            "guarantees_broken": {k for k in served.guarantee_counters if counters[k]},
            "buckets_used": served.pipe.staging.allocated // 3,
        }
    finally:
        served.close()


@pytest.fixture(scope="module", params=[
    pytest.param((plane, bucket), id=f"g{plane[0]}_p{plane[1]}-{bucket}")
    for plane in PLANES for bucket in BUCKETS])
def run(request, m):
    (groups, precision), bucket = request.param
    out = served_run(m, tiny_config(m, groups, precision), BUCKETS[bucket])
    assert out["guarantees_broken"] == set()
    out["bucket"] = bucket
    return out


def window_records(m, ctx: dict, k: int):
    tags, meters = ctx["source"].second(k, ctx["schedule"].records_in_second(k))
    return m["check"].Records(ctx["schema"], tags, meters,
                              ctx["config"]["pipeline"]["sketch"])


def over_limit(numbers: dict) -> set:
    return {k for k, (v, lim) in numbers.items() if lim is not None and v > lim}


def with_blocks(ctx: dict, blocks: list) -> dict:
    return {**ctx, "side_outputs": {"sketch_blocks": blocks}}


# ---------------------------------------------------------------------------
# the served path against the plain reference


def test_every_block_equals_the_reference_sketch_bit_for_bit(run, m):
    ctx, check = run["ctx"], m["check"]
    s = ctx["config"]["pipeline"]["sketch"]
    blocks = {b.window: b for b in ctx["side_outputs"]["sketch_blocks"]}
    assert sorted(blocks) == sorted(run["closed"]) \
        == [m["gen"].T0 + k for k in range(len(BUCKETS[run["bucket"]]))]
    assert run["buckets_used"] == (1 if run["bucket"] == "small_bucket" else 2)
    for k, n in enumerate(BUCKETS[run["bucket"]]):
        blk = blocks[m["gen"].T0 + k]
        r = window_records(m, ctx, k)
        want = check.reference_sketch(r, s)
        assert blk.n_updates == n == r.n
        assert np.array_equal(blk.hll, want["hll"])
        assert np.array_equal(blk.cms, want["cms"])
        assert check.hist_differ(np.asarray(blk.hist, np.int64), want["hist"],
                                 want["hist_at_edge"]) == 0
        assert int(blk.hist.sum()) == n  # every record has a latency
    # every record passed the plane, none twice; the exact path saw them too
    c = run["counters"]
    assert c["pipeline.sketch_rows"] == c["feeder.records_in"] == sum(BUCKETS[run["bucket"]])
    assert c["pipeline.sketch_blocks_closed"] == len(blocks)
    assert c["pipeline.sketch_bytes_fetched"] == c["pipeline.sketch_bytes_live"] > 0


def test_the_check_passes_on_a_sound_run(run, m):
    numbers = m["check"].check(run["ctx"])
    assert over_limit(numbers) == set(), {k: numbers[k] for k in over_limit(numbers)}
    assert numbers["sketch.windows_compared"][0] == 4
    assert numbers["sketch.blocks"][0] == len(BUCKETS[run["bucket"]])
    assert numbers["sketch.run_distinct_exact"][0] > 250  # of tiny.py's 300 flows


def lower_one_register(blocks, w):
    blk = next(b for b in blocks if b.window == w)
    g, i = np.argwhere(blk.hll > 0)[0]
    blk.hll[g, i] -= 1


def raise_one_counter(blocks, w):
    next(b for b in blocks if b.window == w).cms[1, 7] += 1


def drop_one_block(blocks, w):
    blocks[:] = [b for b in blocks if b.window != w]


@pytest.mark.parametrize("fault,fails", [
    (lower_one_register, {"sketch.hll_registers_differ"}),
    (raise_one_counter, {"sketch.cms_counters_differ"}),
    (drop_one_block, {"sketch.windows_without_block"}),
], ids=["one_register_lowered", "one_counter_raised", "one_block_dropped"])
def test_the_check_does_not_pass_a_corrupted_block(run, m, fault, fails):
    ctx = run["ctx"]
    blocks = [dataclasses.replace(b, hll=b.hll.copy(), cms=b.cms.copy())
              for b in ctx["side_outputs"]["sketch_blocks"]]
    # the last window closed inside the run: always one of the sampled
    fault(blocks, max(ctx["closed_in_window"]))
    assert fails <= over_limit(m["check"].check(with_blocks(ctx, blocks)))


def test_rows_masked_out_of_the_plane_do_not_pass(m, monkeypatch):
    """1% of every batch's rows kept from the plane and from nothing
    else (chipbench/tests/control_sketch.py's `mask`, at tiny sizes)."""
    import control_sketch

    from deepflow_tpu.aggregator import pipeline

    monkeypatch.setattr(pipeline, "sketch_plane_step", pipeline.sketch_plane_step)
    control_sketch.mask_one_row_in_a_hundred()
    out = served_run(m, tiny_config(m, 16, 12), BUCKETS["both_buckets"])
    over = over_limit(m["check"].check(out["ctx"]))
    assert control_sketch.MUST_FAIL["mask"] <= over
    sent = sum(BUCKETS["both_buckets"])
    assert 0 < sent - out["counters"]["pipeline.sketch_rows"] <= sent // 50
    assert out["counters"]["pipeline.doc_in"] > 0  # the exact path took every row


# ---------------------------------------------------------------------------
# the reference's own algebra, and the blocks' merge against it


def test_union_of_the_groups_registers_is_the_windows(run, m):
    ctx, check = run["ctx"], m["check"]
    s = ctx["config"]["pipeline"]["sketch"]
    blocks = {b.window: b for b in ctx["side_outputs"]["sketch_blocks"]}
    for k in (1, 3):
        r = window_records(m, ctx, k)
        by_group = check.reference_sketch(r, s)["hll"]
        r.group[:] = 0
        whole = check.reference_sketch(r, {**s, "num_groups": 1})["hll"]
        assert np.array_equal(by_group.max(axis=0), whole[0])
        assert np.array_equal(blocks[m["gen"].T0 + k].hll.max(axis=0), whole[0])


def test_merge_over_windows_equals_the_reference_over_their_records(run, m):
    ctx, check = run["ctx"], m["check"]
    s = ctx["config"]["pipeline"]["sketch"]
    seconds = range(len(BUCKETS[run["bucket"]]))
    rs = [window_records(m, ctx, k) for k in seconds]
    union = copy.copy(rs[0])
    for name in ("ip0", "key_cols"):
        setattr(union, name, np.concatenate([getattr(r, name) for r in rs], axis=1))
    for name in ("client_hi", "client_lo", "key_hi", "key_lo", "group", "weight",
                 "rtt", "rtt_valid"):
        setattr(union, name, np.concatenate([getattr(r, name) for r in rs]))
    want = check.reference_sketch(union, s)
    merged = None
    for b in sorted(ctx["side_outputs"]["sketch_blocks"], key=lambda b: b.window):
        merged = b if merged is None else check._union(merged, b)
    assert merged.n_updates == sum(r.n for r in rs)
    assert np.array_equal(merged.hll, want["hll"])
    assert np.array_equal(merged.cms, want["cms"])
    assert check.hist_differ(np.asarray(merged.hist, np.int64), want["hist"],
                             want["hist_at_edge"]) == 0
    exact = check.distinct_rows(union.ip0).shape[1]
    sigma = 1.04 / 2 ** (int(s["hll_precision"]) / 2)
    assert abs(merged.distinct() - exact) / exact <= 3 * sigma


# ---------------------------------------------------------------------------
# the sizing rule of `pending`, at tiny sizes


@pytest.mark.parametrize("pending,lost", [(3, 0), (2, 1)],
                         ids=["ring_less_one_holds", "one_fewer_drops_a_block"])
def test_pending_holds_what_the_last_drain_closes_at_once(m, pending, lost):
    """The last drain closes the ring's three open windows in one program
    and the host empties `pend` after every dispatch that closed one, so
    ring - 1 blocks is what `pend` must hold and one fewer loses a block
    (control_sketch.py's `pending2` at the cell's size)."""
    cfg = tiny_config(m, 16, 12)
    cfg["pipeline"]["sketch"]["pending"] = pending
    out = served_run(m, cfg, BUCKETS["small_bucket"])
    numbers = m["check"].check(out["ctx"])
    assert numbers["sketch.windows_without_block"][0] == lost
    assert (over_limit(numbers) == set()) == (not lost)
    # the dropped block's rows are counted shed on the device, in a lane the
    # host reads with the next batch's counter block: after the last drain
    # there is none, so it is the check that sees the block missing
    assert out["guarantees_broken"] == set()


def test_a_program_whose_plane_counts_prereduced_rows_is_refused_at_once(m, monkeypatch):
    """What the parent commit does with the cell once the benchmark's files
    are laid over it: the builder is there, the program's plane sits behind
    the pre-reduce, and `sut.build` raises before anything is started."""
    from deepflow_tpu.aggregator import pipeline

    assert pipeline.SKETCH_ROWS_ARE_RECORDS is True
    monkeypatch.delattr(pipeline, "SKETCH_ROWS_ARE_RECORDS")
    with pytest.raises(RuntimeError, match="cannot run l4_sketch.*SKETCH_ROWS_ARE_RECORDS"):
        m["sut"].build(tiny_config(m, 16, 12))
