"""The rest of a run with the timed path broken underneath: `correct`
must come out false. The look for a chip is skipped (run_cell is what
follows it); everything else is a run as the driver makes it, at the tiny
size. One case for each fault a one-chip cell can have (there is no
exchange between chips to leave out)."""

import numpy as np
import pytest

import run as chipbench_run
import tiny


def half_of_each_batch_left_out(served):
    """The sink takes the first half of every chunk's rows; the rest never
    reach the device, and no counter says so."""
    sink = served.feeder.sink
    emit = sink.emit

    def broken(chunks, rows, bucket, shed):
        kept = [c.split(max(c.rows // 2, 1))[0] for c in chunks]
        return emit(kept, sum(c.rows for c in kept), bucket, shed)

    sink.emit = broken


def every_other_step_returns_its_state_unchanged(served):
    """Every second staged batch is dropped where the fused step would
    have run: the window state stays as it was."""
    pipe = served.pipe
    ingest_staged = pipe.ingest_staged
    calls = {"n": 0}

    def broken(staged, feeder_shed=0):
        calls["n"] += 1
        if calls["n"] > 8 and calls["n"] % 2:  # after the warm-up's batches
            return []
        return ingest_staged(staged, feeder_shed=feeder_shed)

    pipe.ingest_staged = broken


def an_answer_altered_where_it_is_produced(served):
    """One SUM lane of one document of every flushed window is off by one
    part in 10^4: no count moves, only the row-by-row comparison sees it."""
    pipe = served.pipe
    to_docbatch = pipe._to_docbatch

    def broken(f):
        db = to_docbatch(f)
        meters = np.array(db.meters)
        meters[0, 2] = meters[0, 2] * np.float32(1.0001) + 1
        db.meters = meters
        return db

    pipe._to_docbatch = broken


@pytest.mark.parametrize("fault,numbers", [
    (half_of_each_batch_left_out, {"edge_packet_tx_gap", "unpaired_docs"}),
    (every_other_step_returns_its_state_unchanged, {"edge_packet_tx_gap"}),
    (an_answer_altered_where_it_is_produced, {"sum_rel_err"}),
])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault, numbers):
    out = chipbench_run.run_cell(
        tiny.spec(tmp_path, tiny.SATURATE), seed=2**31 + 77, seconds=4.0,
        trace=False, workdir=str(tmp_path), on_built=fault,
        device={"platform": "cpu", "kind": "cpu", "count": 1})
    assert out["correct"] is False
    over = {k for k, c in out["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]}
    assert numbers & over, out["checks"]
