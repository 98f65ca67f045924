"""The close's passes over host memory, divided over a few threads.

A window close makes two passes over hundreds of megabytes of host
memory on the feed thread, with the devices idle: it touches the
destination it reserved for its rows (one page fault every 4 KiB,
kernel work) and it copies the fetched pages' live cuts into it (the
first CPU read of what the transfer just wrote). Both are plain memory
work that NumPy does with the interpreter lock released, so a pass that
is large enough is cut into contiguous pieces, one a worker; the calling
thread takes the first piece itself and then waits for the others, so
when an entry point returns every byte has been written and nothing
writes to the memory again.

Two entry points, `touched_rows` and `copy_cuts`, serve both window
managers (aggregator/window.py, parallel/sharded.py). Each also returns
how many workers shared the pass (1: the caller alone, the code as it was
before the pool), which is what the managers' `flush_pooled_bytes`
counts.

The worker threads are this module's: daemon threads started the first
time a pass divides, never on import and never where the process may
run on one core. All a worker ever touches is the `uint32` host memory
handed to it for one pass: no span, no counter, no tracer, no JAX
object. An exception in a worker is raised on the calling thread once
every worker has finished. Nothing here is configurable: the two
constants were read on the chip's hosts.
"""

from __future__ import annotations

import os
import queue
import threading
from concurrent.futures import Future
from functools import partial

import numpy as np

# Workers a divided pass is dealt over, the calling thread among them,
# capped by the cores the process may run on. Read on the one-chip host
# (13 cores; PERF.md §6, PR 37): touching 291 MB takes 321 / 189 / 134 /
# 106 ms on 1 / 2 / 4 / 8 threads (1,262 MB: 1,369 / 800 / 584 / 453),
# copying 47 fetched pages into it 30.0 / 16.9 / 11.3 / 9.1 ms (204
# pages: 117 / 67 / 44 / 33). The faults of one mapping divide less well
# than the copy; both still gain from the fifth to the eighth thread.
WORKERS = 8

# A pass under this many bytes is run by the caller alone. On 8 threads
# a pass of 12 MB costs what it costs inline (touch 12.3 against 11.4
# ms, a copy of two pages 3.0 against 1.9), one of 25 MB a third (7.0
# against 23.2; 1.4 against 5.0): under ~8 MB a worker, about a fetched
# page, the hand-off eats the gain. 8 workers x 8 MiB; what a pass under
# it could save is under 50 ms of a touch that the device's fold hides.
POOL_MIN_BYTES = 64 << 20

_TOUCH_STRIDE = 1024  # u32 words in a 4 KiB page

_jobs: queue.SimpleQueue = queue.SimpleQueue()
_threads: list[threading.Thread] = []
_lock = threading.Lock()


def _serve() -> None:
    while True:
        piece, done = _jobs.get()
        try:
            piece()
        except BaseException as e:  # handed to the caller, which raises it
            done.set_exception(e)
        else:
            done.set_result(None)
        # an idle worker holds on to nothing: a piece refers to the
        # reserve or to a close's fetched pages
        del piece, done


def _cores() -> int:
    return len(os.sched_getaffinity(0))


def _shares(nbytes: int, pieces: int) -> int:
    """Workers a pass of `nbytes` that can be cut into `pieces` pieces
    is dealt over."""
    if nbytes < POOL_MIN_BYTES:
        return 1
    return min(WORKERS, _cores(), pieces)


def _run(pieces: list) -> None:
    """Every piece run once: the first by the caller, the others by the
    pool's threads. Returns, or raises a piece's exception, only when
    all of them have finished."""
    with _lock:
        while len(_threads) < len(pieces) - 1:
            t = threading.Thread(target=_serve, daemon=True,
                                 name=f"hostpool-{len(_threads)}")
            t.start()
            _threads.append(t)
    waits = []
    for piece in pieces[1:]:
        waits.append(Future())
        _jobs.put((piece, waits[-1]))
    try:
        pieces[0]()
    finally:
        errors = [w.exception() for w in waits]  # waits for each
    for e in errors:
        if e is not None:
            raise e


def _touch(flat: np.ndarray, a: int, b: int) -> None:
    flat[a:b:_TOUCH_STRIDE] = 0


def touched_rows(rows: int, width: int, order: str) -> tuple[np.ndarray, int]:
    """A fresh `[rows, width]` u32 array in memory order `order` whose
    memory is already the process's, and the workers that touched it:
    one word written every 4 KiB, so every page is faulted in (and
    zeroed by the kernel) here and not under the first copy into it. On
    the chip's host a fault is ~3.6 us, ~0.93 ms a MB for one thread
    (PERF.md §6, PR 34); an allocation of this size is mapped anew every
    time. A worker's piece is one contiguous run of whole pages."""
    flat = np.empty(rows * width, np.uint32)
    pages = -(-flat.size // _TOUCH_STRIDE)
    k = _shares(flat.nbytes, pages)
    if k == 1:
        _touch(flat, 0, flat.size)
    else:
        at = [pages * i // k * _TOUCH_STRIDE for i in range(k + 1)]
        _run([partial(_touch, flat, a, b) for a, b in zip(at, at[1:])])
    return flat.reshape((rows, width), order=order), k


def copy_cuts(cuts: list, out: np.ndarray, axis: int = 0) -> int:
    """`np.concatenate(cuts, axis=axis, out=out)`, every cut written to
    the offset concatenate gives it; returns the workers that shared the
    copy. The cuts are dealt in contiguous runs of about equal rows; a
    worker's run is one `np.concatenate` into its slice of `out`."""
    k = _shares(out.nbytes, len(cuts))
    if k == 1:
        np.concatenate(cuts, axis=axis, out=out)
        return 1
    ends = np.cumsum([c.shape[axis] for c in cuts])
    # run j ends with the first cut that reaches j / k of the rows
    stops = np.unique(np.searchsorted(ends, -(-ends[-1] * np.arange(1, k + 1) // k)) + 1)
    at = [0, *ends.tolist()]  # where cut i starts in `out`
    lead = (slice(None),) * axis
    pieces = [
        partial(np.concatenate, cuts[a:b], axis=axis,
                out=out[lead + (slice(at[a], at[b]),)])
        for a, b in zip([0, *stops.tolist()], stops.tolist())
    ]
    _run(pieces)
    return len(pieces)
