#!/usr/bin/env python3
"""By hand, on the chip: one traced run of a cell that also writes the
profiler slice's extracted events (what trace_reduce.reduce reads) to a
file; tests/data/trace_events.json is such a file, cut down.

    python3 chipbench/tests/dump_trace.py <path> --workload <cell> --seed <n> --seconds <s> --trace 1
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run as chipbench_run  # noqa: E402
import trace_reduce  # noqa: E402

if __name__ == "__main__":
    path, extract = sys.argv[1], trace_reduce.extract

    def extract_and_write(xplane):
        events = extract(xplane)
        with open(path, "w") as f:
            json.dump(events, f)
        return events

    trace_reduce.extract = extract_and_write
    sys.exit(chipbench_run.main(sys.argv[2:]))
