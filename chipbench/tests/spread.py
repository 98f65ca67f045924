#!/usr/bin/env python3
"""By hand: the spread of each metric over the sets that seeds.sh wrote.

    python3 chipbench/tests/spread.py chiprun_out/<cell>.set1.jsonl chiprun_out/<cell>.set2.jsonl

A spread is the distance between the first and the third quartile
(statistics.quantiles, n=4) as a share of the median; the bound follows
from the wider of the two sets' spreads, times five.
"""

import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    sets = []
    for path in sys.argv[1:]:
        with open(path) as f:
            sets.append([json.loads(line) for line in f if line.strip()])
    for name in sets[0][0]["metrics"]:
        row = []
        for runs in sets:
            values = [r["metrics"][name]["value"] for r in runs]
            row.append({"n": len(values), "median": statistics.median(values),
                        "spread": spread(values), "values": values})
        widest = max(r["spread"] for r in row)
        print(json.dumps({"metric": name, "widest_spread": widest,
                          "bound_at_5x": 5 * widest, "sets": row}))
    print(json.dumps({"correct": [all(r["correct"] for r in runs) for runs in sets]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
