#!/usr/bin/env python3
"""By hand, on the CPU (counts): pairs of a population's flows that share a
document key and differ in `pod_id`.

    python3 chipbench/tests/pod_id_pairs.py <first seed> <one past the last> [tuples]

`gen.py` draws `pod_id`, a document tag that is no part of the document
key, independently of a flow's addresses. Two flows that share a
single-side document key (address, l3_epc_id, gpid, protocol, with the
side's direction known; the server side's also holds the server port) then
fold into one document whose `pod_id` is whichever flow's came first: by
arrival in the program, by sort order in the plain reference, and the base
check's `tag_rows_differ` reads it. At 4,194,240 flows a population seed
has ~5 such pairs (`l4_1s_1m`'s 1,048,560 flows under seed 1 have none);
`l4_1s_4m_x4_sketch` names the smallest seed that has none. Prints, a
seed, the pairs on the client side, on the server side and among the edge
documents (which hold both addresses and both gpids), and stops at the
first seed with none.
"""

import sys
import time

import numpy as np
sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))
import gen
schema = gen.load_schema()
def collisions(seed, n=4194240):
    src = gen.FlowSource(schema, {"tuples": n, "keys": "uniform", "seed": seed}, 0)
    p = src.pop
    pod = p["pod_id"].astype(np.uint64)
    out = []
    for name, fields, live in (
        ("single0", ("ip0_w3", "l3_epc_id", "gpid0", "protocol"), p["direction0"] != 0),
        ("single1", ("ip1_w3", "l3_epc_id1", "gpid1", "protocol", "server_port"), p["direction1"] != 0),
    ):
        k = np.zeros(n, np.uint64)
        for f in fields:
            v = p[f].astype(np.uint64)
            if f.startswith("ip"):
                v = v - np.uint64(0x0A000000)
                assert int(v.max()) < (1 << 24)
            bits = {"server_port": 16, "protocol": 5, "gpid0": 10, "gpid1": 10, "l3_epc_id": 6, "l3_epc_id1": 6}.get(f, 24)
            assert int(v.max()) < (1 << bits)
            k = (k << np.uint64(bits)) | v
        k, q = k[live], pod[live]
        order = np.argsort(k, kind="stable")
        ks, qs = k[order], q[order]
        same = ks[1:] == ks[:-1]
        out.append(int((same & (qs[1:] != qs[:-1])).sum()))
    # edge documents: both addresses, both gpids, both l3_epc_ids, port, protocol
    a = (p["ip0_w3"].astype(np.uint64) << np.uint64(32)) | p["ip1_w3"].astype(np.uint64)
    b = np.zeros(n, np.uint64)
    for f, bits in (("l3_epc_id", 6), ("l3_epc_id1", 6), ("gpid0", 10), ("gpid1", 10),
                    ("protocol", 5), ("server_port", 16)):
        b = (b << np.uint64(bits)) | p[f].astype(np.uint64)
    order = np.lexsort((b, a))
    same = (a[order][1:] == a[order][:-1]) & (b[order][1:] == b[order][:-1])
    out.append(int((same & (pod[order][1:] != pod[order][:-1])).sum()))
    return out
if __name__ == "__main__":
    lo, hi = int(sys.argv[1]), int(sys.argv[2])
    tuples = int(sys.argv[3]) if len(sys.argv) > 3 else 4194240
    for seed in range(lo, hi):
        t = time.time()
        c = collisions(seed, tuples)
        print(seed, c, round(time.time() - t, 1), flush=True)
        if sum(c) == 0:
            print("FOUND", seed, flush=True)
            break
