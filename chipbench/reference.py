"""The plain reference: one window's flow records -> its documents, and
the comparison that decides `correct`.

NumPy only; imports nothing of the program and takes nothing the program
made. Copied from `chip_smoke.py` (`_group_rows`, `_group_reduce`,
`reference_docs`, `compare_docs`; PR 22 proved them on the chip against
the program's scalar oracle), with the schema read from schema.json and
the comparison returning its numbers instead of raising, so that every
run can print each beside its limit.

Semantics (the guarantees of both deployments): records group by their
whole tag row, fan out to the <=4 documents a flow yields (single-side x2,
edge x2), and group again by document key; SUM lanes add, MAX lanes take
the maximum. Keys, counts and MAX lanes are exact; SUM lanes, f32 on the
device, lie within SUM_RTOL of this f64 sum.
"""

from __future__ import annotations

import numpy as np

SUM_RTOL = 1e-6  # stated by the configuration: f32 tree-order sums vs f64


class OutsideDomain(ValueError):
    """The records are not of the kind this reference's fanout covers."""


def _group_rows(cols: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Sort n rows given COLUMN-major (`cols` [k, n] u32) and find the
    groups of equal rows: (order [n], starts [g]). Exact, no hashing: the
    columns that vary are bit-packed, by their own widths, into as few u64
    words as hold them, and np.lexsort orders the words."""
    n = cols.shape[1]
    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    top = cols.max(axis=1)
    words, cur, used = [], np.zeros(n, np.uint64), 0
    for c in np.flatnonzero(top != cols.min(axis=1)):
        bits = int(top[c]).bit_length()
        if used + bits > 64:
            words.append(cur)
            cur, used = np.zeros(n, np.uint64), 0
        cur = (cur << np.uint64(bits)) | cols[c].astype(np.uint64)
        used += bits
    words.append(cur)
    order = np.lexsort(words[::-1])
    differs = np.zeros(n, bool)
    differs[0] = True
    for w in words:
        ws = w[order]
        differs[1:] |= ws[1:] != ws[:-1]
    return order, np.flatnonzero(differs)


def _group_reduce(cols, meters, sum_mask, acc_dtype=np.float64):
    """Group `meters` [n, M] by the rows `cols` [k, n] holds column-major
    and reduce each lane in `acc_dtype` with np.add/np.maximum.reduceat.
    Returns (index of each group's first row [g], reduced [g, M] f64).
    Lanes that are zero everywhere skip the reduce."""
    order, starts = _group_rows(cols)
    out = np.zeros((starts.size, meters.shape[1]), np.float64)
    for c in np.flatnonzero(meters.any(axis=0)):
        col = meters[:, c][order].astype(acc_dtype)
        fn = np.add if sum_mask[c] else np.maximum
        out[:, c] = fn.reduceat(col, starts).astype(np.float64)
    return order[starts], out


def reference_docs(schema: dict, tags: np.ndarray, meters: np.ndarray,
                   acc_dtype=np.float64) -> tuple[np.ndarray, np.ndarray]:
    """Expected documents of ONE window's records (`tags` [T, n] u32 in
    `flow_record_tag_fields` order, `meters` [n, M]): (doc tags [g, D] u32
    in `doc_tags` order, doc meters [g, M] f64).

    `acc_dtype` below f64 is the low-precision CONTROL (never a run's
    reference): the same arithmetic with every sum and maximum held in
    that type."""
    fields = schema["flow_record_tag_fields"]
    fm = schema["flow_meter"]
    doc_names = [d["name"] for d in schema["doc_tags"]]
    key_mask = np.array([d["key"] for d in schema["doc_tags"]])
    e, fan = schema["enums"], schema["fanout"]
    sum_mask = np.array([f["op"] == "sum" for f in fm])
    first, m_u = _group_reduce(tags, meters, sum_mask, acc_dtype)
    r = {f: tags[i][first] for i, f in enumerate(fields)}

    in_domain = (
        (r["signal_source"] == e["signal_source_packet"])
        & (r["is_active_host0"] == 1) & (r["is_active_host1"] == 1)
        & (r["is_active_service"] == 1)
        & ((r["protocol"] == 6) | (r["protocol"] == 17))
        & (r["is_vip0"] == 0) & (r["is_vip1"] == 0)
        & (r["l3_epc_id"] != 0xFFFE) & (r["l3_epc_id"] < 0x8000)
        & (r["l3_epc_id1"] < 0x8000)
        & ((r["direction0"] & 0xF8) == 0) & ((r["direction1"] & 0xF8) == 0)
        & (r["direction0"] != 3) & (r["direction1"] != 3)  # LOCAL_TO_LOCAL
    )
    if not bool(in_domain.all()):
        raise OutsideDomain(f"{int((~in_domain).sum())} records outside the "
                            "reference fanout's domain")

    # meter as seen from side 1: tx/rx lanes swap, zero_on_reverse zero
    names = [f["name"] for f in fm]
    rev = np.arange(len(fm))
    zero = np.zeros(len(fm), bool)
    for i, f in enumerate(fm):
        if f["reverse_with"]:
            rev[i] = names.index(f["reverse_with"])
        zero[i] = f["zero_on_reverse"]
    m_rev = np.where(zero[None, :], 0.0, m_u[:, rev])

    n = first.size
    ix = doc_names.index

    def doc(rows, **cols):  # column-major [D, docs]
        t = np.zeros((len(doc_names), int(rows.sum())), np.uint32)
        shared = dict(
            meter_id=e["meter_id_flow"], global_thread_id=fan["global_thread_id"],
            agent_id=fan["agent_id"], is_ipv6=r["is_ipv6"], protocol=r["protocol"],
            tap_type=r["tap_type"], signal_source=r["signal_source"],
            pod_id=r["pod_id"],
        )
        for k, v in {**shared, **cols}.items():
            t[ix(k)] = v[rows] if isinstance(v, np.ndarray) else v
        return t

    d0, d1 = r["direction0"], r["direction1"]
    ip0 = {f"ip0_w{w}": r[f"ip0_w{w}"] for w in range(4)}
    ip1_as_0 = {f"ip0_w{w}": r[f"ip1_w{w}"] for w in range(4)}
    ip1 = {f"ip1_w{w}": r[f"ip1_w{w}"] for w in range(4)}
    port = r["server_port"]
    out_t, out_m = [], []
    s0 = d0 != 0
    out_t.append(doc(s0, code_id=e["code_single_ip_port"], **ip0,
                     l3_epc_id=r["l3_epc_id"], direction=d0, tap_side=d0,
                     server_port=0, gpid0=r["gpid0"]))
    out_m.append(m_u[s0])
    s1 = d1 != 0
    out_t.append(doc(s1, code_id=e["code_single_ip_port"], **ip1_as_0,
                     l3_epc_id=r["l3_epc_id1"], direction=d1, tap_side=d1,
                     server_port=port, gpid0=r["gpid1"]))
    out_m.append(m_rev[s1])
    # edge documents: one per known direction; a flow with neither gets
    # one with direction NONE (0)
    edge = dict(code_id=e["code_edge_ip_port"], **ip0, **ip1,
                l3_epc_id=r["l3_epc_id"], l3_epc_id1=r["l3_epc_id1"],
                server_port=port, tap_port=r["tap_port"],
                gpid0=r["gpid0"], gpid1=r["gpid1"])
    for rows, d in ((s0, d0), (s1, d1), (~s0 & ~s1, np.zeros(n, np.uint32))):
        out_t.append(doc(rows, direction=d, tap_side=d, **edge))
        out_m.append(m_u[rows])
    dt = np.concatenate(out_t, axis=1)
    dm = np.concatenate(out_m)
    first, red = _group_reduce(dt[key_mask], dm, sum_mask, acc_dtype)
    return np.ascontiguousarray(dt[:, first].T), red


def compare_docs(schema: dict, got_tags, got_meters, want_tags, want_meters) -> dict:
    """The numbers `correct` is decided on, for one window: documents
    that have no partner with the same key on the other side, paired
    documents whose tag rows or MAX lanes differ, and the widest relative
    gap of a SUM lane (against max(|reference|, 1))."""
    key_cols = np.flatnonzero([d["key"] for d in schema["doc_tags"]])
    is_sum = np.array([f["op"] == "sum" for f in schema["flow_meter"]])
    ng, nw = got_tags.shape[0], want_tags.shape[0]
    both = np.ascontiguousarray(
        np.concatenate([got_tags[:, key_cols], want_tags[:, key_cols]]).T)
    order, starts = _group_rows(both)
    size = np.diff(np.append(starts, ng + nw))
    from_got = np.add.reduceat((order < ng).astype(np.int64), starts) \
        if starts.size else np.zeros(0, np.int64)
    paired = (size == 2) & (from_got == 1)
    unpaired_docs = int(size[~paired].sum())
    p = starts[paired]
    a, b = order[p], order[p + 1]
    go = np.where(a < ng, a, b)
    wo = np.where(a < ng, b, a) - ng
    tag_rows_differ = int((got_tags[go] != want_tags[wo]).any(axis=1).sum())
    g = got_meters[go].astype(np.float64)
    w = want_meters[wo]
    max_lanes_differ = int((g[:, ~is_sum] != w[:, ~is_sum]).sum())
    err = np.abs(g[:, is_sum] - w[:, is_sum]) / np.maximum(np.abs(w[:, is_sum]), 1.0)
    return {
        "docs": nw, "docs_got": ng,
        "unpaired_docs": unpaired_docs,
        "tag_rows_differ": tag_rows_differ,
        "max_lanes_differ": max_lanes_differ,
        "sum_rel_err": float(err.max(initial=0.0)),
    }


LIMITS = {  # number -> limit; exact comparisons have the limit 0
    "unpaired_docs": 0, "tag_rows_differ": 0, "max_lanes_differ": 0,
    "sum_rel_err": SUM_RTOL,
}


def merge_worst(results: list[dict]) -> dict:
    """The worst of each compared number over several windows."""
    return {k: max((r[k] for r in results), default=0) for k in LIMITS}
