"""The sketch plane's closed blocks against a plain reference sketch
(`l4_1s_1m_sketch`; named by a configuration's `"checks"`).

`side_outputs["sketch_blocks"]` holds one closed block a window, as the
deployment's builder kept them. The check reads a block's `window`,
`n_updates`, `hll` [G, m], `cms` [D, W], `hist` [G, B] and asks it what a
querier asks: `distinct()`, `distinct_per_group()`, `estimate(hi, lo)`,
`topk(k)`, `merge(other)`. What it compares them with is made here, from
the records the generator sent, by this file's OWN NumPy copy of the
sketch: straightforward loops and `np.maximum.at` / `np.add.at` over a
second's regenerated records, with no ring, no pending buffer, no packing,
and nothing imported from the program. The program's choices it has to
share to be comparable (each copied, with where it lives):

  fingerprint   murmur3-32 body over u32 columns, seeds 0x9747B28C (hi)
                and 0x3C6EF372 (lo), fmix32 finish (ops/hashing.py)
  client        fingerprint of ip0_w0..w3: the sketched entity
  flow key      fingerprint of ip0 x4, ip1 x4, server_port, protocol
  group         (l3_epc_id1 * 131 + server_port) mod num_groups
  HLL           register = client.lo & (m - 1); rank = leading zeros of
                client.hi + 1, 1...33 (aggregator/sketchplane.py)
  count-min     row d of a key: h = hi + d * lo (u32), h ^= h >> 15,
                h *= 0x2C1B3C6D, h ^= h >> 12, column = h & (W - 1)
                (ops/cms.py); weight = byte_tx as int32
  histogram     value = rtt_sum / max(rtt_count, 1) in float32, records
                with rtt_count > 0; bin = how many of the B - 1 edges
                float32(vmin * gamma^k), k = 1...B - 1, are at or below
                the value, i.e. floor(log_gamma(v / vmin)) cut to 0...B - 1,
                decided by comparisons and by nobody's log; one count in
                (group, bin) (ops/histogram.py)

The exact distinct counts are `reference._group_rows` over the ip0 words.

Numbers and why each limit is what it is:

  sketch.windows_without_block, sketch.blocks_without_window, limit 0:
      one block a closed window, none for a window nobody sent.
  sketch.rows_missing, limit 0: sum over windows of |n_updates - records
      sent for it|: every record passed the plane once.
  sketch.hll_registers_differ, sketch.cms_counters_differ, limit 0, on
      the prefix window and the run's sampled windows (the last closed in
      the timed window and two others drawn from --seed, as run.py draws
      its own): integer state and commutative updates (max, add), so any
      difference is a lost, doubled or misrouted row.
  sketch.hist_bins_differ, limit 0, same windows: bins compared as running
      sums, a group at a time. The edges are one table of float32 numbers
      on both sides, so a latency is binned alike wherever it is the same
      float32; what may differ is the latency itself, by one float32 step
      (the device's division is not correctly rounded). A record whose
      latency is one step from an edge may sit on either side of it, which
      moves one running sum by one: a running sum may differ by the count
      of such records at its edge and no more, and a group's total not at
      all. (With rtt_count = 1, as the generator sends, there are none.)
  sketch.hll_worst_rel_err <= distinct_worst_sigmas x 1.04 / sqrt(m)
      (2.44% at p = 14), sketch.hll_mean_rel_err <= distinct_mean_rel_err
      (0.01), on EVERY closed window, the window's register-max union
      against its exact distinct clients. 1.04 / sqrt(m) is HyperLogLog's
      standard error (Flajolet et al. 2007); the source's "<1%" is held on
      the mean, where p = 14 can hold it (mean absolute error of an
      unbiased estimator with sigma 0.81% is ~0.65%); p = 12 (sigma 1.63%)
      fails it.
  sketch.hll_group_worst_rel_err, sampled windows, every service row with
      at least GROUP_MIN_CLIENTS exact clients. Such a row is in linear
      counting's range (n << 2.5 m): with V empty registers of m the
      estimate is m ln(m / V), standard error sqrt(m (e^t - t - 1)) / n at
      load t = n / m (Whang et al. 1990), ~1 / sqrt(2 m) = 0.55% at p = 14
      for small t; a row past 2.5 m gets 1.04 / sqrt(m). Limit:
      GROUP_SIGMAS x the largest of these over the rows compared (a
      thousand rows a run: 6 sigmas, not 3).
  sketch.hll_run_rel_err <= the same sigmas x 1.04 / sqrt(m): the union of
      all closed blocks (`merge`: register max) against the exact distinct
      clients of everything sent: the source's "1M true cardinality", and
      the merge the querier and the 1 m tier rely on.
  sketch.cms_under, limit 0: count-min never underestimates; over
      CMS_FLOWS flows drawn from --seed in each sampled window.
  sketch.cms_over_share <= e^-D: the share of those flows whose estimate
      is over the exact bytes by more than (e / W) x the window's bytes
      (Cormode & Muthukrishnan 2005).
  sketch.topk_unsound, limit 0: every key `topk(TOPK)` returns was sent in
      that window and its estimate is not under its exact bytes. Recall is
      NOT judged: under uniform keys no flow is heavy (the largest of
      ~232k flows holds ~5e-5 of a window's bytes), so which 32 the sketch
      keeps is noise; heavy hitters are BASELINE configs[3]'s (ROADMAP R6).
"""

import dataclasses
import math

import numpy as np

import gen
import reference

SAMPLED_WINDOWS = 3  # as run.py's
CMS_FLOWS = 4096
TOPK = 32
GROUP_MIN_CLIENTS = 256
GROUP_SIGMAS = 6.0

_C1, _C2 = np.uint32(0xCC9E2D51), np.uint32(0x1B873593)
SEED_HI, SEED_LO = 0x9747B28C, 0x3C6EF372


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def fingerprint(cols: list, seed: int) -> np.ndarray:
    """murmur3-32 over a list of [n] u32 columns (u32 arithmetic wraps)."""
    h = np.full(cols[0].shape, seed, np.uint32)
    for c in cols:
        k = _rotl(c.astype(np.uint32) * _C1, 15) * _C2
        h = _rotl(h ^ k, 13) * np.uint32(5) + np.uint32(0xE6546B64)
    h = h ^ np.uint32(len(cols) * 4)
    h = (h ^ (h >> np.uint32(16))) * np.uint32(0x85EBCA6B)
    h = (h ^ (h >> np.uint32(13))) * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def leading_zeros(x: np.ndarray) -> np.ndarray:
    bits = np.zeros(x.shape, np.int64)  # bit length, a bit at a time
    for b in range(32):
        bits[(x >> np.uint32(b)) != 0] = b + 1
    return 32 - bits


def cms_columns(hi, lo, depth: int, width: int) -> np.ndarray:
    """[depth, n] column of each key in each row."""
    out = np.zeros((depth, hi.size), np.int64)
    for d in range(depth):
        h = hi + np.uint32(d) * lo
        h = (h ^ (h >> np.uint32(15))) * np.uint32(0x2C1B3C6D)
        h = h ^ (h >> np.uint32(12))
        out[d] = h & np.uint32(width - 1)
    return out


class Records:
    """One window's records as the plane sees them: the per-record
    quantities of the table above."""

    def __init__(self, schema: dict, tags: np.ndarray, meters: np.ndarray, s: dict):
        f = schema["flow_record_tag_fields"].index
        m = [x["name"] for x in schema["flow_meter"]].index
        ip0 = [tags[f(f"ip0_w{w}")] for w in range(4)]
        ip1 = [tags[f(f"ip1_w{w}")] for w in range(4)]
        port, proto = tags[f("server_port")], tags[f("protocol")]
        self.ip0 = np.ascontiguousarray(np.stack(ip0))
        self.key_cols = np.ascontiguousarray(np.stack(ip0 + ip1 + [port, proto]))
        self.client_hi = fingerprint(ip0, SEED_HI)
        self.client_lo = fingerprint(ip0, SEED_LO)
        self.key_hi = fingerprint(ip0 + ip1 + [port, proto], SEED_HI)
        self.key_lo = fingerprint(ip0 + ip1 + [port, proto], SEED_LO)
        self.group = ((tags[f("l3_epc_id1")] * np.uint32(131) + port)
                      % np.uint32(s["num_groups"])).astype(np.int64)
        self.weight = meters[:, m("byte_tx")].astype(np.int32).astype(np.int64)
        count = meters[:, m("rtt_count")]
        self.rtt = meters[:, m("rtt_sum")] / np.maximum(count, np.float32(1.0))
        self.rtt_valid = count > 0
        self.n = int(port.size)


def reference_sketch(r: Records, s: dict) -> dict:
    """The window's HLL registers [G, m], count-min counters [D, W],
    histogram bins [G, B], and for the histogram the records one float32
    step from each bin's lower edge [G, B]."""
    g, m = int(s["num_groups"]), 1 << int(s["hll_precision"])
    d, w, b = int(s["cms_depth"]), int(s["cms_width"]), int(s["hist_bins"])
    hll = np.zeros((g, m), np.int64)
    np.maximum.at(hll, (r.group, (r.client_lo & np.uint32(m - 1)).astype(np.int64)),
                  leading_zeros(r.client_hi) + 1)
    cms = np.zeros((d, w), np.int64)
    for row, cols in zip(cms, cms_columns(r.key_hi, r.key_lo, d, w)):
        np.add.at(row, cols, r.weight)
    edges = (float(s["hist_vmin"]) * np.power(
        float(s["hist_gamma"]), np.arange(1, b, dtype=np.float64))).astype(np.float32)

    def bin_of(v):
        return np.searchsorted(edges, v, side="right")  # edges at or below v

    ok = r.rtt_valid
    at = bin_of(r.rtt)
    hist = np.zeros((g, b), np.int64)
    np.add.at(hist, (r.group[ok], at[ok]), 1)
    # a record one float32 step under edge k (bin k - 1) or on it (bin k)
    above = bin_of(np.nextafter(r.rtt, np.float32(np.inf)))
    below = bin_of(np.nextafter(r.rtt, np.float32(-np.inf)))
    at_edge = np.zeros((g, b), np.int64)
    for near, edge in ((ok & (above != at), above), (ok & (below != at), at)):
        np.add.at(at_edge, (r.group[near], edge[near]), 1)
    return {"hll": hll, "cms": cms, "hist": hist, "hist_at_edge": at_edge}


def hist_differ(got: np.ndarray, want: np.ndarray, at_edge: np.ndarray) -> int:
    """Running sums (a group's records in bins <= k) that differ by more
    than the records one float32 step from the edge above bin k, plus the
    groups whose totals differ."""
    cg, cw = np.cumsum(got, axis=1), np.cumsum(want, axis=1)
    loose = np.abs(cg[:, :-1] - cw[:, :-1]) > at_edge[:, 1:]
    return int(loose.sum() + (cg[:, -1] != cw[:, -1]).sum())


def distinct_rows(cols: np.ndarray) -> np.ndarray:
    """The distinct columns of `cols` [k, n]."""
    order, starts = reference._group_rows(cols)
    return np.ascontiguousarray(cols[:, order[starts]])


def flow_bytes(r: Records):
    """Exact bytes of each distinct flow of the window: (key hi, key lo,
    bytes), grouped by the key's ten columns, not by its fingerprint."""
    order, starts = reference._group_rows(r.key_cols)
    first = order[starts]
    return r.key_hi[first], r.key_lo[first], np.add.reduceat(r.weight[order], starts)


def group_sigma(n: np.ndarray, m: int) -> np.ndarray:
    """Standard error of a row's estimate at `n` exact clients."""
    t = n / m
    linear = np.sqrt(m * (np.exp(t) - t - 1.0)) / np.maximum(n, 1)
    return np.where(n <= 2.5 * m, linear, 1.04 / math.sqrt(m))


def sampled(ctx: dict, sent: dict) -> list:
    """The windows run.py's own comparison draws, by the same rule, and
    the prefix window."""
    full = sorted(w for w in ctx["closed_in_window"] if w != gen.T0 and w in sent)
    rng = np.random.default_rng([int(ctx["seed"]), 0x5A])
    sample = set(full[-1:])
    rest = [w for w in full if w not in sample]
    if rest:
        sample |= set(rng.choice(rest, min(SAMPLED_WINDOWS - 1, len(rest)),
                                 replace=False).tolist())
    return sorted(sample | ({gen.T0} & set(sent)))


def check(ctx: dict) -> dict:
    schema, source, schedule = ctx["schema"], ctx["source"], ctx["schedule"]
    s = ctx["config"]["pipeline"]["sketch"]
    m = 1 << int(s["hll_precision"])
    hll_sigma = 1.04 / math.sqrt(m)
    kept = ctx["side_outputs"]["sketch_blocks"]
    blocks = {int(b.window): b for b in kept}
    sent = {gen.T0 + x["second"]: x for x in ctx["sent_seconds"]}

    def records(w) -> Records:
        x = sent[w]
        tags, meters = source.second(x["second"], schedule.records_in_second(x["second"]))
        return Records(schema, tags[:, :x["records"]], meters[:x["records"]], s)

    compare = [w for w in sampled(ctx, sent) if w in blocks]
    regs = counters = bins = under = over = asked = unsound = 0
    group_worst, group_limit = 0.0, GROUP_SIGMAS / math.sqrt(2 * m)
    rel_err, clients_seen = {}, []
    for w in sorted(sent):
        r = records(w)
        clients = distinct_rows(r.ip0)
        clients_seen.append(clients)
        if w not in blocks:
            continue
        blk = blocks[w]
        rel_err[w] = abs(blk.distinct() - clients.shape[1]) / clients.shape[1]
        if w not in compare:
            continue
        want = reference_sketch(r, s)
        regs += int(np.count_nonzero(blk.hll != want["hll"]))
        counters += int(np.count_nonzero(blk.cms != want["cms"]))
        bins += hist_differ(np.asarray(blk.hist, np.int64), want["hist"],
                            want["hist_at_edge"])
        # a service row's clients: distinct (group, ip0) rows, counted by group
        per_group = np.bincount(
            distinct_rows(np.vstack([r.group.astype(np.uint32)[None], r.ip0]))[0]
            .astype(np.int64), minlength=int(s["num_groups"]))
        big = per_group >= GROUP_MIN_CLIENTS
        if big.any():
            est = np.asarray(blk.distinct_per_group(), np.float64)
            group_worst = max(group_worst, float(
                (np.abs(est[big] - per_group[big]) / per_group[big]).max()))
            group_limit = max(group_limit, GROUP_SIGMAS * float(
                group_sigma(per_group[big].astype(np.float64), m).max()))
        # count-min and top-K against the window's exact bytes a flow
        k_hi, k_lo, exact = flow_bytes(r)
        rng = np.random.default_rng([int(ctx["seed"]), 0x5B, int(w - gen.T0)])
        pick = rng.choice(exact.size, min(CMS_FLOWS, exact.size), replace=False)
        est = np.asarray(blk.estimate(k_hi[pick], k_lo[pick]), np.int64)
        under += int((est < exact[pick]).sum())
        over += int((est - exact[pick]
                     > math.e / int(s["cms_width"]) * float(r.weight.sum())).sum())
        asked += pick.size
        by_key = dict(zip(((k_hi.astype(np.uint64) << np.uint64(32))
                           | k_lo.astype(np.uint64)).tolist(), exact.tolist()))
        for top in blk.topk(TOPK):
            have = by_key.get((int(top["key_hi"]) << 32) | int(top["key_lo"]))
            unsound += have is None or int(top["estimate"]) < have

    numbers = {
        "sketch.windows_without_block": (len(set(sent) - set(blocks)), 0),
        "sketch.blocks_without_window":
            (len(set(blocks) - set(sent)) + len(kept) - len(blocks), 0),
        "sketch.rows_missing":
            (sum(abs(int(blocks[w].n_updates) - sent[w]["records"])
                 for w in sent if w in blocks), 0),
        "sketch.hll_registers_differ": (regs, 0),
        "sketch.cms_counters_differ": (counters, 0),
        "sketch.hist_bins_differ": (bins, 0),
        "sketch.hll_worst_rel_err": (max(rel_err.values(), default=0.0),
                                     float(s["distinct_worst_sigmas"]) * hll_sigma),
        "sketch.hll_mean_rel_err":
            (sum(rel_err.values()) / max(len(rel_err), 1),
             float(s["distinct_mean_rel_err"])),
        "sketch.hll_group_worst_rel_err": (group_worst, group_limit),
        "sketch.cms_under": (under, 0),
        "sketch.cms_over_share": (over / max(asked, 1), math.exp(-int(s["cms_depth"]))),
        "sketch.topk_unsound": (int(unsound), 0),
        "sketch.windows_compared": (len(compare), None),
        "sketch.blocks": (len(kept), None),
    }
    # the run-wide union: register max over every block, as a querier merges
    if blocks:
        everything = None
        for w in sorted(blocks):
            b = blocks[w]
            everything = b if everything is None else _union(everything, b)
        exact = distinct_rows(np.concatenate(clients_seen, axis=1)).shape[1]
        numbers["sketch.hll_run_rel_err"] = (
            abs(everything.distinct() - exact) / exact,
            float(s["distinct_worst_sigmas"]) * hll_sigma)
        numbers["sketch.run_distinct_exact"] = (exact, None)
    return numbers


def _union(a, b):
    """`a.merge(b)` for blocks of different windows (merge is for one
    window's shards and says so: it asserts the windows equal)."""
    return a.merge(dataclasses.replace(b, window=a.window))
