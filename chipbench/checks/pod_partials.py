"""The pod's partial rows, as the devices handed them over
(`l4_1s_4m_x4_sketch`; named by a configuration's `"checks"`).

A sharded deployment keeps one exact stash a device, so a document key
comes as up to `chips` partial rows a window, one a device, and the
reader merges them by key. The base checks judge the MERGED documents
against the plain reference; this file judges what was merged.
`side_outputs["pod_partials"]` holds, for every closed window, the
partial rows as they came (`tags` [n, D] u32 and their `packet_tx` lane
[n] f32, device-major) and the rows each device gave. Nothing of the program is
imported and nothing it merged is trusted: the grouping below is this
file's own (a byte-wise `np.unique` over the key columns).

Numbers and why each limit is what it is (the configuration's `merge`
guarantee):

  pod.keys_over_one_row_a_device, limit 0, on the prefix window and the
      run's sampled windows (run.py's own draw): document keys that one
      device handed over more than once in a window. A device's stash
      holds one row a key a window; a second row is a fold that missed a
      merge, and the reader's sum would still come out right, so only
      this count shows it.
  pod.windows_missing_a_device, limit 0: full windows (every record of
      a whole event-second sent; not the prefix window, whose few records
      fill only the head of one padded batch, nor a cut last second) in
      which some device handed over no row. A batch is dealt over the
      devices by position, in contiguous shares, padding last, so a whole
      event-second reaches every device; one that is silent lost its
      drain.
  pod.partial_packet_tx_differs, limit 0: windows whose `packet_tx`
      summed over the partial EDGE rows differs from the generator's own
      sum for that second, or from the sum over the merged documents the
      base checks compared. SUM lanes add across devices; small integers
      in float32, so the sums are exact.
  pod.partial_rows_per_doc, printed: partial rows handed over for each
      merged document, over every closed window. Under uniform keys a
      flow rarely meets two devices in a second (~1.1); the Zipf 10k
      population read 3.7 (PR 22).
"""

import numpy as np

import gen

SAMPLED_WINDOWS = 3  # as run.py's


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


def repeated_keys(keys: np.ndarray) -> int:
    """Rows of `keys` [n, k] u32 beyond the first of each distinct row."""
    if keys.shape[0] < 2:
        return 0
    rows = np.ascontiguousarray(keys).view(
        np.dtype((np.void, keys.shape[1] * keys.dtype.itemsize))).ravel()
    return int(rows.shape[0] - np.unique(rows).shape[0])


def check(ctx: dict) -> dict:
    schema = ctx["schema"]
    docs = schema["doc_tags"]
    key = np.flatnonzero([d["key"] for d in docs])
    code = [d["name"] for d in docs].index("code_id")
    edge = schema["enums"]["code_edge_ip_port"]
    lane = [m["name"] for m in schema["flow_meter"]].index("packet_tx")
    partials = ctx["side_outputs"]["pod_partials"]
    sent = {gen.T0 + s["second"]: s for s in ctx["sent_seconds"]}

    def edge_tx(tags, packet_tx) -> int:
        return int(packet_tx[tags[:, code] == edge].astype(np.float64).sum())

    silent = differs = rows = merged_rows = 0
    for w, (tags, packet_tx, counts) in partials.items():
        rows += tags.shape[0]
        if w in ctx["got"]:
            merged_rows += ctx["got"][w][0].shape[0]
        if w not in sent:
            continue  # run.py's `windows_unsent` has it
        s = sent[w]
        full = (w != gen.T0 and s["records"]
                == ctx["schedule"].records_in_second(s["second"]))
        silent += int(full and min(counts) == 0)
        have = edge_tx(tags, packet_tx)
        merged = ctx["got"].get(w)
        differs += int(have != s["edge_packet_tx"] or (
            merged is not None and have != edge_tx(merged[0], merged[1][:, lane])))
    twice = 0
    for w in sampled(ctx, sent):
        if w in partials:
            tags, _packet_tx, counts = partials[w]
            bounds = np.concatenate([[0], np.cumsum(counts)])
            twice += sum(repeated_keys(tags[a:b][:, key])
                         for a, b in zip(bounds, bounds[1:]))
    return {
        "pod.keys_over_one_row_a_device": (twice, 0),
        "pod.windows_missing_a_device": (silent, 0),
        "pod.partial_packet_tx_differs": (differs, 0),
        "pod.partial_rows_per_doc": (rows / merged_rows if merged_rows else 0.0, None),
    }
