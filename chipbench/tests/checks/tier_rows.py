"""Rehearsal check (b): every tier window that holds seconds of the run,
row by row against the reference's group-by over all the records sent for
those seconds (same numbers, same limits as a base window's), and no tier
window missing that event time has passed by `delay`."""

import numpy as np

import gen
import reference


def check(ctx: dict) -> dict:
    schema, source, schedule = ctx["schema"], ctx["source"], ctx["schedule"]
    delay = int(ctx["config"]["pipeline"]["delay"])
    sent = {gen.T0 + s["second"]: s for s in ctx["sent_seconds"]}
    results, starts = [], set()
    for interval, db in ctx["side_outputs"]["tier_docbatches"]:
        start = int(db.timestamp[0])
        inside = sorted(w for w in sent if start <= w < start + interval)
        if not inside:
            continue  # a tier window of the warm-up's event time
        parts = []
        for w in inside:
            s = sent[w]
            tags, meters = source.second(s["second"],
                                         schedule.records_in_second(s["second"]))
            parts.append((tags[:, :s["records"]], meters[:s["records"]]))
        want = reference.reference_docs(
            schema, np.concatenate([t for t, _m in parts], axis=1),
            np.concatenate([m for _t, m in parts]))
        results.append(reference.compare_docs(schema, db.tags, db.meters, *want))
        starts.add((interval, start))
    # a tier window is due once event time has passed its end by `delay`
    due = {(iv, w - w % iv) for iv in ctx["config"]["pipeline"]["cascade"]["intervals"]
           for w in sent if w - w % iv + iv + delay <= max(sent)}
    worst = reference.merge_worst(results)
    out = {f"tier.{k}": (worst[k], lim) for k, lim in reference.LIMITS.items()}
    out["tier.windows_missing"] = (len(due - starts), 0)
    out["tier.windows_compared"] = (len(results), None)
    return out
