"""Rehearsal check (a): each closed sketch block's HLL estimate against
the exact distinct count of what was sent for its second. The sketched
entity is the client address (the four ip0 words of a flow record); the
limit is the configuration's own (`pipeline.sketch.distinct_rel_err`)."""

import numpy as np

import gen
import reference


def check(ctx: dict) -> dict:
    schema, source, schedule = ctx["schema"], ctx["source"], ctx["schedule"]
    fields = schema["flow_record_tag_fields"]
    ip0 = [fields.index(f"ip0_w{w}") for w in range(4)]
    blocks = {int(b.window): b for b in ctx["side_outputs"]["sketch_blocks"]}
    sent = {gen.T0 + s["second"]: s for s in ctx["sent_seconds"]}
    worst = 0.0
    for w, s in sent.items():
        if w not in blocks:
            continue
        tags, _meters = source.second(s["second"],
                                      schedule.records_in_second(s["second"]))
        exact = reference._group_rows(
            np.ascontiguousarray(tags[ip0, :s["records"]]))[1].size
        worst = max(worst, abs(blocks[w].distinct() - exact) / exact)
    return {
        "sketch.windows_without_block": (len(set(sent) - set(blocks)), 0),
        "sketch.hll_rel_err":
            (worst, ctx["config"]["pipeline"]["sketch"]["distinct_rel_err"]),
    }
