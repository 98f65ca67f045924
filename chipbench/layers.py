"""Per-layer metrics, one data file each (chipbench/layers/<name>.json).

A layer file says where its number comes from and how it is normalised;
this module is the one general reader. `read` holds:

  num:   a list of terms, each {"from": <plane>, "name": <key>[, "field":
         <sub-key>]}; the terms add up.
  den:   one such term, optional; the sum is divided by it.
  num_scale, den_scale: constants (unit conversion), default 1.

Planes (`from`): "spans" (the program's SpanTracers over the window: name
-> count / total_us), "counters" (feeder.*, receiver.*, pipeline.* counter
deltas over the window), "run" (the harness's own counts: windows_closed,
records, compile_s_in_window, ...), "generator" (the load generator's
report: sent_records) and "trace" (the profiler slice's reduction:
idle_share_pct, ...).

A reader that finds nothing to read - a span that never ran, a plane that
this run did not take, a zero denominator - returns None and the metric is
left out of the line. It never returns 0 in place of a missing share.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_layer(name: str) -> dict:
    with open(os.path.join(HERE, "layers", f"{name}.json")) as f:
        spec = json.load(f)
    if spec["name"] != name or spec["source"] not in SOURCES:
        raise ValueError(f"layer file {name}.json: bad name or source")
    return spec


def _term(term: dict, planes: dict):
    plane = planes.get(term["from"])
    if plane is None:
        return None
    value = plane
    for key in [term["name"]] + ([term["field"]] if "field" in term else []):
        if not isinstance(value, dict) or key not in value:
            return None
        value = value[key]
    return value if isinstance(value, (int, float)) else None


def read_metric(spec: dict, planes: dict):
    r = spec["read"]
    terms = [_term(t, planes) for t in r["num"]]
    if not terms or any(t is None for t in terms):
        return None
    value = float(sum(terms)) * r.get("num_scale", 1.0)
    if "den" in r:
        den = _term(r["den"], planes)
        if not den:
            return None
        value /= den * r.get("den_scale", 1.0)
    return value
