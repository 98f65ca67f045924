"""Per-layer metrics, one data file each (chipbench/layers/<name>.json).

A layer file says where its number comes from and how it is normalised;
this module is the one general reader. `read` holds one of two forms. A
ratio:

  num:   a list of terms, each {"from": <plane>, "name": <key>[, "field":
         <sub-key>][, "times": <term>]}; a term with `times` is the
         product of the two; the terms add up.
  den:   one such term, optional; the sum is divided by it.
  num_scale, den_scale: constants (unit conversion), default 1.

Or a kernel's share of its roofline, in percent:

  roofline: {"group": <XLA module group>, "bytes": [<terms>],
         "bytes_scale": <number>, "ops": [<terms>], "ops_scale":
         <number>}; `bytes`, `ops` or both. The least time the chip could
         take - the bytes the terms add up to over the chip's
         `hbm_bytes_per_s`, the operations over its `bf16_flops_per_s`,
         the larger of the two where both are given - over the seconds
         the trace gives the group (`trace.module_s[group]`). The bytes
         and operations are what the algorithm needs, computed from
         counts and shapes by the terms; a reading over 100 says they are
         counted too high or the group leaves out part of the work, and
         is printed as it is: the reader does not clamp.

Planes (`from`): "spans" (the program's SpanTracers over the window: name
-> count / total_us), "counters" (feeder.*, receiver.*, pipeline.* counter
deltas over the window), "run" (the harness's own counts: windows_closed,
records, compile_s_in_window, ...), "generator" (the load generator's
report: sent_records), "schema" (record_bytes: what one flow record
brings to the device), "trace" (the profiler slice's reduction:
idle_share_pct, busy_s, window_s, slice_records, module_s / group) and
"peaks" (the chip's row of peaks.json; with "trace", on the chip only).

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
    if not isinstance(value, (int, float)):
        return None
    if "times" in term:
        factor = _term(term["times"], planes)
        return None if factor is None else value * factor
    return value


def _sum(terms: list, planes: dict):
    values = [_term(t, planes) for t in terms]
    if not values or any(v is None for v in values):
        return None
    return sum(values)


def _roofline(r: dict, planes: dict):
    seconds = _term({"from": "trace", "name": "module_s", "field": r["group"]}, planes)
    least = None
    for what, peak in (("bytes", "hbm_bytes_per_s"), ("ops", "bf16_flops_per_s")):
        if what in r:
            total = _sum(r[what], planes)
            per_s = _term({"from": "peaks", "name": peak}, planes)
            if total is None or not per_s:
                return None
            t = total * r.get(f"{what}_scale", 1) / per_s
            least = t if least is None else max(least, t)
    if not least or not seconds:
        return None
    return 100.0 * least / seconds


def read_metric(spec: dict, planes: dict):
    r = spec["read"]
    if "roofline" in r:
        return _roofline(r["roofline"], planes)
    total = _sum(r["num"], planes)
    if total is None:
        return None
    value = float(total) * r.get("num_scale", 1.0)
    if "den" in r:
        den = _term(r["den"], planes)
        if not den:
            return None
        value /= den * r.get("den_scale", 1.0)
    return value
