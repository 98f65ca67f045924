"""From a profiler trace to device metrics: busy and idle time, time by
XLA module and by op, the longest idle gaps and what the host was doing in
them, and the arithmetic of a kernel's share of its roofline (what goes
into it is a layer file's: layers.py).

Two steps, so that the arithmetic can be checked on a small recorded trace
(chipbench/tests/data/trace_events.json) without the profiler:

  extract(xplane_path) -> events: per device plane the "XLA Ops" and "XLA
      Modules" lines as [name, start_ns, duration_ns], and the harness's
      own annotations from the host plane (chipbench.anchor opens the
      slice, chipbench.end closes it).
  reduce(events, ...) -> numbers.

Event times are nanoseconds since the trace began. The anchor annotation
is written with the host's clocks read beside it, which puts the program's
spans (wall clock) on the same axis.
"""

from __future__ import annotations

import bisect
import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ANCHOR, END = "chipbench.anchor", "chipbench.end"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device that is not in the table is an
    error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def load_groups(paths=None) -> dict:
    """XLA module groups: `trace_groups.json` and every
    `trace_groups/*.json` beside it (each {"modules": {group: [name
    prefixes]}}). A later file adds groups; a group name or a prefix that
    an earlier file has defined is an error, so none can be redefined."""
    if paths is None:
        paths = [os.path.join(HERE, "trace_groups.json")] + sorted(
            glob.glob(os.path.join(HERE, "trace_groups", "*.json")))
    groups: dict = {}
    owner: dict = {}
    for path in paths:
        with open(path) as f:
            modules = json.load(f)["modules"]
        for group, prefixes in modules.items():
            if group in groups:
                raise ValueError(f"{path}: module group {group!r} is already defined")
            for prefix in prefixes:
                if prefix in owner:
                    raise ValueError(f"{path}: prefix {prefix!r} already belongs "
                                     f"to group {owner[prefix]!r}")
                owner[prefix] = group
            groups[group] = list(prefixes)
    return groups


def record_bytes(schema: dict) -> int:
    """Bytes one flow record brings to the device, whatever implements the
    step: its tag words and its meter words, 4 bytes each."""
    return 4 * (len(schema["flow_record_tag_fields"]) + len(schema["flow_meter"]))


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {"devices": [], "annotations": [], "lines_seen": {}}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        dev = {"plane": plane.name, "ops": [], "modules": []}
        for line in plane.lines:
            events = list(line.events)
            out["lines_seen"][f"{plane.name}|{line.name}"] = len(events)
            if is_device and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                dev[key] = [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in events]
            elif not is_device:
                out["annotations"] += [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in events if e.name.startswith("chipbench.")]
        if is_device:
            out["devices"].append(dev)
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(a, b) for a, b in merged]


def _clip(events, lo: float, hi: float):
    """Events clipped to [lo, hi): (name, start, end) with end > start."""
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def module_group(name: str, groups: dict) -> str:
    """XLA module event name ("jit_step(1234)") -> the group whose prefix
    list it matches, else its own name without the id."""
    base = name.split("(")[0]
    for group, prefixes in groups.items():
        if any(base == p or base.startswith(p) for p in prefixes):
            return group
    return base


def reduce(events: dict, groups: dict, host_spans=(), anchor_wall_s=None) -> dict:
    """The slice between the anchor and the end annotation.

    `host_spans` are the program's spans as (name, wall_start_s,
    duration_s); `anchor_wall_s` is the wall clock read when the anchor
    was written. Returns busy_s and window_s (averaged over the devices),
    idle_share_pct, seconds by module group, the top ops and the longest
    idle gaps with the innermost host span open at each gap's middle; and
    under `per_device` each device plane's own busy_s and module_s, in
    the planes' order (the means above are over these)."""
    marks = {name: (start, dur) for name, start, dur in events["annotations"]}
    if ANCHOR not in marks or END not in marks:
        raise ValueError("trace holds no anchor/end annotation")
    lo, hi = marks[ANCHOR][0], marks[END][0]
    if not events["devices"] or hi <= lo:
        raise ValueError("trace holds no device plane or an empty slice")
    window_s = (hi - lo) / 1e9
    busy_ns, by_op, by_module, gaps = 0.0, {}, {}, []
    per_device = {"plane": [], "busy_s": [], "module_s": []}
    for dev in events["devices"]:
        ops = list(_clip(dev["ops"], lo, hi))
        busy = _union([(a, b) for _n, a, b in ops])
        dev_busy_ns = sum(b - a for a, b in busy)
        busy_ns += dev_busy_ns
        mods = sorted((a, b, module_group(name, groups))
                      for name, a, b in _clip(dev["modules"], lo, hi))
        dev_module: dict = {}
        for a, b, g in mods:
            by_module[g] = by_module.get(g, 0.0) + (b - a)
            dev_module[g] = dev_module.get(g, 0.0) + (b - a)
        per_device["plane"].append(dev.get("plane"))
        per_device["busy_s"].append(dev_busy_ns / 1e9)
        per_device["module_s"].append({g: v / 1e9 for g, v in dev_module.items()})
        starts = [m[0] for m in mods]
        for name, a, b in ops:
            # an op is named by its instruction and the module it ran in
            i = bisect.bisect_right(starts, a) - 1
            owner = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            key = f"{owner}/{name.split(' = ')[0][:40]}"
            by_op[key] = by_op.get(key, 0.0) + (b - a)
        edges = [lo] + [t for ab in busy for t in ab] + [hi]
        gaps += [(edges[i + 1] - edges[i], edges[i]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(events["devices"])
    busy_s = busy_ns / 1e9 / n_dev

    def host_at(t_ns: float) -> str:
        if anchor_wall_s is None:
            return "unattributed"
        t = anchor_wall_s + (t_ns - lo) / 1e9
        open_ = [(d, n) for n, s, d in host_spans if s <= t < s + d]
        return min(open_)[1] if open_ else "host between spans"

    by_gap: dict = {}
    for length, start in gaps:
        what = host_at(start + length / 2)
        by_gap[what] = by_gap.get(what, 0.0) + length
    top = lambda d: [[k, v / 1e9 / n_dev] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share_pct": 100.0 * (1.0 - busy_s / window_s),
        "module_s": {k: v / 1e9 / n_dev for k, v in by_module.items()},
        "per_device": per_device,
        "device_ops": top({**{f"module:{k}": v for k, v in by_module.items()},
                           **by_op}),
        "idle_gaps": top(by_gap),
        "longest_gap_s": max((g[0] for g in gaps), default=0.0) / 1e9,
    }


def roofline_pct(bytes_moved: float, seconds: float, peak_bytes_per_s: float):
    """The least time the chip could take for these bytes over the time it
    took, in percent; None where there is nothing to divide."""
    if not bytes_moved or not seconds:
        return None
    return 100.0 * (bytes_moved / peak_bytes_per_s) / seconds
