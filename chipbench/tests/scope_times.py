#!/usr/bin/env python3
"""By hand, on the chip: one traced run of a cell that also writes, from
the profiler slice's xplane, the device time by named scope and what the
host annotations hold.

    python3 chipbench/tests/scope_times.py <path> --workload <cell> --seed <n> --seconds <s> --trace 1

The program names its stages (`jax.named_scope`: `step.*` in the fused
step, `fold.*` in the fold, `flush.*` in the range flush) and holds a
`TraceAnnotation` for each of its host spans. `trace_reduce.py` reads
neither yet: it names a device op by its HLO instruction and takes host
spans from the program's ring. This tool shows what a reducer that groups
by scope would read, so that the next benchmark PR can check its numbers
against a file.

The scope path is no stat of the op *event* (`jax.profiler.ProfileData`
shows an event's own stats only: offsets and durations): it is a stat of
the event's *metadata* in the xplane proto. So the run keeps a copy of the
slice's `.xplane.pb`, and a second process (`--reduce`, no JAX, the proto
classes that come with TensorFlow) writes `<path>`: per XLA module group
the seconds under each scope, the heaviest instructions with theirs, which
metadata stat carried the scope, the count and seconds of every host
annotation, and, from the run itself, both tracers' summaries (counts,
`self_us` and the compile lanes, which `Served.spans()` does not pass on)
with the seconds of spans each ring still held when the run ended.
"""

import json
import os
import re
import shutil
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce  # noqa: E402

SCOPE = re.compile(r"(?:step|fold|flush)\.[a-z_]+(?:/(?:step|fold|flush)\.[a-z_]+)*")
# the program's host spans, and the harness's own annotations
SPAN = re.compile(r"^(?:feeder|ingest|stats|window|flush|checkpoint|query|chipbench)\.[a-z_]+$")


def scopes_of(xplane_path: str) -> dict:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    with open(os.path.join(trace_reduce.HERE, "trace_groups.json")) as f:
        groups = json.load(f)["modules"]
    space = xplane_pb2.XSpace()
    with open(xplane_path, "rb") as f:
        space.ParseFromString(f.read())
    out = {"by_scope": {}, "by_instruction": {}, "scope_stat": {},
           "sample_op_metadata": [], "host_annotations": {}}
    for plane in space.planes:
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}

        def stats_of(owner) -> dict:
            got = {}
            for s in owner.stats:
                kind = s.WhichOneof("value")
                value = getattr(s, kind) if kind else None
                if kind == "ref_value":
                    value = stat_name.get(value, value)
                got[stat_name.get(s.metadata_id, str(s.metadata_id))] = value
            return got

        def events(line):
            for e in line.events:
                md = plane.event_metadata[e.metadata_id]
                yield md, line.timestamp_ns * 1000 + e.offset_ps, e.duration_ps

        if not plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                for md, _start, dur in events(line):
                    if SPAN.match(md.name):
                        a = out["host_annotations"].setdefault(
                            md.name, {"line": line.name, "count": 0, "seconds": 0.0})
                        a["count"] += 1
                        a["seconds"] += dur / 1e12
            continue
        lines = {line.name: line for line in plane.lines}
        mods = sorted(
            (start, start + dur, trace_reduce.module_group(md.name, groups))
            for md, start, dur in events(lines[trace_reduce.MODULES_LINE]))
        scope_of = {}  # metadata id -> (scope, the stat that held it)
        for md, start, dur in events(lines[trace_reduce.OPS_LINE]):
            if md.id not in scope_of:
                stats = {**stats_of(md), "(display_name)": md.display_name}
                hit = next(((SCOPE.search(v).group(0), k) for k, v in stats.items()
                            if isinstance(v, str) and SCOPE.search(v)),
                           ("(no scope)", None))
                scope_of[md.id] = hit
                if len(out["sample_op_metadata"]) < 4 and (hit[1] or len(scope_of) < 3):
                    out["sample_op_metadata"].append({
                        "name": md.name[:120],
                        "stats": {k: str(v)[:200] for k, v in stats.items()}})
            scope, stat = scope_of[md.id]
            if stat is not None:
                out["scope_stat"][stat] = out["scope_stat"].get(stat, 0) + 1
            owner = next((g for a, b, g in mods if a <= start < b), "?")
            key = f"{owner}/{scope}"
            out["by_scope"][key] = out["by_scope"].get(key, 0.0) + dur / 1e12
            instr = f"{owner}/{md.name.split(' = ')[0][:40]}"
            rec = out["by_instruction"].setdefault(instr, {"seconds": 0.0, "scope": scope})
            rec["seconds"] += dur / 1e12
    top = sorted(out["by_instruction"].items(), key=lambda kv: -kv[1]["seconds"])[:32]
    out["by_instruction"] = dict(top)
    out["by_scope"] = dict(sorted(out["by_scope"].items(), key=lambda kv: -kv[1]))
    out["note"] = ("a while op's seconds hold its body's ops, which are listed "
                   "again: by_scope adds both, module_s in the result line does not")
    return out


def run_and_keep(path: str, argv: list) -> int:
    """The traced run, with the slice's xplane copied aside before the
    harness removes it, and the tracers read when the deployment closes."""
    import run as chipbench_run
    import sut

    kept, tracers = path + ".xplane.pb", {}
    extract, close = trace_reduce.extract, sut.Served.close

    def extract_and_keep(xplane):
        shutil.copyfile(xplane, kept)
        return extract(xplane)

    def close_and_read(served):
        for name, tr in (("feeder", served.feeder.tracer), ("pipeline", served.pipe.tracer)):
            ring = tr.recent()
            tracers[name] = {
                "summary": tr.summary(), "ring_records": len(ring),
                "ring_holds_s": max(r.start_s for r in ring) - min(r.start_s for r in ring)}
        close(served)

    trace_reduce.extract, sut.Served.close = extract_and_keep, close_and_read
    try:
        rc = chipbench_run.main(argv)
    finally:
        with open(path, "w") as f:
            json.dump({"tracers": tracers}, f, indent=1)
        if os.path.exists(kept):
            subprocess.run([sys.executable, os.path.abspath(__file__), "--reduce",
                            kept, path], check=False)
            os.remove(kept)
    return rc


if __name__ == "__main__":
    if sys.argv[1] == "--reduce":  # <xplane.pb> <path>: add the scopes to <path>
        with open(sys.argv[3]) as f:
            out = json.load(f)
        out.update(scopes_of(sys.argv[2]))
        with open(sys.argv[3], "w") as f:
            json.dump(out, f, indent=1)
    else:
        sys.exit(run_and_keep(sys.argv[1], sys.argv[2:]))
