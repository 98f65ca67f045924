#!/usr/bin/env python3
"""chipbench/run.py - one cell of the benchmark, once, on the chip.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip. It builds the cell's deployment (sut.py),
starts the load generator as a process of its own (loadgen.py), warms up
every program the window uses, runs the window, reads the peak memory,
checks what the window produced against the plain reference
(reference.py; server_check.py for the store and query; then the checks
the configuration names, checks/<name>.py), and prints one JSON line
last. Everything of one configuration, one traffic mix or one
per-layer metric is in a data file that BENCHMARK.json names; see
README.md for how a later PR adds one.

It fails, with no result line, where JAX finds no TPU or fewer chips than
the cell asks for. It never sets a platform.
"""

from __future__ import annotations

import time

_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import gen  # noqa: E402
import layers  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

SAMPLED_WINDOWS = 3  # full windows compared row by row, beside the prefix
IDLE_SLEEP_S = 0.0005  # drive_feeder's wait when a pump took nothing
DRAIN_TIMEOUT_S = 120.0
# where a configuration's `checks` names are looked for; a test appends a
# directory of its own
CHECK_DIRS = [os.path.join(HERE, "checks")]


def say(**rec) -> None:
    print(json.dumps(rec, default=str), flush=True)


class HarnessFailure(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# what BENCHMARK.json names


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic_path = os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    with open(traffic_path) as f:
        traffic = json.load(f)

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "config_path": os.path.join(ROOT, cfg_entry["file"]),
        "traffic_path": traffic_path,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


# ---------------------------------------------------------------------------
# JAX's own compile and cache events (copied from chip_smoke.py)


class CompileClock:
    """Backend compiles (persistent-cache reads included): seconds and
    count, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.compile_s, self.compiles = 0.0, 0
        self.hits = self.misses = 0
        self.programs: list[tuple[str, float]] = []  # (jitted function, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1
            self.programs.append((str(kw.get("fun_name", "?")), secs))

    def slowest(self, since: int = 0, n: int = 8) -> list:
        """The n slowest compiles (or cache reads) after the first `since`."""
        return sorted(self.programs[since:], key=lambda p: -p[1])[:n]

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def read(self) -> dict:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


# ---------------------------------------------------------------------------
# the load generator's process


class Generator:
    def __init__(self, config_path: str, traffic_path: str, seed: int, port: int):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"),
             "--config", config_path, "--traffic", traffic_path,
             "--seed", str(seed), "--port", str(port)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=HERE)
        self.lines: list[dict] = []
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            line = line.strip()
            if line:
                self.lines.append(json.loads(line))

    def wait_ready(self, timeout_s: float = 120.0) -> None:
        deadline = time.monotonic() + timeout_s
        while not self.lines:
            if self.proc.poll() is not None or time.monotonic() > deadline:
                raise HarnessFailure("the load generator did not come up")
            time.sleep(0.01)

    def tell(self, line: str) -> None:
        try:
            self.proc.stdin.write(line + "\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, ValueError):
            pass  # it has already stopped by its own clock

    def report(self):
        return next((r for r in self.lines if r.get("done")), None)

    def close(self) -> None:
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self._reader.join(timeout=5)


# ---------------------------------------------------------------------------
# the profiler slice


class Slice:
    """A jax.profiler trace over part of the window: opened `start_s`
    after t0, closed once it is `seconds` old AND has seen `closes` window
    closes (or is twice as old)."""

    def __init__(self, spec: dict, out_dir: str):
        self.start_s, self.seconds = float(spec["start_s"]), float(spec["seconds"])
        self.closes = int(spec.get("window_closes", 2))
        self.dir = out_dir
        self.state = "before"
        self.anchor_mono = self.anchor_wall = None
        self.records0 = self.records1 = self.closes0 = 0

    def tick(self, now: float, t0: float, records_out: int, n_closed: int) -> None:
        import jax

        if self.state == "before" and now >= t0 + self.start_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
                self.anchor_mono, self.anchor_wall = time.monotonic(), time.time()
            self.records0, self.closes0 = records_out, n_closed
            self.state = "open"
        elif self.state == "open":
            age = now - self.anchor_mono
            if (age >= self.seconds and n_closed - self.closes0 >= self.closes) \
                    or age >= 2 * self.seconds:
                self.stop(records_out)

    def stop(self, records_out: int) -> None:
        import jax

        if self.state != "open":
            return
        with jax.profiler.TraceAnnotation(trace_reduce.END):
            self.records1 = records_out
        jax.profiler.stop_trace()
        self.state = "closed"

    def annotate(self, name: str):
        import jax

        if self.state == "open":
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# the window


def run_window(served, generator, t0: float, seconds: float, slice_=None) -> dict:
    """Drive the feeder for `seconds` and then until everything sent has
    been taken: the clock stops when the last record is on the device and
    the device is done. Returns the flushed DocBatches with the time each
    reached the harness, and both ends of the clock."""
    feeder = served.feeder
    flushed, on_host = [], {}

    def take(out, now):
        for db in served.documents(out):
            flushed.append(db)
            on_host[int(db.timestamp[0])] = now

    base = feeder.get_counters()
    base_in, base_out = base["records_in"], base["records_out"]
    deadline = t0 + seconds
    generator.tell(f"start {t0!r} {seconds!r}")
    quit_sent, seen, report = False, 0, None
    while True:
        with (slice_.annotate("chipbench.pump") if slice_
              else contextlib.nullcontext()):
            out = feeder.pump()
        now = time.monotonic()
        take(out, now)
        c = feeder.get_counters()
        if c["emit_failures"] or c["degraded_entries"]:
            raise HarnessFailure(
                f"the feeder's dispatch into the fused step failed: {c}")
        taken = c["records_in"] - base_in
        if slice_ is not None:
            slice_.tick(now, t0, c["records_out"] - base_out, len(on_host))
        if now >= deadline:
            if not quit_sent:
                generator.tell("q")
                quit_sent = True
            report = report or generator.report()
            if report is not None and taken >= report["sent_records"]:
                break
            if now > deadline + DRAIN_TIMEOUT_S:
                raise HarnessFailure(
                    f"feeder starved: took {taken}, generator said {report}")
        elif taken != seen:
            generator.tell(f"t {taken}")
        if taken == seen:
            time.sleep(IDLE_SLEEP_S)
        seen = taken
    take(feeder.flush(), time.monotonic())
    served.block()
    t_end = time.monotonic()
    if slice_ is not None:
        slice_.stop(feeder.get_counters()["records_out"] - base_out)
    return {"t0": t0, "t_end": t_end, "flushed": flushed, "on_host": on_host,
            "report": report}


# ---------------------------------------------------------------------------
# the comparison that decides `correct`


def by_window(docbatches: list) -> dict:
    out: dict = {}
    for db in docbatches:
        w = int(db.timestamp[0])
        if not (bool(db.valid.all()) and bool((db.timestamp == w).all())):
            raise HarnessFailure("a flushed batch has invalid rows or spans windows")
        t, m = out.get(w, (None, None))
        out[w] = (db.tags if t is None else np.concatenate([t, db.tags]),
                  db.meters if m is None else np.concatenate([m, db.meters]))
    return out


def check(schema, source, schedule, sent_seconds, got, closed_in_window,
          seed, flushed, store_dir) -> dict:
    """The base checks, which every configuration gets and none can switch
    off: every compared number -> (value, limit).

    All windows: each holds exactly the records sent for it (the sum of
    `packet_tx` over its edge documents against the generator's own sum),
    and every second sent has its window. A sample drawn from the seed
    (the last window closed inside the timed window, and SAMPLED_WINDOWS-1
    others) and the prefix window: row by row against the reference. The
    prefix window besides: the program's scalar oracle, and the store and
    query through the composed Server."""
    import server_check

    names = [d["name"] for d in schema["doc_tags"]]
    code, edge = names.index("code_id"), schema["enums"]["code_edge_ip_port"]
    lane = [m["name"] for m in schema["flow_meter"]].index("packet_tx")
    sent = {gen.T0 + s["second"]: s for s in sent_seconds}
    gap = 0
    for w, s in sent.items():
        if w in got:
            tags, meters = got[w]
            have = int(meters[tags[:, code] == edge, lane].astype(np.float64).sum())
            gap = max(gap, abs(have - s["edge_packet_tx"]))
    numbers = {
        "windows_missing": (len(set(sent) - set(got)), 0),
        "windows_unsent": (len(set(got) - set(sent)), 0),
        "edge_packet_tx_gap": (gap, 0),
    }

    full = sorted(w for w in closed_in_window if w != gen.T0 and w in sent)
    rng = np.random.default_rng([int(seed), 0x5A])
    sample = set(full[-1:])
    rest = [w for w in full if w not in sample]
    sample |= set(rng.choice(rest, min(SAMPLED_WINDOWS - 1, len(rest)),
                             replace=False).tolist()) if rest else set()

    def records_sent(w):
        """Window w's records as the generator made them, cut to what it
        sent (the last second of a run may be partial)."""
        s = sent[w]
        tags, meters = source.second(s["second"], schedule.records_in_second(s["second"]))
        return tags[:, :s["records"]], meters[:s["records"]]

    def one(w):
        return reference.compare_docs(
            schema, *got[w], *reference.reference_docs(schema, *records_sent(w)))

    windows = sorted(sample | ({gen.T0} & set(got) & set(sent)))
    with concurrent.futures.ThreadPoolExecutor(max_workers=4) as pool:
        results = list(pool.map(one, windows))
    worst = reference.merge_worst(results)
    numbers.update({k: (worst[k], lim) for k, lim in reference.LIMITS.items()})
    numbers["windows_compared"] = (len(windows), None)
    numbers["docs_compared"] = (sum(r["docs"] for r in results), None)

    if gen.T0 in got and gen.T0 in sent:
        o = reference.compare_docs(
            schema, *got[gen.T0],
            *server_check.oracle_docs(schema, *records_sent(gen.T0)))
        for k, lim in reference.LIMITS.items():
            numbers[f"oracle.{k}"] = (o[k], lim)
        srv = server_check.through_server(
            *got[gen.T0], gen.T0, store_dir, *flushed)
        numbers.update({k: (srv[k], server_check.LIMITS[k]) for k in srv})
    else:
        numbers["prefix_window_missing"] = (1, 0)
    return numbers


def guarantees_of(served) -> tuple:
    """The counters held to 0: sut.GUARANTEE_COUNTERS, or the longer
    list of a deployment that has more to lose."""
    import sut

    short = set(sut.GUARANTEE_COUNTERS) - set(served.guarantee_counters)
    if short:
        raise HarnessFailure(f"the deployment drops guarantee counters "
                             f"{sorted(short)}: the list may grow, never shrink")
    return tuple(served.guarantee_counters)


def named_checks(names: list, ctx: dict, numbers: dict) -> None:
    """The configuration's own checks, after the base checks and outside
    the window: checks/<name>.py exports `check(ctx) -> {number: (value,
    limit)}`, merged into `numbers`. `ctx`: schema, source, schedule,
    sent_seconds (the generator's report of each event-second sent), got
    (window -> (tags, meters) as by_window gives them), closed_in_window,
    seed, config, side_outputs (the deployment's). A number that is
    already there is an error: a named check adds, it replaces nothing."""
    import sut

    for name in names:
        for k, pair in sut.load_named("check", name, CHECK_DIRS).check(ctx).items():
            if k in numbers:
                raise HarnessFailure(
                    f"check {name!r} gives the number {k!r}, which is already compared")
            numbers[k] = tuple(pair)


# ---------------------------------------------------------------------------
# one run


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             *, workdir: str, on_built=None, device=None) -> dict:
    """Everything after the look for a chip. `on_built(served)` lets a
    test break the timed path underneath; `device` is the run's device
    record (platform, kind, count)."""
    import jax

    import sut

    clock = CompileClock()
    config, traffic = spec["config"], spec["traffic"]
    schema = gen.load_schema()
    schedule = gen.Schedule(traffic, schema["wire"]["rows_per_frame"])
    source = gen.FlowSource(schema, config["population"], seed, schedule.key_draw)
    served = sut.build(config)
    generator = Generator(spec["config_path"], spec["traffic_path"], seed, served.port)
    try:
        guarantees = guarantees_of(served)
        if on_built is not None:
            on_built(served)
        warm_docs = served.warm_up(schema, source, schedule)
        generator.wait_ready()
        say(stage="set up", queue=served.queue_kind, warm_up_docs=warm_docs,
            slowest_compiles=clock.slowest(), **clock.read())
        # Nothing should compile inside the window: since PR 27 no program
        # on the close path has a shape that depends on a document count.
        # The persistent cache goes off all the same, so that a compile
        # that comes back is paid in full by every run and counted
        # (`compiles_in_window`), not hidden by a cell's second run finding
        # on disk what the first one left there.
        persistent_cache(False)
        c0, s0, k0 = served.counters(), served.spans(), clock.read()
        slice_ = None
        if trace:
            slice_ = Slice(traffic["trace_slice"], os.path.join(workdir, "trace"))
        t0 = time.monotonic() + 0.05  # the first frame of the window
        setup_s = t0 - _PROCESS_START
        win = run_window(served, generator, t0, seconds, slice_)
        c1, s1, k1 = served.counters(), served.spans(), clock.read()
        persistent_cache(True)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices())
        elapsed = win["t_end"] - win["t0"]
        compile_s = k1["compile_s"] - k0["compile_s"]
        report = win["report"]
        closed_in_window = set(win["on_host"])
        after = served.documents(served.drain())
        got = by_window(win["flushed"] + after)
        counters = {k: c1[k] - c0.get(k, 0) for k in c1}
        absent = [k for k in guarantees if k not in counters]
        if absent:
            raise HarnessFailure(f"the deployment does not count {absent}")
        spans = {n: {f: s1[n][f] - s0.get(n, {}).get(f, 0) for f in s1[n]}
                 for n in s1}
        folded = counters["feeder.records_out"] - counters["feeder.lost_records"]
        say(stage="window", elapsed_s=elapsed, records_sent=report["sent_records"],
            records_folded=folded, windows_closed=len(closed_in_window),
            windows_after_drain=len(got), batches=counters["feeder.batches_out"],
            pad_rows=counters["feeder.pad_rows"],
            compiles_in_window=k1["compiles"] - k0["compiles"],
            compile_s_in_window=compile_s,
            compiled_in_window=clock.programs[k0["compiles"]:k1["compiles"]],
            jit_compiles=c1.get("pipeline.jit_compiles"))

        # the guarantees: nothing shed, lost, overwritten, refused, retraced
        numbers = {k: (counters[k], 0) for k in guarantees}
        numbers["records_unaccounted"] = (report["sent_records"] - folded, 0)
        t_check = time.monotonic()
        numbers.update(check(schema, source, schedule, report["seconds"], got,
                             closed_in_window, seed,
                             (served.flushed_docs(), served.stats_module),
                             os.path.join(workdir, "store")))
        named_checks(config.get("checks", []), {
            "schema": schema, "source": source, "schedule": schedule,
            "sent_seconds": report["seconds"], "got": got,
            "closed_in_window": closed_in_window, "seed": seed,
            "config": config, "side_outputs": served.side_outputs()}, numbers)
        check_s = time.monotonic() - t_check
        correct = all(lim is None or v <= lim for v, lim in numbers.values())
        rows = schema["wire"]["rows_per_frame"]
        failed = min(report["sent_records"], int(
            abs(numbers["records_unaccounted"][0])
            + counters["feeder.shed_records"] + counters["feeder.lost_records"]
            + counters["pipeline.drop_before_window"]
            + counters["pipeline.prereduce_shed"]
            + rows * (counters["feeder.queue_overwritten"]
                      + counters["feeder.bad_frames"]
                      + counters["receiver.bad_frames"])
            + sum(s["records"] for s in report["seconds"]
                  if gen.T0 + s["second"] not in got)))

        # end-to-end
        e2e = {"setup_s": setup_s, "records_per_s": folded / elapsed}
        if k1["compiles"] == k0["compiles"]:
            # the same rate under the name that carries the tight bound: it
            # exists only where nothing compiled inside the window, so a
            # control cell that starts compiling there reports no such metric
            e2e["steady_records_per_s"] = e2e["records_per_s"]
        planes = {"spans": spans, "counters": counters, "generator": report,
                  "schema": {"record_bytes": trace_reduce.record_bytes(schema)},
                  "run": {"windows_closed": len(closed_in_window),
                          "compile_s_in_window": compile_s,
                          "compiles_in_window": k1["compiles"] - k0["compiles"],
                          "records": folded, "elapsed_s": elapsed,
                          "elapsed_less_compile_s": elapsed - compile_s, **e2e}}
        dev = dict(device or {})
        dev["memory_peak_bytes"] = int(peak) if peak else None
        breakdown = None
        if slice_ is not None and slice_.state == "closed" \
                and dev.get("platform") == "tpu":  # no device plane off the chip
            events = trace_reduce.extract(trace_reduce.find_xplane(slice_.dir))
            host_spans = [(r.name, r.start_s, r.duration_us / 1e6)
                          for tr in served.tracers() for r in tr.recent()]
            red = trace_reduce.reduce(events, trace_reduce.load_groups(),
                                      host_spans, slice_.anchor_wall)
            slice_records = slice_.records1 - slice_.records0
            red["slice_records"] = slice_records
            planes["trace"] = red
            planes["peaks"] = trace_reduce.load_peaks(dev.get("kind", ""))
            dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            say(stage="trace", lines_seen=events["lines_seen"],
                module_s=red["module_s"], longest_gap_s=red["longest_gap_s"],
                slice_records=slice_records, per_device=red["per_device"])
            shutil.rmtree(slice_.dir, ignore_errors=True)

        metrics = {}
        if trace:
            # the window's raw counter deltas, for a reader of the run's
            # output: the last line holds metrics only
            say(stage="counters", counters=counters)
            for m in spec["per_layer"]:
                value = layers.read_metric(layers.load_layer(m["name"]), planes)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in spec["end_to_end"]:
                if m["name"] in e2e:
                    metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        say(stage="checked", check_s=check_s, drain_s=elapsed - seconds,
            spans={n: s["total_us"] for n, s in spans.items()})
        result = {"correct": bool(correct), "attempted": report["sent_records"],
                  "failed": failed, "metrics": metrics, "device": dev}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["checks"] = {k: {"value": v, "limit": lim}
                            for k, (v, lim) in numbers.items()}
        return result
    finally:
        generator.close()
        served.close()
        shutil.rmtree(os.path.join(workdir, "store"), ignore_errors=True)


def persistent_cache(on: bool) -> None:
    """Switch JAX's persistent compilation cache (reads and writes) for
    what compiles from here on; the directory stays where it is."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def find_chips(n: int) -> dict:
    """The device record, or exit where JAX finds no TPU or too few."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chipbench needs a TPU: {e}")
    d = devices[0]
    if d.platform != "tpu" or len(devices) < n:
        raise SystemExit(
            f"chipbench needs {n} TPU chip(s); JAX found {len(devices)} x "
            f"{d.platform} ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "deepflow_tpu")):
        raise SystemExit("chipbench needs the program (deepflow_tpu/) beside it")
    spec = load_cell(a.workload)
    workdir = os.path.join(ROOT, ".chipbench", a.workload)
    os.makedirs(workdir, exist_ok=True)

    from deepflow_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = find_chips(int(spec["cell"]["chips"]))
    say(stage="start", workload=a.workload, seed=a.seed, seconds=a.seconds,
        trace=a.trace, device=device, compile_cache=cache_dir)
    result = run_cell(spec, a.seed, a.seconds, bool(a.trace),
                      workdir=workdir, device=device)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
