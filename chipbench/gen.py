"""Traffic generation: flow records and their send schedule.

NumPy only — nothing of the program, and no JAX: the load generator's
process (loadgen.py) must never touch the chip, and the runner calls the
same functions after the window to regenerate, for the reference, exactly
what was sent. Copied in substance from `deepflow_tpu/ingest/replay.py`
`SyntheticFlowGen.flow_batch` (the original is listed in PERF.md for a
later PR to delete), with these changes: each event-second draws from
generators of its own, so any second can be regenerated alone; the records
come out in the wire's layout (a column-major tag matrix in
`flow_record_tag_fields` order and a row-major f32 meter matrix) instead
of as a program `FlowBatch`; and the KEYS (the flow population and which
flow each record belongs to) come from the configuration's own
`population.seed`, the METERS from `--seed`.

Why the keys do not follow `--seed`: the program compiles a slice and a
reshape for every new document count it flushes, and that compile takes
1 to 90 s on the chip depending on the count (PERF.md section 6). Keys
from `--seed` would give every seed other counts, so other work. This
way every seed of a cell closes the same windows' document counts in the
same order, and differs in every meter value.

One general generator reads the two kinds of data file:

  population (a configuration's `population`): `tuples` distinct
      5-tuples; `keys` = "uniform", or "zipf" with exponent `zipf_s`
      (a record's flow is rank r with probability ~ r^-s); `seed`.
  traffic (chipbench/traffic/<name>.json): closed loop. Event-seconds of
      `records_per_event_second` records go out as long as fewer than
      `in_flight_event_seconds` seconds' records are in flight; event time
      runs as fast as the system takes it. `key_draw` = "each_second"
      (every event-second draws its records' flows anew: the set of
      active flows, and with it the window's document count, changes
      from second to second as in a replay) or "same_every_second" (one
      draw serves every second: the same flows are active in every
      window). `prefix_records` = the size of event-second 0, the small
      window that the scalar oracle and the store/query check read.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
T0 = 1_700_000_000  # event time of event-second 0


def load_schema() -> dict:
    with open(os.path.join(HERE, "schema.json")) as f:
        return json.load(f)


class FlowSource:
    """The flow population of one run and its per-second records."""

    def __init__(self, schema: dict, population: dict, seed: int,
                 key_draw: str = "each_second"):
        if key_draw not in ("each_second", "same_every_second"):
            raise ValueError(f"unknown key_draw {key_draw!r}")
        self.schema = schema
        self.seed = int(seed)
        self.key_seed = int(population.get("seed", 0))
        self.key_draw = key_draw
        self.tag_fields = list(schema["flow_record_tag_fields"])
        self.meter_fields = [m["name"] for m in schema["flow_meter"]]
        self.n = n = int(population["tuples"])
        e = schema["enums"]
        keys = population.get("keys", "uniform")
        if keys == "uniform":
            self.cdf = None
        elif keys == "zipf":
            p = np.arange(1, n + 1, dtype=np.float64) ** -float(population["zipf_s"])
            self.cdf = np.cumsum(p / p.sum())
        else:
            raise ValueError(f"unknown key distribution {keys!r}")
        rng = np.random.default_rng([self.key_seed, 0])
        self.pop = {
            "ip0_w3": rng.integers(0x0A000000, 0x0AFFFFFF, n, dtype=np.uint32),
            "ip1_w3": rng.integers(0x0A000000, 0x0AFFFFFF, n, dtype=np.uint32),
            "server_port": rng.choice(
                np.array([80, 443, 3306, 6379, 8080, 9092], np.uint32), n),
            "protocol": rng.choice(np.array([6, 6, 6, 17], np.uint32), n),
            "l3_epc_id": rng.integers(1, 50, n, dtype=np.uint32),
            "l3_epc_id1": rng.integers(1, 50, n, dtype=np.uint32),
            "pod_id": rng.integers(1, 500, n, dtype=np.uint32),
            "gpid0": rng.integers(0, 1000, n, dtype=np.uint32),
            "gpid1": rng.integers(0, 1000, n, dtype=np.uint32),
        }
        u = rng.random(n)  # 70% both directions known, 20% one, 10% none
        self.pop["direction0"] = np.where(
            u < 0.9, np.uint32(e["direction_client_to_server"]), np.uint32(0))
        self.pop["direction1"] = np.where(
            u < 0.7, np.uint32(e["direction_server_to_client"]), np.uint32(0))
        self.const = {
            "global_thread_id": 1, "agent_id": 1,
            "signal_source": e["signal_source_packet"], "tap_type": 3,
            "tap_port": 1, "is_active_host0": 1, "is_active_host1": 1,
            "is_active_service": 1,
        }

    def flows(self, k: int, n: int, stream: int = 1) -> np.ndarray:
        """Which flow (index into the population) each of event-second
        k's `n` records belongs to."""
        draw = 0 if self.key_draw == "same_every_second" else int(k)
        rng = np.random.default_rng([self.key_seed, 1, int(stream), draw])
        if self.cdf is None:
            return rng.integers(0, self.n, n)
        return np.minimum(np.searchsorted(self.cdf, rng.random(n)), self.n - 1)

    def second(self, k: int, n: int, stream: int = 1) -> tuple[np.ndarray, np.ndarray]:
        """Event-second k's `n` records: (tags [T, n] u32, meters [n, M]
        f32), all stamped T0 + k. Stream 1 is the window's traffic; the
        warm-up draws from another, so that it sends no window's records."""
        idx = self.flows(k, n, stream)
        rng = np.random.default_rng([self.seed, int(stream), int(k)])
        pkts = rng.integers(1, 100, n)
        nbytes = pkts * rng.integers(64, 1400, n)
        rtt = rng.integers(100, 50_000, n)
        tags = np.zeros((len(self.tag_fields), n), np.uint32)
        for i, f in enumerate(self.tag_fields):
            if f == "timestamp":
                tags[i] = T0 + int(k)
            elif f in self.pop:
                tags[i] = self.pop[f][idx]
            elif f in self.const:
                tags[i] = self.const[f]
        meters = np.zeros((n, len(self.meter_fields)), np.float32)
        col = self.meter_fields.index
        meters[:, col("packet_tx")] = pkts
        meters[:, col("packet_rx")] = pkts // 2
        meters[:, col("byte_tx")] = nbytes
        meters[:, col("byte_rx")] = nbytes // 2
        meters[:, col("l3_byte_tx")] = nbytes * 9 // 10
        meters[:, col("l3_byte_rx")] = nbytes * 9 // 20
        meters[:, col("rtt_max")] = rtt
        meters[:, col("rtt_sum")] = rtt
        for name in ("new_flow", "rtt_count", "syn", "synack"):
            meters[:, col(name)] = 1
        return tags, meters


class Schedule:
    """How many records each event-second holds and how many may be in
    flight. Pure arithmetic on the traffic file, shared by the generator's
    process and the runner."""

    def __init__(self, traffic: dict, rows_per_frame: int):
        if traffic["loop"] != "closed":
            raise ValueError(f"unknown loop {traffic['loop']!r}")
        self.rows = int(rows_per_frame)
        self.prefix = int(traffic["prefix_records"])
        self.per_second = int(traffic["records_per_event_second"])
        self.key_draw = traffic.get("key_draw", "each_second")
        # records that may be sent and not yet taken by the feeder
        self.budget = self.per_second * int(traffic.get("in_flight_event_seconds", 1))
        # event-seconds whose windows the set-up closes once, so that the
        # window finds their close's programs compiled (see sut.warm_up)
        self.warm_up_seconds = [int(k) for k in traffic.get("warm_up_event_seconds", [])]

    def records_in_second(self, k: int) -> int:
        return self.prefix if k == 0 else self.per_second
