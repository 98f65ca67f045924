"""The load generator: a process of its own that never imports JAX.

The chip belongs to the runner; this process makes the records from the
seed (gen.py), encodes them as TAGGEDFLOW frames (wire.py) and writes
them to the Receiver's TCP port over the traffic file's `clients`
connections (1 where it says nothing), dealing the frames to them in
turn: frame i of the run goes out on connection i mod clients, so one
client sends the frames in today's order and several send the same frames.
It keeps its own clock for the window:
the runner's `start <t0> <seconds>` line fixes both ends, and no frame
goes out at or after t0 + seconds. The loop is closed: a frame goes out
whenever fewer than the budget (`in_flight_event_seconds` event-seconds'
records) are in flight, that is sent and not yet taken by the feeder; the
runner writes `t <records taken>` lines as the feeder takes them, `q` to
end.

stdout: `{"ready": true}` once connected and two event-seconds are made,
then one last line with what was sent: per event-second the records,
frames and the check sum the runner holds every flushed window to.
"""

from __future__ import annotations

import argparse
import json
import queue
import socket
import sys
import threading
import time

import numpy as np

import gen
import wire


def edge_packet_tx(schema: dict, tags: np.ndarray, meters: np.ndarray,
                   rows: int) -> np.ndarray:
    """Cumulative, at each frame's end, of what the records sent so far
    add to `packet_tx` summed over a window's EDGE documents: a flow's
    meter lands unreversed on one edge document per known direction, or
    on one if it knows neither."""
    f = schema["flow_record_tag_fields"].index
    s0 = tags[f("direction0")] != 0
    s1 = tags[f("direction1")] != 0
    mult = s0.astype(np.int64) + s1 + (~s0 & ~s1)
    lane = [m["name"] for m in schema["flow_meter"]].index("packet_tx")
    contrib = meters[:, lane].astype(np.int64) * mult
    starts = np.arange(0, contrib.size, rows)
    return np.cumsum(np.add.reduceat(contrib, starts)) if contrib.size else starts


class Producer(threading.Thread):
    """Makes event-seconds ahead of the sender, two at the most."""

    def __init__(self, source, schedule, schema):
        super().__init__(daemon=True)
        self.source, self.schedule, self.schema = source, schedule, schema
        self.out: queue.Queue = queue.Queue(maxsize=2)
        self.stop = threading.Event()

    def run(self):
        k = 0
        while not self.stop.is_set():
            n = self.schedule.records_in_second(k)
            tags, meters = self.source.second(k, n)
            frames = wire.encode_frames(tags, meters, self.schema["wire"])
            check = edge_packet_tx(self.schema, tags, meters, self.schedule.rows)
            item = (k, n, frames, check)
            while not self.stop.is_set():
                try:
                    self.out.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            k += 1


class Taken(threading.Thread):
    """Reads the runner's lines: `t <n>` = the feeder has taken n records
    of the window so far; `q` (or the pipe's end) = stop."""

    def __init__(self):
        super().__init__(daemon=True)
        self.records, self.quit = 0, False

    def run(self):
        for line in sys.stdin:
            word = line.split()
            if word[:1] == ["t"]:
                self.records = int(word[1])
            else:
                break
        self.quit = True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    a = ap.parse_args(argv)
    with open(a.config) as f:
        config = json.load(f)
    with open(a.traffic) as f:
        traffic = json.load(f)
    schema = gen.load_schema()
    schedule = gen.Schedule(traffic, schema["wire"]["rows_per_frame"])
    producer = Producer(
        gen.FlowSource(schema, config["population"], a.seed, schedule.key_draw),
        schedule, schema)
    producer.start()
    socks = []
    for _ in range(int(traffic.get("clients", 1))):
        sock = socket.create_connection(("127.0.0.1", a.port), timeout=30)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock.settimeout(None)
        socks.append(sock)
    while producer.out.qsize() < 2:
        time.sleep(0.01)
    print(json.dumps({"ready": True}), flush=True)

    start = sys.stdin.readline().split()
    if len(start) != 3 or start[0] != "start":  # the runner gave up
        producer.stop.set()
        for sock in socks:
            sock.close()
        return 1
    deadline = float(start[1]) + float(start[2])
    taken = Taken()
    taken.start()
    budget = schedule.budget
    sent, total, dealt = [], 0, 0
    try:
        while time.monotonic() < deadline and not taken.quit:
            k, n, frames, check = producer.out.get()
            done = 0
            for j, frame in enumerate(frames):
                while (total - taken.records >= budget and not taken.quit
                       and time.monotonic() < deadline):
                    time.sleep(0.0005)
                if taken.quit or time.monotonic() >= deadline:
                    break
                socks[dealt % len(socks)].sendall(frame)
                dealt += 1
                done += 1
                total += min(schedule.rows, n - j * schedule.rows)
            if done:
                sent.append({
                    "second": k, "frames": done,
                    "records": min(done * schedule.rows, n),
                    "edge_packet_tx": int(check[done - 1]),
                })
            if done < len(frames):
                break
    finally:
        producer.stop.set()
        for sock in socks:
            sock.close()
    print(json.dumps({
        "done": True, "seconds": sent,
        "sent_records": sum(s["records"] for s in sent),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
