"""loadgen.py's `clients`: one client (or none named) sends the frame
sequence the parent commit's loadgen sent (data/loadgen_frames.json, made
by this file's `__main__` against a copy of the parent), and four send
the same frames, frame i on connection i mod 4.

The test stands in for the runner: a listening socket for the Receiver,
`start` on the generator's stdin, and no `t` line, so the generator sends
exactly its in-flight budget and stops at its own deadline."""

import hashlib
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
if CHIPBENCH not in sys.path:  # run by hand: conftest.py has not
    sys.path.insert(0, CHIPBENCH)

import tiny  # noqa: E402

RECORDING = os.path.join(HERE, "data", "loadgen_frames.json")
SEED = 2**31 + 4242
# three event-seconds in flight: the prefix frame and 9 frames of 2048,
# 2048 and 1904 rows before the budget holds the sender
TRAFFIC = {**tiny.SATURATE, "in_flight_event_seconds": 3}


def frames_of(stream: bytes) -> list[bytes]:
    out, off = [], 0
    while off < len(stream):
        size, = struct.unpack_from(">I", stream, off)
        out.append(stream[off:off + size])
        off += size
    assert off == len(stream), "a connection ended inside a frame"
    return out


def sent_frames(loadgen: str, traffic: dict, tmp_path) -> tuple[list[list[bytes]], dict]:
    """Run `loadgen` against a socket of this test's: the frames each
    connection carried, in the order it was accepted, and the report."""
    cfg, tr = tmp_path / "config.json", tmp_path / "traffic.json"
    cfg.write_text(json.dumps(tiny.CONFIG))
    tr.write_text(json.dumps(traffic))
    clients = int(traffic.get("clients", 1))
    with socket.create_server(("127.0.0.1", 0)) as server:
        server.settimeout(60)
        proc = subprocess.Popen(
            [sys.executable, loadgen, "--config", str(cfg), "--traffic", str(tr),
             "--seed", str(SEED), "--port", str(server.getsockname()[1])],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=os.path.dirname(loadgen))
        streams = [bytearray() for _ in range(clients)]

        def read(conn, into):
            with conn:
                while chunk := conn.recv(1 << 16):
                    into += chunk

        readers = []
        try:
            for into in streams:
                conn, _addr = server.accept()
                readers.append(threading.Thread(target=read, args=(conn, into)))
                readers[-1].start()
            assert json.loads(proc.stdout.readline()) == {"ready": True}
            proc.stdin.write(f"start {time.monotonic()!r} 1.0\n")
            proc.stdin.flush()
            report = json.loads(proc.stdout.readline())
            assert proc.wait(timeout=30) == 0
        finally:
            proc.kill()
            proc.wait()
            for r in readers:
                r.join(timeout=30)
    assert not any(r.is_alive() for r in readers)
    return [frames_of(bytes(s)) for s in streams], report


def digests(frames: list[bytes]) -> list[str]:
    return [hashlib.sha256(f).hexdigest() for f in frames]


@pytest.fixture(scope="module")
def recording():
    with open(RECORDING) as f:
        return json.load(f)


@pytest.mark.parametrize("clients", [None, 1])
def test_one_client_sends_what_the_parent_sent(tmp_path, recording, clients):
    traffic = dict(TRAFFIC) if clients is None else {**TRAFFIC, "clients": clients}
    (frames,), report = sent_frames(os.path.join(CHIPBENCH, "loadgen.py"),
                                    traffic, tmp_path)
    assert digests(frames) == recording["frames"]
    assert report == recording["report"]


def test_four_clients_send_the_same_frames_dealt_in_turn(tmp_path, recording):
    per_conn, report = sent_frames(os.path.join(CHIPBENCH, "loadgen.py"),
                                   {**TRAFFIC, "clients": 4}, tmp_path)
    want = recording["frames"]
    # connections are accepted in the order they were opened or not: tell
    # them apart by their first frame
    by_first = {digests(frames[:1])[0]: digests(frames) for frames in per_conn}
    assert len(by_first) == 4
    for i in range(4):
        assert by_first[want[i]] == want[i::4]
    assert report == recording["report"]


if __name__ == "__main__":
    # python3 chipbench/tests/test_loadgen_clients.py <parent checkout>/chipbench/loadgen.py
    import pathlib
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        (frames,), report = sent_frames(sys.argv[1], TRAFFIC, pathlib.Path(d))
    with open(RECORDING, "w") as f:
        json.dump({"_about": "sha256 of each frame the parent commit's loadgen.py "
                             "(c200a7a) sent for tiny.CONFIG, tiny.SATURATE with 3 "
                             f"event-seconds in flight, seed {SEED}, no `t` line; "
                             "and its report", "frames": digests(frames),
                   "report": report}, f, indent=1)
    print(len(frames), "frames recorded")
