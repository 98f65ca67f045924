"""Agent daemon composition: pcap replay → full pipeline graph → wire →
server tables (the trident.rs wiring seat, end to end)."""

from __future__ import annotations

import time

import numpy as np
import pytest

from deepflow_tpu.agent.main import Agent, AgentConfig
from deepflow_tpu.agent.packet import TCP_ACK, TCP_PSH, TCP_SYN, craft_tcp, to_batch
from deepflow_tpu.agent.pcap import write_pcap
from deepflow_tpu.aggregator.window import WindowConfig
from deepflow_tpu.ingest.framing import MessageType
from deepflow_tpu.querier.sqlparse import SQLError

T0 = 1_700_000_000
CLI, SRV = 0x0A000001, 0x0A000002


class _ListSender:
    def __init__(self):
        self.msgs = []

    def send(self, msgs):
        self.msgs.extend(msgs)


def _http_session(sport, t):
    req = b"GET /api/cart HTTP/1.1\r\nHost: shop\r\n\r\n"
    resp = b"HTTP/1.1 200 OK\r\nContent-Length: 0\r\n\r\n"
    return [
        (t, 0, craft_tcp(CLI, SRV, sport, 80, flags=TCP_SYN, seq=1)),
        (t, 200, craft_tcp(SRV, CLI, 80, sport, flags=TCP_SYN | TCP_ACK, seq=9, ack=2)),
        (t, 400, craft_tcp(CLI, SRV, sport, 80, flags=TCP_ACK, seq=2, ack=10)),
        (t, 600, craft_tcp(CLI, SRV, sport, 80, flags=TCP_ACK | TCP_PSH, seq=2, ack=10, payload=req)),
        (t + 1, 0, craft_tcp(SRV, CLI, 80, sport, flags=TCP_ACK | TCP_PSH, seq=10, ack=2 + len(req), payload=resp)),
    ]


def test_agent_pcap_replay_produces_all_outputs(tmp_path):
    pkts = []
    for i in range(8):
        pkts += _http_session(40000 + i, T0 + (i % 3))
    # far-future FIN-less tail so windows close during replay
    pkts.append((T0 + 120, 0, craft_tcp(CLI, SRV, 39999, 80, flags=TCP_SYN, seq=1)))
    path = tmp_path / "replay.pcap"
    write_pcap(path, pkts)

    senders = {mt: _ListSender() for mt in
               (MessageType.METRICS, MessageType.TAGGEDFLOW, MessageType.PROTOCOLLOG)}
    agent = Agent(
        AgentConfig(metrics_window=WindowConfig(capacity=1 << 12), batch_size=256),
        senders=senders,
    )
    counters = agent.run_pcap(path, batch_size=64)

    assert counters["packets"] == len(pkts)
    assert counters["docs_sent"] > 0
    assert counters["logs_sent"] >= 8  # 8 paired request+response sessions
    assert senders[MessageType.METRICS].msgs
    assert senders[MessageType.TAGGEDFLOW].msgs
    assert senders[MessageType.PROTOCOLLOG].msgs

    # metric docs decode and include both granularities
    from deepflow_tpu.ingest.codec import DocumentDecoder

    dec = DocumentDecoder()
    batches = dec.decode(senders[MessageType.METRICS].msgs)
    flags = np.concatenate([b.flags for b in batches.values()])
    assert (flags & 1).any() and (flags & 1 == 0).any()  # 1s and 1m docs


def test_agent_to_server_e2e(tmp_path):
    """Real sockets: Agent senders → Server receiver → queryable tables."""
    from deepflow_tpu.server.main import Server
    from deepflow_tpu.utils.config import load_config

    cfg, _ = load_config(
        {
            "receiver": {"tcp_port": 0, "udp_port": 0},
            "ingester": {"n_decoders": 1, "prefer_native": False},
            "storage": {"root": str(tmp_path / "store"), "writer_flush_s": 0.05},
        }
    )
    srv = Server(cfg).start()
    try:
        pkts = []
        for i in range(4):
            pkts += _http_session(41000 + i, T0 + i)
        pkts.append((T0 + 120, 0, craft_tcp(CLI, SRV, 39998, 80, flags=TCP_SYN, seq=1)))
        path = tmp_path / "e2e.pcap"
        write_pcap(path, pkts)

        agent = Agent(
            AgentConfig(
                servers=(("127.0.0.1", srv.receiver.tcp_port),),
                metrics_window=WindowConfig(capacity=1 << 12),
                batch_size=256,
            )
        )
        agent.run_pcap(path, batch_size=64)
        agent.close()

        # under full-suite load the throttler's wall-clock hold and the
        # writer flush can lag; poll the query surface itself
        deadline = time.time() + 60
        m = l7 = None
        while time.time() < deadline:
            if (
                srv.flow_metrics.counters["docs_written"] > 0
                and srv.flow_log.get_counters()["rows_written"] > 0
            ):
                srv.doc_writer.flush()
                srv.flow_log.flush()
                try:
                    m = srv.query.execute(
                        "SELECT packet_tx FROM flow_metrics.network_1s"
                    )
                    l7 = srv.query.execute(
                        "SELECT endpoint, status_code FROM flow_log.l7_flow_log"
                    )
                except (KeyError, SQLError):
                    # tables are created lazily on first write: under
                    # full-suite load "some docs written" can race the
                    # specific table's creation — keep polling
                    m = l7 = None
                if m is not None and m.rows > 0 and l7.rows > 0:
                    break
            time.sleep(0.1)
        assert m is not None and m.rows > 0
        assert l7 is not None and l7.rows > 0
        eps = {r["endpoint"] for r in l7.to_dicts()}
        assert "/api/cart" in eps
    finally:
        srv.stop()


def test_ebpf_bridge_sessions_skip_l4_metrics():
    """Socket-data events flow through the L7 engine, come out tagged
    SignalSource.EBPF, feed the L7 metric plane but never the L4 one
    (ebpf_dispatcher seat; quadruple_generator.rs:420-423 gate)."""
    import jax.numpy as jnp

    from deepflow_tpu.agent.ebpf_bridge import EbpfDispatcher, SocketDataEvent
    from deepflow_tpu.agent.l7.engine import L7Engine
    from deepflow_tpu.aggregator.fanout import FanoutConfig, fanout_l4, fanout_l7
    from deepflow_tpu.datamodel.code import SignalSource
    from deepflow_tpu.flowlog.schema import L7_FLOW_LOG

    disp = EbpfDispatcher(L7Engine())
    req = SocketDataEvent(
        pid=7, ip_src=CLI, ip_dst=SRV, port_src=41000, port_dst=80,
        protocol=6, direction=0,
        payload=b"GET /k HTTP/1.1\r\nHost: h\r\n\r\n",
        timestamp_us=T0 * 10**6,
    )
    resp = SocketDataEvent(
        pid=7, ip_src=CLI, ip_dst=SRV, port_src=41000, port_dst=80,
        protocol=6, direction=1,
        payload=b"HTTP/1.1 200 OK\r\n\r\n",
        timestamp_us=T0 * 10**6 + 900,
    )
    log_batch, app_batch = disp.process([req, resp])
    assert log_batch.size == 1  # paired session
    ii = L7_FLOW_LOG.int_index
    assert log_batch.ints[0, ii("signal_source")] == int(SignalSource.EBPF)
    assert log_batch.ints[0, ii("response_duration")] == 900  # µs rrt

    from deepflow_tpu.datamodel.schema import FLOW_METER

    tags = {k: jnp.asarray(v) for k, v in app_batch.tags.items()}
    app_meters = jnp.asarray(app_batch.meters)
    valid = jnp.asarray(app_batch.valid)
    # L4 gate: same tags with FLOW_METER-shaped meters must emit nothing
    l4_meters = jnp.zeros((app_batch.meters.shape[0], FLOW_METER.num_fields))
    _t, _m, _ts, l4_valid = fanout_l4(tags, l4_meters, valid, FanoutConfig())
    assert not bool(np.asarray(l4_valid).any())
    _t, _m, _ts, l7_valid = fanout_l7(tags, app_meters, valid, FanoutConfig())
    assert bool(np.asarray(l7_valid).any())


def test_live_capture_loopback():
    """AF_PACKET live capture (dispatcher recv_engine seat): real UDP
    datagrams over loopback flow through capture → parse → FlowMap.
    Skipped where the container withholds CAP_NET_RAW."""
    import socket as pysocket
    import threading
    import time as pytime

    import pytest

    try:
        probe = pysocket.socket(
            pysocket.AF_PACKET, pysocket.SOCK_RAW, pysocket.htons(0x0003)
        )
        probe.bind(("lo", 0))
        probe.close()
    except (PermissionError, OSError):
        pytest.skip("AF_PACKET unavailable")

    agent = Agent(AgentConfig(batch_size=256), senders={})

    def blast():
        pytime.sleep(0.2)
        tx = pysocket.socket(pysocket.AF_INET, pysocket.SOCK_DGRAM)
        for i in range(80):
            tx.sendto(b"live-capture-probe-%d" % i, ("127.0.0.1", 39099))
        tx.close()

    t = threading.Thread(target=blast)
    t.start()
    stats = agent.run_live("lo", duration_s=1.5)
    t.join()
    assert stats["capture"]["frames"] >= 80
    assert stats["packets"] >= 80  # parsed + injected into FlowMap
    agent.close()


def test_live_capture_ring_loopback(monkeypatch):
    """TPACKET_V3 mmap block-ring capture (recv_engine/af_packet fast
    path): real UDP over loopback through ring → parse → FlowMap."""
    import socket as pysocket
    import threading
    import time as pytime

    import pytest

    from deepflow_tpu.agent import capture as capture_mod

    try:
        probe = capture_mod.AfPacketRingCapture("lo")
        probe.close()
    except (PermissionError, OSError):
        pytest.skip("AF_PACKET ring unavailable")

    up, over, captures = threading.Event(), threading.Event(), []

    class AnnouncedCapture(capture_mod.AfPacketRingCapture):
        """Says when its ring is mapped and bound: a datagram sent before
        that is never captured."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            captures.append(self)
            up.set()

    monkeypatch.setattr(capture_mod, "AfPacketRingCapture", AnnouncedCapture)
    agent = Agent(AgentConfig(batch_size=256), senders={})

    def chatter():
        up.wait()
        if not captures:
            return  # the run ended before a capture was built
        s = pysocket.socket(pysocket.AF_INET, pysocket.SOCK_DGRAM)
        i = 0
        # the ring hands frames over a block at a time: keep the traffic
        # up until the capture has counted its 120, or the run is over
        while captures[0].counters["frames"] < 120 and not over.is_set():
            s.sendto(b"ring-%d" % i, ("127.0.0.1", 39998))
            i += 1
            pytime.sleep(0.002)
        s.close()

    t = threading.Thread(target=chatter)
    t.start()
    try:
        stats = agent.run_live("lo", duration_s=1.5, ring=True)
    finally:
        over.set()
        up.set()
        t.join(30)
    assert not t.is_alive()
    agent.close()
    assert stats["capture"]["frames"] >= 120, stats["capture"]
    assert stats["capture"]["blocks"] >= 1
    assert agent.counters["packets"] >= 120
