"""Transport v2: binary framing, the multiplexed push stream, the auth
handshake, and the shared dispatcher state bus.

Fast by design — every test runs against fake engines or raw socket
pairs; the real-model streaming scenarios live in ``tools/net_smoke.py``
(``make net-smoke``). Split across four seams:

* framing robustness: the ``_FrameReader`` fuzz surface — truncated,
  oversize, interleaved, and garbage inputs must surface as typed
  ``TransportError{protocol}`` / ``ConnectionError``, never a hang;
* the stream wire end to end: multiplexing, server-pushed tokens and
  terminals, reconnect-through-the-breaker, legacy sniff compat;
* the auth handshake: HMAC hello accepted, wrong/missing token refused
  typed and non-retryable, legacy refused outright when the knob is on,
  and the secret never leaks into build_info;
* the state bus: gossip read/write, self-exclusion, dispatcher
  route-around without a probe, supervisor health-block preservation.
"""

import json
import os
import socket
import struct
import threading
import time

import pytest

import horovod_tpu as hvd
from horovod_tpu import config as hconfig
from horovod_tpu import metrics
from horovod_tpu.serving.scheduler import Request, RequestQueue, RequestStatus
from horovod_tpu.serving.transport import (
    OP_CHALLENGE, OP_HELLO, OP_HELLO_OK, OP_REQUEST, OP_RESPONSE,
    CircuitBreaker, RemoteClient, RemoteDispatcher, SocketReplicaServer,
    TransportError, _FrameReader, _MAX_FRAME, _send_frame, _send_frame2,
    _recv_frame, _StateBus, _V2_MAGIC,
)


@pytest.fixture(autouse=True)
def _restore_world():
    # the connection gauge is fed by a module-global census that spans
    # the whole pytest session (earlier tests leak never-closed
    # clients) — zero it so gauge assertions see only this test's conns
    import horovod_tpu.serving.transport as _t
    with _t._CONN_LOCK:
        for k in _t._CONN_COUNTS:
            _t._CONN_COUNTS[k] = 0
    yield
    for k in ("HOROVOD_SERVE_TRANSPORT", "HOROVOD_SERVE_AUTH_TOKEN",
              "HOROVOD_SERVE_RPC_TIMEOUT", "HOROVOD_SERVE_MAX_RETRIES",
              "HOROVOD_SERVE_HEDGE_MS"):
        os.environ.pop(k, None)
    hconfig.refresh()
    metrics.reset_metrics()


# ---------------------------------------------------------------------------
# engine stand-ins
# ---------------------------------------------------------------------------

class ServeNowEngine:
    """Completes every request instantly: tokens = [0..n)."""

    def __init__(self, name="fake0", slots=4, maxsize=32):
        self.name = name
        self.slots = slots
        self.alive = True
        self.queue = RequestQueue(maxsize=maxsize)

    def start(self):
        pass

    def stop(self):
        pass

    def load(self):
        return self.queue.depth()

    def submit(self, prompt, max_new_tokens, **kw):
        kw.pop("deadline_s", None)
        req = Request(prompt if prompt is not None else [0],
                      max_new_tokens, **kw)
        req.tokens = list(range(max_new_tokens))
        req._finish(RequestStatus.DONE, None)
        return req


class TrickleEngine(ServeNowEngine):
    """Serves asynchronously, committing one token at a time through
    ``Request._commit`` — the push path's real shape: ``on_token`` fires
    per commit, terminal fires at the end, all off-thread."""

    def __init__(self, *a, delay=0.002, **kw):
        super().__init__(*a, **kw)
        self.delay = delay

    def submit(self, prompt, max_new_tokens, **kw):
        kw.pop("deadline_s", None)
        req = Request(prompt if prompt is not None else [0],
                      max_new_tokens, **kw)

        def serve():
            req.start_running()
            for i in range(max_new_tokens):
                time.sleep(self.delay)
                req._commit(i * 2)
            req._finish(RequestStatus.DONE, None)

        threading.Thread(target=serve, daemon=True).start()
        return req


def _pair():
    a, b = socket.socketpair()
    a.settimeout(2.0)
    b.settimeout(2.0)
    return a, b


# ---------------------------------------------------------------------------
# framing robustness (the fuzz surface)
# ---------------------------------------------------------------------------

class TestFraming:
    def test_roundtrip_preserves_stream_id_opcode_payload(self):
        a, b = _pair()
        try:
            _send_frame2(a, threading.Lock(), 7, OP_REQUEST,
                         {"method": "poll", "params": {"id": "x"}})
            sid, op, payload = _FrameReader(b).read()
            assert (sid, op) == (7, OP_REQUEST)
            assert payload == {"method": "poll", "params": {"id": "x"}}
        finally:
            a.close(), b.close()

    def test_many_frames_in_one_burst_parse_in_order(self):
        a, b = _pair()
        try:
            lock = threading.Lock()
            for sid in range(1, 9):
                _send_frame2(a, lock, sid, OP_RESPONSE, {"sid": sid})
            reader = _FrameReader(b)
            got = [reader.read() for _ in range(8)]
            assert [sid for sid, _, _ in got] == list(range(1, 9))
            assert all(p == {"sid": sid} for sid, _, p in got)
        finally:
            a.close(), b.close()

    def test_fragmented_delivery_is_reassembled(self):
        a, b = _pair()
        try:
            payload = json.dumps({"k": "v" * 100}).encode()
            frame = struct.pack(">IIB", len(payload) + 5, 3,
                                OP_RESPONSE) + payload
            reader = _FrameReader(b)
            got = {}

            def read():
                got["frame"] = reader.read()

            t = threading.Thread(target=read)
            t.start()
            for i in range(0, len(frame), 7):   # 7-byte dribbles
                a.sendall(frame[i:i + 7])
                time.sleep(0.001)
            t.join(timeout=5)
            assert got["frame"][0] == 3
        finally:
            a.close(), b.close()

    def test_truncated_frame_is_connection_error_not_hang(self):
        a, b = _pair()
        try:
            a.sendall(struct.pack(">IIB", 50, 1, OP_RESPONSE) + b"{")
            a.close()                   # EOF mid-frame
            with pytest.raises(ConnectionError):
                _FrameReader(b).read()
        finally:
            b.close()

    def test_oversize_length_is_typed_protocol_error(self):
        a, b = _pair()
        try:
            a.sendall(struct.pack(">I", _MAX_FRAME + 1))
            with pytest.raises(TransportError) as ei:
                _FrameReader(b).read()
            assert ei.value.kind == "protocol"
            assert not ei.value.retryable
        finally:
            a.close(), b.close()

    def test_under_header_length_is_typed_protocol_error(self):
        a, b = _pair()
        try:
            a.sendall(struct.pack(">I", 3))   # < stream_id + opcode
            with pytest.raises(TransportError) as ei:
                _FrameReader(b).read()
            assert ei.value.kind == "protocol"
        finally:
            a.close(), b.close()

    def test_garbage_payload_is_typed_protocol_error(self):
        a, b = _pair()
        try:
            junk = b"\xff\xfe not json"
            a.sendall(struct.pack(">IIB", len(junk) + 5, 1,
                                  OP_RESPONSE) + junk)
            with pytest.raises(TransportError) as ei:
                _FrameReader(b).read()
            assert ei.value.kind == "protocol"
        finally:
            a.close(), b.close()

    def test_non_object_payload_is_typed_protocol_error(self):
        a, b = _pair()
        try:
            junk = b"[1,2,3]"
            a.sendall(struct.pack(">IIB", len(junk) + 5, 1,
                                  OP_RESPONSE) + junk)
            with pytest.raises(TransportError) as ei:
                _FrameReader(b).read()
            assert ei.value.kind == "protocol"
        finally:
            a.close(), b.close()

    def test_idle_socket_ticks_timeout_instead_of_hanging(self):
        a, b = _pair()
        try:
            b.settimeout(0.1)
            t0 = time.monotonic()
            with pytest.raises(socket.timeout):
                _FrameReader(b).read()
            assert time.monotonic() - t0 < 1.0
        finally:
            a.close(), b.close()

    def test_garbage_first_byte_on_listener_closes_not_hangs(self):
        # Neither 0xB2 nor a sane legacy length: the server must parse
        # it as a legacy prefix, reject it typed, and close — the
        # client observes EOF within the timeout, never a hang.
        srv = SocketReplicaServer(ServeNowEngine(), 0).start()
        try:
            with socket.create_connection(srv.address, timeout=2) as s:
                s.settimeout(2.0)
                s.sendall(b"\xffgarbage-not-a-frame")
                t0 = time.monotonic()
                try:
                    data = s.recv(4096)
                except ConnectionResetError:
                    data = b""                 # RST is also a close
                assert data == b""             # server closed on us
                assert time.monotonic() - t0 < 5.0
        finally:
            srv.stop()

    def test_legacy_wire_helpers_still_roundtrip(self):
        a, b = _pair()
        try:
            _send_frame(a, {"method": "status", "params": {}})
            assert _recv_frame(b)["method"] == "status"
        finally:
            a.close(), b.close()


# ---------------------------------------------------------------------------
# the stream wire end to end
# ---------------------------------------------------------------------------

class TestStreamWire:
    def test_one_connection_multiplexes_concurrent_rpcs(self):
        metrics.reset_metrics()
        srv = SocketReplicaServer(ServeNowEngine(), 0).start()
        client = RemoteClient(srv.address, transport="stream")
        try:
            ids = [f"mux-{i}" for i in range(8)]
            for rid in ids:
                client.submit({"prompt": [1], "max_new_tokens": 2,
                               "request_id": rid})
            results, errs = [], []

            def poll(rid):
                try:
                    results.append(client.poll(rid))
                except Exception as e:          # noqa: BLE001
                    errs.append(e)

            threads = [threading.Thread(target=poll, args=(rid,))
                       for rid in ids * 2]      # 16 in flight
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            assert not errs
            assert len(results) == 16
            assert all(r["status"] == "done" for r in results)
            # ... all over ONE connection:
            snap = metrics.snapshot()
            opens = [s["value"] for s in
                     snap["gauges"].get("transport_connections", [])
                     if s["labels"].get("state") == "open"]
            assert opens and opens[0] == 1.0
        finally:
            client.close()
            srv.stop()

    def test_server_pushes_tokens_and_terminal_without_polling(self):
        srv = SocketReplicaServer(TrickleEngine(), 0).start()
        disp = RemoteDispatcher(
            clients=[RemoteClient(srv.address, transport="stream")])
        try:
            pushed = []
            h = disp.submit([1, 2], 6, deadline_s=30.0)
            h.on_token = lambda i, t: pushed.append((i, t))
            disp.wait(h)
            assert h.status == "done"
            assert h.tokens == [0, 2, 4, 6, 8, 10]
            assert pushed == [(i, i * 2) for i in range(6)]
            assert h.ttft_client is not None
            # push lag histogram saw the token frames (the pump counts a
            # batch after its sendall: the terminal can reach the client
            # before the last token's lag is observed, so wait for it)
            bound = time.monotonic() + 10.0
            while True:
                lag = metrics.snapshot()["histograms"].get(
                    "transport_stream_push_lag_seconds", [])
                if (lag and lag[0]["count"] >= 6) \
                        or time.monotonic() > bound:
                    break
                time.sleep(0.01)
            assert lag and lag[0]["count"] >= 6
        finally:
            disp.close()
            srv.stop()

    def test_instant_terminal_still_resolves_stream_submit(self):
        # ServeNowEngine finishes DURING submit: the terminal frame can
        # race (or replace) the RPC response — either way wait() ends.
        srv = SocketReplicaServer(ServeNowEngine(), 0).start()
        disp = RemoteDispatcher(
            clients=[RemoteClient(srv.address, transport="stream")])
        try:
            h = disp.wait(disp.submit([1], 4, deadline_s=15.0))
            assert h.status == "done"
            assert h.tokens == [0, 1, 2, 3]
        finally:
            disp.close()
            srv.stop()

    def test_dead_conn_reconnects_lazily_and_gauges_track_it(self):
        metrics.reset_metrics()
        eng = ServeNowEngine()
        srv = SocketReplicaServer(eng, 0).start()
        client = RemoteClient(srv.address, transport="stream",
                              rpc_timeout=0.5, max_retries=2)
        try:
            assert client.status(retry=False)["alive"]
            client._conn.close()               # sever behind its back
            # next RPC reconnects through the same call() machinery
            assert client.status(retry=False)["alive"]
            snap = metrics.snapshot()
            states = {s["labels"]["state"]: s["value"] for s in
                      snap["gauges"].get("transport_connections", [])}
            assert states.get("open") == 1.0
            assert states.get("reconnecting") == 0.0
            # frame accounting ran in both directions
            frames = {(s["labels"]["opcode"], s["labels"]["dir"])
                      for s in snap["counters"].get(
                          "transport_frames_total", [])}
            assert ("request", "tx") in frames
            assert ("response", "rx") in frames
        finally:
            client.close()
            srv.stop()

    def test_legacy_client_still_served_on_same_listener(self):
        srv = SocketReplicaServer(ServeNowEngine(), 0).start()
        legacy = RemoteClient(srv.address, transport="legacy")
        stream = RemoteClient(srv.address, transport="stream")
        try:
            st = legacy.submit({"prompt": [1], "max_new_tokens": 3,
                                "request_id": "compat-1"})
            assert st["status"] == "done"
            # and the stream client sees the same request via dedup
            st2 = stream.submit({"prompt": [1], "max_new_tokens": 3,
                                 "request_id": "compat-1"})
            assert st2["tokens"] == st["tokens"]
        finally:
            stream.close()
            srv.stop()

    def test_request_timeout_poisons_mux_and_retries_reconnect(self):
        # A listener that accepts + handshakes but never answers
        # requests: the client must time out per attempt, poison the
        # conn, and surface a typed retryable timeout — never hang.
        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(4)
        stop = threading.Event()

        def deaf():
            while not stop.is_set():
                lst.settimeout(0.2)
                try:
                    conn, _ = lst.accept()
                except socket.timeout:
                    continue
                except OSError:
                    return
                conn.settimeout(2.0)
                try:
                    conn.recv(1)           # magic
                    _send_frame2(conn, threading.Lock(), 0, OP_CHALLENGE,
                                 {"nonce": "n", "auth": False})
                    _FrameReader(conn).read()    # hello
                    _send_frame2(conn, threading.Lock(), 0, OP_HELLO_OK,
                                 {})
                    time.sleep(5)          # ...then silence
                except (OSError, ConnectionError, TransportError):
                    pass

        t = threading.Thread(target=deaf, daemon=True)
        t.start()
        client = RemoteClient(lst.getsockname(), transport="stream",
                              rpc_timeout=0.3, max_retries=1)
        try:
            t0 = time.monotonic()
            with pytest.raises(TransportError) as ei:
                client.poll("x", deadline=time.monotonic() + 2.0)
            assert ei.value.kind in ("timeout", "deadline")
            assert time.monotonic() - t0 < 5.0
        finally:
            stop.set()
            client.close()
            lst.close()

    def test_duck_typed_stub_clients_take_the_poll_path(self):
        # Stubs without transport/submit_stream must keep working —
        # the dispatcher's stream checks are getattr-guarded.
        class StubClient:
            name = "stub0"
            rpc_timeout = 0.5
            breaker = CircuitBreaker("stub0")

            def __init__(self):
                self.polled = 0

            def status(self, **kw):
                return {"ok": True, "alive": True, "load": 0}

            def submit(self, spec, deadline=None):
                self.spec = spec
                return {"ok": True, "id": spec["request_id"],
                        "status": "queued", "tokens": [],
                        "served_by": self.name, "retryable": False,
                        "reason": None, "ttft": None, "tpot": None,
                        "queue_wait": None}

            def poll(self, rid, deadline=None):
                self.polled += 1
                return {"ok": True, "id": rid, "status": "done",
                        "tokens": [1, 2], "served_by": self.name,
                        "retryable": False, "reason": None,
                        "ttft": 0.0, "tpot": 0.0, "queue_wait": None}

            def cancel(self, rid):
                return None

        stub = StubClient()
        disp = RemoteDispatcher(clients=[stub], hedge_ms=0.0)
        h = disp.wait(disp.submit([1], 2, deadline_s=10.0))
        assert h.status == "done" and h.tokens == [1, 2]
        assert stub.polled >= 1


# ---------------------------------------------------------------------------
# auth handshake
# ---------------------------------------------------------------------------

class TestAuthHandshake:
    TOKEN = "s3cret-token-123"

    def _serve(self):
        return SocketReplicaServer(ServeNowEngine(), 0).start()

    def test_matching_token_streams_normally(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVE_AUTH_TOKEN", self.TOKEN)
        hconfig.refresh()
        srv = self._serve()
        client = RemoteClient(srv.address, transport="stream")
        try:
            st = client.submit({"prompt": [1], "max_new_tokens": 2,
                                "request_id": "auth-ok"})
            assert st["status"] == "done"
        finally:
            client.close()
            srv.stop()

    def test_missing_token_refused_typed_nonretryable(self, monkeypatch):
        # The client captures its token at construction; the server
        # reads config live at handshake. Build the client while auth
        # is off, then turn it on — the lazy connect gets refused.
        srv = self._serve()
        client = RemoteClient(srv.address, transport="stream")
        monkeypatch.setenv("HOROVOD_SERVE_AUTH_TOKEN", self.TOKEN)
        hconfig.refresh()
        try:
            with pytest.raises(TransportError) as ei:
                client.status(retry=False)
            assert ei.value.kind == "auth"
            assert not ei.value.retryable
        finally:
            client.close()
            srv.stop()

    def test_wrong_token_refused_typed_nonretryable(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVE_AUTH_TOKEN", "wrong-token-99")
        hconfig.refresh()
        srv = self._serve()
        client = RemoteClient(srv.address, transport="stream")
        monkeypatch.setenv("HOROVOD_SERVE_AUTH_TOKEN", self.TOKEN)
        hconfig.refresh()
        try:
            with pytest.raises(TransportError) as ei:
                client.status(retry=False)
            assert ei.value.kind == "auth"
            assert not ei.value.retryable
        finally:
            client.close()
            srv.stop()

    def test_legacy_connection_refused_when_token_set(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVE_AUTH_TOKEN", self.TOKEN)
        hconfig.refresh()
        srv = self._serve()
        client = RemoteClient(srv.address, transport="legacy")
        try:
            with pytest.raises(TransportError) as ei:
                client.status(retry=False)
            assert not ei.value.retryable
            assert "auth required" in str(ei.value)
        finally:
            srv.stop()

    def test_token_validated_but_never_in_build_info(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVE_AUTH_TOKEN", "short")
        with pytest.raises(ValueError) as ei:
            hconfig.refresh()
        assert "short" not in str(ei.value).replace("too short", "")
        monkeypatch.setenv("HOROVOD_SERVE_AUTH_TOKEN", self.TOKEN)
        hconfig.refresh()
        info = hvd.build_info()
        assert info["serve_auth_enabled"] is True
        assert self.TOKEN not in json.dumps(info)
        monkeypatch.delenv("HOROVOD_SERVE_AUTH_TOKEN")
        hconfig.refresh()
        assert hvd.build_info()["serve_auth_enabled"] is False

    def test_transport_knob_validated(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVE_TRANSPORT", "carrier-pigeon")
        with pytest.raises(ValueError):
            hconfig.refresh()
        monkeypatch.setenv("HOROVOD_SERVE_TRANSPORT", "legacy")
        hconfig.refresh()
        assert hconfig.get_config().serve_transport == "legacy"
        assert hvd.build_info()["serve_transport"] == "legacy"
        monkeypatch.delenv("HOROVOD_SERVE_TRANSPORT")
        hconfig.refresh()
        assert hconfig.get_config().serve_transport == "stream"


# ---------------------------------------------------------------------------
# shared dispatcher state bus
# ---------------------------------------------------------------------------

class TestStateBus:
    def test_publish_read_roundtrip_and_self_exclusion(self, tmp_path):
        path = str(tmp_path / "membership.json")
        a = _StateBus(path, owner="disp-a")
        b = _StateBus(path, owner="disp-b")
        a.publish("rank1", down_for=5.0)
        assert b.is_down("rank1")
        assert not a.is_down("rank1")      # own marks don't gate self
        assert not b.is_down("rank0")      # unknown name: not down

    def test_down_mark_expires_at_horizon(self, tmp_path):
        path = str(tmp_path / "membership.json")
        a = _StateBus(path, owner="disp-a")
        b = _StateBus(path, owner="disp-b")
        a.publish("rank1", down_for=0.2)
        assert b.is_down("rank1")
        time.sleep(0.5)
        b._read_at = -1e9                  # bypass the read TTL
        assert not b.is_down("rank1")

    def test_load_publish_clears_down_mark(self, tmp_path):
        path = str(tmp_path / "membership.json")
        a = _StateBus(path, owner="disp-a")
        b = _StateBus(path, owner="disp-b")
        a.publish("rank1", down_for=30.0)
        assert b.is_down("rank1")
        a._wrote.clear()                   # bypass the publish throttle
        a.publish("rank1", load=0.5)       # recovered: fresh entry
        b._read_at = -1e9
        assert not b.is_down("rank1")

    def test_dispatcher_routes_around_gossiped_death_without_probe(
            self, tmp_path):
        metrics.reset_metrics()
        path = str(tmp_path / "membership.json")
        peer = _StateBus(path, owner="disp-peer")
        srv = SocketReplicaServer(ServeNowEngine(), 0).start()
        # a "dead" address nothing listens on — a probe would burn a
        # connect timeout and trip the breaker; the bus must prevent it
        dead = socket.socket()
        dead.bind(("127.0.0.1", 0))
        dead_addr = dead.getsockname()
        dead.close()
        live_c = RemoteClient(srv.address, name="rank-live",
                              transport="stream")
        dead_c = RemoteClient(dead_addr, name="rank-dead",
                              transport="stream", rpc_timeout=0.3)
        disp = RemoteDispatcher(clients=[dead_c, live_c], hedge_ms=0.0,
                                state_bus=path)
        try:
            peer.publish("rank-dead", down_for=30.0)
            h = disp.wait(disp.submit([1], 3, deadline_s=15.0))
            assert h.status == "done"
            assert h.tokens == [0, 1, 2]
            assert dead_c.breaker.state == "closed"   # never probed
            assert dead_c._conn is None
            routed = sum(
                s["value"] for s in metrics.snapshot()["counters"].get(
                    "transport_bus_total", [])
                if s["labels"].get("event") == "route_around")
            assert routed >= 1
        finally:
            disp.close()
            srv.stop()

    def test_supervisor_publish_preserves_health_block(self, tmp_path):
        from horovod_tpu.serving.fleet import FleetSupervisor
        path = str(tmp_path / "membership.json")
        sup = FleetSupervisor(lambda name, rank, attempt: None, 1,
                              spares=0, membership_path=path)
        sup._members = {"r0": {"name": "r0", "host": "127.0.0.1",
                               "port": 1234, "attempt": 0}}
        sup._publish_membership()
        bus = _StateBus(path, owner="disp-a")
        bus.publish("r0", down_for=30.0)
        sup._publish_membership()          # atomic rewrite...
        with open(path) as f:
            doc = json.load(f)
        assert doc["version"] == 2
        assert doc["replicas"][0]["name"] == "r0"
        assert "r0" in doc.get("health", {})   # ...keeps the gossip
        assert doc["health"]["r0"]["by"] == "disp-a"

    def test_dispatchers_never_bump_membership_version(self, tmp_path):
        path = str(tmp_path / "membership.json")
        with open(path, "w") as f:
            json.dump({"version": 7, "replicas": []}, f)
        bus = _StateBus(path, owner="disp-a")
        bus.publish("rank0", load=1.0)
        with open(path) as f:
            doc = json.load(f)
        assert doc["version"] == 7         # supervisor's counter intact
        assert doc["health"]["rank0"]["load"] == 1.0


# ---------------------------------------------------------------------------
# doctor: poll-mode fallback finding
# ---------------------------------------------------------------------------

class TestDoctorPollMode:
    @staticmethod
    def _snap(polls=0, pushed=0):
        snap = {"gauges": {}, "counters": {}, "histograms": {}}
        if polls:
            snap["histograms"]["transport_rpc_seconds"] = [
                {"labels": {"method": "poll", "outcome": "ok"},
                 "count": polls, "sum": polls * 0.01}]
        if pushed:
            snap["counters"]["transport_frames_total"] = [
                {"labels": {"opcode": "token", "dir": "tx"},
                 "value": pushed}]
        return snap

    def test_poll_heavy_run_without_pushes_is_flagged(self):
        from horovod_tpu.profiler import _check_transport
        findings = _check_transport(self._snap(polls=50))
        cats = [f["category"] for f in findings]
        assert "transport_poll_mode" in cats
        f = findings[cats.index("transport_poll_mode")]
        assert "HOROVOD_SERVE_TRANSPORT" in f["suggestion"]

    def test_streaming_run_is_not_flagged(self):
        from horovod_tpu.profiler import _check_transport
        findings = _check_transport(self._snap(polls=50, pushed=200))
        assert "transport_poll_mode" not in [f["category"]
                                             for f in findings]

    def test_quiet_snapshot_yields_nothing(self):
        from horovod_tpu.profiler import _check_transport
        assert _check_transport(self._snap()) == []
