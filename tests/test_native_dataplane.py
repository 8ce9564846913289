"""Native C++ dataplane tests (VERDICT r1 #3 — the native hot path).

Pattern follows the reference's RPC integration tests (SURVEY §4): real
loopback sockets, client and server through the public API, no mock
transport. Covers both lanes (native engine / Python stack) in every
pairing, the C++ native-service fast path, the DETACH fallback for
non-TRPC protocols on a native port, and failure fanout.
"""

import ctypes
import socket as _socket
import threading
import time

import pytest

from brpc_tpu.proto import echo_pb2
from brpc_tpu.rpc import (
    Channel,
    ChannelOptions,
    Controller,
    RpcError,
    Server,
    ServerOptions,
    Service,
    Stub,
)
from brpc_tpu.rpc.native_transport import (
    bench_echo_native,
    dataplane_available,
    get_dataplane,
)

pytestmark = pytest.mark.skipif(
    not dataplane_available(), reason="native dataplane did not build")

ECHO = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]


class EchoImpl(Service):
    DESCRIPTOR = ECHO

    def Echo(self, cntl, request, done):
        cntl.response_attachment = cntl.request_attachment
        return echo_pb2.EchoResponse(message=request.message,
                                     payload=request.payload)


@pytest.fixture()
def native_server():
    server = Server(ServerOptions(native_dataplane=True))
    server.add_service(EchoImpl())
    server.start("127.0.0.1:0")
    yield server
    server.stop()
    server.join()


def _stub(server, native=False, timeout_ms=10000):
    ch = Channel(ChannelOptions(protocol="trpc_std", timeout_ms=timeout_ms,
                                native_transport=native))
    ch.init(str(server.listen_endpoint()))
    return Stub(ch, ECHO)


class TestNativeServer:
    def test_python_client_native_server(self, native_server):
        stub = _stub(native_server, native=False)
        r = stub.Echo(echo_pb2.EchoRequest(message="py", payload=b"p" * 1000))
        assert r.message == "py" and r.payload == b"p" * 1000

    def test_native_client_native_server(self, native_server):
        stub = _stub(native_server, native=True)
        r = stub.Echo(echo_pb2.EchoRequest(message="nn", payload=b"n" * 1000))
        assert r.message == "nn" and r.payload == b"n" * 1000

    def test_native_client_python_server(self):
        server = Server(ServerOptions())
        server.add_service(EchoImpl())
        server.start("127.0.0.1:0")
        try:
            stub = _stub(server, native=True)
            r = stub.Echo(echo_pb2.EchoRequest(message="np"))
            assert r.message == "np"
        finally:
            server.stop()
            server.join()

    def test_attachment_roundtrip(self, native_server):
        stub = _stub(native_server, native=True)
        att = bytes(range(256)) * 64
        cntl = Controller()
        cntl.request_attachment = att
        r = stub.Echo(echo_pb2.EchoRequest(message="a"), controller=cntl)
        assert r.message == "a"
        assert cntl.response_attachment == att

    def test_large_payload(self, native_server):
        stub = _stub(native_server, native=True, timeout_ms=30000)
        payload = b"\x5a" * (8 << 20)
        r = stub.Echo(echo_pb2.EchoRequest(message="big", payload=payload))
        assert r.payload == payload

    def test_concurrent_calls(self, native_server):
        stub = _stub(native_server, native=True)
        errs = []

        def worker(i):
            try:
                for k in range(30):
                    msg = f"t{i}.{k}"
                    r = stub.Echo(echo_pb2.EchoRequest(message=msg))
                    assert r.message == msg
            except Exception as e:  # pragma: no cover
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs

    def test_native_echo_fastpath(self, native_server):
        """C++-answered service: correct wire response, no Python handler."""
        native_server.register_native_echo("EchoService", "Echo")
        calls_before = native_server.requests_processed.get_value()
        stub = _stub(native_server, native=True)
        att = b"fast" * 100
        cntl = Controller()
        cntl.request_attachment = att
        r = stub.Echo(echo_pb2.EchoRequest(message="cxx", payload=b"zz"),
                      controller=cntl)
        assert r.message == "cxx" and r.payload == b"zz"
        assert cntl.response_attachment == att
        # the Python service never saw it
        assert native_server.requests_processed.get_value() == calls_before

    def test_server_stop_fails_clients(self, native_server):
        stub = _stub(native_server, native=True, timeout_ms=2000)
        stub.Echo(echo_pb2.EchoRequest(message="ok"))
        native_server.stop()
        native_server.join()
        with pytest.raises(RpcError):
            for _ in range(5):  # conn teardown may race the first call
                stub.Echo(echo_pb2.EchoRequest(message="down"))
                time.sleep(0.1)


class TestDetach:
    def test_http_on_native_port(self, native_server):
        """Non-TRPC bytes on a native port detach to the Python stack: the
        builtin HTTP dashboard answers on the same listener."""
        ep = native_server.listen_endpoint()
        with _socket.create_connection((ep.host, ep.port), timeout=5) as s:
            s.sendall(b"GET /health HTTP/1.1\r\nHost: t\r\n"
                      b"Connection: close\r\n\r\n")
            s.settimeout(5)
            data = b""
            while True:
                try:
                    chunk = s.recv(4096)
                except (TimeoutError, OSError):
                    break
                if not chunk:
                    break
                data += chunk
        assert data.startswith(b"HTTP/1.1 200")

    def test_trpc_still_works_after_detach(self, native_server):
        self.test_http_on_native_port(native_server)
        stub = _stub(native_server, native=True)
        assert stub.Echo(echo_pb2.EchoRequest(message="after")).message \
            == "after"


class TestNativeLaneBench:
    def test_bench_echo_native_smoke(self, native_server):
        native_server.register_native_echo("EchoService", "Echo")
        ep = native_server.listen_endpoint()
        res = bench_echo_native(ep.host, ep.port, conns=2, depth=2,
                                payload=64, duration_ms=200)
        assert res is not None
        assert res["qps"] > 100, res
        assert res["p99_us"] > 0


class TestEngineBasics:
    def test_connect_refused(self):
        dp = get_dataplane()
        # grab a port that is closed: bind+close
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        from brpc_tpu.butil.endpoint import EndPoint

        with pytest.raises(ConnectionError):
            dp.connect(EndPoint.from_ip_port("127.0.0.1", port),
                       timeout_ms=500)

    def test_peer_close_errors_pending(self, native_server):
        """Kill the server mid-call: pending ids get errored, not hung."""
        stub = _stub(native_server, native=True, timeout_ms=3000)
        stub.Echo(echo_pb2.EchoRequest(message="warm"))
        native_server.stop()
        native_server.join()
        t0 = time.monotonic()
        with pytest.raises(RpcError):
            stub.Echo(echo_pb2.EchoRequest(message="x"))
        # failed fast via socket error, not the 3s timeout
        assert time.monotonic() - t0 < 2.5


class TestNativeTpuTunnel:
    """The graft's native lane: TPUC shm tunnel in the C++ engine
    (reference RdmaEndpoint blueprint) + interop with the Python
    transport implementation of the same wire format."""

    @pytest.fixture()
    def tpu_native_server(self):
        server = Server(ServerOptions(native_dataplane=True))
        server.add_service(EchoImpl())
        server.start("tpu://127.0.0.1:0/0")
        yield server
        server.stop()
        server.join()

    def test_native_client_native_server(self, tpu_native_server):
        stub = _stub(tpu_native_server, native=True, timeout_ms=15000)
        r = stub.Echo(echo_pb2.EchoRequest(message="nn",
                                           payload=b"t" * 500000))
        assert r.message == "nn" and len(r.payload) == 500000

    def test_python_client_native_server(self, tpu_native_server):
        stub = _stub(tpu_native_server, native=False, timeout_ms=15000)
        r = stub.Echo(echo_pb2.EchoRequest(message="pn",
                                           payload=b"p" * 300000))
        assert r.message == "pn" and len(r.payload) == 300000

    def test_python_client_heals_against_native_server(
            self, tpu_native_server):
        """A v1 peer's DATA and ACK bodies carry no epoch word, so the
        stale-generation guard is off for it. It has nothing to guard: a
        Python dialer heals on a NEW connection with a new endpoint (the
        epoch is fixed for an endpoint's life), so a frame of the dead
        generation arrives at the dead endpoint or nowhere."""
        from brpc_tpu import fault
        from brpc_tpu import flags as _flags
        from brpc_tpu.tpu import transport as tr

        stub = _stub(tpu_native_server, native=False, timeout_ms=30000)
        assert stub.Echo(echo_pb2.EchoRequest(message="warm")).message \
            == "warm"
        ep = tpu_native_server.listen_endpoint()
        key = (ep.host, ep.port, ep.device_ordinal)
        vs0 = tr._remote_sockets.get(key)
        assert vs0 is not None and vs0.endpoint.peer_version == 1
        stale0 = tr.g_tunnel_stale_epoch_frames.get_value()
        payload = b"h" * (4 * 1024 * 1024)
        _flags.set_flag("fault_injection_enabled", True)
        try:
            # the 3rd DATA frame of the streamed send kills the vsock; the
            # retried attempt lands on a healed tunnel
            fault.arm("tpu.tunnel.kill", after=2)
            r = stub.Echo(echo_pb2.EchoRequest(message="big",
                                               payload=payload))
        finally:
            fault.disarm_all()
            _flags.set_flag("fault_injection_enabled", False)
        assert r.message == "big" and r.payload == payload
        assert vs0.failed                              # the kill was real
        vs1 = tr._remote_sockets.get(key)
        assert vs1 is not None and vs1 is not vs0 and not vs1.failed
        assert vs1.endpoint is not vs0.endpoint
        assert vs1.endpoint.epoch > vs0.endpoint.epoch
        assert vs1.endpoint.peer_version == 1
        assert tr.g_tunnel_stale_epoch_frames.get_value() == stale0

    def test_native_client_python_server(self):
        server = Server(ServerOptions())  # Python tpu transport end
        server.add_service(EchoImpl())
        server.start("tpu://127.0.0.1:0/0")
        try:
            stub = _stub(server, native=True, timeout_ms=15000)
            r = stub.Echo(echo_pb2.EchoRequest(message="np",
                                               payload=b"q" * 300000))
            assert r.message == "np" and len(r.payload) == 300000
        finally:
            server.stop()
            server.join()

    def test_attachment_and_fastpath(self, tpu_native_server):
        tpu_native_server.register_native_echo("EchoService", "Echo")
        stub = _stub(tpu_native_server, native=True, timeout_ms=15000)
        att = bytes(range(256)) * 2048  # 512KB through the block path
        cntl = Controller()
        cntl.request_attachment = att
        r = stub.Echo(echo_pb2.EchoRequest(message="fast"), controller=cntl)
        assert r.message == "fast" and cntl.response_attachment == att

    def test_ordinal_mismatch_refused(self, tpu_native_server):
        ep = tpu_native_server.listen_endpoint()
        from brpc_tpu.butil.endpoint import EndPoint
        from brpc_tpu.rpc.native_transport import get_dataplane

        wrong = EndPoint.from_tpu(ep.host, 7, port=ep.port)
        with pytest.raises(ConnectionError):
            get_dataplane().connect_tpu(wrong, timeout_ms=3000)

    def test_server_stop_fails_tunnel_clients(self, tpu_native_server):
        stub = _stub(tpu_native_server, native=True, timeout_ms=3000)
        stub.Echo(echo_pb2.EchoRequest(message="ok"))
        tpu_native_server.stop()
        tpu_native_server.join()
        with pytest.raises(RpcError):
            for _ in range(5):
                stub.Echo(echo_pb2.EchoRequest(message="down"))
                time.sleep(0.1)


class TestTunnelStress:
    def test_concurrent_mixed_sizes_shared_tunnel(self):
        """8 threads × mixed payload sizes over ONE shared tunnel conn:
        stream ordering, credit accounting, and payload integrity must
        hold under contention."""
        server = Server(ServerOptions(native_dataplane=True))
        server.add_service(EchoImpl())
        server.start("tpu://127.0.0.1:0/0")
        try:
            stub = _stub(server, native=True, timeout_ms=30000)
            sizes = [7, 1000, 65536, 300000, 1 << 20]
            errs = []

            def worker(seed):
                try:
                    for k in range(12):
                        size = sizes[(seed + k) % len(sizes)]
                        fill = bytes([(seed * 31 + k) & 0xFF])
                        cntl = Controller()
                        cntl.timeout_ms = 30000
                        cntl.request_attachment = fill * size
                        r = stub.Echo(echo_pb2.EchoRequest(
                            message=f"{seed}.{k}"), controller=cntl)
                        assert r.message == f"{seed}.{k}"
                        assert cntl.response_attachment == fill * size, \
                            f"payload corrupted at {seed}.{k}"
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert not errs, errs
        finally:
            server.stop()
            server.join()

    def test_idle_sweep_closes_native_conns(self):
        server = Server(ServerOptions(native_dataplane=True,
                                      idle_timeout_s=1))
        server.add_service(EchoImpl())
        server.start("127.0.0.1:0")
        try:
            stub = _stub(server, native=True, timeout_ms=3000)
            stub.Echo(echo_pb2.EchoRequest(message="warm"))
            dp = server._native_dp
            assert len(dp.server_socks(server)) >= 1
            deadline = time.monotonic() + 12  # sweep ticks every 5s
            while time.monotonic() < deadline:
                left = len(dp.server_socks(server))
                if left == 0:
                    break
                time.sleep(0.3)
            assert left == 0, f"{left} native conns survived the idle sweep"
        finally:
            server.stop()
            server.join()

    def test_cpp_fastpath_traffic_keeps_conn_alive(self):
        """Traffic answered entirely in C++ never touches Python's
        last_active — the sweep must consult the engine's counters, not
        kill a busy conn (regression for the sweep's blind spot)."""
        server = Server(ServerOptions(native_dataplane=True,
                                      idle_timeout_s=1))
        server.add_service(EchoImpl())
        server.start("127.0.0.1:0")
        server.register_native_echo("EchoService", "Echo")
        try:
            stub = _stub(server, native=True, timeout_ms=3000)
            deadline = time.monotonic() + 7  # beyond limit + sweep tick
            while time.monotonic() < deadline:
                r = stub.Echo(echo_pb2.EchoRequest(message="alive"))
                assert r.message == "alive"
                time.sleep(0.05)
            assert len(server._native_dp.server_socks(server)) >= 1
        finally:
            server.stop()
            server.join()


class TestNativeFailover:
    def test_lb_retry_steers_around_dead_native_server(self):
        """Two native servers behind an rr LB; one dies under continuous
        load — retries + feedback keep every call succeeding on the
        survivor (reference failure-detection story on the native lane)."""

        class NamedEcho(Service):
            DESCRIPTOR = ECHO

            def __init__(self, name):
                super().__init__()
                self.name = name

            def Echo(self, cntl, request, done):
                return echo_pb2.EchoResponse(message=self.name)

        servers = []
        for name in ("a", "b"):
            s = Server(ServerOptions(native_dataplane=True))
            s.add_service(NamedEcho(name))
            s.start("127.0.0.1:0")
            servers.append(s)
        try:
            url = ",".join(str(s.listen_endpoint()) for s in servers)
            ch = Channel(ChannelOptions(timeout_ms=3000, max_retry=3,
                                        native_transport=True))
            ch.init(f"list://{url}", "rr")
            stub = Stub(ch, ECHO)
            seen = set()
            for _ in range(10):
                seen.add(stub.Echo(echo_pb2.EchoRequest(message="x")).message)
            assert seen == {"a", "b"}
            servers[0].stop()
            servers[0].join()
            after = set()
            for _ in range(20):
                after.add(stub.Echo(
                    echo_pb2.EchoRequest(message="x")).message)
            assert after == {"b"}, after  # every call succeeded via retry
        finally:
            for s in servers:
                s.stop()
                s.join(timeout=2)


class TestTunnelGarbageResilience:
    def test_garbage_on_tpu_listener_kills_only_that_conn(self):
        """Raw TCP garbage at a native tpu listener must fail that conn
        alone; real tunnel clients keep working."""
        import socket as _socket

        server = Server(ServerOptions(native_dataplane=True))
        server.add_service(EchoImpl())
        server.start("tpu://127.0.0.1:0/0")
        try:
            ep = server.listen_endpoint()
            stub = _stub(server, native=True, timeout_ms=10000)
            stub.Echo(echo_pb2.EchoRequest(message="before"))
            for payload in (b"TPUC" + b"\xff" * 64,        # bad frame
                            b"TPUC\x03" + b"\x7f\xff\xff\xff",  # huge len
                            b"\x00" * 32):                 # not TPUC at all
                with _socket.create_connection((ep.host, ep.port),
                                               timeout=5) as s:
                    s.sendall(payload)
                    s.settimeout(2)
                    try:
                        while s.recv(4096):
                            pass
                    except (TimeoutError, OSError):
                        pass
            r = stub.Echo(echo_pb2.EchoRequest(message="after"))
            assert r.message == "after"  # the real tunnel survived
        finally:
            server.stop()
            server.join()

    def test_malformed_zero_copy_data_frames(self):
        """The zero-copy DATA route parses peer-controlled block refs and
        an embedded TRPC header straight out of pool memory — hostile
        geometries (bad indices, lying lengths, split headers, random
        fuzz) must fail ONLY the offending conn, never the process or
        innocent tunnels (round-3 surface; reference trust model is
        rdma_endpoint.cpp's, ours must still not crash)."""
        import random
        import socket as _socket
        import struct as _struct

        server = Server(ServerOptions(native_dataplane=True))
        server.add_service(EchoImpl())
        server.start("tpu://127.0.0.1:0/0")
        server.register_native_echo("EchoService", "Echo")
        try:
            ep = server.listen_endpoint()
            stub = _stub(server, native=True, timeout_ms=10000)
            stub.Echo(echo_pb2.EchoRequest(message="before"))

            def data_frame(body: bytes) -> bytes:
                return b"TPUC\x03" + _struct.pack("!I", len(body)) + body

            def hello() -> bytes:
                j = (b'{"v": 1, "pool": "nonexistent_pool_zz", '
                     b'"bs": 4096, "bc": 4, "ordinal": 0, "pid": 1}')
                return b"TPUC\x01" + _struct.pack("!I", len(j)) + j

            rng = random.Random(7)
            attacks = [
                # block index beyond the pool
                _struct.pack("!II", 0, 1) + _struct.pack("!II", 9999, 64),
                # length beyond the block size
                _struct.pack("!II", 0, 1) + _struct.pack("!II", 0, 1 << 30),
                # nsegs lies about the body size
                _struct.pack("!II", 0, 4096),
                # zero-length segment
                _struct.pack("!II", 0, 2) + _struct.pack("!II", 0, 0) * 2,
            ] + [bytes(rng.randrange(256) for _ in range(rng.randrange(
                1, 128))) for _ in range(20)]
            for body in attacks:
                with _socket.create_connection((ep.host, ep.port),
                                               timeout=5) as s:
                    s.sendall(hello())
                    s.sendall(data_frame(body))
                    s.settimeout(1)
                    try:
                        while s.recv(4096):
                            pass
                    except (TimeoutError, OSError):
                        pass
            r = stub.Echo(echo_pb2.EchoRequest(message="after"))
            assert r.message == "after"  # engine + real tunnel survived
        finally:
            server.stop()
            server.join()


class TestShutdownQuiesce:
    """dp_rt_shutdown must quiesce TPUC sender workers mid-traffic
    (ADVICE r2 medium: detached senders leaked threads/conns/shm and could
    UAF the Runtime at shutdown)."""

    def test_shutdown_under_tunnel_load_returns_promptly(self):
        from brpc_tpu import native

        lib = native.load_dataplane()
        if lib is None:
            pytest.skip("native engine unavailable")
        rt = lib.dp_rt_create(2, 0)
        lid = lib.dp_listen(rt, b"127.0.0.1", 0)
        assert lid >= 0
        lib.dp_listener_set_tpu(rt, lid, 0)
        lib.dp_register_echo(rt, lid, b"EchoService", b"Echo")
        port = lib.dp_listen_port(rt, lid)

        # drive large echoes through the tunnel from a separate bench
        # runtime so per-conn sender workers are live when we shut down
        result = {}

        def bench():
            outs = [ctypes.c_double() for _ in range(5)]
            result["rc"] = lib.dp_bench_echo2(
                b"127.0.0.1", port, 1, 2, 4, 1 << 20, 8000,
                b"EchoService", b"Echo",
                *[ctypes.byref(o) for o in outs])

        t = threading.Thread(target=bench, daemon=True)
        t.start()
        time.sleep(1.0)  # let traffic flow

        done = threading.Event()

        def shut():
            lib.dp_rt_shutdown(rt)
            done.set()

        s = threading.Thread(target=shut, daemon=True)
        s.start()
        assert done.wait(15), "dp_rt_shutdown hung (sender quiesce broken)"
        t.join(timeout=20)
        assert not t.is_alive()
