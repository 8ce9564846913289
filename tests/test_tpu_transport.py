"""Cross-process tpu:// transport tests (VERDICT r1 #1 — the graft).

Pattern follows the reference's RPC integration tests (SURVEY §4): real
sockets, no mock transport. The multi-process test is the round's
acceptance criterion: a Server in process A serving RPCs issued by a
Channel in process B over a tpu:// endpoint, bytes staged through the
shared-memory registered block pool (reference RdmaEndpoint blueprint,
rdma_endpoint.cpp:127-130 handshake, block_pool.cpp, sliding window
rdma_endpoint.h:256-261).
"""

import os
import subprocess
import sys
import threading
import time

import pytest

from brpc_tpu.proto import echo_pb2
from brpc_tpu.rpc import (
    Channel,
    ChannelOptions,
    Controller,
    Server,
    ServerOptions,
    Service,
    Stub,
)

ECHO = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]


class EchoServiceImpl(Service):
    DESCRIPTOR = ECHO

    def Echo(self, cntl, request, done):
        cntl.response_attachment = cntl.request_attachment
        return echo_pb2.EchoResponse(message=request.message,
                                     payload=request.payload)


@pytest.fixture()
def tpu_server():
    server = Server(ServerOptions())
    server.add_service(EchoServiceImpl())
    server.start("tpu://127.0.0.1:0/0")
    yield server
    server.stop()
    server.join()


def _stub_for(server, timeout_ms=10000):
    channel = Channel(ChannelOptions(protocol="trpc_std",
                                     timeout_ms=timeout_ms))
    channel.init(str(server.listen_endpoint()))
    return Stub(channel, ECHO)


class TestTunnelLoopback:
    """Client and server roles in one process, but the full transport in
    between: TCP bootstrap, HELLO handshake, shm block pool, credits."""

    def test_endpoint_is_tpu_scheme(self, tpu_server):
        ep = tpu_server.listen_endpoint()
        assert ep.is_tpu() and ep.port != 0
        assert str(ep).startswith("tpu://")

    def test_small_inline_echo(self, tpu_server):
        stub = _stub_for(tpu_server)
        cntl = Controller()
        cntl.request_attachment = b"tail"
        r = stub.Echo(echo_pb2.EchoRequest(message="hello"), controller=cntl)
        assert r.message == "hello"
        assert cntl.response_attachment == b"tail"

    def test_block_path_roundtrip(self, tpu_server):
        stub = _stub_for(tpu_server)
        payload = bytes(range(256)) * (1024 * 1024 // 256)  # 1MB, patterned
        r = stub.Echo(echo_pb2.EchoRequest(message="big", payload=payload))
        assert r.payload == payload

    def test_payload_larger_than_window_streams(self, tpu_server):
        # 24MB > the 16MB credit window: must stream, not deadlock
        stub = _stub_for(tpu_server, timeout_ms=60000)
        payload = b"\xab" * (24 * 1024 * 1024)
        r = stub.Echo(echo_pb2.EchoRequest(message="huge", payload=payload))
        assert r.payload == payload

    def test_attachment_rides_blocks(self, tpu_server):
        stub = _stub_for(tpu_server)
        att = b"A" * (300 * 1024)  # bigger than one 256KB block
        cntl = Controller()
        cntl.request_attachment = att
        r = stub.Echo(echo_pb2.EchoRequest(message="m"), controller=cntl)
        assert cntl.response_attachment == att

    def test_concurrent_clients_interleave_safely(self, tpu_server):
        stub = _stub_for(tpu_server, timeout_ms=30000)
        errs = []

        def worker(i):
            try:
                payload = bytes([i]) * (512 * 1024 + i)
                for _ in range(3):
                    r = stub.Echo(echo_pb2.EchoRequest(message=str(i),
                                                       payload=payload))
                    assert r.payload == payload, f"worker {i} corrupted"
            except Exception as e:  # noqa: BLE001
                errs.append((i, e))

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errs, errs

    def test_pipelined_async_calls(self, tpu_server):
        channel = Channel(ChannelOptions(protocol="trpc_std",
                                         timeout_ms=30000))
        channel.init(str(tpu_server.listen_endpoint()))
        stub = Stub(channel, ECHO)
        done_evt = threading.Event()
        results = []
        n = 20

        def make_done(i):
            def done(cntl):
                results.append((i, cntl.error_code,
                                cntl.response.message if cntl.response else ""))
                if len(results) == n:
                    done_evt.set()
            return done

        for i in range(n):
            stub.Echo(echo_pb2.EchoRequest(message=f"m{i}"),
                      done=make_done(i))
        assert done_evt.wait(30)
        assert sorted(m for _, code, m in results if code == 0) == \
            sorted(f"m{i}" for i in range(n))

    def test_server_stop_fails_pending_cleanly(self):
        server = Server(ServerOptions())

        # a SUBCLASS scopes the name override — patching the property on
        # the shared Service base renamed every later service in the
        # process (caught when BuiltinViewService started auto-mounting)
        class _SlowSvc(Service):
            @property
            def service_name(self):
                return "EchoService"

        svc = _SlowSvc()

        gate = threading.Event()

        def slow(cntl, request, done):
            gate.wait(5)
            return echo_pb2.EchoResponse(message="late")

        svc.add_method("Echo", slow, echo_pb2.EchoRequest,
                       echo_pb2.EchoResponse)
        server.add_service(svc)
        server.start("tpu://127.0.0.1:0/0")
        stub = _stub_for(server, timeout_ms=2000)
        cntl = Controller()
        finished = threading.Event()
        stub.Echo(echo_pb2.EchoRequest(message="x"), controller=cntl,
                  done=lambda _c: finished.set())
        time.sleep(0.2)
        server.stop()
        server.join(timeout=0.5)
        gate.set()
        assert finished.wait(5)
        # either the late response made it before teardown or the call
        # failed with a socket/timeout error — never a hang
        server.join()


class TestOrdinalAddressing:
    def test_wrong_ordinal_refused(self, tpu_server):
        # server fronts device 0; dialing /3 must be refused at handshake
        ep = tpu_server.listen_endpoint()
        bad = f"tpu://{ep.host}:{ep.port}/3"
        channel = Channel(ChannelOptions(protocol="trpc_std",
                                         timeout_ms=3000, max_retry=0))
        channel.init(bad)
        stub = Stub(channel, ECHO)
        from brpc_tpu.rpc.channel import RpcError

        with pytest.raises((RpcError, ConnectionError)):
            stub.Echo(echo_pb2.EchoRequest(message="x"))
        # the right ordinal still works
        good_stub = _stub_for(tpu_server)
        assert good_stub.Echo(
            echo_pb2.EchoRequest(message="ok")).message == "ok"


class _FakeCtrl:
    """Stand-in bootstrap socket: records every frame the endpoint writes
    so tests can assert exactly which credits were ACKed, and when."""

    def __init__(self):
        self.frames = []          # raw bytes, one entry per write()
        self.failed = False
        self.remote = None
        self.error_code = 0
        self.error_text = ""
        self.on_failed_hook = None
        self.cut_batch_hook = None

    def write(self, data, id_wait=None):
        if self.failed:
            return 1
        self.frames.append(
            data.tobytes() if hasattr(data, "tobytes") else bytes(data))
        return 0

    def set_failed(self, code, reason=""):
        if self.failed:
            return
        self.failed = True
        self.error_code = code
        self.error_text = reason
        if self.on_failed_hook is not None:  # real Socket fires this too
            self.on_failed_hook(code, reason)


def _acked_indices(fake):
    """All block indices returned so far, one list per FT_ACK frame."""
    import struct

    from brpc_tpu.tpu import transport as tr

    out = []
    for raw in fake.frames:
        magic, ftype, blen = struct.unpack_from(tr.CTRL_HDR, raw)
        if ftype == tr.FT_ACK:
            body = raw[tr.CTRL_HDR_SIZE:tr.CTRL_HDR_SIZE + blen]
            vals = struct.unpack(f"!{len(body) // 4}I", body)
            # v2 ACK body: (epoch, count, *indices)
            out.append(list(vals[2:2 + vals[1]]))
    return out


def _make_endpoint():
    from brpc_tpu.policy import ensure_registered
    from brpc_tpu.tpu import transport as tr

    ensure_registered()
    fake = _FakeCtrl()
    ep = tr.TpuEndpoint(fake, role="client", target_ordinal=0,
                        block_size=64 * 1024, block_count=8)
    return tr, fake, ep


def _trpc_response_packet(payload: bytes) -> bytes:
    """A complete, well-formed trpc_std RESPONSE for a correlation id that
    does not exist — the client stack parses and then quietly drops it,
    which is exactly the 'parser consumed the bytes' event."""
    from brpc_tpu.policy.trpc_std import TrpcStdProtocol
    from brpc_tpu.proto import rpc_meta_pb2

    meta = rpc_meta_pb2.RpcMeta()
    meta.correlation_id = 0x7FFF1234
    meta.response.error_code = 0
    return TrpcStdProtocol().pack_response(meta, payload).tobytes()


def _data_frame_body(segs, epoch=0):
    """DATA body referencing pool blocks: [(idx, ln), ...]. Fake-ctrl
    endpoints are built at epoch 0, so the default matches."""
    import struct

    from brpc_tpu.tpu import transport as tr

    body = struct.pack(tr.DATA_BODY_HDR, epoch, 0, len(segs))
    for idx, ln in segs:
        body += struct.pack(tr.SEG_FMT, idx, ln)
    return body


class TestCreditReturnExactlyOnce:
    """Tentpole regression: a borrowed block's credit is released exactly
    once, only after the parser consumed the bytes — and teardown with
    borrows outstanding neither leaks credits nor double-releases."""

    def test_credit_deferred_until_parse_consumes(self):
        from brpc_tpu.butil.iobuf import IOBuf, supports_block_ownership

        if not supports_block_ownership():
            pytest.skip("no block-ownership exporter in this environment")
        tr, fake, ep = _make_endpoint()
        try:
            pkt = _trpc_response_packet(b"\xcd" * 8192)
            half = len(pkt) // 2
            pool = ep.recv_pool
            # peer 'writes' the packet across two registered blocks
            pool._shm.buf[0:half] = pkt[:half]
            blk = pool.block_size
            pool._shm.buf[blk:blk + len(pkt) - half] = pkt[half:]

            # frame 1: only the first half — the parser cannot finish, so
            # NO credit may come back yet
            ep.on_data(IOBuf(_data_frame_body([(0, half)])))
            assert _acked_indices(fake) == []
            assert ep._borrowed_outstanding == 1
            assert ep._released_total == 0

            # frame 2: the rest — the message parses, its body is consumed
            # by the (unknown-cid) response path, credits flow back
            ep.on_data(IOBuf(_data_frame_body([(1, len(pkt) - half)])))
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                acked = [i for frame in _acked_indices(fake) for i in frame]
                if sorted(acked) == [0, 1]:
                    break
                time.sleep(0.01)
            acked = [i for frame in _acked_indices(fake) for i in frame]
            assert sorted(acked) == [0, 1], acked  # each EXACTLY once
            deadline = time.monotonic() + 5
            while ep._borrowed_outstanding and time.monotonic() < deadline:
                time.sleep(0.01)
            assert ep._borrowed_outstanding == 0
            assert ep._released_total == 2
        finally:
            ep.fail(0, "test done")

    def test_teardown_with_outstanding_borrow(self):
        from brpc_tpu.butil.iobuf import IOBuf, supports_block_ownership

        if not supports_block_ownership():
            pytest.skip("no block-ownership exporter in this environment")
        tr, fake, ep = _make_endpoint()
        pkt = _trpc_response_packet(b"\xee" * 4096)
        pool = ep.recv_pool
        pool._shm.buf[0:64] = pkt[:64]   # incomplete head only
        ep.on_data(IOBuf(_data_frame_body([(0, 64)])))
        assert ep._borrowed_outstanding == 1
        assert _acked_indices(fake) == []

        frames_before = len(fake.frames)
        ep.fail(999, "test teardown")
        # the borrow was released exactly once by the teardown clear...
        assert ep._released_total == 1
        assert ep._borrowed_outstanding == 0
        # ...but its credit was NOT acked (peer is gone), and no ack frame
        # was written during/after teardown
        assert _acked_indices(fake) == []
        assert all(f[:4] == tr.CTRL_MAGIC[:4] for f in fake.frames)
        # the pool unmapped inline: no exports were left behind
        assert pool.exports == 0
        assert pool._closed

    def test_teardown_with_inflight_body_defers_pool_close(self):
        from brpc_tpu.butil.iobuf import IOBuf, supports_block_ownership

        if not supports_block_ownership():
            pytest.skip("no block-ownership exporter in this environment")
        tr, fake, ep = _make_endpoint()
        pkt = _trpc_response_packet(b"\xaa" * 4096)
        pool = ep.recv_pool
        pool._shm.buf[0:64] = pkt[:64]
        ep.on_data(IOBuf(_data_frame_body([(0, 64)])))
        # simulate an in-flight message body still holding borrowed bytes
        held = ep.vsock.read_buf.cutn(64)
        ep.fail(999, "teardown with body in flight")
        assert ep._released_total == 0          # the borrow is still live
        assert pool.exports == 1
        assert not pool._closed                 # unmap deferred, not forced
        del held                                 # the fiber finishes
        assert ep._released_total == 1           # exactly once
        assert ep._borrowed_outstanding == 0
        assert pool.exports == 0
        tr._sweep_deferred_pools()               # retry outside the cascade
        assert pool._closed
        assert _acked_indices(fake) == []        # no credit ack after death

    def test_loopback_echo_is_zero_copy(self, tpu_server):
        """Acceptance: block-segment frames cross the receive path with
        ZERO full-payload copies — all segment bytes are borrowed, none
        copied (both directions of a loopback echo count here)."""
        from brpc_tpu.butil.iobuf import supports_block_ownership
        from brpc_tpu.tpu import transport as tr

        if not supports_block_ownership():
            pytest.skip("no block-ownership exporter in this environment")
        stub = _stub_for(tpu_server)
        payload = b"\x5a" * (1024 * 1024)
        stub.Echo(echo_pb2.EchoRequest(message="warm", payload=payload))
        borrowed0 = tr.g_tunnel_borrowed_bytes.get_value()
        copied0 = tr.g_tunnel_copied_bytes.get_value()
        r = stub.Echo(echo_pb2.EchoRequest(message="zc", payload=payload))
        assert r.payload == payload
        borrowed = tr.g_tunnel_borrowed_bytes.get_value() - borrowed0
        copied = tr.g_tunnel_copied_bytes.get_value() - copied0
        # request (server side) + response (client side) both ride blocks
        assert borrowed >= 2 * len(payload), (borrowed, copied)
        assert copied == 0, (borrowed, copied)


class TestWindowAccounting:
    def test_credits_return_after_traffic(self, tpu_server):
        stub = _stub_for(tpu_server)
        payload = b"z" * (2 * 1024 * 1024)
        for _ in range(5):
            r = stub.Echo(echo_pb2.EchoRequest(message="w", payload=payload))
            assert len(r.payload) == len(payload)
        # after all RPCs complete the client's view of the server window
        # must be full again (all credits returned)
        from brpc_tpu.tpu import transport as tr

        with tr._remote_lock:
            vs = next(iter(tr._remote_sockets.values()))
        win = vs.endpoint.window
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            with win._cond:
                if len(win._free) == win.block_count:
                    break
            time.sleep(0.01)
        with win._cond:
            assert len(win._free) == win.block_count


class TestV1Peer:
    """The native lane speaks handshake version 1: no ``gen`` in HELLO and
    no epoch word in DATA / ACK bodies, so ``on_data`` / ``on_ack`` take
    this endpoint's own epoch for the frame's. That is sound only while
    an endpoint with a v1 peer never changes generation."""

    def test_a_repeat_hello_never_restarts_the_epoch(self):
        import json

        from brpc_tpu.tpu import transport as tr

        fake = _FakeCtrl()
        ep = tr.TpuEndpoint(fake, role="server")
        hello = json.dumps({"pool": "no-such-pool", "bs": 65536, "bc": 8,
                            "ordinal": 0}).encode()    # v1: no v, no gen
        try:
            ep.on_hello(hello)
            assert ep.ready.is_set() and not ep._failed
            assert (ep.peer_version, ep.epoch) == (1, 0)
            restarts0 = tr.g_tunnel_epoch_restarts.get_value()
            stale0 = tr.g_tunnel_stale_epoch_frames.get_value()
            pool = ep.recv_pool
            ep.on_hello(hello)
            assert tr.g_tunnel_epoch_restarts.get_value() == restarts0
            assert tr.g_tunnel_stale_epoch_frames.get_value() == stale0 + 1
            assert ep.epoch == 0 and ep.recv_pool is pool
        finally:
            ep.fail(0, "test done")


_CHILD_SERVER = r"""
import sys
from brpc_tpu.proto import echo_pb2
from brpc_tpu.rpc import Server, ServerOptions, Service

class EchoServiceImpl(Service):
    DESCRIPTOR = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]
    def Echo(self, cntl, request, done):
        cntl.response_attachment = cntl.request_attachment
        return echo_pb2.EchoResponse(message="from-child:" + request.message,
                                     payload=request.payload)

server = Server(ServerOptions())
server.add_service(EchoServiceImpl())
server.start("tpu://127.0.0.1:0/0")
print(f"LISTENING {server.listen_endpoint()}", flush=True)
sys.stdin.readline()   # parent closes stdin to stop us
server.stop(); server.join()
"""


class TestTwoProcesses:
    """THE acceptance test: Channel in this process, Server in a child
    process, RPC over tpu:// with payload through the shm block pool."""

    @pytest.fixture()
    def child_server(self):
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        proc = subprocess.Popen(
            [sys.executable, "-c", _CHILD_SERVER],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        line = proc.stdout.readline().strip()
        assert line.startswith("LISTENING "), (
            line, proc.stderr.read() if proc.poll() is not None else "")
        yield line.split(" ", 1)[1]
        try:
            proc.stdin.close()
            proc.wait(10)
        except Exception:
            proc.kill()

    def test_cross_process_echo(self, child_server):
        channel = Channel(ChannelOptions(protocol="trpc_std",
                                         timeout_ms=15000))
        channel.init(child_server)
        stub = Stub(channel, ECHO)
        r = stub.Echo(echo_pb2.EchoRequest(message="ping"))
        assert r.message == "from-child:ping"

    def test_cross_process_bulk_payload(self, child_server):
        channel = Channel(ChannelOptions(protocol="trpc_std",
                                         timeout_ms=30000))
        channel.init(child_server)
        stub = Stub(channel, ECHO)
        payload = bytes(range(256)) * (4 * 1024 * 1024 // 256)
        cntl = Controller()
        cntl.request_attachment = b"side-channel"
        r = stub.Echo(echo_pb2.EchoRequest(message="bulk", payload=payload),
                      controller=cntl)
        assert r.payload == payload
        assert cntl.response_attachment == b"side-channel"

    def test_cross_process_concurrent(self, child_server):
        channel = Channel(ChannelOptions(protocol="trpc_std",
                                         timeout_ms=30000))
        channel.init(child_server)
        stub = Stub(channel, ECHO)
        errs = []

        def worker(i):
            try:
                payload = bytes([i]) * (256 * 1024 * (1 + i % 3))
                r = stub.Echo(echo_pb2.EchoRequest(message=str(i),
                                                   payload=payload))
                assert r.payload == payload
                assert r.message == f"from-child:{i}"
            except Exception as e:  # noqa: BLE001
                errs.append((i, repr(e)))

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errs, errs

    def test_tunnel_failure_errors_inflight_and_reconnects(self, child_server):
        channel = Channel(ChannelOptions(protocol="trpc_std",
                                         timeout_ms=10000, max_retry=0))
        channel.init(child_server)
        stub = Stub(channel, ECHO)
        # prove liveness first
        stub.Echo(echo_pb2.EchoRequest(message="alive"))
        from brpc_tpu.rpc import errors as _errors
        from brpc_tpu.tpu import transport as tr

        with tr._remote_lock:
            vs = [s for s in tr._remote_sockets.values() if not s.failed][0]
        # a call id pending on the tunnel when it dies must get the socket
        # error through the error channel (reference Socket::SetFailed fanout)
        codes = []
        evt = threading.Event()
        from brpc_tpu.fiber import call_id as _cid

        cid = _cid.id_create(
            data=None,
            on_error=lambda d, c, code: (codes.append(code),
                                         _cid.id_unlock_and_destroy(c),
                                         evt.set()))
        vs.add_pending_id(cid)
        vs.close()
        assert evt.wait(5)
        assert codes == [_errors.EFAILEDSOCKET]
        # ...and the next call transparently re-dials a fresh tunnel
        r = stub.Echo(echo_pb2.EchoRequest(message="recovered"))
        assert r.message == "from-child:recovered"
