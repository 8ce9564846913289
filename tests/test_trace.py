"""Deep data-path tracing: phase timelines, structured events, /rpcz
filters + JSON export, the /tpu builtin and the trace_view renderer.

Layout mirrors how the subsystem is consumed:

* span-core units — phase accumulation, the event cap, monotonic-clock
  durations immune to wall skew, JSON round-trips;
* each dispatch path observably stamps its phases — generic (TCP
  baidu_std), native/tunnel (tpu:// trpc_std), batched;
* a credit-starved window produces a measured ``credit_wait_us`` and a
  ``credit_stall`` event on the owning RPC's span;
* the HTTP surface — /rpcz query filters, ?format=json, /tpu state —
  and the offline waterfall renderer;
* sampling off leaves the hot path span-free (the zero-overhead claim).
"""

import io
import json
import threading
import time

import pytest

from brpc_tpu import fault
from brpc_tpu import flags as _flags
from brpc_tpu.policy.http_protocol import http_fetch
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
    errors,
)
from brpc_tpu.trace import span as _span

from test_tpu_transport import _stub_for, tpu_server  # noqa: F401

ECHO = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]


class EchoImpl(Service):
    DESCRIPTOR = ECHO

    def Echo(self, cntl, request, done):
        if request.message == "boom":
            cntl.set_failed(errors.EINTERNAL, "requested failure")
            return None
        cntl.response_attachment = cntl.request_attachment
        return echo_pb2.EchoResponse(message=request.message,
                                     payload=request.payload)


@pytest.fixture()
def traced():
    """Sampling wide open: ratio 1.0 and the collector cap disabled, so
    every span in the test is recorded deterministically."""
    from brpc_tpu.metrics.collector import global_collector

    _flags.set_flag("rpcz_sample_ratio", "1.0")
    _flags.set_flag("collector_max_samples_per_second", "0")
    global_collector()._deny_until = 0.0
    _span.reset_for_test()
    yield
    _flags.set_flag("collector_max_samples_per_second", "1000")


@pytest.fixture()
def tcp_server():
    server = Server().add_service(EchoImpl()).start("127.0.0.1:0")
    yield server
    server.stop()
    server.join(timeout=2)


def addr(server):
    return str(server.listen_endpoint())


def _wait_spans(predicate, timeout=3.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        spans = _span.recent_spans(100)
        if predicate(spans):
            return spans
        time.sleep(0.01)
    return _span.recent_spans(100)


def _find(spans, kind, method="Echo"):
    for s in spans:
        if s.kind == kind and s.method == method:
            return s
    return None


# ------------------------------------------------------------------ span core
class TestSpanCore:
    def test_phase_accumulates_and_clamps(self):
        sp = _span.Span(1, 1, 0, _span.KIND_CLIENT, "S", "M")
        sp.add_phase("send_us", 10.0)
        sp.add_phase("send_us", 5.0)
        sp.add_phase("queue_us", -3.0)  # negative clamps to zero
        assert sp.phases["send_us"] == 15.0
        assert sp.phases["queue_us"] == 0.0

    def test_event_cap_counts_drops(self):
        sp = _span.Span(1, 1, 0, _span.KIND_CLIENT, "S", "M")
        for i in range(_span.MAX_EVENTS_PER_SPAN + 10):
            sp.event("e", i=i)
        assert len(sp.events) == _span.MAX_EVENTS_PER_SPAN
        assert sp.events_dropped == 10
        assert "events dropped" in sp.render()

    def test_durations_ride_monotonic_clock(self, monkeypatch):
        """Wall-clock skew (NTP step) between start and end must not
        corrupt the reported latency — the regression the monotonic
        migration exists to prevent."""
        sp = _span.Span(1, 1, 0, _span.KIND_SERVER, "S", "M")
        real = time.time
        monkeypatch.setattr(time, "time", lambda: real() - 3600.0)
        time.sleep(0.01)
        sp.end(0)
        assert 5_000 < sp.latency_us < 5_000_000

    def test_json_round_trip(self, traced):
        sp = _span.Span(0xabc, 0xdef, 0x123, _span.KIND_SERVER,
                        "EchoService", "Echo", peer="1.2.3.4:5")
        sp.request_size = 64
        sp.add_phase("parse_us", 12.5)
        sp.event("credit_stall", wait_us=8.0, need=4, got=0)
        sp.annotate("hello")
        sp.end(0)
        d = json.loads(json.dumps(sp.to_dict()))
        assert d["trace_id"] == f"{0xabc:016x}"
        assert d["parent_span_id"] == f"{0x123:016x}"
        assert d["phases"]["parse_us"] == 12.5
        assert d["events"][0]["name"] == "credit_stall"
        assert d["events"][0]["need"] == 4
        assert d["annotations"][0]["text"] == "hello"
        td = json.loads(json.dumps(_span.trace_to_dict(0xabc)))
        assert [s["span_id"] for s in td["spans"]] == [f"{0xdef:016x}"]

    def test_recent_spans_filters(self, traced):
        for method, code, us in (("Fast", 0, 10), ("Slow", 0, 90_000),
                                 ("Bad", 7, 20)):
            sp = _span.Span(1, 1, 0, _span.KIND_SERVER, "Svc", method)
            sp.start_mono_us -= us  # synthesize latency
            sp.end(code)
        assert [s.method for s in _span.recent_spans(10)] == \
            ["Bad", "Slow", "Fast"]  # newest first
        assert [s.method for s in _span.recent_spans(10, method="Svc.S")] \
            == ["Slow"]
        assert [s.method for s in
                _span.recent_spans(10, min_latency_us=50_000)] == ["Slow"]
        assert [s.method for s in _span.recent_spans(10, error_only=True)] \
            == ["Bad"]


# ------------------------------------------------------------- generic path
class TestGenericPathPhases:
    def test_server_span_carries_dispatch_phases(self, tcp_server, traced):
        ch = Channel().init(addr(tcp_server))
        Stub(ch, ECHO).Echo(echo_pb2.EchoRequest(message="hi"))
        spans = _wait_spans(lambda ss: _find(ss, "server") is not None)
        srv = _find(spans, "server")
        assert srv is not None
        for name in ("queue_us", "parse_us", "execute_us", "respond_us"):
            assert name in srv.phases, f"missing {name}: {srv.phases}"
        # additivity: the marks never explain more than the span's latency
        assert sum(srv.phases.values()) <= srv.latency_us * 1.05
        client = _find(spans, "client")
        assert client is not None and "parse_us" in client.phases

    def test_phase_aggregates_exposed(self, tcp_server, traced):
        from brpc_tpu.metrics import dump_exposed

        ch = Channel().init(addr(tcp_server))
        Stub(ch, ECHO).Echo(echo_pb2.EchoRequest(message="agg"))
        _wait_spans(lambda ss: _find(ss, "server") is not None)
        snap = dump_exposed()
        assert "g_span_phase_execute_us" in snap


# -------------------------------------------------------------- tunnel path
class TestTunnelPathPhases:
    def test_block_path_echo_phases(self, tpu_server, traced):
        stub = _stub_for(tpu_server, timeout_ms=30000)
        payload = b"\xa5" * (1 << 20)
        r = stub.Echo(echo_pb2.EchoRequest(message="m", payload=payload))
        assert r.payload == payload
        spans = _wait_spans(
            lambda ss: _find(ss, "client") is not None
            and _find(ss, "server") is not None)
        client = _find(spans, "client")
        srv = _find(spans, "server")
        assert client.trace_id == srv.trace_id
        # 1MB rides the block path: the client span must carry send
        # timing, the server span the dispatch phases
        assert client.phases.get("send_us", 0.0) > 0.0
        assert "credit_wait_us" in client.phases
        for name in ("parse_us", "execute_us", "respond_us"):
            assert name in srv.phases
        # the pipelined send stamps one event per posted quantum
        assert any(name == "send_quantum"
                   for _, name, _ in client.events)

    def test_streaming_echo_phases_explain_latency(self, tpu_server,
                                                   traced):
        """Acceptance: a sampled 16MB streaming echo's phase breakdown
        sums to ~the measured trace latency (credit_wait/send on the
        client + queue/parse/execute/respond/send on the server)."""
        stub = _stub_for(tpu_server, timeout_ms=60000)
        payload = bytes(range(256)) * (16 * 1024 * 1024 // 256)
        r = stub.Echo(echo_pb2.EchoRequest(message="big", payload=payload))
        assert r.payload == payload
        spans = _wait_spans(
            lambda ss: _find(ss, "client") is not None
            and _find(ss, "server") is not None, timeout=10.0)
        client = _find(spans, "client")
        srv = _find(spans, "server")
        assert srv.trace_id == client.trace_id
        accounted = sum(client.phases.values()) + sum(srv.phases.values())
        total = client.latency_us
        # the timeline must explain the latency — a large unattributed
        # remainder means a layer stopped stamping its marks (bounded
        # above too: double-counted phases would overshoot the wall time)
        assert accounted >= 0.85 * total, \
            f"phases {accounted:.0f}us explain too little of {total:.0f}us"
        assert accounted <= 1.15 * total, \
            f"phases {accounted:.0f}us overshoot wall time {total:.0f}us"

    def test_credit_stall_measured_under_shrunken_window(self, tpu_server,
                                                         traced):
        from brpc_tpu.tpu import transport

        stub = _stub_for(tpu_server, timeout_ms=30000)
        payload = b"\x42" * (1 << 20)
        stub.Echo(echo_pb2.EchoRequest(message="warm", payload=payload))
        ep = tpu_server.listen_endpoint()
        vs = transport._remote_sockets[
            (ep.host, ep.port, ep.device_ordinal)]
        win = vs.endpoint.window
        time.sleep(0.1)  # let in-flight ACKs settle before seizing
        stolen = []
        while win._free:  # shrink the window to zero credits
            stolen.extend(win.acquire(len(win._free)))
        stalls0 = transport.g_tunnel_credit_stalls.get_value()
        result = []
        t = threading.Thread(target=lambda: result.append(
            stub.Echo(echo_pb2.EchoRequest(message="stalled",
                                           payload=payload))))
        t.start()
        time.sleep(0.25)  # the sender is parked on acquire() now
        win.release(stolen)
        t.join(20)
        assert result and result[0].payload == payload
        assert transport.g_tunnel_credit_stalls.get_value() > stalls0
        spans = _wait_spans(lambda ss: any(
            s.kind == "client" and s.phases.get("credit_wait_us", 0) >
            100_000 for s in ss))
        stalled = next(s for s in spans if s.kind == "client"
                       and s.phases.get("credit_wait_us", 0) > 100_000)
        assert any(name == "credit_stall"
                   for _, name, _ in stalled.events)


# -------------------------------------------------------------- batched path
class TestBatchedPathPhases:
    def test_batch_riders_get_wait_and_execute(self, traced):
        from brpc_tpu.batch import make_batched

        def vec(batch):
            time.sleep(0.02)
            return ["ok"] * batch.size

        bm = make_batched("t.phases", vec, max_batch_size=2, max_delay_us=0,
                          flush_on_poll_batch=False)
        done = []
        spans = []
        for i in range(2):
            cntl = Controller()
            cntl.span = _span.Span(i + 1, i + 1, 0, _span.KIND_SERVER,
                                   "B", "V")
            spans.append(cntl.span)
            bm(cntl, f"req{i}", lambda resp=None: done.append(resp))
        deadline = time.monotonic() + 3
        while len(done) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(done) == 2
        for sp in spans:
            assert "batch_wait_us" in sp.phases
            assert sp.phases["execute_us"] >= 15_000  # the 20ms vec call
            ev = next(f for _, n, f in sp.events if n == "batch")
            assert ev["size"] == 2 and "pad" in ev and "bucket" in ev


# ------------------------------------------------------------- http surface
class TestRpczHttp:
    def _traffic(self, server):
        ch = Channel().init(addr(server))
        stub = Stub(ch, ECHO)
        stub.Echo(echo_pb2.EchoRequest(message="ok"))
        cntl = Controller()
        with pytest.raises(RpcError):
            stub.Echo(echo_pb2.EchoRequest(message="boom"),
                      controller=cntl)
        _wait_spans(lambda ss: any(s.error_code for s in ss
                                   if s.kind == "server"))

    def test_filters(self, tcp_server, traced):
        self._traffic(tcp_server)
        a = addr(tcp_server)
        assert b"EchoService.Echo" in http_fetch(a, "GET", "/rpcz").body
        assert b"EchoService.Echo" in http_fetch(
            a, "GET", "/rpcz?method=EchoService").body
        body = http_fetch(a, "GET", "/rpcz?method=NoSuchService").body
        assert b"EchoService.Echo" not in body
        body = http_fetch(a, "GET", "/rpcz?min_latency_us=999999999").body
        assert b"EchoService.Echo" not in body
        doc = json.loads(http_fetch(
            a, "GET", "/rpcz?error_only=1&format=json").body)
        assert doc["spans"] and all(s["error_code"] for s in doc["spans"])
        resp = http_fetch(a, "GET", "/rpcz?count=notanumber")
        assert resp.status == 400

    def test_json_export_and_trace_fetch(self, tcp_server, traced):
        self._traffic(tcp_server)
        a = addr(tcp_server)
        doc = json.loads(http_fetch(a, "GET", "/rpcz?format=json").body)
        span = next(s for s in doc["spans"]
                    if s["method"] == "Echo" and not s["error_code"])
        assert "phases" in span and "events" in span
        trace = json.loads(http_fetch(
            a, "GET", f"/rpcz/{span['trace_id']}?format=json").body)
        assert trace["trace_id"] == span["trace_id"]
        assert any(s["span_id"] == span["span_id"]
                   for s in trace["spans"])

    def test_tpu_builtin(self, tpu_server, traced):
        from brpc_tpu.builtin import services

        stub = _stub_for(tpu_server)
        stub.Echo(echo_pb2.EchoRequest(message="x",
                                       payload=b"\x01" * (1 << 20)))

        class _Http:
            path = "/tpu"
            query = {"format": "json"}

            def header(self, k, default=""):
                return default

        status, ctype, body = services.tpu_service(tpu_server, _Http())
        assert status == 200
        state = json.loads(body)
        assert state["client_endpoints"], "tunnel client endpoint missing"
        cl = state["client_endpoints"][0]
        assert cl["window_total"] > 0 and "credit_stalls" in cl
        assert state["server_endpoints"], "server endpoint missing"
        assert state["borrowed_peak_blocks"] >= 0
        _Http.query = {}
        status, ctype, body = services.tpu_service(tpu_server, _Http())
        assert status == 200 and "window:" in body

    def test_status_percentiles_and_method_vars(self, tcp_server, traced):
        from brpc_tpu.metrics import dump_exposed

        ch = Channel().init(addr(tcp_server))
        Stub(ch, ECHO).Echo(echo_pb2.EchoRequest(message="p"))
        body = http_fetch(addr(tcp_server), "GET", "/status").body
        assert b"p50=" in body and b"p90=" in body and b"p99=" in body
        # first dispatch auto-exposed the per-method recorder on /vars
        snap = dump_exposed()
        assert "rpc_method_echoservice_echo_latency_p50" in snap
        assert "rpc_method_echoservice_echo_count" in snap

    def test_prometheus_counter_type_lines(self):
        from brpc_tpu.metrics import prometheus_text

        # the TYPE line must say counter, carried by the prometheus_type
        # attribute through expose_as
        txt = prometheus_text()
        assert "# TYPE g_fault_hits counter" in txt


# ------------------------------------------------------------- trace_view
class TestTraceView:
    def test_waterfall_renders_phases_and_events(self, traced):
        root = _span.Span(0x77, 0x77, 0, _span.KIND_CLIENT,
                          "EchoService", "Echo")
        root.add_phase("send_us", 600.0)
        root.add_phase("credit_wait_us", 200.0)
        root.event("credit_stall", wait_us=200.0, need=4, got=0)
        child = _span.Span(0x77, 0x78, 0x77, _span.KIND_SERVER,
                           "EchoService", "Echo")
        child.add_phase("execute_us", 100.0)
        time.sleep(0.002)
        child.end(0)
        root.end(0)
        from tools import trace_view

        out = io.StringIO()
        trace_view.render(_span.trace_to_dict(0x77), out=out)
        text = out.getvalue()
        assert "EchoService.Echo" in text
        assert "phase legend" in text
        assert "[credit_stall]" in text
        assert "client" in text and "server" in text


# ------------------------------------------------------- probabilistic fault
class TestProbabilisticFault:
    def test_p_draw_rides_collector_budget(self, traced):
        from brpc_tpu.fault.core import g_fault_p_skipped

        _flags.set_flag("fault_injection_enabled", True)
        try:
            fault.arm("x.prob", mode="always", p=0.5)
            fired = sum(1 for _ in range(300)
                        if fault.hit("x.prob") is not None)
            # binomial(300, .5): a miss of this bound is ~1e-9
            assert 75 <= fired <= 225
            assert g_fault_p_skipped.get_value() > 0
        finally:
            fault.disarm_all()
            _flags.set_flag("fault_injection_enabled", False)

    def test_p_validated(self):
        with pytest.raises(ValueError):
            fault.arm("x.badp", p=0.0)
        with pytest.raises(ValueError):
            fault.arm("x.badp", p=1.5)


# ------------------------------------------------------------- sampling off
class TestSamplingOff:
    def test_hot_path_is_span_free(self, tcp_server):
        _flags.set_flag("rpcz_sample_ratio", "0.0")
        try:
            _span.reset_for_test()
            ch = Channel().init(addr(tcp_server))
            stub = Stub(ch, ECHO)
            cntl = Controller()
            stub.Echo(echo_pb2.EchoRequest(message="dark"),
                      controller=cntl)
            assert cntl.span is None
            time.sleep(0.1)
            assert _span.recent_spans(10) == []
        finally:
            _flags.set_flag("rpcz_sample_ratio", "1.0")
