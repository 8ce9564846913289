"""Tail-based trace retention (trace/tail.py).

Covers the settle-time decision table (error / QoS-shed / slow-vs-p99 /
watch correlation), the deferred-decision ring (hold, expiry, eviction),
the commit token bucket, the end-to-end wiring through a real server's
dump stream and the ``/rpcz?retained=tail`` + ``/dump`` builtins, and the
headline precision claim: tail retention recovers the delayed-request
traces that head sampling statistically discards.
"""

import json
import time

import pytest

from brpc_tpu import fault
from brpc_tpu import flags as _flags
from brpc_tpu.metrics.watch import (STATE_FIRING, WatchRule, global_watch)
from brpc_tpu.proto import echo_pb2
from brpc_tpu.rpc import Channel, Server, ServerOptions, Stub
from brpc_tpu.rpc.errors import EINTERNAL, ELIMIT, EOVERCROWDED
from brpc_tpu.trace import span as _span
from brpc_tpu.trace.rpc_dump import RpcDumpLoader
from brpc_tpu.trace.tail import (REASON_ERROR, REASON_SHED, REASON_SLOW,
                                 TailRetainer, g_dump_tail_dropped,
                                 g_dump_tail_retained, g_dump_tail_shed)
from tests.test_http import ECHO_DESC, EchoServiceImpl

_TAIL_FLAGS = ("rpc_dump_tail", "rpc_dump_tail_slow_x",
               "rpc_dump_tail_max_per_sec", "rpc_dump_tail_hold_s",
               "rpc_dump_tail_ring", "rpc_dump_ratio",
               "rpc_dump_max_per_sec")


@pytest.fixture(autouse=True)
def _clean_state(empty_registry):
    saved = {name: _flags.get(name) for name in _TAIL_FLAGS}
    _span.reset_for_test()
    # the retainer reads the process's watch rules, and one that another
    # file of this worker left firing (``tunnel_healer_trips`` after the
    # healer's tests) retains every trace at once: the rules are the
    # test's own for its length
    watch = global_watch()
    rules = watch.rules()
    watch.clear()
    yield
    watch.clear()
    for rule in rules:
        watch.add(rule)
    fault.disarm_all()
    for name, value in saved.items():
        _flags.set_flag(name, value)
    _span.reset_for_test()


@pytest.fixture()
def tail_on():
    _flags.set_flag("rpc_dump_tail", True)
    yield


@pytest.fixture()
def fault_enabled():
    _flags.set_flag("fault_injection_enabled", True)
    yield
    fault.disarm_all()
    _flags.set_flag("fault_injection_enabled", False)


# --------------------------------------------------------------- unit layer
class _FakeDumper:
    def __init__(self):
        self.commits = []

    def commit(self, pending, span, error_code):
        self.commits.append((dict(pending), span, error_code))


class _FakeSpan:
    def __init__(self, latency_us):
        self.latency_us = latency_us
        self.retained_reason = ""


@pytest.fixture()
def retainer():
    dumper = _FakeDumper()
    r = TailRetainer(dumper)
    yield r, dumper
    r.close()


class TestDecision:
    def test_disabled_by_default(self):
        assert TailRetainer.enabled() is False
        _flags.set_flag("rpc_dump_tail", True)
        assert TailRetainer.enabled() is True

    def test_error_retained_immediately(self, retainer):
        r, dumper = retainer
        span = _FakeSpan(100.0)
        before = g_dump_tail_retained.get_value()
        r.offer({"k": 1}, span, EINTERNAL, 1000.0)
        assert len(dumper.commits) == 1
        pending, _span_out, code = dumper.commits[0]
        assert pending["retained"] == "tail"
        assert pending["retention_reason"] == REASON_ERROR
        assert code == EINTERNAL
        assert span.retained_reason == REASON_ERROR
        assert g_dump_tail_retained.get_value() == before + 1

    @pytest.mark.parametrize("code", [EOVERCROWDED, ELIMIT])
    def test_qos_shed_retained(self, retainer, code):
        r, dumper = retainer
        span = _FakeSpan(50.0)
        r.offer({}, span, code, 1000.0)
        assert dumper.commits[0][0]["retention_reason"] == REASON_SHED
        assert span.retained_reason == REASON_SHED

    def test_slow_vs_p99_retained(self, retainer):
        r, dumper = retainer
        # slow_x default 2.0: 300 > 2 * 100 retains, 150 does not
        r.offer({}, _FakeSpan(300.0), 0, 100.0)
        assert dumper.commits[0][0]["retention_reason"] == REASON_SLOW
        r.offer({}, _FakeSpan(150.0), 0, 100.0)
        assert len(dumper.commits) == 1
        assert r.state()["held"] == 1

    def test_cold_method_never_slow(self, retainer):
        # p99 == 0 (no samples yet) must not classify everything as slow
        r, dumper = retainer
        r.offer({}, _FakeSpan(1e6), 0, 0.0)
        assert not dumper.commits
        assert r.state()["held"] == 1

    def test_none_span_ignored(self, retainer):
        r, dumper = retainer
        r.offer({}, None, EINTERNAL, 0.0)
        assert not dumper.commits
        assert r.state()["held"] == 0


class TestRing:
    def test_hold_expires_unwritten(self, retainer):
        r, dumper = retainer
        _flags.set_flag("rpc_dump_tail_hold_s", 0.05)
        before = g_dump_tail_dropped.get_value()
        r.offer({}, _FakeSpan(10.0), 0, 1000.0)
        assert r.state()["held"] == 1
        time.sleep(0.08)
        r.offer({}, _FakeSpan(10.0), 0, 1000.0)  # sweeps the expired hold
        assert r.state()["held"] == 1
        assert g_dump_tail_dropped.get_value() == before + 1
        assert not dumper.commits

    def test_ring_cap_evicts_oldest(self, retainer):
        r, dumper = retainer
        _flags.set_flag("rpc_dump_tail_ring", 2)
        before = g_dump_tail_dropped.get_value()
        for _ in range(3):
            r.offer({}, _FakeSpan(10.0), 0, 1000.0)
        assert r.state()["held"] == 2
        assert g_dump_tail_dropped.get_value() == before + 1
        assert not dumper.commits

    def test_close_drops_held(self):
        dumper = _FakeDumper()
        r = TailRetainer(dumper)
        r.offer({}, _FakeSpan(10.0), 0, 1000.0)
        before = g_dump_tail_dropped.get_value()
        hooks = len(global_watch().transition_hooks)
        r.close()
        assert g_dump_tail_dropped.get_value() == before + 1
        assert len(global_watch().transition_hooks) == hooks - 1
        # offers after close are no-ops
        r.offer({}, _FakeSpan(10.0), EINTERNAL, 0.0)
        assert not dumper.commits


class TestTokenBucket:
    def test_cap_sheds_excess_commits(self, retainer):
        r, dumper = retainer
        _flags.set_flag("rpc_dump_tail_max_per_sec", 1)
        before = g_dump_tail_shed.get_value()
        r.offer({}, _FakeSpan(1.0), EINTERNAL, 0.0)
        r.offer({}, _FakeSpan(1.0), EINTERNAL, 0.0)
        assert len(dumper.commits) == 1
        assert g_dump_tail_shed.get_value() == before + 1

    def test_uncapped_when_zero(self, retainer):
        r, dumper = retainer
        _flags.set_flag("rpc_dump_tail_max_per_sec", 0)
        for _ in range(5):
            r.offer({}, _FakeSpan(1.0), EINTERNAL, 0.0)
        assert len(dumper.commits) == 5


class TestWatchCorrelation:
    def test_already_firing_rule_retains_immediately(self, retainer):
        r, dumper = retainer
        rule = global_watch().add(
            WatchRule("tail_hot", "g_x", "threshold", ">", 1.0))
        try:
            rule.state = STATE_FIRING
            r.offer({}, _FakeSpan(10.0), 0, 1000.0)
            assert dumper.commits[0][0]["retention_reason"] == "watch:tail_hot"
        finally:
            global_watch().remove("tail_hot")

    def test_transition_drains_ring(self, retainer):
        r, dumper = retainer
        # the bucket starts with a single token; a drain is a burst
        _flags.set_flag("rpc_dump_tail_max_per_sec", 0)
        spans = [_FakeSpan(10.0), _FakeSpan(20.0)]
        for sp in spans:
            r.offer({}, sp, 0, 1000.0)
        assert r.state()["held"] == 2
        rule = global_watch().add(
            WatchRule("tail_drain", "g_y", "threshold", ">", 1.0))
        try:
            # drive the registry's own transition plumbing so the hook
            # wiring (not just _on_watch) is what's under test
            global_watch()._report(rule, STATE_FIRING)
            assert len(dumper.commits) == 2
            assert all(p["retention_reason"] == "watch:tail_drain"
                       for p, _s, _c in dumper.commits)
            assert r.state()["held"] == 0
            assert all(sp.retained_reason == "watch:tail_drain"
                       for sp in spans)
        finally:
            global_watch().remove("tail_drain")


# ---------------------------------------------------------------- e2e layer
class _FailingEcho(EchoServiceImpl):
    def Echo(self, cntl, request, done):
        if request.message == "boom":
            raise RuntimeError("boom")
        return super().Echo(cntl, request, done)


def _stub_for(server):
    return Stub(Channel().init(str(server.listen_endpoint())), ECHO_DESC)


def _pump(stub, n, msg="w"):
    for _ in range(n):
        stub.Echo(echo_pb2.EchoRequest(message=msg))


class TestServerIntegration:
    def test_error_lands_in_dump_and_rpcz(self, tmp_path, tail_on):
        from brpc_tpu.policy.http_protocol import http_fetch

        _flags.set_flag("rpc_dump_ratio", 0.0)
        _flags.set_flag("rpc_dump_tail_max_per_sec", 0)
        server = Server(ServerOptions(rpc_dump_dir=str(tmp_path)))
        server.add_service(_FailingEcho()).start("127.0.0.1:0")
        try:
            stub = _stub_for(server)
            _pump(stub, 20)
            with pytest.raises(Exception):
                stub.Echo(echo_pb2.EchoRequest(message="boom"))
            deadline = time.monotonic() + 5
            while (server.rpc_dumper.sampled_count < 1
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            addr = str(server.listen_endpoint())

            resp = http_fetch(addr, "GET", "/rpcz?retained=tail&format=json")
            assert resp.status == 200
            doc = json.loads(bytes(resp.body).decode())
            # warmup stragglers may legitimately be retained as slow_p99
            # alongside the seeded failure; select by reason
            errored = [s for s in doc["spans"]
                       if s["retained_reason"] == REASON_ERROR]
            assert len(errored) == 1
            assert errored[0]["error_code"] == EINTERNAL

            resp = http_fetch(addr, "GET", "/dump")
            assert resp.status == 200
            assert b"tail: enabled=True" in bytes(resp.body)
        finally:
            server.stop()
            server.join(timeout=2)
        server.rpc_dumper.close()
        records = [r for r in RpcDumpLoader(str(tmp_path))
                   if r.info.get("retention_reason") == REASON_ERROR]
        assert len(records) == 1
        rec = records[0]
        assert rec.info["retained"] == "tail"
        assert rec.info["error_code"] == EINTERNAL
        assert rec.method_key == "EchoService.Echo"

    def test_fast_traffic_not_dumped_wholesale(self, tmp_path, tail_on):
        _flags.set_flag("rpc_dump_ratio", 0.0)
        server = Server(ServerOptions(rpc_dump_dir=str(tmp_path)))
        server.add_service(EchoServiceImpl()).start("127.0.0.1:0")
        try:
            _pump(_stub_for(server), 50)
        finally:
            server.stop()
            server.join(timeout=2)
        server.rpc_dumper.close()
        # a cold-start straggler or two may genuinely exceed 2x the live
        # p99 and get retained; the point is the fast bulk is not dumped
        records = list(RpcDumpLoader(str(tmp_path)))
        assert len(records) <= 3
        assert all(r.info["retention_reason"] == REASON_SLOW
                   for r in records)


class TestTailPrecision:
    """The acceptance claim: for seeded delayed requests, tail retention
    recalls >= 90% of the delayed traces while head sampling at ratio 0.1
    recalls ~10% of them (and a pile of fast ones nobody will replay)."""

    DELAY_MS = 80
    DELAYED = 10
    # 100 fast calls between delayed ones keeps the outlier weight fraction
    # of the percentile window <= 1%, so the live p99 stays at the fast
    # value and every delayed call settles against it
    FAST_PER_CYCLE = 100

    def _run_server(self, tmp_path, service, calls):
        server = Server(ServerOptions(rpc_dump_dir=str(tmp_path)))
        server.add_service(service).start("127.0.0.1:0")
        try:
            calls(_stub_for(server))
        finally:
            server.stop()
            server.join(timeout=2)
        server.rpc_dumper.close()
        return list(RpcDumpLoader(str(tmp_path)))

    def _delayed_of(self, records):
        # seeded delay is 80ms; fast calls settle well under 60ms even
        # with scheduler noise
        return [r for r in records if r.info.get("latency_us", 0) > 60000]

    def test_tail_recalls_delayed_head_does_not(self, tmp_path, tail_on,
                                                fault_enabled):
        _flags.set_flag("rpc_dump_ratio", 0.0)
        _flags.set_flag("rpc_dump_tail_max_per_sec", 0)

        def tail_calls(stub):
            _pump(stub, self.FAST_PER_CYCLE)  # warm the percentile window
            for _ in range(self.DELAYED):
                fault.arm("rpc.handler.delay", count=1,
                          delay_ms=self.DELAY_MS)
                _pump(stub, 1, msg="delayed")
                _pump(stub, self.FAST_PER_CYCLE)

        tail_records = self._run_server(
            tmp_path / "tail", EchoServiceImpl(), tail_calls)
        tail_delayed = self._delayed_of(tail_records)
        recall = len(tail_delayed) / self.DELAYED
        assert recall >= 0.9, (
            f"tail retention recalled {len(tail_delayed)}/{self.DELAYED} "
            f"delayed traces")
        assert all(r.info["retention_reason"] == REASON_SLOW
                   for r in tail_delayed)
        assert all(r.info["retained"] == "tail" for r in tail_delayed)
        # and it is *selective*: the fast bulk is not dumped wholesale
        assert len(tail_records) <= self.DELAYED + 5

        # head sampling at ratio 0.1 over the same seeded workload:
        # the keep decision happens at arrival, blind to latency
        _flags.set_flag("rpc_dump_tail", False)
        _flags.set_flag("rpc_dump_ratio", 0.1)

        def head_calls(stub):
            for _ in range(self.DELAYED):
                fault.arm("rpc.handler.delay", count=1,
                          delay_ms=self.DELAY_MS)
                _pump(stub, 1, msg="delayed")
                _pump(stub, 9)

        head_records = self._run_server(
            tmp_path / "head", EchoServiceImpl(), head_calls)
        head_delayed = self._delayed_of(head_records)
        # Binomial(10, 0.1): P(>= 7 kept) ~ 1e-5 — head sampling cannot
        # reliably recall the delayed tail
        assert len(head_delayed) <= 6
        assert recall > len(head_delayed) / self.DELAYED
