"""The record -> replay -> diff loop (PR 7 tentpole).

* v2 dump format — begin-at-dispatch/commit-at-settle records carrying
  arrival timestamps, trace ids and the server span's settled phase
  timeline; v1 files still load; rotation and truncated tails tolerated;
* both dispatch paths sample — the generic pipeline over TCP and the
  fast path (exercised against a fake dataplane, since the native engine
  is absent in CI);
* the /dump builtin view and ``rpc_view --dump`` renderer;
* the diff engine — which PHASE moved, gated on relative AND absolute
  thresholds so clean replays stay quiet;
* rpc_replay's open-loop pacing and trace tagging;
* the deterministic end-to-end over tpu://: record a scenario, replay it
  at 2x through the full client stack, and trace_diff localizes an
  injected handler delay to ``execute_us`` on the right method — and
  flags nothing on a clean replay;
* OTLP span export and the stitched /rpcz trace tree.
"""

import json
import os
import struct
import time

import pytest

from brpc_tpu import fault
from brpc_tpu import flags as _flags
from brpc_tpu.proto import echo_pb2, rpc_meta_pb2
from brpc_tpu.rpc import (
    Channel,
    ChannelOptions,
    Server,
    ServerOptions,
    Service,
    Stub,
)
from brpc_tpu.trace import diff as _diff
from brpc_tpu.trace import span as _span
from brpc_tpu.trace.rpc_dump import (
    MAGIC_V2,
    RpcDumper,
    RpcDumpLoader,
    pack_record,
)

ECHO = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]


class EchoImpl(Service):
    DESCRIPTOR = ECHO

    def Echo(self, cntl, request, done):
        return echo_pb2.EchoResponse(message=request.message,
                                     payload=request.payload)


@pytest.fixture()
def traced():
    """Span + dump sampling wide open, span DB clean."""
    from brpc_tpu.metrics.collector import global_collector

    _flags.set_flag("rpcz_sample_ratio", "1.0")
    _flags.set_flag("collector_max_samples_per_second", "0")
    global_collector()._deny_until = 0.0
    _span.reset_for_test()
    yield
    _flags.set_flag("collector_max_samples_per_second", "1000")
    _flags.set_flag("rpc_dump_ratio", "0.0")


def _mk_meta(service="EchoService", method="Echo", trace_id=0, span_id=0,
             log_id=0, timeout_ms=0):
    meta = rpc_meta_pb2.RpcMeta()
    meta.request.service_name = service
    meta.request.method_name = method
    meta.request.trace_id = trace_id
    meta.request.span_id = span_id
    meta.request.log_id = log_id
    meta.request.timeout_ms = timeout_ms
    return meta


def _mk_span(phases, latency_us=1000.0, trace_id=1, span_id=2):
    sp = _span.Span(trace_id, span_id, 0, _span.KIND_SERVER, "S", "M")
    for k, v in phases.items():
        sp.add_phase(k, v)
    sp.end_mono_us = sp.start_mono_us + latency_us  # settle without _db_add
    return sp


def _wait(predicate, timeout=3.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


# ------------------------------------------------------------------ v2 format
class TestV2Format:
    def test_begin_commit_roundtrip(self, tmp_path):
        dumper = RpcDumper(str(tmp_path))
        meta = _mk_meta(trace_id=0xabc, span_id=0xdef, log_id=7,
                        timeout_ms=250)
        pending = dumper.begin(meta, b"wire-bytes")
        assert pending["ts_us"] > 0
        sp = _mk_span({"parse_us": 12.0, "execute_us": 345.6},
                      latency_us=1234.5)
        dumper.commit(pending, sp, error_code=0)
        dumper.close()

        recs = list(RpcDumpLoader(str(tmp_path)))
        assert len(recs) == 1
        rec = recs[0]
        assert rec.version == 2
        assert rec.info["service"] == "EchoService"
        assert rec.info["method"] == "Echo"
        assert rec.info["timeout_ms"] == 250
        assert rec.info["priority"] == 0
        assert rec.info["phases"]["execute_us"] == pytest.approx(345.6)
        assert rec.info["latency_us"] == pytest.approx(1234.5)
        assert rec.trace_id == 0xabc and rec.span_id == 0xdef
        assert rec.ts_us > 0
        assert rec.method_key == "EchoService.Echo"
        # v1-era consumers unpack records as (meta, body) tuples
        m, b = rec
        assert m.request.log_id == 7 and b == b"wire-bytes"

    def test_v1_files_still_load(self, tmp_path):
        p = tmp_path / "requests.0.dump"
        with open(p, "wb") as f:
            f.write(pack_record(_mk_meta(method="Old"), b"v1-body"))
        recs = list(RpcDumpLoader(str(p)))
        assert len(recs) == 1
        assert recs[0].version == 1
        assert recs[0].info == {}
        assert recs[0].ts_us == 0.0
        meta, body = recs[0]
        assert meta.request.method_name == "Old" and body == b"v1-body"

    def test_mixed_version_directory(self, tmp_path):
        with open(tmp_path / "requests.0.dump", "wb") as f:
            f.write(pack_record(_mk_meta(), b"old"))
        dumper = RpcDumper(str(tmp_path))
        # the dumper's own files start at index 0 too — point it elsewhere
        dumper._file_index = 1
        dumper.sample(_mk_meta(), b"new")
        dumper.close()
        recs = list(RpcDumpLoader(str(tmp_path)))
        assert sorted(r.version for r in recs) == [1, 2]

    def test_rotation_at_max_file_bytes(self, tmp_path):
        from brpc_tpu.trace import rpc_dump as _dump

        rot0 = _dump.g_dump_rotations.get_value()
        dumper = RpcDumper(str(tmp_path), max_file_bytes=200)
        for i in range(6):
            dumper.sample(_mk_meta(log_id=i), b"x" * 64)
        dumper.close()
        files = sorted(f for f in os.listdir(tmp_path)
                       if f.endswith(".dump"))
        assert len(files) > 1
        assert _dump.g_dump_rotations.get_value() - rot0 == len(files) - 1
        for f in files:  # every rolled file carries the v2 magic
            assert (tmp_path / f).read_bytes().startswith(MAGIC_V2)
        recs = list(RpcDumpLoader(str(tmp_path)))
        assert sorted(r.meta.request.log_id for r in recs) == list(range(6))

    def test_truncated_tail_v2(self, tmp_path):
        dumper = RpcDumper(str(tmp_path))
        for i in range(3):
            dumper.sample(_mk_meta(log_id=i), b"payload")
        dumper.close()
        p = tmp_path / "requests.0.dump"
        data = p.read_bytes()
        p.write_bytes(data[:-5])  # crash mid-write of the last record
        recs = list(RpcDumpLoader(str(p)))
        assert [r.meta.request.log_id for r in recs] == [0, 1]

    def test_truncated_tail_v1(self, tmp_path):
        p = tmp_path / "requests.0.dump"
        rec = pack_record(_mk_meta(), b"bb")
        with open(p, "wb") as f:
            f.write(rec + rec + struct.pack("!II", 100, 100) + b"short")
        assert len(list(RpcDumpLoader(str(p)))) == 2

    def test_rate_cap_token_bucket(self, tmp_path, traced):
        from brpc_tpu.trace import rpc_dump as _dump

        dumper = RpcDumper(str(tmp_path))
        _flags.set_flag("rpc_dump_ratio", "1.0")
        _flags.set_flag("rpc_dump_max_per_sec", "1")
        try:
            skip0 = _dump.g_dump_skipped.get_value()
            assert dumper.ask_to_be_sampled()  # first token is pre-filled
            assert not dumper.ask_to_be_sampled()  # bucket drained
            assert _dump.g_dump_skipped.get_value() == skip0 + 1
            _flags.set_flag("rpc_dump_max_per_sec", "0")
            assert dumper.ask_to_be_sampled()  # cap off: ratio rules again
        finally:
            _flags.set_flag("rpc_dump_max_per_sec", "0")
            _flags.set_flag("rpc_dump_ratio", "0.0")


# -------------------------------------------------------------- /dump builtin
class _Http:
    def __init__(self, path="/dump", query=None):
        self.path = path
        self.query = query or {}

    def header(self, k, default=""):
        return default


class TestDumpBuiltin:
    def test_view_without_dumper(self):
        from brpc_tpu.builtin.services import dump_service

        status, _ctype, body = dump_service(None, _Http())
        assert status == 200
        assert "no dumper" in body

    def test_view_with_traffic(self, tmp_path, traced):
        from brpc_tpu.builtin.services import dump_service
        from brpc_tpu.policy.http_protocol import http_fetch

        _flags.set_flag("rpc_dump_ratio", "1.0")
        server = (Server(ServerOptions(rpc_dump_dir=str(tmp_path)))
                  .add_service(EchoImpl()).start("127.0.0.1:0"))
        try:
            stub = Stub(Channel().init(str(server.listen_endpoint())), ECHO)
            for i in range(3):
                stub.Echo(echo_pb2.EchoRequest(message=f"d{i}"))
            assert _wait(lambda: server.rpc_dumper.sampled_count >= 3)

            status, _ctype, body = dump_service(
                server, _Http(query={"format": "json"}))
            assert status == 200
            doc = json.loads(body)
            assert doc["rpc_dump_ratio"] == 1.0
            assert doc["dumper"]["per_method"]["EchoService.Echo"] == 3
            assert doc["dumper"]["files"], "dump files listed"

            # and over the server's own HTTP surface
            resp = http_fetch(str(server.listen_endpoint()), "GET", "/dump")
            assert resp.status == 200
            assert b"EchoService.Echo: 3" in resp.body
        finally:
            _flags.set_flag("rpc_dump_ratio", "0.0")
            server.stop()
            server.join(timeout=2)


# ------------------------------------------------------------ dispatch paths
class TestDispatchPathsSample:
    def test_slow_path_records_phases(self, tmp_path, traced):
        _flags.set_flag("rpc_dump_ratio", "1.0")
        server = (Server(ServerOptions(rpc_dump_dir=str(tmp_path)))
                  .add_service(EchoImpl()).start("127.0.0.1:0"))
        try:
            stub = Stub(Channel().init(str(server.listen_endpoint())), ECHO)
            for i in range(3):
                stub.Echo(echo_pb2.EchoRequest(message=f"p{i}"))
            assert _wait(lambda: server.rpc_dumper.sampled_count >= 3)
            server.rpc_dumper.close()
        finally:
            _flags.set_flag("rpc_dump_ratio", "0.0")
            server.stop()
            server.join(timeout=2)
        recs = list(RpcDumpLoader(str(tmp_path)))
        assert len(recs) == 3
        for rec in recs:
            # committed at settle: the full server phase timeline is in
            assert "execute_us" in rec.info["phases"]
            assert "parse_us" in rec.info["phases"]
            assert rec.info["latency_us"] > 0
            assert rec.trace_id != 0  # client tracing was on

    def test_fast_path_records_phases(self, tmp_path, traced):
        """fast_process_request against a fake dataplane: dump sampling
        rides the fast path natively (no slow-lane replay) and the record
        still carries the settled phases."""
        from brpc_tpu.rpc import server_processing as sp_mod

        class _FakeDp:
            def __init__(self):
                self.responses = []

            def respond(self, conn, cid, attempt, code, err, payload,
                        attachment, q, compress_type=0):
                self.responses.append((conn, cid, code, payload))

        class _FakeSock:
            def __init__(self, dp):
                self._dp = dp
                self.conn_id = 17
                self.peer_str = "fake:0"
                self.remote = "fake:0"

        _flags.set_flag("rpc_dump_ratio", "1.0")
        server = (Server(ServerOptions(rpc_dump_dir=str(tmp_path)))
                  .add_service(EchoImpl()).start("127.0.0.1:0"))
        try:
            dp = _FakeDp()
            body = echo_pb2.EchoRequest(message="fast").SerializeToString()
            item = (server, _FakeSock(dp), "EchoService", "Echo",
                    99, 1, 0, 5, 0xfeed, 0xbeef, 0, body, 0.0)
            sp_mod.fast_process_request(item)
            assert dp.responses and dp.responses[0][2] == 0
            server.rpc_dumper.close()
        finally:
            _flags.set_flag("rpc_dump_ratio", "0.0")
            server.stop()
            server.join(timeout=2)
        recs = list(RpcDumpLoader(str(tmp_path)))
        assert len(recs) == 1
        rec = recs[0]
        assert rec.trace_id == 0xfeed
        assert rec.meta.request.span_id == 0xbeef
        assert rec.meta.correlation_id == 99
        assert "execute_us" in rec.info["phases"]
        # raw body survives the round trip for replay
        req = echo_pb2.EchoRequest()
        req.ParseFromString(rec.body)
        assert req.message == "fast"


# ------------------------------------------------------------------ the diff
def _profile(method, n, **phase_us):
    prof = _diff.MethodProfile(method)
    for _ in range(n):
        prof.add(dict(phase_us), sum(phase_us.values()))
    return prof


class TestDiffEngine:
    def test_percentile_nearest_rank(self):
        assert _diff.percentile([], 0.99) == 0.0
        assert _diff.percentile([5.0], 0.5) == 5.0
        vals = list(range(1, 101))
        assert _diff.percentile(vals, 0.99) == 99
        assert _diff.percentile(vals, 1.0) == 100

    def test_flags_the_moved_phase(self):
        base = {"S.M": _profile("S.M", 5, execute_us=1000.0, parse_us=50.0)}
        new = {"S.M": _profile("S.M", 5, execute_us=40000.0, parse_us=50.0)}
        regs = _diff.diff_profiles(base, new)
        assert len(regs) == 1
        r = regs[0]
        assert r.method == "S.M" and r.phase == "execute_us"
        assert "execute p99" in r.describe()
        assert "on S.M" in r.describe()
        assert r.to_dict()["summary"] == r.describe()

    def test_identical_runs_stay_quiet(self):
        base = {"S.M": _profile("S.M", 5, execute_us=1000.0)}
        new = {"S.M": _profile("S.M", 5, execute_us=1000.0)}
        assert _diff.diff_profiles(base, new) == []

    def test_absolute_floor_gates_jitter(self):
        # +150% but only +1.5ms: under the 2ms floor, not a page
        base = {"S.M": _profile("S.M", 5, execute_us=1000.0)}
        new = {"S.M": _profile("S.M", 5, execute_us=2500.0)}
        assert _diff.diff_profiles(base, new) == []
        assert _diff.diff_profiles(base, new, min_delta_us=500.0)

    def test_relative_floor_gates_wide_phases(self):
        # +20ms but only +20%: under the 30% threshold
        base = {"S.M": _profile("S.M", 5, execute_us=100000.0)}
        new = {"S.M": _profile("S.M", 5, execute_us=120000.0)}
        assert _diff.diff_profiles(base, new) == []
        assert _diff.diff_profiles(base, new, threshold=0.1)

    def test_min_samples_and_missing_methods(self):
        base = {"S.M": _profile("S.M", 2, execute_us=100.0)}
        new = {"S.M": _profile("S.M", 2, execute_us=90000.0),
               "S.Other": _profile("S.Other", 9, execute_us=90000.0)}
        assert _diff.diff_profiles(base, new) == []  # n too small / no base

    def test_render_report_marks_regressions(self):
        base = {"S.M": _profile("S.M", 5, execute_us=1000.0)}
        new = {"S.M": _profile("S.M", 5, execute_us=40000.0)}
        regs = _diff.diff_profiles(base, new)
        out = _diff.render_report(base, new, regs)
        assert "<-- REGRESSED" in out
        assert "1 phase regression(s):" in out
        clean = _diff.render_report(base, base, [])
        assert "no phase regressions" in clean

    def test_profiles_from_dump_skips_v1(self, tmp_path):
        with open(tmp_path / "requests.0.dump", "wb") as f:
            f.write(pack_record(_mk_meta(), b"old"))
        dumper = RpcDumper(str(tmp_path))
        dumper._file_index = 1
        dumper.commit(dumper.begin(_mk_meta(), b"new"),
                      _mk_span({"execute_us": 42.0}))
        dumper.close()
        profs = _diff.profiles_from_dump(str(tmp_path))
        assert profs["EchoService.Echo"].count == 1


class TestTraceDiffCLI:
    @staticmethod
    def _spans_json(path, execute_us):
        doc = {"spans": [
            {"kind": "server", "service": "S", "method": "M",
             "phases": {"execute_us": execute_us}, "latency_us": execute_us}
            for _ in range(5)]}
        path.write_text(json.dumps(doc))
        return str(path)

    def test_exit_codes_and_json(self, tmp_path, capsys):
        from tools import trace_diff

        base = self._spans_json(tmp_path / "base.json", 1000.0)
        same = self._spans_json(tmp_path / "same.json", 1100.0)
        bad = self._spans_json(tmp_path / "bad.json", 50000.0)

        assert trace_diff.main([base, same]) == 0
        assert "no phase regressions" in capsys.readouterr().out

        assert trace_diff.main([base, bad, "--json"]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["regressions"][0]["phase"] == "execute_us"
        assert doc["methods_compared"] == ["S.M"]

        assert trace_diff.main([base, str(tmp_path / "nope.json")]) == 2
        assert trace_diff.main([base, same, "--percentile", "0"]) == 2


# -------------------------------------------------------------------- replay
class TestReplayPacing:
    def test_items_sorted_by_arrival_not_commit(self, tmp_path):
        from tools.rpc_replay import load_items

        dumper = RpcDumper(str(tmp_path))
        # commit order 3,1,2 — arrival stamps say 1,2,3
        for log_id, ts in ((3, 3000.0), (1, 1000.0), (2, 2000.0)):
            pending = dumper.begin(_mk_meta(log_id=log_id), b"x")
            pending["ts_us"] = ts * 1000.0  # 1ms apart
            dumper.commit(pending)
        dumper.close()
        items, skipped = load_items(str(tmp_path))
        assert skipped == 0
        assert [i.md.service_name for i in items] == ["EchoService"] * 3
        assert [round(i.offset_s, 3) for i in items] == [0.0, 1.0, 2.0]

    def test_replay_tags_recorded_trace_ids(self, tmp_path, traced):
        from tools import rpc_replay

        _flags.set_flag("rpc_dump_ratio", "1.0")
        server = (Server(ServerOptions(rpc_dump_dir=str(tmp_path)))
                  .add_service(EchoImpl()).start("127.0.0.1:0"))
        try:
            stub = Stub(Channel().init(str(server.listen_endpoint())), ECHO)
            for i in range(3):
                stub.Echo(echo_pb2.EchoRequest(message=f"r{i}"))
            assert _wait(lambda: server.rpc_dumper.sampled_count >= 3)
            server.rpc_dumper.close()
        finally:
            server.stop()
            server.join(timeout=2)
        _flags.set_flag("rpc_dump_ratio", "0.0")
        recorded = {rec.trace_id for rec in RpcDumpLoader(str(tmp_path))}
        assert len(recorded) == 3

        _span.reset_for_test()
        server2 = Server().add_service(EchoImpl()).start("127.0.0.1:0")
        try:
            rc = rpc_replay.main([
                "--dump", str(tmp_path),
                "--server", str(server2.listen_endpoint()),
                "--report-interval", "0"])
            assert rc == 0
            assert _wait(lambda: len([s for s in _span.recent_spans(50)
                                      if s.kind == _span.KIND_SERVER]) >= 3)
        finally:
            server2.stop()
            server2.join(timeout=2)
        spans = _span.recent_spans(50)
        # replayed server spans land under the SAME trace ids as recorded
        srv = [s for s in spans if s.kind == _span.KIND_SERVER]
        assert {s.trace_id for s in srv} == recorded
        # the replay client spans carry the replay annotation and hang
        # under the recorded client span
        cli = [s for s in spans if s.kind == _span.KIND_CLIENT]
        assert cli and all(
            any("replay pass=1" in t for _, t in s.annotations)
            for s in cli)
        assert all(s.parent_span_id for s in cli)


# --------------------------------------------------- the deterministic loop
class TestRecordReplayDiffE2E:
    def _record(self, dump_dir, n=8):
        _flags.set_flag("rpc_dump_ratio", "1.0")
        server = (Server(ServerOptions(rpc_dump_dir=str(dump_dir)))
                  .add_service(EchoImpl()).start("tpu://127.0.0.1:0/0"))
        try:
            ch = Channel(ChannelOptions(protocol="trpc_std",
                                        timeout_ms=10000))
            ch.init(str(server.listen_endpoint()))
            stub = Stub(ch, ECHO)
            for i in range(n):
                stub.Echo(echo_pb2.EchoRequest(message=f"rec{i}"))
            assert _wait(lambda: server.rpc_dumper.sampled_count >= n)
            server.rpc_dumper.close()
        finally:
            _flags.set_flag("rpc_dump_ratio", "0.0")
            server.stop()
            server.join(timeout=2)

    def _replay_2x(self, dump_dir, server):
        from tools import rpc_replay

        rc = rpc_replay.main([
            "--dump", str(dump_dir),
            "--server", str(server.listen_endpoint()),
            "--rate-mult", "2", "--timeout-ms", "10000",
            "--report-interval", "0"])
        assert rc == 0

    def _server_profiles(self, n):
        assert _wait(lambda: len([s for s in _span.recent_spans(100)
                                  if s.kind == _span.KIND_SERVER]) >= n)
        return _diff.profiles_from_spans(
            [s.to_dict() for s in _span.recent_spans(100)], "server")

    # p50 with a 10ms floor: immune to single-sample scheduler hiccups on
    # a loaded CI box, while the injected 30ms stall (shifting the whole
    # distribution) still clears the floor 3x over
    _GATES = dict(q=0.5, min_delta_us=10_000.0)

    def test_diff_localizes_injected_fault_over_tpu(self, tmp_path, traced):
        """Record over tpu://, replay at 2x through the full client stack:
        a clean replay diffs quiet; with rpc.handler.delay armed the diff
        names execute_us on the faulted method — and nothing else."""
        self._record(tmp_path, n=8)
        base = _diff.profiles_from_dump(str(tmp_path))
        assert base["EchoService.Echo"].count == 8

        server = (Server().add_service(EchoImpl())
                  .start("tpu://127.0.0.1:0/0"))
        try:
            # clean replay: no regression may be flagged
            _span.reset_for_test()
            self._replay_2x(tmp_path, server)
            clean = self._server_profiles(8)
            assert _diff.diff_profiles(base, clean, **self._GATES) == []

            # faulted replay: 30ms handler stall on Echo only
            _span.reset_for_test()
            _flags.set_flag("fault_injection_enabled", "true")
            fault.arm("rpc.handler.delay", mode="always",
                      match={"method": "Echo"}, delay_ms=30)
            try:
                self._replay_2x(tmp_path, server)
            finally:
                fault.disarm("rpc.handler.delay")
                _flags.set_flag("fault_injection_enabled", "false")
            faulted = self._server_profiles(8)
            regs = _diff.diff_profiles(base, faulted, **self._GATES)
            assert regs, "injected 30ms stall must be flagged"
            assert regs[0].method == "EchoService.Echo"
            assert regs[0].phase == "execute_us"
            assert regs[0].new_us - regs[0].base_us > 20000
            assert all(r.phase == "execute_us" for r in regs)
        finally:
            server.stop()
            server.join(timeout=2)


# ----------------------------------------------------------- rpc_view --dump
class TestRpcViewDump:
    def test_renders_dump_summary(self, tmp_path, capsys):
        from tools import rpc_view

        with open(tmp_path / "requests.9.dump", "wb") as f:
            f.write(pack_record(_mk_meta(method="Legacy"), b"v1"))
        dumper = RpcDumper(str(tmp_path))
        for _ in range(2):
            dumper.commit(dumper.begin(_mk_meta(), b"bodybytes"),
                          _mk_span({"execute_us": 10.0}))
        dumper.close()

        assert rpc_view.main(["--dump", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "records: 3 (v1/v2; 2 with phase timelines)" in out
        assert "EchoService.Echo" in out and "EchoService.Legacy" in out

    def test_requires_server_or_dump(self, capsys):
        from tools import rpc_view

        with pytest.raises(SystemExit):
            rpc_view.main([])
        assert "server is required" in capsys.readouterr().err

    def test_missing_path_fails_cleanly(self, tmp_path, capsys):
        from tools import rpc_view

        assert rpc_view.main(["--dump", str(tmp_path / "nope")]) == 1


# --------------------------------------------------------------- OTLP export
class TestOtlpExport:
    def test_span_to_otlp_shape(self):
        from brpc_tpu.trace import export as _export

        sp = _mk_span({"execute_us": 99.5}, latency_us=500.0,
                      trace_id=0x1234, span_id=0x5678)
        sp.parent_span_id = 0x42
        sp.error_code = 7
        d = _export.span_to_otlp(sp)
        assert d["traceId"] == f"{0x1234:032x}"
        assert d["spanId"] == f"{0x5678:016x}"
        assert d["parentSpanId"] == f"{0x42:016x}"
        assert d["kind"] == 2  # server
        assert d["status"]["code"] == 2
        phases = {a["key"]: a["value"] for a in d["attributes"]
                  if a["key"].startswith("phase.")}
        assert phases["phase.execute_us"]["doubleValue"] == 99.5
        assert int(d["endTimeUnixNano"]) - int(d["startTimeUnixNano"]) \
            == 500_000

    def test_export_hook_writes_json_lines(self, tmp_path, traced):
        from brpc_tpu.trace import export as _export

        path = tmp_path / "spans.jsonl"
        _export.reset_for_test()
        _flags.set_flag("span_export_path", str(path))
        try:
            n0 = _export.g_spans_exported.get_value()
            sp = _span.Span(0xaa, 0xbb, 0, _span.KIND_CLIENT, "S", "M")
            sp.add_phase("send_us", 5.0)
            sp.end()  # Span.end drives the export hook
            assert _export.g_spans_exported.get_value() == n0 + 1
        finally:
            _flags.set_flag("span_export_path", "")
            _export.reset_for_test()
        lines = path.read_text().splitlines()
        assert len(lines) == 1
        doc = json.loads(lines[0])
        span = doc["resourceSpans"][0]["scopeSpans"][0]["spans"][0]
        assert span["traceId"] == f"{0xaa:032x}"
        assert span["kind"] == 3  # client

    def test_export_off_by_default(self, traced):
        from brpc_tpu.trace import export as _export

        n0 = _export.g_spans_exported.get_value()
        _span.Span(1, 2, 0, _span.KIND_CLIENT, "S", "M").end()
        assert _export.g_spans_exported.get_value() == n0


# ------------------------------------------------------------- stitched tree
class TestStitchedTree:
    def test_build_span_tree_nests_by_parent(self):
        spans = [
            {"span_id": "aa", "parent_span_id": "00", "kind": "client",
             "start_us": 1.0},
            {"span_id": "bb", "parent_span_id": "aa", "kind": "server",
             "start_us": 2.0},
            {"span_id": "cc", "parent_span_id": "bb", "kind": "client",
             "start_us": 3.0},
        ]
        tree = _span.build_span_tree(spans)
        assert len(tree) == 1
        assert tree[0]["kind"] == "client"
        assert tree[0]["children"][0]["kind"] == "server"
        assert tree[0]["children"][0]["children"][0]["span_id"] == "cc"

    def test_trace_to_dict_carries_tree(self, traced):
        tid = 0x777
        cli = _span.Span(tid, 0x1, 0, _span.KIND_CLIENT, "S", "M")
        srv = _span.Span(tid, 0x2, 0x1, _span.KIND_SERVER, "S", "M")
        srv.end()
        cli.end()
        doc = _span.trace_to_dict(tid)
        assert doc["trace_id"] == f"{tid:016x}"
        assert len(doc["spans"]) == 2
        assert len(doc["tree"]) == 1
        assert doc["tree"][0]["children"][0]["kind"] == "server"

    def test_merge_trace_docs_dedups_across_processes(self):
        cli = {"span_id": "aa", "parent_span_id": "00", "kind": "client",
               "start_us": 1.0}
        srv = {"span_id": "bb", "parent_span_id": "aa", "kind": "server",
               "start_us": 2.0}
        merged = _span.merge_trace_docs([
            {"trace_id": "t1", "spans": [cli]},
            {"trace_id": "t1", "spans": [dict(cli), srv]},  # overlap
        ])
        assert merged["trace_id"] == "t1"
        assert len(merged["spans"]) == 2
        assert merged["tree"][0]["children"][0]["span_id"] == "bb"
