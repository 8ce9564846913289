"""CI smoke lane for bench.py (BENCH_QUICK + BENCH_PHASES=shm).

Runs the benchmark's CPU-only shm-sweep phase end to end in a subprocess —
real client/server process pair over the tpu:// tunnel — and asserts the
contract the perf tooling depends on: a machine-readable headline JSON line
on stdout, and the zero-copy receive counters (borrowed vs copied bytes,
ACK batching ratio) on stderr.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench_run():
    env = dict(os.environ,
               BENCH_QUICK="1",
               BENCH_PHASES="shm",
               BENCH_SKIP_DEVICE="1",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          capture_output=True, text=True, timeout=240,
                          cwd=REPO, env=env)
    assert proc.returncode == 0, \
        f"bench.py failed rc={proc.returncode}:\n{proc.stderr[-2000:]}"
    return proc


def test_headline_json(bench_run):
    lines = [l for l in bench_run.stdout.splitlines()
             if l.startswith("{")]
    # headline + 64B qps + vars series overhead
    assert len(lines) == 3, bench_run.stdout
    headline = json.loads(lines[0])
    assert headline["metric"] == "echo_1mb_framework_bandwidth"
    assert headline["unit"] == "GB/s"
    assert headline["value"] > 0, headline


def test_small_message_qps_json(bench_run):
    """The shm sweep must emit the 64B small-message summary line."""
    rows = [json.loads(l) for l in bench_run.stdout.splitlines()
            if l.startswith("{")]
    small = [r for r in rows if r["metric"] == "echo_64b_qps"]
    assert len(small) == 1, bench_run.stdout
    assert small[0]["unit"] == "qps"
    assert small[0]["value"] > 0, small[0]
    assert small[0]["vs_baseline"] > 0, small[0]


def test_vars_series_overhead_metric(bench_run):
    """The shm sweep must emit the series-ring overhead metric, and one
    ring sweep must stay far inside the sampler's 1s tick budget."""
    rows = [json.loads(l) for l in bench_run.stdout.splitlines()
            if l.startswith("{")]
    m = [r for r in rows if r["metric"] == "vars_series_overhead_pct"]
    assert len(m) == 1, bench_run.stdout
    assert m[0]["unit"] == "%"
    assert 0 <= m[0]["value"] < 2.0, m[0]


def test_method_qps_series_nonempty_after_sweep(bench_run):
    """By the end of the shm sweep the bench server's per-method qps var
    must have accumulated live 1-second series samples (the sampler
    daemon sweeps rings while traffic flows)."""
    lines = [l for l in bench_run.stderr.splitlines()
             if l.startswith(
                 "# vars series rpc_method_echoservice_echo_qps")]
    assert lines, bench_run.stderr[-2000:]
    line = lines[0]
    count = int(line.split("count=")[1].split(" ")[0])
    nonzero = int(line.split("nonzero_1s=")[1].split(" ")[0])
    assert count >= 1, line
    assert nonzero >= 1, line


def test_rtc_lane_activates_on_shm_sweep(bench_run):
    """The run-to-completion lane must engage for the sweep's small
    echoes: the bench server's exit report shows inline hits on Echo."""
    rtc = [l for l in bench_run.stderr.splitlines()
           if l.startswith("# rtc ")]
    assert rtc, bench_run.stderr[-2000:]
    line = rtc[0]
    assert "EchoService.Echo" in line, line
    hits = int(line.split("EchoService.Echo:hits=")[1].split(",")[0])
    assert hits > 0, line
    assert "demoted=0" in line, line


def test_only_shm_phase_ran(bench_run):
    err = bench_run.stderr
    assert "# tpu:// sweep" in err
    # the skipped phases must not have produced their reports
    assert "# multi_threaded_echo" not in err
    assert "# hybrid lane" not in err
    assert "# device lane" not in err


@pytest.fixture(scope="module")
def batch_bench_run():
    env = dict(os.environ,
               BENCH_QUICK="1",
               BENCH_PHASES="batch",
               BENCH_SKIP_DEVICE="1",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO, env=env)
    assert proc.returncode == 0, \
        f"bench.py failed rc={proc.returncode}:\n{proc.stderr[-2000:]}"
    return proc


def test_batch_lane_report(batch_bench_run):
    lanes = [l for l in batch_bench_run.stderr.splitlines()
             if l.startswith("# batch lane (")]
    assert len(lanes) == 1, batch_bench_run.stderr
    line = lanes[0]
    assert "per-request qps=" in line and "batched qps=" in line, line
    ratio = float(line.split("batched/per-request = ")[1].split("x")[0])
    # the acceptance floor: coalesced dispatch amortizes per-call jit
    # dispatch + interpreter overhead across the batch
    assert ratio >= 2.0, line
    assert "OK 2x floor" in line, line


def test_batch_lane_vars_counters(batch_bench_run):
    err = batch_bench_run.stderr
    for var in ("g_batch_size", "g_batch_queue_delay_us"):
        lines = [l for l in err.splitlines()
                 if l.startswith(f"# batch lane /vars: {var}")]
        assert lines, f"missing {var} in:\n{err[-2000:]}"
        # a live average: "name : avg (count=N)" with N > 0
        assert "(count=" in lines[0], lines[0]
        count = int(lines[0].split("(count=")[1].split(")")[0])
        assert count > 0, lines[0]


def test_batch_phase_skips_others(batch_bench_run):
    err = batch_bench_run.stderr
    assert "# tpu:// sweep" not in err
    assert "# multi_threaded_echo" not in err
    assert "# device lane" not in err


def test_zero_copy_counters_emitted(bench_run):
    err = bench_run.stderr
    zc = [l for l in err.splitlines()
          if l.startswith("# tpu:// zero-copy receive")]
    assert zc, err
    from brpc_tpu.butil.iobuf import supports_block_ownership

    if not supports_block_ownership():
        return  # degraded environment: counters exist but all-copied
    assert "borrowed=" in zc[0] and "copied=" in zc[0], zc[0]
    borrowed = int(zc[0].split("borrowed=")[1].split("B")[0].replace(",", ""))
    assert borrowed > 0, zc[0]
    assert any(l.startswith("# tpu:// ack batching") for l in err.splitlines())


def test_shrunken_window_peak_report(bench_run):
    """The streaming-parse sweep lane: bench_tpu_sweep reports (and guards)
    peak borrowed-outstanding against the shrunken 64-block window."""
    err = bench_run.stderr
    peaks = [l for l in err.splitlines()
             if l.startswith("# tpu:// borrowed peak:")]
    assert peaks, err[-2000:]
    line = peaks[0]
    peak = int(line.split("borrowed peak:")[1].split("blocks")[0])
    window = int(line.split("(window")[1].split(")")[0])
    assert window == 64, line
    from brpc_tpu.butil.iobuf import supports_block_ownership

    if supports_block_ownership():
        # the whole point of streaming claims: the footprint never
        # approaches the window even with 16MB messages in the sweep
        assert peak < window, line


def test_tunnel_counters_on_vars(bench_run):
    """The zero-copy counters must be queryable through the /vars surface
    (expose registry), not just printed by bench.py."""
    from brpc_tpu.metrics.variable import get_exposed
    from brpc_tpu.tpu import transport  # noqa: F401  (registers on import)

    for name in ("g_tunnel_borrowed_bytes", "g_tunnel_copied_bytes",
                 "g_tunnel_borrowed_peak_blocks"):
        assert get_exposed(name) is not None, name


@pytest.fixture(scope="module")
def profile_bench_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("prof") / "bench.folded"
    env = dict(os.environ,
               BENCH_QUICK="1",
               BENCH_PROFILE_OUT=str(out),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                           "--profile"],
                          capture_output=True, text=True, timeout=240,
                          cwd=REPO, env=env)
    assert proc.returncode == 0, \
        f"bench.py --profile failed rc={proc.returncode}:\n" \
        f"{proc.stderr[-2000:]}"
    return proc, out


def test_profile_folded_artifact(profile_bench_run):
    """--profile must leave a non-empty folded-stacks artifact the flame
    and diff tools can consume."""
    proc, out = profile_bench_run
    text = out.read_text()
    stacks = [l for l in text.splitlines()
              if l and not l.startswith("#")]
    assert stacks, text[:500]
    for line in stacks:
        stack, _, weight = line.rpartition(" ")
        assert int(weight) > 0, line
        assert stack.startswith("role="), line
        assert ";phase=" in stack, line


def test_profile_budget_table_and_ratio(profile_bench_run):
    """The per-call CPU budget table must print per-phase us/call rows and
    an attributed-vs-measured sum within the +-25% acceptance band."""
    proc, _ = profile_bench_run
    err = proc.stderr
    assert "# per-call CPU budget by phase" in err
    phase_rows = [l for l in err.splitlines()
                  if l.startswith("#   ") and "us/call" in l]
    assert len(phase_rows) >= 2, err[-2000:]
    budget = [l for l in err.splitlines()
              if l.startswith("# profile budget:")]
    assert budget, err[-2000:]
    ratio = float(budget[0].split("ratio=")[1])
    assert 0.75 <= ratio <= 1.25, budget[0]
    # and the machine-readable line on stdout agrees
    rows = [json.loads(l) for l in proc.stdout.splitlines()
            if l.startswith("{")]
    metric = [r for r in rows
              if r["metric"] == "profile_attributed_cpu_ratio"]
    assert len(metric) == 1, proc.stdout
    assert 0.75 <= metric[0]["value"] <= 1.25, metric[0]


def test_sampler_overhead_under_two_pct_at_default_hz():
    """The always-on rate must be affordable: sampling a live 64B echo
    lane at the default continuous hz costs <2% of wall time — with a live
    serving engine folded in, so the guard also prices the engine's
    registered step-loop thread and the g_serving_* series rings."""
    import time

    from brpc_tpu import flags as _flags
    from brpc_tpu.profiling.sampler import ProfileSession
    from brpc_tpu.proto import echo_pb2
    from brpc_tpu.rpc import Channel, ChannelOptions, Server, Service, Stub
    from test_serving import _stub_engine

    ECHO = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]

    class EchoImpl(Service):
        DESCRIPTOR = ECHO

        def Echo(self, cntl, request, done):
            return echo_pb2.EchoResponse(message=request.message,
                                         payload=request.payload)

    hz = float(_flags.get("tpu_prof_continuous_hz"))
    assert hz > 0
    # the guard must cover the series plane: Server.start installs the
    # ring sweep on the same 1s sampler daemon the guard exercises
    from brpc_tpu.metrics.series import global_series

    assert _flags.get("var_series_enabled")
    ticks_before = global_series().ticks
    srv = Server().add_service(EchoImpl()).start("tpu://127.0.0.1:0/0")
    engine = _stub_engine(step_s=0.002)
    try:
        # decode activity spanning the whole sampled window: the engine's
        # "serving" thread is profiler-registered, so its stacks are in
        # every tick the guard prices
        for _ in range(3):
            assert engine.submit(engine.model.synth_prompt(4), 500)[0] == 0
        ch = Channel(ChannelOptions(protocol="trpc_std", timeout_ms=10000))
        ch.init(str(srv.listen_endpoint()))
        stub = Stub(ch, ECHO)
        req = echo_pb2.EchoRequest(message="x", payload=b"\xab" * 64)
        stub.Echo(req)  # warmup
        sess = ProfileSession(hz=hz, budget=False).start()
        t0 = time.monotonic()
        deadline = t0 + 1.5
        while time.monotonic() < deadline:
            stub.Echo(req)
        wall = time.monotonic() - t0
        prof = sess.stop()
        assert engine.steps > 0, "serving engine never stepped in-window"
    finally:
        srv.stop()
        srv.join(timeout=2)
        engine.stop()
    overhead = prof.sample_time_s / wall
    assert overhead < 0.02, (
        f"sampler self-time {overhead:.2%} of wall at {hz:g}hz "
        f"({prof.ticks} ticks, sample_time={prof.sample_time_s:.4f}s)")
    # the series sweep ran during the window and its own cost stays far
    # inside the 1s tick budget (same <2% bar as the profiler)
    series = global_series()
    assert series.ticks > ticks_before, "series rings never ticked"
    avg_tick = series.total_tick_s / max(series.ticks, 1)
    assert avg_tick < 0.02, (
        f"series ring sweep averages {avg_tick * 1e3:.2f}ms per 1s tick")


def test_record_replay_diff_smoke(tmp_path):
    """The record -> replay -> diff loop on the shm lane, end to end
    through the CLI tools: ~2s of recorded echo traffic over tpu://, a 2x
    open-loop replay via tools/rpc_replay, and tools/trace_diff comparing
    the recorded phase timelines against the replayed ones — exit 0, no
    regression flagged on an unchanged server."""
    import json as _json
    import time

    from brpc_tpu import flags as _flags
    from brpc_tpu.metrics.collector import global_collector
    from brpc_tpu.proto import echo_pb2
    from brpc_tpu.rpc import (Channel, ChannelOptions, Server,
                              ServerOptions, Service, Stub)
    from brpc_tpu.trace import span as _span
    from tools import rpc_replay, trace_diff

    ECHO = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]

    class EchoImpl(Service):
        DESCRIPTOR = ECHO

        def Echo(self, cntl, request, done):
            return echo_pb2.EchoResponse(message=request.message)

    record_dir = tmp_path / "dumps"
    _flags.set_flag("rpcz_sample_ratio", "1.0")
    _flags.set_flag("rpc_dump_ratio", "1.0")
    _flags.set_flag("collector_max_samples_per_second", "0")
    global_collector()._deny_until = 0.0
    _span.reset_for_test()
    try:
        server = (Server(ServerOptions(rpc_dump_dir=str(record_dir)))
                  .add_service(EchoImpl()).start("tpu://127.0.0.1:0/0"))
        try:
            ch = Channel(ChannelOptions(protocol="trpc_std",
                                        timeout_ms=10000))
            ch.init(str(server.listen_endpoint()))
            stub = Stub(ch, ECHO)
            deadline = time.monotonic() + 2.0
            sent = 0
            while time.monotonic() < deadline and sent < 60:
                stub.Echo(echo_pb2.EchoRequest(message=f"s{sent}"))
                sent += 1
                time.sleep(0.01)  # real inter-arrival gaps to halve
            t = time.monotonic() + 2.0
            while (server.rpc_dumper.sampled_count < sent
                   and time.monotonic() < t):
                time.sleep(0.01)
            assert server.rpc_dumper.sampled_count >= sent
            server.rpc_dumper.close()
        finally:
            server.stop()
            server.join(timeout=2)
        _flags.set_flag("rpc_dump_ratio", "0.0")

        _span.reset_for_test()
        server2 = Server().add_service(EchoImpl()).start("tpu://127.0.0.1:0/0")
        try:
            t0 = time.monotonic()
            rc = rpc_replay.main([
                "--dump", str(record_dir),
                "--server", str(server2.listen_endpoint()),
                "--rate-mult", "2", "--timeout-ms", "10000",
                "--report-interval", "0"])
            replay_s = time.monotonic() - t0
            assert rc == 0
            # 2x rate-mult: the ~1.5s+ recorded schedule replays in ~half
            assert replay_s < 1.5, f"2x replay took {replay_s:.2f}s"
            t = time.monotonic() + 2.0
            while (len([s for s in _span.recent_spans(200)
                        if s.kind == _span.KIND_SERVER]) < sent
                   and time.monotonic() < t):
                time.sleep(0.01)
        finally:
            server2.stop()
            server2.join(timeout=2)
        replayed = tmp_path / "replayed.json"
        replayed.write_text(_json.dumps({"spans": [
            s.to_dict() for s in _span.recent_spans(200)]}))
        # p50 + 10ms floor: quiet on an unchanged server even on a noisy box
        rc = trace_diff.main([str(record_dir), str(replayed),
                              "--percentile", "50",
                              "--min-delta-us", "10000"])
        assert rc == 0
    finally:
        _flags.set_flag("rpc_dump_ratio", "0.0")
        _flags.set_flag("collector_max_samples_per_second", "1000")


BASELINE_FOLDED = os.path.join(REPO, "tests", "data",
                               "bench_profile_baseline.folded")


def test_per_phase_cpu_ratchet_vs_baseline(profile_bench_run, capsys):
    """The committed folded baseline gates per-phase CPU share: a live
    --profile run must not move any phase=* synthetic root frame by more
    than 5 percentage points of whole-process samples (measured run-to-run
    noise on this lane is <1pp; a phase whose per-call CPU blows up shows
    here with the phase named)."""
    from tools import prof_diff

    _, out = profile_bench_run
    rc = prof_diff.main([BASELINE_FOLDED, str(out), "--total",
                         "--only-prefix", "phase=",
                         "--fail-above-pct", "5"])
    captured = capsys.readouterr()
    assert rc == 0, f"per-phase CPU ratchet tripped:\n{captured.out}"


def test_per_phase_ratchet_names_moved_phase(tmp_path, capsys):
    """Sensitivity check, no live run needed: inflate the baseline's
    phase=parse stacks 9x and the ratchet must exit 1 with the moved
    phase ranked as the top mover."""
    from tools import prof_diff

    doctored = []
    for line in open(BASELINE_FOLDED, encoding="utf-8"):
        stack, _, weight = line.rstrip("\n").rpartition(" ")
        if ";phase=parse;" in stack:
            weight = str(int(weight) * 9)
        doctored.append(f"{stack} {weight}")
    bad = tmp_path / "doctored.folded"
    bad.write_text("\n".join(doctored) + "\n")
    rc = prof_diff.main([BASELINE_FOLDED, str(bad), "--total",
                         "--only-prefix", "phase=",
                         "--fail-above-pct", "5", "--json"])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert report["movers"], report
    assert report["movers"][0]["frame"] == "phase=parse", report["movers"]
    assert report["movers"][0]["delta_pct"] > 5, report["movers"][0]
    # the filter keeps the ratchet to the synthetic phase frames only
    assert all(m["frame"].startswith("phase=") for m in report["movers"])
