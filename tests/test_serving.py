"""Serving plane: paged KV cache, iteration-level scheduler, RPC surface.

Four layers, cheapest first:

* the KV block manager as a pure ledger — alloc/free/refcount/fork,
  watermark admission, the BRPC_TPU_CHECK-style audits catching a
  corrupted ledger;
* the scheduler against a stub model (no device programs, no compiles) —
  admission policy, static-vs-continuous refill, deadline expiry in the
  queue, and the chaos points (socket death mid-generation, forced KV
  exhaustion, decode stalls) proving every abort path returns all blocks;
* the real tiny transformer through the engine — greedy determinism,
  TTFT strictly inside full-generation latency, a short request
  overtaking a long one (the continuous-batching headline behavior);
* the RPC surface — Generate with and without streaming, TokenDelta
  frames matching the final response, and the committed rpc_dump corpus
  replayed against a fresh server with trace_diff gating the phase
  timelines (prefill_us/decode_us).
"""

import json
import os
import threading
import time
import types

import numpy as np
import pytest

from brpc_tpu import fault
from brpc_tpu import flags as _flags
from brpc_tpu.rpc import errors
from brpc_tpu.serving import (
    EngineConfig,
    KVCacheConfig,
    LlmServingService,
    ModelConfig,
    PagedKVCache,
    ServingEngine,
    TinyTransformer,
)
from brpc_tpu.serving.kv_cache import KVCacheFull

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = os.path.join(REPO, "tests", "data", "serving_corpus")


def _small_kv(num_blocks=16, block_size=8, watermark=0.9, layers=1,
              kv_dim=8, check=True):
    kv = PagedKVCache(KVCacheConfig(block_size=block_size,
                                    num_blocks=num_blocks,
                                    watermark=watermark),
                      layers, kv_dim)
    kv._check = check  # audit every alloc/free like BRPC_TPU_CHECK=1
    return kv


# ---------------------------------------------------------------- KV ledger
class TestKVCache:
    def test_alloc_free_roundtrip(self):
        kv = _small_kv()
        table = kv.alloc_sequence(1, 20)  # 3 blocks at block_size 8
        assert len(table) == 3
        assert kv.used_blocks == 3 and kv.free_blocks == 13
        assert 0 not in table  # block 0 is the pad-scatter scratch block
        assert kv.free_sequence(1) == 3
        assert kv.used_blocks == 0
        kv.assert_idle("after roundtrip")

    def test_extend_grows_only_the_tail(self):
        kv = _small_kv()
        t0 = kv.alloc_sequence(7, 8)  # exactly one block
        t1 = kv.extend_sequence(7, 9)  # crosses into a second block
        assert t1[: len(t0)] == t0 and len(t1) == 2
        assert kv.extend_sequence(7, 16) == t1  # still fits, no growth
        kv.free_sequence(7)
        kv.assert_idle()

    def test_fork_shares_blocks_by_refcount(self):
        kv = _small_kv()
        src = kv.alloc_sequence(1, 24)
        dst = kv.fork_sequence(1, 2)
        assert dst == src
        assert kv.used_blocks == 3  # shared, not copied
        assert kv.free_sequence(1) == 0  # dst still holds every block
        assert kv.used_blocks == 3
        assert kv.free_sequence(2) == 3
        kv.assert_idle("after fork teardown")

    def test_watermark_keeps_decode_headroom(self):
        kv = _small_kv(num_blocks=8, watermark=0.5)  # admit limit: 4 blocks
        assert kv.can_admit(32)  # 4 blocks, exactly at the watermark
        assert not kv.can_admit(33)  # 5 blocks would eat decode headroom
        kv.alloc_sequence(1, 24)  # 3 used
        assert kv.can_admit(8) and not kv.can_admit(9)
        # but a RUNNING sequence may still grow into the slack above it
        kv.extend_sequence(1, 8 * 6)
        assert kv.used_blocks == 6
        kv.free_sequence(1)
        kv.assert_idle()

    def test_exhaustion_raises_kv_cache_full(self):
        kv = _small_kv(num_blocks=4, watermark=1.0)
        kv.alloc_sequence(1, 8 * 3)
        with pytest.raises(KVCacheFull):
            kv.alloc_sequence(2, 8 * 2)
        before = kv.snapshot()
        assert before["blocks_used"] == 3  # failed alloc took nothing
        kv.free_sequence(1)
        kv.assert_idle()

    def test_audit_catches_a_corrupted_ledger(self):
        kv = _small_kv()
        kv.alloc_sequence(1, 8)
        kv._ref[kv._tables[1][0]] += 1  # corrupt: ref without a table
        with pytest.raises(AssertionError, match="ledger violation"):
            kv.extend_sequence(1, 9)

    def test_assert_idle_names_the_leak(self):
        kv = _small_kv()
        kv.alloc_sequence(3, 8 * 2)
        with pytest.raises(AssertionError, match="leaked"):
            kv.assert_idle("leak probe")
        kv.free_sequence(3)
        kv.assert_idle()


# ------------------------------------------------------- scheduler (stubbed)
class _StubModel:
    """Pure-Python stand-in: the engine's scheduling is model-agnostic, so
    admission/abort paths are testable without compiling device programs."""

    def __init__(self, step_s=0.0):
        self.config = types.SimpleNamespace(max_context=4096)
        self.step_s = step_s
        self.prefills = 0

    def synth_prompt(self, n):
        return np.arange(1, n + 1, dtype=np.int32)

    def prefill(self, prompt, table):
        self.prefills += 1
        if self.step_s:
            time.sleep(self.step_s)
        return 1

    def decode_step(self, tokens, positions, tables):
        if self.step_s:
            time.sleep(self.step_s)
        return np.full(len(tables), 2, dtype=np.int32)


class _Cntl:
    """Just enough controller for the engine's getattr probes."""

    def __init__(self, deadline_mono=0.0):
        self.deadline_mono = deadline_mono
        self._srv_socket = types.SimpleNamespace(failed=False)
        self.code = 0
        self.text = ""

    def set_failed(self, code, text):
        self.code, self.text = code, text


def _stub_engine(step_s=0.0, start=True, **cfg):
    kv = _small_kv(num_blocks=cfg.pop("num_blocks", 32),
                   watermark=cfg.pop("watermark", 0.9))
    cfg.setdefault("idle_wait_s", 0.005)
    eng = ServingEngine(_StubModel(step_s), kv, EngineConfig(**cfg))
    if start:
        eng.start()
    return eng


def _submit_wait(engine, plen, max_new, cntl=None, timeout=30.0):
    ev = threading.Event()
    box = []

    def done(resp):
        box.append(resp)
        ev.set()

    code, _ = engine.submit(engine.model.synth_prompt(plen), max_new,
                            cntl=cntl, done=done)
    assert code == 0, errors.error_text(code)
    assert ev.wait(timeout), "generation never completed"
    return box[0]


class TestScheduling:
    def test_queue_cap_rejects_overcrowded(self):
        eng = _stub_engine(start=False, max_queue=2)
        eng.running = True  # accept submits without the step loop draining
        try:
            for _ in range(2):
                code, _ = eng.submit(eng.model.synth_prompt(4), 2)
                assert code == 0
            code, seq = eng.submit(eng.model.synth_prompt(4), 2)
            assert code == errors.EOVERCROWDED and seq is None
        finally:
            eng.running = False
            eng._abort_all_locked_out(errors.ELOGOFF, "test teardown")
            eng.kv.assert_idle("queue-cap teardown")

    def test_deadline_spent_rejected_at_admission(self):
        eng = _stub_engine(start=False)
        eng.running = True
        try:
            code, _ = eng.submit(eng.model.synth_prompt(4), 2,
                                 cntl=_Cntl(time.monotonic() - 0.1))
            assert code == errors.ERPCTIMEDOUT
        finally:
            eng.running = False

    def test_watermark_rejects_before_queueing(self):
        # 8 blocks * 0.5 watermark = 4-block admit limit; 5 blocks asked
        eng = _stub_engine(start=False, num_blocks=8, watermark=0.5)
        eng.running = True
        try:
            rejects0 = eng.kv.used_blocks
            code, _ = eng.submit(eng.model.synth_prompt(8 * 4 + 1), 2)
            assert code == errors.EOVERCROWDED
            assert eng.kv.used_blocks == rejects0  # nothing was allocated
        finally:
            eng.running = False

    def test_continuous_refills_between_steps(self):
        eng = _stub_engine(start=False, max_batch=4)
        eng.running = True
        assert eng.submit(eng.model.synth_prompt(4), 2)[0] == 0
        with eng._cv:
            assert len(eng._admit_locked()) == 1
        assert eng.submit(eng.model.synth_prompt(4), 2)[0] == 0
        with eng._cv:
            admitted = eng._admit_locked()  # running non-empty, still admits
        assert len(admitted) == 1
        eng.running = False
        eng._abort_all_locked_out(errors.ELOGOFF, "test teardown")
        eng.kv.assert_idle("continuous teardown")

    def test_expired_deadline_in_queue_finishes_timedout(self):
        eng = _stub_engine(start=False)
        eng.running = True
        cntl = _Cntl(time.monotonic() + 0.01)
        ev = threading.Event()
        code, _ = eng.submit(eng.model.synth_prompt(4), 2, cntl=cntl,
                             done=lambda r: ev.set())
        assert code == 0
        time.sleep(0.03)  # let the queued deadline expire
        with eng._cv:
            assert eng._admit_locked() == []
        assert ev.wait(1.0)
        assert cntl.code == errors.ERPCTIMEDOUT
        eng.running = False
        eng.kv.assert_idle("deadline teardown")

    def test_stop_aborts_in_flight_and_pool_is_whole(self):
        eng = _stub_engine(step_s=0.01)
        cntl = _Cntl()
        ev = threading.Event()
        code, seq = eng.submit(eng.model.synth_prompt(4), 1000, cntl=cntl,
                               done=lambda r: ev.set())
        assert code == 0
        deadline = time.monotonic() + 5.0
        while not seq.out_tokens and time.monotonic() < deadline:
            time.sleep(0.005)
        assert seq.out_tokens, "generation never started"
        eng.stop()
        assert ev.wait(5.0)
        assert cntl.code == errors.ELOGOFF
        eng.kv.assert_idle("stop teardown")


# ------------------------------------------------------------------- chaos
@pytest.fixture()
def fault_enabled():
    _flags.set_flag("fault_injection_enabled", True)
    yield
    fault.disarm_all()
    _flags.set_flag("fault_injection_enabled", False)


@pytest.mark.chaos
class TestServingChaos:
    def test_socket_death_mid_generation_frees_every_block(self):
        """The tunnel-kill contract: a connection that dies mid-generation
        aborts the sequence with a retriable EFAILEDSOCKET and every KV
        block returns to the pool."""
        eng = _stub_engine(step_s=0.005)
        try:
            cntl = _Cntl()
            ev = threading.Event()
            box = []

            def done(resp):
                box.append(resp)
                ev.set()

            code, seq = eng.submit(eng.model.synth_prompt(4), 1000,
                                   cntl=cntl, done=done)
            assert code == 0
            deadline = time.monotonic() + 5.0
            while len(seq.out_tokens) < 3 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert len(seq.out_tokens) >= 3, "generation never got going"
            cntl._srv_socket.failed = True  # the tunnel dies here
            assert ev.wait(5.0), "abort never reached the done callback"
            assert box == [None]
            assert cntl.code == errors.EFAILEDSOCKET
            deadline = time.monotonic() + 5.0
            while eng.running_count and time.monotonic() < deadline:
                time.sleep(0.005)
        finally:
            eng.stop()
        eng.kv.assert_idle("post socket death")  # zero leaked blocks

    def test_kv_exhaust_fault_forces_overcrowded(self, fault_enabled):
        eng = _stub_engine()
        try:
            from brpc_tpu.serving.kv_cache import \
                g_serving_kv_admission_rejects
            before = g_serving_kv_admission_rejects.get_value()
            fault.arm("serving.kv.exhaust", mode="always", count=2)
            for _ in range(2):
                code, _ = eng.submit(eng.model.synth_prompt(4), 2)
                assert code == errors.EOVERCROWDED  # retriable reject
            assert g_serving_kv_admission_rejects.get_value() == before + 2
            # trigger exhausted: the same request is admitted again
            assert _submit_wait(eng, 4, 2) is not None
        finally:
            eng.stop()
        eng.kv.assert_idle("post exhaust fault")

    def test_decode_stall_fault_delays_the_step(self, fault_enabled):
        eng = _stub_engine()
        try:
            fault.arm("serving.decode.stall", mode="oneshot", delay_ms=80)
            t0 = time.monotonic()
            resp = _submit_wait(eng, 4, 2)
            assert resp is not None
            assert time.monotonic() - t0 >= 0.08
        finally:
            eng.stop()
        eng.kv.assert_idle("post stall fault")


# --------------------------------------------------------- real model lane
@pytest.fixture(scope="module")
def serving():
    """One small compiled engine for the whole module; warmup covers every
    (batch, context) jit bucket the tests below touch — twice, because
    donated pool outputs give each program a second signature."""
    cfg = ModelConfig(vocab=64, d_model=16, n_heads=2, n_layers=1,
                      max_context=256)
    kv = PagedKVCache(KVCacheConfig(block_size=8, num_blocks=64),
                      cfg.n_layers, cfg.kv_dim)
    kv._check = True  # every alloc/free audited throughout the module
    model = TinyTransformer(cfg, kv)
    eng = ServingEngine(model, kv, EngineConfig(max_batch=4,
                                                token_budget=128,
                                                idle_wait_s=0.005)).start()
    for _ in range(2):
        _submit_wait(eng, 16, 4, timeout=180.0)
        _submit_wait(eng, 16, 64, timeout=180.0)
    yield eng
    eng.stop()
    kv.assert_idle("module teardown")
    model.close()


class TestEngineRealModel:
    def test_greedy_generation_is_deterministic(self, serving):
        a = _submit_wait(serving, 16, 8)
        b = _submit_wait(serving, 16, 8)
        assert len(a.tokens) == 8
        assert list(a.tokens) == list(b.tokens)
        assert a.finish_reason == "length"

    def test_ttft_strictly_inside_full_latency(self, serving):
        t0 = time.monotonic()
        resp = _submit_wait(serving, 16, 32)
        wall_us = (time.monotonic() - t0) * 1e6
        assert len(resp.tokens) == 32
        assert 0 < resp.ttft_us < wall_us, (
            f"ttft {resp.ttft_us}us not inside full latency {wall_us:.0f}us")

    def test_short_request_overtakes_long(self, serving):
        """The continuous-batching headline: a 2-token request submitted
        AFTER a 64-token one completes first, because admission happens
        between decode steps instead of behind the running gang."""
        order = []
        evs = [threading.Event(), threading.Event()]

        def done_for(tag, ev):
            def done(resp):
                order.append(tag)
                ev.set()
            return done

        code, _ = serving.submit(serving.model.synth_prompt(16), 64,
                                 done=done_for("long", evs[0]))
        assert code == 0
        code, _ = serving.submit(serving.model.synth_prompt(16), 2,
                                 done=done_for("short", evs[1]))
        assert code == 0
        for ev in evs:
            assert ev.wait(120.0)
        assert order[0] == "short"

    def test_snapshot_reports_the_step_loop(self, serving):
        _submit_wait(serving, 16, 4)
        snap = serving.snapshot()
        assert snap["steps"] > 0 and snap["tokens_generated"] > 0
        # nothing in flight: only radix-tree-held prefix chains remain
        assert snap["kv"]["blocks_used"] == snap["kv"]["blocks_cached"]
        assert snap["step_us_p50"] > 0


# -------------------------------------------------------------- RPC surface
@pytest.fixture(scope="module")
def served(serving):
    from brpc_tpu.proto import serving_pb2
    from brpc_tpu.rpc import Channel, ChannelOptions, Server, Stub

    server = Server().add_service(LlmServingService(serving)) \
        .start("127.0.0.1:0")
    ch = Channel(ChannelOptions(protocol="trpc_std", timeout_ms=60000))
    ch.init(str(server.listen_endpoint()))
    stub = Stub(ch, serving_pb2.DESCRIPTOR.services_by_name["LlmService"])
    yield stub
    server.stop()
    server.join(timeout=2)


class TestServingRpc:
    def test_generate_matches_engine_lane(self, serving, served):
        from brpc_tpu.proto import serving_pb2

        direct = _submit_wait(serving, 16, 8)
        resp = served.Generate(serving_pb2.GenerateRequest(
            prompt_len=16, max_new_tokens=8))
        assert list(resp.tokens) == list(direct.tokens)
        assert resp.prompt_len == 16 and resp.ttft_us > 0

    def test_missing_prompt_is_erequest(self, served):
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.rpc import Controller
        from brpc_tpu.rpc.channel import RpcError

        cntl = Controller()
        with pytest.raises(RpcError):
            served.Generate(serving_pb2.GenerateRequest(max_new_tokens=4),
                            controller=cntl)
        assert cntl.failed() and cntl.error_code == errors.EREQUEST

    def test_streamed_deltas_match_the_response(self, served):
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.rpc import Controller
        from brpc_tpu.rpc.stream import (StreamOptions, stream_close,
                                         stream_create)

        frames = []
        got_first = threading.Event()

        def on_received(sid, msgs):
            for m in msgs:
                d = serving_pb2.TokenDelta()
                d.ParseFromString(m)
                frames.append(d)
            got_first.set()

        sid = stream_create(StreamOptions(on_received=on_received))
        cntl = Controller()
        cntl.stream_id = sid
        cntl.timeout_ms = 60000
        resp = served.Generate(serving_pb2.GenerateRequest(
            prompt_len=16, max_new_tokens=8), controller=cntl)
        stream_close(sid)
        assert not cntl.failed(), cntl.error_text()
        assert got_first.wait(1.0), "no TokenDelta ever arrived"
        streamed = [t for d in frames for t in d.tokens]
        assert streamed == list(resp.tokens)
        assert frames[-1].done

    def test_stats_surface(self, serving, served):
        from brpc_tpu.proto import serving_pb2

        stats = served.Stats(serving_pb2.ServingStatsRequest())
        assert stats.kv_blocks_total == serving.kv.num_blocks
        assert stats.steps >= serving.steps - 1  # racy read, same ballpark


# ------------------------------------------------- corpus replay/diff gate
def test_serving_corpus_replays_and_phases_hold(tmp_path):
    """The committed rpc_dump corpus (tools/record_serving_corpus.py)
    replayed against a fresh serving stack: every recorded Generate
    succeeds, the replayed server spans carry the engine's
    prefill_us/decode_us phases, and tools/trace_diff finds no phase
    regression at p50 with a 50ms floor."""
    from brpc_tpu.metrics.collector import global_collector
    from brpc_tpu.rpc import Server
    from brpc_tpu.trace import span as _span
    from tools import record_serving_corpus as recorder
    from tools import rpc_replay, trace_diff

    dumps = [f for f in os.listdir(CORPUS) if f.endswith(".dump")]
    assert dumps, "committed corpus missing; run tools/record_serving_corpus"

    _flags.set_flag("rpcz_sample_ratio", "1.0")
    _flags.set_flag("collector_max_samples_per_second", "0")
    global_collector()._deny_until = 0.0
    engine = recorder.build_engine()
    try:
        recorder.warm_engine(engine)
        _span.reset_for_test()
        server = Server().add_service(LlmServingService(engine)) \
            .start("127.0.0.1:0")
        try:
            rc = rpc_replay.main([
                "--dump", CORPUS,
                "--server", str(server.listen_endpoint()),
                "--rate-mult", "2", "--timeout-ms", "30000",
                "--report-interval", "0"])
            assert rc == 0
            deadline = time.monotonic() + 5.0
            while (len([s for s in _span.recent_spans(200)
                        if s.kind == _span.KIND_SERVER])
                   < len(recorder.SCHEDULE)
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        finally:
            server.stop()
            server.join(timeout=2)
        spans = [s for s in _span.recent_spans(200)
                 if s.kind == _span.KIND_SERVER]
        assert len(spans) >= len(recorder.SCHEDULE)
        with_phases = [s for s in spans
                       if "prefill_us" in s.phases and "decode_us" in s.phases]
        assert with_phases, "no replayed span carries the engine phases"
        # the wait in the engine's queue at twice the recorded rate IS the
        # open-loop queueing noise that must not flake the gate (on a
        # loaded CPU it passes the floor), and the recorded corpus predates
        # the phase; tests/test_serving_spans.py holds it to the engine's
        # own counters
        docs = [s.to_dict() for s in _span.recent_spans(200)]
        assert any("serving_queue_us" in d["phases"] for d in docs)
        for d in docs:
            d["phases"].pop("serving_queue_us", None)
        replayed = tmp_path / "replayed.json"
        replayed.write_text(json.dumps({"spans": docs}))
        # p50 + 50ms floor: open-loop queueing noise must not flake the gate
        rc = trace_diff.main([CORPUS, str(replayed),
                              "--percentile", "50",
                              "--min-delta-us", "50000"])
        assert rc == 0
    finally:
        engine.stop()
        engine.kv.assert_idle("corpus gate teardown")
        engine.model.close()
        _flags.set_flag("rpcz_sample_ratio", "1.0")
        _flags.set_flag("collector_max_samples_per_second", "1000")
