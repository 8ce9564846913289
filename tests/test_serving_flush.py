"""A decode step's frames on the engine's writer thread
(serving/frame_writer.py).

The loop thread writes a prefill's first-token frame itself and hands
every later frame of a sequence, with what completes its RPC, to ONE
writer thread a step. What must hold whichever thread writes:

* per stream, frames arrive in the order they were made, and the frame
  with ``done`` precedes the RPC's response (plain decode and the
  speculative verify lane);
* an abort's ``stream_close`` comes behind the stream's queued frames;
* a stream whose credit window is full stalls the writer, never the loop;
* ``stop()`` writes what was handed off before it returns;
* a failed write marks the sequence, and the loop drops the stream at
  its next reap; the response still carries every token;
* two threads writing to one connection at once (the loop's inline
  frames and responses beside the writer's batches) lose no frame;
* the counters (``snapshot()["host"]["flush"]``, ``frames_inline``) and
  ``/serving``'s ``host:`` line say how often it engaged.

A pure-Python stand-in model keeps the engine's scheduling exact and fast;
counts and order only, no test holds a duration to a threshold.
"""

import threading
import time
import types

import numpy as np
import pytest

from brpc_tpu.profiling import registry as _prof
from brpc_tpu.proto import echo_pb2, serving_pb2
from brpc_tpu.rpc import Service, errors
from brpc_tpu.rpc import stream as _stream
from brpc_tpu.serving import (EngineConfig, KVCacheConfig, LlmServingService,
                              PagedKVCache, ServingEngine)
from brpc_tpu.serving.frame_writer import ROLE

LOOP_THREAD = "brpc-serving-engine"
WRITER_THREAD = "brpc-serving-writer"
CYCLE = 7
ECHO_DESC = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]


def _next(tok):
    """The stand-in model's greedy next token: a cycle, so that prompt
    lookup drafts what the verifier accepts."""
    return int(tok) % CYCLE + 1


class _CycleModel:
    """Decodes the cycle 1..CYCLE; ``verify_step`` makes the speculative
    lane available (every draft token it agrees with is accepted)."""

    def __init__(self, step_s=0.0):
        self.config = types.SimpleNamespace(max_context=4096, vocab=64)
        self.step_s = step_s

    def synth_prompt(self, n):
        return (np.arange(n) % CYCLE + 1).astype(np.int32)

    def prefill(self, prompt, table):
        return _next(prompt[-1])

    def decode_step(self, tokens, positions, tables):
        if self.step_s:
            time.sleep(self.step_s)
        return np.array([_next(t) for t in tokens], dtype=np.int32)

    def verify_step(self, tokens, positions, tables, drafts):
        if self.step_s:
            time.sleep(self.step_s)
        return [np.array([_next(t)] + [_next(x) for x in d], dtype=np.int32)
                for t, d in zip(tokens, drafts)]


def _engine(step_s=0.0, spec_k=0, start=True):
    kv = PagedKVCache(KVCacheConfig(block_size=8, num_blocks=256,
                                    watermark=0.95), 1, 8)
    eng = ServingEngine(_CycleModel(step_s), kv,
                        EngineConfig(max_batch=8, token_budget=512,
                                     idle_wait_s=0.005, spec_k=spec_k),
                        prefix_cache=False)
    return eng.start() if start else eng


class _Log:
    """What reached the stream layer and the RPC's ``done``, in order:
    ``(kind, stream_id, payload, thread name)``."""

    def __init__(self, monkeypatch, write_s=0.0, fail=None):
        self.events = []
        self.fail = fail or {}              # stream_id -> failing write no.
        self._writes = {}
        real_close = _stream.stream_close

        def stream_write(sid, data, timeout=None):
            n = self._writes[sid] = self._writes.get(sid, 0) + 1
            if write_s:
                time.sleep(write_s)
            self._add("frame", sid, serving_pb2.TokenDelta.FromString(data))
            return errors.ESTREAMCLOSED if self.fail.get(sid) == n else 0

        def stream_close(sid):
            self._add("close", sid, None)
            real_close(sid)

        monkeypatch.setattr(_stream, "stream_write", stream_write)
        monkeypatch.setattr(_stream, "stream_close", stream_close)

    def _add(self, kind, sid, payload):
        self.events.append((kind, sid, payload,
                            threading.current_thread().name))

    def done_for(self, sid, box):
        ev = threading.Event()

        def done(resp):
            self._add("resp", sid, resp)
            box.append(resp)
            ev.set()
        return done, ev

    def of(self, sid):
        return [e for e in self.events if e[1] == sid]


def _submit(eng, log, sid, plen, max_new, cntl=None):
    box = []
    done, ev = log.done_for(sid, box)
    code, seq = eng.submit(eng.model.synth_prompt(plen), max_new,
                           cntl=cntl, done=done, stream_id=sid)
    assert code == 0
    return seq, ev, box


class _Cntl:
    def __init__(self):
        self._srv_socket = types.SimpleNamespace(failed=False)
        self.code = 0

    def set_failed(self, code, text):
        self.code = code


def _frames_then_response(events):
    """The stream's frames, asserting that they come in step order with
    ``done`` on the last one only, and that the response comes last."""
    frames = [e[2] for e in events if e[0] == "frame"]
    assert [e[0] for e in events] == ["frame"] * len(frames) + ["resp"]
    steps = [f.step for f in frames]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    assert [f.done for f in frames] == [False] * (len(frames) - 1) + [True]
    return frames


# ------------------------------------------------------ order and done
@pytest.mark.parametrize("spec_k", [0, 3], ids=["plain", "spec"])
def test_frames_keep_their_order_and_precede_the_response(monkeypatch,
                                                          spec_k):
    log = _Log(monkeypatch, write_s=0.001)
    eng = _engine(spec_k=spec_k)
    try:
        jobs = {101: (9, 5), 102: (12, 17), 103: (4, 2), 104: (21, 30)}
        subs = {sid: _submit(eng, log, sid, plen, new)
                for sid, (plen, new) in jobs.items()}
        for _seq, ev, _box in subs.values():
            assert ev.wait(30)
    finally:
        eng.stop()
    eng.kv.assert_idle("flush order")
    for sid, (seq, _ev, box) in subs.items():
        mine = log.of(sid)
        frames = _frames_then_response(mine)
        assert [t for f in frames for t in f.tokens] == list(box[0].tokens)
        assert len(box[0].tokens) == jobs[sid][1]
        # the first token's frame from the loop, the rest and the response
        # from the writer
        assert mine[0][3] == LOOP_THREAD
        assert {e[3] for e in mine[1:]} <= {WRITER_THREAD}
        assert seq.flushed == (jobs[sid][1] > 1)
        if spec_k:
            assert all(f.accepted <= len(f.tokens) for f in frames)
    if spec_k:
        # the cycle is drafted from the prompt: frames of several tokens
        assert eng.spec_stats.accepted > 0
        assert any(len(e[2].tokens) > 1 for e in log.events
                   if e[0] == "frame")


def test_a_sequence_done_at_prefill_is_answered_on_the_loop(monkeypatch):
    """One token out, finished at prefill: its frame and its response are
    written inline, as before the writer existed; the writer gets
    nothing."""
    log = _Log(monkeypatch)
    eng = _engine()
    try:
        _seq, ev, box = _submit(eng, log, 111, 16, 1)
        assert ev.wait(30)
    finally:
        eng.stop()
    mine = log.of(111)
    _frames_then_response(mine)
    assert {e[3] for e in mine} == {LOOP_THREAD}
    assert eng.snapshot()["host"]["flush"][:2] == [0, 0]
    assert eng.frames_inline == 1 and list(box[0].tokens) == [_next(16)]


# -------------------------------------------------- when it is handed on
class _SyncingModel(_CycleModel):
    """Waits for its result in a ``model.sync`` wait span, as every model
    of the repo does, and notes the engine's unhanded batch at the launch
    and inside the wait."""

    def __init__(self):
        super().__init__()
        self.engine = None
        self.at_launch, self.in_sync = [], []

    def decode_step(self, tokens, positions, tables):
        self.at_launch.append(len(self.engine._outbox))
        with _prof.wait_span("model.sync"):
            self.in_sync.append(len(self.engine._outbox))
            return super().decode_step(tokens, positions, tables)


def test_the_batch_goes_to_the_writer_as_the_loop_next_waits(monkeypatch):
    """A step's frames are still the loop's at the next step's launch and
    the writer's once the loop waits for that step's result: the writer
    takes the interpreter while the loop has no use for it."""
    log = _Log(monkeypatch)
    model = _SyncingModel()
    kv = PagedKVCache(KVCacheConfig(block_size=8, num_blocks=64), 1, 8)
    eng = model.engine = ServingEngine(
        model, kv, EngineConfig(max_batch=4, idle_wait_s=0.005),
        prefix_cache=False)
    eng.start()
    try:
        subs = [_submit(eng, log, 501 + i, 8, 12) for i in range(3)]
        for _seq, ev, _box in subs:
            assert ev.wait(30)
    finally:
        eng.stop()
    assert model.in_sync and set(model.in_sync) == {0}
    assert max(model.at_launch) >= 2
    for i, (_seq, _ev, box) in enumerate(subs):
        frames = _frames_then_response(log.of(501 + i))
        assert [t for f in frames for t in f.tokens] == list(box[0].tokens)


# ------------------------------------------------------------- an abort
def test_an_abort_closes_the_stream_behind_its_frames(monkeypatch):
    log = _Log(monkeypatch, write_s=0.01)     # the writer falls behind
    eng = _engine(step_s=0.001)
    cntl = _Cntl()
    try:
        seq, ev, box = _submit(eng, log, 201, 8, 400, cntl=cntl)
        while len(seq.out_tokens) < 6:
            time.sleep(0.001)
        cntl._srv_socket.failed = True        # the connection died
        assert ev.wait(30)
        written = len(seq.out_tokens)
    finally:
        eng.stop()
    eng.kv.assert_idle("flush abort")
    assert cntl.code == errors.EFAILEDSOCKET and box == [None]
    kinds = [e[0] for e in log.of(201)]
    # every frame handed off before the abort is written, then the close,
    # then the failed response
    assert kinds == ["frame"] * written + ["close", "resp"]
    assert {e[3] for e in log.of(201)[1:]} == {WRITER_THREAD}


# -------------------------------------------------- a full credit window
class _Sock:
    failed = False

    def __init__(self):
        self.packets = 0

    def write(self, packet, id_wait=None):
        self.packets += 1
        return 0


def _bound_stream(window):
    st = _stream.Stream(_stream.StreamOptions(window_bytes=window))
    st.stream_id = _stream._stream_pool.insert(st)
    st.bind(_Sock(), remote_stream_id=1)
    return st


def test_a_full_credit_window_stalls_the_writer_not_the_loop():
    """The peer never consumes: the stream's first frame fills its
    window, and the writer blocks on the second. The loop steps on, and
    answers a sequence that streams nothing, meanwhile."""
    st = _bound_stream(window=1)
    eng = _engine(step_s=0.001)
    try:
        stalled, other = [], []
        ev_a, ev_b = threading.Event(), threading.Event()
        eng.submit(eng.model.synth_prompt(8), 6, stream_id=st.stream_id,
                   done=lambda r: (stalled.append(r), ev_a.set()))
        eng.submit(eng.model.synth_prompt(8), 40,
                   done=lambda r: (other.append(r), ev_b.set()))
        assert ev_b.wait(30), "the loop stalled behind a full window"
        steps = eng.steps
        assert steps >= 39 and not ev_a.is_set()
        assert st.socket.packets == 1           # the inline first frame
        assert eng._writer._unfinished >= 1
        # the peer consumes: the writer goes on, and the stalled
        # sequence's frames and response follow
        st.on_feedback(1 << 20)
        assert ev_a.wait(30)
        assert st.socket.packets == 6
        assert len(stalled[0].tokens) == 6 and len(other[0].tokens) == 40
    finally:
        eng.stop()
        st.close(send_frame=False)
    eng.kv.assert_idle("flush stall")


# ----------------------------------------------------------------- stop
def test_stop_writes_a_pending_batch_before_it_returns(monkeypatch):
    log = _Log(monkeypatch, write_s=0.005)
    eng = _engine(step_s=0.001)
    cntls = {sid: _Cntl() for sid in (301, 302, 303)}
    subs = {sid: _submit(eng, log, sid, 8, 30, cntl=cntl)
            for sid, cntl in cntls.items()}
    writer = eng._writer._thread
    try:
        end = time.monotonic() + 30
        while eng._writer._unfinished < 2 and time.monotonic() < end:
            time.sleep(0.001)
        assert eng._writer._unfinished >= 2
    finally:
        eng.stop()
    assert writer is not None and not writer.is_alive()
    assert eng._writer._unfinished == 0
    handed = eng._writer.frames
    assert handed == sum(1 for e in log.events
                         if e[0] == "frame" and e[3] == WRITER_THREAD)
    eng.kv.assert_idle("flush stop")
    for sid, (seq, ev, box) in subs.items():
        assert ev.is_set() and len(box) == 1
        kinds = [e[0] for e in log.of(sid)]
        assert kinds[-1] == "resp" and kinds.count("resp") == 1
        if cntls[sid].code:       # aborted by stop: closed behind frames
            assert cntls[sid].code == errors.ELOGOFF and box == [None]
            assert kinds == ["frame"] * len(seq.out_tokens) + ["close",
                                                               "resp"]
        else:
            assert kinds == ["frame"] * 30 + ["resp"]


# ------------------------------------------------------ a failed write
def test_a_failed_write_marks_the_sequence_and_the_loop_drops_it(
        monkeypatch):
    log = _Log(monkeypatch, fail={401: 2})   # the first decode frame fails
    eng = _engine(step_s=0.002)
    try:
        seq, ev, box = _submit(eng, log, 401, 8, 40)
        assert ev.wait(30)
    finally:
        eng.stop()
    assert seq.stream_lost and seq.stream_id == 0
    # no write after the failed one; the loop stopped queueing frames
    # once it read the mark, and the response carries every token
    assert [e[0] for e in log.of(401)] == ["frame", "frame", "resp"]
    assert eng._writer.frames < 39
    assert len(box[0].tokens) == 40


# ----------------------------------------- two writers on one connection
class _PushService(Service):
    """Accepts a stream per call; the test writes on them from its own
    threads."""

    DESCRIPTOR = ECHO_DESC

    def __init__(self):
        super().__init__()
        self.sids = []

    def Echo(self, cntl, request, done):
        self.sids.append(_stream.stream_accept(cntl, _stream.StreamOptions()))
        return echo_pb2.EchoResponse(message="ok")


LANES = [("127.0.0.1:0", "tcp"), ("tpu://127.0.0.1:0/0", "tpu")]


def _lane_or_skip():
    from brpc_tpu.rpc.native_transport import dataplane_available

    if not dataplane_available():
        pytest.skip("native engine unavailable")


@pytest.mark.parametrize("listen", [a for a, _ in LANES],
                         ids=[b for _, b in LANES])
def test_two_threads_write_to_one_connection_at_once(listen):
    """What the engine does on a serving connection: the loop thread's
    inline frames beside the writer's batch, each through
    ``NativeSocket.write`` / ``dp_send``. Every frame of both streams
    arrives, in order (a gap aborts a stream)."""
    _lane_or_skip()
    from brpc_tpu.rpc import (Channel, ChannelOptions, Controller, Server,
                              ServerOptions, Stub)

    push = _PushService()
    server = Server(ServerOptions(native_dataplane=True))
    server.add_service(push)
    server.start(listen)
    n, mine = 400, []
    try:
        ch = Channel(ChannelOptions(timeout_ms=10000,
                                    native_transport=True)).init(
            str(server.listen_endpoint()))
        stub = Stub(ch, ECHO_DESC)
        got = {0: [], 1: []}
        full = {0: threading.Event(), 1: threading.Event()}
        for i in (0, 1):
            def on_received(_sid, msgs, i=i):
                got[i].extend(msgs)
                if len(got[i]) >= n:
                    full[i].set()
            cntl = Controller()
            cntl.stream_id = _stream.stream_create(
                _stream.StreamOptions(on_received=on_received))
            mine.append(cntl.stream_id)
            stub.Echo(echo_pb2.EchoRequest(message="open"), controller=cntl)
        assert len(push.sids) == 2
        go = threading.Barrier(2)

        def writer(i):
            go.wait()
            for k in range(n):
                # sizes vary so that writes of both threads interleave
                body = f"{i}:{k}:".encode() + b"x" * (k % 7 * 300)
                assert _stream.stream_write(push.sids[i], body) == 0
        threads = [threading.Thread(target=writer, args=(i,))
                   for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for i in (0, 1):
            assert full[i].wait(30), f"stream {i}: {len(got[i])}/{n}"
            assert [int(m.split(b":")[1]) for m in got[i]] == list(range(n))
            assert all(m.startswith(f"{i}:".encode()) for m in got[i])
    finally:
        for sid in push.sids + mine:
            _stream.stream_close(sid)
        server.stop()
        server.join(timeout=2)


@pytest.mark.parametrize("listen", [a for a, _ in LANES],
                         ids=[b for _, b in LANES])
def test_streamed_tokens_equal_the_response_over_one_connection(listen):
    """Many streamed Generates and some unstreamed ones on one channel, as
    the benchmark's client sends them: prefill frames and inline
    responses from the loop, decode frames and responses from the writer,
    on one connection at once."""
    _lane_or_skip()
    from brpc_tpu.rpc import (Channel, ChannelOptions, Controller, Server,
                              ServerOptions, Stub)

    eng = _engine()
    server = Server(ServerOptions(native_dataplane=True))
    server.add_service(LlmServingService(eng))
    server.start(listen)
    try:
        ch = Channel(ChannelOptions(timeout_ms=60000,
                                    native_transport=True)).init(
            str(server.listen_endpoint()))
        stub = Stub(ch, serving_pb2.DESCRIPTOR.services_by_name["LlmService"])
        calls = []
        for i in range(16):
            streamed, max_new = i % 4 != 3, (1 if i % 4 == 3 else 8 + 3 * i)
            frames, ended = [], threading.Event()
            cntl = Controller()
            cntl.timeout_ms = 60000
            if streamed:
                def on_received(_sid, msgs, frames=frames):
                    frames.extend(serving_pb2.TokenDelta.FromString(m)
                                  for m in msgs)
                cntl.stream_id = _stream.stream_create(
                    _stream.StreamOptions(on_received=on_received))
            box = []
            stub.Generate(serving_pb2.GenerateRequest(
                prompt_tokens=list(range(1, 5 + i)),
                max_new_tokens=max_new), controller=cntl,
                done=lambda c, box=box, ended=ended: (box.append(c),
                                                      ended.set()))
            calls.append((streamed, max_new, frames, box, ended, cntl))
        for streamed, max_new, frames, box, ended, cntl in calls:
            assert ended.wait(60)
            assert not box[0].failed(), box[0].error_text()
            tokens = list(box[0].response.tokens)
            assert len(tokens) == max_new
            if not streamed:
                continue
            end = time.monotonic() + 10
            while not (frames and frames[-1].done) \
                    and time.monotonic() < end:
                time.sleep(0.002)
            assert [t for f in frames for t in f.tokens] == tokens
            assert [f.step for f in frames] == list(range(1, max_new + 1))
            _stream.stream_close(cntl.stream_id)
    finally:
        server.stop()
        server.join(timeout=2)
        eng.stop()
    eng.kv.assert_idle("flush over one connection")


# -------------------------------------------------------------- counters
@pytest.fixture(scope="module")
def counted():
    """Streamed sequences through real streams (a bound Stream per
    sequence), the snapshots around them, and /serving while the engine
    runs."""
    from brpc_tpu.builtin.services import serving_service

    eng = _engine()
    streams = [_bound_stream(window=1 << 20) for _ in range(4)]
    news = [1, 6, 11, 3]
    try:
        snap0 = eng.snapshot()
        evs = []
        for st, new in zip(streams, news):
            ev = threading.Event()
            eng.submit(eng.model.synth_prompt(10), new,
                       stream_id=st.stream_id, done=lambda r, ev=ev: ev.set())
            evs.append(ev)
        for ev in evs:
            assert ev.wait(30)
        snap1 = eng.snapshot()
        pages = {fmt: serving_service(None, types.SimpleNamespace(
            query={"format": "json"} if fmt == "json" else {}))[2]
            for fmt in ("text", "json")}
    finally:
        eng.stop()
        for st in streams:
            st.close(send_frame=False)
    return {"engine": eng, "snap0": snap0, "snap1": snap1, "news": news,
            "packets": [st.socket.packets for st in streams],
            "pages": pages}


def _diff(counted, role, name):
    a = counted["snap0"]["host"]["spans"].get(role, {}).get(name, [0, 0])
    b = counted["snap1"]["host"]["spans"].get(role, {}).get(name, [0, 0])
    return b[0] - a[0]


@pytest.mark.parametrize("where", ["snapshot", "text", "json"])
def test_flush_counters(counted, where):
    import json

    news = counted["news"]
    flushed, inline = sum(n - 1 for n in news), len(news)
    assert counted["packets"] == news
    if where == "snapshot":
        host = counted["snap1"]["host"]
        batches, frames, lag_us, backlog = host["flush"]
        assert frames == flushed and host["frames_inline"] == inline
        assert 1 <= batches <= counted["snap1"]["steps"]
        assert lag_us > 0 and backlog >= 0
        assert counted["snap0"]["host"]["flush"] == [0, 0, 0.0, 0]
    elif where == "json":
        snaps = json.loads(counted["pages"]["json"])["engines"]
        mine = [s for s in snaps
                if s["host"]["flush"][1] == flushed
                and s["host"]["frames_inline"] == inline]
        assert len(mine) >= 1
    else:
        host = [ln for ln in counted["pages"]["text"].splitlines()
                if ln.startswith("  host: ")]
        assert any("flush batches=" in ln and f"frames={flushed} " in ln
                   and "lag_us=" in ln and "backlog_max=" in ln
                   and f"frames_inline={inline}" in ln for ln in host)


def test_the_writer_has_a_role_of_its_own(counted):
    """The writer's spans land under ``serving.writer``, the loop's inline
    frames under ``serving``; neither is ``user``, the benchmark's
    client."""
    news = counted["news"]
    host = counted["snap1"]["host"]
    assert _diff(counted, ROLE, "rpc.stream_write") == sum(n - 1
                                                          for n in news)
    assert _diff(counted, ROLE, "engine.flush") == host["flush"][0]
    assert _diff(counted, "serving", "rpc.stream_write") == len(news)
    assert _diff(counted, "user", "rpc.stream_write") == 0
    assert ROLE in host["threads"] and host["threads"][ROLE][1] >= 0
