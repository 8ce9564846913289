"""The serving host path on the profiler's clock (profiling/registry.py).

Three layers, cheapest first:

* the span primitive alone, without ``jax``: nesting, the flat timeline
  ``set_phase`` keeps, self times, the sampler's view of a phase;
* a tiny engine with no profiler session: the counters ``snapshot()``
  reads (``span_us``, ``admitted``, ``queue_wait_us_sum``) against what the
  engine did, the rpcz phase, ``/serving``;
* the same engine behind a ``Server`` under ``jax.profiler``: the trace is
  read back with ``ProfileData`` and every ``brpc.*`` span of the table in
  ``docs/serving.md`` is there, nested as the table says, tied by ``seq``
  and the correlation id.

Counts and containment only: no test here holds a duration to a threshold.
"""

import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from brpc_tpu import flags as _flags
from brpc_tpu.profiling import registry as _prof
from brpc_tpu.serving import (EngineConfig, KVCacheConfig, LlmServingService,
                              ModelConfig, PagedKVCache, ServingEngine,
                              TinyTransformer)
from brpc_tpu.serving.kv_cache import KVCacheFull

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RPC_SPANS = ["rpc.parse", "rpc.execute", "rpc.respond", "rpc.stream_write",
             "rpc.call", "rpc.on_response"]
LOOP_SPANS = ["engine.idle", "engine.pool_wait", "engine.admit",
              "engine.step", "engine.prefill", "engine.decode_prep",
              "engine.commit", "engine.reap", "engine.prefix_commit",
              "model.prefill",
              "model.decode", "model.prep", "model.launch", "model.sync"]
ALL_SPANS = RPC_SPANS + ["engine.submit"] + LOOP_SPANS


# ------------------------------------------------------------ the primitive
class TestPrimitive:
    def setup_method(self):
        _prof.unregister_current_thread()   # a clean state for this thread

    teardown_method = setup_method

    def test_span_nests_and_restores_the_phase(self):
        me = threading.get_ident()
        with _prof.span("engine.step", step=1):
            assert _prof.phase_of(me) == "step"
            with _prof.span("model.sync"):
                assert _prof.phase_of(me) == "sync"
            assert _prof.phase_of(me) == "step"
        assert _prof.phase_of(me) is None

    def test_self_times_partition_the_parent(self):
        with _prof.span("engine.step") as outer:
            with _prof.span("model.prep"):
                pass
            with _prof.span("model.sync"):
                pass
        st = _prof.thread_spans()
        assert [st[n][0] for n in ("engine.step", "model.prep",
                                   "model.sync")] == [1, 1, 1]
        count, total, own = st["engine.step"]
        assert total == outer.elapsed_ns
        assert own == total - st["model.prep"][1] - st["model.sync"][1]
        assert 0 <= own <= total

    @pytest.mark.parametrize("inside", [None, "engine.reap"])
    def test_set_phase_keeps_one_span_open_per_level(self, inside):
        """parse -> execute -> (inline done: respond -> send -> respond)
        -> execute -> restored: side by side, never inside one another,
        whether the thread is a worker (no span beneath) or the engine's
        loop inside ``engine.reap``."""
        me = threading.get_ident()
        outer = _prof.span(inside) if inside else None
        if outer:
            outer.__enter__()
        base = _prof.set_phase("rpc.parse", cid=7)
        assert base == inside
        assert _prof.set_phase("rpc.execute", cid=7) == "rpc.parse"
        prev = _prof.set_phase("rpc.respond", cid=7)
        inner = _prof.set_phase("rpc.send")
        assert (prev, inner) == ("rpc.execute", "rpc.respond")
        assert _prof.phase_of(me) == "send"
        _prof.set_phase(inner)
        _prof.set_phase(prev)
        assert _prof.phase_of(me) == "execute"
        _prof.set_phase(base)
        assert _prof.phase_of(me) == (inside and "reap")
        assert _prof._threads[me].flat is None   # nothing left open
        if outer:
            outer.__exit__(None, None, None)
        st = _prof.thread_spans()
        counts = {n: st[n][0] for n in st}
        assert counts == dict({"rpc.parse": 1, "rpc.execute": 2,
                               "rpc.respond": 2, "rpc.send": 1},
                              **({inside: 1} if inside else {}))
        flat_ns = sum(st[n][1] for n in counts if n != inside)
        assert all(st[n][1] == st[n][2] for n in counts if n != inside)
        if inside:   # the flat spans are the parent's children, no more
            assert st[inside][1] - st[inside][2] == flat_ns

    def test_restoring_the_marker_opens_no_span(self):
        with _prof.span("engine.commit"):
            prev = _prof.set_phase("rpc.respond")
            _prof.set_phase(prev)
        assert _prof.thread_spans()["engine.commit"][0] == 1

    def test_a_phase_left_open_is_closed_by_its_span(self):
        with _prof.span("engine.reap"):
            _prof.set_phase("rpc.respond")   # the restore never came
        st = _prof.thread_spans()
        assert st["rpc.respond"][0] == 1
        assert _prof._threads[threading.get_ident()].flat is None

    def test_phase_is_the_older_name_of_span(self):
        assert _prof.phase is _prof.span

    @pytest.mark.parametrize("module", ["brpc_tpu.rpc", "brpc_tpu.profiling",
                                        "brpc_tpu.rpc.server_processing"])
    def test_importing_the_rpc_path_does_not_import_jax(self, module):
        code = (f"import sys, {module}\n"
                "from brpc_tpu.profiling import registry as r\n"
                "with r.span('rpc.call', cid=1): r.set_phase('rpc.parse')\n"
                "assert r.thread_spans()['rpc.call'][0] == 1\n"
                "assert r._trace_annotation is None\n"
                "sys.exit(1 if 'jax' in sys.modules else 0)\n")
        assert subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              timeout=120).returncode == 0


# ------------------------------------------------------------ a tiny engine
def _engine():
    cfg = ModelConfig(vocab=256, d_model=32, n_heads=2, n_layers=2,
                      attn="reference")
    kv = PagedKVCache(KVCacheConfig(block_size=16, num_blocks=256),
                      cfg.n_layers, cfg.kv_dim)
    return ServingEngine(TinyTransformer(cfg, kv), kv,
                         EngineConfig(max_batch=4, token_budget=512))


def _prompt(i: int, n: int) -> np.ndarray:
    """Prompts that share no prefix (so each is a cold prefill)."""
    return ((np.arange(n) * 7 + 13 * i + 1) % 255 + 1).astype(np.int32)


def _drain(engine, jobs, timeout=120.0):
    """Submit (prompt, max_new) jobs, wait for all; returns the sequences."""
    seqs, evs = [], []
    for prompt, max_new in jobs:
        ev = threading.Event()
        code, seq = engine.submit(prompt, max_new,
                                  done=lambda _r, ev=ev: ev.set())
        assert code == 0
        seqs.append(seq)
        evs.append(ev)
    for ev in evs:
        assert ev.wait(timeout), "a generation never finished"
    return seqs


def _settled(engine, timeout=10.0):
    """The snapshot once the loop has gone idle (its last step counted)."""
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        snap = engine.snapshot()
        step = snap["span_us"].get("engine.step", [0])[0]
        if not snap["running"] and not snap["queue_depth"] \
                and step == snap["steps"]:
            return snap
        time.sleep(0.005)
    raise AssertionError("the engine did not settle")


JOBS = [(_prompt(i, n), new) for i, (n, new) in
        enumerate([(16, 4), (40, 6), (24, 1), (33, 5), (16, 3), (64, 2)])]


@pytest.fixture(scope="class")
def ran():
    """An engine that served JOBS with no profiler session, stopped."""
    engine = _engine().start()
    phases = {"prefill": set(), "decode": set()}
    kv = engine.kv
    check_one, check_batch = kv.assert_writable, kv.assert_writable_batch

    def assert_writable(*a):           # model.prefill's own time
        phases["prefill"].add(_prof.phase_of(threading.get_ident()))
        return check_one(*a)

    def assert_writable_batch(*a):     # model.decode's own time
        phases["decode"].add(_prof.phase_of(threading.get_ident()))
        return check_batch(*a)

    kv.assert_writable, kv.assert_writable_batch = \
        assert_writable, assert_writable_batch
    try:
        seqs = _drain(engine, JOBS)
        snap = _settled(engine)
    finally:
        engine.stop()
        engine.model.close()
    return {"engine": engine, "seqs": seqs, "snap": snap, "phases": phases}


class TestCountersWithoutASession:
    def test_span_counts_match_the_engines_own(self, ran):
        snap, spans = ran["snap"], ran["snap"]["span_us"]
        assert snap["admitted"] == len(JOBS)
        assert spans["engine.step"][0] == snap["steps"] > 0
        assert spans["engine.prefill"][0] == snap["admitted"]
        assert spans["model.prefill"][0] == snap["admitted"]
        # a step with a batch holds one decode launch; its three parts
        # occur once per launch, prefill's or decode's
        assert 0 < spans["model.decode"][0] <= snap["steps"]
        launches = spans["model.prefill"][0] + spans["model.decode"][0]
        for part in ("model.prep", "model.launch", "model.sync"):
            assert spans[part][0] == launches
        assert spans["engine.decode_prep"][0] == spans["model.decode"][0]
        assert spans["engine.commit"][0] == launches
        assert spans["engine.reap"][0] == 2 * snap["steps"]
        assert spans["engine.admit"][0] >= snap["steps"]

    @pytest.mark.parametrize("name", [n for n in LOOP_SPANS
                                      if n != "engine.pool_wait"])
    def test_every_loop_span_is_counted(self, ran, name):
        count, total_us, self_us = ran["snap"]["span_us"][name]
        assert count > 0 and 0 <= self_us <= total_us

    def test_self_times_add_up_to_the_outermost_spans(self, ran):
        """admit, pool_wait and step are the loop's outermost spans: the
        self times of everything add up to their totals, so no time is
        counted twice or lost."""
        spans = ran["snap"]["span_us"]
        outer = sum(spans[n][1] for n in ("engine.admit", "engine.step",
                                          "engine.pool_wait") if n in spans)
        own = sum(v[2] for v in spans.values())
        assert own == pytest.approx(outer, abs=0.1 * len(spans))
        assert sum(ran["snap"]["loop_share"].values()) == \
            pytest.approx(1.0, abs=1e-3)

    def test_queue_wait_is_submit_to_admission(self, ran):
        seqs, snap = ran["seqs"], ran["snap"]
        assert all(s.t_admit >= s.t_submit > 0 for s in seqs)
        want = sum((s.t_admit - s.t_submit) * 1e6 for s in seqs)
        assert snap["queue_wait_us_sum"] == pytest.approx(want, rel=1e-9)
        assert snap["queue_wait_us_mean"] == pytest.approx(
            want / len(seqs), abs=0.06)

    def test_sampler_still_reads_prefill_and_decode(self, ran):
        assert ran["phases"] == {"prefill": {"prefill"},
                                 "decode": {"decode"}}

    def test_counters_outlive_the_loop_thread(self, ran):
        """stop() ended the thread (and the idle wait it was in): the
        engine still reads every counter."""
        after, before = ran["engine"].snapshot()["span_us"], \
            ran["snap"]["span_us"]
        waiting = {"engine.idle", "engine.admit"}
        assert {n: v for n, v in after.items() if n not in waiting} == \
            {n: v for n, v in before.items() if n not in waiting}
        assert all(after[n][0] >= before.get(n, [0])[0] for n in waiting)

    def test_step_time_comes_from_the_step_span(self, ran):
        engine = ran["engine"]
        assert 0 < engine.last_step_us <= \
            ran["snap"]["span_us"]["engine.step"][1]
        shard = ran["snap"]["shard_steps"][0]
        assert shard["steps"] == ran["snap"]["span_us"]["model.decode"][0]
        assert 0 < shard["last_us"] and 0 < shard["avg_us"]


def test_pool_wait_is_a_span_of_its_own():
    """Work waits and nothing can be admitted: the loop sleeps inside
    ``engine.pool_wait``, not outside every span."""
    engine = _engine()
    refused, alloc = [], engine._alloc_for

    def alloc_for(seq):
        if len(refused) < 6:
            refused.append(seq.seq_id)
            raise KVCacheFull("planted")
        return alloc(seq)

    engine._alloc_for = alloc_for
    engine.start()
    try:
        _drain(engine, JOBS[:1])
        snap = _settled(engine)
    finally:
        engine.stop()
        engine.model.close()
    assert snap["span_us"]["engine.pool_wait"][0] >= 3
    assert snap["admitted"] == 1


# ------------------------------------------------- behind a Server, no trace
@pytest.fixture(scope="module")
def served():
    from brpc_tpu.proto import serving_pb2
    from brpc_tpu.rpc import Channel, ChannelOptions, Server, Stub

    engine = _engine().start()
    _drain(engine, JOBS)            # every program the tests reach is warm
    server = Server().add_service(LlmServingService(engine)) \
        .start("127.0.0.1:0")
    ch = Channel(ChannelOptions(timeout_ms=120_000))
    ch.init(str(server.listen_endpoint()))
    stub = Stub(ch, serving_pb2.DESCRIPTOR.services_by_name["LlmService"])
    yield engine, server, stub
    server.stop()
    server.join(timeout=2)
    engine.stop()
    engine.model.close()


def _generate(stub, prompt, max_new):
    """One streamed Generate, as the benchmark's client sends it."""
    from brpc_tpu.proto import serving_pb2
    from brpc_tpu.rpc import Controller
    from brpc_tpu.rpc.stream import StreamOptions, stream_close, stream_create

    final = threading.Event()

    def on_received(_sid, msgs):
        for raw in msgs:
            delta = serving_pb2.TokenDelta()
            delta.ParseFromString(raw)
            if delta.done:
                final.set()

    sid = stream_create(StreamOptions(on_received=on_received))
    cntl = Controller()
    cntl.stream_id = sid
    cntl.timeout_ms = 120_000
    resp = stub.Generate(serving_pb2.GenerateRequest(
        prompt_tokens=prompt.tolist(), max_new_tokens=max_new),
        controller=cntl)
    assert not cntl.failed(), cntl.error_text()
    assert final.wait(30), "the final frame never came"
    stream_close(sid)
    return resp


def test_queue_wait_rides_the_rpcz_span(served):
    from brpc_tpu.metrics.collector import global_collector
    from brpc_tpu.trace import span as _span

    _engine_, _server, stub = served
    _flags.set_flag("rpcz_sample_ratio", "1.0")
    _flags.set_flag("collector_max_samples_per_second", "0")
    global_collector()._deny_until = 0.0
    _span.reset_for_test()
    try:
        _generate(stub, _prompt(40, 20), 3)
        end = time.monotonic() + 5.0
        while time.monotonic() < end:
            mine = [s for s in _span.recent_spans(50)
                    if s.kind == _span.KIND_SERVER and s.method == "Generate"]
            if mine:
                break
            time.sleep(0.01)
        assert mine, "the server span never reached the span DB"
        phases = mine[0].phases
        assert "serving_queue_us" in _span.PHASE_NAMES
        assert phases["serving_queue_us"] >= 0
        assert {"prefill_us", "decode_us"} <= set(phases)
    finally:
        _flags.set_flag("collector_max_samples_per_second", "1000")


@pytest.mark.parametrize("fmt", ["text", "json"])
def test_serving_page_shows_queue_wait_and_the_loops_time(served, fmt):
    import json
    import types

    from brpc_tpu.builtin.services import serving_service

    engine, server, _stub = served
    query = {"format": "json"} if fmt == "json" else {}
    code, _ctype, body = serving_service(
        server, types.SimpleNamespace(query=query))
    assert code == 200
    if fmt == "json":
        snap = json.loads(body)["engines"][-1]
        assert snap["admitted"] >= len(JOBS)
        assert snap["queue_wait_us_mean"] >= 0
        assert set(snap["loop_share"]) == set(snap["span_us"])
        assert "engine.idle" in snap["loop_share"]
    else:
        assert "queue_wait_us mean=" in body
        assert "loop: " in body and "engine.idle=" in body


# ------------------------------------------------------- under the profiler
class Ev:
    __slots__ = ("name", "lo", "hi", "ids", "line")

    def __init__(self, name, lo, hi, ids, line):
        self.name, self.lo, self.hi, self.ids, self.line = \
            name, lo, hi, ids, line

    def holds(self, other) -> bool:
        return (self.line == other.line and self.lo <= other.lo
                and other.hi <= self.hi and self is not other)


TRACED = [(_prompt(50 + i, n), new) for i, (n, new) in
          enumerate([(16, 4), (40, 3), (24, 1), (33, 5)])]
# the last prompt extends a served one: the prefix cache answers it through
# prefill_suffix, the decode-shaped prefill
TRACED.append((np.concatenate([TRACED[1][0], _prompt(60, 24)]), 2))


@pytest.fixture(scope="module")
def trace(served, tmp_path_factory):
    """All ``brpc.*`` events of one profiler session over TRACED."""
    import jax

    engine, _server, stub = served
    out = str(tmp_path_factory.mktemp("spans_trace"))
    # one admission finds the pool full a few times: engine.pool_wait
    refused, alloc = [], engine._alloc_for

    def alloc_for(seq):
        if len(refused) < 4:
            refused.append(seq.seq_id)
            raise KVCacheFull("planted")
        return alloc(seq)

    engine._alloc_for = alloc_for
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        for prompt, max_new in TRACED:
            _generate(stub, prompt, max_new)
            time.sleep(0.02)     # the loop goes idle between requests
    finally:
        jax.profiler.stop_trace()
        engine._alloc_for = alloc
    path = sorted(glob.glob(os.path.join(
        out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("brpc."):
                    events.append(Ev(e.name[5:], e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     dict(e.stats), (plane.name, i)))
    return events


def _loop_line(events):
    lines = {e.line for e in events if e.name == "engine.step"}
    assert len(lines) == 1, "engine.step on more than one thread"
    return sorted((e for e in events if e.line in lines),
                  key=lambda e: (e.lo, -e.hi))


def _leaves(line_events):
    """The thread's leaf segments [(lo, hi, name)]: at every instant the
    innermost open span. Asserts that the spans nest (none straddles)."""
    out, stack = [], []

    def pop_until(t):
        while stack and stack[-1][0].hi <= t:
            ev, edge = stack.pop()
            if ev.hi > edge:
                out.append((edge, ev.hi, ev.name))
            if stack:
                stack[-1][1] = ev.hi
    for ev in line_events:
        pop_until(ev.lo)
        if stack:
            top, edge = stack[-1]
            assert ev.hi <= top.hi, f"{ev.name} straddles {top.name}"
            if ev.lo > edge:
                out.append((edge, ev.lo, top.name))
        stack.append([ev, ev.lo])
    pop_until(float("inf"))
    return sorted(out)


class TestProfilerTrace:
    @pytest.mark.parametrize("name", ALL_SPANS)
    def test_every_span_of_the_table_occurs(self, trace, name):
        assert any(e.name == name for e in trace)

    def test_loop_threads_leaves_partition_its_time(self, trace):
        leaves = _leaves(_loop_line(trace))
        assert all(a[1] <= b[0] for a, b in zip(leaves, leaves[1:]))
        covered = sum(hi - lo for lo, hi, _n in leaves)
        assert covered >= 0.99 * (leaves[-1][1] - leaves[0][0])
        assert {n for _lo, _hi, n in leaves} >= {
            "engine.idle", "engine.pool_wait", "engine.admit", "model.prep",
            "model.launch", "model.sync", "rpc.stream_write"}

    def test_loop_thread_holds_only_the_loops_spans(self, trace):
        names = {e.name for e in _loop_line(trace)}
        assert names >= set(LOOP_SPANS)
        assert "engine.submit" not in names and "rpc.call" not in names

    def test_each_prefill_nests_in_a_step_and_holds_one_launch(self, trace):
        prefills = [e for e in trace if e.name == "engine.prefill"]
        assert len(prefills) == len(TRACED)
        for p in prefills:
            assert sum(1 for s in trace
                       if s.name == "engine.step" and s.holds(p)) == 1
            inner = [m for m in trace
                     if m.name == "model.prefill" and p.holds(m)]
            assert len(inner) == 1
            assert inner[0].ids["n"] == p.ids["n"]
        assert sorted(p.ids["n"] for p in prefills) == \
            sorted(len(prompt) for prompt, _new in TRACED)

    def test_the_prefix_hit_is_a_decode_shaped_prefill(self, trace):
        hits = [m for m in trace
                if m.name == "model.prefill" and "start" in m.ids]
        assert len(hits) == 1 and hits[0].ids["n"] == len(TRACED[-1][0])
        assert sum(1 for d in trace
                   if d.name == "model.decode" and hits[0].holds(d)) == 1

    def test_submit_ties_the_rpc_spans_to_the_engines(self, trace):
        submits = [e for e in trace if e.name == "engine.submit"]
        assert len(submits) == len(TRACED)
        seqs = set()
        for sub in submits:
            outer = [x for x in trace
                     if x.name == "rpc.execute" and x.holds(sub)]
            assert len(outer) == 1
            assert outer[0].ids["cid"] == sub.ids["cid"] != 0
            seqs.add(sub.ids["seq"])
        assert seqs == {e.ids["seq"] for e in trace
                        if e.name == "engine.prefill"}

    def test_a_requests_rpc_phases_share_its_correlation_id(self, trace):
        for cid in {e.ids["cid"] for e in trace
                    if e.name == "engine.submit"}:
            mine = {e.name for e in trace if e.ids.get("cid") == cid}
            assert mine >= {"rpc.parse", "rpc.execute", "rpc.respond",
                            "engine.submit"}

    def test_launch_parts_lie_inside_their_model_span(self, trace):
        outer = [e for e in trace
                 if e.name in ("model.prefill", "model.decode")]
        for part in (e for e in trace if e.name in
                     ("model.prep", "model.launch", "model.sync")):
            assert any(o.holds(part) for o in outer), part.name

    def test_steps_and_decodes_carry_their_sizes(self, trace):
        for d in (e for e in trace if e.name == "model.decode"):
            assert 1 <= d.ids["B"] <= d.ids["b_bucket"]
            assert d.ids["l_bucket"] % 16 == 0
        steps = [e.ids["step"] for e in _loop_line(trace)
                 if e.name == "engine.step"]
        assert steps == list(range(steps[0], steps[0] + len(steps)))
