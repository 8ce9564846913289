"""The serving host path on the profiler's clock (profiling/registry.py).

Three layers, cheapest first:

* the span primitive alone, without ``jax``: nesting, the flat timeline
  ``set_phase`` keeps, self times, long closes, the CPU time inside the
  spans that wait by design, the sampler's view of a phase;
* a tiny engine with no profiler session: the counters ``snapshot()``
  reads (``span_us``, ``host``, ``admitted``, ``queue_wait_us_sum``) against
  what the engine did, the rpcz phases, ``/serving``;
* the same engine behind a ``Server`` under ``jax.profiler``: the trace is
  read back with ``ProfileData`` and every ``brpc.*`` span of the table in
  ``docs/serving.md`` is there, nested as the table says, tied by ``seq``
  and the correlation id.

Counts and containment only: no test here holds a duration to a threshold.
"""

import contextlib
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
WRITER_SPANS = ["engine.flush"]
ALL_SPANS = RPC_SPANS + ["engine.submit"] + LOOP_SPANS + WRITER_SPANS
HOST_KEYS = {"wall_us", "loop", "waits", "spans", "threads", "lane_wait",
             "gc", "flush", "frames_inline"}


# ------------------------------------------------------------ the primitive
class TestPrimitive:
    def setup_method(self):
        _prof.unregister_current_thread()   # a clean state for this thread

    def teardown_method(self):
        _prof.unregister_current_thread()

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
        count, total, own = st["engine.step"][:3]
        assert total == outer.elapsed_ns
        assert own == total - st["model.prep"][1] - st["model.sync"][1]
        assert 0 <= own <= total

    def test_a_threads_cpu_is_its_waits_plus_its_work(self):
        """Only a wait_span reads the CPU clock, once at each end: what the
        thread used between them is the wait's, the rest of the thread's
        CPU time is its working spans'."""
        with _cpu_clock_readings() as reads:
            with _prof.span("engine.step"):
                with _prof.span("model.launch"):
                    _spin(0.01)
                with _prof.wait_span("model.sync"):
                    time.sleep(0.01)
                with _prof.span("engine.commit"):
                    _spin(0.01)
                base = _prof.set_phase("rpc.respond")
                _prof.set_phase(base)
            mine = time.thread_time_ns()
        assert len(reads) == 2
        waits = _prof.thread_waits()
        assert waits == {"model.sync": reads[1] - reads[0]}
        assert 0 <= waits["model.sync"] < mine - 15_000_000

    def test_a_threads_wait_hook_runs_as_each_of_its_waits_opens(self):
        """``on_wait``: called before the wait opens (the enclosing span's
        phase), on the thread that named it only, until it names None."""
        me = threading.get_ident()
        seen = []
        _prof.on_wait(lambda: seen.append(_prof.phase_of(me)))
        try:
            with _prof.span("engine.step"):
                with _prof.span("model.launch"):
                    pass
                with _prof.wait_span("model.sync"):
                    pass

            def elsewhere():
                with _prof.wait_span("engine.idle"):
                    pass
            other = threading.Thread(target=elsewhere)
            other.start()
            other.join()
        finally:
            _prof.on_wait(None)
        with _prof.wait_span("engine.idle"):
            pass
        assert seen == ["step"]

    def test_a_wait_that_spins_is_on_the_cpu_and_one_that_sleeps_is_not(
            self):
        """Spun by the thread's own CPU clock, so a loaded machine that
        takes the core away stretches the wall time and not the test."""
        with _prof.wait_span("engine.pool_wait"):
            end = time.thread_time_ns() + 50_000_000
            while time.thread_time_ns() < end:
                pass
        with _prof.wait_span("model.sync"):
            time.sleep(0.05)
        st, waits = _prof.thread_spans(), _prof.thread_waits()
        # all of the 50 ms it spun, and no more than the wall time it took
        # (a tick of the clock's grain to spare)
        assert 50_000_000 <= waits["engine.pool_wait"] \
            <= st["engine.pool_wait"][2] + 10_000_000
        assert 0 <= waits["model.sync"] < st["model.sync"][2] / 10

    def test_a_waits_cpu_reads_lie_inside_its_own_wall_time(self):
        """Wall, CPU at the open and CPU, wall at the close: what a read of
        the CPU clock costs (microseconds on a sandboxed host) is the
        wait's time, not its neighbours'."""
        order = []
        wall, cpu = _prof.perf_counter_ns, _prof.thread_time_ns
        _prof.perf_counter_ns = lambda: (order.append("wall"), wall())[1]
        _prof.thread_time_ns = lambda: (order.append("cpu"), cpu())[1]
        try:
            with _prof.span("engine.step"):
                del order[:]
                with _prof.wait_span("engine.idle"):
                    pass
                inside = list(order)
        finally:
            _prof.perf_counter_ns, _prof.thread_time_ns = wall, cpu
        assert inside == ["wall", "cpu", "cpu", "wall"]

    def test_the_waits_name_themselves(self):
        """A name is in ``thread_waits()`` if and only if it was opened as
        a wait_span: the program says which spans wait, no reader lists
        them."""
        with _prof.span("engine.step"):
            with _prof.span("model.launch"):
                pass
            with _prof.wait_span("model.sync"):
                pass
            with _prof.wait_span("model.sync"):
                pass
        with _prof.wait_span("engine.idle"):
            pass
        st, waits = _prof.thread_spans(), _prof.thread_waits()
        assert set(waits) == {"model.sync", "engine.idle"}
        assert set(st) == {"engine.step", "model.launch", "model.sync",
                           "engine.idle"}
        assert st["model.sync"][0] == 2 and st["engine.idle"][0] == 1
        # a wait is a span like any other on the wall clock
        assert st["engine.step"][1] - st["engine.step"][2] == \
            st["model.launch"][1] + st["model.sync"][1]

    @pytest.mark.parametrize("inside", [None, "engine.reap"])
    def test_a_set_phase_swap_reads_each_clock_once(self, inside):
        """Where one span closes and the next opens, one read of the wall
        clock serves both; a restore that opens nothing reads it once, a
        restore with nothing open reads nothing; the CPU clock is never
        read."""
        calls = {"wall": 0, "cpu": 0}
        wall, cpu = _prof.perf_counter_ns, _prof.thread_time_ns

        def count(which, clock):
            def read():
                calls[which] += 1
                return clock()
            return read

        outer = _prof.span(inside) if inside else None
        if outer:
            outer.__enter__()
        _prof.perf_counter_ns = count("wall", wall)
        _prof.thread_time_ns = count("cpu", cpu)
        try:
            base = _prof.set_phase("rpc.parse")          # opens
            assert calls == {"wall": 1, "cpu": 0}
            _prof.set_phase("rpc.execute")               # closes and opens
            assert calls == {"wall": 2, "cpu": 0}
            _prof.set_phase("rpc.respond")               # closes and opens
            assert calls == {"wall": 3, "cpu": 0}
            _prof.set_phase(base)                        # closes
            assert calls == {"wall": 4, "cpu": 0}
            _prof.set_phase(base)                        # nothing open
            assert calls == {"wall": 4, "cpu": 0}
        finally:
            _prof.perf_counter_ns, _prof.thread_time_ns = wall, cpu
        if outer:
            outer.__exit__(None, None, None)
        st = _prof.thread_spans()
        # the three lie side by side: each began where the last ended
        flat = [st[n] for n in ("rpc.parse", "rpc.execute", "rpc.respond")]
        assert all(rec[0] == 1 and rec[1] == rec[2] for rec in flat)
        if inside:
            assert st[inside][1] - st[inside][2] == sum(r[1] for r in flat)

    def test_a_long_close_counts_in_its_span_and_not_in_the_parent(self):
        assert _prof.LONG_SELF_NS == 50_000_000
        with _prof.span("engine.step"):
            with _prof.span("model.sync"):
                time.sleep(0.06)
            with _prof.span("model.prep"):
                pass
        st = _prof.thread_spans()
        assert st["model.sync"][3] == 1
        assert st["model.sync"][4] == st["model.sync"][2] >= 60_000_000
        # the parent's total is over the line, its SELF time is not
        assert st["engine.step"][1] >= 60_000_000
        assert st["engine.step"][3:] == [0, 0]
        assert st["model.prep"][3:] == [0, 0]

    def test_a_record_has_five_numbers(self):
        with _prof.span("engine.admit"):
            _prof.set_phase("rpc.respond")
            with _prof.wait_span("engine.idle"):
                pass
        for rec in _prof.thread_spans().values():
            assert len(rec) == 5 and all(isinstance(v, int) for v in rec)

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


def _spin(seconds: float) -> None:
    """Hold the CPU for about ``seconds`` of wall time."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@contextlib.contextmanager
def _cpu_clock_readings():
    """Every reading the registry takes of the CPU clock meanwhile."""
    reads, real = [], _prof.thread_time_ns

    def clock():
        reads.append(real())
        return reads[-1]

    _prof.thread_time_ns = clock
    try:
        yield reads
    finally:
        _prof.thread_time_ns = real


# ------------------------------------------------- the whole process, by role
class TestProcessByRole:
    """``cpu_by_role`` / ``spans_by_role`` / ``gc_pauses``: read at
    snapshot time, cumulative, and they keep a thread that has ended."""

    def test_cpu_by_role_keeps_a_thread_after_it_unregisters(self):
        before = _prof.cpu_by_role().get("test.spinner", [0, 0])
        seen = {}
        go = threading.Event()

        def run():
            _prof.register_current_thread("test.spinner")
            _spin(0.05)
            seen["live"] = _prof.cpu_by_role()["test.spinner"]
            with _prof.span("rpc.execute"):
                _spin(0.02)
            _prof.unregister_current_thread()
            seen["own"] = time.thread_time_ns()
            go.wait(10)

        th = threading.Thread(target=run)
        th.start()
        end = time.monotonic() + 10
        while "own" not in seen and time.monotonic() < end:
            time.sleep(0.005)
        # unregistered and still alive: counted once, at its last reading
        held = _prof.cpu_by_role()["test.spinner"]
        go.set()
        th.join()
        after = _prof.cpu_by_role()["test.spinner"]
        assert seen["live"][0] == before[0] + 1
        assert held[1] - before[1] >= seen["live"][1] - before[1] > 0
        assert held[1] - before[1] <= seen["own"]
        # ended: no live thread of the role, its CPU is still there (with
        # what it used on its way out)
        assert after[0] == before[0] and after[1] >= held[1]
        spans = _prof.spans_by_role()["test.spinner"]["rpc.execute"]
        assert spans[0] >= 1 and spans[2] > 0

    def test_the_table_adds_up_to_the_process(self):
        table = _prof.cpu_by_role()
        assert {"process", "runtime", "user"} <= set(table)
        listed = sum(cpu for role, (_n, cpu) in table.items()
                     if role != "process")
        assert listed == table["process"][1]
        assert table["process"][0] == sum(
            1 for th in threading.enumerate() if th.native_id is not None)
        again = _prof.cpu_by_role()
        assert again["process"][1] >= table["process"][1]
        assert again["user"][1] >= table["user"][1]

    def test_the_caller_lists_threads_python_does_not_know(self):
        """The native lane's: they count in their own roles, and what they
        used is no longer ``runtime``'s."""
        table = _prof.cpu_by_role({"lane.test": [2, 5_000]})
        assert table["lane.test"] == [2, 5_000]
        listed = sum(cpu for role, (_n, cpu) in table.items()
                     if role != "process")
        assert listed == table["process"][1] or table["runtime"][1] == 0
        assert "lane.test" not in _prof.cpu_by_role()

    def test_two_snapshots_at_once_retire_a_thread_once(self):
        """``/serving`` and a benchmark read the table together while
        threads end: none is folded twice and no reading raises."""
        before = _prof.cpu_by_role().get("test.brief", [0, 0])
        retired = _prof._retired_cpu.get("test.brief", [0, 0])[0]
        errors, stop = [], threading.Event()

        def read():
            try:
                while not stop.is_set():
                    _prof.cpu_by_role()
            except Exception as e:      # a KeyError at the fold
                errors.append(e)

        def brief():
            _prof.register_current_thread("test.brief")
            _spin(0.002)

        readers = [threading.Thread(target=read) for _ in range(3)]
        every = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)     # threads change hands mid-fold
        try:
            for th in readers:
                th.start()
            for _ in range(40):
                th = threading.Thread(target=brief)
                th.start()
                th.join()
            stop.set()
            for th in readers:
                th.join()
        finally:
            sys.setswitchinterval(every)
        after = _prof.cpu_by_role()["test.brief"]
        assert not errors
        assert after[0] == before[0]            # none of them lives
        assert _prof._retired_cpu["test.brief"][0] == retired + 40
        assert after[1] > before[1]

    def test_a_thread_between_the_listing_and_its_last_reading_waits(self):
        """A thread leaves ``threading.enumerate()`` a moment before its
        locals are dropped and it reports its last reading: a table made
        in between keeps it at the reading it has and folds it only once
        the thread has spoken (folded then and again later, its CPU would
        count twice: the engine's loop as ``user`` on top of ``serving``)."""
        nid = 2**22 + 12345      # no thread of this process
        role = "test.pending"
        _prof._cpu_seen[nid] = (role, 5_000)
        _prof._watched.add(nid)
        try:
            between = _prof.cpu_by_role()
            assert between[role] == [0, 5_000]
            assert role not in _prof._retired_cpu
            _prof._cpu_final[nid] = (role, 7_000)    # its finalizer ran
            _prof._watched.discard(nid)
            assert _prof.cpu_by_role()[role] == [0, 7_000]
            assert _prof.cpu_by_role()[role] == [0, 7_000]      # once
            assert _prof._retired_cpu[role] == [1, 7_000]
            assert nid not in _prof._cpu_seen and nid not in _prof._cpu_final
        finally:
            _prof._watched.discard(nid)
            _prof._cpu_seen.pop(nid, None)
            _prof._cpu_final.pop(nid, None)
            _prof._retired_cpu.pop(role, None)

    def test_a_thread_that_ends_without_a_word_is_kept(self):
        """Born and ended between two readings, no role, no unregister (a
        benchmark's closed-loop client): its spans and its CPU stay in
        ``user``, and it leaves no entry for the next owner of its ident."""
        idents = []

        def run():
            idents.append(threading.get_ident())
            with _prof.span("rpc.call"):
                _spin(0.03)
                spent.append(time.thread_time_ns())

        spent = []
        a = _prof.cpu_by_role()
        calls = _prof.spans_by_role().get("user", {}).get(
            "rpc.call", [0, 0, 0])
        th = threading.Thread(target=run)
        th.start()
        th.join()
        b = _prof.cpu_by_role()
        after = _prof.spans_by_role()["user"]["rpc.call"]
        assert idents[0] not in _prof._threads
        assert after[0] == calls[0] + 1
        assert after[2] > calls[2]
        spun = spent[0]
        assert spun > 0
        # this thread only waited meanwhile: user's CPU grew by the
        # other's, and the process's by at least as much
        assert b["user"][1] - a["user"][1] >= spun
        assert b["process"][1] - a["process"][1] >= spun
        assert b["user"][0] == a["user"][0]

    def test_prune_folds_a_dead_threads_spans_into_its_role(self):
        """A record whose thread is gone (no thread ended it): prune drops
        the ident's entries and keeps the numbers under the role."""
        dead = max(list(_prof._threads) + [threading.get_ident()]) + 1
        st = _prof._threads[dead] = _prof._ThreadSpans()
        st.stats["rpc.parse"] = [2, 900, 700, 0, 0]
        _prof._roles[dead] = "test.pruned"
        before = _prof.spans_by_role()["test.pruned"]["rpc.parse"]
        import sys as _sys
        _prof.prune(_sys._current_frames().keys())
        assert dead not in _prof._threads and dead not in _prof._roles
        assert _prof.spans_by_role()["test.pruned"]["rpc.parse"] == before
        _prof.prune(_sys._current_frames().keys())      # once, not twice
        assert _prof.spans_by_role()["test.pruned"]["rpc.parse"] == before

    def test_collections_are_counted_by_one_hook(self):
        import gc

        with _prof.span("engine.reap"):     # the first span installs it
            pass
        assert gc.callbacks.count(_prof._on_gc) == 1
        n, pause, worst = _prof.gc_pauses()
        gc.collect()
        n1, pause1, worst1 = _prof.gc_pauses()
        assert n1 == n + 1 and pause1 > pause and worst1 >= worst
        assert worst1 <= pause1


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

    def test_host_has_its_seven_groups(self, ran):
        host = ran["snap"]["host"]
        assert set(host) == HOST_KEYS
        assert set(host["loop"]) == set(ran["snap"]["span_us"])
        for name, (own, long_n, long_own) in host["loop"].items():
            # the self time span_us shows, beside its long closes
            assert own == ran["snap"]["span_us"][name][2]
            assert long_n >= 0 and 0 <= long_own <= own
        assert {"process", "runtime"} <= set(host["threads"])
        assert set(host["lane_wait"]) == {"request", "response", "stream"}
        assert len(host["gc"]) == 3

    def test_the_engine_says_which_of_its_spans_wait(self, ran):
        """``waits`` names the loop's spans that wait by design, with the
        CPU used inside them: the idle wait sleeps, so it took less CPU
        than wall, and all the waits together less than the loop thread
        used."""
        host = ran["snap"]["host"]
        assert {"engine.idle", "model.sync"} <= set(host["waits"]) \
            <= {"engine.idle", "engine.pool_wait", "model.sync"}
        assert 0 <= host["waits"]["engine.idle"] \
            < host["loop"]["engine.idle"][0]
        assert all(cpu >= 0 for cpu in host["waits"].values())
        assert sum(host["waits"].values()) \
            <= ran["engine"].snapshot()["host"]["threads"]["serving"][1]

    def test_the_loops_spans_are_in_the_serving_role(self, ran):
        """``spans`` sums every thread by role, live and ended (this
        engine's loop thread has ended; other engines' loops add theirs)."""
        host = ran["engine"].snapshot()["host"]
        serving = host["spans"]["serving"]
        for name, (own, _n, _l) in host["loop"].items():
            count, role_own = serving[name]
            assert count >= ran["snap"]["span_us"][name][0]
            assert role_own >= own - 0.1
        assert host["threads"]["serving"][1] > 0

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


def _numbers(tree):
    """Every number of a nested snapshot group, by its path."""
    if isinstance(tree, dict):
        return {(k,) + path: v for k, sub in tree.items()
                for path, v in _numbers(sub).items()}
    if isinstance(tree, list):
        return {(i,) + path: v for i, sub in enumerate(tree)
                for path, v in _numbers(sub).items()}
    return {(): tree}


def test_host_differences_across_a_served_generate(served):
    """Every number of ``host`` is cumulative: across a request none goes
    down (a maximum stays or rises), and the loop thread took CPU."""
    engine, _server, stub = served
    a = engine.snapshot()
    _generate(stub, _prompt(41, 24), 3)
    b = engine.snapshot()
    before, after = _numbers(a["host"]), _numbers(b["host"])
    assert set(before) <= set(after)
    down = {path: (before[path], after[path]) for path in before
            if after[path] < before[path] and path[0] != "threads"}
    assert not down
    # thread COUNTS may fall (a fiber worker that ended); CPU does not
    cpu_down = {path: (before[path], after[path]) for path in before
                if path[0] == "threads" and path[-1] == 1
                and after[path] < before[path]}
    assert not cpu_down
    assert b["host"]["wall_us"] > a["host"]["wall_us"]
    step = [y - x for x, y in zip(a["host"]["loop"]["engine.step"],
                                  b["host"]["loop"]["engine.step"])]
    assert step[0] > 0
    assert b["host"]["threads"]["serving"][1] \
        > a["host"]["threads"]["serving"][1]
    assert b["steps"] > a["steps"]
    # span_us and loop_share keep their shape
    assert all(len(v) == 3 for v in b["span_us"].values())
    assert set(b["loop_share"]) == set(b["span_us"])


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
        assert set(snap["host"]) == HOST_KEYS
    else:
        assert "queue_wait_us mean=" in body
        assert "loop: " in body and "engine.idle=" in body
        host = [ln for ln in body.splitlines() if ln.startswith("  host: ")]
        assert len(host) == 1
        assert "cpu_s " in host[0] and "serving=" in host[0] \
            and "process=" in host[0] and "runtime=" in host[0]
        assert "lane_wait_us request=" in host[0] and "gc=" in host[0]


# ------------------------------------------------------ over the native lane
def _lane_built() -> bool:
    from brpc_tpu.rpc.native_transport import dataplane_available
    return dataplane_available()


needs_lane = pytest.mark.skipif(not _lane_built(),
                                reason="native engine unavailable")


def _server_spans(method, start_from=0):
    from brpc_tpu.trace import span as _span

    end = time.monotonic() + 5.0
    while time.monotonic() < end:
        mine = [s for s in _span.recent_spans(50)
                if s.kind == _span.KIND_SERVER and s.method == method]
        if len(mine) > start_from:
            return mine
        time.sleep(0.01)
    raise AssertionError(f"no server span of {method} reached the span DB")


@pytest.fixture()
def rpcz_on():
    from brpc_tpu.metrics.collector import global_collector
    from brpc_tpu.trace import span as _span

    _flags.set_flag("rpcz_sample_ratio", "1.0")
    _flags.set_flag("collector_max_samples_per_second", "0")
    global_collector()._deny_until = 0.0
    _span.reset_for_test()
    yield
    _flags.set_flag("collector_max_samples_per_second", "1000")


@needs_lane
def test_the_lanes_stamp_round_trips_a_generate(served, rpcz_on):
    """A Generate over the native lane: the frame is stamped where it
    leaves the wire (DpEvent.t_ns), the poller counts its wait, and the
    request's arrival is that stamp, so rpcz shows ``queue_us``."""
    from brpc_tpu.proto import serving_pb2
    from brpc_tpu.rpc import (Channel, ChannelOptions, Server, ServerOptions,
                              Stub)
    from brpc_tpu.rpc.native_transport import lane_wait

    engine, _server, _stub = served
    server = Server(ServerOptions(native_dataplane=True)) \
        .add_service(LlmServingService(engine)).start("127.0.0.1:0")
    try:
        ch = Channel(ChannelOptions(native_transport=True,
                                    timeout_ms=120_000))
        ch.init(str(server.listen_endpoint()))
        stub = Stub(ch, serving_pb2.DESCRIPTOR.services_by_name["LlmService"])
        a = lane_wait()
        t0 = time.perf_counter_ns()
        _generate(stub, _prompt(42, 20), 3)
        elapsed_ns = time.perf_counter_ns() - t0
        b = lane_wait()
        host = engine.snapshot()["host"]["lane_wait"]
    finally:
        server.stop()
        server.join(timeout=2)
    # client and server share this process: one request in, its response
    # back, and the token frames (the final one included)
    assert b["request"][0] - a["request"][0] == 1
    assert b["response"][0] - a["response"][0] == 1
    assert b["stream"][0] - a["stream"][0] >= 1
    for kind in ("request", "response", "stream"):
        wait = b[kind][1] - a[kind][1]
        assert 0 <= wait < 1_000_000_000 and wait <= elapsed_ns * (
            b[kind][0] - a[kind][0])
        assert b[kind][2] >= a[kind][2] and b[kind][2] <= b[kind][1]
        assert host[kind][0] >= b[kind][0]      # the engine shows the same
    phases = _server_spans("Generate")[0].phases
    assert 0 <= phases["queue_us"] < 1e6
    assert phases["serving_queue_us"] >= 0


@needs_lane
def test_the_fast_paths_span_gets_queue_us_from_the_stamp(rpcz_on):
    """A plain unary call rides EV_REQUEST: the poller hands the lane's
    stamp on as the request's arrival."""
    from brpc_tpu.proto import echo_pb2
    from brpc_tpu.rpc import (Channel, ChannelOptions, Server, ServerOptions,
                              Service, Stub)
    from brpc_tpu.rpc.native_transport import lane_wait

    class Echo(Service):
        DESCRIPTOR = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]

        def Echo(self, cntl, request, done):
            return echo_pb2.EchoResponse(message=request.message)

    server = Server(ServerOptions(native_dataplane=True)) \
        .add_service(Echo()).start("127.0.0.1:0")
    try:
        ch = Channel(ChannelOptions(protocol="trpc_std",
                                    native_transport=True, timeout_ms=5000))
        ch.init(str(server.listen_endpoint()))
        a = lane_wait()
        resp = Stub(ch, Echo.DESCRIPTOR).Echo(
            echo_pb2.EchoRequest(message="stamped"))
        b = lane_wait()
    finally:
        server.stop()
        server.join(timeout=2)
    assert resp.message == "stamped"
    assert b["request"][0] - a["request"][0] == 1
    assert 0 <= b["request"][1] - a["request"][1] < 1_000_000_000
    assert 0 <= _server_spans("Echo")[0].phases["queue_us"] < 1e6


@pytest.mark.parametrize("stamped", [True, False])
def test_the_fast_paths_deadline_starts_at_the_arrival(stamped):
    """The budget of a request that came with the lane's stamp starts
    where it left the wire, as on the Python lanes; one with no stamp
    (a caller that has none) starts it at the dispatch."""
    from brpc_tpu.proto import echo_pb2
    from brpc_tpu.rpc import Server, Service
    from brpc_tpu.rpc import server_processing as sp_mod

    seen = []

    class Echo(Service):
        DESCRIPTOR = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]

        def Echo(self, cntl, request, done):
            seen.append(cntl.deadline_mono)
            return echo_pb2.EchoResponse(message=request.message)

    class _FakeDp:
        responses = []

        def respond(self, conn, cid, attempt, code, err, payload,
                    attachment, q, compress_type=0):
            self.responses.append(code)

    class _FakeSock:
        _dp = _FakeDp()
        conn_id = 17
        peer_str = remote = "fake:0"

    server = Server().add_service(Echo()).start("127.0.0.1:0")
    try:
        body = echo_pb2.EchoRequest(message="late").SerializeToString()
        arrival = time.monotonic() - 0.25 if stamped else 0.0
        t0 = time.monotonic()
        sp_mod.fast_process_request(
            (server, _FakeSock(), "EchoService", "Echo", 7, 1, 0, 0, 0, 0,
             5000, body, arrival))
        t1 = time.monotonic()
    finally:
        server.stop()
        server.join(timeout=2)
    assert _FakeSock._dp.responses == [0] and len(seen) == 1
    if stamped:
        assert seen[0] == arrival + 5.0
    else:
        assert t0 + 5.0 <= seen[0] <= t1 + 5.0


@needs_lane
def test_the_lanes_last_cpu_reading_stands_after_shutdown():
    """An engine of its own, shut down: its threads are gone from the
    role table's counts, what they used is not (a cumulative number that
    fell to 0 would difference to less than nothing)."""
    from brpc_tpu.rpc.native_transport import NativeDataplane

    dp = NativeDataplane()
    try:
        live = dp.thread_stats()
    finally:
        dp.shutdown()
    after = dp.thread_stats()
    assert live["lane.loop"][0] >= 1
    assert after["lane.loop"][0] == after["lane.sender"][0] == 0
    assert after["lane.loop"][1] >= live["lane.loop"][1]
    assert after == dp.thread_stats()


@needs_lane
def test_the_lanes_threads_are_in_the_role_table():
    from brpc_tpu.rpc.native_transport import get_dataplane, lane_cpu

    dp = get_dataplane()
    stats = dp.thread_stats()
    assert set(stats) == {"lane.loop", "lane.sender"}
    assert stats["lane.loop"][0] >= 2 and stats["lane.loop"][1] > 0
    table = _prof.cpu_by_role(lane_cpu())
    assert table["lane.loop"][0] == stats["lane.loop"][0]
    assert table["lane.loop"][1] >= stats["lane.loop"][1]
    assert table["lane.sender"][1] >= 0


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

    def test_decode_frames_are_written_on_the_writer_threads_line(
            self, trace):
        """A prefill's first-token frame stays on the loop's line (the
        case above); every later frame is written on the writer thread's
        line, inside its ``engine.flush``, and so are the responses of
        the sequences that had frames there."""
        loop = {e.line for e in trace if e.name == "engine.step"}
        flushes = [e for e in trace if e.name == "engine.flush"]
        lines = {e.line for e in flushes}
        assert len(lines) == 1 and not lines & loop
        writes = [e for e in trace if e.name == "rpc.stream_write"]
        on_writer = [w for w in writes if w.line in lines]
        assert all(any(f.holds(w) for f in flushes) for w in on_writer)
        # one frame a token: the first from the loop, the rest from here
        assert len(on_writer) == sum(new - 1 for _p, new in TRACED)
        assert sum(1 for w in writes if w.line in loop) == len(TRACED)
        assert sum(1 for e in trace if e.name == "rpc.respond"
                   and e.line in lines) == sum(1 for _p, new in TRACED
                                               if new > 1)

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
