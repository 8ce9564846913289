"""The readers of the program's own spans: the pure stage of
``harness/program_spans.py`` on timelines small enough to work by hand, the
manifest with the nine metrics that read them, and a traced rehearsal of
each serving cell in which every one of them finds something to read.

    python -m pytest benchmark/tests/test_program_spans.py -q     (CPU)
"""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import manifest, program_spans as ps  # noqa: E402

NEW = {"prefill-closed": ["queue_wait_ms.prefill",
                          "idle_engine_waiting.prefill",
                          "idle_engine_working.prefill",
                          "rpc_host_ms_per_request"],
       "chat-steady": ["queue_wait_ms.decode", "idle_engine_waiting.decode",
                       "idle_engine_working.decode", "decode_step_host_ms",
                       "prefill_share_of_step"]}


def ev(name, lo, hi, thread="loop", **ids):
    return ["brpc." + name, float(lo), float(hi - lo), thread, ids]


# one decode step by hand (ns), window 0..1000, device busy 300..600 and
# 620..700:
#   idle 0..100 | admit 100..150 | step 150..900 { decode_prep 160..200,
#   model.decode 200..800 { prep 210..280, launch 280..320, sync 320..780 },
#   commit 800..880 { stream_write 820..860 } } | idle 900..1000
HOST = [
    ev("engine.idle", 0, 100), ev("engine.admit", 100, 150, admitted=0),
    ev("engine.step", 150, 900, step=4, batch=2),
    ev("engine.decode_prep", 160, 200, batch=2),
    ev("model.decode", 200, 800, B=2, b_bucket=2, l_bucket=32),
    ev("model.prep", 210, 280), ev("model.launch", 280, 320),
    ev("model.sync", 320, 780), ev("engine.commit", 800, 880, batch=2),
    ev("rpc.stream_write", 820, 860, stream=9),
    ev("engine.idle", 900, 1000),
    # an RPC thread: one request's three phases side by side, the submit
    # inside execute; and the client's thread
    ev("rpc.parse", 10, 30, "rpc", cid=5), ev("rpc.execute", 30, 90, "rpc",
                                              cid=5),
    ev("engine.submit", 40, 80, "rpc", seq=3, cid=5),
    ev("rpc.respond", 905, 925, "rpc", cid=5),
    ev("rpc.respond", 930, 940, "rpc"),          # reopened after a send
    ev("rpc.call", 0, 8, "client", cid=5),
    ev("rpc.on_response", 950, 960, "client", cid=5),
]
BUSY = [(300.0, 600.0), (620.0, 700.0)]


@pytest.fixture
def spans():
    return ps.ProgramSpans(HOST, BUSY, 0.0, 1000.0)


def test_leaf_segments_are_the_innermost_span_at_every_instant():
    segs = ps.leaf_segments([(s, s + d, n) for n, s, d, t, _i in HOST
                             if t == "loop"])
    assert segs == [
        (0, 100, "brpc.engine.idle"), (100, 150, "brpc.engine.admit"),
        (150, 160, "brpc.engine.step"), (160, 200, "brpc.engine.decode_prep"),
        (200, 210, "brpc.model.decode"), (210, 280, "brpc.model.prep"),
        (280, 320, "brpc.model.launch"), (320, 780, "brpc.model.sync"),
        (780, 800, "brpc.model.decode"), (800, 820, "brpc.engine.commit"),
        (820, 860, "brpc.rpc.stream_write"), (860, 880, "brpc.engine.commit"),
        (880, 900, "brpc.engine.step"), (900, 1000, "brpc.engine.idle")]
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
    # a child that outlasts its parent is cut to it; an empty one is dropped
    assert ps.leaf_segments([(0, 10, "a"), (5, 15, "b"), (7, 7, "c")]) == [
        (0, 5, "a"), (5, 10, "b")]


def test_the_loop_thread_is_the_one_that_holds_the_steps(spans):
    assert spans.loop == "loop" and bool(spans)
    assert spans.loop_cover() == pytest.approx(1.0)
    assert not ps.ProgramSpans([], BUSY, 0.0, 1000.0)    # the parent: none
    only_rpc = [e for e in HOST if e[3] != "loop"]
    assert not ps.ProgramSpans(only_rpc, BUSY, 0.0, 1000.0)


def test_a_gap_that_straddles_leaves_is_split_by_overlap(spans):
    assert spans.idle == [(0, 300), (600, 620), (700, 1000)]
    by, longest = spans.idle_by_leaf()
    # 0..300 straddles idle, admit, step, decode_prep, model.decode, prep,
    # and half of launch; its MIDDLE (150) names one of them only
    assert by == {
        "brpc.engine.idle": 100 + 100, "brpc.engine.admit": 50,
        "brpc.engine.step": 10 + 20, "brpc.engine.decode_prep": 40,
        "brpc.model.decode": 10 + 20, "brpc.model.prep": 70,
        "brpc.model.launch": 20, "brpc.model.sync": 20 + 80,
        "brpc.engine.commit": 20 + 20, "brpc.rpc.stream_write": 40}
    assert longest == 0
    assert sum(by.values()) == 300 + 20 + 300


def test_waiting_plus_working_is_the_idle_share(spans):
    waiting, working = spans.idle_shares()
    assert waiting == pytest.approx(20.0)
    assert working == pytest.approx(42.0)
    idle_share = 100.0 * (1000 - 380) / 1000
    assert waiting + working == pytest.approx(idle_share)


def test_idle_that_no_span_covers_is_named_so_and_counted_in_neither():
    """A hole inside the timeline is ``(no span)``; what lies before the
    first leaf or after the last (a span open when the profiler's session
    starts or stops is never recorded) is the window's edge."""
    host = [e for e in HOST if e[0] != "brpc.engine.admit"
            and not (e[0] == "brpc.engine.idle" and e[1] == 900)]
    spans = ps.ProgramSpans(host, BUSY, 0.0, 1000.0)
    by, longest = spans.idle_by_leaf()
    assert by[ps.UNNAMED] == 50 and longest == 50      # admit's place
    assert by[ps.EDGE] == 100                          # the last idle wait's
    waiting, working = spans.idle_shares()
    assert (waiting, working) == (pytest.approx(10.0), pytest.approx(37.0))
    assert spans.loop_cover() == pytest.approx(0.85)
    rows = spans.table()
    assert any("(no span)" in r for r in rows)
    assert any("(window edge)" in r for r in rows)


def test_sync_idle_by_where_it_lies_in_the_launch(spans):
    # sync 320..780: the device is busy from before it to 600, 620..700
    assert spans.sync_idle() == {"head": 0, "between": 20, "tail": 80}
    late = ps.ProgramSpans(HOST, [(400.0, 500.0)], 0.0, 1000.0)
    assert late.sync_idle() == {"head": 80, "between": 0, "tail": 280}
    done_before = ps.ProgramSpans(HOST, [(290.0, 310.0)], 0.0, 1000.0)
    assert done_before.sync_idle() == {"head": 0, "between": 0, "tail": 460}


def test_spans_are_cut_at_the_windows_edges():
    spans = ps.ProgramSpans(HOST, BUSY, 50.0, 950.0)
    assert spans.window_ns == 900
    by, _ = spans.idle_by_leaf()
    assert by["brpc.engine.idle"] == 50 + 50
    assert spans.count("brpc.rpc.call") == 0       # wholly before the window


def _run(spans, **window):
    said = []
    run = types.SimpleNamespace(program_spans=spans, reduced=object(),
                                window=window, say=said.append)
    return run, said


def _reader(name):
    import importlib.util

    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_readers_on_the_hand_worked_timeline(spans):
    snap0 = {"queue_wait_us_sum": 1000.0, "admitted": 2}
    snap1 = {"queue_wait_us_sum": 9000.0, "admitted": 6}
    run, _said = _run(spans, snap0=snap0, snap1=snap1)
    assert _reader("queue_wait_ms.prefill")(run) == pytest.approx(2.0)
    assert _reader("queue_wait_ms.decode")(run) == pytest.approx(2.0)
    assert _reader("idle_engine_waiting.decode")(run) == pytest.approx(20.0)
    assert _reader("idle_engine_working.prefill")(run) == pytest.approx(42.0)
    # leaf time of brpc.rpc.* on every thread: stream_write 40, parse 20,
    # execute 60 - 40 (the submit inside it), respond 20 + 10, call 8,
    # on_response 10; one respond carries a correlation id: one request
    assert _reader("rpc_host_ms_per_request")(run) == pytest.approx(
        (40 + 20 + 20 + 30 + 8 + 10) / 1e6)
    # the step 150..900 holds a decode: 750 ns less 380 busy
    assert _reader("decode_step_host_ms")(run) == pytest.approx(370 / 1e6)
    assert _reader("prefill_share_of_step")(run) == 0.0


def test_readers_find_nothing_in_a_program_without_the_spans():
    """The parent of the PR that added the spans: no ``brpc.*`` event, no
    ``queue_wait_us_sum`` in the snapshot. Every reader returns None."""
    old = {"steps": 3, "tokens_generated": 9}
    run, _said = _run(ps.ProgramSpans([], BUSY, 0.0, 1000.0),
                      snap0=old, snap1=dict(old, steps=5))
    for cell in NEW:
        for name in NEW[cell]:
            assert _reader(name)(run) is None, name
    untraced, _ = _run(None)
    untraced.reduced = None
    assert _reader("idle_engine_waiting.prefill")(untraced) is None


def test_manifest_is_clean_with_the_nine_new_metrics():
    man = manifest.load(ROOT)
    assert manifest.check(man, ROOT) == []
    assert len(man["per_layer"]) == 25
    by_name = {m["name"]: m for m in man["per_layer"]}
    for cell, names in NEW.items():
        for name in names:
            assert by_name[name]["workloads"] == [cell]
            assert by_name[name]["source"] in ("program_span",
                                               "program_counter")
    # appended, nothing that was there moved
    assert [m["name"] for m in man["per_layer"][16:]] == [
        "queue_wait_ms.prefill", "queue_wait_ms.decode",
        "idle_engine_waiting.prefill", "idle_engine_working.prefill",
        "idle_engine_waiting.decode", "idle_engine_working.decode",
        "rpc_host_ms_per_request", "decode_step_host_ms",
        "prefill_share_of_step"]


@pytest.mark.parametrize("cell", sorted(NEW))
def test_a_traced_rehearsal_reads_every_new_metric(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--rehearse-cpu", "--trace", "1", "--seed", str(2**31 + 1234),
         "--seconds", "6"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={k: v for k, v in os.environ.items()
             if k not in ("XLA_FLAGS", "JAX_PLATFORMS")})
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    assert line.startswith("[REHEARSAL cpu]")
    result = json.loads(line[line.index("{"):])
    assert result["correct"] is True, result["checks"]
    metrics = result["metrics"]
    for name in NEW[cell]:
        assert name in metrics, (name, sorted(metrics))
    kind = "prefill" if cell == "prefill-closed" else "decode"
    idle = metrics[f"idle_share.{kind}"]["value"]
    named = (metrics[f"idle_engine_waiting.{kind}"]["value"]
             + metrics[f"idle_engine_working.{kind}"]["value"])
    # a rehearsal's 3 s window loses up to a span at each edge (a 50 ms
    # idle wait): what no span covers is the difference, and is small
    assert idle - 4.0 <= named <= idle + 1e-6
    assert "by the loop thread's leaf span" in out.stderr
    assert "within brpc.model.sync" in out.stderr
