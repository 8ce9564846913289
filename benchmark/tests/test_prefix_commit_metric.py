"""``prefix_commit_ms``: the reader on timelines small enough to work by
hand, its entry in the manifest, and a traced rehearsal of its cell in which
it finds something to read.

    python -m pytest benchmark/tests/test_prefix_commit_metric.py -q     (CPU)
"""

import importlib.util
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

NAME = "prefix_commit_ms"
CELL = "prefill-closed"


def ev(name, lo, hi, thread="loop", **ids):
    return ["brpc." + name, float(lo), float(hi - lo), thread, ids]


# two prefill steps by hand (ns), window 0..1000: each reaps one finished
# sequence, whose commit into the radix tree lies inside the reap
#   step 0..480 { prefill 10..300, reap 300..480 { prefix_commit 320..440 } }
#   step 500..1000 { prefill 510..800, reap 800..990 { prefix_commit 830..890 } }
HOST = [
    ev("engine.step", 0, 480, step=1, batch=0),
    ev("engine.prefill", 10, 300, seq=1, n=400),
    ev("engine.reap", 300, 480, finished=1),
    ev("engine.prefix_commit", 320, 440, seq=1),
    ev("engine.step", 500, 1000, step=2, batch=0),
    ev("engine.prefill", 510, 800, seq=2, n=900),
    ev("engine.reap", 800, 990, finished=1),
    ev("engine.prefix_commit", 830, 890, seq=2),
    # a second engine's loop would be another thread: not this cell's
    ev("engine.prefix_commit", 100, 900, "other", seq=7),
]
BUSY = [(20.0, 290.0), (520.0, 790.0)]


def _read(spans, traced=True):
    path = os.path.join(BENCH, "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + NAME, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    run = types.SimpleNamespace(program_spans=spans, window={},
                                reduced=object() if traced else None,
                                say=lambda _row: None)
    return mod.read(run)


def test_mean_time_of_the_loop_threads_commit_spans():
    spans = ps.ProgramSpans(HOST, BUSY, 0.0, 1000.0)
    assert spans.loop == "loop"
    assert _read(spans) == pytest.approx((120 + 60) / 2 / 1e6)


def test_a_commit_cut_by_the_windows_edge_counts_for_its_part_inside():
    spans = ps.ProgramSpans(HOST, BUSY, 400.0, 1000.0)
    assert _read(spans) == pytest.approx((40 + 60) / 2 / 1e6)
    # wholly outside: one span left
    spans = ps.ProgramSpans(HOST, BUSY, 450.0, 1000.0)
    assert _read(spans) == pytest.approx(60 / 1e6)


@pytest.mark.parametrize("host", [
    [],                                                   # no brpc.* span
    [e for e in HOST if e[0] != "brpc.engine.prefix_commit"],   # none of these
    [e for e in HOST if e[3] != "loop"],                  # no loop thread
], ids=["no-spans", "no-commit-span", "no-loop-thread"])
def test_nothing_to_read_is_none_and_never_zero(host):
    assert _read(ps.ProgramSpans(host, BUSY, 0.0, 1000.0)) is None


def test_an_untraced_run_reads_none():
    assert _read(None, traced=False) is None


def test_the_manifest_has_the_entry_last_and_is_clean():
    man = manifest.load(ROOT)
    assert manifest.check(man, ROOT) == []
    assert man["per_layer"][-1] == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "KV manager",
        "moves": "ttft_p90_ms", "workloads": [CELL]}
    layers = {m["layer"] for m in man["per_layer"][:-1]}
    assert "KV manager" in layers          # a layer the manifest already names


def test_a_traced_rehearsal_reads_it():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--trace", "1", "--seed", str(2**31 + 2727),
         "--seconds", "6"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={k: v for k, v in os.environ.items()
             if k not in ("XLA_FLAGS", "JAX_PLATFORMS")})
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    result = json.loads(line[line.index("{"):])
    assert result["correct"] is True, result["checks"]
    got = result["metrics"][NAME]
    assert got["unit"] == "ms" and 0 < got["value"] < 1000
