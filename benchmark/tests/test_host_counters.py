"""The six host counters (``harness/host_counters.py``): each reader on two
snapshots small enough to work by hand, None without ``host``, their twelve
entries in the manifest, and a traced rehearsal in which each finds
something to read.

    python -m pytest benchmark/tests/test_host_counters.py -q     (CPU)
"""

import importlib.util
import json
import math
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import host_counters, manifest  # noqa: E402

NAMES = ["loop_cpu_ms", "loop_offcpu_ms", "loop_stall_ms",
         "contender_cpu_share", "client_cpu_share", "lane_wait_ms"]
DECODE_CELLS = ["chat-steady", "reason-steady", "rag-steady",
                "longdoc-steady"]

# a window of 2 s of wall time and 100 steps. The loop thread's spans,
# [self_us, long_n, long_self_us], and the CPU inside those that wait:
#   engine.idle     waits: +900 000 of wall, +1 000 of CPU    (left out)
#   model.sync      waits: +500 000, +20 000, one long close  (left out)
#   model.launch    working: +200 000 of wall
#   engine.commit   working: +100 000, one close of 60 000 us
#   rpc.stream_write  working, new in the window: 50 000
# the loop thread (role serving) used +241 000 of CPU, 21 000 of it waiting:
# working spans: wall 350 000, CPU 220 000, long 60 000
SNAP0 = {
    "steps": 400,
    "host": {
        "wall_us": 10_000_000.0,
        "loop": {"engine.idle": [5_000_000.0, 3, 400_000.0],
                 "model.sync": [1_000_000.0, 0, 0.0],
                 "model.launch": [300_000.0, 0, 0.0],
                 "engine.commit": [80_000.0, 0, 0.0]},
        "waits": {"engine.idle": 9_000.0, "model.sync": 30_000.0},
        "spans": {
            "serving": {"model.launch": [400, 300_000.0]},
            "poller": {"rpc.on_response": [50, 9_000.0],
                       "rpc.parse": [50, 4_000.0]},
            "user": {"rpc.call": [50, 70_000.0]},
        },
        "threads": {"serving": [1, 500_000.0], "poller": [1, 100_000.0],
                    "user": [3, 2_000_000.0], "lane.loop": [4, 50_000.0],
                    "runtime": [0, 350_000.0], "process": [9, 3_000_000.0]},
        "lane_wait": {"request": [50, 10_000.0, 900.0],
                      "response": [50, 20_000.0, 800.0],
                      "stream": [500, 90_000.0, 700.0]},
        "gc": [4, 30_000.0, 12_000.0],
    },
}
SNAP1 = {
    "steps": 500,
    "host": {
        "wall_us": 12_000_000.0,
        "loop": {"engine.idle": [5_900_000.0, 4, 460_000.0],
                 "model.sync": [1_500_000.0, 1, 70_000.0],
                 "model.launch": [500_000.0, 0, 0.0],
                 "engine.commit": [180_000.0, 1, 60_000.0],
                 "rpc.stream_write": [50_000.0, 0, 0.0]},
        # the pool filled once in the window: a wait that is new in it
        "waits": {"engine.idle": 10_000.0, "model.sync": 50_000.0,
                  "engine.pool_wait": 0.0},
        "spans": {
            "serving": {"model.launch": [500, 500_000.0]},
            "poller": {"rpc.on_response": [90, 25_000.0],
                       "rpc.parse": [90, 9_000.0]},
            "worker": {"rpc.call": [10, 8_000.0]},
            "user": {"rpc.call": [90, 150_000.0]},
        },
        # process +1 400 000, serving +241 000, user +600 000
        "threads": {"serving": [1, 741_000.0], "poller": [1, 200_000.0],
                    "worker": [2, 30_000.0],
                    "user": [3, 2_600_000.0], "lane.loop": [4, 90_000.0],
                    "runtime": [0, 739_000.0], "process": [11, 4_400_000.0]},
        "lane_wait": {"request": [90, 30_000.0, 2_500.0],
                      "response": [90, 50_000.0, 800.0],
                      "stream": [900, 190_000.0, 700.0]},
        "gc": [5, 40_000.0, 12_000.0],
    },
}
BY_HAND = {
    "loop_cpu_ms": (241_000 - 21_000) / 100 / 1e3,          # 2.2 ms a step
    "loop_offcpu_ms": (350_000 - 220_000) / 100 / 1e3,      # 1.3 ms a step
    "loop_stall_ms": 60_000 / 1e3 / 2.0,                    # 30 ms a second
    "contender_cpu_share": 100 * (1_400_000 - 241_000) / 2_000_000,
    "client_cpu_share": 100 * 600_000 / 2_000_000,          # the user role
    "lane_wait_ms": (30_000 - 10_000) / 40 / 1e3,           # 0.5 ms
}


def _reader(name, phase):
    path = os.path.join(BENCH, "layer_metrics", f"{name}.{phase}.py")
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}_{phase}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(snap0, snap1):
    return types.SimpleNamespace(window={"snap0": snap0, "snap1": snap1},
                                 reduced=None, program_spans=None)


@pytest.mark.parametrize("phase", ["prefill", "decode"])
@pytest.mark.parametrize("name", NAMES)
def test_each_reader_gives_the_number_worked_out_by_hand(name, phase):
    got = _reader(name, phase)(_run(SNAP0, SNAP1))
    assert got == pytest.approx(BY_HAND[name], rel=1e-12)


def test_cpu_and_offcpu_add_up_to_the_working_spans_wall_time():
    run = _run(SNAP0, SNAP1)
    assert (host_counters.loop_cpu_ms(run) + host_counters.loop_offcpu_ms(run)
            == pytest.approx(350_000 / 100 / 1e3))
    assert host_counters.client_cpu_share(run) \
        <= host_counters.contender_cpu_share(run)


def test_the_program_says_which_spans_wait_and_the_readers_keep_no_list():
    assert not hasattr(host_counters, "WAITING")
    # all of the loop's time, the waiting spans with it
    everything = sum(b[0] - SNAP0["host"]["loop"].get(n, [0])[0]
                     for n, b in SNAP1["host"]["loop"].items())
    assert everything == 350_000 + 900_000 + 500_000
    # a span the program names as waiting leaves the working sums: were
    # model.launch one, its 200 000 us of wall would go with it
    snap1 = json.loads(json.dumps(SNAP1))
    snap0 = json.loads(json.dumps(SNAP0))
    snap0["host"]["waits"]["model.launch"] = 0.0
    snap1["host"]["waits"]["model.launch"] = 0.0
    run = _run(snap0, snap1)
    assert (host_counters.loop_cpu_ms(run) + host_counters.loop_offcpu_ms(run)
            == pytest.approx(150_000 / 100 / 1e3))


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("snaps", [
    (None, None),
    ({"steps": 1}, {"steps": 2}),                          # an older program
    (SNAP0, {"steps": 500}),
], ids=["no-snapshots", "no-host", "host-on-one-side"])
def test_without_host_a_reader_gives_none_and_does_not_raise(name, snaps):
    assert _reader(name, "decode")(_run(*snaps)) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_window_in_which_nothing_happened_reads_none_not_a_division(name):
    assert _reader(name, "prefill")(_run(SNAP0, SNAP0)) is None


def test_the_manifest_ends_with_the_twelve_entries_and_is_clean():
    man = manifest.load(ROOT)
    assert manifest.check(man, ROOT) == []
    mine = man["per_layer"][-12:]
    assert [m["name"] for m in mine] == [
        f"{n}.{p}" for n in NAMES for p in ("prefill", "decode")]
    for m in mine:
        name, phase = m["name"].rsplit(".", 1)
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["layer"] == ("Scheduler" if name.startswith("loop_")
                              else "RPC / host path")
        assert m["unit"] == {"loop_stall_ms": "ms/s",
                             "contender_cpu_share": "%",
                             "client_cpu_share": "%"}.get(name, "ms")
        if phase == "prefill":
            assert (m["moves"], m["workloads"]) == ("ttft_p90_ms",
                                                    ["prefill-closed"])
        else:
            assert m["workloads"] == DECODE_CELLS
            assert m["moves"] == ("answer_mean_ms" if name == "lane_wait_ms"
                                  else "gap_mean_ms")
    # layers the manifest already named
    assert {m["layer"] for m in mine} <= {
        m["layer"] for m in man["per_layer"][:-12]}


def test_run_py_check_passes():
    out = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                          "--check"], capture_output=True, text=True,
                         timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]


@pytest.mark.parametrize("cell,phase", [("prefill-closed", "prefill"),
                                        ("chat-steady", "decode")])
def test_a_traced_rehearsal_reads_all_six(cell, phase):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--rehearse-cpu", "--trace", "1", "--seed", str(2**31 + 3737),
         "--seconds", "6"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env={k: v for k, v in os.environ.items()
             if k not in ("XLA_FLAGS", "JAX_PLATFORMS")})
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    result = json.loads(line[line.index("{"):])
    assert result["correct"] is True, result["checks"]
    got = {n: result["metrics"][f"{n}.{phase}"] for n in NAMES}
    assert all(math.isfinite(m["value"]) and m["value"] >= 0
               for m in got.values()), got
    assert got["loop_cpu_ms"]["value"] > 0
    assert got["client_cpu_share"]["value"] \
        <= got["contender_cpu_share"]["value"]
