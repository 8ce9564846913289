"""tools/host_probe.py: the host counters of an UNTRACED benchmark run.

The window's differences on two hand-made ``host`` groups, and one run of a
cell's CPU rehearsal through the tool (``benchmark/run.py`` unedited): the
six counters are there without a profiler. Counts and inequalities only.
"""

import importlib.util
import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "host_probe.py")


def _tool():
    spec = importlib.util.spec_from_file_location("host_probe", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


H0 = {"wall_us": 1_000_000.0,
      "loop": {"engine.idle": [600_000.0, 2, 150_000.0],
               "model.launch": [100_000.0, 0, 0.0]},
      "waits": {"engine.idle": 2_000.0},
      "threads": {"serving": [1, 90_000.0], "process": [4, 400_000.0]},
      "lane_wait": {"request": [10, 900.0, 200.0]},
      "gc": [3, 9_000.0, 5_000.0]}
H1 = {"wall_us": 3_000_000.0,
      "loop": {"engine.idle": [1_600_000.0, 3, 210_000.0],
               "model.launch": [500_000.0, 0, 0.0],
               "model.sync": [590_000.0, 1, 70_000.0]},
      "waits": {"engine.idle": 5_000.0, "model.sync": 40_000.0},
      "threads": {"serving": [1, 390_000.0], "process": [4, 1_400_000.0],
                  "poller": [1, 50_000.0]},
      "lane_wait": {"request": [30, 2_900.0, 700.0]},
      "gc": [5, 19_000.0, 6_000.0]}


def test_the_windows_differences_by_hand():
    d = _tool()._difference(H0, H1, steps=200)
    assert d["wall_s"] == 2.0
    # 1 000 000 + 400 000 + 590 000 of the loop's self time in 2 000 000
    assert d["loop_self_over_wall"] == pytest.approx(0.995)
    assert d["loop_ms_a_step"] == {"engine.idle": [5.0, 1, 60.0],
                                   "model.launch": [2.0, 0, 0.0],
                                   "model.sync": [2.95, 1, 70.0]}
    assert d["waits_cpu_ms_a_step"] == {"engine.idle": 0.015,
                                        "model.sync": 0.2}
    assert d["threads_cpu_s"] == {"serving": 0.3, "process": 1.0,
                                  "poller": 0.05}
    assert d["lane_wait"] == {"request": [20, 2000.0, 700.0]}
    assert d["gc"] == [2, 10_000.0, 6_000.0]


def test_an_untraced_run_prints_the_six_counters(tmp_path):
    out = subprocess.run(
        [sys.executable, TOOL, "--tag", "t", "--out", str(tmp_path),
         "--workload", "chat-steady", "--rehearse-cpu", "--trace", "0",
         "--seed", str(2**31 + 3738), "--seconds", "4"],
        capture_output=True, text=True, timeout=900, cwd=REPO,
        env={k: v for k, v in os.environ.items()
             if k not in ("XLA_FLAGS", "JAX_PLATFORMS")})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("HOSTPROBE ")]
    assert len(lines) == 1
    probe = json.loads(lines[0][len("HOSTPROBE "):])
    assert probe["correct"] is True and probe["rc"] == 0
    # the run was untraced: its own line carries no per-layer metric
    assert "loop_cpu_ms.decode" not in probe["metrics"]
    assert "gap_mean_ms" in probe["metrics"]
    assert set(probe["host"]) == set(_tool().COUNTERS)
    assert all(math.isfinite(v) and v >= 0 for v in probe["host"].values())
    assert probe["host"]["loop_cpu_ms"] > 0
    assert probe["host"]["client_cpu_share"] \
        <= probe["host"]["contender_cpu_share"]
    assert probe["steps"] > 0 and 0.9 < probe["loop_self_over_wall"] <= 1.0
    assert {"engine.idle", "model.sync"} <= set(probe["waits_cpu_ms_a_step"])
    kept = json.load(open(tmp_path / "t.json"))
    assert set(kept) == {"host0", "host1", "steps", "probe"}
    assert kept["host1"]["wall_us"] > kept["host0"]["wall_us"]
