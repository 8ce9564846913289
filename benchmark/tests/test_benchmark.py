"""The benchmark's own tests: the reduction against a recorded trace, the
work counts against hand-worked numbers, the generator, the manifest, the
control at a small size, and whole runs with the timed path broken.

    python -m pytest benchmark/tests -q        (CPU; about three minutes)
"""

import gzip
import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import loadgen, manifest, peaks, trace_reduce, work  # noqa: E402


# ------------------------------------------------------------ trace reduction
@pytest.fixture(scope="module")
def recorded():
    """7 launches (2 prefill, 5 decode) of chat-steady on the v5e, cut from
    the first chip trace of PR 25: 4060 device ops, 202 ms."""
    with gzip.open(os.path.join(HERE, "recorded_trace.json.gz"), "rt") as f:
        return json.load(f)


def _raster(intervals, hi_ns, step=100.0):
    """Busy time by painting a timeline, 100 ns a cell: another way to the
    union than merging intervals."""
    cells = np.zeros(int(hi_ns / step) + 2, dtype=bool)
    for s, e in intervals:
        cells[int(math.floor(s / step)):int(math.ceil(e / step))] = True
    return cells.sum() * step


def test_recorded_trace_busy_union_and_idle_share(recorded):
    red = trace_reduce.Reduced(recorded)
    ops = recorded["devices"]["0"]
    assert red.window_s == pytest.approx(0.202122693)
    painted = _raster([(s, s + d) for s, d, _n in ops], red.window_s * 1e9)
    # painting rounds each of 4060 ops outward by up to 2 cells
    assert red.busy_s("0") * 1e9 == pytest.approx(painted, rel=5e-3)
    assert red.busy_s("0") == pytest.approx(0.173407336, rel=1e-6)
    assert red.idle_share() == pytest.approx(1 - 0.173407336 / 0.202122693)


def test_recorded_trace_device_time_per_annotation(recorded):
    red = trace_reduce.Reduced(recorded)
    spans = [(s, s + d, n) for n, s, d, _t in recorded["host"]
             if n != "bench.window"]
    want = {"bench.prefill": 0.0, "bench.decode": 0.0, "": 0.0}
    for s, d, _n in recorded["devices"]["0"]:   # no op encloses another here
        mid = s + d / 2
        name = next((n for a, b, n in spans if a <= mid < b), "")
        want[name] += d
    assert red.launches("bench.prefill") == 2
    assert red.launches("bench.decode") == 5
    assert red.device_ns_in("bench.prefill") == pytest.approx(
        want["bench.prefill"])
    assert red.device_ns_in("bench.decode") == pytest.approx(
        want["bench.decode"])
    assert red.device_ns_in("bench.decode") == pytest.approx(162551088.0)
    assert red.unattributed_share() == pytest.approx(
        want[""] / sum(want.values()))
    # the gather of the padded context leads, as the first trace showed
    assert red.top_ops(1)[0][0] == "slice_bitcast_fusion"


def test_hand_worked_timeline_gaps_self_time_and_collectives():
    """A timeline small enough to work by hand (ns)."""
    events = {
        "devices": {"0": [
            [100, 400, "while.1"],          # encloses the next two
            [150, 100, "fusion.7"],
            [300, 150, "collective-permute-done.2"],
            [700, 100, "fusion.8"],         # after a 200 ns gap, in decode
            [900, 50, "vmap_jit_flash_attention__.3"],   # 100 ns gap, between
        ]},
        "host": [["bench.window", 0, 1000, "t"],
                 ["bench.prefill", 50, 500, "t"],     # 50..550
                 ["bench.decode", 560, 260, "t"]],    # 560..820
    }
    red = trace_reduce.Reduced(events)
    assert red.busy_s("0") * 1e9 == pytest.approx(400 + 100 + 50)
    assert red.idle_share() == pytest.approx(0.45)
    selfs = {n: x for _s, _e, n, x in red.ops["0"]}
    assert selfs["while.1"] == 150            # 400 less its children's 250
    assert red.device_ns_in("bench.prefill") == 400
    assert red.device_ns_in("bench.decode") == 100
    assert red.collective_ns("0") == 150
    assert red.op_ns("flash")[0] == 50
    gaps = dict(red.idle_gaps())
    # 0..100 lies in no call at its middle (50 is where prefill begins: in),
    # 500..700 has its middle at 600 (decode), 800..900 at 850 and
    # 950..1000 at 975 (between calls)
    assert gaps["in bench.prefill"] * 1e9 == pytest.approx(100)
    assert gaps["in bench.decode"] * 1e9 == pytest.approx(200)
    assert gaps["between calls"] * 1e9 == pytest.approx(150)
    assert trace_reduce.group_name("fusion.12") == "fusion"
    assert trace_reduce.short_name("%a.1 = f32[2]{0} fusion(%b)") == "a.1"


def test_extract_reads_annotations_from_an_xplane_file(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.decode"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = trace_reduce.extract(trace_reduce.find_xplane(str(tmp_path)),
                                  "cpu")
    names = [h[0] for h in events["host"]]
    assert "bench.window" in names and "bench.decode" in names
    assert events["devices"]["0"]


# ---------------------------------------------------------------- work counts
SERVE = {"vocab": 1000, "d_model": 8, "n_heads": 2, "n_layers": 3}
TRAIN = {"vocab": 1000, "d_model": 8, "n_heads": 2, "n_layers": 3, "d_ff": 20}


@pytest.mark.parametrize("what", ["prefill", "decode", "train"])
def test_work_counts_against_hand_worked_numbers(what):
    if what == "prefill":
        # layer weights 8*24 + 8*8 + 2*8*16 = 512; 10 rows: 2*10*512 = 10240;
        # attention 2*8*10*11 = 1760; 3 layers 36000; head 2*8*1000 = 16000
        assert work.prefill_flops(10, SERVE) == 52000
        # weights 3*512 + 8000 = 9536, K/V written 3*2*10*8 = 480, float32
        assert work.prefill_bytes(10, SERVE) == 4 * (9536 + 480)
    elif what == "decode":
        # two rows, contexts 5 and 7: per layer 2*2*512 = 2048 and attention
        # 4*8*(5+7) = 384; 3 layers 7296; head 2*2*8*1000 = 32000
        assert work.decode_step_flops([5, 7], SERVE) == 39296
        # weights 9536 once; live K/V rows 2*(5+7)*8*3 = 576; float32
        assert work.decode_step_bytes([5, 7], SERVE) == 4 * (9536 + 576)
        t, bound = peaks.roofline_seconds(
            39296, 40448, {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e6})
        assert (t, bound) == (pytest.approx(0.040448), "bytes")
    else:
        # layer 8*24 + 8*8 + 2*8*20 = 576; 3 layers 1728; head 8000: 9728
        assert work.train_matmul_weights(TRAIN) == 9728
        # one sequence of 6: 6*6*9728 = 350208; attention forward
        # 2*8*6*7 = 672 a layer, x3 for forward+backward, x3 layers = 6048
        assert work.train_step_flops(1, 6, TRAIN) == 356256
        assert work.train_step_flops(2, 6, TRAIN) == 2 * 356256


# ------------------------------------------------------------- the generator
OPEN = {"loop": "open", "rate_per_s": 4.0,
        "prompt_len": {"dist": "lognormal", "median": 50, "sigma": 0.8,
                       "min": 8, "max": 200},
        "max_new_tokens": {"dist": "uniform", "min": 2, "max": 9}}


def test_same_seed_same_schedule_other_seed_same_work():
    a = loadgen.build_schedule(OPEN, 2**31 + 7, 10.0, 300)
    b = loadgen.build_schedule(OPEN, 2**31 + 7, 10.0, 300)
    c = loadgen.build_schedule(OPEN, 5, 10.0, 300)
    assert len(a) == 40
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    # every seed: the same arrivals and sizes in the same order (one fixed
    # realisation, replayed); the token ids come from the seed
    shape = lambda s: [(r.due_s, len(r.prompt), r.max_new) for r in s]
    assert shape(a) == shape(b) == shape(c)
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, c))
    assert 0 < a[0].due_s and a[-1].due_s < 10.0
    # another order_seed: the same sizes and gaps, in another order
    d = loadgen.build_schedule(dict(OPEN, order_seed=2), 5, 10.0, 300)
    assert shape(d) != shape(c)
    assert sorted(len(r.prompt) for r in d) == sorted(len(r.prompt)
                                                      for r in c)
    assert sorted(r.max_new for r in d) == sorted(r.max_new for r in c)
    gaps = lambda s: sorted(np.round(np.diff([0.0] + [r.due_s for r in s]), 9))
    assert gaps(d) == gaps(c)
    assert all(8 <= len(r.prompt) <= 200 and 2 <= r.max_new <= 9 for r in a)
    assert all(1 <= int(t) < 300 for r in a for t in r.prompt)


def test_open_loop_times_from_due_and_reports_lateness():
    """A sender that stalls the generator: the requests behind the stall are
    sent late, say so, and are timed from when they were DUE."""
    now = [100.0]
    reqs = loadgen.build_schedule(dict(OPEN, rate_per_s=1.0), 3, 4.0, 300)

    def sleep(dt):
        now[0] += dt

    def send(r, on_done):
        if r.idx == 0:
            now[0] += 2.5                       # the stall
        r.frame_t = [now[0] + 0.010, now[0] + 0.030]
        r.t_done = now[0] + 0.030
        on_done(r)

    out = loadgen.run_open(reqs, send, 4.0, clock=lambda: now[0], sleep=sleep)
    assert out["drained"] and len(out["sent"]) == 4
    assert out["late_max_ms"] > 1000
    ttft = loadgen.ttft_ms(reqs)
    late = [1e3 * (r.t_sent - r.t_due) for r in reqs]
    for r, t, l in zip(reqs[1:], ttft[1:], late[1:]):
        assert t == pytest.approx(l + 10.0)     # due time, not send time
    assert loadgen.gaps_ms(reqs) == pytest.approx([20.0] * 4)
    assert loadgen.answer_ms(reqs)[1:] == pytest.approx(
        [l + 30.0 for l in late[1:]])      # to the LAST frame, from due
    # a request that never answered is beyond every percentile
    reqs[2].error = "no answer"
    assert loadgen.percentile(loadgen.ttft_ms(reqs), 90) == math.inf


# --------------------------------------------------------------- the manifest
def test_manifest_check_passes_and_catches_a_missing_reader(tmp_path):
    man = manifest.load(ROOT)
    assert manifest.check(man, ROOT) == []
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    victim = man["per_layer"][0]["name"]
    os.remove(tmp_path / "benchmark" / "layer_metrics" / (victim + ".py"))
    bad = manifest.check(man, str(tmp_path))
    assert len(bad) == 1 and victim in bad[0]
    broken = json.loads(json.dumps(man))
    broken["per_layer"][0]["moves"] = "no_such_metric"
    broken["workloads"][0]["chips"] = 4
    broken["workloads"][1]["chips"] = 4
    bad = manifest.check(broken, ROOT)
    assert any("no_such_metric" in b for b in bad)
    assert any("ask for 4 chips" in b for b in bad)


# ------------------------------------------------- the control, and the faults
@pytest.mark.parametrize("traffic", ["prefill-closed", "chat-steady"])
def test_serving_control_fails_the_cells_own_limits_at_a_small_size(traffic):
    """The control of the serving cells at a size a test can hold, through
    the runner's own ``judge`` and the cell's own limits: the reference in
    the configuration's arithmetic, put in the program's place, is correct;
    the reference in bfloat16 storage, put there, is not."""
    from harness import common, reference, serve_runner as sr

    m = {"vocab": 512, "d_model": 128, "n_heads": 4, "n_layers": 4}
    mix = manifest.load_json(ROOT, f"benchmark/traffic/{traffic}.json")
    cfg = manifest.load_json(ROOT, "benchmark/configs/tiny-w2048-serve.json")
    new = 1 if traffic == "prefill-closed" else 20
    mix = dict(mix, max_new_tokens={"dist": "const", "value": new},
               check_kv_requests=3)
    ref = reference.ServeReference(
        11, m, cfg["runner_args"]["reference"]["mode"], pad_to=64)
    rng = np.random.default_rng(1)
    reqs, held = [], {}
    for i in range(3):
        prompt = rng.integers(1, 512, size=40 + 9 * i, dtype=np.int32)
        served = []
        for _ in range(new):                # greedy decode by the reference
            logits, _kv = ref.forward(prompt, served + [0], rows_pad=new)
            served.append(int(np.asarray(logits)[-1].argmax()))
        r = loadgen.Request(idx=i, prompt=prompt, max_new=new, tokens=served,
                            streamed=list(served), t_done=1.0)
        reqs.append(r)
        rows = ref.forward(prompt, served, rows_pad=new)[1]
        held[id(r)] = ((len(prompt) + new - 1) // 16 * 16, rows)
    sound = sr.judge(reqs, reqs, held, ref, mix, mix["limits"])
    assert common.correct_of(sound["checks"]), sound["checks"]
    assert sound["kv_rows"]["prefill"] > 0
    assert (sound["kv_rows"]["decode"] > 0) == (new > 1)
    control = sr.judge(reqs, reqs, held, ref, mix, mix["limits"],
                       control=True)
    assert not common.correct_of(control["checks"]), control["checks"]
    # no rows to compare is not correct either
    none = sr.judge(reqs, reqs, {}, ref, mix, mix["limits"])
    assert none["checks"]["kv_rows_short"]["value"] == 3
    assert not common.correct_of(none["checks"])


def test_training_control_and_faults_fail_at_a_small_size():
    """The control of the train cell (every matmul operand rounded to
    float8) and the two faults the reference can carry, at a size a test can
    hold, judged by the cell's own limits: each fails one number at least,
    and the reference against itself fails none."""
    from harness import reference, train_runner

    m = {"vocab": 256, "d_model": 64, "n_heads": 4, "n_layers": 2,
         "d_ff": 128, "dtype": "float32"}
    limits = manifest.load_json(
        ROOT, "benchmark/traffic/train-16k-sp4.json")["limits"]
    jobs = [{"seed": 3}, {"seed": 3, "lower": True},
            {"seed": 3, "fault": "no_exchange"},
            {"seed": 3, "fault": "half_tokens"}]
    ref, control, no_exchange, half = reference.train_reference_jobs(
        jobs, m, 1, 256, 0.01, 3)

    def failed(got):
        checks = train_runner.judge(got, ref, limits)
        return [k for k, c in checks.items() if c["value"] > c["limit"]]

    assert failed(ref) == []
    assert failed(control)
    assert failed(no_exchange)
    assert failed(half)
    # a state left unchanged reads 1 by the measure of the norms
    still = dict(ref, grad_norms={k: 0.0 for k in ref["grad_norms"]},
                 change_norms={k: 0.0 for k in ref["change_norms"]})
    checks = train_runner.judge(still, ref, limits)
    assert checks["change_norm_gap"]["value"] == pytest.approx(1.0)


def _broken(fault, workload):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run.py"), fault,
         workload, "--seed", "77", "--seconds", "3", "--trace", "0"],
        capture_output=True, text=True, timeout=600,
        env={k: v for k, v in os.environ.items()
             if k not in ("XLA_FLAGS", "JAX_PLATFORMS")})
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    assert line.startswith("[REHEARSAL cpu]")
    return json.loads(line[line.index("{"):])


@pytest.mark.parametrize("fault,workload", [
    ("token_altered", "chat-steady"),
    ("kv_store_bfloat16", "prefill-closed"),
    ("state_unchanged", "train-16k-sp4"),
    ("half_batch", "train-16k-sp4"),
    ("no_exchange", "train-16k-sp4"),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(fault, workload):
    sound = _SOUND.setdefault(workload, _broken("none", workload))
    assert sound["correct"] is True, sound["checks"]
    broken = _broken(fault, workload)
    assert broken["correct"] is False, broken["checks"]
    failed = [k for k, c in broken["checks"].items()
              if c["value"] > c["limit"]]
    assert failed


_SOUND = {}
