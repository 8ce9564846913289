"""The ``sambay`` block's own tests (CPU, small size): its work counts against
hand-worked numbers, its control through the runner's ``judge`` with the
cell's own limits, and whole runs of ``run.py`` with the timed path broken.

    python -m pytest benchmark/tests/test_sambay_block.py -q
"""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from blocks import sambay  # noqa: E402
from harness import loadgen, manifest  # noqa: E402

CONFIG = "benchmark/configs/phi4-mini-flash-l16-serve.json"
MIX = "benchmark/traffic/reason-steady.json"


def test_the_cut_keeps_the_published_keys_and_counts_2193M_parameters():
    cfg = manifest.load_json(ROOT, CONFIG)
    m = cfg["runner_args"]["model"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "sliding_window", "mb_per_layer",
                "num_hidden_layers", "vocab_size", "layer_norm_eps"):
        assert m[key] == cfg[key], key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 32
    work = importlib.import_module("blocks.sambay.work")
    ref = importlib.import_module("blocks.sambay.reference")
    assert ref.layer_kinds(16) == (["mamba", "window"] * 4
                                   + ["mamba", "full"] + ["gmu", "cross"] * 3)
    # the issue's arithmetic: 119.9M / 98.3M / 104.9M / 91.8M a layer
    z = ref.sizes(m)
    assert round(work.layer_weights("mamba", z) / 1e6, 1) == 119.8
    assert round(work.layer_weights("window", z) / 1e6, 1) == 98.3
    assert round(work.layer_weights("gmu", z) / 1e6, 1) == 104.9
    assert round(work.layer_weights("cross", z) / 1e6, 1) == 91.8
    assert round(work.weight_count(m) / 1e6) == 2193
    # every drawn or constant weight is in the count or is a norm or a bias
    drawn = sum(int(np.prod(s)) for _n, s, _h in ref.weight_specs(m))
    assert 0 <= drawn - work.weight_count(m) < 1e6
    # the published depth: 3853M
    assert round(work.weight_count(dict(m, num_hidden_layers=32)) / 1e7) \
        == 385      # 3852M: the published 3.8B


def test_work_counts_against_hand_worked_numbers():
    m = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=16, sliding_window=4, num_hidden_layers=4,
             vocab_size=32, layer_norm_eps=1e-5, d_state=2, d_conv=4,
             expand=2, dt_rank=1)
    # layers: mamba, window, mamba (memory), full; no cross-decoder at N=4
    d, di, ff, kvd = 8, 16, 16, 4
    mamba = d * 2 * di + di * (1 + 4) + 1 * di + di * d + 3 * d * ff
    attn = d * (d + 2 * kvd) + d * d + 3 * d * ff
    scan_w = di * (4 + 2 + 2)
    weights = 2 * mamba + 2 * attn + 2 * scan_w + 32 * d
    assert sambay.decode_step_bytes([], m) == 4 * weights
    # one row at context 10: window layer reads 4 rows, the full layer 10;
    # two Mamba layers' state (2 x 16 + 3 x 16 floats) read and written
    state = 4 * 2 * di * (2 + 3)
    assert sambay.decode_step_bytes([10], m) == \
        4 * (weights + 2 * kvd * (4 + 10)) + 2 * state
    pair = 4 * 6 * 2              # H maps x (2 hd + 4 hd) flops
    scan_row = di * (2 * 4 + 7 * 2)
    assert sambay.decode_step_flops([10], m) == \
        2 * weights + 2 * scan_row + pair * (4 + 10)
    # prefill of 6 rows: all four layers over 6 rows, the head for one
    s = 6
    banded = 1 + 2 + 3 + 4 + 4 + 4
    assert sambay.prefill_flops(s, m) == (
        2 * s * (2 * mamba + 2 * attn) + 2 * s * scan_row
        + pair * (banded + s * (s + 1) // 2) + 2 * d * 32)
    assert sambay.prefill_bytes(s, m) == \
        4 * (weights + 2 * kvd * (4 + s)) + state
    # the cross-decoder runs for ONE row: doubling the prompt adds no
    # cross-decoder matmul work
    m8 = dict(m, num_hidden_layers=8)
    z = importlib.import_module("blocks.sambay.reference").sizes(m8)
    work = importlib.import_module("blocks.sambay.work")
    cross = 2 * (work.layer_weights("gmu", z) + work.layer_weights("cross", z))
    self_dec = sum(work.layer_weights(k, z) for k in
                   ("mamba", "window") * 2 + ("mamba", "full"))
    growth = sambay.prefill_flops(12, m8) - sambay.prefill_flops(6, m8)
    assert growth < 2 * 6 * self_dec + 2 * 6 * 3 * scan_row + pair * 400
    assert growth < 2 * 6 * (self_dec + cross)


def test_control_fails_the_cells_own_limits_at_a_small_size():
    """The control at a size a test can hold, through the runner's own
    ``judge`` and the cell's own limits: the reference in the
    configuration's arithmetic, put in the program's place, is correct; the
    reference in bfloat16 storage, put there, is not; no state to read is
    not correct either."""
    from harness import common, serve_runner as sr

    cfg = manifest.load_json(ROOT, CONFIG)
    assert cfg["block"] == "sambay"
    m = dict(cfg["runner_args"]["model"]["rehearsal"], mb_per_layer=2,
             layer_norm_eps=1e-5, d_state=16, d_conv=4, expand=2,
             num_hidden_layers=8)
    new = 20
    mix = dict(manifest.load_json(ROOT, MIX),
               max_new_tokens={"dist": "const", "value": new},
               check_kv_requests=3)
    ref = sambay.reference(11, dict(cfg["runner_args"], model=m), pad_to=32)
    assert ref.mode == cfg["runner_args"]["reference"]["mode"]
    rng = np.random.default_rng(1)
    reqs, held = [], {}
    for i in range(3):
        prompt = rng.integers(1, 256, size=30 + 9 * i, dtype=np.int32)
        served = []
        for _ in range(new):                # greedy decode by the reference
            logits, _st = ref.forward(prompt, served + [0], rows_pad=new)
            served.append(int(np.asarray(logits)[-1].argmax()))
        r = loadgen.Request(idx=i, prompt=prompt, max_new=new, tokens=served,
                            streamed=list(served), t_done=1.0)
        reqs.append(r)
        n = len(prompt) + new - 1
        state = dict(ref.forward(prompt, served, rows_pad=new)[1],
                     ring_lo=max(0, n - 32))
        held[id(r)] = (n, state)
    sound = sr.judge(sambay, reqs, reqs, held, ref, mix, mix["limits"])
    assert common.correct_of(sound["checks"]), sound["checks"]
    assert sound["kv_rows"]["prefill"] > 0 and sound["kv_rows"]["decode"] > 0
    assert set(sambay.STATE_CHECKS) <= set(sound["checks"])
    control = sr.judge(sambay, reqs, reqs, held, ref, mix, mix["limits"],
                       control=True)
    assert not common.correct_of(control["checks"]), control["checks"]
    failed = {k for k, c in control["checks"].items()
              if c["value"] > c["limit"]}
    assert failed & set(sambay.STATE_CHECKS), control["checks"]
    # the shared full layer's rows are held too: the same K/V with its V
    # rows taken from K (a wrong gather that keeps every norm) is not correct
    mixed = {k: (n, dict(st, vf=st["kf"])) for k, (n, st) in held.items()}
    wrong = sr.judge(sambay, reqs, reqs, mixed, ref, mix, mix["limits"])
    assert {k for k, c in wrong["checks"].items()
            if c["value"] > c["limit"]} == {"kvf_gap_prefill",
                                            "kvf_gap_decode"}
    none = sr.judge(sambay, reqs, reqs, {}, ref, mix, mix["limits"])
    assert none["checks"]["state_short"]["value"] == 3
    assert not common.correct_of(none["checks"])
    # a ring that holds none of prefill's rows leaves that part unread
    late = {k: (n, dict(st, ring_lo=n - 1)) for k, (n, st) in held.items()}
    unread = sr.judge(sambay, reqs, reqs, late, ref, mix, mix["limits"])
    assert unread["checks"]["kv1_gap_prefill"]["value"] > 1
    assert not common.correct_of(unread["checks"])


def _broken(fault):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_sambay.py"), fault,
         "reason-steady", "--seed", "77", "--seconds", "3", "--trace", "0"],
        capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items()
             if k not in ("XLA_FLAGS", "JAX_PLATFORMS")})
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    assert line.startswith("[REHEARSAL cpu]")
    return json.loads(line[line.index("{"):])


def test_a_sound_rehearsal_of_the_cell_ends_correct():
    sound = _broken("none")
    assert sound["correct"] is True, sound["checks"]
    assert sound["failed"] == 0 and sound["compiles_in_window"] == 0
    assert sound["kv_requests"] >= 3


@pytest.mark.parametrize("fault,by", [
    ("state_unchanged", "ssm0_gap_decode"),
    ("window_short", "logit_gap"),
    ("memory_wrong_layer", "logit_gap"),
    ("shared_kv_float8", "kvf_gap_decode"),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(fault, by):
    broken = _broken(fault)
    assert broken["correct"] is False, broken["checks"]
    failed = [k for k, c in broken["checks"].items()
              if c["value"] > c["limit"]]
    assert by in failed, broken["checks"]
