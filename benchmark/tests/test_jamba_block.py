"""The ``jamba`` block's own tests (CPU, small size): the configuration against
the catalog row, its work counts against hand-worked numbers, its control
through the runner's ``judge`` with the cell's own limits, and whole runs of
``run.py`` with the timed path broken.

    python -m pytest benchmark/tests/test_jamba_block.py -q
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

from blocks import jamba  # noqa: E402
from harness import loadgen, manifest  # noqa: E402

CONFIG = "benchmark/configs/jamba2-3b-serve.json"
MIX = "benchmark/traffic/longdoc-steady.json"
# the catalog row's ``config`` (architectures.jsonl, AI21-Jamba2-3B)
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
             intermediate_size=128, num_hidden_layers=8, attn_layer_period=4,
             attn_layer_offset=1, mamba_d_state=16, mamba_d_conv=4,
             mamba_expand=2, mamba_dt_rank=4, rms_norm_eps=1e-6,
             vocab_size=256)


def test_the_configuration_is_the_catalog_row_uncut():
    cfg = manifest.load_json(ROOT, CONFIG)
    for key, value in CATALOG.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == [] and cfg["block"] == "jamba"
    m = cfg["runner_args"]["model"]
    renamed = {"rms_norm_eps", "vocab_size", "hidden_size",
               "intermediate_size", "num_attention_heads",
               "num_key_value_heads", "num_hidden_layers",
               "attn_layer_period", "attn_layer_offset", "mamba_d_state",
               "mamba_d_conv", "mamba_expand", "mamba_dt_rank"}
    for key in renamed:
        assert m[key] == cfg[key], key
    assert m["max_context"] == cfg["max_position_embeddings"]
    assert cfg["published"]["parameters"] == 3_029_337_472 \
        == jamba.weight_count(m)
    man = manifest.load(ROOT)
    entry = [c for c in man["configs"] if c["name"] == "jamba2-3b-serve"][0]
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    # the engine's budget leaves ONE chunk size beside up to max_batch rows
    eng = cfg["runner_args"]["engine"]
    assert {(eng["token_budget"] - b) // 128 * 128
            for b in range(eng["max_batch"] + 1)} == {2048}


def test_work_counts_against_hand_worked_numbers():
    m = dict(SMALL, hidden_size=8, num_attention_heads=2,
             intermediate_size=16, num_hidden_layers=4, attn_layer_period=4,
             attn_layer_offset=1, mamba_d_state=2, mamba_dt_rank=1,
             vocab_size=32)
    # layers: mamba, attention, mamba, mamba
    d, di, ff, kvd, hd, h, r, n, kc = 8, 16, 16, 4, 4, 2, 1, 2, 4
    mamba_mats = d * 2 * di + di * (r + 2 * n) + r * di + di * d
    attn_mats = 2 * d * d + 2 * d * kvd
    mlp = 3 * d * ff
    mats = 3 * mamba_mats + attn_mats + 4 * mlp
    vectors = (3 * (kc * di + di + r + 2 * n + di + n * di + di)
               + 4 * 2 * d + d)
    weights = mats + vectors + 32 * d
    work = importlib.import_module("blocks.jamba.work")
    assert work.matrix_weights(m) == mats
    assert jamba.weight_count(m) == weights
    state = 4 * 3 * di * (n + kc - 1)
    assert jamba.decode_step_bytes([], m) == 2 * weights
    # one row at context 10: one attention layer reads 10 rows of K and V
    # in bfloat16; three Mamba layers' state read and written in float32
    assert jamba.decode_step_bytes([10], m) == \
        2 * (weights + 2 * kvd * 10) + 2 * state
    pair = h * 4 * hd
    scan_row = di * (2 * kc + 7 * n)
    assert jamba.decode_step_flops([10], m) == \
        2 * (mats + d * 32) + 3 * scan_row + pair * 10
    # a chunk of 6 rows from row 4: its rows over the 4 before and among
    # themselves; the head only where it ends the prompt
    pairs = 6 * 4 + 6 * 7 // 2
    assert jamba.prefill_chunk_flops(6, 4, m, False) == \
        2 * 6 * mats + pair * pairs
    assert jamba.prefill_chunk_flops(6, 4, m, True) == \
        2 * 6 * mats + pair * pairs + 2 * d * 32
    # a prompt's matmul work is the same however it is cut
    whole = jamba.prefill_chunk_flops(10, 0, m, True)
    assert whole == (jamba.prefill_chunk_flops(4, 0, m, False)
                     + jamba.prefill_chunk_flops(6, 4, m, True))
    assert jamba.prefill_flops(10, m) == whole + 10 * 3 * scan_row
    assert jamba.prefill_bytes(10, m) == \
        2 * (weights + 2 * kvd * 10) + state


def test_control_fails_the_cells_own_limits_at_a_small_size():
    """The control at a size a test can hold, through the runner's own
    ``judge`` and the cell's own limits: the reference in the
    configuration's arithmetic, put in the program's place, is correct; the
    reference in float8 storage with a bfloat16 scan state, put there, is
    not; no state to read is not correct either."""
    from harness import common, serve_runner as sr

    cfg = manifest.load_json(ROOT, CONFIG)
    new = 20
    mix = dict(manifest.load_json(ROOT, MIX),
               max_new_tokens={"dist": "const", "value": new},
               check_kv_requests=3)
    ref = jamba.reference(11, dict(cfg["runner_args"], model=SMALL),
                          pad_to=32)
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
        held[id(r)] = (n, ref.forward(prompt, served, rows_pad=new)[1])
    sound = sr.judge(jamba, reqs, reqs, held, ref, mix, mix["limits"])
    assert common.correct_of(sound["checks"]), sound["checks"]
    assert sound["kv_rows"]["prefill"] > 0 and sound["kv_rows"]["decode"] > 0
    assert set(jamba.STATE_CHECKS) <= set(sound["checks"])
    control = sr.judge(jamba, reqs, reqs, held, ref, mix, mix["limits"],
                       control=True)
    assert not common.correct_of(control["checks"]), control["checks"]
    failed = {k for k, c in control["checks"].items()
              if c["value"] > c["limit"]}
    assert {"ssm0_gap_prefill", "ssm0_gap_decode"} <= failed, \
        control["checks"]
    # the pages' V rows taken from K (a wrong write that keeps every norm)
    mixed = {k: (n, dict(st, vf=st["kf"])) for k, (n, st) in held.items()}
    wrong = sr.judge(jamba, reqs, reqs, mixed, ref, mix, mix["limits"])
    assert {k for k, c in wrong["checks"].items()
            if c["value"] > c["limit"]} == {"kvf_gap_prefill",
                                            "kvf_gap_decode"}
    none = sr.judge(jamba, reqs, reqs, {}, ref, mix, mix["limits"])
    assert none["checks"]["state_short"]["value"] == 3
    assert not common.correct_of(none["checks"])


def test_the_checked_sample_holds_the_windows_longest_request():
    """``held_state`` and ``sample_of`` draw with the longest request in
    the draw, so a prompt of many chunks is always compared."""
    from harness import reference, serve_runner as sr

    reqs = [loadgen.Request(idx=i, prompt=np.zeros(n, np.int32), max_new=2,
                            tokens=[1, 2], streamed=[1, 2], t_done=1.0)
            for i, n in enumerate((100, 3000, 40, 250, 31000, 700))]
    for seed in range(5):
        assert len(reference.pick_sample(reqs, 3, seed)[0].prompt) == 31000
        held = {id(r): None for r in reference.pick_sample(reqs, 3, seed)}
        assert len(sr.sample_of(reqs, held, 3, seed)[0].prompt) == 31000


def _broken(fault):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_jamba.py"), fault,
         "longdoc-steady", "--seed", "77", "--seconds", "3", "--trace", "0"],
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
    # the sample held a prompt of more than one chunk
    assert sound["kv_rows"]["prefill"] > 3 * 128


@pytest.mark.parametrize("fault,by", [
    ("scan_from_zero", "ssm0_gap_prefill"),
    ("conv_tail_dropped", "ssm0_gap_prefill"),
    ("no_inner_norms", "ssm0_gap_prefill"),
    ("attention_wrong_index", "kvf_gap_prefill"),
    ("k_rows_for_v", "kvf_gap_decode"),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(fault, by):
    broken = _broken(fault)
    assert broken["correct"] is False, broken["checks"]
    failed = [k for k, c in broken["checks"].items()
              if c["value"] > c["limit"]]
    assert by in failed, broken["checks"]
