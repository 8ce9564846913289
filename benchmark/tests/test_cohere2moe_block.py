"""The ``cohere2moe`` block's own tests (CPU, small size): the configuration
against the catalog's keys and the issue's arithmetic, its work counts
against hand-worked numbers, its control through the runner's ``judge`` with
the cell's own limits, and whole runs of ``run.py`` with the timed path
broken.

    python -m pytest benchmark/tests/test_cohere2moe_block.py -q
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

from blocks import cohere2moe  # noqa: E402
from harness import loadgen, manifest  # noqa: E402

CONFIG = "benchmark/configs/command-a-plus-l4-e16-serve.json"
MIX = "benchmark/traffic/rag-steady.json"
work = importlib.import_module("blocks.cohere2moe.work")
ref_mod = importlib.import_module("blocks.cohere2moe.reference")


def test_the_cut_keeps_the_published_widths_and_counts_4733M_parameters():
    cfg = manifest.load_json(ROOT, CONFIG)
    m = cfg["runner_args"]["model"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "intermediate_size", "num_experts_per_tok",
                "num_shared_experts", "sliding_window", "rope_theta",
                "layer_norm_eps", "logit_scale", "layer_types",
                "num_experts", "vocab_size"):
        assert m[key] == cfg[key], key
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], cfg["sliding_window"]) == (
        4096, 128, 8, 128, 4096, 8, 4, 4096)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts", "vocab_size"]
    assert cfg["published"]["num_experts"] == m["num_routed_experts"] == 128
    assert cfg["published"]["vocab_size"] == 262144
    assert "8 chips share each layer" in cfg["deployment"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 4
    z = ref_mod.sizes(m)
    # the issue's arithmetic: 142.6M + 201.3M + 0.5M + 16 x 50.33M a layer
    assert round(work.dense_layer_weights(z) / 1e6, 1) == 343.9
    assert round(work.expert_weights(z) / 1e6, 2) == 50.33
    assert round(work.weight_count(m) / 1e6) == 4733
    drawn = sum(int(np.prod(s)) for _n, _i, s, _f in ref_mod.matrices(m))
    assert drawn == work.weight_count(m)
    assert work.pairs_a_row(z) == 1.0
    assert round(work.experts_hit(16, z), 1) == 10.3
    assert round(work.experts_hit(8, z), 1) == 6.5


def test_work_counts_against_hand_worked_numbers():
    m = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2,
             head_dim=4, intermediate_size=16, num_experts=2,
             num_routed_experts=8, num_experts_per_tok=4,
             num_shared_experts=2, sliding_window=4,
             layer_types=["sliding_attention", "full_attention"],
             layer_norm_eps=1e-5, vocab_size=32)
    d, qd, kvd, ff = 8, 16, 8, 16
    dense = 2 * d * qd + 2 * d * kvd + 2 * 3 * d * ff      # a layer, bf16
    router = d * 8                                         # float32
    expert = 3 * d * ff
    # 4 x 2 / 8 = 1 pair a row; 2 held experts: 2 (1 - 1/2^P) hit
    hit1, hit3 = 2 * (1 - 0.5), 2 * (1 - 0.125)
    assert cohere2moe.decode_step_bytes([], m) == \
        2 * (2 * dense + 32 * d) + 4 * 2 * router
    # one row at context 10: the window layer reads 4 rows, the full one 10
    assert cohere2moe.decode_step_bytes([10], m) == int(
        2 * (2 * dense + 32 * d) + 4 * 2 * router
        + 2 * (2 * hit1 * expert + 2 * 4 * 1 * d)
        + 2 * 2 * kvd * (4 + 10))
    pair = 4 * 4 * 4              # H heads x 4 hd flops a (query, key) pair
    assert cohere2moe.decode_step_flops([10, 3, 7], m) == int(
        2 * 3 * (2 * (dense + router) + 32 * d) + 2 * 3 * 2 * expert
        + pair * ((4 + 3 + 4) + (10 + 3 + 7)))
    s = 6
    banded = 1 + 2 + 3 + 4 + 4 + 4
    assert cohere2moe.prefill_flops(s, m) == int(
        2 * (2 * s * (dense + router) + s * 2 * expert)
        + pair * (banded + s * (s + 1) // 2) + 2 * d * 32)
    hit6 = 2 * (1 - 0.5 ** 6)
    assert cohere2moe.prefill_bytes(s, m) == int(
        2 * (2 * dense + 4 * router)
        + 2 * (2 * hit6 * expert + 2 * 4 * s * d)
        + 2 * (32 * d + 2 * kvd * (4 + s)))
    # the kernel's least time counts prefill and decode launches alike
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    pattern, least = cohere2moe.KERNELS["moe_expert_roofline"]
    assert pattern == "moe_grouped_matmul"
    want = 2 * ((2 * hit3 * expert + 2 * 4 * 3 * d)
                + (2 * hit6 * expert + 2 * 4 * 6 * d)) / 1e9
    assert abs(least({"prefill": [6], "decode": [[9, 9, 9]]}, m, peak)
               - want) < 1e-12


def test_control_fails_the_cells_own_limits_at_a_small_size():
    """Through the runner's own ``judge`` and the cell's own limits: the
    reference in the configuration's arithmetic, put in the program's
    place, is correct; the reference with weights and K/V rows in float8,
    put there, is not, by the first layer's rows among others; no state to
    read is not correct either; thin tokens are left out and counted."""
    from harness import common, serve_runner as sr

    cfg = manifest.load_json(ROOT, CONFIG)
    assert cfg["block"] == "cohere2moe"
    full = cfg["runner_args"]["model"]
    m = dict({k: v for k, v in full.items() if k != "rehearsal"},
             **full["rehearsal"])
    new = 20
    mix = dict(manifest.load_json(ROOT, MIX),
               max_new_tokens={"dist": "const", "value": new},
               check_kv_requests=3)
    # the configuration's arithmetic; the margin of its small twin (16
    # experts lie 8 times farther apart than 128 do)
    margin = cfg["runner_args"]["reference"]["rehearsal"]["route_margin"]
    args = dict(cfg["runner_args"], model=m, reference=dict(
        cfg["runner_args"]["reference"], route_margin=margin))
    ref = cohere2moe.reference(11, args, pad_to=32)
    assert ref.mode == cfg["runner_args"]["reference"]["mode"]
    assert ref.route_margin == margin
    rng = np.random.default_rng(1)
    reqs, held = [], {}
    for i in range(3):
        prompt = rng.integers(1, 256, size=30 + 9 * i, dtype=np.int32)
        served = []
        for _ in range(new):                # greedy decode by the reference
            ref.route_margin = 0.0          # (no row flat while choosing)
            logits, _st = ref.forward(prompt, served + [0], rows_pad=new)
            served.append(int(np.asarray(logits)[-1].argmax()))
        ref.route_margin = margin
        r = loadgen.Request(idx=i, prompt=prompt, max_new=new, tokens=served,
                            streamed=list(served), t_done=1.0)
        reqs.append(r)
        n = len(prompt) + new - 1
        state = dict(ref.forward(prompt, served, rows_pad=new)[1],
                     ring_lo=max(0, n - 32))
        held[id(r)] = (n, state)
    sound = sr.judge(cohere2moe, reqs, reqs, held, ref, mix, mix["limits"])
    assert common.correct_of(sound["checks"]), json.dumps(sound["checks"])
    assert sound["kv_rows"]["prefill"] > 0 and sound["kv_rows"]["decode"] > 0
    assert set(cohere2moe.STATE_CHECKS) <= set(sound["checks"])
    control = sr.judge(cohere2moe, reqs, reqs, held, ref, mix, mix["limits"],
                       control=True)
    assert not common.correct_of(control["checks"]), control["checks"]
    failed = {k for k, c in control["checks"].items()
              if c["value"] > c["limit"]}
    assert {"kv0_gap_prefill", "kv0_gap_decode"} <= failed, control["checks"]
    # V rows taken from K (a wrong gather that keeps every norm)
    mixed = {k: (n, dict(st, vf=st["kf"])) for k, (n, st) in held.items()}
    wrong = sr.judge(cohere2moe, reqs, reqs, mixed, ref, mix, mix["limits"])
    assert {k for k, c in wrong["checks"].items()
            if c["value"] > c["limit"]} == {"kvf_gap_prefill",
                                            "kvf_gap_decode"}
    none = sr.judge(cohere2moe, reqs, reqs, {}, ref, mix, mix["limits"])
    assert none["checks"]["state_short"]["value"] == 3
    assert not common.correct_of(none["checks"])
    # every token thin: nothing is held to logit_gap or the full layer's
    # rows, and the share of thin tokens is what fails
    ref.route_margin = 10.0
    thin = sr.judge(cohere2moe, reqs, reqs, held, ref, mix, mix["limits"])
    assert thin["checks"]["logit_gap"]["value"] == 0.0
    assert thin["checks"]["route_thin_share_prefill"]["value"] == 1.0
    assert "route_thin_share_decode" not in thin["checks"]   # printed only
    assert thin["kv_gap_by_layer"]["decode"][-1] == 1.0
    assert not common.correct_of(thin["checks"])


def _broken(fault):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_cohere2moe.py"), fault,
         "rag-steady", "--seed", "77", "--seconds", "3", "--trace", "0"],
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
    assert sound["kv_requests"] >= 2


@pytest.mark.parametrize("fault,by", [
    ("expert_float8", ("kvf_gap_prefill", "kvf_gap_decode")),
    ("router_top7", ("logit_gap",)),
    ("shared_sum", ("logit_gap",)),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(fault, by):
    broken = _broken(fault)
    assert broken["correct"] is False, broken["checks"]
    failed = [k for k, c in broken["checks"].items()
              if c["value"] > c["limit"]]
    assert set(by) & set(failed), broken["checks"]
