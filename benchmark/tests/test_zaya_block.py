"""The ``zaya`` block's own tests (CPU, small size): the configuration against
the catalog row, its work counts against hand-worked numbers, its control
through the runner's ``judge`` with the cell's own limits, and whole runs of
``run.py`` with the timed path broken.

    python -m pytest benchmark/tests/test_zaya_block.py -q
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

from blocks import zaya  # noqa: E402
from harness import loadgen, manifest  # noqa: E402

CONFIG = "benchmark/configs/zaya1-8b-l20-serve.json"
MIX = "benchmark/traffic/solve-steady.json"
work = importlib.import_module("blocks.zaya.work")
ref_mod = importlib.import_module("blocks.zaya.reference")
# the catalog row's ``config`` (architectures.jsonl, ZAYA1-8B)
CATALOG = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}


def _small(cfg):
    full = cfg["runner_args"]["model"]
    return dict({k: v for k, v in full.items() if k != "rehearsal"},
                **full["rehearsal"])


def test_the_configuration_is_the_catalog_row_at_half_its_depth():
    cfg = manifest.load_json(ROOT, CONFIG)
    reduced = {"num_hidden_layers", "layer_types"}
    for key, value in CATALOG.items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert set(cfg["reduced"]) == reduced and cfg["block"] == "zaya"
    assert cfg["num_hidden_layers"] == 20
    assert cfg["layer_types"] == ["hybrid"] * 20
    assert cfg["published"]["num_hidden_layers"] == 40
    m = cfg["runner_args"]["model"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "num_experts",
                "num_experts_per_tok", "router_hidden_size", "cca_time0",
                "cca_time1", "partial_rotary_factor", "rms_norm_eps",
                "num_hidden_layers", "vocab_size"):
        assert m[key] == cfg[key], key
    assert m["rope_theta"] == cfg["rope_parameters"]["hybrid"]["rope_theta"]
    assert m["max_context"] == 32768 < cfg["max_position_embeddings"]
    assert zaya.weight_count(m) == 4_688_810_364
    assert cfg["published"]["parameters"] == 8_840_485_624 \
        == zaya.weight_count(dict(m, num_hidden_layers=40))
    for key in ("latent_widths", "value_shift", "conv_mixing", "qk_mean",
                "qk_norm_and_temperature", "rotary", "router", "skip_output",
                "residual_scaling", "initialisation"):
        assert key in cfg["assumed"], key
    assert "stage 0 of a 2-stage pipeline" in cfg["deployment"]
    man = manifest.load(ROOT)
    entry = [c for c in man["configs"] if c["name"] == "zaya1-8b-l20-serve"][0]
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == cfg["source"]
    cell = manifest.cell_of(man, "solve-steady")
    assert cell["chips"] == 1 and cell["config"] == "zaya1-8b-l20-serve"
    assert [m["name"] for m in man["per_layer"][-2:]] == [
        "expert_skip_share", "expert_rows_per_hit"]
    # a step's chunk of a long prompt is one program's rows
    eng = cfg["runner_args"]["engine"]
    assert {-(-((eng["token_budget"] - b) // 128 * 128) // 2048)
            for b in range(eng["max_batch"] + 1)} == {1}


def test_work_counts_against_hand_worked_numbers():
    m = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2,
             head_dim=4, moe_intermediate_size=16, num_experts=3,
             num_experts_per_tok=1, router_hidden_size=4, cca_time0=2,
             cca_time1=2, partial_rotary_factor=0.5, rope_theta=5e6,
             rms_norm_eps=1e-5, num_hidden_layers=2, vocab_size=32)
    d, qd, kvd, hd, qk, ff, rw, e = 8, 16, 8, 4, 24, 16, 4, 3
    mats = d * qd + d * kvd + 2 * d * hd + qd * d          # the projections
    conv = 2 * qk + qk + 2 * 6 * hd * hd + qk              # taps and biases
    attn = mats + conv + 2                                 # + temperature
    router_mats = d * rw + 2 * rw * rw + rw * (e + 1)
    router = router_mats + rw + 2 * rw + 2 * rw + (e + 1)
    expert = 3 * d * ff
    layer = attn + router + e * expert + 2 * d + 2 * 4 * d
    weights = 2 * layer + 32 * d + d
    assert zaya.weight_count(m) == weights
    assert work.stored_bytes(m) == 2 * weights + 2 * 2 * router
    dense = 2 * (attn + 2 * d + 2 * 4 * d) + 4 * router    # a layer's bytes
    tail = 2 * qk + hd
    assert ref_mod.tail_width(ref_mod.sizes(m)) == tail
    # no row: no expert is hit, nothing but the dense weights and the head
    assert zaya.decode_step_bytes([], m) == 2 * dense + 2 * 32 * d
    # one row at context 10: 3/4 of a pair a layer, 3 (1 - 3/4) experts
    # expected hit, the row's tail read and written, 10 rows of K and V
    hit = 3 * (1 - 0.75)
    routed = 2 * hit * expert + 2 * 4 * 0.75 * d
    assert zaya.decode_step_bytes([10], m) == int(
        2 * (dense + routed + 2 * 4 * tail + 2 * 2 * kvd * 10) + 2 * 32 * d)
    row = 2 * (mats + router_mats) + 2 * 2 * 6 * hd * hd
    pair = 4 * 4 * hd             # H heads x 4 hd flops a (query, key) pair
    assert zaya.decode_step_flops([10, 3], m) == int(
        2 * (2 * row + 2 * 0.75 * 2 * expert + pair * 13) + 2 * 2 * d * 32)
    # a chunk of 6 rows from row 4: its rows over the 4 before and among
    # themselves; the head only where it ends the prompt
    pairs = 6 * 4 + 6 * 7 // 2
    assert zaya.prefill_chunk_flops(6, 4, m, False) == int(
        2 * (6 * row + 6 * 0.75 * 2 * expert + pair * pairs))
    assert zaya.prefill_chunk_flops(6, 4, m, True) \
        == zaya.prefill_chunk_flops(6, 4, m, False) + 2 * d * 32
    # a prompt's work is the same however it is cut
    assert zaya.prefill_flops(10, m) == (
        zaya.prefill_chunk_flops(4, 0, m, False)
        + zaya.prefill_chunk_flops(6, 4, m, True))
    hit6 = 3 * (1 - 0.75 ** 6)
    assert zaya.prefill_bytes(6, m) == int(
        2 * (dense + 2 * hit6 * expert + 2 * 4 * 6 * 0.75 * d
             + 2 * 2 * kvd * 6 + 4 * tail) + 2 * 32 * d)
    # the kernel's least time counts prefill and decode launches alike
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    pattern, least = zaya.KERNELS["moe_expert_roofline"]
    assert pattern == "moe_grouped_matmul"
    hit2 = 3 * (1 - 0.75 ** 2)
    want = 2 * ((2 * hit2 * expert + 2 * 4 * 2 * 0.75 * d)
                + (2 * hit6 * expert + 2 * 4 * 6 * 0.75 * d)) / 1e9
    assert abs(least({"prefill": [6], "decode": [[9, 9]]}, m, peak)
               - want) < 1e-12
    # a later chunk is logged by its END: it counts the rows past the chunk
    # before it (256 -> 300: 44 rows), a whole prompt all of its own
    assert work.launch_rows([100, 256, 300, 90, 128, 64]) == [
        100, 256, 44, 90, 128, 64]
    # the published widths: 12.3 of 16 experts expected at 24 rows
    z = ref_mod.sizes(manifest.load_json(ROOT, CONFIG)["runner_args"]["model"])
    assert round(work.experts_hit(24, z), 1) == 12.3
    assert round(work.pairs_a_row(z), 3) == 0.941


def test_control_fails_the_cells_own_limits_at_a_small_size():
    """Through the runner's own ``judge`` and the cell's own limits: the
    reference in the configuration's arithmetic, put in the program's place,
    is correct; the reference with matrices and K/V rows in float8, put
    there, is not, by layer 0's rows and tail among others; no state to read
    is not correct either; thin tokens are left out and counted."""
    from harness import common, serve_runner as sr

    cfg = manifest.load_json(ROOT, CONFIG)
    m = _small(cfg)
    new = 20
    mix = dict(manifest.load_json(ROOT, MIX),
               max_new_tokens={"dist": "const", "value": new},
               check_kv_requests=3)
    margin = cfg["runner_args"]["reference"]["rehearsal"]["route_margin"]
    args = dict(cfg["runner_args"], model=m, reference=dict(
        cfg["runner_args"]["reference"], route_margin=margin))
    ref = zaya.reference(11, args, pad_to=32)
    assert ref.mode == cfg["runner_args"]["reference"]["mode"]
    assert ref.route_margin == margin
    rng = np.random.default_rng(1)
    reqs, held = [], {}
    flips = ref_mod.FLIP_SHARE
    for i in range(3):
        prompt = rng.integers(1, 256, size=30 + 9 * i, dtype=np.int32)
        served = []
        # (no row flat while choosing: no margin, no row taken for one
        # routed otherwise)
        ref.route_margin, ref_mod.FLIP_SHARE = 0.0, 0.0
        for _ in range(new):                # greedy decode by the reference
            logits, _st = ref.forward(prompt, served + [0], rows_pad=new)
            served.append(int(np.asarray(logits)[-1].argmax()))
        ref.route_margin, ref_mod.FLIP_SHARE = margin, flips
        r = loadgen.Request(idx=i, prompt=prompt, max_new=new, tokens=served,
                            streamed=list(served), t_done=1.0)
        reqs.append(r)
        n = len(prompt) + new - 1
        held[id(r)] = (n, ref.forward(prompt, served, rows_pad=new)[1])
    sound = sr.judge(zaya, reqs, reqs, held, ref, mix, mix["limits"])
    assert common.correct_of(sound["checks"]), json.dumps(sound["checks"])
    assert sound["kv_rows"]["prefill"] > 0 and sound["kv_rows"]["decode"] > 0
    assert set(zaya.STATE_CHECKS) <= set(sound["checks"]) \
        | {"route_thin_share_decode"}
    control = sr.judge(zaya, reqs, reqs, held, ref, mix, mix["limits"],
                       control=True)
    assert not common.correct_of(control["checks"]), control["checks"]
    failed = {k for k, c in control["checks"].items()
              if c["value"] > c["limit"]}
    assert {"kv0_gap_prefill", "kv0_gap_decode", "tail0_gap_prefill",
            "tail0_gap_decode"} <= failed, control["checks"]
    # the second value head taken from the first (a wrong shift that keeps
    # every norm)
    hd = m["head_dim"]

    def unshifted(st):
        v = np.asarray(st["v0"])
        return dict(st, v0=np.concatenate([v[:, :hd], v[:, :hd]], axis=1))

    mixed = {k: (n, unshifted(st)) for k, (n, st) in held.items()}
    wrong = sr.judge(zaya, reqs, reqs, mixed, ref, mix, mix["limits"])
    assert {k for k, c in wrong["checks"].items()
            if c["value"] > c["limit"]} == {"kv0_gap_prefill",
                                            "kv0_gap_decode"}
    none = sr.judge(zaya, reqs, reqs, {}, ref, mix, mix["limits"])
    assert none["checks"]["state_short"]["value"] == 3
    assert not common.correct_of(none["checks"])
    # every token thin: nothing is held to logit_gap or the last layer's
    # rows, and the share of thin tokens is what fails
    ref.route_margin = 10.0
    thin = sr.judge(zaya, reqs, reqs, held, ref, mix, mix["limits"])
    assert thin["checks"]["logit_gap"]["value"] == 0.0
    assert thin["checks"]["route_thin_share_prefill"]["value"] == 1.0
    assert thin["kv_gap_by_layer"]["decode"][-1] == 1.0
    assert not common.correct_of(thin["checks"])


def _broken(fault, trace="0"):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_zaya.py"), fault,
         "solve-steady", "--seed", "77", "--seconds", "3", "--trace", trace],
        capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items()
             if k not in ("XLA_FLAGS", "JAX_PLATFORMS")})
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    assert line.startswith("[REHEARSAL cpu]")
    return json.loads(line[line.index("{"):])


def test_a_sound_rehearsal_of_the_cell_ends_correct():
    sound = _broken("none", trace="1")
    assert sound["correct"] is True, sound["checks"]
    assert sound["failed"] == 0 and sound["compiles_in_window"] == 0
    assert sound["kv_requests"] >= 3
    # the sample held a prompt of more than one chunk
    assert sound["kv_rows"]["prefill"] > 3 * 128
    # a traced run's line has what the new readers and the shared ones read
    for name in ("expert_skip_share", "expert_rows_per_hit",
                 "expert_pairs_per_step", "experts_hit_share",
                 "cache_bytes_per_token"):
        assert sound["metrics"][name]["value"] > 0, name


@pytest.mark.parametrize("fault,by", [
    ("tail_dropped", ("kv0_gap_prefill",)),
    ("value_same_token", ("kv0_gap_prefill", "kv0_gap_decode")),
    ("router_state_not_handed_down", ("kvL_gap_prefill", "logit_gap")),
    ("skip_as_expert_0", ("kvL_gap_prefill", "logit_gap")),
    ("k_before_rotary", ("kv0_gap_prefill", "kv0_gap_decode")),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(fault, by):
    broken = _broken(fault)
    assert broken["correct"] is False, broken["checks"]
    failed = [k for k, c in broken["checks"].items()
              if c["value"] > c["limit"]]
    assert set(by) & set(failed), broken["checks"]
