"""The ``glm4moelite`` block's own tests (CPU, small size): the configuration
against the catalog row, its work counts against hand-worked numbers, its
control through the runner's ``judge`` with the cell's own limits, and whole
runs of ``run.py`` with the timed path broken.

    python -m pytest benchmark/tests/test_glm4moelite_block.py -q
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

from blocks import glm4moelite as glm  # noqa: E402
from harness import loadgen, manifest  # noqa: E402

CONFIG = "benchmark/configs/glm-4.7-flash-l24-e8-serve.json"
MIX = "benchmark/traffic/agent-steady.json"
work = importlib.import_module("blocks.glm4moelite.work")
ref_mod = importlib.import_module("blocks.glm4moelite.reference")
# the catalog row's ``config`` (architectures.jsonl, GLM-4.7-Flash)
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def _small(cfg):
    full = cfg["runner_args"]["model"]
    return dict({k: v for k, v in full.items() if k != "rehearsal"},
                **full["rehearsal"])


def test_the_configuration_is_the_catalog_row_cut_in_depth_and_experts():
    cfg = manifest.load_json(ROOT, CONFIG)
    reduced = {"num_hidden_layers", "n_routed_experts"}
    for key, value in CATALOG.items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert set(cfg["reduced"]) == reduced and cfg["block"] == "glm4moelite"
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"]) == (24, 8)
    assert cfg["published"]["num_hidden_layers"] == 47
    assert cfg["published"]["n_routed_experts"] == 64
    m = cfg["runner_args"]["model"]
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
                "first_k_dense_replace", "routed_scaling_factor",
                "rope_theta", "rms_norm_eps", "num_hidden_layers",
                "vocab_size"):
        assert m[key] == cfg[key], key
    assert m["num_routed_experts"] == 64 and m["expert_rank"] == 0
    assert m["max_context"] == 32768 < cfg["max_position_embeddings"]
    assert glm.weight_count(m) == 3_176_138_176
    assert cfg["published"]["parameters"] == 29_943_393_920 \
        == glm.weight_count(m, layers=47, held=64)
    for key in ("rotary", "softmax_scale", "low_rank_norms", "wkvb_layout",
                "router", "shared_expert", "experts", "initialisation"):
        assert key in cfg["assumed"], key
    assert "8 share each layer, 2 pipeline stages" in cfg["deployment"]
    assert any("multi-token-prediction" in d for d in cfg["departures"])
    man = manifest.load(ROOT)
    entry = [c for c in man["configs"]
             if c["name"] == "glm-4.7-flash-l24-e8-serve"][0]
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == cfg["source"]
    cell = manifest.cell_of(man, "agent-steady")
    assert cell["chips"] == 1
    assert cell["config"] == "glm-4.7-flash-l24-e8-serve"
    mine = {m["name"]: m for m in man["per_layer"]
            if m["name"] in ("mla_decode_roofline", "latent_rows_per_step")}
    assert len(mine) == 2
    assert all(m["workloads"][0] == "agent-steady"
               and m["moves"] == "gap_mean_ms" for m in mine.values())
    # a step's chunk of a long prompt is one program's rows
    eng = cfg["runner_args"]["engine"]
    assert {-(-((eng["token_budget"] - b) // 128 * 128) // 2048)
            for b in range(eng["max_batch"] + 1)} == {1}
    # the pool by reckoning: 196 608 tokens at 27 648 B
    kv = cfg["runner_args"]["kv"]
    assert kv["num_blocks"] * kv["block_size"] * 24 * 1152 == 5_435_817_984


def test_work_counts_against_hand_worked_numbers():
    m = dict(hidden_size=8, num_attention_heads=2, q_lora_rank=6,
             kv_lora_rank=4, qk_nope_head_dim=3, qk_rope_head_dim=2,
             v_head_dim=5, intermediate_size=16, moe_intermediate_size=12,
             n_routed_experts=2, num_routed_experts=8, expert_rank=0,
             num_experts_per_tok=2, n_shared_experts=1,
             first_k_dense_replace=1, routed_scaling_factor=1.8,
             rope_theta=1e6, rms_norm_eps=1e-5, num_hidden_layers=3,
             vocab_size=32)
    d, h, ql, r, nope, rot, vd, ffd, ff, e, held = 8, 2, 6, 4, 3, 2, 5, 16, \
        12, 8, 2
    lat, qk = r + rot, nope + rot
    mats = d * ql + ql * h * qk + d * lat + r * h * (nope + vd) + h * vd * d
    attn = mats + ql + r
    dense, expert, router = 3 * d * ffd, 3 * d * ff, d * e + e
    layer0 = attn + dense + 2 * d
    layer = attn + expert + router + 2 * d + held * expert
    weights = layer0 + 2 * layer + 2 * 32 * d + d
    z = ref_mod.sizes(m)
    assert work.attention_weights(z) == attn
    assert glm.weight_count(m) == weights
    assert glm.weight_count(m, layers=2, held=8) == (
        layer0 + attn + expert + router + 2 * d + 8 * expert
        + 2 * 32 * d + d)
    assert work.stored_bytes(m) == 2 * weights + 2 * 2 * router
    # what every step reads: all but the routed experts and the embedding
    always = 2 * (3 * (attn + 2 * d) + dense + 2 * expert + 32 * d + d) \
        + 4 * 2 * router
    assert glm.decode_step_bytes([], m) == always
    # one row at context 10: top-2 of 8 with 2 held is half a pair a layer,
    # 2 (1 - (1/2)^(1/2)) held experts expected hit; 10 latent rows read
    # and the row's own written, ONCE each (key and value at once)
    pairs = 2 * 2 / 8
    hit = 2 * (1 - 0.5 ** pairs)
    routed = 2 * hit * expert + 2 * 4 * pairs * d
    assert glm.decode_step_bytes([10], m) == int(
        always + 2 * routed + 3 * 2 * lat * 11)
    row = 2 * (3 * mats + dense + 2 * (expert + d * e))
    absorbed = h * 2 * (lat + r)     # a head's scores and output a row
    assert glm.decode_step_flops([10, 3], m) == int(
        2 * row + 2 * 2 * pairs * 2 * expert + 3 * absorbed * 13
        + 2 * 2 * d * 32)
    # a chunk of 6 rows from row 4, expanded: 2 (qk + vd) a head and pair
    n_pairs = 6 * 4 + 6 * 7 // 2
    expanded = h * 2 * (qk + vd)
    assert glm.prefill_chunk_flops(6, 4, m, False) == int(
        6 * row + 2 * 6 * pairs * 2 * expert + 3 * expanded * n_pairs)
    assert glm.prefill_chunk_flops(6, 4, m, True) \
        == glm.prefill_chunk_flops(6, 4, m, False) + 2 * d * 32
    # a prompt's work is the same however it is cut
    assert glm.prefill_flops(10, m) == (
        glm.prefill_chunk_flops(4, 0, m, False)
        + glm.prefill_chunk_flops(6, 4, m, True))
    hit6 = 2 * (1 - 0.5 ** (6 * pairs))
    assert glm.prefill_bytes(6, m) == int(
        always + 2 * (2 * hit6 * expert + 2 * 4 * 6 * pairs * d)
        + 3 * 2 * lat * 6)
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    pattern, least = glm.KERNELS["moe_expert_roofline"]
    assert pattern == "moe_grouped_matmul"
    hit2 = 2 * (1 - 0.5 ** (2 * pairs))
    want = 2 * ((2 * hit2 * expert + 2 * 4 * 2 * pairs * d)
                + (2 * hit6 * expert + 2 * 4 * 6 * pairs * d)) / 1e9
    assert abs(least({"prefill": [6], "decode": [[9, 9]]}, m, peak)
               - want) < 1e-12
    # the decode kernel's least time: each live row once, 2 lat bytes, in
    # every layer (the bytes bound it)
    pattern, least = glm.KERNELS["mla_decode_roofline"]
    assert pattern == "mla_paged_decode"
    assert abs(least({"prefill": [6], "decode": [[9, 9], [100]]}, m, peak)
               - 3 * 2 * lat * 118 / 1e9) < 1e-15
    assert glm.mla_decode_least_s({"prefill": [], "decode": []}, m,
                                  peak) == 0
    # the published widths: 1152 B and 2 x 20 x (576 + 512) operations a
    # row; 4.4 of 8 held experts expected at 16 rows (8 pairs a layer)
    z = ref_mod.sizes(manifest.load_json(ROOT, CONFIG)["runner_args"]["model"])
    assert 2 * z["lat"] == 1152
    assert 2 * z["h"] * (z["lat"] + z["r"]) == 43_520
    assert work.pairs_a_row(z) == 0.5
    assert round(work.experts_hit(16, z), 2) == round(8 * (1 - 0.875 ** 8), 2)


def test_control_fails_the_cells_own_limits_at_a_small_size():
    """Through the runner's own ``judge`` and the cell's own limits: the
    reference in the configuration's arithmetic, put in the program's place,
    is correct; the reference with matrices and latent rows in float8, put
    there, is not, by layer 0's rows among others; no state to read is not
    correct either; thin tokens are left out and counted."""
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
    ref = glm.reference(11, args, pad_to=32)
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
    sound = sr.judge(glm, reqs, reqs, held, ref, mix, mix["limits"])
    assert common.correct_of(sound["checks"]), json.dumps(sound["checks"])
    assert sound["kv_rows"]["prefill"] > 0 and sound["kv_rows"]["decode"] > 0
    assert set(glm.STATE_CHECKS) <= set(sound["checks"])
    control = sr.judge(glm, reqs, reqs, held, ref, mix, mix["limits"],
                       control=True)
    assert not common.correct_of(control["checks"]), control["checks"]
    failed = {k for k, c in control["checks"].items()
              if c["value"] > c["limit"]}
    assert {"lat0_gap_prefill", "lat0_gap_decode"} <= failed, \
        control["checks"]
    # the rotary key left unrotated (a fault that keeps every norm)
    rot = m["qk_rope_head_dim"]

    def unrotated(st):
        lat = np.array(st["lat0"])
        lat[:, -rot:] = np.roll(lat[:, -rot:], 1, axis=1)
        return dict(st, lat0=lat)

    mixed = {k: (n, unrotated(st)) for k, (n, st) in held.items()}
    wrong = sr.judge(glm, reqs, reqs, mixed, ref, mix, mix["limits"])
    assert {k for k, c in wrong["checks"].items()
            if c["value"] > c["limit"]} == {"lat0_gap_prefill",
                                            "lat0_gap_decode"}
    none = sr.judge(glm, reqs, reqs, {}, ref, mix, mix["limits"])
    assert none["checks"]["state_short"]["value"] == 3
    assert not common.correct_of(none["checks"])
    # every token thin: nothing is held to logit_gap or the last layer's
    # rows, and the share of thin tokens is what fails
    ref.route_margin = 10.0
    thin = sr.judge(glm, reqs, reqs, held, ref, mix, mix["limits"])
    assert thin["checks"]["logit_gap"]["value"] == 0.0
    assert thin["checks"]["route_thin_share_prefill"]["value"] == 1.0
    assert thin["kv_gap_by_layer"]["decode"][-1] == 1.0
    assert not common.correct_of(thin["checks"])


def _broken(fault, trace="0"):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_glm4moelite.py"), fault,
         "agent-steady", "--seed", "77", "--seconds", "3", "--trace", trace],
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
    # a traced run's line has what the new reader and the shared ones read
    for name in ("latent_rows_per_step", "expert_pairs_per_step",
                 "experts_hit_share", "cache_bytes_per_token",
                 "prefill_chunk_ms", "batch_occupancy"):
        assert sound["metrics"][name]["value"] > 0, name
    # a token holds kv_lora + rot values a layer, and no value row
    assert sound["metrics"]["cache_bytes_per_token"]["value"] < 2 * 4 * 40 * 2


@pytest.mark.parametrize("fault,by", [
    ("chunk_from_row_0", ("latL_gap_prefill", "logit_gap")),
    ("key_before_rotary", ("lat0_gap_prefill", "lat0_gap_decode")),
    ("scale_forgotten", ("latL_gap_prefill", "logit_gap")),
    ("decode_reads_one_page", ("latL_gap_decode", "logit_gap")),
    ("shared_expert_dropped", ("latL_gap_prefill", "logit_gap")),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(fault, by):
    broken = _broken(fault)
    assert broken["correct"] is False, broken["checks"]
    failed = [k for k, c in broken["checks"].items()
              if c["value"] > c["limit"]]
    assert set(by) & set(failed), broken["checks"]
