"""The ``zaya`` model (compressed convolutional attention beside top-1 experts
behind an MLP router that carries its state down the layers) against the
plain reference (``benchmark/blocks/zaya/reference.py``) at a small size on
the CPU: prefill, then decode through the cache, against the reference's full
forward on seeded weights; a prompt prefilled in chunks against the same
prompt prefilled whole; each piece of the layer dropped in turn; and through
``ServingEngine``, where a long prompt goes a chunk a step beside the decode
rows. Tokens are compared through the reference's LOGITS.

Tolerances. Both sides compute in float32 on the CPU (no operand rounding)
over the same stored weight VALUES, so they differ by the order of float32
sums and, where a value lands within that of a bfloat16 rounding boundary, by
one bfloat16 step of a stored K/V element: ``TAIL_TOL`` 2e-5 for layer 0's
float32 tail (no stored row lies before it; 0 measured), ``ROWS_TOL`` 1e-3
for the bfloat16 K/V rows (one flipped element of a 32-wide row reads 1e-4;
a wrong row reads of order 1), ``LOGIT_TOL`` 1e-4 (logits are of order 0.3).
A dropped piece reads 1e-2 and more (``test_each_piece_is_in_the_model``).
"""

import importlib
import json
import os
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

R = importlib.import_module("blocks.zaya.reference")  # noqa: E402
W = importlib.import_module("blocks.zaya.work")  # noqa: E402
from brpc_tpu.serving import (EngineConfig, HybridCacheConfig,  # noqa: E402
                              LlmServingService, ServingEngine, ZayaConfig,
                              ZayaModel, zaya_model)
from brpc_tpu.serving.moe_model import expert_layer  # noqa: E402

M = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
         head_dim=16, moe_intermediate_size=64, num_experts=4,
         num_experts_per_tok=1, router_hidden_size=16, cca_time0=2,
         cca_time1=2, partial_rotary_factor=0.5, rope_theta=5e6,
         rms_norm_eps=1e-5, num_hidden_layers=4, vocab_size=256)
PUBLISHED = dict(M, hidden_size=2048, num_attention_heads=8, head_dim=128,
                 moe_intermediate_size=2048, num_experts=16,
                 router_hidden_size=256, num_hidden_layers=20,
                 vocab_size=262272)
SEED, NEW, BS = 5, 10, 16
LENS = (37, 9, 150)
TAIL_TOL, ROWS_TOL, LOGIT_TOL = 2e-5, 1e-3, 1e-4


def _values(host):
    """The recipe's stored arrays as float32 values."""
    return {k: (v if v.dtype == np.float32 else
                (v.astype(np.uint32) << 16).view(np.float32))
            for k, v in host.items()}


def _bf16(x):
    bits = R.bf16_bits(np.asarray(x, np.float32)).astype(np.uint32) << 16
    return bits.view(np.float32).reshape(np.shape(x))


def _weights():
    """The recipe's draw, with the norm weights (constants in the recipe)
    drawn as well and the attention's output projection at the spread of
    the other matrices (the recipe's is a hundredth: PERF.md section 4), so
    that every term carries weight."""
    host = _values(R.draw_weights(SEED, M))
    rng = np.random.RandomState(1)
    for k, v in host.items():
        if k.endswith(".wo"):
            host[k] = _bf16(v * 100.0)
        elif k.endswith(("ln1", "ln2", "lnf")):
            host[k] = _bf16(1 + rng.standard_normal(v.shape) * 0.1)
        elif k.endswith("r_ln"):
            host[k] = (1 + rng.standard_normal(v.shape) * 0.1).astype(
                np.float32)
    return host


def _ref(host):
    return R.Reference(SEED, M, "float32", pad_to=16, host_weights={
        k: (v if R.is_float32(k) else R.bf16_bits(v))
        for k, v in host.items()})


def _stand(weights=None, attn="reference", **cache):
    cfg = ZayaConfig(**M, max_context=1024, seed=SEED, attn=attn)
    cache = dict(dict(block_size=BS, num_blocks=96, max_sequences=4), **cache)
    kv = cfg.cache(HybridCacheConfig(**cache))
    return ZayaModel(cfg, kv, weights=weights), kv


def _rel(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def _rows(pool, layer, table, n):
    pos = np.arange(n)
    at = np.asarray(table, np.int32)[pos // BS] * BS + pos % BS
    return np.asarray(pool[layer].astype(np.float32))[at]


def _tail(kv, copy, layer, table):
    return np.asarray(kv.conv[copy, layer, table.slot, 0])


def _decode(model, kv, sids, prompts, outs, steps):
    for step in range(steps):
        tables = [kv.extend_sequence(s, len(p) + step + 1)
                  for s, p in zip(sids, prompts)]
        nxt = model.decode_step(
            np.asarray([o[-1] for o in outs], np.int32),
            np.asarray([len(p) + step for p in prompts], np.int32), tables)
        for o, t in zip(outs, nxt):
            o.append(int(t))
    return tables


@pytest.fixture(scope="module")
def world():
    """Three prompts prefilled whole, then decoded together for NEW - 1
    steps; the reference's forward over each prompt + answer."""
    host = _weights()
    model, kv = _stand(weights=host)
    ref = _ref(host)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 256, size=n).astype(np.int32) for n in LENS]
    sids = [1, 2, 3]
    tables = [kv.alloc_sequence(s, len(p)) for s, p in zip(sids, prompts)]
    outs = [[model.prefill(p, t)] for p, t in zip(prompts, tables)]
    at_end = [np.asarray(kv.conv[0, :, t.slot, 0]) for t in tables]
    counted = json.loads(json.dumps(model.moe_counters))
    tables = _decode(model, kv, sids, prompts, outs, NEW - 1)
    refs, routed = [], []
    for p, o in zip(prompts, outs):
        refs.append(ref.forward(p, o, rows_pad=NEW))
        routed.append(ref.routed)
    return dict(host=host, model=model, kv=kv, ref=ref, prompts=prompts,
                tables=tables, outs=outs, refs=refs, at_end=at_end,
                routed=routed, counted=counted)


# ------------------------------------------------ against the plain reference
@pytest.mark.parametrize("i", range(len(LENS)))
def test_prefill_token_is_the_references_best_logit(world, i):
    logits = np.asarray(world["refs"][i][0])
    assert logits[0].max() - logits[0, world["outs"][i][0]] <= LOGIT_TOL


@pytest.mark.parametrize("i", range(len(LENS)))
def test_decode_through_the_cache_follows_the_references_logits(world, i):
    logits = np.asarray(world["refs"][i][0])
    served = np.asarray(world["outs"][i])
    assert len(served) == NEW >= 9
    gaps = logits.max(axis=-1) - logits[np.arange(NEW), served]
    assert gaps.max() <= LOGIT_TOL, gaps


@pytest.mark.parametrize("i", range(len(LENS)))
@pytest.mark.parametrize("layer,part", [(0, "0"), (-1, "L")])
def test_rows_left_in_the_pages_are_the_references(world, i, layer, part):
    """Layer 0's rows (their second V head the token before's) and the last
    layer's, behind every expert sublayer and hand-down of the router's
    state before it."""
    kv, t, state = world["kv"], world["tables"][i], world["refs"][i][1]
    n = LENS[i] + NEW - 1
    for pool, name in ((kv.full.k_pool, "k"), (kv.full.v_pool, "v")):
        assert _rel(_rows(pool, layer, t, n),
                    np.asarray(state[name + part])[:n]) <= ROWS_TOL


@pytest.mark.parametrize("i", range(len(LENS)))
def test_layer_0s_tail_equals_the_references(world, i):
    kv, t, state = world["kv"], world["tables"][i], world["refs"][i][1]
    # index 1 of the leading axis keeps the prompt's end; 0 runs on
    for got, want in ((world["at_end"][i][0], state["tail0"][0]),
                      (_tail(kv, 1, 0, t), state["tail0"][0]),
                      (_tail(kv, 0, 0, t), state["tail0"][1])):
        assert _rel(got, want) <= TAIL_TOL
    assert np.abs(np.asarray(state["tail0"][1])).min() > 0   # a whole tail


def test_the_routers_choices_are_the_references_and_the_skip_is_counted(
        world):
    """The program's counters against what the reference routed: pairs to
    the experts, rows to the output that computes nothing."""
    skip = M["num_experts"]
    for phase, span in (("prefill", lambda n: slice(0, n)),
                        ("decode", lambda n: slice(n, n + NEW - 1))):
        rows = np.concatenate([r[:, span(n)].reshape(-1)
                               for r, n in zip(world["routed"], LENS)])
        c = (world["counted"] if phase == "prefill"
             else world["model"].moe_counters)[phase]
        assert c["skipped"] == int((rows == skip).sum()) > 0
        assert c["pairs"] == int((rows != skip).sum())
    c = world["model"].moe_counters["decode"]
    assert c["layer_launches"] == (NEW - 1) * M["num_hidden_layers"]
    assert c["pairs"] + c["skipped"] == c["layer_launches"] * len(LENS)
    assert set(np.unique(np.concatenate(
        [r.reshape(-1) for r in world["routed"]]))) == set(range(skip + 1))


def test_flash_carry_path_agrees_with_the_blocked_one(world):
    """The kernel path (interpreted on the CPU), whole and from a chunk
    boundary, serves the blocked path's first token and state."""
    model, kv = _stand(weights=world["host"], attn="flash")
    p = world["prompts"][2]
    t = kv.alloc_sequence(1, len(p))
    model.prefill_suffix(p[:128], t, 0)
    first = model.prefill_suffix(p, t, 128)
    assert first == world["outs"][2][0]
    state = world["refs"][2][1]
    assert _rel(_tail(kv, 1, 0, t), state["tail0"][0]) <= TAIL_TOL
    assert _rel(_rows(kv.full.k_pool, -1, t, len(p)),
                np.asarray(state["kL"])[:len(p)]) <= ROWS_TOL


# -------------------------------------------- chunked against whole prefill
@pytest.mark.parametrize("cuts", [
    (70,),              # splits a block
    (64, 128),          # on block boundaries
    (128, 149),         # the last chunk is the prompt's last row alone
    (7, 8, 130),        # a chunk of ONE row mid-prompt
], ids=["mid_block", "on_blocks", "last_row_alone", "one_row_chunk"])
def test_chunked_prefill_agrees_with_whole_prefill(world, cuts):
    """The same prompt in chunks: every layer's tail (both conv stages' rows
    and the shifted value), every layer's K/V rows and the first token agree
    with the whole prefill to rounding."""
    model, kv = world["model"], world["kv"]
    p, whole = world["prompts"][2], world["tables"][2]
    t = kv.alloc_sequence(9, len(p))
    try:
        edges = (0,) + cuts + (len(p),)
        for a, b in zip(edges, edges[1:]):
            first = model.prefill_suffix(p[:b], t, a)
        assert first == world["outs"][2][0]
        for layer in range(M["num_hidden_layers"]):
            tol = 5e-4 if layer else TAIL_TOL
            for copy in (0, 1):
                assert _rel(_tail(kv, copy, layer, t),
                            world["at_end"][2][layer]) <= tol
            for pool in (kv.full.k_pool, kv.full.v_pool):
                assert _rel(_rows(pool, layer, t, len(p)),
                            _rows(pool, layer, whole, len(p))) <= ROWS_TOL
    finally:
        kv.free_sequence(9)


def test_a_chunk_started_from_a_zero_tail_is_far_outside(world):
    """The fault the comparison is there for: the second chunk as a prompt
    of its own (tail zero, rows from 0) parts by orders."""
    model, kv = world["model"], world["kv"]
    p = world["prompts"][2]
    t = kv.alloc_sequence(9, len(p))
    try:
        model.prefill_suffix(p[:70], t, 0)
        model.prefill_suffix(p[70:], t, 0)
        # the first row of that chunk saw zeros where rows 68, 69 were
        whole = _rows(kv.full.k_pool, 0, world["tables"][2], len(p))
        assert _rel(_rows(kv.full.k_pool, 0, t, 1), whole[70:71]) \
            > 100 * ROWS_TOL
    finally:
        kv.free_sequence(9)


# ------------------------------------------------------- each piece matters
def _without(piece, host):
    """(weights, patches) of a program with one piece of the layer left
    out: by the weights that carry it where it has some, by a patch of the
    program's own function where it has none."""
    w, patches = dict(host), {}
    layers = range(M["num_hidden_layers"])
    if piece == "value_shift":
        orig = ZayaModel._layer

        def layer(self, w_, i, x, r, counts, live, pos, tile, mix, attend):
            def same_token(zz, v2):
                return mix(zz, v2)[0], v2

            return orig(self, w_, i, x, r, counts, live, pos, tile,
                        same_token, attend)

        patches[(ZayaModel, "_layer")] = layer
    elif piece == "second_conv":
        for l in layers:
            eye = np.zeros_like(w[f"l{l}.c1w"])
            eye[-1] = np.eye(M["head_dim"])      # the last tap passes
            w[f"l{l}.c1w"], w[f"l{l}.c1b"] = eye, w[f"l{l}.c1b"] * 0
    elif piece == "qk_mean":
        patches[(zaya_model, "qk_mean")] = lambda cfg, zz: (0.0, 0.0)
    elif piece == "temperature":
        for l in layers:
            w[f"l{l}.temp"] = np.ones_like(w[f"l{l}.temp"])
    elif piece == "depth_average":
        orig_router = zaya_model.zaya_router
        patches[(zaya_model, "zaya_router")] = \
            lambda cfg, wl, h, r, live: orig_router(cfg, wl, h, r * 0, live)
    elif piece == "balancing_biases":
        for l in layers:
            w[f"l{l}.r_bias"] = w[f"l{l}.r_bias"] * 0
    elif piece == "residual_scaling":
        for l in layers:
            for k in ("res_a", "res_m"):
                w[f"l{l}.{k}"] = np.broadcast_to(
                    np.asarray([1, 0, 1, 0], np.float32)[:, None],
                    w[f"l{l}.{k}"].shape).copy()
    else:
        raise ValueError(piece)
    return w, patches


@pytest.mark.parametrize("piece", [
    "value_shift", "second_conv", "qk_mean", "temperature", "depth_average",
    "balancing_biases", "residual_scaling"])
def test_each_piece_is_in_the_model(world, piece, monkeypatch):
    """The program with ONE piece dropped, against the reference that has
    it: the last layer's rows, the logits or the routing part by orders of
    the tolerance the whole model is held to."""
    weights, patches = _without(piece, world["host"])
    for (owner, name), fn in patches.items():
        monkeypatch.setattr(owner, name, fn)
    model, kv = _stand(weights=weights)
    p, state = world["prompts"][2], world["refs"][2][1]
    t = kv.alloc_sequence(1, len(p))
    model.prefill(p, t)
    off = max(_rel(_rows(pool, -1, t, len(p)), np.asarray(state[k])[:len(p)])
              for pool, k in ((kv.full.k_pool, "kL"), (kv.full.v_pool, "vL")))
    assert off > 10 * ROWS_TOL, (piece, off)


# ------------------------------------------------------------ single pieces
def test_a_row_sent_to_the_skip_output_gets_exactly_the_scaled_residual():
    import jax.numpy as jnp

    cfg = ZayaConfig(**M)
    rng = np.random.RandomState(3)
    d, ff, n = cfg.d_model, cfg.d_ff, cfg.n_experts
    h = jnp.asarray(rng.standard_normal((8, d)), jnp.float32)
    wgu = jnp.asarray(rng.standard_normal((n, d, 2 * ff)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((n, ff, d)) * 0.1, jnp.float32)
    idx = jnp.asarray([[0], [n], [2], [n], [-1], [3], [n], [1]], jnp.int32)
    out, cnt = expert_layer(cfg, h, idx, jnp.ones((8, 1)), wgu, wd, 16)
    out = np.asarray(out)
    skipped = np.asarray(idx)[:, 0] >= n
    assert (out[skipped] == 0).all() and (out[4] == 0).all()
    assert (np.abs(out[[0, 2, 5, 7]]).max(axis=1) > 0).all()
    assert np.asarray(cnt).tolist() == [1, 1, 1, 1]
    res = jnp.asarray(rng.standard_normal((4, d)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((8, d)), jnp.float32)
    merged = np.asarray(zaya_model.residual_scale(res, x, out))
    want = np.asarray(res[0] * x + res[1] + res[3])
    assert np.array_equal(merged[skipped], want[skipped])


@pytest.mark.parametrize("first", [None, 4])
def test_expert_layer_at_top_1_with_every_expert_held_is_the_plain_loop(
        first):
    """``k = 1``, ``held = all``, an index that is nobody's among them; with
    ``first`` the experts lie in a stack of two layers' and the second
    layer's are read."""
    import jax
    import jax.numpy as jnp

    cfg = ZayaConfig(**M)
    rng = np.random.RandomState(4)
    d, ff, n = cfg.d_model, cfg.d_ff, cfg.n_experts
    h = jnp.asarray(rng.standard_normal((40, d)), jnp.float32)
    stack = 2 * n if first else n
    wgu = jnp.asarray(rng.standard_normal((stack, d, 2 * ff)) * 0.1,
                      jnp.float32)
    wd = jnp.asarray(rng.standard_normal((stack, ff, d)) * 0.1, jnp.float32)
    idx = jnp.asarray(rng.randint(0, n + 1, size=(40, 1)), jnp.int32)
    wts = jnp.asarray(rng.uniform(0.2, 0.9, size=(40, 1)), jnp.float32)
    out, cnt = expert_layer(cfg, h, idx, wts, wgu, wd, 16, first=first)
    want = np.zeros((40, d), np.float32)
    with jax.default_matmul_precision("highest"):
        for e in range(n):
            gu = h @ wgu[(first or 0) + e]
            y = (jax.nn.silu(gu[:, :ff]) * gu[:, ff:]) @ wd[(first or 0) + e]
            want += np.asarray(jnp.where(idx == e, wts * y, 0.0))
    assert _rel(out, want) <= 1e-5
    assert np.asarray(cnt).tolist() == [
        int((np.asarray(idx) == e).sum()) for e in range(n)]


def test_rotary_turns_the_first_half_of_a_head_and_leaves_the_rest():
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.standard_normal((6, 3, 128)), jnp.float32)
    pos = jnp.asarray([0, 1, 7, 100, 4095, 32767], jnp.int32)
    got = np.asarray(zaya_model.rope_half(x, pos, 64, 5e6))
    assert np.array_equal(got[..., 64:], np.asarray(x)[..., 64:])
    assert np.array_equal(got[0], np.asarray(x)[0])          # position 0
    assert np.abs(got[1:, :, :64] - np.asarray(x)[1:, :, :64]).max() > 0.1
    # pairs (j, j + 32) turn: each keeps its norm
    pair = lambda a: a[..., :32] ** 2 + a[..., 32:64] ** 2   # noqa: E731
    assert np.allclose(pair(got), pair(np.asarray(x)), rtol=1e-4, atol=1e-5)
    # the reference's own slice-and-concatenate form
    assert np.abs(got - np.asarray(R.rope_half(x, pos, 64, 5e6))).max() \
        <= 1e-5


# -------------------------------------------------- the count and the manager
def test_weight_count_at_the_published_widths_from_shapes_on_both_sides():
    """``work.py`` (the reference's shapes) and the program's shapes give
    the configuration's count; the whole model's is the published 8.84B."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "zaya1-8b-l20-serve.json")) as f:
        cfg_file = json.load(f)
    m = {k: v for k, v in cfg_file["runner_args"]["model"].items()
         if k not in ("rehearsal", "max_context", "attn")}
    assert m == {k: PUBLISHED[k] for k in m}
    assert W.weight_count(m) == 4_688_810_364
    assert W.layer_parameters(R.sizes(m)) == 207_583_763
    assert W.weight_count(dict(m, num_hidden_layers=40)) == 8_840_485_624 \
        == cfg_file["published"]["parameters"]
    cfg = ZayaConfig(**m)
    mine = sum(int(np.prod(a[2])) for layer in [None] + list(range(20))
               for a in cfg.arrays(layer))
    assert mine == 4_688_810_364
    assert sum(int(np.prod(a[2])) for a in R.arrays(m)) == mine
    assert W.stored_bytes(m) == 2 * mine + 2 * 20 * 661_009


def test_staged_bytes_are_two_a_parameter_and_four_in_the_router(world):
    """Every parameter is held once: bfloat16, the router's float32."""
    z = R.sizes(M)
    router = M["num_hidden_layers"] * W._count(R.ROUTER, z)
    assert world["model"].param_nbytes == 2 * W.weight_count(M) + 2 * router \
        == W.stored_bytes(M)


def test_program_and_reference_draw_the_same_weights():
    """With no weights handed over, the model draws the recipe the
    reference draws independently."""
    model, _kv = _stand()
    host = _values(R.draw_weights(SEED, M))
    for l in (0, 3):
        for k, arr in model.layer_weights(l).items():
            assert np.array_equal(np.asarray(arr.astype(np.float32)),
                                  host[f"l{l}.{k}"]), k
    for k in ("embed", "lnf"):
        assert np.array_equal(
            np.asarray(model._params[k].astype(np.float32)), host[k])


def test_without_a_scan_state_the_manager_allocates_none_and_counts_tails(
        world):
    kv, cfg = world["kv"], world["model"].config
    layers = M["num_hidden_layers"]
    assert kv.ssm.size == 0 and kv.ssm.shape[3] == 0
    assert kv.conv.shape == (2, layers, 5, 1, cfg.tail_width)
    assert cfg.tail_width == 2 * (4 + 2) * 16 + 16
    assert kv.recurrent_state and kv.ring_blocks == 0
    snap = kv.snapshot()
    assert snap["slots"] == {"used": 3, "total": 4}
    blocks = sum(-(-(n + NEW - 1) // BS) for n in LENS)
    row = 2 * layers * cfg.kv_dim * 2               # K and V, bfloat16
    assert snap["cache_bytes"] == blocks * BS * row \
        + 3 * layers * cfg.tail_width * 4
    # (the chunk tests, where this worker ran them, held a fourth sequence)
    assert snap["cache_bytes_peak"] >= snap["cache_bytes"]
    # the published widths: 20 KB a token, 215 KB of tails a sequence
    big = ZayaConfig(**PUBLISHED)
    assert 2 * 20 * big.kv_dim * 2 == 20480
    assert 20 * big.tail_width * 4 == 215_040


# ------------------------------------------------------------ through the engine
@pytest.fixture(scope="module")
def served(world):
    """ONE model and manager for the engine tests (its programs compile
    once); each test starts an engine of its own over it and leaves the
    manager idle."""
    return _stand(weights=world["host"])


@pytest.fixture
def engine(served):
    model, kv = served
    made = []

    def start(budget):
        eng = ServingEngine(model, kv, EngineConfig(
            max_batch=4, token_budget=budget, idle_wait_s=0.005)).start()
        LlmServingService(eng)
        made.append(eng)
        return model, kv, eng

    yield start
    for eng in made:
        eng.stop()
    for name in ("prefill_suffix", "decode_step"):
        model.__dict__.pop(name, None)
    kv.assert_idle("engine test left the manager idle")


def _submit(eng, prompt, new, got, key):
    ev = threading.Event()

    def done(resp):
        got[key] = list(resp.tokens) if resp is not None else None
        ev.set()

    code, seq = eng.submit(prompt, new, done=done)
    assert code == 0
    return ev, seq


@pytest.fixture(scope="module")
def long_prompt():
    return np.random.RandomState(7).randint(1, 256, size=700).astype(np.int32)


@pytest.fixture(scope="module")
def unchunked(world, served, long_prompt):
    """The long prompt and a short one served with a budget that holds
    either whole."""
    model, kv = served
    eng = ServingEngine(model, kv, EngineConfig(
        max_batch=4, token_budget=2048, idle_wait_s=0.005)).start()
    got = {}
    evs = [_submit(eng, long_prompt, 6, got, "long")[0],
           _submit(eng, world["prompts"][0], 40, got, "short")[0]]
    assert all(ev.wait(180) for ev in evs)
    snap = eng.snapshot()
    eng.stop()
    kv.assert_idle("engine stopped")
    assert snap["prefill_chunks"] == 0
    return got


def test_a_long_prompt_goes_a_chunk_a_step_beside_the_decode_rows(
        world, long_prompt, unchunked, engine):
    """token_budget 132 leaves 128 rows a step: the 700-row prompt takes 6
    steps beside the running sequence; the served tokens equal those of
    unchunked serving; ``snapshot()["moe"]`` and `/serving` count the rows
    the router skipped."""
    model, kv, eng = engine(132)
    assert eng._chunk_unit == 128
    before = json.loads(json.dumps(eng.snapshot()["moe"]))
    chunks = []
    orig = model.prefill_suffix

    def suffix(tokens, table, start):
        chunks.append((start, len(tokens)))
        return orig(tokens, table, start)

    model.prefill_suffix = suffix
    got = {}
    ev_short, _ = _submit(eng, world["prompts"][0], 40, got, "short")
    while eng.tokens_generated < 2:      # the short one is decoding
        threading.Event().wait(0.002)
    ev_long, _ = _submit(eng, long_prompt, 6, got, "long")
    assert ev_short.wait(180) and ev_long.wait(180)
    snap = eng.snapshot()
    from brpc_tpu.builtin.services import serving_service
    from brpc_tpu.policy.http_protocol import HttpMessage
    lines = [l for l in serving_service(None, HttpMessage())[2].splitlines()
             if l.strip().startswith("moe:")]
    eng.stop()
    kv.assert_idle("engine stopped")
    assert got == unchunked
    assert chunks == [(0, 128), (128, 256), (256, 384), (384, 512),
                      (512, 640), (640, 700)]
    assert snap["prefill_chunks"] == 6 and snap["prefill_chunk_rows"] == 700
    moe = snap["moe"]
    assert moe["experts_held"] == M["num_experts"]
    for phase in ("decode", "prefill"):
        now, was = moe[phase], before[phase]
        assert now["skipped"] > was["skipped"]
        assert now["pairs"] > was["pairs"]
    # every decode row of every layer went to an expert or to the skip
    dec = {k: moe["decode"][k] - before["decode"][k] for k in moe["decode"]}
    assert dec["pairs"] + dec["skipped"] == (39 + 5) * M["num_hidden_layers"]
    assert lines and f"skipped={moe['decode']['skipped']}" in lines[0]
    assert f"skipped={moe['prefill']['skipped']}" in lines[0]


def test_cancel_mid_prompt_frees_slot_and_pages(world, long_prompt, engine):
    class Sock:
        failed = False

    class Cntl:
        _srv_socket = Sock()
        deadline_mono = 0.0

        def set_failed(self, code, reason):
            self.failed = (code, reason)

    model, kv, eng = engine(132)
    cntl, got = Cntl(), {}
    orig = model.prefill_suffix

    def suffix(tokens, table, start):
        if start >= 256:
            cntl._srv_socket.failed = True      # the client went away
        return orig(tokens, table, start)

    model.prefill_suffix = suffix
    ev = threading.Event()

    def done(resp):
        got["long"] = resp
        ev.set()

    code, seq = eng.submit(long_prompt, 6, cntl=cntl, done=done)
    assert code == 0 and ev.wait(180)
    assert got["long"] is None and "mid-prompt" in cntl.failed[1]
    assert seq.prefilled < len(long_prompt)
    # the engine is still serving
    ev2, _ = _submit(eng, world["prompts"][1], 3, got, "after")
    assert ev2.wait(120) and len(got["after"]) == 3
    kv.assert_idle("cancelled mid-prompt")
    eng.stop()
    kv.assert_idle("engine stopped")
