"""The ``jamba`` model (Mamba-1 layers with normed dt / B / C beside
position-free attention of several query heads over ONE key/value head) and
its chunked prefill against the plain reference
(``benchmark/blocks/jamba/reference.py``) at a small size on the CPU: prefill,
then decode through the cache, against the reference's full forward on seeded
weights (norm weights drawn too, so every term carries weight); a prompt
prefilled in chunks against the same prompt prefilled whole; and through
``ServingEngine``, where a long prompt goes a chunk a step beside the decode
rows. Tokens are compared through the reference's LOGITS.

Tolerances. Both sides compute in float32 on the CPU (no operand rounding)
over the same bfloat16 weight VALUES, so they differ by the order of float32
sums, and where a value lands within that of a bfloat16 rounding boundary, by
one bfloat16 step of a stored K/V element: ``STATE_TOL`` 2e-5 for the float32
recurrent state of the FIRST Mamba layer (no stored row lies before it),
``DEEP_TOL`` 5e-4 for the Mamba layers behind an attention layer (a flipped
K/V element moves what follows it: 4e-5 measured), ``ROWS_TOL`` 1e-3 for the
bfloat16 K/V rows (one flipped element of a 16-wide row reads 4e-4; a wrong
row reads of order 1),
``LOGIT_TOL`` 1e-4 (logits are of order 0.3). A dropped inner norm reads 1e-1
and more (``test_a_model_without_the_inner_norms_fails_the_comparison``).
"""

import importlib
import os
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

R = importlib.import_module("blocks.jamba.reference")  # noqa: E402
W = importlib.import_module("blocks.jamba.work")  # noqa: E402
from brpc_tpu.serving import (EngineConfig, HybridCacheConfig,  # noqa: E402
                              JambaConfig, JambaModel, LlmServingService,
                              SambaYConfig, SambaYModel, ServingEngine,
                              build_prefix_cache)

M = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
         intermediate_size=128, num_hidden_layers=8, attn_layer_period=4,
         attn_layer_offset=1, mamba_d_state=16, mamba_d_conv=4,
         mamba_expand=2, mamba_dt_rank=4, rms_norm_eps=1e-6, vocab_size=256)
PUBLISHED = dict(hidden_size=2560, num_attention_heads=20,
                 num_key_value_heads=1, intermediate_size=8192,
                 num_hidden_layers=28, attn_layer_period=14,
                 attn_layer_offset=7, mamba_d_state=16, mamba_d_conv=4,
                 mamba_expand=2, mamba_dt_rank=160, rms_norm_eps=1e-6,
                 vocab_size=65536)
SEED, NEW, BS = 5, 12, 16
LENS = (37, 9, 150)
STATE_TOL, DEEP_TOL, ROWS_TOL, LOGIT_TOL = 2e-5, 5e-4, 1e-3, 1e-4
NORMS = ("dt_norm", "b_norm", "c_norm")


def _bf16(x):
    """x rounded to bfloat16 values, float32."""
    bits = R.bf16_bits(np.asarray(x, np.float32)).astype(np.uint32) << 16
    return bits.view(np.float32).reshape(np.shape(x))


def _weights():
    """The recipe's draw as float32 values, with every norm weight (the
    three inner norms' too) and ``D`` drawn as well."""
    host = {k: (v.astype(np.uint32) << 16).view(np.float32)
            for k, v in R.draw_weights(SEED, M).items()}
    rng = np.random.RandomState(1)
    for k, v in host.items():
        if k.endswith(("ln1", "ln2", "lnf", ".dd") + NORMS):
            host[k] = _bf16(1 + rng.standard_normal(v.shape) * 0.1)
    return host


def _ref(host):
    return R.Reference(SEED, M, "float32", pad_to=16,
                       host_weights={k: R.bf16_bits(v)
                                     for k, v in host.items()})


def _stand(weights=None, attn="reference", **cache):
    cfg = JambaConfig(**M, max_context=1024, seed=SEED, attn=attn)
    cache = dict(dict(block_size=BS, num_blocks=96, max_sequences=4), **cache)
    kv = cfg.cache(HybridCacheConfig(**cache))
    return JambaModel(cfg, kv, weights=weights), kv


def _rel(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def _rows(kv, pool, layer, table, n):
    pos = np.arange(n)
    at = np.asarray(table, np.int32)[pos // BS] * BS + pos % BS
    return np.asarray(pool[layer].astype(np.float32))[at]


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
    at_end = [(np.asarray(kv.ssm[0, :, t.slot]),
               np.asarray(kv.conv[0, :, t.slot])) for t in tables]
    tables = _decode(model, kv, sids, prompts, outs, NEW - 1)
    refs = [ref.forward(p, o, rows_pad=NEW) for p, o in zip(prompts, outs)]
    return dict(host=host, model=model, kv=kv, ref=ref, prompts=prompts,
                tables=tables, outs=outs, refs=refs, at_end=at_end)


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
def test_recurrent_state_equals_the_references(world, i, layer, part):
    kv, t, state = world["kv"], world["tables"][i], world["refs"][i][1]
    ssm_end, conv_end = world["at_end"][i]
    # index 1 of the leading axis keeps the prompt's end; 0 runs on
    for got, want in (
            (ssm_end[layer], state["ssm" + part][0]),
            (kv.ssm[1, layer, t.slot], state["ssm" + part][0]),
            (kv.ssm[0, layer, t.slot], state["ssm" + part][1]),
            (conv_end[layer], state["conv" + part][0]),
            (kv.conv[1, layer, t.slot], state["conv" + part][0]),
            (kv.conv[0, layer, t.slot], state["conv" + part][1])):
        assert _rel(got, want) <= (DEEP_TOL if layer else STATE_TOL)


@pytest.mark.parametrize("i", range(len(LENS)))
def test_first_attention_layers_rows_equal_the_references(world, i):
    kv, t, state = world["kv"], world["tables"][i], world["refs"][i][1]
    n = LENS[i] + NEW - 1
    for pool, name in ((kv.full.k_pool, "kf"), (kv.full.v_pool, "vf")):
        assert _rel(_rows(kv, pool, 0, t, n),
                    np.asarray(state[name])[:n]) <= ROWS_TOL


def test_flash_carry_path_agrees_with_the_blocked_one(world):
    """The kernel path (interpreted on the CPU), whole and from a chunk
    boundary, serves the blocked path's first token and state."""
    model, kv = _stand(weights=world["host"], attn="flash")
    p = world["prompts"][2]
    t = kv.alloc_sequence(1, len(p))
    model.prefill_suffix(p[:128], t, 0)
    first = model.prefill_suffix(p, t, 128)
    assert first == world["outs"][2][0]
    # the FIRST Mamba layer has no stored K/V row before it: the scan
    # kernel alone, held to STATE_TOL
    assert _rel(kv.ssm[1, 0, t.slot],
                world["refs"][2][1]["ssm0"][0]) <= STATE_TOL
    # the LAST lies behind both attention layers: DEEP_TOL, as in
    # test_recurrent_state_equals_the_references (readings: PERF.md §6)
    assert _rel(kv.ssm[1, -1, t.slot],
                world["refs"][2][1]["ssmL"][0]) <= DEEP_TOL


# -------------------------------------------- chunked against whole prefill
@pytest.mark.parametrize("cuts", [
    (70,),              # splits a scan chunk and a block
    (64, 128),          # on block boundaries, one on a scan chunk's
    (128, 149),         # the last chunk is the prompt's last row alone
    (7, 8, 130),        # a chunk of ONE row mid-prompt
], ids=["mid_scan_chunk", "on_blocks", "last_row_alone", "one_row_chunk"])
def test_chunked_prefill_agrees_with_whole_prefill(world, cuts):
    """The same prompt in chunks: scan state, conv tail (every Mamba layer),
    both attention layers' K/V rows and the first token agree with the
    whole prefill to rounding."""
    model, kv = world["model"], world["kv"]
    p, whole = world["prompts"][2], world["tables"][2]
    t = kv.alloc_sequence(9, len(p))
    try:
        edges = (0,) + cuts + (len(p),)
        for a, b in zip(edges, edges[1:]):
            first = model.prefill_suffix(p[:b], t, a)
        assert first == world["outs"][2][0]
        ssm_end, conv_end = world["at_end"][2]
        for layer in range(kv.ssm.shape[1]):
            tol = DEEP_TOL if layer else STATE_TOL
            assert _rel(kv.ssm[0, layer, t.slot], ssm_end[layer]) <= tol
            assert _rel(kv.ssm[1, layer, t.slot], ssm_end[layer]) <= tol
            assert _rel(kv.conv[0, layer, t.slot], conv_end[layer]) <= tol
        for layer in range(2):
            for pool in (kv.full.k_pool, kv.full.v_pool):
                assert _rel(_rows(kv, pool, layer, t, len(p)),
                            _rows(kv, pool, layer, whole, len(p))) <= ROWS_TOL
    finally:
        kv.free_sequence(9)


def test_a_chunk_started_from_zero_is_far_outside_the_tolerance(world):
    """The fault the comparison is there for: the second chunk as a prompt
    of its own (state and tail zero, rows from 0) parts by orders."""
    model, kv = world["model"], world["kv"]
    p = world["prompts"][2]
    t = kv.alloc_sequence(9, len(p))
    try:
        model.prefill_suffix(p[:70], t, 0)
        model.prefill_suffix(p[70:], t, 0)
        assert _rel(kv.ssm[0, 0, t.slot],
                    world["at_end"][2][0][0]) > 100 * STATE_TOL
    finally:
        kv.free_sequence(9)


# ------------------------------------------------------------- the inner norms
@pytest.mark.parametrize("norm", NORMS)
def test_each_inner_norm_is_in_the_model(world, norm):
    """A weight of 2 in ONE inner norm of the first Mamba layer moves the
    logits, in program and reference alike."""
    p = world["prompts"][0]
    doubled = dict(world["host"])
    doubled["l0." + norm] = doubled["l0." + norm] * 2.0
    model, kv = _stand(weights=doubled)
    t = kv.alloc_sequence(1, len(p))
    tok = model.prefill(p, t)
    logits, state = _ref(doubled).forward(p, [tok], rows_pad=NEW)
    logits = np.asarray(logits)
    assert logits[0].max() - logits[0, tok] <= LOGIT_TOL
    assert _rel(kv.ssm[0, 0, t.slot], state["ssm0"][0]) <= STATE_TOL
    base = np.asarray(world["refs"][0][0])[0]
    assert np.abs(logits[0] - base).max() > 100 * LOGIT_TOL


def test_a_model_without_the_inner_norms_fails_the_comparison(world):
    """The norms' statistics left out (each weight applied to the bare
    ``dt_r``, B and C): the first layer's state parts from the reference's
    by orders."""
    from brpc_tpu.serving import hybrid_model

    orig, calls = hybrid_model._rms, []

    def no_inner(x, w, eps):
        if x.shape[-1] in (M["mamba_dt_rank"], M["mamba_d_state"]):
            calls.append(x.shape)
            return x * w
        return orig(x, w, eps)

    p = world["prompts"][0]
    hybrid_model._rms = no_inner
    try:
        bare, kv = _stand(weights=world["host"])
        t = kv.alloc_sequence(1, len(p))
        bare.prefill(p, t)
    finally:
        hybrid_model._rms = orig
    assert calls
    assert _rel(kv.ssm[0, 0, t.slot],
                world["refs"][0][1]["ssm0"][0]) > 1000 * STATE_TOL


# -------------------------------------------------- layer order and the count
def test_layer_order_is_attention_at_offset_in_each_period():
    cfg = JambaConfig(**PUBLISHED)
    assert [l for l, k in enumerate(cfg.kinds) if k == "full"] == [7, 21]
    assert cfg.count("mamba") == 26 and cfg.n_layers == 28
    assert cfg.runs() == [("mamba", 0, 0, 7), ("full", 7, 0, 1),
                          ("mamba", 8, 7, 13), ("full", 21, 1, 1),
                          ("mamba", 22, 20, 6)]
    assert R.layer_kinds(PUBLISHED) == cfg.kinds
    small = JambaConfig(**M)
    assert [l for l, k in enumerate(small.kinds) if k == "full"] == [1, 5]


def test_weight_count_at_the_published_widths_is_3_029_337_472():
    assert W.weight_count(PUBLISHED) == 3_029_337_472
    z = R.sizes(PUBLISHED)
    assert W.layer_parameters("mamba", z) == 104_161_472
    assert W.layer_parameters("full", z) == 76_682_240
    # the program's shapes give the same count, from shapes alone
    cfg = JambaConfig(**PUBLISHED)
    sh, kinds = cfg.shapes(), cfg.kinds
    from brpc_tpu.serving import jamba_model as J

    def params(names):
        return sum(int(np.prod(sh[k])) for k in names)

    total = (kinds.count("mamba") * params(J.MAMBA)
             + kinds.count("full") * params(J.ATTN)
             + len(kinds) * params(J.EVERY)
             + cfg.vocab * cfg.d_model + cfg.d_model)
    assert total == 3_029_337_472


def test_staged_bytes_are_two_a_parameter(world):
    """Every parameter is held once, in bfloat16: the staged bytes are
    twice ``weight_count`` (at the published widths 6.06 GB)."""
    assert world["model"].param_nbytes == 2 * W.weight_count(M)
    assert 2 * W.weight_count(PUBLISHED) == 6_058_674_944


def test_program_and_reference_draw_the_same_weights(world):
    """With no weights handed over, the model draws the recipe the
    reference draws independently."""
    model, _kv = _stand()
    host = R.draw_weights(SEED, M)
    for l in (0, 1):
        for k, arr in model.layer_weights(l).items():
            want = (host[f"l{l}.{k}"].astype(np.uint32) << 16).view(
                np.float32)
            assert np.array_equal(np.asarray(arr.astype(np.float32)), want), k


# --------------------------------------------------------------- the manager
def test_no_ring_is_allocated_or_counted_without_window_layers(world):
    kv = world["kv"]
    snap = kv.snapshot()
    assert kv.ring_blocks == 0 and kv.window.k_pool.size == 0
    assert snap["window"] == {"used": 0, "total": 0, "ring_blocks": 0}
    assert snap["full"]["used"] > 0 and snap["slots"]["used"] == 3
    assert all(t.window == () for t in world["tables"])
    assert build_prefix_cache(kv) is None
    # a sequence's bytes: its pages of both attention layers + its slot
    t = kv.block_table(2)
    per_block = 2 * 2 * BS * M["hidden_size"] // M["num_attention_heads"] * 2
    slot = 6 * 128 * (16 + 3) * 4
    assert kv._block_bytes["full"] == per_block
    assert kv.snapshot()["cache_bytes"] == (
        snap["full"]["used"] * per_block + 3 * slot)
    assert len(t) == kv.blocks_for(LENS[1] + NEW - 1)


def test_a_program_is_lowered_once_whatever_launch_came_first(world):
    """The manager's arrays are ON the store's device from the start, as
    every launch returns them: the program that happens to be launched
    first over a fresh manager is not lowered a second time at its next
    launch (it was: uncommitted pools in, committed pools out, and a
    benchmark's window compiled a program its warm-up had run)."""
    import jax.monitoring

    model, kv = _stand(weights=world["host"])
    for arr in (kv.full.k_pool, kv.full.v_pool, kv.window.k_pool, kv.ssm,
                kv.conv):
        assert arr.committed
    lowered, on = [], [True]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_kw: on[0] and lowered.append(event))
    p = world["prompts"][0]
    counts = []
    for sid in (1, 2, 3):
        model.prefill(p, kv.alloc_sequence(sid, len(p)))
        counts.append(sum("jaxpr_to_mlir" in e for e in lowered))
    on[0] = False
    assert counts[0] >= 1 and counts[1] == counts[2] == counts[0]


def test_sambay_still_refuses_a_suffix():
    cfg = SambaYConfig(max_context=256)
    kv = cfg.cache(HybridCacheConfig(block_size=BS, num_blocks=32,
                                     max_sequences=2, window=16))
    model = SambaYModel(cfg, kv)
    assert not model.CONTINUES_PREFILL
    t = kv.alloc_sequence(1, 40)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        model.prefill_suffix(np.arange(1, 41, dtype=np.int32), t, 16)
    kv.free_sequence(1)
    kv.assert_idle()


# ---------------------------------------------------- through ServingEngine
@pytest.fixture(scope="module")
def served(world):
    """ONE model and manager for the engine tests (its programs compile
    once); each test starts an engine of its own over it and leaves the
    manager idle."""
    return _stand(weights=world["host"])


@pytest.fixture
def engine(served):
    """``engine(budget)`` -> (model, kv, a started engine); stopped, the
    model's methods unwrapped and the manager held to idle afterwards."""
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
    assert snap["prefill_chunks"] == 0 and snap["prefill_chunk_rows"] == 0
    return got


def test_a_long_prompt_goes_a_chunk_a_step_beside_the_decode_rows(
        world, long_prompt, unchunked, engine):
    """token_budget 132 leaves 128 rows a step: the 700-row prompt takes 6
    steps, each of which also runs the decode launch, so the running
    sequence receives one token every step; the served tokens equal those
    of unchunked serving; the counters count."""
    model, kv, eng = engine(132)
    log = []
    orig_chunk, orig_decode = model.prefill_suffix, model.decode_step

    def suffix(tokens, table, start):
        log.append(("chunk", eng.steps, start, len(tokens)))
        return orig_chunk(tokens, table, start)

    def decode(tokens, positions, tables):
        log.append(("decode", eng.steps, len(tokens)))
        return orig_decode(tokens, positions, tables)

    model.prefill_suffix, model.decode_step = suffix, decode
    got = {}
    ev_short, _ = _submit(eng, world["prompts"][0], 40, got, "short")
    while eng.tokens_generated < 2:      # the short one is decoding
        threading.Event().wait(0.002)
    ev_long, _ = _submit(eng, long_prompt, 6, got, "long")
    assert ev_short.wait(180) and ev_long.wait(180)
    snap = eng.snapshot()
    page_line = None
    from brpc_tpu.builtin.services import serving_service
    from brpc_tpu.policy.http_protocol import HttpMessage
    for line in serving_service(None, HttpMessage())[2].splitlines():
        if "prefill chunks" in line:
            page_line = line
    eng.stop()
    kv.assert_idle("engine stopped")
    assert got == unchunked
    chunks = [e for e in log if e[0] == "chunk"]
    assert [(s, n) for _k, _step, s, n in chunks] == [
        (0, 128), (128, 256), (256, 384), (384, 512), (512, 640), (640, 700)]
    steps = [step for _k, step, _s, _n in chunks]
    assert steps == list(range(steps[0], steps[0] + 6))   # one a step
    decoded = {e[1] for e in log if e[0] == "decode"}
    assert set(steps[:-1]) <= decoded     # the decode launch ran beside each
    assert snap["prefill_chunks"] == 6 and snap["prefill_chunk_rows"] == 700
    assert snap["prefilling"] == 0
    assert page_line and "6" in page_line and "700" in page_line


def test_snapshot_counts_the_rows_and_launches_handed_to_the_scan(
        long_prompt, engine):
    """``snapshot()["scan"]``: every prefill launch of a model with Mamba
    layers runs the scan kernel once a layer over its padded rows. The
    700-row prompt at 128 rows a step is 6 launches of 128 padded rows
    (the last holds 60) over the 6 Mamba layers; `/serving` prints it."""
    model, kv, eng = engine(132)
    before = dict(eng.snapshot()["scan"])
    got = {}
    ev, _ = _submit(eng, long_prompt, 2, got, "long")
    assert ev.wait(180)
    snap = eng.snapshot()
    from brpc_tpu.builtin.services import serving_service
    from brpc_tpu.policy.http_protocol import HttpMessage
    lines = [l for l in serving_service(None, HttpMessage())[2].splitlines()
             if l.strip().startswith("scan:")]
    eng.stop()
    assert snap["prefill_chunks"] == 6
    assert snap["scan"]["launches"] - before["launches"] == 6
    assert snap["scan"]["rows"] - before["rows"] == 6 * 128 * 6
    assert model.config.count("mamba") == 6
    assert lines and f"launches={snap['scan']['launches']}" in lines[0]


def test_a_model_without_mamba_layers_has_no_scan_part():
    from brpc_tpu.serving.engine import ServingEngine as E

    class NoScan:
        pass

    eng = E.__new__(E)
    eng.model = NoScan()
    assert eng._scan_snapshot() is None


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
    snap = eng.snapshot()
    assert snap["prefilling"] == 0 and snap["running"] == 0
    # the engine is still serving
    ev2, _ = _submit(eng, world["prompts"][1], 3, got, "after")
    assert ev2.wait(120) and len(got["after"]) == 3
    kv.assert_idle("cancelled mid-prompt")
    eng.stop()
    kv.assert_idle("engine stopped")


def test_stop_mid_prompt_leaves_the_manager_idle(long_prompt, engine):
    model, kv, eng = engine(132)
    started = threading.Event()
    orig = model.prefill_suffix

    def suffix(tokens, table, start):
        started.set()
        return orig(tokens, table, start)

    model.prefill_suffix = suffix
    eng.submit(long_prompt, 6, done=lambda _r: None)
    assert started.wait(120)
    eng.stop()
    kv.assert_idle("stopped mid-prompt")


def test_a_model_that_cannot_continue_keeps_whole_prompt_prefill():
    """``SambaYModel`` under the same budget: a prompt over it waits (as
    before), one under it is ONE launch through ``prefill``."""
    cfg = SambaYConfig(max_context=256)
    kv = cfg.cache(HybridCacheConfig(block_size=BS, num_blocks=32,
                                     max_sequences=2, window=16))
    model = SambaYModel(cfg, kv)
    eng = ServingEngine(model, kv, EngineConfig(max_batch=2, token_budget=64,
                                                idle_wait_s=0.005))
    assert eng._chunk_unit == 0
    calls = []
    orig = model.prefill
    model.prefill = lambda toks, table: (calls.append(len(toks)),
                                         orig(toks, table))[1]
    eng.start()
    got = {}
    ev, _ = _submit(eng, np.arange(1, 41, dtype=np.int32), 2, got, "a")
    assert ev.wait(120) and calls == [40]
    snap = eng.snapshot()
    assert snap["prefill_chunks"] == 0 and snap["prefilling"] == 0
    eng.stop()
    kv.assert_idle()
