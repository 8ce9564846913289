"""The SambaY model and its cache manager against the plain reference
(``benchmark/blocks/sambay/reference.py``) at a small size on the CPU: prefill,
then decode through the caches, against the reference's full forward on
seeded weights (biases and norm weights drawn too, so every term carries
weight); contexts pass the window so the ring wraps; the batch has unequal
lengths. Tokens are compared through the reference's LOGITS: the served
token's logit has to be the reference's best to ``LOGIT_TOL``.

Tolerances. Both sides compute in float32 on the CPU (no operand rounding), so
they differ by the order of float32 sums only: relative 1e-6 a matmul, a few
1e-6 after eight layers. ``STATE_TOL`` 2e-5 and ``LOGIT_TOL`` 1e-4 (logits are
of order 1) leave ten times that and are far under what a missing term gives
(a window one row short reads 1e-2 and more, ``test_window_one_row_short``).
"""

import importlib
import os
import re
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

# (the package's ``reference`` attribute is the block's factory function)
R = importlib.import_module("blocks.sambay.reference")  # noqa: E402
from brpc_tpu.serving import (EngineConfig, HybridCacheConfig,  # noqa: E402
                              HybridStateCache, LlmServingService,
                              SambaYConfig, SambaYModel, ServingEngine,
                              build_prefix_cache)
from brpc_tpu.serving.kv_cache import KVCacheFull  # noqa: E402

M = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
         intermediate_size=128, sliding_window=16, mb_per_layer=2,
         num_hidden_layers=8, vocab_size=256, layer_norm_eps=1e-5,
         d_state=16, d_conv=4, expand=2, dt_rank=4)
SEED, NEW, BS = 5, 40, 16
LENS = (37, 9, 70)          # past the window, inside it, several bands
STATE_TOL, LOGIT_TOL = 2e-5, 1e-4


def _weights():
    """The recipe's draw, with biases and norm weights drawn as well."""
    host = R.draw_weights(SEED, M)
    rng = np.random.RandomState(1)
    for k, v in host.items():
        if k.endswith(("_b", "bqkv", "bq", "bo")):
            host[k] = (rng.standard_normal(v.shape) * 0.1).astype(np.float32)
        elif k.endswith("_w") and "conv" not in k:
            host[k] = (1 + rng.standard_normal(v.shape) * 0.1
                       ).astype(np.float32)
    return host


def _stand(attn="reference", weights=None, **cache):
    cfg = SambaYConfig(**M, max_context=256, seed=SEED, attn=attn)
    cache = dict(dict(block_size=BS, num_blocks=64, max_sequences=4,
                      window=16), **cache)
    kv = cfg.cache(HybridCacheConfig(**cache))
    return SambaYModel(cfg, kv, weights=weights), kv


def _rel(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


@pytest.fixture(scope="module")
def world():
    """Three prompts prefilled, then decoded together for NEW - 1 steps;
    the reference's forward over each prompt + answer."""
    host = _weights()
    model, kv = _stand(weights=host)
    ref = R.Reference(SEED, M, "float32", host_weights=host, pad_to=16)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 256, size=n).astype(np.int32) for n in LENS]
    tables = [kv.alloc_sequence(i + 1, len(p)) for i, p in enumerate(prompts)]
    outs = [[model.prefill(p, t)] for p, t in zip(prompts, tables)]
    for _ in range(NEW - 1):
        tabs = [kv.extend_sequence(i + 1, len(p) + len(o))
                for i, (p, o) in enumerate(zip(prompts, outs))]
        nxt = model.decode_step(
            np.array([o[-1] for o in outs], np.int32),
            np.array([len(p) + len(o) - 1 for p, o in zip(prompts, outs)],
                     np.int32), tabs)
        for o, t in zip(outs, nxt):
            o.append(int(t))
    refs = [ref.forward(p, o, rows_pad=NEW) for p, o in zip(prompts, outs)]
    return {"model": model, "kv": kv, "ref": ref, "host": host,
            "prompts": prompts, "outs": outs,
            "logits": [np.asarray(l) for l, _ in refs],
            "state": [s for _, s in refs],
            "tables": [kv.block_table(i + 1) for i in range(len(LENS))]}


SEQS = pytest.mark.parametrize("i", range(len(LENS)),
                               ids=[f"prompt{n}" for n in LENS])


@SEQS
def test_prefill_token_is_the_references_best_logit(world, i):
    row = world["logits"][i][0]
    assert row.max() - row[world["outs"][i][0]] <= LOGIT_TOL


@SEQS
def test_decode_through_the_caches_follows_the_references_logits(world, i):
    lg, out = world["logits"][i], np.array(world["outs"][i])
    assert len(out) == NEW
    gaps = lg.max(-1) - lg[np.arange(NEW), out]
    assert gaps.max() <= LOGIT_TOL, gaps


@SEQS
def test_recurrent_state_at_the_prompts_end_equals_the_references(world, i):
    kv, t, st = world["kv"], world["tables"][i], world["state"][i]
    for l in range(kv.ssm.shape[1]):     # every Mamba layer keeps both
        assert float(np.abs(kv.ssm[1, l, t.slot]).sum()) > 0
    assert _rel(kv.ssm[1, 0, t.slot], st["ssm"][0]) <= STATE_TOL
    assert _rel(kv.conv[1, 0, t.slot], st["conv"][0]) <= STATE_TOL


@SEQS
def test_recurrent_state_after_the_decode_steps_equals_the_references(world,
                                                                      i):
    kv, t, st = world["kv"], world["tables"][i], world["state"][i]
    assert _rel(kv.ssm[0, 0, t.slot], st["ssm"][1]) <= STATE_TOL
    assert _rel(kv.conv[0, 0, t.slot], st["conv"][1]) <= STATE_TOL
    # the decode steps did overwrite what prefill left
    assert _rel(kv.ssm[0, 0, t.slot], kv.ssm[1, 0, t.slot]) > 1e-3


@SEQS
def test_window_rows_still_in_the_ring_equal_the_references(world, i):
    kv, t, st = world["kv"], world["tables"][i], world["state"][i]
    n = LENS[i] + NEW - 1
    ring = kv.config.ring_blocks
    assert n > ring * BS, "the ring has to wrap in this test"
    pos = np.arange(n - ring * BS, n)
    rows = np.asarray(t.window)[(pos // BS) % ring] * BS + pos % BS
    for pool, name in ((kv.window.k_pool, "k1"), (kv.window.v_pool, "v1")):
        assert _rel(pool[0][rows], np.asarray(st[name])[pos]) <= STATE_TOL


@SEQS
def test_full_layer_rows_equal_the_references(world, i):
    kv, t, st = world["kv"], world["tables"][i], world["state"][i]
    pos = np.arange(LENS[i] + NEW - 1)
    rows = np.asarray(t)[pos // BS] * BS + pos % BS
    for pool, name in ((kv.full.k_pool, "kf"), (kv.full.v_pool, "vf")):
        assert _rel(pool[0][rows], np.asarray(st[name])[pos]) <= STATE_TOL


@pytest.mark.parametrize("n", [9, 37])
def test_flash_kernel_path_agrees_with_the_reference(world, n):
    """The prefill program with the Pallas flash kernel (interpreted here)
    in place of the einsums: first token and first-layer state."""
    model, kv = _stand(attn="flash", weights=world["host"])
    prompt = world["prompts"][LENS.index(n)]
    table = kv.alloc_sequence(1, n)
    first = model.prefill(prompt, table)
    row = world["logits"][LENS.index(n)][0]
    assert row.max() - row[first] <= LOGIT_TOL
    st = world["state"][LENS.index(n)]
    assert _rel(kv.ssm[1, 0, table.slot], st["ssm"][0]) <= STATE_TOL
    kv.free_sequence(1)
    kv.assert_idle()


def _padded(tables, rows, blocks):
    """A batch's tables as ``decode_step`` pads them: (rows, blocks)."""
    out = np.zeros((rows, blocks), np.int32)
    for i, t in enumerate(tables):
        out[i, :len(t)] = t
    return out


def test_the_decode_program_makes_no_copy_of_a_whole_window_layer(world):
    """The rings are read as whole blocks with the layer inside the gather's
    index: no op of the lowered decode program yields an array of one whole
    window layer, ``(ring pool rows, kv_dim)`` (what ``pool[layer]`` first
    makes)."""
    model, kv = world["model"], world["kv"]
    b, l = 8, 64
    i32 = np.int32
    text = model._decode_fn(b, l).lower(
        model._params, kv.full.k_pool, kv.full.v_pool, kv.window.k_pool,
        kv.window.v_pool, kv.ssm, kv.conv, np.zeros(b, i32),
        np.zeros(b, i32), np.zeros((b, l // BS), i32),
        np.zeros((b, kv.ring_blocks), i32), np.zeros(b, i32)).as_text()
    layer = tuple(kv.window.k_pool.shape[1:])
    shapes = {tuple(int(d) for d in s.split("x"))
              for s in re.findall(r"tensor<(\d+(?:x\d+)*)x[a-z]", text)}
    assert tuple(kv.window.k_pool.shape) in shapes     # the pools go through
    whole = {s for s in shapes if s[-2:] == layer
             and np.prod(s) == np.prod(layer)}
    assert not whole, whole


def test_the_block_read_equals_the_row_by_row_read(world, monkeypatch):
    """One decode step whose batch holds a ring that has wrapped (37 rows),
    a row inside the window (9) and padded rows (position 0): the K and V
    each attention layer reads equal, on its live positions, what the
    row-by-row read gives (each context position's pool row through the
    table), element for element; the live positions are the window's and the
    causal ones."""
    import jax

    from brpc_tpu.serving import hybrid_model

    model, kv = _stand(weights=world["host"])
    prompts = [world["prompts"][LENS.index(n)] for n in (37, 9)]
    tables = [kv.alloc_sequence(i + 1, len(p)) for i, p in enumerate(prompts)]
    first = [model.prefill(p, t) for p, t in zip(prompts, tables)]
    tables = [kv.extend_sequence(i + 1, len(p) + 1)
              for i, p in enumerate(prompts)]
    pos = np.array([len(p) for p in prompts], np.int32)
    ring_rows, window = kv.ring_blocks * BS, M["sliding_window"]
    assert pos[0] > ring_rows and pos[1] < window
    seen = []
    attend = hybrid_model._attend_decode

    def record(cfg, qh, kc, vc, live):
        seen.append(tuple(np.asarray(a) for a in (kc, vc, live)))
        return attend(cfg, qh, kc, vc, live)

    monkeypatch.setattr(hybrid_model, "_attend_decode", record)
    with jax.disable_jit():
        model.decode_step(np.array(first, np.int32), pos, tables)
    b_bucket, l_bucket = model._decode_buckets(len(pos), tables)
    assert b_bucket > len(pos)
    pos = np.pad(pos, (0, b_bucket - len(pos)))
    tabs = _padded(tables, b_bucket, l_bucket // BS)
    rings = _padded([t.window for t in tables], b_bucket, kv.ring_blocks)

    def rows_read(pool, layer, table, n):
        """Position t of the context at pool row ``table[t // BS] * BS + t
        % BS``, gathered one row at a time."""
        t = np.arange(n)
        return np.asarray(pool)[layer][table[:, t // BS] * BS + t % BS]

    t = np.arange(l_bucket)
    n_ring = min(ring_rows, l_bucket)
    ring_live = np.asarray(hybrid_model._ring_live(pos, ring_rows, window))
    want = [("window", 0), ("window", 1), ("full", 0), ("cross", 0)]
    assert len(seen) == len(want)
    for (kind, layer), (kc, vc, live) in zip(want, seen):
        if kind == "window":
            pools, table, n = (kv.window.k_pool, kv.window.v_pool), rings, \
                n_ring
            assert not ring_live[:, n:].any()
            np.testing.assert_array_equal(live, ring_live[:, :n])
        else:
            pools, table, n = (kv.full.k_pool, kv.full.v_pool), tabs, l_bucket
            np.testing.assert_array_equal(live, t[None, :] <= pos[:, None])
        assert live[:2].sum(1).tolist() == (
            [window, pos[1] + 1] if kind == "window" else list(pos[:2] + 1))
        assert live[2:].sum(1).tolist() == [1] * (b_bucket - 2)  # position 0
        for got, pool in zip((kc, vc), pools):
            ref = rows_read(pool, layer, table, n)
            assert got.shape == ref.shape
            assert np.abs(ref[live[:2].nonzero()]).max() > 0
            np.testing.assert_array_equal(got[live], ref[live])
    for i in (1, 2):
        kv.free_sequence(i)
    kv.assert_idle()


def test_window_one_row_short_is_far_outside_the_tolerance(world):
    """What the tolerances are there to catch: the reference with its window
    one row shorter moves the first window layer's output by far more."""
    m = dict(M, sliding_window=15)
    ref = R.Reference(SEED, m, "float32", host_weights=world["host"],
                      pad_to=16)
    logits, _ = ref.forward(world["prompts"][0], world["outs"][0],
                            rows_pad=NEW)
    assert np.abs(np.asarray(logits) - world["logits"][0]).max() > 1e-3


# ------------------------------------------------------------------ the ledger
def _cache(**kw):
    cfg = dict(dict(block_size=16, num_blocks=32, max_sequences=3,
                    window=32), **kw)
    return HybridStateCache(HybridCacheConfig(**cfg), kv_dim=8,
                            window_layers=2, recurrent_layers=2, d_inner=16,
                            d_state=4, d_conv=4)


def test_alloc_extend_free_leave_the_manager_idle():
    kv = _cache()
    t = kv.alloc_sequence(7, 40)
    assert len(t) == 3 and len(t.window) == 3 and t.slot >= 1
    t2 = kv.extend_sequence(7, 100)
    assert len(t2) == 7 and t2.window == t.window and t2.slot == t.slot
    assert kv.block_table(7) == t2 and kv.seq_len(7) == 100
    snap = kv.snapshot()
    assert snap["blocks_used"] == 7 == snap["full"]["used"]
    assert snap["window"]["used"] == 3 and snap["slots"]["used"] == 1
    # 7 full-layer blocks and a ring of 3 over 2 layers, K and V, 16 rows of
    # 8 floats; ONE running state of 2 layers x 16 channels x (4 + 3): the
    # copy at the prompt's end, which no request needs, is not counted
    assert snap["cache_bytes"] == (7 + 3 * 2) * 2 * 16 * 8 * 4 \
        + 2 * 16 * (4 + 3) * 4
    assert snap["tokens_at_peak"] == 100
    assert kv.free_sequence(7) == 7
    kv.assert_idle("test")
    assert kv.snapshot()["cache_bytes"] == 0


@pytest.mark.parametrize("kind", ["slots", "ring", "full"])
def test_can_admit_counts_every_kind_of_state(kind):
    kv = _cache(num_blocks={"full": 4}.get(kind, 32),
                max_sequences={"slots": 1, "ring": 1}.get(kind, 3))
    assert kv.can_admit(16)
    kv.alloc_sequence(1, 16 if kind != "full" else 48)
    assert not kv.can_admit(16)
    with pytest.raises(KVCacheFull):
        kv.alloc_sequence(2, 16 if kind != "full" else 48)
    # nothing of the refused sequence is held: all or nothing
    assert kv.block_table(2) is None
    kv.free_sequence(1)
    kv.assert_idle()
    assert kv.can_admit(16)


def test_the_ring_never_grows_and_counts_what_it_recycles():
    kv = _cache()
    t = kv.alloc_sequence(1, 10)
    for n in range(11, 120):
        assert kv.extend_sequence(1, n).window == t.window
    snap = kv.snapshot()
    assert snap["window"]["used"] == kv.config.ring_blocks == 3
    # 119 tokens are 8 blocks; all past the ring's 3 wrote over an old one
    assert snap["window_blocks_recycled"] == 8 - 3
    kv.free_sequence(1)


def test_retired_names_a_finished_sequences_state_until_it_is_reused():
    kv = _cache(max_sequences=2)
    t = kv.alloc_sequence(1, 20)
    kv.free_sequence(1)
    old = kv.retired(1)
    assert list(old) == list(t) and old.slot == t.slot and old.tokens == 20
    assert kv.retired_ids() == [1]
    kv.alloc_sequence(2, 20)         # oldest first: other blocks, other slot
    assert kv.retired(1) is not None
    kv.alloc_sequence(3, 20)         # the slot comes round again
    assert kv.retired(1) is None and kv.retired(99) is None
    for s in (2, 3):
        kv.free_sequence(s)
    kv.assert_idle()


def test_every_pool_hands_free_blocks_out_oldest_first():
    """No flag: a plain ``PagedKVCache`` does what the manager's pools do, so
    a freed sequence's rows outlive the next admission."""
    from brpc_tpu.serving.kv_cache import KVCacheConfig, PagedKVCache

    pool = PagedKVCache(KVCacheConfig(16, 6, 1.0), 1, 8, device_pools=False)
    first = pool.alloc_sequence(1, 32)
    pool.free_sequence(1)
    second = pool.alloc_sequence(2, 32)
    assert not set(first) & set(second)
    third = pool.alloc_sequence(3, 48)      # 2 never used, then the oldest
    assert third[-1] == first[0]
    for s_id in (2, 3):
        pool.free_sequence(s_id)
    pool.assert_idle()


def test_peak_counters_start_anew_on_request():
    kv = _cache()
    kv.alloc_sequence(1, 64)
    kv.free_sequence(1)
    snap = kv.snapshot()
    assert snap["cache_bytes_peak"] > 0 and snap["tokens_at_peak"] == 64
    kv.reset_peak()
    assert kv.snapshot()["cache_bytes_peak"] == 0


# -------------------------------------------------- what is refused, loudly
def test_no_prefix_cache_is_built_over_recurrent_state():
    assert build_prefix_cache(_cache()) is None


@pytest.mark.parametrize("cfg", [dict(spec_k=2), dict(role="prefill"),
                                 dict(role="decode")],
                         ids=["spec_k", "role_prefill", "role_decode"])
def test_engine_refuses_speculation_and_migration_roles(world, cfg):
    with pytest.raises(ValueError, match="recurrent state"):
        ServingEngine(world["model"], world["kv"], EngineConfig(**cfg))


def test_engine_refuses_a_migrator(world):
    eng = ServingEngine(world["model"], world["kv"], EngineConfig())
    assert eng.prefix is None
    with pytest.raises(ValueError, match="recurrent state"):
        eng.set_migrator(object())


def test_model_refuses_a_suffix_and_two_rows_of_one_sequence(world):
    model, t = world["model"], world["tables"][0]
    with pytest.raises(NotImplementedError, match="recurrent state"):
        model.prefill_suffix(world["prompts"][0], t, 16)
    with pytest.raises(ValueError, match="one row a sequence"):
        model.decode_step(np.array([1, 2], np.int32),
                          np.array([3, 4], np.int32), [t, t])


# ---------------------------------------------------- through ServingEngine
def test_generate_through_the_engine_serves_the_same_tokens(world):
    """The normal path: ServingEngine over the model and the manager, no
    prefix cache; the tokens are those of the direct calls above (which the
    reference's logits vouch for); the manager is idle afterwards and the
    /serving page shows each kind of state."""
    model, kv = _stand(weights=world["host"])
    eng = ServingEngine(model, kv, EngineConfig(
        max_batch=4, token_budget=256, idle_wait_s=0.005)).start()
    LlmServingService(eng)
    got, evs = {}, []
    for i, p in enumerate(world["prompts"]):
        ev = threading.Event()
        evs.append(ev)

        def done(resp, i=i, ev=ev):
            got[i] = list(resp.tokens)
            ev.set()

        code, _ = eng.submit(p, NEW, done=done)
        assert code == 0
    assert all(ev.wait(120) for ev in evs)
    for i in range(len(LENS)):
        assert got[i] == world["outs"][i]
    snap = eng.snapshot()
    assert snap["prefix"] is None and snap["kv"]["slots"]["total"] == 4
    from brpc_tpu.builtin.services import serving_service
    from brpc_tpu.policy.http_protocol import HttpMessage
    page = serving_service(None, HttpMessage())[2]
    assert "kv window:" in page and "slots:" in page and "recycled=" in page
    eng.stop()
    kv.assert_idle("engine stopped")


def test_a_decode_step_is_one_launch_and_one_host_sync(world):
    """``SambaYModel.FUSED_STEP``, counted from outside: over an engine run
    on the manager with its ledger armed (so the engine audits each step
    too), ``step_dispatch`` moves by one launch and one host sync a decode
    step whatever the batch, and by one of each a prefill."""
    from brpc_tpu.tpu.device_lane import DispatchCounter, step_dispatch

    model, kv = _stand(weights=world["host"])
    kv._check = True
    orig, deltas = model.decode_step, []

    def counted(tokens, positions, tables):
        before = step_dispatch.snapshot()
        out = orig(tokens, positions, tables)
        deltas.append(DispatchCounter.delta(before, step_dispatch.snapshot()))
        return out

    model.decode_step = counted
    eng = ServingEngine(model, kv, EngineConfig(
        max_batch=4, token_budget=256, idle_wait_s=0.005)).start()
    before = step_dispatch.snapshot()
    evs = []
    for p in world["prompts"]:
        ev = threading.Event()
        evs.append(ev)
        code, _ = eng.submit(p, 8, done=lambda _r, ev=ev: ev.set())
        assert code == 0
    assert all(ev.wait(120) for ev in evs)
    launches, _ops, syncs = DispatchCounter.delta(before,
                                                  step_dispatch.snapshot())
    eng.stop()
    kv.assert_idle("engine stopped")
    assert deltas and all((l, s) == (1, 1) for l, _o, s in deltas), deltas
    assert launches == syncs == len(world["prompts"]) + len(deltas)
    assert len(deltas) == eng.steps    # no step without a decode batch
