"""The ``cohere2_moe`` model (``serving/moe_model.py``) over the hybrid cache
manager against the plain reference (``benchmark/blocks/cohere2moe/
reference.py``) at a small size on the CPU: prefill, then decode through the
rings and pages, against the reference's full forward on seeded weights;
contexts pass the window so the ring wraps; the batch has unequal lengths and
its rows hit different experts. Tokens are compared through the reference's
LOGITS; the share test adds the parts of all ranks up to the uncut layer.

Tolerances. Both sides multiply exactly on the CPU (weights are bfloat16
VALUES, activations float32), so they differ by the order of float32 sums,
1e-6 a matmul, and by the few K/V elements that such a difference rounds the
other way at the bfloat16 store (one in some thousands, by 2^-8 of itself:
6e-5 of a row's norm). ``STATE_TOL`` 5e-4 and ``LOGIT_TOL`` 1e-3 (logits are
of order 0.5) leave room over that and are far under what a missing term
gives (an expert left out reads 1e-1).
"""

import importlib
import os
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

R = importlib.import_module("blocks.cohere2moe.reference")  # noqa: E402
W = importlib.import_module("blocks.cohere2moe.work")  # noqa: E402
from brpc_tpu.serving import (Cohere2MoeConfig, Cohere2MoeModel,  # noqa: E402
                              EngineConfig, HybridCacheConfig,
                              HybridStateCache, LlmServingService,
                              ServingEngine, build_prefix_cache)
from brpc_tpu.serving import moe_model  # noqa: E402

RANKS = 4
M = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
         head_dim=16, intermediate_size=64, num_experts=4,
         num_routed_experts=16, expert_rank=1, num_experts_per_tok=4,
         num_shared_experts=2, sliding_window=16,
         layer_types=["sliding_attention"] * 3 + ["full_attention"],
         rope_theta=50000.0, layer_norm_eps=1e-5, logit_scale=1.0,
         vocab_size=256)
SEED, NEW, BS = 5, 40, 16
LENS = (37, 9, 70)          # past the window, inside it, several blocks
STATE_TOL, LOGIT_TOL = 5e-4, 1e-3


def _f32(bits):
    """A reference weight (bfloat16 bits, or the router's float32) as the
    float32 host array the program's ``weights=`` takes."""
    if bits.dtype == np.float32:
        return bits
    return (bits.astype(np.uint32) << np.uint32(16)).view(np.float32)


def _host(m=M):
    return {k: _f32(v) for k, v in R.draw_weights(SEED, m).items()}


def _stand(attn="reference", weights=None, m=M, **cache):
    cfg = Cohere2MoeConfig(**m, max_context=256, seed=SEED, attn=attn)
    cache = dict(dict(block_size=BS, num_blocks=64, max_sequences=4,
                      window=16), **cache)
    kv = cfg.cache(HybridCacheConfig(**cache))
    return Cohere2MoeModel(cfg, kv, weights=weights), kv


def _rel(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def _serve(model, kv, prompts, new):
    """Prefill each prompt, then decode them together for new - 1 steps."""
    tables = [kv.alloc_sequence(i + 1, len(p)) for i, p in enumerate(prompts)]
    outs = [[model.prefill(p, t)] for p, t in zip(prompts, tables)]
    for _ in range(new - 1):
        tabs = [kv.extend_sequence(i + 1, len(p) + len(o))
                for i, (p, o) in enumerate(zip(prompts, outs))]
        nxt = model.decode_step(
            np.array([o[-1] for o in outs], np.int32),
            np.array([len(p) + len(o) - 1 for p, o in zip(prompts, outs)],
                     np.int32), tabs)
        for o, t in zip(outs, nxt):
            o.append(int(t))
    return outs


@pytest.fixture(scope="module")
def world():
    host = _host()
    model, kv = _stand(weights=host)
    ref = R.Reference(SEED, M, "float32", pad_to=16)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 256, size=n).astype(np.int32) for n in LENS]
    outs = _serve(model, kv, prompts, NEW)
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
def test_decode_through_rings_and_pages_follows_the_references_logits(world,
                                                                      i):
    lg, out = world["logits"][i], np.array(world["outs"][i])
    assert len(out) == NEW
    gaps = lg.max(-1) - lg[np.arange(NEW), out]
    assert gaps.max() <= LOGIT_TOL, gaps


def _rows(kv, table, n, pool):
    """Positions 0 .. n - 1 of a live sequence as ``pool`` holds them: the
    ring's rows (those it still has) or the full layer's."""
    pos = np.arange(n)
    if pool == "window":
        ring = kv.config.ring_blocks
        lo = max(0, n - ring * BS)
        pos = pos[lo:]
        rows = np.asarray(table.window)[(pos // BS) % ring] * BS + pos % BS
        return pos, (kv.window.k_pool[0][rows], kv.window.v_pool[0][rows])
    rows = np.asarray(table)[pos // BS] * BS + pos % BS
    return pos, (kv.full.k_pool[0][rows], kv.full.v_pool[0][rows])


@SEQS
@pytest.mark.parametrize("pool,names", [("window", ("k0", "v0")),
                                        ("full", ("kf", "vf"))])
def test_rows_left_in_ring_and_pages_are_the_references(world, i, pool,
                                                        names):
    """What prefill and the decode steps wrote: the first window layer's
    rows still in the (wrapped) ring, K rotated, and the full layer's."""
    n = LENS[i] + NEW - 1
    pos, got = _rows(world["kv"], world["tables"][i], n, pool)
    if pool == "window" and n > world["kv"].config.ring_blocks * BS:
        assert pos[0] > 0       # the ring wrapped: rows behind it are gone
    for name, rows in zip(names, got):
        want = np.asarray(world["state"][i][name])[pos]
        assert _rel(np.asarray(rows, np.float32), want) <= STATE_TOL, name


def test_the_ring_wrapped_and_the_manager_counted_it(world):
    snap = world["kv"].snapshot()
    assert snap["window_blocks_recycled"] > 0
    assert snap["window"]["ring_blocks"] == 2
    assert snap["slots"]["used"] == len(LENS)


def test_the_batchs_rows_hit_different_experts(world):
    """The decode launches of ``world`` ran three rows at a time: by the
    counters each layer-launch computed about a pair a row here (k x held /
    experts = 1) on more than one held expert, and never more pairs on one
    expert than in all."""
    c = world["model"].moe_counters
    dec, pre = c["decode"], c["prefill"]
    launches = dec["layer_launches"]
    assert launches == (NEW - 1) * len(M["layer_types"])
    assert 0.5 * 3 * launches <= dec["pairs"] <= 2 * 3 * launches
    assert launches < dec["experts_hit"] <= M["num_experts"] * launches
    assert dec["pairs_max_expert"] <= dec["pairs"]
    assert pre["layer_launches"] == len(LENS) * len(M["layer_types"])
    assert pre["pairs"] > 0 and c["experts_held"] == M["num_experts"]


# ------------------------------------------------------------ the chip's share
@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("layer", [0, 3])
def test_the_parts_of_all_ranks_add_up_to_the_uncut_layer(side, layer):
    """Every rank routes over all 16 experts and computes its own 4; the
    shared experts' mean, which every rank computes alike, counted once:
    the sum is the uncut reference's layer output."""
    import jax
    import jax.numpy as jnp

    uncut = dict(M, num_experts=16, expert_rank=0)
    w_all = {k: jnp.asarray(v) for k, v in _host(uncut).items()}
    x = jnp.asarray(np.random.RandomState(3).standard_normal(
        (24, M["hidden_size"])), jnp.float32)
    _att, whole, shared = R.layer(uncut, w_all, layer, x)
    total = np.asarray(shared, np.float64)
    for rank in range(RANKS):
        m = dict(M, expert_rank=rank)
        if side == "reference":
            _a, part, sh = R.layer(m, w_all, layer, x)
            part = part - sh
        else:
            cfg = Cohere2MoeConfig(**m)
            p = f"l{layer}."
            h = moe_model._ln(x, 1.0, 0.0, cfg.eps)
            ids = range(cfg.expert_lo, cfg.expert_lo + cfg.held)
            wgu = jnp.stack([jnp.concatenate(
                [w_all[f"{p}e{e}.wg"], w_all[f"{p}e{e}.wu"]], 1)
                for e in ids])
            wd = jnp.stack([w_all[f"{p}e{e}.wd"] for e in ids])
            idx, wts = moe_model.route(cfg, h, w_all[p + "router"],
                                       jnp.ones((24,), bool))
            part, cnt = jax.jit(
                lambda h, idx, wts: moe_model.expert_layer(
                    cfg, h, idx, wts, wgu, wd, tile=16))(h, idx, wts)
            assert int(cnt.sum()) == int(((idx >= cfg.expert_lo) & (
                idx < cfg.expert_lo + cfg.held)).sum())
        total = total + np.asarray(part, np.float64)
    assert _rel(total, whole) <= 1e-5


def test_the_shared_experts_are_averaged_not_summed():
    import jax.numpy as jnp

    cfg = Cohere2MoeConfig(**M)
    host = _host()
    h = jnp.asarray(np.random.RandomState(4).standard_normal((8, 64)),
                    jnp.float32)
    each = []
    for i in range(cfg.n_shared):
        g, u, d = (host[f"l0.s{i}.{n}"] for n in ("wg", "wu", "wd"))
        hh = np.asarray(h, np.float64)
        a = hh @ g
        each.append((a / (1 + np.exp(-a)) * (hh @ u)) @ d)
    wgu = jnp.concatenate([jnp.asarray(host[f"l0.s{i}.{n}"])
                           for n in ("wg", "wu")
                           for i in range(cfg.n_shared)], axis=1)
    wd = jnp.concatenate([jnp.asarray(host[f"l0.s{i}.wd"])
                          for i in range(cfg.n_shared)], axis=0)
    got = moe_model.shared_experts(cfg, h, wgu, wd)
    assert _rel(got, sum(each) / cfg.n_shared) <= 1e-5


def test_program_and_reference_draw_the_same_weights_independently():
    """Two recipes, written twice: the program's own draw (no ``weights=``)
    stages the values the reference draws."""
    model, _kv = _stand()
    host = _host()
    p = model._params
    assert np.array_equal(np.asarray(p["embed"], np.float32), host["embed"])
    assert np.array_equal(np.asarray(p["l2.router"]), host["l2.router"])
    ff, lo = M["intermediate_size"], M["expert_rank"] * M["num_experts"]
    e_wgu = np.asarray(p["l1.e_wgu"], np.float32)
    assert np.array_equal(e_wgu[2, :, :ff], host[f"l1.e{lo + 2}.wg"])
    assert np.array_equal(e_wgu[2, :, ff:], host[f"l1.e{lo + 2}.wu"])
    assert np.array_equal(np.asarray(p["l3.e_wd"], np.float32)[3],
                          host[f"l3.e{lo + 3}.wd"])
    s_wgu = np.asarray(p["l0.s_wgu"], np.float32)
    assert np.array_equal(s_wgu[:, ff:2 * ff], host["l0.s1.wg"])
    assert np.array_equal(s_wgu[:, 2 * ff:3 * ff], host["l0.s0.wu"])
    assert np.array_equal(np.asarray(p["l0.wo"], np.float32), host["l0.wo"])


def test_the_blocks_weight_count_is_what_the_program_stages():
    model, _kv = _stand()
    z = R.sizes(M)
    router = z["layers"] * z["d"] * z["experts"]
    norms = (z["layers"] + 1) * z["d"]
    assert model.param_nbytes == (2 * (W.weight_count(M) - router)
                                  + 4 * router + 4 * norms)


# --------------------------------------------------------------- attention
def test_flash_prefill_and_blocked_window_serve_the_same_tokens(world):
    """``attn="flash"``: the kernel (interpreted here) within the window
    bucket, the blocked scan past it; the tokens are those of the masked
    path, which the reference's logits vouch for."""
    model, kv = _stand(attn="flash", weights=world["host"])
    outs = _serve(model, kv, world["prompts"][1:], 6)
    assert outs == [o[:6] for o in world["outs"][1:]]


def test_rope_turns_pairs_by_the_position_and_keeps_their_norm():
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(6).standard_normal((5, 2, 16)),
                    jnp.float32)
    pos = jnp.asarray([0, 1, 7, 100, 4095])
    got = np.asarray(moe_model.rope(x, pos, 50000.0), np.float64)
    assert np.array_equal(got[0], np.asarray(x[0], np.float64))
    xs = np.asarray(x, np.float64)
    for j in (0, 3, 7):
        ang = np.asarray(pos, np.float64) * 50000.0 ** (-2 * j / 16)
        want0 = xs[:, :, 2 * j] * np.cos(ang)[:, None] \
            - xs[:, :, 2 * j + 1] * np.sin(ang)[:, None]
        assert np.abs(got[:, :, 2 * j] - want0).max() <= 2e-3
    assert np.allclose(np.linalg.norm(got, axis=-1),
                       np.linalg.norm(xs, axis=-1), rtol=1e-5)


def _rope_stride2(x, pos, theta):
    """The oracle: the rotation as it was written until PR 34, pairs taken
    by stride-2 slices and put back by a stack."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd),
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


@pytest.mark.parametrize("pos", [0, 1, 4095, 8191])
@pytest.mark.parametrize("hd", [16, 128])
def test_rope_is_the_stride_two_form_to_an_ulp_pairs_interleaved(hd, pos):
    """Same products, one sum: the pair-swap product moves values and
    changes none, so the rotation agrees with the sliced form to an ulp of
    the larger term, element for element in the INTERLEAVED layout (K is
    stored so)."""
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(hd + pos).standard_normal(
        (3, 4, hd)), jnp.float32)
    at = jnp.asarray([pos, pos, 7])
    got = np.asarray(moe_model.rope(x, at, 50000.0))
    want = np.asarray(_rope_stride2(x, at, 50000.0))
    assert got.dtype == np.float32 and got.shape == want.shape
    ulp = np.spacing(np.maximum(np.abs(want), np.abs(np.asarray(x)).max()))
    assert (np.abs(got - want) <= ulp).all()
    if pos == 0:
        assert np.array_equal(got[:2], np.asarray(x)[:2])


def _prefill_jaxpr(model, s_bucket):
    """The prefill program of ``s_bucket`` rows, traced over the manager's
    own arrays (nothing runs)."""
    import jax

    kv = model.kv
    blocks = -(-s_bucket // kv.block_size)
    args = (model._params, kv.full.k_pool, kv.full.v_pool, kv.window.k_pool,
            kv.window.v_pool, kv.ssm, kv.conv,
            np.zeros(s_bucket, np.int32), np.zeros(blocks, np.int32),
            np.zeros(kv.config.ring_blocks, np.int32), np.int32(0),
            np.int32(s_bucket))
    fn = model._prefill_fn(s_bucket, True)
    return jax.make_jaxpr(fn.__wrapped__)(*args)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("s_bucket,kernel_layers", [(16, 4), (32, 1)])
def test_the_prefill_program_hands_the_kernel_what_the_projections_made(
        world, s_bucket, kernel_layers):
    """Structure, read off the traced program: no slice strides the minor
    axis (the rotation's pairs), ONE ``pallas_call`` an attention layer that
    takes the kernel (not one a head), and none of its operands is a K/V
    head repeated for its query heads."""
    model, _kv = _stand(attn="flash", weights=world["host"])
    eqns = list(_eqns(_prefill_jaxpr(model, s_bucket).jaxpr))
    for e in eqns:
        if e.primitive.name == "slice" and e.params["strides"]:
            assert e.params["strides"][-1] == 1, e
    flash = [e for e in eqns if e.primitive.name == "pallas_call"
             and "flash" in e.params["name"]]
    assert len(flash) == kernel_layers
    k_size = s_bucket * M["num_key_value_heads"] * M["head_dim"]
    for e in flash:
        sizes = sorted(int(np.prod(v.aval.shape)) for v in e.invars)
        assert sizes == [k_size, k_size,
                         s_bucket * M["num_attention_heads"] * M["head_dim"]]


@pytest.mark.parametrize("n,kernel,blocked", [(9, 4, 0), (37, 1, 3)],
                         ids=["inside_the_window", "past_the_window"])
def test_prefill_counts_the_layers_the_kernel_and_the_blocked_scan_took(
        world, n, kernel, blocked):
    """``kernel_layers`` / ``blocked_layers``: what the launched program's
    layers were built as. Inside the window every layer takes the kernel;
    past it the window layers scan blocks and the full layer alone keeps
    the kernel. Without the kernel every layer is blocked."""
    for attn, want in (("flash", (kernel, blocked)), ("reference", (0, 4))):
        model, kv = _stand(attn=attn, weights=world["host"])
        prompt = np.arange(1, n + 1, dtype=np.int32)
        for seq in (1, 2):
            model.prefill(prompt, kv.alloc_sequence(seq, n))
        c = model.moe_counters["prefill"]
        assert (c["kernel_layers"], c["blocked_layers"]) == tuple(
            2 * w for w in want)
        assert c["layer_launches"] == 8
        model.reset_moe_counters()
        assert model.moe_counters["prefill"]["kernel_layers"] == 0
        assert "kernel_layers" not in model.moe_counters["decode"]


def test_a_decode_step_gathers_its_blocks_and_copies_no_layer_of_a_pool(
        world):
    """``pool[layer]`` ahead of the gather is a copy of the whole layer (on
    the chip 404 MB of the rings, six times a step): the layer rides in the
    gather's index, and no slice takes a pool for its operand."""
    import jax

    model, kv = _stand(weights=world["host"])
    b, ctx = 8, 64
    args = (model._params, kv.full.k_pool, kv.full.v_pool, kv.window.k_pool,
            kv.window.v_pool, kv.ssm, kv.conv, np.zeros(b, np.int32),
            np.ones(b, np.int32), np.zeros((b, ctx // BS), np.int32),
            np.zeros((b, kv.config.ring_blocks), np.int32),
            np.zeros(b, np.int32))
    jaxpr = jax.make_jaxpr(model._decode_fn(b, ctx))(*args)
    pools = {kv.full.k_pool.shape, kv.window.k_pool.shape}
    eqns = list(_eqns(jaxpr.jaxpr))
    for e in eqns:
        if e.primitive.name in ("slice", "dynamic_slice", "squeeze"):
            assert e.invars[0].aval.shape not in pools, e
    reads = [e for e in eqns if e.primitive.name == "gather"
             and e.invars[0].aval.shape[-2:] == (BS, M["head_dim"] * 2)]
    assert len(reads) == 2 * len(model.config.kinds)


def test_grouped_matmul_reads_one_expert_a_tile_and_zeroes_unused_tiles():
    import jax.numpy as jnp

    from brpc_tpu.tpu import pallas_ops

    rng = np.random.default_rng(0)
    tm, k, n = 16, 64, 128
    x = jnp.asarray(rng.standard_normal((6 * tm, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, k, n)), jnp.bfloat16)
    te = jnp.asarray([0, 0, 2, 3, 3, 3], jnp.int32)
    out = np.asarray(pallas_ops.moe_grouped_matmul(x, w, te, 4, block_rows=tm,
                                                   block_cols=64))
    for t in range(6):
        want = (np.asarray(x[t * tm:(t + 1) * tm])
                @ np.asarray(w[int(te[t])], np.float32) if t < 4 else 0.0)
        assert np.abs(out[t * tm:(t + 1) * tm] - want).max() <= 1e-4


# ------------------------------------------------------- the cache manager
def test_the_manager_takes_full_layers_a_dtype_and_no_recurrent_layer():
    import jax.numpy as jnp

    kv = HybridStateCache(HybridCacheConfig(block_size=16, num_blocks=8,
                                            max_sequences=2, window=16),
                          kv_dim=32, window_layers=3, full_layers=2,
                          dtype=jnp.bfloat16)
    assert kv.full.k_pool.shape == (2, 9 * 16, 32)
    assert kv.full.k_pool.dtype == kv.window.v_pool.dtype == jnp.bfloat16
    assert kv.ssm.size == kv.conv.size == 0
    assert kv.state_overwritten and not kv.recurrent_state
    kv.alloc_sequence(1, 20)
    # 2 blocks x 2 full layers + a ring of 2 blocks x 3 window layers, K and
    # V, 16 rows x 32 x 2 bytes
    assert kv.snapshot()["cache_bytes"] == (2 * 2 + 2 * 3) * 2 * 16 * 32 * 2
    kv.free_sequence(1)
    kv.assert_idle("test")


def test_a_recurrent_manager_says_so_and_counts_float32():
    kv = HybridStateCache(HybridCacheConfig(block_size=16, num_blocks=8,
                                            max_sequences=2, window=16),
                          kv_dim=32, window_layers=1, recurrent_layers=2,
                          d_inner=8, d_state=4, d_conv=4)
    assert kv.state_overwritten and kv.recurrent_state
    kv.alloc_sequence(1, 16)
    assert kv.snapshot()["cache_bytes"] == (
        (1 + 2) * 2 * 16 * 32 * 4 + 2 * 8 * 4 * (4 + 3))


# -------------------------------------------------- what is refused, loudly
def test_no_prefix_cache_is_built_over_rings(world):
    """Rings and no recurrence: a ring overwrites rows, so a cached prefix's
    rows behind the window are gone."""
    assert not world["kv"].recurrent_state
    assert build_prefix_cache(world["kv"]) is None


@pytest.mark.parametrize("cfg", [dict(spec_k=2), dict(role="prefill"),
                                 dict(role="decode")],
                         ids=["spec_k", "role_prefill", "role_decode"])
def test_engine_refuses_speculation_and_migration_roles_over_rings(world,
                                                                   cfg):
    with pytest.raises(ValueError, match="window rings"):
        ServingEngine(world["model"], world["kv"], EngineConfig(**cfg))


def test_engine_refuses_a_migrator_over_rings(world):
    eng = ServingEngine(world["model"], world["kv"], EngineConfig())
    assert eng.prefix is None
    with pytest.raises(ValueError, match="window rings"):
        eng.set_migrator(object())


def test_migration_into_a_manager_with_rings_is_rejected(world):
    from brpc_tpu.proto import serving_pb2
    from brpc_tpu.serving.migration import MigrationReceiver

    eng = ServingEngine(world["model"], world["kv"], EngineConfig())
    rx = MigrationReceiver(eng)

    class Meta:
        class stream_settings:
            stream_id = 7

    class Cntl:
        _srv_meta = Meta

    ack = rx.open(Cntl(), serving_pb2.MigrateRequest(
        block_size=BS, layers=1, kv_dim=world["kv"].kv_dim))
    assert not ack.accepted and "window rings" in ack.message


def test_model_refuses_a_suffix_and_two_rows_of_one_sequence(world):
    model, t = world["model"], world["tables"][0]
    with pytest.raises(NotImplementedError, match="ring rows"):
        model.prefill_suffix(world["prompts"][0], t, 16)
    with pytest.raises(ValueError, match="one row a sequence"):
        model.decode_step(np.array([1, 2], np.int32),
                          np.array([3, 4], np.int32), [t, t])


# ---------------------------------------------------- through ServingEngine
def test_generate_through_the_engine_serves_the_same_tokens(world):
    """The normal path: ServingEngine over the model and the manager, no
    prefix cache; the tokens are those of the direct calls above (which the
    reference's logits vouch for); the expert counters reach ``snapshot()``
    and the /serving page."""
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
    assert snap["prefix"] is None and snap["decode"] is None
    moe = snap["moe"]
    assert moe["experts_held"] == M["num_experts"]
    assert moe["decode"]["layer_launches"] == eng.steps * 4
    assert moe["decode"]["pairs"] > 0 and moe["prefill"]["pairs"] > 0
    assert set(moe["decode"]) == {"pairs", "experts_hit", "layer_launches",
                                  "pairs_max_expert"}
    # prompts of 37, 9 and 70 rows over a window of 16: the 9-row one alone
    # lies inside it; this stand runs no kernel, so every layer is blocked
    assert set(moe["prefill"]) == set(moe["decode"]) | {"kernel_layers",
                                                        "blocked_layers"}
    assert (moe["prefill"]["kernel_layers"],
            moe["prefill"]["blocked_layers"]) == (0, 12)
    from brpc_tpu.builtin.services import serving_service
    from brpc_tpu.policy.http_protocol import HttpMessage
    page = serving_service(None, HttpMessage())[2]
    assert "moe: held=4 decode pairs=" in page and "hit_share=" in page
    assert "kernel_layers=0 blocked_layers=12" in page
    eng.stop()
    kv.assert_idle("engine stopped")


def test_a_decode_step_is_one_launch_and_one_host_sync(world):
    """``FUSED_STEP``, counted from outside and by the engine's own audit
    (the manager's ledger armed): the expert counters come back in the SAME
    sync as the tokens."""
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
    assert model.moe_counters["decode"]["layer_launches"] == 4 * len(deltas)
