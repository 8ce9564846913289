"""The ``glm4_moe_lite`` model (multi-head latent attention over ONE latent
row a token a layer, expanded for prefill and absorbed for decode, beside a
chip's share of sigmoid-routed top-k experts chosen with a selection bias,
one shared expert and a leading dense layer) against the plain reference
(``benchmark/blocks/glm4moelite/reference.py``, the EXPANDED form only) at a
small size on the CPU: prefill, then decode through the latent pages, against
the reference's full forward on seeded weights; a prompt prefilled in chunks
against the same prompt prefilled whole; the decode kernel (interpreted)
against a plain softmax over gathered rows; the router against its
equations; the shares of an expert layer against the uncut layer; each piece
of the layer dropped in turn; and through ``ServingEngine``. Tokens are
compared through the reference's LOGITS. Every comparison of a decode step
with the reference is also the proof that absorbed equals expanded.

Tolerances. Both sides compute in float32 on the CPU (no operand rounding)
over the same stored weight VALUES, so they differ by the order of float32
sums (the absorbed form sums ``q Wuk' c'`` where the expanded form sums ``q (c
Wuk)'``) and, where a value lands within that of a bfloat16 rounding boundary,
by one bfloat16 step of a stored latent element: ``ROWS_TOL`` 1e-3 for the
bfloat16 latent rows (one flipped element of a 40-wide row reads 1e-4; a
wrong row reads of order 1), ``LOGIT_TOL`` 1e-4 (logits are of order 0.5),
``KERNEL_TOL`` 1e-5 for the kernel alone in float32 (sums of 128 products
of order 1; bfloat16 operands read 1e-2). Computed with bfloat16
operands where float32 is stated, the rows read 3e-3 and more and the logits
1e-3 and more (``test_the_tolerances_fail_a_step_below``); a dropped piece
reads 1e-2 and more (``test_each_piece_is_in_the_model``).
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

R = importlib.import_module("blocks.glm4moelite.reference")  # noqa: E402
W = importlib.import_module("blocks.glm4moelite.work")  # noqa: E402
from brpc_tpu.serving import (EngineConfig, GlmMoeLiteConfig,  # noqa: E402
                              GlmMoeLiteModel, HybridCacheConfig,
                              HybridStateCache, LlmServingService,
                              ServingEngine, glm_model, moe_model)
from brpc_tpu.tpu import pallas_ops  # noqa: E402

M = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=32,
         kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
         v_head_dim=24, intermediate_size=128, moe_intermediate_size=32,
         n_routed_experts=4, num_routed_experts=16, expert_rank=0,
         num_experts_per_tok=4, n_shared_experts=1, first_k_dense_replace=1,
         routed_scaling_factor=1.8, rope_theta=1e6, rms_norm_eps=1e-5,
         num_hidden_layers=4, vocab_size=256)
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "glm-4.7-flash-l24-e8-serve.json")
SEED, NEW, BS = 5, 10, 16
LENS = (37, 9, 150)
ROWS_TOL, LOGIT_TOL, KERNEL_TOL = 1e-3, 1e-4, 1e-5


def _values(host):
    """The recipe's stored arrays as float32 values."""
    return {k: (v if v.dtype == np.float32 else
                (v.astype(np.uint32) << 16).view(np.float32))
            for k, v in host.items()}


def _bf16(x):
    bits = R.bf16_bits(np.asarray(x, np.float32)).astype(np.uint32) << 16
    return bits.view(np.float32).reshape(np.shape(x))


def _weights(m=M):
    """The recipe's draw, with the norm weights (constants in the recipe)
    drawn as well, so that every term carries weight."""
    host = _values(R.draw_weights(SEED, m))
    rng = np.random.RandomState(1)
    for k, v in host.items():
        if k.endswith(("ln1", "ln2", "lnf", "q_ln", "kv_ln")):
            host[k] = _bf16(1 + rng.standard_normal(v.shape) * 0.1)
    return host


def _ref(host, mode="float32", m=M):
    return R.Reference(SEED, m, mode, pad_to=16, host_weights={
        k: (v if R.is_float32(k) else R.bf16_bits(v))
        for k, v in host.items()})


def _stand(weights=None, attn="reference", m=M, max_context=1024, **cache):
    cfg = GlmMoeLiteConfig(**m, max_context=max_context, seed=SEED,
                           attn=attn)
    cache = dict(dict(block_size=BS, num_blocks=96, max_sequences=4), **cache)
    kv = cfg.cache(HybridCacheConfig(**cache))
    return GlmMoeLiteModel(cfg, kv, weights=weights), kv


def _rel(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def _rows(kv, layer, table, n):
    """The ``kv_dim`` values a page keeps of positions ``0 .. n - 1``."""
    pos = np.arange(n)
    at = np.asarray(table, np.int32)[pos // BS] * BS + pos % BS
    return np.asarray(kv.full.k_pool[layer].astype(np.float32))[
        at, :kv.kv_dim]


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
    counted = json.loads(json.dumps(
        {"moe": model.moe_counters, "mla": model.mla_counters}))
    tables = _decode(model, kv, sids, prompts, outs, NEW - 1)
    refs = [ref.forward(p, o, rows_pad=NEW) for p, o in zip(prompts, outs)]
    return dict(host=host, model=model, kv=kv, ref=ref, prompts=prompts,
                tables=tables, outs=outs, refs=refs, counted=counted)


# ------------------------------------------------ against the plain reference
@pytest.mark.parametrize("i", range(len(LENS)))
def test_prefill_token_is_the_references_best_logit(world, i):
    logits = np.asarray(world["refs"][i][0])
    assert logits[0].max() - logits[0, world["outs"][i][0]] <= LOGIT_TOL


@pytest.mark.parametrize("i", range(len(LENS)))
def test_absorbed_decode_follows_the_expanded_references_logits(world, i):
    logits = np.asarray(world["refs"][i][0])
    served = np.asarray(world["outs"][i])
    assert len(served) == NEW >= 9
    gaps = logits.max(axis=-1) - logits[np.arange(NEW), served]
    assert gaps.max() <= LOGIT_TOL, gaps
    assert np.ptp(logits, axis=-1).min() > 100 * LOGIT_TOL   # none is flat


@pytest.mark.parametrize("i", range(len(LENS)))
@pytest.mark.parametrize("layer,part", [(0, "lat0"), (-1, "latL")])
def test_latent_rows_left_in_the_pages_are_the_references(world, i, layer,
                                                          part):
    """Layer 0's rows (the down-projection, its norm, the rotated key) and
    the last layer's, behind every attention (expanded for the prompt's
    rows, absorbed for the served ones) and expert sublayer before it."""
    kv, t, state = world["kv"], world["tables"][i], world["refs"][i][1]
    n = LENS[i] + NEW - 1
    for lo, hi in ((0, LENS[i]), (LENS[i], n)):
        assert _rel(_rows(kv, layer, t, n)[lo:hi],
                    np.asarray(state[part])[lo:hi]) <= ROWS_TOL


def test_the_pages_pad_columns_hold_nothing(world):
    kv = world["kv"]
    assert kv.full.k_pool.shape[-1] == 128 and kv.kv_dim == 40
    assert not np.asarray(kv.full.k_pool[:, :, kv.kv_dim:]).any()


def test_the_tolerances_fail_a_step_below(world):
    """The reference with bfloat16 operands where float32 is stated parts
    from the program by more than each tolerance admits."""
    low = _ref(world["host"], mode="bfloat16_operands")
    i = 2
    logits, state = low.forward(world["prompts"][i], world["outs"][i],
                                rows_pad=NEW)
    exact = np.asarray(world["refs"][i][0])
    assert np.abs(np.asarray(logits) - exact).max() > 5 * LOGIT_TOL
    kv, t = world["kv"], world["tables"][i]
    n = LENS[i] + NEW - 1
    assert _rel(_rows(kv, -1, t, n), np.asarray(state["latL"])[:n]) \
        > 2 * ROWS_TOL


def test_the_counters_count_pairs_and_latent_rows(world):
    model = world["model"]
    layers = M["num_hidden_layers"]
    moe, mla = world["counted"]["moe"], world["counted"]["mla"]
    assert moe["prefill"]["layer_launches"] == len(LENS) * (layers - 1)
    # top-4 of 16 with 4 held: a row makes between 0 and 4 pairs a layer
    assert 0 < moe["prefill"]["pairs"] <= 4 * sum(LENS) * (layers - 1)
    assert mla["prefill"] == {"launches": len(LENS),
                              "latent_rows": sum(LENS) * layers,
                              "expanded_rows": 0, "skipped_rows": 0,
                              "bucket_rows": 0}
    now = model.mla_counters["decode"]
    assert now["launches"] == NEW - 1
    assert now["latent_rows"] == layers * sum(
        n + s + 1 for n in LENS for s in range(NEW - 1))
    assert model.moe_counters["decode"]["layer_launches"] \
        == (NEW - 1) * (layers - 1)


# -------------------------------------------- chunked against whole prefill
@pytest.mark.parametrize("cuts", [
    (70,),              # splits a block
    (64, 128),          # three chunks on block boundaries
    (128, 149),         # the last chunk is the prompt's last row alone
    (7, 8, 130),        # a chunk of ONE row mid-prompt
], ids=["mid_block", "three_on_blocks", "last_row_alone", "one_row_chunk"])
@pytest.mark.parametrize("attn", ["reference", "flash"])
def test_chunked_prefill_agrees_with_whole_prefill(world, cuts, attn):
    """The same prompt in chunks, a later chunk's K and V built again from
    the latent pages: every layer's rows and the first token agree with the
    whole prefill to rounding, through the blocked path and through the
    flash kernels (interpreted: the whole-prompt call and the carry)."""
    model, kv = world["model"], world["kv"]
    if attn == "flash":
        model, kv = _stand(weights=world["host"], attn="flash")
    p = world["prompts"][2]
    state = world["refs"][2][1]
    t = kv.alloc_sequence(9, len(p))
    try:
        edges = (0,) + cuts + (len(p),)
        for a, b in zip(edges, edges[1:]):
            first = model.prefill_suffix(p[:b], t, a)
        assert first == world["outs"][2][0]
        for layer, part in ((0, "lat0"), (-1, "latL")):
            assert _rel(_rows(kv, layer, t, len(p)),
                        np.asarray(state[part])[:len(p)]) <= ROWS_TOL
        for layer in range(M["num_hidden_layers"]):
            assert _rel(_rows(kv, layer, t, len(p)),
                        _rows(world["kv"], layer, world["tables"][2],
                              len(p))) <= ROWS_TOL
    finally:
        kv.free_sequence(9)


@pytest.mark.parametrize("attn,skipped", [("reference", 0),
                                           ("flash", 2 * (4096 - 1024))])
def test_later_chunks_count_the_rows_they_expand_again(world, attn, skipped):
    """Two later chunks, each over a context bucket of 4096 rows (the floor
    at a context of 4096): the blocked path builds K and V of all of them;
    the kernel reads the first tile of ``MLA_CONTEXT_TILE`` rows alone (both
    chunks end at or before row 150), and ``skipped_rows`` counts the
    rest."""
    assert pallas_ops.MLA_CONTEXT_TILE == 1024
    model, kv = _stand(weights=world["host"], attn=attn, max_context=4096)
    p = world["prompts"][2]
    before = dict(model.mla_counters["prefill"])
    t = kv.alloc_sequence(9, len(p))
    try:
        for a, b in ((0, 64), (64, 128), (128, len(p))):
            model.prefill_suffix(p[:b], t, a)
    finally:
        kv.free_sequence(9)
    after, layers = model.mla_counters["prefill"], M["num_hidden_layers"]
    assert after["launches"] - before["launches"] == 3
    assert after["expanded_rows"] - before["expanded_rows"] \
        == (64 + 128) * layers
    assert after["latent_rows"] - before["latent_rows"] \
        == (64 + 128 + len(p)) * layers
    assert after["bucket_rows"] - before["bucket_rows"] == 2 * 4096 * layers
    assert after["skipped_rows"] - before["skipped_rows"] == skipped * layers


def test_a_chunk_that_reads_no_context_is_far_outside(world):
    """The fault the comparison is there for: the second chunk as a prompt
    of its own (rows from 0, nothing before them) parts by orders."""
    model, kv = world["model"], world["kv"]
    p = world["prompts"][2]
    t = kv.alloc_sequence(9, len(p) - 70)
    try:
        model.prefill_suffix(p[70:], t, 0)
        assert _rel(_rows(kv, -1, t, len(p) - 70),
                    np.asarray(world["refs"][2][1]["latL"])[70:len(p)]) \
            > 100 * ROWS_TOL
    finally:
        kv.free_sequence(9)


# ------------------------------------------------------------ the kernel alone
def _plain_decode(q, pool, layer, table, length, d_v, scale):
    idx = np.concatenate([np.arange(BS) + t * BS for t in table])[:length]
    rows = np.asarray(pool[layer], np.float64)[idx]
    s = np.asarray(q, np.float64) @ rows.T * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ rows[:, :d_v]


@pytest.mark.parametrize("lengths,width,chunk", [
    ((1, 37, 144, 200), 16, 64),     # one row; inside a page; a page's end
    ((5, 64, 65, 128), 8, 64),       # a chunk's end, and one past it
    ((33, 2, 250, 17), 32, 128),     # a table wider than any row needs
    ((16, 48, 80, 112), 8, 16),      # a page a step
], ids=["ragged", "chunk_edges", "wide_table", "page_steps"])
def test_mla_paged_decode_equals_a_plain_softmax_over_gathered_rows(
        lengths, width, chunk):
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    layers, blocks, d, d_v, heads = 3, 48, 128, 96, 5
    pool = jnp.asarray(rng.standard_normal((layers, (blocks + 1) * BS, d)),
                       jnp.float32)
    table = np.zeros((len(lengths), width), np.int32)
    order, k = rng.permutation(np.arange(1, blocks + 1)), 0
    for b, n in enumerate(lengths):
        pages = -(-n // BS)
        table[b, :pages] = order[k:k + pages]
        k += pages
    q = jnp.asarray(rng.standard_normal((len(lengths), heads, d)),
                    jnp.float32)
    for layer in (0, 2):
        out = pallas_ops.mla_paged_decode(
            q, pool, layer, jnp.asarray(table), jnp.asarray(lengths),
            block_size=BS, d_v=d_v, scale=0.25, chunk=chunk)
        assert out.shape == (len(lengths), heads, d_v)
        for b, n in enumerate(lengths):
            want = _plain_decode(q[b], pool, layer, table[b], n, d_v, 0.25)
            assert np.abs(np.asarray(out[b]) - want).max() <= KERNEL_TOL


def _bf16_plain_decode(q, pool, layer, table, length, d_v, scale):
    """``_plain_decode`` with both products' operands rounded to bfloat16
    as the compiled kernel rounds them (the probabilities after the row's
    maximum; the kernel rounds them after its chunk's)."""
    idx = np.concatenate([np.arange(BS) + t * BS for t in table])[:length]
    rows = _bf16(np.asarray(pool[layer], np.float32)[idx]).astype(np.float64)
    s = _bf16(np.asarray(q, np.float32)).astype(np.float64) @ rows.T * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    return (_bf16(p.astype(np.float32)).astype(np.float64)
            @ rows[:, :d_v]) / p.sum(-1, keepdims=True)


@pytest.mark.parametrize("lengths,width,chunk", [
    ((1, 37, 144, 200), 16, 64), ((33, 2, 250, 17), 32, 128),
], ids=["ragged", "wide_table"])
def test_mla_paged_decode_at_bfloat16_operands_is_the_chips_rounding(
        lengths, width, chunk):
    """What the compiled kernel computes with (``operand_dtype`` bfloat16,
    the default on the TPU; the interpreted default rounds nothing): both
    products' operands bfloat16, float32 sums. Against the plain form on
    operands rounded the same way it differs only by where the
    probabilities are rounded, well inside what the rounding itself moves
    (which ``KERNEL_TOL`` would refuse)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    blocks, d, d_v, heads = 48, 128, 96, 5
    pool = jnp.asarray(rng.standard_normal((2, (blocks + 1) * BS, d)),
                       jnp.float32)
    table = np.zeros((len(lengths), width), np.int32)
    order, k = rng.permutation(np.arange(1, blocks + 1)), 0
    for b, n in enumerate(lengths):
        pages = -(-n // BS)
        table[b, :pages] = order[k:k + pages]
        k += pages
    q = jnp.asarray(rng.standard_normal((len(lengths), heads, d)),
                    jnp.float32)
    got = np.asarray(pallas_ops.mla_paged_decode(
        q, pool, 1, jnp.asarray(table), jnp.asarray(lengths),
        block_size=BS, d_v=d_v, scale=0.25, chunk=chunk, interpret=True,
        operand_dtype=jnp.bfloat16))
    gap = rounding = 0.0
    for b, n in enumerate(lengths):
        want = _bf16_plain_decode(q[b], pool, 1, table[b], n, d_v, 0.25)
        exact = _plain_decode(q[b], pool, 1, table[b], n, d_v, 0.25)
        gap = max(gap, np.abs(got[b] - want).max())
        rounding = max(rounding, np.abs(exact - want).max())
    assert rounding > 100 * KERNEL_TOL, rounding
    assert gap < 2e-2 and gap < 2 * rounding + 1e-3, (gap, rounding)


def test_mla_paged_decode_ignores_what_lies_past_a_rows_length():
    """Rows past the length, in the row's last page and in the pages its
    table names after it, weigh nothing: whatever finite values a pool
    holds there (another sequence's rows) change no bit of the output."""
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    pool = rng.standard_normal((2, 9 * BS, 128)).astype(np.float32)
    lengths, table = (21, 3), np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]])
    q = jnp.asarray(rng.standard_normal((2, 3, 128)), jnp.float32)
    dirty = pool.copy()
    dirty[:, 0:BS] = 1e6                          # the scratch block
    dirty[:, 2 * BS + 5:5 * BS] = -1e6            # past row 0's 21 rows
    dirty[:, 5 * BS + 3:] = 1e6                   # past row 1's 3 rows
    want = pallas_ops.mla_paged_decode(
        q, jnp.asarray(pool), 1, jnp.asarray(table), jnp.asarray(lengths),
        block_size=BS, d_v=96, scale=0.25, chunk=32)
    got = pallas_ops.mla_paged_decode(
        q, jnp.asarray(dirty), 1, jnp.asarray(table), jnp.asarray(lengths),
        block_size=BS, d_v=96, scale=0.25, chunk=32)
    assert np.isfinite(np.asarray(got)).all()
    assert np.array_equal(np.asarray(got), np.asarray(want))


def _plain_chunk(q, ctx, wuk, wuv, start, length):
    """A chunk's rows over K and V built from the latent rows ``[0, start +
    length)`` in float64, a softmax a head: (C, H x v)."""
    h, r, nope = wuk.shape
    hd = q.shape[1] // h
    end, c = start + length, q.shape[0]
    rows = np.asarray(ctx, np.float64)[:end]
    live = start + np.arange(c)[:, None] >= np.arange(end)[None, :]
    out = []
    for j in range(h):
        k = np.concatenate([rows[:, :r] @ wuk[j], rows[:, r:r + hd - nope]],
                           axis=1)
        s = np.asarray(q, np.float64)[:, j * hd:(j + 1) * hd] @ k.T
        s = np.where(live, s / np.sqrt(hd), -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out.append((p / p.sum(-1, keepdims=True)) @ (rows[:, :r] @ wuv[j]))
    return np.concatenate(out, axis=1)


@pytest.mark.parametrize("heads,c,n_ctx,start,length", [
    (5, 64, 256, 128, 64),     # several heads, the chunk on a tile's edge
    (3, 64, 256, 37, 64),      # a chunk from a row inside a tile
    (3, 64, 256, 100, 40),     # pad rows past the chunk's length
    (3, 64, 256, 150, 1),      # a chunk of one row
    (3, 64, 1024, 70, 50),     # a bucket far wider than the chunk's end
], ids=["heads", "unaligned_start", "pad_rows", "one_row", "wide_bucket"])
def test_mla_chunk_attention_equals_a_softmax_over_built_keys(
        heads, c, n_ctx, start, length):
    """The later chunk's kernel (interpreted) at tiles of 64 context rows
    and blocks of 32 query rows, against K and V built from the latent rows
    in float64. Every row of ``ctx`` past the chunk's end is not-a-number:
    whole tiles of them are never read and the rest weigh nothing, so every
    output row, pads too, is finite."""
    import jax.numpy as jnp

    rng = np.random.default_rng(heads * 1000 + start)
    r, nope, rot, d_v, d = 32, 16, 8, 24, 128
    ctx = rng.standard_normal((n_ctx, d)).astype(np.float32)
    ctx[:, r + rot:] = 0.0
    ctx[start + length:] = np.nan
    q = rng.standard_normal((c, heads * (nope + rot))).astype(np.float32)
    wuk = (rng.standard_normal((heads, r, nope)) / np.sqrt(r)).astype(
        np.float32)
    wuv = (rng.standard_normal((heads, r, d_v)) / np.sqrt(r)).astype(
        np.float32)
    got = np.asarray(pallas_ops.mla_chunk_attention(
        jnp.asarray(q), jnp.asarray(ctx), jnp.asarray(wuk), jnp.asarray(wuv),
        start, length, block_q=32, block_k=64))
    assert got.shape == (c, heads * d_v) and np.isfinite(got).all()
    want = _plain_chunk(q, ctx, wuk, wuv, start, length)
    assert np.abs(got[:length] - want[:length]).max() <= KERNEL_TOL


# ------------------------------------------------------------------ the router
def _route_args():
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    cfg = GlmMoeLiteConfig(**M)
    h = jnp.asarray(rng.standard_normal((200, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 16)) * 0.06, jnp.float32)
    b = jnp.asarray(rng.standard_normal(16) * 0.05, jnp.float32)
    return cfg, h, w, b, jnp.ones(200, bool)


def test_the_bias_moves_the_choice_and_not_the_weight():
    cfg, h, w, b, live = _route_args()
    s = 1 / (1 + np.exp(-np.asarray(h) @ np.asarray(w)))
    idx, wts = moe_model.route(cfg, h, w, live, bias=b, scale=1.8)
    idx, wts = np.asarray(idx), np.asarray(wts)
    want = np.argsort(-(s + np.asarray(b)), axis=-1)[:, :4]
    assert np.array_equal(np.sort(idx, -1), np.sort(want, -1))
    plain = np.argsort(-s, axis=-1)[:, :4]
    moved = (np.sort(idx, -1) != np.sort(plain, -1)).any(-1)
    assert 0.05 < moved.mean() < 0.95      # the bias decides some rows
    chosen = np.take_along_axis(s, idx, -1)
    assert np.abs(wts - 1.8 * chosen / chosen.sum(-1, keepdims=True)).max() \
        <= 1e-6


def test_the_weights_sum_to_the_scaling_factor():
    cfg, h, w, b, live = _route_args()
    _idx, wts = moe_model.route(cfg, h, w, live, bias=b, scale=1.8)
    assert np.abs(np.asarray(wts).sum(-1) - 1.8).max() <= 1e-6


def test_without_bias_and_scale_route_is_what_it_was():
    """The defaults trace the program the other expert model has: the top k
    of the scores themselves, weights summing to one."""
    import jax

    cfg, h, w, b, live = _route_args()
    idx, wts = moe_model.route(cfg, h, w, live)
    s = jax.nn.sigmoid(np.asarray(h) @ np.asarray(w))
    top, want = jax.lax.top_k(s, 4)
    assert np.array_equal(np.asarray(idx), np.asarray(want))
    assert np.abs(np.asarray(wts) - np.asarray(
        top / top.sum(-1, keepdims=True))).max() <= 1e-6
    zero = moe_model.route(cfg, h, w, live, bias=b * 0, scale=1.0)
    assert np.array_equal(np.asarray(zero[0]), np.asarray(idx))


def test_a_row_that_is_not_live_routes_nowhere():
    cfg, h, w, b, live = _route_args()
    idx, _ = moe_model.route(cfg, h, w, live.at[3].set(False), bias=b,
                             scale=1.8)
    assert (np.asarray(idx)[3] == -1).all() and (np.asarray(idx)[4] >= 0).all()


# ------------------------------------------------------------------ the share
def test_the_ranks_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """The four ranks' routed parts (each through the program's own
    ``route`` and ``expert_layer`` over the experts it holds) plus the
    shared expert counted once add up to the uncut reference's layer."""
    import jax.numpy as jnp

    uncut = dict(M, n_routed_experts=16, num_routed_experts=16)
    host = _weights(uncut)
    w = {k[3:]: jnp.asarray(v) for k, v in host.items()
         if k.startswith("l2.")}
    x = jnp.asarray(np.random.RandomState(8).standard_normal((96, 64)),
                    jnp.float32)
    routed, shared = R.sublayer_parts(uncut, w, x, 0, 16)
    whole = np.asarray(routed + shared)
    hn = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5) \
        * w["ln2"]
    live, ff = jnp.ones(96, bool), M["moe_intermediate_size"]
    total, pairs = 0.0, 0
    for rank in range(4):
        cfg = GlmMoeLiteConfig(**dict(M, expert_rank=rank))
        idx, wts = moe_model.route(cfg, hn, w["router"], live,
                                   bias=w["r_bias"], scale=cfg.route_scale)
        held = range(cfg.expert_lo, cfg.expert_lo + cfg.held)
        wgu = jnp.stack([jnp.concatenate(
            [w[f"e{e}.wg"], w[f"e{e}.wu"]], axis=1) for e in held])
        wd = jnp.stack([w[f"e{e}.wd"] for e in held])
        part, cnt = moe_model.expert_layer(cfg, hn, idx, wts, wgu, wd, 16)
        # the reference's own share is this rank's part
        mine, _ = R.sublayer_parts(dict(M, expert_rank=rank), w, x,
                                   cfg.expert_lo, cfg.held)
        assert _rel(part, mine) <= 1e-5
        total, pairs = total + np.asarray(part), pairs + int(cnt.sum())
    cfg = GlmMoeLiteConfig(**M)
    once = moe_model.shared_experts(
        cfg, hn, jnp.concatenate([w["s0.wg"], w["s0.wu"]], axis=1),
        w["s0.wd"])
    assert pairs == 96 * 4           # every pair is exactly one rank's
    assert _rel(total + np.asarray(once), whole) <= 1e-5
    assert _rel(total, whole) > 0.1 and _rel(np.asarray(once), whole) > 0.1


# ------------------------------------------------------- each piece matters
def _without(piece, host):
    """(weights, patches) of a program with one piece of the layer left
    out: by the weights that carry it where it has some, by a patch of the
    program's own function where it has none."""
    w, patches = dict(host), {}
    layers = range(1, M["num_hidden_layers"])
    if piece == "selection_bias":
        for l in layers:
            w[f"l{l}.r_bias"] = w[f"l{l}.r_bias"] * 0
    elif piece == "scaling_factor":
        orig = moe_model.route
        patches[(glm_model, "route")] = lambda *a, **kw: orig(
            *a, **dict(kw, scale=None))
    elif piece == "shared_expert":
        patches[(glm_model, "shared_experts")] = \
            lambda cfg, h, wgu, wd: h * 0
    elif piece == "rotary":
        patches[(glm_model, "rope")] = lambda x, pos, theta: x
    elif piece == "latent_norm":
        for l in range(M["num_hidden_layers"]):
            w[f"l{l}.kv_ln"] = np.ones_like(w[f"l{l}.kv_ln"])
    elif piece == "dense_layer":
        w["l0.wd"] = w["l0.wd"] * 0
    else:
        raise ValueError(piece)
    return w, patches


@pytest.mark.parametrize("piece", [
    "selection_bias", "scaling_factor", "shared_expert", "rotary",
    "latent_norm", "dense_layer"])
def test_each_piece_is_in_the_model(world, piece, monkeypatch):
    """The program with ONE piece dropped, against the reference that has
    it: the last layer's rows part by orders of the tolerance the whole
    model is held to."""
    weights, patches = _without(piece, world["host"])
    for (owner, name), fn in patches.items():
        monkeypatch.setattr(owner, name, fn)
    model, kv = _stand(weights=weights)
    p, state = world["prompts"][2], world["refs"][2][1]
    t = kv.alloc_sequence(1, len(p))
    model.prefill(p, t)
    off = _rel(_rows(kv, -1, t, len(p)), np.asarray(state["latL"])[:len(p)])
    assert off > 10 * ROWS_TOL, (piece, off)


def test_rotary_turns_interleaved_pairs_of_all_the_rope_dims():
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(0).standard_normal((5, 3, 8)),
                    jnp.float32)
    pos = jnp.asarray([0, 1, 7, 100, 30000])
    got = np.asarray(moe_model.rope(x, pos, 1e6))
    assert np.allclose(got[0], np.asarray(x)[0], atol=1e-6)
    pair = lambda a: a[..., 0::2] ** 2 + a[..., 1::2] ** 2   # noqa: E731
    assert np.allclose(pair(got), pair(np.asarray(x)), rtol=1e-4, atol=1e-5)
    assert np.abs(got - np.asarray(R.rope(x, pos, 1e6))).max() <= 1e-5
    assert (np.abs(got[1:] - np.asarray(x)[1:]) > 1e-3).any(axis=(0, 1)).all()


# -------------------------------------------------- the count and the manager
def _published():
    with open(CONFIG_FILE) as f:
        cfg_file = json.load(f)
    m = {k: v for k, v in cfg_file["runner_args"]["model"].items()
         if k not in ("rehearsal", "max_context", "attn")}
    return cfg_file, m


def test_weight_count_at_the_published_widths_from_shapes_on_both_sides():
    """``work.py`` (the reference's shapes) and the program's shapes give
    the issue's numbers: this cut's, a layer's, and the whole model's."""
    cfg_file, m = _published()
    z = R.sizes(m)
    assert W.attention_weights(z) == 21_759_232
    assert W.layer_parameters(z, 0, 8) == 84_677_888
    assert W.layer_parameters(z, 1, 0) == 31_331_648
    assert W.layer_parameters(z, 1, 8) == 106_829_120
    assert W.layer_parameters(z, 1, 64) == 635_311_424
    assert W.weight_count(m) == 3_176_138_176
    assert W.weight_count(m, layers=47, held=64) == 29_943_393_920 \
        == cfg_file["published"]["parameters"]
    cfg = GlmMoeLiteConfig(**m)
    mine = sum(int(np.prod(a[2])) for layer in [None] + list(range(24))
               for a in cfg.arrays(layer))
    assert mine == 3_176_138_176
    assert sum(int(np.prod(a[2])) for a in R.arrays(m)) == mine
    assert W.stored_bytes(m) == 2 * mine + 2 * 23 * 131_136


def test_the_configuration_file_states_its_cut_and_its_departures():
    """(The published keys against the catalog row:
    ``benchmark/tests/test_glm4moelite_block.py``.)"""
    cfg_file, m = _published()
    assert cfg_file["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert (cfg_file["num_hidden_layers"], cfg_file["n_routed_experts"]) \
        == (24, 8)
    assert (cfg_file["published"]["num_hidden_layers"],
            cfg_file["published"]["n_routed_experts"]) == (47, 64)
    assert m["num_routed_experts"] == 64 and m["n_routed_experts"] == 8
    for k in ("deployment", "cut", "departures", "assumed", "precision",
              "weights"):
        assert cfg_file[k]
    assert any("multi-token-prediction" in d for d in cfg_file["departures"])


def test_staged_bytes_are_two_a_parameter_and_four_in_the_router(world):
    """Every parameter is held once (Wkvb as its split, not beside it):
    bfloat16, router and bias float32."""
    z = R.sizes(M)
    router = (M["num_hidden_layers"] - 1) * W.router_weights(z)
    assert world["model"].param_nbytes == 2 * W.weight_count(M) + 2 * router \
        == W.stored_bytes(M)


def test_program_and_reference_draw_the_same_weights():
    """With no weights handed over, the model draws the recipe the
    reference draws independently (Wkvb put together again from its
    split)."""
    model, _kv = _stand()
    host = _values(R.draw_weights(SEED, M))
    for l in (0, 3):
        got = model.layer_weights(l)
        assert set(got) == {k[3:] for k in host if k.startswith(f"l{l}.")}
        for k, arr in got.items():
            assert np.array_equal(np.asarray(arr.astype(np.float32)),
                                  host[f"l{l}.{k}"]), k
    for k in ("embed", "head", "lnf"):
        assert np.array_equal(
            np.asarray(model._params[k].astype(np.float32)), host[k])


def test_a_page_of_one_array_allocates_no_value_bytes(world):
    kv, layers = world["kv"], M["num_hidden_layers"]
    assert kv.v_dim == 0 and kv.kv_dim == 40
    assert kv.full.v_pool.shape == (layers, 97 * BS, 0)
    assert kv.full.v_pool.size == 0 and kv.window.v_pool.size == 0
    assert kv.ssm.size == 0 and kv.conv.size == 0 and kv.ring_blocks == 0
    assert not kv.recurrent_state and kv.state_overwritten
    snap = kv.snapshot()
    assert snap["page_row"] == {"k": 40, "v": 0}
    blocks = sum(-(-(n + NEW - 1) // BS) for n in LENS)
    assert snap["cache_bytes"] >= blocks * BS * layers * 40 * 2
    assert kv._block_bytes["full"] == BS * layers * 40 * 2
    # the published widths: 1152 B a token a layer in bfloat16
    _cfg_file, m = _published()
    big = GlmMoeLiteConfig(**m)
    assert big.kv_dim == 576
    tiny = HybridStateCache(
        HybridCacheConfig(block_size=BS, num_blocks=2, max_sequences=1),
        big.kv_dim, 0, full_layers=24, dtype="bfloat16", v_dim=0)
    # allocated at whole lane tiles, counted at the values held
    assert tiny.full.k_pool.shape == (24, 3 * BS, 640)
    assert tiny._block_bytes["full"] == BS * 24 * 1152
    tiny.alloc_sequence(1, BS)
    assert tiny.snapshot()["cache_bytes"] == BS * 24 * 1152 == BS * 27_648
    tiny.free_sequence(1)
    tiny.close()


def _manager_of(name):
    from brpc_tpu.serving import (Cohere2MoeConfig, JambaConfig,
                                  SambaYConfig, ZayaConfig)

    cc = HybridCacheConfig(block_size=BS, num_blocks=32, max_sequences=2,
                           window=16)
    if name == "sambay":
        cfg = SambaYConfig()
        return cfg.cache(cc), 4, (1, cfg.count("window"))
    if name == "cohere2moe":
        cfg = Cohere2MoeConfig()
        return cfg.cache(cc), 2, (cfg.count("full"), cfg.count("window"))
    if name == "jamba":
        cfg = JambaConfig()
        return cfg.cache(cc), 2, (cfg.count("full"), 0)
    cfg = ZayaConfig()
    return cfg.cache(cc), 2, (cfg.n_layers, 0)


@pytest.mark.parametrize("name", ["sambay", "cohere2moe", "jamba", "zaya"])
def test_the_other_models_managers_report_the_bytes_they_reported(name):
    """K and V of equal width: a block counts ``2 * kv_dim`` values a row,
    as before a page could be one array."""
    kv, itemsize, (full, window) = _manager_of(name)
    try:
        assert kv.v_dim == kv.kv_dim
        assert kv.full.v_pool.shape == kv.full.k_pool.shape
        assert kv.full.k_pool.shape[-1] == kv.kv_dim      # nothing padded
        assert kv._block_bytes == {
            "full": 2 * full * BS * kv.kv_dim * itemsize,
            "window": 2 * window * BS * kv.kv_dim * itemsize}
        assert kv.snapshot()["page_row"] == {"k": kv.kv_dim, "v": kv.kv_dim}
        kv.alloc_sequence(1, 3 * BS)
        assert kv.snapshot()["cache_bytes"] == (
            3 * kv._block_bytes["full"]
            + kv.ring_blocks * kv._block_bytes["window"] + kv._slot_bytes)
        kv.free_sequence(1)
    finally:
        kv.close()


# --------------------------------------------------------- spans and counters
def test_the_programs_carry_their_named_scopes(world):
    """Every scope a profile is read by is in the lowered programs:
    ``mla_expand`` in prefill only, ``mla_absorb`` in decode only."""
    import jax.numpy as jnp

    model, kv = world["model"], world["kv"]
    pools = (kv.full.k_pool, kv.full.v_pool, kv.window.k_pool,
             kv.window.v_pool, kv.ssm, kv.conv)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)   # noqa: E731
    chunk = model._chunk_fn(512, 1024, False).lower(
        model._params, *pools, i32(512), i32(64), i32(), i32(), i32()
    ).as_text(debug_info=True)
    decode = model._decode_fn(8, 1024).lower(
        model._params, *pools, i32(8), i32(8), i32(8, 64), i32(8, 0), i32(8)
    ).as_text(debug_info=True)
    shared = ("mla_down", "mla_attention", "rope", "router", "moe_dispatch",
              "experts", "shared_experts", "dense_mlp", "head")
    for scope in shared + ("mla_expand",):
        assert scope in chunk, scope
    for scope in shared + ("mla_absorb", "mla_paged_decode"):
        assert scope in decode, scope
    assert "mla_absorb" not in chunk and "mla_expand" not in decode


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


def test_the_engine_serves_what_the_reference_would(world, unchunked):
    logits, _ = world["ref"].forward(world["prompts"][0],
                                     unchunked["short"], rows_pad=40)
    logits = np.asarray(logits)
    gaps = logits.max(axis=-1) - logits[np.arange(40), unchunked["short"]]
    assert gaps.max() <= LOGIT_TOL


def test_a_long_prompt_goes_a_chunk_a_step_beside_the_decode_rows(
        world, long_prompt, unchunked, engine):
    """token_budget 132 leaves 128 rows a step: the 700-row prompt takes 6
    steps beside the running sequence; the served tokens equal those of
    unchunked serving; ``snapshot()["mla"]`` and the ``mla:`` line of
    `/serving` count the latent rows read and the rows expanded again."""
    model, kv, eng = engine(132)
    assert eng._chunk_unit == 128
    before = json.loads(json.dumps(eng.snapshot()["mla"]))
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
             if l.strip().startswith(("mla:", "moe:"))]
    eng.stop()
    kv.assert_idle("engine stopped")
    assert got == unchunked
    assert chunks == [(0, 128), (128, 256), (256, 384), (384, 512),
                      (512, 640), (640, 700)]
    assert snap["prefill_chunks"] == 6 and snap["prefill_chunk_rows"] == 700
    layers = M["num_hidden_layers"]
    mla, moe = snap["mla"], snap["moe"]
    pre = {k: mla["prefill"][k] - before["prefill"][k]
           for k in mla["prefill"]}
    # the short prompt whole, then six chunks of the long one
    assert pre == {"launches": 7,
                   "latent_rows": layers * (LENS[0] + 128 + 256 + 384 + 512
                                            + 640 + 700),
                   "expanded_rows": layers * (128 + 256 + 384 + 512 + 640),
                   # five later chunks over buckets of 1024 rows; the
                   # blocked path (the CPU's default) reads all of them
                   "skipped_rows": 0, "bucket_rows": layers * 5 * 1024}
    dec = {k: mla["decode"][k] - before["decode"][k] for k in mla["decode"]}
    assert dec["launches"] >= 39 and dec["expanded_rows"] == 0
    assert dec["latent_rows"] == layers * (
        sum(LENS[0] + s + 1 for s in range(39))
        + sum(700 + s + 1 for s in range(5)))
    assert moe["experts_held"] == 4 and moe["decode"]["pairs"] > 0
    assert len(lines) == 2
    assert f"latent_rows={mla['decode']['latent_rows']}" in lines[1]
    assert f"expanded_rows={mla['prefill']['expanded_rows']}" in lines[1]
    assert f"skipped_rows={mla['prefill']['skipped_rows']}" in lines[1]
    assert f"bucket_rows={mla['prefill']['bucket_rows']}" in lines[1]
