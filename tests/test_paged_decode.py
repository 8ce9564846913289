"""Decode attention over the K/V pages where they lie
(tpu/pallas_ops.py ``paged_decode_attention``; serving/model.py
``_decode_body``): the kernel in interpret mode against the gather body.

Three layers, cheapest first:

* the kernel alone against ``_gather_attention`` on one pool: ragged
  lengths, padded rows on scratch block 0, shuffled tables, every table
  width a bucket can have, the pool slice and head count of a tp/dp
  shard, the bfloat16 operands the chip computes with;
* the decode program with the kernel in it (``_decode_paged`` patched on
  the CPU, where it answers False by itself): rows that share a table
  see each other's same-launch writes (``verify_step``'s and
  ``prefill_suffix``'s shape), a 64-token greedy answer equals the gather
  path's, the mesh model's shard_map body takes the same kernel;
* the counters that say which path ran, up to ``/serving``.
"""

import threading
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from brpc_tpu.serving import (EngineConfig, KVCacheConfig, MeshTransformer,
                              ModelConfig, PagedKVCache, ServingEngine,
                              ShardedKVCache, TinyTransformer)
from brpc_tpu.serving import model as model_mod
from brpc_tpu.tpu import pallas_ops

BS = 16


# ------------------------------------------------------------ kernel alone
def _pools(rng, layers, blocks, bs, dim):
    shape = (layers, (blocks + 1) * bs, dim)
    return (jnp.asarray(rng.standard_normal(shape), jnp.float32),
            jnp.asarray(rng.standard_normal(shape), jnp.float32))


def _tables(rng, lengths, n_pages, blocks, bs, shuffle=True):
    """One block table a row over distinct blocks 1..blocks, in a shuffled
    (non-contiguous) or ascending order, padded with scratch block 0."""
    ids = np.arange(1, blocks + 1)
    if shuffle:
        ids = rng.permutation(ids)
    out = np.zeros((len(lengths), n_pages), np.int32)
    used = 0
    for b, n in enumerate(lengths):
        live = -(-int(n) // bs)
        out[b, :live] = ids[used:used + live]
        used += live
    assert used <= blocks
    return out


def _oracle(q, kpool, vpool, layer, tables, lengths, n_heads, bs,
            operand_dtype=None):
    """The gather body's attention on the same pool and tables."""
    B, n_pages = tables.shape
    L = n_pages * bs
    slots = (tables[:, :, None] * bs + np.arange(bs)).reshape(B, L)
    mask = np.arange(L)[None, :] < np.asarray(lengths)[:, None]
    kl, vl = kpool[layer], vpool[layer]
    if operand_dtype is not None:   # what the chip rounds its operands to
        q, kl, vl = (a.astype(operand_dtype).astype(jnp.float32)
                     for a in (q, kl, vl))
    return model_mod._gather_attention(q, kl, vl, jnp.asarray(slots),
                                       jnp.asarray(mask), n_heads)


KERNEL_CASES = {
    # lengths, table width in pages, heads, head_dim, block size
    "ragged_1_bs-1_bs_bs+1_full": ([1, BS - 1, BS, BS + 1, 16 * BS], 16,
                                   4, 16, BS),
    "one_row_one_position": ([1], 2, 4, 16, BS),
    "table_narrower_than_a_chunk": ([3, 2 * BS], 2, 4, 16, BS),
    "table_of_one_chunk": ([5, 8 * BS, 4 * BS + 1], 8, 4, 16, BS),
    "table_of_four_chunks": ([32 * BS, 1, 17 * BS - 1, 8 * BS + 1], 32,
                             2, 32, BS),
    "padded_rows_on_scratch_block_0": ([40, 1, 1, 1], 4, 4, 16, BS),
    "every_row_full": ([8 * BS] * 4, 8, 4, 16, BS),
    "tp_shard_slice_2_heads_of_64": ([BS + 3, 100, 7], 8, 2, 64, BS),
    "dp_shard_slice_one_head": ([9, 2 * BS + 1], 4, 1, 32, BS),
    "block_size_8": ([1, 7, 8, 9, 128, 77], 16, 4, 16, 8),
    "sixteen_rows": (list(range(1, 17 * 12, 12))[:16], 16, 4, 16, BS),
}


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
@pytest.mark.parametrize("shuffle", [True, False],
                         ids=["shuffled_table", "ascending_table"])
def test_kernel_equals_gather_attention(name, shuffle):
    lengths, n_pages, n_heads, hd, bs = KERNEL_CASES[name]
    rng = np.random.default_rng(len(name))
    blocks = sum(-(-n // bs) for n in lengths) + 3
    dim, layers = n_heads * hd, 3
    kpool, vpool = _pools(rng, layers, blocks, bs, dim)
    if "padded" in name:
        # padded rows carry a table of noughts: scratch block 0
        tables = _tables(rng, lengths[:1], n_pages, blocks, bs, shuffle)
        tables = np.concatenate(
            [tables, np.zeros((len(lengths) - 1, n_pages), np.int32)])
    else:
        tables = _tables(rng, lengths, n_pages, blocks, bs, shuffle)
    q = jnp.asarray(rng.standard_normal((len(lengths), dim)), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    for layer in (0, layers - 1):   # the layer is an operand, not a static
        got = pallas_ops.paged_decode_attention(
            q, kpool, vpool, jnp.int32(layer), jnp.asarray(tables), lens,
            n_heads=n_heads, block_size=bs, interpret=True)
        want = _oracle(q, kpool, vpool, layer, tables, lengths, n_heads, bs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_kernel_ignores_what_lies_past_a_rows_length():
    """Pages past a row's length are never read: poison in every slot the
    lengths do not cover (NaN would survive a masked product) changes
    nothing, in the pool's dead pages and in the tail of the last page."""
    rng = np.random.default_rng(7)
    lengths, n_pages, n_heads, hd = [1, BS + 1, 3 * BS], 8, 4, 16
    kpool, vpool = _pools(rng, 2, 12, BS, n_heads * hd)
    tables = _tables(rng, lengths, n_pages, 12, BS)
    q = jnp.asarray(rng.standard_normal((3, n_heads * hd)), jnp.float32)

    def run(kp, vp):
        return np.asarray(pallas_ops.paged_decode_attention(
            q, kp, vp, jnp.int32(1), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32), n_heads=n_heads,
            block_size=BS, interpret=True))

    live = np.zeros(kpool.shape[1], bool)
    for b, n in enumerate(lengths):
        pos = np.arange(n)
        live[tables[b, pos // BS] * BS + pos % BS] = True
    poison = jnp.where(jnp.asarray(live)[None, :, None], 0.0, jnp.nan)
    clean = run(kpool, vpool)
    assert np.isfinite(clean).all()
    np.testing.assert_array_equal(run(kpool + poison, vpool), clean)
    # V's dead rows meet a probability of exactly 0, not a skipped read:
    # a finite value there must not matter either
    huge = jnp.where(jnp.asarray(live)[None, :, None], 0.0, 1e30)
    np.testing.assert_array_equal(run(kpool + huge, vpool + huge), clean)


@pytest.mark.parametrize("name", ["ragged_1_bs-1_bs_bs+1_full",
                                  "table_of_four_chunks"])
def test_kernel_bfloat16_operands_are_the_chips_rounding(name):
    """What the compiled kernel computes with: both products' operands
    rounded to bfloat16, float32 sums. Against the gather body on
    operands rounded the same way the kernel differs only by where the
    probabilities are rounded (after the block's maximum, not the
    row's)."""
    lengths, n_pages, n_heads, hd, bs = KERNEL_CASES[name]
    rng = np.random.default_rng(11)
    blocks = sum(-(-n // bs) for n in lengths) + 1
    kpool, vpool = _pools(rng, 2, blocks, bs, n_heads * hd)
    tables = _tables(rng, lengths, n_pages, blocks, bs)
    q = jnp.asarray(rng.standard_normal((len(lengths), n_heads * hd)),
                    jnp.float32)
    got = pallas_ops.paged_decode_attention(
        q, kpool, vpool, jnp.int32(1), jnp.asarray(tables),
        jnp.asarray(lengths, jnp.int32), n_heads=n_heads, block_size=bs,
        interpret=True, operand_dtype=jnp.bfloat16)
    want = _oracle(q, kpool, vpool, 1, tables, lengths, n_heads, bs,
                   operand_dtype=jnp.bfloat16)
    exact = _oracle(q, kpool, vpool, 1, tables, lengths, n_heads, bs)
    gap = float(jnp.max(jnp.abs(got - want)))
    rounding = float(jnp.max(jnp.abs(exact - want)))
    assert gap < 2e-2 and gap < 2 * rounding + 1e-3, (gap, rounding)


def test_kernel_refuses_a_table_it_cannot_chunk():
    """A table of any width is walked a chunk at a time, its last chunk
    ragged where the width does not divide into chunks
    (``test_kernel_takes_a_table_whose_last_chunk_is_ragged``); what it
    cannot walk is a table its scalar memory does not hold."""
    rng = np.random.default_rng(0)
    kpool, vpool = _pools(rng, 1, 16, BS, 64)
    rows = pallas_ops.PAGED_TABLE_BYTES // (128 * 4)
    with pytest.raises(ValueError, match="SMEM"):
        pallas_ops.paged_decode_attention(
            jnp.zeros((2 * rows, 64)), kpool, vpool, jnp.int32(0),
            jnp.zeros((2 * rows, 128), jnp.int32),
            jnp.ones((2 * rows,), jnp.int32),
            n_heads=4, block_size=BS, interpret=True)


# ------------------------------------ grouped key/value heads, window rings
def gather_decode_attention(q, k_pool, v_pool, layer, block_tables,
                            lengths, n_heads, block_size, n_kv_heads=None,
                            ring=None, **_):
    """The path the grouped kernel replaced in ``Cohere2MoeModel``'s
    decode: each row's context gathered out of the pool whole blocks at a
    time, padded to the table (``_block_context``), the grouped einsum and
    ``_softmax_out``; a ring's rows masked by ``_ring_live``. It takes the
    kernel's arguments, so it can stand in for it."""
    import math

    from brpc_tpu.serving.hybrid_model import _block_context, _mm, _ring_live
    from brpc_tpu.serving.moe_model import _softmax_out

    g = n_kv_heads or n_heads
    B, hd = q.shape[0], q.shape[1] // n_heads
    kc = _block_context(k_pool, layer, block_tables, block_size)
    vc = _block_context(v_pool, layer, block_tables, block_size)
    pos = jnp.asarray(lengths) - 1
    if ring is None:
        live = jnp.arange(kc.shape[1])[None, :] <= pos[:, None]
    else:
        live = _ring_live(pos, *ring)[:, :kc.shape[1]]
    qh = q.reshape(B, g, n_heads // g, hd)
    kh, vh = (c.reshape(B, -1, g, hd) for c in (kc, vc))
    sc = _mm(qh, kh, "bgjd,bkgd->bgjk") / math.sqrt(hd)
    out = _softmax_out(sc, live[:, None, None, :], vh, "bgjk,bkgd->bgjd")
    return out.reshape(B, -1)


def _grouped(rng, lengths, n_pages, heads, kv_heads, hd, dtype, padded=0,
             layers=2):
    """Pools of ``dtype`` rows of ``kv_heads * hd``, shuffled tables (every
    page of a row live: a ring is allocated whole), the last ``padded``
    rows a padded row's (length 1, table of noughts), and queries."""
    dim = kv_heads * hd
    live = [n_pages] * (len(lengths) - padded)
    blocks = n_pages * len(live) + 2
    kpool, vpool = (jnp.asarray(rng.standard_normal(
        (layers, (blocks + 1) * BS, dim)), dtype) for _ in range(2))
    tables = np.zeros((len(lengths), n_pages), np.int32)
    tables[:len(live)] = _tables(rng, [n * BS for n in live], n_pages,
                                 blocks, BS)
    q = jnp.asarray(rng.standard_normal((len(lengths), heads * hd)),
                    jnp.float32)
    return q, kpool, vpool, jnp.asarray(tables), \
        jnp.asarray(lengths, jnp.int32)


def _against_the_gather(lengths, n_pages, heads, kv_heads, hd, dtype,
                        padded=0, ring=None, seed=0, fetch_plan=False):
    rng = np.random.default_rng(seed)
    q, kpool, vpool, tables, lens = _grouped(
        rng, lengths, n_pages, heads, kv_heads, hd, dtype, padded)
    got = pallas_ops.paged_decode_attention(
        q, kpool, vpool, jnp.int32(1), tables, lens, n_heads=heads,
        n_kv_heads=kv_heads, block_size=BS, interpret=True, ring=ring,
        fetch_plan=fetch_plan)
    want = gather_decode_attention(q, kpool, vpool, 1, tables, lens, heads,
                                   BS, kv_heads, ring)
    n = len(lengths) - padded     # a padded row's output is unread
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


BF = jnp.bfloat16
GROUPED_CASES = {
    # lengths (the last `padded` rows padded), table pages, heads, key/value
    # heads, head size, pool dtype, padded rows. Page edges at 16, step
    # edges at 128
    "128_over_8_page_and_step_edges": (
        [1, 16, 17, 127, 129, 512], 32, 128, 8, 128, BF, 0),
    "128_over_8_padded_rows": ([300, 33, 1, 1], 32, 128, 8, 128, BF, 2),
    "4_over_2_page_edges": ([1, 15, 16, 17, 40, 64], 4, 4, 2, 16,
                            jnp.float32, 0),
    "4_over_2_padded_rows": ([9, 64, 1, 1, 1], 4, 4, 2, 16, BF, 3),
}


# the grid walks every column of the table, or the rows' live steps alone
WALKS = pytest.mark.parametrize("fetch_plan", [False, True],
                                ids=["grid", "plan"])


@WALKS
@pytest.mark.parametrize("name", sorted(GROUPED_CASES))
def test_grouped_heads_equal_the_gather_and_the_grouped_einsum(name,
                                                               fetch_plan):
    lengths, n_pages, heads, kv_heads, hd, dtype, padded = \
        GROUPED_CASES[name]
    _against_the_gather(lengths, n_pages, heads, kv_heads, hd, dtype,
                        padded, fetch_plan=fetch_plan)


RING_CASES = {
    # positions, ring pages (the table), (ring rows, window), heads,
    # key/value heads, head size, pool dtype
    "wrapped": ([64, 70, 100, 127, 200, 1000], 4, (64, 48), 4, 2, 16,
                jnp.float32),
    "not_wrapped": ([0, 5, 47, 48, 63], 4, (64, 48), 4, 2, 16, jnp.float32),
    "ring_rows_over_window": ([10, 16, 17, 63, 64, 65, 150], 4, (64, 16),
                              4, 2, 16, BF),
    # Command A+'s widths, a ring of 10 pages: the last of two steps ragged
    "two_steps_the_last_ragged": ([100, 127, 159, 160, 287, 375], 10,
                                  (160, 128), 128, 8, 128, BF),
    # a window far narrower than the ring: one of the two steps holds no
    # live row (the first, the second, neither)
    "a_step_with_no_live_row": ([484, 375, 405, 63], 16, (256, 64),
                                128, 8, 128, BF),
    # a context bucket under the ring: the table holds the rows the
    # positions reach
    "table_narrower_than_the_ring": ([3, 33, 63], 4, (128, 96), 4, 2, 16,
                                     jnp.float32),
}


@WALKS
@pytest.mark.parametrize("name", sorted(RING_CASES))
def test_a_ring_equals_the_gather_masked_to_the_window(name, fetch_plan):
    pos, n_pages, ring, heads, kv_heads, hd, dtype = RING_CASES[name]
    _against_the_gather([p + 1 for p in pos], n_pages, heads, kv_heads, hd,
                        dtype, ring=ring, seed=len(name),
                        fetch_plan=fetch_plan)


def _paged_call(heads, kv_heads, hd, dtype, rows, n_pages, ring=None,
                fetch_plan=False):
    """The kernel's pallas_call as traced for the chip (nothing runs)."""
    S = jax.ShapeDtypeStruct
    pool = S((2, 64 * BS, kv_heads * hd), dtype)
    jaxpr = jax.make_jaxpr(lambda q, k, v, t, n: (
        pallas_ops.paged_decode_attention(
            q, k, v, jnp.int32(0), t, n, n_heads=heads, n_kv_heads=kv_heads,
            block_size=BS, interpret=False, ring=ring,
            fetch_plan=fetch_plan)))(
        S((rows, heads * hd), jnp.float32), pool, pool,
        S((rows, n_pages), jnp.int32), S((rows,), jnp.int32))
    (call,) = [e for e in _eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    return call.params["grid_mapping"]


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("heads,kv_heads,hd,dtype", [
    (32, 16, 128, jnp.float32),         # rows of 8 KiB (the toy's width)
    (128, 8, 128, BF),                  # Command A+'s rows of 2 KiB
], ids=["rows_of_8_kib", "rows_of_2_kib"])
def test_a_grid_step_takes_128_positions_whatever_the_rows_width(
        heads, kv_heads, hd, dtype):
    """A step takes 128 positions (8 pages) at any row width: the grid
    walks a 64-page table in 8 steps, and rows that end on either side of
    a step's edge, or two steps on, equal the gather's."""
    mapping = _paged_call(heads, kv_heads, hd, dtype, 4, 64)
    assert tuple(mapping.grid) == (4, 8)
    _against_the_gather([1, 127, 129, 391], 32, heads, kv_heads, hd, dtype,
                        seed=heads)


def test_a_fetch_plan_walks_the_live_steps_of_a_wide_table():
    """With ``fetch_plan`` the grid is ONE dimension whose size is an
    operand (the rows' live steps, summed on the device), beside the table
    the plan's row and chunk of each step: a table of 512 pages (Command
    A+'s served context) costs a short row its own steps, and rows of 1 to
    5 steps, a padded row among them, over a table of 40 pages equal the
    gather's."""
    from jax._src.pallas import core as pallas_core

    mapping = _paged_call(128, 8, 128, BF, 8, 512, fetch_plan=True)
    assert len(mapping.grid) == 1
    assert mapping.grid[0] is pallas_core.dynamic_grid_dim
    assert mapping.num_dynamic_grid_bounds == 1
    assert mapping.num_index_operands == 5
    _against_the_gather([1, 129, 640, 100, 1], 40, 4, 2, 16, jnp.float32,
                        padded=1, fetch_plan=True, seed=9)


def test_kernel_takes_a_table_whose_last_chunk_is_ragged():
    """A ring of 257 pages (Command A+'s: ceil(4096 / 16) + 1) walked 8
    pages a step: 33 steps, the last of ONE page; compiled nowhere."""
    mapping = _paged_call(128, 8, 128, BF, 8, 257, ring=(4112, 4096))
    assert tuple(mapping.grid) == (8, 33)
    # and at a width that runs here: 12 pages at 8 a step
    rng = np.random.default_rng(3)
    lengths = [1, 8 * BS, 8 * BS + 1, 12 * BS]
    q, kpool, vpool, tables, lens = _grouped(
        rng, lengths, 12, 16, 16, 128, jnp.float32)
    assert tuple(_paged_call(16, 16, 128, jnp.float32, 4, 12).grid) == (4, 2)
    got = pallas_ops.paged_decode_attention(
        q, kpool, vpool, jnp.int32(1), tables, lens, n_heads=16,
        block_size=BS, interpret=True)
    want = _oracle(q, kpool, vpool, 1, np.asarray(tables), lengths, 16, BS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_a_key_value_head_a_query_head_is_the_toys_kernel():
    """``n_kv_heads == n_heads`` is the call the toy model makes, bit for
    bit; at ``chat-steady``'s widths (16 heads of 128, float32 rows of
    2048, tables of 128 pages) a step takes 8 pages (128 positions), the
    row as one block (1, 2048) of block-diagonal heads."""
    name = "ragged_1_bs-1_bs_bs+1_full"
    lengths, n_pages, n_heads, hd, bs = KERNEL_CASES[name]
    rng = np.random.default_rng(5)
    blocks = sum(-(-n // bs) for n in lengths) + 1
    kpool, vpool = _pools(rng, 2, blocks, bs, n_heads * hd)
    tables = jnp.asarray(_tables(rng, lengths, n_pages, blocks, bs))
    q = jnp.asarray(rng.standard_normal((len(lengths), n_heads * hd)),
                    jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    toy, same = (np.asarray(pallas_ops.paged_decode_attention(
        q, kpool, vpool, jnp.int32(1), tables, lens, n_heads=n_heads,
        block_size=bs, interpret=True, **kw))
        for kw in ({}, {"n_kv_heads": n_heads}))
    np.testing.assert_array_equal(toy, same)
    mapping = _paged_call(16, 16, 128, jnp.float32, 8, 128)
    assert tuple(mapping.grid) == (8, 16)
    blocks = [tuple(getattr(d, "block_size", d) for d in m.block_shape)
              for m in mapping.block_mappings]
    assert blocks[0] == blocks[-1] == (1, 1, 2048)
    assert len(blocks) == 2 + 2 * 8


# ----------------------------------------------- the decode program, paged
CFG = dict(vocab=128, d_model=64, n_heads=4, n_layers=2, max_context=512,
           seed=2)


def _force(model, paged):
    """Put the kernel into this instance's decode programs (the class
    answers from the backend: False here), and make attention matter: the
    toy's tied head otherwise answers the token it was given, whatever
    the context says."""
    if paged:
        model._decode_paged = lambda b_bucket, n_pages: True
    for l in range(model.config.n_layers):
        model._params[f"wo{l}"] = model._params[f"wo{l}"] * 16.0


def _stack(paged, blocks=96):
    cfg = ModelConfig(**CFG)
    kv = PagedKVCache(KVCacheConfig(block_size=BS, num_blocks=blocks),
                      cfg.n_layers, cfg.kv_dim)
    model = TinyTransformer(cfg, kv)
    _force(model, paged)
    return cfg, kv, model


def _close(model, kv):
    """Leave nothing behind for the tests that read the process-wide
    gauges over every live cache (``_fleet_skew``)."""
    for seq in list(kv.live_sequences()):
        kv.free_sequence(seq)
    model.close()
    kv.close()


def _greedy(model, kv, prompt, n_new, seq=1):
    """Prefill and n_new - 1 decode steps of one sequence."""
    table = kv.alloc_sequence(seq, len(prompt))
    out = [model.prefill(prompt, table)]
    for _ in range(n_new - 1):
        pos = len(prompt) + len(out) - 1
        table = kv.extend_sequence(seq, pos + 1)
        nxt = model.decode_step(np.asarray([out[-1]], np.int32),
                                np.asarray([pos], np.int32), [table])
        out.append(int(nxt[0]))
    return out


def test_path_is_chosen_by_the_backend_and_the_tables_size(monkeypatch):
    cfg, kv, model = _stack(paged=False)
    assert model._decode_paged(8, 64) is False          # the CPU substrate
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    assert model._decode_paged(8, 64) is True
    assert model._decode_paged(16, 128) is True
    rows = pallas_ops.PAGED_TABLE_BYTES // (128 * 4)
    assert model._decode_paged(rows, 128) is True
    assert model._decode_paged(2 * rows, 128) is False  # past the SMEM
    _close(model, kv)


def test_greedy_tokens_equal_on_both_paths_over_a_64_token_answer():
    answers, pools = [], []
    for paged in (False, True):
        cfg, kv, model = _stack(paged)
        prompt = np.random.default_rng(2).integers(
            1, cfg.vocab, size=37, dtype=np.int32)
        answers.append(_greedy(model, kv, prompt, 64))
        pools.append((np.asarray(kv.k_pool), np.asarray(kv.v_pool)))
        c = model.decode_counters
        assert c["decode_launches_paged"] == (63 if paged else 0)
        assert c["decode_launches_gather"] == (0 if paged else 63)
        # one row whose length grows 38 .. 100 under a table padded to
        # a power of two of pages (4, then 8), two rows a bucket
        live = sum(-(-(n + 1) // BS) for n in range(37, 100))
        assert c["decode_pages_live"] == live
        assert c["decode_pages_bucket"] == sum(
            2 * max(2, model_mod._next_pow2(-(-(n + 1) // BS)))
            for n in range(37, 100))
        # two context buckets (4 and 8 pages): the gather body compiles a
        # program for each, the kernel's widened tables share one
        assert len(model._decode_cache) == 2
        assert len(model._decode_fns) == (1 if paged else 2)
        _close(model, kv)
    assert answers[0] == answers[1]
    assert len(set(answers[0])) > 4, "an answer of one token proves little"
    for (k0, v0), (k1, v1) in zip(pools, pools[1:]):
        np.testing.assert_allclose(k0, k1, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(v0, v1, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", ["verify_step", "prefill_suffix"])
def test_rows_sharing_a_table_see_same_launch_writes(shape):
    """k+1 rows of ONE sequence in one launch: each row's K/V is written
    before any row's attention, so row j reads rows 0..j-1's same-launch
    writes and the launch gives the tokens of sequential steps."""
    cfg, kv, model = _stack(paged=True)
    prompt = np.random.default_rng(2).integers(1, cfg.vocab, size=29,
                                               dtype=np.int32)
    want = _greedy(model, kv, prompt, 9, seq=1)
    if shape == "verify_step":
        # a second sequence: prefill, one decode step, then verify the
        # sequential answer's next 6 tokens as drafts in ONE launch
        table = kv.alloc_sequence(2, len(prompt))
        first = model.prefill(prompt, table)
        assert first == want[0]
        drafts = want[1:7]
        table = kv.extend_sequence(2, len(prompt) + len(drafts) + 1)
        got = model.verify_step([first], [len(prompt)], [table], [drafts])
        assert list(got[0]) == want[1:8]
    else:
        # a second sequence prefilled cold, then its last 13 positions
        # again as suffix rows over the first 16: 13 rows share the table
        table = kv.alloc_sequence(2, len(prompt))
        assert model.prefill(prompt, table) == want[0]
        pos = np.arange(len(prompt))
        slots = np.asarray(table)[pos // BS] * BS + pos % BS
        cold = np.asarray(kv.k_pool)[:, slots], np.asarray(kv.v_pool)[:, slots]
        assert model.prefill_suffix(prompt, table, BS) == want[0]
        np.testing.assert_allclose(np.asarray(kv.k_pool)[:, slots], cold[0],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(kv.v_pool)[:, slots], cold[1],
                                   rtol=1e-4, atol=1e-5)
    assert model.decode_counters["decode_launches_gather"] == 0
    _close(model, kv)


def test_mixed_batch_with_padded_rows_equals_the_gather_path():
    """Three sequences of unlike lengths in a 4-row bucket: the padded
    row reads one position of scratch block 0 and writes there."""
    outs = []
    for paged in (False, True):
        cfg, kv, model = _stack(paged)
        rng = np.random.default_rng(3)
        lens = [5, 47, 130]
        tables, last = [], []
        for i, n in enumerate(lens):
            t = kv.alloc_sequence(10 + i, n)
            last.append(model.prefill(
                rng.integers(1, cfg.vocab, size=n, dtype=np.int32), t))
            tables.append(t)
        steps = []
        for step in range(20):
            pos = [n + step for n in lens]
            tables = [kv.extend_sequence(10 + i, p + 1)
                      for i, p in enumerate(pos)]
            last = list(model.decode_step(np.asarray(last, np.int32),
                                          np.asarray(pos, np.int32),
                                          tables))
            steps.append(last)
        outs.append(steps)
        _close(model, kv)
    assert outs[0] == outs[1]


def test_mesh_model_takes_the_kernel_inside_shard_map():
    """dp=2 groups, each a pool slice of its own: the shard_map body is
    the single-device body, kernel included."""
    cfg = ModelConfig(vocab=128, d_model=32, n_heads=2, n_layers=2)
    prompts = [np.arange(1, 20, dtype=np.int32),
               np.arange(3, 60, dtype=np.int32),
               np.arange(7, 16, dtype=np.int32)]
    outs = []
    for paged in (False, True):
        kv = ShardedKVCache(KVCacheConfig(block_size=BS, num_blocks=64),
                            cfg.n_layers, cfg.kv_dim)
        model = MeshTransformer(cfg, kv)
        _force(model, paged)
        last, tables = [], []
        for i, p in enumerate(prompts):
            t = kv.alloc_sequence(100 + i, len(p))
            last.append(model.prefill(p, t))
            tables.append(t)
        assert len({t.shard for t in tables}) == 2, "one dp group only"
        steps = []
        for step in range(6):
            pos = [len(p) + step for p in prompts]
            tables = [kv.extend_sequence(100 + i, n + 1)
                      for i, n in enumerate(pos)]
            last = list(model.decode_step(np.asarray(last, np.int32),
                                          np.asarray(pos, np.int32),
                                          tables))
            steps.append(last)
        outs.append(steps)
        c = model.decode_counters
        assert c["decode_launches_paged"] == (6 if paged else 0)
        # both dp groups run a bucket of 4 rows x 4 pages, 6 times
        assert c["decode_pages_bucket"] == 6 * 2 * 4 * 4
        _close(model, kv)
    assert outs[0] == outs[1]


# ------------------------------------------------------------ the counters
def test_counters_reach_the_snapshot_and_the_serving_page():
    from brpc_tpu.builtin.services import serving_service

    cfg, kv, model = _stack(paged=True)
    engine = ServingEngine(model, kv, EngineConfig(
        max_batch=4, token_budget=256, idle_wait_s=0.002)).start()
    try:
        evs = []
        for plen, n_new in [(20, 12), (45, 6)]:
            ev = threading.Event()
            code, _ = engine.submit(model.synth_prompt(plen), n_new,
                                    done=lambda _r, ev=ev: ev.set())
            assert code == 0
            evs.append(ev)
        for ev in evs:
            assert ev.wait(300)
        dec = engine.snapshot()["decode"]
        assert dec["decode_launches_paged"] > 0
        assert dec["decode_launches_gather"] == 0
        assert 0 < dec["decode_pages_live"] <= dec["decode_pages_bucket"]
        assert dec["live_share"] == round(
            dec["decode_pages_live"] / dec["decode_pages_bucket"], 4)
        status, _ctype, text = serving_service(
            None, types.SimpleNamespace(query={}, path="/serving"))
        assert status == 200
        line = [l for l in text.splitlines() if "decode: launches" in l]
        assert line and f"paged={dec['decode_launches_paged']}" in line[-1]
        assert "gather=0" in line[-1]
    finally:
        engine.stop()
    kv.assert_idle()
    _close(model, kv)


def test_a_model_without_the_counters_has_no_decode_line():
    engine = ServingEngine.__new__(ServingEngine)
    engine.model = types.SimpleNamespace()
    assert engine._counters()["decode"] is None
    assert engine.counter_lines(engine._counters()) == []


# -------------------------------------- compiled for the chip, no chip here
_COMPILE_SHAPES = [(8, 64), (16, 64), (16, 128), (2, 2)]

_COMPILE_SCRIPT = r"""
import functools, json, sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from brpc_tpu.tpu import pallas_ops

try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print(json.dumps({"skip": str(e)[:300]}))
    sys.exit(0)
one_chip = SingleDeviceSharding(topo.devices[0])
d, layers, blocks, bs = 2048, 12, 2048, 16


def S(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def f(q, kpool, vpool, layer, tables, lengths):
    return pallas_ops.paged_decode_attention(
        q, kpool, vpool, layer, tables, lengths, n_heads=16,
        block_size=bs, interpret=False)


out = {}
pool = S((layers, (blocks + 1) * bs, d))
for rows, pages in json.loads(sys.argv[1]):
    try:
        c = jax.jit(f).lower(
            S((rows, d)), pool, pool, S((), jnp.int32),
            S((rows, pages), jnp.int32), S((rows,), jnp.int32)).compile()
        out[f"{rows}x{pages}"] = {
            "custom_call": "tpu_custom_call" in c.as_text(),
            "temp": c.memory_analysis().temp_size_in_bytes}
    except Exception as e:
        out[f"{rows}x{pages}"] = {"error": str(e)[:500]}

# the toy's kernel as `chat-steady` serves it (8 rows, tables of 128 pages):
# the Mosaic module's text without debug locations, by sha256
import base64, hashlib, re
from jax._src.lib import tpu  # registers the Mosaic dialect
from jaxlib.mlir import ir

hlo = jax.jit(f).lower(S((8, d)), pool, pool, S((), jnp.int32),
                       S((8, 128), jnp.int32), S((8,), jnp.int32)).as_text()
config = json.loads(re.findall(r'backend_config = "([^"]*)"',
                               hlo)[0].replace("\\22", '"'))
with ir.Context() as ctx:
    ctx.allow_unregistered_dialects = True
    module = ir.Module.parse(base64.b64decode(
        config["custom_call_config"]["body"]))
    out["toy_kernel"] = hashlib.sha256(module.operation.get_asm(
        enable_debug_info=False).encode()).hexdigest()


# the grouped matmul of the expert layer (serving/moe_model.py) at the
# sparse-expert cell's widths: (rows, tile, K, N) over 16 held experts
def g(x, w, tile_expert, used, tile):
    return pallas_ops.moe_grouped_matmul(x, w, tile_expert, used,
                                         block_rows=tile, interpret=False)


for rows, tile, k, n in json.loads(sys.argv[2]):
    try:
        c = jax.jit(g, static_argnums=4).lower(
            S((rows, k), jnp.bfloat16), S((16, k, n), jnp.bfloat16),
            S((rows // tile,), jnp.int32), S((), jnp.int32), tile).compile()
        out[f"gmm{rows}x{tile}x{k}x{n}"] = {
            "custom_call": "tpu_custom_call" in c.as_text(),
            "temp": c.memory_analysis().temp_size_in_bytes}
    except Exception as e:
        out[f"gmm{rows}x{tile}x{k}x{n}"] = {"error": str(e)[:500]}


# the selective scan of a prefill launch (serving/hybrid_model.py) at the
# hybrid cells' widths: (rows, d_inner, d_state)
for rows, di, n in json.loads(sys.argv[3]):
    try:
        c = jax.jit(functools.partial(
            pallas_ops.ssm_scan, interpret=False)).lower(
            S((rows, di)), S((rows, di)), S((rows, n)), S((rows, n)),
            S((n, di)), S((n, di))).compile()
        out[f"scan{rows}x{di}x{n}"] = {
            "custom_call": "tpu_custom_call" in c.as_text(),
            "temp": c.memory_analysis().temp_size_in_bytes}
    except Exception as e:
        out[f"scan{rows}x{di}x{n}"] = {"error": str(e)[:500]}


# the absorbed decode over latent pages (serving/glm_model.py) at the
# latent cell's widths: 20 heads over rows of 576 values allocated at 640,
# 24 layers x 12288 blocks of 16 bfloat16 rows; (rows, table pages)
def m(q, pool, layer, tables, lengths):
    return pallas_ops.mla_paged_decode(
        q, pool, layer, tables, lengths, block_size=bs, d_v=512,
        scale=1.0 / 16, interpret=False)


latent = S((24, (12288 + 1) * bs, 640), jnp.bfloat16)
for rows, pages in json.loads(sys.argv[4]):
    try:
        c = jax.jit(m).lower(
            S((rows, 20, 640)), latent, S((), jnp.int32),
            S((rows, pages), jnp.int32), S((rows,), jnp.int32)).compile()
        out[f"mla{rows}x{pages}"] = {
            "custom_call": "tpu_custom_call" in c.as_text(),
            "temp": c.memory_analysis().temp_size_in_bytes}
    except Exception as e:
        out[f"mla{rows}x{pages}"] = {"error": str(e)[:500]}


# a later prefill chunk over latent rows (serving/glm_model.py) at the
# latent cell's widths: 20 heads of 256 (192 + 64 rotary), latent 512, rows
# allocated at 640, bfloat16; (chunk rows, context rows)
def mc(q, ctx, wuk, wuv, start, length):
    return pallas_ops.mla_chunk_attention(q, ctx, wuk, wuv, start, length,
                                          interpret=False)


bf = jnp.bfloat16
for rows, ctx_rows in json.loads(sys.argv[5]):
    try:
        c = jax.jit(mc).lower(
            S((rows, 20 * 256), bf), S((ctx_rows, 640), bf),
            S((20, 512, 192), bf), S((20, 512, 256), bf),
            S((), jnp.int32), S((), jnp.int32)).compile()
        out[f"mlachunk{rows}x{ctx_rows}"] = {
            "custom_call": "tpu_custom_call" in c.as_text(),
            "temp": c.memory_analysis().temp_size_in_bytes}
    except Exception as e:
        out[f"mlachunk{rows}x{ctx_rows}"] = {"error": str(e)[:500]}


# decode attention over grouped heads (serving/moe_model.py) at the
# sparse-expert cell's widths: 128 query heads over 8 of 128, bfloat16
# pools of the full layer (16384 blocks) and of three window layers' rings
# (48 of 257 blocks); (rows, table pages, ring rows or 0)
full = S((1, (16384 + 1) * bs, 1024), bf)
rings = S((3, (48 * 257 + 1) * bs, 1024), bf)
for rows, pages, ring_rows in json.loads(sys.argv[6]):
    ring = (ring_rows, 4096) if ring_rows else None
    pool = rings if ring else full

    def gq(q, kpool, vpool, layer, tables, lengths, ring=ring):
        return pallas_ops.paged_decode_attention(
            q, kpool, vpool, layer, tables, lengths, n_heads=128,
            n_kv_heads=8, block_size=bs, interpret=False, ring=ring,
            fetch_plan=True)

    try:
        c = jax.jit(gq).lower(
            S((rows, 128 * 128)), pool, pool, S((), jnp.int32),
            S((rows, pages), jnp.int32), S((rows,), jnp.int32)).compile()
        out[f"gqa{rows}x{pages}x{ring_rows}"] = {
            "custom_call": "tpu_custom_call" in c.as_text(),
            "temp": c.memory_analysis().temp_size_in_bytes}
    except Exception as e:
        out[f"gqa{rows}x{pages}x{ring_rows}"] = {"error": str(e)[:500]}
print(json.dumps(out))
"""
# decode gate-and-up and down at 32 rows (8 x 32 pairs + 16 x 15 pads),
# prefill's gate-and-up over a chunk of 1024 rows
_GMM_SHAPES = [(496, 16, 4096, 8192), (496, 16, 4096, 4096),
               (10240, 128, 4096, 8192)]
# a chunk of `longdoc-steady` and its smallest last chunk; of `reason-steady`'s
# prefill buckets the smallest, the window-sized, one whose rows do not
# divide by a power of two past 512 and the longest
_SCAN_SHAPES = [(2048, 5120, 16), (128, 5120, 16), (16, 5120, 16),
                (512, 5120, 16), (2560, 5120, 16), (3072, 5120, 16)]


# a step of `agent-steady` and its widest: rows x pages of the block table
_MLA_SHAPES = [(16, 512), (32, 2048)]
# later chunks of `agent-steady`: the smallest bucket, and a full chunk at
# the narrowest and the widest context
_MLA_CHUNK_SHAPES = [(512, 4096), (2048, 4096), (2048, 32768)]
# steps of `rag-steady`: the full layer at the widest context bucket, and a
# window layer's ring (257 pages: the last step ragged) and a short bucket's
_GQA_SHAPES = [(8, 512, 0), (32, 512, 0), (8, 257, 4112), (8, 64, 4112)]


@pytest.fixture(scope="module")
def compiled_for_v5e():
    """The kernel compiled for a described (not attached) v5e at every
    shape below, in ONE child process: the TPU's library is loaded there
    and gone when it exits, so the worker that runs this file (and goes
    on to other files' servers and forks) never holds it."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _COMPILE_SCRIPT,
         json.dumps(_COMPILE_SHAPES), json.dumps(_GMM_SHAPES),
         json.dumps(_SCAN_SHAPES), json.dumps(_MLA_SHAPES),
         json.dumps(_MLA_CHUNK_SHAPES), json.dumps(_GQA_SHAPES)],
        env=env, capture_output=True, text=True, timeout=600)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert proc.returncode == 0 and lines, proc.stderr[-2000:]
    out = json.loads(lines[-1])
    if "skip" in out:
        pytest.skip(f"no v5e:2x2 topology can be described here: "
                    f"{out['skip']}")
    return out


@pytest.mark.parametrize("rows,pages", _COMPILE_SHAPES)
def test_kernel_compiles_for_the_v5e_at_the_cells_shapes(compiled_for_v5e,
                                                         rows, pages):
    """Mosaic accepts the kernel at d_model 2048, 16 heads, pools of 2048
    blocks x 12 layers (shapes only: nothing runs), and XLA hands it the
    pools without a copy: no temporary near a pool's size."""
    got = compiled_for_v5e[f"{rows}x{pages}"]
    assert "error" not in got, got
    assert got["custom_call"]
    assert got["temp"] < 64 << 20


# the toy's kernel lowered before it took grouped heads, rings and a fetch
# plan (the parent of the change that added them, at this JAX and libtpu)
TOY_KERNEL_SHA256 = \
    "16c84137238cb7707a3ea8657b7e71f487fbc969f16ae41be9e4e2a7ea1b8cf4"


def test_the_toys_kernel_is_the_mosaic_module_it_was(compiled_for_v5e):
    """``chat-steady``'s call (one key/value head a query head, no ring, a
    grid over every column of tables 128 pages wide) lowers to the same
    Mosaic module, debug locations aside, as before the kernel took grouped
    heads, rings and a fetch plan."""
    assert compiled_for_v5e["toy_kernel"] == TOY_KERNEL_SHA256


@pytest.mark.parametrize("rows,tile,k,n", _GMM_SHAPES)
def test_grouped_matmul_compiles_for_the_v5e_at_the_cells_shapes(
        compiled_for_v5e, rows, tile, k, n):
    """Mosaic accepts ``moe_grouped_matmul`` at the sparse-expert cell's
    widths (bfloat16 operands, 16 held experts of 4096 x 8192 and 4096 x
    4096, tiles of 16 rows at decode and 128 in prefill), and XLA hands it
    the experts' weights without a copy."""
    got = compiled_for_v5e[f"gmm{rows}x{tile}x{k}x{n}"]
    assert "error" not in got, got
    assert got["custom_call"]
    assert got["temp"] < 64 << 20


@pytest.mark.parametrize("rows,di,n", _SCAN_SHAPES)
def test_ssm_scan_compiles_for_the_v5e_at_the_cells_shapes(
        compiled_for_v5e, rows, di, n):
    """Mosaic accepts ``ssm_scan`` at the hybrid cells' widths (d_inner
    5120, d_state 16, float32) for a whole chunk, the smallest last chunk
    and SambaY's prefill buckets; what XLA adds around it is the layout
    change of dt, u and y, never a (rows, d_state, d_inner) tensor."""
    got = compiled_for_v5e[f"scan{rows}x{di}x{n}"]
    assert "error" not in got, got
    assert got["custom_call"]
    assert got["temp"] < 4 * rows * di * 4


@pytest.mark.parametrize("rows,pages", _MLA_SHAPES)
def test_mla_paged_decode_compiles_for_the_v5e_at_the_cells_shapes(
        compiled_for_v5e, rows, pages):
    """Mosaic accepts ``mla_paged_decode`` at the latent cell's widths (20
    heads, rows allocated at 640 values of which 576 are held, 24 layers x
    12288 blocks of 16 bfloat16 rows: its own copies out of HBM take whole
    lane tiles), and XLA hands it the 6 GB pool without a copy."""
    got = compiled_for_v5e[f"mla{rows}x{pages}"]
    assert "error" not in got, got
    assert got["custom_call"]
    assert got["temp"] < 64 << 20


@pytest.mark.parametrize("rows,ctx_rows", _MLA_CHUNK_SHAPES)
def test_mla_chunk_attention_compiles_for_the_v5e_at_the_cells_shapes(
        compiled_for_v5e, rows, ctx_rows):
    """Mosaic accepts ``mla_chunk_attention`` at the latent cell's widths
    (20 heads of 256, latent 512 in rows allocated at 640, bfloat16) within
    its VMEM, and XLA builds no per-head K or V around it: no temporary
    near one head's keys over the context."""
    got = compiled_for_v5e[f"mlachunk{rows}x{ctx_rows}"]
    assert "error" not in got, got
    assert got["custom_call"]
    assert got["temp"] < ctx_rows * 256 * 2


@pytest.mark.parametrize("rows,pages,ring_rows", _GQA_SHAPES)
def test_grouped_paged_decode_compiles_for_the_v5e_at_the_cells_shapes(
        compiled_for_v5e, rows, pages, ring_rows):
    """Mosaic accepts ``paged_decode_attention`` over grouped heads at the
    sparse-expert cell's widths (128 query heads over 8 of 128, bfloat16
    rows of 1024) on the full layer's pages and on a
    window layer's ring, within its VMEM, and XLA hands it the pools
    without a copy."""
    got = compiled_for_v5e[f"gqa{rows}x{pages}x{ring_rows}"]
    assert "error" not in got, got
    assert got["custom_call"]
    assert got["temp"] < 64 << 20
