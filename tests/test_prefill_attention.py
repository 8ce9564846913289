"""The toy block's prefill attention: ONE call of the folded flash forward a
layer, the rows as the projection made them (``serving/model.py``
``_prefill_attention`` -> ``pallas_ops.flash_attention_rows``). CPU,
interpreted: the numbers against the O(S^2) reference a head, the traced
program's structure, and the tile rule as a pure function of shape and
itemsize (what the chip's sweep of PR 40 chose; a time is never read here).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from brpc_tpu.serving import (KVCacheConfig, ModelConfig, PagedKVCache,  # noqa: E402
                              TinyTransformer)
from brpc_tpu.serving.model import _prefill_attention  # noqa: E402
from brpc_tpu.tpu import pallas_ops  # noqa: E402


def _packed(s, heads, hd, seed=0):
    rng = np.random.default_rng(seed + s)
    return jnp.asarray(rng.normal(size=(s, 3 * heads * hd)) * 0.5,
                       dtype=jnp.float32)


def _a_head(qkv, heads, hd, h):
    """Head ``h`` through the reference, cut out of the packed rows here."""
    q, k, v = (qkv[:, (part * heads + h) * hd:(part * heads + h + 1) * hd]
               for part in range(3))
    return pallas_ops.attention_reference(q, k, v, causal=True)


@pytest.mark.parametrize("s", [16, 64, 128, 384, 640])
@pytest.mark.parametrize("heads,hd", [(16, 128), (4, 16)])
def test_every_head_matches_the_reference(heads, hd, s):
    qkv = _packed(s, heads, hd)
    out = _prefill_attention(qkv, heads, True)
    assert out.shape == (s, heads * hd) and out.dtype == jnp.float32
    for h in range(heads):
        np.testing.assert_allclose(
            np.asarray(out[:, h * hd:(h + 1) * hd]),
            np.asarray(_a_head(qkv, heads, hd, h)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_prefill_attention(qkv, heads, False)),
        rtol=1e-5, atol=1e-5)


def test_two_tiles_a_head_and_operands_at_their_own_offsets():
    """Past the one-tile lengths the triangular grid walks (0,0), (1,0),
    (1,1) a head block; q, k and v may each lie elsewhere in their rows."""
    s, heads, hd = 2048, 2, 128
    assert pallas_ops._rows_tiles(s, heads, hd, 4, (2, 0, 4)) == (1024, 1)
    qkv = _packed(s, heads, hd, seed=3)
    k_first = jnp.concatenate([qkv[:, heads * hd:2 * heads * hd],
                               qkv[:, :heads * hd], qkv[:, 2 * heads * hd:]],
                              axis=1)
    out = pallas_ops.flash_attention_rows(k_first, k_first, k_first, heads,
                                          hd, heads_at=(2, 0, 4))
    for h in range(heads):
        np.testing.assert_allclose(
            np.asarray(out[:, h * hd:(h + 1) * hd]),
            np.asarray(_a_head(qkv, heads, hd, h)), rtol=1e-5, atol=1e-5)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("bucket", [16, 128, 256])
def test_the_prefill_program_has_one_kernel_a_layer_and_no_transpose(bucket):
    """Read off the traced program: ONE ``pallas_call`` a layer, under a
    name the benchmark's ``flash_prefill_roofline`` finds
    (``flash_attention``), handed the packed projection three times; the
    only transpose left is the tied head's ``embed.T``."""
    cfg = ModelConfig(vocab=256, d_model=64, n_heads=4, n_layers=2,
                      attn="flash")
    kv = PagedKVCache(KVCacheConfig(block_size=16, num_blocks=32),
                      cfg.n_layers, cfg.kv_dim)
    model = TinyTransformer(cfg, kv)
    try:
        jaxpr = jax.make_jaxpr(model._prefill_fn(bucket, True))(
            model._params, kv.k_pool, kv.v_pool,
            np.zeros(bucket, np.int32), np.zeros(bucket, np.int32), 5)
    finally:
        model.close()
    eqns = list(_eqns(jaxpr.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == cfg.n_layers
    for e in kernels:
        assert "flash_attention" in e.params["name"]
        assert [v.aval.shape for v in e.invars] == [
            (bucket, 3 * cfg.d_model)] * 3
    wrappers = [e for e in eqns if e.params.get("name") ==
                "flash_attention_rows" and e.primitive.name != "pallas_call"]
    assert len(wrappers) == cfg.n_layers
    for e in wrappers:    # the SAME rows three times: nothing was split
        assert len({id(v) for v in e.invars}) == 1
    for e in eqns:
        if e.primitive.name == "transpose":
            assert e.invars[0].aval.shape == (cfg.vocab, cfg.d_model), e


# what the sweep on a v5e chose (PERF.md section 6, PR 40): the whole bucket
# is ONE tile a head; heads share a step only under 512 rows
CELL_BUCKETS = list(range(384, 1537, 128))


@pytest.mark.parametrize("itemsize", [4, 2])
@pytest.mark.parametrize("s", CELL_BUCKETS)
def test_the_tile_of_each_bucket_of_the_cell(s, itemsize):
    assert len(CELL_BUCKETS) == 10
    assert pallas_ops._rows_tiles(s, 16, 128, itemsize,
                                  (0, 16, 32)) == (s, 1)


@pytest.mark.parametrize("s,itemsize,heads,want", [
    (16, 4, 16, (16, 4)), (128, 4, 16, (128, 4)), (256, 4, 16, (256, 2)),
    (128, 4, 2, (128, 2)),          # no more heads a step than there are
    (1792, 4, 16, (1792, 1)),       # the longest float32 tile that fits
    (1920, 4, 16, (640, 1)),        # float32 tiles no longer fit whole ...
    (1920, 2, 16, (1920, 1)),       # ... where bfloat16 ones still do
    (2048, 4, 16, (1024, 1)), (2048, 2, 16, (1024, 1)),
    (3968, 4, 16, (128, 4)),        # 31 x 128: no divisor between
])
def test_the_tile_follows_from_shape_and_itemsize(s, itemsize, heads, want):
    at = (0, heads, 2 * heads)
    assert pallas_ops._rows_tiles(s, heads, 128, itemsize, at) == want


@pytest.mark.parametrize("at,bn", [((0, 16, 32), 4), ((0, 4, 8), 4),
                                   ((0, 2, 4), 2), ((0, 3, 6), 1)])
def test_heads_a_step_divide_every_offset(at, bn):
    assert pallas_ops._rows_tiles(128, 16, 128, 4, at) == (128, bn)


def test_the_grouped_heads_first_call_keeps_its_program():
    """``rag-steady``'s prefill (128 query heads over 8 K/V heads of 128,
    bfloat16, 2048 rows) through ``flash_attention_mha``: the folded grid
    at tiles of 1024, one head a step, as before the rows-first call came
    (traced for the chip here, compiled nowhere)."""
    assert pallas_ops._pick_blocks(2048, 2048, None, None, False,
                                   True) == (1024, 1024)
    q = jax.ShapeDtypeStruct((1, 128, 2048, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8, 2048, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v: pallas_ops.flash_attention_mha(
        q, k, v, causal=True, interpret=False))(q, kv, kv)
    (call,) = [e for e in _eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "flash_fwd_folded"
    mapping = call.params["grid_mapping"]
    assert tuple(mapping.grid) == (128, 3)
    blocks = [tuple(getattr(d, "block_size", d) for d in m.block_shape)
              for m in mapping.block_mappings]
    assert blocks == [(1, 1024, 128)] * 4 + [(1, 1024, 1)]
