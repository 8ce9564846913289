"""The hybrid serving lane's shared module, and SambaY's model.

**The lane.** Every model over a
:class:`~brpc_tpu.serving.hybrid_cache.HybridStateCache` -- SambaY here,
Command A+ (``moe_model``), Jamba (``jamba_model``), ZAYA1 (``zaya_model``)
and GLM-4.7-Flash (``glm_model``) -- subclasses :class:`HybridServingModel`
and imports from this module alone (beside ``hybrid_cache`` and ``tpu/``):
the launch and its spans, the buckets, the staging pipeline, the expert
counters and their ``/serving`` lines, and every layer function more than
one model calls. A model writes its configuration, the layout of its stacks,
its draw recipe, its two programs and the names of its counters
(docs/serving.md "Adding an architecture").

**SambaY** is a decoder-hybrid-decoder: Mamba-1 layers, sliding-window and
full differential attention, gated memory units over ONE shared K/V
(arXiv:2507.06607; the architecture of Phi-4-mini-flash-reasoning).

Layers ``0 .. N/2 + 1`` are the self-decoder: (Mamba, window attention)
pairs, then the memory Mamba layer ``N/2`` and the one FULL attention layer
``N/2 + 1``. Layers ``N/2 + 2 .. N - 1`` are the cross-decoder: (gated memory
unit, cross attention) pairs that read layer ``N/2``'s scan output and the
full layer's K/V and keep no state of their own. Every layer is followed by a
gated SiLU MLP; LayerNorm with bias; tied head; no positional encoding.
``benchmark/blocks/sambay/reference.py`` states each equation.

Two programs over different layers:

- ``prefill``: the self-decoder over all rows of the prompt -- one scan
  kernel per Mamba layer (``ssm_scan``), window attention (the flash
  kernel while the prompt fits the window, a banded einsum past it), the full
  layer through the flash kernel -- writing the recurrent state at the prompt's
  end, the last ring of window rows and the full layer's rows; then the
  cross-decoder for the LAST row only (the architecture's linear-time
  prefill), and the head.
- ``decode_step``: ONE fused launch for the batch: per Mamba layer a conv
  tail shift and one recurrence step on the sequence's slot (``ssm_step``),
  window layers over the ring's rows, the full layer's K/V gathered ONCE and
  read by it and by every cross layer.

Float32 weights, state and pools; every matmul at the backend's default
precision; the scan's state and its elementwise recurrence stay float32.
Weights are drawn and staged matrix by matrix and held once. Greedy argmax.

Not served (refused loudly): a prompt continued from a cached prefix
(``prefill_suffix`` with ``start > 0``: nothing records the recurrent state
at the matched length, and the window rings keep no row behind a chunk) and
so speculative verification. ``HybridServingModel`` carries the chunked form
for a model without rings (``CONTINUES_PREFILL``: ``serving/jamba_model.py``).
"""

from __future__ import annotations

import functools
import math
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import numpy as np

from brpc_tpu.profiling.registry import span as _span
from brpc_tpu.profiling.registry import wait_span as _wait_span
from brpc_tpu.serving.hybrid_cache import HybridStateCache

NEG = -1e30
# rows a chunk of a prompt is a multiple of (``PREFILL_GRANULE``), and the
# smallest padded chunk: whole row blocks of the scan kernel
SCAN_CHUNK = 128
QUERY_BLOCK = 128    # query rows a step of a blocked attention


# ---------------------------------------------------------------- buckets
def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, n).bit_length() - 1)


def context_floors(max_context: int, decode: int, prefill: int):
    """A configuration's (``decode_context_floor``,
    ``prefill_context_floor``): the smallest context bucket of a decode
    program and of a later prefill chunk, the model's own floors cut to the
    largest power of two within ``max_context``."""
    cap = _pow2_floor(max_context)
    return min(cap, decode), min(cap, prefill)


class SambaYConfig:
    """Read from the published configuration's keys, plus the Mamba-1 sizes
    the family fixes by convention (``d_state``, ``d_conv``, ``expand``,
    ``dt_rank`` = ceil(hidden / 16)), so another size of the family is a
    change of data."""

    def __init__(self, hidden_size: int = 64, num_attention_heads: int = 4,
                 num_key_value_heads: int = 2, intermediate_size: int = 128,
                 sliding_window: int = 16, mb_per_layer: int = 2,
                 num_hidden_layers: int = 8, vocab_size: int = 256,
                 layer_norm_eps: float = 1e-5, d_state: int = 16,
                 d_conv: int = 4, expand: int = 2, dt_rank: int = 0,
                 max_context: int = 1024, seed: int = 0,
                 attn: str = "auto"):
        h, hkv = num_attention_heads, num_key_value_heads
        if hidden_size % h or h % 2 or hkv % 2 or h % hkv:
            raise ValueError("heads must pair up and divide the hidden size")
        if num_hidden_layers % 4 or mb_per_layer != 2:
            raise ValueError("SambaY: layers divide by 4, Mamba every 2nd")
        if sliding_window & (sliding_window - 1) or sliding_window < 16:
            raise ValueError("sliding_window must be a power of two >= 16")
        self.d_model = hidden_size
        self.n_heads, self.n_kv_heads = h, hkv
        self.d_mlp = intermediate_size
        self.window = sliding_window
        self.n_layers = num_hidden_layers
        self.vocab = vocab_size
        self.eps = layer_norm_eps
        self.d_state, self.d_conv = d_state, d_conv
        self.d_inner = expand * hidden_size
        self.dt_rank = dt_rank or -(-hidden_size // 16)
        self.max_context = max_context
        # a decode program reads the full layer's context from a window's
        # rows up (a step reads every weight, and the full layer is ONE)
        self.decode_context_floor = sliding_window
        self.seed = seed
        self.attn = attn            # as ModelConfig.attn
        # ``mamba`` | ``window`` | ``full`` | ``gmu`` | ``cross`` by layer
        half = num_hidden_layers // 2
        self.kinds = [
            ("mamba" if l <= half else "gmu") if l % 2 == 0
            else "window" if l < half
            else "full" if l == half + 1 else "cross"
            for l in range(num_hidden_layers)]

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def memory_layer(self) -> int:
        """The Mamba layer whose scan output the gated memory units read."""
        return self.n_layers // 2

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)

    def cache(self, cache_config, store=None) -> HybridStateCache:
        """The manager this model needs, sized by ``cache_config``."""
        return HybridStateCache(
            cache_config, self.kv_dim, self.count("window"),
            self.count("mamba"), self.d_inner, self.d_state, self.d_conv,
            store=store)

    def weight_specs(self):
        """(name, shape, how) in the order of the draw: ``normal`` is one
        ``standard_normal`` stream scaled by 0.5/sqrt(rows), ``lambda`` one
        scaled by 0.1; the rest are constants (the family's initialisation:
        ``A = -(1..d_state)``, a ``dt`` bias whose softplus runs 1e-3..1e-1
        over the channels, ``D`` = 1, norm weights 1, biases 0)."""
        d, di, ff, hd = self.d_model, self.d_inner, self.d_mlp, self.head_dim
        specs = [("embed", (self.vocab, d), "normal")]
        for l, kind in enumerate(self.kinds):
            p = f"l{l}."
            specs += [(p + "ln1_w", (d,), "ones"),
                      (p + "ln1_b", (d,), "zeros")]
            if kind == "mamba":
                specs += [
                    (p + "win", (d, 2 * di), "normal"),
                    (p + "conv_w", (self.d_conv, di), "normal"),
                    (p + "conv_b", (di,), "zeros"),
                    (p + "wx", (di, self.dt_rank + 2 * self.d_state),
                     "normal"),
                    (p + "wdt", (self.dt_rank, di), "normal"),
                    (p + "b_dt", (di,), "dt_bias"),
                    (p + "a_log", (self.d_state, di), "a_log"),
                    (p + "dd", (di,), "ones"),
                    (p + "wout", (di, d), "normal")]
            elif kind == "gmu":
                specs += [(p + "w1", (d, di), "normal"),
                          (p + "w2", (di, d), "normal")]
            else:
                if kind == "cross":
                    specs += [(p + "wq", (d, d), "normal"),
                              (p + "bq", (d,), "zeros")]
                else:
                    specs += [
                        (p + "wqkv", (d, d + 2 * self.kv_dim), "normal"),
                        (p + "bqkv", (d + 2 * self.kv_dim,), "zeros")]
                specs += [(p + "lam", (4, hd), "lambda"),
                          (p + "sub_w", (2 * hd,), "ones"),
                          (p + "wo", (d, d), "normal"),
                          (p + "bo", (d,), "zeros")]
            specs += [(p + "ln2_w", (d,), "ones"),
                      (p + "ln2_b", (d,), "zeros"),
                      (p + "wg", (d, ff), "normal"),
                      (p + "wu", (d, ff), "normal"),
                      (p + "wd", (ff, d), "normal")]
        return specs + [("lnf_w", (d,), "ones"), ("lnf_b", (d,), "zeros")]


def _draw(rng, shape, how: str) -> np.ndarray:
    if how in ("normal", "lambda"):
        scale = 0.1 if how == "lambda" else 0.5 / math.sqrt(shape[0])
        out = np.empty(shape, np.float32)
        flat = out.reshape(-1)
        # pieces of ONE stream: the float64 temporaries stay small
        for i in range(0, flat.size, 1 << 24):
            n = min(1 << 24, flat.size - i)
            flat[i:i + n] = rng.standard_normal(n) * scale
        return out
    if how == "ones":
        return np.ones(shape, np.float32)
    if how == "zeros":
        return np.zeros(shape, np.float32)
    if how == "a_log":
        return np.broadcast_to(
            np.log(np.arange(1, shape[0] + 1, dtype=np.float64))[:, None],
            shape).astype(np.float32)
    if how == "dt_bias":
        dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), shape[0]))
        return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
    raise ValueError(f"unknown weight recipe {how!r}")


def prefill_bucket(s: int, window: int) -> int:
    """The prefill program's padded length: powers of two (from 16) up to
    the window, then multiples of the window -- few programs for prompts of
    thousands of rows, and every length past the window splits into whole
    bands."""
    if s <= window:
        return max(16, _next_pow2(s))
    return -(-s // window) * window


def decode_buckets(n_rows: int, tables, block_size: int, floor: int):
    """The decode program's padded (rows, context): rows to 8, 16, ...;
    context to a power of two of blocks from ``floor`` rows up. A step reads
    every weight whatever its rows, so the padding costs little and few
    programs compile."""
    blocks = max(len(t) for t in tables)
    return (max(8, _next_pow2(n_rows)),
            max(floor, _next_pow2(blocks) * block_size))




# ---------------------------------------------------------------- weights
def draw_matrix(seed: int, stream: int, shape, fan_in: int) -> np.ndarray:
    """One matrix of the recipe: ``Generator(Philox(key=[seed, stream]))``,
    ``standard_normal`` float32 in row-major order, times
    ``0.5 / sqrt(fan_in)``."""
    rng = np.random.Generator(np.random.Philox(key=[seed, stream]))
    out = rng.standard_normal(shape, dtype=np.float32)
    out *= np.float32(0.5 / math.sqrt(fan_in))
    return out


def draw_array(seed: int, stream, shape, const, spread) -> np.ndarray:
    """One float32 array of the recipe: ``Generator(Philox(key=[seed,
    stream]))``'s ``standard_normal`` float32 in row-major order, times the
    spread, plus the constant; the constant alone where ``stream`` is None."""
    if stream is None:
        return np.broadcast_to(np.float32(const), shape).copy()
    rng = np.random.Generator(np.random.Philox(key=[seed, stream]))
    out = rng.standard_normal(shape, dtype=np.float32)
    out *= np.asarray(spread, np.float32)
    out += np.asarray(const, np.float32)
    return out


def _handed(weights, name: str, shape) -> np.ndarray:
    """A test's array for weight ``name``, float32 on the host, its shape
    checked."""
    got = np.asarray(weights[name], np.float32)  # tpulint: disable=no-per-token-host-sync
    if got.shape != tuple(shape):
        raise ValueError(f"weight {name}: {got.shape} != {shape}")
    return got


@functools.lru_cache(maxsize=None)
def _filler():
    import jax

    @functools.partial(jax.jit, donate_argnums=0)
    def fill(buf, part, *start):
        part = part.reshape((1,) * (buf.ndim - part.ndim) + part.shape)
        start = start + (0,) * (buf.ndim - len(start))
        return jax.lax.dynamic_update_slice(buf, part.astype(buf.dtype),
                                            start)

    return fill


def fill_stack(buf, part, *start):
    """``buf`` (donated) with ``part`` written in, cast to ``buf``'s dtype,
    at ``start``: ``part`` gains leading axes of one where it has fewer than
    ``buf``, and ``start`` zeros for the axes it leaves out."""
    return _filler()(buf, part, *start)


# ----------------------------------------------------- shared layer functions
def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def _operand_dtype():
    """What a matmul's operands are rounded to: the TPU's default precision
    rounds both to bfloat16, so they are cast (the weights ARE bfloat16:
    nothing is converted on the way in); elsewhere products are exact."""
    import jax.numpy as jnp

    from brpc_tpu.tpu.pallas_ops import _on_tpu

    return jnp.bfloat16 if _on_tpu() else jnp.float32


def _mm(a, b, spec: Optional[str] = None):
    """``a @ b`` (or ``einsum(spec, a, b)``) on rounded operands, summed in
    float32."""
    import jax.numpy as jnp

    dt = _operand_dtype()
    a, b = a.astype(dt), b.astype(dt)
    if spec is None:
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def rope(x, pos, theta: float):
    """``rope_gptj``: pairs ``(2j, 2j + 1)`` of the last axis turned by
    ``pos * theta^(-2j / hd)``, the pairs left interleaved. x (rows, heads,
    hd) float32, pos (rows,). As ``x cos + swap(x) sin`` at the full head
    width, ``swap(x)[2j] = -x[2j + 1]``, ``swap(x)[2j + 1] = x[2j]`` a
    product with the fixed signed pair-swap matrix (exact at ``highest``:
    every column has one entry, 1 or -1): no slice strides the lanes."""
    import jax
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = np.repeat(theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd), 2)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    swap = np.zeros((hd, hd), np.float32)
    even = np.arange(0, hd, 2)
    swap[even + 1, even], swap[even, even + 1] = -1.0, 1.0
    turned = jnp.matmul(x, jnp.asarray(swap),
                        precision=jax.lax.Precision.HIGHEST)
    return (x * jnp.cos(ang)[:, None, :]
            + turned * jnp.sin(ang)[:, None, :])


def route(cfg, h, w_router, live, bias=None, scale=None):
    """``sigmoid(h Wr)`` in float32 at ``highest``, the ``top_k`` largest,
    weights renormalised over them. ``bias`` (experts,): a stored selection
    bias added to the scores for the CHOICE only (the weights are the
    chosen experts' scores without it); ``scale``: a factor on the
    renormalised weights. Returns (rows, k) expert ids (published indices;
    ``-1`` for a row that is not live) and weights."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.dot(h, w_router,
                               precision=jax.lax.Precision.HIGHEST))
    if bias is None:
        top, idx = jax.lax.top_k(s, cfg.top_k)
    else:
        _, idx = jax.lax.top_k(s + bias, cfg.top_k)
        top = jnp.take_along_axis(s, idx, axis=-1)
    wts = top / jnp.sum(top, axis=-1, keepdims=True)
    return (jnp.where(live[:, None], idx, -1),
            wts if scale is None else wts * scale)


def expert_layer(cfg, h, idx, wts, wgu, wd, tile: int, first=None):
    """This chip's part of ``sum_e w_e E_e(h)``: the pairs (row, expert)
    whose expert is held here, sorted by expert, each expert's run padded
    to whole tiles of ``tile`` rows, through the grouped matmul (gate and up
    as one product, then down), and gathered back to their rows. h (R, d)
    float32; idx, wts (R, k) from :func:`route`; wgu (held, d, 2 ff), wd
    (held, ff, d). An index that is nobody's here (another chip's expert,
    an output that computes nothing, ``-1``) is left out. ``first``: where
    held expert 0 lies in ``wgu`` and ``wd`` when they stack the experts of
    many layers (layers x held, ...), so that the kernel indexes the stack
    and no layer is sliced out of it. Returns (R, d) float32 and the pairs
    held by expert (held,) int32."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.tpu import pallas_ops

    r, k = idx.shape
    n = cfg.held
    with jax.named_scope("moe_dispatch"):
        local = idx - cfg.expert_lo
        here = (idx >= 0) & (local >= 0) & (local < n)
        key = jnp.where(here, local, n).reshape(-1)          # (R k,)
        order = jnp.argsort(key, stable=True)
        cnt = jnp.sum(key[:, None] == jnp.arange(n)[None, :], axis=0,
                      dtype=jnp.int32)
        padded = -(-cnt // tile) * tile
        p_end, u_end = jnp.cumsum(padded), jnp.cumsum(cnt)
        p_start, u_start = p_end - padded, u_end - cnt
        # every pair here fits: R k pairs, and at most tile - 1 pads a run
        rows = -(-(r * k + n * (tile - 1)) // tile) * tile
        tile_expert = jnp.minimum(jnp.searchsorted(
            p_end, jnp.arange(rows // tile) * tile, side="right"), n - 1)
        # the sorted, padded rows: row p of expert e's run is its off-th pair
        p = jnp.arange(rows)
        e_of = tile_expert[p // tile]
        off = p - p_start[e_of]
        pair = order[jnp.minimum(u_start[e_of] + off, r * k - 1)]
        x = jnp.where((off < cnt[e_of])[:, None], h[pair // k], 0.0)
        # where each pair's row went: its rank in the sorted order
        rank = jnp.zeros(r * k, jnp.int32).at[order].set(
            jnp.arange(r * k, dtype=jnp.int32))
        e_pair = jnp.minimum(key, n - 1)
        dest = jnp.where(here.reshape(-1),
                         p_start[e_pair] + rank - u_start[e_pair], 0)
    with jax.named_scope("experts"):
        used = p_end[-1] // tile
        if first is not None:
            tile_expert = tile_expert + first
        gmm = functools.partial(pallas_ops.moe_grouped_matmul,
                                tile_expert=tile_expert, tiles_used=used,
                                block_rows=tile)
        gu = gmm(x.astype(_operand_dtype()), wgu)
        act = jax.nn.silu(gu[:, :cfg.d_ff]) * gu[:, cfg.d_ff:]
        y = gmm(act.astype(_operand_dtype()), wd)
    with jax.named_scope("moe_dispatch"):
        got = y[dest].reshape(r, k, -1)
        out = jnp.sum(jnp.where(here[..., None], wts[..., None] * got, 0.0),
                      axis=1)
    return out, cnt


# the ``decode`` counter family of a model whose decode attention may read
# the pages in place (``pallas_ops.paged_decode_attention``): its launches by
# attention path, the pages the rows' positions cover (what the kernel
# fetches) and the pages of the tables the launches were handed (what a
# gather of the padded context copies); counted on the host
DECODE_COUNTERS = ("decode_launches_paged", "decode_launches_gather",
                   "decode_pages_live", "decode_pages_bucket")


def decode_family(counters: Dict[str, int]) -> Dict[str, object]:
    """The ``decode`` family as ``counters()`` reports it: the counts and
    ``live_share``, the live pages over the tables' pages."""
    c = dict(counters)
    c["live_share"] = round(
        c["decode_pages_live"] / max(1, c["decode_pages_bucket"]), 4)
    return c


def decode_lines(snap) -> List[str]:
    """``/serving``'s line of the ``decode`` family in ``snap`` (an engine's
    snapshot): which path the launches took, and how much of the tables
    the rows' positions cover."""
    dec = snap.get("decode")
    if not dec:
        return []
    return [f"  decode: launches paged={dec['decode_launches_paged']} "
            f"gather={dec['decode_launches_gather']} "
            f"pages live={dec['decode_pages_live']} "
            f"bucket={dec['decode_pages_bucket']} "
            f"live_share={dec['live_share']:.2f}"]


def expert_counts(cnt, *more):
    """What a launch returns of one expert layer, from the pairs held by
    expert ``cnt``: the pairs computed here, the held experts hit and the
    most pairs of one expert (``HybridServingModel.MOE_COUNTERS``' first
    three), then ``more``."""
    import jax.numpy as jnp

    return jnp.stack([jnp.sum(cnt), jnp.sum(cnt > 0), jnp.max(cnt), *more])


def shared_experts(cfg, h, wgu, wd):
    """The mean of the shared experts' outputs: their gate and up
    projections side by side, their down projections stacked, so the sum
    over them is one product's."""
    import jax

    with jax.named_scope("shared_experts"):
        gu = _mm(h, wgu)
        half = cfg.n_shared * cfg.d_ff
        return _mm(jax.nn.silu(gu[:, :half]) * gu[:, half:], wd) \
            / cfg.n_shared


def attend_chunk_blocked(cfg, q, k, v, start):
    """Causal attention of a chunk's rows over the context: query ``i`` (row
    ``start + i``) over keys ``0 .. start + i``, a block of query rows at a
    time so that the scores held are heads x block x context. q (C, H, hd);
    k, v (L, G, hd)."""
    import jax
    import jax.numpy as jnp

    c, g = q.shape[0], k.shape[1]
    qb = min(QUERY_BLOCK, c)
    qh = q.reshape(c // qb, qb, g, cfg.n_heads // g, cfg.head_dim)
    k_pos = jnp.arange(k.shape[0])[None, :]

    def one(args):
        qc, i0 = args
        sc = _mm(qc, k, "qgjd,kgd->gjqk") / math.sqrt(cfg.head_dim)
        live = k_pos <= (start + i0 + jnp.arange(qb))[:, None]
        prob = jax.nn.softmax(jnp.where(live, sc, NEG), axis=-1)
        return _mm(prob, v, "gjqk,kgd->qgjd")

    out = jax.lax.map(one, (qh, jnp.arange(c // qb) * qb))
    return out.reshape(c, cfg.n_heads, cfg.head_dim)


def attend_chunk_flash(cfg, q, k, v, start):
    """The same through the flash carry kernel, a query head a pass (a loop
    over the heads: one kernel, the key/value head read in place), with the
    chunk's first row as the kernel's run-time offset; tiles past a query
    block's rows are skipped. Operands as :func:`_mm` rounds them."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.tpu import pallas_ops

    c, g, hd = q.shape[0], k.shape[1], cfg.head_dim
    dt = _operand_dtype()
    per = cfg.n_heads // g
    m0 = jnp.full((c, 1), pallas_ops.NEG_INF, jnp.float32)
    l0 = jnp.zeros((c, 1), jnp.float32)
    acc0 = jnp.zeros((c, hd), jnp.float32)
    kh, vh = k.astype(dt).transpose(1, 0, 2), v.astype(dt).transpose(1, 0, 2)

    def head(args):
        qh, j = args
        _m, l, acc = pallas_ops.flash_attention_carry(
            qh, kh[j // per], vh[j // per], m0, l0, acc0, start, 0,
            causal=True, block_q=min(512, c), block_k=512)
        return acc / l

    out = jax.lax.map(head, (q.astype(dt).transpose(1, 0, 2),
                             jnp.arange(cfg.n_heads)))
    return out.transpose(1, 0, 2)


def _block_context(pool, layer: int, blocks, block_size: int):
    """Rows of ONE layer of a pool read through block tables, whole blocks
    at a time (a block is ``block_size`` contiguous rows, gathered three
    times as fast as its rows one by one, measured), the layer inside the
    gather's index: ``pool[layer]`` first is a copy of the whole layer.
    ``blocks`` (..., n) -> (..., n * block_size, width), position p of a
    context at its row p."""
    layers, _, width = pool.shape
    return pool.reshape(layers, -1, block_size, width)[layer, blocks] \
        .reshape(blocks.shape[:-1] + (-1, width))


# ------------------------------------ layer functions (SambaY's; and Mamba's)
def _ln(x, w, b, eps):
    import jax
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rms(x, w, eps):
    """RMSNorm over the last axis, statistics in float32."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def _mlp(cfg, p, w, x):
    import jax

    with jax.named_scope("mlp"):
        h = _ln(x, w[p + "ln2_w"], w[p + "ln2_b"], cfg.eps)
        return x + (jax.nn.silu(h @ w[p + "wg"]) * (h @ w[p + "wu"])) \
            @ w[p + "wd"]


def ssm_scan(dt, u, bm, cm, a, s0=None):
    """``s_t = exp(dt_t a) * s_{t-1} + (dt_t u_t) b_t'`` over all rows from
    ``s0`` (zero where it is left out: a prompt's first row; a later chunk
    of the prompt gives the state its slot holds), ``y_t = s_t c_t``: ONE
    Pallas kernel a layer that keeps the state in VMEM and walks the rows
    (``pallas_ops.ssm_scan``). dt, u (S, di); bm, cm (S, n); a, s0 (n, di).
    State is (n, di): the wide axis last. Float32 throughout, no matmul; a
    row with ``dt == 0`` leaves the state as it was, which is how callers
    mask pads. Returns the last state and y."""
    from brpc_tpu.tpu import pallas_ops

    return pallas_ops.ssm_scan(dt, u, bm, cm, a, s0)


def conv_windows(u_in, tail):
    """The causal conv's input windows of a prompt's rows: ``u_in`` (S, di)
    behind ``tail`` (d_conv - 1, di), the rows before them (zeros before a
    prompt's first row; a later chunk gives the tail its slot holds).
    Returns the padded rows (d_conv - 1 + S, di) and the windows (S, d_conv,
    di), window t ending with row t."""
    import jax.numpy as jnp

    s_len, kc = u_in.shape[0], tail.shape[0] + 1
    upad = jnp.concatenate([tail, u_in])
    return upad, jnp.stack([upad[j:j + s_len] for j in range(kc)], axis=1)


def _mamba_inputs(cfg, p, w, window_u, mm=None):
    """What both programs share of a Mamba layer: from the conv's input
    windows ``window_u`` (..., d_conv, di) to (u, dt, B, C). A layer that
    has them (``dt_norm``, ``b_norm``, ``c_norm`` beside its weights) takes
    the RMS norm of each of ``dt_r``, B and C over its own width first, in
    float32. ``mm``: the caller's matmul where it rounds operands itself
    (``@`` at the backend's default precision where it is left out)."""
    import jax
    import jax.numpy as jnp

    mm = mm or jnp.matmul
    with jax.named_scope("conv"):
        u = jax.nn.silu(jnp.sum(window_u * w[p + "conv_w"], axis=-2)
                        + w[p + "conv_b"])
    dbc = mm(u, w[p + "wx"])
    r, n = cfg.dt_rank, cfg.d_state
    dt_r, bm, cm = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    if p + "dt_norm" in w:
        with jax.named_scope("inner_norms"):
            dt_r, bm, cm = (_rms(x, w[p + k], cfg.eps) for x, k in
                            ((dt_r, "dt_norm"), (bm, "b_norm"),
                             (cm, "c_norm")))
    dt = jax.nn.softplus(mm(dt_r, w[p + "wdt"]) + w[p + "b_dt"])
    return u, dt, bm, cm


def _q_heads(cfg, q):
    """q (..., d) -> (..., g, per, 2, hd): consecutive heads pair, and each
    of the g key/value pairs serves ``per`` query pairs."""
    g, per = cfg.n_kv_heads // 2, cfg.n_heads // cfg.n_kv_heads
    return q.reshape(q.shape[:-1] + (g, per, 2, cfg.head_dim))


def _kv_heads(cfg, k, v):
    """k (..., kvd) -> (..., g, 2, hd); v -> (..., g, 2 hd)."""
    g, hd = cfg.n_kv_heads // 2, cfg.head_dim
    return (k.reshape(k.shape[:-1] + (g, 2, hd)),
            v.reshape(v.shape[:-1] + (g, 2 * hd)))


def _diff_out(cfg, p, w, l, a):
    """From the two softmax maps' outputs ``a`` (..., g, per, 2, 2 hd) to the
    layer's output (..., d): ``(1 - lambda_init) RMSNorm(a1 - lambda a2)``,
    then the output projection."""
    import jax
    import jax.numpy as jnp

    lam_v = w[p + "lam"]
    li = 0.8 - 0.6 * math.exp(-0.3 * l)
    lam = (jnp.exp(jnp.sum(lam_v[0] * lam_v[1]))
           - jnp.exp(jnp.sum(lam_v[2] * lam_v[3])) + li)
    dif = a[..., 0, :] - lam * a[..., 1, :]
    o = dif * jax.lax.rsqrt(jnp.mean(jnp.square(dif), axis=-1, keepdims=True)
                            + cfg.eps)
    o = (1.0 - li) * o * w[p + "sub_w"]
    return o.reshape(o.shape[:-3] + (-1,)) @ w[p + "wo"] + w[p + "bo"]


def _attend_rows(cfg, qh, kh, vh, live):
    """One sequence: qh (Q, g, per, 2, hd) over kh (K, g, 2, hd), vh (K, g,
    2 hd) with ``live`` (Q, K). Masked einsums."""
    import jax
    import jax.numpy as jnp

    sc = jnp.einsum("qgjcd,kgcd->gjcqk", qh, kh) / math.sqrt(cfg.head_dim)
    prob = jax.nn.softmax(jnp.where(live, sc, NEG), axis=-1)
    return jnp.einsum("gjcqk,kge->qgjce", prob, vh)


def _attend_flash(cfg, qh, kh, vh):
    """Full causal attention of one sequence in ONE call of the batched
    flash forward (``pallas_ops.flash_attention_mha``), which takes v as
    wide as q: each softmax map runs on the two hd-wide halves of its v.
    The K/V heads are (pair, map, v half); every query pair of a pair reads
    its map's head in place, so only q is repeated, over the v halves."""
    import jax.numpy as jnp

    from brpc_tpu.tpu import pallas_ops

    s, g, per, _, hd = qh.shape
    heads = (g, 2, 2)                      # pair, map, v half
    q = jnp.broadcast_to(qh.transpose(1, 3, 2, 0, 4)[:, :, None],
                         heads + (per, s, hd))
    k = jnp.broadcast_to(kh.transpose(1, 2, 0, 3)[:, :, None],
                         heads + (s, hd))
    v = jnp.broadcast_to(vh.reshape(s, g, 2, hd).transpose(1, 2, 0, 3)
                         [:, None], heads + (s, hd))
    out = pallas_ops.flash_attention_mha(
        q.reshape(1, -1, s, hd), k.reshape(1, -1, s, hd),
        v.reshape(1, -1, s, hd), causal=True)
    # (g, map, v half, per, s, hd) -> (s, g, per, map, 2 hd)
    return out.reshape(heads + (per, s, hd)).transpose(4, 0, 3, 1, 2, 5) \
        .reshape(s, g, per, 2, 2 * hd)


def _attend_banded(cfg, qh, kh, vh):
    """Window attention of one sequence longer than the window: bands of
    ``window`` query rows, each over its own and the previous band's keys."""
    import jax
    import jax.numpy as jnp

    s, win = qh.shape[0], cfg.window
    nb = s // win

    def two_bands(x):      # (s, ...) -> (nb, 2 win, ...): previous + own
        pad = jnp.concatenate([jnp.zeros((win,) + x.shape[1:], x.dtype), x])
        return jnp.concatenate(
            [pad[:-win].reshape((nb, win) + x.shape[1:]),
             pad[win:].reshape((nb, win) + x.shape[1:])], axis=1)

    i = jnp.arange(win)[:, None]
    j = jnp.arange(2 * win)[None, :]
    band = (j <= win + i) & (j > i)      # key (c-1) win + j, query c win + i

    def one(args):
        qc, kc, vc, c = args
        return _attend_rows(cfg, qc, kc, vc, band & ((c > 0) | (j >= win)))

    a = jax.lax.map(one, (qh.reshape((nb, win) + qh.shape[1:]),
                          two_bands(kh), two_bands(vh), jnp.arange(nb)))
    return a.reshape((s,) + a.shape[2:])


def _attend_decode(cfg, qh, kc, vc, live):
    """One query row a sequence over its gathered context: qh (B, g, per, 2,
    hd); kc, vc (B, K, kv_dim) as :func:`_block_context` leaves them, K
    before the heads; ``live`` (B, K). Returns (B, g, per, 2, 2 hd), as
    :func:`_diff_out` takes it.

    The einsums read the context whole rows at a time, as it lies: each
    query head is spread over the row's kv_dim values, zero outside its own
    key head's hd, and each takes back the 2 hd values of its own pair's v.
    Splitting the row into heads instead makes the device relay out the
    whole context (its minor axis would be hd wide); the products of zeros
    cost MXU time a decode step has to spare, and every sum is the one over
    the head's own values."""
    import jax
    import jax.numpy as jnp

    b, g, per, _, hd = qh.shape
    own_g = jnp.eye(g, dtype=qh.dtype)
    own = (own_g[:, None, None, :, None] * jnp.eye(2, dtype=qh.dtype)
           [None, None, :, None, :])                    # (g, 1, 2, g, 2)
    qx = (qh[:, :, :, :, None, None, :] * own[None, ..., None]).reshape(
        b, g * per * 2, -1)                             # (B, heads, kv_dim)
    sc = jnp.einsum("bhe,bke->bhk", qx, kc) / math.sqrt(hd)
    prob = jax.nn.softmax(jnp.where(live[:, None, :], sc, NEG), axis=-1)
    out = jnp.einsum("bhk,bke->bhe", prob, vc).reshape(b, g, per, 2, g, -1)
    return jnp.sum(out * own_g[None, :, None, None, :, None], axis=4)


def _ring_live(pos, ring_rows: int, window: int):
    """(B, ring rows): which rows of its ring a decode row at ``pos`` reads.
    Ring row r holds the newest position p <= pos with p = r (mod ring
    rows); it is read while p lies inside the window."""
    import jax.numpy as jnp

    r = jnp.arange(ring_rows)
    held = pos[:, None] - (pos[:, None] - r[None, :]) % ring_rows
    return (held >= 0) & (held > pos[:, None] - window)


class HybridServingModel:
    """What every model over a :class:`HybridStateCache` shares: the launch
    over the manager's six device arrays, the padded host arguments, the
    ``prep`` / ``launch`` / ``sync`` spans, the buckets, the staging
    pipeline, the counters and the interface
    :class:`~brpc_tpu.serving.engine.ServingEngine` drives. A subclass
    stages ``self._params`` / ``self._handles`` and gives the two programs:

    - ``_prefill_fn(s_bucket, use_flash)`` -> jitted ``impl(w, fk, fv, wk,
      wv, ssm, conv, tokens, table, ring_table, slot, length)``
    - ``_decode_fn(b_bucket, l_bucket)`` -> jitted ``impl(w, fk, fv, wk,
      wv, ssm, conv, tokens, pos, tables, ring_tables, slots)``

    each returning the six arrays and ONE int32 array whose first entries
    are the next tokens (one for prefill, ``b_bucket`` for decode); what
    follows them, if anything, reaches ``_note_counters`` from the same
    host sync: a row of ``MOE_COUNTERS`` an expert layer.

    A model whose prefill can go on from what its slot and pages hold
    declares ``CONTINUES_PREFILL`` and gives, in place of ``_prefill_fn``,

    - ``_chunk_fn(c_bucket, l_bucket, use_flash)`` -> jitted ``impl(w, fk,
      fv, wk, wv, ssm, conv, tokens, table, slot, length, start)``: rows
      ``[start, start + length)`` of a prompt, ``c_bucket`` padded rows over
      ``l_bucket`` context rows read through ``table``

    and :class:`~brpc_tpu.serving.engine.ServingEngine` then prefills a long
    prompt a chunk a step (``prefill_suffix`` with ``start > 0``).

    The configuration gives ``decode_context_floor`` (and, for a model that
    continues a prefill, ``prefill_context_floor``): the smallest context
    bucket of each program."""

    FUSED_STEP = True     # decode_step: one launch, one host sync
    # prefill_suffix(start > 0) goes on from the slot's scan state and conv
    # tail and the rows in the pages: nothing else of the model's state
    # looks back (no window ring, whose rows behind a chunk are overwritten)
    CONTINUES_PREFILL = False
    # rows an engine's chunk of a prompt is a multiple of
    PREFILL_GRANULE = SCAN_CHUNK
    # a decode program's rows: a power of two from 8, or (True) the next
    # multiple of 8 (a step then gathers no padded row's context in vain)
    DECODE_ROWS_BY_8 = False
    # what a launch returns per expert layer after its tokens, by name
    # (``moe_counters``; none: no expert layer) ...
    MOE_COUNTERS = ()
    # ... and what the host adds up of a launched prefill program's build
    PREFILL_MOE_COUNTERS = ()

    def _init_programs(self, config, kv: HybridStateCache) -> None:
        self.config = config
        self.kv = kv
        self.store = kv.store
        self._lock = threading.Lock()
        self._prefill_cache = {}
        self._decode_cache = {}
        self._params, self._handles, self.param_nbytes = {}, [], 0
        # the prefill launches that ran the scan kernel and the rows handed
        # to it (a launch's padded rows x its Mamba layers); None without
        # Mamba layers
        self.scan_counters = ({"launches": 0, "rows": 0}
                              if config.count("mamba") else None)
        # the expert layers': ``experts_held`` and a dict a phase
        self.moe_counters = None
        if self.MOE_COUNTERS:
            self.moe_counters = {"experts_held": config.held}
            self.reset_moe_counters()

    def _stage(self, name: str, arr) -> None:
        """Hold one device array as a weight, registered with the store."""
        handle, nbytes = self.store.adopt(arr)
        self._params[name] = arr
        self._handles.append(handle)
        self.param_nbytes += nbytes

    def _drawn_layers(self, recipe, draw, weights=None):
        """The staging pipeline of a model whose arrays each have a stream
        of their own. ``recipe(layer)`` lists ``(name, *args)`` of every
        array of ``layer`` (of no layer where it is None: the embedding and
        the like), the array's shape second of ``args``; ``draw(seed,
        *args)`` draws one on the host, or ``weights[name]`` stands in for
        it (tests; its shape checked). Yields ``(layer, take)`` for None and
        then every layer in order while the NEXT one's arrays are drawn on
        four threads (numpy frees the interpreter); ``take(name)`` is the
        array on the device, float32. The caller stages ONE array at a time
        (into its stacks: :func:`fill_stack`): set-up, not a step loop."""
        import jax

        given, dev = weights or {}, self.store.device
        n, seed = self.config.n_layers, self.config.seed

        def host(name, *args):
            return (_handed(given, name, args[1]) if name in given
                    else draw(seed, *args))

        with ThreadPoolExecutor(4) as pool:
            def start(layer):
                return {a[0]: pool.submit(host, *a) for a in recipe(layer)}

            ahead = start(None)
            for layer in [None] + list(range(n)):
                drawn, nxt = ahead, 0 if layer is None else layer + 1
                ahead = start(nxt) if nxt < n else {}

                def take(name, drawn=drawn):
                    return jax.device_put(drawn.pop(name).result(), dev)  # tpulint: disable=no-per-op-step-dispatch

                yield layer, take

    def _use_flash(self) -> bool:
        if self.config.attn in ("flash", "reference"):
            return self.config.attn == "flash"
        from brpc_tpu.tpu.pallas_ops import _on_tpu

        return _on_tpu()

    def _decode_buckets(self, n_rows: int, tables):
        """The decode program's padded (rows, context): rows as
        ``DECODE_ROWS_BY_8`` says, context as :func:`decode_buckets` from
        the configuration's floor."""
        rows, ctx = decode_buckets(n_rows, tables, self.kv.block_size,
                                   self.config.decode_context_floor)
        return (-(-n_rows // 8) * 8 if self.DECODE_ROWS_BY_8 else rows, ctx)

    # ------------------------------------------------------------ counters
    def reset_moe_counters(self) -> None:
        for phase, more in (("decode", ()),
                            ("prefill", self.PREFILL_MOE_COUNTERS)):
            self.moe_counters[phase] = dict.fromkeys(
                self.MOE_COUNTERS + ("layer_launches",) + more, 0)

    def _note_counters(self, phase: str, tail) -> None:
        """``tail``: what a launch returned after its tokens (host array),
        a row of ``MOE_COUNTERS`` an expert layer, summed here."""
        if not self.MOE_COUNTERS:
            return
        c = self.moe_counters[phase]
        per_layer = np.asarray(tail, np.int64).reshape(
            -1, len(self.MOE_COUNTERS))
        c["layer_launches"] += len(per_layer)
        for name, total in zip(self.MOE_COUNTERS, per_layer.sum(axis=0)):
            c[name] += int(total)

    def counters(self) -> Dict[str, object]:
        """The counter families this model keeps, by name, for
        ``ServingEngine.snapshot()`` (which copies them), None where the
        model has no such layer. ``moe``: ``experts_held`` and, for decode
        and prefill apart, the token-expert pairs this chip computed, the
        distinct held experts hit, the most pairs of one expert (summed
        over layer-launches), the ``layer_launches`` and whatever else the
        class counts (``moe_model``: the attention layers of the launched
        prefill programs that took the flash kernel and the blocked scan;
        ``zaya_model``: the rows sent to the output that computes nothing).
        ``scan``: the prefill ``launches`` that ran the ``ssm_scan`` kernel
        and the ``rows`` handed to it (a launch's padded rows times its
        Mamba layers)."""
        return {"moe": self.moe_counters, "scan": self.scan_counters}

    def counter_lines(self, snap) -> List[str]:
        """``/serving``'s lines of this model's families in ``snap`` (an
        engine's snapshot)."""
        out = []
        # the expert layer: how loaded this chip's experts are, and the
        # share of them (so of their weights) a decode step reaches
        moe = snap.get("moe")
        if moe:
            parts = []
            for phase in ("decode", "prefill"):
                c = moe[phase]
                n = max(1, c["layer_launches"])
                parts.append(
                    f"{phase} pairs={c['pairs']} "
                    f"experts_hit={c['experts_hit']} "
                    f"layer_launches={c['layer_launches']} "
                    f"pairs_max_expert={c['pairs_max_expert']} "
                    f"(pairs/launch={c['pairs'] / n:.1f} hit_share="
                    f"{c['experts_hit'] / n / moe['experts_held']:.2f})"
                    + (f" kernel_layers={c['kernel_layers']} "
                       f"blocked_layers={c['blocked_layers']}"
                       if "kernel_layers" in c else "")
                    # rows a router sent to an output that computes nothing
                    + (f" skipped={c['skipped']}" if "skipped" in c else ""))
            out.append(f"  moe: held={moe['experts_held']} "
                       + " | ".join(parts))
        # the selective scan: prefill launches that ran the kernel, and the
        # rows (padded rows x Mamba layers) they handed it
        scan = snap.get("scan")
        if scan:
            out.append(f"  scan: launches={scan['launches']} "
                       f"rows={scan['rows']}")
        return out

    # -------------------------------------------------------------- launch
    def _launch(self, fn, writes, *args):
        """One launch over the manager's six device arrays (donated in,
        installed again as they come back). ``writes``: (table, first row,
        last row + 1) of the full-layer rows the launch writes: under an
        armed ledger every block they lie in has to be exclusively owned.
        Returns the launch's further output."""
        kv = self.kv
        for table, start, stop in writes:
            kv.full.assert_writable(table, start, stop)
        out = fn(self._params, kv.full.k_pool, kv.full.v_pool,
                 kv.window.k_pool, kv.window.v_pool, kv.ssm, kv.conv, *args)
        kv.full.update_pools(out[0], out[1])
        kv.window.update_pools(out[2], out[3])
        kv.update_state(out[4], out[5])
        return out[6]

    def _program(self, cache: dict, key, make):
        """The jitted program of ``key``, built once."""
        with self._lock:
            fn = cache.get(key)
            if fn is None:
                fn = cache[key] = make(*key)
        return fn

    def _first_token(self, fn, writes, toks, *args) -> int:
        """A prefill launch over the padded token rows ``toks`` and its one
        host sync: the token it returns first, what follows it to
        ``_note_counters``."""
        from brpc_tpu.tpu.device_lane import step_dispatch

        if self.scan_counters is not None:
            self.scan_counters["launches"] += 1
            self.scan_counters["rows"] += \
                len(toks) * self.config.count("mamba")
        with _span("model.launch"):
            step_dispatch.note_launch(1)
            nxt = self._launch(fn, writes, toks, *args)
        with _wait_span("model.sync"):
            host = np.asarray(nxt).reshape(-1)
            step_dispatch.note_host_sync()
        self._note_counters("prefill", host[1:])
        return int(host[0])

    def prefill(self, tokens: np.ndarray, table) -> int:
        """Prompt prefill for ONE sequence: write its recurrent state, its
        ring and its full-layer rows, return the first token (greedy)."""
        if self.CONTINUES_PREFILL:
            return self._prefill_rows(tokens, table, 0)
        s = len(tokens)
        bucket = prefill_bucket(s, self.config.window)
        with _span("model.prefill", n=s, bucket=bucket):
            with _span("model.prep"):
                fn = self._program(self._prefill_cache,
                                   (bucket, self._use_flash()),
                                   self._prefill_fn)
                toks = np.zeros(bucket, dtype=np.int32)
                toks[:s] = tokens
                tab = np.zeros(-(-bucket // self.kv.block_size), np.int32)
                n = min(len(tab), len(table))
                tab[:n] = table[:n]
                ring = np.asarray(table.window, np.int32)
            return self._first_token(fn, [(table, 0, s)], toks, tab, ring,
                                     np.int32(table.slot), np.int32(s))

    def prefill_suffix(self, tokens: np.ndarray, table, start: int) -> int:
        """Rows ``[start, len(tokens))`` of the prompt ``tokens``. A model
        that ``CONTINUES_PREFILL`` goes on from what the SAME sequence's
        earlier chunks left in its slot and pages (the scan from the slot's
        state, the conv from its tail, attention over rows ``[0,
        len(tokens))`` through the table); the token it returns is the
        prompt's first where ``tokens`` ends the prompt, and means nothing
        before. Any other model takes only ``start == 0`` (the whole prompt,
        as ``prefill``): a suffix needs the recurrent state at ``start`` and
        the ring rows behind it, which nothing records."""
        if self.CONTINUES_PREFILL:
            return self._prefill_rows(tokens, table, start)
        if start:
            raise NotImplementedError(
                f"{type(self).__name__}: no prefill from a cached prefix: "
                f"the recurrent state and the ring rows at row {start} are "
                "not recorded")
        return self.prefill(tokens, table)

    def _chunk_buckets(self, n: int, end: int, start: int):
        """The chunk program's padded (rows, context): rows to a power of
        two from ``SCAN_CHUNK``; a prompt's first chunk is its own context,
        a later one reads a power of two of rows from the configuration's
        ``prefill_context_floor`` up. Few programs: one chunk size serves
        every step of a long prompt but its last."""
        c = max(SCAN_CHUNK, _next_pow2(n))
        if not start:
            return c, c
        return c, max(self.config.prefill_context_floor, _next_pow2(end))

    def _prefill_rows(self, tokens: np.ndarray, table, start: int) -> int:
        """One launch over rows ``[start, len(tokens))`` of a prompt."""
        end = len(tokens)
        n = end - start
        if n < 1 or start < 0:
            raise ValueError(f"prefill of rows [{start}, {end})")
        c_bucket, l_bucket = self._chunk_buckets(n, end, start)
        with _span("model.prefill", n=n, start=start, bucket=c_bucket,
                   context=l_bucket):
            with _span("model.prep"):
                fn = self._program(
                    self._prefill_cache,
                    (c_bucket, l_bucket, self._use_flash()), self._chunk_fn)
                toks = np.zeros(c_bucket, dtype=np.int32)
                toks[:n] = tokens[start:]
                tab = np.zeros(l_bucket // self.kv.block_size, np.int32)
                k = min(len(tab), len(table))
                tab[:k] = table[:k]
            return self._first_token(fn, [(table, start, end)], toks, tab,
                                     np.int32(table.slot), np.int32(n),
                                     np.int32(start))

    def decode_step(self, tokens: np.ndarray, positions: np.ndarray,
                    tables: List[Sequence[int]]) -> np.ndarray:
        """ONE fused dispatch for the whole decode batch (one row a
        sequence): every layer's state stepped or appended, the next token
        of each sequence returned, host-materialized once."""
        from brpc_tpu.tpu.device_lane import step_dispatch

        B = len(tokens)
        kv = self.kv
        b_bucket, l_bucket = self._decode_buckets(B, tables)
        with _span("model.decode", B=B, b_bucket=b_bucket,
                   l_bucket=l_bucket):
            with _span("model.prep"):
                if len({t.slot for t in tables}) != B:
                    raise ValueError(
                        f"{type(self).__name__}.decode_step: one row a "
                        "sequence (a ring row and a recurrent state step "
                        "once a launch)")
                fn = self._program(self._decode_cache, (b_bucket, l_bucket),
                                   self._decode_fn)
                toks = np.zeros(b_bucket, dtype=np.int32)
                toks[:B] = tokens
                pos = np.zeros(b_bucket, dtype=np.int32)
                pos[:B] = positions
                tabs = np.zeros((b_bucket, l_bucket // kv.block_size),
                                np.int32)
                rings = np.zeros((b_bucket, kv.ring_blocks), np.int32)
                slots = np.zeros(b_bucket, np.int32)
                for i, t in enumerate(tables):
                    tabs[i, :len(t)] = t
                    rings[i] = t.window
                    slots[i] = t.slot
            with _span("model.launch"):
                step_dispatch.note_launch(1)
                nxt = self._launch(
                    fn, [(t, int(p), int(p) + 1)
                         for t, p in zip(tables, positions)],
                    toks, pos, tabs, rings, slots)
            with _wait_span("model.sync"):
                host = np.asarray(nxt)
                step_dispatch.note_host_sync()
            self._note_counters("decode", host[b_bucket:])
            return host[:B]

    def close(self) -> None:
        for h in self._handles:
            self.store.free(h)
        self._handles = []

    def synth_prompt(self, length: int) -> np.ndarray:
        """As ``TinyTransformer.synth_prompt``: keyed only by length."""
        v = self.config.vocab
        return ((np.arange(length, dtype=np.int64) * 31 + 7)
                % (v - 1)).astype(np.int32) + 1

class SambaYModel(HybridServingModel):
    """Weights + the prefill and decode programs over a HybridStateCache."""

    def __init__(self, config: SambaYConfig, kv: HybridStateCache,
                 weights=None):
        """``weights``: a dict of host arrays by ``config.weight_specs()``'s
        names (tests); drawn from ``config.seed`` where it is left out."""
        import jax

        self._init_programs(config, kv)
        # ---- weights: drawn, staged and registered ONE matrix at a time
        rng = np.random.RandomState(config.seed)
        for name, shape, how in config.weight_specs():
            # one matrix at a time ON PURPOSE (set-up, not a step loop): a
            # stacked transfer would hold the 8.8 GB twice
            host = (_draw(rng, shape, how) if weights is None else
                    _handed(weights, name, shape))
            self._stage(name, jax.device_put(host, self.store.device))  # tpulint: disable=no-per-op-step-dispatch

    # ------------------------------------------------------------- prefill
    def _prefill_fn(self, s_bucket: int, use_flash: bool):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        kinds = cfg.kinds
        half = cfg.n_layers // 2
        bs, ring = self.kv.block_size, self.kv.config.ring_blocks
        ring_rows = ring * bs
        scope = jax.named_scope

        def attend(qh, kh, vh, window):
            if window and s_bucket > cfg.window:
                return _attend_banded(cfg, qh, kh, vh)
            if use_flash:       # s_bucket <= window: the window cuts nothing
                return _attend_flash(cfg, qh, kh, vh)
            t = jnp.arange(s_bucket)
            return _attend_rows(cfg, qh, kh, vh, t[None, :] <= t[:, None])

        def impl(w, fk, fv, wk, wv, ssm, conv, tokens, table, ring_table,
                 slot, length):
            t = jnp.arange(s_bucket)
            live = t < length
            # where each row's K/V goes: pads and rows the ring has no room
            # for (more than a ring before the end) go to scratch row 0
            full_slots = jnp.where(live, table[t // bs] * bs + t % bs, 0)
            ring_slots = jnp.where(
                live & (t >= length - ring_rows),
                ring_table[(t // bs) % ring] * bs + t % bs, 0)
            x = w["embed"][tokens]
            i_m = i_w = 0
            for l in range(half + 2):
                p, kind = f"l{l}.", kinds[l]
                h = _ln(x, w[p + "ln1_w"], w[p + "ln1_b"], cfg.eps)
                if kind == "mamba":
                    uz = h @ w[p + "win"]
                    u_in, gate = uz[:, :cfg.d_inner], uz[:, cfg.d_inner:]
                    kc = cfg.d_conv
                    upad, windows = conv_windows(
                        u_in, jnp.zeros((kc - 1, cfg.d_inner), x.dtype))
                    u, dt, bm, cm = _mamba_inputs(cfg, p, w, windows)
                    with scope("ssm_scan"):
                        dt = jnp.where(live[:, None], dt, 0.0)  # pads: stay
                        s_end, ys = ssm_scan(dt, u, bm, cm,
                                             -jnp.exp(w[p + "a_log"]))
                        y = ys + w[p + "dd"] * u
                        tail = jax.lax.dynamic_slice(
                            upad, (length, 0), (kc - 1, cfg.d_inner))
                        # the running state and the prompt's end, both
                        ssm = ssm.at[:, i_m, slot].set(s_end)
                        conv = conv.at[:, i_m, slot].set(tail)
                    if l == cfg.memory_layer:
                        mem = y[length - 1]
                    x = x + (y * jax.nn.silu(gate)) @ w[p + "wout"]
                    i_m += 1
                else:
                    name = ("window_attention" if kind == "window"
                            else "full_attention")
                    with scope(name):
                        qkv = h @ w[p + "wqkv"] + w[p + "bqkv"]
                        q, k, v = jnp.split(
                            qkv, [cfg.d_model, cfg.d_model + cfg.kv_dim],
                            axis=-1)
                        if kind == "window":
                            wk = wk.at[i_w, ring_slots].set(k)
                            wv = wv.at[i_w, ring_slots].set(v)
                            i_w += 1
                        else:
                            fk = fk.at[0, full_slots].set(k)
                            fv = fv.at[0, full_slots].set(v)
                            k_full, v_full = k, v
                        a = attend(_q_heads(cfg, q), *_kv_heads(cfg, k, v),
                                   kind == "window")
                        x = x + _diff_out(cfg, p, w, l, a)
                x = _mlp(cfg, p, w, x)
            # ---- the cross-decoder, for the last row only
            x = x[length - 1][None]
            kh, vh = _kv_heads(cfg, k_full, v_full)
            for l in range(half + 2, cfg.n_layers):
                p, kind = f"l{l}.", kinds[l]
                h = _ln(x, w[p + "ln1_w"], w[p + "ln1_b"], cfg.eps)
                if kind == "gmu":
                    with scope("gmu"):
                        x = x + (mem[None] * jax.nn.silu(h @ w[p + "w1"])) \
                            @ w[p + "w2"]
                else:
                    with scope("cross_attention"):
                        q = h @ w[p + "wq"] + w[p + "bq"]
                        a = _attend_rows(cfg, _q_heads(cfg, q), kh, vh,
                                         live[None, :])
                        x = x + _diff_out(cfg, p, w, l, a)
                x = _mlp(cfg, p, w, x)
            with scope("head"):
                last = _ln(x[0], w["lnf_w"], w["lnf_b"], cfg.eps)
                nxt = jnp.argmax(last @ w["embed"].T).astype(jnp.int32)
            return fk, fv, wk, wv, ssm, conv, nxt

        return jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))

    # -------------------------------------------------------------- decode
    def _decode_fn(self, b_bucket: int, l_bucket: int):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        kinds = cfg.kinds
        bs, ring = self.kv.block_size, self.kv.config.ring_blocks
        ring_rows = ring * bs
        # every row's position lies under l_bucket: a ring that has not
        # wrapped holds position p at ring row p
        ring_read = min(ring_rows, l_bucket)
        scope = jax.named_scope
        rows = jnp.arange(b_bucket)

        def impl(w, fk, fv, wk, wv, ssm, conv, tokens, pos, tables,
                 ring_tables, slots):
            # full layer: context position t at row t of the gathered
            # blocks; this token's row
            t = jnp.arange(l_bucket)
            full_live = t[None, :] <= pos[:, None]
            full_write = tables[rows, pos // bs] * bs + pos % bs
            ring_live = _ring_live(pos, ring_rows, cfg.window)[:, :ring_read]
            at = pos % ring_rows
            ring_write = ring_tables[rows, at // bs] * bs + at % bs
            ring_blocks = ring_tables[:, :ring_read // bs]

            x = w["embed"][tokens]
            i_m = i_w = 0
            for l, kind in enumerate(kinds):
                p = f"l{l}."
                h = _ln(x, w[p + "ln1_w"], w[p + "ln1_b"], cfg.eps)
                if kind == "mamba":
                    uz = h @ w[p + "win"]
                    u_in, gate = uz[:, :cfg.d_inner], uz[:, cfg.d_inner:]
                    windows = jnp.concatenate(
                        [conv[0, i_m, slots], u_in[:, None, :]], axis=1)
                    u, dt, bm, cm = _mamba_inputs(cfg, p, w, windows)
                    with scope("ssm_step"):
                        a = -jnp.exp(w[p + "a_log"])
                        s = (jnp.exp(dt[:, None, :] * a[None])
                             * ssm[0, i_m, slots]
                             + (dt * u)[:, None, :] * bm[:, :, None])
                        y = jnp.sum(s * cm[:, :, None], axis=1) \
                            + w[p + "dd"] * u
                        ssm = ssm.at[0, i_m, slots].set(s)
                        conv = conv.at[0, i_m, slots].set(windows[:, 1:])
                    if l == cfg.memory_layer:
                        mem = y
                    x = x + (y * jax.nn.silu(gate)) @ w[p + "wout"]
                    i_m += 1
                elif kind == "gmu":
                    with scope("gmu"):
                        x = x + (mem * jax.nn.silu(h @ w[p + "w1"])) \
                            @ w[p + "w2"]
                elif kind == "cross":
                    with scope("cross_attention"):
                        q = h @ w[p + "wq"] + w[p + "bq"]
                        x = x + _diff_out(
                            cfg, p, w, l,
                            _attend_decode(cfg, _q_heads(cfg, q), kf, vf,
                                           full_live))
                else:
                    name = ("window_attention" if kind == "window"
                            else "full_attention")
                    with scope(name):
                        qkv = h @ w[p + "wqkv"] + w[p + "bqkv"]
                        q, k, v = jnp.split(
                            qkv, [cfg.d_model, cfg.d_model + cfg.kv_dim],
                            axis=-1)
                        if kind == "window":
                            wk = wk.at[i_w, ring_write].set(k)
                            wv = wv.at[i_w, ring_write].set(v)
                            kc = _block_context(wk, i_w, ring_blocks, bs)
                            vc = _block_context(wv, i_w, ring_blocks, bs)
                            live = ring_live
                            i_w += 1
                        else:       # gathered ONCE, read by the cross layers
                            fk = fk.at[0, full_write].set(k)
                            fv = fv.at[0, full_write].set(v)
                            kf = _block_context(fk, 0, tables, bs)
                            vf = _block_context(fv, 0, tables, bs)
                            kc, vc, live = kf, vf, full_live
                        x = x + _diff_out(
                            cfg, p, w, l,
                            _attend_decode(cfg, _q_heads(cfg, q), kc, vc,
                                           live))
                x = _mlp(cfg, p, w, x)
            with scope("head"):
                last = _ln(x, w["lnf_w"], w["lnf_b"], cfg.eps)
                nxt = jnp.argmax(last @ w["embed"].T, axis=-1)
            return fk, fv, wk, wv, ssm, conv, nxt.astype(jnp.int32)

        return jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))
