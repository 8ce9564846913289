"""A ``cohere2_moe`` decoder on the serving plane (the architecture of
Command A+): a PARALLEL block ``x <- x + Attn(LN x) + FFN(LN x)``, sliding-
window layers with rotary positions beside position-free full layers, many
query heads over few key/value heads, and an expert layer of sigmoid-routed
top-k experts beside shared experts whose outputs are averaged.
``benchmark/blocks/cohere2moe/reference.py`` states each equation.

**The chip's share.** An expert layer is told which experts it holds
(``experts_held`` of ``num_routed_experts``, the ``expert_rank``-th run of
them), routes over ALL of them, and computes its own experts' part of the
result for the token-expert pairs routed to them; what the absent experts
would add is left out, and that partial sum goes on. Attention, the router
and the shared experts are whole. Nothing stands in for the absent chips or
their exchange.

Two programs over a :class:`~brpc_tpu.serving.hybrid_cache.HybridStateCache`
(window rings, as many full layers' pages as ``layer_types`` has, no
recurrent state), launched by what
:class:`~brpc_tpu.serving.hybrid_model.HybridServingModel` shares with the
SambaY lane (buckets, ``prep`` / ``launch`` / ``sync``, one launch and one
sync a decode step):

- ``prefill``: all rows of one prompt. A layer the window cuts nothing of
  is ONE call of the batched flash forward over q, k and v as the
  projections made them (rounded as every matmul's operands, a query head
  reading its key/value head in place); a window layer of a longer prompt
  scans query blocks of ``QUERY_BLOCK`` rows over ``window + QUERY_BLOCK``
  keys, so nothing holds heads x rows x rows. The expert layer runs over
  chunks of ``MOE_CHUNK`` rows.
- ``decode_step``: one fused launch for the batch over ring rows and pages.

The routed product is ONE routine for both, :func:`expert_layer`: pairs
sorted by expert, each expert's run padded to whole tiles, no pair dropped,
static shapes by bucket, through ``pallas_ops.moe_grouped_matmul`` (a tile
streams one expert's weights; an expert no row hit is never read). Each
launch returns, beside its tokens and in the same sync, per layer the pairs
computed here, the distinct held experts hit and the most pairs of one
expert: ``moe_counters``, which ``ServingEngine.snapshot()["moe"]`` reads
(prefill's also how many layers of the launched programs took the kernel and
how many the blocked scan, summed on the host).

Storage: weights and K/V pools bfloat16 (K stored rotated); the router's
weights, the residual stream, softmax, router and every sum float32; matmuls
at the backend's default precision (operands rounded to bfloat16 on the TPU,
exact elsewhere), the router's product at ``highest``. Greedy argmax.
"""

from __future__ import annotations

import functools
import math
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Sequence

import numpy as np

from brpc_tpu.serving.hybrid_cache import HybridStateCache
from brpc_tpu.serving.hybrid_model import (NEG, HybridServingModel, _ln,
                                           _ring_live, decode_buckets)

QUERY_BLOCK = 128    # query rows a step of the blocked window attention
MOE_CHUNK = 1024     # rows a pass of the expert layer in prefill
DECODE_CONTEXT_FLOOR = 1024   # rows of the smallest decode context bucket
COUNTERS = ("pairs", "experts_hit", "pairs_max_expert")
# how a launched prefill program's attention layers were built (host sums)
LAYER_PATHS = ("kernel_layers", "blocked_layers")


class Cohere2MoeConfig:
    """Read from the published configuration's keys. ``num_experts`` is how
    many routed experts THIS chip holds (the ``expert_rank``-th run of them);
    ``num_routed_experts`` the published count the router is as wide as
    (``num_experts`` where it is left out: the uncut layer)."""

    def __init__(self, hidden_size: int = 64, num_attention_heads: int = 8,
                 num_key_value_heads: int = 2, head_dim: int = 16,
                 intermediate_size: int = 64, num_experts: int = 8,
                 num_routed_experts: int = 0, expert_rank: int = 0,
                 num_experts_per_tok: int = 2, num_shared_experts: int = 2,
                 sliding_window: int = 16,
                 layer_types: Sequence[str] = ("sliding_attention",
                                               "full_attention"),
                 num_hidden_layers: int = 0, rope_theta: float = 50000.0,
                 layer_norm_eps: float = 1e-5, logit_scale: float = 1.0,
                 vocab_size: int = 256, max_context: int = 1024,
                 seed: int = 0, attn: str = "auto"):
        total = num_routed_experts or num_experts
        if num_attention_heads % num_key_value_heads or head_dim % 2:
            raise ValueError("query heads divide over key/value heads, and "
                             "rotary pairs need an even head size")
        if total % num_experts or not 0 <= expert_rank < total // num_experts:
            raise ValueError("the held experts are one of total / held runs")
        if num_experts_per_tok > total:
            raise ValueError("more experts a token than experts")
        if sliding_window & (sliding_window - 1) or sliding_window < 16:
            raise ValueError("sliding_window must be a power of two >= 16")
        if num_hidden_layers and num_hidden_layers != len(layer_types):
            raise ValueError("layer_types names every layer")
        kinds = {"sliding_attention": "window", "full_attention": "full"}
        self.kinds = [kinds[t] for t in layer_types]
        self.d_model = hidden_size
        self.n_heads, self.n_kv_heads = num_attention_heads, num_key_value_heads
        self.head_dim = head_dim
        self.d_ff = intermediate_size
        self.n_experts, self.held = total, num_experts
        self.expert_lo = expert_rank * num_experts
        self.top_k, self.n_shared = num_experts_per_tok, num_shared_experts
        self.window = sliding_window
        self.n_layers = len(self.kinds)
        self.theta = float(rope_theta)
        self.eps = layer_norm_eps
        self.logit_scale = float(logit_scale)
        self.vocab = vocab_size
        self.max_context = max_context
        # the smallest context bucket of a decode program: short contexts
        # read that many ring rows and pages, not a whole window's
        self.decode_context_floor = min(sliding_window, DECODE_CONTEXT_FLOOR)
        self.seed = seed
        self.attn = attn            # as ModelConfig.attn

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)

    def cache(self, cache_config, store=None) -> HybridStateCache:
        """The manager this model needs, sized by ``cache_config``."""
        import jax.numpy as jnp

        return HybridStateCache(
            cache_config, self.kv_dim, self.count("window"), store=store,
            full_layers=self.count("full"), dtype=jnp.bfloat16)

    # ---- weights: one generator a matrix, so a chip draws only what it
    # holds and every rank draws the same expert alike
    def matrices(self, layer: Optional[int] = None):
        """(name, stream id, shape, fan-in) of every drawn matrix this chip
        holds of ``layer`` (the embedding where it is None), (rows in,
        columns out); expert ``e`` of a layer is the PUBLISHED index."""
        d, ff = self.d_model, self.d_ff
        if layer is None:
            # 0.1 / sqrt(d): with rows as long as the layers' outputs a
            # tied head returns the token it was given
            return [("embed", 10 ** 6, (self.vocab, d), 25 * d)]
        l, base = layer, 1000 * layer
        out = [(f"l{l}.wq", base, (d, self.q_dim), d),
               (f"l{l}.wk", base + 1, (d, self.kv_dim), d),
               (f"l{l}.wv", base + 2, (d, self.kv_dim), d),
               (f"l{l}.wo", base + 3, (self.q_dim, d), self.q_dim),
               (f"l{l}.router", base + 4, (d, self.n_experts), d)]
        for kind, first, n, sid in (
                ("s", 0, self.n_shared, base + 10),
                ("e", self.expert_lo, self.held, base + 100)):
            for i in range(first, first + n):
                out += [(f"l{l}.{kind}{i}.wg", sid + 3 * i, (d, ff), d),
                        (f"l{l}.{kind}{i}.wu", sid + 3 * i + 1, (d, ff), d),
                        (f"l{l}.{kind}{i}.wd", sid + 3 * i + 2, (ff, d), ff)]
        return out


def draw_matrix(seed: int, stream: int, shape, fan_in: int) -> np.ndarray:
    """One matrix of the recipe: ``Generator(Philox(key=[seed, stream]))``,
    ``standard_normal`` float32 in row-major order, times
    ``0.5 / sqrt(fan_in)``."""
    rng = np.random.Generator(np.random.Philox(key=[seed, stream]))
    out = rng.standard_normal(shape, dtype=np.float32)
    out *= np.float32(0.5 / math.sqrt(fan_in))
    return out


# ------------------------------------------------------------ layer functions
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


def _softmax_out(sc, live, v, spec):
    import jax
    import jax.numpy as jnp

    prob = jax.nn.softmax(jnp.where(live, sc, NEG), axis=-1)
    return _mm(prob, v, spec)


def attend_flash(q, k, v):
    """Causal attention of one sequence in ONE call of the batched flash
    forward: q (S, H, hd), k, v (S, G, hd); query head ``h`` reads key/value
    head ``h // (H / G)`` in place. Operands rounded as :func:`_mm` rounds
    them (K and V are as stored), sums float32. Returns (S, H, hd) in the
    operands' dtype: what the output projection rounds to."""
    from brpc_tpu.tpu import pallas_ops

    dt = _operand_dtype()
    qh, kh, vh = (x.astype(dt).transpose(1, 0, 2)[None] for x in (q, k, v))
    out = pallas_ops.flash_attention_mha(qh, kh, vh, causal=True)
    return out[0].transpose(1, 0, 2)


def attend_blocked(cfg, q, k, v, window: int):
    """Causal attention of one sequence, row ``t`` over rows ``t - window +
    1 .. t``, as a scan over blocks of query rows, each over the
    ``window + block`` keys that end with it (all keys where the window
    cuts nothing): the scores held are heads x block x that. q (S, H, hd);
    k, v (S, G, hd)."""
    import jax
    import jax.numpy as jnp

    s, g, hd = k.shape
    qb = min(QUERY_BLOCK, s)
    span = min(s, window + qb)
    front = span - qb
    qh = q.reshape(s // qb, qb, g, cfg.n_heads // g, hd)
    kp = jnp.concatenate([jnp.zeros((front, g, hd), k.dtype), k])
    vp = jnp.concatenate([jnp.zeros((front, g, hd), v.dtype), v])
    i = jnp.arange(qb)[:, None]
    j = jnp.arange(span)[None, :]

    def one(args):
        qc, c = args
        kc = jax.lax.dynamic_slice_in_dim(kp, c * qb, span)
        vc = jax.lax.dynamic_slice_in_dim(vp, c * qb, span)
        # key j of the slice is row c qb - front + j; query i is row c qb + i
        live = ((j >= front - c * qb) & (j <= front + i)
                & (j > front + i - window))
        sc = _mm(qc, kc, "qgjd,kgd->gjqk") / math.sqrt(hd)
        return _softmax_out(sc, live, vc, "gjqk,kgd->qgjd")

    out = jax.lax.map(one, (qh, jnp.arange(s // qb)))
    return out.reshape(s, cfg.n_heads, hd)


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


class Cohere2MoeModel(HybridServingModel):
    """Weights + the prefill and decode programs over a HybridStateCache."""

    def __init__(self, config: Cohere2MoeConfig, kv: HybridStateCache,
                 weights: Optional[Dict[str, np.ndarray]] = None):
        """``weights``: host arrays by ``config.matrices()``'s names
        (tests); drawn from ``config.seed`` where it is left out."""
        import jax
        import jax.numpy as jnp

        self._init_programs(config, kv)
        self.moe_counters = {"experts_held": config.held}
        self.reset_moe_counters()
        cfg = config
        dev = self.store.device
        d, ff = cfg.d_model, cfg.d_ff

        def host(name, sid, shape, fan_in):
            if weights is None:
                return draw_matrix(cfg.seed, sid, shape, fan_in)
            got = np.asarray(weights[name], np.float32)  # tpulint: disable=no-per-token-host-sync
            if got.shape != tuple(shape):
                raise ValueError(f"weight {name}: {got.shape} != {shape}")
            return got

        @functools.partial(jax.jit, donate_argnums=0)
        def fill(buf, part, *start):
            return jax.lax.dynamic_update_slice(buf, part.astype(buf.dtype),
                                                start)

        # drawn a layer ahead on threads (numpy frees the interpreter),
        # staged ONE matrix at a time into the arrays the programs read:
        # set-up, not a step loop
        with ThreadPoolExecutor(4) as pool:
            def start(layer):
                return {m[0]: pool.submit(host, *m)
                        for m in cfg.matrices(layer)}

            def take(drawn, name):
                return jax.device_put(drawn.pop(name).result(), dev)  # tpulint: disable=no-per-op-step-dispatch

            drawn, ahead = start(None), start(0)
            self._stage("embed", take(drawn, "embed").astype(jnp.bfloat16))
            for l in range(cfg.n_layers):
                drawn, p = ahead, f"l{l}."
                ahead = start(l + 1) if l + 1 < cfg.n_layers else {}
                for name in ("wq", "wk", "wv", "wo"):
                    self._stage(p + name,
                                take(drawn, p + name).astype(jnp.bfloat16))
                self._stage(p + "router", take(drawn, p + "router"))
                self._stage(p + "ln_w", jnp.ones((d,), jnp.float32))
                # shared experts: gates side by side, then ups; downs stacked
                ns = cfg.n_shared
                wgu = jnp.zeros((d, 2 * ns * ff), jnp.bfloat16)
                wd = jnp.zeros((ns * ff, d), jnp.bfloat16)
                for i in range(ns):
                    wgu = fill(wgu, take(drawn, f"{p}s{i}.wg"), 0, i * ff)
                    wgu = fill(wgu, take(drawn, f"{p}s{i}.wu"), 0,
                               (ns + i) * ff)
                    wd = fill(wd, take(drawn, f"{p}s{i}.wd"), i * ff, 0)
                self._stage(p + "s_wgu", wgu)
                self._stage(p + "s_wd", wd)
                # held experts: (held, d, gate | up) and (held, ff, d)
                wgu = jnp.zeros((cfg.held, d, 2 * ff), jnp.bfloat16)
                wd = jnp.zeros((cfg.held, ff, d), jnp.bfloat16)
                for i in range(cfg.held):
                    e = cfg.expert_lo + i
                    wgu = fill(wgu, take(drawn, f"{p}e{e}.wg")[None], i, 0, 0)
                    wgu = fill(wgu, take(drawn, f"{p}e{e}.wu")[None], i, 0,
                               ff)
                    wd = fill(wd, take(drawn, f"{p}e{e}.wd")[None], i, 0, 0)
                self._stage(p + "e_wgu", wgu)
                self._stage(p + "e_wd", wd)
            self._stage("lnf_w", jnp.ones((d,), jnp.float32))

    def _decode_buckets(self, n_rows: int, tables):
        """Rows to a multiple of 8 (a step gathers every padded row's
        context), the context as the hybrid lane's from its own floor."""
        return (-(-n_rows // 8) * 8,
                decode_buckets(n_rows, tables, self.kv.block_size,
                               self.config.decode_context_floor)[1])

    def reset_moe_counters(self) -> None:
        for phase, more in (("decode", ()), ("prefill", LAYER_PATHS)):
            self.moe_counters[phase] = dict.fromkeys(
                COUNTERS + ("layer_launches",) + more, 0)

    def _note_counters(self, phase: str, tail) -> None:
        c = self.moe_counters[phase]
        per_layer = np.asarray(tail, np.int64).reshape(-1, len(COUNTERS))
        c["layer_launches"] += len(per_layer)
        for name, total in zip(COUNTERS, per_layer.sum(axis=0)):
            c[name] += int(total)

    # ---- what both programs share of a layer
    def _qkv(self, p, w, h, pos, rotate: bool):
        import jax

        cfg = self.config
        q = _mm(h, w[p + "wq"]).reshape(-1, cfg.n_heads, cfg.head_dim)
        k = _mm(h, w[p + "wk"]).reshape(-1, cfg.n_kv_heads, cfg.head_dim)
        v = _mm(h, w[p + "wv"])
        if rotate:
            with jax.named_scope("rope"):
                q, k = rope(q, pos, cfg.theta), rope(k, pos, cfg.theta)
        return q, k.reshape(-1, cfg.kv_dim), v

    def _ffn(self, p, w, h, live, tile: int):
        """The expert layer over rows h: routed part (this chip's) + the
        shared experts' mean, and the pairs held by expert."""
        import jax

        cfg = self.config
        with jax.named_scope("router"):
            idx, wts = route(cfg, h, w[p + "router"], live)
        routed, cnt = expert_layer(cfg, h, idx, wts, w[p + "e_wgu"],
                                   w[p + "e_wd"], tile)
        return routed + shared_experts(cfg, h, w[p + "s_wgu"],
                                       w[p + "s_wd"]), cnt

    @staticmethod
    def _counted(cnt):
        import jax.numpy as jnp

        return jnp.stack([jnp.sum(cnt), jnp.sum(cnt > 0), jnp.max(cnt)])

    # ------------------------------------------------------------- prefill
    def _prefill_fn(self, s_bucket: int, use_flash: bool):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        bs, ring = self.kv.block_size, self.kv.config.ring_blocks
        ring_rows = ring * bs
        scope = jax.named_scope
        chunk = min(MOE_CHUNK, s_bucket)
        pool_dt = self.kv.full.k_pool.dtype

        # the kernel serves a layer where the window cuts nothing
        kernel = [use_flash and (kind == "full" or s_bucket <= cfg.window)
                  for kind in cfg.kinds]

        def attend(q, k, v, l: int):
            if kernel[l]:
                return attend_flash(q, k, v)
            window = cfg.window if cfg.kinds[l] == "window" else s_bucket
            return attend_blocked(cfg, q, k, v, min(window, s_bucket))

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
            x = w["embed"][tokens].astype(jnp.float32)
            counts = []
            i_w = i_f = 0
            for l, kind in enumerate(cfg.kinds):
                p = f"l{l}."
                h = _ln(x, w[p + "ln_w"], 0.0, cfg.eps)
                with scope("window_attention" if kind == "window"
                           else "full_attention"):
                    q, k, v = self._qkv(p, w, h, t, kind == "window")
                    k, v = k.astype(pool_dt), v.astype(pool_dt)  # as stored
                    if kind == "window":
                        wk = wk.at[i_w, ring_slots].set(k)
                        wv = wv.at[i_w, ring_slots].set(v)
                        i_w += 1
                    else:
                        fk = fk.at[i_f, full_slots].set(k)
                        fv = fv.at[i_f, full_slots].set(v)
                        i_f += 1
                    a = attend(q, k.reshape(s_bucket, cfg.n_kv_heads, -1),
                               v.reshape(s_bucket, cfg.n_kv_heads, -1), l)
                    att = _mm(a.reshape(s_bucket, cfg.q_dim), w[p + "wo"])

                def ffn(args):
                    return self._ffn(p, w, args[0], args[1], tile=128)

                y, cnt = jax.lax.map(
                    ffn, (h.reshape(-1, chunk, cfg.d_model),
                          live.reshape(-1, chunk)))
                counts.append(self._counted(jnp.sum(cnt, axis=0)))
                x = x + att + y.reshape(s_bucket, cfg.d_model)
            with scope("head"):
                last = _ln(x[length - 1], w["lnf_w"], 0.0, cfg.eps)
                nxt = jnp.argmax(cfg.logit_scale
                                 * _mm(last[None], w["embed"].T)[0])
            out = jnp.concatenate([nxt[None].astype(jnp.int32)]
                                  + [c.astype(jnp.int32) for c in counts])
            return fk, fv, wk, wv, ssm, conv, out

        program = jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))
        built = dict(zip(LAYER_PATHS, (sum(kernel),
                                       cfg.n_layers - sum(kernel))))

        @functools.wraps(program)
        def launch(*args):
            for name, n in built.items():
                self.moe_counters["prefill"][name] += n
            return program(*args)

        return launch

    # -------------------------------------------------------------- decode
    def _decode_fn(self, b_bucket: int, l_bucket: int):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        bs, ring = self.kv.block_size, self.kv.config.ring_blocks
        ring_rows = ring * bs
        # every row's position lies under l_bucket: a ring that has not
        # wrapped holds position p at ring row p
        ring_read = min(ring_rows, l_bucket)
        scope = jax.named_scope
        rows = jnp.arange(b_bucket)
        pool_dt = self.kv.full.k_pool.dtype
        g, per, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, \
            cfg.head_dim

        def impl(w, fk, fv, wk, wv, ssm, conv, tokens, pos, tables,
                 ring_tables, slots):
            del slots           # no recurrent state: the row is the slot
            t = jnp.arange(l_bucket)
            full_live = (t[None, :] <= pos[:, None])[:, None, None, :]
            full_write = tables[rows, pos // bs] * bs + pos % bs
            ring_live = _ring_live(pos, ring_rows, cfg.window)[
                :, None, None, :ring_read]
            at = pos % ring_rows
            ring_write = ring_tables[rows, at // bs] * bs + at % bs
            ring_blocks = ring_tables[:, :ring_read // bs]
            # a padded row (position 0: a decode row's is its prompt's
            # length at least) routes nowhere
            live = pos > 0

            def context(pool, layer: int, blocks):
                """A batch's rows of one layer of a pool, whole BLOCKS at a
                time (a block is 16 contiguous rows: gathered three times
                as fast as its rows one by one, measured), the layer inside
                the gather's index: ``pool[layer]`` first is a copy of the
                whole layer, 404 MB of the rings a window layer."""
                return pool.reshape(len(pool), -1, bs, cfg.kv_dim)[
                    layer, blocks].reshape(b_bucket, -1, cfg.kv_dim)

            def attend(q, kc, vc, mask):
                qh = q.reshape(b_bucket, g, per, hd)
                kh = kc.reshape(b_bucket, -1, g, hd)
                vh = vc.reshape(b_bucket, -1, g, hd)
                sc = _mm(qh, kh, "bgjd,bkgd->bgjk") / math.sqrt(hd)
                return _softmax_out(sc, mask, vh, "bgjk,bkgd->bgjd")

            x = w["embed"][tokens].astype(jnp.float32)
            counts = []
            i_w = i_f = 0
            for l, kind in enumerate(cfg.kinds):
                p = f"l{l}."
                h = _ln(x, w[p + "ln_w"], 0.0, cfg.eps)
                with scope("window_attention" if kind == "window"
                           else "full_attention"):
                    q, k, v = self._qkv(p, w, h, pos, kind == "window")
                    k, v = k.astype(pool_dt), v.astype(pool_dt)
                    if kind == "window":
                        wk = wk.at[i_w, ring_write].set(k)
                        wv = wv.at[i_w, ring_write].set(v)
                        a = attend(q, context(wk, i_w, ring_blocks),
                                   context(wv, i_w, ring_blocks), ring_live)
                        i_w += 1
                    else:
                        fk = fk.at[i_f, full_write].set(k)
                        fv = fv.at[i_f, full_write].set(v)
                        a = attend(q, context(fk, i_f, tables),
                                   context(fv, i_f, tables), full_live)
                        i_f += 1
                    att = _mm(a.reshape(b_bucket, cfg.q_dim), w[p + "wo"])
                y, cnt = self._ffn(p, w, h, live, tile=16)
                counts.append(self._counted(cnt))
                x = x + att + y
            with scope("head"):
                last = _ln(x, w["lnf_w"], 0.0, cfg.eps)
                nxt = jnp.argmax(cfg.logit_scale * _mm(last, w["embed"].T),
                                 axis=-1)
            out = jnp.concatenate([nxt.astype(jnp.int32)]
                                  + [c.astype(jnp.int32) for c in counts])
            return fk, fv, wk, wv, ssm, conv, out

        return jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))
