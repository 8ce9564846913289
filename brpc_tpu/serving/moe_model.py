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
- ``decode_step``: one fused launch for the batch. A layer's attention is
  ONE call of ``pallas_ops.paged_decode_attention`` over the pools in
  place, a query head's group reading its key/value head's columns: the
  full layer's pages as far as each row's position, a window layer's ring
  of rows through its ring table, masked to the window, the kernel's grid
  the rows' live steps alone (``fetch_plan``). Nothing is gathered, and
  every decode program takes the served context's whole table: ONE
  program a row bucket.

The routed product is ONE routine for both, the lane's
:func:`~brpc_tpu.serving.hybrid_model.expert_layer`: pairs sorted by expert,
each expert's run padded to whole tiles, no pair dropped, static shapes by
bucket, through ``pallas_ops.moe_grouped_matmul`` (a tile streams one
expert's weights; an expert no row hit is never read). Each launch returns,
beside its tokens and in the same sync, per layer the pairs computed here,
the distinct held experts hit and the most pairs of one expert: the model's
``moe`` counter family (``moe_counters``; prefill's also how many layers of
the launched programs took the kernel and how many the blocked scan, summed
on the host), which ``ServingEngine.snapshot()`` copies and ``/serving``
prints as the model formats it. Its ``decode`` family (the toy block's
names, ``hybrid_model.DECODE_COUNTERS``) counts, from the positions the
host hands each decode step, the pages the attention kernel fetched
against the pages of the tables it was handed, summed over the layers.

Storage: weights and K/V pools bfloat16 (K stored rotated); the router's
weights, the residual stream, softmax, router and every sum float32; matmuls
at the backend's default precision (operands rounded to bfloat16 on the TPU,
exact elsewhere), the router's product at ``highest``. Greedy argmax.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from brpc_tpu.serving.hybrid_cache import HybridStateCache
from brpc_tpu.serving.hybrid_model import (DECODE_COUNTERS, NEG,
                                           QUERY_BLOCK, HybridServingModel,
                                           _ln, _mm, _next_pow2,
                                           _operand_dtype, decode_family,
                                           decode_lines, draw_matrix,
                                           expert_counts, expert_layer,
                                           fill_stack, rope, route,
                                           shared_experts)

MOE_CHUNK = 1024     # rows a pass of the expert layer in prefill


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
        # every decode program takes the served context's whole table: the
        # attention kernel walks a row's own pages alone, and a program is
        # seconds of set-up (its kernels lowered on the host whatever the
        # compile cache holds), so ONE program a row bucket
        self.decode_context_floor = _next_pow2(max_context)
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


# ------------------------------------------------------------ layer functions
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


class Cohere2MoeModel(HybridServingModel):
    """Weights + the prefill and decode programs over a HybridStateCache."""

    # a padded row costs its rows of every projection and one step of each
    # layer's attention kernel
    DECODE_ROWS_BY_8 = True
    MOE_COUNTERS = ("pairs", "experts_hit", "pairs_max_expert")
    # how a launched prefill program's attention layers were built
    PREFILL_MOE_COUNTERS = ("kernel_layers", "blocked_layers")

    def __init__(self, config: Cohere2MoeConfig, kv: HybridStateCache,
                 weights: Optional[Dict[str, np.ndarray]] = None):
        """``weights``: host arrays by ``config.matrices()``'s names that
        replace what the recipe draws (tests); the rest is drawn from
        ``config.seed``."""
        import jax.numpy as jnp

        self._init_programs(config, kv)
        # decode attention's pages, counted on the host (counters())
        self.decode_counters = dict.fromkeys(DECODE_COUNTERS, 0)
        cfg, bf = config, jnp.bfloat16
        d, ff, ns = cfg.d_model, cfg.d_ff, cfg.n_shared
        for l, take in self._drawn_layers(cfg.matrices, draw_matrix,
                                          weights):
            if l is None:
                self._stage("embed", take("embed").astype(bf))
                continue
            p = f"l{l}."
            for name in ("wq", "wk", "wv", "wo"):
                self._stage(p + name, take(p + name).astype(bf))
            self._stage(p + "router", take(p + "router"))
            self._stage(p + "ln_w", jnp.ones((d,), jnp.float32))
            # shared experts: gates side by side, then ups; downs stacked
            wgu = jnp.zeros((d, 2 * ns * ff), bf)
            wd = jnp.zeros((ns * ff, d), bf)
            for i in range(ns):
                wgu = fill_stack(wgu, take(f"{p}s{i}.wg"), 0, i * ff)
                wgu = fill_stack(wgu, take(f"{p}s{i}.wu"), 0, (ns + i) * ff)
                wd = fill_stack(wd, take(f"{p}s{i}.wd"), i * ff)
            self._stage(p + "s_wgu", wgu)
            self._stage(p + "s_wd", wd)
            # held experts: (held, d, gate | up) and (held, ff, d)
            wgu = jnp.zeros((cfg.held, d, 2 * ff), bf)
            wd = jnp.zeros((cfg.held, ff, d), bf)
            for i in range(cfg.held):
                e = cfg.expert_lo + i
                wgu = fill_stack(wgu, take(f"{p}e{e}.wg"), i)
                wgu = fill_stack(wgu, take(f"{p}e{e}.wu"), i, 0, ff)
                wd = fill_stack(wd, take(f"{p}e{e}.wd"), i)
            self._stage(p + "e_wgu", wgu)
            self._stage(p + "e_wd", wd)
        self._stage("lnf_w", jnp.ones((d,), jnp.float32))

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
                counts.append(expert_counts(jnp.sum(cnt, axis=0)))
                x = x + att + y.reshape(s_bucket, cfg.d_model)
            with scope("head"):
                last = _ln(x[length - 1], w["lnf_w"], 0.0, cfg.eps)
                nxt = jnp.argmax(cfg.logit_scale
                                 * _mm(last[None], w["embed"].T)[0])
            out = jnp.concatenate([nxt[None].astype(jnp.int32)]
                                  + [c.astype(jnp.int32) for c in counts])
            return fk, fv, wk, wv, ssm, conv, out

        program = jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))
        built = dict(zip(self.PREFILL_MOE_COUNTERS,
                         (sum(kernel), cfg.n_layers - sum(kernel))))

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

        from brpc_tpu.tpu import pallas_ops

        cfg = self.config
        bs, ring = self.kv.block_size, self.kv.config.ring_blocks
        ring_rows = ring * bs
        # every row's position lies under l_bucket: a ring that has not
        # wrapped holds position p at ring row p, so no row reads a ring
        # row past this
        ring_read = min(ring_rows, l_bucket)
        scope = jax.named_scope
        rows = jnp.arange(b_bucket)
        pool_dt = self.kv.full.k_pool.dtype
        attend = functools.partial(
            pallas_ops.paged_decode_attention, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, block_size=bs, fetch_plan=True)

        def impl(w, fk, fv, wk, wv, ssm, conv, tokens, pos, tables,
                 ring_tables, slots):
            del slots           # no recurrent state: the row is the slot
            full_write = tables[rows, pos // bs] * bs + pos % bs
            at = pos % ring_rows
            ring_write = ring_tables[rows, at // bs] * bs + at % bs
            ring_pages = ring_tables[:, :ring_read // bs]
            # the positions 0 .. pos a row attends to; a padded row
            # (position 0: a decode row's is its prompt's length at least)
            # fetches one page, its output unread, and routes nowhere
            length = pos + 1
            live = pos > 0

            x = w["embed"][tokens].astype(jnp.float32)
            counts = []
            i_w = i_f = 0
            for l, kind in enumerate(cfg.kinds):
                p = f"l{l}."
                h = _ln(x, w[p + "ln_w"], 0.0, cfg.eps)
                with scope("window_attention" if kind == "window"
                           else "full_attention"):
                    q, k, v = self._qkv(p, w, h, pos, kind == "window")
                    q = q.reshape(b_bucket, cfg.q_dim)
                    k, v = k.astype(pool_dt), v.astype(pool_dt)
                    if kind == "window":
                        wk = wk.at[i_w, ring_write].set(k)
                        wv = wv.at[i_w, ring_write].set(v)
                        a = attend(q, wk, wv, i_w, ring_pages, length,
                                   ring=(ring_rows, cfg.window))
                        i_w += 1
                    else:
                        fk = fk.at[i_f, full_write].set(k)
                        fv = fv.at[i_f, full_write].set(v)
                        a = attend(q, fk, fv, i_f, tables, length)
                        i_f += 1
                    att = _mm(a, w[p + "wo"])
                y, cnt = self._ffn(p, w, h, live, tile=16)
                counts.append(expert_counts(cnt))
                x = x + att + y
            with scope("head"):
                last = _ln(x, w["lnf_w"], 0.0, cfg.eps)
                nxt = jnp.argmax(cfg.logit_scale * _mm(last, w["embed"].T),
                                 axis=-1)
            out = jnp.concatenate([nxt.astype(jnp.int32)]
                                  + [c.astype(jnp.int32) for c in counts])
            return fk, fv, wk, wv, ssm, conv, out

        return jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))

    def decode_step(self, tokens: np.ndarray, positions: np.ndarray,
                    tables) -> np.ndarray:
        """The lane's decode step, and the ``decode`` family counted from
        the host's positions: the pages each row's attention fetched in
        each layer (its positions' in the full layer, its ring's rows' in a
        window layer) and the pages of the tables handed over."""
        out = super().decode_step(tokens, positions, tables)
        b_bucket, l_bucket = self._decode_buckets(len(tokens), tables)
        bs, ring = self.kv.block_size, self.kv.ring_blocks
        n = np.asarray(positions, np.int64) + 1
        full, window = self.config.count("full"), self.config.count("window")
        c = self.decode_counters
        c["decode_launches_paged"] += 1
        c["decode_pages_live"] += int(
            full * (-(-n // bs)).sum()
            + window * (-(-np.minimum(n, ring * bs) // bs)).sum())
        c["decode_pages_bucket"] += b_bucket * (
            full * (l_bucket // bs) + window * min(ring, l_bucket // bs))
        return out

    def counters(self) -> Dict[str, object]:
        """The lane's families and ``decode`` (``decode_family``)."""
        return dict(super().counters(),
                    decode=decode_family(self.decode_counters))

    def counter_lines(self, snap) -> List[str]:
        return super().counter_lines(snap) + decode_lines(snap)
