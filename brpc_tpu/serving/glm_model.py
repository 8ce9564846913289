"""A ``glm4_moe_lite`` decoder on the serving plane (the architecture of
GLM-4.7-Flash; the block is DeepSeek-V2/V3's): multi-head latent attention
(MLA) whose cache holds ONE row a token a layer, then a gated MLP (the
leading dense layer) or an expert layer of sigmoid-routed top-k experts
chosen with a stored selection bias, beside one shared expert; pre-norm
RMSNorm, two sublayers in sequence; untied head.
``benchmark/blocks/glm4moelite/reference.py`` states each equation.

**The latent page.** A token leaves ``[c | k_r]`` in a layer's page:
``c = RMSNorm(u Wkva[:, :r])`` (``kv_lora_rank`` wide) and ONE rotary key
``k_r = rope(u Wkva[:, r:])`` shared by all heads, stored rotated. Keys and
values of every head are functions of that row (``[k_nope | v]_h = c
Wkvb_h``), so the manager is a :class:`HybridStateCache` with ``kv_dim =
kv_lora_rank + qk_rope_head_dim`` and ``v_dim = 0``: one pool, no value pool
(``serving/hybrid_cache.py``). The manager ALLOCATES such rows at whole
128-lane tiles (``kv_cache.LANES``: the decode kernel copies pages itself and
a copy out of HBM takes whole tiles; the device pads the rows so in any
case); the programs pad what they write and ask to the pool's own width.

**Two attention paths over the same pages.**

- *expanded* (prefill, the definition): K and V are built through ``Wkvb``
  from the rows as stored and attended to by the flash kernels at head width
  ``qk_nope + qk_rope = v_head_dim``. A prompt's first launch builds them
  from its own rows (``flash_attention_mha``, all heads in one call); a later
  chunk (``CONTINUES_PREFILL``) reads its context's rows back through the
  block table, and ONE kernel (``pallas_ops.mla_chunk_attention``) builds
  every head's K and V again a tile of rows at a time in VMEM, only up to
  the chunk's last row, and attends to them there: nothing but the latent
  is ever kept.
- *absorbed* (decode; the same numbers, no K or V built): ``Wkvb`` is staged
  split by head into ``Wuk_h`` and ``Wuv_h``; a query head becomes ``q~_h =
  [q_nope_h Wuk_h' | q_rope_h]``, its scores are ``q~_h [c | k_r]' / sqrt(qk
  head width)``, its output ``(P_h c) Wuv_h``. All heads read each live row
  ONCE a layer, in place, as key and value: ``pallas_ops.mla_paged_decode``.

**The chip's share** of the expert layer is the lane's ``expert_layer``'s: the
layer is told which experts it holds (``expert_rank``-th run of
``n_routed_experts`` of ``num_routed_experts``), routes over all of them and
computes its own experts' part; the shared expert, attention, the router, the
dense layer and the vocabulary are whole.

Layer 0 is apart (its MLP is dense); layers ``1 .. n - 1`` are ONE
``fori_loop`` over stacked weights (the body compiles once), the experts of
all layers in one (layers x held, ...) stack the grouped matmul indexes.
Each launch returns, beside its tokens and in the same sync, the expert
layers' counters (the model's ``moe`` counter family, ``moe_counters``) and
the latent rows it read (its ``mla`` family, ``mla_counters``, which this
module keeps and formats for ``/serving``).

Storage: weights and pages bfloat16, the router and its bias float32;
matmul operands rounded to bfloat16 on the TPU (exact elsewhere), sums
float32; the residual stream, the router (its product at ``highest``), the
norms' statistics, rotary and softmax float32. Greedy argmax.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from brpc_tpu.serving.hybrid_cache import HybridStateCache
from brpc_tpu.serving.hybrid_model import (HybridServingModel,
                                           _block_context, _f32, _mm,
                                           _next_pow2, _operand_dtype, _rms,
                                           attend_chunk_blocked,
                                           context_floors, draw_array,
                                           expert_counts, expert_layer,
                                           fill_stack, rope, route,
                                           shared_experts)

CHUNK_FLOOR = 512             # rows of the smallest padded chunk of a prompt
# rows of the narrowest decode block table (the kernel walks a row's own
# pages: the table's width costs scalar memory, not time)
DECODE_CONTEXT_FLOOR = 8192
PREFILL_CONTEXT_FLOOR = 4096  # rows of a later chunk's smallest context
MLA_COUNTERS = ("latent_rows", "expanded_rows", "skipped_rows",
                "bucket_rows")


class GlmMoeLiteConfig:
    """Read from the published configuration's keys. ``n_routed_experts`` is
    how many routed experts THIS chip holds (the ``expert_rank``-th run of
    them); ``num_routed_experts`` the published count the router is as wide
    as (``n_routed_experts`` where it is left out: the uncut layer)."""

    def __init__(self, hidden_size: int = 64, num_attention_heads: int = 4,
                 q_lora_rank: int = 32, kv_lora_rank: int = 32,
                 qk_nope_head_dim: int = 16, qk_rope_head_dim: int = 8,
                 v_head_dim: int = 24, intermediate_size: int = 128,
                 moe_intermediate_size: int = 32, n_routed_experts: int = 8,
                 num_routed_experts: int = 0, expert_rank: int = 0,
                 num_experts_per_tok: int = 2, n_shared_experts: int = 1,
                 first_k_dense_replace: int = 1,
                 routed_scaling_factor: float = 1.8,
                 rope_theta: float = 1e6, rms_norm_eps: float = 1e-5,
                 num_hidden_layers: int = 4, vocab_size: int = 256,
                 max_context: int = 1024, seed: int = 0, attn: str = "auto"):
        total = num_routed_experts or n_routed_experts
        if qk_nope_head_dim + qk_rope_head_dim != v_head_dim:
            raise ValueError("the flash kernels take q, k and v of one head "
                             "width: qk_nope + qk_rope = v_head_dim")
        if qk_rope_head_dim % 2:
            raise ValueError("rotary pairs need an even rotary width")
        if first_k_dense_replace != 1 or num_hidden_layers < 2:
            raise ValueError("one leading dense layer, then expert layers")
        if n_shared_experts != 1:
            raise ValueError("one shared expert (shared_experts AVERAGES "
                             "several; this block adds them)")
        if total % n_routed_experts or \
                not 0 <= expert_rank < total // n_routed_experts:
            raise ValueError("the held experts are one of total / held runs")
        if num_experts_per_tok > total:
            raise ValueError("more experts a token than experts")
        self.d_model = hidden_size
        self.n_heads = num_attention_heads
        self.q_lora, self.kv_lora = q_lora_rank, kv_lora_rank
        self.nope, self.rot, self.v_dim = (qk_nope_head_dim,
                                           qk_rope_head_dim, v_head_dim)
        self.head_dim = qk_nope_head_dim + qk_rope_head_dim
        self.d_dense = intermediate_size
        # as ``expert_layer`` / ``route`` / ``shared_experts`` read them
        self.d_ff = moe_intermediate_size
        self.n_experts, self.held = total, n_routed_experts
        self.expert_lo = expert_rank * n_routed_experts
        self.top_k, self.n_shared = num_experts_per_tok, n_shared_experts
        self.route_scale = float(routed_scaling_factor)
        self.theta, self.eps = float(rope_theta), rms_norm_eps
        self.n_layers = num_hidden_layers
        self.vocab = vocab_size
        self.max_context = max_context
        self.decode_context_floor, self.prefill_context_floor = \
            context_floors(max_context, DECODE_CONTEXT_FLOOR,
                           PREFILL_CONTEXT_FLOOR)
        self.seed = seed
        self.attn = attn            # as ModelConfig.attn

    @property
    def kv_dim(self) -> int:
        """Values a token leaves in a layer's page: ``[c | k_r]``."""
        return self.kv_lora + self.rot

    def count(self, kind: str) -> int:
        """Every layer keeps pages; none scans, none has a window."""
        return self.n_layers if kind == "full" else 0

    def cache(self, cache_config, store=None) -> HybridStateCache:
        """The manager this model needs, sized by ``cache_config``: latent
        pages of every layer (one pool: ``v_dim = 0``), no ring, no
        recurrent state."""
        import jax.numpy as jnp

        return HybridStateCache(
            cache_config, self.kv_dim, 0, store=store,
            full_layers=self.n_layers, dtype=jnp.bfloat16, v_dim=0)

    # ---- weights: one generator an array, experts by published index, so
    # a chip draws only what it holds and every rank draws an expert alike
    def arrays(self, layer: Optional[int] = None):
        """(name, stream or None, shape, constant, spread) of every array
        this chip holds of ``layer`` (embedding, head and final norm where
        it is None), matrices (rows in, columns out): ``constant + spread *
        standard_normal`` from the array's own stream, the constant alone
        where the spread is 0. A matrix spreads by ``0.5 / sqrt(rows in)``
        (the embedding by ``0.1 / sqrt(d)``, the family's); the selection
        bias by 0.01; every norm weight is 1. ``wkvb`` is the published
        up-projection, a head's ``[k_nope | v]`` columns side by side."""
        d, h = self.d_model, self.n_heads
        mat = lambda rows: 0.5 / math.sqrt(rows)   # noqa: E731
        if layer is None:
            return [("embed", 10 ** 6, (self.vocab, d), 0,
                     0.5 / math.sqrt(25 * d)),
                    ("head", 10 ** 6 + 1, (self.vocab, d), 0, mat(d)),
                    ("lnf", None, (d,), 1, 0)]
        l, base, p = layer, 1000 * layer, f"l{layer}."
        table = [("wqa", 0, (d, self.q_lora)),
                 ("wqb", 1, (self.q_lora, h * self.head_dim)),
                 ("wkva", 2, (d, self.kv_dim)),
                 ("wkvb", 3, (self.kv_lora, h * (self.nope + self.v_dim))),
                 ("wo", 4, (h * self.v_dim, d))]
        if l == 0:
            table += [("wg", 5, (d, self.d_dense)), ("wu", 6, (d, self.d_dense)),
                      ("wd", 7, (self.d_dense, d))]
        else:
            table += [("router", 8, (d, self.n_experts))]
        out = [(p + n, base + sid, shape, 0, mat(shape[0]))
               for n, sid, shape in table]
        out += [(p + n, None, (w,), 1, 0)
                for n, w in (("ln1", d), ("ln2", d), ("q_ln", self.q_lora),
                             ("kv_ln", self.kv_lora))]
        if l == 0:
            return out
        out.append((p + "r_bias", base + 9, (self.n_experts,), 0, 0.01))
        ff = self.d_ff
        for kind, first, n, sid in (
                ("s", 0, self.n_shared, base + 10),
                ("e", self.expert_lo, self.held, base + 100)):
            for i in range(first, first + n):
                for j, (name, shape) in enumerate(
                        (("wg", (d, ff)), ("wu", (d, ff)), ("wd", (ff, d)))):
                    out.append((f"{p}{kind}{i}.{name}", sid + 3 * i + j,
                                shape, 0, mat(shape[0])))
        return out


class GlmMoeLiteModel(HybridServingModel):
    """Weights + the chunk and decode programs over a HybridStateCache."""

    CONTINUES_PREFILL = True
    DECODE_ROWS_BY_8 = True
    MOE_COUNTERS = ("pairs", "experts_hit", "pairs_max_expert")

    def __init__(self, config: GlmMoeLiteConfig, kv: HybridStateCache,
                 weights: Optional[Dict[str, np.ndarray]] = None):
        """``weights``: host arrays by ``config.arrays()``'s names
        (``l3.wqa``, ``l3.e2.wg``, ..., ``embed``, ``head``) that replace
        what the recipe gives (tests); everything else is drawn from
        ``config.seed``."""
        import jax.numpy as jnp

        self._init_programs(config, kv)
        # per phase: launches, the live latent rows they read (summed over
        # the launch's rows, x layers) and, for prefill, the context rows a
        # later chunk built K and V of again, the rows of its context bucket
        # the kernel never read and the bucket's rows (each x layers)
        self.mla_counters = {
            phase: dict.fromkeys(("launches",) + MLA_COUNTERS, 0)
            for phase in ("decode", "prefill")}
        cfg = config
        d, ff, n = cfg.d_model, cfg.d_ff, cfg.n_layers
        h, r, ns = cfg.n_heads, cfg.kv_lora, cfg.n_shared

        def by_head(wkvb, lo, hi):
            """(r, h x (nope + v)) -> (h, r, hi - lo): the columns ``[lo,
            hi)`` of every head's ``[k_nope | v]``."""
            w3 = wkvb.reshape(r, h, cfg.nope + cfg.v_dim)[:, :, lo:hi]
            return w3.transpose(1, 0, 2)

        bf = jnp.bfloat16
        shapes = {
            "wqkva": (n, d, cfg.q_lora + cfg.kv_dim),
            "wqb": (n, cfg.q_lora, h * cfg.head_dim),
            "wuk": (n, h, r, cfg.nope), "wuv": (n, h, r, cfg.v_dim),
            "wo": (n, h * cfg.v_dim, d), "ln1": (n, d), "ln2": (n, d),
            "q_ln": (n, cfg.q_lora), "kv_ln": (n, r),
            "s_wgu": (n - 1, d, 2 * ns * ff),
            "s_wd": (n - 1, ns * ff, d),
            # (expert layers x held, d, gate | up), (..., ff, d)
            "e_wgu": ((n - 1) * cfg.held, d, 2 * ff),
            "e_wd": ((n - 1) * cfg.held, ff, d),
            "d_wgu": (d, 2 * cfg.d_dense), "d_wd": (cfg.d_dense, d)}
        for l, take in self._drawn_layers(cfg.arrays, draw_array, weights):
            if l is None:
                # embedding and head first, float32 on their way to
                # bfloat16, before the stacks take their share of the device
                for name in ("embed", "head", "lnf"):
                    self._stage(name, take(name).astype(bf))
                st = {k: jnp.zeros(s, bf) for k, s in shapes.items()}
                st["router"] = jnp.zeros((n - 1, d, cfg.n_experts),
                                         jnp.float32)
                st["r_bias"] = jnp.zeros((n - 1, cfg.n_experts), jnp.float32)
                continue
            p = f"l{l}."
            st["wqkva"] = fill_stack(st["wqkva"], take(p + "wqa"), l)
            st["wqkva"] = fill_stack(st["wqkva"], take(p + "wkva"), l, 0,
                                     cfg.q_lora)
            wkvb = take(p + "wkvb")
            st["wuk"] = fill_stack(st["wuk"], by_head(wkvb, 0, cfg.nope), l)
            st["wuv"] = fill_stack(st["wuv"], by_head(
                wkvb, cfg.nope, cfg.nope + cfg.v_dim), l)
            del wkvb
            for k in ("wqb", "wo", "ln1", "ln2", "q_ln", "kv_ln"):
                st[k] = fill_stack(st[k], take(p + k), l)
            if l == 0:
                st["d_wgu"] = fill_stack(st["d_wgu"], take(p + "wg"))
                st["d_wgu"] = fill_stack(st["d_wgu"], take(p + "wu"), 0,
                                         cfg.d_dense)
                st["d_wd"] = fill_stack(st["d_wd"], take(p + "wd"))
                continue
            m = l - 1       # of the expert layers
            st["router"] = fill_stack(st["router"], take(p + "router"), m)
            st["r_bias"] = fill_stack(st["r_bias"], take(p + "r_bias"), m)
            # shared experts: gates side by side, then ups; downs stacked
            for i in range(ns):
                q = f"{p}s{i}."
                st["s_wgu"] = fill_stack(st["s_wgu"], take(q + "wg"), m, 0,
                                         i * ff)
                st["s_wgu"] = fill_stack(st["s_wgu"], take(q + "wu"), m, 0,
                                         (ns + i) * ff)
                st["s_wd"] = fill_stack(st["s_wd"], take(q + "wd"), m,
                                        i * ff)
            for i in range(cfg.held):
                at, q = m * cfg.held + i, f"{p}e{cfg.expert_lo + i}."
                st["e_wgu"] = fill_stack(st["e_wgu"], take(q + "wg"), at)
                st["e_wgu"] = fill_stack(st["e_wgu"], take(q + "wu"), at, 0,
                                         ff)
                st["e_wd"] = fill_stack(st["e_wd"], take(q + "wd"), at)
        for name, arr in st.items():
            self._stage(name, arr)

    # ------------------------------------------------------------- buckets
    def _chunk_buckets(self, n: int, end: int, start: int):
        """Rows to a power of two from ``CHUNK_FLOOR``; a prompt's first
        launch is its own context, a later one reads a power of two of rows
        from the configuration's floor up, and always more than its own (the
        program of a first launch builds K and V from its rows alone)."""
        c = max(min(CHUNK_FLOOR, self.config.prefill_context_floor),
                _next_pow2(n))
        if not start:
            return c, c
        return c, max(self.config.prefill_context_floor, _next_pow2(end),
                      2 * c)

    # ------------------------------------------------------------ counters
    def _note_counters(self, phase: str, tail) -> None:
        """``tail``: per expert layer its ``MOE_COUNTERS``, then the rows of
        ``MLA_COUNTERS`` a layer."""
        tail = np.asarray(tail, np.int64)
        m = self.mla_counters[phase]
        m["launches"] += 1
        for name, rows in zip(MLA_COUNTERS, tail[-len(MLA_COUNTERS):]):
            m[name] += int(rows) * self.config.n_layers
        super()._note_counters(phase, tail[:-len(MLA_COUNTERS)])

    def counters(self) -> Dict[str, object]:
        """The lane's families and ``mla``: for decode and prefill apart the
        ``launches``, the live ``latent_rows`` they read (a launch's live
        context rows times its layers: what the decode kernel fetches, once
        each) and, for prefill, the ``expanded_rows`` (context rows whose K
        and V a later chunk built again), the ``skipped_rows`` (rows of a
        later chunk's context bucket past its last row's tile, which the
        kernel never read) and the ``bucket_rows`` (the later chunks'
        context buckets), each times the layers."""
        return dict(super().counters(), mla=self.mla_counters)

    def counter_lines(self, snap) -> List[str]:
        out = super().counter_lines(snap)
        # latent attention: the live latent rows a launch read (x layers;
        # a decode step fetches each once), the context rows later chunks
        # of a prompt built K and V of again, and the rows of their context
        # buckets the kernel never read, of the buckets' rows
        mla = snap.get("mla")
        if mla:
            dec, pre = mla["decode"], mla["prefill"]
            out.append(
                f"  mla: decode launches={dec['launches']} "
                f"latent_rows={dec['latent_rows']} | prefill "
                f"launches={pre['launches']} "
                f"latent_rows={pre['latent_rows']} "
                f"expanded_rows={pre['expanded_rows']} "
                f"skipped_rows={pre['skipped_rows']} "
                f"bucket_rows={pre['bucket_rows']}")
        return out

    # ---- what both programs share of a layer
    def _down(self, w, i, x, pos):
        """The two low-rank projections of rows ``x`` at positions ``pos``:
        the queries (R, H, head width; the rotary part rotated) and the row
        the page keeps, ``[c | k_r | 0]`` (R, row width) in the pool's
        dtype."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        ql, r = cfg.q_lora, cfg.kv_lora
        with jax.named_scope("mla_down"):
            down = _mm(_rms(x, _f32(w["ln1"][i]), cfg.eps), w["wqkva"][i])
            c_q = _rms(down[:, :ql], _f32(w["q_ln"][i]), cfg.eps)
            c = _rms(down[:, ql:ql + r], _f32(w["kv_ln"][i]), cfg.eps)
            q = _mm(c_q, w["wqb"][i]).reshape(-1, cfg.n_heads, cfg.head_dim)
        with jax.named_scope("rope"):
            q = jnp.concatenate(
                [q[..., :cfg.nope], rope(q[..., cfg.nope:], pos, cfg.theta)],
                axis=-1)
            k_r = rope(down[:, None, ql + r:], pos, cfg.theta)[:, 0]
        pool = self.kv.full.k_pool     # rows allocated wider than they hold
        pad = jnp.zeros((len(x), pool.shape[-1] - cfg.kv_dim), jnp.float32)
        return q, jnp.concatenate([c, k_r, pad], axis=-1).astype(pool.dtype)

    def _dense(self, w, x):
        import jax

        cfg = self.config
        with jax.named_scope("dense_mlp"):
            gu = _mm(_rms(x, _f32(w["ln2"][0]), cfg.eps), w["d_wgu"])
            return _mm(jax.nn.silu(gu[:, :cfg.d_dense]) * gu[:, cfg.d_dense:],
                       w["d_wd"])

    def _experts(self, w, i, x, live, tile: int):
        """Expert layer ``i`` (>= 1) over rows ``x``: this chip's part of
        the routed sum plus the shared expert, and its counters."""
        import jax
        import jax.numpy as jnp

        cfg, m = self.config, i - 1
        hn = _rms(x, _f32(w["ln2"][i]), cfg.eps)
        with jax.named_scope("router"):
            idx, wts = route(cfg, hn, w["router"][m], live,
                             bias=w["r_bias"][m], scale=cfg.route_scale)
        routed, cnt = expert_layer(cfg, hn, idx, wts, w["e_wgu"], w["e_wd"],
                                   tile, first=m * cfg.held)
        y = routed + shared_experts(cfg, hn, w["s_wgu"][m], w["s_wd"][m])
        return y, expert_counts(cnt).astype(jnp.int32)

    def _layers(self, w, x, fk, live, tile: int, attend):
        """Every layer over rows ``x``: ``attend(i, x, fk) -> (attention's
        output, fk)`` is the program's own. Returns x, fk and the expert
        layers' counters (layers - 1, 3)."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        att, fk = attend(0, x, fk)
        x = x + att
        x = x + self._dense(w, x)

        def layer(i, carry):
            x, fk, counts = carry
            att, fk = attend(i, x, fk)
            x = x + att
            y, counted = self._experts(w, i, x, live, tile)
            return x + y, fk, counts.at[i - 1].set(counted)

        return jax.lax.fori_loop(
            1, cfg.n_layers, layer,
            (x, fk, jnp.zeros((cfg.n_layers - 1, len(self.MOE_COUNTERS)),
                              jnp.int32)))

    def _out(self, nxt, counts, *mla):
        import jax.numpy as jnp

        return jnp.concatenate(
            [nxt.astype(jnp.int32).reshape(-1), counts.reshape(-1),
             jnp.stack(mla).astype(jnp.int32)])

    # --------------------------------------------------------------- chunk
    def _chunk_fn(self, c_bucket: int, l_bucket: int, use_flash: bool):
        import jax
        import jax.numpy as jnp

        from brpc_tpu.tpu import pallas_ops

        cfg = self.config
        bs, h, r = self.kv.block_size, cfg.n_heads, cfg.kv_lora
        first = l_bucket == c_bucket      # a prompt's first launch
        tile = min(pallas_ops.MLA_CONTEXT_TILE, l_bucket)
        dt = _operand_dtype()

        def expand(w, i, rows):
            """K and V of every head from latent rows as stored: (H, L,
            head width) each, in the operands' dtype."""
            c = rows[:, :r]
            k_r = rows[:, r:cfg.kv_dim].astype(dt)
            with jax.named_scope("mla_expand"):
                k_n = _mm(c, w["wuk"][i], "lc,hcn->hln").astype(dt)
                v = _mm(c, w["wuv"][i], "lc,hcv->hlv").astype(dt)
            k = jnp.concatenate(
                [k_n, jnp.broadcast_to(k_r[None], (h,) + k_r.shape)], axis=-1)
            return k, v

        def attend_first(w, i, q, rows):
            k, v = expand(w, i, rows)
            if use_flash:
                out = pallas_ops.flash_attention_mha(
                    q.astype(dt).transpose(1, 0, 2)[None], k[None], v[None],
                    causal=True)
                return out[0].transpose(1, 0, 2)
            return attend_chunk_blocked(cfg, q, k.transpose(1, 0, 2),
                                        v.transpose(1, 0, 2), 0)

        def attend_later(w, i, q, ctx, start, length):
            if not use_flash:
                k, v = expand(w, i, ctx)
                return attend_chunk_blocked(cfg, q, k.transpose(1, 0, 2),
                                            v.transpose(1, 0, 2), start)
            # every head in one kernel: its K and V built a tile of rows at
            # a time in VMEM, only up to the chunk's last row
            return pallas_ops.mla_chunk_attention(
                q.astype(dt).reshape(c_bucket, h * cfg.head_dim), ctx,
                w["wuk"][i], w["wuv"][i], start, length, block_k=tile)

        def impl(w, fk, fv, wk, wv, ssm, conv, tokens, table, slot, length,
                 start):
            del slot            # no recurrent state
            t = jnp.arange(c_bucket)
            live = t < length
            row = start + t
            # where each row's latent goes: pads go to scratch row 0
            slots = jnp.where(live, table[row // bs] * bs + row % bs, 0)

            def attend(i, x, fk):
                q, lat = self._down(w, i, x, row)
                fk = fk.at[i, slots].set(lat)
                with jax.named_scope("mla_attention"):
                    if first:
                        a = attend_first(w, i, q, lat)
                    else:
                        # rows [0, start + length) back through the table,
                        # whole blocks at a time, this chunk's among them
                        ctx = _block_context(fk, i, table, bs)
                        a = attend_later(w, i, q, ctx, start, length)
                    return _mm(a.reshape(c_bucket, h * cfg.v_dim),
                               w["wo"][i]), fk

            x, fk, counts = self._layers(
                w, _f32(w["embed"][tokens]), fk, live, 128, attend)
            with jax.named_scope("head"):
                last = _rms(x[length - 1], _f32(w["lnf"]), cfg.eps)
                nxt = jnp.argmax(_mm(last[None], w["head"].T)[0])
            if first:
                skipped = bucket = 0
            else:   # the context rows the kernel never reads
                bucket = l_bucket
                skipped = (l_bucket - ((start + length - 1) // tile + 1)
                           * tile if use_flash else 0)
            return fk, fv, wk, wv, ssm, conv, self._out(
                nxt, counts, start + length, start, skipped, bucket)

        return jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))

    # -------------------------------------------------------------- decode
    def _decode_fn(self, b_bucket: int, l_bucket: int):
        import jax
        import jax.numpy as jnp

        from brpc_tpu.tpu import pallas_ops

        cfg = self.config
        bs, h = self.kv.block_size, cfg.n_heads
        rows = jnp.arange(b_bucket)
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def impl(w, fk, fv, wk, wv, ssm, conv, tokens, pos, tables,
                 ring_tables, slots):
            del ring_tables, slots    # no ring, no recurrent state
            write = tables[rows, pos // bs] * bs + pos % bs
            # a padded row (position 0: a decode row's is its prompt's
            # length at least) routes nowhere
            live = pos > 0
            lengths = pos + 1

            def attend(i, x, fk):
                q, lat = self._down(w, i, x, pos)
                fk = fk.at[i, write].set(lat)
                with jax.named_scope("mla_absorb"):
                    q_abs = _mm(q[..., :cfg.nope], w["wuk"][i],
                                "bhn,hcn->bhc")
                    pad = jnp.zeros((b_bucket, h,
                                     fk.shape[-1] - cfg.kv_dim), jnp.float32)
                    q_abs = jnp.concatenate([q_abs, q[..., cfg.nope:], pad],
                                            axis=-1)
                with jax.named_scope("mla_attention"):
                    # every live row of the layer once, where it lies
                    o = pallas_ops.mla_paged_decode(
                        q_abs, fk, i, tables, lengths, block_size=bs,
                        d_v=cfg.kv_lora, scale=scale)
                with jax.named_scope("mla_absorb"):
                    a = _mm(o, w["wuv"][i], "bhc,hcv->bhv")
                return _mm(a.reshape(b_bucket, h * cfg.v_dim),
                           w["wo"][i]), fk

            x, fk, counts = self._layers(
                w, _f32(w["embed"][tokens]), fk, live, 16, attend)
            with jax.named_scope("head"):
                last = _rms(x, _f32(w["lnf"]), cfg.eps)
                nxt = jnp.argmax(_mm(last, w["head"].T), axis=-1)
            return fk, fv, wk, wv, ssm, conv, self._out(
                nxt, counts, jnp.sum(jnp.where(live, lengths, 0)), 0, 0, 0)

        return jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))

    def layer_weights(self, l: int) -> Dict[str, "object"]:
        """Layer ``l``'s staged arrays by ``config.arrays()``'s names (no
        ``l<l>.`` in front; ``wkvb`` put together again from its split)."""
        import jax.numpy as jnp

        cfg, w = self.config, self._params
        out = {k: w[k][l] for k in ("wqb", "wo", "ln1", "ln2", "q_ln",
                                    "kv_ln")}
        out["wqa"] = w["wqkva"][l][:, :cfg.q_lora]
        out["wkva"] = w["wqkva"][l][:, cfg.q_lora:]
        out["wkvb"] = jnp.concatenate([w["wuk"][l], w["wuv"][l]],
                                      axis=-1).transpose(1, 0, 2).reshape(
            cfg.kv_lora, -1)
        if l == 0:
            out["wg"] = w["d_wgu"][:, :cfg.d_dense]
            out["wu"] = w["d_wgu"][:, cfg.d_dense:]
            out["wd"] = w["d_wd"]
            return out
        m, ff, ns = l - 1, cfg.d_ff, cfg.n_shared
        out["router"], out["r_bias"] = w["router"][m], w["r_bias"][m]
        for i in range(ns):
            out[f"s{i}.wg"] = w["s_wgu"][m][:, i * ff:(i + 1) * ff]
            out[f"s{i}.wu"] = w["s_wgu"][m][:, (ns + i) * ff:
                                            (ns + i + 1) * ff]
            out[f"s{i}.wd"] = w["s_wd"][m][i * ff:(i + 1) * ff]
        for i in range(cfg.held):
            at, e = m * cfg.held + i, cfg.expert_lo + i
            out[f"e{e}.wg"] = w["e_wgu"][at][:, :ff]
            out[f"e{e}.wu"] = w["e_wgu"][at][:, ff:]
            out[f"e{e}.wd"] = w["e_wd"][at]
        return out
