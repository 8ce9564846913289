"""A ``jamba`` decoder on the serving plane (the architecture of
AI21-Jamba2-3B): Mamba-1 layers whose ``dt``, B and C each pass an RMS norm of
their own, beside a few position-free attention layers of many query heads
over ONE key/value head; every layer followed by a gated SiLU MLP; RMSNorm;
tied head; no positional encoding anywhere.
``benchmark/blocks/jamba/reference.py`` states each equation.

Layer ``l`` is an attention layer where ``l % attn_layer_period ==
attn_layer_offset`` and a Mamba layer otherwise (``num_experts`` 1: every
feed-forward is the dense one).

Two programs over a :class:`~brpc_tpu.serving.hybrid_cache.HybridStateCache`
(recurrent slots beside as many full layers' pages as the model has attention
layers; NO window layer, so no ring), launched by what
:class:`~brpc_tpu.serving.hybrid_model.HybridServingModel` shares with the
other hybrid lanes:

- the CHUNK program (``CONTINUES_PREFILL``): rows ``[start, start + n)`` of
  one prompt. Every Mamba layer scans from the slot's state and convolves
  behind the slot's tail (zeros where ``start == 0``) and writes both back;
  every attention layer writes its rows to the pages and attends over rows
  ``[0, start + n)`` read back through the block table (the flash carry
  kernel a head with run-time offsets on the TPU, a blocked masked einsum
  elsewhere); the head runs for the chunk's last row. A whole prompt is the
  chunk with ``start == 0``; a long prompt is a chunk an engine step.
- ``decode_step``: one fused launch for the batch: per Mamba layer a conv
  tail shift and one recurrence step on the sequence's slot, per attention
  layer the row appended and the context gathered whole blocks at a time.

Consecutive Mamba layers run as ONE loop over their stacked weights (a
``fori_loop`` that indexes the stack: the layer's body compiles once a run of
layers, not once a layer), so a program of 28 layers compiles five bodies.

Storage: every weight and the K/V pools bfloat16; matmul operands rounded to
bfloat16 on the TPU (exact elsewhere), sums float32; the residual stream, the
recurrent state, the conv tail, the scan's elementwise recurrence, softplus,
the norms' statistics and the softmax float32. Greedy argmax.
"""

from __future__ import annotations

import functools
import math
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

import numpy as np

from brpc_tpu.serving.hybrid_cache import HybridStateCache
from brpc_tpu.serving.hybrid_model import (NEG, HybridServingModel,
                                           _mamba_inputs, _rms, conv_windows,
                                           decode_buckets, ssm_scan)
from brpc_tpu.serving.moe_model import _mm, _operand_dtype, draw_matrix

QUERY_BLOCK = 128             # query rows a step of the blocked attention
DECODE_CONTEXT_FLOOR = 2048   # rows of the smallest decode context bucket
PREFILL_CONTEXT_FLOOR = 8192  # rows of a later chunk's smallest context

# the stacks a Mamba layer, an attention layer and every layer's MLP read,
# each (layers of that kind, ...): name -> shape of one layer's
MAMBA = ("win", "conv_w", "conv_b", "wx", "dt_norm", "b_norm", "c_norm",
         "wdt", "b_dt", "a_log", "dd", "wout")
ATTN = ("wq", "wk", "wv", "wo")
EVERY = ("ln1", "ln2", "wg", "wu", "wd")


class JambaConfig:
    """Read from the published configuration's keys."""

    def __init__(self, hidden_size: int = 64, num_attention_heads: int = 4,
                 num_key_value_heads: int = 1, intermediate_size: int = 128,
                 num_hidden_layers: int = 8, attn_layer_period: int = 4,
                 attn_layer_offset: int = 1, mamba_d_state: int = 16,
                 mamba_d_conv: int = 4, mamba_expand: int = 2,
                 mamba_dt_rank: int = 4, rms_norm_eps: float = 1e-6,
                 vocab_size: int = 256, max_context: int = 1024,
                 seed: int = 0, attn: str = "auto"):
        h, g = num_attention_heads, num_key_value_heads
        if hidden_size % h or h % g:
            raise ValueError("heads divide the hidden size, and query heads "
                             "divide over key/value heads")
        if not 0 <= attn_layer_offset < attn_layer_period:
            raise ValueError("attn_layer_offset lies inside the period")
        self.d_model = hidden_size
        self.n_heads, self.n_kv_heads = h, g
        self.head_dim = hidden_size // h
        self.d_mlp = intermediate_size
        self.n_layers = num_hidden_layers
        self.kinds = ["full" if l % attn_layer_period == attn_layer_offset
                      else "mamba" for l in range(num_hidden_layers)]
        self.d_state, self.d_conv = mamba_d_state, mamba_d_conv
        self.d_inner = mamba_expand * hidden_size
        self.dt_rank = mamba_dt_rank
        self.eps = rms_norm_eps
        self.vocab = vocab_size
        self.max_context = max_context
        self.decode_context_floor = min(_pow2_floor(max_context),
                                        DECODE_CONTEXT_FLOOR)
        self.prefill_context_floor = min(_pow2_floor(max_context),
                                         PREFILL_CONTEXT_FLOOR)
        self.seed = seed
        self.attn = attn            # as ModelConfig.attn

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)

    def runs(self):
        """The layers as runs of one kind: (kind, first layer, first of its
        kind, how many)."""
        out, seen = [], {"mamba": 0, "full": 0}
        for l, kind in enumerate(self.kinds):
            if out and out[-1][0] == kind:
                out[-1][3] += 1
            else:
                out.append([kind, l, seen[kind], 1])
            seen[kind] += 1
        return [tuple(r) for r in out]

    def cache(self, cache_config, store=None) -> HybridStateCache:
        """The manager this model needs, sized by ``cache_config``."""
        import jax.numpy as jnp

        return HybridStateCache(
            cache_config, self.kv_dim, 0, self.count("mamba"), self.d_inner,
            self.d_state, self.d_conv, store=store,
            full_layers=self.count("full"), dtype=jnp.bfloat16)

    # ---- weights: one generator a drawn array, constants by the family's
    # initialisation
    def shapes(self) -> Dict[str, tuple]:
        """One layer's shape of every stack, (rows in, columns out)."""
        d, di, ff = self.d_model, self.d_inner, self.d_mlp
        r, n, kc = self.dt_rank, self.d_state, self.d_conv
        return {"win": (d, 2 * di), "conv_w": (kc, di), "conv_b": (di,),
                "wx": (di, r + 2 * n), "dt_norm": (r,), "b_norm": (n,),
                "c_norm": (n,), "wdt": (r, di), "b_dt": (di,),
                "a_log": (n, di), "dd": (di,), "wout": (di, d),
                "wq": (d, d), "wk": (d, self.kv_dim), "wv": (d, self.kv_dim),
                "wo": (d, d), "ln1": (d,), "ln2": (d,), "wg": (d, ff),
                "wu": (d, ff), "wd": (ff, d)}

    def drawn(self, layer: Optional[int] = None):
        """(name, stream id, shape, fan-in) of every DRAWN array of
        ``layer`` (the embedding where it is None)."""
        if layer is None:
            # 0.1 / sqrt(d): with rows as long as the layers' outputs a
            # tied head returns the token it was given
            return [("embed", 10 ** 6, (self.vocab, self.d_model),
                     25 * self.d_model)]
        sh, base = self.shapes(), 1000 * layer
        if self.kinds[layer] == "mamba":
            mixer = [("win", 0, 0), ("conv_w", 1, 0), ("conv_b", 2, 25),
                     ("wx", 3, 0), ("wdt", 4, 0), ("wout", 5, 0)]
        else:
            mixer = [("wq", 0, 0), ("wk", 1, 0), ("wv", 2, 0), ("wo", 3, 0)]
        return [(f"l{layer}.{name}", base + sid, sh[name],
                 fan or sh[name][0])
                for name, sid, fan in mixer + [("wg", 10, 0), ("wu", 11, 0),
                                               ("wd", 12, 0)]]

    def constant(self, name: str) -> np.ndarray:
        """One layer's array of a stack that is not drawn: ``A_log =
        log(1..d_state)`` in every channel, a ``dt`` bias whose softplus
        runs 1e-3..1e-1 over the channels, ``D`` and every norm weight 1."""
        shape = self.shapes()[name]
        if name == "a_log":
            return np.broadcast_to(np.log(np.arange(
                1, shape[0] + 1, dtype=np.float64))[:, None],
                shape).astype(np.float32)
        if name == "b_dt":
            dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1),
                                    shape[0]))
            return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        return np.ones(shape, np.float32)


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, n).bit_length() - 1)


# ------------------------------------------------------------ layer functions
def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def _mamba_weights(cfg, w, i):
    """Layer ``i`` (of the Mamba layers) out of the stacks, as
    ``_mamba_inputs`` names them; the vectors and the conv's weights in
    float32, the matrices as stored."""
    out = {k: w["m." + k][i] for k in MAMBA}
    for k in ("conv_w", "conv_b", "dt_norm", "b_norm", "c_norm", "b_dt",
              "a_log", "dd"):
        out[k] = _f32(out[k])
    return out


def _mlp(cfg, w, l, x):
    import jax

    with jax.named_scope("mlp"):
        h = _rms(x, _f32(w["ln2"][l]), cfg.eps)
        return x + _mm(jax.nn.silu(_mm(h, w["wg"][l])) * _mm(h, w["wu"][l]),
                       w["wd"][l])


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


class JambaModel(HybridServingModel):
    """Weights + the chunk and decode programs over a HybridStateCache."""

    CONTINUES_PREFILL = True

    def __init__(self, config: JambaConfig, kv: HybridStateCache,
                 weights: Optional[Dict[str, np.ndarray]] = None):
        """``weights``: host arrays by layer (``l3.win``, ``l3.dt_norm``,
        ..., ``embed``, ``lnf``) that replace what the recipe gives
        (tests); everything else is drawn from ``config.seed`` or set to the
        family's constant."""
        import jax
        import jax.numpy as jnp

        self._init_programs(config, kv)
        cfg, dev = config, self.store.device
        given = dict(weights or {})
        shapes = cfg.shapes()

        def handed(name, shape):
            got = np.asarray(given[name], np.float32)  # tpulint: disable=no-per-token-host-sync
            if got.shape != tuple(shape):
                raise ValueError(f"weight {name}: {got.shape} != {shape}")
            return got

        def host(name, sid, shape, fan_in):
            if name in given:
                return handed(name, shape)
            return draw_matrix(cfg.seed, sid, shape, fan_in)

        @functools.partial(jax.jit, donate_argnums=0)
        def fill(buf, part, i):
            return jax.lax.dynamic_update_index_in_dim(
                buf, part.astype(buf.dtype), i, 0)

        n_of = {"m.": cfg.count("mamba"), "a.": cfg.count("full"),
                "": cfg.n_layers}
        stacks = {pre + k: jnp.zeros((n_of[pre],) + shapes[k], jnp.bfloat16)
                  for pre, names in (("m.", MAMBA), ("a.", ATTN),
                                     ("", EVERY)) for k in names}
        # drawn a layer ahead on threads (numpy frees the interpreter),
        # staged ONE array at a time into the stacks the programs read:
        # set-up, not a step loop
        with ThreadPoolExecutor(4) as pool:
            def start(layer):
                return {m[0]: pool.submit(host, *m)
                        for m in cfg.drawn(layer)}

            drawn, ahead = start(None), start(0)
            self._stage("embed", jax.device_put(
                drawn.pop("embed").result(), dev).astype(jnp.bfloat16))
            seen = {"mamba": 0, "full": 0}
            for l, kind in enumerate(cfg.kinds):
                drawn = ahead
                ahead = start(l + 1) if l + 1 < cfg.n_layers else {}
                pre, names = (("m.", MAMBA) if kind == "mamba"
                              else ("a.", ATTN))
                for stack, k, i in ([(pre + k, k, seen[kind])
                                     for k in names]
                                    + [(k, k, l) for k in EVERY]):
                    name = f"l{l}.{k}"
                    part = (drawn.pop(name).result() if name in drawn
                            else handed(name, shapes[k]) if name in given
                            else cfg.constant(k))
                    stacks[stack] = fill(stacks[stack],
                                         jax.device_put(part, dev), i)  # tpulint: disable=no-per-op-step-dispatch
                seen[kind] += 1
        for name, arr in stacks.items():
            self._stage(name, arr)
        self._stage("lnf", jax.device_put(
            np.asarray(given.get("lnf", np.ones(cfg.d_model)), np.float32),  # tpulint: disable=no-per-token-host-sync
            dev).astype(jnp.bfloat16))

    def _decode_buckets(self, n_rows: int, tables):
        return decode_buckets(n_rows, tables, self.kv.block_size,
                              self.config.decode_context_floor)

    # ---- what both programs share of a layer
    def _mamba(self, w, i, l, x, scan):
        """Mamba layer ``i`` (layer ``l`` of the model) over rows ``x``:
        ``scan(u_in, wl) -> y`` is the program's own part (conv windows,
        recurrence, state)."""
        import jax

        cfg = self.config
        wl = _mamba_weights(cfg, w, i)
        h = _rms(x, _f32(w["ln1"][l]), cfg.eps)
        uz = _mm(h, wl["win"])
        y = scan(uz[:, :cfg.d_inner], wl)
        x = x + _mm(y * jax.nn.silu(uz[:, cfg.d_inner:]), wl["wout"])
        return _mlp(cfg, w, l, x)

    def _attention(self, w, i, l, x, attend):
        """Attention layer ``i`` (layer ``l``): ``attend(q, k, v) -> a`` is
        the program's own part (the rows' writes and the context's reads);
        q (rows, H, hd), k and v (rows, kv_dim) as stored."""
        import jax

        cfg = self.config
        pool_dt = self.kv.full.k_pool.dtype
        with jax.named_scope("full_attention"):
            h = _rms(x, _f32(w["ln1"][l]), cfg.eps)
            q = _mm(h, w["a.wq"][i]).reshape(-1, cfg.n_heads, cfg.head_dim)
            k = _mm(h, w["a.wk"][i]).astype(pool_dt)
            v = _mm(h, w["a.wv"][i]).astype(pool_dt)
            a = attend(q, k, v)
            x = x + _mm(a.reshape(-1, cfg.d_model), w["a.wo"][i])
        return _mlp(cfg, w, l, x)

    def _layers(self, w, carry, mamba, attention):
        """Every layer in order over ``carry = (x, fk, fv, ssm, conv)``: a
        run of Mamba layers as one loop over the stacks' index, an attention
        layer as it comes."""
        import jax

        for kind, l0, i0, n in self.config.runs():
            if kind == "mamba":
                carry = jax.lax.fori_loop(
                    i0, i0 + n,
                    lambda i, c, d=l0 - i0: mamba(w, i, i + d, c), carry)
            else:
                for j in range(n):
                    carry = attention(w, i0 + j, l0 + j, carry)
        return carry

    def _context(self, pool, layer: int, blocks):
        """Rows of one layer of a pool, whole BLOCKS at a time, the layer
        inside the gather's index (``pool[layer]`` first would copy the
        layer)."""
        bs = self.kv.block_size
        return pool.reshape(len(pool), -1, bs, self.config.kv_dim)[
            layer, blocks]

    # --------------------------------------------------------------- chunk
    def _chunk_fn(self, c_bucket: int, l_bucket: int, use_flash: bool):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        bs, kc = self.kv.block_size, cfg.d_conv
        scope = jax.named_scope
        attend_rows = attend_chunk_flash if use_flash else \
            attend_chunk_blocked

        def impl(w, fk, fv, wk, wv, ssm, conv, tokens, table, slot, length,
                 start):
            t = jnp.arange(c_bucket)
            live = t < length
            row = start + t
            # where each row's K/V goes: pads go to scratch row 0
            full_slots = jnp.where(live, table[row // bs] * bs + row % bs, 0)
            later = start > 0     # the slot holds this prompt's state

            def mamba(w, i, l, carry):
                x, fk, fv, ssm, conv = carry

                def scan(u_in, wl):
                    nonlocal ssm, conv
                    tail = jnp.where(later, conv[i, slot, :, 0], 0.0)
                    upad, windows = conv_windows(u_in, tail)
                    u, dt, bm, cm = _mamba_inputs(cfg, "", wl, windows, _mm)
                    with scope("ssm_scan"):
                        dt = jnp.where(live[:, None], dt, 0.0)  # pads: stay
                        s_end, ys = ssm_scan(
                            dt, u, bm, cm, -jnp.exp(wl["a_log"]),
                            jnp.where(later, ssm[0, i, slot], 0.0))
                        tail = jax.lax.dynamic_slice(
                            upad, (length, 0), (kc - 1, cfg.d_inner))
                        # the running state and the prompt's end, both: a
                        # later chunk writes over what an earlier one left
                        ssm = ssm.at[:, i, slot].set(s_end)
                        conv = conv.at[i, slot].set(
                            jnp.broadcast_to(tail[:, None], conv.shape[2:]))
                        return ys + wl["dd"] * u

                x = self._mamba(w, i, l, x, scan)
                return x, fk, fv, ssm, conv

            def attention(w, i, l, carry):
                x, fk, fv, ssm, conv = carry

                def attend(q, k, v):
                    nonlocal fk, fv
                    fk = fk.at[i, full_slots].set(k)
                    fv = fv.at[i, full_slots].set(v)
                    # rows [0, start + length) back through the table, this
                    # chunk's among them
                    return attend_rows(
                        cfg, q,
                        self._context(fk, i, table).reshape(
                            l_bucket, cfg.n_kv_heads, -1),
                        self._context(fv, i, table).reshape(
                            l_bucket, cfg.n_kv_heads, -1), start)

                x = self._attention(w, i, l, x, attend)
                return x, fk, fv, ssm, conv

            x = _f32(w["embed"][tokens])
            # the conv tails in the order the device keeps them (a view: see
            # the decode program), one slot's tile read and written a layer
            x, fk, fv, ssm, conv = self._layers(
                w, (x, fk, fv, ssm, conv.transpose(1, 2, 3, 0, 4)), mamba,
                attention)
            conv = conv.transpose(3, 0, 1, 2, 4)
            with scope("head"):
                last = _rms(x[length - 1], _f32(w["lnf"]), cfg.eps)
                nxt = jnp.argmax(_mm(last[None], w["embed"].T)[0])
            return fk, fv, wk, wv, ssm, conv, nxt[None].astype(jnp.int32)

        return jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))

    # -------------------------------------------------------------- decode
    def _decode_fn(self, b_bucket: int, l_bucket: int):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        bs = self.kv.block_size
        scope = jax.named_scope
        rows = jnp.arange(b_bucket)
        g, per, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, \
            cfg.head_dim

        def impl(w, fk, fv, wk, wv, ssm, conv, tokens, pos, tables,
                 ring_tables, slots):
            del ring_tables     # no window layer: no ring
            t = jnp.arange(l_bucket)
            full_live = (t[None, :] <= pos[:, None])[:, None, None, :]
            full_write = tables[rows, pos // bs] * bs + pos % bs

            def mamba(w, i, l, carry):
                x, fk, fv, ssm, tails = carry

                def step(u_in, wl):
                    nonlocal ssm, tails
                    windows = jnp.concatenate(
                        [tails[i], u_in[:, None, :]], axis=1)
                    u, dt, bm, cm = _mamba_inputs(cfg, "", wl, windows, _mm)
                    with scope("ssm_step"):
                        a = -jnp.exp(wl["a_log"])
                        s = (jnp.exp(dt[:, None, :] * a[None])
                             * ssm[0, i, slots]
                             + (dt * u)[:, None, :] * bm[:, :, None])
                        ssm = ssm.at[0, i, slots].set(s)
                        tails = tails.at[i].set(windows[:, 1:])
                        return jnp.sum(s * cm[:, :, None], axis=1) \
                            + wl["dd"] * u

                x = self._mamba(w, i, l, x, step)
                return x, fk, fv, ssm, tails

            def attention(w, i, l, carry):
                x, fk, fv, ssm, tails = carry

                def attend(q, k, v):
                    nonlocal fk, fv
                    fk = fk.at[i, full_write].set(k)
                    fv = fv.at[i, full_write].set(v)
                    kh = self._context(fk, i, tables).reshape(
                        b_bucket, l_bucket, g, hd)
                    vh = self._context(fv, i, tables).reshape(
                        b_bucket, l_bucket, g, hd)
                    sc = _mm(q.reshape(b_bucket, g, per, hd), kh,
                             "bgjd,bkgd->bgjk") / math.sqrt(hd)
                    prob = jax.nn.softmax(jnp.where(full_live, sc, NEG),
                                          axis=-1)
                    return _mm(prob, vh, "bgjk,bkgd->bgjd")

                x = self._attention(w, i, l, x, attend)
                return x, fk, fv, ssm, tails

            x = _f32(w["embed"][tokens])
            # every layer's conv tail of the batch's slots at once, and back
            # at once, and the loops carry the batch's tails only. The cache
            # keeps the array (copy, layer, slot, row, channel); the TPU lays
            # it out with the two COPIES innermost beside the channels (a row
            # of d_conv - 1 = 3 would waste a tile), so it is addressed in
            # that order: the transposes are views, ONE gather and ONE
            # scatter move whole tiles of the batch's (layer, slot) pairs in
            # place, and copy 1 (the prompt's end) rides along untouched.
            # Indexing copy 0 out instead lays the whole array out anew on
            # the way in and out (412 MB each way at the published sizes,
            # read in the compiled program)
            with scope("conv"):
                by_slot = (jnp.arange(conv.shape[1])[:, None], slots[None, :])
                both = conv.transpose(1, 2, 3, 0, 4)[by_slot]
                tails = both[:, :, :, 0]
            x, fk, fv, ssm, tails = self._layers(
                w, (x, fk, fv, ssm, tails), mamba, attention)
            with scope("conv"):
                conv = conv.transpose(1, 2, 3, 0, 4).at[by_slot].set(
                    both.at[:, :, :, 0].set(tails)).transpose(3, 0, 1, 2, 4)
            with scope("head"):
                last = _rms(x, _f32(w["lnf"]), cfg.eps)
                nxt = jnp.argmax(_mm(last, w["embed"].T), axis=-1)
            return fk, fv, wk, wv, ssm, conv, nxt.astype(jnp.int32)

        return jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))

    def layer_weights(self, l: int) -> Dict[str, "object"]:
        """Layer ``l``'s staged arrays out of the stacks, by name."""
        kind = self.config.kinds[l]
        i = self.config.kinds[:l].count(kind)
        pre, names = ("m.", MAMBA) if kind == "mamba" else ("a.", ATTN)
        out = {k: self._params[pre + k][i] for k in names}
        out.update({k: self._params[k][l] for k in EVERY})
        return out

