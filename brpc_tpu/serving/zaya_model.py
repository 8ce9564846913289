"""A ``zaya`` decoder on the serving plane (the architecture of ZAYA1-8B):
every layer a compressed convolutional attention (CCA: queries and keys mixed
over time by two short convs inside a narrow latent, the second value head
taken from the token before) and then ONE of 16 experts chosen by an MLP
router whose state runs down the layers beside the residual stream, or no
expert at all; both sublayers merged by learned residual scaling; RMSNorm;
tied head. ``benchmark/blocks/zaya/reference.py`` states each equation.

Two streams go down the layers: the residual ``x`` (rows, d) and the
router's ``r`` (rows, router width), ``r = 0`` before layer 0. Every layer is
alike, so each program is ONE ``fori_loop`` over the layers' stacked weights
(the body compiles once); the experts of all layers lie in one (layers x
experts, ...) stack that the grouped matmul indexes by ``layer * experts +
expert``, so no layer's experts are sliced out on the way to the kernel.

Two programs over a :class:`~brpc_tpu.serving.hybrid_cache.HybridStateCache`
(K/V pages of EVERY layer beside a recurrent slot a sequence that holds a
conv TAIL a layer and no scan state: ``d_state = 0``; no ring), launched by
what :class:`~brpc_tpu.serving.hybrid_model.HybridServingModel` shares with
the other hybrid lanes:

- the CHUNK program (``CONTINUES_PREFILL``): rows ``[start, start + n)`` of
  one prompt. Every layer convolves behind the slot's tail (the last two rows
  of ``[q~ | k~]`` and the row before's second value head; zeros where
  ``start == 0``) and writes the tail back, writes its K (mixed, normed,
  rotated) and V rows to the pages and attends over rows ``[0, start + n)``
  read back through the block table; the head runs for the chunk's last row.
- ``decode_step``: one fused launch for the batch: per layer a tail shift,
  the row appended and the context gathered whole blocks at a time.

The routed product is :func:`~brpc_tpu.serving.moe_model.expert_layer` at
``k = 1`` with every expert held; a row the router sends to output
``num_experts`` (the skip) is nobody's and computes nothing. Each launch
returns, beside its tokens and in the same sync, per layer the pairs
computed, the experts hit, the most pairs of one expert and the rows
skipped: ``moe_counters``, read by ``ServingEngine.snapshot()["moe"]``.

Storage: weights and K/V pools bfloat16, the router's arrays float32; matmul
operands rounded to bfloat16 on the TPU (exact elsewhere), sums float32; both
streams, the whole router (its products at ``highest``), the tails, both
convs, the q-k mean, the L2 norms, rotary, softmax and the norms' statistics
float32. Greedy argmax.
"""

from __future__ import annotations

import functools
import math
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

import numpy as np

from brpc_tpu.serving.hybrid_cache import HybridStateCache
from brpc_tpu.serving.hybrid_model import (NEG, HybridServingModel, _rms,
                                           conv_windows, decode_buckets)
from brpc_tpu.serving.jamba_model import (_pow2_floor, attend_chunk_blocked,
                                          attend_chunk_flash)
from brpc_tpu.serving.moe_model import _mm, expert_layer

DECODE_CONTEXT_FLOOR = 2048   # rows of the smallest decode context bucket
PREFILL_CONTEXT_FLOOR = 4096  # rows of a later chunk's smallest context
COUNTERS = ("pairs", "experts_hit", "pairs_max_expert", "skipped")

# the stacks a layer reads, each (layers, ...): bfloat16, and the router's
# float32; the experts' two stacks are (layers x experts, ...)
PROJ = ("wq", "wk", "wv1", "wv2")       # side by side in ``wqkv``
# read in float32: the convs' weights, the norms', the residual scaling
VECTORS = ("c0w", "c0b", "c1w", "c1b", "temp", "ln1", "ln2", "res_a", "res_m")
STACKS = ("wqkv", "wo") + VECTORS
ROUTER = ("r_wd", "r_bd", "r_g", "r_ln", "r_w1", "r_b1", "r_w2", "r_b2",
          "r_w3", "r_bias")
_RES = (np.asarray([1, 0, 1, 0], np.float32)[:, None],
        np.asarray([0.1, 0.001, 0.1, 0.001], np.float32)[:, None])


class ZayaConfig:
    """Read from the published configuration's keys."""

    def __init__(self, hidden_size: int = 64, num_attention_heads: int = 4,
                 num_key_value_heads: int = 2, head_dim: int = 16,
                 moe_intermediate_size: int = 64, num_experts: int = 4,
                 num_experts_per_tok: int = 1, router_hidden_size: int = 16,
                 cca_time0: int = 2, cca_time1: int = 2,
                 partial_rotary_factor: float = 0.5,
                 rope_theta: float = 5e6, rms_norm_eps: float = 1e-5,
                 num_hidden_layers: int = 4, vocab_size: int = 256,
                 max_context: int = 1024, seed: int = 0, attn: str = "auto"):
        h, g = num_attention_heads, num_key_value_heads
        if g != 2 or h % g:
            raise ValueError("the value shift makes 2 key/value heads, and "
                             "query heads divide over them")
        if num_experts_per_tok != 1:
            raise ValueError("the router picks one output a token")
        if min(cca_time0, cca_time1) < 1 or cca_time0 + cca_time1 < 3:
            raise ValueError("the convs look back one row at least")
        rot = int(head_dim * partial_rotary_factor)
        if rot % 2 or not 0 < rot <= head_dim:
            raise ValueError("rotary pairs need an even share of a head")
        self.d_model = hidden_size
        self.n_heads, self.n_kv_heads, self.head_dim = h, g, head_dim
        self.d_ff = moe_intermediate_size
        # as ``expert_layer`` reads them: every expert is held here
        self.n_experts = self.held = num_experts
        self.expert_lo = 0
        self.d_router = router_hidden_size
        self.t0, self.t1 = cca_time0, cca_time1
        self.rot, self.theta = rot, float(rope_theta)
        self.eps = rms_norm_eps
        self.n_layers = num_hidden_layers
        self.vocab = vocab_size
        self.max_context = max_context
        self.decode_context_floor = min(_pow2_floor(max_context),
                                        DECODE_CONTEXT_FLOOR)
        self.prefill_context_floor = min(_pow2_floor(max_context),
                                         PREFILL_CONTEXT_FLOOR)
        self.seed = seed
        self.attn = attn            # as ModelConfig.attn

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def qk_dim(self) -> int:
        """Channels the convs mix: the queries' and the keys' latents."""
        return self.q_dim + self.kv_dim

    @property
    def back(self) -> int:
        """Rows of ``[q~ | k~]`` before a row that its convs read."""
        return self.t0 + self.t1 - 2

    @property
    def tail_width(self) -> int:
        """Floats of one layer's tail a sequence: ``back`` rows of ``[q~ |
        k~]`` and one row of the second value head."""
        return self.back * self.qk_dim + self.head_dim

    def count(self, kind: str) -> int:
        """No layer scans (``HybridServingModel`` asks for ``mamba``)."""
        return self.n_layers if kind == "full" else 0

    def cache(self, cache_config, store=None) -> HybridStateCache:
        """The manager this model needs, sized by ``cache_config``: pages
        of every layer, and a slot whose tail is one row of ``tail_width``
        a layer with no scan state."""
        import jax.numpy as jnp

        return HybridStateCache(
            cache_config, self.kv_dim, 0, self.n_layers, self.tail_width,
            0, 2, store=store, full_layers=self.n_layers,
            dtype=jnp.bfloat16)

    # ---- weights: one generator an array, ``constant + spread * n``
    def shapes(self) -> Dict[str, tuple]:
        """One layer's shape of every array but the experts', matrices as
        (rows in, columns out)."""
        d, hd, rw = self.d_model, self.head_dim, self.d_router
        heads = self.n_heads + self.n_kv_heads
        return {"wq": (d, self.q_dim), "wk": (d, self.kv_dim),
                "wv1": (d, hd), "wv2": (d, hd), "wo": (self.q_dim, d),
                "c0w": (self.t0, self.qk_dim), "c0b": (self.qk_dim,),
                "c1w": (self.t1, heads, hd, hd), "c1b": (self.qk_dim,),
                "temp": (self.n_kv_heads,), "ln1": (d,), "ln2": (d,),
                "res_a": (4, d), "res_m": (4, d), "r_wd": (d, rw),
                "r_bd": (rw,), "r_g": (rw,), "r_ln": (rw,),
                "r_w1": (rw, rw), "r_b1": (rw,), "r_w2": (rw, rw),
                "r_b2": (rw,), "r_w3": (rw, self.n_experts + 1),
                "r_bias": (self.n_experts + 1,)}

    def arrays(self, layer: Optional[int] = None):
        """(name, stream or None, shape, constant, spread) of every array
        of ``layer`` (the embedding and the final norm where it is None):
        ``constant + spread * standard_normal`` from the array's own
        stream, no draw where the spread is 0. A matrix spreads by ``0.5 /
        sqrt(rows in)`` (the grouped conv: taps x channels in; the
        router's MLP by ``0.25 / sqrt(rows in)`` twice and ``100 /
        sqrt(rows in)`` last: two GELU layers that small spreads keep
        nearly linear prefer no output whatever the token, and the last
        restores a softmax that is not flat; its biases by 0.005; the
        attention's output projection by ``0.005 / sqrt(rows in)``: what
        attention adds is much the same for every row of a context, and at
        the other matrices' spread it drowns what tells tokens apart, after
        which every row goes to one expert); the residual scales lie about
        1 and their biases about 0 (by 0.001, for the same reason), the
        depth average's ``g`` about 0.5, the convs' taps about 0.5: none of
        them vanishes, and leaving one out shows."""
        d, ff = self.d_model, self.d_ff
        if layer is None:
            # 0.1 / sqrt(d): with rows as long as the layers' outputs a
            # tied head returns the token it was given
            return [("embed", 10 ** 6, (self.vocab, d), 0,
                     0.5 / math.sqrt(25 * d)), ("lnf", None, (d,), 1, 0)]
        sh, base, p = self.shapes(), 1000 * layer, f"l{layer}."
        mat = lambda rows, by=0.5: by / math.sqrt(rows)   # noqa: E731
        rw = self.d_router
        table = [("wq", 0, 0, mat(d)), ("wk", 1, 0, mat(d)),
                 ("wv1", 2, 0, mat(d)), ("wv2", 3, 0, mat(d)),
                 ("wo", 4, 0, mat(self.q_dim, 0.005)), ("c0w", 5, 0.5, 0.1),
                 ("c0b", 6, 0, 0.1),
                 ("c1w", 7, 0, mat(self.t1 * self.head_dim)),
                 ("c1b", 8, 0, 0.1), ("temp", 9, 1, 0.1), ("ln1", 10, 1, 0),
                 ("ln2", 11, 1, 0), ("res_a", 20) + _RES,
                 ("res_m", 21) + _RES, ("r_wd", 30, 0, mat(d)),
                 ("r_bd", 31, 0, 0.005), ("r_g", 32, 0.5, 0.1),
                 ("r_ln", 33, 1, 0), ("r_w1", 34, 0, mat(rw, 0.25)),
                 ("r_b1", 35, 0, 0.005), ("r_w2", 36, 0, mat(rw, 0.25)),
                 ("r_b2", 37, 0, 0.005), ("r_w3", 38, 0, mat(rw, 100.0)),
                 ("r_bias", 39, 0, 0.005)]
        out = [(p + name, None if isinstance(spread, (int, float))
                and spread == 0 else base + sid, sh[name], const, spread)
               for name, sid, const, spread in table]
        for e in range(self.n_experts):
            for j, (name, shape) in enumerate(
                    (("wg", (d, ff)), ("wu", (d, ff)), ("wd", (ff, d)))):
                out.append((f"{p}e{e}.{name}", base + 100 + 3 * e + j,
                            shape, 0, mat(shape[0])))
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


# ------------------------------------------------------------ layer functions
def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def rope_half(x, pos, rot: int, theta: float):
    """Rotate-half rotary on the first ``rot`` dims of a head: dims ``j`` and
    ``j + rot / 2`` turn by ``pos * theta^(-2j / rot)``, the dims from
    ``rot`` on pass. x (rows, heads, hd) float32, pos (rows,). As ``x cos +
    swap(x) sin`` at the full head width (``swap(x)[j] = -x[j + rot / 2]``,
    ``swap(x)[j + rot / 2] = x[j]``, 0 past ``rot``, a product with a fixed
    signed matrix, exact at ``highest``; angle 0 past ``rot``): no slice
    cuts the lanes of a head."""
    import jax
    import jax.numpy as jnp

    hd, half = x.shape[-1], rot // 2
    inv = np.zeros(hd, np.float64)
    inv[:half] = inv[half:rot] = theta ** (-np.arange(0, rot, 2,
                                                      dtype=np.float64) / rot)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    swap = np.zeros((hd, hd), np.float32)
    j = np.arange(half)
    swap[j + half, j], swap[j, j + half] = -1.0, 1.0
    turned = jnp.matmul(x, jnp.asarray(swap),
                        precision=jax.lax.Precision.HIGHEST)
    return (x * jnp.cos(ang)[:, None, :]
            + turned * jnp.sin(ang)[:, None, :])


def qk_mean(cfg, zz):
    """The q-k mean of rows ``zz = [q~ | k~]`` (R, qk): what is added to the
    convs' output, ``m_q = (q~ + k~ of its group) / 2`` (R, G, H / G, hd) and
    ``m_k = (mean of the group's q~ + k~) / 2`` (R, G, 1, hd)."""
    import jax.numpy as jnp

    r, g, hd = zz.shape[0], cfg.n_kv_heads, cfg.head_dim
    q_in = zz[:, :cfg.q_dim].reshape(r, g, cfg.n_heads // g, hd)
    k_in = zz[:, cfg.q_dim:].reshape(r, g, 1, hd)
    return ((q_in + k_in) / 2,
            (jnp.mean(q_in, axis=2, keepdims=True) + k_in) / 2)


def cca_mix(cfg, wl, windows, v1, v2_back, pos):
    """What both programs share of the attention sublayer between its
    projections and its scores. ``windows`` (R, back + 1, qk): each row's
    ``[q~ | k~]`` behind the ``back`` rows before it; ``v1`` (R, hd) this
    row's first value head, ``v2_back`` (R, hd) the row BEFORE's second.
    Both convs (depthwise, then grouped by head; neither pads), the q-k
    mean, each head scaled to L2 norm ``sqrt(hd)``, the keys' temperature,
    rotary. Returns q (R, H, hd), k and v (R, kv_dim), float32."""
    import jax
    import jax.numpy as jnp

    r = windows.shape[0]
    g, hd = cfg.n_kv_heads, cfg.head_dim
    per, qd = cfg.n_heads // g, cfg.q_dim
    c0w, c1w = wl["c0w"], wl["c1w"]
    c1 = wl["c1b"]
    for j in range(cfg.t1):
        c0 = wl["c0b"] + sum(c0w[a] * windows[:, j + a]
                             for a in range(cfg.t0))
        c1 = c1 + jnp.einsum(
            "rgc,gcd->rgd", c0.reshape(r, -1, hd), c1w[j],
            precision=jax.lax.Precision.HIGHEST).reshape(r, -1)
    m_q, m_k = qk_mean(cfg, windows[:, -1])
    q = _rms(c1[:, :qd].reshape(r, g, per, hd) + m_q, 1.0, cfg.eps)
    k = _rms(c1[:, qd:].reshape(r, g, 1, hd) + m_k, 1.0, cfg.eps) \
        * wl["temp"][None, :, None, None]
    with jax.named_scope("rope"):
        q = rope_half(q.reshape(r, cfg.n_heads, hd), pos, cfg.rot, cfg.theta)
        k = rope_half(k.reshape(r, g, hd), pos, cfg.rot, cfg.theta)
    return q, k.reshape(r, cfg.kv_dim), jnp.concatenate([v1, v2_back],
                                                        axis=-1)


def zaya_router(cfg, wl, h, r, live):
    """The router of one layer over rows ``h`` (R, d) and the router state
    ``r`` (R, router width) of the layer below: ``r <- h Wd + bd + g * r``
    (handed on), a two-layer GELU MLP over ``RMSNorm(r)`` to ``num_experts +
    1`` outputs, softmax, and the output with the largest ``p + bias``.
    Float32, every product at ``highest``. Returns r, (R, 1) output ids (the
    skip is ``num_experts``; ``-1`` for a row that is not live) and (R, 1)
    the chosen output's ``p``."""
    import jax
    import jax.numpy as jnp

    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    gelu = functools.partial(jax.nn.gelu, approximate=False)
    r = dot(h, wl["r_wd"]) + wl["r_bd"] + wl["r_g"] * r
    a = gelu(dot(_rms(r, wl["r_ln"], cfg.eps), wl["r_w1"]) + wl["r_b1"])
    a = gelu(dot(a, wl["r_w2"]) + wl["r_b2"])
    p = jax.nn.softmax(dot(a, wl["r_w3"]), axis=-1)
    idx = jnp.argmax(p + wl["r_bias"], axis=-1)[:, None]
    return (r, jnp.where(live[:, None], idx, -1).astype(jnp.int32),
            jnp.take_along_axis(p, idx, axis=-1))


def residual_scale(res, x, f):
    """``(a_r * x + b_r) + (a_o * f + b_o)``; ``res`` (4, d) in that
    order."""
    import jax

    with jax.named_scope("residual_scale"):
        return (res[0] * x + res[1]) + (res[2] * f + res[3])


class ZayaModel(HybridServingModel):
    """Weights + the chunk and decode programs over a HybridStateCache."""

    CONTINUES_PREFILL = True

    def __init__(self, config: ZayaConfig, kv: HybridStateCache,
                 weights: Optional[Dict[str, np.ndarray]] = None):
        """``weights``: host arrays by ``config.arrays()``'s names
        (``l3.wq``, ``l3.e2.wg``, ..., ``embed``, ``lnf``) that replace
        what the recipe gives (tests); everything else is drawn from
        ``config.seed``."""
        import jax
        import jax.numpy as jnp

        self._init_programs(config, kv)
        self.moe_counters = {"experts_held": config.held}
        self.reset_moe_counters()
        cfg, dev = config, self.store.device
        given = dict(weights or {})
        sh = cfg.shapes()
        d, ff, n_e = cfg.d_model, cfg.d_ff, cfg.n_experts

        def host(name, stream, shape, const, spread):
            if name not in given:
                return draw_array(cfg.seed, stream, shape, const, spread)
            got = np.asarray(given[name], np.float32)  # tpulint: disable=no-per-token-host-sync
            if got.shape != tuple(shape):
                raise ValueError(f"weight {name}: {got.shape} != {shape}")
            return got

        @functools.partial(jax.jit, donate_argnums=0)
        def fill(buf, part, *start):
            return jax.lax.dynamic_update_slice(buf, part.astype(buf.dtype),
                                                start)

        # drawn a layer ahead on threads (numpy frees the interpreter),
        # staged ONE array at a time into the stacks the programs read:
        # set-up, not a step loop
        with ThreadPoolExecutor(4) as pool:
            def start(layer):
                return {a[0]: pool.submit(host, *a)
                        for a in cfg.arrays(layer)}

            def take(drawn, name):
                return jax.device_put(drawn.pop(name).result(), dev)  # tpulint: disable=no-per-op-step-dispatch

            drawn, ahead = start(None), start(0)
            # the embedding first, float32 on its way to bfloat16, BEFORE
            # the stacks take their share of the device (8.4 GB at the
            # published sizes): the device's peak is then the programs',
            # not the staging's
            for name in ("embed", "lnf"):
                self._stage(name, take(drawn, name).astype(jnp.bfloat16))
            n = cfg.n_layers
            wide = sum(sh[k][1] for k in PROJ)
            stacks = {k: jnp.zeros(
                (n,) + ((d, wide) if k == "wqkv" else sh[k]), jnp.bfloat16)
                for k in STACKS}
            stacks.update({k: jnp.zeros((n,) + sh[k], jnp.float32)
                           for k in ROUTER})
            # (layers x experts, d, gate | up), (layers x experts, ff, d)
            stacks["e_wgu"] = jnp.zeros((n * n_e, d, 2 * ff), jnp.bfloat16)
            stacks["e_wd"] = jnp.zeros((n * n_e, ff, d), jnp.bfloat16)
            for l in range(n):
                drawn, p = ahead, f"l{l}."
                ahead = start(l + 1) if l + 1 < n else {}
                col = 0
                for k in PROJ:
                    stacks["wqkv"] = fill(stacks["wqkv"],
                                          take(drawn, p + k)[None], l, 0, col)
                    col += sh[k][1]
                for k in STACKS[1:] + ROUTER:
                    part = take(drawn, p + k)[None]
                    stacks[k] = fill(stacks[k], part, l,
                                     *(0,) * (part.ndim - 1))
                for e in range(n_e):
                    i, q = l * n_e + e, f"{p}e{e}."
                    stacks["e_wgu"] = fill(stacks["e_wgu"],
                                           take(drawn, q + "wg")[None],
                                           i, 0, 0)
                    stacks["e_wgu"] = fill(stacks["e_wgu"],
                                           take(drawn, q + "wu")[None],
                                           i, 0, ff)
                    stacks["e_wd"] = fill(stacks["e_wd"],
                                          take(drawn, q + "wd")[None],
                                          i, 0, 0)
        for name, arr in stacks.items():
            self._stage(name, arr)

    def _decode_buckets(self, n_rows: int, tables):
        return decode_buckets(n_rows, tables, self.kv.block_size,
                              self.config.decode_context_floor)

    def reset_moe_counters(self) -> None:
        for phase in ("decode", "prefill"):
            self.moe_counters[phase] = dict.fromkeys(
                COUNTERS + ("layer_launches",), 0)

    def _note_counters(self, phase: str, tail) -> None:
        c = self.moe_counters[phase]
        per_layer = np.asarray(tail, np.int64).reshape(-1, len(COUNTERS))
        c["layer_launches"] += len(per_layer)
        for name, total in zip(COUNTERS, per_layer.sum(axis=0)):
            c[name] += int(total)

    # ---- what both programs share of a layer
    @staticmethod
    def _layer_weights(w, i):
        """Layer ``i`` out of the stacks (the experts' stay whole): the
        vectors and the convs' weights float32, the matrices as stored."""
        wl = {k: w[k][i] for k in STACKS + ROUTER}
        wl.update({k: _f32(wl[k]) for k in VECTORS})
        return wl

    def _layer(self, w, i, x, r, counts, live, pos, tile, mix, attend):
        """Layer ``i`` over rows ``x`` with the router state ``r`` of the
        layer below. ``mix(zz, v2) -> (windows, v2_back)`` and ``attend(q,
        k, v) -> a`` are the program's own parts: the tail before and after
        these rows, the rows' writes and the context's reads."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        wl = self._layer_weights(w, i)
        qk, hd = cfg.qk_dim, cfg.head_dim
        with jax.named_scope("cca_mix"):
            proj = _mm(_rms(x, wl["ln1"], cfg.eps), wl["wqkv"])
            windows, v2_back = mix(proj[:, :qk], proj[:, qk + hd:])
            q, k, v = cca_mix(cfg, wl, windows, proj[:, qk:qk + hd],
                              v2_back, pos)
        with jax.named_scope("cca_attention"):
            a = attend(q, k, v)
            att = _mm(a.reshape(-1, cfg.q_dim), wl["wo"])
        x = residual_scale(wl["res_a"], x, att)
        h = _rms(x, wl["ln2"], cfg.eps)
        with jax.named_scope("zaya_router"):
            r, idx, p_e = zaya_router(cfg, wl, h, r, live)
        y, cnt = expert_layer(cfg, h, idx, p_e, w["e_wgu"], w["e_wd"], tile,
                              first=i * cfg.held)
        x = residual_scale(wl["res_m"], x, y)
        counted = jnp.stack([jnp.sum(cnt), jnp.sum(cnt > 0), jnp.max(cnt),
                             jnp.sum(idx == cfg.n_experts)])
        return x, r, counts.at[i].set(counted.astype(jnp.int32))

    def _context(self, pool, layer, blocks):
        """Rows of one layer of a pool, whole BLOCKS at a time, the layer
        inside the gather's index (``pool[layer]`` first would copy the
        layer)."""
        bs = self.kv.block_size
        return pool.reshape(len(pool), -1, bs, self.config.kv_dim)[
            layer, blocks]

    def _start(self, w, tokens):
        import jax.numpy as jnp

        cfg = self.config
        return (_f32(w["embed"][tokens]),
                jnp.zeros((len(tokens), cfg.d_router), jnp.float32),
                jnp.zeros((cfg.n_layers, len(COUNTERS)), jnp.int32))

    # --------------------------------------------------------------- chunk
    def _chunk_fn(self, c_bucket: int, l_bucket: int, use_flash: bool):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        bs, g, hd = self.kv.block_size, cfg.n_kv_heads, cfg.head_dim
        back, qk = cfg.back, cfg.qk_dim
        pool_dt = self.kv.full.k_pool.dtype
        attend_rows = attend_chunk_flash if use_flash else \
            attend_chunk_blocked

        def impl(w, fk, fv, wk, wv, ssm, conv, tokens, table, slot, length,
                 start):
            t = jnp.arange(c_bucket)
            live = t < length
            row = start + t
            # where each row's K/V goes: pads go to scratch row 0
            full_slots = jnp.where(live, table[row // bs] * bs + row % bs, 0)
            later = start > 0     # the slot holds this prompt's tail

            def layer(i, carry):
                x, r, fk, fv, conv, counts = carry

                def mix(zz, v2):
                    nonlocal conv
                    tail = jnp.where(later, conv[0, i, slot, 0], 0.0)
                    zpad, windows = conv_windows(
                        zz, tail[:back * qk].reshape(back, qk))
                    v2pad = jnp.concatenate([tail[None, back * qk:], v2])
                    after = jnp.concatenate([
                        jax.lax.dynamic_slice(zpad, (length, 0),
                                              (back, qk)).reshape(-1),
                        jax.lax.dynamic_slice(v2pad, (length, 0),
                                              (1, hd)).reshape(-1)])
                    # the running tail and the prompt's end, both: a later
                    # chunk writes over what an earlier one left
                    conv = conv.at[:, i, slot, 0].set(
                        jnp.broadcast_to(after, (2,) + after.shape))
                    return windows, v2pad[:-1]

                def attend(q, k, v):
                    nonlocal fk, fv
                    fk = fk.at[i, full_slots].set(k.astype(pool_dt))
                    fv = fv.at[i, full_slots].set(v.astype(pool_dt))
                    # rows [0, start + length) back through the table, this
                    # chunk's among them
                    return attend_rows(
                        cfg, q,
                        self._context(fk, i, table).reshape(l_bucket, g, hd),
                        self._context(fv, i, table).reshape(l_bucket, g, hd),
                        start)

                x, r, counts = self._layer(w, i, x, r, counts, live, row,
                                           128, mix, attend)
                return x, r, fk, fv, conv, counts

            x, r, counts = self._start(w, tokens)
            x, r, fk, fv, conv, counts = jax.lax.fori_loop(
                0, cfg.n_layers, layer, (x, r, fk, fv, conv, counts))
            with jax.named_scope("head"):
                last = _rms(x[length - 1], _f32(w["lnf"]), cfg.eps)
                nxt = jnp.argmax(_mm(last[None], w["embed"].T)[0])
            out = jnp.concatenate([nxt[None].astype(jnp.int32),
                                   counts.reshape(-1)])
            return fk, fv, wk, wv, ssm, conv, out

        return jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))

    # -------------------------------------------------------------- decode
    def _decode_fn(self, b_bucket: int, l_bucket: int):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        bs = self.kv.block_size
        rows = jnp.arange(b_bucket)
        g, per, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, \
            cfg.head_dim
        cut = cfg.back * cfg.qk_dim
        pool_dt = self.kv.full.k_pool.dtype

        def impl(w, fk, fv, wk, wv, ssm, conv, tokens, pos, tables,
                 ring_tables, slots):
            del ring_tables     # no window layer: no ring
            t = jnp.arange(l_bucket)
            full_live = (t[None, :] <= pos[:, None])[:, None, :]
            full_write = tables[rows, pos // bs] * bs + pos % bs
            # a padded row (position 0: a decode row's is its prompt's
            # length at least) routes nowhere
            live = pos > 0
            # (1, g, 1, g, 1): which key/value head's lanes a query reads
            own = jnp.eye(g, dtype=jnp.float32)[None, :, None, :, None]

            def layer(i, carry):
                x, r, fk, fv, tails, counts = carry

                def mix(zz, v2):
                    nonlocal tails
                    tail = tails[i]
                    windows = jnp.concatenate(
                        [tail[:, :cut].reshape(b_bucket, cfg.back, -1),
                         zz[:, None]], axis=1)
                    tails = tails.at[i].set(jnp.concatenate(
                        [windows[:, 1:].reshape(b_bucket, cut), v2],
                        axis=-1))
                    return windows, tail[:, cut:]

                def attend(q, k, v):
                    nonlocal fk, fv
                    fk = fk.at[i, full_write].set(k.astype(pool_dt))
                    fv = fv.at[i, full_write].set(v.astype(pool_dt))
                    # the context is read as it was gathered, (rows, both
                    # heads' lanes): a query reads the whole row with zeros
                    # in the other head's lanes, and keeps its own head's
                    # half of the output. Heads as a batch axis of the
                    # product would lay every gathered context out anew
                    # (two copies of it a layer, read in the compiled
                    # program)
                    kc = self._context(fk, i, tables).reshape(
                        b_bucket, l_bucket, g * hd)
                    vc = self._context(fv, i, tables).reshape(
                        b_bucket, l_bucket, g * hd)
                    qw = (q.reshape(b_bucket, g, per, 1, hd)
                          * own).reshape(b_bucket, g * per, g * hd)
                    sc = _mm(qw, kc, "bjd,bkd->bjk") / math.sqrt(hd)
                    prob = jax.nn.softmax(jnp.where(full_live, sc, NEG),
                                          axis=-1)
                    a = _mm(prob, vc, "bjk,bkd->bjd")
                    return jnp.sum(a.reshape(b_bucket, g, per, g, hd) * own,
                                   axis=3)

                x, r, counts = self._layer(w, i, x, r, counts, live, pos,
                                           16, mix, attend)
                return x, r, fk, fv, tails, counts

            x, r, counts = self._start(w, tokens)
            # every layer's tail of the batch's slots at once, and back at
            # once: the loop carries the batch's tails only, and the copy at
            # the prompt's end (index 1) is not touched. (The compiler lays
            # the whole array out anew for the gather and back after the
            # scatter, 42 MB each way at the published sizes: read in the
            # compiled program; addressing it copies-innermost, as
            # ``jamba_model`` does its wider tails, changes nothing here.)
            by_slot = (0, jnp.arange(cfg.n_layers)[:, None], slots[None, :],
                       0)
            with jax.named_scope("cca_mix"):
                tails = conv[by_slot]
            x, r, fk, fv, tails, counts = jax.lax.fori_loop(
                0, cfg.n_layers, layer, (x, r, fk, fv, tails, counts))
            with jax.named_scope("cca_mix"):
                conv = conv.at[by_slot].set(tails)
            with jax.named_scope("head"):
                last = _rms(x, _f32(w["lnf"]), cfg.eps)
                nxt = jnp.argmax(_mm(last, w["embed"].T), axis=-1)
            out = jnp.concatenate([nxt.astype(jnp.int32),
                                   counts.reshape(-1)])
            return fk, fv, wk, wv, ssm, conv, out

        return jax.jit(impl, donate_argnums=(1, 2, 3, 4, 5, 6))

    def layer_weights(self, l: int) -> Dict[str, "object"]:
        """Layer ``l``'s staged arrays by ``config.arrays()``'s names (no
        ``l<l>.`` in front)."""
        cfg, w = self.config, self._params
        sh = cfg.shapes()
        out = {k: w[k][l] for k in STACKS[1:] + ROUTER}
        col = 0
        for k in PROJ:
            out[k] = w["wqkv"][l][:, col:col + sh[k][1]]
            col += sh[k][1]
        for e in range(cfg.n_experts):
            i = l * cfg.n_experts + e
            out[f"e{e}.wg"] = w["e_wgu"][i][:, :cfg.d_ff]
            out[f"e{e}.wu"] = w["e_wgu"][i][:, cfg.d_ff:]
            out[f"e{e}.wd"] = w["e_wd"][i]
        return out
