"""Block ``zaya``'s plain reference: what ``correct`` is decided against.

The ``zaya`` decoder (ZAYA1-8B; configuration keys of
https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json; the mechanisms
as published in arXiv:2510.04476, Compressed Convolutional Attention, and
arXiv:2511.17127, the ZAYA1 report) in straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``: the convs and the value shift over
the WHOLE sequence from zeros (no tail, no chunk, no cache), attention as
masked einsums over blocks of query rows, every expert over every row with
the router's choice as a mask, no kernel, no sorting. One jitted function a
layer, called layer by layer with that layer's weights, its matmuls over
blocks of rows. It imports nothing of the program and takes nothing the
program made: it draws its own weights from the seed by the recipe the
configuration states (``arrays`` / ``draw`` below).

The layers. ``d`` hidden, ``H`` query heads over ``G`` key/value heads of
``hd``, ``E`` experts of width ``ff``, router width ``rw``, 0-based layer
``l``; two streams go down the layers, the residual ``x`` (S, d) and the
router's ``r`` (S, rw), ``r = 0`` before layer 0. Every layer is an attention
sublayer, then an expert sublayer, each merged by learned residual scaling:
``x <- (a_r * x + b_r) + (a_o * f(RMSNorm(x)) + b_o)`` (four d-vectors a
sublayer; ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``).

- **Attention (CCA)**, ``u = RMSNorm(x)``: ``q~ = u Wq`` (S, H, hd), ``k~ = u
  Wk`` (S, G, hd); value shift ``v_t = [u_t Wv1 | u_{t-1} Wv2]`` (G = 2 heads
  of hd: one from this token, one from the token before, ``u_{-1} = 0``);
  conv mixing over ``z = [q~ | k~]`` (S, (H + G) hd) behind ``cca_time0 +
  cca_time1 - 2`` rows of zeros: a depthwise conv of ``cca_time0`` taps with
  bias, then a conv of ``cca_time1`` taps grouped by head (H + G groups, hd ->
  hd each) with bias, neither padded again (row t sees rows t - 2 .. t);
  q-k mean ``m_q = (q~ + repeat(k~)) / 2``, ``m_k = (mean over a group's
  query heads of q~ + k~) / 2``; ``q = conv(z)[: H hd] + m_q``, ``k =
  conv(z)[H hd :] + m_k``; each head of q and k scaled to L2 norm
  ``sqrt(hd)`` (``x / sqrt(mean(x^2) + eps)``), k times a learned scalar a
  key/value head; rotary in the rotate-half form on the first
  ``partial_rotary_factor * hd`` dims of each head (pairs ``(j, j + rot /
  2)``, angle ``p * theta^(-2j / rot)``, float32); causal softmax of ``q k' /
  sqrt(hd)``, query head i over key/value head ``i // (H / G)``; ``out = o
  Wo``. The cache of a serving program holds K after all of that and V; its
  tail is the last two rows of z and ``u_t Wv2`` (``state["tail0"]``).
- **Experts**, ``h = RMSNorm(x)``. Router, float32 with no operand rounded:
  ``r <- h Wd + bd + g * r`` (this r goes to the next layer); ``s = gelu(
  gelu(RMSNorm_rw(r) W1 + b1) W2 + b2) W3`` (E + 1 wide; gelu in its erf
  form); ``p = softmax(s)``; chosen ``e = argmax(p + bias)``. ``e < E``:
  ``f = p_e (silu(h Wg_e) * (h Wu_e)) Wd_e``; ``e = E``: ``f = 0`` (the skip
  output computes nothing).
- After the last layer a final RMSNorm, then logits ``= x Embed'`` (tied).

Arithmetic (``mode``). Every weight but the router's is a bfloat16 VALUE in
every mode, as the configuration stores it, and so are the K/V rows (K after
its rotation); the router's arrays are float32. ``bfloat16_operands`` rounds
every matmul operand to bfloat16 and sums in float32 (the TPU's default
precision, which the configuration states), the router, the grouped conv and
the rotation excepted; ``float32`` rounds no operand. The CONTROL
(``control=True``) is the step below: matrices, embedding and K/V rows held
in float8 (e4m3 by ``reduce_precision``, one scale a tensor).

**Routing and the comparison**, after the ``cohere2moe`` block's. Two correct
computations with bfloat16 operands part by rounding alone: a sum in another
order flips one stored K element, a row that attends to it moves by 1e-3
(what attention adds is the remainder of an average), the next rounding of
that row flips more: some 1e-3 a layer, 2-4e-2 twenty layers deep (measured
on the chip and reproduced on the CPU: PERF.md section 4). A top-1 router
turns that into ANOTHER EXPERT for a share of the rows that grows with
depth, and such a row then differs by an expert's whole output in every
layer after: a quarter of the last layer's rows, one served token in a
hundred. Margins foresee only part of it (a row routed otherwise had a wide
margin as often as not), so the deep numbers are held to what most rows do:

- the reference reports every row's MARGIN by layer (largest ``p + bias``
  less second), scaled by depth (``margin * layers / (l + 1)``: the sides
  part more the deeper the layer). A row is THIN where a scaled margin is
  under ``route_margin``; for the last layer's K/V rows also where one of
  the ``cca_time0 + cca_time1 - 2`` rows before it is (the convs read them),
  in any layer before the last. The share of thin rows among a request's
  prompt rows is itself compared (``route_thin_share_prefill``).
- ``kvL_gap_*`` is the MEDIAN of the rows' own distances over the rows that
  are not thin: most rows keep their experts and lie within rounding; a
  fault in the expert sublayer or in the router's hand-down moves most rows.
- a logits row is returned flat (``logit_gap``, a maximum, reads 0 for it)
  where the row is thin at ``LOGIT_MARGINS`` times the margin, and for the
  ``FLIP_SHARE`` of a request's served rows whose served token lies farthest
  under the reference's best: rows routed otherwise, as long as they are
  few; more of them than that is no rounding, and shows.

Layer 0's K/V rows and tail depend on no routing and are compared whole and
to the rounding of single elements.
"""

from __future__ import annotations

import functools
import math
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness.reference import padded

MODES = ("float32", "bfloat16_operands")
QUERY_BLOCK = 256   # rows of queries whose scores are held at a time
ROW_BLOCK = 2048    # rows a matmul of a layer takes at a time
WIDE = 1e9          # the margin where no layer is counted
LOGIT_MARGINS = 2.0  # a logits row is flat where a margin is within this many
FLIP_SHARE = 0.1     # of a request's served rows may be routed otherwise
ROW_PERCENTILE = 50  # of the last layer's rows' own distances


# ------------------------------------------------------------------ geometry
def sizes(m: dict) -> dict:
    """The widths every function here needs, from the configuration's keys."""
    h, g, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    if g != 2 or m["num_experts_per_tok"] != 1:
        raise ValueError("zaya: the value shift makes 2 key/value heads, "
                         "and the router picks one output")
    return {"d": m["hidden_size"], "h": h, "g": g, "hd": hd, "qd": h * hd,
            "kvd": g * hd, "qk": (h + g) * hd, "ff": m["moe_intermediate_size"],
            "e": m["num_experts"], "rw": m["router_hidden_size"],
            "t0": m["cca_time0"], "t1": m["cca_time1"],
            "back": m["cca_time0"] + m["cca_time1"] - 2,
            "rot": int(hd * m["partial_rotary_factor"]),
            "theta": float(m["rope_theta"]), "eps": m["rms_norm_eps"],
            "v": m["vocab_size"], "layers": m["num_hidden_layers"]}


def tail_width(z: dict) -> int:
    """Floats of one layer's tail a sequence: ``back`` rows of z and one of
    the shifted value head."""
    return z["back"] * z["qk"] + z["hd"]


# ------------------------------------------------------------------- weights
# a layer's arrays: name -> (stream within the layer, shape(z), constant,
# spread); ``_mat(by)`` is a matrix's spread, ``by / sqrt(its fan-in)``.
# ``res_*``: the rows a_r, b_r, a_o, b_o of a sublayer's residual scaling.
def _mat(by: float = 0.5):
    return ("over the root of the fan-in", by)


_RES = (np.asarray([1, 0, 1, 0], np.float32)[:, None],
        np.asarray([0.1, 0.001, 0.1, 0.001], np.float32)[:, None])
LAYER = {
    "wq": (0, lambda z: (z["d"], z["qd"]), 0, _mat()),
    "wk": (1, lambda z: (z["d"], z["kvd"]), 0, _mat()),
    "wv1": (2, lambda z: (z["d"], z["hd"]), 0, _mat()),
    "wv2": (3, lambda z: (z["d"], z["hd"]), 0, _mat()),
    "wo": (4, lambda z: (z["qd"], z["d"]), 0, _mat(0.005)),
    "c0w": (5, lambda z: (z["t0"], z["qk"]), 0.5, 0.1),
    "c0b": (6, lambda z: (z["qk"],), 0, 0.1),
    "c1w": (7, lambda z: (z["t1"], z["h"] + z["g"], z["hd"], z["hd"]), 0,
            _mat()),
    "c1b": (8, lambda z: (z["qk"],), 0, 0.1),
    "temp": (9, lambda z: (z["g"],), 1, 0.1),
    "ln1": (10, lambda z: (z["d"],), 1, 0),
    "ln2": (11, lambda z: (z["d"],), 1, 0),
    "res_a": (20, lambda z: (4, z["d"]), *_RES),
    "res_m": (21, lambda z: (4, z["d"]), *_RES),
    "r_wd": (30, lambda z: (z["d"], z["rw"]), 0, _mat()),
    "r_bd": (31, lambda z: (z["rw"],), 0, 0.005),
    "r_g": (32, lambda z: (z["rw"],), 0.5, 0.1),
    "r_ln": (33, lambda z: (z["rw"],), 1, 0),
    "r_w1": (34, lambda z: (z["rw"], z["rw"]), 0, _mat(0.25)),
    "r_b1": (35, lambda z: (z["rw"],), 0, 0.005),
    "r_w2": (36, lambda z: (z["rw"], z["rw"]), 0, _mat(0.25)),
    "r_b2": (37, lambda z: (z["rw"],), 0, 0.005),
    "r_w3": (38, lambda z: (z["rw"], z["e"] + 1), 0, _mat(100.0)),
    "r_bias": (39, lambda z: (z["e"] + 1,), 0, 0.005),
}
ROUTER = tuple(k for k in LAYER if k.startswith("r_"))   # float32 as stored
EXPERT = ("wg", "wu", "wd")      # stream 100 + 3 e + (0, 1, 2)


def _fan_in(name: str, shape) -> int:
    """Rows in of a matrix; the grouped conv's taps x channels in."""
    return shape[0] * shape[2] if name == "c1w" else shape[0]


def arrays(m: dict) -> list:
    """(name, stream or None, shape, constant, spread) of every array:
    ``embed``, then ``l<l>.<name>`` layer by layer (the experts
    ``l<l>.e<e>.wg`` / ``wu`` / ``wd`` by index), then ``lnf``. An array is
    ``constant + spread * n`` with n ``Generator(Philox(key=[seed,
    stream]))``'s ``standard_normal`` float32 in row-major order (no draw
    where the spread is 0). A matrix (rows in, columns out) has constant 0
    and spread ``0.5 / sqrt(rows in)`` (the grouped conv: taps x channels
    in; the router's MLP ``0.25 / sqrt(rows in)`` twice and ``100 /
    sqrt(rows in)`` last: two GELU layers at small spreads are nearly
    linear, so no output is preferred whatever the token, and the last
    restores a softmax that is not flat; the router's biases and the
    balancing biases spread by 0.005 for the same reason; the attention's
    output projection ``0.005 / sqrt(rows in)`` and the residual biases 0.001:
    what attention adds is much the same for every row of a context and the
    biases are the same for all, and at the spread of the other matrices
    they drown what tells tokens apart within a few layers, after which
    every row goes to the same expert (and what attention adds is the
    remainder of an average over hundreds of rows, which two roundings of
    the same softmax disagree on by 1e-2); the embedding ``0.1 / sqrt(d)``: with
    rows as long as the layers' outputs a tied head returns the token it was
    given). Streams: the embedding 10^6; layer l:
    1000 l + the first entry of ``LAYER``, expert e: 1000 l + 100 + 3 e +
    (0 gate, 1 up, 2 down)."""
    z = sizes(m)
    d, ff = z["d"], z["ff"]
    out = [("embed", 10 ** 6, (z["v"], d), 0, 0.5 / math.sqrt(25 * d))]
    for l in range(z["layers"]):
        for name, (sid, shape, const, spread) in LAYER.items():
            shape = shape(z)
            if isinstance(spread, tuple):
                spread = spread[1] / math.sqrt(_fan_in(name, shape))
            stream = 1000 * l + sid
            if isinstance(spread, (int, float)) and spread == 0:
                stream = None
            out.append((f"l{l}.{name}", stream, shape, const, spread))
        for e in range(z["e"]):
            for j, (name, shape) in enumerate(
                    (("wg", (d, ff)), ("wu", (d, ff)), ("wd", (ff, d)))):
                out.append((f"l{l}.e{e}.{name}", 1000 * l + 100 + 3 * e + j,
                            shape, 0, 0.5 / math.sqrt(shape[0])))
    return out + [("lnf", None, (d,), 1, 0)]


def draw(seed: int, stream, shape, const, spread) -> np.ndarray:
    """One float32 array of the recipe."""
    if stream is None:
        return np.broadcast_to(np.float32(const), shape).copy()
    rng = np.random.Generator(np.random.Philox(key=[seed, stream]))
    out = rng.standard_normal(shape, dtype=np.float32)
    out *= np.asarray(spread, np.float32)
    out += np.asarray(const, np.float32)
    return out


def bf16_bits(x: np.ndarray) -> np.ndarray:
    """float32 -> the bits of the nearest bfloat16 (ties to even), uint16."""
    u = np.ascontiguousarray(x).view(np.uint32)
    return ((u + (np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))))
            >> np.uint32(16)).astype(np.uint16)


def is_float32(name: str) -> bool:
    """The router's arrays are stored float32; everything else bfloat16."""
    return name.split(".")[-1] in ROUTER


def draw_weights(seed: int, m: dict, threads: int = 3) -> dict:
    """Every array on the host: bfloat16 bits (uint16), the router's
    float32."""
    def one(spec):
        w = draw(seed, *spec[1:])
        return spec[0], (w if is_float32(spec[0]) else bf16_bits(w))

    with ThreadPoolExecutor(threads) as pool:
        return dict(pool.map(one, arrays(m)))


class HostWeights(threading.Thread):
    """``draw_weights`` on threads of its own, started at once: the draw
    takes as long as the program's own and needs no chip."""

    def __init__(self, seed: int, m: dict):
        super().__init__(daemon=True)
        self.seed, self.m, self.weights = seed, m, None
        self.start()

    def run(self):
        self.weights = draw_weights(self.seed, self.m)

    def get(self) -> dict:
        self.join()
        return self.weights


# ------------------------------------------------------------------- forward
def _b16(x):
    """x as bfloat16 holds it, in float32 (``reduce_precision``: a pair of
    converts is what XLA's excess precision may fold away)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _q8(x):
    """x as float8 (e4m3: 4 exponent bits, 3 of mantissa) would hold it, with
    one scale for the tensor (its largest magnitude mapped to the largest
    finite value of that form, 240), back in float32. By
    ``reduce_precision``: on the TPU the compiler folds a pair of converts
    through float8 away as excess precision, and the control then computes
    what the reference does (measured: PERF.md section 4)."""
    import jax
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _by_rows(fn, *xs):
    """``fn`` over blocks of ``ROW_BLOCK`` rows of ``xs`` (all rows at once
    where the block does not divide them)."""
    import jax

    s = xs[0].shape[0]
    if s <= ROW_BLOCK or s % ROW_BLOCK:
        return fn(*xs)
    out = jax.lax.map(lambda b: fn(*b), tuple(
        x.reshape((s // ROW_BLOCK, ROW_BLOCK) + x.shape[1:]) for x in xs))
    return out.reshape((s,) + out.shape[2:])


def rope_half(x, pos, rot: int, theta: float):
    """Rotate-half rotary over the first ``rot`` dims of the last axis of x
    (rows, heads, hd): dims ``j`` and ``j + rot / 2`` turn by ``pos *
    theta^(-2j / rot)``; the dims from ``rot`` on pass."""
    import jax.numpy as jnp

    half = rot // 2
    inv = jnp.asarray(theta ** (-np.arange(0, rot, 2, dtype=np.float64)
                                / rot), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


@functools.lru_cache(maxsize=None)
def _fns(model_key: str, mode: str, control: bool):
    """The jitted pieces in one arithmetic: ``embed(E, tokens)``,
    ``layer(w, x, r, marks)`` and ``head(lnf, E, x, rows)``."""
    import json

    import jax
    import jax.numpy as jnp

    if mode not in MODES:
        raise ValueError(f"reference: unknown mode {mode!r}")
    z = sizes(json.loads(model_key))
    qo = (lambda x: x) if mode == "float32" else _b16     # matmul operands
    f32 = lambda x: x.astype(jnp.float32)                 # noqa: E731
    wt = (lambda x: _q8(f32(x))) if control else f32      # a stored matrix
    st = _q8 if control else _b16                         # stored K/V rows
    h_n, g, hd, per = z["h"], z["g"], z["hd"], z["h"] // z["g"]
    qd, qk, back = z["qd"], z["qk"], z["back"]

    def mm(a, b):
        return qo(a) @ qo(b)

    def rms(x, w=1.0):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                          keepdims=True) + z["eps"]) * w

    def merge(res, x, f):
        res = f32(res)
        return (res[0] * x + res[1]) + (res[2] * f + res[3])

    def attention(w, x, marks):
        s_len = x.shape[0]
        pos = jnp.arange(s_len)
        proj = jnp.concatenate([wt(w[k]) for k in ("wq", "wk", "wv1",
                                                   "wv2")], axis=1)
        zv = _by_rows(lambda xb: mm(rms(xb, f32(w["ln1"])), proj), x)
        zz, v1, v2 = zv[:, :qk], zv[:, qk:qk + hd], zv[:, qk + hd:]
        # the value shift: the second head is the row before's
        v2_back = jnp.concatenate([jnp.zeros((1, hd), jnp.float32), v2])
        v = st(jnp.concatenate([v1, v2_back[:-1]], axis=-1))
        # conv mixing behind ``back`` rows of zeros, neither conv padded
        zpad = jnp.concatenate([jnp.zeros((back, qk), jnp.float32), zz])
        c0w, c1w = f32(w["c0w"]), f32(w["c1w"])
        n0 = s_len + z["t1"] - 1
        c0 = sum(c0w[a][None, :] * zpad[a:a + n0] for a in range(z["t0"])) \
            + f32(w["c0b"])
        c0 = c0.reshape(n0, h_n + g, hd)
        c1 = sum(jnp.einsum("sgc,gcd->sgd", c0[j:j + s_len], c1w[j])
                 for j in range(z["t1"])).reshape(s_len, qk) + f32(w["c1b"])
        q_in = zz[:, :qd].reshape(s_len, g, per, hd)
        k_in = zz[:, qd:].reshape(s_len, g, 1, hd)
        m_q = (q_in + k_in) / 2
        m_k = (jnp.mean(q_in, axis=2, keepdims=True) + k_in) / 2
        q = rms(c1[:, :qd].reshape(s_len, g, per, hd) + m_q)
        k = rms(c1[:, qd:].reshape(s_len, g, 1, hd) + m_k) \
            * f32(w["temp"])[None, :, None, None]
        q = rope_half(q.reshape(s_len, h_n, hd), pos, z["rot"], z["theta"])
        k = rope_half(k.reshape(s_len, g, hd), pos, z["rot"], z["theta"])
        k = st(k.reshape(s_len, z["kvd"]))
        kh, vh = k.reshape(s_len, g, hd), v.reshape(s_len, g, hd)
        qb = QUERY_BLOCK if s_len % QUERY_BLOCK == 0 else s_len
        wo = wt(w["wo"])

        def block(args):
            qc, q_pos = args
            sc = jnp.einsum("qgjd,kgd->gjqk", qo(qc), qo(kh)) / math.sqrt(hd)
            live = pos[None, :] <= q_pos[:, None]
            prob = jax.nn.softmax(jnp.where(live, sc, -1e30), axis=-1)
            out = jnp.einsum("gjqk,kgd->qgjd", qo(prob), qo(vh))
            return mm(out.reshape(qb, qd), wo)

        att = jax.lax.map(block, (q.reshape(-1, qb, g, per, hd),
                                  pos.reshape(-1, qb)))
        # what a cache's tail holds after ``mk`` rows: the last ``back``
        # rows of z and the last row's second value head
        tails = jnp.stack([jnp.concatenate([
            jax.lax.dynamic_slice(zpad, (mk, 0), (back, qk)).reshape(-1),
            jax.lax.dynamic_slice(v2_back, (mk, 0), (1, hd)).reshape(-1)])
            for mk in marks])
        return merge(w["res_a"], x, att.reshape(s_len, z["d"])), k, v, tails

    def experts(w, x, r):
        def route(xb, rb):
            hb = rms(xb, f32(w["ln2"]))
            rb = hb @ w["r_wd"] + w["r_bd"] + w["r_g"] * rb
            a = jax.nn.gelu(rms(rb, w["r_ln"]) @ w["r_w1"] + w["r_b1"],
                            approximate=False)
            a = jax.nn.gelu(a @ w["r_w2"] + w["r_b2"], approximate=False)
            p = jax.nn.softmax(a @ w["r_w3"], axis=-1)
            top, idx = jax.lax.top_k(p + w["r_bias"], 2)
            p_e = jnp.take_along_axis(p, idx[:, :1], axis=-1)[:, 0]
            return jnp.concatenate(
                [rb, idx[:, :1].astype(jnp.float32), p_e[:, None],
                 (top[:, 0] - top[:, 1])[:, None]], axis=-1)

        routed = _by_rows(route, x, r)
        r = routed[:, :z["rw"]]
        idx = routed[:, z["rw"]].astype(jnp.int32)
        p_e, margin = routed[:, z["rw"] + 1], routed[:, z["rw"] + 2]

        def rows(xb, idx_b, p_b):
            hb = rms(xb, f32(w["ln2"]))
            out = jnp.zeros_like(xb)
            for e in range(z["e"]):      # the skip output (e = E): nothing
                y = mm(jax.nn.silu(mm(hb, wt(w[f"e{e}.wg"])))
                       * mm(hb, wt(w[f"e{e}.wu"])), wt(w[f"e{e}.wd"]))
                out = out + jnp.where((idx_b == e)[:, None],
                                      p_b[:, None] * y, 0.0)
            return out

        f = _by_rows(rows, x, idx, p_e)
        return merge(w["res_m"], x, f), r, margin, idx

    def layer(w, x, r, marks):
        x, k, v, tails = attention(w, x, marks)
        x, r, margin, idx = experts(w, x, r)
        return x, r, k, v, tails, margin, idx

    def embed(e, tokens):
        return (_q8(f32(e)) if control else f32(e))[tokens]

    def head(lnf, e, x, rows):
        e = _q8(f32(e)) if control else f32(e)
        return (qo(rms(x[rows], f32(lnf))) @ qo(e).T).astype(jnp.float32)

    def highest(fn):
        jitted = jax.jit(fn)

        def call(*args):
            with jax.default_matmul_precision("highest"):
                return jitted(*args)

        return call

    return {"embed": highest(embed), "layer": highest(layer),
            "head": highest(head), "layers": z["layers"], "rw": z["rw"]}


# ---------------------------------------------------------- state comparison
PARTS = ("k0", "v0", "tail0", "kL", "vL", "thin")
NOTHING = -1.0     # a part with nothing to read in this request


@functools.lru_cache(maxsize=None)
def _gap_fns():
    import jax
    import jax.numpy as jnp

    def whole(ref, got):
        return jnp.sqrt(jnp.sum(jnp.square(got - ref))
                        / jnp.maximum(jnp.sum(jnp.square(ref)), 1e-30))

    def rows(ref, got):
        """Each row's squared distance and squared norm."""
        return (jnp.sum(jnp.square(got - ref), axis=-1),
                jnp.sum(jnp.square(ref), axis=-1))

    return jax.jit(whole), jax.jit(rows)


ROWS_TRAIL = []     # (scaled margin before the last layer, its K row's gap)


def state_gaps(ref_state: dict, got_state: dict, lo: int, hi: int):
    """(6,) in the order of ``PARTS`` over positions ``[lo, hi)``: how far
    layer 0's K and V rows (all of them, as a share of their norm) and its
    tail (from 0: at the prompt's end; from the prompt's length on: after
    the last consumed row) lie from the reference's; the median of the LAST
    layer's K and V rows' own distances over the positions that are not
    thin (nor behind a thin one, as far back as the convs read); and the
    share of thin positions. ``NOTHING`` where a part has
    no row to read here."""
    import jax.numpy as jnp

    whole, rows = _gap_fns()
    pos = np.arange(ref_state["k0"].shape[0])
    span = (pos >= lo) & (pos < hi)
    reach = np.asarray(ref_state["margin_before_last"])
    thin = reach < float(ref_state["route_margin"])
    behind = thin.copy()        # a row's K and V read the rows before it
    for j in range(1, int(ref_state["back"]) + 1):
        behind[j:] |= thin[:-j]
    thick = span & ~behind

    def parts(k):
        num, den = rows(ref_state[k], jnp.asarray(got_state[k], jnp.float32))
        return np.asarray(num, np.float64), np.asarray(den, np.float64)

    def all_rows(k):
        num, den = parts(k)
        return float(np.sqrt(num[span].sum() / max(den[span].sum(), 1e-30)))

    def by_row(k):
        if not thick.any():
            return NOTHING
        num, den = parts(k)
        own = np.sqrt(num / np.maximum(den, 1e-30))
        if k == "kL":
            ROWS_TRAIL.append((reach[span], own[span]))
        return float(np.percentile(own[thick], ROW_PERCENTILE))

    at = 0 if lo == 0 else 1
    out = [all_rows("k0"), all_rows("v0"),
           float(whole(ref_state["tail0"][at],
                       jnp.asarray(got_state["tail0"][at], jnp.float32))),
           by_row("kL"), by_row("vL"),
           float((span & thin).sum()) / max(1, int(span.sum()))]
    return np.asarray(out, np.float64)


class Reference:
    """Full forward passes over ``prompt + served tokens``, one request at a
    time, padded to a few lengths so that few programs compile."""

    def __init__(self, seed: int, m: dict, mode: str, host_weights=None,
                 pad_to: int = 512, route_margin: float = 0.0):
        import json

        import jax
        import jax.numpy as jnp

        self.m = {k: v for k, v in m.items() if k != "rehearsal"}
        self.key = json.dumps(self.m, sort_keys=True)
        self.mode, self.pad_to = mode, pad_to
        self.route_margin = float(route_margin)
        host = host_weights or draw_weights(seed, m)
        self.w = {}
        for name in list(host):
            arr = jax.device_put(host.pop(name))
            self.w[name] = (arr if arr.dtype == jnp.float32 else
                            jax.lax.bitcast_convert_type(arr, jnp.bfloat16))
        self.trail = []     # (margin, gap) of every served token gone over
        self.routed = None  # the last forward's chosen output, (layers, S)

    def layer(self, l: int) -> dict:
        p = f"l{l}."
        return {k[len(p):]: v for k, v in self.w.items() if k.startswith(p)}

    def forward(self, prompt, served, rows_pad: int, control: bool = False):
        """Over ``prompt + served[:-1]``: float32 logits (len(served),
        vocab), row i the distribution that chose ``served[i]`` (FLAT for a
        thin row; never in the control); and the state (``PARTS``): layer
        0's and the last layer's K and V rows (padded length, kv_dim), layer
        0's tail [at the prompt's end, after the last row], the margins that
        decide which of the last layer's rows are compared, and
        ``route_margin``."""
        import jax.numpy as jnp

        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        toks = np.zeros(padded(len(seq), self.pad_to), np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(padded(len(served), rows_pad), np.int32)
        rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        marks = np.asarray([len(prompt), len(seq)], np.int32)
        fn = _fns(self.key, self.mode, bool(control))
        x = fn["embed"](self.w["embed"], toks)
        r = jnp.zeros((len(toks), fn["rw"]), jnp.float32)
        state, margins, routed = {}, [], []
        last = fn["layers"] - 1
        for l in range(fn["layers"]):
            x, r, k, v, tails, margin, idx = fn["layer"](self.layer(l), x,
                                                         r, marks)
            if l == 0:
                state["k0"], state["v0"], state["tail0"] = k, v, tails
            if l == last:
                state["kL"], state["vL"] = k, v
                # the last layer's rows follow the routing of the layers
                # BEFORE it
                state["margin_before_last"] = (
                    jnp.min(jnp.stack(margins), axis=0) if margins
                    else jnp.full(margin.shape, WIDE))
            # scaled by depth: what a margin of layer l counts as
            margins.append(margin * (fn["layers"] / (l + 1.0)))
            routed.append(idx)
        self.routed = np.asarray(jnp.stack(routed))[:, :len(seq)]
        logits = fn["head"](self.w["lnf"], self.w["embed"], x,
                            rows)[:len(served)]
        state["route_margin"] = self.route_margin
        state["back"] = sizes(self.m)["back"]
        if not control:
            at = np.asarray(jnp.min(jnp.stack(margins), axis=0))[
                rows[:len(served)]]
            tok = jnp.asarray(np.asarray(served, np.int32))
            gap = np.asarray(jnp.max(logits, axis=-1) - jnp.take_along_axis(
                logits, tok[:, None], axis=-1)[:, 0])
            self.trail.append((at, gap))
            flat = at < LOGIT_MARGINS * self.route_margin
            # the few rows farthest off: routed otherwise (module docstring)
            rest = np.where(flat, -1.0, gap)
            worst = np.argsort(-rest)[:int(FLIP_SHARE * len(served))]
            flat[worst[rest[worst] > 0]] = True
            logits = jnp.where(jnp.asarray(flat)[:, None], 0.0, logits)
        return logits, state

    def readings(self) -> str:
        """For a limit's reading: at each candidate margin (scaled by depth),
        the share of the served tokens gone over whose logits row would be
        flat and the widest gap of the rest; the share of the last layer's
        rows that would be thin, how many of the rest lie far off (over
        0.2: routed otherwise) and the rest's 50th and 90th percentile."""
        if not self.trail:
            return "no token gone over"
        at = np.concatenate([a for a, _g in self.trail])
        gap = np.concatenate([g for _a, g in self.trail])
        taus = (1e-5, 1e-3, 2e-3, 4e-3, 8e-3, 1.6e-2, 3.2e-2, 6.4e-2, 0.128)
        out = []
        for tau in taus:
            keep = at >= LOGIT_MARGINS * tau
            out.append(f"margin {tau:g}: flat {100 * (1 - keep.mean()):.1f}% "
                       f"widest gap of the rest "
                       f"{gap[keep].max() if keep.any() else 0:.2e}")
        text = f"{len(at)} tokens; " + "; ".join(out)
        for share in (0.01, 0.02, 0.05, FLIP_SHARE):
            left = [np.sort(np.where(a >= LOGIT_MARGINS * self.route_margin,
                                     g, 0.0))[::-1][int(share * len(g))]
                    for a, g in self.trail]
            text += (f"; without the {100 * share:g}% farthest of a "
                     f"request: widest {max(left):.2e}")
        if ROWS_TRAIL:
            reach = np.concatenate([r for r, _o in ROWS_TRAIL])
            own = np.concatenate([o for _r, o in ROWS_TRAIL])
            rows = []
            for tau in taus:
                keep = reach >= tau
                rest = own[keep] if keep.any() else np.zeros(1)
                rows.append(f"margin {tau:g}: thin "
                            f"{100 * (1 - keep.mean()):.1f}% far "
                            f"{100 * (rest > 0.2).mean():.1f}% p50 "
                            f"{np.percentile(rest, 50):.2e} p90 "
                            f"{np.percentile(rest, 90):.2e}")
            text += f" || last layer's K rows ({len(own)}): " + "; ".join(rows)
        return text

    def free(self):
        if self.trail:
            print(f"[zaya reference] {self.readings()}", file=sys.stderr,
                  flush=True)
        del ROWS_TRAIL[:]
        self.w = None
