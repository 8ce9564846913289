"""Block ``glm4moelite``'s plain reference: what ``correct`` is decided
against.

The ``glm4_moe_lite`` decoder (GLM-4.7-Flash; configuration keys of
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json; the block
is DeepSeek-V2/V3's, arXiv:2405.04434 and arXiv:2412.19437) in
straightforward ``jax.numpy`` under ``default_matmul_precision("highest")``:
attention in its EXPANDED form only, K and V of every head built through
``Wkvb`` for every position, masked einsums over blocks of query rows, every
held expert over every row with the router's choice as a mask; no cache, no
chunk, no kernel, no sorting, no absorbed product. One jitted function a
layer, called layer by layer with that layer's weights. It imports nothing of
the program and takes nothing the program made: it draws its own weights from
the seed by the recipe the configuration states (``arrays`` / ``draw``).

The layers. ``d`` hidden, ``H`` heads, ``ql`` / ``r`` the query and key/value
latent widths, a head ``[nope | rot]`` wide for q and k and ``vd`` for v,
0-based layer ``l``, pre-norm, two sublayers in sequence: ``x <- x +
attn(RMSNorm(x))``, then ``x <- x + ffn(RMSNorm(x))``; ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * w``.

- **Latent attention**, ``u = RMSNorm(x)``, position ``p``: ``c_q =
  RMSNorm_ql(u Wqa)``, ``q = c_q Wqb`` as H heads ``[q_nope | q_rope]``; ``[c
  | k_r] = u Wkva``, ``c <- RMSNorm_r(c)``, ``k_r <- rope(k_r, p)`` (ONE
  rotary key a token for all heads), ``q_rope <- rope(q_rope, p)``; rotary on
  pairs ``(2j, 2j + 1)`` of the ``rot`` dims, angle ``p * theta^(-2j / rot)``,
  float32. A serving program's page holds ``[c | k_r]`` (``state["lat*"]``).
  ``[k_nope | v]_h = c Wkvb`` (r -> H x (nope + vd)), ``k_h = [k_nope_h |
  k_r]``, causal softmax of ``q_h k_h' / sqrt(nope + rot)``, ``out = [o_1 ..
  o_H] Wo``.
- **Layer 0's MLP**: ``(silu(h Wg) * (h Wu)) Wd`` at the dense width.
- **Expert layers** ``l >= 1``, ``h = RMSNorm(x)``: router, float32 with no
  operand rounded: ``s = sigmoid(h Wr)`` over all E; the k largest ``s + b``
  are chosen (``b`` the stored selection bias, for the choice only); weights
  the chosen experts' ``s`` over their sum, times the scaling factor;
  ``f = sum_e w_e E_e(h) + S(h)``, ``E_e`` and the one shared expert ``S``
  gated silu MLPs of the expert width.
- After the last layer a final RMSNorm, then logits ``= x Head'`` (untied).

**The share.** The reference is given the program's share: the experts
``expert_rank * held .. + held - 1``. It routes over all E, adds its own
experts' terms of the routed sum and leaves the others out; that partial sum
goes on. ``sublayer_parts`` below is one expert sublayer alone, routed part
and shared part apart, for the share test.

Arithmetic (``mode``). Every weight but the router's is a bfloat16 VALUE in
every mode, as the configuration stores it, and so are the latent rows (the
key after its rotation); router and bias are float32. ``bfloat16_operands``
rounds every matmul operand to bfloat16 and sums in float32 (the TPU's
default precision, which the configuration states), the router excepted;
``float32`` rounds no operand. The CONTROL (``control=True``) is the step
below: matrices, embedding, head and latent rows held in float8 (e4m3 by
``reduce_precision``, one scale a tensor).

**Routing and the comparison**, as the ``cohere2moe`` and ``zaya`` blocks':
two correct computations with bfloat16 operands part by rounding, some 1e-3 a
layer; a token that has an expert HELD here close to the boundary between
its k-th and (k+1)-th ``s + b`` may take that expert on one side only, and
then differs by that expert's output in every layer after. So the reference
reports every row's MARGIN by layer (how far the nearest held expert's ``s +
b`` lies from the mid-point of the k-th and (k+1)-th), scaled by depth
(``margin * layers / (l + 1)``: the sides part more the deeper the layer). A
row is THIN where a scaled margin is under ``route_margin``:

- the share of thin rows among a request's prompt rows is itself compared
  (``route_thin_share_prefill``);
- ``latL_gap_*`` is the MEDIAN of the last layer's rows' own distances over
  the rows not thin in a layer before it: most rows keep their experts and
  lie within rounding; a fault in the expert sublayer, in the attention
  before it or in what a chunk reads back moves most rows;
- a logits row is returned flat (``logit_gap``, a maximum, reads 0 for it)
  where the row is thin at ``LOGIT_MARGINS`` times the margin, and for the
  ``FLIP_SHARE`` of a request's served rows whose served token lies farthest
  under the reference's best.

Layer 0's latent rows depend on no routing and are compared whole.
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
    held = m["n_routed_experts"]
    total = m.get("num_routed_experts") or held
    if m.get("first_k_dense_replace", 1) != 1 or m["n_shared_experts"] != 1:
        raise ValueError("glm4moelite: one leading dense layer, one shared "
                         "expert")
    return {"d": m["hidden_size"], "h": m["num_attention_heads"],
            "ql": m["q_lora_rank"], "r": m["kv_lora_rank"],
            "nope": m["qk_nope_head_dim"], "rot": m["qk_rope_head_dim"],
            "vd": m["v_head_dim"],
            "qk": m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
            "lat": m["kv_lora_rank"] + m["qk_rope_head_dim"],
            "ffd": m["intermediate_size"], "ff": m["moe_intermediate_size"],
            "e": total, "held": held,
            "lo": m.get("expert_rank", 0) * held,
            "k": m["num_experts_per_tok"], "ns": m["n_shared_experts"],
            "scale": float(m["routed_scaling_factor"]),
            "theta": float(m["rope_theta"]), "eps": m["rms_norm_eps"],
            "v": m["vocab_size"], "layers": m["num_hidden_layers"]}


# ------------------------------------------------------------------- weights
# a layer's drawn arrays: name -> (stream within the layer, shape(z)); a
# matrix (rows in, columns out) spreads by 0.5 / sqrt(rows in)
ATTENTION = {
    "wqa": (0, lambda z: (z["d"], z["ql"])),
    "wqb": (1, lambda z: (z["ql"], z["h"] * z["qk"])),
    "wkva": (2, lambda z: (z["d"], z["lat"])),
    "wkvb": (3, lambda z: (z["r"], z["h"] * (z["nope"] + z["vd"]))),
    "wo": (4, lambda z: (z["h"] * z["vd"], z["d"])),
}
DENSE = {"wg": (5, lambda z: (z["d"], z["ffd"])),
         "wu": (6, lambda z: (z["d"], z["ffd"])),
         "wd": (7, lambda z: (z["ffd"], z["d"]))}
NORMS = {"ln1": "d", "ln2": "d", "q_ln": "ql", "kv_ln": "r"}   # all ones
ROUTER = ("router", "r_bias")       # float32 as stored
EXPERT = ("wg", "wu", "wd")         # stream + 3 i + (0, 1, 2)
BIAS_SPREAD = 0.01


def arrays(m: dict) -> list:
    """(name, stream or None, shape, constant, spread) of every array of
    this share: ``embed``, ``head``, ``lnf``, then ``l<l>.<name>`` layer by
    layer (the shared expert ``l<l>.s0.wg`` / ``wu`` / ``wd``, the held
    experts ``l<l>.e<e>.*`` by their PUBLISHED index). An array is
    ``constant + spread * n`` with n ``Generator(Philox(key=[seed,
    stream]))``'s ``standard_normal`` float32 in row-major order (no draw
    where the spread is 0). Streams: the embedding 10^6, the head 10^6 + 1;
    layer l: 1000 l + (0 Wqa, 1 Wqb, 2 Wkva, 3 Wkvb, 4 Wo, 5-7 the dense
    layer's gate, up, down, 8 the router, 9 the selection bias, 10 + 3 i +
    (0, 1, 2) shared expert i, 100 + 3 e + (0, 1, 2) routed expert e)."""
    z = sizes(m)
    d, ff = z["d"], z["ff"]
    mat = lambda rows: 0.5 / math.sqrt(rows)   # noqa: E731
    out = [("embed", 10 ** 6, (z["v"], d), 0, 0.5 / math.sqrt(25 * d)),
           ("head", 10 ** 6 + 1, (z["v"], d), 0, mat(d)),
           ("lnf", None, (d,), 1, 0)]
    for l in range(z["layers"]):
        table = dict(ATTENTION, **(DENSE if l == 0 else {
            "router": (8, lambda z: (z["d"], z["e"]))}))
        for name, (sid, shape) in table.items():
            shape = shape(z)
            out.append((f"l{l}.{name}", 1000 * l + sid, shape, 0,
                        mat(shape[0])))
        out += [(f"l{l}.{name}", None, (z[w],), 1, 0)
                for name, w in NORMS.items()]
        if l == 0:
            continue
        out.append((f"l{l}.r_bias", 1000 * l + 9, (z["e"],), 0, BIAS_SPREAD))
        for kind, first, n, sid in (("s", 0, z["ns"], 10),
                                    ("e", z["lo"], z["held"], 100)):
            for i in range(first, first + n):
                for j, (name, shape) in enumerate(
                        (("wg", (d, ff)), ("wu", (d, ff)), ("wd", (ff, d)))):
                    out.append((f"l{l}.{kind}{i}.{name}",
                                1000 * l + sid + 3 * i + j, shape, 0,
                                mat(shape[0])))
    return out


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
    """Router and selection bias are stored float32; the rest bfloat16."""
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
    """x as float8 (e4m3) would hold it, with one scale for the tensor (its
    largest magnitude mapped to 240), back in float32."""
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


def rope(x, pos, theta: float):
    """Pairs ``(2j, 2j + 1)`` of the last axis of x (rows, heads, rot)
    turned by ``pos * theta^(-2j / rot)``, left interleaved."""
    import jax.numpy as jnp

    rot = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, rot, 2, dtype=np.float64)
                                / rot), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _pieces(z: dict, mode: str, control: bool):
    """The arithmetic's helpers and the sublayers, unjitted."""
    import jax
    import jax.numpy as jnp

    if mode not in MODES:
        raise ValueError(f"reference: unknown mode {mode!r}")
    qo = (lambda x: x) if mode == "float32" else _b16     # matmul operands
    f32 = lambda x: x.astype(jnp.float32)                 # noqa: E731
    wt = (lambda x: _q8(f32(x))) if control else f32      # a stored matrix
    st = _q8 if control else _b16                         # stored latent rows
    h_n, r, nope, vd, qk = z["h"], z["r"], z["nope"], z["vd"], z["qk"]

    def mm(a, b):
        return qo(a) @ qo(b)

    def rms(x, w=1.0):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                          keepdims=True) + z["eps"]) * w

    def mlp(w, p, hb):
        return mm(jax.nn.silu(mm(hb, wt(w[p + "wg"])))
                  * mm(hb, wt(w[p + "wu"])), wt(w[p + "wd"]))

    def attention(w, x):
        s_len = x.shape[0]
        pos = jnp.arange(s_len)

        def down(xb, pb):
            u = rms(xb, f32(w["ln1"]))
            c_q = rms(mm(u, wt(w["wqa"])), f32(w["q_ln"]))
            q = mm(c_q, wt(w["wqb"])).reshape(-1, h_n, qk)
            q = jnp.concatenate([q[..., :nope],
                                 rope(q[..., nope:], pb, z["theta"])],
                                axis=-1)
            ckr = mm(u, wt(w["wkva"]))
            k_r = rope(ckr[:, None, r:], pb, z["theta"])[:, 0]
            lat = st(jnp.concatenate(
                [rms(ckr[:, :r], f32(w["kv_ln"])), k_r], axis=-1))
            return jnp.concatenate([q.reshape(-1, h_n * qk), lat], axis=-1)

        ql = _by_rows(down, x, pos)
        q, lat = ql[:, :h_n * qk].reshape(s_len, h_n, qk), ql[:, h_n * qk:]
        # K and V of every head and position through Wkvb, from the row a
        # page would keep
        kv = _by_rows(lambda cb: mm(cb, wt(w["wkvb"])), lat[:, :r]).reshape(
            s_len, h_n, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(lat[:, None, r:],
                                              (s_len, h_n, z["rot"]))],
            axis=-1)
        v = kv[..., nope:]
        qb = QUERY_BLOCK if s_len % QUERY_BLOCK == 0 else s_len
        wo = wt(w["wo"])

        def block(args):
            qc, q_pos = args
            sc = jnp.einsum("qhd,khd->hqk", qo(qc), qo(k)) / math.sqrt(qk)
            live = pos[None, :] <= q_pos[:, None]
            prob = jax.nn.softmax(jnp.where(live, sc, -1e30), axis=-1)
            out = jnp.einsum("hqk,khd->qhd", qo(prob), qo(v))
            return mm(out.reshape(qb, h_n * vd), wo)

        att = jax.lax.map(block, (q.reshape(-1, qb, h_n, qk),
                                  pos.reshape(-1, qb)))
        return x + att.reshape(s_len, z["d"]), lat

    def dense(w, x):
        return x + _by_rows(
            lambda xb: mlp(w, "", rms(xb, f32(w["ln2"]))), x)

    def parts(w, hb, lo, held):
        """Over normed rows ``hb``: the routed part of experts ``lo .. lo +
        held - 1``, the shared expert's part, and each row's margin (the
        module docstring) with respect to those experts."""
        s = jax.nn.sigmoid(hb @ w["router"])
        sb = s + w["r_bias"]
        top, idx = jax.lax.top_k(sb, z["k"] + 1)
        idx = idx[:, :z["k"]]
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        wts = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * z["scale"]
        edge = 0.5 * (top[:, -2] + top[:, -1])
        margin = jnp.min(jnp.abs(sb[:, lo:lo + held] - edge[:, None]),
                         axis=-1)
        out = jnp.zeros_like(hb)
        for e in range(lo, lo + held):
            w_e = jnp.sum(jnp.where(idx == e, wts, 0.0), axis=-1)
            out = out + w_e[:, None] * mlp(w, f"e{e}.", hb)
        return out, mlp(w, "s0.", hb), margin

    def experts(w, x):
        def rows(xb):
            routed, shared, margin = parts(
                w, rms(xb, f32(w["ln2"])), z["lo"], z["held"])
            return jnp.concatenate([xb + routed + shared, margin[:, None]],
                                   axis=-1)

        out = _by_rows(rows, x)
        return out[:, :-1], out[:, -1]

    return {"attention": attention, "dense": dense, "experts": experts,
            "parts": parts, "rms": rms, "qo": qo, "f32": f32,
            "control": control}


@functools.lru_cache(maxsize=None)
def _fns(model_key: str, mode: str, control: bool):
    """The jitted pieces in one arithmetic: ``embed(E, tokens)``,
    ``first(w, x)`` (layer 0), ``layer(w, x)`` and ``head(lnf, H, x,
    rows)``."""
    import json

    import jax
    import jax.numpy as jnp

    z = sizes(json.loads(model_key))
    p = _pieces(z, mode, control)
    f32, qo, rms = p["f32"], p["qo"], p["rms"]

    def first(w, x):
        x, lat = p["attention"](w, x)
        return p["dense"](w, x), lat

    def layer(w, x):
        x, lat = p["attention"](w, x)
        x, margin = p["experts"](w, x)
        return x, lat, margin

    def embed(e, tokens):
        return (_q8(f32(e)) if control else f32(e))[tokens]

    def head(lnf, hw, x, rows):
        hw = _q8(f32(hw)) if control else f32(hw)
        return (qo(rms(x[rows], f32(lnf))) @ qo(hw).T).astype(jnp.float32)

    def highest(fn):
        jitted = jax.jit(fn)

        def call(*args):
            with jax.default_matmul_precision("highest"):
                return jitted(*args)

        return call

    return {"embed": highest(embed), "first": highest(first),
            "layer": highest(layer), "head": highest(head),
            "layers": z["layers"]}


def sublayer_parts(m: dict, w: dict, x, lo: int, held: int,
                   mode: str = "float32"):
    """One expert sublayer alone over rows ``x`` (its own norm included),
    for the share test: the routed part of experts ``lo .. lo + held - 1``
    and the shared expert's part. ``w``: the layer's arrays by name (no
    ``l<l>.`` in front), float32 or as stored."""
    import jax

    z = sizes(m)
    p = _pieces(z, mode, False)
    with jax.default_matmul_precision("highest"):
        return p["parts"](w, p["rms"](x, p["f32"](w["ln2"])), lo, held)[:2]


# ---------------------------------------------------------- state comparison
PARTS = ("lat0", "latL", "thin")
NOTHING = -1.0     # a part with nothing to read in this request
ROWS_TRAIL = []    # (scaled margin before the last layer, its row's gap)


@functools.lru_cache(maxsize=None)
def _rows_fn():
    import jax
    import jax.numpy as jnp

    def rows(ref, got):
        """Each row's squared distance and squared norm."""
        return (jnp.sum(jnp.square(got - ref), axis=-1),
                jnp.sum(jnp.square(ref), axis=-1))

    return jax.jit(rows)


def state_gaps(ref_state: dict, got_state: dict, lo: int, hi: int):
    """(3,) in the order of ``PARTS`` over positions ``[lo, hi)``: how far
    layer 0's latent rows (all of them, as a share of their norm) lie from
    the reference's; the median of the LAST layer's rows' own distances over
    the positions that are not thin; and the share of thin positions.
    ``NOTHING`` where a part has no row to read here."""
    import jax.numpy as jnp

    pos = np.arange(ref_state["lat0"].shape[0])
    span = (pos >= lo) & (pos < hi)
    reach = np.asarray(ref_state["margin_before_last"])
    thin = reach < float(ref_state["route_margin"])
    thick = span & ~thin

    def parts(k):
        num, den = _rows_fn()(ref_state[k],
                              jnp.asarray(got_state[k], jnp.float32))
        return np.asarray(num, np.float64), np.asarray(den, np.float64)

    num, den = parts("lat0")
    out = [float(np.sqrt(num[span].sum() / max(den[span].sum(), 1e-30)))]
    if thick.any():
        num, den = parts("latL")
        own = np.sqrt(num / np.maximum(den, 1e-30))
        ROWS_TRAIL.append((reach[span], own[span]))
        out.append(float(np.percentile(own[thick], ROW_PERCENTILE)))
    else:
        out.append(NOTHING)
    out.append(float((span & thin).sum()) / max(1, int(span.sum())))
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

    def layer(self, l: int) -> dict:
        p = f"l{l}."
        return {k[len(p):]: v for k, v in self.w.items() if k.startswith(p)}

    def forward(self, prompt, served, rows_pad: int, control: bool = False):
        """Over ``prompt + served[:-1]``: float32 logits (len(served),
        vocab), row i the distribution that chose ``served[i]`` (FLAT for a
        thin row; never in the control); and the state (``PARTS``): layer
        0's and the last layer's latent rows (padded length, kv_lora + rot),
        the margins that decide which of the last layer's rows are compared,
        and ``route_margin``."""
        import jax.numpy as jnp

        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        toks = np.zeros(padded(len(seq), self.pad_to), np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(padded(len(served), rows_pad), np.int32)
        rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        fn = _fns(self.key, self.mode, bool(control))
        x = fn["embed"](self.w["embed"], toks)
        x, lat = fn["first"](self.layer(0), x)
        state, margins = {"lat0": lat}, []
        last = fn["layers"] - 1
        for l in range(1, fn["layers"]):
            x, lat, margin = fn["layer"](self.layer(l), x)
            if l == last:
                state["latL"] = lat
                # the last layer's rows follow the routing of the layers
                # BEFORE it
                state["margin_before_last"] = (
                    jnp.min(jnp.stack(margins), axis=0) if margins
                    else jnp.full(margin.shape, WIDE))
            # scaled by depth: what a margin of layer l counts as
            margins.append(margin * (fn["layers"] / (l + 1.0)))
        logits = fn["head"](self.w["lnf"], self.w["head"], x,
                            rows)[:len(served)]
        state["route_margin"] = self.route_margin
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
        taus = (1e-5, 2.5e-4, 5e-4, 1e-3, 2e-3, 4e-3, 8e-3, 1.6e-2)
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
            text += (f" || last layer's latent rows ({len(own)}): "
                     + "; ".join(rows))
        return text

    def free(self):
        if self.trail:
            print(f"[glm4moelite reference] {self.readings()}",
                  file=sys.stderr, flush=True)
        del ROWS_TRAIL[:]
        self.w = None
