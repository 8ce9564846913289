"""Block ``jamba``'s plain reference: what ``correct`` is decided against.

The ``jamba`` decoder (AI21-Jamba2-3B; configuration keys of
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json) in
straightforward ``jax.numpy`` under ``default_matmul_precision("highest")``:
the recurrence as a sequential ``lax.scan`` from zero over every row,
attention as masked einsums over blocks of query rows (so that the scores
fit), no cache, no chunk, no kernel. One jitted function a KIND of layer,
called layer by layer with that layer's weights, its matmuls over blocks of
rows: between layers only the residual stream lives, so a request of 33 000
rows fits the chip beside the weights. It imports nothing of the program and
takes nothing the program made: it draws its own weights from the seed by
the recipe the configuration states (``arrays`` / ``draw`` below).

The layers. ``d`` hidden, ``di = mamba_expand * d``, ``H`` query heads over
``G`` key/value heads of ``hd = d / H``, ``N`` layers, 0-based ``l``. Layer
``l`` is an ATTENTION layer where ``l % attn_layer_period ==
attn_layer_offset`` and a MAMBA layer otherwise (``num_experts`` 1: every
feed-forward is the dense one). Every layer: ``x = x + Mixer_l(RMSNorm(x))``,
then ``x = x + MLP(RMSNorm(x))``; ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) *
w`` (eps ``rms_norm_eps``); ``MLP(h) = (silu(h Wg) * (h Wu)) Wd``, no bias.
After the last layer a final RMSNorm, then logits = ``x E'`` with the tied
embedding ``E``. No positional encoding anywhere.

- **Mamba** (``mamba_d_state`` n, ``mamba_d_conv`` k, ``mamba_dt_rank`` r):
  ``[u_in, z] = h Win``; ``u = silu(conv1d_causal(u_in; k, depthwise) +
  b_conv)``; ``[dt_r, B, C] = u Wx`` (r + n + n); **``dt_r = RMSNorm_r(dt_r)``,
  ``B = RMSNorm_n(B)``, ``C = RMSNorm_n(C)``, each with its own weight** (what
  Jamba adds to Mamba-1); ``dt = softplus(dt_r Wdt + b_dt)``; ``A =
  -exp(A_log)``; ``s_t = exp(dt_t A) * s_{t-1} + (dt_t u_t) B_t'``, ``y_t =
  s_t C_t + D * u_t``; out = ``(y * silu(z)) Wout``.
- **Attention**: ``q = h Wq`` (H x hd), ``k = h Wk``, ``v = h Wv`` (G x hd),
  no bias, no rotary, no window; query head i reads key/value head ``i //
  (H / G)``; scale ``1 / sqrt(hd)``; causal. Output ``concat(heads) Wo``.

Arithmetic (``mode``). Every weight is a bfloat16 VALUE in every mode, as the
configuration stores it (the vectors too: ``A_log``, the ``dt`` bias, ``D``,
the conv's weights, the norms'), and so are the K/V rows. ``bfloat16_operands``
rounds every matmul operand to bfloat16 and sums in float32 (the TPU's default
precision, which the configuration states); ``float32`` rounds no operand. The
residual stream, the scan's state and elementwise recurrence, softplus, the
norms and the softmax are float32 in both. The CONTROL (``control=True``) is
the step below: the matrices, the embedding and the K/V rows held in float8
(e4m3, one scale a tensor), the scan's state stored in bfloat16 after every
row.
"""

from __future__ import annotations

import functools
import math
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness.reference import padded

MODES = ("float32", "bfloat16_operands")
QUERY_BLOCK = 256   # rows of queries whose scores are held at a time
ROW_BLOCK = 4096    # rows a matmul of a layer takes at a time

MAMBA = ("win", "conv_w", "conv_b", "wx", "dt_norm", "b_norm", "c_norm",
         "wdt", "b_dt", "a_log", "dd", "wout")
ATTN = ("wq", "wk", "wv", "wo")
EVERY = ("ln1", "ln2", "wg", "wu", "wd")


# ------------------------------------------------------------------ geometry
def layer_kinds(m: dict) -> list:
    """``mamba`` | ``full`` for each layer, by the ``jamba`` model type's
    rule: attention where ``l % period == offset``."""
    return ["full" if l % m["attn_layer_period"] == m["attn_layer_offset"]
            else "mamba" for l in range(m["num_hidden_layers"])]


def sizes(m: dict) -> dict:
    """The widths every function here needs, from the configuration's keys."""
    d, h, g = (m["hidden_size"], m["num_attention_heads"],
               m["num_key_value_heads"])
    kinds = layer_kinds(m)
    return {"d": d, "di": m["mamba_expand"] * d, "h": h, "g": g,
            "hd": d // h, "kvd": g * (d // h), "ff": m["intermediate_size"],
            "n": m["mamba_d_state"], "kc": m["mamba_d_conv"],
            "r": m["mamba_dt_rank"], "v": m["vocab_size"], "kinds": kinds,
            "layers": len(kinds), "eps": m["rms_norm_eps"]}


def shapes(z: dict) -> dict:
    """One layer's shape of every array, matrices as (rows in, columns
    out)."""
    d, di, ff, r, n = z["d"], z["di"], z["ff"], z["r"], z["n"]
    return {"win": (d, 2 * di), "conv_w": (z["kc"], di), "conv_b": (di,),
            "wx": (di, r + 2 * n), "dt_norm": (r,), "b_norm": (n,),
            "c_norm": (n,), "wdt": (r, di), "b_dt": (di,), "a_log": (n, di),
            "dd": (di,), "wout": (di, d), "wq": (d, d), "wk": (d, z["kvd"]),
            "wv": (d, z["kvd"]), "wo": (d, d), "ln1": (d,), "ln2": (d,),
            "wg": (d, ff), "wu": (d, ff), "wd": (ff, d)}


# ------------------------------------------------------------------- weights
# a DRAWN array: its stream within the layer and its fan-in (0: its rows)
DRAWN = {"win": (0, 0), "conv_w": (1, 0), "conv_b": (2, 25), "wx": (3, 0),
         "wdt": (4, 0), "wout": (5, 0), "wq": (0, 0), "wk": (1, 0),
         "wv": (2, 0), "wo": (3, 0), "wg": (10, 0), "wu": (11, 0),
         "wd": (12, 0)}


def arrays(m: dict) -> list:
    """(name, stream or None, shape, fan-in) of every array: ``embed``, then
    ``l<l>.<name>`` layer by layer, then ``lnf``. A drawn array is
    ``Generator(Philox(key=[seed, stream]))``'s ``standard_normal`` float32
    in row-major order times ``0.5 / sqrt(fan-in)`` (fan-in: its rows in;
    the conv's bias 25, so 0.1; the embedding 25 d, so ``0.1 / sqrt(d)``:
    with rows as long as the layers' outputs a tied head returns the token it
    was given). Streams: the embedding 10^6; layer l: 1000 l + (Mamba: 0 win,
    1 conv_w, 2 conv_b, 3 wx, 4 wdt, 5 wout; attention: 0 wq, 1 wk, 2 wv, 3
    wo; 10 wg, 11 wu, 12 wd). The rest are the family's constants (stream
    None): ``A_log = log(1..n)`` in every channel, the ``dt`` bias the
    inverse softplus of ``1e-3 * 100^(c / (di - 1))`` for channel c, ``D`` and
    every norm weight 1."""
    z = sizes(m)
    sh = shapes(z)
    out = [("embed", 10 ** 6, (z["v"], z["d"]), 25 * z["d"])]
    for l, kind in enumerate(z["kinds"]):
        for name in (MAMBA if kind == "mamba" else ATTN) + EVERY:
            sid, fan = DRAWN.get(name, (None, 0))
            out.append((f"l{l}.{name}",
                        None if sid is None else 1000 * l + sid, sh[name],
                        fan or sh[name][0]))
    return out + [("lnf", None, (z["d"],), 0)]


def draw(seed: int, name: str, stream, shape, fan_in: int) -> np.ndarray:
    """One float32 array of the recipe."""
    if stream is not None:
        rng = np.random.Generator(np.random.Philox(key=[seed, stream]))
        out = rng.standard_normal(shape, dtype=np.float32)
        out *= np.float32(0.5 / math.sqrt(fan_in))
        return out
    if name.endswith(".a_log"):
        return np.broadcast_to(
            np.log(np.arange(1, shape[0] + 1, dtype=np.float64))[:, None],
            shape).astype(np.float32)
    if name.endswith(".b_dt"):
        dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), shape[0]))
        return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
    return np.ones(shape, np.float32)


def bf16_bits(x: np.ndarray) -> np.ndarray:
    """float32 -> the bits of the nearest bfloat16 (ties to even), uint16."""
    u = np.ascontiguousarray(x).view(np.uint32)
    return ((u + (np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))))
            >> np.uint32(16)).astype(np.uint16)


def draw_weights(seed: int, m: dict, threads: int = 3) -> dict:
    """Every array on the host as bfloat16 bits (uint16)."""
    def one(spec):
        return spec[0], bf16_bits(draw(seed, *spec))

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
    largest magnitude mapped to the format's 448), back in float32."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return ((x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale)


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


@functools.lru_cache(maxsize=None)
def _fns(model_key: str, mode: str, control: bool):
    """The jitted pieces in one arithmetic: ``embed(E, tokens)``,
    ``mamba(w, x, marks)`` and ``attention(w, x)`` (a layer with its MLP
    each), ``head(lnf, E, x, rows)``."""
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
    ss = _b16 if control else (lambda x: x)               # the scan's state
    di, n, kc, r = z["di"], z["n"], z["kc"], z["r"]
    g, per, hd = z["g"], z["h"] // z["g"], z["hd"]

    def mm(a, b):
        return qo(a) @ qo(b)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                          keepdims=True) + z["eps"]) * f32(w)

    def mlp(w, x):
        wg, wu, wd = wt(w["wg"]), wt(w["wu"]), wt(w["wd"])

        def rows(xb):
            h = rms(xb, w["ln2"])
            return xb + mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)

        return _by_rows(rows, x)

    def mamba(w, x, marks):
        """marks (2,): the prompt's length and the rows consumed. Returns
        the rows after the layer, the scan state [at the prompt's end, after
        the last row] and the conv tails there."""
        s_len = x.shape[0]
        pos = jnp.arange(s_len)
        win, wx, wdt, wout = (wt(w[k]) for k in ("win", "wx", "wdt", "wout"))
        uz = _by_rows(lambda xb: mm(rms(xb, w["ln1"]), win), x)
        u_in, gate = uz[:, :di], uz[:, di:]
        upad = jnp.concatenate([jnp.zeros((kc - 1, di), jnp.float32), u_in])
        conv_w = f32(w["conv_w"])
        conv = sum(conv_w[j][None, :] * upad[j:j + s_len] for j in range(kc))
        u = jax.nn.silu(conv + f32(w["conv_b"]))

        def inputs(ub):
            dbc = mm(ub, wx)
            dt_r = rms(dbc[:, :r], w["dt_norm"])
            bm = rms(dbc[:, r:r + n], w["b_norm"])
            cm = rms(dbc[:, r + n:], w["c_norm"])
            dt = jax.nn.softplus(mm(dt_r, wdt) + f32(w["b_dt"]))
            return jnp.concatenate([dt, bm, cm], axis=-1)

        dbc = _by_rows(inputs, u)
        dt, bm, cm = dbc[:, :di], dbc[:, di:di + n], dbc[:, di + n:]
        dt = jnp.where((pos < marks[1])[:, None], dt, 0.0)   # pads: s stays
        a = -jnp.exp(f32(w["a_log"]))                        # (n, di)

        def step(carry, inp):
            s, s_mark = carry
            dt_t, u_t, b_t, c_t, t = inp
            s = ss(jnp.exp(dt_t[None, :] * a) * s
                   + (dt_t * u_t)[None, :] * b_t[:, None])
            s_mark = jnp.where(t == marks[0] - 1, s, s_mark)
            return (s, s_mark), jnp.sum(s * c_t[:, None], axis=0)

        zero = jnp.zeros((n, di), jnp.float32)
        (s_end, s_mark), ys = jax.lax.scan(step, (zero, zero),
                                           (dt, u, bm, cm, pos))
        tails = jnp.stack([jax.lax.dynamic_slice(upad, (mk, 0), (kc - 1, di))
                           for mk in marks])
        dd = f32(w["dd"])
        x = x + _by_rows(lambda yb, ub, gb: mm((yb + dd * ub)
                                               * jax.nn.silu(gb), wout),
                         ys, u, gate)
        return mlp(w, x), jnp.stack([s_mark, s_end]), tails

    def attention(w, x):
        """Returns the rows after the layer and the layer's K and V rows as
        stored."""
        s_len = x.shape[0]
        pos = jnp.arange(s_len)
        wq, wk, wv, wo = (wt(w[k]) for k in ATTN)
        qkv = _by_rows(lambda xb: mm(rms(xb, w["ln1"]),
                                     jnp.concatenate([wq, wk, wv], axis=1)),
                       x)
        q = qkv[:, :z["d"]].reshape(s_len, g, per, hd)
        k = st(qkv[:, z["d"]:z["d"] + z["kvd"]])
        v = st(qkv[:, z["d"] + z["kvd"]:])
        kh, vh = k.reshape(s_len, g, hd), v.reshape(s_len, g, hd)
        qb = QUERY_BLOCK if s_len % QUERY_BLOCK == 0 else s_len

        def block(args):
            qc, q_pos = args
            sc = jnp.einsum("qgjd,kgd->gjqk", qo(qc), qo(kh)) / math.sqrt(hd)
            live = pos[None, :] <= q_pos[:, None]
            prob = jax.nn.softmax(jnp.where(live, sc, -1e30), axis=-1)
            out = jnp.einsum("gjqk,kgd->qgjd", qo(prob), qo(vh))
            return mm(out.reshape(qb, z["d"]), wo)

        att = jax.lax.map(block, (q.reshape(-1, qb, g, per, hd),
                                  pos.reshape(-1, qb)))
        return mlp(w, x + att.reshape(s_len, z["d"])), k, v

    def embed(e, tokens):
        return (_q8(f32(e)) if control else f32(e))[tokens]

    def head(lnf, e, x, rows):
        e = _q8(f32(e)) if control else f32(e)
        return (qo(rms(x[rows], lnf)) @ qo(e).T).astype(jnp.float32)

    def highest(fn):
        jitted = jax.jit(fn)

        def call(*args):
            with jax.default_matmul_precision("highest"):
                return jitted(*args)

        return call

    return {"embed": highest(embed), "mamba": highest(mamba),
            "attention": highest(attention), "head": highest(head),
            "kinds": z["kinds"]}


# ---------------------------------------------------------- state comparison
PARTS = ("ssm0", "conv0", "ssmL", "convL", "kf", "vf")
NOTHING = -1.0     # a part with nothing to read in this request


@functools.lru_cache(maxsize=None)
def _gap_fns():
    import jax
    import jax.numpy as jnp

    def whole(ref, got):
        return jnp.sqrt(jnp.sum(jnp.square(got - ref))
                        / jnp.maximum(jnp.sum(jnp.square(ref)), 1e-30))

    def rows(ref, got, lo, hi):
        pos = jnp.arange(ref.shape[0])
        live = ((pos >= lo) & (pos < hi))[:, None]
        num = jnp.sum(jnp.where(live, jnp.square(got - ref), 0.0))
        den = jnp.sum(jnp.where(live, jnp.square(ref), 0.0))
        return jnp.sqrt(num / jnp.maximum(den, 1e-30))

    return jax.jit(whole), jax.jit(rows)


def state_gaps(ref_state: dict, got_state: dict, lo: int, hi: int):
    """(6,) in the order of ``PARTS``: how far what the cache held lies from
    the reference's, as a share of the reference's norm. Positions ``[lo,
    hi)``: from 0 it is the part prefill wrote (the first and the last Mamba
    layer's scan state and conv tail at the prompt's end, which every chunk
    of the prompt has carried; the first attention layer's rows in the
    pages); from the prompt's length on it is what the decode steps wrote
    (the states after the last consumed row; their rows). ``NOTHING`` where
    a part has no row to read here."""
    import jax.numpy as jnp

    whole, rows = _gap_fns()
    at = 0 if lo == 0 else 1
    out = [float(whole(ref_state[k][at],
                       jnp.asarray(got_state[k][at], jnp.float32)))
           for k in ("ssm0", "conv0", "ssmL", "convL")]
    for k in ("kf", "vf"):
        out.append(float(rows(ref_state[k],
                              jnp.asarray(got_state[k], jnp.float32),
                              lo, hi)) if hi > lo else NOTHING)
    return np.asarray(out, np.float64)


class Reference:
    """Full forward passes over ``prompt + served tokens``, one request at a
    time, padded to a few lengths so that few programs compile."""

    def __init__(self, seed: int, m: dict, mode: str, host_weights=None,
                 pad_to: int = 512):
        import json

        import jax
        import jax.numpy as jnp

        self.m = {k: v for k, v in m.items() if k != "rehearsal"}
        self.key = json.dumps(self.m, sort_keys=True)
        self.mode, self.pad_to = mode, pad_to
        host = host_weights or draw_weights(seed, m)
        self.w = {}
        for name in list(host):
            self.w[name] = jax.lax.bitcast_convert_type(
                jax.device_put(host.pop(name)), jnp.bfloat16)

    def layer(self, l: int) -> dict:
        p = f"l{l}."
        return {k[len(p):]: v for k, v in self.w.items() if k.startswith(p)}

    def forward(self, prompt, served, rows_pad: int, control: bool = False):
        """Over ``prompt + served[:-1]``: float32 logits (len(served),
        vocab), row i the distribution that chose ``served[i]``; and the
        state (``PARTS``): the first and the last Mamba layer's scan state
        and conv tail at the prompt's end and after the last row, the first
        attention layer's K and V rows (padded length, kv_dim). ``control``
        computes it in the precision below."""
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        toks = np.zeros(padded(len(seq), self.pad_to), np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(padded(len(served), rows_pad), np.int32)
        rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        marks = np.asarray([len(prompt), len(seq)], np.int32)
        fn = _fns(self.key, self.mode, bool(control))
        kinds = fn["kinds"]
        first_m, last_m = kinds.index("mamba"), \
            len(kinds) - 1 - kinds[::-1].index("mamba")
        x = fn["embed"](self.w["embed"], toks)
        state = {}
        for l, kind in enumerate(kinds):
            if kind == "mamba":
                x, ssm, tails = fn["mamba"](self.layer(l), x, marks)
                if l == first_m:
                    state["ssm0"], state["conv0"] = ssm, tails
                if l == last_m:
                    state["ssmL"], state["convL"] = ssm, tails
            else:
                x, k, v = fn["attention"](self.layer(l), x)
                state.setdefault("kf", k)
                state.setdefault("vf", v)
        logits = fn["head"](self.w["lnf"], self.w["embed"], x, rows)
        return logits[:len(served)], state

    def free(self):
        self.w = None
