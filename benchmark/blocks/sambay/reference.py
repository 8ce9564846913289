"""Block ``sambay``'s plain reference: what ``correct`` is decided against.

The SambaY decoder-hybrid-decoder (arXiv:2507.06607; configuration keys of
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json)
in straightforward ``jax.numpy`` under ``default_matmul_precision("highest")``:
the recurrence as a sequential ``lax.scan``, attention as masked einsums (over
blocks of query rows, so that the scores fit), no cache, no kernel, EVERY
layer over EVERY row. It imports nothing of the program and takes nothing the
program made: it draws its own weights from the seed by the recipe the
configuration states (``weight_specs`` / ``draw_weights`` below).

The layers. ``d`` hidden, ``di = expand * d``, ``H`` query heads over ``Hkv``
key/value heads of ``hd = d / H``, ``N`` layers, 0-based ``l``. Every layer:
``x = x + Mixer_l(LN(x))``, then ``x = x + MLP(LN(x))``; ``LN`` is LayerNorm
with weight and bias (eps ``layer_norm_eps``); ``MLP(h) = (silu(h Wg) * (h
Wu)) Wd``, no bias. After the last layer a final ``LN``, then logits = ``x
E'`` with the tied embedding ``E``. No positional encoding anywhere (+).

- **Mamba-1** (``l`` even, ``l <= N/2``): ``[u, z] = h Win``; ``u =
  silu(conv1d_causal(u; k = d_conv (+), depthwise, bias))``; ``[dt_r, B, C] =
  u Wx`` (``dt_rank`` (+) + ``d_state`` (+) + ``d_state``); ``dt = softplus(
  dt_r Wdt + b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(dt_t A) * s_{t-1} +
  (dt_t u_t) B_t'``, ``y_t = s_t C_t + D * u_t``; out = ``(y * silu(z))
  Wout``. Layer ``N/2``'s ``y`` (before the gate) (+) is the memory ``m``.
- **Differential attention** (``l`` odd; window ``sliding_window`` for ``l <
  N/2``: row ``t`` sees rows ``t - window + 1 .. t`` (+); full causal at ``l =
  N/2 + 1``): ``[q, k, v] = h Wqkv + b`` (+); consecutive heads pair (+):
  ``q1, q2`` (H/2 pairs), ``k1, k2``, ``v = [v1 | v2]`` (Hkv/2 pairs, each
  shared by ``H / Hkv`` query pairs); ``a_i = softmax(q_i k_i' / sqrt(hd) +
  mask) v``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` (+); ``o = (1 - lambda_init) *
  RMSNorm_{2 hd}(a_1 - lambda a_2)``; out = ``o Wo + b`` (+).
- **Gated memory unit** (``l`` even, ``l > N/2``): out = ``(m * silu(h W1))
  W2``, ``m`` the memory of the SAME token position.
- **Cross attention** (``l`` odd, ``l > N/2 + 1``): ``q = h Wq + b`` (+) only;
  differential attention over the K and V that layer ``N/2 + 1`` wrote (full
  causal); out = ``o Wo + b`` (+). It has no K/V of its own.

Each (+) is not in the published ``config.json``; it is set by the family's
convention and listed under ``assumed`` in the configuration file.

Arithmetic (``mode``): ``float32`` rounds nothing; ``bfloat16_operands`` is
float32 storage with every matmul operand rounded to bfloat16 and float32 sums
(the TPU's default matmul precision, which the configuration states); the scan
and every elementwise step stay float32 in both. The CONTROL (``control=True``)
is the nearest precision below: weights, activations and the scan's state
stored in bfloat16.
"""

from __future__ import annotations

import functools
import math
import threading

import numpy as np

from harness.reference import padded

MODES = ("float32", "bfloat16_operands", "bfloat16")
QUERY_BLOCK = 512   # rows of queries whose scores are held at a time


# ------------------------------------------------------------------ geometry
def layer_kinds(n_layers: int) -> list:
    """``mamba`` | ``window`` | ``full`` | ``gmu`` | ``cross`` for each
    layer, by the published rule (``N % 4 == 0``)."""
    if n_layers % 4:
        raise ValueError("sambay: num_hidden_layers must divide by 4")
    half = n_layers // 2
    kinds = []
    for l in range(n_layers):
        if l % 2 == 0:
            kinds.append("mamba" if l <= half else "gmu")
        elif l < half:
            kinds.append("window")
        else:
            kinds.append("full" if l == half + 1 else "cross")
    return kinds


def sizes(m: dict) -> dict:
    """The widths every function here needs, from the configuration's keys."""
    d = m["hidden_size"]
    h, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return {"d": d, "di": m["expand"] * d, "h": h, "hkv": hkv,
            "hd": d // h, "kvd": hkv * (d // h), "ff": m["intermediate_size"],
            "n": m["d_state"], "kc": m["d_conv"], "r": m["dt_rank"],
            "v": m["vocab_size"], "layers": m["num_hidden_layers"],
            "window": m["sliding_window"], "eps": m["layer_norm_eps"]}


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


# ------------------------------------------------------------------- weights
def weight_specs(m: dict) -> list:
    """(name, shape, how) in the order of the recipe. ``normal``: one
    ``standard_normal`` draw scaled by 0.5/sqrt(rows); ``lambda``: one draw
    scaled by 0.1; the rest are the family's constants and draw nothing."""
    z = sizes(m)
    d, di, ff, hd = z["d"], z["di"], z["ff"], z["hd"]
    specs = [("embed", (z["v"], d), "normal")]
    for l, kind in enumerate(layer_kinds(z["layers"])):
        p = f"l{l}."
        specs += [(p + "ln1_w", (d,), "ones"), (p + "ln1_b", (d,), "zeros")]
        if kind == "mamba":
            specs += [(p + "win", (d, 2 * di), "normal"),
                      (p + "conv_w", (z["kc"], di), "normal"),
                      (p + "conv_b", (di,), "zeros"),
                      (p + "wx", (di, z["r"] + 2 * z["n"]), "normal"),
                      (p + "wdt", (z["r"], di), "normal"),
                      (p + "b_dt", (di,), "dt_bias"),
                      (p + "a_log", (z["n"], di), "a_log"),
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
                specs += [(p + "wqkv", (d, d + 2 * z["kvd"]), "normal"),
                          (p + "bqkv", (d + 2 * z["kvd"],), "zeros")]
            specs += [(p + "lam", (4, hd), "lambda"),
                      (p + "sub_w", (2 * hd,), "ones"),
                      (p + "wo", (d, d), "normal"), (p + "bo", (d,), "zeros")]
        specs += [(p + "ln2_w", (d,), "ones"), (p + "ln2_b", (d,), "zeros"),
                  (p + "wg", (d, ff), "normal"), (p + "wu", (d, ff), "normal"),
                  (p + "wd", (ff, d), "normal")]
    return specs + [("lnf_w", (d,), "ones"), ("lnf_b", (d,), "zeros")]


def draw(rng, shape, how: str) -> np.ndarray:
    """One float32 array of the recipe. A drawn matrix is ONE stream of
    ``standard_normal`` values (taken in pieces, which gives the same values
    as one call and keeps the float64 temporaries small)."""
    if how in ("normal", "lambda"):
        scale = 0.1 if how == "lambda" else 0.5 / math.sqrt(shape[0])
        out = np.empty(shape, np.float32)
        flat = out.reshape(-1)
        for i in range(0, flat.size, 1 << 24):
            n = min(1 << 24, flat.size - i)
            flat[i:i + n] = rng.standard_normal(n) * scale
        return out
    if how == "ones":
        return np.ones(shape, np.float32)
    if how == "zeros":
        return np.zeros(shape, np.float32)
    if how == "a_log":      # A = -(1 .. d_state) in every channel
        return np.broadcast_to(
            np.log(np.arange(1, shape[0] + 1, dtype=np.float64))[:, None],
            shape).astype(np.float32)
    if how == "dt_bias":    # softplus(b) runs 1e-3 .. 1e-1 over the channels
        dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), shape[0]))
        return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
    raise ValueError(f"sambay reference: unknown recipe {how!r}")


def draw_weights(seed: int, m: dict) -> dict:
    """Every weight on the host from ``numpy.random.RandomState(seed)``, in
    the order of ``weight_specs``."""
    rng = np.random.RandomState(seed)
    return {name: draw(rng, shape, how)
            for name, shape, how in weight_specs(m)}


class HostWeights(threading.Thread):
    """``draw_weights`` on a thread of its own, started at once: the draw
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


@functools.lru_cache(maxsize=None)
def _forward_fn(model_items: tuple, mode: str):
    """One full causal forward over every row. See the module docstring."""
    import jax
    import jax.numpy as jnp

    if mode not in MODES:
        raise ValueError(f"reference: unknown mode {mode!r}")
    z = sizes(dict(model_items))
    kinds = layer_kinds(z["layers"])
    half = z["layers"] // 2
    first_window = kinds.index("window")
    qo = (lambda x: x) if mode == "float32" else _b16     # matmul operands
    low = mode == "bfloat16"
    qa = _b16 if low else (lambda x: x)                   # stored values
    hd, g = z["hd"], z["hkv"] // 2
    per = z["h"] // z["hkv"]          # query pairs a key/value pair serves

    def mm(a, b):
        return qo(a) @ qo(qa(b))

    def ln(x, w, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + z["eps"]) * w + b

    def mamba(w, p, h, pos, marks):
        s_len, di, kc = h.shape[0], z["di"], z["kc"]
        uz = qa(mm(h, w[p + "win"]))
        u, gate = uz[:, :di], uz[:, di:]
        upad = jnp.concatenate([jnp.zeros((kc - 1, di), u.dtype), u])
        conv = sum(w[p + "conv_w"][j][None, :] * upad[j:j + s_len]
                   for j in range(kc))
        uc = qa(jax.nn.silu(conv + w[p + "conv_b"]))
        dbc = qa(mm(uc, w[p + "wx"]))
        dt_r, bm, cm = jnp.split(dbc, [z["r"], z["r"] + z["n"]], axis=-1)
        dt = jax.nn.softplus(mm(dt_r, w[p + "wdt"]) + w[p + "b_dt"])
        dt = jnp.where((pos < marks[1])[:, None], dt, 0.0)   # pads: s stays
        a = -jnp.exp(w[p + "a_log"])                         # (n, di)

        def step(carry, inp):
            s, s_mark = carry
            dt_t, u_t, b_t, c_t, t = inp
            s = qa(jnp.exp(dt_t[None, :] * a) * s
                   + (dt_t * u_t)[None, :] * b_t[:, None])
            s_mark = jnp.where(t == marks[0] - 1, s, s_mark)
            return (s, s_mark), jnp.sum(s * c_t[:, None], axis=0)

        zero = jnp.zeros((z["n"], di), jnp.float32)
        (s_end, s_mark), ys = jax.lax.scan(step, (zero, zero),
                                           (dt, uc, bm, cm, pos))
        y = qa(ys + w[p + "dd"] * uc)
        tails = jnp.stack([jax.lax.dynamic_slice(upad, (mk, 0), (kc - 1, di))
                           for mk in marks])
        out = mm(qa(y * jax.nn.silu(gate)), w[p + "wout"])
        return out, y, jnp.stack([s_mark, s_end]), tails

    def attend(w, p, l, q, k, v, window):
        """Differential attention of every row of ``q`` over ``k`` / ``v``,
        a block of query rows at a time."""
        s_len = q.shape[0]
        qh = q.reshape(s_len, g, per, 2, hd)
        kh = k.reshape(s_len, g, 2, hd)
        vh = v.reshape(s_len, g, 2 * hd)
        qb = QUERY_BLOCK if s_len % QUERY_BLOCK == 0 else s_len
        k_pos = jnp.arange(s_len)

        def block(args):
            qc, q_pos = args
            sc = jnp.einsum("qgjcd,kgcd->gjcqk", qo(qc), qo(kh)) \
                / math.sqrt(hd)
            live = k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                live &= k_pos[None, :] > q_pos[:, None] - window
            prob = jax.nn.softmax(jnp.where(live, sc, -1e30), axis=-1)
            return jnp.einsum("gjcqk,kge->qgjce", qo(qa(prob)), qo(vh))

        a = jax.lax.map(block, (qh.reshape(-1, qb, g, per, 2, hd),
                                k_pos.reshape(-1, qb)))
        a = qa(a.reshape(s_len, g, per, 2, 2 * hd))
        lam_v = w[p + "lam"]
        li = lambda_init(l)
        lam = (jnp.exp(jnp.sum(lam_v[0] * lam_v[1]))
               - jnp.exp(jnp.sum(lam_v[2] * lam_v[3])) + li)
        dif = a[:, :, :, 0] - lam * a[:, :, :, 1]
        o = dif * jax.lax.rsqrt(jnp.mean(jnp.square(dif), axis=-1,
                                         keepdims=True) + z["eps"])
        o = (1.0 - li) * o * w[p + "sub_w"]
        return mm(qa(o.reshape(s_len, -1)), w[p + "wo"]) + w[p + "bo"]

    def fwd(w, tokens, rows, marks):
        """tokens (S,) padded at the END; rows (R,) positions whose
        next-token logits are wanted; marks (2,): prompt length and rows
        consumed. Returns the logits and the state a cache would hold."""
        pos = jnp.arange(tokens.shape[0])
        x = qa(w["embed"][tokens])
        state, mem, kf, vf = {}, None, None, None
        for l, kind in enumerate(kinds):
            p = f"l{l}."
            h = qa(ln(x, w[p + "ln1_w"], w[p + "ln1_b"]))
            if kind == "mamba":
                out, y, ssm, tails = mamba(w, p, h, pos, marks)
                if l == 0:
                    state["ssm"], state["conv"] = ssm, tails
                if l == half:
                    mem = y
            elif kind == "gmu":
                out = mm(qa(mem * jax.nn.silu(mm(h, w[p + "w1"]))),
                         w[p + "w2"])
            elif kind == "cross":
                q = qa(mm(h, w[p + "wq"]) + w[p + "bq"])
                out = attend(w, p, l, q, kf, vf, None)
            else:
                qkv = qa(mm(h, w[p + "wqkv"]) + w[p + "bqkv"])
                q, k, v = jnp.split(qkv, [z["d"], z["d"] + z["kvd"]], axis=-1)
                if l == first_window:
                    state["k1"], state["v1"] = k, v
                if kind == "full":
                    kf, vf = k, v
                    state["kf"], state["vf"] = k, v
                out = attend(w, p, l, q, k, v,
                             z["window"] if kind == "window" else None)
            x = qa(x + out)
            h2 = qa(ln(x, w[p + "ln2_w"], w[p + "ln2_b"]))
            act = qa(jax.nn.silu(mm(h2, w[p + "wg"])) * mm(h2, w[p + "wu"]))
            x = qa(x + mm(act, w[p + "wd"]))
        last = qa(ln(x[rows], w["lnf_w"], w["lnf_b"]))
        logits = qo(last) @ qo(qa(w["embed"])).T
        return logits.astype(jnp.float32), state

    jitted = jax.jit(fwd)

    def highest(w, tokens, rows, marks):
        with jax.default_matmul_precision("highest"):
            return jitted(w, tokens, rows, marks)

    return highest


# ---------------------------------------------------------- state comparison
PARTS = ("ssm", "conv", "k1", "v1", "kf", "vf")
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
    hi)``: from 0 it is the part prefill wrote (the recurrent state at the
    prompt's end; the rows prefill left in the window ring and in the full
    layer's pages); from the prompt's length on it is what the decode steps
    wrote (the state after the last consumed row; their rows). ``got_state``
    may carry ``ring_lo``, the first position the ring still holds: window rows
    before it were overwritten and are not compared. ``NOTHING`` where a part
    has no row to read here."""
    import jax.numpy as jnp

    whole, rows = _gap_fns()
    at = 0 if lo == 0 else 1
    out = [float(whole(ref_state[k][at], jnp.asarray(got_state[k][at])))
           for k in ("ssm", "conv")]
    ring_lo = max(lo, int(got_state.get("ring_lo", 0)))
    for k, first in (("k1", ring_lo), ("v1", ring_lo), ("kf", lo),
                     ("vf", lo)):
        out.append(float(rows(ref_state[k], jnp.asarray(got_state[k]),
                              first, hi)) if hi > first else NOTHING)
    return np.asarray(out, np.float64)


class Reference:
    """Full forward passes over ``prompt + served tokens``, one request at a
    time, padded to a few lengths so that few programs compile."""

    def __init__(self, seed: int, m: dict, mode: str, host_weights=None,
                 pad_to: int = 512):
        import jax

        self.m = {k: v for k, v in m.items()
                  if isinstance(v, (int, float))}
        self.mode, self.pad_to = mode, pad_to
        host = host_weights or draw_weights(seed, m)
        self.w = {k: jax.device_put(v) for k, v in host.items()}

    def forward(self, prompt, served, rows_pad: int, control: bool = False):
        """Over ``prompt + served[:-1]``: float32 logits (len(served),
        vocab), row i the distribution that chose ``served[i]``; and the
        state (``PARTS``): the first Mamba layer's scan state and conv tail
        at the prompt's end and after the last row, the first window layer's
        and the full layer's K and V rows (padded length, kv_dim).
        ``control`` computes it in bfloat16 storage."""
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        toks = np.zeros(padded(len(seq), self.pad_to), np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(padded(len(served), rows_pad), np.int32)
        rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        fn = _forward_fn(tuple(sorted(self.m.items())),
                         "bfloat16" if control else self.mode)
        logits, state = fn(self.w, toks, rows,
                           np.asarray([len(prompt), len(seq)], np.int32))
        return logits[:len(served)], state

    def free(self):
        self.w = None
