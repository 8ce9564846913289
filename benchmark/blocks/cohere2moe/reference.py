"""Block ``cohere2moe``'s plain reference: what ``correct`` is decided against.

The ``cohere2_moe`` decoder (Command A+; configuration keys of
https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json)
in straightforward ``jax.numpy`` under ``default_matmul_precision("highest")``:
attention as masked einsums over blocks of query rows (so that the scores
fit), every held expert over every row with the routing weight as a mask, no
cache, no kernel, no sorting. It imports nothing of the program and takes
nothing the program made: it draws its own weights from the seed by the
recipe the configuration states (``matrices`` / ``draw_matrix`` below).

The layers. ``d`` hidden, ``H`` query heads over ``G`` key/value heads of
``hd``, ``ff`` one expert's width, ``E`` routed experts of which the ``k``
best serve a token, ``Ns`` shared experts, 0-based layer ``l``, rows ``x``
(S, d):

- ``h = LN(x)``: LayerNorm, weight only (1), no bias, eps ``layer_norm_eps``.
- Parallel block: ``x <- x + Attn_l(h) + FFN_l(h)``, both from the same h.
- ``Attn``: ``q = h Wq`` (H x hd), ``k = h Wk``, ``v = h Wv`` (G x hd), no
  bias, no q/k norm; query head i reads key/value head ``i // (H / G)``;
  scale ``1 / sqrt(hd)``; causal. ``layer_types[l] == "sliding_attention"``:
  q and k turned by ``rope_gptj`` (pairs ``(2j, 2j + 1)``, angle ``p *
  theta^(-2j / hd)``, all hd dims; float32), row t sees rows ``t - window +
  1 .. t``. ``"full_attention"``: no positional encoding, the whole context.
  Output ``concat(heads) Wo``.
- ``FFN``: ``s = sigmoid(h Wr)`` (E wide; float32 at ``highest``, operands
  not rounded); the k largest; ``w_e = s_e / sum of those k``
  (``norm_topk_prob``); ``E_e(h) = (silu(h Wg) * (h Wu)) Wd``; result
  ``sum_e w_e E_e(h) + (1 / Ns) sum_s E_s(h)`` (+: the reading of
  ``shared_expert_combination_strategy: "average"``).
- After the last layer ``LN``, then ``logit_scale * h Embed'`` (tied).

**The share.** The reference is given the program's share: the experts
``expert_rank * held .. + held - 1`` and the vocabulary's first
``vocab_size`` rows. It routes over all E, adds its own experts' terms of
the routed sum and leaves the others out; that partial sum goes on.
``layer(...)`` below is one layer alone, for the share test.

Arithmetic (``mode``). Weights (the router's excepted: float32) and K/V rows
are bfloat16 VALUES in every mode, as the configuration stores them (K after
its rotation). ``bfloat16_operands`` rounds every matmul operand to bfloat16
and sums in float32 (the TPU's default precision, which the configuration
states); ``float32`` rounds no operand. The CONTROL (``control=True``) is the
step below: weights and K/V rows held in float8 (e4m3, one scale a tensor).

**Routing and the comparison.** A token whose k-th and (k+1)-th router
scores lie closer than the program's and the reference's rounding differ may
route differently in the two and then differ by a k-th of an expert's
output, which no tolerance fit for rounding admits. The reference therefore
reports, for every row and layer, its MARGIN: how far the nearest score of
an expert HELD here lies from the boundary between the k-th and the
(k+1)-th score (their mid-point): a held expert closer to it than the two
sides' scores differ may be among the k on one side only; experts held
elsewhere change nothing this chip computes but a weight sum, by less than
the margin. A row whose margin in any layer is under ``route_margin``
(``runner_args.reference``) is THIN: its logits row is returned flat, so
that ``judge``'s ``logit_gap`` reads 0 for it; its row of the full layer's
K/V (which follows the layers before it) is left out of ``kvf_gap_*``; and
the share of thin rows among a request's PROMPT rows (independent random
tokens) is itself a number compared, ``route_thin_share_prefill``. Among
the served rows the share is printed and not compared: greedy decoding over
random weights falls into short cycles, so one thin state repeats and a
request's share there is none or most of it. The first layer's K/V rows
depend on no routing and are compared whole. (Measured on the chip, PERF.md
section 4: the two sides' scores differ by up to 4e-4 three layers deep; a
swapped expert moves that row of the next layers' K/V by 3-9e-2 of its norm,
ten times the rounding level.)
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
QUERY_BLOCK = 128   # rows of queries whose scores are held at a time
WIDE = 1e9          # the margin of a row no near-tie of which matters here


# ------------------------------------------------------------------ geometry
def sizes(m: dict) -> dict:
    """The widths every function here needs, from the configuration's keys.
    ``num_experts`` counts the experts held here; ``num_routed_experts`` the
    published count (the same where it is left out)."""
    h, g = m["num_attention_heads"], m["num_key_value_heads"]
    held = m["num_experts"]
    total = m.get("num_routed_experts") or held
    kinds = ["window" if t == "sliding_attention" else "full"
             for t in m["layer_types"]]
    return {"d": m["hidden_size"], "h": h, "g": g, "hd": m["head_dim"],
            "qd": h * m["head_dim"], "kvd": g * m["head_dim"],
            "ff": m["intermediate_size"], "experts": total, "held": held,
            "lo": m.get("expert_rank", 0) * held,
            "k": m["num_experts_per_tok"], "ns": m["num_shared_experts"],
            "v": m["vocab_size"], "kinds": kinds, "layers": len(kinds),
            "window": m["sliding_window"], "eps": m["layer_norm_eps"],
            "theta": float(m.get("rope_theta", 50000.0)),
            "logit_scale": float(m.get("logit_scale", 1.0))}


# ------------------------------------------------------------------- weights
def matrices(m: dict) -> list:
    """(name, stream, shape, fan-in) of every drawn matrix of the share, as
    (rows in, columns out); a matrix is scaled by 0.5 / sqrt(fan-in), its
    rows in, but the embedding by 0.1 / sqrt(d) (a fan-in of 25 d: with
    rows as long as the layers' outputs a tied head returns the token it
    was given, and every answer is one token repeated). Stream ids: the
    embedding 10^6; layer l: 1000 l + (0 wq, 1 wk, 2 wv, 3 wo, 4 router,
    10 + 3 s + (0 wg, 1 wu, 2 wd) for shared expert s, 100 + 3 e + (0, 1, 2)
    for routed expert e by its PUBLISHED index)."""
    z = sizes(m)
    d, ff = z["d"], z["ff"]
    out = [("embed", 10 ** 6, (z["v"], d), 25 * d)]
    for l in range(z["layers"]):
        b = 1000 * l
        out += [(f"l{l}.wq", b, (d, z["qd"]), d),
                (f"l{l}.wk", b + 1, (d, z["kvd"]), d),
                (f"l{l}.wv", b + 2, (d, z["kvd"]), d),
                (f"l{l}.wo", b + 3, (z["qd"], d), z["qd"]),
                (f"l{l}.router", b + 4, (d, z["experts"]), d)]
        ids = ([("s", i, b + 10) for i in range(z["ns"])]
               + [("e", i, b + 100) for i in range(z["lo"],
                                                   z["lo"] + z["held"])])
        for kind, i, first in ids:
            out += [(f"l{l}.{kind}{i}.wg", first + 3 * i, (d, ff), d),
                    (f"l{l}.{kind}{i}.wu", first + 3 * i + 1, (d, ff), d),
                    (f"l{l}.{kind}{i}.wd", first + 3 * i + 2, (ff, d), ff)]
    return out


def draw_matrix(seed: int, stream: int, shape, fan_in: int) -> np.ndarray:
    """``numpy.random.Generator(Philox(key=[seed, stream]))``, one
    ``standard_normal`` float32 draw in row-major order, times ``0.5 /
    sqrt(fan_in)`` (a float32 product)."""
    rng = np.random.Generator(np.random.Philox(key=[seed, stream]))
    out = rng.standard_normal(shape, dtype=np.float32)
    out *= np.float32(0.5 / math.sqrt(fan_in))
    return out


def bf16_bits(x: np.ndarray) -> np.ndarray:
    """float32 -> the bits of the nearest bfloat16 (ties to even), uint16:
    the 18.9 GB of float32 weights never exist at once."""
    u = x.view(np.uint32)
    return ((u + (np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))))
            >> np.uint32(16)).astype(np.uint16)


def draw_weights(seed: int, m: dict, threads: int = 3) -> dict:
    """Every matrix of the share on the host: bfloat16 bits (uint16), the
    router's float32."""
    def one(spec):
        name, stream, shape, fan_in = spec
        w = draw_matrix(seed, stream, shape, fan_in)
        return name, (w if name.endswith(".router") else bf16_bits(w))

    with ThreadPoolExecutor(threads) as pool:
        return dict(pool.map(one, matrices(m)))


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


def rope(x, pos, theta: float):
    """``rope_gptj`` over the last axis of x (rows, heads, hd)."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd),
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def _layer_fns(z: dict, mode: str, control: bool):
    """The layer's pieces in one arithmetic: ``ln``, ``attn(w, p, h, pos,
    kind) -> (out, k, v)`` and ``ffn(w, p, h) -> (out, margin)``."""
    import jax
    import jax.numpy as jnp

    if mode not in MODES:
        raise ValueError(f"reference: unknown mode {mode!r}")
    qo = (lambda x: x) if mode == "float32" else _b16     # matmul operands
    st = _q8 if control else _b16                         # stored K/V rows
    # a stored weight: its bfloat16 value, or that held in float8
    wt = ((lambda x: _q8(x.astype(jnp.float32))) if control
          else (lambda x: x.astype(jnp.float32)))
    g, per, hd = z["g"], z["h"] // z["g"], z["hd"]

    def mm(a, b):
        return qo(a) @ qo(wt(b))

    def ln(x):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + z["eps"])

    def attn(w, p, h, pos, kind):
        s_len = h.shape[0]
        q = mm(h, w[p + "wq"]).reshape(s_len, z["h"], hd)
        k = mm(h, w[p + "wk"]).reshape(s_len, g, hd)
        v = st(mm(h, w[p + "wv"]))
        if kind == "window":
            q, k = rope(q, pos, z["theta"]), rope(k, pos, z["theta"])
        k = st(k.reshape(s_len, z["kvd"]))
        kh, vh = k.reshape(s_len, g, hd), v.reshape(s_len, g, hd)
        qb = QUERY_BLOCK if s_len % QUERY_BLOCK == 0 else s_len

        def block(args):
            qc, q_pos = args
            sc = jnp.einsum("qgjd,kgd->gjqk", qo(qc), qo(kh)) / math.sqrt(hd)
            live = pos[None, :] <= q_pos[:, None]
            if kind == "window":
                live &= pos[None, :] > q_pos[:, None] - z["window"]
            prob = jax.nn.softmax(jnp.where(live, sc, -1e30), axis=-1)
            return jnp.einsum("gjqk,kgd->qgjd", qo(prob), qo(vh))

        a = jax.lax.map(block, (q.reshape(-1, qb, g, per, hd),
                                pos.reshape(-1, qb)))
        return mm(a.reshape(s_len, z["qd"]), w[p + "wo"]), k, v

    def expert(w, name, h):
        return mm(jax.nn.silu(mm(h, w[name + ".wg"])) * mm(h, w[name + ".wu"]),
                  w[name + ".wd"])

    def ffn(w, p, h, ranks=None):
        """The routed sum over the experts of ``ranks`` (this share's where
        it is None) plus the shared experts' mean; and each row's margin
        (the module docstring)."""
        s = jax.nn.sigmoid(jnp.dot(h, w[p + "router"],
                                   precision=jax.lax.Precision.HIGHEST))
        top, idx = jax.lax.top_k(s, z["k"] + 1)
        wts = top[:, :z["k"]] / jnp.sum(top[:, :z["k"]], axis=-1,
                                        keepdims=True)
        lo = z["lo"]
        # how far the nearest HELD expert's score lies from the boundary
        # between the k-th and the (k+1)-th: closer than the two sides'
        # rounding differ, it may be in the top k on one side only
        edge = 0.5 * (top[:, -2] + top[:, -1])
        margin = jnp.min(jnp.abs(s[:, lo:lo + z["held"]] - edge[:, None]),
                         axis=-1) if z["held"] else jnp.full(s.shape[:1],
                                                             WIDE)
        out = jnp.zeros_like(h)
        for e in range(lo, lo + z["held"]):
            w_e = jnp.sum(jnp.where(idx[:, :z["k"]] == e, wts, 0.0), axis=-1)
            out = out + w_e[:, None] * expert(w, f"{p}e{e}", h)
        shared = sum(expert(w, f"{p}s{i}", h) for i in range(z["ns"]))
        return out + shared / z["ns"], margin

    return ln, attn, ffn


@functools.lru_cache(maxsize=None)
def _forward_fn(model_key: tuple, mode: str, control: bool):
    """One full causal forward over every row. See the module docstring."""
    import json

    import jax
    import jax.numpy as jnp

    z = sizes(json.loads(model_key[0]))
    ln, attn, ffn = _layer_fns(z, mode, control)
    first_full = z["kinds"].index("full")

    def fwd(w, tokens, rows):
        """tokens (S,) padded at the END; rows (R,) positions whose
        next-token logits are wanted. Returns the logits, the state a cache
        would hold and every row's margin by layer."""
        pos = jnp.arange(tokens.shape[0])
        emb = (_q8 if control else (lambda x: x))(
            w["embed"].astype(jnp.float32))
        x = emb[tokens]
        state, margins = {}, []
        for l, kind in enumerate(z["kinds"]):
            p = f"l{l}."
            h = ln(x)
            att, k, v = attn(w, p, h, pos, kind)
            if l == 0:
                state["k0"], state["v0"] = k, v
            if l == first_full:
                state["kf"], state["vf"] = k, v
            out, margin = ffn(w, p, h)
            margins.append(margin)
            x = x + att + out
        last = ln(x[rows])
        qo = (lambda a: a) if mode == "float32" else _b16
        logits = z["logit_scale"] * (qo(last) @ qo(emb).T)
        margins = jnp.stack(margins)
        # the full layer's rows follow the routing of the layers BEFORE it
        state["margin_before_full"] = jnp.min(
            jnp.concatenate([margins[:first_full],
                             jnp.full_like(margins[:1], WIDE)]), axis=0)
        return logits.astype(jnp.float32), state, jnp.min(margins, axis=0)

    jitted = jax.jit(fwd)

    def highest(w, tokens, rows):
        with jax.default_matmul_precision("highest"):
            return jitted(w, tokens, rows)

    return highest


def layer(m: dict, w: dict, l: int, x, mode: str = "float32"):
    """Layer ``l`` alone over rows ``x`` (S, d) at positions 0 .. S - 1:
    ``(attention's output, this share's FFN output: its experts' part of the
    routed sum + the shared experts' mean, the shared experts' mean)``. The
    share test adds the ranks' parts, the shared mean counted once."""
    import jax
    import jax.numpy as jnp

    z = sizes(m)
    ln, attn, ffn = _layer_fns(z, mode, False)
    p = f"l{l}."
    with jax.default_matmul_precision("highest"):
        h = ln(x)
        att, _k, _v = attn(w, p, h, jnp.arange(x.shape[0]), z["kinds"][l])
        out, _margin = ffn(w, p, h)
        only_shared, _ = _layer_fns({**z, "held": 0}, mode, False)[2](w, p, h)
    return att, out, only_shared


# ---------------------------------------------------------- state comparison
PARTS = ("k0", "v0", "kf", "vf", "thin")
NOTHING = -1.0     # a part with nothing to read in this request


@functools.lru_cache(maxsize=None)
def _rows_gap():
    import jax
    import jax.numpy as jnp

    def rows(ref, got, live):
        live = live[:, None]
        num = jnp.sum(jnp.where(live, jnp.square(got - ref), 0.0))
        den = jnp.sum(jnp.where(live, jnp.square(ref), 0.0))
        return jnp.sqrt(num / jnp.maximum(den, 1e-30))

    return jax.jit(rows)


def state_gaps(ref_state: dict, got_state: dict, lo: int, hi: int):
    """(5,) in the order of ``PARTS`` over positions ``[lo, hi)``: how far
    the first window layer's K and V rows still in the ring (from
    ``got_state["ring_lo"]`` on) and the first full layer's rows at
    positions that are not thin lie from the reference's, as a share of the
    reference's norm; and the share of thin positions among them.
    ``NOTHING`` where a part has no row to read here."""
    import jax.numpy as jnp

    rows = _rows_gap()
    pos = np.arange(ref_state["k0"].shape[0])
    span = (pos >= lo) & (pos < hi)
    ring = span & (pos >= int(got_state.get("ring_lo", 0)))
    thin = np.asarray(ref_state["margin_before_full"]) \
        < float(ref_state["route_margin"])
    thick = span & ~thin
    out = []
    for k, live in (("k0", ring), ("v0", ring), ("kf", thick),
                    ("vf", thick)):
        out.append(float(rows(ref_state[k],
                              jnp.asarray(got_state[k], jnp.float32),
                              jnp.asarray(live))) if live.any() else NOTHING)
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
        self.key = (json.dumps(self.m, sort_keys=True),)
        self.mode, self.pad_to = mode, pad_to
        self.route_margin = float(route_margin)
        host = host_weights or draw_weights(seed, m)
        self.w = {}
        for name in list(host):
            arr = jax.device_put(host.pop(name))
            self.w[name] = (arr if arr.dtype == jnp.float32 else
                            jax.lax.bitcast_convert_type(arr, jnp.bfloat16))
        self.trail = []     # (margin, gap) of every served token gone over

    def forward(self, prompt, served, rows_pad: int, control: bool = False):
        """Over ``prompt + served[:-1]``: float32 logits (len(served),
        vocab), row i the distribution that chose ``served[i]`` (FLAT for a
        thin row, see the module docstring; never in the control); and the
        state (``PARTS``): the first window layer's and the first full
        layer's K and V rows (padded length, kv_dim), the margins that
        decide which of the latter are compared, and ``route_margin``."""
        import jax.numpy as jnp

        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        toks = np.zeros(padded(len(seq), self.pad_to), np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(padded(len(served), rows_pad), np.int32)
        rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        fn = _forward_fn(self.key, self.mode, bool(control))
        logits, state, margin = fn(self.w, toks, rows)
        logits = logits[:len(served)]
        state["route_margin"] = self.route_margin
        if not control:
            at = np.asarray(margin)[rows[:len(served)]]
            tok = jnp.asarray(np.asarray(served, np.int32))
            gap = np.asarray(jnp.max(logits, axis=-1) - jnp.take_along_axis(
                logits, tok[:, None], axis=-1)[:, 0])
            self.trail.append((at, gap))
            logits = jnp.where(jnp.asarray(at < self.route_margin)[:, None],
                               0.0, logits)
        return logits, state

    def readings(self) -> str:
        """For a limit's reading: at each candidate margin, the share of the
        served tokens gone over that would be thin and the widest gap of
        the rest."""
        if not self.trail:
            return "no token gone over"
        at = np.concatenate([a for a, _g in self.trail])
        gap = np.concatenate([g for _a, g in self.trail])
        out = []
        for tau in (0.0, 1e-4, 3e-4, 1e-3, 2e-3, 4e-3, 8e-3):
            keep = at >= tau
            out.append(f"margin {tau:g}: thin {100 * (1 - keep.mean()):.2f}% "
                       f"widest gap of the rest "
                       f"{gap[keep].max() if keep.any() else 0:.3e}")
        return f"{len(at)} tokens; " + "; ".join(out)

    def free(self):
        if self.trail:
            print(f"[cohere2moe reference] {self.readings()}",
                  file=sys.stderr, flush=True)
        self.w = None
