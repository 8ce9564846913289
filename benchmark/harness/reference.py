"""The plain references: what `correct` is decided against.

Straightforward ``jax.numpy``, every product summed in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, nothing imported from the program and nothing taken from it. The
weights are made here from the seed by the recipe the configuration file
states (the same recipe the program follows; the arrays are never shared).

The CONTROL computes the same mathematics in the nearest precision below
the one the configuration states. Put in the program's place it has to come
out as not correct (``benchmark/limits.py`` runs it on the chip through the
runners' own ``judge``; the benchmark's own runs never do).

- serving: the configuration states float32 storage at the TPU's default
  matmul precision, which rounds every matmul operand to bfloat16; the
  reference computes exactly that, plainly (``bfloat16_operands``). The
  control is bfloat16 storage: weights and every activation.
- training (configuration states bfloat16 parameters and activations): the
  reference keeps the parameter store and the update rule of the
  configuration (bfloat16, ``p - lr * g.astype(bfloat16)``) and does the
  forward and backward arithmetic in float32; the control rounds every
  matmul operand to float8 (e4m3, one scale a tensor).
"""

from __future__ import annotations

import functools
import math
import threading

import numpy as np


def _q8(x):
    """x as float8 (e4m3) would hold it, with one scale for the tensor
    (its largest magnitude mapped to the format's 448), back in float32:
    the operand rounding of a float8 matmul."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return ((x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale)


# =================================================================== serving
def serve_weight_shapes(m: dict):
    d, v = m["d_model"], m["vocab"]
    shapes = [("embed", (v, d))]
    for l in range(m["n_layers"]):
        shapes += [(f"wqkv{l}", (d, 3 * d)), (f"wo{l}", (d, d)),
                   (f"w1{l}", (d, 2 * d)), (f"w2{l}", (2 * d, d))]
    return shapes


def serve_weights_host(seed: int, m: dict) -> dict:
    """Float32 weights from the seed: ``numpy.random.RandomState(seed)``,
    one ``standard_normal`` draw per matrix in the order of
    ``serve_weight_shapes``, scaled by ``0.5 / sqrt(rows)``."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape in serve_weight_shapes(m):
        n = int(np.prod(shape))
        out[name] = (rng.standard_normal(n) * (0.5 / np.sqrt(shape[0]))
                     ).astype(np.float32).reshape(shape)
    return out


class HostWeights(threading.Thread):
    """``serve_weights_host`` on a thread of its own, started at once: the
    draw takes as long as the program's own and needs no chip, so it runs
    beside the program's set-up and not after the window."""

    def __init__(self, seed: int, m: dict):
        super().__init__(daemon=True)
        self.seed, self.m, self.weights = seed, m, None
        self.start()

    def run(self):
        self.weights = serve_weights_host(self.seed, self.m)

    def get(self) -> dict:
        self.join()
        return self.weights


def _rms(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


MODES = ("float32", "bfloat16_operands", "bfloat16")


def _b16(x):
    """x as bfloat16 holds it, in float32 (``reduce_precision``: a pair of
    converts is what XLA's excess precision may fold away)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.lru_cache(maxsize=None)
def _serve_forward_fn(n_layers: int, n_heads: int, mode: str):
    """One full causal forward. ``mode`` is the arithmetic:

    - ``float32``: nothing rounded, every product at ``highest``;
    - ``bfloat16_operands``: float32 storage, every matmul operand rounded
      to bfloat16, products summed in float32 -- what the configuration
      states for the chip (the TPU's default matmul precision, one
      bfloat16 pass);
    - ``bfloat16``: the CONTROL, the nearest precision below: weights and
      every activation stored in bfloat16 (sums still float32)."""
    import jax
    import jax.numpy as jnp

    if mode not in MODES:
        raise ValueError(f"reference: unknown mode {mode!r}")
    qo = (lambda x: x) if mode == "float32" else _b16     # matmul operands
    qa = _b16 if mode == "bfloat16" else (lambda x: x)    # stored activations

    def fwd(w, tokens, rows):
        """tokens (S,) padded at the END (causal: pads change no earlier
        row); rows (R,) positions whose next-token logits are wanted.
        Returns those logits and every layer's K and V rows (L, S, D) as
        the paged cache stores them."""
        s = tokens.shape[0]
        x = qa(w["embed"][tokens])
        hd = x.shape[-1] // n_heads
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        ks, vs = [], []
        for l in range(n_layers):
            qkv = qa(qo(_rms(x)) @ qo(w[f"wqkv{l}"]))
            q, k, v = jnp.split(qkv, 3, axis=-1)
            ks.append(k)
            vs.append(v)
            qh, kh, vh = (t.reshape(s, n_heads, hd) for t in (q, k, v))
            sc = jnp.einsum("qhd,khd->hqk", qo(qh), qo(kh)) / math.sqrt(hd)
            sc = jnp.where(mask[None], sc, -1e30)
            p = jax.nn.softmax(sc, axis=-1)
            a = qa(jnp.einsum("hqk,khd->qhd", qo(p), qo(vh)).reshape(s, -1))
            x = qa(x + qo(a) @ qo(w[f"wo{l}"]))
            h = qa(jax.nn.relu(qo(_rms(x)) @ qo(w[f"w1{l}"])))
            x = qa(x + qo(h) @ qo(w[f"w2{l}"]))
        logits = qo(_rms(x[rows])) @ qo(w["embed"]).T
        return logits.astype(jnp.float32), jnp.stack(ks), jnp.stack(vs)

    jitted = jax.jit(fwd)

    def highest(w, tokens, rows):
        with jax.default_matmul_precision("highest"):
            return jitted(w, tokens, rows)

    return highest


@functools.lru_cache(maxsize=None)
def _kv_gap_fn():
    import jax
    import jax.numpy as jnp

    def gaps(ref, got, lo, hi):
        """Per layer, over rows [lo, hi): the norm of ``got - ref`` as a
        share of the norm of ``ref`` (both (L, S, D))."""
        pos = jnp.arange(ref.shape[1])
        live = ((pos >= lo) & (pos < hi))[None, :, None]
        num = jnp.sum(jnp.where(live, jnp.square(got - ref), 0.0), (1, 2))
        den = jnp.sum(jnp.where(live, jnp.square(ref), 0.0), (1, 2))
        return jnp.sqrt(num / jnp.maximum(den, 1e-30))

    return jax.jit(gaps)


def kv_gaps(ref_kv, got_kv, lo: int, hi: int) -> np.ndarray:
    """(2, L): per layer, for K and for V, how far ``got_kv`` lies from
    ``ref_kv`` over the rows [lo, hi), as a share of the reference's norm
    there. Each is a pair (K, V) of (L, S, D) arrays."""
    import jax.numpy as jnp

    fn = _kv_gap_fn()
    return np.asarray([np.asarray(fn(r, jnp.asarray(g), lo, hi), np.float64)
                       for r, g in zip(ref_kv, got_kv)])


def padded(n: int, pad_to: int) -> int:
    """n rounded up to the next multiple of ``pad_to``."""
    return -(-n // pad_to) * pad_to


class ServeReference:
    """Full forward passes over ``prompt + served tokens``, one request at
    a time, padded to a few lengths so that few programs compile."""

    def __init__(self, seed: int, m: dict, mode: str, host_weights=None,
                 pad_to: int = 512):
        import jax

        self.m, self.mode, self.pad_to = m, mode, pad_to
        host = host_weights or serve_weights_host(seed, m)
        self.w = {k: jax.device_put(v) for k, v in host.items()}
        self.w_low = None

    def forward(self, prompt, served, rows_pad: int, control: bool = False):
        """Over ``prompt + served[:-1]``: float32 logits (len(served),
        vocab), row i the distribution that chose ``served[i]``; and the K
        and V rows of every layer, (L, S_pad, D) each. ``control`` computes
        it in bfloat16 (see ``_serve_forward_fn``)."""
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        toks = np.zeros(padded(len(seq), self.pad_to), np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(padded(len(served), rows_pad), np.int32)
        rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        w = self.w
        if control:
            if self.w_low is None:
                self.w_low = {k: _b16(v) for k, v in self.w.items()}
            w = self.w_low
        fn = _serve_forward_fn(self.m["n_layers"], self.m["n_heads"],
                               "bfloat16" if control else self.mode)
        logits, k, v = fn(w, toks, rows)
        return logits[:len(served)], (k, v)

    def free(self):
        self.w = self.w_low = None


def token_gaps(ref_logits, tokens) -> np.ndarray:
    """By how much each token's reference logit lies below the reference's
    best at that position (0 where the token IS the reference's best)."""
    import jax.numpy as jnp

    tok = jnp.asarray(np.asarray(tokens, np.int32))
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tok[:, None], axis=-1)[:, 0]
    return np.asarray(best - got, dtype=np.float64)


# ================================================================== training
def _key(seed):
    """``jax.random.PRNGKey`` of a seed taken modulo 2**32 (a Python int or
    a traced uint32: the same key either way)."""
    import jax
    import jax.numpy as jnp

    if isinstance(seed, int):
        seed = seed % 2**32
    return jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))


def train_init_params(seed, m: dict):
    """Parameters from the seed by the configuration's recipe
    (``PRNGKey(seed)`` split into 2 + n_layers keys; each matrix
    ``normal * d_model**-0.5`` rounded to the configuration's ``dtype``;
    norms are ones). Written again here from ``tpu/train.py:init_params``;
    nothing is imported."""
    import jax
    import jax.numpy as jnp

    d, ff, v, nl = m["d_model"], m["d_ff"], m["vocab"], m["n_layers"]
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[m["dtype"]]
    keys = jax.random.split(_key(seed), 2 + nl)
    scale = d ** -0.5

    def dense(key, shape):
        return (jax.random.normal(key, shape) * scale).astype(dt)

    layers = []
    for i in range(nl):
        k = jax.random.split(keys[2 + i], 4)
        layers.append({"ln1": jnp.ones((d,), dt),
                       "wqkv": dense(k[0], (d, 3 * d)),
                       "wo": dense(k[1], (d, d)),
                       "ln2": jnp.ones((d,), dt),
                       "w1": dense(k[2], (d, ff)),
                       "w2": dense(k[3], (ff, d))})
    return {"embed": dense(keys[0], (v, d)), "head": dense(keys[1], (d, v)),
            "ln_f": jnp.ones((d,), dt), "layers": layers}


def train_batch(seed, step, batch: int, seq: int, vocab: int):
    """The batch of one step, from the seed: tokens uniform over the
    vocabulary, targets the tokens rolled left by one."""
    import jax
    import jax.numpy as jnp

    if isinstance(seed, int):
        seed = seed % 2**32
    salted = jnp.asarray(seed, jnp.uint32) ^ jnp.uint32(0x5EED)
    key = jax.random.fold_in(_key(salted), step)
    tokens = jax.random.randint(key, (batch, seq), 0, vocab)
    return tokens, jnp.roll(tokens, -1, axis=1)


FLAGS = ("lower", "no_exchange", "half_tokens")


def _train_loss(params, tokens, targets, flags, *, n_heads: int,
                q_block: int, row_block: int):
    """Mean next-token loss of ONE sequence (tokens (S,)), in float32,
    layer by layer under ``jax.checkpoint`` and attention in query blocks,
    so that 16k tokens fit one chip. ``flags`` (booleans, data not code, so
    one program serves the reference, its control and its faults):
    ``lower`` rounds every matmul operand to float8 e4m3 (the control);
    ``no_exchange`` lets each quarter of the sequence attend to itself
    alone (the ring's hops left out); ``half_tokens`` leaves the second
    half of the rows out of the loss and takes the mean over the rest."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    s = tokens.shape[0]
    lower, no_exchange, half_tokens = (flags[k] for k in FLAGS)

    def mm(a, b):
        return jnp.where(lower, _q8(a), a) @ jnp.where(lower, _q8(b), b)

    def norm(x, w):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6
        ) * w.astype(f32)

    def attend(q, k, v):
        hd = q.shape[-1]
        kpos = jnp.arange(s)

        def one(i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 0)
            qpos = i * q_block + jnp.arange(q_block)
            sc = jnp.einsum("qhd,khd->hqk", qs, k) / math.sqrt(hd)
            live = kpos[None, :] <= qpos[:, None]
            same = (kpos[None, :] // (s // 4)) == (qpos[:, None] // (s // 4))
            live = live & (same | ~no_exchange)
            sc = jnp.where(live[None], sc, -1e30)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, v)

        out = jax.lax.map(jax.checkpoint(one), jnp.arange(s // q_block))
        return out.reshape(s, -1)

    @jax.checkpoint
    def layer(x, lp):
        d = x.shape[-1]
        qkv = mm(norm(x, lp["ln1"]), lp["wqkv"].astype(f32))
        qkv = qkv.reshape(s, 3, n_heads, d // n_heads)
        att = attend(qkv[:, 0], qkv[:, 1], qkv[:, 2])
        x = x + mm(att, lp["wo"].astype(f32))
        h = jax.nn.gelu(mm(norm(x, lp["ln2"]), lp["w1"].astype(f32)))
        return x + mm(h, lp["w2"].astype(f32))

    x = params["embed"].astype(f32)[tokens]
    for lp in params["layers"]:
        x = layer(x, lp)
    x = norm(x, params["ln_f"])
    head = params["head"].astype(f32)
    weight = jnp.where(half_tokens & (jnp.arange(s) >= s // 2), 0.0, 1.0)

    @jax.checkpoint
    def rows_nll(xb, tb, wb):
        logp = jax.nn.log_softmax(mm(xb, head), axis=-1)
        return -jnp.sum(wb * jnp.take_along_axis(logp, tb[:, None],
                                                 axis=-1)[:, 0])

    nb = s // row_block
    nll = jax.lax.map(lambda a: rows_nll(*a),
                      (x.reshape(nb, row_block, -1),
                       targets.reshape(nb, row_block),
                       weight.reshape(nb, row_block)))
    return jnp.sum(nll) / jnp.sum(weight)


def _leaf_norms(before, after):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            b.astype(jnp.float32) - a.astype(jnp.float32)))), before, after)


@functools.lru_cache(maxsize=None)
def _train_program(model_items: tuple, batch: int, seq: int, lr: float,
                   steps: int, devices: tuple):
    """ONE program for every job: each device follows its own job (a seed
    and its flags) from the initial parameters through ``steps`` steps,
    under ``shard_map`` with nothing exchanged. Returns per device the
    losses and, per leaf, the norm of the change after every step."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    m = dict(model_items)
    q_block, row_block = min(seq, 1024), min(seq // 2, 2048)
    mesh = Mesh(np.array(devices), ("job",))

    def one_job(seed, flagvec):
        flags = {k: flagvec[i] for i, k in enumerate(FLAGS)}
        p0 = train_init_params(seed, m)

        def batch_loss(p, tokens, targets):
            per = [_train_loss(p, tokens[b], targets[b], flags,
                               n_heads=m["n_heads"], q_block=q_block,
                               row_block=row_block) for b in range(batch)]
            return sum(per) / len(per)

        def step(params, i):
            tokens, targets = train_batch(seed, i, batch, seq, m["vocab"])
            loss, grads = jax.value_and_grad(batch_loss)(params, tokens,
                                                         targets)
            # the configuration's store and update rule: SGD in its dtype
            new = jax.tree_util.tree_map(
                lambda p, g: p - lr * g.astype(p.dtype), params, grads)
            # the norms are of the parameters AS STORED: without the barrier
            # XLA (excess precision) folds the rounding to the store's type
            # out of the norm and reads the unrounded update
            new = jax.lax.optimization_barrier(new)
            return new, (loss, _leaf_norms(p0, new))

        _last, (losses, norms) = jax.lax.scan(step, p0, jnp.arange(steps))
        return losses, norms

    def per_device(seeds, flagmat):
        losses, norms = one_job(seeds[0], flagmat[0])
        return losses[None], jax.tree_util.tree_map(lambda a: a[None], norms)

    fn = jax.jit(shard_map(per_device, mesh=mesh, in_specs=(P("job"),
                                                             P("job")),
                           out_specs=P("job"), check_vma=False))

    def run(seeds, flagmat):
        with jax.default_matmul_precision("highest"):
            return fn(seeds, flagmat)

    run.jitted = fn      # for a compile-only pass without a chip
    return run


def train_reference_jobs(jobs, m: dict, batch: int, seq: int, lr: float,
                         steps: int, devices=None):
    """Follow each job -- ``{"seed": n, "lower": bool, "fault": name}`` --
    through the first ``steps`` steps, as many at a time as there are
    devices (one job a device; a short last round repeats its last job).
    Returns per job the losses and, per leaf, the norm of the first update
    over lr (the gradient as the optimizer applied it) and of the change
    after all the steps: the readings ``train_runner`` takes of the
    program."""
    import jax

    devices = tuple(devices or jax.devices())
    run = _train_program(tuple(sorted(m.items())), batch, seq, float(lr),
                         steps, devices)
    n, out = len(devices), []
    for lo in range(0, len(jobs), n):
        part = list(jobs[lo:lo + n])
        padded = part + [part[-1]] * (n - len(part))
        seeds = np.array([j["seed"] % 2**32 for j in padded], np.uint32)
        flagmat = np.array(
            [[bool(j.get("lower")), j.get("fault") == "no_exchange",
              j.get("fault") == "half_tokens"] for j in padded], bool)
        losses, norms = jax.device_get(run(seeds, flagmat))
        flat = jax.tree_util.tree_flatten_with_path(norms)[0]
        for i in range(len(part)):
            out.append({
                "losses": [float(x) for x in losses[i]],
                "grad_norms": {jax.tree_util.keystr(k): float(v[i, 0]) / lr
                               for k, v in flat},
                "change_norms": {jax.tree_util.keystr(k): float(v[i, -1])
                                 for k, v in flat}})
    return out


def train_reference_steps(seed: int, m: dict, batch: int, seq: int,
                          lr: float, steps: int, devices=None) -> dict:
    """The plain reference's one job (see ``train_reference_jobs``)."""
    return train_reference_jobs([{"seed": seed}], m, batch, seq, lr, steps,
                                devices)[0]


def leaf_change_norms(before, after, scale: float) -> dict:
    """{leaf path: ||after - before|| * scale}, norms taken in float32."""
    import jax

    norms = jax.jit(_leaf_norms)(before, after)
    flat = jax.tree_util.tree_flatten_with_path(norms)[0]
    return {jax.tree_util.keystr(k): float(v) * scale for k, v in flat}


def worst_leaf_gap(got: dict, ref: dict, skip_below: float = 0.0):
    """The widest gap between the program's norm and the reference's over
    the leaves, each measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger. Leaves whose reference norm
    is under ``skip_below`` x the median are left out (gradient nought to
    rounding). Returns (gap, leaf)."""
    med = float(np.median(list(ref.values())))
    worst, where = 0.0, ""
    for k, r in ref.items():
        if r < skip_below * med:
            continue
        gap = abs(got[k] - r) / max(r, med, 1e-30)
        if gap >= worst:
            worst, where = gap, k
    return worst, where
