"""Flagship workload: a transformer LM whose distributed traffic rides the
framework's collective layer.

This is the north-star demo (BASELINE.json): "parameter-server and allreduce
traffic carried over the framework rides XLA collectives over ICI". The
model trains under a dp×sp×tp mesh:

  dp — gradients sum over data shards (GSPMD-inserted psum = the
       ParallelChannel 'sum' merger over the dp axis)
  tp — attention heads + MLP width sharded; row-parallel matmuls psum over
       tp (PartitionChannel semantics)
  sp — sequence sharded; attention runs as ring attention (ring.py), KV
       blocks streaming between neighbors exactly like the reference's
       credit-windowed streams (SURVEY §5.7 mapping). Under the causal mask
       the rows go round in ring.py's zigzag order (every shard an early
       and a late block, so every chip has the same kernels to run): the
       batch is permuted once where it enters, and nothing after it
       depends on a row's position (no positional encoding, per-row norms
       and MLP, the loss a mean over rows)

Everything compiles under one jit. The ring's transfers fly under its
kernels by the order ring.py gives its hops; the gradients' all-reduce is
not overlapped with anything yet (PERF.md section 5). Pallas RMSNorm
(pallas_ops.py) is used on TPU.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from brpc_tpu.tpu.pallas_ops import rmsnorm, rmsnorm_reference
from brpc_tpu.tpu.ring import ring_attention, shard_rows


@dataclass(frozen=True)
class ModelConfig:
    vocab: int = 1024
    d_model: int = 256
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 1024
    max_seq: int = 512
    dtype: Any = jnp.float32
    use_pallas_norm: bool = False  # flip on for TPU runs
    # Pallas flash attention is the DEFAULT attention (VERDICT r3 #3:
    # load-bearing, not a demo): single-device runs the batched
    # fwd+bwd kernels, the sharded path runs the carry-form kernel
    # inside ring attention with a Pallas ring backward. Flip off to get
    # plain XLA attention (the numerics oracle / MFU baseline).
    use_flash_attention: bool = True
    use_fused_xent: bool = False       # Pallas fused cross-entropy loss

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def init_params(rng, cfg: ModelConfig) -> Dict:
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    scale = cfg.d_model ** -0.5

    def dense(key, shape):
        return (jax.random.normal(key, shape) * scale).astype(cfg.dtype)

    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 4)
        layers.append({
            "ln1": jnp.ones((cfg.d_model,), cfg.dtype),
            "wqkv": dense(k[0], (cfg.d_model, 3 * cfg.d_model)),
            "wo": dense(k[1], (cfg.d_model, cfg.d_model)),
            "ln2": jnp.ones((cfg.d_model,), cfg.dtype),
            "w1": dense(k[2], (cfg.d_model, cfg.d_ff)),
            "w2": dense(k[3], (cfg.d_ff, cfg.d_model)),
        })
    return {
        "embed": dense(keys[0], (cfg.vocab, cfg.d_model)),
        "head": dense(keys[1], (cfg.d_model, cfg.vocab)),
        "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
        "layers": layers,
    }


def param_shardings(cfg: ModelConfig, mesh: Mesh) -> Dict:
    """tp shards model width; everything is replicated over dp/sp."""
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    layer = {
        "ln1": ns(), "ln2": ns(),
        "wqkv": ns(None, "tp"),   # column-parallel: heads split over tp
        "wo": ns("tp", None),     # row-parallel: psum over tp after matmul
        "w1": ns(None, "tp"),
        "w2": ns("tp", None),
    }
    return {
        "embed": ns(None, "tp"),
        "head": ns(None, "tp"),
        "ln_f": ns(),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }


def _norm(x, w, cfg: ModelConfig):
    if cfg.use_pallas_norm:
        return rmsnorm(x, w)
    return rmsnorm_reference(x, w)


def _ring_layout(seq: int, mesh: Mesh, causal: bool):
    """How the rows of a sequence go over the mesh's sp axis: (ring.py's
    layout, the sequence position of each row in it; None where that is
    the sequence's own order). Zigzag wherever the causal mask would give
    the last chip sp times the first one's kernels and the rows cut into
    2 sp blocks."""
    sp = mesh.shape["sp"] if mesh is not None else 1
    if not causal or sp == 1 or seq % (2 * sp):
        return "contiguous", None
    return "zigzag", shard_rows(seq, sp, "zigzag")


def forward(params, tokens, cfg: ModelConfig, mesh: Mesh = None,
            causal: bool = True):
    """tokens [B, S] -> logits [B, S, V], both in sequence order. With a
    mesh, activations are dp/sp-sharded and attention is ring attention
    over sp; where the rows go round in another order (``_ring_layout``)
    the logits are put back here, a [B, S, V] relayout that the train
    step does not pay: ``loss_fn`` permutes its targets instead."""
    layout, order = _ring_layout(tokens.shape[1], mesh, causal)
    if order is None:
        return _forward_rows(params, tokens, cfg, mesh, causal, layout)
    logits = _forward_rows(params, tokens[:, order], cfg, mesh, causal,
                           layout)
    return logits[:, np.argsort(order)]


def _forward_rows(params, tokens, cfg: ModelConfig, mesh: Mesh,
                  causal: bool, layout: str):
    """``forward`` on rows that are already in ``layout``'s order; the
    logits come back in that order too."""
    B, S = tokens.shape
    H, Dh = cfg.n_heads, cfg.head_dim

    def constrain(x, *spec):
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))

    x = params["embed"][tokens].astype(cfg.dtype)  # [B,S,D]
    x = constrain(x, "dp", "sp", None)
    for layer in params["layers"]:
        with jax.named_scope("layer"):   # metadata only
            h = _norm(x, layer["ln1"], cfg)
            qkv = h @ layer["wqkv"]                    # [B,S,3D]
            qkv = qkv.reshape(B, S, 3, H, Dh)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            if mesh is not None:
                q = constrain(q, "dp", "sp", "tp", None)
                k = constrain(k, "dp", "sp", "tp", None)
                v = constrain(v, "dp", "sp", "tp", None)
                att = ring_attention(q, k, v, mesh, axis="sp", causal=causal,
                                     batch_axis="dp", head_axis="tp",
                                     use_flash=cfg.use_flash_attention,
                                     layout=layout)
            elif cfg.use_flash_attention:
                from brpc_tpu.tpu.pallas_ops import flash_attention_mha

                # [B,S,H,Dh] -> [B,H,S,Dh] for the per-head kernel
                att = flash_attention_mha(
                    q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), causal=causal,
                ).transpose(0, 2, 1, 3).astype(cfg.dtype)
            else:
                from brpc_tpu.tpu.ring import full_attention_reference

                att = full_attention_reference(q, k, v, causal=causal)
            att = att.reshape(B, S, cfg.d_model)
            x = x + att @ layer["wo"]
            x = constrain(x, "dp", "sp", None)
            h = _norm(x, layer["ln2"], cfg)
            x = x + jax.nn.gelu(h @ layer["w1"]) @ layer["w2"]
            x = constrain(x, "dp", "sp", None)
    with jax.named_scope("head"):
        x = _norm(x, params["ln_f"], cfg)
        logits = x @ params["head"]
        return constrain(logits, "dp", "sp", None)


def loss_fn(params, batch, cfg: ModelConfig, mesh: Mesh = None):
    tokens, targets = batch
    layout, order = _ring_layout(tokens.shape[1], mesh, True)
    if order is not None:
        # two int32 [B, S] arrays, once a step; no activation is ever
        # permuted back
        tokens, targets = tokens[:, order], targets[:, order]
    logits = _forward_rows(params, tokens, cfg, mesh, True,
                           layout).astype(jnp.float32)
    with jax.named_scope("loss"):
        if cfg.use_fused_xent and mesh is None:
            from brpc_tpu.tpu.pallas_ops import softmax_xent

            B, S, V = logits.shape
            return softmax_xent(logits.reshape(B * S, V),
                                targets.reshape(-1))
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -jnp.mean(ll)


def sgd_train_step(params, batch, cfg: ModelConfig, mesh: Mesh = None,
                   lr: float = 1e-3):
    """One full training step (fwd+bwd+update). GSPMD inserts the dp-psum
    for gradients and tp-psums for row-parallel matmuls automatically."""
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg, mesh)
    with jax.named_scope("update"):
        params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g.astype(p.dtype), params, grads)
    return params, loss


def make_train_step(cfg: ModelConfig, mesh: Mesh = None, lr: float = 1e-3):
    """Jitted train step + the shardings for params and batch. Without a
    mesh it is the single-device step (batched flash kernels, fused
    cross-entropy) and both shardings are None."""
    if mesh is None:
        @partial(jax.jit, donate_argnums=(0,))
        def step1(params, batch):
            return sgd_train_step(params, batch, cfg, None, lr)

        return step1, None, None
    pshard = param_shardings(cfg, mesh)
    batch_shard = (
        NamedSharding(mesh, P("dp", "sp")),
        NamedSharding(mesh, P("dp", "sp")),
    )

    @partial(jax.jit,
             in_shardings=(pshard, batch_shard),
             out_shardings=(pshard, NamedSharding(mesh, P())),
             donate_argnums=(0,))
    def step(params, batch):
        return sgd_train_step(params, batch, cfg, mesh, lr)

    return step, pshard, batch_shard


def demo_batch(rng, cfg: ModelConfig, batch: int, seq: int):
    tokens = jax.random.randint(rng, (batch, seq), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    return tokens, targets
