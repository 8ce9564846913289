"""Drive a whole run of ``run.py`` (rehearsal: the look for a chip is
skipped, everything else is the run's own code) with the timed path broken
underneath by one fault. ``test_benchmark.py`` starts this in a process of
its own and reads ``correct`` from the line.

    python broken_run.py <fault> <workload> [run.py arguments]

Faults: ``none``; ``token_altered`` (serving: a token changed where it is
produced); ``kv_store_bfloat16`` (serving: the K/V rows rounded to bfloat16
where the pools are stored, the lower-precision store a later PR might try);
``state_unchanged`` (training: the step returns its state as it
got it); ``half_batch`` (training: half of the rows left out of the loss,
the mean taken over the rest); ``no_exchange`` (training: the exchange
between chips left out -- every quarter of the sequence attends to itself).
"""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def plant(fault: str) -> None:
    if fault == "none":
        return
    if fault == "token_altered":
        from brpc_tpu.serving import model

        orig = model.TinyTransformer.decode_step

        def decode_step(self, tokens, positions, tables):
            out = np.array(orig(self, tokens, positions, tables))
            out[0] = (out[0] + 1) % self.config.vocab
            return out

        model.TinyTransformer.decode_step = decode_step
        return
    if fault == "kv_store_bfloat16":
        import jax.numpy as jnp

        from brpc_tpu.serving import kv_cache

        orig_update = kv_cache.PagedKVCache.update_pools

        def update_pools(self, k_pool, v_pool):
            low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
            orig_update(self, low(k_pool), low(v_pool))

        kv_cache.PagedKVCache.update_pools = update_pools
        return
    import jax
    import jax.numpy as jnp

    from brpc_tpu.tpu import train

    if fault == "state_unchanged":
        orig_make = train.make_train_step

        def make_train_step(cfg, mesh=None, lr=1e-3):
            step, pshard, bshard = orig_make(cfg, mesh, lr)

            def lazy(params, batch):
                _new, loss = step(jax.tree_util.tree_map(jnp.copy, params),
                                  batch)
                return params, loss

            return lazy, pshard, bshard

        train.make_train_step = make_train_step
    elif fault == "half_batch":
        def loss_fn(params, batch, cfg, mesh=None):
            tokens, targets = batch
            logits = train.forward(params, tokens, cfg, mesh).astype(
                jnp.float32)
            half = tokens.shape[1] // 2
            logp = jax.nn.log_softmax(logits[:, :half], axis=-1)
            ll = jnp.take_along_axis(logp, targets[:, :half, None], axis=-1)
            return -jnp.mean(ll)

        train.loss_fn = loss_fn
    elif fault == "no_exchange":
        def ring_attention(q, k, v, mesh, axis="sp", causal=True, **_kw):
            n = mesh.shape[axis]
            b, s, h, d = q.shape
            qs, ks, vs = (x.reshape(b, n, s // n, h, d) for x in (q, k, v))
            sc = jnp.einsum("bnqhd,bnkhd->bnhqk", qs, ks) / (d ** 0.5)
            live = jnp.tril(jnp.ones((s // n, s // n), dtype=bool))
            sc = jnp.where(live, sc, -1e30)
            out = jnp.einsum("bnhqk,bnkhd->bnqhd",
                             jax.nn.softmax(sc, axis=-1), vs)
            return out.reshape(b, s, h, d).astype(q.dtype)

        train.ring_attention = ring_attention
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, workload, rest = sys.argv[1], sys.argv[2], sys.argv[3:]
    n = "4" if workload.startswith("train") else "1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    plant(fault)
    import run

    sys.exit(run.main(["--workload", workload, "--rehearse-cpu", n] + rest))
