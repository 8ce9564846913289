"""Mesh-sharded serving model: shard_map prefill/decode over dp/sp/tp.

:class:`MeshTransformer` lowers the toy transformer's serving programs
onto the full device mesh (``tpu/mesh.serving_mesh``) against a
:class:`~brpc_tpu.serving.kv_cache.ShardedKVCache`:

- **decode** — ONE shard_map program over the WHOLE mesh per engine step:
  the batch is grouped by owning dp shard, each dp group runs the exact
  single-device decode body (``model._decode_body``) against its local
  pool slice, and the step still costs one fused launch + one host
  materialization regardless of mesh size (the dispatch-count invariant
  the engine asserts under BRPC_TPU_CHECK).
- **prefill** — flash/reference attention tp-sharded over heads: each tp
  device attends its head slice, the head outputs are all_gather'ed back
  before the output projection (gather, not row-parallel psum, so the
  projection contracts the identical operands in the identical order as
  single-device — greedy equivalence stays BIT-exact, not just
  approximate). Every dp group traces the same program SPMD-style; only
  the owner's pool slice takes the K/V scatter.
- **ring lane** — prompts past ``ring_threshold`` run the ring-attention
  sequence-parallel path over this mesh's ``sp`` axis (``tpu/ring.py``),
  scattering into the owner's slice of the stacked pools.

Weights are replicated across the mesh and the stacked KV pools are
sharded over ``dp`` by ``named_sharding``; jit follows the input
shardings.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from brpc_tpu.profiling.registry import span as _span
from brpc_tpu.profiling.registry import wait_span as _wait_span
from brpc_tpu.serving.kv_cache import ShardedKVCache
from brpc_tpu.serving.model import (ModelConfig, TinyTransformer,
                                    _block_tables, _decode_body,
                                    _decode_buckets,
                                    _prefill_attention, _prefill_bucket,
                                    _rms)


class MeshTransformer(TinyTransformer):
    """TinyTransformer lowered across the serving mesh."""

    def __init__(self, config: ModelConfig, kv: ShardedKVCache,
                 store=None, mesh=None):
        mesh = mesh if mesh is not None else kv.mesh
        for ax in ("dp", "sp", "tp"):
            if ax not in mesh.axis_names:
                raise ValueError(f"serving mesh needs a {ax!r} axis, "
                                 f"got {mesh.axis_names}")
        self.dp = int(mesh.shape["dp"])
        self.tp = int(mesh.shape["tp"])
        if config.n_heads % self.tp:
            raise ValueError(
                f"n_heads={config.n_heads} must divide tp={self.tp}")
        if self.dp != kv.n_shards:
            raise ValueError(f"mesh dp={self.dp} != kv shards "
                             f"{kv.n_shards}")
        super().__init__(config, kv, store=store, mesh=mesh)

    # ------------------------------------------------------------- prefill
    def _mesh_prefill_fn(self, s_bucket: int, use_flash: bool):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from brpc_tpu.tpu.collective import shard_map_norep

        cfg = self.config
        H, hd = cfg.n_heads, cfg.head_dim
        Hl = H // self.tp

        def local(params, kpools, vpools, tokens, slots, length, owner):
            # every device traces the same prompt SPMD-style; tp shards
            # the attention heads, dp decides who keeps the K/V scatter
            kp, vp = kpools[0], vpools[0]
            dp_i = lax.axis_index("dp")
            tp_i = lax.axis_index("tp")
            own = (dp_i == owner)
            x = params["embed"][tokens]                      # (S, D)
            for l in range(cfg.n_layers):
                h = _rms(x)
                qkv = h @ params[f"wqkv{l}"]
                q, k, vv = jnp.split(qkv, 3, axis=-1)
                kp = jnp.where(own, kp.at[l, slots].set(k), kp)
                vp = jnp.where(own, vp.at[l, slots].set(vv), vp)
                # tp head shard: attend only this device's head slice,
                # packed again as the projection packs all heads
                local_qkv = jnp.concatenate(
                    [lax.dynamic_slice_in_dim(part, tp_i * Hl * hd, Hl * hd,
                                              1) for part in (q, k, vv)],
                    axis=1)
                attn = _prefill_attention(local_qkv, Hl, use_flash)
                # gather heads back before the projection: the matmul then
                # contracts the same (S, H*hd) operand as single-device,
                # keeping greedy decode bit-identical across mesh shapes
                attn = lax.all_gather(attn, "tp", axis=1, tiled=True)
                x = x + attn @ params[f"wo{l}"]
                h2 = _rms(x)
                x = x + jax.nn.relu(h2 @ params[f"w1{l}"]) @ params[f"w2{l}"]
            last = _rms(x[length - 1])
            logits = last @ params["embed"].T
            nxt = jnp.argmax(logits).astype(jnp.int32)
            return kp[None], vp[None], nxt

        sm = shard_map_norep(
            local, self.mesh,
            in_specs=(P(), P("dp"), P("dp"), P(), P(), P(), P()),
            out_specs=(P("dp"), P("dp"), P()))
        return jax.jit(sm, donate_argnums=(1, 2))

    def prefill(self, tokens: np.ndarray, table: Sequence[int]) -> int:
        s = len(tokens)
        bucket = _prefill_bucket(s)
        with _span("model.prefill", n=s, bucket=bucket):
            if s >= self.config.ring_threshold:
                return self._prefill_ring(tokens, table)
            self.kv.assert_writable(table, 0, s)
            with _span("model.prep"):
                shard = getattr(table, "shard", 0)
                use_flash = self._use_flash()
                key = (bucket, use_flash)
                with self._lock:
                    fn = self._prefill_cache.get(key)
                    if fn is None:
                        fn = self._mesh_prefill_fn(bucket, use_flash)
                        self._prefill_cache[key] = fn
                toks = np.zeros(bucket, dtype=np.int32)
                toks[:s] = tokens
                slots = self._slots_for(table, s, bucket)
            from brpc_tpu.tpu.device_lane import step_dispatch
            with _span("model.launch"):
                step_dispatch.note_launch(1)
                kpools, vpools, nxt = fn(self._params, self.kv.k_pools,
                                         self.kv.v_pools, toks, slots,
                                         np.int32(s), np.int32(shard))
                self.kv.update_pools(kpools, vpools)
            with _wait_span("model.sync"):
                first = int(nxt)
                step_dispatch.note_host_sync()
            return first

    def _prefill_ring(self, tokens: np.ndarray,
                      table: Sequence[int]) -> int:
        """Long-context lane over THIS mesh's sp axis: ring attention per
        layer, K/V scattered into the owner's slice of the stacked
        pools. Host-side layer loop as in the single-device lane."""
        import jax
        import jax.numpy as jnp

        from brpc_tpu.tpu import ring
        from brpc_tpu.tpu.device_lane import step_dispatch

        cfg = self.config
        H, hd = cfg.n_heads, cfg.head_dim
        shard = int(getattr(table, "shard", 0))
        n = int(self.mesh.shape["sp"])
        s = len(tokens)
        self.kv.assert_writable(table, 0, s)
        pad = ((s + n - 1) // n) * n
        p = self._params
        with _span("model.launch"):   # one launch a layer, none waited for
            toks = np.zeros(pad, dtype=np.int32)
            toks[:s] = tokens
            x = p["embed"][jnp.asarray(toks)]
            kpools, vpools = self.kv.k_pools, self.kv.v_pools
            slots = jnp.asarray(self._slots_for(table, s, pad))
            for l in range(cfg.n_layers):
                h = _rms(x)
                qkv = h @ p[f"wqkv{l}"]
                q, k, vv = jnp.split(qkv, 3, axis=-1)
                kpools = kpools.at[shard, l, slots].set(k)
                vpools = vpools.at[shard, l, slots].set(vv)
                qh = q.reshape(1, pad, H, hd)
                kh = k.reshape(1, pad, H, hd)
                vh = vv.reshape(1, pad, H, hd)
                step_dispatch.note_launch(1)
                attn = ring.ring_attention(qh, kh, vh, self.mesh, "sp",
                                           causal=True)
                x = x + attn.reshape(pad, -1) @ p[f"wo{l}"]
                h2 = _rms(x)
                x = x + jax.nn.relu(h2 @ p[f"w1{l}"]) @ p[f"w2{l}"]
            self.kv.update_pools(kpools, vpools)
            logits = _rms(x[s - 1]) @ p["embed"].T
        with _wait_span("model.sync"):
            first = int(jnp.argmax(logits))
            step_dispatch.note_host_sync()
        return first

    # -------------------------------------------------------------- decode
    def _decode_fn(self, b_bucket: int, l_table: int, paged: bool):
        import jax
        from jax.sharding import PartitionSpec as P

        from brpc_tpu.tpu.collective import shard_map_norep

        cfg = self.config

        def local(params, kpools, vpools, tokens, positions, block_tables):
            # each dp group decodes its own sub-batch from its own pool
            # slice; sp/tp devices in the group replicate the compute so
            # the whole mesh stays inside ONE program launch
            kp, vp, nxt = _decode_body(
                cfg, params, kpools[0], vpools[0], tokens[0], positions[0],
                block_tables[0], b_bucket, l_table, paged)
            return kp[None], vp[None], nxt[None]

        sm = shard_map_norep(
            local, self.mesh,
            in_specs=(P(), P("dp"), P("dp"), P("dp"), P("dp"), P("dp")),
            out_specs=(P("dp"), P("dp"), P("dp")))
        return jax.jit(sm, donate_argnums=(1, 2))

    def decode_step(self, tokens: np.ndarray, positions: np.ndarray,
                    tables: List[Sequence[int]]) -> np.ndarray:
        """ONE fused launch for the WHOLE mesh: sequences grouped by
        owning dp shard, per-shard sub-batches padded to a common bucket,
        one shard_map program, one host materialization.

        Speculative ``verify_step`` rides this unchanged: a sequence's
        k+1 verify rows share its ShardTable, so the shard grouping keeps
        them contiguous and in position order on the owning dp shard —
        the per-shard ``_decode_body`` sees exactly the single-pool row
        layout and the verify lowering stays bit-identical across tp/dp
        splits, still one launch and one sync for the whole mesh."""
        B = len(tokens)
        dp = self.dp
        # bucket by TOTAL batch, not the max per-shard group: the shard
        # split depends on seq-id hashing, so group-derived buckets churn
        # the jit cache across otherwise-identical workloads (a cold
        # compile mid-serving is a multi-hundred-ms step); total-batch
        # buckets cost a little padding and make the combo set a pure
        # function of the workload
        b_bucket, l_bucket = _decode_buckets(B, tables, self.kv.block_size)
        with _span("model.decode", B=B, b_bucket=b_bucket,
                   l_bucket=l_bucket):
            self.kv.assert_writable_batch(tables, positions)
            with _span("model.prep"):
                groups: List[List[int]] = [[] for _ in range(dp)]
                for i, t in enumerate(tables):
                    groups[getattr(t, "shard", 0)].append(i)
                fn, width = self._decode_program(b_bucket, l_bucket,
                                                 positions, groups=dp)
                toks = np.zeros((dp, b_bucket), dtype=np.int32)
                pos = np.zeros((dp, b_bucket), dtype=np.int32)
                for shard, g in enumerate(groups):
                    for j, i in enumerate(g):
                        toks[shard, j] = tokens[i]
                        pos[shard, j] = positions[i]
                block_tables = np.stack([
                    _block_tables([tables[i] for i in g], b_bucket, width)
                    for g in groups])
            from brpc_tpu.tpu.device_lane import step_dispatch
            with _span("model.launch"):
                step_dispatch.note_launch(1)
                kpools, vpools, nxt = fn(self._params, self.kv.k_pools,
                                         self.kv.v_pools, toks, pos,
                                         block_tables)
                self.kv.update_pools(kpools, vpools)
            with _wait_span("model.sync"):
                flat = np.asarray(nxt)
                step_dispatch.note_host_sync()
                out = np.zeros(B, dtype=np.int32)
                for shard, g in enumerate(groups):
                    for j, i in enumerate(g):
                        out[i] = flat[shard, j]
            return out
