"""Continuous-batching inference serving plane.

The device-side pieces of this repo (STREAM-rate HBM staging, flash/ring
attention, the batch runtime, streams, priority lanes) compose here into
one LLM-shaped request path, the way bRPC's value was the composed
Server + batching + streaming + deadline stack rather than any single
mechanism:

- :mod:`brpc_tpu.serving.kv_cache` — paged KV-cache block manager over
  DeviceStore HBM handles (fixed-size blocks, per-sequence block tables,
  refcounts, watermark admission backpressure).
- :mod:`brpc_tpu.serving.model` — a toy transformer whose weights and KV
  pools are streamed into HBM by handle; flash-attention prefill and a
  ring-attention long-context path.
- :mod:`brpc_tpu.serving.engine` — the iteration-level scheduler: each
  step is a mixed prefill+decode batch under a token budget, new requests
  admitted *between* decode steps (continuous batching).
- :mod:`brpc_tpu.serving.service` — the LlmService RPC surface with
  per-request token streaming over the Stream API.
- :mod:`brpc_tpu.serving.mesh_model` + the sharded KV classes — the
  mesh-sharded lane: per-device KV pools over the serving mesh's ``dp``
  axis, shard_map prefill/decode that keeps each engine step at ONE
  fused launch + ONE host sync across the whole mesh.
- :mod:`brpc_tpu.serving.router` — client-side shard routing: Generate
  lands on the owning partition through PartitionChannel (prefix-hash
  routed when the fleet runs the prefix cache); shard failures come back
  retriable (EFAILEDSOCKET).
- :mod:`brpc_tpu.serving.prefix_cache` — radix tree over token prefixes
  mapping to refcounted KV block chains: admission forks the longest
  cached prefix (zero copies), completion commits blocks back
  (insert-or-share), eviction is watermark-aware LRU over refcount-1
  chains.
- :mod:`brpc_tpu.serving.migration` — live KV block-chain migration over
  the ``tpu://`` record lane: a prefill shard hands a just-prefilled
  sequence to a decode shard (disaggregated serving), and a dying shard
  drains its live sequences onto survivors, with the paged ledger's
  quiesce/export/adopt handshake keeping block ownership single-writer
  throughout.
- :mod:`brpc_tpu.serving.qos` — multi-tenant QoS: weighted fair-share
  admission (stride-scheduled token budget per tenant), per-tenant
  queue caps, and the closed-loop overload governor — an AutoLimiter
  gradient ceiling driven by the queue-wait series ring, shedding
  best-effort lanes first so a protected tenant survives an overload
  wave EOVERCROWDED-retriable instead of everyone drowning together.
- :mod:`brpc_tpu.serving.hybrid_model` + :mod:`brpc_tpu.serving.hybrid_cache`
  — a SambaY decoder-hybrid-decoder (Mamba-1, window and full differential
  attention, gated memory units over one shared K/V) and its cache manager:
  full-layer pages, window rings and recurrent slots under one ledger.
- :mod:`brpc_tpu.serving.moe_model` — a ``cohere2_moe`` decoder over the
  same manager (window rings and full layers' pages, bfloat16, no
  recurrence) as ONE chip's share of an expert-parallel layer: a sigmoid
  router over all experts, a grouped product over the experts held here
  inside the one fused decode launch, shared experts averaged, a parallel
  attention + FFN block, rotary window layers beside position-free full
  ones.
- :mod:`brpc_tpu.serving.jamba_model` — a ``jamba`` decoder over the same
  manager (recurrent slots beside several full layers' pages, no ring,
  bfloat16 with a float32 recurrent state): Mamba-1 layers with normed
  dt / B / C beside position-free attention of many query heads over one
  key/value head, whose prefill CONTINUES from the slot's state and the
  pages, so the engine prefills a long prompt a chunk a step.
- :mod:`brpc_tpu.serving.zaya_model` — a ``zaya`` decoder over the same
  manager (pages of every layer beside a conv tail a layer and no scan
  state, bfloat16 with float32 tails, streams and router): compressed
  convolutional attention inside a narrow latent, then one of 16 whole
  experts, or none, chosen by an MLP router whose state goes down the
  layers beside the residual stream; it continues a prefill too.
- :mod:`brpc_tpu.serving.glm_model` — a ``glm4_moe_lite`` decoder over the
  same manager with ONE array a page row (``v_dim = 0``): multi-head latent
  attention whose page keeps the compressed latent and the shared rotary
  key, expanded through the flash kernels for prefill and absorbed for
  decode (``pallas_ops.mla_paged_decode`` reads each live row once, in
  place), beside a chip's share of sigmoid-routed top-k experts chosen with
  a selection bias, one shared expert and a leading dense layer.
- :mod:`brpc_tpu.serving.speculative` — the speculative-decoding draft
  lane: host-side prompt-lookup drafting (zero weights, zero device
  work, lint-pinned) feeding the model's one fused ``verify_step``
  launch per step; greedy acceptance keeps outputs bit-identical to
  plain decode while committing up to k+1 tokens per step.
"""

from brpc_tpu.serving.kv_cache import (KVCacheConfig, PagedKVCache,
                                       ShardedKVCache, ShardTable)
from brpc_tpu.serving.model import ModelConfig, TinyTransformer
from brpc_tpu.serving.engine import EngineConfig, ServingEngine, active_engines
from brpc_tpu.serving.prefix_cache import (PrefixCache, ShardedPrefixCache,
                                           build_prefix_cache,
                                           prefix_route_key)
from brpc_tpu.serving.qos import (QosConfig, QosGovernor, QosLimiter,
                                  TenantScheduler)
from brpc_tpu.serving.service import LlmServingService
from brpc_tpu.serving.speculative import (AdaptiveK, accept_longest_prefix,
                                          draft_tokens)


def __getattr__(name):
    # MeshTransformer / ShardedLlmChannel import lazily: they pull in the
    # mesh + combo-channel stacks, which plain single-device users of
    # this package never need at import time
    if name == "MeshTransformer":
        from brpc_tpu.serving.mesh_model import MeshTransformer
        return MeshTransformer
    if name == "ShardedLlmChannel":
        from brpc_tpu.serving.router import ShardedLlmChannel
        return ShardedLlmChannel
    # the migration plane imports lazily too: co-located deployments
    # never pay for the record-lane / fault wiring at import time
    if name == "KVMigrator":
        from brpc_tpu.serving.migration import KVMigrator
        return KVMigrator
    if name == "MigrationReceiver":
        from brpc_tpu.serving.migration import MigrationReceiver
        return MigrationReceiver
    # the hybrid (state-space + window + full attention) lane, as lazily
    if name in ("HybridCacheConfig", "HybridStateCache", "HybridTable"):
        from brpc_tpu.serving import hybrid_cache
        return getattr(hybrid_cache, name)
    if name in ("SambaYConfig", "SambaYModel", "HybridServingModel"):
        from brpc_tpu.serving import hybrid_model
        return getattr(hybrid_model, name)
    if name in ("Cohere2MoeConfig", "Cohere2MoeModel"):
        from brpc_tpu.serving import moe_model
        return getattr(moe_model, name)
    if name in ("JambaConfig", "JambaModel"):
        from brpc_tpu.serving import jamba_model
        return getattr(jamba_model, name)
    if name in ("ZayaConfig", "ZayaModel"):
        from brpc_tpu.serving import zaya_model
        return getattr(zaya_model, name)
    if name in ("GlmMoeLiteConfig", "GlmMoeLiteModel"):
        from brpc_tpu.serving import glm_model
        return getattr(glm_model, name)
    raise AttributeError(name)


__all__ = [
    "KVCacheConfig", "PagedKVCache", "ShardedKVCache", "ShardTable",
    "ModelConfig", "TinyTransformer", "MeshTransformer",
    "EngineConfig", "ServingEngine", "active_engines",
    "PrefixCache", "ShardedPrefixCache", "build_prefix_cache",
    "prefix_route_key",
    "LlmServingService", "ShardedLlmChannel",
    "KVMigrator", "MigrationReceiver",
    "HybridCacheConfig", "HybridStateCache", "HybridTable",
    "SambaYConfig", "SambaYModel", "HybridServingModel",
    "Cohere2MoeConfig", "Cohere2MoeModel", "JambaConfig", "JambaModel",
    "ZayaConfig", "ZayaModel", "GlmMoeLiteConfig", "GlmMoeLiteModel",
    "AdaptiveK", "accept_longest_prefix", "draft_tokens",
    "QosConfig", "QosGovernor", "QosLimiter", "TenantScheduler",
]
