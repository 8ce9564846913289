"""LLM serving example — the serving plane's flagship server.

Continuous-batching engine (paged KV over DeviceStore, iteration-level
scheduling) behind LlmService, with token streaming over the Stream API.
Browse http://<host>:<port>/serving while the client runs to watch batch
occupancy and the KV watermark.

    python examples/llm_server/server.py [--port 8011]
"""

import argparse
import sys
import time

from brpc_tpu.rpc import Server
from brpc_tpu.serving import (
    EngineConfig,
    KVCacheConfig,
    LlmServingService,
    ModelConfig,
    PagedKVCache,
    ServingEngine,
    TinyTransformer,
)


def build_engine(args) -> ServingEngine:
    model_cfg = ModelConfig(vocab=args.vocab, d_model=args.d_model,
                            n_heads=args.n_heads, n_layers=args.n_layers)
    kv = PagedKVCache(
        KVCacheConfig(block_size=args.block_size,
                      num_blocks=args.num_blocks,
                      watermark=args.watermark),
        model_cfg.n_layers, model_cfg.kv_dim)
    model = TinyTransformer(model_cfg, kv)
    engine = ServingEngine(model, kv, EngineConfig(
        max_batch=args.max_batch, token_budget=args.token_budget))
    return engine.start()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=8011)
    ap.add_argument("--max_batch", type=int, default=8)
    ap.add_argument("--token_budget", type=int, default=512)
    ap.add_argument("--block_size", type=int, default=16)
    ap.add_argument("--num_blocks", type=int, default=256)
    ap.add_argument("--watermark", type=float, default=0.90)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--d_model", type=int, default=64)
    ap.add_argument("--n_heads", type=int, default=4)
    ap.add_argument("--n_layers", type=int, default=2)
    ap.add_argument("--run_seconds", type=float, default=0)
    args = ap.parse_args(argv)

    from brpc_tpu.tpu.compile_cache import enable_compile_cache

    enable_compile_cache()  # one program per shape bucket: keep them
    engine = build_engine(args)
    server = Server().add_service(LlmServingService(engine))
    server.start(f"0.0.0.0:{args.port}")
    print(f"LlmServer on {server.listen_endpoint()} "
          f"(continuous batching, "
          f"{args.num_blocks}x{args.block_size}-token KV blocks) — "
          f"see /serving", flush=True)
    try:
        time.sleep(args.run_seconds or 1e9)
    except KeyboardInterrupt:
        pass
    server.stop()
    server.join()
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
