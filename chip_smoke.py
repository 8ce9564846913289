"""chip_smoke.py — the standing proof that the system starts on the chip.

One process, run from the root of a checkout:

    python chip_smoke.py                 # needs a TPU; 1 chip or 4
    python chip_smoke.py --rehearse-cpu  # same code, tiny shapes, on the CPU
    python chip_smoke.py --rehearse-cpu 4   # ... over 4 virtual devices

It drives the two paths users pay for through the entry points they call:

- serving: ``ServingEngine`` + ``LlmServingService`` on a ``Server``
  listening on ``tpu://127.0.0.1:0/0``, a ``Channel``/``Stub`` in this
  process, streamed ``Generate`` calls (what examples/llm_server does);
- training: three steps of ``train.make_train_step``;

at the widest configuration the repo's records have run (d_model=2048,
16 heads of 128, vocab 32768, 12 layers; weights random from a seed).
With one device it runs the single-device forms; with several it runs the
mesh forms (``MeshTransformer`` + ``ShardedKVCache`` over
``serving_mesh()`` with one prompt through the ring lane, the sharded
train step with flash on, the ParallelChannel collective fan-out) and
checks that every device holds shards and memory.

Every phase checks its own output; any failure raises and the exit code
is non-zero. Nothing is caught and reported as skipped. The last line of
stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import threading
import time

T0 = time.monotonic()
TAG = ""  # "[REHEARSAL cpu] " on every line of a rehearsal


def say(msg: str) -> None:
    print(f"{TAG}[{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class Sizes:
    """The at-scale shapes, and the tiny ones a CPU rehearsal uses."""

    def __init__(self, rehearsal: bool, n_devices: int):
        mesh = n_devices > 1
        if rehearsal:
            self.serve = dict(vocab=256, d_model=64, n_heads=4, n_layers=2)
            self.train = dict(vocab=256, d_model=64, n_heads=4, n_layers=2,
                              d_ff=128)
            self.batch, self.seq, self.train_dtype = 4, 64, "float32"
            self.ring_threshold, long_prompt = 96, 100
            self.num_blocks = 64
        else:
            self.serve = dict(vocab=32768, d_model=2048, n_heads=16,
                              n_layers=12)
            self.train = dict(vocab=32768, d_model=2048, n_heads=16,
                              n_layers=12, d_ff=8192)
            self.batch, self.seq, self.train_dtype = 4, 1024, "bfloat16"
            self.ring_threshold, long_prompt = 4096, 4100
            self.num_blocks = 512
        # one prompt <= 128 (pow-2 bucket), one in 129..1024 (128-multiple
        # bucket, several q tiles in the kernel); the mesh form adds one
        # past ring_threshold so the ring lane crosses sp
        self.warm_prompts = [20, 40] if rehearsal else [100, 300]
        self.concurrent = [12, 20, 33, 40] if rehearsal \
            else [48, 100, 128, 300]
        self.long_prompt = long_prompt if mesh else 0
        self.max_context = 8192 if mesh and not rehearsal else 1024
        self.max_new = 32 if not rehearsal else 8
        # "auto" is what a user runs: the flash kernel on a TPU. On the
        # CPU "auto" means the reference einsum, so a rehearsal names the
        # kernel to walk the path the chip will take (interpreted Pallas)
        self.attn = "flash" if rehearsal else "auto"


# --------------------------------------------------------------- serving
def _build_serving(sizes: Sizes, mesh, attn: str, num_blocks: int):
    from brpc_tpu.serving import (KVCacheConfig, MeshTransformer,
                                  ModelConfig, PagedKVCache, ShardedKVCache,
                                  TinyTransformer)

    cfg = ModelConfig(**sizes.serve, max_context=sizes.max_context,
                      attn=attn, ring_threshold=sizes.ring_threshold)
    kvc = KVCacheConfig(block_size=16, num_blocks=num_blocks)
    if mesh is None:
        kv = PagedKVCache(kvc, cfg.n_layers, cfg.kv_dim)
        return TinyTransformer(cfg, kv), kv
    kv = ShardedKVCache(kvc, cfg.n_layers, cfg.kv_dim, mesh=mesh)
    return MeshTransformer(cfg, kv), kv


def _reference_first_tokens(sizes: Sizes, mesh, lengths) -> dict:
    """First greedy token per prompt from a model built with
    attn="reference" (the O(S^2) einsum), called directly — outside the
    served path — on the same device and the same seeded weights."""
    blocks = max(lengths) // 16 + 2
    model, kv = _build_serving(sizes, mesh, "reference", blocks)
    out = {}
    for i, n in enumerate(lengths):
        table = kv.alloc_sequence(10_000 + i, n)
        out[n] = model.prefill(model.synth_prompt(n), table)
        kv.free_sequence(10_000 + i)
    kv.assert_idle("chip_smoke reference")
    model.close()
    return out


def _generate(stub, prompt_len: int, max_new: int):
    """One streamed Generate, as examples/llm_server/client.py does it.
    Returns (response tokens, concatenated TokenDelta tokens)."""
    from brpc_tpu import Controller, StreamOptions, stream_close, stream_create
    from brpc_tpu.proto import serving_pb2

    frames, final = [], threading.Event()

    def on_received(sid, msgs):
        for raw in msgs:
            delta = serving_pb2.TokenDelta()
            delta.ParseFromString(raw)
            frames.extend(delta.tokens)
            if delta.done:
                final.set()

    sid = stream_create(StreamOptions(on_received=on_received))
    cntl = Controller()
    cntl.stream_id = sid
    cntl.timeout_ms = 600_000
    resp = stub.Generate(
        serving_pb2.GenerateRequest(prompt_len=prompt_len,
                                    max_new_tokens=max_new),
        controller=cntl)
    check(not cntl.failed(),
          f"Generate(prompt_len={prompt_len}) failed: {cntl.error_text()}")
    check(final.wait(timeout=60),
          f"Generate(prompt_len={prompt_len}): no final TokenDelta frame")
    stream_close(sid)
    return list(resp.tokens), frames


def serving_phase(sizes: Sizes, mesh, native_lane: bool) -> None:
    import jax

    from brpc_tpu import Channel, ChannelOptions, Server, ServerOptions, Stub
    from brpc_tpu.proto import serving_pb2
    from brpc_tpu.serving import (EngineConfig, LlmServingService,
                                  ServingEngine)

    lengths = sorted(set(sizes.warm_prompts + sizes.concurrent
                         + ([sizes.long_prompt] if sizes.long_prompt
                            else [])))
    t = time.monotonic()
    want_first = _reference_first_tokens(sizes, mesh, lengths)
    say(f"serving: reference-attention first tokens for prompts {lengths} "
        f"in {time.monotonic() - t:.1f}s")

    t = time.monotonic()
    model, kv = _build_serving(sizes, mesh, sizes.attn, sizes.num_blocks)
    say(f"serving: model {sizes.serve} float32 staged "
        f"({model.param_nbytes / 2**30:.2f} GiB by handle) "
        f"in {time.monotonic() - t:.1f}s")
    if mesh is not None:
        _assert_spread("serving params", jax.tree.leaves(model._params), mesh)
        _assert_spread("serving KV pools", [kv.k_pools, kv.v_pools], mesh)
        memory_report(mesh.devices.flat)
    engine = ServingEngine(model, kv, EngineConfig(
        max_batch=8, token_budget=2 * sizes.max_context)).start()
    server = Server(ServerOptions(native_dataplane=native_lane))
    server.add_service(LlmServingService(engine))
    server.start("tpu://127.0.0.1:0/0")
    try:
        ch = Channel(ChannelOptions(native_transport=native_lane,
                                    timeout_ms=600_000))
        ch.init(str(server.listen_endpoint()))
        stub = Stub(ch, serving_pb2.DESCRIPTOR.services_by_name["LlmService"])

        def one(n):
            t1 = time.monotonic()
            tokens, frames = _generate(stub, n, sizes.max_new)
            check(len(tokens) == sizes.max_new,
                  f"prompt {n}: asked {sizes.max_new} tokens, "
                  f"got {len(tokens)}")
            check(frames == tokens,
                  f"prompt {n}: stream frames {frames} != response {tokens}")
            check(tokens[0] == want_first[n],
                  f"prompt {n}: first token {tokens[0]} != "
                  f"{want_first[n]} from attn='reference'")
            return time.monotonic() - t1

        for n in ([sizes.long_prompt] if sizes.long_prompt else []) \
                + sizes.warm_prompts:
            lane = "ring lane over sp" if n >= sizes.ring_threshold \
                else "flash prefill"
            say(f"serving: Generate prompt={n} new={sizes.max_new} "
                f"({lane}) OK in {one(n):.1f}s (set-up: includes compiles)")

        steps0, toks0 = engine.steps, engine.tokens_generated
        errs, threads = [], []

        def run(n):
            try:
                one(n)
            except BaseException as e:  # re-raised on the main thread
                errs.append(e)

        for n in sizes.concurrent:
            threads.append(threading.Thread(target=run, args=(n,)))
            threads[-1].start()
        for th in threads:
            th.join(timeout=900)
            check(not th.is_alive(), "concurrent Generate did not finish")
        if errs:
            raise errs[0]
        steps = engine.steps - steps0
        toks = engine.tokens_generated - toks0
        check(toks > 2 * steps,
              f"{toks} tokens in {steps} steps: the four requests never "
              f"shared a decode batch of more than 2")
        say(f"serving: 4 concurrent Generate prompts={sizes.concurrent} OK "
            f"({toks} tokens in {steps} engine steps)")
        dec = engine.snapshot()["decode"]
        # on the chip decode attention reads the pages in place; the
        # rehearsal's CPU takes the gather body
        ran, other = (("paged", "gather") if jax.default_backend() == "tpu"
                      else ("gather", "paged"))
        check(dec[f"decode_launches_{ran}"] > 0
              and dec[f"decode_launches_{other}"] == 0,
              f"decode launches took the wrong attention path: {dec}")
        say(f"serving: decode launches paged={dec['decode_launches_paged']} "
            f"gather={dec['decode_launches_gather']}, the rows' lengths "
            f"cover {dec['live_share']:.0%} of the padded buckets' pages")
    finally:
        server.stop()
        server.join()
        engine.stop()
    kv.assert_idle("chip_smoke serving")
    model.close()
    say("serving: engine stopped, KV ledger idle")


# ---------------------------------------------------------------- training
def train_phase(sizes: Sizes, devices) -> None:
    import jax
    import jax.numpy as jnp

    import __graft_entry__ as graft
    from brpc_tpu.tpu import train

    mesh_form = len(devices) > 1
    # Mosaic kernels are not auto-partitioned under GSPMD (the Pallas norm
    # raises NotImplementedError at trace time on a mesh) and loss_fn
    # takes the fused cross-entropy only without a mesh: the mesh form
    # runs with flash ON (carry-form kernel inside the ring) and XLA
    # norm/loss; one chip runs all three kernels
    cfg = train.ModelConfig(
        **sizes.train, max_seq=sizes.seq,
        dtype=getattr(jnp, sizes.train_dtype),
        use_flash_attention=True, use_pallas_norm=not mesh_form,
        use_fused_xent=not mesh_form)
    t = time.monotonic()
    mesh, params, losses = graft.train_steps(
        cfg, sizes.batch, sizes.seq, steps=3, lr=1e-2,
        devices=devices if mesh_form else None)
    if mesh_form:
        _assert_spread("train params", jax.tree.leaves(params), mesh)
        form = f"mesh {dict(mesh.shape)}, flash ON (ring), XLA norm/loss"
    else:
        form = "single device, flash+norm+xent kernels ON"
    check(all(l == l and abs(l) != float("inf") for l in losses),
          f"train losses not finite: {losses}")
    check(losses[2] < losses[0], f"train loss did not fall: {losses}")
    say(f"train: 3 steps {sizes.train} {cfg.dtype.__name__} "
        f"B={sizes.batch} S={sizes.seq} ({form}) losses="
        f"{[round(l, 4) for l in losses]} OK in "
        f"{time.monotonic() - t:.1f}s (set-up: includes compile)")


# ------------------------------------------------------------------- mesh
def _assert_spread(what: str, arrays, mesh) -> None:
    want = set(mesh.devices.flat)
    for a in arrays:
        check(a.sharding.device_set == want,
              f"{what}: an array lives on {len(a.sharding.device_set)} of "
              f"{len(want)} mesh devices")


def fanout_phase(devices) -> None:
    import __graft_entry__ as graft

    graft.collective_fanout(devices)
    say(f"fanout: ParallelChannel.call_tensor == _call_tensor_rpc over "
        f"{len(devices)} tpu://localhost/<i> sub-channels (gather, sum) OK")


def memory_report(devices) -> None:
    """What each device holds now. A backend that reports memory (the
    TPU does, the CPU does not) must report some on every device."""
    for d in devices:
        stats = d.memory_stats()
        if stats is None:
            say(f"device {d.id}: backend reports no memory stats")
            continue
        say(f"device {d.id}: bytes_in_use="
            f"{stats['bytes_in_use'] / 2**30:.2f} GiB "
            f"peak={stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")
        check(stats["bytes_in_use"] > 0,
              f"device {d.id} holds no memory: the work is not spread")


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    global TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", nargs="?", type=int, const=1,
                    default=0, metavar="N",
                    help="debug run at tiny shapes on N virtual CPU "
                         "devices (default 1); never a chip result")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        TAG = "[REHEARSAL cpu] "
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={args.rehearse_cpu}").strip()

    from brpc_tpu.tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        sys.exit(f"chip_smoke: needs a TPU, but JAX reports platform="
                 f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
                 f"device(s)). --rehearse-cpu runs the same code at tiny "
                 f"shapes for debugging; it is not a chip result.")
    from brpc_tpu import native
    from brpc_tpu.tpu.mesh import describe_devices

    native_lane = native.load_dataplane() is not None
    cache0 = _cache_entries(cache_dir)
    say(describe_devices())
    say(f"jax={jax.__version__} jaxlib={_version('jaxlib')} "
        f"libtpu={_version('libtpu')} python={sys.version.split()[0]}")
    placed = ("placed by JAX_COMPILATION_CACHE_DIR"
              if os.environ.get("JAX_COMPILATION_CACHE_DIR")
              else "default, set in code")
    say(f"compile cache: {cache_dir} ({cache0} entries at start; {placed})")
    say("rpc lane: " + ("native engine (C++ dataplane, TPUC shm tunnel)"
                        if native_lane else
                        f"Python twin (native engine not built: "
                        f"{native.dataplane_build_error()})"))

    sizes = Sizes(bool(args.rehearse_cpu), len(devices))
    mesh = None
    if len(devices) > 1:
        from brpc_tpu.tpu.mesh import serving_mesh

        mesh = serving_mesh(devices)
        say(f"mesh forms: serving_mesh {dict(mesh.shape)} over "
            f"{len(devices)} devices")
    serving_phase(sizes, mesh, native_lane)
    train_phase(sizes, devices)
    if mesh is not None:
        fanout_phase(devices)
    memory_report(devices)
    cache1 = _cache_entries(cache_dir)
    say(f"compile cache: {cache1} entries at end (+{cache1 - cache0}); "
        f"total set-up + run {time.monotonic() - T0:.1f}s")
    result = {"ok": True,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices)}}
    if args.rehearse_cpu:
        result = {"rehearsal": True, **result}
    print(TAG + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
