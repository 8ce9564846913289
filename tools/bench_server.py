"""Echo server subprocess for bench.py and the rdma_performance-style sweep.

Run as a child process so client and server do not share a GIL — the
reference benchmarks likewise run client and server as separate binaries
(/root/reference/example/multi_threaded_echo_c++/server.cpp). Prints
``LISTEN <endpoint>`` once the listener is up, then serves until stdin
closes (the parent holds the pipe). A mode that computes with JAX
(--batch/--device) owns the chip for its lifetime and prints
``DEVICE platform=... device_kind=... count=...`` before LISTEN.

    python tools/bench_server.py --listen 127.0.0.1:0
    python tools/bench_server.py --listen tpu://127.0.0.1:0/0
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from brpc_tpu.proto import echo_pb2  # noqa: E402
from brpc_tpu.rpc import Server, ServerOptions, Service  # noqa: E402


class BatchBenchService(Service):
    """--batch mode: the same jitted MLP served two ways, so bench.py can
    compare dispatch disciplines head to head on one process.

      Infer         — per-request: one jit call per RPC (B=1)
      InferBatched  — adaptive batching (brpc_tpu.batch): concurrent RPCs
                      coalesce into one padded jit call per bucket

    Requests reuse EchoRequest (no protoc in the container): ``payload``
    carries DIM float32 features; the response message is the output row's
    checksum so the client can verify real compute happened per item."""

    service_name = "BatchBench"
    DIM = 256
    LAYERS = 32
    BUCKETS = (1, 8, 32)

    def __init__(self):
        super().__init__()
        import numpy as np
        import jax
        import jax.numpy as jnp

        from brpc_tpu.batch import make_batched

        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        scale = 1.0 / np.sqrt(self.DIM)
        W = jax.random.normal(k1, (self.LAYERS, self.DIM, self.DIM),
                              jnp.float32) * scale
        b = jax.random.normal(k2, (self.LAYERS, self.DIM), jnp.float32) * .01

        @jax.jit
        def fwd(x):  # (B, DIM) -> (B, DIM)
            def layer(h, wb):
                return jax.nn.relu(h @ wb[0] + wb[1]), None
            h, _ = jax.lax.scan(layer, x, (W, b))
            return h

        self._np = np
        self._fwd = fwd
        self.add_method("Infer", self.Infer,
                        echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        self.add_method(
            "InferBatched",
            make_batched("BatchBench.InferBatched", self.InferBatched,
                         max_batch_size=self.BUCKETS[-1], max_delay_us=2000,
                         bucket_shapes=self.BUCKETS,
                         # steady pipelined load: let size/deadline shape
                         # the batches; boundary flushes would fragment
                         # them (each readable event admits only a few)
                         flush_on_poll_batch=False),
            echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        # pre-warm every bucket so first-compile never lands on a request
        for bb in self.BUCKETS:
            fwd(np.zeros((bb, self.DIM), np.float32)).block_until_ready()

    def _row(self, request):
        x = self._np.frombuffer(request.payload, self._np.float32)
        if x.shape != (self.DIM,):
            raise ValueError(f"want {self.DIM} float32 features, "
                             f"got {x.size}")
        return x

    def Infer(self, cntl, request, done):
        y = self._fwd(self._row(request)[None])
        return echo_pb2.EchoResponse(message=f"{float(y[0].sum()):.4f}")

    def InferBatched(self, batch):
        from brpc_tpu.rpc import errors

        rows = []
        for i, r in enumerate(batch.requests):
            try:
                rows.append(self._row(r))
            except Exception as e:
                batch.fail(i, errors.EREQUEST, str(e))
                rows.append(self._np.zeros(self.DIM, self._np.float32))
        x = batch.stack(rows)
        y = self._fwd(x)                     # ONE call for the whole batch
        sums = self._np.asarray(y.sum(axis=1))
        return [echo_pb2.EchoResponse(message=f"{float(sums[i]):.4f}")
                for i in range(batch.size)]


class EchoServiceImpl(Service):
    DESCRIPTOR = echo_pb2.DESCRIPTOR.services_by_name["EchoService"]

    def __init__(self, device_stream_impl=None):
        super().__init__()
        # --device mode: "device-stream[:window]" Echo requests open a
        # streaming-into-HBM stream (tpu/device_stream.py) on this port
        self.device_stream_impl = device_stream_impl

    def Echo(self, cntl, request, done):
        if (self.device_stream_impl is not None
                and request.message.startswith("device-stream")):
            return self.device_stream_impl.Echo(cntl, request, done)
        cntl.response_attachment = cntl.request_attachment
        return echo_pb2.EchoResponse(message=request.message,
                                     payload=request.payload)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--listen", default="127.0.0.1:0")
    ap.add_argument("--native", action="store_true",
                    help="serve through the C++ dataplane engine")
    ap.add_argument("--native_echo", action="store_true",
                    help="answer EchoService.Echo entirely in C++")
    ap.add_argument("--inline", action="store_true",
                    help="run user methods inline on the native poller "
                         "(the reference's usercode-in-parsing-bthread "
                         "default; safe for non-blocking handlers)")
    ap.add_argument("--device", action="store_true",
                    help="serve DeviceDataService (this process owns the "
                         "chip; payloads live in HBM, tpu/device_lane.py)")
    ap.add_argument("--batch", action="store_true",
                    help="serve BatchBench (same jitted MLP as Infer "
                         "per-request vs InferBatched through the "
                         "adaptive batcher, brpc_tpu/batch/)")
    ap.add_argument("--null", action="store_true",
                    help="answer Echo as the null-service CONTROL: raw "
                         "body echo from the poll loop, no policy "
                         "(bench ceiling isolation, VERDICT r4 #2a)")
    ap.add_argument("--shard-workers", type=int, default=0,
                    help="spread dispatch over N worker processes "
                         "(brpc_tpu/shard sharded dispatch plane; the "
                         "workers serve the same trpc_std echo)")
    args = ap.parse_args(argv)
    if args.null and not args.native:
        ap.error("--null requires --native (the control lane lives in "
                 "the native poll loop; without it you would measure the "
                 "full-policy path and call it the ceiling)")
    if args.shard_workers > 0:
        from brpc_tpu import flags

        flags.set_flag("tpu_shard_workers", args.shard_workers)
    owns_device = args.batch or args.device
    if owns_device:
        from brpc_tpu.tpu.compile_cache import enable_compile_cache

        enable_compile_cache()  # before the first compile below
    server = Server(ServerOptions(
        native_dataplane=args.native, usercode_inline=args.inline,
        shard_factory="brpc_tpu.shard.testing:echo_services"))
    stream_impl = None
    if args.device:
        from brpc_tpu.tpu.device_lane import DeviceDataService
        from brpc_tpu.tpu.device_stream import DeviceStreamEchoService

        dds = DeviceDataService()
        server.add_service(dds)
        # streaming-into-HBM lane (tpu/device_stream.py): blocks arrive
        # by reference, consumption = heavy on-device pump, block kept
        # resident so the bench can stream it repeatedly
        stream_impl = DeviceStreamEchoService(dds.store, rounds=1024,
                                              free_after=False)
    if args.batch:
        server.add_service(BatchBenchService())
    server.add_service(EchoServiceImpl(device_stream_impl=stream_impl))
    server.start(args.listen)
    if args.native_echo:
        server.register_native_echo("EchoService", "Echo")
    if args.null:
        server.register_null_method("EchoService", "Echo")
    if args.shard_workers > 0 and server._shard_plane is not None:
        # don't print LISTEN until the workers can take traffic — the
        # sweep must measure the plane, not worker interpreter boot
        server._shard_plane.wait_ready(30.0)
    if owns_device:
        from brpc_tpu.tpu.mesh import describe_devices

        print(f"DEVICE {describe_devices()}", flush=True)
    print(f"LISTEN {server.listen_endpoint()}", flush=True)
    try:
        sys.stdin.read()  # parent closing the pipe is the stop signal
    except KeyboardInterrupt:
        pass
    server.stop()
    server.join()
    # run-to-completion activation report: which methods ran inline on
    # the cut loop this run (bench.py surfaces this on its stderr; the
    # test_bench_quick smoke asserts the lane engaged on the shm sweep)
    from brpc_tpu.rpc import run_to_completion as _rtc

    st = _rtc.stats()
    per_method = " ".join(
        f"{name}:hits={m['hits']},ema_us={m['ema_us']},"
        f"demoted={int(m['demoted'])}"
        for name, m in st["methods"].items()) or "no-methods"
    print(f"# rtc inline_requests={st['inline_requests']} "
          f"inline_responses={st['inline_responses']} "
          f"demotions={st['demotions']} {per_method}",
          file=sys.stderr, flush=True)
    # series-ring report: the per-method qps rings the sampler daemon
    # accumulated while the sweep ran (test_bench_quick asserts these are
    # non-empty after the shm phase)
    from brpc_tpu.metrics.series import global_series

    for name, d in sorted(global_series().dump("rpc_method_*_qps").items()):
        nonzero = sum(1 for v in d["second"] if v)
        print(f"# vars series {name}: count={d['count']} "
              f"nonzero_1s={nonzero} last={d['last']}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
