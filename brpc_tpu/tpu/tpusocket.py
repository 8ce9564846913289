"""TpuSocket — the Socket contract over the device DMA engine.

This is the transport graft (SURVEY §5.8): where a TCP Socket's wire is the
NIC and an RdmaEndpoint's wire is the HCA, a TpuSocket's wire is the PJRT
transfer engine — request payloads are DMA'd host->HBM, the addressed method
runs as a compiled XLA program on the device, and the result is DMA'd back;
completion wakes the RPC's call-id exactly like a response arriving off the
network. The RdmaEndpoint design maps over (SURVEY §3.5):

  TCP handshake exch GID/QPN  ->  tpu:// endpoint resolution to a device
  registered block pool       ->  pinned/aligned host numpy staging buffers
  post_send / CQ polling      ->  jax async dispatch / block_until_ready
  sliding window              ->  per-socket in-flight op bound

The whole client state machine (call ids, attempt versions, timeouts,
retries, hedging) is reused unchanged — a TpuSocket just happens to "reach"
a device instead of a peer host. Methods are registered as device programs;
EchoService.Echo ships by default so the reference's echo/rdma_performance
benchmarks run against a chip with no NIC in the datapath.
"""

from __future__ import annotations

import functools
import threading
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from brpc_tpu import fault as _fault
from brpc_tpu.butil.endpoint import EndPoint
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.butil.resource_pool import VersionedPool
from brpc_tpu.fiber import call_id as _cid
from brpc_tpu.fiber.execution_queue import ExecutionQueue
from brpc_tpu.metrics.reducer import Adder
from brpc_tpu.proto import rpc_meta_pb2
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.protocol import ParsedMessage

# device-side traffic counters (the /vars view of the "ICI NIC")
g_tpu_in_bytes = Adder("g_tpu_in_bytes")
g_tpu_out_bytes = Adder("g_tpu_out_bytes")

_fault.register("tpu.device.crash",
                "raise inside a registered device method (loopback path); "
                "the caller sees EINTERNAL, the socket survives")


class DeviceMethodRegistry:
    """Methods addressable on a device: 'Service.Method' -> handler.

    handler(device, meta, payload: bytes, attachment: bytes)
        -> (error_code, response_payload: bytes, attachment_out: bytes)
    """

    def __init__(self):
        self._methods: Dict[str, Callable] = {}
        self._lock = threading.Lock()

    def register(self, service: str, method: str, handler: Callable) -> None:
        with self._lock:
            self._methods[f"{service}.{method}"] = handler

    def find(self, service: str, method: str) -> Optional[Callable]:
        with self._lock:
            return self._methods.get(f"{service}.{method}")


_registry = DeviceMethodRegistry()


def register_device_method(service: str, method: str, handler: Callable) -> None:
    _registry.register(service, method, handler)


def device_method_registry() -> DeviceMethodRegistry:
    return _registry


# --------------------------------------------------------------------------
# default device programs
# --------------------------------------------------------------------------
@functools.cache
def _echo_program() -> Callable:
    """One program for every chip: jit follows the committed input, so
    the caller's ``device_put`` IS the placement."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x: x + jnp.uint8(0))


def _device_echo(device, meta, payload: bytes, attachment: bytes):
    """EchoService.Echo on a chip: payload + attachment round-trip HBM.

    SYNCHRONOUS (dispatch + materialize in one frame). Device-side
    overlap for pipelined traffic lives in the device-resident lane
    instead (tpu/device_lane.py: async Copy with fused batch dispatch
    never materializes on the host), which is also where bulk-throughput
    callers should be — this echo pays a full host->HBM->host round trip
    per call by design.
    """
    import jax

    from brpc_tpu.proto import echo_pb2

    req = echo_pb2.EchoRequest()
    req.ParseFromString(payload)
    blob = req.payload + attachment
    if not blob:
        resp = echo_pb2.EchoResponse(message=req.message)
        return errors.OK, resp.SerializeToString(), b""
    arr = np.frombuffer(blob, dtype=np.uint8)
    back = np.asarray(_echo_program()(jax.device_put(arr, device)))
    blob_out = back.tobytes()
    payload_out = blob_out[: len(req.payload)]
    att_out = blob_out[len(req.payload):]
    resp = echo_pb2.EchoResponse(message=req.message, payload=payload_out)
    return errors.OK, resp.SerializeToString(), att_out


_registry.register("EchoService", "Echo", _device_echo)


# --------------------------------------------------------------------------
# the socket
# --------------------------------------------------------------------------
class TpuSocket:
    """Implements the subset of the Socket contract the client stack uses:
    write(packet, id_wait), pending-id bookkeeping, set_failed, stats."""

    def __init__(self, remote: EndPoint):
        from brpc_tpu.tpu.mesh import resolve_device

        self.remote = remote
        self.device = resolve_device(remote)
        self.failed = False
        self.error_code = 0
        self.error_text = ""
        self.in_bytes = 0
        self.out_bytes = 0
        self.in_messages = 0
        self.out_messages = 0
        self._pending_ids = set()
        self._pending_lock = threading.Lock()
        # ordered executor = the device's submission queue (one in-flight
        # program per socket; the DMA engine pipelines underneath)
        self._queue = ExecutionQueue(self._run_batch)
        self.socket_id = _tpu_socket_pool.insert(self)

    # ---------------------------------------------------- socket contract
    def add_pending_id(self, cid: int) -> None:
        with self._pending_lock:
            self._pending_ids.add(cid)

    def remove_pending_id(self, cid: int) -> bool:
        """True iff the entry was present (caller owns its error delivery)."""
        with self._pending_lock:
            if cid in self._pending_ids:
                self._pending_ids.discard(cid)
                return True
            return False

    def write(self, data, id_wait: Optional[int] = None) -> int:
        if self.failed:
            if id_wait is not None:
                _cid.id_error(id_wait, errors.EFAILEDSOCKET)
            return errors.EFAILEDSOCKET
        packet = data if isinstance(data, IOBuf) else IOBuf(bytes(data))
        n = len(packet)
        self.out_bytes += n
        g_tpu_out_bytes.put(n)
        if id_wait is not None:
            self.add_pending_id(id_wait)
        self._queue.execute(packet)
        return 0

    def set_failed(self, code: int, reason: str = "") -> None:
        if code == errors.OK:
            code = errors.EFAILEDSOCKET  # never fail "successfully"
        if self.failed:
            return
        self.failed = True
        self.error_code = code
        self.error_text = reason
        _tpu_socket_pool.remove(self.socket_id)
        with _sockets_lock:
            _sockets.pop((self.remote.host, self.remote.device_ordinal), None)
        with self._pending_lock:
            pending = list(self._pending_ids)
            self._pending_ids.clear()
        from brpc_tpu.tpu.transport import _retriable

        fan = _retriable(code)
        for cid in pending:
            _cid.id_error(cid, fan)

    def close(self) -> None:
        self.set_failed(errors.EFAILEDSOCKET, "closed locally")

    # ------------------------------------------------------- the datapath
    def _run_batch(self, batch) -> None:
        if batch is None:
            return
        for packet in batch:
            self._run_one(packet)

    def _run_one(self, packet: IOBuf) -> None:
        from brpc_tpu.policy.trpc_std import TrpcStdProtocol
        from brpc_tpu.rpc.controller import handle_response_message
        from brpc_tpu.rpc.protocol import find_protocol

        proto = find_protocol("trpc_std") or TrpcStdProtocol()
        rc, msg = proto.parse(packet)
        if msg is None:
            return
        self.in_messages += 1
        meta = msg.meta
        handler = _registry.find(meta.request.service_name,
                                 meta.request.method_name)
        payload, attachment = TrpcStdProtocol.split_attachment(msg)
        err_text = ""
        if handler is None:
            code, resp_payload, att_out = errors.ENOMETHOD, b"", b""
            err_text = (f"no device method {meta.request.service_name}."
                        f"{meta.request.method_name}")
        else:
            try:
                if _fault.hit("tpu.device.crash") is not None:
                    raise RuntimeError("fault injected device crash")
                code, resp_payload, att_out = handler(
                    self.device, meta, payload, attachment)
            except Exception as e:
                code, resp_payload, att_out = errors.EINTERNAL, b"", b""
                err_text = f"device method raised: {e}"
        # build the response exactly as a remote peer would
        rmeta = rpc_meta_pb2.RpcMeta()
        rmeta.response.error_code = code
        if code != errors.OK:
            rmeta.response.error_text = err_text
        rmeta.correlation_id = meta.correlation_id
        rmeta.attempt_version = meta.attempt_version
        rmeta.attachment_size = len(att_out)
        body = IOBuf()
        if resp_payload:
            body.append(resp_payload)
        if att_out:
            body.append(att_out)
        n = len(body)
        self.in_bytes += n
        g_tpu_in_bytes.put(n)
        resp_msg = ParsedMessage(msg.protocol, rmeta, body)
        resp_msg.socket = self
        handle_response_message(resp_msg)


_tpu_socket_pool: VersionedPool = VersionedPool()
_sockets: Dict[Tuple[str, int], TpuSocket] = {}
_sockets_lock = threading.Lock()


def get_tpu_socket(ep: EndPoint, connect_timeout: float = 3.0):
    """Shared per-device socket (the SocketMap of the device world).

    Routing: ``tpu://host:port/ordinal`` (port set) is a REMOTE device — a
    peer process serving that chip; dial the cross-process tunnel
    (tpu/transport.py). ``tpu://host/ordinal`` (no port) is a local chip of
    this process; calls run as device programs in-process (the loopback
    fast path, like the reference short-circuiting 127.0.0.1).

    ``connect_timeout`` bounds a remote (re)dial — callers with a per-call
    deadline pass the smaller of the two budgets so a dead tunnel fails
    the call instead of outliving it."""
    if ep.port:
        from brpc_tpu.tpu.transport import connect_tpu

        return connect_tpu(ep, connect_timeout=connect_timeout)
    key = (ep.host, ep.device_ordinal)
    with _sockets_lock:
        sock = _sockets.get(key)
        if sock is None or sock.failed:
            sock = TpuSocket(ep)
            _sockets[key] = sock
        return sock
