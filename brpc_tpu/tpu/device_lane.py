"""device_lane — device-resident RPC payloads (the honest ICI-analog).

SURVEY §5.8 maps the reference's RDMA transport onto the PJRT transfer
engine. The host↔HBM wire is the slow side of that mapping (its rate is
not measured on the current machine; see PERF.md), so payloads are not
staged through the device per RPC. What real TPU systems do instead:
tensors LIVE in HBM, the host orchestrates, and data-plane movement
happens on-device (ICI for multi-chip). This module gives the RPC
framework exactly that contract:

- ``DeviceStore``: handle -> jax.Array registry on the serving process's
  chip. Handles are small integers that ride normal RPC responses; the
  payload bytes stay in HBM.
- ``DeviceDataService``: a standard Service (full policy path — runs over
  any transport: TCP, the shm tunnel, h2) exposing
  ``Put`` (attachment -> HBM, returns handle), ``Copy`` (handle -> new
  handle, on-device DMA — the data-plane op), ``Stats`` (bytes resident /
  moved), ``Get`` (handle -> attachment) and ``Free``.
- Device methods for the in-process TpuSocket lane (tpu/tpusocket.py)
  registered under the same names.

``Copy`` dispatches asynchronously (jax async dispatch IS the DMA queue);
pipelined Copy RPCs overlap on the device like pipelined RDMA writes on a
QP — the per-op sync happens only when a result is fetched or ``Stats``
asks for a fence.

Reference counterpart: rdma/block_pool.cpp registers memory once and
moves data by reference; here HBM is the registered memory and handles
are the references.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

import numpy as np

from brpc_tpu.metrics.reducer import Adder
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.server import Service
from brpc_tpu.proto import device_lane_pb2

g_device_resident_bytes = Adder("g_device_resident_bytes")
g_device_moved_bytes = Adder("g_device_moved_bytes")
g_device_fused_launches = Adder("g_device_fused_launches")
g_device_fused_ops = Adder("g_device_fused_ops")
g_device_host_syncs = Adder("g_device_host_syncs")


class DispatchCounter:
    """Fused-launch / host-sync ledger for step-level dispatch coalescing.

    The serving engine's contract is that one step costs ONE fused device
    program plus ONE host materialization, no matter the batch or mesh
    size. The contract is only enforceable if launches are *countable*:
    the model notes every program launch and every host sync here, the
    engine asserts the per-step delta under BRPC_TPU_CHECK, and the bench
    lanes derive device-op rates from the same numbers."""

    def __init__(self):
        self._lock = threading.Lock()
        self.launches = 0
        self.ops = 0
        self.host_syncs = 0

    def note_launch(self, n_ops: int = 1) -> None:
        with self._lock:
            self.launches += 1
            self.ops += n_ops
        g_device_fused_launches.put(1)
        g_device_fused_ops.put(n_ops)

    def note_host_sync(self) -> None:
        with self._lock:
            self.host_syncs += 1
        g_device_host_syncs.put(1)

    def snapshot(self) -> Tuple[int, int, int]:
        with self._lock:
            return self.launches, self.ops, self.host_syncs

    @staticmethod
    def delta(before: Tuple[int, int, int],
              after: Tuple[int, int, int]) -> Tuple[int, int, int]:
        return tuple(a - b for a, b in zip(after, before))


# process-wide counter the serving step loop reports into (tests snapshot
# around a step; /serving and the bench lanes read the running totals)
step_dispatch = DispatchCounter()


class DeviceStore:
    """handle -> device array registry for one process's chip."""

    def __init__(self, device=None):
        import collections
        import jax

        self._device = device if device is not None else jax.devices()[0]
        self._lock = threading.Lock()
        self._next = 1
        self._arrays: Dict[int, object] = {}
        self._copy_fn = None
        # transient copy outputs: held long enough to be fence-able, then
        # dropped — sustained data-plane traffic must not grow residency
        # until the allocator thrashes
        self._transient = collections.deque(maxlen=32)
        # dispatch coalescing (isolated vs back-to-back dispatch cost:
        # not measured on the current machine) — transient copies queue
        # here and a dedicated thread issues them contiguously, the
        # command-buffer trick every real device runtime plays
        self._dq = collections.deque()
        self._dq_cv = threading.Condition()
        self._dq_thread = None
        self._dq_busy = False
        self._batch_fns: Dict[int, object] = {}  # k -> fused copy program
        # per-STORE accounting (the global Adders below aggregate across
        # stores for /vars; Stats answers for THIS store)
        self._resident_bytes = 0
        self._moved_bytes = 0

    @property
    def device(self):
        return self._device

    # ------------------------------------------------------------- data plane
    def put(self, data: bytes) -> Tuple[int, int]:
        """Stage bytes into HBM (the one host->device crossing); returns
        (handle, nbytes)."""
        import jax

        arr = jax.device_put(np.frombuffer(data, dtype=np.uint8),
                             self._device)
        with self._lock:
            h = self._next
            self._next += 1
            self._arrays[h] = arr
            self._resident_bytes += len(data)
        g_device_resident_bytes.put(len(data))
        return h, len(data)

    def copy(self, handle: int,
             transient: bool = False) -> Optional[Tuple[int, int]]:
        """On-device copy: HBM -> HBM through the compiled datapath (async
        dispatch; this is the device data-plane op RPCs orchestrate).
        transient=True keeps the output only in a bounded ring (handle 0):
        sustained traffic measured without growing residency."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            arr = self._arrays.get(handle)
        if arr is None:
            return None
        if self._copy_fn is None:
            # no placement argument: jit follows the committed input,
            # which put()/adopt() left on this store's device
            self._copy_fn = jax.jit(lambda x: x + jnp.uint8(0))
        n = arr.nbytes
        if transient:
            # coalesced dispatch: the RPC answers with handle 0 now; the
            # dispatcher thread issues queued copies back-to-back
            with self._dq_cv:
                if self._dq_thread is None:
                    self._dq_thread = threading.Thread(
                        target=self._dispatch_loop, daemon=True,
                        name="brpc-device-dispatch")
                    self._dq_thread.start()
                self._dq.append(arr)
                self._dq_cv.notify()
            with self._lock:
                self._moved_bytes += 2 * n
            g_device_moved_bytes.put(2 * n)
            return 0, n
        out = self._copy_fn(arr)  # async: queues DMA, returns immediately
        step_dispatch.note_launch(1)
        with self._lock:
            h = self._next
            self._next += 1
            self._arrays[h] = out
            self._resident_bytes += n
            self._moved_bytes += 2 * n
        g_device_resident_bytes.put(n)
        g_device_moved_bytes.put(2 * n)  # read + write through HBM
        return h, n

    def copy_coalesced(self, handle: int,
                       count: int) -> Optional[Tuple[int, int]]:
        """Enqueue ``count`` transient copies of one handle as a SINGLE
        Python-level dispatch — the per-step batch API the serving engine
        rides: all of a step's device ops land in the dispatch queue in
        one call and the dispatcher thread fuses them into O(1) compiled
        programs instead of ``count`` isolated dispatches.
        Returns (0, total_bytes_queued) like a transient copy."""
        with self._lock:
            arr = self._arrays.get(handle)
        if arr is None:
            return None
        count = max(1, min(int(count), 4096))
        n = arr.nbytes
        with self._dq_cv:
            if self._dq_thread is None:
                self._dq_thread = threading.Thread(
                    target=self._dispatch_loop, daemon=True,
                    name="brpc-device-dispatch")
                self._dq_thread.start()
            self._dq.extend([arr] * count)
            self._dq_cv.notify()
        with self._lock:
            self._moved_bytes += 2 * n * count
        g_device_moved_bytes.put(2 * n * count)
        return 0, n * count

    def pump(self, handle: int, rounds: int) -> Optional[Tuple[int, int]]:
        """`rounds` HBM echo round trips over the array via the Pallas copy
        loop (tpu/bench_kernels.echo_loop_probe) with a DEPENDENT 4-byte
        fetch: the checksum both completes the work and proves the
        copies preserved the data. Returns (checksum, moved_bytes)."""
        import jax
        import jax.numpy as jnp

        from brpc_tpu.tpu.bench_kernels import echo_loop_probe
        from brpc_tpu.tpu.pallas_ops import _on_tpu

        with self._lock:
            arr = self._arrays.get(handle)
        if arr is None:
            return None
        rounds = max(1, min(int(rounds), 100000))
        lanes = 2048
        words = arr.nbytes // 4
        rows = max(1, words // lanes)
        use = rows * lanes * 4
        if use > arr.nbytes:
            return None  # need at least one full row
        x8 = arr[:use].reshape(rows, lanes, 4)
        x2d = jax.lax.bitcast_convert_type(x8, jnp.int32).reshape(rows,
                                                                  lanes)
        val = echo_loop_probe(x2d, rounds=rounds, interpret=not _on_tpu())
        checksum = int(jax.device_get(val))  # dependent fetch = real sync
        moved = 4 * rounds * use  # 2 copies x (read+write) per round
        with self._lock:
            self._moved_bytes += moved
        g_device_moved_bytes.put(moved)
        return checksum, moved

    def get(self, handle: int) -> Optional[bytes]:
        with self._lock:
            arr = self._arrays.get(handle)
        if arr is None:
            return None
        return np.asarray(arr).tobytes()

    def lookup(self, handle: int):
        """The device-resident array behind a handle (no host copy) — how
        batched methods (brpc_tpu.batch) gather HBM operands for one fused
        call instead of fetching per item."""
        with self._lock:
            return self._arrays.get(handle)

    def adopt(self, arr) -> Tuple[int, int]:
        """Register an already-device-resident array under a fresh handle
        (no host crossing). The serving plane parks its paged KV pools here
        so pool residency shows up in /vars and Stats next to staged
        payloads."""
        with self._lock:
            h = self._next
            self._next += 1
            self._arrays[h] = arr
            self._resident_bytes += arr.nbytes
        g_device_resident_bytes.put(arr.nbytes)
        return h, arr.nbytes

    def replace(self, handle: int, arr) -> bool:
        """Swap the array behind a live handle. Functional updates (jit
        with donated buffers) produce a NEW array each step; the handle
        stays the stable name for the pool across steps."""
        with self._lock:
            old = self._arrays.get(handle)
            if old is None:
                return False
            self._arrays[handle] = arr
            delta = arr.nbytes - old.nbytes
            self._resident_bytes += delta
        if delta:
            g_device_resident_bytes.put(delta)
        return True

    def free(self, handle: int) -> bool:
        with self._lock:
            arr = self._arrays.pop(handle, None)
            if arr is not None:
                self._resident_bytes -= arr.nbytes
        if arr is None:
            return False
        g_device_resident_bytes.put(-arr.nbytes)
        return True

    def _batched_copy_fn(self, k: int):
        """One compiled program copying k arrays — a whole queue drain is
        ONE dispatch. Under a busy server the GIL opens gaps between
        Python-level dispatches, which defeats device command coalescing
        (gap and per-op cost: not measured on the current machine);
        fusing k ops into one executable sidesteps the interpreter, the
        classic XLA batch-the-work move."""
        import jax
        import jax.numpy as jnp

        fn = self._batch_fns.get(k)
        if fn is None:
            fn = jax.jit(lambda *xs: tuple(x + jnp.uint8(0) for x in xs))
            self._batch_fns[k] = fn
        return fn

    def _dispatch_loop(self) -> None:
        import logging

        from brpc_tpu.profiling import registry as _prof

        _prof.register_current_thread(_prof.ROLE_BATCH)
        while True:
            with self._dq_cv:
                while not self._dq:
                    self._dq_busy = False
                    self._dq_cv.notify_all()  # fence waiters
                    self._dq_cv.wait()
                self._dq_busy = True
                batch = list(self._dq)
                self._dq.clear()
            try:
                # group same-spec arrays, pad to a power-of-two bucket so
                # the jit cache stays small, run each group as one dispatch
                groups = {}
                for a in batch:
                    groups.setdefault((a.shape, str(a.dtype)), []).append(a)
                for arrs in groups.values():
                    i = 0
                    while i < len(arrs):
                        left = len(arrs) - i
                        k = 1
                        while k * 2 <= min(left, 32):
                            k *= 2
                        fn = self._batched_copy_fn(k)
                        outs = fn(*arrs[i:i + k])
                        step_dispatch.note_launch(k)
                        self._transient.extend(outs)
                        i += k
            except Exception:
                # the thread must survive (a dead dispatcher with
                # _dq_busy=True wedges every fence() forever); the dropped
                # batch only loses transient outputs
                logging.getLogger("brpc_tpu").exception(
                    "device dispatch batch failed (dropped)")

    def fence(self) -> None:
        """Block until every queued device op has retired."""
        with self._dq_cv:
            while self._dq or self._dq_busy:
                self._dq_cv.wait(0.01)
        with self._lock:
            arrs = list(self._arrays.values())
        for a in arrs:
            a.block_until_ready()
        for a in list(self._transient):
            a.block_until_ready()

    def stats(self) -> Tuple[int, int, int]:
        with self._lock:
            return (len(self._arrays), self._resident_bytes,
                    self._moved_bytes)


_store: Optional[DeviceStore] = None
_store_lock = threading.Lock()


def global_store() -> DeviceStore:
    global _store
    with _store_lock:
        if _store is None:
            _store = DeviceStore()
        return _store


class DeviceDataService(Service):
    """Device-resident payload service over the normal RPC stack (full
    policy path; any transport). Payload bytes ride attachments exactly
    once (Put/Get); Copy moves data purely on-device."""

    DESCRIPTOR = device_lane_pb2.DESCRIPTOR.services_by_name[
        "DeviceDataService"]

    def __init__(self, store: Optional[DeviceStore] = None):
        super().__init__()
        self.store = store or global_store()

    def Put(self, cntl, request, done):
        handle, n = self.store.put(cntl.request_attachment)
        return device_lane_pb2.DeviceHandle(handle=handle, nbytes=n)

    def Copy(self, cntl, request, done):
        # request.nbytes == -1: transient output (bounded ring, handle 0);
        # request.nbytes == -k (k > 1): k transient copies coalesced into
        # ONE RPC — the per-step batch ride that lifts device-op rate past
        # the per-RPC dispatch ceiling
        if request.nbytes < -1:
            out = self.store.copy_coalesced(request.handle, -request.nbytes)
        else:
            out = self.store.copy(request.handle,
                                  transient=request.nbytes == -1)
        if out is None:
            cntl.set_failed(errors.ENOMETHOD,
                            f"no device handle {request.handle}")
            return device_lane_pb2.DeviceHandle()
        h, n = out
        return device_lane_pb2.DeviceHandle(handle=h, nbytes=n)

    def Pump(self, cntl, request, done):
        out = self.store.pump(request.handle, request.rounds)
        if out is None:
            cntl.set_failed(errors.ENOMETHOD,
                            f"no pumpable device handle {request.handle}")
            return device_lane_pb2.PumpResult()
        checksum, moved = out
        return device_lane_pb2.PumpResult(checksum=checksum,
                                          moved_bytes=moved)

    def Get(self, cntl, request, done):
        data = self.store.get(request.handle)
        if data is None:
            cntl.set_failed(errors.ENOMETHOD,
                            f"no device handle {request.handle}")
            return device_lane_pb2.DeviceHandle()
        cntl.response_attachment = data
        return device_lane_pb2.DeviceHandle(handle=request.handle,
                                            nbytes=len(data))

    def Free(self, cntl, request, done):
        ok = self.store.free(request.handle)
        return device_lane_pb2.DeviceHandle(
            handle=request.handle if ok else 0)

    def Stats(self, cntl, request, done):
        if request.fence:
            self.store.fence()
        count, resident, moved = self.store.stats()
        return device_lane_pb2.DeviceStats(
            handles=count, resident_bytes=resident, moved_bytes=moved)


# ---------------------------------------------------------------------------
# in-process TpuSocket lane (tpu/tpusocket.py): the same service addressable
# as device programs on a local chip (tpu://host/ordinal, no port)
# ---------------------------------------------------------------------------
_tpusock_svc: Optional[DeviceDataService] = None


def _tpusock_call(device, meta, payload: bytes, attachment: bytes,
                  method: str):
    # one service instance (the descriptor walk in Service.__init__ is
    # per-RPC waste otherwise); the store is the global singleton anyway
    global _tpusock_svc
    svc = _tpusock_svc
    if svc is None:
        svc = _tpusock_svc = DeviceDataService(global_store())

    class _Cntl:
        request_attachment = attachment
        response_attachment = b""

        def set_failed(self, code, text=""):
            self._err = (code, text)

        _err = None

    req_cls = svc.find_method(method).request_class
    req = req_cls()
    req.ParseFromString(payload)
    cntl = _Cntl()
    resp = getattr(svc, method)(cntl, req, None)
    if cntl._err is not None:
        return cntl._err[0], b"", b""
    return 0, resp.SerializeToString(), cntl.response_attachment


def _register_tpusocket_methods() -> None:
    from brpc_tpu.tpu.tpusocket import register_device_method

    for m in ("Put", "Copy", "Pump", "Get", "Free", "Stats"):
        register_device_method(
            "DeviceDataService", m,
            lambda device, meta, p, a, _m=m: _tpusock_call(device, meta,
                                                           p, a, _m))


_register_tpusocket_methods()
