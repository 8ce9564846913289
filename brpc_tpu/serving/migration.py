"""KV block-chain migration over the tpu:// record lane.

The missing primitive of the disaggregated serving plane: ship a LIVE
sequence's paged KV between shards without re-prefilling a single token.
The control message (:class:`MigrateRequest`, the manifest: tokens so
far, chain geometry, refcount-audited length) rides a normal RPC; the
raw block bytes do NOT — they stream over the existing STREAM→HBM record
lane (``tpu/device_stream.py``): one 16-byte ``(handle, nbytes)`` record
per block, credit-windowed on staged HBM bytes, the same lane bench.py's
device phase drives (rate not measured on the current machine; see
PERF.md).

Ownership is a two-phase handshake with **no window where the chain is
owned by nobody or by both sides**:

1. source: ``quiesce_sequence`` (forced ledger audit; any write clears
   the mark) → ``export_chain`` → ``MigrateOpen`` with the manifest; the
   destination allocates a *staging* chain (blocks owned by the staging
   id throughout the transfer) and accepts the record stream.
2. source streams one record per block (k-half ‖ v-half, position
   order); the destination materializes each staged payload host-side
   (:func:`~brpc_tpu.tpu.device_stream.host_sink_options`), credits flow
   back as consumption happens.
3. when the last block lands the destination scatters the chain into its
   pools with ONE functional update per pool (``assert_writable`` first
   — staging blocks are refcount-1 by construction, and the
   cow-before-write lint holds here like everywhere else), adopts the
   chain under the destination sequence id (``adopt_sequence``,
   refcount++), frees the staging id, and parks the sequence in the
   destination engine.
4. ``MigrateCommit``'s reply IS the adoption ACK: only on
   ``accepted=True`` does the source ``release_exported`` its chain.
   Any failure — stream write error, drop fault, timeout, engine
   stopped — leaves the source chain intact (``unquiesce_sequence``)
   so the sequence falls back to local decode.

Fault points: ``serving.migrate.stall`` (delay_ms per block record on
the source) and ``serving.migrate.drop`` (destination tunnel dies
mid-migration: the receiver fails the transfer, frees its staging chain,
and the source keeps the sequence — chaos-gated with zero leaked blocks
on both pools).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from brpc_tpu import fault as _fault
from brpc_tpu import flags as _flags
from brpc_tpu.metrics.reducer import Adder
from brpc_tpu.metrics.status import PassiveStatus

_fault.register("serving.migrate.stall",
                "stall the source between migrated block records "
                "(delay_ms=)")
_fault.register("serving.migrate.drop",
                "kill the destination tunnel mid-migration (after=N "
                "records): the receiver fails the transfer and the "
                "source retains the chain")

g_serving_migrate_seqs = Adder("g_serving_migrate_seqs")
g_serving_migrate_blocks = Adder("g_serving_migrate_blocks")
g_serving_migrate_bytes = Adder("g_serving_migrate_bytes")
g_serving_migrate_failed = Adder("g_serving_migrate_failed")

_inflight_lock = threading.Lock()
_inflight = [0]  # migration state machines live in this process (out+in)


def _inflight_delta(d: int) -> None:
    with _inflight_lock:
        _inflight[0] += d


g_serving_migrate_inflight = PassiveStatus(lambda: _inflight[0]) \
    .expose("g_serving_migrate_inflight")
g_serving_migrate_inflight.prometheus_type = "gauge"


# --------------------------------------------------------- pool plumbing
def _pool_views(kv, table) -> Tuple[object, object]:
    """The (k, v) device arrays holding ``table``'s slots — the stacked
    per-mesh pools for a :class:`ShardedKVCache` chain, the flat pools
    otherwise."""
    shard = getattr(table, "shard", None)
    if shard is not None and hasattr(kv, "k_pools"):
        return kv.k_pools[shard], kv.v_pools[shard]
    return kv.k_pool, kv.v_pool


def _slot_index(table, block_size: int) -> np.ndarray:
    return np.concatenate([np.arange(b * block_size, (b + 1) * block_size)
                           for b in table])


def read_chain_blocks(kv, table, block_bytes: int) -> List[bytes]:
    """Serialize a chain's blocks for the record lane: ONE host
    materialization of the gathered slots per pool, then one
    ``k ‖ v`` payload per block, in table (= position) order."""
    bs = kv.block_size
    k, v = _pool_views(kv, table)
    idx = _slot_index(table, bs)
    k_host = np.ascontiguousarray(np.asarray(k[:, idx, :]))
    v_host = np.ascontiguousarray(np.asarray(v[:, idx, :]))
    out: List[bytes] = []
    for i in range(len(table)):
        s = slice(i * bs, (i + 1) * bs)
        payload = (k_host[:, s, :].tobytes() + v_host[:, s, :].tobytes())
        if len(payload) != block_bytes:
            raise AssertionError(
                f"block payload {len(payload)}B != manifest "
                f"{block_bytes}B")
        out.append(payload)
    return out


_scatter_jit = None


def _fused_scatter():
    """One donated dispatch for both pools — the eager two-``.at[].set``
    form costs two launches plus two full-pool copies, all spent while
    ``pool_gate`` is stalling the destination's decode loop."""
    global _scatter_jit
    if _scatter_jit is None:
        import jax

        def impl(kp, vp, idx, kn, vn):
            return kp.at[:, idx, :].set(kn), vp.at[:, idx, :].set(vn)

        _scatter_jit = jax.jit(impl, donate_argnums=(0, 1))
    return _scatter_jit


def write_chain_blocks(kv, staging_table, payloads: List[bytes],
                       ntokens: int) -> None:
    """Scatter received block payloads into the destination pools: one
    fused donated launch + one ``update_pools`` swap for the WHOLE
    chain. Staging blocks are exclusively owned (refcount 1) by the
    staging id — ``assert_writable`` proves it under the armed ledger
    before any slot is touched."""
    bs = kv.block_size
    layers, kv_dim = kv.layers, kv.kv_dim
    kv.assert_writable(staging_table, 0, len(staging_table) * bs)
    ks, vs = [], []
    for p in payloads:
        arr = np.frombuffer(p, dtype=np.float32).reshape(
            2, layers, bs, kv_dim)
        ks.append(arr[0])
        vs.append(arr[1])
    # pad the scatter to a power-of-two block count (re-writing block 0
    # with its own data) — chain lengths vary per migration, and a fresh
    # shape means a fresh jit trace stalling the decode loop ~50ms
    padn = max(4, 1 << (len(payloads) - 1).bit_length())
    ks.extend([ks[0]] * (padn - len(payloads)))
    vs.extend([vs[0]] * (padn - len(payloads)))
    k_new = np.concatenate(ks, axis=1)  # (layers, padn*bs, kv_dim)
    v_new = np.concatenate(vs, axis=1)
    idx = _slot_index(staging_table, bs)
    idx = np.concatenate(
        [idx] + [idx[:bs]] * (padn - len(payloads)))
    shard = getattr(staging_table, "shard", None)
    if shard is not None and hasattr(kv, "k_pools"):
        k2 = kv.k_pools.at[shard, :, idx, :].set(k_new)
        v2 = kv.v_pools.at[shard, :, idx, :].set(v_new)
    else:
        # the engine's own decode step donates the pools every launch,
        # so donation here follows the same ownership discipline (the
        # caller holds pool_gate — no concurrent reader of the old refs)
        k2, v2 = _fused_scatter()(kv.k_pool, kv.v_pool, idx,
                                  k_new, v_new)
    kv.update_pools(k2, v2)


def chain_block_bytes(kv) -> int:
    """Per-record payload size: k and v halves of one block."""
    return 2 * kv.layers * kv.block_size * kv.kv_dim * 4  # float32


# ---------------------------------------------------------------- source
class KVMigrator:
    """Source side: serialize + stream + release-on-ACK.

    One migrator per (engine, destination) pair; the engine calls
    :meth:`migrate` from its step loop (post-prefill handoff) or from
    the drain path in ``stop()`` (shard-death recovery). The sequence
    MUST be quiescent — no launch outstanding — which both call sites
    guarantee by construction; ``quiesce_sequence`` re-audits the ledger
    and arms the export gate regardless."""

    def __init__(self, dest_addr: str, dest_shard: int = 0,
                 window_bytes: Optional[int] = None,
                 timeout_s: Optional[float] = None,
                 channel_options=None):
        self.dest_addr = dest_addr
        self.dest_shard = dest_shard
        self._window = window_bytes
        self._timeout = timeout_s
        self._channel_options = channel_options
        self._channel = None
        self._lock = threading.Lock()
        self.seqs = 0
        self.blocks = 0
        self.bytes = 0
        self.failed = 0
        self.send_s = 0.0  # wall seconds inside stream+commit (gbps)

    # lazily built so constructing a migrator never dials anything
    def _stub(self):
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.rpc import Channel, ChannelOptions, Stub

        with self._lock:
            if self._channel is None:
                opts = self._channel_options or ChannelOptions(
                    protocol="trpc_std", timeout_ms=60000)
                ch = Channel(opts)
                ch.init(self.dest_addr)
                self._channel = ch
            return Stub(self._channel,
                        serving_pb2.DESCRIPTOR.services_by_name[
                            "LlmService"])

    def _window_bytes(self) -> int:
        if self._window is not None:
            return self._window
        return int(_flags.get("serving_migrate_window_mb")) << 20

    def _timeout_s(self) -> float:
        if self._timeout is not None:
            return self._timeout
        return float(_flags.get("serving_migrate_timeout_ms")) / 1000.0

    def migrate(self, seq, kv, recovery: bool = False) -> Optional[int]:
        """Ship ``seq``'s chain to the destination engine. Returns the
        adopted destination sequence id, or None — in which case the
        chain is STILL OWNED LOCALLY and the sequence can keep decoding
        here (fallback) or be aborted retriably by the caller."""
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.rpc import Controller, RpcError
        from brpc_tpu.rpc.stream import (StreamOptions, stream_close,
                                         stream_create)
        from brpc_tpu.tpu.device_stream import record_measure, send_handle

        timeout = self._timeout_s()
        _inflight_delta(1)
        sid = 0
        try:
            kv.quiesce_sequence(seq.seq_id)
            table, ntokens = kv.export_chain(seq.seq_id)
            block_bytes = chain_block_bytes(kv)
            manifest = serving_pb2.MigrateRequest(
                seq_id=seq.seq_id,
                prompt_tokens=[int(t) for t in seq.prompt],
                out_tokens=[int(t) for t in seq.out_tokens],
                max_new_tokens=seq.max_new_tokens,
                stop_token=seq.stop_token,
                ntokens=ntokens,
                n_blocks=len(table),
                block_size=kv.block_size,
                layers=kv.layers,
                kv_dim=kv.kv_dim,
                block_bytes=block_bytes,
                recovery=recovery)
            stub = self._stub()
            t0 = time.monotonic()
            sid = stream_create(StreamOptions(
                window_bytes=self._window_bytes(),
                measure=record_measure))
            cntl = Controller()
            cntl.stream_id = sid
            cntl.timeout_ms = int(timeout * 1000)
            ack = stub.MigrateOpen(manifest, controller=cntl)
            if not ack.accepted:
                raise RuntimeError(f"migrate rejected: {ack.message!r}")
            store = kv.store
            payloads = read_chain_blocks(kv, table, block_bytes)
            for payload in payloads:
                _fault.maybe_sleep(_fault.hit("serving.migrate.stall"))
                h, n = store.put(payload)
                rc = send_handle(sid, h, n, timeout=timeout)
                if rc != 0:
                    store.free(h)
                    raise RuntimeError(
                        f"migration stream write failed rc={rc}")
            cntl2 = Controller()
            cntl2.timeout_ms = int(timeout * 1000)
            ack2 = stub.MigrateCommit(
                serving_pb2.MigrateCommitRequest(seq_id=seq.seq_id),
                controller=cntl2)
            if not ack2.accepted:
                raise RuntimeError(
                    f"migrate commit rejected: {ack2.message!r}")
            # the destination ACKed adoption — ownership moves NOW
            freed = kv.release_exported(seq.seq_id)
            dt = time.monotonic() - t0
            with self._lock:
                self.seqs += 1
                self.blocks += len(table)
                self.bytes += block_bytes * len(table)
                self.send_s += dt
            g_serving_migrate_seqs.put(1)
            g_serving_migrate_blocks.put(len(table))
            g_serving_migrate_bytes.put(block_bytes * len(table))
            del freed
            return int(ack2.dest_seq_id)
        except (RpcError, RuntimeError, AssertionError, KeyError,
                OSError):
            # the chain never left local ownership: un-arm the export
            # gate and let the caller fall back to local decode
            try:
                kv.unquiesce_sequence(seq.seq_id)
            except Exception:
                pass
            with self._lock:
                self.failed += 1
            g_serving_migrate_failed.put(1)
            return None
        finally:
            if sid:
                stream_close(sid)
            _inflight_delta(-1)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            gbps = (self.bytes / self.send_s / 1e9) if self.send_s else 0.0
            return {"dest": self.dest_addr, "dest_shard": self.dest_shard,
                    "seqs": self.seqs, "blocks": self.blocks,
                    "bytes": self.bytes, "failed": self.failed,
                    "gbps": gbps}


# ------------------------------------------------------------- receiver
class _Inbound:
    """One in-flight inbound migration's state machine."""

    __slots__ = ("manifest", "staging_id", "staging_table", "payloads",
                 "state", "event", "dest_seq_id", "message", "lock",
                 "t_open")

    def __init__(self, manifest, staging_id, staging_table):
        self.manifest = manifest
        self.staging_id = staging_id
        self.staging_table = staging_table
        self.payloads: List[bytes] = []
        self.state = "open"  # open -> done | failed
        self.event = threading.Event()
        self.dest_seq_id = 0
        self.message = ""
        self.lock = threading.Lock()
        self.t_open = time.monotonic()


class MigrationReceiver:
    """Destination side: staging-alloc → buffer stream → scatter →
    adopt → park in the engine. Owned by :class:`LlmServingService`;
    the ``MigrateOpen``/``MigrateCommit`` handlers delegate here."""

    def __init__(self, engine):
        self.engine = engine
        self._lock = threading.Lock()
        self._inbound: Dict[int, _Inbound] = {}
        self.seqs_in = 0
        self.failed_in = 0

    # ------------------------------------------------------------- open
    def open(self, cntl, request):
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.rpc.stream import stream_accept
        from brpc_tpu.serving.kv_cache import KVCacheFull
        from brpc_tpu.tpu.device_stream import host_sink_options

        kv = self.engine.kv

        def reject(msg: str):
            return serving_pb2.MigrateAck(accepted=False, message=msg)

        meta = getattr(cntl, "_srv_meta", None)
        sid = 0
        if meta is not None and meta.stream_settings.stream_id:
            sid = meta.stream_settings.stream_id
        if not sid:
            return reject("migration needs a record stream")
        if getattr(kv, "state_overwritten", False):
            return reject("this shard's cache manager overwrites state in "
                          "place (window rings, recurrent state): no "
                          "migration into it")
        if (request.block_size != kv.block_size
                or request.layers != kv.layers
                or request.kv_dim != kv.kv_dim):
            return reject(
                f"geometry mismatch: got bs={request.block_size}/"
                f"L={request.layers}/d={request.kv_dim}, pool has "
                f"bs={kv.block_size}/L={kv.layers}/d={kv.kv_dim}")
        if request.block_bytes != chain_block_bytes(kv):
            return reject(f"block_bytes {request.block_bytes} != "
                          f"{chain_block_bytes(kv)}")
        if request.n_blocks != kv.blocks_for(request.ntokens):
            return reject(f"{request.n_blocks} blocks cannot carry "
                          f"{request.ntokens} tokens")
        if not self.engine.running:
            return reject("destination engine is not running")
        # the staging id owns the blocks for the whole transfer; engine
        # sequence ids start at 1, so the negated source id never
        # collides with a live local table
        staging_id = -(abs(int(request.seq_id)) + 1)
        try:
            staging_table = kv.alloc_sequence(staging_id, request.ntokens)
        except (KVCacheFull, ValueError) as e:
            return reject(f"staging alloc failed: {e}")
        inb = _Inbound(request.__class__.FromString(
            request.SerializeToString()), staging_id, staging_table)
        with self._lock:
            self._inbound[int(request.seq_id)] = inb
        _inflight_delta(1)
        window = int(_flags.get("serving_migrate_window_mb")) << 20

        def sink(data: bytes) -> None:
            self._on_block(inb, data)

        def on_closed(_sid: int) -> None:
            # producer went away without completing: fail + free staging
            self._fail(inb, "stream closed before commit")

        stream_accept(cntl, host_sink_options(
            sink, window, store=kv.store, on_closed=on_closed))
        return serving_pb2.MigrateAck(accepted=True,
                                      blocks=request.n_blocks)

    # ------------------------------------------------------- stream sink
    def _on_block(self, inb: _Inbound, data: bytes) -> None:
        drop = _fault.hit("serving.migrate.drop")
        with inb.lock:
            if inb.state != "open":
                return  # already failed/done — discard stragglers
            if drop is not None:
                pass  # fall through to the failure path below
            elif len(data) != inb.manifest.block_bytes:
                drop = {"reason": f"short block ({len(data)}B)"}
            else:
                inb.payloads.append(data)
                if len(inb.payloads) < inb.manifest.n_blocks:
                    return
        if drop is not None:
            self._fail(inb, str(drop.get("reason",
                                         "destination tunnel killed")))
            return
        self._commit_inbound(inb)

    def _commit_inbound(self, inb: _Inbound) -> None:
        """All blocks landed: scatter, adopt, park. Runs on the stream's
        receive thread — the scatter is one fused update per pool."""
        kv = self.engine.kv
        m = inb.manifest
        try:
            # pool_gate keeps the scatter off the step loop's donated
            # buffers — an unsynchronized .at[].set races the decode
            # launch and dies with "buffer has been deleted or donated"
            with self.engine.pool_gate:
                write_chain_blocks(kv, inb.staging_table, inb.payloads,
                                   m.ntokens)
            seq = self.engine.make_adopted_sequence(
                np.asarray(list(m.prompt_tokens), dtype=np.int32),
                list(m.out_tokens), m.max_new_tokens, m.stop_token)
            kv.adopt_sequence(seq.seq_id, inb.staging_table, m.ntokens)
            if not self.engine.adopt_migrated(seq, recovery=m.recovery):
                kv.free_sequence(seq.seq_id)
                raise RuntimeError("destination engine refused adoption")
        except Exception as e:  # noqa: BLE001 — any failure = clean abort
            self._fail(inb, f"adoption failed: {e}")
            return
        kv.free_sequence(inb.staging_id)  # chain now owned by seq alone
        with inb.lock:
            inb.state = "done"
            inb.dest_seq_id = seq.seq_id
        with self._lock:
            self.seqs_in += 1
        _inflight_delta(-1)
        inb.event.set()

    def _fail(self, inb: _Inbound, msg: str) -> None:
        with inb.lock:
            if inb.state != "open":
                return
            inb.state = "failed"
            inb.message = msg
        self.engine.kv.free_sequence(inb.staging_id)  # zero leaked blocks
        with self._lock:
            self.failed_in += 1
            for key, v in list(self._inbound.items()):
                if v is inb:
                    del self._inbound[key]
        _inflight_delta(-1)
        g_serving_migrate_failed.put(1)
        inb.event.set()

    # ------------------------------------------------------------ commit
    def commit(self, cntl, request):
        from brpc_tpu.proto import serving_pb2

        with self._lock:
            inb = self._inbound.pop(int(request.seq_id), None)
        if inb is None:
            return serving_pb2.MigrateAck(
                accepted=False, message=f"no open migration for "
                                        f"sequence {request.seq_id}")
        timeout = float(_flags.get("serving_migrate_timeout_ms")) / 1000.0
        if not inb.event.wait(timeout):
            self._fail(inb, "migration timed out awaiting blocks")
        with inb.lock:
            ok = inb.state == "done"
            return serving_pb2.MigrateAck(
                accepted=ok, dest_seq_id=inb.dest_seq_id,
                blocks=len(inb.payloads), message=inb.message)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {"seqs_in": self.seqs_in, "failed_in": self.failed_in,
                    "pending_in": len(self._inbound)}
