"""Paged KV cache over DeviceStore HBM handles.

vLLM-style block-paged KV management mapped onto this repo's device lane:
the K and V pools are single device arrays of ``num_blocks`` fixed-size
blocks, registered in a :class:`~brpc_tpu.tpu.device_lane.DeviceStore`
under stable handles (``adopt``/``replace``), so pool residency is visible
to /vars and DeviceStats like any other staged payload. Sequences own
*block tables* — host-side lists of physical block ids — that grow on
demand as decode appends tokens; allocation and free are refcounted so a
forked prefix can share blocks.

Admission backpressure is watermark-based: a new sequence is admitted only
while the pool (after its prefill blocks) stays under ``watermark`` of
capacity. The slack above the watermark is decode headroom — blocks that
*running* sequences may still grow into — so admission rejections
(surfaced as EOVERCROWDED, which the tunnel retry policy already treats as
retriable) come before mid-generation exhaustion, not instead of it.

Physical block 0 is a scratch block: padded lanes of the fused
prefill/decode programs scatter there, so it is never handed out and never
counted in capacity.

Under ``BRPC_TPU_CHECK=1`` every alloc/free re-audits the invariants
(free + used = capacity, refcounts consistent with tables), and
:meth:`PagedKVCache.assert_idle` gives teardown the same discipline the
CreditLedger gives tunnel windows: a chaos-killed generation must return
every block before the engine reports the pool whole.

**Sharded mode** (:class:`ShardedKVCache`): one block pool per ``dp``
shard of the serving mesh. Each shard keeps its own ledger-only
:class:`PagedKVCache` (free list, refcounts, watermark, CHECK audits —
per pool, exactly as single-device), while the device-resident K/V live
as ONE stacked ``(dp, layers, slots, kv_dim)`` pair sharded over the
``dp`` axis, so every shard's pool is resident on its own devices and
the fused decode program still launches ONCE for the whole mesh. Block
tables name (shard, block) pairs — a :class:`ShardTable` is the block-id
list plus the owning shard — and a sequence routes to its shard with the
same splitmix64 ``shard_for`` the dispatch plane uses (VersionedPool
``version << 32`` cids must spread, not pin to shard 0). fork/extend/
free stay device-local: they only ever touch the owning shard's ledger.
"""

from __future__ import annotations

import collections
import threading
import weakref
from typing import Dict, List, Optional, Tuple

import numpy as np

from brpc_tpu.metrics.reducer import Adder
from brpc_tpu.metrics.status import PassiveStatus

g_serving_kv_block_allocs = Adder("g_serving_kv_block_allocs")
g_serving_kv_block_frees = Adder("g_serving_kv_block_frees")
g_serving_kv_admission_rejects = Adder("g_serving_kv_admission_rejects")

_caches: "weakref.WeakSet[PagedKVCache]" = weakref.WeakSet()


def _sum_caches(attr) -> int:
    return sum(attr(c) for c in list(_caches))


g_serving_kv_blocks_total = PassiveStatus(
    lambda: _sum_caches(lambda c: c.num_blocks)) \
    .expose("g_serving_kv_blocks_total")
g_serving_kv_blocks_total.prometheus_type = "gauge"
g_serving_kv_blocks_used = PassiveStatus(
    lambda: _sum_caches(lambda c: c.used_blocks)) \
    .expose("g_serving_kv_blocks_used")
g_serving_kv_blocks_used.prometheus_type = "gauge"


LANES = 128   # values a row of the device's tiling holds


class KVCacheFull(Exception):
    """Raised when the pool cannot satisfy an allocation (maps to
    EOVERCROWDED at the RPC surface)."""


class KVCacheConfig:
    def __init__(self, block_size: int = 16, num_blocks: int = 128,
                 watermark: float = 0.90):
        if block_size < 1 or num_blocks < 1:
            raise ValueError("block_size/num_blocks must be >= 1")
        if not 0.0 < watermark <= 1.0:
            raise ValueError("watermark must be in (0, 1]")
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.watermark = watermark


class PagedKVCache:
    """Block manager + the device-resident K/V pools behind it.

    ``device_pools=False`` runs ledger-only: the full block/refcount/
    watermark/audit machinery with no device arrays of its own — how
    :class:`ShardedKVCache` gives every shard its own ledger while the
    device residency lives in the stacked per-mesh pools.

    ``v_dim``: the width of a row of ``v_pool`` where it is not ``kv_dim``'s.
    At 0 a page row is ONE array: ``k_pool`` holds whatever the model keeps
    of a token (a compressed latent that is key and value at once), and
    ``v_pool`` is an empty ``(layers, slots, 0)`` array that costs nothing
    and goes through every launch beside it. Such a pool's rows are
    ALLOCATED at whole 128-lane tiles (``LANES``; the columns past
    ``kv_dim`` hold nothing and are counted nowhere): a one-array page is
    read where it lies by a kernel that copies pages itself, a copy out of
    HBM takes whole tiles, and the device pads an array's rows to them in
    HBM whatever the shape says, so the padded width costs the memory it
    cost anyway."""

    def __init__(self, config: KVCacheConfig, layers: int, kv_dim: int,
                 store=None, dtype=None, device_pools: bool = True,
                 v_dim: Optional[int] = None):
        self.config = config
        self.layers = layers
        self.kv_dim = kv_dim
        self.v_dim = kv_dim if v_dim is None else v_dim
        self._lock = threading.Lock()
        self.store = store
        self.k_pool = self.v_pool = None
        self.k_handle = self.v_handle = 0
        if device_pools:
            import jax.numpy as jnp

            from brpc_tpu.tpu.device_lane import global_store

            if store is None:
                self.store = global_store()
            # physical block 0 is scratch (pad scatter target): +1 below
            slots = (config.num_blocks + 1) * config.block_size
            dtype = dtype or jnp.float32
            # ON the store's device from the start (committed, as every
            # launch returns them): a program whose first launch saw them
            # uncommitted is lowered a second time at its next one
            # a one-array page's rows: whole lane tiles (the docstring)
            wide = kv_dim if self.v_dim else -(-kv_dim // LANES) * LANES
            self.k_pool = jnp.zeros((layers, slots, wide), dtype=dtype,
                                    device=self.store.device)
            self.v_pool = jnp.zeros((layers, slots, self.v_dim), dtype=dtype,
                                    device=self.store.device)
            self.k_handle, _ = self.store.adopt(self.k_pool)
            self.v_handle, _ = self.store.adopt(self.v_pool)
        # the block that has lain free longest goes out first, so a freed
        # sequence's rows stay readable for as long as the pool's slack
        # allows (serving/hybrid_cache.py: ``retired``)
        self._free = collections.deque(range(1, config.num_blocks + 1))
        self._ref: Dict[int, int] = {}
        self._tables: Dict[int, List[int]] = {}
        self._seq_len: Dict[int, int] = {}
        # blocks held by the prefix cache's radix tree (no table): each
        # hold contributes to _ref, audited as cache-held, not table-held
        self._cache_ref: Dict[int, int] = {}
        # sharded pools are ledger-only; their owner installs the device
        # copy used by cow_block against the stacked per-mesh pools
        self._cow_copy_fn = None
        # sequences audited + frozen for export (migration); any write
        # (extend/cow) clears the mark, so export_chain can only see a
        # chain with no in-flight mutations since its quiesce
        self._quiesced: set = set()
        self._check = False
        try:
            from brpc_tpu.analysis import runtime_check
            self._check = bool(runtime_check.ACTIVE)
        except Exception:
            pass
        if device_pools:
            # ledger-only shards are accounted by their ShardedKVCache,
            # not double-counted in the fleet totals
            _caches.add(self)

    # ------------------------------------------------------------- geometry
    @property
    def num_blocks(self) -> int:
        return self.config.num_blocks

    @property
    def block_size(self) -> int:
        return self.config.block_size

    @property
    def used_blocks(self) -> int:
        with self._lock:
            return self.config.num_blocks - len(self._free)

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def used_ratio(self) -> float:
        return self.used_blocks / float(self.config.num_blocks)

    def blocks_for(self, ntokens: int) -> int:
        bs = self.config.block_size
        return max(1, (ntokens + bs - 1) // bs)

    # ------------------------------------------------------------ admission
    def can_admit(self, ntokens: int, route_key: Optional[int] = None,
                  shard: Optional[int] = None) -> bool:
        """Watermark admission: the pool after this sequence's prefill
        blocks must stay at or under ``watermark`` of capacity, leaving
        the slack as decode headroom for sequences already running.
        (``shard`` is accepted for interface parity with the sharded
        cache; a single pool has nowhere else to route.)"""
        need = self.blocks_for(ntokens)
        limit = int(self.config.watermark * self.config.num_blocks)
        with self._lock:
            used = self.config.num_blocks - len(self._free)
            return used + need <= limit

    def note_rejected(self) -> None:
        g_serving_kv_admission_rejects.put(1)

    # ----------------------------------------------------------- block ops
    def _take_block_locked(self) -> int:
        if not self._free:
            raise KVCacheFull(
                f"kv pool exhausted ({self.config.num_blocks} blocks)")
        b = self._free.popleft()
        self._ref[b] = 1
        return b

    def alloc_sequence(self, seq_id: int, ntokens: int) -> List[int]:
        """Allocate blocks covering an ``ntokens``-long prefix; returns the
        block table (physical ids, in position order)."""
        need = self.blocks_for(ntokens)
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id} already has a table")
            if len(self._free) < need:
                g_serving_kv_admission_rejects.put(1)
                raise KVCacheFull(
                    f"need {need} blocks, {len(self._free)} free")
            table = [self._take_block_locked() for _ in range(need)]
            self._tables[seq_id] = table
            self._seq_len[seq_id] = ntokens
            self._audit_locked()
        g_serving_kv_block_allocs.put(need)
        return list(table)

    def extend_sequence(self, seq_id: int, new_len: int) -> List[int]:
        """Grow a block table so it covers ``new_len`` tokens (decode
        append). Shared blocks stay shared — only fresh tail blocks are
        allocated."""
        with self._lock:
            table = self._tables.get(seq_id)
            if table is None:
                raise KeyError(f"unknown sequence {seq_id}")
            need = self.blocks_for(new_len)
            grew = 0
            while len(table) < need:
                table.append(self._take_block_locked())
                grew += 1
            self._seq_len[seq_id] = new_len
            self._quiesced.discard(seq_id)
            self._audit_locked()
        if grew:
            g_serving_kv_block_allocs.put(grew)
        return list(table)

    def truncate_sequence(self, seq_id: int, new_len: int) -> int:
        """Shrink a table back to ``new_len`` tokens (speculative-decode
        rollback): tail blocks past ``blocks_for(new_len)`` drop one ref
        and return to the free list at zero, exactly mirroring
        ``free_sequence``'s accounting so the armed audit and the
        prefix-cache refcounts stay balanced. Returns blocks freed."""
        freed = 0
        with self._lock:
            table = self._tables.get(seq_id)
            if table is None:
                raise KeyError(f"unknown sequence {seq_id}")
            keep = self.blocks_for(new_len)
            while len(table) > keep:
                b = table.pop()
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    del self._ref[b]
                    self._free.append(b)
                    freed += 1
            self._seq_len[seq_id] = min(self._seq_len.get(seq_id, new_len),
                                        new_len)
            self._quiesced.discard(seq_id)
            self._audit_locked()
        if freed:
            g_serving_kv_block_frees.put(freed)
        return freed

    def fork_sequence(self, src_seq: int, dst_seq: int) -> List[int]:
        """Share ``src``'s blocks with a new sequence (refcount++); the
        caller copies the partial tail block device-side before either
        sequence appends."""
        with self._lock:
            table = self._tables.get(src_seq)
            if table is None:
                raise KeyError(f"unknown sequence {src_seq}")
            if dst_seq in self._tables:
                raise ValueError(f"sequence {dst_seq} already has a table")
            for b in table:
                self._ref[b] += 1
            self._tables[dst_seq] = list(table)
            self._seq_len[dst_seq] = self._seq_len[src_seq]
            self._audit_locked()
        return list(self._tables[dst_seq])

    def adopt_sequence(self, seq_id: int, blocks: List[int],
                       ntokens: int) -> List[int]:
        """Register a new sequence whose table IS an existing block chain
        (a prefix-cache hit): refcount++ on every chain block, zero
        allocations, zero copies. The chain must be live (held by the
        radix tree and/or other sequences) and must cover ``ntokens``."""
        bs = self.config.block_size
        if ntokens > len(blocks) * bs:
            raise ValueError(f"chain of {len(blocks)} blocks cannot cover "
                             f"{ntokens} tokens (block_size {bs})")
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id} already has a table")
            for b in blocks:
                if b not in self._ref:
                    raise KeyError(f"block {b} is not live")
            for b in blocks:
                self._ref[b] += 1
            self._tables[seq_id] = list(blocks)
            self._seq_len[seq_id] = ntokens
            self._audit_locked()
        return list(blocks)

    def retain_block(self, block: int) -> None:
        """Take a prefix-cache hold on a live block (radix-tree commit):
        the block survives free_sequence until release_block drops the
        hold. Cache holds are audited separately from table holds."""
        with self._lock:
            if block not in self._ref:
                raise KeyError(f"block {block} is not live")
            self._ref[block] += 1
            self._cache_ref[block] = self._cache_ref.get(block, 0) + 1
            self._audit_locked()

    def release_block(self, block: int) -> int:
        """Drop a prefix-cache hold (eviction / tree clear); the block
        returns to the free list when its refcount hits zero. Returns
        blocks actually freed (0 or 1)."""
        freed = 0
        with self._lock:
            held = self._cache_ref.get(block, 0)
            if held < 1:
                raise KeyError(f"block {block} has no cache hold")
            if held == 1:
                del self._cache_ref[block]
            else:
                self._cache_ref[block] = held - 1
            self._ref[block] -= 1
            if self._ref[block] == 0:
                del self._ref[block]
                self._free.append(block)
                freed = 1
            self._audit_locked()
        if freed:
            g_serving_kv_block_frees.put(freed)
        return freed

    def block_ref(self, block: int) -> int:
        with self._lock:
            return self._ref.get(block, 0)

    def cache_held_blocks(self) -> int:
        """Distinct blocks currently pinned by prefix-cache holds."""
        with self._lock:
            return len(self._cache_ref)

    # -------------------------------------------------------- copy-on-write
    def cow_block(self, seq_id: int, block_index: int) -> int:
        """Copy-on-write split: make ``table[block_index]`` exclusively
        owned by ``seq_id`` before a write lands in it. Exclusive blocks
        (refcount == 1) pass through untouched; shared ones get a fresh
        block, a device-side page copy, and the table entry swapped —
        the writer never mutates a block another chain can still read."""
        with self._lock:
            table = self._tables.get(seq_id)
            if table is None:
                raise KeyError(f"unknown sequence {seq_id}")
            if not 0 <= block_index < len(table):
                raise IndexError(f"block index {block_index} outside "
                                 f"table of {len(table)}")
            src = table[block_index]
            self._quiesced.discard(seq_id)  # a write is coming
            if self._ref.get(src, 0) == 1:
                return src  # exclusive already — no split needed
            dst = self._take_block_locked()
        g_serving_kv_block_allocs.put(1)
        # device page copy OUTSIDE the ledger lock (it dispatches); the
        # source stays refcounted by this sequence until the swap below
        copy = self._cow_copy_fn or self._cow_copy_block_device
        copy(dst, src)
        with self._lock:
            table[block_index] = dst
            self._ref[src] -= 1
            if self._ref[src] == 0:
                del self._ref[src]
                self._free.append(src)
            self._audit_locked()
        return dst

    def ensure_writable(self, seq_id: int, pos: int) -> int:
        """COW front door for the engine: split the block that the write
        at token position ``pos`` lands in, if shared. Returns the
        (possibly fresh) physical block id."""
        return self.cow_block(seq_id, pos // self.config.block_size)

    def _cow_copy_block_device(self, dst: int, src: int) -> None:
        if self.k_pool is None:
            return  # ledger-only pool with no cow hook installed
        bs = self.config.block_size
        d0, s0 = dst * bs, src * bs
        k = self.k_pool.at[:, d0:d0 + bs, :].set(
            self.k_pool[:, s0:s0 + bs, :])
        v = self.v_pool.at[:, d0:d0 + bs, :].set(
            self.v_pool[:, s0:s0 + bs, :])
        self.update_pools(k, v)

    def assert_writable(self, table, start: int, stop: int) -> None:
        """COW-contract guard (armed ledger only): every block the write
        range ``[start, stop)`` lands in must be exclusively owned —
        refcount 1 — else a shared (forked or tree-held) page would be
        silently clobbered. The serving model calls this before every
        pool-scattering launch; tpulint's cow-before-write rule keeps
        future write sites doing the same."""
        if not self._check or stop <= start:
            return
        bs = self.config.block_size
        with self._lock:
            for bi in range(start // bs, (stop - 1) // bs + 1):
                b = table[bi]
                ref = self._ref.get(b, 0)
                if ref != 1:
                    raise AssertionError(
                        f"cow violation: write in [{start},{stop}) hits "
                        f"block {b} (table[{bi}]) with refcount {ref}; "
                        f"shared blocks must be cow-split before writing")

    def assert_writable_batch(self, tables, positions) -> None:
        """Per-row COW guard for a decode batch: row i writes exactly at
        ``positions[i]`` in ``tables[i]``."""
        if not self._check:
            return
        for t, p in zip(tables, positions):
            self.assert_writable(t, int(p), int(p) + 1)

    # ------------------------------------------------------------ migration
    def quiesce_sequence(self, seq_id: int) -> int:
        """Freeze a sequence for export: re-audit the ledger and mark the
        chain quiesced. Any subsequent write (extend/cow) clears the mark,
        so :meth:`export_chain` can never serialize a chain with in-flight
        writes or un-audited refcounts. Returns the chain length covered
        (tokens). The engine calls this only once the step loop has no
        launch outstanding for the sequence."""
        with self._lock:
            if seq_id not in self._tables:
                raise KeyError(f"unknown sequence {seq_id}")
            # force the audit even on disarmed ledgers — exporting a chain
            # whose refcounts disagree with the tables ships corruption
            problems = self._invariant_problems_locked()
            if problems:
                raise AssertionError(
                    "refusing to quiesce over a broken ledger: " +
                    "; ".join(problems))
            self._quiesced.add(seq_id)
            return self._seq_len[seq_id]

    def export_chain(self, seq_id: int) -> Tuple[List[int], int]:
        """Snapshot a quiesced sequence's (block table, ntokens) for
        migration. The chain stays owned by the source until
        :meth:`release_exported` — the destination ACK is what moves
        ownership, so there is no window where the blocks belong to
        nobody (or to both sides)."""
        with self._lock:
            if seq_id not in self._tables:
                raise KeyError(f"unknown sequence {seq_id}")
            if seq_id not in self._quiesced:
                raise AssertionError(
                    f"export of sequence {seq_id} without quiesce: call "
                    f"quiesce_sequence first (no in-flight writes may be "
                    f"outstanding when a chain leaves the pool)")
            return list(self._tables[seq_id]), self._seq_len[seq_id]

    def release_exported(self, seq_id: int) -> int:
        """Drop the source's ownership of a migrated chain after the
        destination ACKed adoption. Returns blocks freed."""
        with self._lock:
            self._quiesced.discard(seq_id)
        return self.free_sequence(seq_id)

    def unquiesce_sequence(self, seq_id: int) -> None:
        """Abort an export (migration failed): the chain stays local and
        writable again."""
        with self._lock:
            self._quiesced.discard(seq_id)

    def free_sequence(self, seq_id: int) -> int:
        """Drop a sequence's table; blocks return to the free list when
        their refcount hits zero. Returns blocks actually freed."""
        freed = 0
        with self._lock:
            table = self._tables.pop(seq_id, None)
            self._seq_len.pop(seq_id, None)
            self._quiesced.discard(seq_id)
            if table is None:
                return 0
            for b in table:
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    del self._ref[b]
                    self._free.append(b)
                    freed += 1
            self._audit_locked()
        if freed:
            g_serving_kv_block_frees.put(freed)
        return freed

    def block_table(self, seq_id: int) -> Optional[List[int]]:
        with self._lock:
            t = self._tables.get(seq_id)
            return list(t) if t is not None else None

    def seq_len(self, seq_id: int) -> int:
        with self._lock:
            return self._seq_len.get(seq_id, 0)

    def live_sequences(self) -> List[int]:
        with self._lock:
            return sorted(self._tables)

    # ------------------------------------------------------------ pool swap
    def update_pools(self, k_pool, v_pool) -> None:
        """Install the post-step pool arrays (functional update output) and
        re-point the DeviceStore handles at them — one swap per engine
        step, not per token."""
        self.k_pool = k_pool
        self.v_pool = v_pool
        self.store.replace(self.k_handle, k_pool)
        self.store.replace(self.v_handle, v_pool)

    # ---------------------------------------------------------------- audit
    def _audit_locked(self) -> None:
        if not self._check:
            return
        problems = self._invariant_problems_locked()
        if problems:
            raise AssertionError("kv ledger violation: " +
                                 "; ".join(problems))

    def _invariant_problems_locked(self) -> List[str]:
        problems: List[str] = []
        held: Dict[int, int] = {}
        for seq, table in self._tables.items():
            for b in table:
                held[b] = held.get(b, 0) + 1
        for b, n in self._cache_ref.items():
            held[b] = held.get(b, 0) + n
        if held != self._ref:
            problems.append(
                f"refcounts {self._ref} disagree with tables {held}")
        in_free = set(self._free)
        if len(in_free) != len(self._free):
            problems.append("duplicate block on the free list")
        overlap = in_free & set(held)
        if overlap:
            problems.append(f"blocks {sorted(overlap)} both free and held")
        if len(self._free) + len(self._ref) != self.config.num_blocks:
            problems.append(
                f"{len(self._free)} free + {len(self._ref)} held != "
                f"{self.config.num_blocks} capacity")
        return problems

    def assert_idle(self, context: str = "") -> None:
        """Teardown wholeness check, mirroring CreditLedger.assert_balanced:
        every block must be back on the free list with no refs held."""
        with self._lock:
            problems = self._invariant_problems_locked()
            if self._tables:
                problems.append(
                    f"{len(self._tables)} sequence table(s) still live: "
                    f"{sorted(self._tables)}")
            if self._cache_ref:
                problems.append(
                    f"{len(self._cache_ref)} block hold(s) still owned by "
                    f"the prefix cache: {sorted(self._cache_ref)}")
            if len(self._free) != self.config.num_blocks:
                problems.append(
                    f"{self.config.num_blocks - len(self._free)} "
                    f"block(s) leaked")
        if problems:
            where = f" [{context}]" if context else ""
            raise AssertionError(f"kv pool not idle{where}: " +
                                 "; ".join(problems))

    def close(self) -> None:
        if self.k_handle:
            self.store.free(self.k_handle)
            self.store.free(self.v_handle)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            used = self.config.num_blocks - len(self._free)
            return {
                "block_size": self.config.block_size,
                "blocks_total": self.config.num_blocks,
                "blocks_used": used,
                "blocks_free": len(self._free),
                "watermark": self.config.watermark,
                "used_ratio": used / float(self.config.num_blocks),
                "sequences": len(self._tables),
                "blocks_cached": len(self._cache_ref),
            }


class ShardTable(list):
    """A block table that knows which dp shard owns it — the (device,
    block) naming of the sharded plane. It IS the plain block-id list
    everywhere the single-device path expects one; the mesh model reads
    ``.shard`` to place the sequence's compute and K/V scatter."""

    def __init__(self, shard: int, blocks):
        super().__init__(blocks)
        self.shard = shard


_sharded: "weakref.WeakSet[ShardedKVCache]" = weakref.WeakSet()


def _fleet_skew() -> float:
    """Worst per-device occupancy excess over its cache's fleet mean —
    the quantity the serving_shard_skew watch rule fires on. 0 when
    perfectly balanced (or nothing sharded is live)."""
    worst = 0.0
    for c in list(_sharded):
        ratios = [p.used_ratio() for p in c.pools]
        if ratios:
            worst = max(worst, max(ratios) - sum(ratios) / len(ratios))
    return worst


g_serving_kv_shard_skew = PassiveStatus(_fleet_skew) \
    .expose("g_serving_kv_shard_skew")
g_serving_kv_shard_skew.prometheus_type = "gauge"


class ShardedKVCache:
    """Per-device block pools over the serving mesh's ``dp`` axis.

    One ledger-only :class:`PagedKVCache` per shard carries the block
    accounting (watermark, refcounts, BRPC_TPU_CHECK audits — enforced
    PER POOL), while the device-resident K/V are ONE stacked
    ``(dp, layers, slots, kv_dim)`` array pair sharded over ``dp`` via
    :func:`~brpc_tpu.tpu.mesh.named_sharding`, registered once in the
    DeviceStore. Sequences route to shards with the dispatch plane's
    splitmix64 ``shard_for`` (stable under VersionedPool cid reuse);
    fork/extend/free only ever touch the owning shard's ledger."""

    def __init__(self, config: KVCacheConfig, layers: int, kv_dim: int,
                 mesh=None, store=None, dtype=None):
        import jax
        import jax.numpy as jnp

        from brpc_tpu.shard.plane import shard_for
        from brpc_tpu.tpu.device_lane import global_store
        from brpc_tpu.tpu.mesh import named_sharding, serving_mesh

        if mesh is None:
            mesh = serving_mesh()
        if "dp" not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no dp axis")
        self.config = config
        self.layers = layers
        self.kv_dim = kv_dim
        self.mesh = mesh
        self.n_shards = int(mesh.shape["dp"])
        self.store = store if store is not None else global_store()
        self._route = shard_for
        self._lock = threading.Lock()
        self.pools = [PagedKVCache(config, layers, kv_dim,
                                   device_pools=False)
                      for _ in range(self.n_shards)]
        for i, p in enumerate(self.pools):
            # ledger-only shards cow-copy through the stacked mesh pools
            p._cow_copy_fn = (lambda dst, src, _s=i:
                              self._cow_copy_block_shard(_s, dst, src))
        self._shard_of: Dict[int, int] = {}
        slots = (config.num_blocks + 1) * config.block_size
        dtype = dtype or jnp.float32
        shape = (self.n_shards, layers, slots, kv_dim)
        sharding = named_sharding(mesh, "dp")
        self.k_pools = jax.device_put(jnp.zeros(shape, dtype=dtype),
                                      sharding)
        self.v_pools = jax.device_put(jnp.zeros(shape, dtype=dtype),
                                      sharding)
        self.k_handle, _ = self.store.adopt(self.k_pools)
        self.v_handle, _ = self.store.adopt(self.v_pools)
        _caches.add(self)   # fleet totals (/vars) see the aggregate
        _sharded.add(self)  # skew gauge sees the per-shard spread

    # ------------------------------------------------------------- geometry
    @property
    def num_blocks(self) -> int:
        return self.config.num_blocks * self.n_shards

    @property
    def block_size(self) -> int:
        return self.config.block_size

    @property
    def used_blocks(self) -> int:
        return sum(p.used_blocks for p in self.pools)

    @property
    def free_blocks(self) -> int:
        return sum(p.free_blocks for p in self.pools)

    def used_ratio(self) -> float:
        return self.used_blocks / float(self.num_blocks)

    def blocks_for(self, ntokens: int) -> int:
        return self.pools[0].blocks_for(ntokens)

    # the CHECK arming surface tests use (kv._check = True) fans out to
    # every shard ledger — the audit contract is per pool
    @property
    def _check(self) -> bool:
        return any(p._check for p in self.pools)

    @_check.setter
    def _check(self, v: bool) -> None:
        for p in self.pools:
            p._check = v

    # -------------------------------------------------------------- routing
    def shard_of(self, seq_id: int) -> int:
        """The dp shard owning (or that would own) a sequence. Live
        sequences keep their pinned shard; new ones route by splitmix64,
        so a retry re-submitted with the same id lands on the same pool."""
        with self._lock:
            pinned = self._shard_of.get(seq_id)
        if pinned is not None:
            return pinned
        return self._route(seq_id, self.n_shards)

    def _pool_of(self, seq_id: int) -> Optional[Tuple[int, PagedKVCache]]:
        with self._lock:
            shard = self._shard_of.get(seq_id)
        if shard is None:
            return None
        return shard, self.pools[shard]

    # ------------------------------------------------------------ admission
    def can_admit(self, ntokens: int, route_key: Optional[int] = None,
                  shard: Optional[int] = None) -> bool:
        """Watermark admission against the OWNING shard's pool when the
        placement is known — an explicit ``shard`` (prefix-hash routing)
        beats the ``route_key`` hash — and against the fleet aggregate
        otherwise."""
        if shard is not None:
            return self.pools[shard].can_admit(ntokens)
        if route_key is not None:
            return self.pools[self.shard_of(route_key)].can_admit(ntokens)
        need = self.blocks_for(ntokens)
        limit = int(self.config.watermark * self.num_blocks)
        return self.used_blocks + need <= limit

    def note_rejected(self) -> None:
        g_serving_kv_admission_rejects.put(1)

    # ----------------------------------------------------------- block ops
    def alloc_sequence(self, seq_id: int, ntokens: int,
                       shard: Optional[int] = None) -> ShardTable:
        if shard is None:
            shard = self.shard_of(seq_id)
        table = self.pools[shard].alloc_sequence(seq_id, ntokens)
        with self._lock:
            self._shard_of[seq_id] = shard
        return ShardTable(shard, table)

    def pin_shard(self, seq_id: int, shard: int) -> None:
        """Pin a sequence to a shard ahead of ledger registration — the
        prefix cache pins hits to the shard whose tree holds the chain,
        overriding the splitmix64 route."""
        if not 0 <= shard < self.n_shards:
            raise ValueError(f"shard {shard} outside [0, {self.n_shards})")
        with self._lock:
            self._shard_of[seq_id] = shard

    def extend_sequence(self, seq_id: int, new_len: int) -> ShardTable:
        got = self._pool_of(seq_id)
        if got is None:
            raise KeyError(f"unknown sequence {seq_id}")
        shard, pool = got
        return ShardTable(shard, pool.extend_sequence(seq_id, new_len))

    def truncate_sequence(self, seq_id: int, new_len: int) -> int:
        got = self._pool_of(seq_id)
        if got is None:
            raise KeyError(f"unknown sequence {seq_id}")
        return got[1].truncate_sequence(seq_id, new_len)

    def fork_sequence(self, src_seq: int, dst_seq: int) -> ShardTable:
        """Device-local fork: the child shares the parent's blocks, so it
        MUST live on the parent's shard — the fork pins it there, not the
        hash route."""
        got = self._pool_of(src_seq)
        if got is None:
            raise KeyError(f"unknown sequence {src_seq}")
        shard, pool = got
        table = pool.fork_sequence(src_seq, dst_seq)
        with self._lock:
            self._shard_of[dst_seq] = shard
        return ShardTable(shard, table)

    def free_sequence(self, seq_id: int) -> int:
        with self._lock:
            shard = self._shard_of.pop(seq_id, None)
        if shard is None:
            return 0
        return self.pools[shard].free_sequence(seq_id)

    def adopt_sequence(self, seq_id: int, blocks, ntokens: int,
                       shard: Optional[int] = None) -> ShardTable:
        """Register a sequence over an existing live chain on ``shard``
        (migration staging adopt): refcount++ on every chain block, no
        allocation. Defaults to the chain's own shard when ``blocks`` is
        a :class:`ShardTable`."""
        if shard is None:
            shard = getattr(blocks, "shard", None)
        if shard is None:
            raise ValueError("adopt_sequence on a sharded pool needs the "
                             "owning shard (ShardTable or shard=)")
        table = self.pools[shard].adopt_sequence(seq_id, list(blocks),
                                                 ntokens)
        with self._lock:
            self._shard_of[seq_id] = shard
        return ShardTable(shard, table)

    # ------------------------------------------------------------ migration
    def quiesce_sequence(self, seq_id: int) -> int:
        got = self._pool_of(seq_id)
        if got is None:
            raise KeyError(f"unknown sequence {seq_id}")
        return got[1].quiesce_sequence(seq_id)

    def export_chain(self, seq_id: int) -> Tuple[ShardTable, int]:
        got = self._pool_of(seq_id)
        if got is None:
            raise KeyError(f"unknown sequence {seq_id}")
        shard, pool = got
        blocks, ntokens = pool.export_chain(seq_id)
        return ShardTable(shard, blocks), ntokens

    def release_exported(self, seq_id: int) -> int:
        with self._lock:
            shard = self._shard_of.pop(seq_id, None)
        if shard is None:
            return 0
        return self.pools[shard].release_exported(seq_id)

    def unquiesce_sequence(self, seq_id: int) -> None:
        got = self._pool_of(seq_id)
        if got is not None:
            got[1].unquiesce_sequence(seq_id)

    # -------------------------------------------------------- copy-on-write
    def cow_block(self, seq_id: int, block_index: int) -> int:
        got = self._pool_of(seq_id)
        if got is None:
            raise KeyError(f"unknown sequence {seq_id}")
        return got[1].cow_block(seq_id, block_index)

    def ensure_writable(self, seq_id: int, pos: int) -> int:
        return self.cow_block(seq_id, pos // self.config.block_size)

    def _cow_copy_block_shard(self, shard: int, dst: int, src: int) -> None:
        """Device page copy for a ledger-only shard pool, against the
        stacked per-mesh arrays (one functional update, one swap)."""
        bs = self.config.block_size
        d0, s0 = dst * bs, src * bs
        k = self.k_pools.at[shard, :, d0:d0 + bs, :].set(
            self.k_pools[shard, :, s0:s0 + bs, :])
        v = self.v_pools.at[shard, :, d0:d0 + bs, :].set(
            self.v_pools[shard, :, s0:s0 + bs, :])
        self.update_pools(k, v)

    def assert_writable(self, table, start: int, stop: int) -> None:
        self.pools[getattr(table, "shard", 0)].assert_writable(
            table, start, stop)

    def assert_writable_batch(self, tables, positions) -> None:
        if not self._check:
            return
        for t, p in zip(tables, positions):
            self.assert_writable(t, int(p), int(p) + 1)

    def block_table(self, seq_id: int) -> Optional[ShardTable]:
        got = self._pool_of(seq_id)
        if got is None:
            return None
        shard, pool = got
        table = pool.block_table(seq_id)
        return ShardTable(shard, table) if table is not None else None

    def seq_len(self, seq_id: int) -> int:
        got = self._pool_of(seq_id)
        return got[1].seq_len(seq_id) if got else 0

    def live_sequences(self) -> List[int]:
        out: List[int] = []
        for p in self.pools:
            out.extend(p.live_sequences())
        return sorted(out)

    # ------------------------------------------------------------ pool swap
    def update_pools(self, k_pools, v_pools) -> None:
        """Install the post-step stacked pools (functional update output)
        and re-point the DeviceStore handles — one swap per engine step
        for the WHOLE mesh, not per shard."""
        self.k_pools = k_pools
        self.v_pools = v_pools
        self.store.replace(self.k_handle, k_pools)
        self.store.replace(self.v_handle, v_pools)

    # ---------------------------------------------------------------- audit
    def assert_idle(self, context: str = "") -> None:
        for i, p in enumerate(self.pools):
            where = f"shard {i}" + (f", {context}" if context else "")
            p.assert_idle(where)
        with self._lock:
            if self._shard_of:
                raise AssertionError(
                    f"sharded kv not idle [{context}]: routing entries "
                    f"for {sorted(self._shard_of)} still pinned")

    def close(self) -> None:
        self.store.free(self.k_handle)
        self.store.free(self.v_handle)

    def snapshot(self) -> Dict[str, object]:
        used = self.used_blocks
        total = self.num_blocks
        dev_rows = np.asarray(self.mesh.devices).reshape(self.n_shards, -1)
        shards = []
        for i, p in enumerate(self.pools):
            s = p.snapshot()
            s["shard"] = i
            s["devices"] = [str(d) for d in dev_rows[i]]
            shards.append(s)
        with self._lock:
            shard_map = dict(sorted(self._shard_of.items()))
        ratios = [s["used_ratio"] for s in shards]
        return {
            "block_size": self.config.block_size,
            "blocks_total": total,
            "blocks_used": used,
            "blocks_free": total - used,
            "watermark": self.config.watermark,
            "used_ratio": used / float(total),
            "sequences": sum(s["sequences"] for s in shards),
            "blocks_cached": sum(s["blocks_cached"] for s in shards),
            "n_shards": self.n_shards,
            "shard_skew": max(ratios) - sum(ratios) / len(ratios),
            "shards": shards,
            "shard_map": shard_map,
        }
