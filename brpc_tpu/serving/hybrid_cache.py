"""The cache manager of a hybrid (state-space + window + full attention)
model: ONE manager, three kinds of per-sequence state, one ledger.

- **full-layer pages**: the K/V rows of the layer(s) that attend over the
  whole context; they grow with the context, 16 rows a block, exactly as
  :class:`~brpc_tpu.serving.kv_cache.PagedKVCache` manages them (it IS one).
- **window pages**: the K/V rows of the sliding-window layers, a RING of
  ``ceil(window / block_size) + 1`` blocks a sequence: position ``p`` lies at
  ring row ``p mod (ring blocks * block_size)``, so the rows behind the window
  are overwritten in place and the ring never grows. A second
  :class:`PagedKVCache` (its ``layers`` the window layers) keeps its blocks.
- **recurrent slots**: one slot a sequence in two device arrays, the scan
  state ``(2, layers, slots + 1, d_state, d_inner)`` and the conv tail ``(2,
  layers, slots + 1, d_conv - 1, d_inner)`` of every state-space layer,
  overwritten every token. Index 0 of the leading axis is the RUNNING state;
  index 1 is the state at the PROMPT'S END, written once by prefill and kept
  beside it: no request reads it (a later reuse of a whole prompt would
  start from it), it is there so that what prefill's scan wrote can be held
  to a reference after the decode steps have overwritten the running state,
  and ``cache_bytes`` leaves it out. Slot 0 is scratch (padded rows of a
  launch write there), as block 0 is in a pool.

``alloc_sequence`` takes all three or nothing; ``extend_sequence`` grows the
full-layer table only; ``free_sequence`` returns all three. A sequence's
table is a :class:`HybridTable`: the list of its full-layer blocks (so
``len(table)`` is what grows), with ``.window`` (its ring's blocks, in ring
order) and ``.slot``.

Free blocks (as every :class:`PagedKVCache` hands them out) and slots go out
OLDEST FIRST, so what a finished sequence wrote stays on the device until
the slack of the pools has gone round; :meth:`HybridStateCache.retired`
returns a finished sequence's table until one of its blocks or its slot has
been handed out again.

A model may have any number of full layers (``full_layers``), no
state-space layer at all (``recurrent_layers = 0``: the slot arrays are
empty and a slot is only a row's index), no window layer at all
(``window_layers = 0``: ``ring_blocks`` is 0, no ring is allocated, counted
or asked for at admission, and a table's ``.window`` is empty) and pools
narrower than float32 (``dtype``); the byte counts follow the pools' dtype.

A page row need not be a K row and a V row of equal width. ``v_dim`` is the
width of a row of the value pools where it is not ``kv_dim``'s; at ``v_dim =
0`` a page row is ONE array of ``kv_dim`` values, whatever the model keeps
of a token that is key and value at once (multi-head latent attention: the
compressed latent and the shared rotary key side by side,
``serving/glm_model.py``), the value pools are empty ``(layers, slots, 0)``
arrays that cost nothing and go through every launch beside the others, and
a block's bytes count ``kv_dim + v_dim`` values a row where two pools of
equal width count ``2 * kv_dim``. (The device pool of such a page is
allocated at whole 128-lane tiles, as ``PagedKVCache`` says; the byte counts
stay those of the values a token holds.) Nothing of such a page is
overwritten, but the manager declares ``state_overwritten`` all the same for
now: prefix reuse, truncation and migration over one-array pages are not
built (ROADMAP M5).

``state_overwritten = True`` is the declaration the rest of the serving
plane reads: a ring row and a recurrent state are written over in place, and
nothing records either at a block boundary yet, so ``build_prefix_cache``
builds no radix tree over this manager (a hit needs the rows behind the
window and the state at the matched length), and ``ServingEngine`` refuses
speculative decoding (a rejected draft cannot be rolled back out of an
overwritten row or state) and migration. ``recurrent_state`` says only what
its name does: whether the model keeps a state-space state.

``snapshot()`` keeps the keys its readers have (``blocks_used``,
``blocks_total``, ``used_ratio``, ...: ``serving/service.py``'s ``Stats`` and
the ``/serving`` page): they count the pages of the kind that GROWS with the
context, the full layer's. Each kind's own numbers lie under ``full``,
``window`` and ``slots``.
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, List, Optional

import numpy as np

from brpc_tpu.serving.kv_cache import (KVCacheConfig, KVCacheFull,
                                       PagedKVCache)


class HybridCacheConfig:
    def __init__(self, block_size: int = 16, num_blocks: int = 128,
                 max_sequences: int = 8, window: int = 512,
                 watermark: float = 0.90):
        if max_sequences < 1 or window < 1:
            raise ValueError("max_sequences/window must be >= 1")
        self.block_size = block_size
        self.num_blocks = num_blocks          # of the full layer's pool
        self.max_sequences = max_sequences    # rings and recurrent slots
        self.window = window
        self.watermark = watermark

    @property
    def ring_blocks(self) -> int:
        """Blocks in a sequence's ring: the window, and one more because
        the window's first row need not lie at a block's edge."""
        return -(-self.window // self.block_size) + 1


class HybridTable(list):
    """A sequence's full-layer block table, which also names its window
    ring and its recurrent slot; ``tokens`` is the context the table covers
    where the manager says so (``retired``)."""

    def __init__(self, blocks, window, slot: int, tokens: int = 0):
        super().__init__(blocks)
        self.window = tuple(window)
        self.slot = slot
        self.tokens = tokens


class HybridStateCache:
    """See the module docstring. ``window_layers`` / ``recurrent_layers`` /
    ``full_layers`` say how many layers of each kind the model has (SambaY:
    ONE full layer, whose K/V the architecture shares); ``dtype`` is the
    K/V pools' (float32 where it is left out; the recurrent state is always
    float32); ``v_dim`` the width of a value row where it is not ``kv_dim``
    (0: a page row is one array, the module docstring)."""

    # rings and recurrent states are written over in place: no prefix
    # reuse, speculation or migration over this manager
    state_overwritten = True

    def __init__(self, config: HybridCacheConfig, kv_dim: int,
                 window_layers: int, recurrent_layers: int = 0,
                 d_inner: int = 0, d_state: int = 0, d_conv: int = 1,
                 store=None, full_layers: int = 1, dtype=None,
                 v_dim: Optional[int] = None):
        import jax.numpy as jnp

        self.config = config
        self.kv_dim = kv_dim
        self.v_dim = kv_dim if v_dim is None else v_dim
        self.recurrent_state = recurrent_layers > 0
        # blocks in a sequence's ring: none for a model with no window layer
        # (its ``window`` pool has no layer and hands nothing out)
        self.ring_blocks = config.ring_blocks if window_layers else 0
        self.full = PagedKVCache(
            KVCacheConfig(config.block_size, config.num_blocks,
                          config.watermark),
            full_layers, kv_dim, store=store, dtype=dtype, v_dim=v_dim)
        self.window = PagedKVCache(
            KVCacheConfig(config.block_size,
                          max(1, config.max_sequences * self.ring_blocks),
                          1.0),
            window_layers, kv_dim, store=self.full.store, dtype=dtype,
            v_dim=v_dim)
        self.store = self.full.store
        self._lock = threading.Lock()
        n = config.max_sequences
        self.ssm = jnp.zeros((2, recurrent_layers, n + 1, d_state, d_inner),
                             jnp.float32, device=self.store.device)
        self.conv = jnp.zeros((2, recurrent_layers, n + 1, d_conv - 1,
                               d_inner), jnp.float32,
                              device=self.store.device)
        self.ssm_handle, _ = self.store.adopt(self.ssm)
        self.conv_handle, _ = self.store.adopt(self.conv)
        self._free_slots = collections.deque(range(1, n + 1))
        self._slot_of: Dict[int, int] = {}
        self._len: Dict[int, int] = {}
        # who was handed each block or slot last: retired() checks it
        self._full_owner = np.zeros(config.num_blocks + 1, np.int64)
        self._ring_owner = np.zeros(self.window.num_blocks + 1, np.int64)
        self._slot_owner = np.zeros(n + 1, np.int64)
        self._retired: "collections.OrderedDict[int, HybridTable]" = \
            collections.OrderedDict()
        bs, f32 = config.block_size, 4
        # a page row: its key values and its value values (none at v_dim 0)
        row = (kv_dim + self.v_dim) * self.full.k_pool.dtype.itemsize
        self._block_bytes = {
            "full": full_layers * bs * row,
            "window": window_layers * bs * row}
        # the running state: what a live sequence needs
        self._slot_bytes = (recurrent_layers * d_inner * f32
                            * (d_state + d_conv - 1))
        self.window_blocks_recycled = 0
        self.cache_bytes_peak = 0
        self.tokens_at_peak = 0
        self._check = self.full._check

    # ------------------------------------------------------------- geometry
    @property
    def block_size(self) -> int:
        return self.config.block_size

    @property
    def num_blocks(self) -> int:
        return self.config.num_blocks

    @property
    def used_blocks(self) -> int:
        return self.full.used_blocks

    def used_ratio(self) -> float:
        return self.full.used_ratio()

    def blocks_for(self, ntokens: int) -> int:
        return self.full.blocks_for(ntokens)

    # ------------------------------------------------------------ admission
    def can_admit(self, ntokens: int, route_key: Optional[int] = None,
                  shard: Optional[int] = None) -> bool:
        """The full layer's watermark as :meth:`PagedKVCache.can_admit`
        applies it, AND a ring and a recurrent slot free for the sequence."""
        with self._lock:
            return (bool(self._free_slots)
                    and self.window.free_blocks >= self.ring_blocks
                    and self.full.can_admit(ntokens))

    def note_rejected(self) -> None:
        self.full.note_rejected()

    # ----------------------------------------------------------- sequences
    def alloc_sequence(self, seq_id: int, ntokens: int) -> HybridTable:
        """Pages covering ``ntokens``, a ring and a slot: all or nothing."""
        cfg = self.config
        with self._lock:
            if seq_id in self._slot_of:
                raise ValueError(f"sequence {seq_id} already has a table")
            if (not self._free_slots
                    or self.window.free_blocks < self.ring_blocks):
                self.full.note_rejected()
                raise KVCacheFull(
                    f"no ring or recurrent slot free "
                    f"({cfg.max_sequences} sequences)")
            blocks = self.full.alloc_sequence(seq_id, ntokens)  # may raise
            ring = (self.window.alloc_sequence(
                seq_id, self.ring_blocks * cfg.block_size)
                if self.ring_blocks else ())
            slot = self._free_slots.popleft()
            self._slot_of[seq_id] = slot
            self._len[seq_id] = ntokens
            self._full_owner[blocks] = seq_id
            self._ring_owner[list(ring)] = seq_id
            self._slot_owner[slot] = seq_id
            self._note_peak_locked()
            return HybridTable(blocks, ring, slot)

    def extend_sequence(self, seq_id: int, new_len: int) -> HybridTable:
        """Grow the full-layer table to cover ``new_len`` tokens. The ring
        and the slot do not grow: the ring's oldest block is written over
        (counted in ``window_blocks_recycled``)."""
        with self._lock:
            slot = self._slot_of.get(seq_id)
            if slot is None:
                raise KeyError(f"unknown sequence {seq_id}")
            old = self._len[seq_id]
            blocks = self.full.extend_sequence(seq_id, new_len)
            grown = blocks[self.full.blocks_for(old):]
            if grown:
                self._full_owner[grown] = seq_id
            ring_n = self.ring_blocks

            def wrapped(n):
                return max(0, self.full.blocks_for(n) - ring_n)

            if ring_n:
                self.window_blocks_recycled += wrapped(new_len) - wrapped(old)
            self._len[seq_id] = max(old, new_len)
            self._note_peak_locked()
            return HybridTable(blocks, self._ring_of(seq_id), slot)

    def free_sequence(self, seq_id: int) -> int:
        """Return pages, ring and slot. Returns full-layer blocks freed."""
        with self._lock:
            slot = self._slot_of.pop(seq_id, None)
            if slot is None:
                return 0
            table = HybridTable(self.full.block_table(seq_id),
                                self._ring_of(seq_id), slot,
                                self._len.pop(seq_id))
            freed = self.full.free_sequence(seq_id)
            if self.ring_blocks:
                self.window.free_sequence(seq_id)
            self._free_slots.append(slot)
            self._retired[seq_id] = table
            while len(self._retired) > 4 * self.config.max_sequences:
                self._retired.popitem(last=False)
            return freed

    def block_table(self, seq_id: int) -> Optional[HybridTable]:
        with self._lock:
            slot = self._slot_of.get(seq_id)
            if slot is None:
                return None
            return HybridTable(self.full.block_table(seq_id),
                               self._ring_of(seq_id), slot)

    def _ring_of(self, seq_id: int):
        """The sequence's ring, in ring order; () for a model that has no
        window layer: it holds no ring."""
        return self.window.block_table(seq_id) if self.ring_blocks else ()

    def seq_len(self, seq_id: int) -> int:
        with self._lock:
            return self._len.get(seq_id, 0)

    def retired(self, seq_id: int) -> Optional[HybridTable]:
        """A FREED sequence's table (with ``.tokens``, the context it was
        freed at) while every block and the slot it names still hold what
        the sequence wrote: until one of them is handed out again."""
        with self._lock:
            t = self._retired.get(seq_id)
            if t is None:
                return None
            if ((self._full_owner[list(t)] != seq_id).any()
                    or (self._ring_owner[list(t.window)] != seq_id).any()
                    or self._slot_owner[t.slot] != seq_id):
                del self._retired[seq_id]
                return None
            return t

    def retired_ids(self) -> List[int]:
        """Freed sequences :meth:`retired` may still know, oldest first."""
        with self._lock:
            return list(self._retired)

    # ------------------------------------------------------------ pool swap
    def update_state(self, ssm, conv) -> None:
        """Install the recurrent arrays a launch returned (donated in)."""
        self.ssm, self.conv = ssm, conv
        self.store.replace(self.ssm_handle, ssm)
        self.store.replace(self.conv_handle, conv)

    # ---------------------------------------------------------------- audit
    def assert_idle(self, context: str = "") -> None:
        self.full.assert_idle(context)
        self.window.assert_idle(context)
        with self._lock:
            if (self._slot_of
                    or len(self._free_slots) != self.config.max_sequences):
                where = f" [{context}]" if context else ""
                raise AssertionError(
                    f"recurrent slots not idle{where}: "
                    f"{sorted(self._slot_of)} live, "
                    f"{len(self._free_slots)} of "
                    f"{self.config.max_sequences} free")

    def close(self) -> None:
        self.full.close()
        self.window.close()
        if self.ssm_handle:
            self.store.free(self.ssm_handle)
            self.store.free(self.conv_handle)
            self.ssm_handle = self.conv_handle = 0

    # ----------------------------------------------------------- visibility
    def _cache_bytes_locked(self) -> int:
        return (self.full.used_blocks * self._block_bytes["full"]
                + self.window.used_blocks * self._block_bytes["window"]
                + len(self._slot_of) * self._slot_bytes)

    def _note_peak_locked(self) -> None:
        held = self._cache_bytes_locked()
        if held > self.cache_bytes_peak:
            self.cache_bytes_peak = held
            self.tokens_at_peak = sum(self._len.values())

    def reset_peak(self) -> None:
        """Start ``cache_bytes_peak`` / ``tokens_at_peak`` anew (a benchmark
        does after its warm-up)."""
        with self._lock:
            self.cache_bytes_peak = self.tokens_at_peak = 0

    def snapshot(self) -> Dict[str, object]:
        """``blocks_*``, ``used_ratio``, ``watermark``, ``sequences``: the
        FULL layer's pool, the kind that grows with the context (what
        ``Stats``, ``/serving`` and a sampler of occupancy have always
        read). ``full`` / ``window`` / ``slots``: each kind's used and
        total. ``page_row``: the values a token holds a layer in the key
        and in the value pools (``{"k": kv_dim, "v": v_dim}``; ``v`` 0: a
        page row is one array, key and value at once). ``cache_bytes``:
        bytes of pages and slots held by live sequences, a page row counted
        at ``k + v`` values; ``cache_bytes_peak`` with ``tokens_at_peak``
        (the live context tokens at that moment) since the last
        ``reset_peak()``."""
        with self._lock:
            snap = self.full.snapshot()
            snap["sequences"] = len(self._slot_of)
            snap["full"] = {"used": snap["blocks_used"],
                            "total": snap["blocks_total"]}
            ringed = self.window if self.ring_blocks else None
            snap["window"] = {"used": ringed.used_blocks if ringed else 0,
                              "total": ringed.num_blocks if ringed else 0,
                              "ring_blocks": self.ring_blocks}
            snap["slots"] = {"used": len(self._slot_of),
                             "total": self.config.max_sequences}
            snap["page_row"] = {"k": self.kv_dim, "v": self.v_dim}
            snap["window_blocks_recycled"] = self.window_blocks_recycled
            snap["cache_bytes"] = self._cache_bytes_locked()
            snap["cache_bytes_peak"] = self.cache_bytes_peak
            snap["tokens_at_peak"] = self.tokens_at_peak
            return snap
