"""Radix prefix cache: copy-on-write KV reuse over the paged pools.

The SGLang step on top of the vLLM one (RadixAttention, Zheng et al.
2024 over PagedAttention, Kwon et al. 2023): a radix tree over token-id
prefixes whose nodes map to committed, refcounted KV block chains in the
:class:`~brpc_tpu.serving.kv_cache.PagedKVCache` ledger. Each tree node
covers exactly ONE full block (``block_size`` token ids) and pins one
physical block via ``retain_block``; a root-to-node path is a
block-aligned prefix chain.

On admission the engine matches the longest block-aligned cached prefix
of the prompt and *forks* the chain — ``adopt_sequence`` bumps refcounts,
zero device copies — then prefills only the suffix. The match is capped
at ``len(prompt) - 1`` tokens so at least one suffix token always runs
through the model (the engine needs a first sampled token, and position
``len(prompt) - 1``'s K/V must be written by the new sequence anyway).
Writes into the divergence block go copy-on-write (``cow_block``): a
shared block is never mutated, so forked generations stay bit-identical
to cold-start.

On sequence completion the engine *commits* the sequence's full blocks
back into the tree: walking existing nodes shares them (the committer's
duplicate block simply frees with the sequence), new nodes take a cache
hold on the committer's block (insert-or-share).

Eviction is LRU over refcount-1 chains ONLY — a block some live sequence
still shares is never evicted, so decode headroom is never stolen — and
watermark-aware: commits trim the tree back under
``serving_prefix_evict_watermark`` occupancy, and admission that would
reject with EOVERCROWDED first asks the tree to give blocks back
(``evict_for_admission``). ``KVCacheFull`` semantics are unchanged: the
tree only ever *releases* holds, it cannot defer a rejection the
watermark would still make.

The victim is always the evictable leaf with the lowest stamp. Finding it
never walks the tree: the tree keeps an index of its leaves, ordered by
stamp, up to date where it changes (commit's insert, fork's and commit's
restamp, eviction exposing a parent, clear). Every stamping stamps one
root-to-node path with a fresh tick, so a node's stamp never exceeds its
parent's and no two leaves share one: the order is total, and a stamped
leaf always moves to the index's end. Whether a leaf is evictable
(``kv.block_ref == 1``) changes outside the tree, when a forked sequence
frees its chain, so it is tested when a victim is chosen: the scan runs
from the oldest leaf past the shared ones, and ``evict_scanned`` counts
the leaves it looked at (one an evicted block where nothing is shared).

**Sharded mode** (:class:`ShardedPrefixCache`): one tree per dp shard,
each over its shard's ledger pool. Placement is prefix-hash routed —
``prefix_route_key`` folds the first cached-block-aligned window of
token ids (same FNV-1a spread as ``generate_route_key``) so same-prefix
traffic lands on the shard that holds the chain, fleet-wide, and the
:class:`~brpc_tpu.serving.router.GenerateRouter` computes the identical
shard client-side.
"""

from __future__ import annotations

import threading
from bisect import bisect_left, insort
from typing import Dict, List, Optional, Tuple

from brpc_tpu import fault as _fault
from brpc_tpu import flags as _flags
from brpc_tpu.metrics.reducer import Adder
from brpc_tpu.metrics.status import PassiveStatus
from brpc_tpu.serving.kv_cache import PagedKVCache, ShardedKVCache

_fault.register("serving.prefix.evict",
                "force radix prefix-cache eviction churn (blocks=)")

g_serving_prefix_hit_seqs = Adder("g_serving_prefix_hit_seqs")
g_serving_prefix_hit_blocks = Adder("g_serving_prefix_hit_blocks")
g_serving_prefix_hit_tokens = Adder("g_serving_prefix_hit_tokens")
g_serving_prefix_miss_seqs = Adder("g_serving_prefix_miss_seqs")
g_serving_prefix_inserted_blocks = Adder("g_serving_prefix_inserted_blocks")
g_serving_prefix_evicted_blocks = Adder("g_serving_prefix_evicted_blocks")


def _hit_ratio() -> float:
    hits = g_serving_prefix_hit_seqs.get_value()
    misses = g_serving_prefix_miss_seqs.get_value()
    total = hits + misses
    return hits / total if total else 0.0


g_serving_prefix_hit_ratio = PassiveStatus(_hit_ratio) \
    .expose("g_serving_prefix_hit_ratio")
g_serving_prefix_hit_ratio.prometheus_type = "gauge"


def prefix_route_key(tokens, block_size: int) -> Optional[int]:
    """Fold the first cached-block-aligned window of token ids into a
    64-bit route key — the SAME FNV-1a spread ``generate_route_key``
    uses, but over only ``tokens[:block_size]``, so every prompt sharing
    a cacheable first block hashes to the same shard. Returns None when
    the prompt cannot produce a cache hit at all (shorter than one full
    block plus the mandatory suffix token), letting callers fall back to
    whole-prompt routing."""
    if len(tokens) < block_size + 1:
        return None
    key = 0xCBF29CE484222325
    for t in tokens[:block_size]:
        key = ((key ^ (int(t) & 0xFFFFFFFF)) * 0x100000001B3) \
            & 0xFFFFFFFFFFFFFFFF
    return key


class _Node:
    """One full block of token ids; pins one physical block in the pool
    ledger while it lives in the tree."""

    __slots__ = ("key", "block", "children", "parent", "stamp")

    def __init__(self, key: Tuple[int, ...], block: int, parent):
        self.key = key
        self.block = block
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.parent = parent
        self.stamp = 0


class PrefixCache:
    """Radix tree over one pool's block-aligned prefixes.

    Lock order: the tree lock is OUTER, the pool's ledger lock inner
    (every ``kv.*`` call below takes it) — never the reverse."""

    def __init__(self, kv: PagedKVCache, shard: int = 0):
        self.kv = kv
        self.shard = shard
        self._lock = threading.Lock()
        self._root = _Node((), -1, None)
        self._tick = 0  # monotonic LRU clock (stamps, not wall time)
        self._nodes = 0
        # the leaves, oldest first: their stamps sorted (unique among
        # leaves, see the module docstring) and the leaf of each stamp
        self._leaf_stamps: List[int] = []
        self._leaf_of: Dict[int, _Node] = {}
        self.hit_seqs = 0
        self.miss_seqs = 0
        self.hit_blocks = 0
        self.hit_tokens = 0
        self.inserted_blocks = 0
        self.evicted_blocks = 0
        self.evict_scanned = 0  # leaves examined while choosing victims

    @staticmethod
    def enabled() -> bool:
        return bool(_flags.get("serving_prefix_cache_enabled"))

    # ---------------------------------------------------------- leaf index
    def _index_leaf_locked(self, node: _Node) -> None:
        """``node`` is a leaf now: it enters the index at its stamp (the
        end, where it has just been stamped with the current tick)."""
        insort(self._leaf_stamps, node.stamp)
        self._leaf_of[node.stamp] = node

    def _unindex_leaf_locked(self, node: _Node) -> None:
        """``node`` stops being the leaf of its stamp: it is evicted, or
        about to be stamped again or to get a child."""
        del self._leaf_stamps[bisect_left(self._leaf_stamps, node.stamp)]
        del self._leaf_of[node.stamp]

    # ------------------------------------------------------------- matching
    def _walk_locked(self, tokens) -> List[_Node]:
        """Longest cached block-aligned chain covering a PROPER prefix of
        ``tokens`` — capped at ``len(tokens) - 1`` so the suffix prefill
        always has at least one token to run."""
        bs = self.kv.config.block_size
        limit = max(0, (len(tokens) - 1) // bs)
        chain: List[_Node] = []
        node = self._root
        for i in range(limit):
            key = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            child = node.children.get(key)
            if child is None:
                break
            chain.append(child)
            node = child
        return chain

    def match_len(self, tokens) -> int:
        """Cached-prefix length in tokens (block-aligned, < len(tokens))."""
        with self._lock:
            return len(self._walk_locked(tokens)) * self.kv.config.block_size

    def route_shard(self, tokens) -> Optional[int]:
        return None  # single pool: nowhere to route

    # ---------------------------------------------------------------- fork
    def fork(self, seq_id: int, tokens) -> int:
        """Admission-side hit path: match the longest cached prefix, adopt
        its chain for ``seq_id`` (refcount++, zero copies), and return the
        matched token count — 0 on a miss (caller allocates cold)."""
        self._maybe_fault_evict()
        if not self.enabled():
            return 0
        with self._lock:
            chain = self._walk_locked(tokens)
            if not chain:
                self.miss_seqs += 1
                g_serving_prefix_miss_seqs.put(1)
                return 0
            self._tick += 1
            last = chain[-1]
            if not last.children:
                self._unindex_leaf_locked(last)
            for n in chain:
                n.stamp = self._tick
            if not last.children:
                self._index_leaf_locked(last)
            blocks = [n.block for n in chain]
            matched = len(blocks) * self.kv.config.block_size
            self.kv.adopt_sequence(seq_id, blocks, matched)
            self.hit_seqs += 1
            self.hit_blocks += len(blocks)
            self.hit_tokens += matched
        g_serving_prefix_hit_seqs.put(1)
        g_serving_prefix_hit_blocks.put(len(blocks))
        g_serving_prefix_hit_tokens.put(matched)
        return matched

    # -------------------------------------------------------------- commit
    def commit(self, seq_id: int, tokens, valid_len: int) -> int:
        """Completion-side insert-or-share: walk ``seq_id``'s table along
        the tree, sharing existing nodes and pinning new ones. Only FULL
        blocks whose K/V are entirely written (``valid_len``) commit; the
        committer's duplicate of an already-cached block simply frees
        with the sequence. Returns blocks newly inserted."""
        if not self.enabled():
            return 0
        table = self.kv.block_table(seq_id)
        if table is None:
            return 0
        bs = self.kv.config.block_size
        n_full = min(int(valid_len), len(tokens)) // bs
        inserted = 0
        with self._lock:
            self._tick += 1
            node = self._root
            for i in range(n_full):
                key = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
                child = node.children.get(key)
                if child is None:
                    self.kv.retain_block(table[i])
                    child = _Node(key, table[i], node)
                    node.children[key] = child
                    self._nodes += 1
                    inserted += 1
                elif not child.children:
                    # the one indexed leaf a path can hold: restamped
                    # here, and a parent from the next block on
                    self._unindex_leaf_locked(child)
                child.stamp = self._tick
                node = child
            # the deepest node of the path is its only leaf, if it is one
            if node is not self._root and not node.children:
                self._index_leaf_locked(node)
        if inserted:
            self.inserted_blocks += inserted
            g_serving_prefix_inserted_blocks.put(inserted)
        self._trim()
        return inserted

    # ------------------------------------------------------------ eviction
    def _take_victim_locked(self) -> Optional[_Node]:
        """Take the oldest leaf whose block the tree is the SOLE owner of
        (refcount 1) out of the index: chains a live sequence still
        shares are passed over, never stolen from."""
        for stamp in self._leaf_stamps:
            self.evict_scanned += 1
            node = self._leaf_of[stamp]
            if self.kv.block_ref(node.block) == 1:
                self._unindex_leaf_locked(node)
                return node
        return None

    def _evict_locked(self, nblocks: int) -> int:
        """LRU-evict up to ``nblocks`` leaf blocks; freeing a leaf can
        expose its parent, which enters the index at its own stamp."""
        evicted = 0
        while evicted < nblocks:
            victim = self._take_victim_locked()
            if victim is None:
                break
            parent = victim.parent
            del parent.children[victim.key]
            if not parent.children and parent is not self._root:
                self._index_leaf_locked(parent)
            self._nodes -= 1
            self.kv.release_block(victim.block)
            evicted += 1
        if evicted:
            self.evicted_blocks += evicted
            g_serving_prefix_evicted_blocks.put(evicted)
        return evicted

    def _maybe_fault_evict(self) -> None:
        p = _fault.hit("serving.prefix.evict")
        if p is None:
            return
        with self._lock:
            self._evict_locked(int(p.get("blocks", 1)))

    def _trim(self) -> int:
        """Watermark-aware trim: give blocks back until pool occupancy is
        under ``serving_prefix_evict_watermark`` (or nothing evictable
        remains — shared chains stay)."""
        mark = float(_flags.get("serving_prefix_evict_watermark"))
        total = 0
        while self.kv.used_ratio() > mark:
            with self._lock:
                if not self._evict_locked(1):
                    break
            total += 1
        return total

    def evict_for_admission(self, ntokens: int, shard: Optional[int] = None,
                            route_key: Optional[int] = None) -> bool:
        """Give blocks back until the pool would admit ``ntokens`` —
        called on the EOVERCROWDED path BEFORE rejecting. Returns True if
        admission now passes; the watermark itself is unchanged, only
        tree-held (refcount-1) blocks are released."""
        while not self.kv.can_admit(ntokens):
            with self._lock:
                if not self._evict_locked(1):
                    return False
        return True

    # ------------------------------------------------------------ lifecycle
    def clear(self) -> int:
        """Release every tree hold (engine stop): the pool must audit
        idle afterwards."""
        released = 0
        with self._lock:
            stack = list(self._root.children.values())
            while stack:
                n = stack.pop()
                stack.extend(n.children.values())
                self.kv.release_block(n.block)
                released += 1
            self._root.children.clear()
            self._nodes = 0
            self._leaf_stamps.clear()
            self._leaf_of.clear()
        return released

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            nodes = self._nodes
        hits, misses = self.hit_seqs, self.miss_seqs
        total = hits + misses
        return {
            "enabled": self.enabled(),
            "nodes": nodes,
            "blocks": nodes,
            "hit_seqs": hits,
            "miss_seqs": misses,
            "hit_blocks": self.hit_blocks,
            "hit_tokens": self.hit_tokens,
            "inserted_blocks": self.inserted_blocks,
            "evicted_blocks": self.evicted_blocks,
            "evict_scanned": self.evict_scanned,
            "hit_ratio": hits / total if total else 0.0,
        }


class ShardedPrefixCache:
    """One radix tree per dp shard, each over its shard's ledger pool.

    Placement must agree end to end: ``route_shard`` (server-side
    admission) and :class:`~brpc_tpu.serving.router.GenerateRouter`
    (client-side stub routing) both put ``prefix_route_key`` through the
    dispatch plane's splitmix64 ``shard_for`` — same-prefix traffic
    lands where the chain lives."""

    def __init__(self, kv: ShardedKVCache):
        from brpc_tpu.shard.plane import shard_for
        self.kv = kv
        self._route = shard_for
        self.trees = [PrefixCache(pool, shard=i)
                      for i, pool in enumerate(kv.pools)]

    @staticmethod
    def enabled() -> bool:
        return PrefixCache.enabled()

    def route_shard(self, tokens) -> Optional[int]:
        """Prefix-hash placement for a prompt, or None when it cannot hit
        the cache (too short) — callers fall back to seq-id routing."""
        if not self.enabled():
            return None
        key = prefix_route_key(tokens, self.kv.config.block_size)
        if key is None:
            return None
        return self._route(key, self.kv.n_shards)

    def match_len(self, tokens) -> int:
        shard = self.route_shard(tokens)
        if shard is None:
            return 0
        return self.trees[shard].match_len(tokens)

    def fork(self, seq_id: int, tokens) -> int:
        shard = self.route_shard(tokens)
        if shard is None:
            return 0
        matched = self.trees[shard].fork(seq_id, tokens)
        if matched:
            # the chain pins the sequence to its shard (adopt registered
            # it in that pool's ledger; routing must agree)
            self.kv.pin_shard(seq_id, shard)
        return matched

    def commit(self, seq_id: int, tokens, valid_len: int) -> int:
        got = self.kv._pool_of(seq_id)
        if got is None:
            return 0
        return self.trees[got[0]].commit(seq_id, tokens, valid_len)

    def evict_for_admission(self, ntokens: int, shard: Optional[int] = None,
                            route_key: Optional[int] = None) -> bool:
        if shard is None and route_key is not None:
            shard = self.kv.shard_of(route_key)
        if shard is None:
            return any(t.evict_for_admission(ntokens) for t in self.trees)
        return self.trees[shard].evict_for_admission(ntokens)

    def clear(self) -> int:
        return sum(t.clear() for t in self.trees)

    def snapshot(self) -> Dict[str, object]:
        shards = [t.snapshot() for t in self.trees]
        agg = {k: sum(s[k] for s in shards)
               for k in ("nodes", "blocks", "hit_seqs", "miss_seqs",
                         "hit_blocks", "hit_tokens", "inserted_blocks",
                         "evicted_blocks", "evict_scanned")}
        total = agg["hit_seqs"] + agg["miss_seqs"]
        agg["hit_ratio"] = agg["hit_seqs"] / total if total else 0.0
        agg["enabled"] = self.enabled()
        agg["shards"] = shards
        return agg


def build_prefix_cache(kv):
    """The engine's factory: per-shard trees over a ShardedKVCache, one
    tree over a plain pool, none over a manager that declares
    ``state_overwritten`` (a hit needs the recurrent state at the matched
    length and the ring rows behind it, which nothing records yet): the
    engine then runs without a prefix cache."""
    if getattr(kv, "state_overwritten", False):
        return None
    if isinstance(kv, ShardedKVCache):
        return ShardedPrefixCache(kv)
    return PrefixCache(kv)
