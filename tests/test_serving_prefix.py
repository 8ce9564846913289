"""Radix prefix cache: copy-on-write KV reuse across the serving plane.

Five layers, cheapest first:

* the ledger's cache-hold surface — retain/release/adopt, copy-on-write
  block splits, the armed ``assert_writable`` range audit, and
  ``assert_idle`` naming lingering tree holds;
* the radix tree as a pure data structure over a ledger pool — commit
  (insert-or-share), block-aligned matching capped at a proper prefix,
  LRU eviction over refcount-1 chains ONLY, watermark trim, the
  admission-pressure release valve, and the kill-switch flag;
* the evictable-leaf index against the plain reference it replaced (a
  walk of the whole tree for every evicted block): the same victims in
  the same order over random interleavings, and a cost counted in leaves
  examined, not read from a clock;
* prefix-hash routing — ``prefix_route_key`` semantics and the fleet
  contract that client-side :class:`GenerateRouter` and server-side
  :class:`ShardedPrefixCache` place the same prompt on the same shard;
* the real tiny transformer through the engine — the correctness
  oracle (forked generations bit-identical to cold-start on the
  committed corpus schedule), the ``/serving`` builtin's prefix
  section, the thrash watch rule, and the eviction-churn chaos lane
  proving zero leaked blocks under an armed ledger.
"""

import collections
import itertools
import random
import threading
import types

import pytest

from brpc_tpu import fault
from brpc_tpu import flags as _flags
from brpc_tpu.serving import (
    EngineConfig,
    KVCacheConfig,
    ModelConfig,
    PagedKVCache,
    PrefixCache,
    ServingEngine,
    ShardedKVCache,
    ShardedPrefixCache,
    TinyTransformer,
    build_prefix_cache,
    prefix_route_key,
)
from brpc_tpu.serving.kv_cache import KVCacheFull
from brpc_tpu.shard.plane import shard_for

# the committed replay corpus's schedule: synth prompts are arange(1, n+1),
# so every prompt shares its first block(s) with every longer one — the
# exact shared-system-prompt traffic the radix tree exists for
from tools.record_serving_corpus import SCHEDULE


def _kv(num_blocks=64, block_size=8, watermark=1.0, layers=1, kv_dim=8):
    kv = PagedKVCache(KVCacheConfig(block_size=block_size,
                                    num_blocks=num_blocks,
                                    watermark=watermark),
                      layers, kv_dim)
    kv._check = True  # audit every ledger mutation like BRPC_TPU_CHECK=1
    return kv


# ------------------------------------------------------ ledger cache holds
class TestLedgerCacheHolds:
    def test_retain_release_roundtrip(self):
        kv = _kv()
        t = kv.alloc_sequence(1, 16)  # 2 blocks
        kv.retain_block(t[0])
        assert kv.cache_held_blocks() == 1
        assert kv.block_ref(t[0]) == 2
        assert kv.free_sequence(1) == 1  # t[1] freed; t[0] cache-held
        assert kv.used_blocks == 1
        assert kv.release_block(t[0]) == 1  # last hold: block freed
        kv.assert_idle("after release")

    def test_release_without_hold_raises(self):
        kv = _kv()
        t = kv.alloc_sequence(1, 8)
        with pytest.raises(KeyError):
            kv.release_block(t[0])  # table-held, but no cache hold
        kv.free_sequence(1)
        kv.assert_idle()

    def test_assert_idle_names_lingering_cache_holds(self):
        kv = _kv()
        t = kv.alloc_sequence(1, 8)
        kv.retain_block(t[0])
        kv.free_sequence(1)
        with pytest.raises(AssertionError, match="prefix cache"):
            kv.assert_idle("cache hold probe")
        kv.release_block(t[0])
        kv.assert_idle()

    def test_adopt_shares_a_cached_chain(self):
        kv = _kv()
        t = kv.alloc_sequence(1, 24)  # 3 blocks
        for b in t:
            kv.retain_block(b)  # the tree pins the whole chain
        kv.free_sequence(1)
        assert kv.used_blocks == 3  # the chain outlives its sequence
        kv.adopt_sequence(2, t[:2], 16)  # fork: 2 blocks, zero copies
        assert list(kv.block_table(2)) == t[:2]
        assert kv.block_ref(t[0]) == 2 and kv.block_ref(t[2]) == 1
        ext = kv.extend_sequence(2, 17)  # grows a FRESH tail block
        assert ext[:2] == t[:2] and len(ext) == 3 and ext[2] != t[2]
        assert kv.free_sequence(2) == 1  # only the private tail frees
        for b in t:
            kv.release_block(b)
        kv.assert_idle("after adopt teardown")

    def test_cow_block_splits_shared_then_passes_through(self):
        kv = _kv()
        copies = []
        kv._cow_copy_fn = lambda dst, src: copies.append((dst, src))
        t = kv.alloc_sequence(1, 16)
        kv.fork_sequence(1, 2)  # both tables share both blocks
        new = kv.cow_block(2, 0)
        assert new != t[0] and copies == [(new, t[0])]
        assert kv.block_ref(t[0]) == 1 and kv.block_ref(new) == 1
        assert list(kv.block_table(2)) == [new, t[1]]
        assert kv.block_ref(t[1]) == 2  # index 1 untouched, still shared
        # sole owner now: passthrough, no second device copy
        assert kv.cow_block(2, 0) == new and len(copies) == 1
        kv.free_sequence(1)
        kv.free_sequence(2)
        kv.assert_idle("after cow teardown")

    def test_ensure_writable_maps_position_to_block(self):
        kv = _kv()
        t = kv.alloc_sequence(1, 24)
        kv.fork_sequence(1, 2)
        copies = []
        kv._cow_copy_fn = lambda dst, src: copies.append((dst, src))
        got = kv.ensure_writable(2, 8)  # position 8 -> block index 1
        assert copies == [(got, t[1])]
        kv.free_sequence(1)
        kv.free_sequence(2)
        kv.assert_idle()

    def test_assert_writable_catches_shared_write_ranges(self):
        kv = _kv()
        kv._cow_copy_fn = lambda dst, src: None
        t = kv.alloc_sequence(1, 16)
        kv.fork_sequence(1, 2)
        with pytest.raises(AssertionError, match="cow violation"):
            kv.assert_writable(t, 0, 16)
        kv.cow_block(2, 0)
        # block index 1 is still shared: writing there must still trip
        with pytest.raises(AssertionError, match="cow violation"):
            kv.assert_writable(kv.block_table(2), 8, 16)
        kv.assert_writable(kv.block_table(2), 0, 8)  # split block: fine
        kv.free_sequence(1)
        kv.free_sequence(2)
        kv.assert_idle()


# ------------------------------------------------------------- radix tree
def _commit_chain(kv, tree, seq_id, tokens):
    """The engine's completion path in miniature: alloc a sequence whose
    K/V is considered fully written, commit its full blocks into the
    tree, then free the sequence (tree holds survive)."""
    kv.alloc_sequence(seq_id, len(tokens))
    inserted = tree.commit(seq_id, tokens, len(tokens))
    kv.free_sequence(seq_id)
    return inserted


class TestPrefixRadixTree:
    def _tree(self, num_blocks=64, block_size=8):
        kv = _kv(num_blocks=num_blocks, block_size=block_size)
        return kv, PrefixCache(kv)

    def test_commit_then_match_is_block_aligned_and_proper(self):
        kv, tree = self._tree()
        toks = list(range(1, 21))  # 20 tokens: exactly 2 full blocks
        assert _commit_chain(kv, tree, 1, toks) == 2
        assert kv.used_blocks == 2  # the chain outlives its sequence
        assert tree.match_len(toks) == 16
        assert tree.match_len(toks[:17]) == 16
        # a 16-token prompt may only match 8: one suffix token must run
        assert tree.match_len(toks[:16]) == 8
        assert tree.match_len(list(range(100, 120))) == 0
        tree.clear()
        kv.assert_idle("after clear")

    def test_fork_adopts_the_chain_and_counts_hits(self):
        kv, tree = self._tree()
        toks = list(range(1, 25))  # 3 blocks
        _commit_chain(kv, tree, 1, toks)
        assert tree.fork(2, toks + [99]) == 24
        assert len(kv.block_table(2)) == 3  # the whole chain, no copies
        snap = tree.snapshot()
        assert snap["hit_seqs"] == 1 and snap["hit_blocks"] == 3
        assert snap["hit_tokens"] == 24 and snap["hit_ratio"] == 1.0
        assert tree.fork(3, [7] * 9) == 0  # miss: caller allocates cold
        assert tree.snapshot()["miss_seqs"] == 1
        kv.free_sequence(2)
        tree.clear()
        kv.assert_idle()

    def test_insert_or_share_keeps_the_trees_block(self):
        kv, tree = self._tree()
        toks = list(range(1, 17))
        _commit_chain(kv, tree, 1, toks)
        used = kv.used_blocks
        # a duplicate commit inserts nothing: the committer's blocks
        # free with its sequence, the tree keeps ITS copies
        kv.alloc_sequence(2, 16)
        assert tree.commit(2, toks, 16) == 0
        kv.free_sequence(2)
        assert kv.used_blocks == used
        tree.clear()
        kv.assert_idle()

    def test_divergent_prompts_share_the_common_prefix(self):
        kv, tree = self._tree()
        a = list(range(1, 17))
        b = a[:8] + [50 + i for i in range(8)]
        _commit_chain(kv, tree, 1, a)
        assert _commit_chain(kv, tree, 2, b) == 1  # first block shared
        assert kv.used_blocks == 3
        assert tree.match_len(a + [0]) == 16
        assert tree.match_len(b + [0]) == 16
        tree.clear()
        kv.assert_idle()

    def test_partial_last_block_never_commits(self):
        kv, tree = self._tree()
        toks = list(range(1, 21))  # 20 tokens but only 17 valid
        kv.alloc_sequence(1, 20)
        # valid_len 17: block 2 (tokens 16..19) is partially written
        assert tree.commit(1, toks, 17) == 2
        kv.free_sequence(1)
        assert kv.used_blocks == 2
        tree.clear()
        kv.assert_idle()

    def test_eviction_is_lru_over_sole_owner_leaves(self):
        kv, tree = self._tree()
        a, b, c = (list(range(s, s + 8)) for s in (1, 11, 21))
        for sid, toks in ((1, a), (2, b), (3, c)):
            _commit_chain(kv, tree, sid, toks)
        # touch a and c (fork + drop), leaving b least-recently used
        for sid, toks in ((4, a), (5, c)):
            assert tree.fork(sid, toks + [0]) == 8
            kv.free_sequence(sid)
        with tree._lock:
            assert tree._evict_locked(1) == 1
        assert tree.match_len(b + [0]) == 0  # b went first
        assert tree.match_len(a + [0]) == 8
        assert tree.match_len(c + [0]) == 8
        tree.clear()
        kv.assert_idle()

    def test_shared_chains_are_never_evicted(self):
        kv, tree = self._tree()
        a = list(range(1, 9))
        _commit_chain(kv, tree, 1, a + [0])
        assert tree.fork(2, a + [0]) == 8  # a live sequence shares it
        with tree._lock:
            assert tree._evict_locked(10) == 0  # refcount 2: untouchable
        kv.free_sequence(2)
        with tree._lock:
            assert tree._evict_locked(10) == 1  # sole owner again
        kv.assert_idle("after final evict")

    def test_evict_for_admission_frees_exactly_enough(self):
        kv, tree = self._tree(num_blocks=8)  # block 0 scratch: 7 usable
        chains = [list(range(10 * i + 1, 10 * i + 9)) for i in range(3)]
        for sid, toks in enumerate(chains, start=1):
            _commit_chain(kv, tree, sid, toks)
        assert kv.used_blocks == 3
        assert not kv.can_admit(48)  # 6 blocks > the 5 free
        assert tree.evict_for_admission(48) is True
        assert kv.used_blocks == 2  # gave back exactly one LRU chain
        assert kv.can_admit(48)
        # more than eviction can ever provide fails cleanly (and empties
        # nothing a live sequence would need)
        assert tree.evict_for_admission(9 * 8) is False
        tree.clear()
        kv.assert_idle()

    def test_commit_trims_back_under_the_watermark(self):
        kv, tree = self._tree(num_blocks=8)
        old = _flags.get("serving_prefix_evict_watermark")
        try:
            # 8-block pool, 0.25 watermark: at most 2 blocks may stay
            _flags.set_flag("serving_prefix_evict_watermark", "0.25")
            for sid in range(1, 5):
                toks = list(range(100 * sid, 100 * sid + 8))
                _commit_chain(kv, tree, sid, toks)
            assert kv.used_ratio() <= 0.25
            assert tree.snapshot()["evicted_blocks"] > 0
        finally:
            _flags.set_flag("serving_prefix_evict_watermark", str(old))
        tree.clear()
        kv.assert_idle()

    def test_kill_switch_flag_bypasses_the_tree(self):
        kv, tree = self._tree()
        toks = list(range(1, 17))
        old = _flags.get("serving_prefix_cache_enabled")
        try:
            _flags.set_flag("serving_prefix_cache_enabled", False)
            kv.alloc_sequence(1, 16)
            assert tree.commit(1, toks, 16) == 0
            kv.free_sequence(1)
            assert tree.fork(2, toks + [0]) == 0
            assert tree.snapshot()["enabled"] is False
            kv.assert_idle("disabled tree takes no holds")
        finally:
            _flags.set_flag("serving_prefix_cache_enabled", old)

    def test_evict_fault_point_is_registered(self):
        points = {p["point"] for p in fault.snapshot()}
        assert "serving.prefix.evict" in points


# ------------------------------------------- the leaf index against the walk
class _WalkingPrefixCache(PrefixCache):
    """The plain reference: eviction as it was before the leaf index (PR
    26), a depth-first walk of the whole tree for every evicted block and
    ``min`` over the refcount-1 leaves it finds. It keeps no index."""

    def _index_leaf_locked(self, node):
        pass

    def _unindex_leaf_locked(self, node):
        pass

    def _evictable_leaves_locked(self):
        out = []
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            elif self.kv.block_ref(n.block) == 1:
                out.append(n)
        return out

    def _evict_locked(self, nblocks):
        evicted = 0
        while evicted < nblocks:
            leaves = self._evictable_leaves_locked()
            if not leaves:
                break
            victim = min(leaves, key=lambda n: n.stamp)
            del victim.parent.children[victim.key]
            self._nodes -= 1
            self.kv.release_block(victim.block)
            evicted += 1
        if evicted:
            self.evicted_blocks += evicted
        return evicted


def _log_releases(pool, log, shard=0):
    """Every tree hold ``pool`` drops from here on, in order."""
    inner = pool.release_block

    def release_block(block):
        log.append((shard, block))
        return inner(block)

    pool.release_block = release_block


def _tree_leaves(tree):
    """The tree's leaves by a walk, oldest first (what the index holds)."""
    out, stack = [], list(tree._root.children.values())
    while stack:
        n = stack.pop()
        stack.extend(n.children.values())
        if not n.children:
            out.append(n)
    return sorted(out, key=lambda n: n.stamp)


def _assert_index_whole(tree):
    leaves = _tree_leaves(tree)
    assert tree._leaf_stamps == [n.stamp for n in leaves]
    assert [tree._leaf_of[s] for s in tree._leaf_stamps] == leaves


def _counters(snap):
    """A snapshot without ``evict_scanned``, the one counter that tells
    the index from the walk."""
    out = {k: v for k, v in snap.items() if k != "evict_scanned"}
    if "shards" in out:
        out["shards"] = [_counters(s) for s in out["shards"]]
    return out


def _drive(kv, cache, seed, steps, bs, whole=()):
    """One random interleaving of what an engine does to its prefix cache
    (admission that may evict, fork or cold allocation, commit, abort)
    plus the eviction fault, a bare ``evict_for_admission`` and ``clear``,
    drawn from ``seed`` alone: two pools that evict alike see the same
    calls. Prompts share blocks level by level, so the tree branches and
    chains are forked while they are cached. Ends with every sequence
    finished; returns the snapshot before the last ``clear``."""
    rng = random.Random(seed)
    live, ids = [], itertools.count(1)

    def prompt():
        toks = []
        for level in range(rng.randint(1, 9)):
            toks += [10 * level + min(int(rng.expovariate(1.0)), 4)] * bs
        return toks + [7] * rng.randint(1, bs)

    def request():
        sid, toks = next(ids), prompt()
        shard = cache.route_shard(toks)
        need = len(toks) - cache.match_len(toks)
        if not kv.can_admit(need, route_key=sid, shard=shard) \
                and not cache.evict_for_admission(need, shard=shard,
                                                  route_key=sid):
            return
        try:
            if cache.fork(sid, toks):
                kv.extend_sequence(sid, len(toks))
            elif shard is not None:
                kv.alloc_sequence(sid, len(toks), shard=shard)
            else:
                kv.alloc_sequence(sid, len(toks))
        except KVCacheFull:
            kv.free_sequence(sid)
            return
        live.append((sid, toks))

    def finish(commit):
        sid, toks = live.pop(rng.randrange(len(live)))
        if commit:
            cache.commit(sid, toks, len(toks))
        kv.free_sequence(sid)

    for _ in range(steps):
        op = rng.random()
        if op < 0.50 or not live:
            request()
        elif op < 0.85:
            finish(commit=op < 0.80)
        elif op < 0.92:
            fault.arm("serving.prefix.evict", mode="oneshot",
                      blocks=rng.randint(1, 6))
            request()
            fault.disarm_all()
        elif op < 0.99:
            cache.evict_for_admission(bs * rng.randint(1, 40))
        else:
            cache.clear()
        for tree in whole:
            _assert_index_whole(tree)
    while live:
        finish(commit=True)
    snap = cache.snapshot()
    cache.clear()
    kv.assert_idle("after the drive's clear")
    return snap


def _ledger(num_blocks, bs):
    kv = PagedKVCache(KVCacheConfig(block_size=bs, num_blocks=num_blocks),
                      1, 8, device_pools=False)
    kv._check = True
    return kv


class TestLeafIndexAgainstTheWalk:
    @pytest.mark.parametrize("num_blocks", [12, 40, 160])
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_same_victims_in_the_same_order(self, seed, num_blocks,
                                            fault_enabled):
        bs, steps = 4, 1200
        logs, snaps = [], []
        for cls in (PrefixCache, _WalkingPrefixCache):
            kv, log = _ledger(num_blocks, bs), []
            _log_releases(kv, log)
            tree = cls(kv)
            snaps.append(_drive(kv, tree, seed, steps, bs,
                                whole=[tree] if cls is PrefixCache else []))
            logs.append(log)
        assert logs[0] == logs[1]
        assert _counters(snaps[0]) == _counters(snaps[1])
        assert snaps[0]["evicted_blocks"] > 5 * num_blocks  # it did churn
        # leaves were passed over while a forked sequence shared them
        assert snaps[0]["evict_scanned"] > snaps[0]["evicted_blocks"]

    def test_same_victims_through_the_sharded_cache(self, fault_enabled):
        bs, logs, snaps = 4, [], []
        for cls in (PrefixCache, _WalkingPrefixCache):
            kv = ShardedKVCache(KVCacheConfig(block_size=bs, num_blocks=24),
                                1, 8)
            kv._check = True
            try:
                log = []
                for i, pool in enumerate(kv.pools):
                    _log_releases(pool, log, shard=i)
                spc = ShardedPrefixCache(kv)
                spc.trees = [cls(pool, shard=i)
                             for i, pool in enumerate(kv.pools)]
                snaps.append(_drive(kv, spc, 5, 1500, bs))
                logs.append(log)
            finally:
                kv.close()
        assert logs[0] == logs[1]
        assert _counters(snaps[0]) == _counters(snaps[1])
        assert {shard for shard, _b in logs[0]} == {0, 1}
        assert snaps[0]["evict_scanned"] == sum(
            s["evict_scanned"] for s in snaps[0]["shards"]) > 0

    def test_an_eviction_costs_in_leaves_not_in_blocks(self):
        """The engine's finish path (commit, then free) on the benchmark's
        pool and prompt lengths, nothing shared: the cost of choosing a
        victim is counted in leaves examined, so no clock is read."""
        rng = random.Random(27)
        kv = PagedKVCache(KVCacheConfig(block_size=16, num_blocks=2048),
                          1, 8, device_pools=False)
        tree = PrefixCache(kv)
        mark = float(_flags.get("serving_prefix_evict_watermark"))

        def finish_one(sid):
            n = rng.randint(384, 1536)
            _commit_chain(kv, tree, sid,
                          [rng.randrange(1, 49152) for _ in range(n)])

        sid = 0
        while not tree.evicted_blocks:   # fill the pool to the watermark
            sid += 1
            finish_one(sid)
        before = tree.snapshot()
        for _ in range(50):
            sid += 1
            finish_one(sid)
        after = tree.snapshot()
        chains = len(_tree_leaves(tree))
        assert after["nodes"] > 1500 and 20 < chains < 40
        assert kv.used_ratio() <= mark
        evicted = after["evicted_blocks"] - before["evicted_blocks"]
        scanned = after["evict_scanned"] - before["evict_scanned"]
        assert evicted > 2000
        assert scanned / evicted < chains + 1    # the walk: about 1640
        assert scanned == evicted   # ordered, nothing shared: one each
        tree.clear()
        kv.assert_idle()



# --------------------------------------------------- prefix-hash routing
class TestPrefixRouting:
    def test_route_key_none_below_one_block_plus_suffix(self):
        assert prefix_route_key(list(range(16)), 16) is None
        assert prefix_route_key(list(range(17)), 16) is not None

    def test_route_key_depends_only_on_the_first_block(self):
        a = list(range(1, 40))
        b = a[:16] + [9] * 30
        assert prefix_route_key(a, 16) == prefix_route_key(b, 16)
        c = [2] + a[1:]
        assert prefix_route_key(c, 16) != prefix_route_key(a, 16)

    def test_client_and_server_place_the_same_shard(self):
        """The fleet contract: the client stub's GenerateRouter and the
        server's ShardedPrefixCache admission compute the SAME shard for
        a prompt, so same-prefix traffic lands where the chain lives."""
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.serving.router import (GenerateRouter,
                                             generate_route_key)

        kv = ShardedKVCache(KVCacheConfig(block_size=16, num_blocks=64),
                            1, 8)
        try:
            spc = ShardedPrefixCache(kv)
            router = GenerateRouter(kv.n_shards, block_size=16)
            placed = set()
            for seed in range(12):
                toks = [seed * 31 + i for i in range(20)]
                req = serving_pb2.GenerateRequest(prompt_tokens=toks)
                client = shard_for(router.route_key(req), kv.n_shards)
                assert client == spc.route_shard(toks)
                placed.add(client)
            assert placed == {0, 1}  # the hash actually spreads
            # short prompts fall back to whole-prompt routing
            short = serving_pb2.GenerateRequest(prompt_tokens=[1, 2, 3])
            assert router.route_key(short) == generate_route_key(short)
            assert spc.route_shard([1, 2, 3]) is None
        finally:
            kv.close()

    def test_sharded_fork_pins_the_sequence_to_the_chain_shard(self):
        kv = ShardedKVCache(KVCacheConfig(block_size=16, num_blocks=64),
                            1, 8)
        kv._check = True
        try:
            spc = ShardedPrefixCache(kv)
            toks = list(range(1, 33))  # 2 full blocks
            shard = spc.route_shard(toks + [0])
            assert shard is not None
            # build the chain where routing says it lives
            kv.alloc_sequence(101, 32, shard=shard)
            assert spc.commit(101, toks, 32) == 2
            kv.free_sequence(101)
            assert spc.match_len(toks + [0]) == 32
            assert spc.fork(202, toks + [0]) == 32
            # the fork pinned the sequence onto the chain's shard
            assert kv.block_table(202).shard == shard
            kv.free_sequence(202)
            assert spc.clear() == 2
            kv.assert_idle("sharded teardown")
        finally:
            kv.close()


# --------------------------------------------------------- engine wiring
class TestEngineWiring:
    def test_stub_models_get_no_prefix_cache(self):
        # no prefill_suffix on the model: the engine must not auto-build
        model = types.SimpleNamespace(
            config=types.SimpleNamespace(max_context=4096))
        eng = ServingEngine(model, _kv(), EngineConfig())
        assert eng.prefix is None

    def test_build_prefix_cache_dispatches_on_pool_type(self):
        assert isinstance(build_prefix_cache(_kv()), PrefixCache)
        skv = ShardedKVCache(KVCacheConfig(block_size=16, num_blocks=32),
                             1, 8)
        try:
            assert isinstance(build_prefix_cache(skv), ShardedPrefixCache)
        finally:
            skv.close()

    def test_thrash_watch_rule_installed_with_reloadable_bound(self):
        from brpc_tpu.metrics.watch import (KIND_RATE, global_watch,
                                            install_default_rules)

        install_default_rules()
        rules = {r.name: r for r in global_watch().rules()}
        rule = rules.get("serving_prefix_thrash")
        assert rule is not None, sorted(rules)
        assert rule.var == "g_serving_prefix_evicted_blocks"
        assert rule.kind == KIND_RATE
        assert rule.bound() == _flags.get("serving_prefix_thrash_rate")
        old = _flags.get("serving_prefix_thrash_rate")
        try:
            _flags.set_flag("serving_prefix_thrash_rate", "5")
            assert rule.bound() == 5.0
        finally:
            _flags.set_flag("serving_prefix_thrash_rate", str(old))


# ------------------------------------------------- real model: the oracle
MODEL_CFG = dict(vocab=256, d_model=32, n_heads=2, n_layers=2)


@pytest.fixture(scope="module")
def stack():
    """One compiled TinyTransformer + armed pool for the module; engines
    are per-run (the jit cache in the model is the expensive part)."""
    cfg = ModelConfig(**MODEL_CFG)
    kv = PagedKVCache(KVCacheConfig(block_size=16, num_blocks=256),
                      cfg.n_layers, cfg.kv_dim)
    kv._check = True  # armed ledger throughout
    model = TinyTransformer(cfg, kv)
    yield model, kv
    model.close()


def _run_schedule(model, kv, schedule, prefix_cache=None):
    """Drive one engine through the schedule; returns (token lists in
    submit order, final engine snapshot)."""
    engine = ServingEngine(model, kv, EngineConfig(
        max_batch=8, token_budget=512, idle_wait_s=0.002),
        prefix_cache=prefix_cache).start()
    try:
        evs, seqs = [], []
        for plen, max_new in schedule:
            ev = threading.Event()
            code, seq = engine.submit(model.synth_prompt(plen), max_new,
                                      done=lambda _r, ev=ev: ev.set())
            assert code == 0, f"submit rejected: {code}"
            evs.append(ev)
            seqs.append(seq)
        for ev in evs:
            assert ev.wait(300), "schedule run stalled"
        snap = engine.snapshot()
        return [list(s.out_tokens) for s in seqs], snap
    finally:
        engine.stop()


@pytest.fixture(scope="module")
def cold_reference(stack):
    """Cold-start outputs on the committed corpus schedule, from an
    engine with the prefix cache explicitly disabled."""
    model, kv = stack
    out, snap = _run_schedule(model, kv, SCHEDULE, prefix_cache=False)
    assert snap["prefix"] is None
    kv.assert_idle("cold reference teardown")
    return out


class TestForkOracle:
    def test_warm_outputs_bit_identical_to_cold(self, stack,
                                                cold_reference):
        """The acceptance oracle: generations that fork cached prefix
        chains are list-equal to cold-start on the committed corpus
        schedule — copy-on-write means a shared block is never mutated,
        so reuse cannot perturb a single logit."""
        model, kv = stack
        warm, snap = _run_schedule(model, kv, SCHEDULE * 2)
        assert warm == cold_reference * 2
        pfx = snap["prefix"]
        assert pfx["hit_seqs"] > 0 and pfx["hit_blocks"] > 0, pfx
        assert pfx["inserted_blocks"] > 0
        assert 0 < pfx["hit_ratio"] <= 1
        kv.assert_idle("oracle teardown")  # stop() cleared every hold

    def test_serving_builtin_reports_the_prefix_section(self, stack):
        import json as _json

        from brpc_tpu.builtin.services import serving_service

        model, kv = stack
        engine = ServingEngine(model, kv, EngineConfig(
            max_batch=8, token_budget=512, idle_wait_s=0.002)).start()
        try:
            evs = []
            for plen, max_new in SCHEDULE[:4]:
                ev = threading.Event()
                code, _ = engine.submit(model.synth_prompt(plen), max_new,
                                        done=lambda _r, ev=ev: ev.set())
                assert code == 0
                evs.append(ev)
            for ev in evs:
                assert ev.wait(300)
            status, _ctype, body = serving_service(
                None, types.SimpleNamespace(query={"format": "json"},
                                            path="/serving"))
            assert status == 200
            snap = _json.loads(body)["engines"][-1]
            assert snap["prefix"]["enabled"]
            assert snap["prefix"]["inserted_blocks"] > 0
            assert snap["kv"]["blocks_cached"] > 0
            status, _ctype, text = serving_service(
                None, types.SimpleNamespace(query={}, path="/serving"))
            assert status == 200
            assert "prefix: nodes=" in text and "hit_ratio=" in text
        finally:
            engine.stop()
        kv.assert_idle("builtin teardown")


@pytest.mark.parametrize("mesh", [False, True], ids=["single", "mesh"])
def test_a_warm_request_costs_its_suffix_and_one_launch(mesh, stack,
                                                        monkeypatch):
    """What a hit saves, in counts: over a corpus of one shared prompt with
    a tail token of each request's own, every request after the first
    prefills its suffix alone, in ONE ``prefill_suffix`` launch and no
    ``prefill``; the hit ratio is (n - 1) / n."""
    from brpc_tpu.tpu.device_lane import DispatchCounter, step_dispatch

    if mesh:
        from brpc_tpu.serving import MeshTransformer

        cfg = ModelConfig(**MODEL_CFG)
        kv = ShardedKVCache(KVCacheConfig(block_size=16, num_blocks=256),
                            cfg.n_layers, cfg.kv_dim)
        kv._check = True
        model = MeshTransformer(cfg, kv)
    else:
        model, kv = stack
    n, shared = 5, 4 * 16           # four whole blocks, then one own token
    calls = collections.Counter()
    for name in ("prefill", "prefill_suffix"):
        def counted(*args, name=name, orig=getattr(model, name)):
            calls[name] += 1
            return orig(*args)
        monkeypatch.setattr(model, name, counted)
    engine = ServingEngine(model, kv, EngineConfig(
        max_batch=4, token_budget=512, idle_wait_s=0.002)).start()
    try:
        base = model.synth_prompt(shared + 1)
        for i in range(n):
            prompt = base.copy()
            prompt[-1] = 1 + (7 * i + 3) % (MODEL_CFG["vocab"] - 1)
            calls.clear()
            tokens0 = engine.prefill_tokens
            before = step_dispatch.snapshot()
            ev = threading.Event()
            code, _ = engine.submit(prompt, 1, done=lambda _r: ev.set())
            assert code == 0 and ev.wait(300)
            launches, _ops, syncs = DispatchCounter.delta(
                before, step_dispatch.snapshot())
            if i == 0:
                assert dict(calls) == {"prefill": 1}
                assert engine.prefill_tokens - tokens0 == shared + 1
            else:
                assert dict(calls) == {"prefill_suffix": 1}
                assert engine.prefill_tokens - tokens0 == 1
                assert (launches, syncs) == (1, 1)
        pfx = engine.snapshot()["prefix"]
        assert (pfx["hit_seqs"], pfx["miss_seqs"]) == (n - 1, 1)
        assert pfx["hit_ratio"] == (n - 1) / n
        assert pfx["hit_blocks"] == (n - 1) * shared // 16
    finally:
        engine.stop()
        kv.assert_idle("warm-request teardown")
        if mesh:
            model.close()


# ------------------------------------------------------------------ chaos
@pytest.fixture()
def fault_enabled():
    _flags.set_flag("fault_injection_enabled", True)
    yield
    fault.disarm_all()
    _flags.set_flag("fault_injection_enabled", False)


@pytest.mark.chaos
class TestPrefixChaos:
    def test_eviction_churn_keeps_outputs_and_pool_whole(
            self, stack, cold_reference, fault_enabled):
        """Chaos: every admission force-evicts radix chains
        (serving.prefix.evict armed always) while the corpus schedule
        runs warm. Outputs stay bit-identical to cold-start, the armed
        ledger's per-mutation audits hold throughout, and after stop()
        the pool is whole — zero leaked blocks, zero lingering holds."""
        model, kv = stack
        fault.arm("serving.prefix.evict", mode="always", blocks=2)
        try:
            # two passes: the first populates the tree so the second's
            # admissions actually have chains to churn out from under
            churned, snap = _run_schedule(model, kv, SCHEDULE * 2)
        finally:
            fault.disarm_all()
        assert churned == cold_reference * 2
        assert snap["prefix"]["evicted_blocks"] > 0, snap["prefix"]
        kv.assert_idle("post eviction churn")
