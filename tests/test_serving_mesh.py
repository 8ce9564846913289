"""Mesh-sharded serving plane (brpc_tpu/serving/mesh_model.py, router.py,
ShardedKVCache): CPU-sim equivalence, the per-step dispatch invariant,
routing stability, and the sharded failure contract.

tests/conftest.py forces 8 virtual CPU devices, so the serving mesh here
is the REAL dp=2/sp=2/tp=2 split the multichip dryrun proves — not a
degenerate 1x1x1. Greedy decode is deterministic, so "sharded output ==
single-device output" is an exact list equality, not a tolerance check.
"""

import collections
import threading
import time
import types

import numpy as np
import pytest

from brpc_tpu.proto import serving_pb2
from brpc_tpu.rpc import Channel, ChannelOptions, Controller, RpcError, \
    Server, errors
from brpc_tpu.serving import (EngineConfig, KVCacheConfig, ModelConfig,
                              MeshTransformer, PagedKVCache, ServingEngine,
                              ShardedKVCache, ShardedLlmChannel,
                              TinyTransformer)
from brpc_tpu.rpc.combo_channels import SKIP
from brpc_tpu.serving.router import (GENERATE_MD, STATS_MD, GenerateRouter,
                                     StatsMerger, generate_route_key)
from brpc_tpu.serving.service import LlmServingService
from brpc_tpu.shard.plane import shard_for
from brpc_tpu.tpu.device_lane import DispatchCounter, step_dispatch

# the committed replay corpus's schedule (prompts synthesized from length
# alone, greedy argmax decode -> bit-replayable token streams)
from tools.record_serving_corpus import SCHEDULE

CFG = dict(vocab=256, d_model=32, n_heads=2, n_layers=2)


def _run_schedule(model, kv, schedule):
    """Drive one engine through the corpus schedule; returns each
    sequence's greedy token list in submit order."""
    engine = ServingEngine(model, kv, EngineConfig(
        max_batch=8, token_budget=512, idle_wait_s=0.002)).start()
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
        return [list(s.out_tokens) for s in seqs]
    finally:
        engine.stop()


@pytest.fixture(scope="module")
def mesh_stack():
    """One MeshTransformer + armed ShardedKVCache shared by the module
    (the mesh jit cache is the expensive part; engines are per-test)."""
    cfg = ModelConfig(**CFG)
    kv = ShardedKVCache(KVCacheConfig(block_size=16, num_blocks=256),
                        cfg.n_layers, cfg.kv_dim)
    kv._check = True  # armed ledger: per-pool accounting + engine audit
    model = MeshTransformer(cfg, kv)
    yield cfg, model, kv
    model.close()


def _refill_every_step(lengths, max_batch):
    """(steps, decode rows) a FIFO queue of answer lengths costs when every
    step first refills the free rows: an admitted sequence's prefill yields
    its first token, then every live row decodes one."""
    waiting, running = collections.deque(lengths), []
    steps = rows = 0
    while waiting or running:
        while waiting and len(running) < max_batch:
            running.append(waiting.popleft() - 1)        # its prefill
        running = [n for n in running if n > 0]
        rows += len(running)
        running = [n - 1 for n in running if n > 1]      # the decode step
        steps += 1
    return steps, rows


@pytest.mark.parametrize("stack", ["single", "mesh"])
def test_mixed_corpus_drains_in_the_steps_its_lengths_predict(stack,
                                                              request):
    """Three short answers to one long: the engine refills freed rows
    between decode steps, so the corpus costs the steps the lengths
    predict and no more. A batch that drained before it refilled would
    take ``sum(max(gang) - 1)`` steps, 1.5 times as many and more."""
    lengths = [64 if i % 4 == 3 else 4 for i in range(16)]
    max_batch = 4
    if stack == "mesh":
        cfg, model, kv = request.getfixturevalue("mesh_stack")
    else:
        cfg = ModelConfig(**CFG)
        kv = PagedKVCache(KVCacheConfig(block_size=16, num_blocks=256),
                          cfg.n_layers, cfg.kv_dim)
        kv._check = True
        model = TinyTransformer(cfg, kv)
    engine = ServingEngine(model, kv, EngineConfig(
        max_batch=max_batch, token_budget=256, idle_wait_s=0.002),
        prefix_cache=False).start()
    try:
        evs = []
        # the loop thread admits under this lock: held here, the whole
        # corpus is queued before the first step, whatever the threads do
        with engine._cv:
            for n in lengths:
                ev = threading.Event()
                code, _ = engine.submit(model.synth_prompt(16), n,
                                        done=lambda _r, ev=ev: ev.set())
                assert code == 0
                evs.append(ev)
        for ev in evs:
            assert ev.wait(300), "corpus run stalled"
        snap = engine.snapshot()
    finally:
        engine.stop()
        kv.assert_idle()
        if stack == "single":
            model.close()
    steps, rows = _refill_every_step(lengths, max_batch)
    assert snap["tokens_generated"] == sum(lengths)
    assert snap["steps"] == steps
    assert snap["batch_occupancy_avg"] == round(rows / steps, 3)
    gangs = [lengths[i:i + max_batch]
             for i in range(0, len(lengths), max_batch)]
    assert sum(max(g) - 1 for g in gangs) >= 1.5 * steps


class TestMeshEquivalence:
    def test_mesh_is_dp2_sp2_tp2(self, mesh_stack):
        _, model, kv = mesh_stack
        assert kv.n_shards == 2
        assert dict(model.mesh.shape) == {"dp": 2, "sp": 2, "tp": 2}

    def test_corpus_schedule_tokens_identical_to_single_device(
            self, mesh_stack):
        """The acceptance gate: the sharded stack must produce the SAME
        greedy tokens as the single-device stack on the committed corpus
        schedule — bit-exact lowering, not approximately-equal serving."""
        cfg, model, kv = mesh_stack
        ref_kv = PagedKVCache(KVCacheConfig(block_size=16, num_blocks=256),
                              cfg.n_layers, cfg.kv_dim)
        ref_model = TinyTransformer(ModelConfig(**CFG), ref_kv)
        try:
            ref = _run_schedule(ref_model, ref_kv, SCHEDULE)
        finally:
            ref_model.close()
        got = _run_schedule(model, kv, SCHEDULE)
        assert got == ref
        kv.assert_idle()

    @pytest.mark.parametrize("plen", [5, 20, 40, 150])
    def test_flash_prefill_token_identical_to_single_device(self, plen):
        """Through the kernel (``attn="flash"``, interpreted here): the
        mesh prefill hands the rows-first call ITS heads (tp=2 of 4, packed
        again) and the single-device one all four; the first token and
        what follows must not differ by a bit of an argmax."""
        cfg = ModelConfig(vocab=256, d_model=64, n_heads=4, n_layers=2,
                          attn="flash")
        kvc = KVCacheConfig(block_size=16, num_blocks=64)
        kv = ShardedKVCache(kvc, cfg.n_layers, cfg.kv_dim)
        model = MeshTransformer(cfg, kv)
        ref_kv = PagedKVCache(kvc, cfg.n_layers, cfg.kv_dim)
        ref_model = TinyTransformer(cfg, ref_kv)
        try:
            assert model.tp == 2
            got = _run_schedule(model, kv, [(plen, 3)])
            ref = _run_schedule(ref_model, ref_kv, [(plen, 3)])
        finally:
            model.close()
            ref_model.close()
        assert got == ref and len(got[0]) == 3

    def test_dispatch_invariant_one_launch_one_sync_per_step(
            self, mesh_stack):
        """Every decode step costs exactly ONE fused program launch and
        ONE host materialization — the coalescing contract the whole PR
        rides, asserted from OUTSIDE the engine (the engine also asserts
        it internally per step because kv._check is armed)."""
        _, model, kv = mesh_stack
        orig = model.decode_step
        deltas = []

        def audited(tokens, positions, tables):
            before = step_dispatch.snapshot()
            out = orig(tokens, positions, tables)
            deltas.append(DispatchCounter.delta(
                before, step_dispatch.snapshot()))
            return out

        model.decode_step = audited
        try:
            _run_schedule(model, kv, SCHEDULE[:6])
        finally:
            model.decode_step = orig
        assert deltas, "no decode steps ran"
        assert all((launches, syncs) == (1, 1)
                   for launches, _ops, syncs in deltas), deltas
        kv.assert_idle()

    def test_serving_builtin_reports_per_shard_occupancy(self, mesh_stack):
        """/serving (text + ?format=json) must expose the per-device view:
        per-shard occupancy, the block-table shard map, and per-shard
        step latency."""
        import json as _json

        from brpc_tpu.builtin.services import serving_service

        _, model, kv = mesh_stack
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
            assert snap["kv"]["n_shards"] == 2
            shards = snap["kv"]["shards"]
            assert [s["shard"] for s in shards] == [0, 1]
            assert all(s["blocks_total"] > 0 and s["devices"]
                       for s in shards)
            # every completed sequence freed its blocks again; what stays
            # used is exactly the prefix cache's committed chains
            assert all(s["blocks_used"] == s["blocks_cached"]
                       for s in shards)
            assert "shard_steps" in snap and snap["shard_steps"]
            status, _ctype, text = serving_service(
                None, types.SimpleNamespace(query={}, path="/serving"))
            assert status == 200
            assert "sharded: n_shards=2" in text
            assert "[shard 0]" in text and "[shard 1]" in text
        finally:
            engine.stop()
        kv.assert_idle()


class TestShardSkewWatchRule:
    def test_rule_installed_with_reloadable_bound(self):
        from brpc_tpu import flags as _flags
        from brpc_tpu.metrics.watch import (WatchRule, global_watch,
                                            install_default_rules)

        install_default_rules()
        rules = {r.name: r for r in global_watch().rules()}
        rule = rules.get("serving_shard_skew")
        assert rule is not None, sorted(rules)
        assert rule.var == "g_serving_kv_shard_skew"
        # the bound re-reads the flag every tick: /flags?setvalue=
        # retunes the live rule without re-installing it
        assert rule.bound() == _flags.get("serving_shard_skew_ratio")
        old = _flags.get("serving_shard_skew_ratio")
        try:
            _flags.set_flag("serving_shard_skew_ratio", "0.5")
            assert rule.bound() == 0.5
            assert "0.5" in rule.condition()
        finally:
            _flags.set_flag("serving_shard_skew_ratio", str(old))
        assert rule.bound() == old

    def test_value_fn_failure_falls_back_to_static_bound(self):
        from brpc_tpu.metrics.watch import KIND_THRESHOLD, WatchRule

        boom = WatchRule("t_boom", "v", KIND_THRESHOLD, ">", 0.25,
                         value_fn=lambda: (_ for _ in ()).throw(
                             RuntimeError("flag gone")))
        assert boom.bound() == 0.25

    def test_skew_gauge_tracks_unbalanced_pools(self):
        from brpc_tpu.serving.kv_cache import _fleet_skew

        kv = ShardedKVCache(KVCacheConfig(block_size=16, num_blocks=32),
                            1, 8)
        try:
            assert _fleet_skew() == 0.0  # idle fleet: balanced
            # pin blocks onto ONE shard: seq ids chosen so shard_of lands
            # on shard 0 every time
            sids = [s for s in range(1, 200) if kv.shard_of(s) == 0][:4]
            for s in sids:
                kv.alloc_sequence(s, 64)
            assert _fleet_skew() > 0.2
            for s in sids:
                kv.free_sequence(s)
            assert _fleet_skew() == 0.0
        finally:
            kv.close()


class TestRoutingStability:
    def test_versioned_cid_reuse_spreads_across_shards(self):
        """VersionedPool reuses slot 0 with only the high-bits version
        advancing, so real cids look like ``version << 32`` — exactly the
        pattern a truncating hash pins to shard 0. The splitmix64 spread
        must still balance them, and stay deterministic."""
        cids = [(v << 32) for v in range(1, 129)]
        shards = [shard_for(c, 2) for c in cids]
        assert set(shards) == {0, 1}
        share = sum(shards) / len(shards)
        assert 0.3 < share < 0.7, f"skewed spread: {share}"
        assert [shard_for(c, 2) for c in cids] == shards  # stable

    def test_block_table_routing_stable_under_cid_reuse(self):
        """Alloc/free cycles with VersionedPool-shaped seq ids: the block
        table's shard must equal shard_of(seq_id) every time, including
        when a reused id comes back — and nothing leaks."""
        kv = ShardedKVCache(KVCacheConfig(block_size=16, num_blocks=32),
                            1, 8)
        try:
            seen = set()
            for v in range(1, 41):
                cid = v << 32
                table = kv.alloc_sequence(cid, 20)
                assert table.shard == kv.shard_of(cid)
                assert kv.block_table(cid).shard == table.shard
                seen.add(table.shard)
                kv.free_sequence(cid)
                # the SAME cid re-allocated lands on the SAME shard
                again = kv.alloc_sequence(cid, 20)
                assert again.shard == table.shard
                kv.free_sequence(cid)
            assert seen == {0, 1}
            kv.assert_idle()
        finally:
            kv.close()


class TestGenerateRouter:
    def test_generate_maps_to_single_owner_partition(self):
        req = serving_pb2.GenerateRequest(prompt_tokens=[3, 1, 4, 1, 5])
        for n in (2, 4):
            router = GenerateRouter(n)
            decisions = [router.map(i, GENERATE_MD, req, None)
                         for i in range(n)]
            live = [i for i, d in enumerate(decisions) if d is not SKIP]
            assert live == [shard_for(generate_route_key(req), n)]

    def test_stats_fans_out_to_every_partition(self):
        router = GenerateRouter(4)
        req = serving_pb2.ServingStatsRequest()
        decisions = [router.map(i, STATS_MD, req, None) for i in range(4)]
        assert all(d is not SKIP for d in decisions)

    def test_route_key_deterministic_and_prompt_dependent(self):
        a = serving_pb2.GenerateRequest(prompt_tokens=[1, 2, 3])
        b = serving_pb2.GenerateRequest(prompt_tokens=[1, 2, 4])
        assert generate_route_key(a) == generate_route_key(a)
        assert generate_route_key(a) != generate_route_key(b)
        # synth-prompt requests route on prompt_len
        c = serving_pb2.GenerateRequest(prompt_len=16)
        d = serving_pb2.GenerateRequest(prompt_len=32)
        assert generate_route_key(c) != generate_route_key(d)

    def test_stats_merger_sums_shard_gauges(self):
        merger = StatsMerger()
        total = serving_pb2.ServingStats()
        for used in (3, 5):
            sub = serving_pb2.ServingStats(
                seqs_running=1, seqs_waiting=2, kv_blocks_total=128,
                kv_blocks_used=used, steps=10, tokens_generated=40)
            assert merger.merge(total, sub) == merger.MERGED
        assert total.kv_blocks_total == 256
        assert total.kv_blocks_used == 8
        assert total.seqs_running == 2 and total.tokens_generated == 80


class TestShardedGenerateChaos:
    def _fleet(self, n_layers=4):
        """n=2 shard-per-server fleet, each engine over its own ARMED
        paged pool (the deployment the router's i/n tags name)."""
        fleet = []
        for _ in range(2):
            cfg = ModelConfig(vocab=256, d_model=32, n_heads=2,
                              n_layers=n_layers)
            kv = PagedKVCache(KVCacheConfig(block_size=16, num_blocks=64),
                              cfg.n_layers, cfg.kv_dim)
            kv._check = True
            model = TinyTransformer(cfg, kv)
            engine = ServingEngine(model, kv, EngineConfig(
                max_batch=4, token_budget=256, idle_wait_s=0.002)).start()
            srv = Server().add_service(
                LlmServingService(engine)).start("127.0.0.1:0")
            fleet.append((srv, engine, model, kv))
        return fleet

    def test_shard_death_mid_generate_is_retriable_and_leak_free(self):
        """Chaos: the owning shard's server dies mid-Generate. The caller
        must see retriable EFAILEDSOCKET naming the shard (NOT the
        parallel-channel ETOOMANYFAILS verdict), and under the armed
        ledger every device-local block the doomed sequence held must
        come back — zero leaks."""
        fleet = self._fleet()
        try:
            url = (f"list://{fleet[0][0].listen_endpoint()} 0/2,"
                   f"{fleet[1][0].listen_endpoint()} 1/2")
            ch = ShardedLlmChannel(
                url, 2, options=ChannelOptions(protocol="trpc_std",
                                               timeout_ms=60000))
            req = serving_pb2.GenerateRequest(prompt_len=16,
                                              max_new_tokens=200)
            owner = ch.shard_of(req)
            # same route key as the chaos request (prompt_len routes), so
            # this warms the OWNER engine's jit buckets: the chaos run's
            # timing is then decode-bound, not compile-bound
            warm = ch.generate(serving_pb2.GenerateRequest(
                prompt_len=16, max_new_tokens=4))
            assert len(warm.tokens) == 4
            def kill(srv=fleet[owner][0]):
                # stop() alone is graceful (in-flight finishes); process
                # death is stop + zero-deadline join, which force-closes
                # the live connections under the request
                srv.stop()
                srv.join(timeout=0)

            killer = threading.Timer(0.05, kill)
            killer.start()
            try:
                with pytest.raises(RpcError) as ei:
                    ch.generate(req)
            finally:
                killer.cancel()
            assert ei.value.error_code == errors.EFAILEDSOCKET
            assert "retriable" in str(ei.value)
            assert f"shard {owner}/2" in str(ei.value)
            # the OTHER shard never saw either call (partitioned routing,
            # not fan-out)
            other_engine = fleet[1 - owner][1]
            assert other_engine.tokens_generated == 0
        finally:
            for srv, engine, model, kv in fleet:
                srv.stop()
                srv.join(timeout=2)
                engine.stop()
                # the armed ledger proves the doomed sequence's blocks
                # were returned: any leak raises here
                kv.assert_idle()
                model.close()

    def test_owner_shard_death_with_warm_prefix_leaks_nothing(self):
        """Chaos x prefix cache: the owning shard dies mid-Generate while
        the doomed sequence is FORKED from a committed radix chain. The
        abort must return only the sequence's own holds — the tree's
        refcounts stay consistent under the armed ledger (any drift
        raises inside the engine's per-step audit), and after stop()
        clears the tree the pool is bit-for-bit whole."""
        fleet = self._fleet(n_layers=2)
        try:
            url = (f"list://{fleet[0][0].listen_endpoint()} 0/2,"
                   f"{fleet[1][0].listen_endpoint()} 1/2")
            ch = ShardedLlmChannel(
                url, 2, options=ChannelOptions(protocol="trpc_std",
                                               timeout_ms=60000))
            # block_size=16: a 48-token prompt commits 3 full blocks, so
            # the repeat warm pass (and the doomed request) fork 2 of them
            req = serving_pb2.GenerateRequest(prompt_len=48,
                                              max_new_tokens=200)
            owner = ch.shard_of(req)
            owner_engine = fleet[owner][1]
            warms = [ch.generate(serving_pb2.GenerateRequest(
                prompt_len=48, max_new_tokens=4)) for _ in range(2)]
            # the warm hit is bit-identical to the cold pass
            assert list(warms[0].tokens) == list(warms[1].tokens)
            pfx = owner_engine.snapshot()["prefix"]
            assert pfx["hit_seqs"] >= 1 and pfx["blocks"] > 0, pfx

            def kill(srv=fleet[owner][0]):
                srv.stop()
                srv.join(timeout=0)

            killer = threading.Timer(0.05, kill)
            killer.start()
            try:
                with pytest.raises(RpcError) as ei:
                    ch.generate(req)
            finally:
                killer.cancel()
            assert ei.value.error_code == errors.EFAILEDSOCKET
            deadline = time.monotonic() + 5.0
            while owner_engine.running_count and time.monotonic() < deadline:
                time.sleep(0.005)
            # the doomed fork's private blocks came back; exactly the
            # tree-held committed chains stay pinned
            snap = owner_engine.kv.snapshot()
            assert snap["blocks_cached"] > 0
            assert snap["blocks_used"] == snap["blocks_cached"]
        finally:
            for srv, engine, model, kv in fleet:
                srv.stop()
                srv.join(timeout=2)
                engine.stop()  # clears the radix tree's holds
                kv.assert_idle()  # zero leaked blocks, zero cache holds
                model.close()

    def test_fleet_stats_merge_across_shards(self):
        fleet = self._fleet(n_layers=2)
        try:
            url = (f"list://{fleet[0][0].listen_endpoint()} 0/2,"
                   f"{fleet[1][0].listen_endpoint()} 1/2")
            ch = ShardedLlmChannel(
                url, 2, options=ChannelOptions(protocol="trpc_std",
                                               timeout_ms=60000))
            # land one generation on EACH shard (prompt_len routes; 16
            # and 32 hash to different shards for n=2 — asserted, not
            # assumed)
            lens = {ch.shard_of(serving_pb2.GenerateRequest(prompt_len=L)):
                    L for L in (16, 32, 48, 64)}
            assert set(lens) == {0, 1}
            for L in lens.values():
                r = ch.generate(serving_pb2.GenerateRequest(
                    prompt_len=L, max_new_tokens=4))
                assert len(r.tokens) == 4
            stats = ch.stats()
            assert stats.tokens_generated == 8
            # fleet totals: both pools' capacity summed
            assert stats.kv_blocks_total == 2 * 64
            # in-flight work drained; only prefix-cache chains stay used
            cached = sum(e.kv.snapshot()["blocks_cached"]
                         for _s, e, _m, _k in fleet)
            assert stats.kv_blocks_used == cached
        finally:
            for srv, engine, model, kv in fleet:
                srv.stop()
                srv.join(timeout=2)
                engine.stop()
                kv.assert_idle()
                model.close()
