"""Disaggregated prefill/decode: KV block-chain migration over the
tpu:// record lane (brpc_tpu/serving/migration.py).

Four layers, cheapest first:

* the ledger's migration surface — quiesce/export/release-on-ACK on the
  source, adopt-from-staging on the destination, the export gate that
  refuses un-quiesced chains, and write-clears-quiesce semantics;
* the wire protocol — manifest validation (geometry, block_bytes,
  capacity), staging ownership for the whole transfer, and the
  commit-as-ACK contract;
* the disaggregated serving plane end to end — a prefill-role engine
  hands every just-prefilled chain to a decode-role engine over a real
  loopback server, the two-stage ShardedLlmChannel dispatch stitches the
  replies, and the migrated generation is BIT-IDENTICAL to a co-located
  run on the committed corpus schedule (zero re-prefilled tokens, both
  armed pools idle at teardown);
* chaos — serving.migrate.drop kills the destination tunnel
  mid-transfer (source retains the chain and decodes locally, zero
  leaked blocks on either pool), and shard death drains live sequences
  onto a survivor where the client's retry resumes without re-prefill.
"""

import threading
import time
import types

import numpy as np
import pytest

from brpc_tpu import fault
from brpc_tpu import flags as _flags
from brpc_tpu.proto import serving_pb2
from brpc_tpu.rpc import ChannelOptions, Server, errors
from brpc_tpu.serving import (EngineConfig, KVCacheConfig, ModelConfig,
                              PagedKVCache, ServingEngine,
                              ShardedLlmChannel, TinyTransformer)
from brpc_tpu.serving.migration import (KVMigrator, MigrationReceiver,
                                        chain_block_bytes,
                                        g_serving_migrate_failed,
                                        g_serving_migrate_seqs,
                                        read_chain_blocks,
                                        write_chain_blocks)
from brpc_tpu.serving.service import LlmServingService

# the committed replay corpus's schedule (synth prompts, greedy argmax
# decode -> bit-replayable token streams)
from tools.record_serving_corpus import SCHEDULE

CFG = dict(vocab=256, d_model=32, n_heads=2, n_layers=2)


def _kv(num_blocks=128, block_size=16, layers=2, kv_dim=16):
    kv = PagedKVCache(KVCacheConfig(block_size=block_size,
                                    num_blocks=num_blocks),
                      layers, kv_dim)
    kv._check = True  # armed ledger: audit every mutation
    return kv


def _build_engine(role="both", num_blocks=128):
    cfg = ModelConfig(**CFG)
    kv = PagedKVCache(KVCacheConfig(block_size=16, num_blocks=num_blocks),
                      cfg.n_layers, cfg.kv_dim)
    kv._check = True
    model = TinyTransformer(cfg, kv)
    engine = ServingEngine(model, kv, EngineConfig(
        max_batch=8, token_budget=512, idle_wait_s=0.002, role=role),
        prefix_cache=False).start()
    return engine, kv, model


def _teardown(engine, kv, model):
    engine.stop()
    kv.assert_idle()
    model.close()


def _submit(engine, prompt, max_new, resume=0, cntl=None):
    ev = threading.Event()
    box = {}
    code, seq = engine.submit(
        prompt, max_new, cntl=cntl,
        done=lambda r, box=box, ev=ev: (box.update(r=r), ev.set()),
        resume_seq_id=resume)
    return code, seq, ev, box


@pytest.fixture
def fault_enabled():
    _flags.set_flag("fault_injection_enabled", True)
    yield
    fault.disarm_all()
    _flags.set_flag("fault_injection_enabled", False)


# ------------------------------------------------- ledger migration surface
class TestLedgerMigrationSurface:
    def test_quiesce_export_release_roundtrip(self):
        kv = _kv()
        t = kv.alloc_sequence(1, 40)  # 3 blocks
        assert kv.quiesce_sequence(1) == 40
        table, ntokens = kv.export_chain(1)
        assert list(table) == list(t) and ntokens == 40
        assert kv.release_exported(1) == 3
        kv.assert_idle("after release_exported")

    def test_export_without_quiesce_refused(self):
        kv = _kv()
        kv.alloc_sequence(1, 16)
        with pytest.raises(AssertionError, match="without quiesce"):
            kv.export_chain(1)
        kv.free_sequence(1)
        kv.assert_idle()

    def test_write_clears_the_quiesce_mark(self):
        # any ledger write between quiesce and export re-arms the gate:
        # the exported table must be the table the destination adopts
        kv = _kv()
        kv.alloc_sequence(1, 16)
        kv.quiesce_sequence(1)
        kv.extend_sequence(1, 17)
        with pytest.raises(AssertionError, match="without quiesce"):
            kv.export_chain(1)
        kv.unquiesce_sequence(1)
        kv.free_sequence(1)
        kv.assert_idle()

    def test_unquiesce_restores_local_fallback(self):
        kv = _kv()
        kv.alloc_sequence(1, 16)
        kv.quiesce_sequence(1)
        kv.unquiesce_sequence(1)
        with pytest.raises(AssertionError):
            kv.export_chain(1)  # gate re-armed: not exportable
        kv.extend_sequence(1, 32)  # and the chain still grows locally
        kv.free_sequence(1)
        kv.assert_idle()

    def test_staging_adopt_handoff_keeps_single_ownership(self):
        """The receiver-side choreography: staging id owns the blocks
        through the transfer, adoption bumps to 2, freeing the staging
        id leaves the destination sequence as the sole owner."""
        kv = _kv()
        staging = -(1 + 1)
        t = kv.alloc_sequence(staging, 40)
        for b in t:
            assert kv.block_ref(b) == 1
        kv.adopt_sequence(7, t, 40)
        for b in t:
            assert kv.block_ref(b) == 2
        kv.free_sequence(staging)
        for b in t:
            assert kv.block_ref(b) == 1
        assert list(kv.block_table(7)) == list(t)
        kv.extend_sequence(7, 41)  # adopted chain decodes normally
        kv.free_sequence(7)
        kv.assert_idle("after staging handoff")

    def test_chain_bytes_roundtrip_through_pools(self):
        """read_chain_blocks ∘ write_chain_blocks is the identity on the
        chain's slots: what the source serializes is exactly what the
        destination's pools hold after the fused scatter."""
        src = _kv()
        dst = _kv()
        t = src.alloc_sequence(1, 40)
        bb = chain_block_bytes(src)
        assert bb == chain_block_bytes(dst)
        # write a recognizable pattern through the source pools
        k = np.asarray(src.k_pool).copy()
        v = np.asarray(src.v_pool).copy()
        for i, b in enumerate(t):
            sl = slice(b * src.block_size, (b + 1) * src.block_size)
            k[:, sl, :] = float(i + 1)
            v[:, sl, :] = -float(i + 1)
        import jax.numpy as jnp

        src.update_pools(jnp.asarray(k), jnp.asarray(v))
        payloads = read_chain_blocks(src, t, bb)
        assert len(payloads) == 3 and all(len(p) == bb for p in payloads)
        st = dst.alloc_sequence(-2, 40)
        write_chain_blocks(dst, st, payloads, 40)
        got_k = np.asarray(dst.k_pool)
        got_v = np.asarray(dst.v_pool)
        for i, b in enumerate(st):
            sl = slice(b * dst.block_size, (b + 1) * dst.block_size)
            assert np.all(got_k[:, sl, :] == float(i + 1))
            assert np.all(got_v[:, sl, :] == -float(i + 1))
        src.free_sequence(1)
        dst.free_sequence(-2)
        src.assert_idle()
        dst.assert_idle()


# --------------------------------------------------------- wire validation
class TestManifestValidation:
    def _receiver_reject(self, engine, **overrides):
        rx = MigrationReceiver(engine)
        kv = engine.kv
        fields = dict(seq_id=5, prompt_tokens=[1, 2, 3], out_tokens=[4],
                      max_new_tokens=8, stop_token=0, ntokens=4,
                      n_blocks=1, block_size=kv.block_size,
                      layers=kv.layers, kv_dim=kv.kv_dim,
                      block_bytes=chain_block_bytes(kv), recovery=False)
        fields.update(overrides)
        req = serving_pb2.MigrateRequest(**fields)
        # a controller with no stream settings at all
        cntl = types.SimpleNamespace(_srv_meta=None)
        return rx.open(cntl, req)

    def test_open_without_stream_rejected(self):
        engine, kv, model = _build_engine()
        try:
            ack = self._receiver_reject(engine)
            assert not ack.accepted and "stream" in ack.message
        finally:
            _teardown(engine, kv, model)

    def test_geometry_and_capacity_mismatches_rejected(self):
        engine, kv, model = _build_engine()
        meta = types.SimpleNamespace(
            stream_settings=types.SimpleNamespace(stream_id=1))

        def open_with(**overrides):
            rx = MigrationReceiver(engine)
            fields = dict(seq_id=5, prompt_tokens=[1, 2, 3],
                          out_tokens=[4], max_new_tokens=8, stop_token=0,
                          ntokens=4, n_blocks=1,
                          block_size=kv.block_size, layers=kv.layers,
                          kv_dim=kv.kv_dim,
                          block_bytes=chain_block_bytes(kv),
                          recovery=False)
            fields.update(overrides)
            cntl = types.SimpleNamespace(_srv_meta=meta)
            return rx.open(cntl, serving_pb2.MigrateRequest(**fields))

        try:
            ack = open_with(block_size=8)
            assert not ack.accepted and "geometry" in ack.message
            ack = open_with(kv_dim=kv.kv_dim * 2)
            assert not ack.accepted and "geometry" in ack.message
            ack = open_with(block_bytes=1)
            assert not ack.accepted and "block_bytes" in ack.message
            # 1 block cannot carry 40 tokens at block_size 16
            ack = open_with(ntokens=40)
            assert not ack.accepted and "cannot carry" in ack.message
            kv.assert_idle("rejects must not leak staging chains")
        finally:
            _teardown(engine, kv, model)

    def test_commit_unknown_sequence_rejected(self):
        engine, kv, model = _build_engine()
        try:
            rx = MigrationReceiver(engine)
            ack = rx.commit(None,
                            serving_pb2.MigrateCommitRequest(seq_id=99))
            assert not ack.accepted and "no open migration" in ack.message
        finally:
            _teardown(engine, kv, model)


# ---------------------------------------------------- disaggregated plane
@pytest.fixture
def disagg_pair():
    """prefill-role engine + decode-role engine behind a real loopback
    LlmService, wired with a KVMigrator — the minimal disaggregated
    deployment."""
    dec, dec_kv, dec_model = _build_engine(role="decode")
    srv = Server().add_service(
        LlmServingService(dec)).start("127.0.0.1:0")
    pre, pre_kv, pre_model = _build_engine(role="prefill")
    pre.set_migrator(KVMigrator(f"{srv.listen_endpoint()}"))
    yield pre, dec, srv
    pre.stop()
    srv.stop()
    srv.join(timeout=2)
    dec.stop()
    # the acceptance gate: zero leaked blocks on BOTH armed pools
    pre_kv.assert_idle("prefill pool after disaggregated run")
    dec_kv.assert_idle("decode pool after disaggregated run")
    pre_model.close()
    dec_model.close()


class TestDisaggregatedServing:
    def test_corpus_schedule_bit_identical_to_colocated(self, disagg_pair):
        """The correctness oracle: every sequence of the committed corpus
        schedule, prefill on one engine + migrate + decode on the other,
        produces EXACTLY the co-located engine's greedy tokens — and the
        decode engine never prefills a single token."""
        pre, dec, _srv = disagg_pair
        ref_engine, ref_kv, ref_model = _build_engine()
        try:
            ref = []
            for plen, max_new in SCHEDULE:
                code, seq, ev, _ = _submit(
                    ref_engine, ref_model.synth_prompt(plen), max_new)
                assert code == 0
                assert ev.wait(300), "reference run stalled"
                ref.append(list(seq.out_tokens))
        finally:
            _teardown(ref_engine, ref_kv, ref_model)

        assert dec.prefill_tokens == 0
        got = []
        for plen, max_new in SCHEDULE:
            code, _seq, ev, box = _submit(
                pre, pre.model.synth_prompt(plen), max_new)
            assert code == 0
            assert ev.wait(300), "prefill stage stalled"
            h = box["r"]
            assert h.finish_reason == "handoff"
            assert h.handoff_shard == pre.migrator.dest_shard
            assert len(h.tokens) >= 1  # prefill emitted the first token
            code, _seq2, ev2, box2 = _submit(
                dec, np.zeros(0, dtype=np.int32), 0, resume=h.seq_id)
            assert code == 0
            assert ev2.wait(300), "decode stage stalled"
            a = box2["r"]
            got.append(list(h.tokens) + list(a.tokens))
        assert got == ref
        # zero re-prefilled tokens: the decode engine only ever decoded
        assert dec.prefill_tokens == 0
        assert pre.migrator.seqs == len(SCHEDULE)
        assert pre.migrator.failed == 0

    def test_decode_engine_commits_every_decode_token_and_no_prefill(
            self, disagg_pair):
        """What disaggregation moves, in counts: the decode-role engine
        commits every token past each sequence's first and prefills none;
        the migrator moved one chain a sequence, as many blocks as the
        prompts fill."""
        pre, dec, _srv = disagg_pair
        sched = [(16, 4), (40, 8), (16, 4), (70, 12)]
        for plen, max_new in sched:
            code, _s, ev, box = _submit(
                pre, pre.model.synth_prompt(plen), max_new)
            assert code == 0 and ev.wait(300)
            h = box["r"]
            assert h.finish_reason == "handoff" and len(h.tokens) == 1
            code, _s2, ev2, box2 = _submit(
                dec, np.zeros(0, dtype=np.int32), 0, resume=h.seq_id)
            assert code == 0 and ev2.wait(300)
            assert len(box2["r"].tokens) == max_new - 1
        assert dec.prefill_tokens == 0
        assert dec.tokens_generated == sum(n - 1 for _, n in sched)
        out = pre.snapshot()["migration"]["out"]
        assert (out["seqs"], out["failed"]) == (len(sched), 0)
        assert out["blocks"] == sum(pre.kv.blocks_for(p) for p, _ in sched)
        assert out["bytes"] == out["blocks"] * chain_block_bytes(pre.kv)
        assert dec.snapshot()["migration"]["in"]["seqs_in"] == len(sched)

    def test_prefill_engine_never_decodes_a_handed_off_sequence(
            self, disagg_pair):
        """The other half: the prefill-role engine launches one prefill a
        sequence and no decode step, whatever the answers' lengths."""
        pre, dec, _srv = disagg_pair
        sched = [(16, 4), (32, 64), (16, 4)]
        handoffs = []
        for plen, max_new in sched:
            code, _s, ev, box = _submit(
                pre, pre.model.synth_prompt(plen), max_new)
            assert code == 0 and ev.wait(300)
            handoffs.append(box["r"].seq_id)
        snap = pre.snapshot()
        assert snap["decode"]["decode_launches_paged"] \
            + snap["decode"]["decode_launches_gather"] == 0
        assert snap["tokens_generated"] == len(sched)
        assert snap["steps"] == len(sched)
        assert snap["batch_occupancy_avg"] == 0
        assert pre.prefill_tokens == sum(p for p, _ in sched)
        for sid in handoffs:
            code, _s2, ev2, _b2 = _submit(
                dec, np.zeros(0, dtype=np.int32), 0, resume=sid)
            assert code == 0 and ev2.wait(300)

    def test_resume_attach_is_single_use(self, disagg_pair):
        pre, dec, _srv = disagg_pair
        code, _s, ev, box = _submit(pre, pre.model.synth_prompt(16), 4)
        assert code == 0 and ev.wait(300)
        h = box["r"]
        code, _s2, ev2, _b2 = _submit(
            dec, np.zeros(0, dtype=np.int32), 0, resume=h.seq_id)
        assert code == 0 and ev2.wait(300)
        # the sequence finished and detached: a second attach is EREQUEST
        code, _s3, _ev3, _b3 = _submit(
            dec, np.zeros(0, dtype=np.int32), 0, resume=h.seq_id)
        assert code == errors.EREQUEST

    def test_unknown_resume_id_is_erequest(self, disagg_pair):
        _pre, dec, _srv = disagg_pair
        code, _s, _ev, _b = _submit(
            dec, np.zeros(0, dtype=np.int32), 0, resume=424242)
        assert code == errors.EREQUEST

    def test_migrate_metrics_and_snapshot(self, disagg_pair):
        pre, dec, _srv = disagg_pair
        seqs0 = g_serving_migrate_seqs.get_value()
        code, _s, ev, box = _submit(pre, pre.model.synth_prompt(16), 4)
        assert code == 0 and ev.wait(300)
        h = box["r"]
        code, _s2, ev2, _b2 = _submit(
            dec, np.zeros(0, dtype=np.int32), 0, resume=h.seq_id)
        assert code == 0 and ev2.wait(300)
        assert g_serving_migrate_seqs.get_value() == seqs0 + 1
        out = pre.snapshot()["migration"]
        assert out["parked"] == 0
        assert out["out"]["seqs"] >= 1 and out["out"]["bytes"] > 0
        assert out["out"]["gbps"] > 0
        inn = dec.snapshot()["migration"]
        assert inn["in"]["seqs_in"] >= 1
        assert inn["in"]["pending_in"] == 0


class TestTwoStageRouter:
    def test_two_stage_dispatch_stitches_the_generation(self):
        """Client-side contract: a ShardedLlmChannel over [prefill shard
        0, decode shard 1] with prefill_partitions=[0] issues stage 1 to
        the prefill shard, follows the handoff to shard 1, and returns
        ONE stitched response equal to the co-located generation."""
        ref_engine, ref_kv, ref_model = _build_engine()
        try:
            code, seq, ev, _ = _submit(ref_engine,
                                       ref_model.synth_prompt(24), 6)
            assert code == 0 and ev.wait(300)
            ref_toks = list(seq.out_tokens)
        finally:
            _teardown(ref_engine, ref_kv, ref_model)

        pre, pre_kv, pre_model = _build_engine(role="prefill")
        dec, dec_kv, dec_model = _build_engine(role="decode")
        srv0 = Server().add_service(
            LlmServingService(pre)).start("127.0.0.1:0")
        srv1 = Server().add_service(
            LlmServingService(dec)).start("127.0.0.1:0")
        pre.set_migrator(
            KVMigrator(f"{srv1.listen_endpoint()}", dest_shard=1))
        try:
            url = (f"list://{srv0.listen_endpoint()} 0/2,"
                   f"{srv1.listen_endpoint()} 1/2")
            ch = ShardedLlmChannel(
                url, 2,
                options=ChannelOptions(protocol="trpc_std",
                                       timeout_ms=60000),
                prefill_partitions=[0])
            req = serving_pb2.GenerateRequest(prompt_len=24,
                                              max_new_tokens=6)
            assert ch.shard_of(req) == 0  # fresh prompts -> prefill shard
            resp = ch.generate(req)
            assert list(resp.tokens) == ref_toks
            assert resp.prompt_len == 24
            assert resp.steps == len(ref_toks)
            assert resp.finish_reason != "handoff"  # fully stitched
            # resume requests route by the handoff meta, not the hash
            follow = serving_pb2.GenerateRequest(resume_seq_id=7,
                                                 resume_shard=1)
            assert ch.shard_of(follow) == 1
        finally:
            srv0.stop()
            srv0.join(timeout=2)
            srv1.stop()
            srv1.join(timeout=2)
            pre.stop()
            dec.stop()
            pre_kv.assert_idle("prefill pool after two-stage dispatch")
            dec_kv.assert_idle("decode pool after two-stage dispatch")
            pre_model.close()
            dec_model.close()


# ------------------------------------------------------------------ chaos
@pytest.mark.chaos
class TestMigrationChaos:
    def test_drop_fault_falls_back_to_local_decode(self, fault_enabled,
                                                   disagg_pair):
        """serving.migrate.drop kills the destination tunnel on every
        transfer: the source must retain the chain and decode the
        sequence LOCALLY to the same greedy tokens — no stranded
        ownership, zero leaked blocks on either armed pool (the fixture
        teardown proves it)."""
        pre, dec, _srv = disagg_pair
        ref_engine, ref_kv, ref_model = _build_engine()
        try:
            code, seq, ev, _ = _submit(ref_engine,
                                       ref_model.synth_prompt(16), 6)
            assert code == 0 and ev.wait(300)
            ref_toks = list(seq.out_tokens)
        finally:
            _teardown(ref_engine, ref_kv, ref_model)

        failed0 = g_serving_migrate_failed.get_value()
        fault.arm("serving.migrate.drop", mode="always")
        try:
            code, _s, ev, box = _submit(pre, pre.model.synth_prompt(16), 6)
            assert code == 0
            assert ev.wait(300), "local-fallback decode stalled"
        finally:
            fault.disarm_all()
        r = box["r"]
        # NOT a handoff: the prefill engine finished the whole generation
        assert r.finish_reason == "length"
        assert list(r.tokens) == ref_toks
        assert pre.migrator.failed >= 1
        assert g_serving_migrate_failed.get_value() > failed0
        # the decode engine adopted nothing
        assert dec.snapshot()["migration"]["in"]["seqs_in"] == 0
        assert dec.snapshot()["migration"]["in"]["pending_in"] == 0

    def test_stall_fault_delays_but_completes(self, fault_enabled,
                                              disagg_pair):
        pre, dec, _srv = disagg_pair
        fault.arm("serving.migrate.stall", mode="oneshot", delay_ms=50)
        try:
            t0 = time.monotonic()
            code, _s, ev, box = _submit(pre, pre.model.synth_prompt(16), 4)
            assert code == 0 and ev.wait(300)
            h = box["r"]
            assert h.finish_reason == "handoff"
            assert time.monotonic() - t0 >= 0.05
        finally:
            fault.disarm_all()
        code, _s2, ev2, _b2 = _submit(
            dec, np.zeros(0, dtype=np.int32), 0, resume=h.seq_id)
        assert code == 0 and ev2.wait(300)

    def test_shard_death_drains_onto_survivor_without_reprefill(self):
        """Kill a shard mid-generation: stop() drains its live chains to
        the survivor (recovery migration), the client's retry of the SAME
        request attaches to the migrated sequence by prompt match, and
        the full generation comes back bit-identical to an uninterrupted
        run — with the survivor having prefilled ZERO tokens."""
        ref_engine, ref_kv, ref_model = _build_engine()
        try:
            code, seq, ev, _ = _submit(ref_engine,
                                       ref_model.synth_prompt(24), 32)
            assert code == 0 and ev.wait(300)
            ref_toks = list(seq.out_tokens)
        finally:
            _teardown(ref_engine, ref_kv, ref_model)

        dying, dying_kv, dying_model = _build_engine()
        surv, surv_kv, surv_model = _build_engine()
        srv = Server().add_service(
            LlmServingService(surv)).start("127.0.0.1:0")
        dying.set_migrator(KVMigrator(f"{srv.listen_endpoint()}"))
        try:
            cntl = types.SimpleNamespace(
                failed_code=0,
                set_failed=lambda c, m, _s=None: None)
            box = {}
            ev = threading.Event()

            def set_failed(code, msg):
                cntl.failed_code = code
                cntl.failed_msg = msg

            cntl.set_failed = set_failed
            code, seq = dying.submit(
                dying_model.synth_prompt(24), 32, cntl=cntl,
                done=lambda r, box=box, ev=ev: (box.update(r=r),
                                                ev.set()))
            assert code == 0
            # let it decode a few tokens, then kill the shard
            deadline = time.monotonic() + 60
            while len(seq.out_tokens) < 4:
                assert time.monotonic() < deadline, "decode never started"
                time.sleep(0.005)
            dying.stop()
            assert ev.wait(60), "doomed RPC never completed"
            # the client saw a RETRIABLE failure naming the drain
            assert box["r"] is None
            assert cntl.failed_code == errors.EFAILEDSOCKET
            assert "migrated to survivor" in cntl.failed_msg
            assert dying.migrator.seqs == 1
            # the retry: same prompt/max_new on the survivor attaches to
            # the live migrated sequence — full token list, no prefill
            pf0 = surv.prefill_tokens
            code, _s2, ev2, box2 = _submit(
                surv, surv_model.synth_prompt(24), 32)
            assert code == 0
            assert ev2.wait(300), "recovered generation stalled"
            r = box2["r"]
            assert list(r.tokens) == ref_toks
            assert surv.prefill_tokens == pf0  # zero re-prefilled tokens
        finally:
            srv.stop()
            srv.join(timeout=2)
            surv.stop()
            dying_kv.assert_idle("dying pool after drain")
            surv_kv.assert_idle("survivor pool after recovery")
            dying_model.close()
            surv_model.close()


# ------------------------------------------------------------ observability
class TestMigrationObservability:
    def test_backlog_watch_rule_installed_and_reloadable(self):
        from brpc_tpu.metrics.watch import global_watch, install_default_rules

        install_default_rules()
        rules = {r.name: r for r in global_watch().rules()}
        assert "serving_migrate_backlog" in rules
        rule = rules["serving_migrate_backlog"]
        assert rule.var == "g_serving_migrate_inflight"
        assert rule.kind == "threshold"
        assert rule.bound() == float(_flags.get("serving_migrate_backlog_max"))
        old = _flags.get("serving_migrate_backlog_max")
        try:
            _flags.set_flag("serving_migrate_backlog_max", "2")
            assert rule.bound() == 2.0  # reloadable, no restart
        finally:
            _flags.set_flag("serving_migrate_backlog_max", str(old))

    def test_serving_builtin_reports_migration(self, disagg_pair):
        import json as _json

        from brpc_tpu.builtin.services import serving_service

        pre, dec, _srv = disagg_pair
        code, _s, ev, box = _submit(pre, pre.model.synth_prompt(16), 4)
        assert code == 0 and ev.wait(300)
        h = box["r"]
        code, _s2, ev2, _b2 = _submit(
            dec, np.zeros(0, dtype=np.int32), 0, resume=h.seq_id)
        assert code == 0 and ev2.wait(300)

        http = types.SimpleNamespace(query={}, path="/serving")
        _st, _ct, body = serving_service(None, http)
        mig_lines = [l for l in body.splitlines()
                     if l.strip().startswith("migrate:")]
        assert mig_lines, body
        joined = "\n".join(mig_lines)
        assert "role=prefill" in joined and "role=decode" in joined
        assert "out ->" in joined and "in seqs" in joined

        http = types.SimpleNamespace(query={"format": "json"},
                                     path="/serving")
        _st, ct, body = serving_service(None, http)
        assert "json" in ct
        snaps = _json.loads(body)["engines"]
        migs = [s["migration"] for s in snaps if s.get("migration")]
        assert any(m.get("out", {}).get("seqs", 0) >= 1 for m in migs)
        assert any(m.get("in", {}).get("seqs_in", 0) >= 1 for m in migs)

    def test_migration_vars_exposed(self):
        from brpc_tpu.metrics.variable import get_exposed

        for name in ("g_serving_migrate_seqs", "g_serving_migrate_blocks",
                     "g_serving_migrate_bytes", "g_serving_migrate_failed",
                     "g_serving_migrate_inflight"):
            assert get_exposed(name) is not None, name
