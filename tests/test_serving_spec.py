"""Speculative decoding: k-token draft + one fused verify, exact oracle.

Layers, cheapest first:

* the draft lane pure-host: prompt-lookup matching, the AdaptiveK
  controller's shrink/collapse policy, the misdraft fault;
* the KV ledger's rollback primitive — ``truncate_sequence`` frees only
  the tail, respects shared refcounts (prefix-cache forks), and keeps
  the armed audit green;
* the model's ``verify_step`` against sequential ``decode_step``s — the
  same-launch write-before-gather semantics that make k+1 rows in one
  program equal k+1 steps;
* the engine end to end — the exact oracle (speculative outputs
  list-equal to the non-speculative lane on both committed corpus
  schedules, with the (1,1) dispatch audit armed), TokenDelta
  ``accepted`` framing, variable-spend budgeting;
* misdraft chaos — accept rate pinned ~0 still terminates bit-identical,
  leaks zero blocks, and the collapse guard bounds the wasted rows;
* the committed repetition-heavy corpus replayed through the
  rpc_replay→trace_diff gate, like the base corpus.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from brpc_tpu import fault
from brpc_tpu import flags as _flags
from brpc_tpu.serving import (
    EngineConfig,
    KVCacheConfig,
    LlmServingService,
    ModelConfig,
    PagedKVCache,
    ServingEngine,
    TinyTransformer,
)
from brpc_tpu.serving import speculative as spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS_SPEC = os.path.join(REPO, "tests", "data", "serving_corpus_spec")

# mixed synth-prompt schedule (the base corpus shape) + repetitive
# motif prompts (the spec corpus shape, tokens < the test vocab of 64)
BASE_SCHED = [(16, 4), (32, 8), (16, 6), (16, 4), (32, 8), (16, 6)]
_MOTIFS = [[7, 12, 19, 3, 12, 19], [41, 41, 9, 33, 41, 41, 9],
           [50, 5, 60, 5, 50, 5, 60]]
REP_SCHED = [(18, 16, 0), (21, 24, 1), (16, 16, 2), (18, 24, 0)]


def _motif_prompt(plen, motif):
    m = _MOTIFS[motif % len(_MOTIFS)]
    return np.asarray((m * (plen // len(m) + 1))[:plen], dtype=np.int32)


def _gen(engine, prompt, max_new, stream_id=0, timeout=120.0):
    ev = threading.Event()
    box = {}
    code, _ = engine.submit(np.asarray(prompt, dtype=np.int32), max_new,
                            stream_id=stream_id,
                            done=lambda r, b=box, e=ev: (b.update(r=r),
                                                         e.set()))
    assert code == 0, f"submit rejected: {code}"
    assert ev.wait(timeout), "generation timed out"
    return list(box["r"].tokens)


def _run_base(engine):
    """BASE_SCHED submitted open-loop, all responses collected in order."""
    evs = []
    for plen, max_new in BASE_SCHED:
        ev, box = threading.Event(), {}
        code, _ = engine.submit(engine.model.synth_prompt(plen), max_new,
                                done=lambda r, b=box, e=ev: (b.update(r=r),
                                                             e.set()))
        assert code == 0
        evs.append((ev, box))
    return [(e.wait(180), list(b["r"].tokens))[1] for e, b in evs]


def _run_rep(engine):
    evs = []
    for plen, max_new, motif in REP_SCHED:
        ev, box = threading.Event(), {}
        code, _ = engine.submit(_motif_prompt(plen, motif), max_new,
                                done=lambda r, b=box, e=ev: (b.update(r=r),
                                                             e.set()))
        assert code == 0
        evs.append((ev, box))
    return [(e.wait(180), list(b["r"].tokens))[1] for e, b in evs]


# ---------------------------------------------------------------- draft lane
class TestDrafter:
    def test_longest_ngram_most_recent_occurrence_wins(self):
        #           0  1  2  3  4  5  6  7
        history = [1, 2, 3, 9, 1, 2, 3, 9]
        # trailing 3-gram (2,3,9) last occurred at 1..3 -> continuation 1,2
        # wait: occurrence search excludes the tail itself
        assert spec.draft_tokens(history, 2) == [1, 2]

    def test_shorter_ngram_fallback(self):
        history = [5, 6, 7, 8, 6]
        # no 3- or 2-gram recurs; trailing 1-gram 6 followed 5 -> drafts 7, 8
        assert spec.draft_tokens(history, 3) == [7, 8, 6]

    def test_no_match_returns_empty(self):
        assert spec.draft_tokens([1, 2, 3, 4, 5], 4) == []
        assert spec.draft_tokens([1], 4) == []
        assert spec.draft_tokens([1, 1, 1], 0) == []

    def test_draft_capped_at_k(self):
        history = [1, 2, 3, 4, 1, 2]
        d = spec.draft_tokens(history, 2)
        assert d == [3, 4]

    def test_accept_longest_prefix(self):
        a, committed = spec.accept_longest_prefix([5, 6, 7], [5, 6, 9, 8])
        assert a == 2 and committed == [5, 6, 9]
        a, committed = spec.accept_longest_prefix([5, 6, 7], [5, 6, 7, 8])
        assert a == 3 and committed == [5, 6, 7, 8]  # full accept + bonus
        a, committed = spec.accept_longest_prefix([], [4])
        assert a == 0 and committed == [4]  # empty draft = plain decode

    def test_misdraft_fault_forces_garbage(self):
        _flags.set_flag("fault_injection_enabled", True)
        try:
            fault.arm("serving.spec.misdraft", mode="always")
            history = [1, 2, 3, 1, 2, 3]
            d = spec.draft_tokens(history, 4, vocab=64)
            # the real matcher would draft [1, 2, 3, ...]; the fault
            # replaces it with the deterministic walk off the last token
            assert d == [4, 5, 6, 7]
            assert all(0 <= t < 64 for t in d)
        finally:
            fault.disarm_all()
            _flags.set_flag("fault_injection_enabled", False)


class TestAdaptiveK:
    def test_grows_on_full_accept(self):
        ctl = spec.AdaptiveK(4)
        ctl.k = 2
        ctl.update(drafted=2, accepted=2)
        assert ctl.k == 3
        ctl.update(drafted=3, accepted=3)
        assert ctl.k == 4
        ctl.update(drafted=4, accepted=4)
        assert ctl.k == 4  # capped

    def test_partial_accept_re_aims(self):
        ctl = spec.AdaptiveK(8)
        ctl.update(drafted=8, accepted=2)
        assert ctl.k == 3
        assert not ctl.collapsed

    def test_collapse_after_zero_streak(self):
        ctl = spec.AdaptiveK(4, collapse_after=4)
        ks = []
        for _ in range(4):
            ctl.update(drafted=max(1, ctl.k), accepted=0)
            ks.append(ctl.k)
        assert ks == [2, 1, 1, 0]
        assert ctl.collapsed
        # collapsed is terminal: empty drafts never resurrect k
        ctl.update(drafted=0, accepted=0)
        assert ctl.k == 0

    def test_accept_resets_streak(self):
        ctl = spec.AdaptiveK(4, collapse_after=3)
        ctl.update(drafted=4, accepted=0)
        ctl.update(drafted=2, accepted=0)
        ctl.update(drafted=1, accepted=1)  # full accept for drafted=1
        assert ctl.zero_streak == 0 and not ctl.collapsed


# ------------------------------------------------------------ KV rollback
def _small_kv(num_blocks=16, block_size=8):
    kv = PagedKVCache(KVCacheConfig(block_size=block_size,
                                    num_blocks=num_blocks), 1, 8)
    kv._check = True
    return kv


class TestTruncateRollback:
    def test_truncate_frees_only_the_tail(self):
        kv = _small_kv()
        kv.alloc_sequence(1, 10)          # 2 blocks
        kv.extend_sequence(1, 30)         # 4 blocks (speculative headroom)
        assert kv.used_blocks == 4
        freed = kv.truncate_sequence(1, 12)
        assert freed == 2                 # back to blocks_for(12) == 2
        assert kv.used_blocks == 2
        assert kv.seq_len(1) == 12
        kv.free_sequence(1)
        kv.assert_idle("after truncate roundtrip")

    def test_truncate_noop_when_within_coverage(self):
        kv = _small_kv()
        kv.alloc_sequence(1, 16)
        assert kv.truncate_sequence(1, 16) == 0
        kv.free_sequence(1)
        kv.assert_idle()

    def test_truncate_respects_shared_refcounts(self):
        # a prefix-cache-style fork shares blocks; rollback on one
        # sequence must not free the other's tail
        kv = _small_kv()
        kv.alloc_sequence(1, 24)          # 3 blocks
        kv.fork_sequence(1, 2)            # shared refcount 2
        kv.extend_sequence(2, 40)         # +2 private tail blocks
        assert kv.used_blocks == 5
        freed = kv.truncate_sequence(2, 24)
        assert freed == 2                 # only the private tail came back
        assert kv.used_blocks == 3
        assert kv.block_table(1) == kv.block_table(2)
        kv.free_sequence(2)
        assert kv.used_blocks == 3        # still held by seq 1
        kv.free_sequence(1)
        kv.assert_idle("after shared truncate")

    def test_truncate_unknown_sequence_raises(self):
        kv = _small_kv()
        with pytest.raises(KeyError):
            kv.truncate_sequence(77, 8)

    def test_truncate_discards_quiesce_mark(self):
        kv = _small_kv()
        kv.alloc_sequence(1, 24)
        kv.quiesce_sequence(1)
        kv.truncate_sequence(1, 8)
        with pytest.raises(AssertionError):
            kv.export_chain(1)            # chain mutated, mark gone
        kv.free_sequence(1)
        kv.assert_idle()

    def test_sharded_truncate_routes_to_owner(self):
        from brpc_tpu.serving import ShardedKVCache

        kv = ShardedKVCache(KVCacheConfig(block_size=8, num_blocks=32),
                            1, 8)
        kv._check = True
        kv.alloc_sequence(5, 10)
        kv.extend_sequence(5, 40)
        freed = kv.truncate_sequence(5, 10)
        assert freed == 3                 # 5 blocks back to blocks_for(10)
        kv.free_sequence(5)
        kv.assert_idle("sharded truncate teardown")


# ------------------------------------------------- verify == sequential
@pytest.mark.slow
def test_verify_step_equals_sequential_decode():
    """k+1 rows in ONE verify launch produce the same argmax stream as
    k+1 sequential decode steps: per layer, all rows' K/V writes land
    before any gather and the causal mask keeps row j inside its own
    prefix — the prefill_suffix semantics, batched."""
    cfg = ModelConfig(vocab=64, d_model=16, n_heads=2, n_layers=1,
                      max_context=128)
    kv = PagedKVCache(KVCacheConfig(block_size=8, num_blocks=64),
                      cfg.n_layers, cfg.kv_dim)
    kv._check = True
    model = TinyTransformer(cfg, kv)
    try:
        prompt = model.synth_prompt(16)
        k = 4

        # reference: prefill + k+1 sequential decode steps
        kv.alloc_sequence(1, len(prompt) + 1)
        t = kv.block_table(1)
        seq_tokens = [model.prefill(prompt, t)]
        for i in range(k + 1):
            ctx = len(prompt) + len(seq_tokens)
            table = kv.extend_sequence(1, ctx)
            out = model.decode_step(
                np.asarray([seq_tokens[-1]], dtype=np.int32),
                np.asarray([ctx - 1], dtype=np.int32), [table])
            seq_tokens.append(int(out[0]))
        kv.free_sequence(1)

        # speculative: one verify launch over a perfect draft
        kv.alloc_sequence(2, len(prompt) + 1)
        t = kv.block_table(2)
        first = model.prefill(prompt, t)
        assert first == seq_tokens[0]
        draft = seq_tokens[1:k + 1]       # the true continuation
        ctx = len(prompt) + 1             # prompt + first token committed
        table = kv.extend_sequence(2, ctx + k)
        outs = model.verify_step([first], [ctx - 1], [table], [draft])
        m = [int(x) for x in outs[0]]
        assert m == seq_tokens[1:k + 2], (
            "verify argmax diverged from sequential decode")
        kv.free_sequence(2)
        kv.assert_idle("verify-vs-sequential teardown")
    finally:
        model.close()


# -------------------------------------------------------- engine fixtures
def _build_engine(spec_k):
    cfg = ModelConfig(vocab=64, d_model=16, n_heads=2, n_layers=1,
                      max_context=256)
    kv = PagedKVCache(KVCacheConfig(block_size=8, num_blocks=64),
                      cfg.n_layers, cfg.kv_dim)
    kv._check = True  # arms the engine's (1,1) dispatch assert per step
    model = TinyTransformer(cfg, kv)
    return ServingEngine(model, kv,
                         EngineConfig(max_batch=4, token_budget=128,
                                      idle_wait_s=0.005, spec_k=spec_k),
                         prefix_cache=False).start()


@pytest.fixture(scope="module")
def lanes():
    """Baseline (spec_k=0) and speculative (spec_k=4) engines over
    identical models; warmup runs both schedules twice through each so
    every jit bucket is hot before any timed or counted assertion."""
    base = _build_engine(0)
    sp = _build_engine(4)
    for eng in (base, sp):
        for _ in range(2):
            _run_base(eng)
            _run_rep(eng)
    yield base, sp
    for eng in (base, sp):
        eng.stop()
        eng.kv.assert_idle("spec lanes teardown")
        eng.model.close()


# ------------------------------------------------------------ exact oracle
class TestSpecOracle:
    def test_base_schedule_bit_identical(self, lanes):
        base, sp = lanes
        assert _run_base(base) == _run_base(sp)
        assert sp.kv.used_blocks == 0  # rollback leaked nothing

    def test_repetitive_schedule_bit_identical_fewer_steps(self, lanes):
        base, sp = lanes
        s0b, s0s = base.steps, sp.steps
        out_b = _run_rep(base)
        out_s = _run_rep(sp)
        assert out_b == out_s
        steps_b, steps_s = base.steps - s0b, sp.steps - s0s
        # the whole point: prompt-lookup hits on repetitive traffic, so
        # the speculative lane commits multiple tokens per step
        assert steps_s < steps_b, (steps_s, steps_b)
        st = sp.spec_stats
        assert st is not None and st.accepted > 0
        assert sp.kv.used_blocks == 0

    def test_spec_corpus_schedule_bit_identical(self, lanes):
        """The committed spec-corpus schedule shape (motif prompts),
        exact list-equality — the oracle the ISSUE gates on, at the
        test-model scale; the full recorded corpus replays below."""
        base, sp = lanes
        assert _run_rep(base) == _run_rep(sp)

    def test_snapshot_and_gauges_surface(self, lanes):
        _, sp = lanes
        snap = sp.snapshot()["spec"]
        assert snap is not None and snap["k_max"] == 4
        assert snap["drafted"] >= snap["accepted"] >= 0
        assert 0.0 <= snap["accept_rate"] <= 1.0
        assert spec.accept_rate() >= 0.0  # passive gauge computes

    def test_serving_builtin_renders_spec_line(self, lanes):
        import types

        from brpc_tpu.builtin.services import serving_service

        base, sp = lanes
        status, _ctype, text = serving_service(
            None, types.SimpleNamespace(query={}, path="/serving"))
        assert status == 200
        assert "spec: k_max=4" in text
        assert "accept_rate=" in text and "collapsed_seqs=" in text
        status, _ctype, body = serving_service(
            None, types.SimpleNamespace(query={"format": "json"},
                                        path="/serving"))
        assert status == 200
        snaps = json.loads(body)["engines"]
        specs = [e["spec"] for e in snaps if e.get("spec")]
        assert any(s["k_max"] == 4 and s["drafted"] > 0 for s in specs)
        # the non-speculative lane advertises no spec section at all
        assert any(e.get("spec") is None for e in snaps)

    def test_token_budget_counts_draft_rows(self, lanes):
        _, sp = lanes
        from brpc_tpu.serving.engine import Sequence

        seq = Sequence(np.zeros(4, dtype=np.int32), 8)
        assert sp._decode_cost(seq) == 5  # 1 + spec_k before first step
        seq.spec = spec.AdaptiveK(4)
        seq.spec.k = 2
        assert sp._decode_cost(seq) == 3
        seq.spec.k = 0                    # collapsed: plain decode cost
        assert sp._decode_cost(seq) == 1

    def test_streaming_frames_carry_accepted_counts(self, lanes,
                                                    monkeypatch):
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.rpc import stream as _stream

        _, sp = lanes
        frames = []
        monkeypatch.setattr(
            _stream, "stream_write",
            lambda sid, payload: (frames.append(
                serving_pb2.TokenDelta.FromString(payload)), 0)[1])
        plen, max_new, motif = REP_SCHED[1]
        toks = _gen(sp, _motif_prompt(plen, motif), max_new, stream_id=7)
        assert [t for f in frames for t in f.tokens] == toks
        assert frames[-1].done
        # repetitive prompt -> some frame committed accepted drafts, and
        # no frame claims more accepted than it carries tokens
        assert any(f.accepted > 0 for f in frames)
        assert all(f.accepted <= len(f.tokens) for f in frames)


# -------------------------------------------------------- misdraft chaos
@pytest.fixture
def fault_enabled():
    _flags.set_flag("fault_injection_enabled", True)
    yield
    fault.disarm_all()
    _flags.set_flag("fault_injection_enabled", False)


@pytest.mark.chaos
class TestMisdraftChaos:
    def test_garbage_drafts_terminate_bit_identical_no_leaks(
            self, lanes, fault_enabled):
        base, sp = lanes
        out_b = _run_rep(base)

        st = sp.spec_stats
        d0, a0 = st.drafted, st.accepted
        fault.arm("serving.spec.misdraft", mode="always")
        try:
            out_s = _run_rep(sp)
        finally:
            fault.disarm_all()
        # bit-identical even with every draft adversarial: the verifier
        # rejects, the bonus token carries the stream, rollback cleans up
        assert out_s == out_b
        assert sp.kv.used_blocks == 0, "misdraft run leaked KV blocks"
        drafted = st.drafted - d0
        accepted = st.accepted - a0
        assert drafted > 0
        # the walk never matches the argmax stream -> accept rate ~0
        assert accepted / drafted < 0.2, (accepted, drafted)
        # the collapse guard bounds the waste: each sequence stops
        # drafting after the zero-accept streak (4+2+1+1 rows max, plus
        # slack for the rare accidental accept resetting a streak)
        assert drafted <= len(REP_SCHED) * 16, drafted
        assert st.collapsed_seqs > 0

    def test_throughput_degrades_gracefully(self, lanes, fault_enabled):
        """Auto-disable via the adaptive-k floor: once collapsed, steps
        are plain decodes, so the misdraft lane's step count matches the
        baseline's (1 token/step)."""
        base, sp = lanes
        out_b = _run_rep(base)

        fault.arm("serving.spec.misdraft", mode="always")
        s0 = sp.steps
        try:
            out_s = _run_rep(sp)
        finally:
            fault.disarm_all()
        assert out_s == out_b
        # rejected steps commit exactly the bonus token, so the misdraft
        # lane needs no more steps than the baseline schedule (modulo
        # admission batching)
        tokens_total = sum(mn for _, mn, _ in REP_SCHED)
        assert sp.steps - s0 <= tokens_total + len(REP_SCHED)


@pytest.mark.parametrize("drafts", ["lookup", "misdraft"])
def test_steps_a_committed_token(lanes, fault_enabled, drafts):
    """What speculation saves, in counts, one sequence at a time so that a
    step is one sequence's: a step commits its accepted drafts and one
    token more, so tokens == sequences + steps + accepted. With the
    lookup's drafts on the repetitive corpus that is under 1/1.3 steps a
    token at an accept rate over a half; with every draft wrong (the fault
    stands for a corpus the lookup cannot predict: this toy's greedy
    answers repeat themselves whatever the prompt) every sequence
    collapses and is back to one step a token."""
    base, sp = lanes
    prompts = [(_motif_prompt(plen, motif), max_new)
               for plen, max_new, motif in REP_SCHED]
    plain0 = base.steps
    out_b = [_gen(base, p, n) for p, n in prompts]
    tokens = sum(n for _, n in prompts)
    assert base.steps - plain0 == tokens - len(prompts)
    st = sp.spec_stats
    s0, d0, a0, c0 = sp.steps, st.drafted, st.accepted, st.collapsed_seqs
    if drafts == "misdraft":
        fault.arm("serving.spec.misdraft", mode="always")
    try:
        out_s = [_gen(sp, p, n) for p, n in prompts]
    finally:
        fault.disarm_all()
    assert out_s == out_b
    steps, drafted = sp.steps - s0, st.drafted - d0
    accepted = st.accepted - a0
    assert tokens == len(prompts) + steps + accepted
    if drafts == "lookup":
        assert steps / tokens <= 1 / 1.3, (steps, tokens)
        assert accepted / drafted > 0.5, (accepted, drafted)
    else:
        assert st.collapsed_seqs - c0 == len(prompts)
        assert accepted / drafted < 0.2, (accepted, drafted)
        # k halves 4, 2, 1, 1 and the sequence drafts no more
        assert drafted <= len(prompts) * 16, drafted
    assert sp.kv.used_blocks == 0


# ------------------------------------------- corpus replay/diff gate
def test_spec_corpus_replays_and_phases_hold(tmp_path):
    """The committed repetition-heavy corpus
    (tools/record_serving_corpus_spec.py) replayed against a fresh
    SPECULATIVE serving stack: every recorded Generate succeeds with the
    recorded token counts, drafting actually hits (accept rate well
    above zero), spans carry the engine phases, and trace_diff holds the
    p50 phase timelines."""
    from brpc_tpu.metrics.collector import global_collector
    from brpc_tpu.rpc import Server
    from brpc_tpu.trace import span as _span
    from tools import record_serving_corpus_spec as recorder
    from tools import rpc_replay, trace_diff

    dumps = [f for f in os.listdir(CORPUS_SPEC) if f.endswith(".dump")]
    assert dumps, ("committed spec corpus missing; run "
                   "tools/record_serving_corpus_spec")

    _flags.set_flag("rpcz_sample_ratio", "1.0")
    _flags.set_flag("collector_max_samples_per_second", "0")
    global_collector()._deny_until = 0.0
    engine = recorder.build_engine()
    try:
        recorder.warm_engine(engine)
        _span.reset_for_test()
        server = Server().add_service(LlmServingService(engine)) \
            .start("127.0.0.1:0")
        try:
            rc = rpc_replay.main([
                "--dump", CORPUS_SPEC,
                "--server", str(server.listen_endpoint()),
                "--rate-mult", "2", "--timeout-ms", "30000",
                "--report-interval", "0"])
            assert rc == 0
            deadline = time.monotonic() + 5.0
            while (len([s for s in _span.recent_spans(200)
                        if s.kind == _span.KIND_SERVER])
                   < len(recorder.SCHEDULE)
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        finally:
            server.stop()
            server.join(timeout=2)
        spans = [s for s in _span.recent_spans(200)
                 if s.kind == _span.KIND_SERVER]
        assert len(spans) >= len(recorder.SCHEDULE)
        with_phases = [s for s in spans
                       if "prefill_us" in s.phases
                       and "decode_us" in s.phases]
        assert with_phases, "no replayed span carries the engine phases"
        # the corpus is repetition-heavy BY CONSTRUCTION — if drafting
        # stopped hitting on it, the speculative lane silently lost its
        # reason to exist; gate on the engine's own accept rate
        st = engine.spec_stats
        assert st is not None and st.drafted > 0
        assert st.accept_rate() > 0.5, st.snapshot()
        # the wait in the engine's queue is the replay's load, not the
        # server's phase: the recorded corpus predates it and the gate
        # stays on what it held (tests/test_serving_spans.py holds the wait
        # to the engine's own counters)
        docs = [s.to_dict() for s in _span.recent_spans(200)]
        for d in docs:
            d["phases"].pop("serving_queue_us", None)
        replayed = tmp_path / "replayed.json"
        replayed.write_text(json.dumps({"spans": docs}))
        rc = trace_diff.main([CORPUS_SPEC, str(replayed),
                              "--percentile", "50",
                              "--min-delta-us", "50000"])
        assert rc == 0
    finally:
        engine.stop()
        engine.kv.assert_idle("spec corpus gate teardown")
        engine.model.close()
        _flags.set_flag("rpcz_sample_ratio", "1.0")
        _flags.set_flag("collector_max_samples_per_second", "1000")


# -------------------------------------------------- watch rule / flag
def test_spec_collapse_rule_installed_with_reloadable_bound():
    from brpc_tpu.metrics.watch import (KIND_THRESHOLD, global_watch,
                                        install_default_rules)

    install_default_rules()
    rule = {r.name: r for r in global_watch().rules()}["serving_spec_collapse"]
    assert rule.var == "g_serving_spec_accept_rate"
    assert rule.kind == KIND_THRESHOLD and rule.op == "<"
    assert rule.value_fn is not None
    assert rule.value_fn() == pytest.approx(
        _flags.get("serving_spec_accept_rate_min"))
    _flags.set_flag("serving_spec_accept_rate_min", "0.4")
    try:
        assert rule.value_fn() == pytest.approx(0.4)
    finally:
        _flags.set_flag("serving_spec_accept_rate_min", "0.2")


def test_accept_rate_gauge_windows_and_idles_high():
    spec.reset_rate_window()
    assert spec.accept_rate() == 1.0  # idle engines must not alarm
    spec.note_step(10, 1)
    spec.note_step(10, 1)
    assert spec.accept_rate() == pytest.approx(0.1)
    spec.reset_rate_window()
