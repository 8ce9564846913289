"""Multi-tenant QoS: weighted fair share, the closed loop, overload survival.

Five layers, cheapest first:

* the TenantScheduler as a pure ledger — stride accounting converging to
  the weight ratio, idle-share redistribution and one-step reclaim,
  per-lane queue caps, deadline death at the admission boundary, the
  protected carve-out, and best-effort-first shed ordering;
* the QosLimiter gradient — multiplicative shrink under rising queue
  wait, additive recovery gated on inflight, both clamps;
* the governor's tick against a stub engine — queued best-effort work
  shed EOVERCROWDED down to the ceiling, the protected lane untouched,
  every block back in the pool;
* identity on the wire — Controller ``tenant_id``/``priority`` through
  RequestMeta to the engine's lanes, the committed overload corpus
  carrying it, and rpc_replay's --tenant-override restamping it;
* the acceptance gate — the diurnal-overload corpus replayed at 2x the
  recorded rate: the protected tenant's p99 holds within 1.5x its
  unloaded baseline while best-effort sheds EOVERCROWDED, and the same
  wave with QoS off violates the bound.
"""

import collections
import json
import os
import threading
import time
import types

import pytest

from brpc_tpu import fault
from brpc_tpu import flags as _flags
from brpc_tpu.rpc import errors
from brpc_tpu.serving import LlmServingService
from brpc_tpu.serving.qos import (DEFAULT_TENANT, QosConfig, QosLimiter,
                                  TenantScheduler)
from test_serving import _Cntl, _stub_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS_OVERLOAD = os.path.join(REPO, "tests", "data",
                               "serving_corpus_overload")


def _seq(tenant, priority=0, cost=16, t_submit=None):
    """A scheduler-shaped sequence: just the fields the ledger reads."""
    return types.SimpleNamespace(
        tenant_id=tenant, priority=priority, cntl=None,
        t_submit=time.monotonic() if t_submit is None else t_submit,
        cost=cost)


def _cost(s):
    return s.cost


# ------------------------------------------------- fair share (pure ledger)
class TestFairShare:
    def _run_steps(self, sched, lanes, steps, budget):
        """Admission rounds with every listed lane kept saturated."""
        for _ in range(steps):
            for tenant, prio in lanes:
                while sched.tenant_depth(tenant) < 4:
                    assert sched.enqueue(_seq(tenant, prio)) == 0
            b = budget
            while True:
                head = sched.peek(b, _cost)
                if head is None:
                    break
                sched.commit(head, head.cost)
                b -= head.cost

    def test_equal_weights_split_tokens_evenly(self):
        sched = TenantScheduler(QosConfig(tenants={"a": 1.0, "b": 1.0}))
        self._run_steps(sched, [("a", 0), ("b", 0)], steps=100, budget=32)
        snap = sched.snapshot()["tenants"]
        total = snap["a"]["admitted_tokens"] + snap["b"]["admitted_tokens"]
        assert total == 100 * 32
        assert abs(snap["a"]["token_share"] - 0.5) <= 0.05  # <=10% skew

    def test_weighted_share_converges_to_weight_ratio(self):
        sched = TenantScheduler(QosConfig(tenants={"heavy": 3.0,
                                                   "light": 1.0}))
        self._run_steps(sched, [("heavy", 0), ("light", 0)],
                        steps=100, budget=64)
        snap = sched.snapshot()["tenants"]
        assert abs(snap["heavy"]["token_share"] - 0.75) <= 0.05

    def test_idle_share_redistributes_and_is_reclaimed_within_one_step(self):
        sched = TenantScheduler(QosConfig(tenants={"a": 1.0, "b": 1.0}))
        self._run_steps(sched, [("a", 0), ("b", 0)], steps=10, budget=32)
        # b goes idle: drain its lane, keep a saturated
        for s in list(sched.iter_waiting()):
            if s.tenant_id == "b":
                sched.drop(s)
        before = sched.snapshot()["tenants"]["a"]["admitted_tokens"]
        self._run_steps(sched, [("a", 0)], steps=10, budget=32)
        after = sched.snapshot()["tenants"]["a"]["admitted_tokens"]
        assert after - before == 10 * 32  # the idle share redistributed
        # b returns: its clamped clock competes again within ONE step —
        # no catch-up burst, but no lockout either
        assert sched.enqueue(_seq("b")) == 0
        admitted, b = [], 32
        while True:
            head = sched.peek(b, _cost)
            if head is None:
                break
            sched.commit(head, head.cost)
            b -= head.cost
            admitted.append(head.tenant_id)
        assert "b" in admitted

    def test_queue_cap_sheds_retriable_per_lane(self):
        sched = TenantScheduler(QosConfig(queue_cap=2))
        assert sched.enqueue(_seq("bulk")) == 0
        assert sched.enqueue(_seq("bulk")) == 0
        assert sched.enqueue(_seq("bulk")) == errors.EOVERCROWDED
        assert sched.snapshot()["tenants"]["bulk"]["shed"] == 1
        assert sched.enqueue(_seq("other")) == 0  # the cap is per lane

    def test_deadline_rechecked_at_admission_boundary(self):
        sched = TenantScheduler(QosConfig())
        dead = time.monotonic() - 0.1
        assert sched.admission_check("t", 0, deadline_mono=dead) \
            == errors.ERPCTIMEDOUT

    def test_protected_carveout_above_ceiling(self):
        sched = TenantScheduler(QosConfig(ceiling_start=4.0,
                                          ceiling_min=2.0,
                                          protected_priority=1))
        for _ in range(4):
            assert sched.enqueue(_seq("bulk", 0)) == 0
        # best-effort load sits at the ceiling: bulk sheds, protected rides
        assert sched.admission_check("bulk", 0) == errors.EOVERCROWDED
        assert sched.admission_check("prod", 1) == 0
        for _ in range(4):
            assert sched.enqueue(_seq("prod", 1)) == 0
        # the protected lane ALONE now exceeds the ceiling: it sheds too
        assert sched.admission_check("prod", 1) == errors.EOVERCROWDED

    def test_shed_victims_best_effort_oldest_first(self):
        sched = TenantScheduler(QosConfig(protected_priority=1))
        now = time.monotonic()
        old = _seq("bulk", 0, t_submit=now - 2.0)
        mid = _seq("bulk", 0, t_submit=now - 1.0)
        prod = _seq("prod", 1, t_submit=now - 3.0)
        for s in (prod, mid, old):
            assert sched.enqueue(s) == 0
        assert sched.shed_victims(2) == [old, mid]  # age order, p0 first
        # protected is never shed while it fits under the ceiling
        assert sched.shed_victims(5) == []
        assert sched.tenant_depth("prod") == 1


# -------------------------------------------------------- gradient limiter
class TestLimiter:
    def test_rising_wait_shrinks_multiplicatively(self):
        lim = QosLimiter(QosConfig(ceiling_start=8.0, ceiling_min=2.0))
        # first sample IS the floor: gradient 1, additive probe
        assert lim.observe(1000.0, inflight=0) == pytest.approx(9.0)
        # avg EMA 5000, min drifted to 1010 -> gradient clamps at 0.5
        assert lim.observe(9000.0, inflight=0) == pytest.approx(5.5)

    def test_floor_and_recovery_gated_by_inflight(self):
        lim = QosLimiter(QosConfig(ceiling_start=4.0, ceiling_min=2.0,
                                   ceiling_max=6.0))
        for _ in range(50):
            lim.observe(lim._avg_wait_us * 10 + 1000.0, inflight=0)
        assert lim.ceiling == pytest.approx(2.0)  # clamped at the floor
        # an empty sample under saturation is NOT evidence of headroom
        assert lim.observe(0.0, inflight=10) == pytest.approx(2.0)
        for _ in range(50):
            lim.observe(0.0, inflight=0)
        assert lim.ceiling == 6.0  # additive recovery up to the max


# ------------------------------------------------ governor (stub engine)
class TestGovernor:
    def test_tick_sheds_queued_best_effort_down_to_ceiling(self):
        qos = QosConfig(ceiling_start=8.0, ceiling_min=2.0, queue_cap=32)
        eng = _stub_engine(start=False, qos=qos)
        eng.running = True
        subs = []

        def submit(tenant, prio):
            cntl = _Cntl()
            ev = threading.Event()
            code, seq = eng.submit(eng.model.synth_prompt(4), 2,
                                   cntl=cntl, tenant_id=tenant,
                                   priority=prio,
                                   done=lambda r, e=ev: e.set())
            assert code == 0
            subs.append((cntl, ev, seq))
            return seq

        try:
            submit("prod", 1)
            bulk = [submit("bulk", 0) for _ in range(5)]
            gov = eng._qos_governor
            assert gov is not None
            assert eng.queue_depth == 6
            gov.tick(sample_us=1000.0)  # warms the floor: no shed
            assert eng.queue_depth == 6
            gov.tick(sample_us=30000.0)  # 30x the floor: shrink + shed
            ceiling = eng.qos.limiter.ceiling
            assert ceiling < 6.0
            shed = [s for (c, e, s) in subs
                    if c.code == errors.EOVERCROWDED]
            assert len(shed) == 6 - int(ceiling)
            assert all(s.tenant_id == "bulk" for s in shed)
            assert shed[0] is bulk[0]  # oldest best-effort went first
            assert subs[0][0].code == 0  # the protected request survived
            assert gov.sheds == len(shed)
            # the shed done-callbacks already fired (retriable contract)
            for (c, e, s) in subs:
                if c.code == errors.EOVERCROWDED:
                    assert e.wait(5.0)
        finally:
            eng.running = False
            eng._abort_all_locked_out(errors.ELOGOFF, "teardown")
        eng.kv.assert_idle("governor teardown")  # zero leaked KV blocks

    def test_governor_rides_the_sampler_hook_lifecycle(self):
        from brpc_tpu.metrics.series import global_series

        eng = _stub_engine(qos=QosConfig())
        try:
            assert eng._qos_governor in global_series().post_tick_hooks
        finally:
            eng.stop()
        assert eng._qos_governor not in global_series().post_tick_hooks
        eng.kv.assert_idle("hook lifecycle teardown")


# ------------------------------------------------------------------- chaos
@pytest.fixture()
def fault_enabled():
    _flags.set_flag("fault_injection_enabled", True)
    yield
    fault.disarm_all()
    _flags.set_flag("fault_injection_enabled", False)


@pytest.mark.chaos
class TestQosChaos:
    def test_burst_fault_sheds_bulk_protects_prod_and_recovers(
            self, fault_enabled):
        qos = QosConfig(tenants={"prod": 8.0, "bulk": 1.0}, queue_cap=4,
                        protected_priority=1)
        eng = _stub_engine(step_s=0.002, max_batch=4, token_budget=64,
                           num_blocks=64, qos=qos)
        try:
            def prod_once():
                cntl = _Cntl()
                ev = threading.Event()
                t0 = time.monotonic()
                code, _ = eng.submit(eng.model.synth_prompt(8), 4,
                                     cntl=cntl, tenant_id="prod",
                                     priority=1,
                                     done=lambda r, e=ev: e.set())
                assert code == 0
                assert ev.wait(30)
                assert cntl.code == 0
                return time.monotonic() - t0

            unloaded = sorted(prod_once() for _ in range(8))[-1]

            # each real bulk submit fans out 7 synthetic clones: 96
            # offered against a lane capped at 4
            fault.arm("serving.qos.burst", mode="always", factor=8,
                      match={"tenant": "bulk"})
            for _ in range(12):
                eng.submit(eng.model.synth_prompt(8), 4, tenant_id="bulk",
                           priority=0, done=lambda r: None)
            burst_p99 = sorted(prod_once() for _ in range(8))[-1]
            snap = eng.qos.snapshot()["tenants"]
            assert snap["bulk"]["shed"] > 0  # the flood shed EOVERCROWDED
            assert snap["prod"]["shed"] == 0  # the protected lane never did
            # protected p99 holds within bound under the armed burst
            assert burst_p99 <= unloaded * 4 + 0.05, (burst_p99, unloaded)

            fault.disarm_all()
            # recovery: the lane drains and a plain bulk request completes
            deadline = time.monotonic() + 30
            while (eng.queue_depth or eng.running_count) \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            cntl = _Cntl()
            ev = threading.Event()
            code, _ = eng.submit(eng.model.synth_prompt(8), 4, cntl=cntl,
                                 tenant_id="bulk", priority=0,
                                 done=lambda r, e=ev: e.set())
            assert code == 0 and ev.wait(30) and cntl.code == 0
        finally:
            eng.stop()
        eng.kv.assert_idle("post burst fault")  # zero leaked KV blocks


# ----------------------------------------------------- identity on the wire
def _serving_server(eng):
    from brpc_tpu.rpc import Server

    return Server().add_service(LlmServingService(eng)).start("127.0.0.1:0")


class TestWireIdentity:
    def test_tenant_and_priority_ride_request_meta(self):
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.rpc import Channel, ChannelOptions, Controller, Stub

        eng = _stub_engine(qos=QosConfig(tenants={"prod": 2.0}))
        server = _serving_server(eng)
        try:
            ch = Channel(ChannelOptions(protocol="trpc_std",
                                        timeout_ms=30000))
            ch.init(str(server.listen_endpoint()))
            stub = Stub(ch, serving_pb2.DESCRIPTOR
                        .services_by_name["LlmService"])
            cntl = Controller()
            cntl.tenant_id = "prod"
            cntl.priority = 1
            resp = stub.Generate(serving_pb2.GenerateRequest(
                prompt_len=8, max_new_tokens=2), controller=cntl)
            assert not cntl.failed() and len(resp.tokens) == 2
            # no identity -> the default lane bills it
            resp = stub.Generate(serving_pb2.GenerateRequest(
                prompt_len=8, max_new_tokens=2), controller=Controller())
            assert len(resp.tokens) == 2
            snap = eng.qos.snapshot()["tenants"]
            assert snap["prod"]["admitted"] == 1
            assert snap[DEFAULT_TENANT]["admitted"] == 1
        finally:
            server.stop()
            server.join(timeout=2)
            eng.stop()
        eng.kv.assert_idle("wire identity teardown")

    def test_overload_corpus_records_identity(self):
        from tools import record_serving_corpus_overload as recorder
        from tools.rpc_replay import load_items

        items, skipped = load_items(CORPUS_OVERLOAD)
        assert skipped == 0 and len(items) == len(recorder.SCHEDULE)
        got = collections.Counter((i.tenant, i.priority) for i in items)
        want = collections.Counter(
            (t, p) for _, t, p, _, _ in recorder.SCHEDULE)
        assert got == want

    def test_replay_overrides_restamp_every_record(self, tmp_path):
        from tools import rpc_replay

        eng = _stub_engine(max_batch=8, token_budget=512, num_blocks=256,
                           qos=QosConfig(queue_cap=64))
        server = _serving_server(eng)
        try:
            out = tmp_path / "replay.json"
            rc = rpc_replay.main([
                "--dump", CORPUS_OVERLOAD,
                "--server", str(server.listen_endpoint()),
                "--rate-mult", "20", "--timeout-ms", "30000",
                "--report-interval", "0",
                "--tenant-override", "probe", "--priority-override", "1",
                "--json-out", str(out)])
            assert rc == 0
            data = json.loads(out.read_text())
            assert list(data["tenants"]) == ["probe"]
            assert data["tenants"]["probe"]["ok"] == data["sent"]
            snap = eng.qos.snapshot()["tenants"]
            assert snap["probe"]["admitted"] == data["sent"]
        finally:
            server.stop()
            server.join(timeout=2)
            eng.stop()
        eng.kv.assert_idle("override replay teardown")


# ----------------------------------------------------------- observability
class TestObservability:
    def test_snapshot_and_builtin_page_render_qos(self):
        eng = _stub_engine(qos=QosConfig(tenants={"prod": 2.0}))
        try:
            cntl = _Cntl()
            ev = threading.Event()
            code, _ = eng.submit(eng.model.synth_prompt(8), 2, cntl=cntl,
                                 tenant_id="prod", priority=1,
                                 done=lambda r, e=ev: e.set())
            assert code == 0 and ev.wait(30)
            snap = eng.snapshot()["qos"]
            assert snap["tenants"]["prod"]["admitted"] >= 1
            assert {"ceiling", "min_wait_us", "avg_wait_us", "updates"} \
                <= set(snap["limiter"])

            from brpc_tpu.builtin.services import serving_service
            http = types.SimpleNamespace(query={}, path="/serving")
            _st, _ct, body = serving_service(None, http)
            assert "qos: ceiling=" in body
            assert "[tenant prod]" in body
            http = types.SimpleNamespace(query={"format": "json"},
                                         path="/serving")
            _st, ct, body = serving_service(None, http)
            assert "json" in ct
            snaps = json.loads(body)["engines"]
            assert any(s.get("qos") for s in snaps)
        finally:
            eng.stop()
        eng.kv.assert_idle("qos page teardown")

    def test_qos_vars_and_gauges_track_live_engines(self):
        from brpc_tpu.serving import qos as qos_mod

        qos = QosConfig(tenants={"prod": 2.0}, ceiling_start=6.0,
                        ceiling_min=2.0, ceiling_max=6.0)
        eng = _stub_engine(step_s=0.02, max_batch=1, qos=qos)
        tvars = qos_mod._vars_for_tenant("prod")
        a0 = tvars["admitted"].get_value()
        s0 = tvars["shed"].get_value()
        evs = []
        try:
            sheds = 0
            for _ in range(10):
                ev = threading.Event()
                code, _ = eng.submit(eng.model.synth_prompt(4), 4,
                                     tenant_id="prod", priority=0,
                                     done=lambda r, e=ev: e.set())
                if code == errors.EOVERCROWDED:
                    sheds += 1
                else:
                    evs.append(ev)
            assert sheds >= 4  # 10 offered vs a ceiling of 6
            with eng._cv:  # atomic vs the step loop
                assert tvars["depth"].get_value() \
                    == eng.qos.tenant_depth("prod")
                assert qos_mod.g_serving_qos_occupancy.get_value() > 0.0
                assert qos_mod.g_serving_qos_max_wait_ms.get_value() >= 0.0
            for ev in evs:
                assert ev.wait(30)
            assert tvars["admitted"].get_value() - a0 == len(evs)
            assert tvars["shed"].get_value() - s0 == sheds
        finally:
            eng.stop()
        eng.kv.assert_idle("qos vars teardown")


def test_qos_starvation_rule_installed_with_reloadable_bound():
    from brpc_tpu.metrics.watch import (KIND_THRESHOLD, global_watch,
                                        install_default_rules)

    install_default_rules()
    rule = {r.name: r
            for r in global_watch().rules()}["serving_qos_starvation"]
    assert rule.var == "g_serving_qos_max_wait_ms"
    assert rule.kind == KIND_THRESHOLD and rule.op == ">"
    assert rule.value_fn is not None
    assert rule.value_fn() == pytest.approx(
        _flags.get("serving_qos_starvation_ms"))
    _flags.set_flag("serving_qos_starvation_ms", "500")
    try:
        assert rule.value_fn() == pytest.approx(500.0)
    finally:
        _flags.set_flag("serving_qos_starvation_ms", "2000")


# ------------------------------------- corpus sweep through the tier-1 gate
def test_overload_corpus_replays_clean_through_qos_at_recorded_rate(
        tmp_path):
    """The committed overload corpus at the RECORDED rate against the
    real model WITH QoS armed: inside capacity nothing sheds, the replay
    restamps both tenants onto their lanes, and trace_diff finds no
    phase regression at p50 with a 50ms floor — the same tier-1 gate the
    base serving corpus rides."""
    from brpc_tpu.metrics.collector import global_collector
    from brpc_tpu.trace import span as _span
    from tools import record_serving_corpus_overload as recorder
    from tools import rpc_replay, trace_diff

    dumps = [f for f in os.listdir(CORPUS_OVERLOAD)
             if f.endswith(".dump")]
    assert dumps, ("committed overload corpus missing; run "
                   "tools/record_serving_corpus_overload.py")

    _flags.set_flag("rpcz_sample_ratio", "1.0")
    _flags.set_flag("collector_max_samples_per_second", "0")
    global_collector()._deny_until = 0.0
    # ceiling floor above the corpus's 40-request worst case: this test
    # gates identity restamp + trace parity at the recorded rate, not
    # the closed loop (the overload test owns that) — queue waits here
    # run ~1s by construction, and on a contended CI box enough 1 Hz
    # governor ticks land inside the replay to crush an unfloored
    # ceiling below peak inflight and shed work that IS inside capacity
    engine = recorder.build_engine(qos=QosConfig(
        tenants={"prod": 4.0, "batch": 1.0}, queue_cap=64,
        ceiling_min=48.0))
    try:
        recorder.warm_engine(engine)
        _span.reset_for_test()
        server = _serving_server(engine)
        try:
            rc = rpc_replay.main([
                "--dump", CORPUS_OVERLOAD,
                "--server", str(server.listen_endpoint()),
                "--rate-mult", "1", "--timeout-ms", "30000",
                "--report-interval", "0"])
            assert rc == 0  # inside capacity: nothing shed, nothing failed
            deadline = time.monotonic() + 5.0
            while (len([s for s in _span.recent_spans(200)
                        if s.kind == _span.KIND_SERVER])
                   < len(recorder.SCHEDULE)
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        finally:
            server.stop()
            server.join(timeout=2)
        # the replay restamped the recorded identity: both lanes billed
        snap = engine.qos.snapshot()["tenants"]
        n_prod = sum(1 for r in recorder.SCHEDULE
                     if r[1] == recorder.PROD)
        assert snap["prod"]["admitted"] == n_prod
        assert snap["batch"]["admitted"] == len(recorder.SCHEDULE) - n_prod
        # the wait in the engine's queue is the replay's load, not the
        # server's phase: the recorded corpus predates it and the gate
        # stays on what it held (tests/test_serving_spans.py holds the wait
        # to the engine's own counters)
        docs = [s.to_dict() for s in _span.recent_spans(200)]
        for d in docs:
            d["phases"].pop("serving_queue_us", None)
        replayed = tmp_path / "replayed.json"
        replayed.write_text(json.dumps({"spans": docs}))
        rc = trace_diff.main([CORPUS_OVERLOAD, str(replayed),
                              "--percentile", "50",
                              "--min-delta-us", "50000"])
        assert rc == 0
    finally:
        engine.stop()
        engine.kv.assert_idle("overload corpus gate teardown")
        engine.model.close()
        _flags.set_flag("rpcz_sample_ratio", "1.0")
        _flags.set_flag("collector_max_samples_per_second", "1000")


# --------------------------------------- closed-loop overload (acceptance)
# what one decode step of the recorded engine took: it turns the corpus's
# arrival offsets into steps, and steps spent queued into the governor's
# sample
STEP_S = 0.005


class _ByHand:
    """A stub-model engine whose loop the test turns: arrivals, admission,
    the model's step and the governor's tick all fall on step boundaries,
    so a latency is a count of steps and no run differs from the last."""

    def __init__(self, qos, **cfg):
        self.eng = _stub_engine(start=False, qos=qos, num_blocks=256,
                                max_queue=256, **cfg)
        self.eng.running = True
        self.step = 0
        self.refused = collections.Counter()    # tenant -> submits refused
        self.finished = []                      # (tenant, code, steps)
        self._at = {}                           # seq_id -> step submitted
        self._waits = []                        # steps queued, since a tick

    def submit(self, tenant, priority, plen, max_new):
        at, cntl = self.step, _Cntl()
        code, seq = self.eng.submit(
            self.eng.model.synth_prompt(plen), max_new, cntl=cntl,
            tenant_id=tenant, priority=priority,
            done=lambda _r: self.finished.append(
                (tenant, cntl.code, self.step - at)))
        if code != 0:
            assert code == errors.EOVERCROWDED  # retriable, never an error
            self.refused[tenant] += 1
        else:
            self._at[seq.seq_id] = at

    def turn(self):
        eng = self.eng
        with eng._cv:
            admitted = eng._admit_locked()
        self._waits += [self.step - self._at[s.seq_id] for s in admitted]
        if admitted or eng._running:
            with eng.pool_gate:
                eng._step(admitted)
        self.step += 1

    def tick(self):
        """The governor's tick, fed the mean wait of what was admitted
        since the last one (a step in the queue reads as STEP_S)."""
        waits, self._waits = self._waits, []
        self.eng._qos_governor.tick(
            sample_us=(1 + sum(waits) / len(waits)) * STEP_S * 1e6
            if waits else 0.0)

    def busy(self):
        return bool(self.eng.queue_depth or self.eng._running)

    def worst(self, tenant):
        got = [n for t, code, n in self.finished if t == tenant]
        assert got and all(code == 0 for t, code, _ in self.finished
                           if t == tenant)
        return max(got)

    def close(self, what):
        self.eng.running = False
        self.eng._abort_all_locked_out(errors.ELOGOFF, "test teardown")
        self.eng.kv.assert_idle(what)


def _replay_by_steps(qos, rate_mult, tick_every=20):
    """The overload corpus's arrivals at ``rate_mult`` times the recorded
    rate through a saturable engine (5 rows, 64 tokens a step)."""
    from tools import record_serving_corpus_overload as recorder

    run = _ByHand(qos, max_batch=5, token_budget=64)
    arrivals = collections.deque(
        (int(off / (rate_mult * STEP_S)), tenant, prio, plen, max_new)
        for off, tenant, prio, plen, max_new in recorder.SCHEDULE)
    while arrivals or run.busy():
        while arrivals and arrivals[0][0] <= run.step:
            run.submit(*arrivals.popleft()[1:])
        run.turn()
        if qos is not None and run.step % tick_every == 0:
            run.tick()
        assert run.step < 10_000, "the replay never drained"
    return run


def test_closed_loop_overload_protects_prod_and_sheds_batch():
    """The acceptance gate, in steps: the diurnal-overload corpus at 2x
    the recorded rate against a saturable engine. With QoS armed the
    protected tenant's worst request stays within 1.5x its unloaded worst
    while best-effort sheds EOVERCROWDED; the identical wave against the
    same engine with QoS off violates the bound."""
    # ceiling pinned one below max_batch: best-effort can never occupy
    # every slot, so the protected lane always has admission headroom —
    # the closed-loop's dynamic version of this is exercised above
    qos_cfg = QosConfig(tenants={"prod": 8.0, "batch": 1.0}, queue_cap=8,
                        protected_priority=1, ceiling_start=3.0,
                        ceiling_min=2.0, ceiling_max=3.0)
    # unloaded baseline: a quarter of the recorded rate leaves every
    # request effectively alone on the engine
    base = _replay_by_steps(qos_cfg, 0.25)
    assert not base.refused
    unloaded = base.worst("prod")
    base.close("unloaded teardown")

    # 2x the recorded rate: the batch burst pushes past saturation
    over = _replay_by_steps(qos_cfg, 2)
    snap = over.eng.qos.snapshot()["tenants"]
    assert over.refused["prod"] == 0 and snap["prod"]["shed"] == 0
    assert snap["batch"]["shed"] > 0     # best-effort shed EOVERCROWDED
    # sheds, not errors: every batch request was refused at the door,
    # shed from the queue by the governor, or served
    codes = collections.Counter(code for t, code, _ in over.finished
                                if t == "batch")
    assert set(codes) <= {0, errors.EOVERCROWDED}
    assert snap["batch"]["shed"] == (over.refused["batch"]
                                     + codes[errors.EOVERCROWDED])
    assert over.worst("prod") <= 1.5 * unloaded, (over.worst("prod"),
                                                  unloaded)
    over.close("overload qos teardown")

    # the control arm: same engine shape, same wave, QoS off — the
    # burst queues ahead of the protected traffic and the bound breaks
    fifo = _replay_by_steps(None, 2)
    assert not fifo.refused
    assert fifo.worst("prod") > 1.5 * unloaded, (fifo.worst("prod"),
                                                 unloaded)
    fifo.close("overload fifo teardown")


def test_a_best_effort_flood_sheds_on_its_own_lane_only():
    """A flood three times the lane's cap, then steady protected work,
    with the governor ticked every four steps on the waits it caused:
    every shed, at the door or from the queue, is the flood's; the
    protected lane sheds none and every one of its requests is served."""
    qos_cfg = QosConfig(tenants={"prod": 4.0, "batch": 1.0}, queue_cap=12,
                        protected_priority=1, ceiling_start=16.0)
    run = _ByHand(qos_cfg, max_batch=2, token_budget=64)
    flood, prod_reqs = 36, 8
    for _ in range(flood):
        run.submit("batch", 0, 16, 8)
    assert run.refused["batch"] == flood - 12    # the lane's cap, no more
    sent = 0
    while sent < prod_reqs or run.busy():
        if sent < prod_reqs:
            run.submit("prod", 1, 16, 8)
            sent += 1
        run.turn()
        if run.step % 4 == 0:
            run.tick()
        assert run.step < 10_000, "the flood never drained"
    gov = run.eng._qos_governor
    snap = run.eng.qos.snapshot()["tenants"]
    assert gov.sheds > 0                         # the loop did close
    assert run.refused["prod"] == 0 and snap["prod"]["shed"] == 0
    assert snap["batch"]["shed"] == run.refused["batch"] + gov.sheds
    assert snap["prod"]["admitted"] == prod_reqs
    assert snap["batch"]["admitted"] + snap["batch"]["shed"] == flood
    assert [c for t, c, _ in run.finished if t == "prod"] == [0] * prod_reqs
    run.close("flood teardown")


@pytest.mark.parametrize("weights", [(3.0, 1.0), (1.0, 1.0)],
                         ids=["3to1", "1to1"])
def test_prod_share_of_admitted_tokens_follows_its_weight(weights):
    """Through the engine's own admission, both lanes kept backlogged and
    the step's token budget the only limit: over 100 steps prod's share
    of the admitted tokens is its weight's share, to 5 points."""
    qos_cfg = QosConfig(tenants={"prod": weights[0], "batch": weights[1]})
    run = _ByHand(qos_cfg, max_batch=8, token_budget=32)
    for _ in range(100):
        for tenant in ("prod", "batch"):
            while run.eng.qos.tenant_depth(tenant) < 4:
                run.submit(tenant, 0, 16, 1)
        run.turn()
    snap = run.eng.qos.snapshot()["tenants"]
    assert snap["prod"]["admitted_tokens"] \
        + snap["batch"]["admitted_tokens"] == 100 * 32
    want = weights[0] / sum(weights)
    assert abs(snap["prod"]["token_share"] - want) <= 0.05, snap
    assert not run.refused
    run.close("share teardown")
