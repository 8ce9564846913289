"""Time-series metrics plane (ISSUE 12) — series tiers under a fake clock,
the sampler-tick sweep with opt-outs, watch rules firing/clearing, the
/vars series+SVG contract, fleet merge (unit + workers=2 e2e), and the
Prometheus exposition round-trip."""

import json
import time

import pytest

from brpc_tpu import flags
from brpc_tpu.metrics import prometheus_text
from brpc_tpu.metrics.reducer import Adder, Maxer
from brpc_tpu.metrics.series import (
    HOUR_SAMPLES,
    MINUTE_SAMPLES,
    SECOND_SAMPLES,
    SeriesRegistry,
    VarSeries,
    global_series,
)
from brpc_tpu.metrics.status import PassiveStatus, Status
from brpc_tpu.metrics.watch import (
    STATE_FIRING,
    STATE_NO_DATA,
    STATE_OK,
    WatchRegistry,
    WatchRule,
)
from tests.test_shard import shard_flags  # noqa: F401 (fixture reuse)


@pytest.fixture(autouse=True)
def _clean_state(empty_registry):
    global_series().clear()
    yield
    global_series().clear()


class _Http:
    """Minimal HttpMessage stand-in for invoking builtin handlers."""

    def __init__(self, path, query=None, headers=None):
        self.path = path
        self.query = query or {}
        self.headers = headers or {}

    def header(self, name, default=""):
        return self.headers.get(name, default)


# ------------------------------------------------------------- tier rings
class TestVarSeriesTiers:
    def test_identity_prefill_and_shapes(self):
        s = VarSeries()
        d = s.to_dict()
        assert d["second"] == [0] * SECOND_SAMPLES
        assert d["minute"] == [0] * MINUTE_SAMPLES
        assert d["hour"] == [0] * HOUR_SAMPLES
        assert d["count"] == 0

    def test_second_ring_wrap_keeps_newest_60(self):
        s = VarSeries()
        for i in range(70):
            s.append(i)
        assert s.to_dict()["second"] == list(range(10, 70))

    def test_minute_rollup_exact_avg(self):
        s = VarSeries()
        for i in range(1, 61):          # 1..60, avg = 30.5 -> int floor 30
            s.append(i)
        d = s.to_dict()
        assert d["minute"][-1] == 30
        assert d["minute"][:-1] == [0] * (MINUTE_SAMPLES - 1)

    def test_minute_rollup_float_keeps_fraction(self):
        s = VarSeries()
        for i in range(1, 61):
            s.append(float(i))
        assert s.to_dict()["minute"][-1] == pytest.approx(30.5)
        assert s.to_dict()["float"] is True

    def test_hour_rollup_exact(self):
        s = VarSeries()
        for _ in range(SECOND_SAMPLES * MINUTE_SAMPLES):
            s.append(7)
        d = s.to_dict()
        assert d["hour"][-1] == 7
        assert d["minute"] == [7] * MINUTE_SAMPLES
        assert d["count"] == 3600

    def test_max_reduce_op(self):
        s = VarSeries(reduce_op="max")
        for i in range(60):
            s.append(i)
        assert s.to_dict()["minute"][-1] == 59

    def test_unknown_reduce_falls_back_to_avg(self):
        assert VarSeries(reduce_op="bogus").reduce_op == "avg"


# ------------------------------------------------------------- the sweep
class TestSeriesRegistry:
    def test_sweep_appends_numeric_exposed_vars(self):
        a = Adder("t_series_adder")
        reg = SeriesRegistry()
        for i in range(5):
            a.put(2)
            reg.tick()
        d = reg.dump("t_series_*")["t_series_adder"]
        assert d["count"] == 5
        assert d["second"][-5:] == [2, 4, 6, 8, 10]
        assert d["last"] == 10

    def test_non_numeric_and_bool_vars_skipped(self):
        Status("hello").expose("t_series_str")
        Status(True).expose("t_series_bool")
        Status(3).expose("t_series_int")
        reg = SeriesRegistry()
        reg.tick()
        names = reg.names()
        assert "t_series_int" in names
        assert "t_series_str" not in names
        assert "t_series_bool" not in names

    def test_var_attr_opt_out_honored(self):
        v = Status(1)
        v.series_opt_out = True
        v.expose("t_series_optout_attr")
        reg = SeriesRegistry()
        reg.tick()
        assert "t_series_optout_attr" not in reg.names()

    def test_programmatic_glob_opt_out_drops_existing(self):
        Status(1).expose("worker0_t_x")
        Status(1).expose("t_series_kept")
        reg = SeriesRegistry()
        reg.tick()
        assert "worker0_t_x" in reg.names()
        reg.opt_out("worker*_*")
        assert "worker0_t_x" not in reg.names()
        reg.tick()
        assert "worker0_t_x" not in reg.names()
        assert "t_series_kept" in reg.names()

    def test_flag_glob_opt_out(self):
        Status(1).expose("t_highcard_x")
        flags.set_flag("var_series_optout", "t_highcard_*")
        try:
            reg = SeriesRegistry()
            reg.tick()
            assert "t_highcard_x" not in reg.names()
        finally:
            flags.set_flag("var_series_optout", "")

    def test_enabled_flag_gates_sweep(self):
        Status(1).expose("t_series_gated")
        reg = SeriesRegistry()
        flags.set_flag("var_series_enabled", False)
        try:
            reg.tick()
            assert reg.names() == []
            assert reg.ticks == 0
        finally:
            flags.set_flag("var_series_enabled", True)
        reg.tick()
        assert "t_series_gated" in reg.names()

    def test_hidden_var_series_gced(self):
        v = Status(1).expose("t_series_gc")
        reg = SeriesRegistry()
        reg.tick()
        assert "t_series_gc" in reg.names()
        v.hide()
        reg.tick()
        assert "t_series_gc" not in reg.names()

    def test_series_reduce_attr_picked_up(self):
        m = Maxer()
        v = PassiveStatus(m.get_value)
        v.series_reduce = "max"
        v.expose("t_series_maxer")
        reg = SeriesRegistry()
        for i in range(60):
            m.put(i)
            reg.tick()
        assert reg.dump("t_series_maxer")["t_series_maxer"]["minute"][-1] == 59


# ------------------------------------------------------------ watch rules
class TestWatchRules:
    def _reg_with_var(self, name="t_watch_v"):
        self.status = Status(0)
        self.status.expose(name)
        return SeriesRegistry()

    def test_threshold_fires_and_clears_on_spike(self):
        reg = self._reg_with_var()
        w = WatchRegistry()
        r = w.add(WatchRule("spike", "t_watch_v", "threshold", ">", 10,
                            for_ticks=2, clear_ticks=3))
        reg.tick()
        w.evaluate_all(reg)
        assert r.state == STATE_OK
        self.status.set_value(50)            # the spike
        reg.tick()
        w.evaluate_all(reg)
        assert r.state == STATE_OK           # debounce: 1 of 2 ticks
        reg.tick()
        w.evaluate_all(reg)
        assert r.state == STATE_FIRING
        self.status.set_value(0)             # drain
        for _ in range(2):
            reg.tick()
            w.evaluate_all(reg)
            assert r.state == STATE_FIRING   # 2 of 3 clear ticks
        reg.tick()
        w.evaluate_all(reg)
        assert r.state == STATE_OK
        assert r.transitions == 2

    def test_delta_kind(self):
        reg = self._reg_with_var()
        w = WatchRegistry()
        r = w.add(WatchRule("jump", "t_watch_v", "delta", ">=", 5,
                            window_s=10))
        for i in range(3):
            self.status.set_value(i)         # +1/tick: delta below 5
            reg.tick()
            w.evaluate_all(reg)
        assert r.state == STATE_OK
        self.status.set_value(100)
        reg.tick()
        w.evaluate_all(reg)
        assert r.state == STATE_FIRING
        assert r.observed >= 5

    def test_rate_kind_normalizes_per_second(self):
        reg = self._reg_with_var()
        w = WatchRegistry()
        r = w.add(WatchRule("fast", "t_watch_v", "rate", ">", 3,
                            window_s=4))
        value = 0
        for _ in range(6):
            value += 10                      # 10/s >= 3/s
            self.status.set_value(value)
            reg.tick()
            w.evaluate_all(reg)
        assert r.state == STATE_FIRING
        assert r.observed == pytest.approx(10.0)

    def test_no_data_until_var_appears(self):
        reg = SeriesRegistry()
        w = WatchRegistry()
        r = w.add(WatchRule("ghost", "t_watch_missing", "threshold", ">", 0))
        reg.tick()
        w.evaluate_all(reg)
        assert r.state == STATE_NO_DATA

    def test_firing_emits_structured_span(self):
        from brpc_tpu.trace import span as _span

        _span.reset_for_test()
        reg = self._reg_with_var()
        w = WatchRegistry()
        w.add(WatchRule("spanful", "t_watch_v", "threshold", ">", 10,
                        for_ticks=1))
        self.status.set_value(99)
        reg.tick()
        w.evaluate_all(reg)
        spans = _span.recent_spans(10, method="spanful")
        assert spans, "watch transition must land in the span DB"
        _off, ev_name, fields = spans[0].events[0]
        assert ev_name == "watch_firing"
        assert fields["rule"] == "spanful"
        assert fields["state"] == STATE_FIRING

    def test_bad_rule_params_rejected(self):
        with pytest.raises(ValueError):
            WatchRule("x", "v", "nope", ">", 1)
        with pytest.raises(ValueError):
            WatchRule("x", "v", "threshold", "~", 1)
        with pytest.raises(ValueError):
            WatchRule("x", "v", "threshold", ">", 1, for_ticks=0)

    def test_post_tick_hook_runs_watch_in_sampler_tick(self):
        reg = self._reg_with_var()
        w = WatchRegistry()
        r = w.add(WatchRule("hooked", "t_watch_v", "threshold", ">", 10,
                            for_ticks=1))
        reg.post_tick_hooks.append(w.evaluate_all)
        self.status.set_value(42)
        reg.tick()                            # one tick: sweep + evaluate
        assert r.state == STATE_FIRING


# ----------------------------------------------------- /vars + /watch http
class TestVarsServiceContract:
    def test_series_json_glob(self):
        from brpc_tpu.builtin.services import vars_service

        a = Adder("t_http_qps")
        for i in range(3):
            a.put(5)
            global_series().tick()
        st, ct, body = vars_service(
            None, _Http("/vars", {"series": "json", "name": "t_http_*"}))
        assert st == 200 and "json" in ct
        doc = json.loads(body)
        assert doc["workers"] == 0
        sd = doc["series"]["t_http_qps"]
        # >=: the bvar-sampler daemon (started by earlier server tests in
        # the same process) may interleave extra ticks with ours
        assert sd["count"] >= 3
        assert sd["second"][-1] == 15
        assert len(sd["second"]) == SECOND_SAMPLES

    def test_detail_series_json_and_404(self):
        from brpc_tpu.builtin.services import vars_service

        Adder("t_http_one").put(1)
        global_series().tick()
        st, _, body = vars_service(
            None, _Http("/vars/t_http_one", {"series": "json"}))
        assert st == 200
        assert json.loads(body)["t_http_one"]["count"] >= 1
        st, _, _ = vars_service(
            None, _Http("/vars/t_http_missing", {"series": "json"}))
        assert st == 404

    def test_detail_svg_contract(self):
        from brpc_tpu.builtin.services import vars_service

        Adder("t_http_svg").put(3)
        global_series().tick()
        st, ct, body = vars_service(
            None, _Http("/vars/t_http_svg", {"format": "svg"}))
        assert st == 200 and ct == "image/svg+xml"
        assert body.startswith("<svg") and body.endswith("</svg>")
        for tier in ("second", "minute", "hour"):
            assert tier in body
        assert "polyline" in body

    def test_detail_html_page(self):
        from brpc_tpu.builtin.services import vars_service

        Adder("t_http_page").put(9)
        global_series().tick()
        st, ct, body = vars_service(
            None, _Http("/vars/t_http_page", {},
                        {"accept": "text/html"}))
        assert st == 200 and "html" in ct
        assert "<svg" in body and "t_http_page" in body

    def test_plain_text_mentions_series(self):
        from brpc_tpu.builtin.services import vars_service

        Adder("t_http_txt").put(2)
        global_series().tick()
        st, ct, body = vars_service(None, _Http("/vars/t_http_txt"))
        assert st == 200 and "text" in ct
        assert "t_http_txt : 2" in body
        assert "series" in body

    def test_watch_builtin_text_and_json(self):
        from brpc_tpu.builtin.services import watch_service
        from brpc_tpu.metrics.watch import global_watch

        rule = WatchRule("t_watch_http", "t_nope", "threshold", ">", 1)
        global_watch().add(rule)
        try:
            st, ct, body = watch_service(None, _Http("/watch"))
            assert st == 200 and "t_watch_http" in body
            st, ct, body = watch_service(
                None, _Http("/watch", {"format": "json"}))
            doc = json.loads(body)
            mine = [r for r in doc["rules"] if r["name"] == "t_watch_http"]
            assert mine and mine[0]["state"] == STATE_NO_DATA
            assert mine[0]["var"] == "t_nope"
        finally:
            global_watch().remove("t_watch_http")


# ------------------------------------------------------------- fleet merge
class TestFleetMergeUnit:
    def _snap(self, index, vars_):
        return json.dumps({"index": index, "vars": vars_}).encode()

    def test_sum_max_and_worker_namespacing(self):
        from brpc_tpu.metrics.variable import get_exposed
        from brpc_tpu.shard.fleet import FleetVars

        fv = FleetVars()
        try:
            fv.on_snapshot(0, self._snap(0, {
                "g_reqs": ["sum", "counter", 7],
                "peak": ["max", "gauge", 10]}))
            fv.on_snapshot(1, self._snap(1, {
                "g_reqs": ["sum", "counter", 5],
                "peak": ["max", "gauge", 30]}))
            assert get_exposed("fleet_g_reqs").get_value() == 12
            assert get_exposed("fleet_peak").get_value() == 30
            assert get_exposed("worker0_g_reqs").get_value() == 7
            assert get_exposed("worker1_g_reqs").get_value() == 5
            assert get_exposed("fleet_shard_workers").get_value() == 2
            # fleet == sum of per-worker vars for Adder-backed counters
            assert get_exposed("fleet_g_reqs").get_value() == \
                get_exposed("worker0_g_reqs").get_value() + \
                get_exposed("worker1_g_reqs").get_value()
        finally:
            fv.hide_all()

    def test_latency_merges_qps_weighted(self):
        from brpc_tpu.metrics.variable import get_exposed
        from brpc_tpu.shard.fleet import FleetVars

        fv = FleetVars()
        try:
            fv.on_snapshot(0, self._snap(0, {
                "m_latency": ["wavg_qps", "gauge", 100],
                "m_qps": ["sum", "gauge", 30]}))
            fv.on_snapshot(1, self._snap(1, {
                "m_latency": ["wavg_qps", "gauge", 200],
                "m_qps": ["sum", "gauge", 10]}))
            # (100*30 + 200*10) / 40 = 125
            assert get_exposed("fleet_m_latency").get_value() == \
                pytest.approx(125.0)
            assert get_exposed("fleet_m_qps").get_value() == 40
        finally:
            fv.hide_all()

    def test_worker_vars_opted_out_of_series(self):
        from brpc_tpu.shard.fleet import FleetVars

        fv = FleetVars()
        try:
            fv.on_snapshot(0, self._snap(0, {"g_x": ["sum", "counter", 1]}))
            reg = SeriesRegistry()
            reg.tick()
            assert "worker0_g_x" not in reg.names()   # high-cardinality
            assert "fleet_g_x" in reg.names()          # aggregate keeps series
        finally:
            fv.hide_all()

    def test_fleet_vars_carry_help_and_merge_op_derivation(self):
        from brpc_tpu.metrics.variable import get_exposed
        from brpc_tpu.shard.fleet import FleetVars, _merge_op

        fv = FleetVars()
        try:
            fv.on_snapshot(0, self._snap(0, {"g_x": ["sum", "counter", 1]}))
            var = get_exposed("fleet_g_x")
            assert "W_VARS" in var.prometheus_help
            assert var.prometheus_type == "counter"
        finally:
            fv.hide_all()
        a = Adder()
        assert _merge_op("g_anything", a) == "sum"
        assert _merge_op("x_latency", Status(0)) == "wavg_qps"
        assert _merge_op("x_latency_p99", Status(0)) == "max"
        assert _merge_op("x_max_latency", Status(0)) == "max"
        assert _merge_op("x_qps", Status(0)) == "sum"

    def test_malformed_snapshot_ignored(self):
        from brpc_tpu.shard.fleet import FleetVars

        fv = FleetVars()
        try:
            fv.on_snapshot(0, b"not json")
            fv.on_snapshot(0, b'{"index": 0, "vars": {"x": "bad"}}')
            assert fv.workers_reporting() <= 1
        finally:
            fv.hide_all()

    def test_worker_snapshot_numeric_only(self):
        from brpc_tpu.shard.fleet import worker_snapshot

        Adder("t_fleet_counter").put(3)
        Status("text").expose("t_fleet_text")
        doc = json.loads(worker_snapshot(4).decode())
        assert doc["index"] == 4
        assert doc["vars"]["t_fleet_counter"] == ["sum", "counter", 3]
        assert "t_fleet_text" not in doc["vars"]


# ------------------------------------------------- prometheus round-trip
def _parse_exposition(text):
    """A deliberately real scrape parse: TYPE/HELP comments + samples."""
    types, helps, samples = {}, {}, {}
    for line in text.splitlines():
        if not line or line.isspace():
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, mtype = rest.partition(" ")
            assert mtype in ("gauge", "counter"), line
            types[name] = mtype
        elif line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, h = rest.partition(" ")
            helps[name] = h
        elif line.startswith("#"):
            raise AssertionError(f"unknown comment: {line}")
        else:
            name_part, _, value = line.rpartition(" ")
            name = name_part.partition("{")[0]
            samples[name] = float(value)
    return types, helps, samples


class TestPrometheusRoundTrip:
    def test_window_persecond_passive_are_gauges(self):
        from brpc_tpu.metrics import PerSecond, SamplerCollector, Window

        coll = SamplerCollector(interval_s=3600)
        a = Adder("t_prom_total")
        win = Window(a, window_size=10, collector=coll)
        win.expose("t_prom_window")
        ps = PerSecond(a, window_size=10, collector=coll)
        ps.expose("t_prom_qps")
        PassiveStatus(lambda: 5).expose("t_prom_passive")
        a.put(3)
        coll.tick_all()
        types, _helps, samples = _parse_exposition(prometheus_text())
        assert types["t_prom_total"] == "counter"
        assert types["t_prom_window"] == "gauge"
        assert types["t_prom_qps"] == "gauge"
        assert types["t_prom_passive"] == "gauge"
        assert samples["t_prom_total"] == 3.0

    def test_latency_recorder_count_is_counter_rest_gauge(self):
        from brpc_tpu.metrics import LatencyRecorder

        rec = LatencyRecorder(window_size=10)
        rec.expose("t_prom_m")
        rec.record(100)
        types, _helps, _samples = _parse_exposition(prometheus_text())
        assert types["t_prom_m_count"] == "counter"
        assert types["t_prom_m_latency"] == "gauge"
        assert types["t_prom_m_qps"] == "gauge"
        assert types["t_prom_m_max_latency"] == "gauge"

    def test_fleet_vars_round_trip_with_help(self):
        from brpc_tpu.shard.fleet import FleetVars

        fv = FleetVars()
        try:
            fv.on_snapshot(0, json.dumps({
                "index": 0,
                "vars": {"g_fleet_rt": ["sum", "counter", 2]}}).encode())
            fv.on_snapshot(1, json.dumps({
                "index": 1,
                "vars": {"g_fleet_rt": ["sum", "counter", 3]}}).encode())
            types, helps, samples = _parse_exposition(prometheus_text())
            assert types["fleet_g_fleet_rt"] == "counter"
            assert "W_VARS merge" in helps["fleet_g_fleet_rt"]
            assert samples["fleet_g_fleet_rt"] == 5.0
            assert samples["worker0_g_fleet_rt"] == 2.0
            assert types["fleet_shard_workers"] == "gauge"
        finally:
            fv.hide_all()

    def test_cluster_vars_round_trip_with_merge_help(self):
        from brpc_tpu.fleet import FleetObserver

        def fetch(addr, path):
            if path != "/vars?series=json":
                return {"engines": [], "rules": []}
            n = 2 if addr == "a:1" else 3
            return {"workers": 0, "series": {},
                    "vars": {"g_cluster_rt": ["sum", "counter", n]}}

        obs = FleetObserver("list://a:1,b:2", fetch=fetch)
        try:
            assert obs.scrape_once() == 2
            types, helps, samples = _parse_exposition(prometheus_text())
            assert types["cluster_g_cluster_rt"] == "counter"
            assert "sum" in helps["cluster_g_cluster_rt"]
            assert samples["cluster_g_cluster_rt"] == 5.0
            assert types["cluster_fleet_members_live"] == "gauge"
            assert samples["cluster_fleet_members_live"] == 2.0
        finally:
            obs.hide_all()


# ------------------------------------------------------- vars_view smoke
class TestVarsViewTool:
    def test_render_from_dump(self, capsys):
        import importlib

        vars_view = importlib.import_module("tools.vars_view")
        s = VarSeries()
        for i in range(10):
            s.append(i)
        doc = {"workers": 2, "series": {"qps_a": s.to_dict()}}
        out = vars_view.render(doc, "*", "second")
        assert "qps_a" in out
        assert "workers=2" in out
        assert "min=0" in out and "last=9" in out
        # sparkline uses the unicode ramp
        assert any(ch in out for ch in "▁▂▃▄▅▆▇█")

    def test_main_reads_file(self, tmp_path, capsys):
        import importlib

        vars_view = importlib.import_module("tools.vars_view")
        s = VarSeries()
        s.append(4)
        p = tmp_path / "snap.json"
        p.write_text(json.dumps({"series": {"x": s.to_dict()}}))
        assert vars_view.main([str(p), "--name", "x"]) == 0
        out = capsys.readouterr().out
        assert "x" in out and "last=4" in out

    def test_no_match(self, tmp_path):
        import importlib

        vars_view = importlib.import_module("tools.vars_view")
        assert "no vars match" in vars_view.render({"series": {}}, "*",
                                                   "second")

    def test_render_fleet_merges_op_correctly(self):
        import importlib

        vars_view = importlib.import_module("tools.vars_view")

        def member_doc(values, op="sum", ptype="counter"):
            s = VarSeries()
            for v in values:
                s.append(v)
            return {"series": {"g_reqs": s.to_dict()},
                    "vars": {"g_reqs": [op, ptype, values[-1]]}}

        docs = {"hosta:1": member_doc([1, 2, 3]),
                "hostb:2": member_doc([10, 20, 30])}
        out = vars_view.render_fleet(docs, "g_reqs", "second")
        assert "hosta:1" in out and "hostb:2" in out
        assert "[sum]" in out
        # merged row: element-wise sum, so last = 3 + 30
        assert "=merged" in out
        assert "last=33" in out

    def test_render_fleet_max_op(self):
        import importlib

        vars_view = importlib.import_module("tools.vars_view")
        mk = lambda v: {"series": {"p99": dict(VarSeries().to_dict(),
                                               second=[v], last=v)},
                        "vars": {"p99": ["max", "gauge", v]}}
        out = vars_view.render_fleet({"a:1": mk(900.0), "b:2": mk(100.0)},
                                     "p99", "second")
        assert "[max]" in out
        assert "last=900" in out


# ----------------------------------------------------------- workers=2 e2e
@pytest.mark.slow
class TestFleetE2E:
    def test_w_vars_merge_and_series(self, shard_flags):
        """The ISSUE 12 acceptance path: 2 shard workers ship W_VARS
        snapshots; the parent's fleet aggregates are op-correct and the
        per-method qps var accumulates >=30 one-second series samples
        (ticks driven manually — count-based rollups need no wall clock)."""
        from brpc_tpu.metrics import global_collector
        from brpc_tpu.metrics.variable import get_exposed
        from tests.test_shard import _echo_server, _stub_for
        from brpc_tpu.proto import echo_pb2

        srv = _echo_server()
        try:
            assert srv._shard_plane.wait_ready(15.0)
            stub = _stub_for(srv)
            for i in range(40):
                req = echo_pb2.EchoRequest(message=f"fleet-{i}")
                resp = stub.Echo(req)
                assert resp.message == f"fleet-{i}"
            # wait for both workers' W_VARS snapshots to land
            deadline = time.monotonic() + 15.0
            count_name = "fleet_rpc_method_echoservice_echo_count"
            while time.monotonic() < deadline:
                fleet_count = get_exposed(count_name)
                if (srv._shard_plane.fleet.workers_reporting() == 2
                        and fleet_count is not None
                        and fleet_count.get_value() >= 40):
                    break
                time.sleep(0.1)
            assert srv._shard_plane.fleet.workers_reporting() == 2
            w0 = get_exposed("worker0_rpc_method_echoservice_echo_count")
            w1 = get_exposed("worker1_rpc_method_echoservice_echo_count")
            fleet = get_exposed(count_name)
            assert fleet is not None and w0 is not None and w1 is not None
            assert fleet.get_value() == w0.get_value() + w1.get_value()
            assert fleet.get_value() >= 40
            # per-method qps visible fleet-wide
            assert get_exposed(
                "fleet_rpc_method_echoservice_echo_qps") is not None
            # >=30 one-second series samples for a per-method qps var via
            # the parent's sampler tick (manual — no 30 s of wall clock)
            for _ in range(31):
                global_collector().tick_all()
            from brpc_tpu.builtin.services import vars_service

            st, _, body = vars_service(
                srv, _Http("/vars", {
                    "series": "json",
                    "name": "fleet_rpc_method_*_qps"}))
            doc = json.loads(body)
            assert doc["workers"] == 2
            qps_series = doc["series"][
                "fleet_rpc_method_echoservice_echo_qps"]
            assert qps_series["count"] >= 30
            # workerN_* mirrors stay out of the series plane (opt-out)
            assert not [n for n in doc["series"] if n.startswith("worker")]
        finally:
            srv.stop()
            srv.join()

    def test_seeded_deadline_spike_flips_watch_rule(self, shard_flags):
        """Acceptance: a seeded deadline-expiry spike flips the pre-wired
        rule to firing on /watch, then back to ok once the window drains."""
        from brpc_tpu.builtin.services import watch_service
        from brpc_tpu.metrics import global_collector
        from brpc_tpu.metrics.watch import global_watch
        from brpc_tpu.rpc import server_processing as sp
        from tests.test_shard import _echo_server

        srv = _echo_server()   # Server.start installs the default rules
        try:
            rule = {r.name: r for r in global_watch().rules()}[
                "deadline_expiry_rate"]
            # the autouse registry clean may have hidden the module Adder's
            # wrapper; re-expose so the series sweep sees it again
            if sp.g_server_deadline_expired._var.name is None:
                sp.g_server_deadline_expired._var.expose(
                    "g_server_deadline_expired")

            def state_on_watch():
                _, _, body = watch_service(
                    srv, _Http("/watch", {"format": "json"}))
                rules = json.loads(body)["rules"]
                return {r["name"]: r["state"] for r in rules}[
                    "deadline_expiry_rate"]

            for _ in range(3):
                global_collector().tick_all()     # baseline samples
            # seed the spike: way past 0.5 expiries/s over the 10 s window
            for _ in range(rule.for_ticks + 1):
                sp.g_server_deadline_expired.put(50)
                global_collector().tick_all()
            assert rule.state == STATE_FIRING
            assert state_on_watch() == STATE_FIRING
            # drain: rate falls back to 0 once the spike leaves the window
            for _ in range(rule.window_s + rule.clear_ticks + 2):
                global_collector().tick_all()
            assert rule.state == STATE_OK
            assert state_on_watch() == STATE_OK
        finally:
            srv.stop()
            srv.join()
