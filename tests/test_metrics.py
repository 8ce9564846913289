"""Metrics tests (pattern: reference test/bvar_*_unittest.cpp — real threads
hammering reducers, manual sampler ticks instead of 1 s sleeps)."""

import threading

import pytest

from brpc_tpu.metrics import (
    Adder,
    Maxer,
    Miner,
    IntRecorder,
    LatencyRecorder,
    Percentile,
    PerSecond,
    SamplerCollector,
    Status,
    PassiveStatus,
    MultiDimension,
    Window,
    dump_exposed,
    get_exposed,
    prometheus_text,
)


pytestmark = pytest.mark.usefixtures("empty_registry")


class TestReducers:
    def test_adder_single_thread(self):
        a = Adder()
        a << 1 << 2 << 3
        assert a.get_value() == 6

    def test_adder_many_threads(self):
        a = Adder()
        n_threads, per_thread = 8, 10_000

        def worker():
            for _ in range(per_thread):
                a.put(1)

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert a.get_value() == n_threads * per_thread

    def test_maxer_miner(self):
        m, mi = Maxer(), Miner()
        for v in [3, 9, 1]:
            m.put(v)
            mi.put(v)
        assert m.get_value() == 9
        assert mi.get_value() == 1

    def test_reset_zeroes(self):
        a = Adder()
        a.put(5)
        assert a.reset() == 5
        assert a.get_value() == 0


class TestWindow:
    def test_window_delta_partial_series(self):
        col = SamplerCollector(interval_s=3600)  # never auto-ticks in test
        a = Adder()
        w = Window(a, window_size=3, collector=col)
        a.put(10)
        col.tick_all()  # sample: 10
        a.put(5)
        col.tick_all()  # sample: 15
        # series started inside the window: everything counts
        assert w.get_value() == 15

    def test_window_delta_full_ring(self):
        col = SamplerCollector(interval_s=3600)
        a = Adder()
        w = Window(a, window_size=2, collector=col)
        for v in (10, 5, 2):
            a.put(v)
            col.tick_all()  # cumulative samples: 10, 15, 17
        # last 2 seconds saw +5 and +2
        assert w.get_value() == 7

    def test_per_second(self):
        col = SamplerCollector(interval_s=3600)
        a = Adder()
        qps = PerSecond(a, window_size=10, collector=col)
        for _ in range(3):
            a.put(100)
            col.tick_all()
        assert qps.get_value() == pytest.approx(100, rel=0.5)


class TestWindowNonInvertible:
    def test_windowed_miner(self):
        from brpc_tpu.metrics import Miner

        col = SamplerCollector(interval_s=3600)
        mi = Miner()
        w = Window(mi, window_size=3, collector=col)
        mi.put(5)
        col.tick_all()
        assert w.get_value() == 5  # not clamped to 0 by the empty identity

    def test_windowed_maxer_negative(self):
        from brpc_tpu.metrics import Maxer

        col = SamplerCollector(interval_s=3600)
        m = Maxer()
        w = Window(m, window_size=3, collector=col)
        m.put(-7)
        col.tick_all()
        assert w.get_value() == -7


class TestThreadDeathRetirement:
    def test_adder_survives_thread_death(self):
        import gc

        a = Adder()

        def worker():
            a.put(10)

        for _ in range(5):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        del t
        gc.collect()
        assert a.get_value() == 50
        # dead-thread agents folded into _retired, not leaked in the list
        assert len(a._agents) <= 1

    def test_percentile_survives_thread_death(self):
        import gc

        p = Percentile()

        def worker():
            for i in range(100):
                p.put(i)

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        del t
        gc.collect()
        assert p.get_value().count == 100


class TestPercentile:
    def test_count_weighted_merge(self):
        from brpc_tpu.metrics import PercentileSamples

        hot = PercentileSamples()
        hot.add_group([100.0] * 1000, 1_000_000)  # 1M fast events
        cold = PercentileSamples()
        cold.add_group([5000.0] * 1000, 2_000)    # 2k slow events
        hot.merge(cold)
        # p50 must reflect the 500x traffic imbalance, not 50/50 samples
        assert hot.get_number(0.5) == 100.0
        assert hot.get_number(0.999) == 5000.0

    def test_basic_distribution(self):
        p = Percentile()
        for i in range(1000):
            p.put(i)
        samples = p.get_value()
        assert samples.count == 1000
        assert 450 <= samples.get_number(0.5) <= 550
        assert samples.get_number(0.99) >= 900

    def test_multithread_counts(self):
        p = Percentile()

        def worker():
            for i in range(5000):
                p.put(i)

        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert p.get_value().count == 20_000


class TestLatencyRecorder:
    def test_bundle(self):
        col = SamplerCollector(interval_s=3600)
        rec = LatencyRecorder(window_size=10, collector=col)
        for v in range(1, 101):
            rec.record(v * 10.0)
        col.tick_all()
        assert rec.count() == 100
        assert rec.latency() == pytest.approx(505.0, rel=0.01)
        assert rec.max_latency() == 1000.0
        assert rec.latency_percentile(0.99) >= 950
        assert rec.qps() > 0

    def test_describe(self):
        rec = LatencyRecorder(collector=SamplerCollector(interval_s=3600))
        rec.record(100)
        d = rec.describe()
        assert "qps" in d and "p99" in d


class TestRegistry:
    def test_expose_and_dump(self):
        s = Status(42)
        s.expose("my_status")
        assert get_exposed("my_status") is s
        assert dump_exposed()["my_status"] == "42"
        s.hide()
        assert get_exposed("my_status") is None

    def test_passive_status(self):
        calls = []
        p = PassiveStatus(lambda: len(calls))
        p.expose("passive")
        calls.append(1)
        assert p.get_value() == 1

    def test_expose_name_normalization(self):
        Status(1).expose("Foo::Bar baz")
        assert get_exposed("foo_bar_baz") is not None

    def test_adder_expose(self):
        a = Adder("requests_total")
        a.put(3)
        assert dump_exposed()["requests_total"] == "3"


class TestMultiDimension:
    def test_labels(self):
        md = MultiDimension(("method", "code"))
        md.get_stats(("echo", "200")).set_value(5)
        md.get_stats(("echo", "500")).set_value(1)
        assert md.count_stats() == 2
        assert md.get_stats(("echo", "200")).get_value() == 5
        assert md.has_stats(("echo", "500"))
        md.delete_stats(("echo", "500"))
        assert md.count_stats() == 1

    def test_factory_form_and_prometheus_labels(self):
        from brpc_tpu.metrics import Adder
        from brpc_tpu.metrics.status import prometheus_text

        md = MultiDimension(Adder, ["svc"]).expose("md_prom_test")
        md.stats(["a"]).put(2)
        md.stats(["b"]).put(7)
        text = prometheus_text()
        assert 'md_prom_test{svc="a"} 2' in text
        assert 'md_prom_test{svc="b"} 7' in text

    def test_arity_check(self):
        md = MultiDimension(("a",))
        with pytest.raises(ValueError):
            md.get_stats(("x", "y"))


class TestPrometheus:
    def test_text_format(self):
        Status(7).expose("numeric_var")
        Status("hello").expose("string_var")
        text = prometheus_text()
        assert "# TYPE numeric_var gauge" in text
        assert "numeric_var 7" in text
        assert "string_var" not in text  # non-numeric excluded
