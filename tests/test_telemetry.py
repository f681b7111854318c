"""Unified telemetry spine tests (ISSUE 2): registry semantics,
thread-safety, Prometheus rendering, the ``/metrics`` endpoint, the
chrome-trace span buffer, and — the part that matters — the hot paths
(prefetcher, compile cache, fit funnels) actually recording during a
tiny ``fit()``."""
import json
import math
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from deeplearning4j_tpu.common import telemetry
from deeplearning4j_tpu.common.telemetry import (DEFAULT_BUCKETS,
                                                 MetricsRegistry,
                                                 MetricsReporterListener)

_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _fresh_registry():
    MetricsRegistry._reset_for_tests()
    yield
    MetricsRegistry._reset_for_tests()


def _net_and_data(n=64):
    from deeplearning4j_tpu.activations import Activation
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.lossfunctions import LossFunction
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    rng = np.random.RandomState(0)
    x = rng.randn(n, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x[:, 0] > 0).astype(int)]
    net = MultiLayerNetwork(
        (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
         .list()
         .layer(DenseLayer(n_out=8, activation=Activation.RELU))
         .layer(OutputLayer(n_out=2, activation=Activation.SOFTMAX,
                            loss_function=LossFunction.MCXENT))
         .set_input_type(InputType.feed_forward(4)).build())).init()
    return net, DataSet(x, y)


class TestRegistry:
    def test_counter_gauge_basics(self):
        c = telemetry.counter("dl4j_t_total", "help")
        c.inc()
        c.inc(2, model="a")
        assert c.value() == 1
        assert c.value(model="a") == 2
        g = telemetry.gauge("dl4j_t_gauge", "help")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value() == 6

    def test_registration_idempotent_and_kind_checked(self):
        a = telemetry.counter("dl4j_t_same", "x")
        b = telemetry.counter("dl4j_t_same", "other help ignored")
        assert a is b
        with pytest.raises(ValueError, match="already registered"):
            telemetry.gauge("dl4j_t_same", "x")

    def test_histogram_bucketing(self):
        h = telemetry.histogram("dl4j_t_h", "x", buckets=(0.01, 0.1, 1))
        for v in (0.005, 0.01, 0.05, 0.5, 5.0):
            h.observe(v)
        s = h._series[()]
        # le=0.01 gets 0.005 AND the boundary value 0.01 (le is <=)
        assert s.counts == [2, 1, 1, 1]
        assert s.count == 5
        assert abs(s.sum - 5.565) < 1e-9
        assert h.count_of() == 5

    def test_histogram_quantile_estimate(self):
        h = telemetry.histogram("dl4j_t_q", "x",
                                buckets=(0.01, 0.1, 1.0))
        assert math.isnan(h.quantile(0.5))      # no observations yet
        for v in (0.005, 0.02, 0.05, 0.2, 5.0):
            h.observe(v)
        # median target 2.5 lands in the (0.01, 0.1] bucket (2 obs):
        # linear interpolation inside it
        q50 = h.quantile(0.5)
        assert 0.01 < q50 <= 0.1
        # +Inf observations clamp to the top finite edge
        assert h.quantile(0.99) == 1.0
        assert h.quantile(0.2) <= 0.01

    def test_histogram_quantile_empty_is_nan(self):
        """Regression: an empty series must answer NaN, not 0.0 — a
        0.0 p99 on a dashboard reads as 'everything was instant'
        when nothing was observed at all."""
        h = telemetry.histogram("dl4j_t_q_empty", "x",
                                buckets=(0.01, 0.1, 1.0))
        for q in (0.0, 0.5, 0.99):
            assert math.isnan(h.quantile(q))
        # an unseen label set is just as empty as an unseen series
        h.observe(0.05, model="a")
        assert math.isnan(h.quantile(0.5, model="b"))
        assert not math.isnan(h.quantile(0.5, model="a"))

    def test_disabled_records_nothing(self):
        reg = MetricsRegistry.get()
        reg.set_enabled(False)
        c = telemetry.counter("dl4j_t_off", "x")
        c.inc()
        telemetry.histogram("dl4j_t_off_h", "x").observe(1.0)
        with telemetry.span("off_span"):
            pass
        assert c.value() == 0
        assert telemetry.histogram("dl4j_t_off_h", "x").count_of() == 0
        assert not any(e["name"] == "off_span"
                       for e in telemetry.trace_events())

    def test_env_gate(self, monkeypatch):
        from deeplearning4j_tpu.common.environment import Environment
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "0")
        Environment.reset()
        MetricsRegistry._reset_for_tests()
        try:
            assert not MetricsRegistry.get().enabled
        finally:
            monkeypatch.delenv("DL4J_TPU_TELEMETRY")
            Environment.reset()
            MetricsRegistry._reset_for_tests()

    def test_thread_safety_concurrent_writers(self):
        c = telemetry.counter("dl4j_t_mt_total", "x")
        h = telemetry.histogram("dl4j_t_mt_h", "x")
        n_threads, n_ops = 8, 2000
        start = threading.Barrier(n_threads)

        def work(i):
            start.wait()
            for _ in range(n_ops):
                c.inc(worker=str(i % 2))
                h.observe(0.001)

        ts = [threading.Thread(target=work, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        total = sum(c.value(worker=str(w)) for w in (0, 1))
        assert total == n_threads * n_ops       # no lost increments
        assert h.count_of() == n_threads * n_ops
        assert abs(h.sum_of() - n_threads * n_ops * 0.001) < 1e-6

    def test_prometheus_rendering(self):
        telemetry.counter("dl4j_t_c_total", "a counter").inc(
            3, model="mln")
        telemetry.gauge("dl4j_t_g", "a gauge").set(2.5)
        h = telemetry.histogram("dl4j_t_h_seconds", "a hist",
                                buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = MetricsRegistry.get().render_prometheus()
        assert "# TYPE dl4j_t_c_total counter" in text
        assert 'dl4j_t_c_total{model="mln"} 3' in text
        assert "# TYPE dl4j_t_g gauge" in text
        assert "dl4j_t_g 2.5" in text
        assert "# HELP dl4j_t_h_seconds a hist" in text
        # cumulative buckets + +Inf + sum/count
        assert 'dl4j_t_h_seconds_bucket{le="0.1"} 1' in text
        assert 'dl4j_t_h_seconds_bucket{le="1"} 2' in text
        assert 'dl4j_t_h_seconds_bucket{le="+Inf"} 2' in text
        assert "dl4j_t_h_seconds_count 2" in text

    def test_summary_snapshot(self):
        telemetry.counter("dl4j_t_c_total", "x").inc(model="a")
        telemetry.histogram("dl4j_t_h", "x").observe(2.0)
        s = MetricsRegistry.get().summary()
        assert s["dl4j_t_c_total"]["model=a"] == 1
        assert s["dl4j_t_h"][""]["count"] == 1
        assert s["dl4j_t_h"][""]["mean"] == 2.0
        json.dumps(s)                       # JSON-serializable


class TestSpans:
    def test_span_and_instant_events(self):
        with telemetry.span("outer", stage="test"):
            telemetry.instant("marker", k=1)
        events = telemetry.trace_events()
        names = [e["name"] for e in events]
        assert "outer" in names and "marker" in names
        outer = next(e for e in events if e["name"] == "outer")
        assert outer["ph"] == "X" and outer["dur"] >= 0
        assert outer["args"] == {"stage": "test"}

    def test_export_and_merge(self, tmp_path):
        with telemetry.span("a"):
            pass
        p1 = telemetry.export_chrome_trace(str(tmp_path / "t1.json"))
        doc = json.load(open(p1))
        assert any(e["name"] == "a" for e in doc["traceEvents"])
        assert doc["metadata"]["dropped_events"] == 0
        # merge with a jax.profiler-shaped second trace
        p2 = tmp_path / "t2.json"
        p2.write_text(json.dumps(
            {"traceEvents": [{"name": "tpu_op", "ph": "X", "pid": 9,
                              "tid": 1, "ts": 1, "dur": 2}]}))
        merged = telemetry.merge_chrome_traces(
            str(tmp_path / "m.json"), p1, str(p2))
        events = json.load(open(merged))["traceEvents"]
        assert {"a", "tpu_op"} <= {e["name"] for e in events}

    def test_merge_host_traces_keeps_named_scopes(self, tmp_path):
        """The layerprof join depends on three merge invariants: the
        ``dl4j.<scope>`` strings survive verbatim (attribute_trace
        keys on them), the pid remap keeps every event attached to
        its host's process_name row, and the clock shift keeps each
        host's event stream monotonic on the leader timeline."""
        leader = tmp_path / "leader.json"
        worker = tmp_path / "worker.json"
        leader.write_text(json.dumps({"traceEvents": [
            {"name": "dl4j.layer_0", "ph": "X", "pid": 7, "tid": 1,
             "ts": 100, "dur": 10},
            {"name": "jit_step", "ph": "X", "pid": 7, "tid": 1,
             "ts": 120, "dur": 5,
             "args": {"op_name": "dl4j.layer_1/dot"}},
        ]}))
        worker.write_text(json.dumps({"traceEvents": [
            {"name": "transpose(dl4j.layer_0)", "ph": "X", "pid": 7,
             "tid": 1, "ts": 5000, "dur": 8},
            {"name": "dl4j.encoder.ffn", "ph": "X", "pid": 7,
             "tid": 1, "ts": 5100, "dur": 12},
        ]}))
        merged = telemetry.merge_host_traces(
            str(tmp_path / "m.json"),
            {"path": str(leader), "host": "leader",
             "clock_offset_s": 0.0},
            {"path": str(worker), "host": "worker1",
             "clock_offset_s": 0.004})
        doc = json.load(open(merged))
        events = doc["traceEvents"]
        # scope strings survive verbatim, in names and in op_name args
        names = {e["name"] for e in events}
        assert {"dl4j.layer_0", "transpose(dl4j.layer_0)",
                "dl4j.encoder.ffn"} <= names
        jit = next(e for e in events if e["name"] == "jit_step")
        assert jit["args"]["op_name"] == "dl4j.layer_1/dot"
        # pid remap: same source pid 7 lands on distinct rows, each
        # labeled with its host
        proc = {e["pid"]: e["args"]["name"] for e in events
                if e.get("ph") == "M" and e["name"] == "process_name"}
        assert sorted(proc.values()) == ["leader", "worker1"]
        by_host = {proc[e["pid"]] for e in events if e.get("ph") == "X"}
        assert by_host == {"leader", "worker1"}
        # clock shift: worker events moved onto the leader clock
        # (-4000us) and each host's stream stays monotonic
        ffn = next(e for e in events if e["name"] == "dl4j.encoder.ffn")
        assert ffn["ts"] == 5100 - 4000
        for host in ("leader", "worker1"):
            ts = [e["ts"] for e in events
                  if e.get("ph") == "X" and proc[e["pid"]] == host]
            assert ts == sorted(ts)

    def test_buffer_cap_counts_drops(self, tmp_path):
        buf = telemetry._trace_buffer
        old_max = buf.max_events
        buf.max_events = len(buf.events) + 1
        try:
            with telemetry.span("kept"):
                pass
            with telemetry.span("dropped"):
                pass
            assert buf.dropped == 1
            doc = json.load(open(telemetry.export_chrome_trace(
                str(tmp_path / "t.json"))))
            assert doc["metadata"]["dropped_events"] == 1
        finally:
            buf.max_events = old_max


class TestMetricsEndpoint:
    def test_metrics_roundtrip(self):
        from deeplearning4j_tpu.ui import UIServer
        telemetry.counter("dl4j_t_served_total", "x").inc(5)
        server = UIServer.get_instance().start(port=0)
        try:
            resp = urllib.request.urlopen(server.url + "/metrics")
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
            assert "dl4j_t_served_total 5" in text
            assert "# TYPE dl4j_t_served_total counter" in text
        finally:
            server.stop()


class TestInstrumentedFit:
    def test_fit_records_step_prefetch_and_cache_metrics(self):
        """The acceptance-criteria smoke: a tiny fit() over a real
        iterator yields non-zero step-time histogram counts, prefetch
        queue-depth samples + staged batches, and compile-cache
        hit/miss counters — all visible in one Prometheus page."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import \
            ListDataSetIterator
        net, ds = _net_and_data(64)
        batches = [DataSet(ds.features[i:i + 16], ds.labels[i:i + 16])
                   for i in range(0, 64, 16)]
        it = ListDataSetIterator(batches, batch_size=16)
        net.fit(it, n_epochs=2)

        h = telemetry.histogram("dl4j_train_step_seconds", "")
        assert h.count_of(model="MultiLayerNetwork") == 8
        assert h.sum_of(model="MultiLayerNetwork") > 0
        staged = telemetry.counter(
            "dl4j_prefetch_batches_staged_total", "")
        assert staged.value() == 8
        stall = telemetry.histogram("dl4j_feed_stall_seconds", "")
        # one observation per queue pop (incl. the end-of-epoch
        # sentinel pull): at least one per consumed batch
        assert stall.count_of(source="device_prefetch") >= 8
        hits = telemetry.counter("dl4j_compile_cache_hits_total", "")
        misses = telemetry.counter(
            "dl4j_compile_cache_misses_total", "")
        name = "MultiLayerNetwork train step"
        assert misses.value(network=name) == 1      # one signature
        assert hits.value(network=name) == 7        # 7 reuses
        # the whole panel renders
        text = MetricsRegistry.get().render_prometheus()
        assert "dl4j_train_step_seconds_count" in text
        assert "dl4j_prefetch_queue_depth" in text

    def test_retrace_counter_on_shape_churn(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        net, ds = _net_and_data(64)
        net.fit(DataSet(ds.features[:32], ds.labels[:32]))
        net.fit(DataSet(ds.features[:48], ds.labels[:48]))
        retr = telemetry.counter("dl4j_retrace_total", "")
        assert retr.value(network="MultiLayerNetwork train step") == 1
        assert any(e["name"] == "retrace"
                   for e in telemetry.trace_events())

    def test_reporter_listener_folds_snapshots(self):
        from deeplearning4j_tpu.ui import InMemoryStatsStorage
        storage = InMemoryStatsStorage()
        net, ds = _net_and_data()
        net.set_listeners(MetricsReporterListener(storage, frequency=2))
        net.fit(ds, n_epochs=5)
        reports = storage.get_reports()
        assert len(reports) == 3                    # iterations 0,2,4
        tel = reports[-1]["telemetry"]
        assert "dl4j_train_step_seconds" in tel
        assert tel["dl4j_train_step_seconds"][
            "model=MultiLayerNetwork"]["count"] >= 4

    def test_checkpoint_metrics(self, tmp_path):
        from deeplearning4j_tpu.utils.checkpoint import \
            CheckpointListener
        net, ds = _net_and_data()
        lis = CheckpointListener(tmp_path, save_every_n_epochs=1,
                                 asynchronous=False)
        net.add_listeners(lis)
        net.fit([ds], n_epochs=2)
        assert telemetry.histogram(
            "dl4j_checkpoint_save_seconds", "").count_of() == 2
        saved_bytes = telemetry.counter(
            "dl4j_checkpoint_bytes_total", "").value(op="save")
        assert saved_bytes > 0
        CheckpointListener.load_checkpoint(tmp_path)
        assert telemetry.histogram(
            "dl4j_checkpoint_load_seconds", "").count_of() == 1
        assert telemetry.counter(
            "dl4j_checkpoint_bytes_total", "").value(op="load") > 0

    def test_inference_queue_metrics(self):
        from deeplearning4j_tpu.parallel.inference import \
            ParallelInference
        net, ds = _net_and_data()
        pi = (ParallelInference.Builder(net).workers(1)
              .batch_limit(8).build())
        try:
            futs = [pi.submit(ds.features[i:i + 2])
                    for i in range(0, 8, 2)]
            for f in futs:
                assert f.result(timeout=30).shape[-1] == 2
        finally:
            pi.shutdown()
        assert telemetry.counter(
            "dl4j_inference_requests_total", "").value(
                mode="BATCHED") == 4
        assert telemetry.histogram(
            "dl4j_inference_queue_seconds", "").count_of() == 4
        occ = telemetry.histogram("dl4j_inference_batch_occupancy", "")
        assert occ.count_of() >= 1


class TestOverhead:
    def test_disabled_overhead_is_trivial(self):
        """With the gate off a record call must cost no more than a
        bare method call — budget is generous (5µs) to stay robust on
        loaded CI, but catches accidental work on the off path."""
        import time
        reg = MetricsRegistry.get()
        c = telemetry.counter("dl4j_t_ovh_total", "x")
        reg.set_enabled(False)
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            c.inc()
        per_op = (time.perf_counter() - t0) / n
        assert per_op < 5e-6

    def test_enabled_step_overhead_under_one_pct(self):
        """ISSUE acceptance: <1% step-time impact with telemetry on.
        Measured deterministically: the FULL per-step record (a
        step_span = one histogram observe + one trace event) is timed
        per-op and compared against a 1ms step — the floor of any
        real accelerator step (CPU-proxy LeNet steps are ~1ms, TPU
        ResNet/BERT steps are tens of ms, so 1% here is the worst
        case). bench_telemetry.py measures the real fit() funnel."""
        import time
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            with telemetry.step_span("ovh"):
                pass
        per_step = (time.perf_counter() - t0) / n
        telemetry._trace_buffer.clear()
        assert per_step < 0.01 * 1e-3       # <1% of a 1ms step


class TestCatalogChecker:
    def test_catalog_in_sync(self):
        """Tier-1 wiring for scripts/check_telemetry_catalog.py: every
        registered metric is documented in README, none are stale."""
        out = subprocess.run(
            [sys.executable,
             str(_ROOT / "scripts" / "check_telemetry_catalog.py")],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr


class TestSatellites:
    def test_score_listener_logs_not_prints(self, capsys, caplog):
        import logging
        from deeplearning4j_tpu.optimize.listeners import \
            ScoreIterationListener
        net, ds = _net_and_data()
        net.set_listeners(ScoreIterationListener(1))
        with caplog.at_level(logging.INFO, logger="deeplearning4j_tpu"):
            net.fit(ds)
        assert "Score at iteration" in caplog.text
        assert "Score at iteration" not in capsys.readouterr().out

    def test_score_listener_stdout_opt_in(self, capsys):
        from deeplearning4j_tpu.optimize.listeners import \
            ScoreIterationListener
        net, ds = _net_and_data()
        net.set_listeners(ScoreIterationListener(1, stdout=True))
        net.fit(ds)
        assert "Score at iteration" in capsys.readouterr().out

    def test_performance_listener_logs_not_prints(self, capsys, caplog):
        import logging
        from deeplearning4j_tpu.optimize.listeners import \
            PerformanceListener
        net, ds = _net_and_data()
        net.set_listeners(PerformanceListener(frequency=1))
        with caplog.at_level(logging.INFO, logger="deeplearning4j_tpu"):
            net.fit(ds, n_epochs=3)
        assert "iters/sec" in caplog.text
        assert "iters/sec" not in capsys.readouterr().out

    def test_profiling_listener_counts_drops(self, tmp_path, caplog):
        import logging
        from deeplearning4j_tpu.ui import ProfilingListener
        p = str(tmp_path / "trace.json")
        prof = ProfilingListener(p, max_events=2)
        net, ds = _net_and_data()
        net.set_listeners(prof)
        with caplog.at_level(logging.WARNING,
                             logger="deeplearning4j_tpu"):
            net.fit([ds, ds, ds, ds, ds, ds], n_epochs=1)
        doc = json.load(open(p))
        assert len(doc["traceEvents"]) == 2
        assert doc["metadata"]["dropped_events"] == prof.dropped > 0
        assert "dropped" in caplog.text

    def test_file_stats_storage_skips_corrupt_tail(self, tmp_path,
                                                   caplog):
        import logging
        from deeplearning4j_tpu.ui import FileStatsStorage
        p = tmp_path / "stats.jsonl"
        s = FileStatsStorage(str(p))
        s.put_report({"iteration": 0, "time": 1.0, "score": 2.0})
        s.put_report({"iteration": 1, "time": 2.0, "score": 1.0})
        # simulate a crash mid-append: truncated trailing line
        with open(p, "a") as f:
            f.write('{"iteration": 2, "time": 3.0, "sco')
        with caplog.at_level(logging.WARNING,
                             logger="deeplearning4j_tpu"):
            again = FileStatsStorage(str(p))
        assert len(again.get_reports()) == 2
        assert again.latest()["iteration"] == 1
        assert "corrupt" in caplog.text
        # storage stays appendable after a dirty resume
        again.put_report({"iteration": 3, "time": 4.0, "score": 0.5})
        assert FileStatsStorage(str(p)).latest()["iteration"] == 3


def _stamp(kind):
    """Record one event of ``kind`` and return it."""
    from deeplearning4j_tpu.common import tracectx
    n = len(telemetry.trace_events())
    if kind == "span":
        with telemetry.span("clock.span"):
            pass
    elif kind == "instant":
        telemetry.instant("clock.instant")
    elif kind == "span_at":
        telemetry.span_at("clock.span_at",
                          telemetry.us_of(time.perf_counter()) * 1e-6,
                          0.0)
    elif kind == "step_span":
        with telemetry.step_span("Clock"):
            pass
    elif kind == "phase_at":
        t = time.perf_counter()
        tracectx.TraceContext("m", "predict").phase_at("queue", t, t)
    elif kind == "phase":
        with tracectx.TraceContext("m", "predict").phase("device"):
            pass
    else:
        t = tracectx.TraceContext("m", "predict")
        t.finish(200)
    ev = telemetry.trace_events()[n:]
    assert len(ev) == 1
    return ev[0]


class TestOneClock:
    """ISSUE 26: one anchor, read once; every span, instant and
    request phase is stamped in epoch microseconds derived from
    ``perf_counter`` through it."""

    @pytest.mark.parametrize("kind", [
        "span", "instant", "span_at", "step_span", "phase_at", "phase",
        "request"])
    def test_stamps_through_now_us_and_ignores_the_wall_clock(
            self, kind, monkeypatch):
        # a wall clock that is stepped back an hour at every read: a
        # stamp taken from it would fall outside the bracket, and two
        # in a row would run backwards
        wall = iter(range(10**9, 0, -3600))
        monkeypatch.setattr(time, "time", lambda: float(next(wall)))
        lo = telemetry.now_us()
        first = _stamp(kind)
        second = _stamp(kind)
        hi = telemetry.now_us()
        assert lo <= first["ts"] <= second["ts"] <= hi
        assert first.get("dur", 0) >= 0

    def test_perf_counter_of_inverts_now_us(self):
        for _ in range(200):
            p0 = time.perf_counter()
            ts = telemetry.now_us()
            p1 = time.perf_counter()
            assert p0 - 2e-6 <= telemetry.perf_counter_of(ts) <= p1 + 2e-6
            assert abs(telemetry.us_of(telemetry.perf_counter_of(ts))
                       - ts) <= 1
        wall_s, perf_s = telemetry.CLOCK_ANCHOR_S
        assert abs(telemetry.us_of(perf_s) - wall_s * 1e6) <= 2
        # the epoch axis the exporters expect: the anchor is "now"
        assert abs(telemetry.now_us() * 1e-6 - time.time()) < 3600

    def test_monotonic_is_the_same_clock_as_perf_counter(self):
        # phase instants callers took with time.monotonic() convert
        # through the same anchor (Linux: both CLOCK_MONOTONIC)
        assert abs(time.monotonic() - time.perf_counter()) < 1e-3

    def test_no_wall_or_monotonic_clock_stamps_in_the_two_modules(self):
        import re
        for mod in ("telemetry", "tracectx"):
            src = (_ROOT / "deeplearning4j_tpu" / "common"
                   / f"{mod}.py").read_text()
            assert not re.search(r"time\.monotonic\(\)", src), mod
            # what is left of time.time() stamps no span: a histogram's
            # exemplar and a stats report's own "time" field
            left = [ln.strip() for ln in src.splitlines()
                    if "time.time()" in ln and not ln.lstrip()
                    .startswith("#")]
            assert all("exemplar" in ln or '"time"' in ln
                       for ln in left), left

    def test_span_names_its_parent_and_hands_down_the_shared_id(self):
        with telemetry.span("outer", iter=7, model="m") as outer:
            with telemetry.span("inner", rows=3) as inner:
                inner["late"] = 1           # known when the block ends
                with telemetry.step_span("Net"):
                    pass
            with telemetry.span("own", iter=8):
                pass
        with telemetry.span("after"):
            pass
        ev = {e["name"]: e for e in telemetry.trace_events()}
        assert ev["outer"]["args"] == {"iter": 7, "model": "m"} == outer
        assert ev["inner"]["args"] == {"parent": "outer", "iter": 7,
                                       "rows": 3, "late": 1}
        assert ev["train_step"]["args"] == {"parent": "inner", "iter": 7,
                                            "model": "Net"}
        assert ev["own"]["args"] == {"parent": "outer", "iter": 8}
        assert ev["after"]["args"] == {}    # nothing open: no parent
        for child, parent in (("inner", "outer"), ("train_step", "inner"),
                              ("own", "outer")):
            c, p = ev[child], ev[parent]
            assert p["ts"] <= c["ts"]
            assert c["ts"] + c["dur"] <= p["ts"] + p["dur"]

    def test_parent_is_per_thread(self):
        seen = {}

        def other():
            with telemetry.span("elsewhere") as args:
                seen.update(args)
        with telemetry.span("here", iter=1):
            t = threading.Thread(target=other)
            t.start()
            t.join(10)
        assert not t.is_alive() and seen == {}

    def test_a_raising_block_still_closes_its_span(self):
        with pytest.raises(ValueError):
            with telemetry.span("outer"):
                with telemetry.span("boom"):
                    raise ValueError
        with telemetry.span("next"):
            pass
        ev = {e["name"]: e["args"] for e in telemetry.trace_events()}
        assert ev["boom"] == {"parent": "outer"} and ev["next"] == {}

    def test_fit_batch_is_the_parent_of_train_step(self):
        from deeplearning4j_tpu.activations import Activation
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.learning import Adam
        from deeplearning4j_tpu.lossfunctions import LossFunction
        from deeplearning4j_tpu.nn import (InputType,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                       OutputLayer)
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        g = (NeuralNetConfiguration.Builder().seed(0)
             .updater(Adam(1e-2)).graph_builder().add_inputs("in"))
        g.add_layer("d", DenseLayer(n_out=4,
                                    activation=Activation.RELU), "in")
        g.add_layer("out", OutputLayer(
            n_out=2, loss_function=LossFunction.MCXENT,
            activation=Activation.SOFTMAX), "d")
        g.set_outputs("out")
        g.set_input_types(InputType.feed_forward(4))
        net = ComputationGraph(g.build()).init()
        rng = np.random.RandomState(0)
        ds = DataSet(rng.randn(8, 4).astype(np.float32),
                     np.eye(2, dtype=np.float32)[rng.randint(0, 2, 8)])
        for _ in range(3):
            net.fit(ds)
        ev = telemetry.trace_events()
        batches = [e for e in ev if e["name"] == "fit.batch"]
        steps = [e for e in ev if e["name"] == "train_step"]
        assert [e["args"]["iter"] for e in batches] == [0, 1, 2]
        assert len(steps) == 3
        for b, s in zip(batches, steps):
            assert s["args"]["parent"] == "fit.batch"
            assert s["args"]["iter"] == b["args"]["iter"]
            assert s["args"]["model"] == "ComputationGraph"
            assert b["ts"] <= s["ts"]
            assert s["ts"] + s["dur"] <= b["ts"] + b["dur"]
