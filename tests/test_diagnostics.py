"""Diagnostics layer tests (ISSUE 7): HBM accounting, collective
spans, the numerics watchdog (clean runs never trip; an injected NaN
trips within one step, with first-bad-leaf attribution), flight
recorder ring semantics, crash/SIGTERM dump artifacts, bench
provenance, and the bench-regression gate's self-test on two
synthetic driver-wrapper records."""
import json
import os
import signal
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from deeplearning4j_tpu.common import diagnostics, telemetry
from deeplearning4j_tpu.common.diagnostics import (FlightRecorder,
                                                   NumericsEvent)
from deeplearning4j_tpu.common.environment import Environment
from deeplearning4j_tpu.common.telemetry import MetricsRegistry

_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _fresh_state():
    MetricsRegistry._reset_for_tests()
    Environment.reset()
    FlightRecorder._reset_for_tests()
    yield
    MetricsRegistry._reset_for_tests()
    Environment.reset()
    FlightRecorder._reset_for_tests()


def _net_and_data(n=32):
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


# ----------------------------------------------------------------------
# HBM accounting
class TestHbmAccounting:
    STATS = [{"id": 0, "kind": "fake-tpu", "bytes_in_use": 1000,
              "peak_bytes_in_use": 1500, "bytes_limit": 4000},
             {"id": 1, "kind": "fake-tpu", "bytes_in_use": 900,
              "peak_bytes_in_use": 1600, "bytes_limit": 4000}]

    def test_gauges_from_injected_stats(self):
        diagnostics.update_hbm_gauges(self.STATS)
        live = telemetry.gauge("dl4j_hbm_live_bytes")
        peak = telemetry.gauge("dl4j_hbm_peak_bytes")
        assert live.value(device="0") == 1000
        assert live.value(device="1") == 900
        assert peak.value(device="1") == 1600
        text = MetricsRegistry.get().render_prometheus()
        assert 'dl4j_hbm_live_bytes{device="0"} 1000' in text

    def test_memory_report_attribution(self):
        net, ds = _net_and_data()
        net.fit(ds)                 # records a step -> tracks the model
        rep = diagnostics.memory_report()
        assert rep["schema_version"] == diagnostics.SCHEMA_VERSION
        models = [v for k, v in rep["models"].items()
                  if k.startswith("MultiLayerNetwork")]
        assert models and models[0]["params_bytes"] > 0
        assert models[0]["updater_state_bytes"] > 0     # Adam m+v
        assert rep["accounted_bytes"] >= models[0]["params_bytes"]
        # narrowing to one model keys by bare class name
        one = diagnostics.memory_report(model=net)
        assert one["models"]["MultiLayerNetwork"]["params_bytes"] == \
            models[0]["params_bytes"]

    def test_report_shape_on_cpu(self):
        # CPU backend exposes no allocator stats: devices empty, no
        # residual estimate (it would be meaningless), totals zero
        rep = diagnostics.memory_report()
        if not rep["devices"]:
            assert rep["live_bytes_total"] == 0
            assert "activations_and_workspace_bytes_est" not in rep

    def test_roofline_classification(self):
        # 10 TF/s achieved against a 100 TF/s / 100 GB/s machine:
        # AI = 1e13/1e12 = 10 flops/B, ridge = 1000 -> HBM bound
        r = diagnostics.roofline(1e13, 1e12, 1.0, peak_tflops=100,
                                 peak_hbm_gbps=100)
        assert r["bound"] == "hbm"
        assert r["pct_of_roof"] == r["pct_hbm_peak"] == 1000.0
        # flip the intensity: compute bound
        r = diagnostics.roofline(1e14, 1e9, 1.0, peak_tflops=100,
                                 peak_hbm_gbps=100)
        assert r["bound"] == "compute"
        # no peaks known (non-TPU): classification keys absent
        r = diagnostics.roofline(1e12, 1e9, 1.0)
        assert "bound" not in r and r["tflops"] == 1.0


# ----------------------------------------------------------------------
# collective spans
class TestCollectiveSpan:
    def test_emits_span_histogram_and_bytes(self):
        with diagnostics.collective_span("update_exchange", "data",
                                         4096, mode="all_reduce"):
            pass
        h = telemetry.histogram("dl4j_collective_seconds")
        assert h.count_of(kind="update_exchange", axis="data") == 1
        c = telemetry.counter("dl4j_collective_bytes_total")
        assert c.value(kind="update_exchange", axis="data") == 4096
        names = [e["name"] for e in telemetry.trace_events()]
        assert "collective.update_exchange" in names

    def test_zero_bytes_skips_counter(self):
        with diagnostics.collective_span("global_assembly", "data"):
            pass
        assert telemetry.histogram("dl4j_collective_seconds").count_of(
            kind="global_assembly", axis="data") == 1
        assert "dl4j_collective_bytes_total" not in \
            MetricsRegistry.get()._metrics

    def test_disabled_is_bare(self):
        MetricsRegistry.get().set_enabled(False)
        with diagnostics.collective_span("update_exchange", "data",
                                         4096):
            pass
        assert "dl4j_collective_seconds" not in \
            MetricsRegistry.get()._metrics


# ----------------------------------------------------------------------
# numerics watchdog
@pytest.fixture()
def _watchdog(monkeypatch, tmp_path):
    monkeypatch.setenv("DL4J_TPU_NUMERICS_WATCHDOG", "1")
    monkeypatch.setenv("DL4J_TPU_FLIGHT_RECORDER_DIR", str(tmp_path))
    Environment.reset()
    FlightRecorder._reset_for_tests()
    yield tmp_path


class TestNumericsWatchdog:
    def test_first_nonfinite_attribution(self):
        import jax.numpy as jnp
        tree = {"a": jnp.ones((3,), jnp.float32),
                "b": jnp.asarray([0.0, 1.0, np.nan, 2.0], jnp.float32)}
        bad = diagnostics.first_nonfinite(tree)
        assert bad is not None
        assert "b" in bad["leaf"]
        assert bad["flat_index"] == 2
        assert diagnostics.first_nonfinite(
            {"a": jnp.ones((3,), jnp.float32)}) is None

    def test_clean_run_never_trips(self, _watchdog):
        net, ds = _net_and_data()
        for _ in range(5):
            net.fit(ds)
        assert net.iteration_count == 5
        c = telemetry.counter("dl4j_numerics_trips_total")
        assert c.value(model="MultiLayerNetwork", group="loss") == 0
        assert not list(_watchdog.glob("flightrec_*"))

    def test_nan_input_trips_within_one_step(self, _watchdog):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        net, ds = _net_and_data()
        net.fit(ds)                             # step 0: clean
        bad_x = np.array(ds.features)
        bad_x[0, 0] = np.nan
        with pytest.raises(NumericsEvent) as ei:
            net.fit(DataSet(bad_x, np.array(ds.labels)))
        ev = ei.value
        assert ev.step == 1                     # caught on ITS step
        assert ev.tensor_group == "loss"
        assert not np.isfinite(ev.value)
        # attribution scanned the poisoned post-update params
        assert ev.first_bad is not None
        assert ev.first_bad["leaf"]
        c = telemetry.counter("dl4j_numerics_trips_total")
        assert c.value(model="MultiLayerNetwork", group="loss") == 1
        # the recorder dumped, and the poisoned step is in the ring
        # exactly once (no double record from after_step + the trip)
        dumps = list(_watchdog.glob("flightrec_*_numerics.jsonl"))
        assert len(dumps) == 1
        lines = [json.loads(s) for s in
                 dumps[0].read_text().splitlines()]
        meta, recs = lines[0], lines[1:]
        assert meta["reason"] == "numerics"
        assert meta["event"]["step"] == 1
        assert [r["step"] for r in recs] == [0, 1]
        assert not np.isfinite(recs[1]["loss"])
        # the in-jit global grad norm was wired in (watchdog was armed
        # when the step traced) and materialized at dump time
        assert recs[0]["grad_norm"] is not None
        assert np.isfinite(recs[0]["grad_norm"])

    def test_sampling_skips_intermediate_steps(self, _watchdog,
                                               monkeypatch):
        monkeypatch.setenv("DL4J_TPU_NUMERICS_SAMPLE", "1000")
        Environment.reset()
        FlightRecorder._reset_for_tests()
        from deeplearning4j_tpu.datasets.dataset import DataSet
        net, ds = _net_and_data()
        net.fit(ds)                             # step 0: 0 % 1000 == 0
        bad_x = np.array(ds.features)
        bad_x[:] = np.nan
        # steps 1..3 are off-sample: the poison flows through unchecked
        for _ in range(3):
            net.fit(DataSet(bad_x, np.array(ds.labels)))
        assert net.iteration_count == 4

    def test_off_by_default(self, tmp_path):
        assert not diagnostics.watchdog_enabled()
        # check_numerics is a no-op even on a NaN loss
        diagnostics.check_numerics(None, "m", 0, float("nan"))


# ----------------------------------------------------------------------
# flight recorder
class TestFlightRecorder:
    def test_ring_truncates_to_capacity(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FLIGHT_RECORDER_STEPS", "8")
        Environment.reset()
        FlightRecorder._reset_for_tests()
        rec = FlightRecorder.get()
        assert rec.max_steps == 8
        for i in range(20):
            rec.record(self, "t", i, 0.5)
        steps = [r["step"] for r in rec.records()]
        assert steps == list(range(12, 20))

    def test_record_fields_and_lazy_loss(self, tmp_path):
        import jax.numpy as jnp
        rec = FlightRecorder.get()
        rec.dir = str(tmp_path)
        dev_loss = jnp.float32(0.25)        # device scalar stays lazy
        rec.record(self, "t", 0, dev_loss, None, grad_norm=None)
        r = rec.records()[0]
        for key in ("step", "t", "model", "step_seconds", "loss",
                    "grad_norm", "retraces", "collective_bytes",
                    "hbm_live_bytes", "hbm_peak_bytes"):
            assert key in r
        assert r["loss"] is dev_loss        # not float()ed on record
        path = rec.dump("manual")
        recs = [json.loads(s) for s in
                Path(path).read_text().splitlines()][1:]
        assert recs[0]["loss"] == 0.25      # materialized at dump

    def test_dump_writes_trace_and_dedups(self, tmp_path):
        rec = FlightRecorder.get()
        rec.dir = str(tmp_path)
        rec.record(self, "t", 0, 0.5)
        path = rec.dump("manual", event={"why": "test"})
        assert path and os.path.exists(path)
        assert os.path.exists(path.replace(".jsonl", ".trace.json"))
        meta = json.loads(Path(path).read_text().splitlines()[0])
        assert meta["event"] == {"why": "test"}
        assert meta["ring_capacity"] == rec.max_steps
        # second dump for the same reason: suppressed
        assert rec.dump("manual") is None
        c = telemetry.counter("dl4j_flightrec_dumps_total")
        assert c.value(reason="manual") == 1

    def test_disabled_records_nothing(self, monkeypatch, tmp_path):
        monkeypatch.setenv("DL4J_TPU_FLIGHT_RECORDER", "0")
        Environment.reset()
        FlightRecorder._reset_for_tests()
        rec = FlightRecorder.get()
        rec.record(self, "t", 0, 0.5)
        assert rec.records() == []
        assert rec.dump("manual") is None

    def test_fit_populates_ring(self):
        net, ds = _net_and_data()
        for _ in range(3):
            net.fit(ds)
        rec = FlightRecorder.get()
        recs = [r for r in rec.records()
                if r["model"] == "MultiLayerNetwork"]
        assert [r["step"] for r in recs] == [0, 1, 2]
        assert recs[0]["step_seconds"] is not None
        assert recs[0]["step_seconds"] > 0


_SUBPROC_PRELUDE = textwrap.dedent("""\
    import os, sys
    sys.path.insert(0, {root!r})
    import numpy as np
    from deeplearning4j_tpu.activations import Activation
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.lossfunctions import LossFunction
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    rng = np.random.RandomState(0)
    x = rng.randn(16, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x[:, 0] > 0).astype(int)]
    net = MultiLayerNetwork(
        (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
         .list()
         .layer(DenseLayer(n_out=8, activation=Activation.RELU))
         .layer(OutputLayer(n_out=2, activation=Activation.SOFTMAX,
                            loss_function=LossFunction.MCXENT))
         .set_input_type(InputType.feed_forward(4)).build())).init()
    ds = DataSet(x, y)
""").format(root=str(_ROOT))


def _run_subproc(body: str, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DL4J_TPU_FLIGHT_RECORDER_DIR=str(tmp_path))
    return subprocess.run(
        [sys.executable, "-c", _SUBPROC_PRELUDE + body],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(_ROOT))


class TestCrashArtifacts:
    def test_crash_dump_has_final_window(self, tmp_path):
        # acceptance bar: after a crash mid-training the dump holds the
        # final >=32 steps with time/loss/grad-norm/collective/HBM
        # fields
        p = _run_subproc(textwrap.dedent("""\
            for _ in range(40):
                net.fit(ds)
            raise RuntimeError("boom")
        """), tmp_path)
        assert p.returncode != 0
        assert "boom" in p.stderr           # original traceback kept
        dumps = list(tmp_path.glob("flightrec_*_crash.jsonl"))
        assert len(dumps) == 1, p.stderr
        lines = [json.loads(s) for s in
                 dumps[0].read_text().splitlines()]
        meta, recs = lines[0], lines[1:]
        assert meta["reason"] == "crash"
        assert "boom" in meta["event"]["error"]
        assert len(recs) >= 32
        assert [r["step"] for r in recs] == list(range(40))
        for r in recs:
            assert r["step_seconds"] > 0
            assert np.isfinite(r["loss"])
            assert r["collective_bytes"] >= 0
            assert "hbm_live_bytes" in r
        assert dumps[0].with_name(
            dumps[0].name.replace(".jsonl", ".trace.json")).exists()

    def test_sigterm_dump_and_redelivery(self, tmp_path):
        # preemption path: dump, then die OF SIGTERM (exit status must
        # still tell the scheduler the truth)
        p = _run_subproc(textwrap.dedent("""\
            import signal
            for _ in range(3):
                net.fit(ds)
            os.kill(os.getpid(), signal.SIGTERM)
        """), tmp_path)
        assert p.returncode == -signal.SIGTERM, p.stderr
        dumps = list(tmp_path.glob("flightrec_*_sigterm.jsonl"))
        assert len(dumps) == 1, p.stderr
        lines = [json.loads(s) for s in
                 dumps[0].read_text().splitlines()]
        assert lines[0]["reason"] == "sigterm"
        assert [r["step"] for r in lines[1:]] == [0, 1, 2]


# ----------------------------------------------------------------------
# bench provenance + regression gate
class TestBenchMeta:
    def test_fields(self):
        meta = diagnostics.bench_meta()
        assert meta["schema_version"] == diagnostics.SCHEMA_VERSION
        import jax
        assert meta["jax_version"] == jax.__version__
        assert meta["platform"] in ("cpu", "tpu", "gpu")
        assert meta["device_count"] >= 1
        assert isinstance(meta["env"], dict)


def _driver_record(n: int, line: dict) -> dict:
    """A driver-wrapper bench record: the bench's JSON line as the
    tail of a captured run, parsed once more under ``parsed``."""
    return {"n": n,
            "cmd": "if [ -f bench.py ]; then python bench.py; "
                   "else exit 0; fi",
            "rc": 0,
            "tail": "WARNING: a line of stderr noise\n"
                    + json.dumps(line) + "\n",
            "parsed": line}


class TestRegressionGate:
    @pytest.fixture(autouse=True)
    def _two_rounds(self, tmp_path):
        # two synthetic rounds with the keys a real round carries; the
        # newer one moved the headline by -0.5%
        base = {"metric": "resnet50_train_throughput", "value": 2610.38,
                "min": 2608.85, "max": 2618.73, "n_trials": 5,
                "unit": "images/sec/chip", "vs_baseline": 1.0,
                "tflops": 58.1, "pct_bf16_peak": 29.5,
                "pct_hbm_peak": 94.0,
                "scaling_harness_canary_ips": 1416.7,
                "pipeline_overhead_cpu_proxy_pct": -0.9}
        fresh = dict(base, value=2597.71, min=2597.46, max=2598.28,
                     tflops=57.8, pct_bf16_peak=29.3, pct_hbm_peak=93.5,
                     scaling_harness_canary_ips=945.9,
                     pipeline_overhead_cpu_proxy_pct=1.4)
        r04, r05 = tmp_path / "BENCH_r04.json", tmp_path / "BENCH_r05.json"
        r04.write_text(json.dumps(_driver_record(4, base), indent=2))
        r05.write_text(json.dumps(_driver_record(5, fresh), indent=2))
        self.R04, self.R05 = str(r04), str(r05)

    def _main(self, argv):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "check_bench_regression",
            _ROOT / "scripts" / "check_bench_regression.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_r04_to_r05_passes_default_threshold(self, capsys):
        mod = self._main(None)
        assert mod.main([self.R04, self.R05, "-q"]) == 0

    def test_tight_threshold_flags_throughput_drop(self, capsys):
        # r04 -> r05 moved the headline images/s by ~-0.5%: invisible
        # at the default 10%, a regression at 0.2%
        mod = self._main(None)
        assert mod.main([self.R04, self.R05, "--threshold", "0.2",
                         "-q"]) == 1
        out = capsys.readouterr().out
        assert "REGRESSION" in out and "value" in out

    def test_unusable_input_is_rc2(self, tmp_path):
        mod = self._main(None)
        bad = tmp_path / "bad.json"
        bad.write_text("[]")
        assert mod.main([str(bad), self.R05]) == 2

    def test_pct_metrics_compare_in_points(self):
        mod = self._main(None)
        base = {"metric": "m", "value": 100.0, "overhead_pct": -0.9}
        fresh = {"metric": "m", "value": 100.0, "overhead_pct": 1.4}
        regs, _, _ = mod.compare(base, fresh, 10.0)
        # 2.3 points of overhead growth is under a 10-point threshold;
        # the old relative math would have read it as -256%
        assert regs == []
        regs, _, _ = mod.compare(base, fresh, 1.0)
        assert [r[0] for r in regs] == ["overhead_pct"]

    def test_canary_keys_skipped(self):
        mod = self._main(None)
        base = {"metric": "m", "scaling_canary_ips": 100.0}
        fresh = {"metric": "m", "scaling_canary_ips": 1.0}
        regs, _, _ = mod.compare(base, fresh, 10.0)
        assert regs == []
