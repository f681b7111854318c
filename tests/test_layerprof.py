"""Layer-attribution observatory tests (ISSUE 14): scope annotation
semantics and gating, the analytic HLO partition (incl. while-loop
trip counts), static/dynamic attribution and their reconciliation
contract, the kernel-decision join, and the report surfaces
(``/api/layers``, flight-recorder ``top_layer``, ``dl4j_layer_*``
metrics)."""
import json
import urllib.error
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

from deeplearning4j_tpu.common import layerprof, telemetry
from deeplearning4j_tpu.common.environment import Environment


@pytest.fixture(autouse=True)
def _fresh_layerprof():
    layerprof.reset()
    Environment.get().extra.pop("layerprof", None)
    yield
    layerprof.reset()
    Environment.get().extra.pop("layerprof", None)


def _tiny_net_and_data():
    from deeplearning4j_tpu.activations import Activation
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.lossfunctions import LossFunction
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 16)]
    net = MultiLayerNetwork(
        (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-3))
         .list()
         .layer(DenseLayer(n_out=16, activation=Activation.RELU))
         .layer(OutputLayer(n_out=4, activation=Activation.SOFTMAX,
                            loss_function=LossFunction.MCXENT))
         .set_input_type(InputType.feed_forward(8)).build())).init()
    return net, x, y


class TestScope:
    def test_sanitize(self):
        assert layerprof.sanitize("layer_0") == "layer_0"
        assert layerprof.sanitize("conv 1x1/a!") == "conv_1x1_a_"
        assert layerprof.sanitize("enc.ffn") == "enc.ffn"
        assert layerprof.sanitize("") == "_"

    def test_scope_stack_nests_and_pops(self):
        assert layerprof.current_scope() is None
        with layerprof.scope("outer"):
            assert layerprof.current_scope() == "outer"
            with layerprof.scope("inner x"):
                assert layerprof.current_scope() == "inner_x"
            assert layerprof.current_scope() == "outer"
        assert layerprof.current_scope() is None

    def test_gate_off_is_a_null_scope(self):
        Environment.get().extra["layerprof"] = False
        assert not layerprof.enabled()
        with layerprof.scope("ghost"):
            assert layerprof.current_scope() is None
        Environment.get().extra["layerprof"] = True
        assert layerprof.enabled()

    def test_env_var_gate(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_LAYERPROF", "0")
        assert not layerprof.enabled()
        # Environment.extra overrides the env var
        Environment.get().extra["layerprof"] = True
        assert layerprof.enabled()


class TestKernelJoin:
    def test_note_selection_joins_on_active_scope(self):
        sel = SimpleNamespace(kernel="conv_epilogue", fused=True,
                              decision="heuristic", reason="big tile")
        with layerprof.scope("layer_3"):
            layerprof.note_selection(sel)
            layerprof.note_selection(sel)
        got = layerprof.kernel_decisions("layer_3")
        assert got["conv_epilogue"]["fused"] is True
        assert got["conv_epilogue"]["decision"] == "heuristic"
        assert got["conv_epilogue"]["sites"] == 2
        # outside any scope the decision still lands somewhere visible
        layerprof.note_selection(SimpleNamespace(
            kernel="flash", fused=False, decision="structural",
            reason="seq too short"))
        assert "flash" in layerprof.kernel_decisions("_unscoped")


_SCAN_HLO = """\
%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]) parameter(0)
  %a = f32[4,4] get-tuple-element(%p), index=1
  %d = f32[4,4] dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/dl4j.scan_layer/dot"}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[4,4]) tuple(%i, %d)
}

%cond (p.1: (s32[], f32[4,4])) -> pred[] {
  %p.1 = (s32[], f32[4,4]) parameter(0)
  %i.1 = s32[] get-tuple-element(%p.1), index=0
  %trip = s32[] constant(8)
  ROOT %lt = pred[] compare(%i.1, %trip), direction=LT
}

ENTRY %main (x: f32[4,4]) -> f32[4,4] {
  %x = f32[4,4] parameter(0)
  %d0 = f32[4,4] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/dl4j.head/dot"}
  %w = (s32[], f32[4,4]) while(%d0), condition=%cond, body=%body
  ROOT %r = f32[4,4] get-tuple-element(%w), index=1
}
"""


class TestHloParser:
    def test_while_body_weighted_by_trip_count(self):
        """A lax.scan-shaped while must charge its body per executed
        trip: the dot inside an 8-trip loop costs 8x the identical
        entry-level dot; the cond's comparison work stays free."""
        costs = layerprof.parse_hlo(_SCAN_HLO)
        # 4x4 @ 4x4 dot = 2*16*4 = 128 flops
        assert costs["head"].flops_fwd == pytest.approx(128.0)
        assert costs["scan_layer"].flops_fwd == pytest.approx(8 * 128.0)

    def test_transpose_opname_lands_in_bwd(self):
        hlo = _SCAN_HLO.replace(
            'op_name="jit(f)/dl4j.head/dot"',
            'op_name="jit(f)/transpose(dl4j.head)/dot"')
        costs = layerprof.parse_hlo(hlo)
        assert costs["head"].flops_bwd == pytest.approx(128.0)
        assert costs["head"].flops_fwd == 0.0


class TestStaticAttribution:
    def test_jitted_fn_partition_reconciles(self):
        import jax
        import jax.numpy as jnp

        def loss(w1, w2, x):
            with layerprof.scope("dense1"):
                h = jnp.tanh(x @ w1)
            with layerprof.scope("dense2"):
                o = h @ w2
            return (o * o).sum()

        rng = np.random.RandomState(0)
        args = (jnp.asarray(rng.randn(32, 64), jnp.float32),
                jnp.asarray(rng.randn(64, 16), jnp.float32),
                jnp.asarray(rng.randn(8, 32), jnp.float32))
        compiled = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1))
        ).lower(*args).compile()
        rep = layerprof.attribute_compiled(compiled, model_name="toy")

        for name in ("dense1", "dense2"):
            ent = rep["layers"][name]
            assert ent["flops_fwd"] > 0, name
            assert ent["flops_bwd"] > 0, name
            assert ent["bound"] in ("compute", "hbm")
            assert ent["est_ms"] > 0
        # the contract the CI gate sells: per-layer sums == totals
        assert layerprof.reconcile_error_pct(rep) < 1.0
        assert rep["coverage"]["flops"] > 0.5
        assert rep["time_source"] == "static_roofline_model"
        # publication side effects
        assert layerprof.last_report() is rep
        assert layerprof.top_layer() in rep["layers"]

    def test_mln_layer_report_and_surfaces(self):
        from deeplearning4j_tpu.common import diagnostics
        net, x, y = _tiny_net_and_data()
        rep = net.layer_report(x, y)
        assert {"layer_0", "layer_1"} <= set(rep["layers"])
        assert layerprof.reconcile_error_pct(rep) < 1.0
        for name in ("layer_0", "layer_1"):
            ent = rep["layers"][name]
            assert ent["flops_fwd"] > 0 and ent["flops_bwd"] > 0
            # the dl4j_layer_* gauges track the report
            assert telemetry.gauge(
                "dl4j_layer_flops", "x").value(layer=name) \
                == ent["flops"]
            assert telemetry.gauge(
                "dl4j_layer_bytes", "x").value(layer=name) \
                == ent["bytes"]
        # flight-recorder records stamp the heaviest layer
        assert layerprof.top_layer() is not None
        fr = diagnostics.FlightRecorder.get()
        fr.record(net, "test", 0, 0.5)
        assert fr.records()[-1]["top_layer"] == layerprof.top_layer()


class TestDynamicAttribution:
    def _events(self):
        return [
            {"name": "dl4j.layer_0", "ph": "X", "ts": 0, "dur": 2000},
            {"name": "fusion.7", "ph": "X", "ts": 10, "dur": 1000,
             "args": {"op_name": "jit(step)/dl4j.layer_0/dot"}},
            {"name": "transpose(dl4j.layer_0)", "ph": "X", "ts": 20,
             "dur": 4000},
            {"name": "dl4j.layer_1", "ph": "B", "ts": 30},  # not ph=X
            {"name": "no_scope_here", "ph": "X", "ts": 40, "dur": 99},
        ]

    def test_attribute_trace_buckets_and_observes(self):
        before = telemetry.histogram(
            "dl4j_layer_seconds", "x").count_of(
            layer="layer_0", **{"pass": "fwd"})
        out = layerprof.attribute_trace(self._events())
        assert set(out) == {"layer_0"}
        assert out["layer_0"]["fwd_ms"] == pytest.approx(3.0)
        assert out["layer_0"]["bwd_ms"] == pytest.approx(4.0)
        after = telemetry.histogram(
            "dl4j_layer_seconds", "x").count_of(
            layer="layer_0", **{"pass": "fwd"})
        assert after == before + 1

    def test_share_step_time_and_join(self):
        net, x, y = _tiny_net_and_data()
        rep = net.layer_report(x, y)
        split = layerprof.share_step_time(rep, 10.0)
        # the measured wall time is conserved across the split
        total = sum(m["fwd_ms"] + m["bwd_ms"] for m in split.values())
        assert total == pytest.approx(10.0, rel=1e-6)
        assert rep["time_source"] == "static_share_proxy"
        # the CPU backend is not in diagnostics.DEVICE_PEAKS: the v5e
        # ridge is a labelled weighting and nothing is a percent of it
        assert rep["peaks"]["weighting_only"]
        for name in ("layer_0", "layer_1"):
            ent = rep["layers"][name]
            assert ent["fwd_ms"] + ent["bwd_ms"] > 0
            assert ent.get("pct_of_roof") is None
        # peaks the caller vouches for do give a percent
        rep_p = net.layer_report(x, y, peak_tflops=1.0,
                                 peak_hbm_gbps=1.0)
        layerprof.share_step_time(rep_p, 10.0)
        assert rep_p["layers"]["layer_0"]["pct_of_roof"] is not None
        # explicit join path: measured ms replace the shares
        rep2 = layerprof.join_dynamic(
            rep, {"layer_0": {"fwd_ms": 1.0, "bwd_ms": 2.0}},
            time_source="trace")
        assert rep2["layers"]["layer_0"]["fwd_ms"] == 1.0
        assert rep2["time_source"] == "trace"


class TestApiLayers:
    def test_endpoint_404_then_report(self):
        from deeplearning4j_tpu.ui.server import UIServer
        ui = UIServer()                  # fresh instance, not the
        ui.start(port=0)                 # singleton: tests stay isolated
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(ui.url + "/api/layers")
            assert ei.value.code == 404
            assert "no layer report" in json.loads(
                ei.value.read().decode())["error"]

            net, x, y = _tiny_net_and_data()
            rep = net.layer_report(x, y)
            with urllib.request.urlopen(ui.url + "/api/layers") as r:
                assert r.status == 200
                body = json.loads(r.read().decode())
            assert set(body["layers"]) == set(rep["layers"])
            assert body["totals"]["flops"] == rep["totals"]["flops"]
        finally:
            ui.stop()
