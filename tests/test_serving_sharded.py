"""Sharded-model serving residency (ISSUE 15): a dense checkpoint
restored onto a virtual 8-device mesh and kept resident sharded
between requests must serve the single-chip dense path's outputs (to a
float32 ulp: ``_assert_same_math``), with ~1/N of the dense parameter
bytes on each chip.

Runs on the 8-virtual-CPU-device rig (conftest sets
``xla_force_host_platform_device_count=8``); the module is listed in
``_MESH_ONLY_MODULES`` so it is skipped when the flag did not stick.
"""
import threading

import numpy as np
import pytest

from deeplearning4j_tpu.common import telemetry
from deeplearning4j_tpu.common.telemetry import MetricsRegistry
from deeplearning4j_tpu.serving import ModelRegistry, ServingBatcher


@pytest.fixture(autouse=True)
def _fresh_registry():
    MetricsRegistry._reset_for_tests()
    yield
    MetricsRegistry._reset_for_tests()


def _mlp(seed=42):
    """A small MLN whose layer widths divide by tp=2 (16 and 4), so
    the same net exercises dp-only and (dp x tp) residency."""
    from deeplearning4j_tpu.activations import Activation
    from deeplearning4j_tpu.learning.updaters import Sgd
    from deeplearning4j_tpu.lossfunctions import LossFunction
    from deeplearning4j_tpu.nn.conf.builders import \
        NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_in=8, n_out=16,
                              activation=Activation.TANH))
            .layer(OutputLayer(n_out=4,
                               loss_function=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(8))
            .build())
    return MultiLayerNetwork(conf).init()


def _mesh_1d():
    import jax

    from deeplearning4j_tpu.parallel.mesh import make_mesh
    return make_mesh({"data": 8}, jax.devices()[:8])


def _mesh_2d():
    import jax

    from deeplearning4j_tpu.parallel.mesh import make_mesh
    return make_mesh({"data": 4, "model": 2}, jax.devices()[:8])


def _dense_bytes(params) -> int:
    import jax
    return sum(int(np.prod(leaf.shape, dtype=np.int64) *
                   np.dtype(leaf.dtype).itemsize)
               for leaf in jax.tree_util.tree_leaves(params)
               if hasattr(leaf, "shape"))


def _assert_same_math(out, ref):
    """A sharded forward against the dense one: two partitionings of
    one computation. GSPMD orders the matmul reductions differently, so
    they agree to a float32 ulp (1.19e-07 absolute measured here), not
    bitwise; one program run twice stays ``assert_array_equal``."""
    np.testing.assert_allclose(out, ref, rtol=1e-6)


# ----------------------------------------------------------------------
class TestShardedServingEquivalence:
    @pytest.mark.parametrize("mode", ["sharded", "fsdp"])
    def test_bitwise_equal_to_dense_and_no_retrace(self, mode):
        """The tentpole acceptance: a dense checkpoint served with
        1/N-sharded residency returns the dense outputs to an ulp, and
        post-warmup requests never retrace."""
        net = _mlp()
        rng = np.random.RandomState(0)
        xs = [rng.randn(n, 8).astype(np.float32)
              for n in (1, 3, 8, 11)]
        refs = [np.asarray(net.output(x)) for x in xs]

        reg = ModelRegistry(_mesh_1d(), default_buckets=(8, 16))
        ver = reg.register("m", net, warmup_shape=(8,), mode=mode)
        assert ver.batcher.mode == mode
        assert ver.batcher._serve_params is not None
        for x, ref in zip(xs, refs):
            out = ver.batcher.submit(x).result(timeout=60)
            _assert_same_math(out, ref)
        assert reg.retraces_since_warmup("m") == 0
        # the describe() surface carries the residency mode
        assert reg.describe()[0]["versions"][0]["mode"] == mode
        reg.shutdown()

    def test_fsdp_times_tp_on_2d_mesh_bitwise_equal(self):
        """(dp=4 x tp=2): tensor-parallel leaves ride under TP_KEY,
        compute is gathered back to replicated — the same math."""
        net = _mlp(seed=7)
        rng = np.random.RandomState(1)
        xs = [rng.randn(n, 8).astype(np.float32) for n in (2, 8, 13)]
        refs = [np.asarray(net.output(x)) for x in xs]

        reg = ModelRegistry(_mesh_2d(), default_buckets=(8, 16))
        ver = reg.register("m2d", net, warmup_shape=(8,),
                           mode="fsdp", tensor_parallel=2)
        # the layout really engaged tp: at least one entry has tp specs
        assert ver.batcher._serve_tp_specs
        for x, ref in zip(xs, refs):
            out = ver.batcher.submit(x).result(timeout=60)
            _assert_same_math(out, ref)
        assert reg.retraces_since_warmup("m2d") == 0
        reg.shutdown()

    def test_sharded_mode_on_2d_mesh_defaults_tp_to_model_axis(self):
        """tensor_parallel=None on a (data, model) mesh picks up the
        model-axis extent automatically."""
        net = _mlp(seed=9)
        x = np.random.RandomState(2).randn(4, 8).astype(np.float32)
        ref = np.asarray(net.output(x))
        reg = ModelRegistry(_mesh_2d(), default_buckets=(8,))
        ver = reg.register("auto", net, warmup_shape=(8,),
                           mode="sharded")
        assert ver.batcher._serve_tp_specs
        _assert_same_math(ver.batcher.submit(x).result(timeout=60), ref)
        reg.shutdown()

    def test_tensor_parallel_must_match_mesh(self):
        net = _mlp()
        b = ServingBatcher(net, buckets=(8,), mesh=_mesh_1d(),
                           mode="sharded", tensor_parallel=3)
        with pytest.raises(ValueError, match="tensor_parallel"):
            b.warmup((8,))
        b.shutdown()


# ----------------------------------------------------------------------
class TestShardedResidency:
    def test_per_chip_residency_is_fraction_of_dense(self):
        """The memory half of the acceptance: what one chip holds
        under sharded residency is ~1/8 of the dense tree (flat-pad
        overhead allowed), surfaced through batcher.params ->
        memory_report and the residency gauge."""
        from deeplearning4j_tpu.common.diagnostics import memory_report
        from deeplearning4j_tpu.serving.residency import \
            resident_param_bytes
        net = _mlp()
        dense = _dense_bytes(net.params)
        reg = ModelRegistry(_mesh_1d(), default_buckets=(8,))
        ver = reg.register("m", net, warmup_shape=(8,), mode="sharded")

        resident = resident_param_bytes(ver.batcher.params)
        assert 0 < resident <= dense / 4, \
            f"resident {resident} not ~1/8 of dense {dense}"
        # ravel-pad keeps it near 1/8, never below the exact shard
        assert resident >= dense / 8

        report = memory_report(model=ver.batcher)
        attr = report["models"]["ServingBatcher"]
        assert attr["params_resident_bytes"] == resident
        # logical bytes stay the full checkpoint size
        assert attr["params_bytes"] >= dense

        g = telemetry.gauge("dl4j_serving_param_resident_bytes")
        assert g.value(model="m", mode="sharded") == resident
        reg.shutdown()

    def test_dense_mode_keeps_model_params_surface(self):
        """mode='dense' leaves batcher.params aliased to the model's
        own tree — no placed layout, no gauge."""
        net = _mlp()
        b = ServingBatcher(net, buckets=(8,))
        assert b._serve_params is None
        assert b.params is net.params
        b.shutdown()

    def test_model_output_stays_dense_after_sharded_serving(self):
        """The sharded layout lives on the batcher, never the model:
        the training-side model.output path is untouched."""
        net = _mlp()
        x = np.random.RandomState(3).randn(5, 8).astype(np.float32)
        ref = np.asarray(net.output(x))
        reg = ModelRegistry(_mesh_1d(), default_buckets=(8,))
        ver = reg.register("m", net, warmup_shape=(8,), mode="fsdp")
        ver.batcher.submit(x).result(timeout=60)
        # model params are still the plain dense tree
        np.testing.assert_array_equal(np.asarray(net.output(x)), ref)
        reg.shutdown()


# ----------------------------------------------------------------------
class TestShardedLifecycle:
    def test_hot_swap_while_sharded_is_hitless(self):
        """Hot-swapping a sharded model under a request stream drops
        nothing: every response matches v1's or v2's dense math."""
        net1, net2 = _mlp(seed=42), _mlp(seed=99)
        x = np.random.RandomState(4).randn(4, 8).astype(np.float32)
        ref1 = np.asarray(net1.output(x))
        ref2 = np.asarray(net2.output(x))
        assert not np.allclose(ref1, ref2, rtol=1e-3)

        reg = ModelRegistry(_mesh_1d(), default_buckets=(8,))
        reg.register("m", net1, warmup_shape=(8,), mode="sharded")

        results, errors = [], []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    fut = reg.model("m").batcher.submit(x)
                    results.append(np.asarray(fut.result(timeout=60)))
                except Exception as e:      # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            ver2 = reg.register("m", net2, warmup_shape=(8,),
                                mode="sharded")
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        assert not errors, errors[:3]
        assert results
        for out in results:
            assert (np.allclose(out, ref1, rtol=1e-6, atol=0)
                    or np.allclose(out, ref2, rtol=1e-6, atol=0))
        # post-swap traffic serves v2, the same math, still warm
        _assert_same_math(ver2.batcher.submit(x).result(timeout=60),
                          ref2)
        assert reg.retraces_since_warmup("m") == 0
        assert telemetry.counter(
            "dl4j_serving_hot_swaps_total").value(model="m") == 1
        reg.shutdown()

    def test_zip_restore_registers_sharded(self, tmp_path):
        """The headline workflow: a dense checkpoint on disk is
        restored straight into sharded residency and serves the dense
        outputs."""
        from deeplearning4j_tpu.utils.serializer import ModelSerializer
        net = _mlp(seed=5)
        x = np.random.RandomState(6).randn(6, 8).astype(np.float32)
        ref = np.asarray(net.output(x))
        path = str(tmp_path / "model.zip")
        ModelSerializer.write_model(net, path)

        reg = ModelRegistry(_mesh_1d(), default_buckets=(8,))
        ver = reg.register("restored", path, warmup_shape=(8,),
                           mode="fsdp")
        assert ver.source == path
        assert ver.batcher._serve_params is not None
        _assert_same_math(ver.batcher.submit(x).result(timeout=60), ref)
        assert reg.retraces_since_warmup("restored") == 0
        reg.shutdown()
