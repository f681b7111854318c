"""Threshold-encoded update exchange (parallel.zero ENCODED — ISSUE
20) on the virtual 8-device CPU mesh, plus the low-precision serving
residency it shares a PR with.

Covers: encoded-vs-dense 20-step convergence under error feedback,
the bitwise dense-layout checkpoint round-trip restored onto a
DIFFERENT device count, the `DL4J_TPU_ENCODED_UPDATE` kill switch and
resolver fallbacks, and `param_dtype="bf16"|"int8"` serving residency
(resident bytes shrink; f32 stays the dense math to an ulp,
low-precision stays within tolerance).
"""
import numpy as np
import pytest

import jax

from deeplearning4j_tpu.activations import Activation
from deeplearning4j_tpu.common.telemetry import MetricsRegistry
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.learning.updaters import (ENCODED_KEY, Adam,
                                                  Sgd, is_encoded)
from deeplearning4j_tpu.lossfunctions import LossFunction
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.weights import WeightInit
from deeplearning4j_tpu.parallel import ParallelWrapper, UpdateExchange
from deeplearning4j_tpu.parallel.mesh import MeshFactory
from deeplearning4j_tpu.parallel.zero import (ensure_encoded_states,
                                              resolve_update_exchange,
                                              states_to_dense)


@pytest.fixture(autouse=True)
def _fresh_registry():
    MetricsRegistry._reset_for_tests()
    yield
    MetricsRegistry._reset_for_tests()


def _mlp(updater=None, seed=42):
    conf = (NeuralNetConfiguration.Builder()
            .seed(seed)
            .updater(updater or Sgd(0.1))
            .weight_init(WeightInit.XAVIER)
            .list()
            .layer(DenseLayer(n_in=8, n_out=16,
                              activation=Activation.TANH))
            .layer(OutputLayer(n_out=3,
                               loss_function=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(8))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)]
    return DataSet(x, y)


def _assert_tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- convergence under error feedback --------------------------------------
def test_encoded_tracks_dense_convergence_20_steps():
    """The satellite's stated tolerance: over 20 steps on identical
    batches, error-feedback residuals must keep the encoded loss
    trajectory within 0.05 absolute of the uncompressed dense run's,
    and the encoded loss must actually descend."""
    batches = [_data(64, seed=i % 4) for i in range(20)]
    # score on the whole training set, all four batches of the cycle:
    # a disjoint random-label probe set can legitimately rise while the
    # fit loss falls, and so can ONE training batch — after step 19
    # batch 0 was last fitted three steps ago and sits at the top of
    # its cycle, above where step 0 left it, in the dense run too
    # (1.0731 against 1.0603) while the set's mean has fallen at every
    # step (1.1303 -> 1.0484)
    def score(net):
        return float(np.mean([net.score(b) for b in batches[:4]]))

    finals = {}
    for mode in ("dense", "encoded"):
        net = _mlp(Adam(0.01), seed=7)
        pw = ParallelWrapper.Builder(net).workers(8) \
            .update_exchange(mode).build()
        first = None
        for ds in batches:
            pw.fit_batch(ds)
            if first is None:
                first = score(net)
        finals[mode] = score(net)
        assert finals[mode] < first, f"{mode} loss did not descend"
        if mode == "encoded":
            assert pw.update_exchange is UpdateExchange.ENCODED
            assert any(is_encoded(s)
                       for s in net.updater_states.values())
    assert abs(finals["encoded"] - finals["dense"]) < 0.05, finals


# -- checkpoint round-trip onto a different device count -------------------
def test_encoded_checkpoint_roundtrips_onto_different_device_count(
        tmp_path):
    """Checkpoints from an encoded run store the exact dense layout
    (params AND the error-feedback residual), restore bitwise, and the
    residual re-ravels losslessly for a different shard count."""
    from deeplearning4j_tpu.utils.serializer import ModelSerializer
    net = _mlp(Adam(0.01), seed=9)
    pw = ParallelWrapper.Builder(net).workers(8) \
        .update_exchange("encoded").build()
    for i in range(3):
        pw.fit_batch(_data(64, seed=i))
    assert any(is_encoded(s) for s in net.updater_states.values())

    path = tmp_path / "enc.zip"
    ModelSerializer.write_model(net, path)
    restored = ModelSerializer.restore_multi_layer_network(path)

    # bitwise round-trip of params and the dense-layout updater state
    _assert_tree_equal(restored.params, net.params)
    live_dense = states_to_dense(net.params, net.updater_states)
    _assert_tree_equal(restored.updater_states, live_dense)

    # the dense residual re-ravels for a DIFFERENT device count and
    # converts back to the identical dense layout (pad zeros only)
    pw4 = ParallelWrapper.Builder(restored).workers(4) \
        .update_exchange("encoded").build()
    pw4.fit_batch(_data(64, seed=3))
    assert pw4.update_exchange is UpdateExchange.ENCODED
    assert pw4.n_workers == 4
    assert any(is_encoded(s)
               for s in restored.updater_states.values())
    assert np.isfinite(restored.score(_data(64, seed=3)))


def test_encoded_reravel_is_lossless_across_shard_counts():
    """ensure -> dense -> ensure(other count) -> dense is bitwise: the
    device-count portability claim, isolated from training noise."""
    from deeplearning4j_tpu.parallel.encoding import resolve_encoding
    net = _mlp(Adam(0.01), seed=3)
    pw = ParallelWrapper.Builder(net).workers(8) \
        .update_exchange("encoded").build()
    pw.fit_batch(_data(64, seed=0))
    enc = resolve_encoding(None)
    dense8 = states_to_dense(net.params, net.updater_states)
    re4 = ensure_encoded_states(net.params, dense8, 4, enc)
    dense4 = states_to_dense(net.params, re4)
    _assert_tree_equal(dense4, dense8)


# -- kill switch and resolver fallbacks ------------------------------------
def test_encoded_kill_switch_demotes_to_sharded(monkeypatch):
    """DL4J_TPU_ENCODED_UPDATE=0 keeps the uncompressed sharded rung
    even when encoded was requested — the exchange still shards, it
    just stops compressing."""
    from deeplearning4j_tpu.common.environment import Environment
    mesh = MeshFactory.data_parallel()
    monkeypatch.setenv("DL4J_TPU_ENCODED_UPDATE", "0")
    Environment.reset()
    try:
        assert resolve_update_exchange(mesh, requested="encoded") \
            is UpdateExchange.SHARDED
        net = _mlp(Adam(0.01))
        pw = ParallelWrapper.Builder(net).workers(8) \
            .update_exchange("encoded").build()
        pw.fit_batch(_data(64))
        assert pw.update_exchange is UpdateExchange.SHARDED
        assert not any(is_encoded(s)
                       for s in net.updater_states.values())
    finally:
        monkeypatch.delenv("DL4J_TPU_ENCODED_UPDATE")
        Environment.reset()


def test_encoded_resolver_fallbacks():
    """Gradient normalization and dp<=1 both demote encoded to DENSE
    (same reasons as the sharded rung: per-layer norms need whole
    gradients; one replica has no wire to compress)."""
    from deeplearning4j_tpu.nn.conf.builders import GradientNormalization
    mesh = MeshFactory.data_parallel()
    net = _mlp()
    net.conf.gradient_normalization = \
        GradientNormalization.CLIP_L2_PER_LAYER
    assert resolve_update_exchange(mesh, requested="encoded",
                                   model=net) is UpdateExchange.DENSE
    one = MeshFactory.data_parallel(1)
    assert resolve_update_exchange(one, requested="encoded") \
        is UpdateExchange.DENSE
    assert resolve_update_exchange(None, requested="encoded") \
        is UpdateExchange.DENSE


def test_encoded_state_strips_when_stepping_dense():
    """Mode change encoded -> dense must not leak the residual into
    dense updater math (ENCODED_KEY stripped at the layout sync)."""
    net = _mlp(Adam(0.01), seed=5)
    pw = ParallelWrapper.Builder(net).workers(8) \
        .update_exchange("encoded").build()
    pw.fit_batch(_data(64, seed=0))
    assert any(is_encoded(s) for s in net.updater_states.values())
    pw2 = ParallelWrapper.Builder(net).workers(8) \
        .update_exchange("dense").build()
    pw2.fit_batch(_data(64, seed=1))
    assert not any(is_encoded(s)
                   for s in net.updater_states.values())
    assert not any(isinstance(s, dict) and ENCODED_KEY in s
                   for s in net.updater_states.values())


# -- low-precision serving residency ---------------------------------------
def _serving_mlp(seed=42):
    conf = (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Sgd(0.1))
            .weight_init(WeightInit.XAVIER)
            .list()
            .layer(DenseLayer(n_in=8, n_out=16,
                              activation=Activation.TANH))
            .layer(OutputLayer(n_out=4,
                               loss_function=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(8))
            .build())
    return MultiLayerNetwork(conf).init()


@pytest.mark.parametrize("mode", ["sharded", "fsdp"])
def test_serving_param_dtype_shrinks_residency_within_tolerance(mode):
    """register(param_dtype=) acceptance: bf16 halves the resident
    param bytes and int8 cuts them to ~1/4 (+ scales), while outputs
    stay the dense math for f32 (to an ulp: the sharded forward is
    another partitioning of it, see test_serving_sharded) and within
    float tolerance for the cast storage dtypes."""
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.serving import ServingBatcher
    from deeplearning4j_tpu.serving.residency import \
        resident_param_bytes
    net = _serving_mlp()
    mesh = make_mesh({"data": 8}, jax.devices()[:8])
    x = np.random.RandomState(0).randn(5, 8).astype(np.float32)
    ref = np.asarray(net.output(x))
    resident = {}
    for pd in (None, "bf16", "int8"):
        b = ServingBatcher(net, buckets=(8,), mesh=mesh, mode=mode,
                           param_dtype=pd)
        b.warmup((8,))
        out = b.submit(x).result(timeout=60)
        resident[pd] = resident_param_bytes(b._serve_params)
        if pd is None:
            np.testing.assert_allclose(out, ref, rtol=1e-6)
        else:
            np.testing.assert_allclose(out, ref, rtol=0.05, atol=0.02)
        b.shutdown()
    assert resident["bf16"] <= resident[None] * 0.55
    assert resident["int8"] <= resident[None] * 0.35


def test_serving_param_dtype_gauge_and_registry_roundtrip():
    """The registry surface: register(param_dtype='bf16') serves and
    the dl4j_serving_param_resident_bytes gauge reads about half the
    f32 series for the same checkpoint."""
    from deeplearning4j_tpu.common import telemetry
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.serving import ModelRegistry
    mesh = make_mesh({"data": 8}, jax.devices()[:8])
    g = telemetry.gauge("dl4j_serving_param_resident_bytes", "")
    reg = ModelRegistry(mesh, default_buckets=(8,))
    reg.register("full", _serving_mlp(), warmup_shape=(8,),
                 mode="sharded")
    reg.register("half", _serving_mlp(), warmup_shape=(8,),
                 mode="sharded", param_dtype="bf16")
    full = g.value(model="full", mode="sharded")
    half = g.value(model="half", mode="sharded")
    assert full and half and half == full // 2
    reg.shutdown()


def test_serving_param_dtype_rejects_dense_mode():
    from deeplearning4j_tpu.serving import ServingBatcher
    with pytest.raises(ValueError, match="param_dtype"):
        ServingBatcher(_serving_mlp(), buckets=(8,), mesh=None,
                       mode="dense", param_dtype="bf16")


def test_kv_dtype_env_default_halves_pool_bytes(monkeypatch):
    """DL4J_TPU_KV_DTYPE=bf16 becomes the KVBlockPool default dtype
    (per-model generate={'kv_dtype': ...} still wins)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.decoder import (DecoderConfig,
                                                   DecoderLM)
    from deeplearning4j_tpu.serving.batcher import ServingBatcher
    conf = DecoderConfig.tiny()
    gen = {"kv_blocks": 8, "kv_block_size": 8, "prompt_buckets": (16,),
           "decode_buckets": (4,), "max_seq_len": 32}
    b32 = ServingBatcher(DecoderLM(conf), buckets=(8,), mesh=None,
                         name="kv32", generate=dict(gen))
    pool32 = b32._ensure_generate().pool
    assert pool32.k.dtype == jnp.float32
    monkeypatch.setenv("DL4J_TPU_KV_DTYPE", "bf16")
    b16 = ServingBatcher(DecoderLM(conf), buckets=(8,), mesh=None,
                         name="kv16", generate=dict(gen))
    pool16 = b16._ensure_generate().pool
    assert pool16.k.dtype == jnp.bfloat16
    assert pool16.pool_bytes == pool32.pool_bytes // 2
    b32.shutdown()
    b16.shutdown()
