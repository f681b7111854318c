"""ZeRO-1 cross-replica sharded weight update (parallel.zero) on the
virtual 8-device CPU mesh (ISSUE 5).

Covers: flat ravel/unravel padding round-trip, update-tail bitwise
equivalence (Sgd) / float tolerance (Adam family), end-to-end sharded
vs dense trainer parity, gradient accumulation = one big-batch step,
checkpoint round-trip of sharded updater state, the env kill switch,
training_mode validation, and the new telemetry surfaces.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.activations import Activation
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.learning.updaters import (Adam, Nesterovs, Sgd,
                                                  dp_flatten_spec,
                                                  dp_ravel, dp_unravel,
                                                  is_dp_sharded)
from deeplearning4j_tpu.lossfunctions import LossFunction
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.weights import WeightInit
from deeplearning4j_tpu.parallel import ParallelWrapper, UpdateExchange
from deeplearning4j_tpu.parallel.mesh import MeshFactory
from deeplearning4j_tpu.parallel.zero import (apply_update_sharded,
                                              resolve_update_exchange,
                                              states_to_dense,
                                              to_sharded_state,
                                              update_exchange_bytes)


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


def _assert_tree_close(a, b, **kw):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)


# -- flat layout -----------------------------------------------------------
def test_dp_ravel_unravel_odd_sizes_roundtrip():
    """Leaves whose total count is NOT a multiple of the shard count
    pad with zeros and unravel back bitwise (the output layer here has
    51 params -> padded to 56 for 8 shards)."""
    rng = np.random.default_rng(0)
    tree = {"W": jnp.asarray(rng.normal(size=(16, 3)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32)}
    flats, spec = dp_ravel(tree, 8)
    (orig, padded), = spec.sizes.values()
    assert orig == 51 and padded == 56 and padded % 8 == 0
    flat = next(iter(flats.values()))
    assert flat.shape == (56,)
    np.testing.assert_array_equal(np.asarray(flat[51:]), np.zeros(5))
    back = dp_unravel(flats, spec)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(tree[k]),
                                      np.asarray(back[k]))


def test_update_exchange_bytes_ring_formula():
    params = {"W": jnp.zeros((10, 10), jnp.float32)}   # 400 bytes
    assert update_exchange_bytes(params, 1) == 0
    assert update_exchange_bytes(params, 8) == int(2 * 7 * 400 / 8)


# -- the update tail, isolated ---------------------------------------------
def test_update_tail_sgd_bitwise_adam_tolerance():
    """Same summed gradient in -> the sharded tail's per-element math
    is the dense updater's: bitwise for Sgd (ISSUE 5 acceptance),
    float tolerance for Adam (f32 fusion ordering)."""
    mesh = MeshFactory.data_parallel()
    rng = np.random.default_rng(0)
    params = {"W": jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(17,)), jnp.float32)}
    grads = {"W": jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(17,)), jnp.float32)}
    for upd, exact in ((Sgd(0.1), True), (Adam(0.01), False)):
        state = upd.init_state(params)
        u, _ = upd.apply(grads, state, jnp.asarray(0))
        dense_new = {k: params[k] - u[k] for k in params}
        sh_state = to_sharded_state(params, state, mesh.shape["data"])
        f = jax.jit(lambda p, g, s: apply_update_sharded(
            upd, g, p, s, jnp.asarray(0), mesh))
        new_p, new_s = f(params, grads, sh_state)
        for k in params:
            a, b = np.asarray(dense_new[k]), np.asarray(new_p[k])
            if exact:
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        if state:
            assert is_dp_sharded(new_s)
            # state leaves actually live 1/N per device
            for leaf in jax.tree_util.tree_leaves(new_s):
                shards = leaf.addressable_shards
                assert len(shards) == 8
                assert shards[0].data.shape[0] == leaf.shape[0] // 8
        else:
            assert new_s == ()


# -- the dispatch over mode and tp, isolated -------------------------------
def _entry(rng, names):
    shapes = {"W": (8, 16), "b": (16,), "g": (7,)}
    return {n: jnp.asarray(rng.normal(size=shapes[n]), jnp.float32)
            for n in names}


_DISPATCH_CASES = [(mode, tp) for mode in ("dense", "sharded", "encoded",
                                           "fsdp")
                   for tp in (None, "mixed")] + \
    [("sharded", "all_tp"), ("encoded", "all_tp")]


@pytest.mark.parametrize("updater", [Sgd(0.1), Adam(0.01)],
                         ids=["sgd", "adam"])
@pytest.mark.parametrize("mode,tp", _DISPATCH_CASES)
def test_apply_update_dispatch_matches_dense(mode, tp, updater):
    """``zero.apply_update`` is the one place that decides which tail
    runs for which mode and how tp leaves split off an entry: two steps
    through it in every mode, with and without tp specs (``mixed``:
    W/b tensor-parallel, g in the dp flats; ``all_tp``: no dp flats at
    all, "no rest" keeps state), land on the plain dense updater's
    params and state. The encoded tail is lossy by design — what it
    must conserve is the error feedback: the gradient the updater saw
    is ``g + residual_in - residual_out`` on the flats' leaves."""
    from deeplearning4j_tpu.learning.updaters import ENCODED_KEY
    from deeplearning4j_tpu.parallel.encoding import (
        EncodingSpec, ResidualClippingPostProcessor)
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.speclayout import SpecLayout
    from deeplearning4j_tpu.parallel import zero
    mode = UpdateExchange(mode)
    rng = np.random.default_rng(3)
    names = ("W", "b") if tp == "all_tp" else ("W", "b", "g")
    params = _entry(rng, names)
    grads = [_entry(rng, names), _entry(rng, names)]
    if tp:
        mesh = make_mesh({"data": 4, "model": 2}, jax.devices()[:8])
        specs = SpecLayout(mesh).infer_entry(
            params, shard_over_data=mode is not UpdateExchange.DENSE)
        assert set(specs) == {"W", "b"}
    else:
        mesh, specs = MeshFactory.data_parallel(), {}
    n = mesh.shape["data"]
    # no residual clipping: it is the one step of the codec that
    # drops error feedback on purpose
    encoding = (EncodingSpec(residual_post=ResidualClippingPostProcessor(
        frequency=0)) if mode is UpdateExchange.ENCODED else None)

    state = updater.init_state(params)
    if mode is UpdateExchange.ENCODED:
        st = zero.ensure_encoded_state(params, state, n, encoding,
                                       tp_names=tuple(specs))
    elif mode is UpdateExchange.DENSE:
        st = state
    else:
        st = zero.to_sharded_state(params, state, n,
                                   tp_names=tuple(specs))
    if mode is UpdateExchange.FSDP:
        flat, fspecs = zero.params_to_fsdp({"e": params}, n,
                                           tp_specs={"e": specs})
        p = flat["e"]

        def to_layout(g):
            return zero.params_to_fsdp({"e": g}, n,
                                       tp_specs={"e": specs})[0]["e"]

        def to_dense(q):
            return zero.params_to_dense({"e": q}, fspecs)["e"]
    else:
        p = params

        def to_layout(g):
            return g

        def to_dense(q):
            return q

    step = jax.jit(lambda g, q, s, it: zero.apply_update(
        updater, g, q, s, it, mesh=mesh, mode=mode, tp_specs=specs,
        encoding=encoding))

    rest_spec = dp_flatten_spec(
        {k: a for k, a in params.items() if k not in specs}, n)

    def residual(s):
        if not (isinstance(s, dict) and ENCODED_KEY in s):
            return None
        return dp_unravel(s[ENCODED_KEY]["residual"], rest_spec)

    ref_p, ref_s = params, state
    for it, g in enumerate(grads):
        res_in = residual(st)
        p, st = step(to_layout(g), p, st, jnp.asarray(it))
        res_out = residual(st)
        if res_out is not None:
            g = {**g, **{k: g[k] + res_in[k] - res_out[k]
                         for k in res_out}}
        u, ref_s = updater.apply(g, ref_s, jnp.asarray(it))
        ref_p = {k: ref_p[k] - u[k] for k in ref_p}
        _assert_tree_close(to_dense(p), ref_p, rtol=2e-6, atol=1e-6)
    if mode is UpdateExchange.ENCODED:
        assert (res_out is None) == (tp == "all_tp")
        st = zero.strip_encoded_state(st)
    _assert_tree_close(zero.to_dense_state(params, st), ref_s,
                       rtol=2e-6, atol=1e-6)


@pytest.mark.parametrize("case", ["keep_dtype", "promotes", "clip"])
def test_apply_update_dense_call_site_arithmetic(case):
    """The two things a call site chooses about the dense tail:
    SameDiff keeps each variable's own dtype under an f32 updater, the
    network classes keep what the subtraction gives; the network
    classes' gradient normalization runs before the updater."""
    from deeplearning4j_tpu.nn.conf.builders import GradientNormalization
    from deeplearning4j_tpu.parallel import zero
    rng = np.random.default_rng(5)
    params = {"W": jnp.asarray(rng.normal(size=(4, 4)), jnp.bfloat16)}
    grads = {"W": jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)}
    upd = Sgd(0.1)
    if case == "clip":
        new_p, _ = zero.apply_update(
            upd, grads, params, (), jnp.asarray(0), normalization=(
                GradientNormalization.CLIP_ELEMENT_WISE_ABSOLUTE_VALUE,
                0.5))
        u, _ = upd.apply({"W": jnp.clip(grads["W"], -0.5, 0.5)}, (),
                         jnp.asarray(0))
        np.testing.assert_array_equal(
            np.asarray(new_p["W"], np.float32),
            np.asarray(params["W"] - u["W"], np.float32))
        return
    new_p, _ = zero.apply_update(upd, grads, params, (), jnp.asarray(0),
                                 keep_dtype=case == "keep_dtype")
    assert new_p["W"].dtype == (jnp.bfloat16 if case == "keep_dtype"
                                else jnp.float32)


# -- resolver --------------------------------------------------------------
def test_resolve_update_exchange():
    mesh = MeshFactory.data_parallel()
    assert resolve_update_exchange(mesh) is UpdateExchange.SHARDED
    assert resolve_update_exchange(mesh, requested="dense") \
        is UpdateExchange.DENSE
    assert resolve_update_exchange(None) is UpdateExchange.DENSE
    one = MeshFactory.data_parallel(1)
    assert resolve_update_exchange(one) is UpdateExchange.DENSE
    with pytest.raises(ValueError, match="update_exchange"):
        resolve_update_exchange(mesh, requested="zerO-3")


def test_resolver_falls_back_on_gradient_normalization():
    from deeplearning4j_tpu.nn.conf.builders import GradientNormalization
    mesh = MeshFactory.data_parallel()
    net = _mlp()
    net.conf.gradient_normalization = \
        GradientNormalization.CLIP_L2_PER_LAYER
    assert resolve_update_exchange(mesh, model=net) \
        is UpdateExchange.DENSE


def test_env_kill_switch_restores_dense(monkeypatch):
    """DL4J_TPU_SHARDED_UPDATE=0 forces the dense tail everywhere,
    even when sharded was requested (ISSUE 5 acceptance)."""
    from deeplearning4j_tpu.common.environment import Environment
    mesh = MeshFactory.data_parallel()
    monkeypatch.setenv("DL4J_TPU_SHARDED_UPDATE", "0")
    Environment.reset()
    try:
        assert resolve_update_exchange(mesh) is UpdateExchange.DENSE
        assert resolve_update_exchange(mesh, requested="sharded") \
            is UpdateExchange.DENSE
        net = _mlp(Adam(0.01))
        pw = ParallelWrapper.Builder(net).workers(8) \
            .update_exchange("sharded").build()
        pw.fit_batch(_data(64))
        assert pw.update_exchange is UpdateExchange.DENSE
        assert not any(is_dp_sharded(s)
                       for s in net.updater_states.values())
    finally:
        monkeypatch.delenv("DL4J_TPU_SHARDED_UPDATE")
        Environment.reset()


# -- end-to-end parity -----------------------------------------------------
@pytest.mark.parametrize("updater,rtol,atol", [
    (Sgd(0.1), 1e-6, 1e-7),
    (Nesterovs(0.1, 0.9), 1e-5, 1e-6),
    (Adam(0.01), 1e-5, 1e-6),
], ids=["sgd", "nesterovs", "adam"])
def test_sharded_matches_dense_end_to_end(updater, rtol, atol):
    """Two identically-seeded nets, same batches: the ZeRO-1 exchange
    must land on the dense exchange's parameters."""
    batches = [_data(64, seed=i) for i in range(3)]
    nets, wrappers = {}, {}
    for mode in ("dense", "sharded"):
        net = _mlp(updater, seed=7)
        pw = ParallelWrapper.Builder(net).workers(8) \
            .update_exchange(mode).build()
        for ds in batches:
            pw.fit_batch(ds)
        nets[mode], wrappers[mode] = net, pw
    assert wrappers["dense"].update_exchange is UpdateExchange.DENSE
    assert wrappers["sharded"].update_exchange is UpdateExchange.SHARDED
    _assert_tree_close(nets["dense"].params, nets["sharded"].params,
                       rtol=rtol, atol=atol)
    # the sharded run's state really is in the flat sharded layout
    sharded_states = nets["sharded"].updater_states
    if jax.tree_util.tree_leaves(nets["dense"].updater_states):
        assert any(is_dp_sharded(s) for s in sharded_states.values())
        _assert_tree_close(
            states_to_dense(nets["sharded"].params, sharded_states),
            nets["dense"].updater_states, rtol=rtol, atol=atol)


def test_accumulation_equals_big_batch_sgd():
    """accumulation_steps=2 over two half-batches == one full-batch
    step for SGD (mean gradient; equal micro-batch sizes)."""
    ds = _data(128, seed=3)
    x, y = np.asarray(ds.features), np.asarray(ds.labels)

    big = _mlp(seed=11)
    pw_big = ParallelWrapper.Builder(big).workers(8).build()
    pw_big.fit_batch(DataSet(x, y))

    accum = _mlp(seed=11)
    init = jax.tree_util.tree_map(np.asarray, accum.params)
    pw_acc = ParallelWrapper.Builder(accum).workers(8) \
        .accumulation_steps(2).build()
    pw_acc.fit_batch(DataSet(x[:64], y[:64]))
    # window not full yet: params unchanged
    _assert_tree_close(accum.params, init, rtol=0, atol=0)
    pw_acc.fit_batch(DataSet(x[64:], y[64:]))

    _assert_tree_close(big.params, accum.params, rtol=1e-5, atol=1e-6)
    # the updater saw ONE update, the listener loop saw two micro-steps
    assert accum.iteration_count == 2
    assert accum._updates_applied == 1


def test_accumulation_flushes_partial_window_at_epoch_end():
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    net = _mlp(seed=5)
    pw = ParallelWrapper.Builder(net).workers(8) \
        .accumulation_steps(4).build()
    it = ListDataSetIterator([_data(32, seed=i) for i in range(3)])
    before = jax.tree_util.tree_map(np.asarray, net.params)
    pw.fit(it, n_epochs=1)      # 3 micro-batches < window of 4
    # the partial window was applied at epoch end, params moved
    moved = any(not np.array_equal(a, np.asarray(b))
                for a, b in zip(jax.tree_util.tree_leaves(before),
                                jax.tree_util.tree_leaves(net.params)))
    assert moved
    assert net._accum_count == 0


# -- checkpoint round-trip -------------------------------------------------
def test_checkpoint_roundtrips_sharded_updater_state(tmp_path):
    """A net training with sharded Adam state checkpoints in the DENSE
    layout and resumes anywhere: restored state matches the live
    sharded state converted down, and training continues."""
    from deeplearning4j_tpu.utils import CheckpointListener
    net = _mlp(Adam(0.01), seed=9)
    lis = CheckpointListener(tmp_path, save_every_n_iterations=2)
    net.set_listeners(lis)
    pw = ParallelWrapper.Builder(net).workers(8) \
        .update_exchange("sharded").build()
    for i in range(2):
        pw.fit_batch(_data(64, seed=i))
    lis.flush()
    assert any(is_dp_sharded(s) for s in net.updater_states.values())

    restored = CheckpointListener.load_checkpoint(tmp_path)
    assert restored.iteration_count == 2
    assert not any(is_dp_sharded(s)
                   for s in restored.updater_states.values())
    _assert_tree_close(
        restored.updater_states,
        states_to_dense(net.params, net.updater_states),
        rtol=1e-6, atol=1e-7)
    _assert_tree_close(restored.params, net.params, rtol=1e-6, atol=1e-7)
    # the restored net trains standalone (dense) ...
    restored.fit(_data(64, seed=2))
    # ... and re-enters the sharded exchange cleanly
    pw2 = ParallelWrapper.Builder(restored).workers(8) \
        .update_exchange("sharded").build()
    pw2.fit_batch(_data(64, seed=3))
    assert np.isfinite(restored.score())


# -- builder / telemetry satellites ---------------------------------------
def test_training_mode_accepts_known_warns_unknown(caplog):
    net = _mlp()
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        b = ParallelWrapper.Builder(net).workers(8) \
            .training_mode("AVERAGING").training_mode("shared_gradients")
        assert not caplog.records
        b.training_mode("GOSSIP_GRADIENTS")
    assert any("GOSSIP_GRADIENTS" in r.getMessage()
               for r in caplog.records)
    with pytest.raises(ValueError):
        ParallelWrapper.Builder(net).update_exchange("bogus")


def test_workers_gauge_and_exchange_counter_and_sparsity_gauge():
    from deeplearning4j_tpu.common import telemetry
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.parallel import (EncodingHandler,
                                             FixedThresholdAlgorithm,
                                             SharedTrainingMaster)
    telemetry.MetricsRegistry._reset_for_tests()
    net = _mlp()
    master = SharedTrainingMaster.Builder().update_exchange("auto").build()
    master.fit(net, ListDataSetIterator([_data(32)]), n_epochs=1)
    # the workers gauge now says WHICH exchange ran
    assert telemetry.gauge("dl4j_dp_workers", "").value(
        master="SharedTrainingMaster", update_exchange="sharded") == 8
    assert telemetry.counter(
        "dl4j_dp_update_exchange_bytes_total", "").value(
            mode="sharded") > 0
    # the once-dead encoding sparsity() helper now feeds a gauge
    h = EncodingHandler(FixedThresholdAlgorithm(0.1))
    h.encode({"W": jnp.asarray([1.0, 0.0, 0.0, 0.0])})
    assert telemetry.gauge("dl4j_dp_encoding_sparsity", "").value() \
        == pytest.approx(0.25)
