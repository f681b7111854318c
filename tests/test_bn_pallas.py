"""Fused Pallas BN-backward (ops/bn_pallas.py, reference parity:
CudnnBatchNormalizationHelper.backprop — SURVEY.md D9/N8).  Off-TPU
the kernels run in interpret mode, so these tests exercise the same
code path the chip runs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common.environment import Environment
from deeplearning4j_tpu.ops.bn_pallas import bn_train_normalize

R = np.random.RandomState(5)


def _reference_bn(x, gamma, beta, eps):
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.var(x, axis=axes)
    y = (x - mean) / jnp.sqrt(var + eps) * gamma + beta
    return y, mean, var


@pytest.fixture
def fused_flag():
    env = Environment.get()
    env.extra["fused_bn_bwd"] = True
    yield
    env.extra.pop("fused_bn_bwd", None)


class TestFusedBnBwd:
    @pytest.mark.parametrize("shape", [(2, 5, 5, 3),   # M=50: ragged
                                       (4, 8, 8, 16),
                                       (32, 7)])       # 2D feature BN
    def test_gradients_match_autodiff(self, shape):
        """dx/dgamma/dbeta from the hand kernels == jax autodiff of
        the plain formulation, f32."""
        x = R.randn(*shape).astype(np.float32)
        C = shape[-1]
        gamma = (1.0 + 0.1 * R.randn(C)).astype(np.float32)
        beta = (0.1 * R.randn(C)).astype(np.float32)
        ct = R.randn(*shape).astype(np.float32)

        def loss_fused(x, g, b):
            y, _, _ = bn_train_normalize(x, g, b, 1e-5)
            return jnp.sum(y * ct)

        def loss_ref(x, g, b):
            y, _, _ = _reference_bn(x, g, b, 1e-5)
            return jnp.sum(y * ct)

        got = jax.grad(loss_fused, argnums=(0, 1, 2))(x, gamma, beta)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, gamma, beta)
        for g_, w_ in zip(got, want):
            np.testing.assert_allclose(np.asarray(g_), np.asarray(w_),
                                       rtol=2e-4, atol=2e-4)

    def test_stat_cotangents_flow(self):
        """Gradients THROUGH the returned mean/var (the running-stat
        update) must match autodiff — the kernel folds the dmean/dvar
        cotangents into the dx coefficients."""
        x = R.randn(3, 4, 4, 2).astype(np.float32)
        g = np.ones(2, np.float32)
        b = np.zeros(2, np.float32)

        def loss_fused(x):
            y, mean, var = bn_train_normalize(x, g, b, 1e-5)
            return jnp.sum(y) + 3.0 * jnp.sum(mean) - 2.0 * jnp.sum(var)

        def loss_ref(x):
            y, mean, var = _reference_bn(x, g, b, 1e-5)
            return jnp.sum(y) + 3.0 * jnp.sum(mean) - 2.0 * jnp.sum(var)

        got = jax.grad(loss_fused)(x)
        want = jax.grad(loss_ref)(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_bf16_activation(self):
        x = (R.randn(4, 6, 6, 8) * 0.5).astype(jnp.bfloat16)
        g = np.ones(8, np.float32)
        b = np.zeros(8, np.float32)
        y, mean, var = bn_train_normalize(x, g, b, 1e-5)
        assert y.dtype == jnp.bfloat16
        dx = jax.grad(lambda x: jnp.sum(
            bn_train_normalize(x, g, b, 1e-5)[0].astype(jnp.float32)))(x)
        assert dx.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(dx, np.float32)).all()

    def test_f64_gradient_check_through_layer(self, fused_flag):
        """Numeric f64 gradient check through a CNN+BN network with the
        fused path ENABLED (the verdict's acceptance bar: 'f64 gradient
        checks pass')."""
        from deeplearning4j_tpu.activations import Activation
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.learning import NoOp
        from deeplearning4j_tpu.lossfunctions import LossFunction
        from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.layers import (
            BatchNormalization, ConvolutionLayer, OutputLayer)
        from deeplearning4j_tpu.utils.gradientcheck import \
            GradientCheckUtil

        conf = (NeuralNetConfiguration.Builder()
                .seed(3)
                .updater(NoOp())
                .list()
                .layer(ConvolutionLayer(n_out=3, kernel_size=(3, 3),
                                        activation=Activation.IDENTITY))
                .layer(BatchNormalization(activation=Activation.TANH))
                .layer(OutputLayer(
                    n_out=2, loss_function=LossFunction.MCXENT,
                    activation=Activation.SOFTMAX))
                .set_input_type(InputType.convolutional(6, 6, 2))
                .build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(0)
        ds = DataSet(rng.randn(4, 6, 6, 2).astype(np.float32),
                     np.eye(2, dtype=np.float32)[rng.randint(0, 2, 4)])
        assert GradientCheckUtil.check_gradients(net, ds), \
            "f64 gradient check failed with fused BN bwd"

    def test_layer_uses_fused_path(self, fused_flag):
        """Flag on: layer forward output must equal the plain path's
        (same statistics, same normalize)."""
        from deeplearning4j_tpu.nn.conf.layers import BatchNormalization
        layer = BatchNormalization()
        x = R.randn(2, 4, 4, 3).astype(np.float32)
        params = {"gamma": jnp.ones(3), "beta": jnp.zeros(3)}
        state = {"mean": jnp.zeros(3), "var": jnp.ones(3)}
        got, st = layer.forward(params, jnp.asarray(x), training=True,
                                state=state)
        env = Environment.get()
        env.extra["fused_bn_bwd"] = False
        want, st2 = layer.forward(params, jnp.asarray(x),
                                  training=True, state=state)
        env.extra["fused_bn_bwd"] = True
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(st["mean"]),
                                   np.asarray(st2["mean"]), rtol=1e-5)


class TestAutoDenseAgainstForcedKernels:
    """The ladder's auto rung runs ``bn_forward_math`` under stock
    autodiff (PERF.md section 6, PR 33); the force rung still reaches
    the kernels. A conv + BN residual block gives the same gradients
    either way, within the tolerance the kernel's own checks use."""

    @pytest.mark.parametrize("act", ["relu", "identity"])
    def test_block_gradients_agree(self, act):
        from deeplearning4j_tpu.activations import Activation
        from deeplearning4j_tpu.nn.conf.layers import BatchNormalization
        from deeplearning4j_tpu.ops import conv_pallas, kernel_select
        bn = BatchNormalization(activation=Activation(act))
        c = 16
        x = R.randn(4, 8, 8, c).astype(np.float32)
        params = {
            "w1": (0.2 * R.randn(3, 3, c, c)).astype(np.float32),
            "w2": (0.2 * R.randn(1, 1, c, c)).astype(np.float32),
            "bn1": {"gamma": (1 + 0.1 * R.randn(c)).astype(np.float32),
                    "beta": (0.1 * R.randn(c)).astype(np.float32)},
            "bn2": {"gamma": (1 + 0.1 * R.randn(c)).astype(np.float32),
                    "beta": (0.1 * R.randn(c)).astype(np.float32)}}
        state = {"mean": jnp.zeros(c), "var": jnp.ones(c)}

        def conv(a, w):
            return conv_pallas.conv_forward(
                a, w, window_strides=(1, 1), padding="SAME",
                rhs_dilation=(1, 1),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        def loss(p, a):
            h, st1 = bn.forward(p["bn1"], conv(a, p["w1"]),
                                training=True, state=state)
            h, st2 = bn.forward(p["bn2"], conv(h, p["w2"]),
                                training=True, state=state)
            # the running statistics' cotangents flow too
            return (jnp.sum(jnp.square(h + a)) + jnp.sum(st1["mean"])
                    - jnp.sum(st2["var"]))

        def grads(**gates):
            env = Environment.get()
            env.extra.update(gates)
            try:
                before = {k: kernel_select.decisions(k)
                          for k in ("bn_fwd", "bn_bwd")}
                out = jax.grad(loss, argnums=(0, 1))(params, x)
                took = {k: {d: n - before[k][d] for d, n in
                            kernel_select.decisions(k).items()
                            if n != before[k][d]} for k in before}
            finally:
                for k in gates:
                    env.extra.pop(k, None)
            return out, took

        dense, took = grads()
        assert {k: set(v) for k, v in took.items()} == {
            "bn_fwd": {"auto_dense"}, "bn_bwd": {"auto_dense"}}
        fused, took = grads(fused_conv="1", fused_bn_bwd="1")
        assert took == {"bn_fwd": {"forced": 2}, "bn_bwd": {"forced": 2}}
        # a leaf whose gradient is analytically zero (bn1's beta before
        # a second BN) is rounding on both sides: one scale for all
        scale = max(float(np.abs(np.asarray(d)).max())
                    for d in jax.tree_util.tree_leaves(dense))
        for d, f in zip(jax.tree_util.tree_leaves(dense),
                        jax.tree_util.tree_leaves(fused)):
            np.testing.assert_allclose(np.asarray(f), np.asarray(d),
                                       rtol=2e-4, atol=2e-4 * scale)
