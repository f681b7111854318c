"""Fused conv/BN/ReLU epilogue kernel family (ops/conv_pallas.py,
reference parity: CudnnConvolutionHelper's
cudnnConvolutionBiasActivationForward — SURVEY.md D9).  Off-TPU the
kernels run in Pallas interpret mode, so these exactness and gradient
checks exercise the SAME code path the chip runs — including an f64
leg, which only exists because interpret mode runs on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.activations import Activation
from deeplearning4j_tpu.common.environment import Environment
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization, ConvolutionLayer, ConvolutionMode)
from deeplearning4j_tpu.nn.conf.layers_conv_1d3d import (
    Convolution1DLayer, Convolution3D)
from deeplearning4j_tpu.ops import conv_pallas

R = np.random.RandomState(13)


@pytest.fixture
def fused_conv():
    """Force the conv-epilogue family on (the auto rung is XLA's
    lowering on every platform, so the fused path needs the force
    rung to run at all)."""
    env = Environment.get()
    env.extra["fused_conv"] = "1"
    yield
    env.extra.pop("fused_conv", None)


@pytest.fixture
def dense_only():
    env = Environment.get()
    env.extra["fused_conv"] = "0"
    env.extra["fused_bn_bwd"] = "0"
    yield
    env.extra.pop("fused_conv", None)
    env.extra.pop("fused_bn_bwd", None)


def _with_gate(value, fn, *args, **kw):
    env = Environment.get()
    old = env.extra.get("fused_conv")
    env.extra["fused_conv"] = value
    try:
        return fn(*args, **kw)
    finally:
        if old is None:
            env.extra.pop("fused_conv", None)
        else:
            env.extra["fused_conv"] = old


# ---------------------------------------------------------------------------
# building blocks vs their dense formulations
# ---------------------------------------------------------------------------
class TestEpilogueKernel:
    @pytest.mark.parametrize("act", ["relu", "identity"])
    @pytest.mark.parametrize("shape", [(2, 5, 5, 16),   # M=50: ragged
                                       (4, 8, 8, 32),
                                       (40, 24)])       # 2D features
    def test_forward_matches_dense(self, act, shape):
        x = R.randn(*shape).astype(np.float32)
        C = shape[-1]
        s = (1.0 + 0.3 * R.randn(C)).astype(np.float32)
        b = (0.2 * R.randn(C)).astype(np.float32)
        got = conv_pallas.scale_shift_act(x, s, b, act)
        ref = x * s + b
        if act == "relu":
            ref = jax.nn.relu(ref)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("act", ["relu", "identity"])
    def test_gradients_match_autodiff(self, act):
        x = R.randn(2, 5, 5, 16).astype(np.float32)
        s = (1.0 + 0.3 * R.randn(16)).astype(np.float32)
        b = (0.2 * R.randn(16)).astype(np.float32)
        ct = R.randn(*x.shape).astype(np.float32)

        def loss_fused(x, s, b):
            return jnp.sum(conv_pallas.scale_shift_act(x, s, b, act)
                           * ct)

        def loss_ref(x, s, b):
            y = x * s + b
            if act == "relu":
                y = jax.nn.relu(y)
            return jnp.sum(y * ct)

        got = jax.grad(loss_fused, argnums=(0, 1, 2))(x, s, b)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, s, b)
        for g_, w_ in zip(got, want):
            np.testing.assert_allclose(np.asarray(g_), np.asarray(w_),
                                       rtol=2e-4, atol=2e-4)

    def test_gradients_f64(self):
        """Interpret mode exists so f64 gradient checks can exercise
        the chip's code path; central differences at 1e-6 only hold
        in doubles."""
        old = jax.config.read("jax_enable_x64")
        jax.config.update("jax_enable_x64", True)
        try:
            x = R.randn(3, 7, 16).astype(np.float64)
            s = (1.0 + 0.3 * R.randn(16)).astype(np.float64)
            b = (0.2 * R.randn(16)).astype(np.float64)
            ct = R.randn(*x.shape)

            def loss(x, s, b):
                return jnp.sum(
                    conv_pallas.scale_shift_act(x, s, b, "relu") * ct)

            got = jax.grad(loss, argnums=(0, 1, 2))(x, s, b)
            eps = 1e-6
            for i, arg in enumerate((x, s, b)):
                flat = arg.ravel()
                j = int(R.randint(flat.size))
                dv = np.zeros_like(flat)
                dv[j] = eps
                args_p = [x, s, b]
                args_m = [x, s, b]
                args_p[i] = (flat + dv).reshape(arg.shape)
                args_m[i] = (flat - dv).reshape(arg.shape)
                fd = (loss(*args_p) - loss(*args_m)) / (2 * eps)
                np.testing.assert_allclose(
                    np.asarray(got[i]).ravel()[j], float(fd),
                    rtol=1e-6, atol=1e-8)
        finally:
            jax.config.update("jax_enable_x64", old)


class TestChannelStats:
    @pytest.mark.parametrize("shape", [(2, 5, 5, 16), (50, 8),
                                       (3, 4, 4, 4, 8)])
    def test_matches_dense_stats(self, shape):
        x = (R.randn(*shape) * 2 + 0.5).astype(np.float32)
        axes = tuple(range(len(shape) - 1))
        mean, var = conv_pallas.channel_stats(x)
        np.testing.assert_allclose(np.asarray(mean),
                                   x.mean(axis=axes), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(var), x.var(axis=axes),
                                   rtol=1e-4, atol=1e-5)

    def test_gradients_match_autodiff(self):
        x = R.randn(2, 5, 5, 16).astype(np.float32)
        wm = R.randn(16).astype(np.float32)
        wv = R.randn(16).astype(np.float32)

        def loss_fused(x):
            m, v = conv_pallas.channel_stats(x)
            return jnp.sum(m * wm) + jnp.sum(v * wv)

        def loss_ref(x):
            axes = tuple(range(x.ndim - 1))
            return (jnp.sum(jnp.mean(x, axes) * wm)
                    + jnp.sum(jnp.var(x, axes) * wv))

        np.testing.assert_allclose(
            np.asarray(jax.grad(loss_fused)(x)),
            np.asarray(jax.grad(loss_ref)(x)), rtol=2e-4, atol=2e-4)


class TestMatmulEpilogue:
    @pytest.mark.parametrize("act", ["relu", "identity"])
    @pytest.mark.parametrize("m", [50, 128])          # ragged + exact
    def test_forward_and_grads_match_dense(self, act, m):
        x = (R.randn(m, 128) * 0.5).astype(np.float32)
        w = (R.randn(128, 128) * 0.1).astype(np.float32)
        b = (0.2 * R.randn(128)).astype(np.float32)

        def fused(x, w, b):
            return conv_pallas.matmul_bias_act(x, w, b, act)

        def ref(x, w, b):
            y = x @ w + b
            return jax.nn.relu(y) if act == "relu" else y

        np.testing.assert_allclose(np.asarray(fused(x, w, b)),
                                   np.asarray(ref(x, w, b)),
                                   rtol=2e-5, atol=2e-5)
        got = jax.grad(lambda *a: jnp.sum(fused(*a) ** 2),
                       argnums=(0, 1, 2))(x, w, b)
        want = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2),
                        argnums=(0, 1, 2))(x, w, b)
        for g_, w_ in zip(got, want):
            np.testing.assert_allclose(np.asarray(g_), np.asarray(w_),
                                       rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# layer call sites: fused vs the dense lowering they replace
# ---------------------------------------------------------------------------
class TestConvLayerParity:
    def _layer_parity(self, layer, input_type, x):
        p = layer.init_params(jax.random.PRNGKey(0), input_type)

        def run(params, x):
            y, _ = layer.forward(params, x, training=True)
            return y

        y_dense = _with_gate("0", run, p, x)
        y_fused = _with_gate("1", run, p, x)
        np.testing.assert_allclose(np.asarray(y_fused),
                                   np.asarray(y_dense), rtol=2e-5,
                                   atol=2e-5)

        def loss(params, x):
            return jnp.sum(run(params, x) ** 2)

        gd = _with_gate("0", jax.grad(loss, argnums=(0, 1)), p, x)
        gf = _with_gate("1", jax.grad(loss, argnums=(0, 1)), p, x)
        for leaf_d, leaf_f in zip(jax.tree_util.tree_leaves(gd),
                                  jax.tree_util.tree_leaves(gf)):
            np.testing.assert_allclose(np.asarray(leaf_f),
                                       np.asarray(leaf_d), rtol=2e-4,
                                       atol=2e-4)

    def test_conv2d_bias_relu(self):
        lay = ConvolutionLayer(
            kernel_size=(3, 3), n_in=16, n_out=16,
            convolution_mode=ConvolutionMode.SAME, has_bias=True,
            activation=Activation.RELU)
        self._layer_parity(lay, InputType.convolutional(8, 8, 16),
                           R.randn(2, 8, 8, 16).astype(np.float32))

    def test_conv2d_pointwise_matmul_path(self):
        """1x1 stride-1 convs with MXU-aligned channels take the
        matmul-epilogue kernel — exactness against the dense conv."""
        lay = ConvolutionLayer(
            kernel_size=(1, 1), n_in=128, n_out=128,
            convolution_mode=ConvolutionMode.SAME, has_bias=True,
            activation=Activation.RELU)
        self._layer_parity(lay, InputType.convolutional(4, 4, 128),
                           R.randn(2, 4, 4, 128).astype(np.float32))

    def test_conv1d_routes_through_entry_point(self):
        lay = Convolution1DLayer(
            kernel_size=3, n_in=16, n_out=16,
            convolution_mode=ConvolutionMode.SAME, has_bias=True,
            activation=Activation.RELU)
        self._layer_parity(lay, InputType.recurrent(16, 12),
                           R.randn(2, 12, 16).astype(np.float32))

    def test_conv3d_routes_through_entry_point(self):
        lay = Convolution3D(
            kernel_size=(2, 2, 2), n_in=8, n_out=8,
            convolution_mode=ConvolutionMode.SAME, has_bias=True,
            activation=Activation.RELU)
        self._layer_parity(
            lay, InputType.convolutional_3d(4, 4, 4, 8),
            R.randn(2, 4, 4, 4, 8).astype(np.float32))

    def test_unaligned_channels_fall_back_dense(self, fused_conv):
        """C % 8 != 0 demotes structurally — the layer still works,
        on the dense path."""
        lay = ConvolutionLayer(
            kernel_size=(3, 3), n_in=3, n_out=5,
            convolution_mode=ConvolutionMode.SAME, has_bias=True,
            activation=Activation.RELU)
        p = lay.init_params(jax.random.PRNGKey(0),
                            InputType.convolutional(6, 6, 3))
        x = R.randn(2, 6, 6, 3).astype(np.float32)
        y, _ = lay.forward(p, x, training=True)
        assert y.shape == (2, 6, 6, 5)


class TestBatchNormLayerParity:
    def _bn(self, activation):
        bn = BatchNormalization(activation=activation)
        it = InputType.convolutional(8, 8, 16)
        bn.set_n_in(it, True)
        return (bn, bn.init_params(jax.random.PRNGKey(1), it),
                bn.init_state(it))

    @pytest.mark.parametrize("activation",
                             [Activation.RELU, Activation.IDENTITY,
                              Activation.TANH])
    def test_training_forward_parity(self, activation):
        """Fused stats+normalize(+act) == the dense math; TANH is not
        streamable so only the stats/normalize fuse."""
        bn, p, st = self._bn(activation)
        x = R.randn(4, 8, 8, 16).astype(np.float32)

        def run(p, x):
            y, new_st = bn.forward(p, x, training=True, state=st)
            return y, new_st

        env = Environment.get()
        env.extra["fused_bn_bwd"] = "0"
        try:
            yd, std = _with_gate("0", run, p, x)
            yf, stf = _with_gate("1", run, p, x)
        finally:
            env.extra.pop("fused_bn_bwd", None)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(yd),
                                   rtol=2e-5, atol=2e-5)
        for k in ("mean", "var"):
            np.testing.assert_allclose(np.asarray(stf[k]),
                                       np.asarray(std[k]), rtol=1e-5,
                                       atol=1e-6)

    def test_training_gradients_parity(self):
        bn, p, st = self._bn(Activation.RELU)
        x = R.randn(4, 8, 8, 16).astype(np.float32)

        def loss(p, x):
            y, _ = bn.forward(p, x, training=True, state=st)
            return jnp.sum(y ** 2)

        env = Environment.get()
        env.extra["fused_bn_bwd"] = "0"
        try:
            gd = _with_gate("0", jax.grad(loss, argnums=(0, 1)), p, x)
            gf = _with_gate("1", jax.grad(loss, argnums=(0, 1)), p, x)
        finally:
            env.extra.pop("fused_bn_bwd", None)
        for leaf_d, leaf_f in zip(jax.tree_util.tree_leaves(gd),
                                  jax.tree_util.tree_leaves(gf)):
            np.testing.assert_allclose(np.asarray(leaf_f),
                                       np.asarray(leaf_d), rtol=5e-4,
                                       atol=5e-4)

    def test_composes_with_fused_bn_backward(self):
        """DL4J_TPU_FUSED_CONV stats forward + DL4J_TPU_FUSED_BN_BWD
        backward, both by the force rung: the full hand-kernel round
        trip tracks the dense autodiff, which is what both gates
        unset (the auto rung, on the chip too since PR 33) and both
        gates killed give, bit for bit."""
        bn, p, st = self._bn(Activation.RELU)
        x = R.randn(4, 8, 8, 16).astype(np.float32)

        def loss(p, x):
            y, _ = bn.forward(p, x, training=True, state=st)
            return jnp.sum(y ** 2)

        env = Environment.get()
        assert "fused_conv" not in env.extra
        assert "fused_bn_bwd" not in env.extra
        ga = jax.grad(loss, argnums=(0, 1))(p, x)
        env.extra["fused_bn_bwd"] = "0"
        gd = _with_gate("0", jax.grad(loss, argnums=(0, 1)), p, x)
        for leaf_a, leaf_d in zip(jax.tree_util.tree_leaves(ga),
                                  jax.tree_util.tree_leaves(gd)):
            np.testing.assert_array_equal(np.asarray(leaf_a),
                                          np.asarray(leaf_d))
        env.extra["fused_bn_bwd"] = "1"
        try:
            gc = _with_gate("1", jax.grad(loss, argnums=(0, 1)), p, x)
        finally:
            env.extra.pop("fused_bn_bwd", None)
        for leaf_d, leaf_c in zip(jax.tree_util.tree_leaves(gd),
                                  jax.tree_util.tree_leaves(gc)):
            np.testing.assert_allclose(np.asarray(leaf_c),
                                       np.asarray(leaf_d), rtol=5e-4,
                                       atol=5e-4)

    def test_inference_epilogue_parity(self):
        bn, p, st = self._bn(Activation.RELU)
        st = {"mean": jnp.asarray(0.3 * R.randn(16), jnp.float32),
              "var": jnp.asarray(1 + 0.1 * R.rand(16), jnp.float32)}
        x = R.randn(4, 8, 8, 16).astype(np.float32)

        def run(p, x):
            y, _ = bn.forward(p, x, training=False, state=st)
            return y

        yd = _with_gate("0", run, p, x)
        yf = _with_gate("1", run, p, x)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(yd),
                                   rtol=2e-5, atol=2e-5)


class TestConvForwardVsDenseLowering:
    """The acceptance bar: fused conv+BN+ReLU forward against the raw
    dense lax.conv_general_dilated lowering, end to end."""

    def test_conv_bn_relu_stack(self, fused_conv):
        x = R.randn(2, 8, 8, 16).astype(np.float32)
        w = (0.1 * R.randn(3, 3, 16, 16)).astype(np.float32)
        gamma = (1 + 0.1 * R.randn(16)).astype(np.float32)
        beta = (0.1 * R.randn(16)).astype(np.float32)
        eps = 1e-5

        def fused(x, w, gamma, beta):
            z = conv_pallas.conv_forward(
                x, w, window_strides=(1, 1), padding="SAME",
                rhs_dilation=(1, 1),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                bias=None, activation=Activation.IDENTITY)
            out = conv_pallas.maybe_fused_bn_train(
                z, gamma, beta, eps, Activation.RELU)
            assert out is not None
            return out[0]

        def dense(x, w, gamma, beta):
            z = jax.lax.conv_general_dilated(
                x, w, window_strides=(1, 1), padding="SAME",
                rhs_dilation=(1, 1),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            axes = (0, 1, 2)
            mean = jnp.mean(z, axes)
            var = jnp.var(z, axes)
            return jax.nn.relu(
                (z - mean) / jnp.sqrt(var + eps) * gamma + beta)

        np.testing.assert_allclose(
            np.asarray(fused(x, w, gamma, beta)),
            np.asarray(dense(x, w, gamma, beta)), rtol=2e-5,
            atol=2e-5)
        got = jax.grad(lambda *a: jnp.sum(fused(*a) ** 2),
                       argnums=(0, 1, 2, 3))(x, w, gamma, beta)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) ** 2),
                        argnums=(0, 1, 2, 3))(x, w, gamma, beta)
        for g_, w_ in zip(got, want):
            np.testing.assert_allclose(np.asarray(g_), np.asarray(w_),
                                       rtol=5e-4, atol=5e-4)
