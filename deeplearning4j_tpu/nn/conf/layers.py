"""Layer configurations + their functional runtime math.

Reference parity: ``org.deeplearning4j.nn.conf.layers.*`` (config classes,
SURVEY.md D1) and ``org.deeplearning4j.nn.layers.**`` (runtime twins, D4).
The reference splits config from runtime layer objects; here each config
dataclass *is* the runtime: it exposes pure functions

    init_params(key, input_type)            -> param dict
    init_state(input_type)                  -> state dict (e.g. BN stats)
    forward(params, x, training, rng, state) -> (y, new_state)
    get_output_type(input_type)             -> InputType

so the network compiles every layer into one jitted step (SURVEY.md §7:
"the layer-config API compiles into a single jitted train step"). There is
no helper seam (D5): cuDNN/oneDNN helpers are replaced by XLA lowerings —
``lax.conv_general_dilated`` / ``lax.reduce_window`` hit the TPU MXU/VPU
directly (BASELINE.json north star: "cuDNN helpers lower to XLA ops").

Layout: conv activations are NHWC, kernels HWIO (XLA:TPU native);
recurrent activations are [batch, time, features]. The reference's NCHW /
[b, f, t] layouts exist only at import boundaries.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.activations import Activation
from deeplearning4j_tpu.learning.updaters import IUpdater
from deeplearning4j_tpu.lossfunctions import LossFunction
from deeplearning4j_tpu.nn.conf.inputs import (
    InputType, InputTypeConvolutional, InputTypeFeedForward,
    InputTypeRecurrent)
from deeplearning4j_tpu.nn.weights import WeightInit


class PoolingType(enum.Enum):
    MAX = "max"
    AVG = "avg"
    SUM = "sum"
    PNORM = "pnorm"


class ConvolutionMode(enum.Enum):
    """Reference: Strict/Truncate/Same. Truncate == XLA VALID."""
    STRICT = "strict"
    TRUNCATE = "truncate"
    SAME = "same"


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


class _Builder:
    """Fluent builder shim for reference-style ``Layer.Builder()`` chains."""

    def __init__(self, cls, *args, **kwargs):
        self._cls = cls
        self._kw = dict(kwargs)
        if args:  # positional kernel size etc. handled per-class
            self._kw.update(cls._builder_positional(*args))

    def __getattr__(self, name):
        def setter(*v):
            self._kw[name] = v[0] if len(v) == 1 else tuple(v)
            return self
        return setter

    def build(self):
        return self._cls(**self._kw)


@dataclass
class Layer:
    """Base layer config. Fields mirror BaseLayer/FeedForwardLayer."""

    n_in: int = 0
    n_out: int = 0
    activation: Activation = Activation.IDENTITY
    weight_init: Optional[WeightInit] = None      # None -> net default
    bias_init: float = 0.0
    updater: Optional[IUpdater] = None            # None -> net default
    l1: Optional[float] = None
    l2: Optional[float] = None
    #: float retain probability OR an IDropout variant (conf.dropout)
    dropout: object = None
    #: optional WeightNoise/DropConnect applied to params in training
    weight_noise: object = None
    #: post-update projections (lists of LayerConstraint); None -> net
    #: default. Reference: o.d.nn.conf.constraint + builder
    #: constrainWeights/constrainBias/constrainAllParameters
    constrain_weights: object = None
    constrain_bias: object = None
    constrain_all: object = None
    #: exact param-name scoping: {"W": [c...], "RW": [c...]} — the
    #: Keras import surface (kernel_constraint vs recurrent_constraint
    #: are per-param, like the reference's BaseConstraint param sets)
    constrain_params: object = None
    name: Optional[str] = None

    def __post_init__(self):
        # accept strings for enum-typed fields (reference: DL4J builders
        # take Activation.RELU; the string spelling is a convenience)
        for f in ("activation", "gate_activation"):
            v = getattr(self, f, None)
            if isinstance(v, str):
                setattr(self, f, Activation.from_name(v))
        if isinstance(self.weight_init, str):
            self.weight_init = WeightInit[self.weight_init.upper()]
        lf = getattr(self, "loss_function", None)
        if isinstance(lf, str):
            self.loss_function = LossFunction[lf.upper()]

    # -- builder parity --------------------------------------------------
    @classmethod
    def Builder(cls, *args, **kwargs) -> _Builder:  # noqa: N802
        return _Builder(cls, *args, **kwargs)

    @staticmethod
    def _builder_positional(*args) -> dict:
        return {}

    # -- runtime protocol ------------------------------------------------
    def has_params(self) -> bool:
        return True

    def has_state(self) -> bool:
        return False

    def is_recurrent(self) -> bool:
        """True for layers with transient per-sequence state (h/c)."""
        return False

    def accepts_mask(self) -> bool:
        """True if forward() takes a per-timestep mask kwarg."""
        return self.is_recurrent()

    def zero_state(self, batch: int, dtype=jnp.float32) -> dict:
        return {}

    def is_pretrain_param(self, name: str) -> bool:
        return False

    def init_params(self, key, input_type: InputType, dtype=jnp.float32):
        return {}

    def init_state(self, input_type: InputType, dtype=jnp.float32):
        return {}

    def forward(self, params, x, *, training: bool, rng=None, state=None):
        raise NotImplementedError

    def get_output_type(self, input_type: InputType) -> InputType:
        raise NotImplementedError

    def set_n_in(self, input_type: InputType, override: bool):
        """Shape inference hook (reference: FeedForwardLayer.setNIn)."""
        if isinstance(input_type, InputTypeFeedForward) and \
                (override or not self.n_in):
            self.n_in = input_type.size

    # -- input dropout (reference applies dropout to layer *input*) ------
    def _maybe_dropout(self, x, training: bool, rng):
        if self.dropout is None or not training or rng is None:
            return x
        from deeplearning4j_tpu.nn.conf.dropout import IDropout
        if isinstance(self.dropout, IDropout):   # reference: IDropout
            return self.dropout.apply(x, rng)
        p = float(self.dropout)
        keep = jax.random.bernoulli(rng, p, x.shape)
        return jnp.where(keep, x / p, 0.0)

    # -- serde -----------------------------------------------------------
    def to_map(self) -> dict:
        from deeplearning4j_tpu.nn.conf.dropout import IDropout, \
            WeightNoise
        from deeplearning4j_tpu.nn.conf.constraints import \
            constraints_to_map
        d = {"@class": type(self).__name__}
        for k, v in self.__dict__.items():
            if isinstance(v, enum.Enum):
                v = v.name
            elif isinstance(v, (IUpdater, IDropout, WeightNoise)):
                v = v.to_map()
            elif isinstance(v, LossFunction):
                v = v.name
            elif k in ("constrain_weights", "constrain_bias",
                       "constrain_all"):
                v = constraints_to_map(v)
            elif k == "constrain_params" and v is not None:
                v = {pk: constraints_to_map(pv) for pk, pv in v.items()}
            d[k] = v
        return d

    @staticmethod
    def from_map(d: dict) -> "Layer":
        d = dict(d)
        cls = LAYER_REGISTRY[d.pop("@class")]
        # enum-name strings for activation/weight_init/loss_function are
        # coerced by Layer.__post_init__; only non-Layer-field enums here
        for k, v in list(d.items()):
            if k == "updater" and isinstance(v, dict):
                d[k] = IUpdater.from_map(v)
            elif k == "dropout" and isinstance(v, dict):
                from deeplearning4j_tpu.nn.conf.dropout import IDropout
                d[k] = IDropout.from_map(v)
            elif k == "weight_noise" and isinstance(v, dict):
                from deeplearning4j_tpu.nn.conf.dropout import \
                    WeightNoise
                d[k] = WeightNoise.from_map(v)
            elif k in ("constrain_weights", "constrain_bias",
                       "constrain_all") and isinstance(v, list):
                from deeplearning4j_tpu.nn.conf.constraints import \
                    constraints_from_map
                d[k] = constraints_from_map(v)
            elif k == "constrain_params" and isinstance(v, dict):
                from deeplearning4j_tpu.nn.conf.constraints import \
                    constraints_from_map
                d[k] = {pk: constraints_from_map(pv)
                        for pk, pv in v.items()}
            elif k in ("pooling_type",) and isinstance(v, str):
                d[k] = PoolingType[v]
            elif k in ("convolution_mode",) and isinstance(v, str):
                d[k] = ConvolutionMode[v]
            elif isinstance(v, list):
                d[k] = tuple(v)
        return cls(**d)


# ---------------------------------------------------------------------------
@dataclass
class DenseLayer(Layer):
    """Fully connected layer (reference: conf.layers.DenseLayer /
    runtime layers.feedforward.dense.DenseLayer)."""

    has_bias: bool = True
    activation: Activation = Activation.SIGMOID

    def init_params(self, key, input_type, dtype=jnp.float32):
        wi = self.weight_init or WeightInit.XAVIER
        k1, _ = jax.random.split(key)
        p = {"W": wi.init(k1, (self.n_in, self.n_out),
                          self.n_in, self.n_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def forward(self, params, x, *, training, rng=None, state=None):
        x = self._maybe_dropout(x, training, rng)
        z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return self.activation(z), state

    def get_output_type(self, input_type):
        return InputType.feed_forward(self.n_out)


@dataclass
class ConvolutionLayer(Layer):
    """2D convolution (reference: conf.layers.ConvolutionLayer; runtime
    convolution.ConvolutionLayer with CudnnConvolutionHelper — here the
    lowering is ``lax.conv_general_dilated`` straight onto the MXU)."""

    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: ConvolutionMode = ConvolutionMode.TRUNCATE
    has_bias: bool = True

    @staticmethod
    def _builder_positional(*args) -> dict:
        # reference: ConvolutionLayer.Builder(kh, kw)
        if len(args) == 1:
            return {"kernel_size": _pair(args[0])}
        return {"kernel_size": (int(args[0]), int(args[1]))}

    def __post_init__(self):
        super().__post_init__()
        self.kernel_size = _pair(self.kernel_size)
        self.stride = _pair(self.stride)
        self.padding = _pair(self.padding)
        self.dilation = _pair(self.dilation)

    def _pad_cfg(self):
        if self.convolution_mode is ConvolutionMode.SAME:
            return "SAME"
        ph, pw = self.padding
        return [(ph, ph), (pw, pw)]

    def init_params(self, key, input_type, dtype=jnp.float32):
        kh, kw = self.kernel_size
        c_in = self.n_in
        fan_in = kh * kw * c_in
        fan_out = kh * kw * self.n_out
        wi = self.weight_init or WeightInit.XAVIER
        k1, _ = jax.random.split(key)
        # HWIO kernel layout (XLA native)
        p = {"W": wi.init(k1, (kh, kw, c_in, self.n_out),
                          fan_in, fan_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def forward(self, params, x, *, training, rng=None, state=None):
        x = self._maybe_dropout(x, training, rng)
        # conv + bias/activation epilogue through the shared fused
        # entry point (ops/conv_pallas.py): when the conv_epilogue
        # kernel-select ladder admits the site the epilogue runs
        # inside Pallas output tiles; otherwise this IS the dense
        # lax.conv_general_dilated lowering the layer always used
        from deeplearning4j_tpu.ops.conv_pallas import conv_forward
        z = conv_forward(
            x, params["W"],
            window_strides=self.stride,
            padding=self._pad_cfg(),
            rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            bias=params["b"] if self.has_bias else None,
            activation=self.activation)
        return z, state

    def set_n_in(self, input_type, override):
        if isinstance(input_type, InputTypeConvolutional) and \
                (override or not self.n_in):
            self.n_in = input_type.channels

    def get_output_type(self, input_type):
        assert isinstance(input_type, InputTypeConvolutional), input_type
        h, w = input_type.height, input_type.width
        kh, kw = self.kernel_size
        sh, sw = self.stride
        dh, dw = self.dilation
        ekh, ekw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
        if self.convolution_mode is ConvolutionMode.SAME:
            oh = -(-h // sh)
            ow = -(-w // sw)
        else:
            ph, pw = self.padding
            oh = (h + 2 * ph - ekh) // sh + 1
            ow = (w + 2 * pw - ekw) // sw + 1
        return InputType.convolutional(oh, ow, self.n_out)


@dataclass
class SubsamplingLayer(Layer):
    """Pooling (reference: conf.layers.SubsamplingLayer; cuDNN/oneDNN
    helpers replaced by ``lax.reduce_window``)."""

    pooling_type: PoolingType = PoolingType.MAX
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: ConvolutionMode = ConvolutionMode.TRUNCATE
    pnorm: int = 2

    @staticmethod
    def _builder_positional(*args) -> dict:
        out = {}
        rest = list(args)
        if rest and isinstance(rest[0], PoolingType):
            out["pooling_type"] = rest.pop(0)
        if rest:
            out["kernel_size"] = _pair(rest.pop(0))
        if rest:
            out["stride"] = _pair(rest.pop(0))
        return out

    def __post_init__(self):
        super().__post_init__()
        self.kernel_size = _pair(self.kernel_size)
        self.stride = _pair(self.stride)
        self.padding = _pair(self.padding)

    def has_params(self) -> bool:
        return False

    def forward(self, params, x, *, training, rng=None, state=None):
        kh, kw = self.kernel_size
        sh, sw = self.stride
        if self.convolution_mode is ConvolutionMode.SAME:
            pad = "SAME"
        else:
            ph, pw = self.padding
            pad = [(0, 0), (ph, ph), (pw, pw), (0, 0)]
        dims = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        if self.pooling_type is PoolingType.MAX:
            z = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims,
                                      strides, pad)
        elif self.pooling_type is PoolingType.SUM:
            z = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides,
                                      pad)
        elif self.pooling_type is PoolingType.AVG:
            s = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides,
                                      pad)
            n = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                      dims, strides, pad)
            z = s / n
        else:  # PNORM
            p = float(self.pnorm)
            s = jax.lax.reduce_window(jnp.abs(x) ** p, 0.0, jax.lax.add,
                                      dims, strides, pad)
            z = s ** (1.0 / p)
        return z, state

    def get_output_type(self, input_type):
        assert isinstance(input_type, InputTypeConvolutional), input_type
        h, w = input_type.height, input_type.width
        kh, kw = self.kernel_size
        sh, sw = self.stride
        if self.convolution_mode is ConvolutionMode.SAME:
            oh, ow = -(-h // sh), -(-w // sw)
        else:
            ph, pw = self.padding
            oh = (h + 2 * ph - kh) // sh + 1
            ow = (w + 2 * pw - kw) // sw + 1
        return InputType.convolutional(oh, ow, input_type.channels)

    def set_n_in(self, input_type, override):
        pass


@dataclass
class BatchNormalization(Layer):
    """Batch norm (reference: conf.layers.BatchNormalization with
    CudnnBatchNormalizationHelper — here plain XLA ops that fuse into the
    surrounding conv; running stats are functional state carried by the
    network, replacing the reference's mutable arrays)."""

    decay: float = 0.9
    eps: float = 1e-5
    gamma_init: float = 1.0
    beta_init: float = 0.0
    lock_gamma_beta: bool = False

    def has_state(self) -> bool:
        return True

    def _nf(self, input_type):
        if isinstance(input_type, InputTypeConvolutional):
            return input_type.channels
        return input_type.size

    def init_params(self, key, input_type, dtype=jnp.float32):
        nf = self._nf(input_type)
        return {"gamma": jnp.full((nf,), self.gamma_init, dtype),
                "beta": jnp.full((nf,), self.beta_init, dtype)}

    def init_state(self, input_type, dtype=jnp.float32):
        nf = self._nf(input_type)
        return {"mean": jnp.zeros((nf,), dtype),
                "var": jnp.ones((nf,), dtype)}

    def forward(self, params, x, *, training, rng=None, state=None):
        if training:
            # shared forward math (one-pass E[x]/E[x^2] for bf16,
            # two-pass for f32; ops/bn_pallas.py:bn_forward_math)
            # under stock autodiff: what the ladder's auto rung picks
            # on every platform, the chip included, where the whole
            # ResNet-50 step is 2.58 times faster this way than with
            # the kernels (PERF.md section 6, PR 33), and what a
            # GSPMD-partitioned step always ran. The force rung keeps
            # the hand kernels reachable: DL4J_TPU_FUSED_BN_BWD=1 runs
            # the SAME forward under a custom_vjp whose backward is
            # the Pallas pair, DL4J_TPU_FUSED_CONV=1 (family bn_fwd)
            # routes the statistics and the normalize through the
            # one-pass kernels of ops/conv_pallas.py; without the
            # fused backward, maybe_fused_bn_train runs those with the
            # relu/identity activation streamed into the epilogue.
            from deeplearning4j_tpu.ops.bn_pallas import (
                bn_forward_math, bn_train_normalize,
                fused_bn_bwd_enabled)
            from deeplearning4j_tpu.ops.conv_pallas import (
                maybe_fused_bn_train)
            act_done = False
            if fused_bn_bwd_enabled():
                out, mean, var = bn_train_normalize(
                    x, params["gamma"], params["beta"], self.eps)
            else:
                fused = maybe_fused_bn_train(
                    x, params["gamma"], params["beta"], self.eps,
                    self.activation)
                if fused is not None:
                    out, mean, var = fused
                    act_done = True
                else:
                    out, mean, var, _ = bn_forward_math(
                        x, params["gamma"], params["beta"], self.eps)
            d = self.decay
            new_state = {"mean": d * state["mean"] + (1 - d) * mean,
                         "var": d * state["var"] + (1 - d) * var}
            return (out if act_done else self.activation(out),
                    new_state)
        acc = jnp.promote_types(x.dtype, jnp.float32)
        mean = state["mean"].astype(acc)
        var = state["var"].astype(acc)
        # x * scale + bias with per-channel scale/bias: one fused
        # multiply-add over the tensor instead of subtract/divide chains
        scale = params["gamma"].astype(var.dtype) / jnp.sqrt(var + self.eps)
        bias = params["beta"].astype(var.dtype) - mean * scale
        from deeplearning4j_tpu.ops.conv_pallas import (
            maybe_bn_inference_epilogue)
        out = maybe_bn_inference_epilogue(x, scale, bias,
                                          self.activation)
        if out is not None:         # scale/shift/act in ONE pass
            return out, state
        out = x * scale.astype(x.dtype) + bias.astype(x.dtype)
        return self.activation(out), state

    def get_output_type(self, input_type):
        return input_type

    def set_n_in(self, input_type, override):
        self.n_in = self.n_out = self._nf(input_type)


@dataclass
class ActivationLayer(Layer):
    def has_params(self) -> bool:
        return False

    def forward(self, params, x, *, training, rng=None, state=None):
        return self.activation(x), state

    def get_output_type(self, input_type):
        return input_type

    def set_n_in(self, input_type, override):
        pass


@dataclass
class DropoutLayer(Layer):
    """Standalone dropout layer; ``dropout`` is the retain probability,
    matching the reference's convention."""

    dropout: float = 0.5

    def has_params(self) -> bool:
        return False

    def forward(self, params, x, *, training, rng=None, state=None):
        return self._maybe_dropout(x, training, rng), state

    def get_output_type(self, input_type):
        return input_type

    def set_n_in(self, input_type, override):
        pass


@dataclass
class EmbeddingLayer(Layer):
    """Index -> vector lookup (reference: conf.layers.EmbeddingLayer).
    Input: int [batch] or [batch, 1]."""

    has_bias: bool = False

    def init_params(self, key, input_type, dtype=jnp.float32):
        wi = self.weight_init or WeightInit.XAVIER
        p = {"W": wi.init(key, (self.n_in, self.n_out),
                          self.n_in, self.n_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def forward(self, params, x, *, training, rng=None, state=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        z = params["W"][idx]
        if self.has_bias:
            z = z + params["b"]
        return self.activation(z), state

    def get_output_type(self, input_type):
        return InputType.feed_forward(self.n_out)

    def set_n_in(self, input_type, override):
        pass  # n_in is vocabulary size; never inferred from input width


@dataclass
class GlobalPoolingLayer(Layer):
    """Global pooling over spatial or time dims (reference:
    conf.layers.GlobalPoolingLayer). Supports masked time averaging."""

    pooling_type: PoolingType = PoolingType.MAX

    def has_params(self) -> bool:
        return False

    def accepts_mask(self) -> bool:
        return True

    def forward(self, params, x, *, training, rng=None, state=None,
                mask=None):
        if x.ndim == 5:          # NDHWC -> pool D,H,W
            axes = (1, 2, 3)
        elif x.ndim == 4:        # NHWC -> pool H,W
            axes = (1, 2)
        elif x.ndim == 3:        # [b, t, f] -> pool t
            axes = (1,)
        else:
            return x, state
        if mask is not None and x.ndim in (3, 5):
            # time mask over [b, t, f] or [b, t, h, w, c] (masked
            # ConvLSTM sequences): padded steps drop out of the pool
            m = mask.reshape(mask.shape[:2] + (1,) * (x.ndim - 2))
            spatial = 1
            for d in x.shape[2:-1]:
                spatial *= d
            if self.pooling_type is PoolingType.MAX:
                z = jnp.max(jnp.where(m > 0, x, -jnp.inf), axis=axes)
            elif self.pooling_type is PoolingType.SUM:
                z = jnp.sum(x * m, axis=axes)
            elif self.pooling_type is PoolingType.AVG:
                denom = jnp.maximum(jnp.sum(mask, axis=1),
                                    1.0)[:, None] * spatial
                z = jnp.sum(x * m, axis=axes) / denom
            else:                # PNORM over unmasked timesteps
                p = float(self.pnorm) if hasattr(self, "pnorm") else 2.0
                z = jnp.sum(jnp.abs(x * m) ** p, axis=axes) ** (1.0 / p)
            return z, state
        if self.pooling_type is PoolingType.MAX:
            z = jnp.max(x, axis=axes)
        elif self.pooling_type is PoolingType.SUM:
            z = jnp.sum(x, axis=axes)
        elif self.pooling_type is PoolingType.AVG:
            z = jnp.mean(x, axis=axes)
        else:
            p = float(self.pnorm) if hasattr(self, "pnorm") else 2.0
            z = jnp.sum(jnp.abs(x) ** p, axis=axes) ** (1.0 / p)
        return z, state

    def get_output_type(self, input_type):
        from deeplearning4j_tpu.nn.conf.inputs import \
            InputTypeConvolutional3D
        if isinstance(input_type, (InputTypeConvolutional,
                                   InputTypeConvolutional3D)):
            return InputType.feed_forward(input_type.channels)
        if isinstance(input_type, InputTypeRecurrent):
            return InputType.feed_forward(input_type.size)
        return input_type

    def set_n_in(self, input_type, override):
        pass


# ---------------------------------------------------------------------------
@dataclass
class BaseOutputLayer(DenseLayer):
    """Common: dense projection + loss head."""

    loss_function: LossFunction = LossFunction.MCXENT
    activation: Activation = Activation.SOFTMAX

    @staticmethod
    def _builder_positional(*args) -> dict:
        return {"loss_function": args[0]} if args else {}

    def compute_loss(self, labels, preds_or_logits, *, from_logits: bool,
                     mask=None, average=True):
        lf = self.loss_function
        if from_logits and lf.supports_logits():
            return lf.score_from_logits(labels, preds_or_logits, mask=mask,
                                        average=average)
        return lf.score(labels, preds_or_logits, mask=mask, average=average)

    def wants_logits(self) -> bool:
        """Fuse final softmax/sigmoid into the loss (TPU-first: avoids the
        reference's prob-space clip+log; same trick its MCXENT+softmax
        fusion performs)."""
        return (self.loss_function.supports_logits() and
                self.activation in (Activation.SOFTMAX, Activation.SIGMOID))

    def forward_logits(self, params, x, *, training, rng=None, state=None):
        x = self._maybe_dropout(x, training, rng)
        z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return z, state


@dataclass
class OutputLayer(BaseOutputLayer):
    """Reference: conf.layers.OutputLayer."""


@dataclass
class RnnOutputLayer(BaseOutputLayer):
    """Per-timestep output head (reference: conf.layers.RnnOutputLayer).
    Input [b, t, f] -> output [b, t, n_out]."""

    def set_n_in(self, input_type, override):
        if isinstance(input_type, InputTypeRecurrent) and \
                (override or not self.n_in):
            self.n_in = input_type.size

    def get_output_type(self, input_type):
        t = input_type.timesteps if isinstance(input_type,
                                               InputTypeRecurrent) else -1
        return InputType.recurrent(self.n_out, t)


@dataclass
class LossLayer(BaseOutputLayer):
    """Loss-only head, no params (reference: conf.layers.LossLayer)."""

    def has_params(self) -> bool:
        return False

    def init_params(self, key, input_type, dtype=jnp.float32):
        return {}

    def forward(self, params, x, *, training, rng=None, state=None):
        return self.activation(x), state

    def forward_logits(self, params, x, *, training, rng=None, state=None):
        return x, state

    def get_output_type(self, input_type):
        return input_type

    def set_n_in(self, input_type, override):
        if isinstance(input_type, InputTypeFeedForward):
            self.n_in = self.n_out = input_type.size


@dataclass
class CnnLossLayer(BaseOutputLayer):
    """Per-pixel loss head on [b, h, w, c] activations — no params,
    no flattening (reference: conf.layers.CnnLossLayer; used by
    segmentation nets like UNet)."""

    activation: Activation = Activation.IDENTITY

    def has_params(self) -> bool:
        return False

    def init_params(self, key, input_type, dtype=jnp.float32):
        return {}

    def set_n_in(self, input_type, override):
        pass

    def get_output_type(self, input_type):
        return input_type

    def wants_logits(self) -> bool:
        return False

    def forward(self, params, x, *, training, rng=None, state=None):
        return self.activation(x), state

    def forward_logits(self, params, x, *, training, rng=None,
                       state=None):
        return x, state


LAYER_REGISTRY: dict = {c.__name__: c for c in
                        (DenseLayer, ConvolutionLayer, SubsamplingLayer,
                         BatchNormalization, ActivationLayer, DropoutLayer,
                         EmbeddingLayer, GlobalPoolingLayer, OutputLayer,
                         RnnOutputLayer, LossLayer, CnnLossLayer)}


def register_layer(cls):
    """Register a layer class for JSON round-trip (zoo/custom layers)."""
    LAYER_REGISTRY[cls.__name__] = cls
    return cls
