"""Unified kernel-selection ladder (ops/kernel_select.py): structural
gate -> force/kill env -> measured auto-heuristic, every decision
counted in dl4j_kernel_select_total{kernel,decision}.  The ladder is
regression-proven against the gates it unified: the attention backend
selector and the fused-BN-backward switch must behave exactly as they
did when each carried its own ad-hoc gate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.activations import Activation
from deeplearning4j_tpu.common.environment import Environment
from deeplearning4j_tpu.ops import conv_pallas, kernel_select
from deeplearning4j_tpu.ops.attention_pallas import (
    flash_attention_override, select_attention_backend)
from deeplearning4j_tpu.ops.bn_pallas import (BN_TPU_READING,
                                              fused_bn_bwd_enabled)


@pytest.fixture(autouse=True)
def _clean_extra():
    env = Environment.get()
    keys = ("fused_conv", "fused_bn_bwd", "flash_attention")
    saved = {k: env.extra.get(k) for k in keys}
    for k in keys:
        env.extra.pop(k, None)
    yield
    for k, v in saved.items():
        if v is None:
            env.extra.pop(k, None)
        else:
            env.extra[k] = v


def _delta(kernel, fn):
    before = kernel_select.decisions(kernel)
    out = fn()
    after = kernel_select.decisions(kernel)
    return out, {d: after[d] - before[d] for d in after
                 if after[d] != before[d]}


class TestLadder:
    def test_structural_gate_dominates_force(self):
        sel, counts = _delta("conv_epilogue", lambda: kernel_select.select(
            "conv_epilogue", structural="dtype int32 is not floating",
            auto=(True, "auto"), override=True,
            use_env_override=False))
        assert not sel.fused
        assert sel.decision == "structural"
        assert "int32" in sel.reason
        assert counts == {"structural": 1}

    def test_force_and_kill_beat_auto(self):
        sel, counts = _delta("conv_epilogue", lambda: kernel_select.select(
            "conv_epilogue", auto=(False, "auto says no"),
            override=True, use_env_override=False))
        assert sel.fused and sel.decision == "forced"
        assert sel.reason == "DL4J_TPU_FUSED_CONV=1 forced"
        assert counts == {"forced": 1}
        sel, counts = _delta("conv_epilogue", lambda: kernel_select.select(
            "conv_epilogue", auto=(True, "auto says yes"),
            override=False, use_env_override=False))
        assert not sel.fused and sel.decision == "killed"
        assert sel.reason == "DL4J_TPU_FUSED_CONV=0 kill switch"
        assert counts == {"killed": 1}

    def test_auto_thunk_decides_when_unset(self):
        sel, counts = _delta("conv_epilogue", lambda: kernel_select.select(
            "conv_epilogue", auto=lambda: (True, "auto: measured"),
            override=None, use_env_override=False))
        assert sel.fused and sel.decision == "auto_fused"
        assert counts == {"auto_fused": 1}
        sel, counts = _delta("conv_epilogue", lambda: kernel_select.select(
            "conv_epilogue", auto=lambda: (False, "auto: dense: measured"),
            override=None, use_env_override=False))
        assert not sel.fused and sel.reason == "auto: dense: measured"
        assert counts == {"auto_dense": 1}

    def test_extra_overrides_env_var(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FUSED_CONV", "0")
        assert kernel_select.gate_override("conv_epilogue") is False
        Environment.get().extra["fused_conv"] = "1"
        assert kernel_select.gate_override("conv_epilogue") is True

    def test_env_var_tristate(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_FUSED_CONV", raising=False)
        assert kernel_select.gate_override("conv_epilogue") is None
        monkeypatch.setenv("DL4J_TPU_FUSED_CONV", "1")
        assert kernel_select.gate_override("conv_epilogue") is True
        monkeypatch.setenv("DL4J_TPU_FUSED_CONV", "0")
        assert kernel_select.gate_override("conv_epilogue") is False


class TestConvFamilyGates:
    def test_structural_demotions_logged_reasons(self):
        cases = [
            # (kwargs, reason substring)
            (dict(out_shape=(2, 8, 8, 16), dtype=jnp.int32,
                  act_name="relu"), "not floating"),
            (dict(out_shape=(2, 8, 8, 5), dtype=jnp.float32,
                  act_name="relu"), "sublane-aligned"),
            (dict(out_shape=(2, 8, 8, 16), dtype=jnp.float32,
                  act_name="tanh"), "not streamable"),
            (dict(out_shape=(2, 8, 8, 16), dtype=jnp.float32,
                  act_name="identity", has_epilogue=False),
             "no epilogue"),
            (dict(out_shape=(16,), dtype=jnp.float32,
                  act_name="relu"), "rank 1"),
        ]
        for kwargs, substr in cases:
            sel = conv_pallas.select_conv_epilogue(
                platform="tpu", override=True, **kwargs)
            assert not sel.fused and sel.decision == "structural"
            assert substr in sel.reason, (kwargs, sel.reason)

    def test_f64_demotes_on_tpu_only(self):
        kw = dict(out_shape=(2, 8, 8, 16), dtype=jnp.float64,
                  act_name="relu", override=True)
        assert not conv_pallas.select_conv_epilogue(
            platform="tpu", **kw).fused
        assert conv_pallas.select_conv_epilogue(
            platform="cpu", **kw).fused

    def test_bn_forward_inference_is_structural(self):
        """The training-vs-inference gate: the batch-stats kernel is
        a training-mode construct; forcing cannot resurrect it in
        inference."""
        sel = conv_pallas.select_bn_forward(
            (2, 8, 8, 16), jnp.float32, training=False,
            platform="tpu", override=True)
        assert not sel.fused and sel.decision == "structural"
        assert "inference" in sel.reason
        assert conv_pallas.select_bn_forward(
            (2, 8, 8, 16), jnp.float32, training=True,
            platform="tpu", override=True).fused

    def test_auto_rung_is_dense_and_names_its_chip_reading(self):
        """A family's auto rung is on for the TPU only where a chip
        run in PERF.md shows the whole program faster with it: none
        does for the conv family, at any size."""
        for shape in ((256, 1024), (8, 16), (256, 56, 56, 256)):
            kw = dict(out_shape=shape, dtype=jnp.float32,
                      act_name="relu", override=None,
                      use_env_override=False)
            sel = conv_pallas.select_conv_epilogue(platform="cpu", **kw)
            assert not sel.fused and "not tpu" in sel.reason
            sel = conv_pallas.select_conv_epilogue(platform="tpu", **kw)
            assert not sel.fused and sel.decision == "auto_dense"
            assert sel.reason == ("auto: dense: "
                                  + conv_pallas.CONV_TPU_READING)
            assert "PERF.md" in sel.reason and "PR 33" in sel.reason
            # the force rung still reaches the kernel
            assert conv_pallas.select_conv_epilogue(
                shape, jnp.float32, "relu", platform="tpu",
                override=True).decision == "forced"

    def test_counter_increments_per_decision(self):
        _, counts = _delta("conv_epilogue", lambda: [
            conv_pallas.select_conv_epilogue(
                (2, 8, 8, 16), jnp.float32, "relu", platform="cpu",
                override=True),
            conv_pallas.select_conv_epilogue(
                (2, 8, 8, 16), jnp.float32, "tanh", platform="cpu",
                override=True),
            conv_pallas.select_conv_epilogue(
                (2, 8, 8, 16), jnp.float32, "relu", platform="cpu",
                override=None, use_env_override=False),
        ])
        assert counts == {"forced": 1, "structural": 1,
                          "auto_dense": 1}


class TestAttentionGateMirrored:
    """The flash gate behaves exactly as before the unification, and
    its decisions now land in the shared counter."""

    Q4 = (2, 4, 512, 64)

    def test_reason_strings_preserved(self):
        assert select_attention_backend(
            self.Q4, self.Q4, has_bias=True) == \
            ("dense", "additive bias is not streamable")
        assert select_attention_backend(
            self.Q4, self.Q4, override=False) == \
            ("dense", "DL4J_TPU_FLASH_ATTENTION=0 kill switch")
        assert select_attention_backend(
            self.Q4, self.Q4, override=True) == \
            ("flash", "DL4J_TPU_FLASH_ATTENTION=1 forced")
        backend, reason = select_attention_backend(
            self.Q4, (2, 4, 8192, 64), platform="tpu", override=None,
            use_env_override=False)
        assert backend == "flash" and "t_k=8192" in reason

    def test_decisions_counted(self):
        _, counts = _delta("attention", lambda: [
            select_attention_backend(self.Q4, self.Q4, has_bias=True),
            select_attention_backend(self.Q4, self.Q4, override=True),
            select_attention_backend(self.Q4, self.Q4,
                                     platform="cpu", override=None,
                                     use_env_override=False),
        ])
        assert counts == {"structural": 1, "forced": 1,
                          "auto_dense": 1}

    def test_override_reads_extra_then_env(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FLASH_ATTENTION", "1")
        assert flash_attention_override() is True
        Environment.get().extra["flash_attention"] = "0"
        assert flash_attention_override() is False


class TestBnBwdGateMirrored:
    def test_env_semantics_preserved(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FUSED_BN_BWD", "1")
        assert fused_bn_bwd_enabled() is True
        monkeypatch.setenv("DL4J_TPU_FUSED_BN_BWD", "0")
        assert fused_bn_bwd_enabled() is False
        monkeypatch.delenv("DL4J_TPU_FUSED_BN_BWD", raising=False)
        # auto rung: XLA's lowering, on the cpu and on the tpu
        assert fused_bn_bwd_enabled() is False
        monkeypatch.setattr(kernel_select, "platform", lambda: "tpu")
        _, counts = _delta("bn_bwd", fused_bn_bwd_enabled)
        assert counts == {"auto_dense": 1}

    def test_decisions_counted(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FUSED_BN_BWD", "1")
        _, counts = _delta("bn_bwd", fused_bn_bwd_enabled)
        assert counts == {"forced": 1}


#: every distinct BN site of ``resnet50.train-1chip`` (b256, 224 px)
RESNET50_BN_SITES = [
    (256, 112, 112, 64), (256, 56, 56, 64), (256, 56, 56, 256),
    (256, 28, 28, 128), (256, 28, 28, 512), (256, 14, 14, 256),
    (256, 14, 14, 1024), (256, 7, 7, 512), (256, 7, 7, 2048)]


class TestBnFamiliesLeaveTheOneChipStep:
    """On a TPU the auto rung of ``bn_fwd`` and ``bn_bwd`` is XLA's
    lowering at every BN site of the training cell (PERF.md section 6,
    PR 33: the whole step is 2.58 times faster without the kernels);
    the force rung still gives the kernels, and the counter says
    which rung decided. Traced through the layer by shape alone."""

    @staticmethod
    def _trace(shape, monkeypatch, **gates):
        from deeplearning4j_tpu.nn.conf.layers import BatchNormalization
        monkeypatch.setattr(kernel_select, "platform", lambda: "tpu")
        Environment.get().extra.update(gates)
        bn = BatchNormalization(activation=Activation.RELU)
        c = shape[-1]
        params = {"gamma": jnp.ones(c), "beta": jnp.zeros(c)}
        state = {"mean": jnp.zeros(c), "var": jnp.ones(c)}
        (out, bwd), fwd = _delta("bn_fwd", lambda: _delta(
            "bn_bwd", lambda: jax.eval_shape(
                lambda x: bn.forward(params, x, training=True,
                                     state=state)[0],
                jax.ShapeDtypeStruct(shape, jnp.bfloat16))))
        assert out.shape == shape and out.dtype == jnp.bfloat16
        return {"bn_fwd": fwd, "bn_bwd": bwd}

    @pytest.mark.parametrize("shape", RESNET50_BN_SITES,
                             ids=lambda s: "x".join(map(str, s)))
    def test_auto_is_dense_and_force_is_the_kernel(self, shape,
                                                   monkeypatch):
        took = self._trace(shape, monkeypatch)
        assert set(took["bn_bwd"]) == {"auto_dense"}
        assert set(took["bn_fwd"]) == {"auto_dense"}
        sel = conv_pallas.select_bn_forward(shape, jnp.bfloat16,
                                            training=True, record=False)
        assert sel.reason == "auto: dense: " + BN_TPU_READING
        assert "PERF.md" in sel.reason and "PR 33" in sel.reason
        took = self._trace(shape, monkeypatch, fused_conv="1",
                           fused_bn_bwd="1")
        assert took == {"bn_fwd": {"forced": 1}, "bn_bwd": {"forced": 1}}


class TestFusedSitesCounter:
    def test_fused_steps_counter_increments(self):
        env = Environment.get()
        env.extra["fused_conv"] = "1"
        try:
            x = jnp.asarray(np.random.RandomState(0)
                            .randn(2, 4, 4, 16), jnp.float32)
            before = conv_pallas._fused_steps.value(site="bn_infer")
            out = conv_pallas.maybe_bn_inference_epilogue(
                x, jnp.ones(16), jnp.zeros(16), Activation.RELU)
            assert out is not None
            after = conv_pallas._fused_steps.value(site="bn_infer")
            assert after == before + 1
        finally:
            env.extra.pop("fused_conv", None)


class TestPartitionedGate:
    """A GSPMD-partitioned program cannot hold a Mosaic kernel (jax
    refuses the lowering), so the mark demotes every family — even a
    forced one — structurally, and the platform alone decides
    interpret mode."""

    def test_partitioned_demotes_even_forced(self):
        with kernel_select.partitioned(4):
            sel, counts = _delta("bn_bwd", lambda: kernel_select.select(
                "bn_bwd", auto=(True, "auto"), override=True))
        assert not sel.fused and sel.decision == "structural"
        assert "partitioned over 4 devices" in sel.reason
        assert counts == {"structural": 1}
        # the mark ends with the block, and one device is no partition
        with kernel_select.partitioned(1):
            assert kernel_select.select(
                "bn_bwd", auto=(True, "auto"), override=True,
                record=False).fused
        assert kernel_select.select(
            "bn_bwd", auto=(True, "auto"), override=True,
            record=False).fused

    def test_model_funnels_mark_what_their_params_span(self):
        class M:
            params = {"w": jnp.ones(2)}
            seen = None

            @kernel_select.marks_partitions
            def fit(self):
                self.seen = kernel_select.select(
                    "bn_bwd", auto=(True, "auto"), record=False).fused
        m = M()
        m.fit()
        assert m.seen is True                       # one device
        devs = jax.devices()
        if len(devs) >= 2:
            mesh = jax.sharding.Mesh(np.array(devs[:2]), ("data",))
            m.params = {"w": jax.device_put(
                jnp.ones(2), jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))}
            m.fit()
            assert m.seen is False                  # replicated over 2

    def test_interpret_mode_is_the_platform(self):
        assert kernel_select.interpret_mode() == (
            jax.default_backend() != "tpu")
