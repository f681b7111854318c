"""Pallas flash-attention backend (ops/attention_pallas.py):
interpret-mode forward/gradient conformance against the dense einsum
reference, the [b, t_k] key-mask reduction, and the backend-selection
heuristic (structural fallbacks, env override, auto thresholds)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.attention import dot_product_attention
from deeplearning4j_tpu.ops.attention_pallas import (
    FLASH_MIN_SEQ, as_key_mask, flash_attention_override, flash_sdpa,
    maybe_flash_sdpa, select_attention_backend)

R = np.random.RandomState(0)


def _qkv(b=2, h=2, t=64, d=8):
    return tuple(jnp.asarray(R.randn(b, h, t, d), jnp.float32)
                 for _ in range(3))


def _dense(q, k, v, scale, key_mask=None):
    mask = (key_mask[:, None, None, :]
            if key_mask is not None else None)
    return dot_product_attention(q, k, v, mask=mask, scale=scale)


class TestFlashConformance:
    """interpret mode runs the SAME kernel code the chip runs."""

    @pytest.mark.parametrize("scale", [None, 0.37])
    def test_forward_matches_dense(self, scale):
        q, k, v = _qkv()
        got = flash_sdpa(q, k, v, scale, block_q=32, block_k=32)
        want = _dense(q, k, v, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_key_mask_matches_dense(self):
        q, k, v = _qkv()
        km = jnp.asarray(
            np.concatenate([np.ones((2, 48)), np.zeros((2, 16))],
                           axis=1), jnp.float32)
        got = flash_sdpa(q, k, v, 0.5, key_mask=km, block_q=32,
                         block_k=32)
        want = _dense(q, k, v, 0.5, key_mask=km)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_rank3_unit_heads(self):
        q, k, v = (x[:, 0] for x in _qkv())
        got = flash_sdpa(q, k, v, block_q=32, block_k=32)
        want = _dense(q[:, None], k[:, None], v[:, None], None)[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients_match_dense(self):
        q, k, v = _qkv(t=32)
        km = jnp.asarray(
            np.concatenate([np.ones((2, 24)), np.zeros((2, 8))],
                           axis=1), jnp.float32)

        def loss_flash(q, k, v):
            return jnp.sum(flash_sdpa(q, k, v, 0.37, key_mask=km,
                                      block_q=16, block_k=16) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(_dense(q, k, v, 0.37, key_mask=km) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


class TestKeyMaskReduction:
    def test_broadcast_forms_reduce(self):
        m = jnp.asarray(R.rand(2, 1, 1, 16) > 0.3, jnp.float32)
        km = as_key_mask(m, 2, 16, 4)
        assert km.shape == (2, 16)
        np.testing.assert_array_equal(np.asarray(km),
                                      np.asarray(m[:, 0, 0, :]))
        # shared-across-batch [1, 1, 1, t_k] broadcasts up
        m1 = m[:1]
        km1 = as_key_mask(m1, 2, 16, 4)
        assert km1.shape == (2, 16)
        np.testing.assert_array_equal(np.asarray(km1[0]),
                                      np.asarray(km1[1]))
        # plain [t_k] vector
        assert as_key_mask(jnp.ones((16,)), 2, 16, 4).shape == (2, 16)

    def test_per_query_and_per_head_masks_rejected(self):
        assert as_key_mask(jnp.ones((2, 1, 16, 16)), 2, 16, 4) is None
        assert as_key_mask(jnp.ones((2, 4, 1, 16)), 2, 16, 4) is None
        assert as_key_mask(jnp.ones((2, 1, 1, 8)), 2, 16, 4) is None


class TestBackendSelection:
    Q4 = (2, 4, 512, 64)

    def test_structural_fallbacks_dominate(self):
        b, r = select_attention_backend(self.Q4, self.Q4,
                                        has_bias=True, override=True)
        assert b == "dense" and "bias" in r
        b, r = select_attention_backend((512, 64), (512, 64),
                                        override=True)
        assert b == "dense" and "rank" in r
        b, r = select_attention_backend(self.Q4, (2, 4, 512, 32),
                                        override=True)
        assert b == "dense" and "mismatch" in r
        b, r = select_attention_backend(self.Q4, self.Q4,
                                        mask_ok=False, override=True)
        assert b == "dense" and "mask" in r

    def test_override_beats_auto(self):
        b, _ = select_attention_backend(self.Q4, self.Q4,
                                        override=True, platform="cpu")
        assert b == "flash"
        long = (2, 4, FLASH_MIN_SEQ, 64)
        b, r = select_attention_backend(long, long, override=False,
                                        platform="tpu")
        assert b == "dense" and "kill switch" in r

    def test_auto_heuristic(self):
        b, r = select_attention_backend(self.Q4, self.Q4,
                                        platform="cpu",
                                        use_env_override=False)
        assert b == "dense" and "not tpu" in r
        long = (2, 4, FLASH_MIN_SEQ, 64)
        b, r = select_attention_backend(long, long, platform="tpu",
                                        use_env_override=False)
        assert b == "flash" and str(FLASH_MIN_SEQ) in r
        # short seq, plenty of HBM: dense wins
        b, _ = select_attention_backend(self.Q4, self.Q4,
                                        platform="tpu",
                                        free_hbm=16 << 30,
                                        use_env_override=False)
        assert b == "dense"
        # short seq but the scores tensor would eat the free HBM
        b, r = select_attention_backend(self.Q4, self.Q4,
                                        platform="tpu",
                                        free_hbm=1 << 20,
                                        use_env_override=False)
        assert b == "flash" and "free HBM" in r

    def test_env_var_gates(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FLASH_ATTENTION", "0")
        assert flash_attention_override() is False
        q, k, v = _qkv(t=16)
        assert maybe_flash_sdpa(q, k, v, 0.5) is None
        monkeypatch.setenv("DL4J_TPU_FLASH_ATTENTION", "1")
        assert flash_attention_override() is True
        out = maybe_flash_sdpa(q, k, v, 0.5)      # interpret on CPU
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_dense(q, k, v, 0.5)),
                                   rtol=2e-5, atol=2e-5)
        monkeypatch.delenv("DL4J_TPU_FLASH_ATTENTION")
        assert flash_attention_override() is None
        # auto on CPU: dense path (returns None)
        assert maybe_flash_sdpa(q, k, v, 0.5) is None

    def test_dense_bias_site_falls_back(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FLASH_ATTENTION", "1")
        q, k, v = _qkv(t=16)
        bias = jnp.asarray(R.randn(2, 2, 16, 16), jnp.float32)
        assert maybe_flash_sdpa(q, k, v, 0.5, bias=bias) is None

    def test_per_query_mask_falls_back(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FLASH_ATTENTION", "1")
        q, k, v = _qkv(t=16)
        causal = jnp.tril(jnp.ones((16, 16)))[None, None]
        assert maybe_flash_sdpa(q, k, v, 0.5, mask=causal) is None


class TestFusedBnBwdDefault:
    """DL4J_TPU_FUSED_BN_BWD: the auto rung is XLA's lowering on every
    platform, the TPU included (PERF.md section 6, PR 33); =0 stays
    the kill switch, =1 forces the kernels anywhere."""

    def test_default_is_dense_on_every_platform(self, monkeypatch):
        from deeplearning4j_tpu.ops import bn_pallas, kernel_select
        monkeypatch.delenv("DL4J_TPU_FUSED_BN_BWD", raising=False)
        assert bn_pallas.fused_bn_bwd_enabled() is False
        monkeypatch.setattr(kernel_select, "platform", lambda: "tpu")
        assert bn_pallas.fused_bn_bwd_enabled() is False
        monkeypatch.setenv("DL4J_TPU_FUSED_BN_BWD", "1")
        assert bn_pallas.fused_bn_bwd_enabled() is True
        monkeypatch.setenv("DL4J_TPU_FUSED_BN_BWD", "0")
        assert bn_pallas.fused_bn_bwd_enabled() is False
