"""Sequence/context parallelism tests (SURVEY.md P9/§5.7 extension).

Every sharded/blocked attention form must equal dense softmax
attention (ops.attention.dot_product_attention) on gathered data.
Runs on the virtual 8-device CPU mesh (conftest)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.attention import dot_product_attention
from deeplearning4j_tpu.parallel import make_mesh
from deeplearning4j_tpu.parallel.sequence import (
    blockwise_attention, flash_attention, ring_attention,
    ring_self_attention, ulysses_self_attention)

from conftest import require_devices


@pytest.fixture(autouse=True)
def _f32_matmuls():
    """These are ALGORITHM-equivalence tests (blocked/sharded vs
    dense); run matmuls at f32 precision so TPU's default-bf16
    multiplies (~2e-3 abs at these scales) don't drown the
    comparison. Production precision is a benchmark concern, not a
    correctness one."""
    with jax.default_matmul_precision("highest"):
        yield


def _qkv(b=2, h=4, t=64, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, t, d).astype(np.float32))
    return mk(), mk(), mk()


def _dense(q, k, v, causal=False):
    mask = None
    if causal:
        t = q.shape[-2]
        mask = jnp.tril(jnp.ones((t, t), bool))
    return dot_product_attention(q, k, v, mask)


class TestBlockwise:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("block_k", [16, 24, 64])
    def test_matches_dense(self, causal, block_k):
        q, k, v = _qkv()
        out = blockwise_attention(q, k, v, causal=causal,
                                  block_k=block_k)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_dense(q, k, v, causal)),
                                   atol=2e-5)

    def test_key_mask(self):
        q, k, v = _qkv(t=32)
        km = jnp.asarray((np.arange(32) < 20)[None, None, :]
                         * np.ones((2, 4, 1)), jnp.float32)
        out = blockwise_attention(q, k, v, key_mask=km, block_k=16)
        ref = dot_product_attention(q, k, v, km[..., None, :])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_grad_matches_dense(self):
        q, k, v = _qkv(b=1, h=2, t=32, d=8)

        def loss_block(q, k, v):
            return jnp.sum(blockwise_attention(q, k, v, causal=True,
                                               block_k=16) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(_dense(q, k, v, True) ** 2)

        g1 = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = _qkv(t=256, d=32)
        out = flash_attention(q, k, v, causal, 128, 128)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_dense(q, k, v, causal)),
                                   atol=2e-5)

    def test_grad_flows(self):
        q, k, v = _qkv(b=1, h=1, t=128, d=16)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(_dense(q, k, v, True) ** 2)

        g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            # 2e-4 abs: sum-of-squares loss over t=128 amplifies the
            # f32 rounding on real TPU to ~5e-5 (relative ~6e-5);
            # CPU sits well under the old 5e-5
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("masked", [False, True])
    def test_kernel_backward_gradcheck(self, causal, masked):
        """r4: the backward is a pair of Pallas dq / dk+dv kernels
        (probabilities recomputed from the saved log-sum-exp), run
        here through interpret mode — the SAME kernel code path as
        TPU — against blockwise autodiff, multi-block grid, all
        causal x mask combinations."""
        from deeplearning4j_tpu.parallel.sequence import \
            blockwise_attention
        rng = np.random.RandomState(0)
        b, h, t, d = 2, 3, 256, 64
        q, k, v = (jnp.asarray(rng.randn(b, h, t, d)
                               .astype(np.float32) * 0.3)
                   for _ in range(3))
        km = None
        if masked:
            kma = np.ones((b, t), np.float32)
            kma[:, t // 2:] = 0.0
            km = jnp.asarray(kma)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal, 128, 128,
                                key_mask=km)
            return jnp.sum(jnp.sin(o))

        def loss_ref(q, k, v):
            kmb = None if km is None else km[:, None, :]
            o = blockwise_attention(q, k, v, causal=causal,
                                    block_k=128, key_mask=kmb)
            return jnp.sum(jnp.sin(o))

        gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, want in zip(gf, gr):
            # 5e-5: the masked case on real TPU sits at ~2.4e-5 even
            # at f32 matmul precision (fully-masked blocks round the
            # lse differently); CPU interpret mode is < 7e-6
            np.testing.assert_allclose(np.asarray(a),
                                       np.asarray(want), atol=5e-5)

    def test_indivisible_lengths_autofit_blocks(self):
        """Blocks that don't divide the sequence shrink to a divisor
        instead of erroring (t=48 with 32-blocks runs at 16)."""
        q, k, v = _qkv(t=48)
        out = flash_attention(q, k, v, False, 32, 32)
        s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        e = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("bhqk,bhkd->bhqd",
                         e / e.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)


class TestRingAttention:

    @pytest.fixture(autouse=True)
    def _needs_mesh(self):
        require_devices(8)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_over_mesh(self, causal):
        mesh = make_mesh({"seq": 8})
        q, k, v = _qkv(t=64)
        out = ring_self_attention(mesh, q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_dense(q, k, v, causal)),
                                   atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_ring_matches_dense(self, causal):
        """r4 use_flash ring: per-shard attention-with-lse merged
        EXACTLY via log-sum-exps; causal decomposes into fully-
        visible / locally-causal / skipped shards.  (On the CPU mesh
        the per-shard call is the exact dense-with-lse reference —
        the MERGE algebra, which is what ring adds, is fully
        exercised; the Pallas kernels themselves are interpret-tested
        in TestFlashAttention.)"""
        mesh = make_mesh({"seq": 8})
        q, k, v = _qkv(t=128, d=32)
        out = ring_self_attention(mesh, q, k, v, causal=causal,
                                  use_flash=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_dense(q, k, v, causal)),
                                   atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_ring_grads_match_dense(self, causal):
        mesh = make_mesh({"seq": 8})
        q, k, v = _qkv(t=128, d=32)

        def loss_r(q, k, v):
            return jnp.sum(jnp.sin(ring_self_attention(
                mesh, q, k, v, causal=causal, use_flash=True)))

        def loss_d(q, k, v):
            return jnp.sum(jnp.sin(_dense(q, k, v, causal)))

        gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
        gd = jax.grad(loss_d, (0, 1, 2))(q, k, v)
        for a, want in zip(gr, gd):
            np.testing.assert_allclose(np.asarray(a),
                                       np.asarray(want), atol=5e-5)

    def test_flash_with_lse_matches_dense_lse(self):
        """flash_attention_with_lse: both outputs conform, and the
        lse COTANGENT flows (a loss using lse directly)."""
        from deeplearning4j_tpu.parallel.sequence import (
            NEG_INF, flash_attention_with_lse)
        q, k, v = _qkv(t=256, d=32)

        def dense_lse(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) \
                / np.sqrt(q.shape[-1])
            return jax.scipy.special.logsumexp(s, axis=-1)

        o, lse = flash_attention_with_lse(q, k, v, False, 128, 128)
        np.testing.assert_allclose(np.asarray(o),
                                   np.asarray(_dense(q, k, v)),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse),
                                   np.asarray(dense_lse(q, k, v)),
                                   atol=2e-5)

        def loss_f(q, k, v):
            _, l = flash_attention_with_lse(q, k, v, False, 128, 128)
            return jnp.sum(jnp.cos(l))

        def loss_d(q, k, v):
            return jnp.sum(jnp.cos(dense_lse(q, k, v)))

        gf = jax.grad(loss_f, (0, 1, 2))(q, k, v)
        gd = jax.grad(loss_d, (0, 1, 2))(q, k, v)
        for a, want in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a),
                                       np.asarray(want), atol=5e-5)

    def test_with_data_axis(self):
        mesh = make_mesh({"data": 2, "seq": 4})
        q, k, v = _qkv(b=4, t=32)
        out = ring_self_attention(mesh, q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_dense(q, k, v, True)),
                                   atol=2e-5)

    def test_grad_through_ring(self):
        mesh = make_mesh({"seq": 4}, jax.devices()[:4])
        q, k, v = _qkv(b=1, h=2, t=32, d=8)

        def loss(q, k, v):
            return jnp.sum(ring_self_attention(mesh, q, k, v,
                                               causal=True) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(_dense(q, k, v, True) ** 2)

        g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)


class TestUlysses:

    @pytest.fixture(autouse=True)
    def _needs_mesh(self):
        require_devices(4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_over_mesh(self, causal):
        mesh = make_mesh({"seq": 4}, jax.devices()[:4])  # h=4 % 4 == 0
        q, k, v = _qkv(t=64)
        out = ulysses_self_attention(mesh, q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_dense(q, k, v, causal)),
                                   atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_use_flash_flag_plumbs(self, causal):
        """use_flash on the CPU mesh keeps the blockwise form (the
        kernel engages on TPU only — validated on-chip: 1349.7 ->
        15.1 ms/step at causal seq 8192, BENCH_notes_r04.md); the
        flag must plumb through and stay exact either way."""
        mesh = make_mesh({"seq": 4}, jax.devices()[:4])
        q, k, v = _qkv(t=64)
        out = ulysses_self_attention(mesh, q, k, v, causal=causal,
                                     use_flash=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_dense(q, k, v, causal)),
                                   atol=2e-5)

    def test_fully_masked_rows_are_zero(self):
        """Fully-masked rows must be 0 like the dense reference, not
        mean(V) (code-review regression)."""
        q, k, v = _qkv(b=1, h=1, t=16, d=8)
        km = jnp.zeros((1, 1, 16))         # everything masked
        out = blockwise_attention(q, k, v, key_mask=km, block_k=8)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.zeros_like(out))

    def test_flash_key_mask_matches_dense(self):
        """In-kernel key masking equals dense masked attention."""
        q, k, v = _qkv(b=2, h=4, t=128, d=16)
        km_np = np.ones((2, 128), np.float32)
        km_np[0, 100:] = 0.0
        km_np[1, 64:] = 0.0
        km = jnp.asarray(km_np)
        out = flash_attention(q, k, v, False, 64, 64, km)
        ref = dot_product_attention(q, k, v, km[:, None, None, :])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_flash_key_mask_grad(self):
        q, k, v = _qkv(b=1, h=2, t=64, d=8)
        km = jnp.asarray(np.concatenate(
            [np.ones((1, 48)), np.zeros((1, 16))], 1), jnp.float32)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, False, 64, 64,
                                           km) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(dot_product_attention(
                q, k, v, km[:, None, None, :]) ** 2)

        g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)
