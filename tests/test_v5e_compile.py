"""The engine's commit and decode programs compiled for a described
TPU v5e, at the benchmark's widths and pool sizes with the depth cut
to two layers: what the chip's compiler does to the KV pool. No chip
is attached and nothing runs; the compiler is the one the chip's
machines have (on-chip-measurement guide, section 2).

The pool is stored as the paged kernel reads it and donated, so the
compiled programs must alias every cache input to an output and hold
no ``copy``, ``transpose``, ``slice`` or ``reshape`` of a pool or of a
layer of it: in ``gpt2-large.decode-offline`` those were 77 % of the
device's time (PERF.md, PR 29).

Every compile of this kind lives in this one file: only the worker
that is given it loads the TPU's library.
"""
from __future__ import annotations

import json
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import kernel_select
from deeplearning4j_tpu.serving.generative import DecodeEngine
from deeplearning4j_tpu.serving.kvcache import KVBlockPool

LAYERS = 2
BUCKET, PROMPT = 32, 256
_CONFIGS = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                        "configs")
_H1 = os.path.join(_CONFIGS, "falcon-h1-34b.json")
_P4 = os.path.join(_CONFIGS, "phi4-mini-flash.json")
_MIMO = os.path.join(_CONFIGS, "mimo-v2.5.json")
#: full, window, window; dense, experts, experts
MIMO_LAYERS = 3
#: the fewest layers that hold every mixer kind of the flash decoder
P4_LAYERS = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _engine(kind):
    """An engine at the cell's widths over shapes alone: abstract
    weights, a pool of numpy zeros that is never placed."""
    if kind == "gpt2-large":
        from deeplearning4j_tpu.models.decoder import (DecoderConfig,
                                                       DecoderLM)
        model = DecoderLM(DecoderConfig(
            vocab_size=50257, n_layers=LAYERS, n_heads=20, d_model=1280,
            d_ff=5120, max_len=1024, eos_id=50257))
        params = jax.eval_shape(model.init)
        pool = KVBlockPool(LAYERS, 385, 16, 20, 64, dtype=jnp.bfloat16,
                           name="t-v5e-gpt2", device_arrays=False)
    elif kind == "phi4-mini-flash":
        from deeplearning4j_tpu.models.phi4_flash import (Phi4FlashConfig,
                                                          Phi4FlashLM)
        cfg = dict(json.load(open(_P4)), num_hidden_layers=P4_LAYERS)
        model = Phi4FlashLM(Phi4FlashConfig.from_published(
            cfg, max_len=cfg["max_len"], eos_id=cfg["vocab_size"]))
        params = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, jnp.bfloat16 if a.ndim == 2 and a.shape[0] > 16
                else a.dtype),
            jax.eval_shape(model.init))
        c = model.conf
        pool = KVBlockPool(model.kv_layers, 8193, 16, c.n_kv_heads,
                           c.head_dim, dtype=jnp.bfloat16, name="t-v5e-p4",
                           state=model.state_shapes(), state_slots=65,
                           device_arrays=False)
    elif kind == "mimo-v2.5":
        from deeplearning4j_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2LM
        cfg = dict(json.load(open(_MIMO)), num_hidden_layers=MIMO_LAYERS)
        model = MiMoV2LM(MiMoV2Config.from_published(
            cfg, max_len=cfg["max_len"], eos_id=cfg["vocab_size"]))
        params = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, jnp.bfloat16 if a.ndim >= 2 and a.shape[-1] != 256
                else a.dtype),
            jax.eval_shape(model.init))
        c = model.conf
        eng = cfg["engine"]
        pool = KVBlockPool(model.kv_layers, eng["kv_blocks"], 16,
                           c.n_kv_heads, c.head_dim, v_head_dim=c.v_head_dim,
                           dtype=jnp.bfloat16, name="t-v5e-mimo",
                           state=model.state_shapes(),
                           state_slots=eng["state_slots"],
                           device_arrays=False)
    else:
        from deeplearning4j_tpu.models.falcon_h1 import (FalconH1Config,
                                                         FalconH1LM)
        cfg = dict(json.load(open(_H1)), num_hidden_layers=LAYERS)
        model = FalconH1LM(FalconH1Config.from_published(
            cfg, max_len=cfg["max_len"], eos_id=cfg["vocab_size"]))
        params = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, jnp.bfloat16 if a.ndim == 2 else a.dtype),
            jax.eval_shape(model.init))
        c = model.conf
        pool = KVBlockPool(LAYERS, 3073, 16, c.n_kv_heads, c.head_dim,
                           dtype=jnp.bfloat16, name="t-v5e-h1",
                           state=model.state_shapes(), state_slots=33,
                           device_arrays=False)
    eng = DecodeEngine(model, params, pool, name=pool.name,
                       prompt_buckets=(PROMPT,), decode_buckets=(BUCKET,),
                       max_seq_len=1024, paged=True)
    return model, pool, eng


def _kernels(pool):
    """Mosaic calls in a decode step: the paged kernel in every layer
    that attends and a state kernel in every layer with a recurrent
    state."""
    if pool.window_bytes and pool.state_bytes:
        # 2 window, 1 full, 1 cross; 3 Mamba-1
        return 4 + pool.state["ssm"].shape[0]
    if pool.window_bytes:           # a full and two window layers; the
        return MIMO_LAYERS          # experts at 32 rows are XLA's products
    return LAYERS * (2 if pool.state else 1)


def _program(kind, program, one_chip):
    """The pool, the jitted commit or decode program and its
    arguments, every one a shape on the described chip."""
    model, pool, eng = _engine(kind)
    i32 = np.int32
    b = BUCKET
    if program == "commit":
        new = jax.eval_shape(
            model.prefill, eng.params, np.zeros((1, PROMPT), i32),
            np.ones((1,), i32))[1:]
        jit = eng._commit_jit()
        args = (pool.arrays, tuple(new),
                np.zeros((pool.blocks_for(PROMPT),), i32),
                *eng._state_arg(i32(0)))
    else:
        jit = eng._decode_jit()
        args = (eng.params, pool.arrays, np.zeros((b,), i32),
                np.zeros((b,), i32), np.zeros((b, eng.max_blocks), i32),
                np.zeros((2,), np.uint32), np.zeros((b,), np.float32),
                np.zeros((b,), i32),
                *eng._state_arg(np.zeros((b,), i32)))
    return pool, jit, jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), args)


@pytest.mark.parametrize("program", ["commit", "decode"])
@pytest.mark.parametrize("kind", ["gpt2-large", "falcon-h1-34b",
                                  "phi4-mini-flash", "mimo-v2.5"])
def test_the_pool_is_written_in_place_and_never_relaid(one_chip, kind,
                                                       program):
    pool, jit, args = _program(kind, program, one_chip)
    with mock.patch.object(kernel_select, "interpret_mode", lambda: False), \
            mock.patch.object(kernel_select, "platform", lambda: "tpu"):
        compiled = jit.lower(*args).compile()
    # every cache input is an output's buffer (the rings too; the
    # device pads the flash decoder's 65 x 3 convolution rows to whole
    # sublane tiles, 0.2 % of its cache)
    held = pool.pool_bytes + pool.state_bytes + pool.window_bytes
    alias = compiled.memory_analysis().alias_size_in_bytes
    assert held <= alias <= (1.005 * held if pool.window_bytes
                             and pool.state_bytes else held)
    text = compiled.as_text()
    shapes = []                 # the whole pool, one layer of it, the rings
    for a in [pool.k, pool.v] + [pool.state[k]
                                 for k in sorted(pool.window_kinds)]:
        shapes += [a.shape, a.shape[1:], (a.shape[0], a.shape[1] * a.shape[2])
                   + a.shape[3:]]
    dims = "|".join(re.escape(",".join(str(n) for n in shp))
                    for shp in shapes)
    moved = [m.group(0) for m in re.finditer(
        r"= bf16\[(?:%s)\]\S* (copy|transpose|slice|reshape|"
        r"dynamic-slice)\(" % dims, text)]
    assert not moved, moved[:4]
    if program == "decode":
        # the paged kernel a layer on the stacked pool (and the state
        # kernel a layer where the model has recurrent state)
        kernels = text.count("custom_call_target=\"tpu_custom_call\"")
        assert kernels == _kernels(pool)


def test_the_mamba1_prefill_scan_compiles_at_the_published_widths(one_chip):
    """The selective scan kernel for the described chip at the cell's
    largest prompt bucket: 16 states x 5120 channels resident, 8 tokens a
    grid step."""
    from deeplearning4j_tpu.ops import ssm_pallas as sp
    f32 = jnp.float32
    shape = lambda *s: jax.ShapeDtypeStruct(s, f32, sharding=one_chip)  # noqa: E731
    with mock.patch.object(kernel_select, "interpret_mode", lambda: False):
        compiled = jax.jit(sp.selective_scan_pallas).lower(
            shape(1, 512, 5120), shape(1, 512, 5120), shape(16, 5120),
            shape(1, 512, 16), shape(1, 512, 16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("gates, per_site", [
    ({}, 0), ({"fused_conv": "1", "fused_bn_bwd": "1"}, 4)],
    ids=["auto", "forced"])
def test_the_bn_kernels_are_in_the_train_step_only_when_forced(
        one_chip, gates, per_site):
    """The train step of a small ResNet for the described chip: on
    auto the ladder picks XLA's lowering at every BN site, as on the
    chip since PR 33 (PERF.md section 6), and the compiled step holds
    no Mosaic call; under ``=1`` it holds the four kernels of every
    site (statistics, normalize, backward sums, dx), and they
    compile."""
    from deeplearning4j_tpu.common.environment import Environment
    from deeplearning4j_tpu.models.zoo import ResNet50
    extra = Environment.get().extra
    extra.update(gates)
    try:
        net = ResNet50(num_classes=16, height=32, width=32,
                       compute_dtype="bfloat16",
                       STAGES=((1, 16), (1, 32))).init()
        net._build_train_step()
        args = (net.params, net.states, net.updater_states,
                [np.zeros((8, 32, 32, 3), np.float32)],
                [np.zeros((8, 16), np.float32)], None, None,
                jnp.asarray(0), jax.random.PRNGKey(0))
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), args)
        with mock.patch.object(kernel_select, "interpret_mode",
                               lambda: False), \
                mock.patch.object(kernel_select, "platform", lambda: "tpu"):
            lowered = net._train_step.lower(*args)
            compiled = lowered.compile()
    finally:
        for k in gates:
            extra.pop(k, None)
    sites = 9                       # the stem, 2 x 3 in blocks, 2 shortcuts
    assert lowered.as_text().count("tpu_custom_call") == per_site * sites
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == per_site * sites
