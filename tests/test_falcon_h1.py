"""Falcon-H1 (parallel Mamba-2 + grouped-query attention) behind the
serving contract: the system against the plain reference on seeded
weights, the chunked scan, the state-update kernel, grouped-query paged
attention, the fourteen multipliers, and the engine's state slots.

Tiny widths with every published ratio kept: 5 query heads a KV head,
2 state groups, a convolution of width 4.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.models.falcon_h1 import program_layout
from chipbench.reference import falcon_h1 as ref
from deeplearning4j_tpu.models.falcon_h1 import FalconH1Config, FalconH1LM
from deeplearning4j_tpu.ops import attention_pallas as ap
from deeplearning4j_tpu.ops import ssm_pallas as sp
from deeplearning4j_tpu.serving.generative import DecodeEngine
from deeplearning4j_tpu.serving.kvcache import KVBlockPool

CFG = {"hidden_size": 40, "num_attention_heads": 10,
       "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 64,
       "vocab_size": 96, "num_hidden_layers": 2, "mamba_d_ssm": 32,
       "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
       "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 128,
       "rms_norm_eps": 1e-5, "rope_theta": 100000000000,
       "embedding_multiplier": 5.65, "lm_head_multiplier": 0.5,
       "attention_in_multiplier": 1.1, "attention_out_multiplier": 0.9,
       "key_multiplier": 0.7, "ssm_in_multiplier": 2.5,
       "ssm_out_multiplier": 0.6,
       "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.35],
       "mlp_multipliers": [0.9, 0.8], "init_std": 0.3}
MULTIPLIERS = (["embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "key_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier"]
               + [f"ssm_multipliers.{i}" for i in range(5)]
               + [f"mlp_multipliers.{i}" for i in range(2)])
T = 310
TOKENS = np.random.RandomState(0).randint(0, 96, T)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _system(cfg=CFG, seed=5, widen=True, **kw):
    """The model class over the reference's seeded weights; ``widen``
    holds them in float32, so that no product rounds its operands."""
    weights = ref.make_params(cfg, seed)
    params = program_layout(weights)
    model = FalconH1LM(FalconH1Config.from_published(cfg, max_len=512,
                                                     **kw))
    return model, (_f32(params) if widen else params), weights


def _reference_logits(cfg, weights, tokens):
    with jax.default_matmul_precision("highest"):
        return ref.forward(cfg, weights, jnp.asarray(tokens))


@pytest.fixture(scope="module")
def want():
    model, params, weights = _system()
    return model, params, _reference_logits(CFG, weights, TOKENS)


# -- the system's forward against the plain reference ---------------------
def test_forward_matches_the_plain_reference(want):
    model, params, logits = want
    got = model.forward(params, TOKENS[None])[0]
    assert got.shape == (T, 96)
    np.testing.assert_allclose(got, logits, atol=2e-5)


def test_forward_with_bfloat16_weights_rounds_and_no_more():
    model, params, weights = _system(widen=False)
    assert params["layer_0"]["gate"].dtype == jnp.bfloat16
    got = model.forward(params, TOKENS[None, :64])[0]
    logits = _reference_logits(CFG, weights, TOKENS[:64])
    err = float(jnp.max(jnp.abs(got - logits)))
    assert 0 < err < 0.02 * float(jnp.max(jnp.abs(logits)))


def _commit(model, pool, k, v, ssm, conv, length, table, slot):
    """What the engine's commit program does, by hand."""
    bs = pool.block_size
    idx = np.arange(k.shape[2])
    rows = np.where(idx < length,
                    np.asarray(table)[np.minimum(idx // bs, len(table) - 1)]
                    * bs + idx % bs, 0)
    kp, vp, s_, c_ = pool.arrays
    flat = (kp.shape[0], -1, kp.shape[3])
    kp = kp.reshape(flat).at[:, rows].set(
        k[:, 0].reshape(flat)).reshape(kp.shape)
    vp = vp.reshape(flat).at[:, rows].set(
        v[:, 0].reshape(flat)).reshape(vp.shape)
    return (kp, vp, s_.at[:, slot].set(ssm[:, 0]),
            c_.at[:, slot].set(conv[:, 0]))


@pytest.mark.parametrize("length,paged", [
    (1, False), (127, False), (128, False), (129, False), (300, False),
    (129, True)])
def test_prefill_then_decode_through_the_cache(want, length, paged):
    """A prompt in a padded bucket, then one token a step through the
    paged K/V and the state slot: the logits at every position from the
    prompt's last on are the full forward's (the Pallas kernel's
    products take bfloat16 operands, the dense gather's float32)."""
    model, params, logits = want
    pool = KVBlockPool(2, 64, 16, 2, 8, state=model.state_shapes(),
                       state_slots=4, name="t-h1")
    tokens = np.zeros((1, 512), np.int32)
    tokens[0, :length] = TOKENS[:length]
    last, k, v, ssm, conv = jax.jit(model.prefill)(
        params, tokens, np.asarray([length], np.int32))
    np.testing.assert_allclose(last[0], logits[length - 1], atol=2e-5)
    table = list(range(1, 22))
    cache = _commit(model, pool, k, v, ssm, conv, length, table, slot=3)
    step = jax.jit(model.decode_step, static_argnames=("paged",))
    tables = np.zeros((2, 32), np.int32)
    tables[0, :len(table)] = table
    for pos in range(length, min(length + 10, T)):
        # row 0 is the sequence, row 1 a dead row on the scratch block
        # and the scratch slot
        out, *cache = step(params, np.asarray([TOKENS[pos], 0], np.int32),
                           np.asarray([pos, 0], np.int32), *cache, tables,
                           np.asarray([3, 0], np.int32), paged=paged)
        np.testing.assert_allclose(out[0], logits[pos],
                                   atol=8e-2 if paged else 5e-5)


def test_prefill_masks_the_padding_out_of_the_state(want):
    """State and tail at ``length - 1`` of a padded bucket are those of
    the unpadded prompt."""
    model, params, _ = want
    tokens = np.zeros((1, 256), np.int32)
    tokens[0, :200] = TOKENS[:200]
    _, _, _, ssm, conv = model.prefill(params, tokens,
                                       np.asarray([200], np.int32))
    _, _, _, ssm0, conv0 = model.prefill(params, TOKENS[None, :200],
                                         np.asarray([200], np.int32))
    np.testing.assert_allclose(ssm, ssm0, atol=2e-5)
    np.testing.assert_allclose(conv, conv0, atol=1e-5)


# -- the chunked scan ------------------------------------------------------
@pytest.mark.parametrize("initial", [False, True],
                         ids=["from_zero", "from_a_state"])
def test_chunked_scan_is_the_token_by_token_recurrence(initial):
    rs = np.random.RandomState(1)
    bt, t, h, p, g, n = 2, 37, 4, 8, 2, 16
    x = rs.randn(bt, t, h, p).astype(np.float32)
    dt = (rs.rand(bt, t, h) * 0.5).astype(np.float32)
    a = -(rs.rand(h) * 4 + 0.1).astype(np.float32)
    b = rs.randn(bt, t, g, n).astype(np.float32)
    c = rs.randn(bt, t, g, n).astype(np.float32)
    s0 = rs.randn(bt, h, p, n).astype(np.float32) if initial else None
    y, final = sp.ssd_chunked_scan(x, dt, a, b, c, chunk=8,
                                   initial_state=s0)
    s = np.zeros((bt, h, p, n), np.float32) if s0 is None else s0
    for i in range(t):
        bh, ch = (np.repeat(m[:, i], h // g, 1) for m in (b, c))
        s = (np.exp(dt[:, i] * a)[..., None, None] * s
             + (dt[:, i][..., None] * x[:, i])[..., None] * bh[:, :, None])
        np.testing.assert_allclose(
            y[:, i], np.einsum("zhpn,zhn->zhp", s, ch), atol=2e-4)
    np.testing.assert_allclose(final, s, atol=1e-5)


# -- the state-update kernel -------------------------------------------------
def test_state_update_kernel_matches_its_jnp_form_in_place():
    rs = np.random.RandomState(2)
    layers, slots_n, h, p, n, g, rows = 3, 6, 4, 8, 16, 2, 4
    state = jnp.asarray(rs.randn(layers, slots_n, h, p, n), jnp.float32)
    slots = jnp.asarray([4, 1, 0, 0], jnp.int32)     # two live, two dead
    x = jnp.asarray(rs.randn(rows, h, p), jnp.float32)
    dt = jnp.asarray(rs.rand(rows, h), jnp.float32)
    decay = jnp.exp(-2.0 * dt)
    b = jnp.asarray(rs.randn(rows, g, n), jnp.float32)
    c = jnp.asarray(rs.randn(rows, g, n), jnp.float32)
    want_s, want_y = sp.ssm_state_update_reference(state, 1, slots, x, dt,
                                                   decay, b, c)
    got_s, got_y = sp.ssm_state_update_pallas(state, 1, slots, x, dt,
                                              decay, b, c)
    np.testing.assert_allclose(got_y[:2], want_y[:2], atol=1e-5)
    np.testing.assert_allclose(got_s[1, [1, 4]], want_s[1, [1, 4]],
                               atol=1e-6)
    assert not np.any(np.asarray(got_y[2:]))        # dead rows: nothing
    # every slot that no live row named is bit-identical, in every layer
    assert np.array_equal(got_s[0], state[0])
    assert np.array_equal(got_s[2], state[2])
    assert np.array_equal(got_s[1, [2, 3, 5]], state[1, [2, 3, 5]])


def test_state_update_ladder():
    assert sp.select_ssm_backend(128, 256, platform="tpu")[0] == "kernel"
    assert sp.select_ssm_backend(128, 256, platform="cpu")[0] == "dense"
    backend, why = sp.select_ssm_backend(8, 16, platform="tpu")
    assert backend == "dense" and "tiles" in why    # structural gate
    assert sp.select_ssm_backend(128, 256, platform="tpu",
                                 override=False)[0] == "dense"
    assert sp.select_ssm_backend(128, 256, platform="cpu",
                                 override=True)[0] == "kernel"


# -- grouped-query paged attention -------------------------------------------
def _paged_case(h_q, h_kv, d=16, seed=3):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(3, h_q, d), jnp.float32)
    # one layer of a pool: [layers, blocks, block, h_kv * d]
    kp = jnp.asarray(rs.randn(1, 12, 8, h_kv * d), jnp.float32)
    vp = jnp.asarray(rs.randn(1, 12, 8, h_kv * d), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [0, 0, 0, 0]],
                         jnp.int32)
    return q, kp, vp, tables, jnp.asarray([20, 9, 1], jnp.int32)


def test_paged_attention_with_five_query_heads_a_kv_head():
    q, kp, vp, tables, lens = _paged_case(10, 2)
    want = ap.paged_attention_reference(q, kp, vp, tables, lens)
    # the grouped reference is plain attention of head i on KV head i // 5
    for row in range(2):
        k = kp[0, tables[row]].reshape(-1, 2, 16)[:lens[row]]
        v = vp[0, tables[row]].reshape(-1, 2, 16)[:lens[row]]
        for i in range(10):
            w = jax.nn.softmax((k[:, i // 5] @ q[row, i]) / 4.0)
            np.testing.assert_allclose(want[row, i], w @ v[:, i // 5],
                                       atol=1e-5)
    got = ap.paged_decode_attention(q, kp, vp, tables, lens)
    np.testing.assert_allclose(got, want, atol=2e-2)     # bf16 products


def test_paged_attention_with_as_many_kv_heads_is_unchanged():
    """``g = 1`` takes the branch it took before: same operands
    ``[b, 1, h * d]`` into the same kernel, same result."""
    q, kp, vp, tables, lens = _paged_case(4, 4)
    want = ap.paged_attention_reference(q, kp, vp, tables, lens)
    got = ap.paged_decode_attention(q, kp, vp, tables, lens)
    np.testing.assert_allclose(got, want, atol=2e-2)
    text = str(jax.make_jaxpr(ap.paged_decode_attention)(q, kp, vp, tables,
                                                         lens))
    assert "f32[3,1,64]" in text and "transpose" not in text


# -- the fourteen multipliers --------------------------------------------------
def _with(cfg, name, factor):
    cfg = dict(cfg)
    if "." in name:
        key, i = name.split(".")
        cfg[key] = list(cfg[key])
        cfg[key][int(i)] *= factor
    else:
        cfg[name] *= factor
    return cfg


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_is_applied_exactly_once(want, name):
    """One multiplier moved by half again: the system still agrees with
    the reference (which applies it once, by the equations), and both
    have moved."""
    _, _, base = want
    cfg = _with(CFG, name, 1.5)
    model, params, weights = _system(cfg)
    logits = _reference_logits(cfg, weights, TOKENS[:48])
    got = model.forward(params, TOKENS[None, :48])[0]
    np.testing.assert_allclose(got, logits, atol=2e-5)
    assert float(jnp.max(jnp.abs(logits - base[:48]))) > 1e-3


# -- the engine: state slots beside the KV pool ---------------------------------
def _engine(state_slots=5, decode_buckets=(4,), **kw):
    model = FalconH1LM(FalconH1Config(eos_id=96))
    params = model.init()
    pool = KVBlockPool(2, 64, 8, 2, 8, name="t-h1e",
                       state=model.state_shapes(), state_slots=state_slots)
    eng = DecodeEngine(model, params, pool, name="t-h1e",
                       prompt_buckets=(16, 32),
                       decode_buckets=decode_buckets, max_seq_len=64, **kw)
    eng.warmup()
    return model, params, pool, eng


def test_churn_serves_the_tokens_each_sequence_gets_alone():
    """Sequences join and leave at different steps; each is served the
    greedy tokens it gets with the engine to itself and by full
    re-forward; slots are reused; nothing compiles after warm-up."""
    model, params, pool, eng = _engine()
    rs = np.random.RandomState(4)
    cases = [(rs.randint(2, 90, n), m) for n, m in
             ((3, 12), (9, 5), (14, 9), (1, 7), (20, 11), (6, 4), (11, 8))]
    alone = [list(eng.submit(p, m)) for p, m in cases]
    assert alone[1] == model.reference_decode(params, cases[1][0], 5)
    got, slots_seen = {}, set()

    def client(i):
        stream = eng.submit(*cases[i])
        slots_seen.add(pool.slot(stream.seq_id))
        got[i] = list(stream)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(cases))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert [got[i] for i in range(len(cases))] == alone
    assert eng.retraces_since_warmup() == 0
    # 4 usable slots served 7 sequences, and all came back
    assert slots_seen <= {0, 1, 2, 3, 4}
    assert pool.free_slots == 4 and pool.free_blocks == pool.usable_blocks
    eng.shutdown()


def test_a_request_that_finds_no_slot_waits_and_is_served():
    """Two usable slots, five requests at once: none is refused, the
    ones without a slot wait in the engine's queue for a retirement."""
    model, params, pool, eng = _engine(state_slots=3, decode_buckets=(2,))
    rs = np.random.RandomState(6)
    prompts = [rs.randint(2, 90, 5) for _ in range(5)]
    streams = [eng.submit(p, 6) for p in prompts]
    served = [s.tokens(timeout=120) for s in streams]
    assert all(len(t) == 6 and s.reason == "max_tokens"
               for t, s in zip(served, streams))
    assert served[4] == model.reference_decode(params, prompts[4], 6)
    assert pool.free_slots == 2 and eng.retraces_since_warmup() == 0
    eng.shutdown()


def test_slot_accounting():
    pool = KVBlockPool(2, 8, 4, 2, 8, device_arrays=False, name="t-slots",
                       state={"ssm": ((4, 8, 16), np.float32),
                              "conv": ((3, 64), np.float32)},
                       state_slots=3)
    assert pool.state["ssm"].shape == (2, 3, 4, 8, 16)
    assert pool.usable_slots == pool.free_slots == 2
    assert len(pool.arrays) == 4 and pool.arrays[2] is pool.state["ssm"]
    a, b = pool.alloc_slot("a"), pool.alloc_slot("b")
    assert {a, b} == {1, 2} and pool.slot("a") == a
    assert pool.alloc_slot("c") is None         # exhausted: waits, no raise
    assert pool.slot("c") == 0                  # ... on the scratch slot
    with pytest.raises(ValueError):
        pool.alloc_slot("a")
    pool.alloc("a", 5)
    assert pool.free("a") == 2 and pool.free("a") == 0      # blocks; idempotent
    assert pool.alloc_slot("c") == a and pool.free_slots == 0
    report = pool.report()["state"]
    assert report["slots"] == {"free": 0, "live": 2, "reserved": 1,
                               "total": 3}
    assert report["bytes"] == pool.state_bytes == 2 * 3 * (4 * 8 * 16 + 3 * 64) * 4
    with pytest.raises(ValueError):
        KVBlockPool(2, 8, 4, 2, 8, device_arrays=False,
                    state={"ssm": ((1,), np.float32)}, state_slots=1)
    plain = KVBlockPool(2, 8, 4, 2, 8, device_arrays=False)
    assert plain.state == {} and len(plain.arrays) == 2
    assert plain.alloc_slot("a") is None and "state" not in plain.report()


def test_the_spans_and_gauges_carry_the_state_counts():
    from deeplearning4j_tpu.common import telemetry
    model, params, pool, eng = _engine()
    list(eng.submit(np.asarray([3, 4, 5]), 4))
    eng.shutdown()
    events = {e["name"]: e["args"] for e in telemetry.trace_events()
              if e.get("ph") == "X" and e["args"].get("model", "t-h1e") == "t-h1e"}
    step, prefill = events["generate.decode_step"], events["generate.prefill"]
    # how many slots are live is the pool's to say (its report and its
    # gauges), not the step span's: with one slot a row it repeated `live`
    assert step["live"] == 1 and "state_live" not in step \
        and "state_slots" not in step
    assert pool.report()["state"]["slots"] == {
        "free": 4, "live": 0, "reserved": 1, "total": 5}
    assert prefill["state_slot"] in (1, 2, 3, 4)
    text = telemetry.MetricsRegistry.get().render_prometheus()
    assert pool.state_bytes == 2 * 5 * (4 * 8 * 16 + 3 * 96) * 4
    assert 'dl4j_state_pool_slots{pool="t-h1e",state="free"} 4' in text
    assert f'dl4j_state_pool_bytes{{pool="t-h1e"}} {pool.state_bytes}' in text


def test_a_joiner_takes_the_row_slot_and_blocks_of_a_retired_sequence(
        joins_a_retired_row):
    """The state slot a cancelled sequence held goes to the request
    admitted behind the steps that still update it: their writes land
    before its commit, and its state is its own prompt's."""
    model, params, pool, eng = _engine(state_slots=3, decode_buckets=(2,))
    prefill = joins_a_retired_row(model, params, pool, eng)
    assert prefill["tokens"] == 9 and prefill["bucket"] == 16
