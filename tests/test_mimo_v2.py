"""MiMo-V2 (full and sliding-window attention with a learned sink, keys
wider than values, sparse experts of which this chip holds a share)
behind the serving contract: the system against the plain reference on
seeded weights, prefill then decode through pool and rings while the
rings wrap, the expert layer's two lowerings against its definition,
that the shares of a layer add up to the uncut layer, that nothing is
dropped under a skewed router, the pool's lanes and gauges, and the
routing counts on the engine's spans.

Tiny widths with the published shape kept: K heads of 24 and V heads of
16, 8 query heads on 2 KV heads (full) and 4 (window), rotary positions
on the first 8 dimensions, a router 16 wide with 4 experts a token of
which experts 4-7 are held; one full and three window layers, a window
of 8 and contexts to 40, so every ring wraps several times and every
prompt past 8 wraps it in the prefill.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.models.mimo_v2 import program_layout
from chipbench.reference import mimo_v2 as ref
from deeplearning4j_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2LM
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.serving.generative import DecodeEngine
from deeplearning4j_tpu.serving.kvcache import KVBlockPool

CFG = {"hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
       "swa_num_key_value_heads": 4, "head_dim": 24, "v_head_dim": 16,
       "intermediate_size": 128, "moe_intermediate_size": 32,
       "router_experts": 16, "n_routed_experts": 4, "experts_first": 4,
       "num_experts_per_tok": 4, "sliding_window": 8,
       "hybrid_layer_pattern": [0, 1, 1, 1], "moe_layer_freq": [0, 1, 1, 1],
       "num_hidden_layers": 4, "rope_theta": 1e7, "swa_rope_theta": 1e4,
       "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
       "layernorm_epsilon": 1e-5, "vocab_size": 96, "init_std": 0.3}
W = CFG["sliding_window"]
T = 40
TOKENS = np.random.RandomState(0).randint(0, 96, T)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _system(cfg=CFG, seed=5, widen=True, **kw):
    """The model class over the reference's seeded weights; ``widen``
    holds them in float32, so that no product rounds its operands."""
    weights = ref.make_params(cfg, seed)
    params = program_layout(weights)
    model = MiMoV2LM(MiMoV2Config.from_published(cfg, max_len=512, **kw))
    return model, (_f32(params) if widen else params), weights


@pytest.fixture(scope="module")
def want():
    model, params, weights = _system()
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(CFG, weights, jnp.asarray(TOKENS))
    return model, params, logits


def test_the_layer_kinds_and_the_share():
    c = MiMoV2Config.from_published(CFG)
    assert c.kinds == ("full", "window", "window", "window")
    assert c.moe_pattern == (0, 1, 1, 1) and c.rotary_dim == 8
    assert (c.n_experts, c.experts_first, c.experts_held) == (16, 4, 4)
    model = MiMoV2LM(c)
    assert model.kv_layers == 1
    assert model.cache_reads() == {
        "kv_readers": 1, "window_layers": 3, "window": 8,
        "kv_token_bytes": 2 * 2 * 40, "window_token_bytes": 2 * 4 * 40}
    with pytest.raises(ValueError):
        MiMoV2Config(experts_first=14, experts_held=4)
    with pytest.raises(ValueError):
        MiMoV2Config(layer_pattern=(0, 1), moe_pattern=(0,))


# -- the system's forward against the plain reference ---------------------
def test_forward_matches_the_plain_reference(want):
    """Float32 weights on both sides: what is left is the order of the
    sums (logits to 9, so 5e-5 is five parts in a million)."""
    model, params, logits = want
    got = model.forward(params, TOKENS[None])[0]
    assert got.shape == (T, 96)
    np.testing.assert_allclose(got, logits, atol=5e-5)


def test_forward_with_bfloat16_weights_rounds_and_no_more():
    """The weights as the benchmark holds them: the products round
    their left operand to bfloat16, a few parts in a thousand of the
    logits' range at most positions. A position where that rounding
    flips a near tie at rank ``top_k`` of the router runs another
    expert and reads far off: few, and the reason the benchmark's limit
    is a mean square over every served token."""
    model, params, weights = _system(widen=False)
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(CFG, weights, jnp.asarray(TOKENS))
    got = model.forward(params, TOKENS[None])[0]
    err = np.max(np.abs(np.asarray(got - logits)), axis=-1)     # [T]
    near = 0.03 * float(jnp.max(jnp.abs(logits)))
    assert np.median(err) > 0 and np.mean(err < near) >= 0.8


def test_prefill_bands_the_window_and_equals_forward(want):
    """The window layers as a band, the head on the last valid position
    of a padded bucket: the full forward's logits at every length
    across the window's edge, and the routing counted over the valid
    rows only."""
    model, params, logits = want
    for length in (1, W - 1, W, W + 1, 3 * W + 5):
        tokens = np.zeros((1, 32), np.int32)
        tokens[0, :length] = TOKENS[:length]
        last, *_, counts = jax.jit(model.prefill)(
            params, tokens, np.asarray([length], np.int32))
        np.testing.assert_allclose(last[0], logits[length - 1], atol=5e-5)
        named = dict(zip(model.step_counts, np.asarray(counts)))
        assert named["moe_rows_all"] == length * 4 * 3
        assert named["moe_experts_held"] == 4 * 3
        assert 0 < named["moe_rows"] <= named["moe_rows_all"]
        assert named["moe_rows_max"] * 4 >= named["moe_rows"] / 3


def _commit(pool, new, length, table, slot):
    """What the engine's commit program does, by hand."""
    k, v, *state = new
    bs = pool.block_size
    idx = np.arange(k.shape[2])
    rows = np.where(idx < length,
                    np.asarray(table)[np.minimum(idx // bs, len(table) - 1)]
                    * bs + idx % bs, 0)
    kp, vp, *slots = pool.arrays

    def put(p, a):
        flat = (p.shape[0], -1, p.shape[3])
        return p.reshape(flat).at[:, rows].set(
            a[:, 0].reshape(a.shape[0], a.shape[2], -1)
            .astype(p.dtype)).reshape(p.shape)
    slots = [a.at[:, slot].set(n[:, 0].reshape(a.shape[:1] + a.shape[2:]))
             for a, n in zip(slots, state)]
    return (put(kp, k), put(vp, v), *slots)


@pytest.mark.parametrize("length,paged", [
    (1, False), (W - 1, False), (W + 1, False), (13, False), (29, False),
    (13, True)])
def test_prefill_then_decode_through_pool_and_rings(want, length, paged):
    """A prompt in a padded bucket (past the window: the prefill wraps
    the rings), then one token a step through the full layer's pool and
    the rings: the logits at every position to the end are the full
    forward's. The dense gather's products are float32: 5e-5 at every
    position. The Pallas kernel's take bfloat16 operands: at these
    draws that moves the logits (to 9) by 0.09 at the median position,
    and where it flips a near tie of the router another expert runs
    and a position reads to 0.9, so the kernel is held by the median
    (0.15) and four positions in five within 0.25; with the sink left
    out of the kernel the median reads 0.32."""
    model, params, logits = want
    pool = KVBlockPool(1, 32, 4, 2, 24, v_head_dim=16,
                       state=model.state_shapes(), state_slots=4,
                       name="t-mimo")
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :length] = TOKENS[:length]
    last, *new, _ = jax.jit(model.prefill)(
        params, tokens, np.asarray([length], np.int32))
    table = list(range(3, 14))
    cache = _commit(pool, new, length, table, slot=3)
    step = jax.jit(model.decode_step, static_argnames=("paged",))
    tables = np.zeros((2, 16), np.int32)
    tables[0, :len(table)] = table
    errs = []
    for pos in range(length, T):
        # row 0 is the sequence, row 1 a dead row on the scratch block
        # and slot, which is routed to no expert
        out, *cache, counts = step(
            params, np.asarray([TOKENS[pos], 0], np.int32),
            np.asarray([pos, 0], np.int32), *cache, tables,
            np.asarray([3, 0], np.int32), paged=paged)
        errs.append(float(np.max(np.abs(np.asarray(out[0] - logits[pos])))))
        assert int(counts[1]) == 4 * 3          # the live row's pairs only
    errs = np.asarray(errs)
    if paged:
        assert np.median(errs) < 0.15 and np.mean(errs < 0.25) >= 0.8
    else:
        assert errs.max() < 5e-5


def test_prefill_wraps_the_rings(want):
    """A ring holds position ``p`` of the prompt's last ``window`` at
    ``p mod window``, from a padded bucket as from the bare prompt."""
    model, params, _ = want
    n = 21
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :n] = TOKENS[:n]
    padded = jax.jit(model.prefill)(params, tokens, np.asarray([n], np.int32))
    exact = model.prefill(params, TOKENS[None, :n], np.asarray([n], np.int32))
    for a, b in zip(padded[3:5], exact[3:5]):
        np.testing.assert_allclose(a, b, atol=1e-5)
    ks = model._body(params, jnp.asarray(TOKENS[None, :n]),
                     jnp.asarray([n], jnp.int32))[1]
    ring = np.asarray(padded[3])                    # [3, 1, W, 96]
    assert ring.shape == (3, 1, W, 4 * 24)
    assert padded[4].shape == (3, 1, W, 4 * 16)
    for p in range(n - W, n):
        np.testing.assert_allclose(ring[0, 0, p % W],
                                   np.asarray(ks[1])[0, p].reshape(-1),
                                   atol=1e-5)


# -- the expert layer ---------------------------------------------------------
def _layer(seed=0, n=40, d=64, f=32, experts=32, count=4):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (n, d)),
            jax.random.normal(k[1], (d, experts)) * 0.3,
            jax.random.normal(k[2], (experts,)) * 0.02,
            tuple(jax.random.normal(k[3 + i], s) * 0.2 for i, s in
                  enumerate([(count, d, f), (count, d, f), (count, f, d)])))


@pytest.mark.parametrize("rung", moe.RUNGS)
def test_a_rung_of_the_expert_layer_is_the_definition(rung):
    h, router, bias, experts = _layer()
    want = moe.expert_reference(h, router, bias, experts, 8, 4, top_k=4)
    got, counts = jax.jit(lambda *a: moe.held_expert_layer(
        *a, 8, 4, top_k=4, rung=rung))(h, router, bias, experts)
    np.testing.assert_allclose(got, want, atol=2e-6)
    idx, _ = moe.route(h, router, bias, 4)
    held = np.asarray((idx >= 8) & (idx < 12))
    sizes = [int(np.sum(np.asarray(idx) == e)) for e in range(8, 12)]
    assert list(np.asarray(counts)) == [held.sum(), 40 * 4,
                                        sum(s > 0 for s in sizes), 4,
                                        max(sizes)]


@pytest.mark.parametrize("rung", moe.RUNGS)
def test_a_skewed_router_drops_nothing(rung):
    """Every row's first two experts are two of the held ones: 80 pairs
    on two experts, twice the rows a trip of the grouped loop takes,
    and the result is still the definition's."""
    h, _, _, experts = _layer()
    h = jnp.abs(h)
    router = jnp.zeros((64, 32)).at[:, 8].set(1.0).at[:, 9].set(0.9)
    bias = jnp.zeros((32,)).at[8].set(5.0).at[9].set(4.0)
    want = moe.expert_reference(h, router, bias, experts, 8, 4, top_k=4)
    got, counts = jax.jit(lambda *a: moe.held_expert_layer(
        *a, 8, 4, top_k=4, rung=rung))(h, router, bias, experts)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(got, want, atol=5e-6)
    assert list(np.asarray(counts))[:3] == [80, 160, 2]
    assert int(counts[4]) == 40


def test_rows_marked_dead_are_routed_nowhere():
    h, router, bias, experts = _layer()
    valid = jnp.arange(40) % 3 != 0
    for rung in moe.RUNGS:
        got, counts = moe.held_expert_layer(h, router, bias, experts, 8, 4,
                                            top_k=4, valid=valid, rung=rung)
        assert float(jnp.max(jnp.abs(got[::3]))) == 0.0
        assert int(counts[1]) == int(valid.sum()) * 4


def test_an_unknown_rung_is_refused():
    h, router, bias, experts = _layer()
    with pytest.raises(ValueError, match="rung"):
        moe.held_expert_layer(h, router, bias, experts, 8, 4, top_k=4,
                              rung="ragged")


def test_off_the_tpu_a_prompts_rows_take_the_dense_rung():
    """Past ``DENSE_MAX_ROWS`` the auto rung is the Pallas product on
    the TPU only: here the layer lowers without a Mosaic call and is the
    definition still."""
    h, router, bias, experts = _layer(n=moe.DENSE_MAX_ROWS + 72)
    fn = jax.jit(lambda *a: moe.held_expert_layer(*a, 8, 4, top_k=4)[0])
    assert "pallas" not in str(jax.make_jaxpr(fn)(h, router, bias, experts))
    want = moe.expert_reference(h, router, bias, experts, 8, 4, top_k=4)
    np.testing.assert_allclose(fn(h, router, bias, experts), want, atol=2e-6)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The parts of the result that the four chips of a 4-way
    expert-parallel layer compute, each over its own 4 of the 16
    experts, sum to what the plain reference gives for the whole
    layer."""
    whole = dict(CFG, n_routed_experts=16, experts_first=0)
    p = _f32(ref.generate_layer(whole, 1, ref.key_of(3)))
    h = jax.random.normal(jax.random.PRNGKey(1), (24, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(whole, p, h)
    total, rows = 0.0, 0
    for first in (0, 4, 8, 12):
        held = tuple(p[k][first:first + 4]
                     for k in ("e_gate", "e_up", "e_down"))
        part, counts = moe.held_expert_layer(
            h, p["router"], p["bias"], held, first, 4, top_k=4)
        assert float(jnp.max(jnp.abs(part))) > 0
        total, rows = total + part, rows + int(counts[0])
        # and the reference cut to that share is that part
        cut = dict(whole, n_routed_experts=4, experts_first=first)
        with jax.default_matmul_precision("highest"):
            mine = ref.experts(cut, dict(p, e_gate=held[0], e_up=held[1],
                                         e_down=held[2]), h)
        np.testing.assert_allclose(part, mine, atol=2e-5)
    assert rows == 24 * 4                       # every pair on one chip
    np.testing.assert_allclose(total, want, atol=3e-5)


def test_the_ladder_picks_from_rows_and_platform():
    pick = moe.select_moe_backend
    assert pick(128, 16, platform="tpu")[0] == "dense"
    assert pick(2048, 16, platform="tpu")[0] == "gmm"
    assert pick(128, 16, platform="cpu")[0] == "dense"
    assert pick(2048, 16, platform="cpu")[0] == "dense"
    assert pick(128, 16, platform="cpu", override=True)[0] == "gmm"
    assert pick(2048, 16, platform="tpu", override=False)[0] == "dense"
    assert pick(256, 16, platform="tpu")[0] == "gmm"
    assert "PERF.md" in pick(2048, 16, platform="tpu")[1]
    assert "PERF.md" in pick(128, 16, platform="tpu")[1]


# -- pool and engine ----------------------------------------------------------
def _engine(state_slots=5, decode_buckets=(4,), **kw):
    model = MiMoV2LM(MiMoV2Config(eos_id=96))
    params = model.init()
    c = model.conf
    pool = KVBlockPool(model.kv_layers, 64, 4, c.n_kv_heads, c.head_dim,
                       v_head_dim=c.v_head_dim, name="t-mimo-e",
                       state=model.state_shapes(), state_slots=state_slots)
    eng = DecodeEngine(model, params, pool, name="t-mimo-e",
                       prompt_buckets=(16, 32),
                       decode_buckets=decode_buckets, max_seq_len=64, **kw)
    eng.warmup()
    return model, params, pool, eng


def test_the_pools_lanes_and_gauges_are_the_arrays_bytes():
    """K 2 x 24 lanes a token and V 2 x 16, rings of 4 x 24 and 4 x 16:
    each gauge and report counts exactly its arrays."""
    from deeplearning4j_tpu.common import telemetry
    model, params, pool, eng = _engine()
    assert pool.k.shape == (1, 64, 4, 48) and pool.v.shape == (1, 64, 4, 32)
    assert pool.state["ring_k"].shape == (3, 5, 2, 4, 96)
    assert pool.state["ring_v"].shape == (3, 5, 2, 4, 64)
    assert pool.pool_bytes == pool.k.nbytes + pool.v.nbytes \
        == 64 * 4 * (48 + 32) * 4
    assert pool.window_bytes == 3 * 5 * W * (96 + 64) * 4
    assert pool.state_bytes == 0
    report = pool.report()
    assert report["bytes"] == pool.pool_bytes
    assert report["layout"] == [1, 64, 4, 48]
    assert report["layout_v"] == [1, 64, 4, 32]
    assert report["window"]["bytes"] == pool.window_bytes
    eng.shutdown()
    text = telemetry.MetricsRegistry.get().render_prometheus()
    assert f'dl4j_kv_pool_bytes{{pool="t-mimo-e"}} {pool.pool_bytes}' in text
    assert f'dl4j_window_pool_bytes{{pool="t-mimo-e"}} ' \
           f'{pool.window_bytes}' in text
    same = KVBlockPool(1, 8, 4, 2, 24, device_arrays=False)
    assert same.v.shape == same.k.shape and "layout_v" not in same.report()


def test_churn_serves_the_tokens_each_sequence_gets_alone():
    """Sequences join and leave at different steps, prompts and
    contexts pass the window; each is served the greedy tokens it gets
    with the engine to itself and by full re-forward; nothing compiles
    after warm-up."""
    model, params, pool, eng = _engine()
    rs = np.random.RandomState(4)
    cases = [(rs.randint(2, 90, n), m) for n, m in
             ((3, 30), (9, 5), (14, 26), (1, 7), (20, 11), (6, 34), (11, 8))]
    alone = [list(eng.submit(p, m)) for p, m in cases]
    for i in (0, 2, 4):
        assert alone[i] == model.reference_decode(params, *cases[i])
    got = {}

    def client(i):
        got[i] = list(eng.submit(*cases[i]))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(cases))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300)
    assert [got[i] for i in range(len(cases))] == alone
    assert eng.retraces_since_warmup() == 0
    assert pool.free_slots == 4 and pool.free_blocks == pool.usable_blocks
    eng.shutdown()


def test_the_engine_through_the_paged_kernel():
    """The same through the Pallas kernel (interpret mode): the sink,
    K and V of different widths, rings and pool read by one kernel."""
    model, params, pool, eng = _engine(paged=True)
    prompt = np.random.RandomState(8).randint(2, 90, 11)
    got = list(eng.submit(prompt, 14))
    logits = model.forward(params, np.asarray([list(prompt) + got]))[0]
    at = np.arange(len(prompt) - 1, len(prompt) + len(got) - 1)
    gap = np.max(logits[at], -1) - logits[at, got]
    assert float(gap.max()) < 8e-2
    eng.shutdown()


def test_the_spans_and_counters_carry_what_the_routing_did():
    """The counts come back with a step's ids: on ``generate.prefill``
    for the prompt, on the ``generate.emit`` of the step for a decode
    step; ``generate.decode_step`` says what the caches read, in tokens
    and in bytes."""
    from deeplearning4j_tpu.common import telemetry
    model, params, pool, eng = _engine()
    rows = telemetry.counter("dl4j_moe_rows_total", "")
    idle = telemetry.counter("dl4j_moe_experts_idle_total", "")
    before = (rows.value(model="t-mimo-e", where="held"),
              rows.value(model="t-mimo-e", where="elsewhere"),
              idle.value(model="t-mimo-e"))
    n_events = len(telemetry.trace_events())
    list(eng.submit(np.arange(3, 15), 9))
    eng.shutdown()
    events = [e for e in telemetry.trace_events()[n_events:]
              if e.get("ph") == "X"]
    prefill = [e["args"] for e in events if e["name"] == "generate.prefill"][-1]
    assert prefill["moe_rows_all"] == 12 * 4 * 3 and prefill["bucket"] == 16
    assert prefill["moe_experts_held"] == 12
    assert 0 < prefill["moe_rows"] <= prefill["moe_rows_all"]
    emits = [e["args"] for e in events if e["name"] == "generate.emit"
             and "moe_rows" in e["args"]]
    assert len(emits) == 8                      # one a decode step
    for a in emits:
        assert a["moe_rows_all"] == 4 * 3 and a["moe_experts_held"] == 12
        assert a["moe_experts_hit"] <= a["moe_rows"] <= 12
        assert a["moe_rows_max"] <= a["moe_rows"]
    steps = [e["args"] for e in events if e["name"] == "generate.decode_step"]
    first = steps[0]                    # one row, context 13: past the window
    assert first["kv_tokens"] == 13 and first["ring_tokens"] == W
    assert first["window_read_tokens"] == 3 * W
    assert first["kv_read_tokens"] == 13 + 3 * W
    assert first["window_read_bytes"] == 3 * W * 320
    assert first["kv_read_bytes"] == 13 * 160 + 3 * W * 320
    held = sum(a["moe_rows"] for a in emits) + prefill["moe_rows"]
    everything = sum(a["moe_rows_all"] for a in emits) + prefill["moe_rows_all"]
    hit = sum(a["moe_experts_hit"] for a in emits) + prefill["moe_experts_hit"]
    # (the warm-up's programs counted too, before ``before`` was read)
    assert rows.value(model="t-mimo-e", where="held") - before[0] == held
    assert rows.value(model="t-mimo-e", where="elsewhere") - before[1] \
        == everything - held
    assert idle.value(model="t-mimo-e") - before[2] == 12 * 9 - hit


def test_layer_kinds_are_named_in_the_lowered_program(want):
    model, params, _ = want
    pool = KVBlockPool(1, 8, 4, 2, 24, v_head_dim=16,
                       state=model.state_shapes(), state_slots=2,
                       name="t-mimo-n")
    i32 = np.int32
    text = jax.jit(model.decode_step).lower(
        params, np.zeros((1,), i32), np.zeros((1,), i32), *pool.arrays,
        np.zeros((1, 4), i32), np.zeros((1,), i32)).as_text(debug_info=True)
    for scope in ("mixer.full", "mixer.window", "ffn.experts", "moe.dense"):
        assert scope in text


def test_a_joiner_takes_the_row_slot_and_blocks_of_a_retired_sequence(
        joins_a_retired_row):
    """The rings and blocks a cancelled sequence held go to the request
    admitted behind the steps that still write them; the prompt's
    routing counts, pulled with its first token, are on its
    ``generate.prefill``."""
    model, params, pool, eng = _engine(state_slots=3, decode_buckets=(2,))
    prefill = joins_a_retired_row(model, params, pool, eng)
    assert prefill["tokens"] == 9
    assert prefill["moe_rows_all"] == 9 * 4 * 3
    assert prefill["moe_experts_held"] == 12
    assert prefill["moe_experts_hit"] <= prefill["moe_rows"] \
        <= prefill["moe_rows_all"]
