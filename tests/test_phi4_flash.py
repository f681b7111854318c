"""Phi-4-mini-flash (Mamba-1 + sliding-window differential attention,
one full-attention cache, a cross-decoder of Gated Memory Units) behind
the serving contract: the system against the plain reference on seeded
weights, the prefill's last-position skip, the window rings through the
paged kernel, the Mamba-1 kernels, and three kinds of state in one
pool.

Tiny widths with the published ratios kept (query width = d_model, 2
query heads a KV head, d_inner = 2 d_model, a state of 16, a
convolution of 4); a window of 8 and contexts to 40, so every ring
wraps several times.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.models.phi4_flash import program_layout
from chipbench.reference import phi4_flash as ref
from deeplearning4j_tpu.models.phi4_flash import Phi4FlashConfig, Phi4FlashLM
from deeplearning4j_tpu.ops import attention_pallas as ap
from deeplearning4j_tpu.ops import ssm_pallas as sp
from deeplearning4j_tpu.serving.generative import DecodeEngine
from deeplearning4j_tpu.serving.kvcache import KVBlockPool

CFG = {"hidden_size": 64, "intermediate_size": 256, "layer_norm_eps": 1e-5,
       "num_attention_heads": 8, "num_hidden_layers": 8,
       "num_key_value_heads": 4, "sliding_window": 8, "vocab_size": 96,
       "mamba_dt_rank": 4, "init_std": 0.3, "x_proj_std": 0.15}
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
    model = Phi4FlashLM(Phi4FlashConfig.from_published(cfg, max_len=512,
                                                       **kw))
    return model, (_f32(params) if widen else params), weights


@pytest.fixture(scope="module")
def want():
    model, params, weights = _system()
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(CFG, weights, jnp.asarray(TOKENS))
    return model, params, logits


def test_the_layer_pattern():
    kinds = Phi4FlashConfig.from_published(
        dict(CFG, num_hidden_layers=32)).kinds
    assert [k for k in kinds[:18]] == ["mamba", "window"] * 8 + ["mamba",
                                                                 "full"]
    assert kinds[18:] == ("gmu", "cross") * 7
    assert kinds == tuple(ref.kind_of(dict(CFG, num_hidden_layers=32), l)
                          for l in range(32))
    with pytest.raises(ValueError):
        Phi4FlashConfig(n_layers=6)


# -- the system's forward against the plain reference ---------------------
def test_forward_matches_the_plain_reference(want):
    """Float32 weights on both sides: what is left is the order of the
    sums (logits to 10, so 3e-5 is three parts in a million)."""
    model, params, logits = want
    got = model.forward(params, TOKENS[None])[0]
    assert got.shape == (T, 96)
    np.testing.assert_allclose(got, logits, atol=3e-5)


def test_forward_with_bfloat16_weights_rounds_and_no_more():
    """The weights as the benchmark holds them: the products round
    their left operand to bfloat16, a few parts in a thousand of the
    logits' range and not zero."""
    model, params, weights = _system(widen=False)
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(CFG, weights, jnp.asarray(TOKENS))
    got = model.forward(params, TOKENS[None])[0]
    err = float(jnp.max(jnp.abs(got - logits)))
    assert 0 < err < 0.03 * float(jnp.max(jnp.abs(logits)))


def test_prefill_skips_the_upper_layers_and_equals_forward(want):
    """Layers from the full one up run on one position: the last logits
    are the full forward's, from a padded bucket, at every length
    across the window's edge."""
    model, params, logits = want
    assert model.prefill_layer_positions(32) == (5 * 32 + 3, 8 * 32)
    for length in (1, W - 1, W, W + 1, 3 * W + 5):
        tokens = np.zeros((1, 32), np.int32)
        tokens[0, :length] = TOKENS[:length]
        last = jax.jit(model.prefill)(
            params, tokens, np.asarray([length], np.int32))[0]
        np.testing.assert_allclose(last[0], logits[length - 1], atol=3e-5)


def _commit(pool, new, length, table, slot):
    """What the engine's commit program does, by hand."""
    k, v, *state = new
    bs = pool.block_size
    idx = np.arange(k.shape[2])
    rows = np.where(idx < length,
                    np.asarray(table)[np.minimum(idx // bs, len(table) - 1)]
                    * bs + idx % bs, 0)
    kp, vp, *slots = pool.arrays
    flat = (kp.shape[0], -1, kp.shape[3])
    kp = kp.reshape(flat).at[:, rows].set(
        k[:, 0].reshape(flat).astype(kp.dtype)).reshape(kp.shape)
    vp = vp.reshape(flat).at[:, rows].set(
        v[:, 0].reshape(flat).astype(vp.dtype)).reshape(vp.shape)
    slots = [a.at[:, slot].set(n[:, 0].reshape(a.shape[:1] + a.shape[2:]))
             for a, n in zip(slots, state)]
    return (kp, vp, *slots)


@pytest.mark.parametrize("length,paged", [
    (1, False), (W - 1, False), (W, False), (W + 1, False), (21, False),
    (W + 1, True)])
def test_prefill_then_decode_through_the_cache(want, length, paged):
    """A prompt in a padded bucket, then one token a step through the
    one K/V layer, the rings and the state slot: the logits at every
    position to the end are the full forward's, while the ring wraps
    (the Pallas kernel's products take bfloat16 operands, 8e-2 on
    logits to 10; the dense gather's float32, 5e-5)."""
    model, params, logits = want
    pool = KVBlockPool(1, 32, 4, 4, 8, state=model.state_shapes(),
                       state_slots=4, name="t-p4")
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :length] = TOKENS[:length]
    last, *new = jax.jit(model.prefill)(
        params, tokens, np.asarray([length], np.int32))
    table = list(range(3, 14))
    cache = _commit(pool, new, length, table, slot=3)
    step = jax.jit(model.decode_step, static_argnames=("paged",))
    tables = np.zeros((2, 16), np.int32)
    tables[0, :len(table)] = table
    for pos in range(length, T):
        # row 0 is the sequence, row 1 a dead row on the scratch block
        # and the scratch slot
        out, *cache = step(params, np.asarray([TOKENS[pos], 0], np.int32),
                           np.asarray([pos, 0], np.int32), *cache, tables,
                           np.asarray([3, 0], np.int32), paged=paged)
        np.testing.assert_allclose(out[0], logits[pos],
                                   atol=8e-2 if paged else 5e-5)


def test_prefill_masks_the_padding_out_of_state_and_rings(want):
    """State, tail and rings at ``length - 1`` of a padded bucket are
    those of the unpadded prompt, and a ring holds position ``p`` at
    ``p mod window``."""
    model, params, _ = want
    n = 13
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :n] = TOKENS[:n]
    padded = jax.jit(model.prefill)(params, tokens, np.asarray([n], np.int32))
    exact = model.prefill(params, TOKENS[None, :n], np.asarray([n], np.int32))
    for a, b in zip(padded[3:], exact[3:]):       # rings, state, tail
        np.testing.assert_allclose(a, b, atol=1e-5)
    # the window layers' K over the whole prompt, against the ring
    ks = model._lower(params, jnp.asarray(TOKENS[None, :n]),
                      jnp.asarray([n], jnp.int32))[2]
    ring = np.asarray(padded[3])                   # [2, 1, W, lanes]
    for p in range(n - W, n):
        np.testing.assert_allclose(ring[0, 0, p % W],
                                   np.asarray(ks[0])[0, p].reshape(-1),
                                   atol=1e-5)


# -- the kernels against their jax.numpy forms (interpret mode) -----------
def test_paged_attention_with_a_shared_value_of_two_heads():
    """``v_group = 2``: a query head scores against its own KV head and
    reads the pair's V, twice as wide. bfloat16 products: 2e-2."""
    rs = np.random.RandomState(3)
    b, h, h_kv, d, bs, nb = 3, 8, 4, 8, 4, 20
    kp = jnp.asarray(rs.randn(2, nb, bs, h_kv * d), jnp.float32)
    vp = jnp.asarray(rs.randn(2, nb, bs, h_kv * d), jnp.float32)
    q = jnp.asarray(rs.randn(b, h, d), jnp.float32)
    tables = jnp.asarray(rs.permutation(np.arange(1, nb))[:b * 5]
                         .reshape(b, 5), jnp.int32)
    lengths = jnp.asarray([1, 9, 20], jnp.int32)
    want = ap.paged_attention_reference(q, kp, vp, tables, lengths, 1,
                                        v_group=2)
    got = ap.paged_decode_attention(q, kp, vp, tables, lengths, 1,
                                    v_group=2)
    assert got.shape == (b, h, 2 * d)
    np.testing.assert_allclose(got, want, atol=2e-2)
    # by hand: query head r, KV head r // 2, the values of heads 2G, 2G+1
    k = np.asarray(kp[1])[np.asarray(tables)].reshape(b, 20, h_kv, d)
    v = np.asarray(vp[1])[np.asarray(tables)].reshape(b, 20, h_kv, d)
    for i, n in enumerate(np.asarray(lengths)):
        for r in range(h):
            s = np.asarray(q)[i, r] @ k[i, :n, r // 2].T / np.sqrt(d)
            w = np.exp(s - s.max())
            g = (r // 2) // 2
            vv = np.concatenate([v[i, :n, 2 * g], v[i, :n, 2 * g + 1]], -1)
            np.testing.assert_allclose(w / w.sum() @ vv, want[i, r],
                                       atol=1e-5)
    # one value a head is today's kernel, bit for bit its own result
    same = ap.paged_decode_attention(q, kp, vp, tables, lengths, 1)
    np.testing.assert_array_equal(
        same, ap.paged_decode_attention(q, kp, vp, tables, lengths, 1,
                                        v_group=1))


def _mamba_case(rows, n=16, ch=256, seed=0):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)      # noqa: E731
    return (-jnp.exp(f(n, ch)), f(*rows, ch), jax.nn.softplus(f(*rows, ch)),
            f(*rows, n), f(*rows, n))


def test_selective_state_update_kernel_in_place():
    """The Mamba-1 decode kernel on the rows' slots of one layer
    (float32 throughout: 1e-5); a dead row reads 0 and every other slot
    and layer is left as it was."""
    a, x, dt, b, c = _mamba_case((4,))
    state = jnp.asarray(np.random.RandomState(1).randn(3, 5, 16, 256),
                        jnp.float32)
    slots = jnp.asarray([2, 0, 4, 1], jnp.int32)
    want_s, want_y = sp.selective_state_update_reference(
        state, 1, slots, x, dt, a, b, c)
    got_s, got_y = sp.selective_state_update_pallas(
        state, 1, slots, x, dt, a, b, c)
    live = np.asarray(slots) != 0
    np.testing.assert_allclose(got_y[live], want_y[live], atol=1e-5)
    assert float(jnp.max(jnp.abs(got_y[~live]))) == 0
    np.testing.assert_allclose(got_s[:, 1:], want_s[:, 1:], atol=1e-5)
    np.testing.assert_array_equal(got_s[0], state[0])
    np.testing.assert_array_equal(got_s[1, 3], state[1, 3])
    # the reference is the recurrence written out
    s1 = (state[1, 2] * jnp.exp(dt[0][None] * a)
          + (dt[0] * x[0])[None] * b[0][:, None])
    np.testing.assert_allclose(want_s[1, 2], s1, atol=1e-6)
    np.testing.assert_allclose(want_y[0], (s1 * c[0][:, None]).sum(0),
                               atol=1e-5)


@pytest.mark.parametrize("t", [1, 8, 21])
def test_selective_scan_kernel_is_the_token_by_token_recurrence(t):
    """The prefill kernel (8 tokens a grid step, the state resident)
    against the ``lax.scan`` over tokens, lengths that are and are not
    whole steps; ``dt = 0`` leaves the state as it is."""
    a, x, dt, b, c = _mamba_case((2, t))
    dt = dt.at[1, t // 2:].set(0.0)
    want_y, want_s = sp.selective_scan_reference(x, dt, a, b, c)
    got_y, got_s = sp.selective_scan_pallas(x, dt, a, b, c)
    np.testing.assert_allclose(got_y, want_y, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)
    half_y, half_s = sp.selective_scan_reference(
        x[1:, :t // 2], dt[1:, :t // 2], a, b[1:, :t // 2], c[1:, :t // 2])
    if t > 1:
        np.testing.assert_allclose(got_s[1], half_s[0], atol=2e-5)


def test_the_mamba1_kernels_go_through_the_ssm_ladder(monkeypatch):
    assert sp.select_ssm_backend(16, 5120, platform="tpu")[0] == "kernel"
    assert sp.select_ssm_backend(16, 100, platform="tpu")[0] == "dense"
    assert sp.select_ssm_backend(16, 5120, platform="cpu")[0] == "dense"
    a, x, dt, b, c = _mamba_case((2, 8))
    monkeypatch.setenv("DL4J_TPU_SSM_STATE", "1")
    forced = sp.selective_scan(x, dt, a, b, c)
    monkeypatch.setenv("DL4J_TPU_SSM_STATE", "0")
    plain = sp.selective_scan(x, dt, a, b, c)
    np.testing.assert_allclose(forced[0], plain[0], atol=2e-5)


# -- the engine: rings and state slots beside the one K/V layer -----------
def _engine(state_slots=5, decode_buckets=(4,), **kw):
    model = Phi4FlashLM(Phi4FlashConfig(eos_id=96))
    params = model.init()
    pool = KVBlockPool(model.kv_layers, 64, 4, 4, 8, name="t-p4e",
                       state=model.state_shapes(), state_slots=state_slots)
    eng = DecodeEngine(model, params, pool, name="t-p4e",
                       prompt_buckets=(16, 32),
                       decode_buckets=decode_buckets, max_seq_len=64, **kw)
    eng.warmup()
    return model, params, pool, eng


def test_churn_serves_the_tokens_each_sequence_gets_alone():
    """Sequences join and leave at different steps, their contexts pass
    the window several times; each is served the greedy tokens it gets
    with the engine to itself and by full re-forward; slots are reused;
    nothing compiles after warm-up."""
    model, params, pool, eng = _engine()
    rs = np.random.RandomState(4)
    cases = [(rs.randint(2, 90, n), m) for n, m in
             ((3, 30), (9, 5), (14, 26), (1, 7), (20, 11), (6, 34), (11, 8))]
    alone = [list(eng.submit(p, m)) for p, m in cases]
    for i in (0, 2):
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
    """The same through the Pallas kernel (interpret mode): rings and
    the shared layer read by one kernel, a request served with a dead
    row and a hole beside it."""
    model, params, pool, eng = _engine(paged=True)
    prompt = np.random.RandomState(8).randint(2, 90, 11)
    got = list(eng.submit(prompt, 14))
    logits = model.forward(params, np.asarray([list(prompt) + got]))[0]
    # greedy under bfloat16 products: each served token's logit is
    # within 8e-2 of the float32 forward's best at its position
    at = np.arange(len(prompt) - 1, len(prompt) + len(got) - 1)
    gap = np.max(logits[at], -1) - logits[at, got]
    assert float(gap.max()) < 8e-2
    eng.shutdown()


def test_a_window_layers_bytes_do_not_grow_with_the_context():
    """The rings are a fixed share a slot: the same bytes with a
    sequence at context 10 and at 40, while the full layer's blocks
    grow; the report and the gauges count each kind."""
    from deeplearning4j_tpu.common import telemetry
    model, params, pool, eng = _engine()
    ring = 2 * 2 * W * 32 * 4                       # K and V, 2 layers
    assert pool.window_bytes == 5 * ring
    assert pool.report()["window"]["bytes_per_slot"] == ring
    assert pool.state_bytes == 3 * 5 * (16 * 128 + 3 * 128) * 4
    assert pool.pool_bytes == 2 * 64 * 4 * 32 * 4
    seen = {}
    for total in (10, 40):
        stream = eng.submit(np.asarray([3, 4, 5, 6]), total - 4)
        first = stream.next(timeout=120)
        tokens = [first] + stream.tokens(timeout=120)
        seen[total] = (pool.window_bytes,
                       {k: a.shape for k, a in pool.state.items()})
        assert len(tokens) == total - 4
    assert seen[10] == seen[40]
    assert pool.blocks_for(10) == 3 and pool.blocks_for(40) == 10
    eng.shutdown()
    text = telemetry.MetricsRegistry.get().render_prometheus()
    assert f'dl4j_window_pool_bytes{{pool="t-p4e"}} {5 * ring}' in text
    assert f'dl4j_state_pool_bytes{{pool="t-p4e"}} {pool.state_bytes}' in text
    assert f'dl4j_kv_pool_bytes{{pool="t-p4e"}} {pool.pool_bytes}' in text
    with pytest.raises(ValueError):                 # a ring is whole blocks
        KVBlockPool(1, 8, 3, 4, 8, device_arrays=False,
                    state=model.state_shapes(), state_slots=3)


def test_the_spans_carry_what_each_kind_of_state_holds_and_reads():
    from deeplearning4j_tpu.common import telemetry
    model, params, pool, eng = _engine()
    list(eng.submit(np.arange(3, 15), 6))
    eng.shutdown()
    events = [e for e in telemetry.trace_events()
              if e.get("ph") == "X" and e["args"].get("model") == "t-p4e"]
    prefill = [e["args"] for e in events if e["name"] == "generate.prefill"][-1]
    assert prefill["positions"] == 16
    assert prefill["layer_positions"] == 5 * 16 + 3
    assert prefill["layer_positions_dense"] == 8 * 16
    steps = [e["args"] for e in events
             if e["name"] == "generate.decode_step"][-5:]
    first = steps[0]                    # one row, context 13: past the window
    assert first["kv_tokens"] == 13 and first["ring_tokens"] == W
    assert first["window_read_tokens"] == 2 * W
    assert first["kv_read_tokens"] == 2 * 13 + 2 * W
    assert first["live"] == 1 and "state_live" not in first
    assert pool.report()["state"]["slots"] == {
        "free": 4, "live": 0, "reserved": 1, "total": 5}
    assert [s["kv_tokens"] for s in steps] == [13, 14, 15, 16, 17]


def test_mixer_kinds_are_named_in_the_lowered_program(want):
    model, params, _ = want
    pool = KVBlockPool(1, 8, 4, 4, 8, state=model.state_shapes(),
                       state_slots=2, name="t-p4n")
    i32 = np.int32
    text = jax.jit(model.decode_step).lower(
        params, np.zeros((1,), i32), np.zeros((1,), i32), *pool.arrays,
        np.zeros((1, 4), i32), np.zeros((1,), i32)).as_text(debug_info=True)
    for kind in ("mamba", "window", "full", "gmu", "cross"):
        assert f"mixer.{kind}" in text



def test_a_joiner_takes_the_row_slot_and_blocks_of_a_retired_sequence(
        joins_a_retired_row):
    """The slot a cancelled sequence held (its Mamba state and its
    window rings) goes to the request admitted behind the steps that
    still write it: their writes land before its commit."""
    model, params, pool, eng = _engine(state_slots=3, decode_buckets=(2,))
    prefill = joins_a_retired_row(model, params, pool, eng)
    assert prefill["tokens"] == 9 and prefill["positions"] == 16
