"""Conformance of the paged decode attention kernel
(``ops.attention_pallas.paged_decode_attention``, interpret mode here)
against the dense gather ``paged_attention_reference``: every length
round a block's and a step's edge, dead rows of the bucket between
live ones, tables out of pool order, both head shapes the chip runs,
float32 and bf16 pools, and KV poisoned wherever ``lengths`` says it
is not live. Then the engine: the same greedy tokens through the
kernel as through the gather."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from deeplearning4j_tpu.ops import attention_pallas as ap

BLOCK = 8
MAX_BLOCKS = 7                    # 56 tokens; not a multiple of 3 blocks
FULL = BLOCK * MAX_BLOCKS


def _pools(h, d, n_blocks, block, dtype, seed, dv=None):
    """One layer's K and V as the pool stores them: ``[n_blocks, block,
    h * d]``, a token's heads side by side (V ``h * dv`` where its
    heads have a width of their own)."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    k = jax.random.normal(kk, (n_blocks, block, h * d), jnp.float32)
    v = jax.random.normal(kv, (n_blocks, block, h * (dv or d)), jnp.float32)
    return kq, np.array(k.astype(dtype)), np.array(v.astype(dtype))


def _tables(lens, block, max_blocks, n_blocks, seed, dead=None):
    """Each row's blocks drawn from a shuffled pool (block 0 is the
    scratch block: dead rows and the padding name it). ``dead`` names
    the rows of length 1 that are dead; by default the odd ones."""
    order = np.random.default_rng(seed).permutation(
        np.arange(1, n_blocks))
    tables = np.zeros((len(lens), max_blocks), np.int32)
    nxt = 0
    for i, n in enumerate(lens):
        if n == 1 and (i % 2 if dead is None else i in dead):
            continue                    # a dead row: all scratch
        need = -(-n // block)
        tables[i, :need] = order[nxt:nxt + need]
        nxt += need
    assert nxt <= order.size, "pool too small for the case"
    return tables


def _poison(pool, tables, lens):
    """NaN in every slot that ``lengths`` does not call live: blocks no
    row names, the slots past a row's length in its last block, the
    scratch block past the one slot a dead row reads."""
    block = pool.shape[1]
    live = np.zeros(pool.shape[:2], bool)
    for row, n in zip(tables, lens):
        for t in range(n):
            live[row[t // block], t % block] = True
    out = pool.astype(np.float32)
    out[~live] = np.nan
    return out.astype(pool.dtype)


def _rows_match(got, want, lens, tag=()):
    """Row by row, so that none hides behind the others; nothing of the
    poisoned KV in the output."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.all(np.isfinite(got)), "poisoned KV reached the output"
    for i in range(len(lens)):
        assert chip_smoke.rel_err(got[i], want[i]) \
            <= chip_smoke.KERNEL_REL_TOL, tag + (i, lens[i])
    return got


def _check(lens, *, h, d, block, max_blocks, dtype, seed=0, h_kv=None,
           dv=None, sink=False, v_group=1, dead=None):
    lens = list(lens)
    n_blocks = 2 + sum(-(-n // block) for n in lens)
    kq, k, v = _pools(h_kv or h, d, n_blocks, block, dtype, seed, dv)
    q = jax.random.normal(kq, (len(lens), h, d), jnp.float32)
    tables = _tables(lens, block, max_blocks, n_blocks, seed, dead)
    lengths = jnp.asarray(lens, jnp.int32)
    more = {}
    if sink:
        more["sink"] = 2.0 * jax.random.normal(jax.random.fold_in(kq, 1),
                                               (h,), jnp.float32)
    want = ap.paged_attention_reference(
        q, jnp.asarray(k)[None], jnp.asarray(v)[None],
        jnp.asarray(tables), lengths, v_group=v_group, **more)
    got = jax.jit(functools.partial(ap.paged_decode_attention,
                                    v_group=v_group))(
        q, jnp.asarray(_poison(k, tables, lens))[None],
        jnp.asarray(_poison(v, tables, lens))[None], jnp.asarray(tables),
        lengths, **more)
    _rows_match(got, want, lens)


@pytest.fixture
def three_blocks_a_step(monkeypatch):
    """Steps of 3 blocks, so a 7-block table takes three steps and its
    last is short: the loop, the prefetch across rows and the slot
    flip run at a size the interpreter holds."""
    monkeypatch.setattr(ap, "_PAGED_STEP_TOKENS", 3 * BLOCK)
    assert ap._paged_blocks_per_step(BLOCK, 128, 4, MAX_BLOCKS) == 3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("length", [
    1, BLOCK - 1, BLOCK, BLOCK + 1, 3 * BLOCK, 3 * BLOCK + 1, FULL - 1,
    FULL], ids=lambda n: f"len{n}")
def test_one_row_at_every_edge(three_blocks_a_step, length, dtype):
    _check([length], h=2, d=64, block=BLOCK, max_blocks=MAX_BLOCKS,
           dtype=dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("lens", [
    (1, 1, FULL, 1, 1, BLOCK + 1),        # dead rows round live ones
    (FULL, 1, 3 * BLOCK, 1),              # a full step, then a dead row
    (1, 1, 1),                            # nothing but dead rows
    (5, 30, 55, 17, 9, 41, 2, 24),        # a full bucket
], ids=["dead-between", "full-then-dead", "all-dead", "all-live"])
def test_bucket_of_rows(three_blocks_a_step, lens, dtype):
    _check(lens, h=2, d=64, block=BLOCK, max_blocks=MAX_BLOCKS,
           dtype=dtype, seed=1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h,d", [(20, 64), (2, 64)],
                         ids=["h20d64", "h2d64"])
def test_the_cells_shapes(h, d, dtype):
    """The engine's block of 16 at the constant the chip runs: 16
    blocks a step over a table of 21, so a 330-token row takes a full
    step and a short one."""
    assert ap._paged_blocks_per_step(16, h * d, 2, 21) == 16
    _check((330, 1, 17, 256, 1), h=h, d=d, block=16, max_blocks=21,
           dtype=dtype, seed=2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h,h_kv,d,dv,sink", [
    (16, 2, 24, 16, False), (16, 2, 24, 16, True), (16, 1, 24, 16, True),
    (8, 8, 32, 32, True), (64, 4, 192, 128, False), (64, 8, 192, 128, True)],
    ids=["g8-v16", "g8-v16-sink", "g16-v16-sink", "g1-sink", "full-layer",
         "window-layer"])
def test_value_heads_of_their_own_width_and_a_sink(h, h_kv, d, dv, sink,
                                                   dtype):
    """V heads narrower than K heads (the accumulator and the output
    ``h_kv * dv`` lanes wide, the K slab ``h_kv * d``), a learned sink
    logit a query head in the softmax's denominator, groups of 8 and 16
    query heads a KV head: against the dense gather, rows at a block's
    and a ring's edges with dead rows between, KV poisoned wherever it
    is not live. The last two are the MiMo-V2.5 cell's layers: 64 query
    heads on 4 KV heads of 192 / 128 over the growing pool, and on 8
    over a ring of 128 positions (8 blocks of 16) with the sink."""
    ring = (h, h_kv) == (64, 8)
    _check((128, 1, 17, 128, 1, 100) if ring else (330, 1, 17, 256, 1),
           h=h, h_kv=h_kv, d=d, dv=dv, sink=sink, block=16,
           max_blocks=8 if ring else 21, dtype=dtype, seed=5)
    if sink:
        # the sink takes probability: without it the result is another
        q = jnp.ones((1, h, d))
        kp = jnp.zeros((1, 2, 16, h_kv * d), dtype)
        vp = jnp.ones((1, 2, 16, h_kv * dv), dtype)
        args = (q, kp, vp, jnp.asarray([[1]]), jnp.asarray([16]))
        out = ap.paged_decode_attention(*args, sink=jnp.zeros((h,)))
        # 16 keys of score 0 and a sink of 0: 16 / 17 of the value
        np.testing.assert_allclose(out, 16 / 17, rtol=1e-2)
        np.testing.assert_allclose(ap.paged_decode_attention(*args), 1.0,
                                   rtol=1e-2)


@pytest.mark.parametrize("lens,dead", [
    ((1, 41, 17, 1, FULL, 9, 1), {0, 3, 6}), ((BLOCK + 3,), set())],
    ids=["dead-first-middle-last", "one-row"])
@pytest.mark.parametrize("more", [
    {}, {"v_group": 2}, {"dv": 16}, {"sink": True},
    {"dv": 16, "sink": True}],
    ids=["plain", "v_group2", "v16", "sink", "v16-sink"])
@pytest.mark.parametrize("g", [1, 2, 5, 8, 16])
def test_every_group_size(three_blocks_a_step, g, more, lens, dead):
    """The kernel writes a sequence's q into the diagonal blocks of a
    scratch whose zeros are made at the launch's first row, and reads
    the result out of the same blocks of the accumulator: so every
    group size the cells run (1, 2, 5, 8, 16 query heads a KV head)
    with a shared V group, V heads of their own width and a sink, on a
    bucket whose first row is dead (it makes the zeros), with dead
    rows in the middle and last, and on a bucket whose first row is
    its only one. Steps of 3 blocks: the longest row takes three."""
    _check(lens, h=2 * g, h_kv=2, d=32, block=BLOCK,
           max_blocks=MAX_BLOCKS, dtype=jnp.bfloat16, seed=6 + g,
           dead=dead, **more)


def test_two_launches_of_one_program_share_nothing(three_blocks_a_step):
    """Two layers through one traced call, the second with the bucket
    the other way round and queries of its own: what a launch keeps
    from its first row (the zeros round q's blocks, the accumulator's
    first state) is rebuilt by the next launch, and nothing of the
    first one's rows shows in the second's."""
    h, h_kv, d, dv = 16, 2, 32, 16
    lens = [1, FULL, 17, 1, 2 * BLOCK]
    n_blocks = 2 + sum(-(-n // BLOCK) for n in lens)
    kq, k0, v0 = _pools(h_kv, d, n_blocks, BLOCK, jnp.bfloat16, 8, dv)
    _, k1, v1 = _pools(h_kv, d, n_blocks, BLOCK, jnp.bfloat16, 9, dv)
    tables = _tables(lens, BLOCK, MAX_BLOCKS, n_blocks, 8, dead={0, 3})
    q = jax.random.normal(kq, (2, len(lens), h, d), jnp.float32)
    sink = jax.random.normal(jax.random.fold_in(kq, 1), (h,))
    k, v = (jnp.stack([jnp.asarray(_poison(a, tables, lens))
                       for a in pair]) for pair in ((k0, k1), (v0, v1)))
    first = (jnp.asarray(tables), jnp.asarray(lens, jnp.int32), 0)
    second = (jnp.asarray(tables[::-1].copy()),
              jnp.asarray(lens[::-1], jnp.int32), 1)

    @jax.jit
    def both(q, k, v):
        return (ap.paged_decode_attention(q[0], k, v, *first, sink=sink),
                ap.paged_decode_attention(q[1], k, v, *second, sink=sink))
    got = both(q, k, v)
    clean_k, clean_v = jnp.stack([k0, k1]), jnp.stack([v0, v1])
    for n, (out, args) in enumerate(zip(got, (first, second))):
        want = ap.paged_attention_reference(q[n], clean_k, clean_v, *args,
                                            sink=sink)
        out = _rows_match(out, want, (lens, lens[::-1])[n], (n,))
        # and alone in its own program the launch gives the same bits
        alone = ap.paged_decode_attention(q[n], k, v, *args, sink=sink)
        np.testing.assert_array_equal(np.asarray(alone), out)


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("h,h_kv", [(4, 4), (10, 2)], ids=["g1", "g5"])
def test_a_layer_of_the_stacked_pool(three_blocks_a_step, h, h_kv, layer):
    """The pool as ``KVBlockPool`` stores it, two layers stacked: the
    kernel takes it whole and the layer as an index, reads that
    layer's blocks only (the other layer is NaN throughout), ragged
    lengths with dead rows on the scratch block between, as many KV
    heads as query heads and five query heads a KV head."""
    d, lens = 32, [41, 1, FULL, 1, BLOCK + 1, 1, 1]
    n_blocks = 2 + sum(-(-n // BLOCK) for n in lens)
    kq, k, v = _pools(h_kv, d, n_blocks, BLOCK, jnp.bfloat16, seed=4)
    q = jax.random.normal(kq, (len(lens), h, d), jnp.float32)
    tables = _tables(lens, BLOCK, MAX_BLOCKS, n_blocks, seed=4)
    assert not tables[[1, 3, 5]].any() and tables[6, 0]   # scratch rows
    lengths = jnp.asarray(lens, jnp.int32)

    def stacked(pool):
        both = np.full((2,) + pool.shape, np.nan, np.float32)
        both[layer] = pool.astype(np.float32)
        return jnp.asarray(both, jnp.bfloat16)
    want = ap.paged_attention_reference(
        q, stacked(k), stacked(v), jnp.asarray(tables), lengths, layer)
    got = jax.jit(ap.paged_decode_attention)(
        q, stacked(_poison(k, tables, lens)),
        stacked(_poison(v, tables, lens)), jnp.asarray(tables), lengths,
        layer)                      # the layer traced, as a model's is not
    assert np.all(np.isfinite(np.asarray(want)))
    got = _rows_match(got, want, lens)
    assert got.shape == (len(lens), h, d)
    # one trace serves every layer: the index is an operand
    again = ap.paged_decode_attention(
        q, stacked(k), stacked(v), jnp.asarray(tables), lengths, layer)
    np.testing.assert_array_equal(np.asarray(again), got)


def test_blocks_per_step_follows_the_shapes():
    per = ap._paged_blocks_per_step
    assert per(16, 1280, 2, 64) == 16         # the cell: 256 tokens
    assert per(16, 1280, 2, 8) == 8           # never past the table
    assert per(16, 16384, 4, 64) == 2         # four slabs inside VMEM
    assert per(512, 1280, 2, 64) == 1         # a block wider than a step


def test_selector_rungs():
    pick = ap.select_paged_backend
    assert pick(32, 64, platform="tpu", use_env_override=False)[0] \
        == "paged"
    assert pick(32, 64, platform="cpu", use_env_override=False)[0] \
        == "dense"
    assert pick(32, 64, platform="tpu", override=False)[0] == "dense"
    assert pick(32, 64, platform="cpu", override=True)[0] == "paged"
    assert pick(0, 64, platform="tpu", override=True)[0] == "dense"


class TestEngine:
    """Greedy decoding through the kernel serves the tokens the dense
    gather serves, with rows joining and leaving mid-batch."""

    @staticmethod
    def _serve(paged):
        from deeplearning4j_tpu.models.decoder import (DecoderConfig,
                                                       DecoderLM)
        from deeplearning4j_tpu.serving.generative import DecodeEngine
        from deeplearning4j_tpu.serving.kvcache import KVBlockPool
        conf = DecoderConfig(vocab_size=64, n_layers=2, n_heads=2,
                             d_model=128, d_ff=128, max_len=64,
                             eos_id=64)
        model = DecoderLM(conf)
        # weights wide enough that attention moves the logits, narrow
        # enough that bf16 products do not turn a near-tie
        params = jax.tree_util.tree_map(
            lambda w: w * 4.0 if w.ndim == 2 else w, model.init())
        pool = KVBlockPool(conf.n_layers, 24, 8, conf.n_heads,
                           conf.head_dim, name=f"t-paged-{paged}")
        eng = DecodeEngine(model, params, pool, name=f"t-paged-{paged}",
                           prompt_buckets=(16,), decode_buckets=(4,),
                           max_seq_len=64, paged=paged)
        eng.warmup()
        rng = np.random.default_rng(7)
        first = [eng.submit(rng.integers(2, 64, n), m)
                 for n, m in ((5, 40), (9, 12), (3, 25))]
        head = first[1].next(timeout=60)        # all three are decoding
        late = [eng.submit(rng.integers(2, 64, n), m)
                for n, m in ((12, 20), (2, 30))]  # one waits for a row
        out = [list(s) for s in first + late]
        out[1].insert(0, head)
        eng.shutdown()
        return out

    def test_same_greedy_tokens_as_the_dense_gather(self):
        dense, paged = self._serve(False), self._serve(True)
        assert [len(t) for t in dense] == [40, 12, 25, 20, 30]
        assert paged == dense
