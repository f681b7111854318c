"""Generative serving engine: sampling primitives, prefill/decode
continuous batching, KV-pool shedding over HTTP, streaming chunked
responses, and residency composition.

Each piece of the ISSUE-16 stack is pinned where an operator would
feel it break: tokens must match the dense full-re-forward reference,
a full pool must shed 429 with a measured Retry-After BEFORE any
chunk is sent, a disconnected client must free its blocks, and a
mid-stream handler exception must terminate the chunk stream as a
truncation the client detects — never a wedged connection.
"""
from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.decoder import DecoderConfig, DecoderLM
from deeplearning4j_tpu.serving.generative import DecodeEngine
from deeplearning4j_tpu.serving.kvcache import (KVBlockPool,
                                                PoolExhausted)


def _decode_args(eng):
    """The decode program's arguments at the largest bucket, all
    zeros: every row dead, greedy."""
    import jax
    b = eng.decode_buckets[-1]
    return (eng.params, eng.pool.arrays, np.zeros((b,), np.int32),
            np.zeros((b,), np.int32),
            np.zeros((b, eng.max_blocks), np.int32),
            jax.random.PRNGKey(0), np.zeros((b,), np.float32),
            np.zeros((b,), np.int32),
            *eng._state_arg(np.zeros((b,), np.int32)))


def _engine(conf=None, *, kv_blocks=64, block=8, prompt_buckets=(16,),
            decode_buckets=(4,), max_seq_len=64, **kw):
    conf = conf or DecoderConfig.tiny()
    model = DecoderLM(conf)
    pool = KVBlockPool(conf.n_layers, kv_blocks, block, conf.n_heads,
                       conf.head_dim, name="t-gen")
    eng = DecodeEngine(model, model.init(), pool, name="t-gen",
                       prompt_buckets=prompt_buckets,
                       decode_buckets=decode_buckets,
                       max_seq_len=max_seq_len, **kw)
    eng.warmup()
    return model, pool, eng


def _always_sort_sample_logits(logits, key, temperature=1.0, top_k=0):
    """``ops.sampling.sample_logits`` as it stood before ISSUE 31: it
    sorted the whole vocabulary and drew noise for every row of every
    call, and discarded both for greedy rows. The oracle of the rungs:
    the same ids, bit for bit."""
    import jax
    import jax.numpy as jnp
    logits = jnp.asarray(logits)
    temp = jnp.broadcast_to(jnp.asarray(temperature, logits.dtype),
                            logits.shape[:-1])
    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_temp = jnp.where(temp > 0, temp, 1.0)
    scaled = logits / safe_temp[..., None]
    vocab = scaled.shape[-1]
    k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32),
                         scaled.shape[:-1])
    kc = jnp.clip(k, 1, vocab)
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    thresh = jnp.take_along_axis(sorted_desc, kc[..., None] - 1,
                                 axis=-1)
    filtered = jnp.where(scaled >= thresh, scaled, -1e9)
    scaled = jnp.where(k[..., None] > 0, filtered, scaled)
    sampled_ids = jax.random.categorical(key, scaled,
                                         axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0, sampled_ids, greedy_ids)


def _sampler_logits(vocab=1017, rows=8, seed=0):
    # a vocabulary that is no multiple of 128
    return np.random.default_rng(seed).normal(
        size=(rows, vocab)).astype(np.float32) * 3.0


def _tied_logits():
    # whole numbers in [-3, 3]: some 145 ties at every value, so every
    # threshold is tied and the ties are kept
    return np.random.default_rng(4).integers(
        -3, 4, size=(8, 1017)).astype(np.float32)


def _ks(*values):
    return np.asarray(values, np.int32)


_MIXED = np.asarray([0, 1.0, 0, 0.7, 1.3, 0, 2.0, 0.4], np.float32)
_ALL = np.full((8,), 0.9, np.float32)
_K0 = np.zeros((8,), np.int32)


def _cap():
    from deeplearning4j_tpu.ops.sampling import TOP_K_CAP
    return TOP_K_CAP


#: name -> () -> (logits, temperature, top_k, the rung it lands on)
_SAMPLER_CASES = {
    "all_greedy": lambda: (
        _sampler_logits(), np.zeros((8,), np.float32), _K0, "argmax"),
    "greedy_rows_with_a_top_k": lambda: (
        _sampler_logits(), np.zeros((8,), np.float32),
        np.full((8,), 1000, np.int32), "argmax"),
    "greedy_and_sampled_mixed": lambda: (
        _sampler_logits(), _MIXED, _K0, "categorical"),
    "all_sampled_top_k_0": lambda: (
        _sampler_logits(), _ALL, _K0, "categorical"),
    "a_greedy_rows_top_k_is_not_read": lambda: (
        _sampler_logits(), _MIXED,
        _ks(1000, 0, 40, 0, 0, 0, 0, 0),
        "categorical"),
    "top_k_1": lambda: (
        _sampler_logits(), _MIXED, np.full((8,), 1, np.int32),
        "top_k"),
    "top_k_40": lambda: (
        _sampler_logits(), _ALL, np.full((8,), 40, np.int32), "top_k"),
    "top_k_one_row_of_eight": lambda: (
        _sampler_logits(), _MIXED,
        _ks(0, 40, 0, 0, 0, 0, 0, 0), "top_k"),
    "top_k_cap": lambda: (
        _sampler_logits(), _MIXED,
        _ks(0, _cap(), 0, 3, 0, 0, 50, _cap()),
        "top_k"),
    "top_k_cap_plus_1": lambda: (
        _sampler_logits(), _MIXED,
        _ks(0, _cap() + 1, 0, 3, 0, 0, 50, 0),
        "sort"),
    "top_k_whole_vocabulary": lambda: (
        _sampler_logits(), _ALL,
        _ks(1017, 1016, 5000, 1, 0, 129, 128, 500),
        "sort"),
    "ties_at_the_threshold_top_k": lambda: (
        _tied_logits(), _MIXED, np.full((8,), 7, np.int32), "top_k"),
    "ties_at_the_threshold_sort": lambda: (
        _tied_logits(), _MIXED, np.full((8,), 300, np.int32), "sort"),
    "vocabulary_under_the_cap": lambda: (
        _sampler_logits(vocab=16), _MIXED,
        _ks(0, 3, 16, 100, 1, 0, 15, 2), "top_k"),
    "vocabulary_of_whole_tiles": lambda: (
        _sampler_logits(vocab=1024), _MIXED,
        _ks(0, 3, 16, 100, 1, 0, 15, 2), "top_k"),
    "scalar_temperature_and_top_k": lambda: (
        _sampler_logits(), 0.8, 40, "top_k"),
    "scalar_top_k_past_the_cap": lambda: (
        _sampler_logits(), 1.2, 400, "sort"),
    "scalar_temperature_alone": lambda: (
        _sampler_logits(), 0.8, 0, "categorical"),
    "scalar_greedy": lambda: (_sampler_logits(), 0.0, 0, "argmax"),
}


def _ordering_ops(lowered):
    """``(operation, under a conditional)`` for every operation of the
    lowered program that orders its operand (a sort, a top-k
    selection), following calls from ``main``: a function called from
    a branch is under the conditional too."""
    from jax._src.lib.mlir import ir
    module = lowered.compiler_ir("stablehlo")
    funcs = {ir.StringAttr(op.attributes["sym_name"]).value: op
             for op in module.body.operations}
    found = []

    def walk(op, under):
        name = op.operation.name
        if name in ("stablehlo.sort", "chlo.top_k"):
            found.append((name, under))
        if name in ("func.call", "call"):
            callee = ir.FlatSymbolRefAttr(op.attributes["callee"]).value
            walk(funcs[callee], under)
        inner = under or name in ("stablehlo.case", "stablehlo.if")
        for region in op.operation.regions:
            for block in region.blocks:
                for child in block.operations:
                    walk(child, inner)

    walk(funcs["main"], False)
    return sorted(set(found))


class TestSampling:
    def test_greedy_is_argmax(self):
        import jax
        from deeplearning4j_tpu.ops.sampling import (greedy,
                                                     sample_logits)
        logits = np.random.default_rng(0).normal(size=(4, 16)) \
            .astype(np.float32)
        ids = np.asarray(greedy(logits))
        assert list(ids) == list(np.argmax(logits, axis=-1))
        # temperature 0 through the stochastic path is greedy too
        ids0 = np.asarray(sample_logits(
            logits, jax.random.PRNGKey(1),
            np.zeros((4,), np.float32), np.zeros((4,), np.int32)))
        assert list(ids0) == list(np.argmax(logits, axis=-1))

    def test_same_key_same_sample_deterministic(self):
        import jax
        from deeplearning4j_tpu.ops.sampling import sample_logits
        logits = np.random.default_rng(1).normal(size=(2, 32)) \
            .astype(np.float32)
        a = np.asarray(sample_logits(logits, jax.random.PRNGKey(7),
                                     temperature=1.0))
        b = np.asarray(sample_logits(logits, jax.random.PRNGKey(7),
                                     temperature=1.0))
        assert list(a) == list(b)

    def test_top_k_restricts_support(self):
        import jax
        from deeplearning4j_tpu.ops.sampling import sample_logits
        logits = np.arange(16, dtype=np.float32)[None, :]
        top3 = {13, 14, 15}
        for i in range(20):
            t = int(np.asarray(sample_logits(
                logits, jax.random.PRNGKey(i), temperature=2.0,
                top_k=3))[0])
            assert t in top3

    def test_distribution_tracks_logit_mass(self):
        """~2:1 logit odds must come out ~2:1 empirically (sanity on
        the categorical plumbing, not a statistical proof)."""
        import jax
        from deeplearning4j_tpu.ops.sampling import sample_logits
        logits = np.log(np.array([[2.0, 1.0, 1e-9]], np.float32))
        n = 600
        draws = np.asarray(sample_logits(
            np.repeat(logits, n, 0), jax.random.PRNGKey(0),
            temperature=1.0))
        counts = np.bincount(draws, minlength=3)
        assert counts[2] == 0
        assert 0.5 < counts[0] / max(counts[1], 1) * 0.5 < 2.0


    @pytest.mark.parametrize("mode", ["jit", "eager"])
    @pytest.mark.parametrize("case", sorted(_SAMPLER_CASES))
    def test_every_rung_gives_the_ids_of_the_always_sort_formula(
            self, case, mode):
        """Bit for bit: whatever rung the batch lands on, the ids are
        those of the formula that sorted the whole vocabulary in every
        call. ``jit`` hands the per-row arguments to one compiled
        program (the device picks the rung); ``eager`` hands them over
        concrete (the rung is picked while tracing)."""
        import jax
        from deeplearning4j_tpu.ops.sampling import (PATHS,
                                                     sample_logits,
                                                     sample_rung)
        logits, temp, top_k, path = _SAMPLER_CASES[case]()
        scalar = np.ndim(temp) == 0
        if not scalar:
            assert PATHS[int(sample_rung(temp, top_k))] == path
        new, old = sample_logits, _always_sort_sample_logits
        if mode == "jit" and scalar:
            # Python scalars closed over, as bench_charrnn.py has them
            new = jax.jit(lambda x, k: sample_logits(x, k, temp, top_k))
            old = jax.jit(lambda x, k: _always_sort_sample_logits(
                x, k, temp, top_k))
            args = ()
        elif mode == "jit":
            new, old, args = jax.jit(new), jax.jit(old), (temp, top_k)
        else:
            args = (temp, top_k)
        for seed in range(5):
            key = jax.random.PRNGKey(seed)
            got = np.asarray(new(logits, key, *args))
            want = np.asarray(old(logits, key, *args))
            assert got.dtype == np.int32 and got.shape == want.shape
            assert got.tolist() == want.tolist(), (case, seed)

    def test_the_rungs_differ_where_they_should(self):
        """The oracle comparison is not vacuous: a ``top_k`` changes a
        sampling row's ids, and a greedy row's never."""
        import jax
        from deeplearning4j_tpu.ops.sampling import sample_logits
        logits = _sampler_logits()
        temp = np.asarray([0, 1.5, 1.5, 0, 1.5, 1.5, 1.5, 1.5],
                          np.float32)
        free = [np.asarray(jax.jit(sample_logits)(
            logits, jax.random.PRNGKey(s), temp,
            np.zeros((8,), np.int32))) for s in range(5)]
        cut = [np.asarray(jax.jit(sample_logits)(
            logits, jax.random.PRNGKey(s), temp,
            np.full((8,), 2, np.int32))) for s in range(5)]
        best = np.argmax(logits, axis=-1)
        assert any((a != b).any() for a, b in zip(free, cut))
        assert all((a[[0, 3]] == best[[0, 3]]).all() for a in free + cut)

    def test_the_vocabulary_is_ordered_only_under_a_branch(self):
        """In the lowered program no sort and no top-k selection stands
        outside a region of the conditional: a batch that does not ask
        for one does not run one. One module, the four arguments."""
        import jax
        from deeplearning4j_tpu.ops.sampling import sample_logits
        lowered = jax.jit(sample_logits).lower(
            np.zeros((4, 1017), np.float32), jax.random.PRNGKey(0),
            np.zeros((4,), np.float32), np.zeros((4,), np.int32))
        assert _ordering_ops(lowered) == [("chlo.top_k", True),
                                          ("stablehlo.sort", True)]
        assert lowered.as_text().count("module @") == 1
        assert len(lowered.in_avals[0]) == 4
        # concrete arguments: the one rung is picked while tracing
        scalar = jax.jit(lambda x, k: sample_logits(x, k, 0.8, 40)) \
            .lower(np.zeros((4, 1017), np.float32),
                   jax.random.PRNGKey(0))
        assert _ordering_ops(scalar) == [("chlo.top_k", False)]
        greedy = jax.jit(lambda x, k: sample_logits(x, k, 0.0)) \
            .lower(np.zeros((4, 1017), np.float32),
                   jax.random.PRNGKey(0))
        assert _ordering_ops(greedy) == []


class TestDecodeEngine:
    def test_greedy_decode_matches_dense_reference(self):
        model, pool, eng = _engine()
        prompt = np.array([5, 9, 2, 7])
        got = list(eng.submit(prompt, 8))
        ref = list(model.reference_decode(eng.params, prompt, 8,
                                          eos_id=model.conf.eos_id))
        assert got == ref
        assert eng.retraces_since_warmup() == 0
        eng.shutdown()

    def test_a_top_k_request_joins_greedy_rows_and_leaves_again(self):
        """ISSUE 31: the decode program does the work its batch asks
        for, and the engine says which. Two greedy requests decode,
        one with a ``top_k`` joins them for three steps: no retrace,
        the greedy rows' tokens are the reference's, the steps name
        the rung they took and the counter counts them."""
        from deeplearning4j_tpu.common.telemetry import MetricsRegistry
        from deeplearning4j_tpu.serving.generative import \
            _sample_path_counter
        MetricsRegistry._reset_for_tests()
        # an eos outside the vocabulary: the sampled request's key folds
        # in the step count at its admission, which the loop's timing
        # sets, so no draw of it may end the request early
        conf = DecoderConfig.tiny()
        model, pool, eng = _engine(DecoderConfig(
            **{**conf.__dict__, "eos_id": conf.vocab_size}))
        try:
            # of one length: the reference compiles once a length
            prompts = [np.array([5, 9, 2, 7]), np.array([8, 3, 6, 1])]
            s1 = eng.submit(prompts[0], 16)
            s2 = eng.submit(prompts[1], 16)
            head = [s1.next(timeout=30) for _ in range(3)]
            s3 = eng.submit(np.array([4, 4, 1]), 4, temperature=0.9,
                            top_k=5)
            sampled = list(s3)
            got = [head + list(s1), list(s2)]
            eng.shutdown()
            assert len(sampled) == 4
            assert eng.retraces_since_warmup() == 0
            for prompt, tokens in zip(prompts, got):
                assert tokens == list(model.reference_decode(
                    eng.params, prompt, 16, eos_id=model.conf.eos_id))
            steps = [e["args"] for e in _spans()
                     if e["name"] == "generate.decode_step"]
            paths = [a["sample_path"] for a in steps]
            # its first token came from its prefill, three from steps
            assert paths.count("top_k") == 3
            assert [p for p, q in zip(paths, [None] + paths)
                    if p != q] == ["argmax", "top_k", "argmax"]
            assert all(a["sample_rows"] == a["bucket"] == 4
                       and a["sample_ordered"]
                       == (4 if a["sample_path"] == "top_k" else 0)
                       for a in steps)
            prefills = [e["args"] for e in _spans()
                        if e["name"] == "generate.prefill"]
            assert [(a["sample_path"], a["sample_rows"],
                     a["sample_ordered"]) for a in prefills] == [
                ("argmax", 1, 0), ("argmax", 1, 0), ("top_k", 1, 1)]
            count = _sample_path_counter()
            assert count.value(model="t-gen", path="top_k") == 3 + 1
            assert count.value(model="t-gen", path="argmax") \
                == len(steps) - 3 + 2
            assert count.value(model="t-gen", path="sort") == 0
        finally:
            eng.shutdown()
            MetricsRegistry._reset_for_tests()

    def test_the_decode_program_orders_nothing_outside_a_branch(self):
        """The sampler's conditional lives inside the one decode
        program: no sort or top-k selection outside a branch, one
        module, the arguments it had (parameters, cache, tokens,
        positions, tables, key, temperatures, top_ks)."""
        import jax
        model, pool, eng = _engine()
        try:
            args = _decode_args(eng)
            lowered = eng._decode_jit().lower(*args)
            assert _ordering_ops(lowered) == [("chlo.top_k", True),
                                              ("stablehlo.sort", True)]
            text = lowered.as_text()
            assert text.count("module @") == 1
            assert text.count("stablehlo.case") == 1
            assert len(jax.tree.leaves(lowered.in_avals)) \
                == len(jax.tree.leaves(args))
            # (and the merge of a joiner's first token into a step's
            # ids, one ``ids.at[row].set`` and nothing else)
            assert set(eng._jits) == {"prefill", "commit", "sample",
                                      "decode", "merge"}
            merge = eng._merge_jit().lower(
                np.zeros((4,), np.int32), np.int32(0),
                np.zeros((1,), np.int32)).as_text()
            assert "stablehlo.scatter" in merge \
                or "dynamic_update_slice" in merge
            assert "stablehlo.sort" not in merge
        finally:
            eng.shutdown()

    def test_multi_block_generation_chains_and_matches(self):
        """A completion long enough to cross several block
        boundaries — the table-chaining path, checked against the
        no-cache reference."""
        model, pool, eng = _engine(block=4, max_seq_len=48)
        prompt = np.array([3, 11, 29])
        got = list(eng.submit(prompt, 24))
        ref = list(model.reference_decode(eng.params, prompt, 24,
                                          eos_id=model.conf.eos_id))
        assert got == ref
        assert pool.live_blocks == 0        # freed on completion
        eng.shutdown()

    def test_eos_mid_batch_frees_blocks_while_others_decode(self):
        """Pick an eos_id that greedy decode is KNOWN to hit (learned
        from a reference run), then decode it next to a sequence that
        never hits EOS: the early one must leave the batch, free its
        blocks, and not perturb the survivor's tokens."""
        conf = DecoderConfig.tiny()
        probe = DecoderLM(conf)
        ref = list(probe.reference_decode(probe.init(),
                                          np.array([5, 9, 2, 7]), 8))
        eos = ref[3]                        # hit at step 4
        conf2 = DecoderConfig(**{**conf.__dict__, "eos_id": eos})
        model, pool, eng = _engine(conf2, decode_buckets=(4,))
        s1 = eng.submit(np.array([5, 9, 2, 7]), 8)
        s2 = eng.submit(np.array([8, 3]), 8)
        t1 = list(s1)
        t2 = list(s2)
        assert s1.reason == "eos" and t1 == ref[:4]
        assert s2.reason == "max_tokens" and len(t2) == 8
        ref2 = list(model.reference_decode(eng.params,
                                           np.array([8, 3]), 8,
                                           eos_id=eos))
        assert t2 == ref2                   # survivor undisturbed
        assert pool.live_blocks == 0
        assert eng.retraces_since_warmup() == 0
        eng.shutdown()

    def test_cancel_frees_blocks_mid_generation(self):
        model, pool, eng = _engine(decode_buckets=(4,))
        stream = eng.submit(np.array([5, 9, 2, 7]), 2000)
        assert stream.next(timeout=10) is not None
        assert pool.live_blocks > 0
        stream.cancel()
        deadline = time.monotonic() + 10
        while pool.live_blocks and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.live_blocks == 0
        assert stream.reason == "cancelled"
        eng.shutdown()

    def test_max_tokens_capped_by_pool_capacity(self):
        """max_tokens silently caps at the engine's max_seq_len so a
        greedy client cannot run a sequence past its block budget."""
        model, pool, eng = _engine(max_seq_len=24, block=8)
        stream = eng.submit(np.array([1, 2, 3, 4]), 10_000)
        toks = list(stream)
        assert len(toks) <= 24 - 4          # the hard capacity cap
        assert stream.reason in ("max_tokens", "eos")
        ref = list(model.reference_decode(eng.params,
                                          np.array([1, 2, 3, 4]),
                                          24 - 4,
                                          eos_id=model.conf.eos_id))
        assert toks == ref                  # capped run still exact
        assert pool.live_blocks == 0
        eng.shutdown()

    def test_submit_sheds_synchronously_when_pool_full(self):
        model, pool, eng = _engine(kv_blocks=3, block=8)  # 2 usable
        s = eng.submit(np.arange(2, 12), 4)               # 2 blocks
        with pytest.raises(PoolExhausted):
            eng.submit(np.arange(2, 12), 4)
        list(s)
        eng.shutdown()

    def test_submit_after_shutdown_restarts_worker(self):
        """Regression (dl4j-lint lock-discipline finding): shutdown
        used to leave ``_worker`` pointing at the joined thread, so a
        later submit enqueued onto a dead queue and its stream hung
        forever. Shutdown now swaps the worker out under the submit
        lock; a post-shutdown submit must see None, start a fresh
        worker, and stream a full, correct completion."""
        model, pool, eng = _engine()
        prompt = np.array([5, 9, 2, 7])
        list(eng.submit(prompt, 4))
        eng.shutdown()
        stream = eng.submit(prompt, 8)
        got = []
        for _ in range(8):
            t = stream.next(timeout=10)
            if t is None:
                break
            got.append(t)
        ref = list(model.reference_decode(eng.params, prompt, 8,
                                          eos_id=model.conf.eos_id))
        assert got == ref
        assert pool.live_blocks == 0
        eng.shutdown()

    def test_shutdown_submit_race_never_strands_stream(self):
        """Hammer shutdown against concurrent submits: every stream a
        submit returns must terminate — served by the old worker
        (drained before shutdown's join returns) or by the fresh one a
        post-shutdown submit starts — never parked on a dead queue."""
        model, pool, eng = _engine()
        prompt = np.array([5, 9, 2])
        streams, errs = [], []

        def submitter():
            for _ in range(6):
                try:
                    streams.append(eng.submit(prompt, 3))
                except PoolExhausted:
                    pass
                except Exception as e:       # noqa: BLE001
                    errs.append(e)

        threads = [threading.Thread(target=submitter)
                   for _ in range(3)]
        for t in threads:
            t.start()
        for _ in range(8):
            eng.shutdown()
            time.sleep(0.005)
        for t in threads:
            t.join()
        assert not errs
        import queue as _queue
        deadline = time.monotonic() + 30
        for s in streams:
            while s.reason is None:
                assert time.monotonic() < deadline, \
                    "stream stranded after shutdown/submit race"
                try:
                    s.next(timeout=0.5)
                except _queue.Empty:
                    pass
        assert sum(1 for s in streams
                   if s.reason in ("max_tokens", "eos")) == len(streams)
        eng.shutdown()
        assert pool.live_blocks == 0


def _cache_engine(kind):
    """A warmed engine over the model class ``kind`` names, its pool
    with state slots where the model has recurrent state."""
    if kind == "decoder":
        return _engine()[1:]
    from deeplearning4j_tpu.models.falcon_h1 import FalconH1LM
    model = FalconH1LM()
    c = model.conf
    pool = KVBlockPool(c.n_layers, 64, 8, c.n_kv_heads, c.head_dim,
                       state=model.state_shapes(), state_slots=5,
                       name="t-gen-h1")
    eng = DecodeEngine(model, model.init(), pool, name="t-gen-h1",
                       prompt_buckets=(16,), decode_buckets=(4,),
                       max_seq_len=64)
    eng.warmup()
    return pool, eng


@pytest.mark.parametrize("program", ["commit", "decode"])
@pytest.mark.parametrize("kind", ["decoder", "falcon-h1"])
def test_the_cache_is_updated_in_place(kind, program):
    """One donation rule for every model: the commit and the decode
    program alias each array of the cache to an output (K, V and the
    state kinds: no second pool is written), and the arrays the pool
    held before a call are gone after it."""
    pool, eng = _cache_engine(kind)
    try:
        t = eng.prompt_buckets[-1]
        if program == "commit":
            _, *new = eng._prefill_jit()(
                eng.params, np.zeros((1, t), np.int32),
                np.asarray([3], np.int32))
            jit = eng._commit_jit()
            args = (pool.arrays, tuple(new),
                    np.zeros((pool.blocks_for(t),), np.int32),
                    *eng._state_arg(np.int32(0)))
        else:
            jit = eng._decode_jit()
            args = _decode_args(eng)
        n = len(pool.arrays)
        assert n == (2 if kind == "decoder" else 4)
        lowered = jit.lower(*args)
        assert lowered.as_text().count("tf.aliasing_output") == n
        assert lowered.compile().memory_analysis().alias_size_in_bytes \
            == pool.pool_bytes + pool.state_bytes
        # and at run time: what went in is deleted, the pool holds
        # the program's own arrays
        before = pool.arrays
        out = jit(*args)
        cache = out if program == "commit" else out[1]
        pool.update_arrays(*cache)
        assert all(a.is_deleted() for a in before)
        assert not any(a.is_deleted() for a in pool.arrays)
        assert [a.shape for a in pool.arrays] == [a.shape for a in before]
    finally:
        eng.shutdown()


def _run_three(eng):
    """Three requests, the third joining while the first two decode:
    admits, steps, mid-batch retirement and a last lone row."""
    s1 = eng.submit(np.array([5, 9, 2, 7]), 6)
    s2 = eng.submit(np.array([8, 3]), 10)
    first = s1.next(timeout=30)
    s3 = eng.submit(np.array([4, 4, 1]), 5)
    out = [[first] + list(s1), list(s2), list(s3)]
    eng.shutdown()
    return out


def _spans(prefix="generate."):
    from deeplearning4j_tpu.common import telemetry
    return [e for e in telemetry.trace_events()
            if e["ph"] == "X" and e["name"].startswith(prefix)]


class TestEngineSpans:
    """ISSUE 26: the loop split where the work happens. One pass that
    admitted or stepped is one ``generate.iteration``; its children
    carry its ``iter``, lie inside it and do not overlap."""

    @pytest.fixture(autouse=True)
    def _clean_ring(self):
        from deeplearning4j_tpu.common.telemetry import MetricsRegistry
        MetricsRegistry._reset_for_tests()
        yield
        MetricsRegistry._reset_for_tests()

    def test_every_iteration_is_partitioned_by_its_children(self):
        model, pool, eng = _engine()
        _spans()                            # warm-up records no span
        assert not _spans()
        tokens = _run_three(eng)
        assert [len(t) for t in tokens][:2] == [6, 10]
        ev = _spans()
        its = [e for e in ev if e["name"] == "generate.iteration"]
        assert [e["args"]["iter"] for e in its] == \
            list(range(1, len(its) + 1))    # exactly one an iteration
        assert len(its) >= 9               # the ten tokens of s2
        for it in its:
            i = it["args"]["iter"]
            mine = [e for e in ev if e is not it
                    and e["args"].get("iter") == i]
            # (a generate.stall has no parent: it is written after
            # the fact, by the iteration that closed it)
            kids = sorted((e for e in mine if e["args"].get("parent")
                           == "generate.iteration"),
                          key=lambda e: e["ts"])
            assert kids, i
            # a pull directly under the iteration lands the step in
            # flight before an admission, or when its rows will not do
            assert {e["name"] for e in kids} <= {
                "generate.admit", "generate.build", "generate.pull",
                "generate.decode_step", "generate.emit"}
            edge = it["ts"]
            for k in kids:                  # inside, in order, disjoint
                assert k["ts"] >= edge
                edge = k["ts"] + k["dur"]
            assert edge <= it["ts"] + it["dur"]
            for step in (k for k in kids
                         if k["name"] == "generate.decode_step"):
                inner = sorted((e for e in mine
                                if e["args"].get("parent")
                                == "generate.decode_step"),
                               key=lambda e: e["ts"])
                # the pull is of the step before: a step dispatched
                # with nothing in flight has none
                assert [e["name"] for e in inner] in (
                    ["generate.dispatch", "generate.pull"],
                    ["generate.dispatch"])
                assert inner[0]["args"]["program"] == "decode_step"
                assert step["ts"] <= inner[0]["ts"]
                edge_in = inner[0]["ts"] + inner[0]["dur"]
                for e in inner[1:]:
                    assert edge_in <= e["ts"]
                    edge_in = e["ts"] + e["dur"]
                assert edge_in <= step["ts"] + step["dur"]
        steps = [e for e in ev if e["name"] == "generate.decode_step"]
        pulls = [e for e in ev if e["name"] == "generate.pull"]
        # every step is pulled once, some of them after later ones
        # were dispatched; an admission into an engine with no step in
        # flight adds one pull, of its first tokens, and prefills queued
        # behind steps are pulled on their own or with their step
        idle = [e for e in ev if e["name"] == "generate.admit"
                and e["args"]["admitted"] and not e["args"]["behind"]]
        assert idle
        assert len(steps) + len(idle) <= len(pulls) \
            <= len(steps) + len(_named(ev, "generate.prefill"))
        assert any(e["args"]["parent"] == "generate.decode_step"
                   for e in pulls)
        # every span of the family belongs to some iteration
        assert all(e["args"].get("iter") for e in ev)

    def test_the_counts_ride_on_the_spans(self):
        model, pool, eng = _engine()
        served = sum(len(t) for t in _run_three(eng))
        ev = _spans()
        steps = [e["args"] for e in ev
                 if e["name"] == "generate.decode_step"]
        assert steps and all(
            1 <= a["live"] <= a["bucket"] == 4
            and 1 <= a["pool_live"] <= a["pool_usable"] == 63
            and a["grid_blocks"] == 4 * eng.max_blocks == 32
            for a in steps)
        assert max(a["live"] for a in steps) == 3
        # a row holds at least one block: the pool's count follows
        assert all(a["pool_live"] >= a["live"] for a in steps)
        prefills = [e["args"] for e in ev
                    if e["name"] == "generate.prefill"]
        assert [a["seq"] for a in prefills] == [1, 2, 3]
        assert [a["tokens"] for a in prefills] == [4, 2, 3]
        for a in prefills:                  # no ctx was passed
            assert a["parent"] == "generate.admit" and "trace" not in a
            assert a["bucket"] == 16 and a["queue_ms"] >= 0
        admits = [e["args"] for e in ev if e["name"] == "generate.admit"]
        assert sum(a["admitted"] for a in admits) == 3
        emits = [e["args"] for e in ev if e["name"] == "generate.emit"]
        # every token but each request's first, which its prefill gave
        assert sum(a["tokens"] for a in emits) == served - 3
        assert sum(a["retired"] for a in emits) == 3

    def test_a_request_context_puts_its_trace_id_on_the_prefill(self):
        from deeplearning4j_tpu.common import tracectx
        model, pool, eng = _engine()
        ctx = tracectx.start("t-gen", "generate")
        list(eng.submit(np.array([5, 9, 2, 7]), 3, ctx=ctx))
        eng.shutdown()
        (prefill,) = [e for e in _spans()
                      if e["name"] == "generate.prefill"]
        assert prefill["args"]["trace"] == ctx.trace_id
        queue = [e for e in _spans("req.") if e["name"] == "req.queue"]
        assert len(queue) == 1
        # the request's phases and the engine's spans share the clock
        assert abs(queue[0]["ts"] + queue[0]["dur"]
                   - prefill["ts"]) < 1000

    def test_an_idle_engine_records_nothing(self):
        model, pool, eng = _engine()
        list(eng.submit(np.array([5, 9]), 2))
        time.sleep(0.2)     # the last iteration's span closes after
        n = len(_spans())   # the consumer has its last token
        time.sleep(0.3)                     # six idle polls
        assert len(_spans()) == n
        eng.shutdown()

    def test_telemetry_off_same_tokens_and_an_empty_ring(
            self, monkeypatch):
        from deeplearning4j_tpu.common import telemetry
        from deeplearning4j_tpu.common.environment import Environment
        on = _run_three(_engine()[2])
        assert _spans()
        monkeypatch.setenv("DL4J_TPU_TELEMETRY", "0")
        Environment.reset()
        telemetry.MetricsRegistry._reset_for_tests()
        try:
            assert not telemetry.enabled()
            off = _run_three(_engine()[2])
            assert telemetry.trace_events() == []
        finally:
            monkeypatch.delenv("DL4J_TPU_TELEMETRY")
            Environment.reset()
        assert on == off

    def test_the_spans_have_twins_in_a_profile(self, tmp_path):
        """Where ``jax.profiler`` can trace this machine: every
        ``generate.*`` ring span lies in the ``/host:CPU`` plane too,
        within 100 us of the ring's stamp mapped as the benchmark maps
        it (``lo + (perf_counter_of(ts) - ta)`` off a ``cb.window``
        annotation)."""
        import glob
        import jax
        from deeplearning4j_tpu.common import telemetry
        model, pool, eng = _engine()
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(tmp_path),
                                     profiler_options=opts)
        except Exception as e:              # noqa: BLE001
            eng.shutdown()
            pytest.skip(f"jax.profiler cannot trace here: {e}")
        try:
            with jax.profiler.TraceAnnotation("cb.window"):
                ta = time.perf_counter()
                _run_three(eng)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        data = jax.profiler.ProfileData.from_file(sorted(files)[-1])
        host = [p for p in data.planes if p.name == "/host:CPU"]
        assert len(host) == 1
        twins = {}
        for line in host[0].lines:
            for e in line.events:
                if e.name.startswith(("generate.", "cb.")):
                    twins.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9, e.duration_ns * 1e-9))
        (lo, _), = twins["cb.window"]
        ring = _spans()
        assert len(ring) > 40
        off = []
        # (generate.stall and generate.prefill are written after the
        # fact with ``span_at``: an interval that is over has no
        # annotation to open)
        for name in {e["name"] for e in ring} - {"generate.stall",
                                                 "generate.prefill"}:
            mine = sorted((lo + telemetry.perf_counter_of(e["ts"]) - ta,
                           e["dur"] * 1e-6)
                          for e in ring if e["name"] == name)
            theirs = sorted(twins[name])
            assert len(mine) == len(theirs), name
            off += [abs(a[0] - b[0]) for a, b in zip(mine, theirs)]
            off += [abs(a[0] + a[1] - b[0] - b[1])
                    for a, b in zip(mine, theirs)]
        # a stall of this shared CPU between the two stamps of one
        # span is not the clock's: the typical span must agree
        assert np.percentile(off, 90) < 100e-6, np.percentile(
            off, [50, 90, 100])


class TestStepInFlight:
    """Dispatched steps stay in flight: the next is built on the rows
    of the last and dispatched before the oldest is pulled, an
    admission lands them first, and what they computed for a row that
    retired is dropped."""

    @pytest.fixture(autouse=True)
    def _clean_ring(self):
        from deeplearning4j_tpu.common.telemetry import MetricsRegistry
        MetricsRegistry._reset_for_tests()
        yield
        MetricsRegistry._reset_for_tests()

    @staticmethod
    def _alone(model, eng, prompt, n):
        return list(model.reference_decode(
            eng.params, np.asarray(prompt), n,
            eos_id=model.conf.eos_id))

    def test_the_next_step_is_dispatched_before_the_last_is_pulled(self):
        model, pool, eng = _engine()
        got = list(eng.submit(np.array([5, 9, 2, 7]), 12))
        eng.shutdown()
        assert got == self._alone(model, eng, [5, 9, 2, 7], 12)
        ev = _spans()
        steps = sorted((e for e in ev
                        if e["name"] == "generate.decode_step"),
                       key=lambda e: e["ts"])
        assert len(steps) == 11             # the first token: prefill
        inner = [sorted((e for e in ev if e["args"].get("parent")
                         == "generate.decode_step"
                         and e["args"]["iter"] == s["args"]["iter"]),
                        key=lambda e: e["ts"]) for s in steps]
        from deeplearning4j_tpu.serving.generative import RUN_AHEAD
        for alone in inner[:RUN_AHEAD]:
            assert [e["name"] for e in alone] == ["generate.dispatch"]
        for pair in inner[RUN_AHEAD:]:
            assert [e["name"] for e in pair] == [
                "generate.dispatch", "generate.pull"]
        # the first token lands under the iteration that admitted it
        # into an idle engine, before the first step; the last steps
        # under the one that finds no row left for another
        landed = sorted((e for e in ev if e["name"] == "generate.pull"
                         and e["args"].get("parent")
                         == "generate.iteration"), key=lambda e: e["ts"])
        assert len(landed) == RUN_AHEAD + 1
        assert landed[0]["ts"] < steps[0]["ts"]
        assert all(e["ts"] > steps[-1]["ts"] for e in landed[1:])

    @pytest.mark.parametrize("lengths", [(3, 9, 6), (9, 3, 6),
                                         (2, 2, 12), (12, 5, 5)])
    def test_rows_keep_their_places_and_leave_holes(self, lengths):
        model, pool, eng = _engine(decode_buckets=(4,))
        prompts = [[5, 9, 2, 7], [8, 3], [4, 4, 1]]
        streams = [eng.submit(np.array(p), n)
                   for p, n in zip(prompts, lengths)]
        got = [list(s) for s in streams]
        eng.shutdown()
        for p, n, g in zip(prompts, lengths, got):
            assert g == self._alone(model, eng, p, n)
        assert pool.live_blocks == 0
        assert eng.retraces_since_warmup() == 0
        steps = sorted((e for e in _spans()
                        if e["name"] == "generate.decode_step"),
                       key=lambda e: e["ts"])
        # a step a row needs, and some more where its last token was
        # an EOS, which the host sees that many steps late
        from deeplearning4j_tpu.serving.generative import RUN_AHEAD
        longest = max(len(g) for g in got)
        assert longest - 1 <= len(steps) <= longest - 1 + RUN_AHEAD
        assert {a["args"]["bucket"] for a in steps} == {4}
        if all(len(g) == n for g, n in zip(got, lengths)):
            # a row that ends on max_tokens leaves a hole and the loop
            # runs on: nothing lands but at the very end
            # (after the landing of the first tokens, which came back
            # before the first step was built)
            landed = [e for e in _spans() if e["name"] == "generate.pull"
                      and e["args"].get("parent") == "generate.iteration"
                      and e["ts"] > steps[0]["ts"]]
            assert len(landed) == min(RUN_AHEAD, len(steps))
            assert all(e["ts"] > steps[-1]["ts"] for e in landed)
        lives = [a["args"]["live"] for a in steps]
        assert lives == sorted(lives, reverse=True)
        assert lives[0] == sum(n > 1 for n in lengths)

    @pytest.mark.parametrize("buckets", [(2,), (2, 4)])
    def test_more_sequences_than_rows_or_a_smaller_bucket(self, buckets):
        """Three sequences on two rows: the third steps when a row is
        free; and with two buckets the rows are packed into the
        smaller one once they fit. Either way the step in flight is
        landed and the rows are built afresh; the one admission (all
        three at once, into an idle engine) joined nothing."""
        model, pool, eng = _engine(decode_buckets=buckets)
        ctl = _Stepper(eng)
        ctl.free = False                    # all three admitted at once
        prompts, lengths = [[5, 9, 2, 7], [8, 3], [4, 4, 1]], (4, 9, 7)
        streams = [eng.submit(np.array(p), n)
                   for p, n in zip(prompts, lengths)]
        assert ctl.parked.wait(60)
        ctl.go()
        got = [list(s) for s in streams]
        eng.shutdown()
        for p, n, g in zip(prompts, lengths, got):
            assert g == self._alone(model, eng, p, n)
        assert pool.live_blocks == 0
        assert eng.retraces_since_warmup() == 0
        ev = _spans()
        used = {e["args"]["bucket"] for e in ev
                if e["name"] == "generate.decode_step"}
        assert used == set(buckets)
        (admit,) = _named(ev, "generate.admit")
        assert (admit["args"]["admitted"], admit["args"]["behind"],
                admit["args"]["joined"]) == (3, 0, 0)
        first = _named(ev, "generate.decode_step")[0]
        assert [e for e in _named(ev, "generate.pull",
                                  parent="generate.iteration")
                if first["ts"] < e["ts"]]

    def test_an_admission_with_a_hole_joins_the_queued_steps(self):
        """The prefill is dispatched behind the steps in flight, not
        after a landing; the next step takes the joiner into a hole of
        its bucket; the joiner's first token reaches its stream before
        its second; both streams are the tokens each gets alone."""
        from deeplearning4j_tpu.common import telemetry, tracectx
        model, pool, eng = _engine(decode_buckets=(4,))
        s1 = eng.submit(np.array([5, 9, 2, 7]), 40)
        head = [s1.next(timeout=30), s1.next(timeout=30)]
        ctx = tracectx.start("t-gen", "generate")
        s2 = eng.submit(np.array([8, 3]), 6, ctx=ctx)
        t2 = list(s2)
        t1 = head + list(s1)
        eng.shutdown()
        assert t2 == self._alone(model, eng, [8, 3], 6)
        assert t1 == self._alone(model, eng, [5, 9, 2, 7], 40)
        assert eng.retraces_since_warmup() == 0
        ev = _spans()
        admits = _named(ev, "generate.admit")
        assert [(a["args"]["admitted"], a["args"]["behind"],
                 a["args"]["joined"]) for a in admits] == [(1, 0, 0),
                                                           (1, 1, 1)]
        i = admits[1]["args"]["iter"]

        def kids(j):
            return [k["name"] for k in sorted(
                (k for k in ev if k["args"].get("iter") == j
                 and k["args"].get("parent") == "generate.iteration"),
                key=lambda k: k["ts"])]
        # no landing: nothing is pulled before the admission, and after
        # it the one program that is due, the oldest step in flight;
        # the admitting pass builds no step, the next one does
        assert kids(i) == ["generate.admit", "generate.pull",
                           "generate.emit"]
        assert kids(i + 1)[:2] == ["generate.build",
                                   "generate.decode_step"]
        (step,) = _named(ev, "generate.decode_step", iter=i + 1)
        assert step["args"]["live"] == 2 and step["args"]["bucket"] == 4
        # the joiner's first token is read back once it is the oldest
        # thing in flight, and handed out before its second
        (prefill,) = _named(ev, "generate.prefill", seq=s2.seq_id)
        assert prefill["args"]["parent"] == "generate.admit" \
            and prefill["args"]["iter"] == i
        second = [e for e in telemetry.trace_events()
                  if e["name"] == "req.inter_token"
                  and e["args"]["trace"] == ctx.trace_id
                  and e["args"]["index"] == 1]
        assert len(second) == 1
        assert prefill["ts"] + prefill["dur"] <= second[0]["ts"]
        counted = telemetry.counter("dl4j_generate_admissions_total", "")
        assert counted.value(model="t-gen", path="idle") == 1
        assert counted.value(model="t-gen", path="joined") == 1

    @pytest.mark.parametrize("how", ["eos", "cancel", "deadline"])
    def test_what_a_step_computed_for_a_retired_row_is_dropped(
            self, how):
        conf = DecoderConfig.tiny()
        probe = DecoderLM(conf)
        ref = list(probe.reference_decode(
            probe.init(), np.array([5, 9, 2, 7]), 8))
        # no EOS but the planted one, and room for 250 tokens, so the
        # row is still decoding when it is told to leave
        conf = DecoderConfig(**{
            **conf.__dict__,
            "eos_id": ref[3] if how == "eos" else conf.vocab_size})
        model, pool, eng = _engine(conf, decode_buckets=(4,),
                                   max_seq_len=256, kv_blocks=80)
        s2 = eng.submit(np.array([8, 3]), 30)
        s1 = eng.submit(np.array([5, 9, 2, 7]),
                        8 if how == "eos" else 2000,
                        deadline=time.monotonic() + 600)
        first = []
        if how != "eos":
            first = [s1.next(timeout=30)]
            assert first[0] is not None
        if how == "cancel":
            s1.cancel()
        elif how == "deadline":             # it passes mid-generation
            seq = eng._live.get(s1.seq_id)
            if seq is not None:
                seq.deadline = time.monotonic()
        t1, t2 = first + list(s1), list(s2)
        eng.shutdown()
        assert s1.reason == {"eos": "eos", "cancel": "cancelled",
                             "deadline": "deadline"}[how]
        if how == "eos":
            assert t1 == ref[:4]            # nothing after the EOS
        else:
            full = self._alone(model, eng, [5, 9, 2, 7], len(t1))
            assert t1 == full               # a prefix of its own run
        assert t2 == self._alone(model, eng, [8, 3], 30)
        assert pool.live_blocks == 0
        assert eng.retraces_since_warmup() == 0


class _Stepper:
    """Parks the engine's loop after a pass when told to, so that a
    test decides what is queued when the next pass looks."""

    def __init__(self, eng):
        self.free, self.sem = True, threading.Semaphore(0)
        self.parked = threading.Event()
        once = eng._decode_iteration

        def gated(*a):
            queued = once(*a)
            if not self.free:
                self.parked.set()
                assert self.sem.acquire(timeout=60)
            return queued
        eng._decode_iteration = gated

    def park(self):
        """Returns once the loop stands still at the end of a pass."""
        self.parked.clear()
        self.free = False
        assert self.parked.wait(60)

    def one_pass(self):
        self.parked.clear()
        self.sem.release()
        assert self.parked.wait(60)

    def go(self):
        self.free = True
        self.sem.release()


def _named(ev, name, **args):
    return sorted((e for e in ev if e["name"] == name
                   and all(e["args"].get(k) == v
                           for k, v in args.items())),
                  key=lambda e: e["ts"])


def _inside(inner, outer):
    return outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"] \
        <= outer["ts"] + outer["dur"]


class TestAdmissionRecords:
    """ISSUE 36: the admission measured where it happens. Counts and
    order only; no time is asserted beyond ``end >= start`` and which
    span an instant lies in.

    An admission into an engine with **no live row** writes no
    ``generate.stall`` at all (and observes nothing): nobody was
    waiting between two tokens, and the last emit it would start from
    belongs to another burst of work."""

    @pytest.fixture(autouse=True)
    def _clean_ring(self):
        from deeplearning4j_tpu.common.telemetry import MetricsRegistry
        MetricsRegistry._reset_for_tests()
        yield
        MetricsRegistry._reset_for_tests()

    @staticmethod
    def _observed():
        from deeplearning4j_tpu.serving.generative import _stall_hist
        return _stall_hist().count_of(model="t-gen")

    @staticmethod
    def _decoding(eng, n=40):
        """One request decoding with steps in flight, the loop parked."""
        ctl = _Stepper(eng)
        s1 = eng.submit(np.array([5, 9, 2, 7]), n)
        assert s1.next(timeout=30) is not None
        assert s1.next(timeout=30) is not None
        ctl.park()
        return ctl, s1

    @staticmethod
    def _emit_at(ev, t):
        """The one ``generate.emit`` that the instant lies in."""
        (e,) = [e for e in _named(ev, "generate.emit")
                if e["ts"] <= t <= e["ts"] + e["dur"]]
        return e

    def _steps_of(self, ev, i):
        """The decode steps dispatched by iterations ``i - 1`` and
        ``i + 1`` (before and after an admission at ``i``, whose pass
        dispatches none) and the emits that handed their tokens out:
        each program in flight is pulled by the pass that queues
        ``RUN_AHEAD`` more behind it, and the admission's pass queues a
        prefill where it queues no step."""
        from deeplearning4j_tpu.serving.generative import RUN_AHEAD
        out = []
        for j in (i - 1, i + 1):
            (step,) = _named(ev, "generate.decode_step", iter=j)
            (emit,) = _named(ev, "generate.emit", iter=j + RUN_AHEAD)
            out.append((step, emit))
        return out

    def test_an_admission_adds_one_record_and_none_to_its_iteration(
            self):
        """The admitting iteration lands nothing and builds no step:
        after ``generate.admit`` it pulls and emits the oldest step in
        flight, which is due, and the next iteration builds and
        dispatches the step that takes the joiner, a step like any
        other. The prefill's record is written when its first token is
        pulled (its ``parent`` and ``iter`` the admission's); the stall
        is the one new record, and it belongs to the iteration that
        closed it."""
        model, pool, eng = _engine()
        ctl, s1 = self._decoding(eng)
        s2 = eng.submit(np.array([8, 3]), 6)
        ctl.go()
        assert len(list(s2)) == 6 and len(list(s1)) == 38
        eng.shutdown()
        ev = _spans()
        assert all(e["dur"] >= 0 for e in ev)
        second = _named(ev, "generate.admit", admitted=1)[1]
        assert second["args"]["behind"] == second["args"]["joined"] == 1
        i = second["args"]["iter"]
        mine = [e for e in ev if e["args"].get("iter") == i]
        after = [e for e in ev if e["args"].get("iter") == i + 1]
        assert not _named(mine + after, "generate.stall")

        def kids(of):
            return sorted((e for e in of if e["args"].get("parent")
                           == "generate.iteration"),
                          key=lambda e: e["ts"])
        assert [e["name"] for e in kids(mine)] == [
            "generate.admit", "generate.pull", "generate.emit"]
        assert [e["name"] for e in kids(after)] == [
            "generate.build", "generate.decode_step", "generate.emit"]
        (prefill,) = _named(mine, "generate.prefill")
        assert prefill["args"]["parent"] == "generate.admit"
        assert second["ts"] <= prefill["ts"] \
            <= second["ts"] + second["dur"]
        assert prefill["ts"] + prefill["dur"] > kids(after)[-1]["ts"]
        assert not [e for e in ev
                    if e["args"].get("parent") == "generate.prefill"]
        # a step like any other: one dispatch of the decode program,
        # the pull of the oldest program in flight behind it
        assert [(e["name"], e["args"].get("program")) for e in after
                if e["args"].get("parent") == "generate.decode_step"] \
            == [("generate.dispatch", "decode_step"),
                ("generate.pull", None)]
        assert kids(after)[1]["args"]["live"] == 2
        assert {e["name"] for e in ev} == {
            "generate.iteration", "generate.admit", "generate.prefill",
            "generate.build", "generate.decode_step",
            "generate.dispatch", "generate.pull", "generate.emit",
            "generate.stall"}
        assert len(_named(ev, "generate.dispatch")) \
            == len(_named(ev, "generate.decode_step"))

    def test_a_stall_carries_the_rows_that_waited(self):
        """In device order: from the emit of the last step dispatched
        before the prefill to the emit of the first dispatched after
        it; between them only the prefill's first token is pulled and
        handed out, the one program that ran between the two steps."""
        model, pool, eng = _engine()
        ctl, s1 = self._decoding(eng)
        s2 = eng.submit(np.array([8, 3]), 6)
        ctl.go()
        list(s2), list(s1)
        eng.shutdown()
        ev = _spans()
        (stall,) = _named(ev, "generate.stall")
        a = stall["args"]
        assert set(a) == {"model", "iter", "rows", "prefills",
                          "prompt_tokens"}
        assert a["rows"] == 1 and a["prefills"] == 1 \
            and a["prompt_tokens"] == 2 and a["model"] == "t-gen"
        admit = _named(ev, "generate.admit", admitted=1)[1]
        (_, opened), (closing, closed) = self._steps_of(
            ev, admit["args"]["iter"])
        assert opened is self._emit_at(ev, stall["ts"])
        assert closed is self._emit_at(ev, stall["ts"] + stall["dur"])
        (between,) = [e for e in _named(ev, "generate.emit")
                      if opened["ts"] < e["ts"] < closed["ts"]]
        assert between["args"]["tokens"] == 0 \
            and between["args"]["iter"] == closed["args"]["iter"] - 1
        assert closing["args"]["live"] == closed["args"]["tokens"] == 2
        assert closed["args"]["iter"] == a["iter"]
        (prefill,) = [e for e in _named(ev, "generate.prefill")
                      if e["args"]["iter"] == admit["args"]["iter"]]
        assert prefill["ts"] < stall["ts"] \
            < prefill["ts"] + prefill["dur"] <= between["ts"] \
            + between["dur"] <= closed["ts"]
        assert self._observed() == 1

    def test_two_requests_queued_together_are_one_episode(self):
        model, pool, eng = _engine()
        ctl, s1 = self._decoding(eng)
        s2 = eng.submit(np.array([8, 3]), 6)
        s3 = eng.submit(np.array([4, 4, 1]), 5)
        ctl.go()
        assert len(list(s2)) == 6 and list(s3)
        list(s1)
        eng.shutdown()
        ev = _spans()
        assert [(e["args"]["admitted"], e["args"]["joined"])
                for e in _named(ev, "generate.admit")][:2] \
            == [(1, 0), (2, 2)]
        (stall,) = _named(ev, "generate.stall")
        a = stall["args"]
        assert a["prefills"] == 2 and a["prompt_tokens"] == 5
        assert a["rows"] == 1               # s1 alone was decoding
        # both first tokens are read back inside it, with its last step
        end = stall["ts"] + stall["dur"]
        assert sum(stall["ts"] <= e["ts"] + e["dur"] <= end
                   for e in _named(ev, "generate.prefill")) == 2
        assert self._observed() == 1

    def test_admissions_in_two_passes_in_a_row_are_one_episode(self):
        """A pass that admits behind the steps in flight builds no
        step, but never two passes in a row: the second admitting pass
        builds the step that takes both joiners, which closes the one
        episode they make."""
        model, pool, eng = _engine()
        ctl, s1 = self._decoding(eng)
        s2 = eng.submit(np.array([8, 3]), 6)
        ctl.one_pass()                      # admits s2, builds no step
        s3 = eng.submit(np.array([4, 4, 1]), 5)
        ctl.go()
        assert len(list(s2)) == 6 and list(s3)
        list(s1)
        eng.shutdown()
        ev = _spans()
        admits = _named(ev, "generate.admit", admitted=1)
        i = admits[1]["args"]["iter"]
        assert admits[2]["args"]["iter"] == i + 1
        assert [e["args"]["joined"] for e in admits] == [0, 1, 1]
        assert not _named(ev, "generate.decode_step", iter=i)
        (step,) = _named(ev, "generate.decode_step", iter=i + 1)
        assert step["args"]["live"] == 3
        (stall,) = _named(ev, "generate.stall")
        assert stall["args"]["prefills"] == 2 \
            and stall["args"]["rows"] == 1
        assert self._observed() == 1

    def test_an_episode_closes_at_the_first_emit_after_wherever_it_is(
            self):
        """Two admissions with a step dispatched between them are two
        episodes: the step after the first admitting pass closes the
        first and, being the last step before the second prefill, opens
        the second; its emit is both ends."""
        model, pool, eng = _engine()
        ctl, s1 = self._decoding(eng)
        s2 = eng.submit(np.array([8, 3]), 9)
        ctl.one_pass()                      # admits s2
        ctl.one_pass()                      # dispatches the step for it
        s3 = eng.submit(np.array([4, 4, 1]), 5)
        ctl.go()
        assert len(list(s2)) == 9 and list(s3)
        list(s1)
        eng.shutdown()
        ev = _spans()
        first, second = _named(ev, "generate.stall")
        admits = _named(ev, "generate.admit", admitted=1)
        assert admits[2]["args"]["iter"] == admits[1]["args"]["iter"] + 2
        assert [e["args"]["joined"] for e in admits] == [0, 1, 1]
        (_, opened), (closing, closed) = self._steps_of(
            ev, admits[1]["args"]["iter"])
        assert opened is self._emit_at(ev, first["ts"])
        assert closed is self._emit_at(ev, first["ts"] + first["dur"])
        assert first["args"]["iter"] == closed["args"]["iter"]
        assert first["args"]["rows"] == 1 \
            and closed["args"]["tokens"] == 2
        # the second starts where the first ended: the same emit
        assert abs(second["ts"] - first["ts"] - first["dur"]) <= 2
        (_, reopened), (_, reclosed) = self._steps_of(
            ev, admits[2]["args"]["iter"])
        assert reopened is closed
        assert reclosed is self._emit_at(ev, second["ts"] + second["dur"])
        assert second["args"]["rows"] == 2
        assert second["args"]["iter"] == first["args"]["iter"] + 2
        assert self._observed() == 2

    def test_an_admission_into_an_idle_engine_writes_no_stall(self):
        model, pool, eng = _engine()
        assert len(list(eng.submit(np.array([5, 9, 2, 7]), 8))) == 8
        time.sleep(0.2)                     # idle: the loop only polls
        assert len(list(eng.submit(np.array([8, 3]), 8))) == 8
        eng.shutdown()
        ev = _spans()
        assert len(_named(ev, "generate.prefill")) == 2
        assert not _named(ev, "generate.stall")
        assert self._observed() == 0

    @pytest.mark.parametrize("joiners", [1, 2])
    def test_the_benchmarks_reader_reads_this_engines_ring(self, joiners):
        """``chipbench/readers/admit_stall.py`` and ``span_attr``, as
        the benchmark's metric files name them, over what a real engine
        wrote: the names the program writes and the names the readers
        look for are the same names: one or two joiners, admitted
        together into the holes of a bucket of four. ``span_attr`` also
        reads the share of the prefills dispatched behind the steps in
        flight that joined them, ``joined`` over ``behind``."""
        from chipbench import harness
        from chipbench.readers import span_attr
        model, pool, eng = _engine()
        ta = time.perf_counter()
        ctl, s1 = self._decoding(eng)
        streams = [eng.submit(np.array(p), 6)
                   for p in ([8, 3], [4, 4, 1])[:joiners]]
        ctl.go()
        [list(s) for s in streams], list(s1)
        eng.shutdown()
        tb = time.perf_counter()
        view = {"host_window": (ta, tb), "window": (50.0, 50.0 + tb - ta),
                "records": {"t0": ta, "t_end": tb}}
        ev = _spans()
        (stall,) = _named(ev, "generate.stall")
        got = harness.read_metrics(
            ["admit_stall_ms_p50", "admit_stall_ms_p95", "admit_gap_share",
             "prefill_ms_p50"], view, "t-gen")
        assert got["admit_stall_ms_p50"] \
            == pytest.approx(stall["dur"] * 1e-3, abs=2e-3)
        assert got["admit_stall_ms_p95"] == got["admit_stall_ms_p50"]
        assert got["admit_gap_share"] == pytest.approx(
            100 / sum(e["args"]["tokens"]
                      for e in _named(ev, "generate.emit")))
        # every prefill admitted behind the steps in flight joined them
        assert span_attr.read(view, span="generate.admit", num="joined",
                              den="behind", stat="mean") == 100.0
        behind = [e for e in _named(ev, "generate.admit")
                  if e["args"]["behind"]]
        assert [e["args"]["joined"] for e in behind] == [joiners]
        # the prefill spans reach their first tokens' pull
        assert got["prefill_ms_p50"] > 0

    @pytest.mark.parametrize("buckets", [(2,), (2, 4)])
    def test_a_landing_for_the_rows_sake_is_no_admission(self, buckets):
        """More sequences than rows, or a smaller bucket: the steps in
        flight are landed with no prefill behind them, so no episode
        opens and no stall is written for it (nor for the burst into
        the idle engine that began it)."""
        model, pool, eng = _engine(decode_buckets=buckets)
        ctl = _Stepper(eng)
        ctl.free = False                    # all three admitted at once
        streams = [eng.submit(np.array(p), n) for p, n in zip(
            [[5, 9, 2, 7], [8, 3], [4, 4, 1]], (4, 9, 7))]
        assert ctl.parked.wait(60)
        ctl.go()
        assert all(list(s) for s in streams)
        eng.shutdown()
        ev = _spans()
        (admit,) = _named(ev, "generate.admit", admitted=3)
        assert admit["args"]["joined"] == admit["args"]["behind"] == 0
        landed = [e for e in _named(ev, "generate.pull")
                  if e["args"].get("parent") == "generate.iteration"
                  and e["ts"] > admit["ts"]]
        assert landed
        assert not _named(ev, "generate.stall")
        assert self._observed() == 0

    def test_more_joiners_than_holes_land_the_steps_and_pack_afresh(
            self):
        """Two requests behind steps whose bucket of two has one hole:
        both are dispatched behind the queue, then the next step does
        not fit them, so what is in flight lands (first tokens and
        all) and the rows are packed afresh, one sequence waiting for a
        row. ``generate.admit`` says so (``joined`` 0 of ``behind``
        2), and so does the counter; every stream is its own run's."""
        from deeplearning4j_tpu.common import telemetry
        model, pool, eng = _engine(decode_buckets=(2,))
        ctl, s1 = self._decoding(eng)
        s2 = eng.submit(np.array([8, 3]), 6)
        s3 = eng.submit(np.array([4, 4, 1]), 5)
        ctl.go()
        t2, t3 = list(s2), list(s3)
        list(s1)
        eng.shutdown()
        alone = TestStepInFlight._alone
        assert t2 == alone(model, eng, [8, 3], 6)
        assert t3 == alone(model, eng, [4, 4, 1], 5)
        assert eng.retraces_since_warmup() == 0 and pool.live_blocks == 0
        ev = _spans()
        admit = _named(ev, "generate.admit", admitted=2)[0]
        assert admit["args"]["behind"] == 2 and admit["args"]["joined"] == 0
        i = admit["args"]["iter"]
        assert _named(ev, "generate.pull", iter=i,
                      parent="generate.iteration")
        counted = telemetry.counter("dl4j_generate_admissions_total", "")
        assert counted.value(model="t-gen", path="landed") == 2
        assert counted.value(model="t-gen", path="joined") == 0
        # the episode's rows waited through the landing and the restart
        (stall,) = _named(ev, "generate.stall")
        assert stall["args"]["prefills"] == 2 and stall["args"]["rows"] == 1

    @pytest.mark.parametrize("kind", ["decoder", "falcon-h1"])
    def test_a_step_span_carries_no_count_of_state_slots(self, kind):
        pool, eng = _cache_engine(kind)
        list(eng.submit(np.array([5, 9, 2, 7]), 4))
        eng.shutdown()
        steps = _named(_spans(), "generate.decode_step")
        assert steps and all(
            not {"state_live", "state_slots"} & set(e["args"])
            and e["args"]["live"] == 1 for e in steps)
        assert bool(pool.state) == (kind != "decoder")
        if pool.state:                      # the pool still says it
            assert pool.report()["state"]["slots"]["live"] == 0


def _mesh_1d():
    import jax

    from deeplearning4j_tpu.parallel.mesh import make_mesh
    return make_mesh({"data": 8}, jax.devices()[:8])


class TestResidencyComposition:
    @pytest.mark.parametrize("mode", ["sharded", "fsdp"])
    def test_sharded_residency_tokens_equal_dense(self, mode):
        """mode="fsdp"/"sharded" on the virtual 8-device mesh must
        stream exactly the dense tokens — the generative version of
        the residency bitwise guarantee."""
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the virtual 8-device mesh")
        from deeplearning4j_tpu.serving.batcher import ServingBatcher
        conf = DecoderConfig.tiny()
        gen_cfg = {"kv_blocks": 32, "kv_block_size": 8,
                   "prompt_buckets": (16,), "decode_buckets": (4,),
                   "max_seq_len": 64}
        dense = ServingBatcher(DecoderLM(conf), buckets=(8,),
                               mesh=None, name="gen-dense",
                               generate=dict(gen_cfg))
        dense.warmup_generate()
        sharded = ServingBatcher(DecoderLM(conf), buckets=(8,),
                                 mesh=_mesh_1d(), name="gen-shard",
                                 mode=mode, generate=dict(gen_cfg))
        sharded.warmup_generate()
        prompt = np.array([5, 9, 2, 7])
        t_dense = list(dense.submit_generate(prompt, 8))
        t_shard = list(sharded.submit_generate(prompt, 8))
        assert t_dense == t_shard
        assert sharded.engine.retraces_since_warmup() == 0
        dense.shutdown()
        sharded.shutdown()


def _serve_generative(**generate_overrides):
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.serving.server import InferenceServer
    conf = DecoderConfig.tiny()
    gen = {"kv_blocks": 32, "kv_block_size": 8,
           "prompt_buckets": (16,), "decode_buckets": (4,),
           "max_seq_len": 64}
    gen.update(generate_overrides)
    reg = ModelRegistry()
    ver = reg.register("lm", DecoderLM(conf), generate=gen)
    srv = InferenceServer(reg).start(0)
    return reg, ver, srv


def _gen_request(port, body, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=timeout)
    conn.request("POST", "/v1/models/lm:generate",
                 body=json.dumps(body).encode(),
                 headers={"Content-Type": "application/json"})
    return conn, conn.getresponse()


class TestGenerateEndpoint:
    def test_streams_ndjson_tokens_then_done(self):
        reg, ver, srv = _serve_generative()
        try:
            conn, resp = _gen_request(
                srv.port, {"prompt": [5, 9, 2, 7], "max_tokens": 6})
            assert resp.status == 200
            assert resp.getheader("Transfer-Encoding") == "chunked"
            assert resp.getheader("X-Model-Version") == "1"
            lines = [json.loads(ln) for ln in
                     resp.read().decode().strip().splitlines()]
            toks = [r["token"] for r in lines if "token" in r]
            done = lines[-1]
            assert done["done"] and done["tokens"] == len(toks) == 6
            model = ver.model
            ref = list(model.reference_decode(
                ver.batcher.engine.params, np.array([5, 9, 2, 7]), 6,
                eos_id=model.conf.eos_id))
            assert toks == ref
            assert ver.retraces_since_warmup() == 0
            conn.close()
        finally:
            srv.stop()
            reg.shutdown()

    def test_non_stream_mode_buffers_one_json(self):
        reg, ver, srv = _serve_generative()
        try:
            conn, resp = _gen_request(
                srv.port, {"prompt": [5, 9], "max_tokens": 4,
                           "stream": False})
            assert resp.status == 200
            doc = json.loads(resp.read())
            assert len(doc["tokens"]) == 4
            assert doc["reason"] == "max_tokens"
            conn.close()
        finally:
            srv.stop()
            reg.shutdown()

    def test_pool_exhaustion_is_429_with_retry_after(self):
        """A prompt the pool cannot hold must shed BEFORE any chunk:
        a plain 429 carrying a positive integer Retry-After."""
        reg, ver, srv = _serve_generative(kv_blocks=3)   # 2 usable
        pool = ver.batcher.engine.pool
        try:
            # occupy every usable block for the request's lifetime
            # (deterministic: an HTTP holder could finish and free
            # its blocks before the shed request lands)
            pool.alloc("hog", pool.usable_blocks * pool.block_size)
            conn, resp = _gen_request(
                srv.port, {"prompt": list(range(2, 12)),
                           "max_tokens": 4})
            assert resp.status == 429
            retry = resp.getheader("Retry-After")
            assert retry is not None and int(retry) >= 1
            doc = json.loads(resp.read())
            assert doc["reason"] == "kv_pool"
            conn.close()
            pool.free("hog")
        finally:
            srv.stop()
            reg.shutdown()

    def test_client_disconnect_frees_blocks(self):
        reg, ver, srv = _serve_generative()
        pool = ver.batcher.engine.pool
        try:
            conn, resp = _gen_request(
                srv.port, {"prompt": [5, 9, 2, 7],
                           "max_tokens": 2000})
            # read one chunk line, then slam the socket shut
            resp.fp.readline()
            conn.sock.close()
            deadline = time.monotonic() + 15
            while pool.live_blocks and time.monotonic() < deadline:
                time.sleep(0.02)
            assert pool.live_blocks == 0
        finally:
            srv.stop()
            reg.shutdown()

    def test_unknown_model_404_and_non_generative_400(self):
        from deeplearning4j_tpu.serving.registry import ModelRegistry
        from deeplearning4j_tpu.serving.server import InferenceServer

        class Dense:
            def output(self, x):
                return x

        reg = ModelRegistry()
        reg.register("plain", Dense())
        srv = InferenceServer(reg).start(0)
        try:
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=10)
            conn.request("POST", "/v1/models/nope:generate",
                         body=b'{"prompt": [1]}')
            assert conn.getresponse().status == 404
            conn.close()
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=10)
            conn.request("POST", "/v1/models/plain:generate",
                         body=b'{"prompt": [1]}')
            assert conn.getresponse().status == 400
            conn.close()
        finally:
            srv.stop()
            reg.shutdown()


class TestRouterRelay:
    def test_router_relays_token_stream_chunked(self):
        from deeplearning4j_tpu.serving.router import ServingRouter
        conf = DecoderConfig.tiny()
        router = ServingRouter(n_replicas=2).start(0)
        try:
            router.rollout("lm", lambda: DecoderLM(conf), generate={
                "kv_blocks": 32, "kv_block_size": 8,
                "prompt_buckets": (16,), "decode_buckets": (4,),
                "max_seq_len": 64})
            conn = http.client.HTTPConnection("127.0.0.1",
                                              router.port, timeout=60)
            conn.request("POST", "/v1/models/lm:generate",
                         body=json.dumps({"prompt": [5, 9, 2, 7],
                                          "max_tokens": 5}).encode(),
                         headers={"Content-Type":
                                  "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("Transfer-Encoding") == "chunked"
            lines = [json.loads(ln) for ln in
                     resp.read().decode().strip().splitlines()]
            assert lines[-1]["done"] and lines[-1]["tokens"] == 5
            conn.close()
        finally:
            router.stop()


class TestChunkedHttpUtil:
    def _boom_server(self, n_good_chunks, explode=True):
        """A QuietHandler that streams n chunks then raises (or ends
        cleanly when explode=False)."""
        from deeplearning4j_tpu.common.httputil import (
            QuietHandler, start_http_server)

        class H(QuietHandler):
            def do_GET(self):           # noqa: N802
                self.begin_chunks("text/plain")
                try:
                    for i in range(n_good_chunks):
                        self.send_chunk(f"c{i}\n".encode())
                    if explode:
                        raise RuntimeError("mid-stream failure")
                    self.end_chunks()
                except RuntimeError:
                    self.abort_chunks()

        return start_http_server(H, 0)

    def test_clean_stream_ends_with_terminal_chunk(self):
        httpd, _ = self._boom_server(3, explode=False)
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", httpd.server_address[1], timeout=10)
            conn.request("GET", "/")
            resp = conn.getresponse()
            assert resp.read() == b"c0\nc1\nc2\n"
            conn.close()
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_mid_stream_exception_truncates_not_wedges(self):
        """The regression: an exception after begin_chunks must
        surface to the client as a PROMPT truncation error — not a
        connection that hangs until timeout."""
        httpd, _ = self._boom_server(2, explode=True)
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", httpd.server_address[1], timeout=10)
            t0 = time.monotonic()
            conn.request("GET", "/")
            resp = conn.getresponse()
            with pytest.raises((http.client.IncompleteRead,
                                http.client.HTTPException, OSError)):
                resp.read()
            assert time.monotonic() - t0 < 8    # no timeout-wedge
            conn.close()
        finally:
            httpd.shutdown()
            httpd.server_close()
