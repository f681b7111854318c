"""Continuous-batching generative decode engine.

The Orca insight applied to the serving stack: autoregressive decode
is *iteration-level* work — the scheduling unit is one token step over
all live sequences, not one request. This module owns that loop:

- **Prefill**: a new request's prompt runs full causal attention
  (through ``sdpa_core``, so the flash-attention ladder applies) on a
  per-prompt-bucket compiled program, its K/V scattered into the paged
  :class:`~deeplearning4j_tpu.serving.kvcache.KVBlockPool`, and its
  first token sampled — the time-to-first-token span. The three
  programs are dispatched behind the decode steps in flight, and the
  first token stays on the device until it is the oldest thing there.
- **Decode**: every engine iteration runs ONE fused step over all live
  sequences — gather KV blocks via block tables, paged attention
  (Pallas kernel or dense-gather fallback via the ``paged_attention``
  kernel-select family), sample, append — compiled once per decode
  bucket, so steady state never retraces while sequences join and
  leave mid-batch (the zero-post-warmup-retrace acceptance bar).
- **Retire**: a sequence leaves on EOS / ``max_tokens`` / client
  disconnect / deadline, and its blocks return to the pool *mid-batch*
  — the remaining sequences keep decoding, the freed blocks admit the
  next prefill.

Consumers read a :class:`TokenStream`: a queue the engine thread
pushes token ids into as they decode — the producer side of the HTTP
chunked-transfer streaming in ``serving.server``. Cancelling the
stream (client disconnect) retires the sequence on the next
iteration.

Dispatch signatures are recorded into the batcher's ``RetraceGuard``,
so ``retraces_since_warmup() == 0`` covers the generative path with
the same proof obligation as predict.

Spans (``telemetry.span``, so also in a running ``jax.profiler`` trace):
one pass of the loop that admitted or stepped is one
``generate.iteration``, and its children split the host's turn by
cause — ``generate.admit``, ``generate.build``,
``generate.decode_step`` (``generate.dispatch`` then ``generate.pull``)
and ``generate.emit``. They inherit the iteration's ``iter``; what no
child covers is the iteration's self time. ``generate.decode_step``
carries the counts of the step it dispatched: ``live`` rows of
``bucket``, ``pool_live`` of ``pool_usable`` blocks, and
``grid_blocks``, the ``bucket x max_blocks`` table positions of which
``pool_live`` name live KV. ``generate.admit`` carries ``admitted``
(requests that left the queue), ``behind`` (prefills dispatched while
decode steps were in flight) and ``joined`` (those of them the next
step took into its holes, with no landing); the counter
``dl4j_generate_admissions_total{model,path=joined|landed|idle}``
counts the prefills the same way (``idle``: nothing was in flight).
A request's ``generate.prefill`` (``span_at``; ``parent``
``generate.admit`` and ``iter`` of the admitting iteration, given
explicitly) is written when its first token is pulled, and spans its
dispatch to that pull: the steps queued ahead of it, its three
programs, the read-back.

**An admission's record** (one an admission episode, none a step).
``generate.stall`` (``span_at``, no parent; ``iter`` of the iteration
that closed it) is **what a row that was decoding waited between two
tokens** across an admission: from the emit of the last step
dispatched before the episode's first prefill to the emit of the first
step dispatched after its last, with ``prefills`` and
``prompt_tokens`` (summed) and ``rows`` (the rows of that closing step
that were live in the opening one: the inter-token gaps that crossed
it). Prefills with no decode step dispatched between them are one
episode; an admission with no step in flight has nobody waiting and
writes none. The same seconds go to the histogram
``dl4j_generate_admission_stall_seconds{model}``.

**The loop runs ahead of the device.** A dispatched step's ids are
not pulled at once: the next pass builds the following step on the
rows of the last one dispatched (their positions one further, its
ids, still on the device, as the tokens) and dispatches it, until
``RUN_AHEAD`` programs are queued behind the oldest in flight (a decode
step, or the prefills queued before one, each counting one), which the
host then pulls and emits (``generate.pull`` and ``generate.emit`` are
of the oldest program in flight, not of the one ``generate.dispatch``
sent).
The device goes from step to step without waiting for the host's
turn, and a stall of the host as long as the queued steps (a
collector's or a hypervisor's pause: 105-125 ms now and then on the
chip's machines) does not starve it. Rows keep their places from step
to step; a row whose last token a step in flight brings, or that has
retired, is a hole (scratch block, scratch slot) in the next, and
what a step computed for a row that retired meanwhile (EOS seen some
steps late, a disconnect) is dropped. **An admission joins the queue**
and does not land it: the prefill, commit and first-token sample are
dispatched right behind the steps in flight (the pool's arrays, passed
from program to program, order them on the device, so a row's blocks
and state slot can go to a joiner while steps that name their old
sequence are still queued: those run before its commit), and the next
step puts each joiner in a hole of the last one's rows, at its prompt
length, its first token merged into that step's ids on the device
(one small program, warmed a decode bucket). The admitting pass builds
no step of its own (the next pass does; never two passes in a row):
its host turn went on the prefills, and with a build and a dispatch
besides its pull would come late for every row. What is in flight is
pulled in device order, so a joiner's first token reaches its stream
before any token of a step that carries it. Only where the joiners do
not fit (no hole, or a sequence waits without a row) or the rows fit a
smaller bucket does the loop land every step in flight (a
``generate.pull`` and ``generate.emit`` each, directly under the
iteration) and pack the rows afresh; so does an admission into an
engine with no step in flight, which lands its first tokens.

A model with recurrent state (state-space layers; it has
``state_shapes()``) gets a **state slot** a sequence from the same
pool: admission takes one before the prefill (a request that finds
none waits at the head of the queue for a retirement), the commit
program writes the prefill's last-position state into it, the decode
step takes the rows' slots beside their block tables (0, the scratch
slot, for a dead row), and retirement frees it with the blocks. The
compiled programs take the cache's arrays as one pytree
(``pool.arrays``: K and V ``[layers, blocks, block, kv_heads *
head_dim]``, then the state arrays), **donated** for every model: the
commit program writes the prompt's blocks and the decode step one row
a sequence a layer into the buffers they were given, and the pool
holds what they return. ``generate.prefill`` then carries the
sequence's ``state_slot`` (how many are live is the pool's gauge,
``dl4j_state_pool_slots``).

A model that keeps **window rings** beside one shared K/V layer (it has
``cache_reads()``; the pool has ``window_bytes``) is served by the same
programs: its rings are slot kinds like any state. Its
``generate.decode_step`` spans also carry ``kv_tokens`` (the growing
layer's live tokens over the step's rows), ``ring_tokens`` (a ring's,
``min(context, window)`` a row), and what the step's layers read of
them, ``window_read_tokens`` of ``kv_read_tokens``; its
``generate.prefill`` spans ``positions`` (the bucket's) and
``layer_positions`` of ``layer_positions_dense`` (a prefill that runs
its upper layers on the last position only computes about half).
Where ``cache_reads()`` names a token's bytes in each cache
(``kv_token_bytes``, ``window_token_bytes``) the step's spans carry
``kv_read_bytes`` and ``window_read_bytes`` too.

A model with **sparse experts** (it has ``step_counts``, the names of
the int32 its ``prefill`` and ``decode_step`` return last) routes on
the device, so the host learns what a step's routing did only with the
step's ids: the counts ride the same pull, ``RUN_AHEAD`` steps after
the dispatch, and land on that step's ``generate.emit`` span (a
prompt's on its ``generate.prefill``): ``moe_rows`` of ``moe_rows_all``
``(row, expert)`` pairs fell on the experts held here, ``moe_experts_hit``
of ``moe_experts_held`` experts had a row, ``moe_rows_max`` the fullest
ones' rows. Counters ``dl4j_moe_rows_total{model,where=held|elsewhere}``
and ``dl4j_moe_experts_idle_total{model}``.
"""
from __future__ import annotations

import collections
import itertools
import queue as _queue
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.common import telemetry
from deeplearning4j_tpu.common.compilecache import RetraceGuard
from deeplearning4j_tpu.serving.admission import DeadlineExceeded
from deeplearning4j_tpu.serving.kvcache import KVBlockPool, PoolExhausted

#: programs queued on the device behind the one whose results the
#: host waits for (decode steps, and prefills with their commit and
#: sample, one each): 120 ms of cover at steps of 30 ms, a little more
#: than the 105-125 ms for which the chip's machines now and then
#: stop a whole process. A freed row is refilled, and an EOS seen,
#: this many steps later than it could be.
RUN_AHEAD = 4

#: terminal reasons a TokenStream closes with
END_REASONS = ("eos", "max_tokens", "cancelled", "deadline", "kv_pool",
               "error")


def _ttft_hist() -> telemetry.Histogram:
    return telemetry.histogram(
        "dl4j_generate_ttft_seconds",
        "time-to-first-token of generate requests: submit -> first "
        "sampled token (prefill queue + prefill compute), per model "
        "(seconds)")


def _intertoken_hist() -> telemetry.Histogram:
    return telemetry.histogram(
        "dl4j_generate_intertoken_seconds",
        "gap between consecutive streamed tokens of one sequence — "
        "the decode-iteration latency a streaming client experiences "
        "(seconds)")


def _stall_hist() -> telemetry.Histogram:
    return telemetry.histogram(
        "dl4j_generate_admission_stall_seconds",
        "what a decoding row waits between two tokens when an admission "
        "falls between them: the token of the last step dispatched "
        "before the prefills to that of the first step dispatched after "
        "them, one observation an admission episode, per model "
        "(seconds)")


def _admissions_counter() -> telemetry.Counter:
    return telemetry.counter(
        "dl4j_generate_admissions_total",
        "prefills dispatched, by model and how they met the decode "
        "steps in flight: joined (queued behind them, each sequence "
        "taking a hole of the next step) | landed (the steps in flight "
        "were landed and the rows packed afresh) | idle (nothing was "
        "in flight)")


def _decode_step_hist() -> telemetry.Histogram:
    return telemetry.histogram(
        "dl4j_generate_decode_step_seconds",
        "wall time of one fused decode iteration over the live batch "
        "(gather + paged attention + sample + append), per model "
        "(seconds)")


def _occupancy_hist() -> telemetry.Histogram:
    return telemetry.histogram(
        "dl4j_serving_batch_occupancy",
        "live rows / bucket-padded rows per serving flush — "
        "how full the warm buckets actually run (1.0 = no "
        "padding waste; continuous batching should push this "
        "up under load)",
        buckets=telemetry.RATIO_BUCKETS)


def _tokens_counter() -> telemetry.Counter:
    return telemetry.counter(
        "dl4j_generate_tokens_total",
        "tokens decoded and streamed, per model — the goodput "
        "numerator")


def _requests_counter() -> telemetry.Counter:
    return telemetry.counter(
        "dl4j_generate_requests_total",
        "generate requests finished, by model and outcome (eos | "
        "max_tokens | cancelled | deadline | kv_pool | error)")


def _live_gauge() -> telemetry.Gauge:
    return telemetry.gauge(
        "dl4j_generate_live_sequences",
        "sequences currently in the continuous decode batch, per "
        "model")


def _disconnects_counter() -> telemetry.Counter:
    return telemetry.counter(
        "dl4j_generate_stream_disconnects_total",
        "generate streams cancelled mid-decode by client disconnect — "
        "their KV blocks return to the pool on the next iteration")


def _sample_path_counter() -> telemetry.Counter:
    return telemetry.counter(
        "dl4j_generate_sample_path_total",
        "executions of the sampler (decode steps and first-token "
        "samples), by model and the rung its batch asked for (argmax "
        "| categorical | top_k | sort): the last two order the "
        "vocabulary for every row of the bucket")


def _moe_rows_counter() -> telemetry.Counter:
    return telemetry.counter(
        "dl4j_moe_rows_total",
        "(row, expert) pairs the router chose, by model and where the "
        "expert lives (held: on this chip, computed here | elsewhere: "
        "another chip's share of the expert-parallel layer)")


def _moe_idle_counter() -> telemetry.Counter:
    return telemetry.counter(
        "dl4j_moe_experts_idle_total",
        "held experts that no row of a step or prompt was routed to, "
        "summed over expert layers and executions, by model: their "
        "weights were resident and unread")


class TokenStream:
    """Consumer handle of one generate request: iterate token ids as
    the engine decodes them; ``reason`` tells how the sequence ended.
    ``cancel()`` (client disconnect) retires the sequence and frees
    its KV blocks on the engine's next iteration."""

    _DONE = object()

    def __init__(self, seq_id: int, prompt_len: int):
        self.seq_id = seq_id
        self.prompt_len = prompt_len
        self.reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self.cancelled = False
        self._q: "_queue.Queue" = _queue.Queue()

    # engine side ------------------------------------------------------
    def _put(self, token: int) -> None:
        self._q.put(int(token))

    def _close(self, reason: str,
               error: Optional[BaseException] = None) -> None:
        if self.reason is None:
            self.reason = reason
            self.error = error
            self._q.put(self._DONE)

    # consumer side ----------------------------------------------------
    def cancel(self) -> None:
        self.cancelled = True

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._DONE:
                if self.error is not None:
                    raise self.error
                return
            yield item

    def next(self, timeout: Optional[float] = None) -> Optional[int]:
        """The next token id, or None when the stream has closed
        (check ``reason``). Raises the stream error on a failed
        sequence, ``queue.Empty`` on timeout — the server's per-token
        wait primitive."""
        item = self._q.get(timeout=timeout)
        if item is self._DONE:
            if self.error is not None:
                raise self.error
            return None
        return item

    def tokens(self, timeout: Optional[float] = None) -> List[int]:
        """Drain the whole stream (blocking); raises the stream error
        if the sequence failed."""
        out: List[int] = []
        deadline = None if timeout is None else (time.monotonic()
                                                 + timeout)
        while True:
            left = None if deadline is None \
                else max(0.0, deadline - time.monotonic())
            item = self._q.get(timeout=left)
            if item is self._DONE:
                if self.error is not None:
                    raise self.error
                return out
            out.append(item)


class _Sequence:
    """Engine-internal live-sequence state."""

    __slots__ = ("seq_id", "stream", "next_token", "position",
                 "generated", "flying", "max_tokens", "temperature",
                 "top_k", "deadline", "t_last", "ctx", "first")

    def __init__(self, seq_id, stream, next_token, position,
                 max_tokens, temperature, top_k, deadline, t_last,
                 ctx=None):
        self.seq_id = seq_id
        self.stream = stream
        self.next_token = int(next_token)   # fed to the next step
        self.position = int(position)       # its index in the sequence
        self.generated = 1                  # the prefill-sampled token
        self.flying = 0                     # steps in flight with it
        self.max_tokens = int(max_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.deadline = deadline
        self.t_last = t_last                # last token emit instant
        self.ctx = ctx                      # request TraceContext
        #: the prefill's sampled id, on the device, until a step takes
        #: it as this row's token or the host pulls it
        self.first = None


class _Step:
    """A dispatched decode step whose ids are still on the device:
    the sequence of each row (None: a dead row or a hole), its bucket,
    the ids, when it was dispatched, the prefills dispatched between
    the step before it and this one, and the admission episode whose
    first end (``opens``) or last (``closes``) its tokens are."""

    __slots__ = ("rows", "bucket", "ids", "t0", "counts", "prefills",
                 "opens", "closes")

    def __init__(self, rows, bucket, ids, t0, counts=None):
        self.rows, self.bucket, self.ids, self.t0 = rows, bucket, ids, t0
        self.counts = counts        # the model's step_counts, on the device
        self.prefills = ()
        self.opens = self.closes = None


class _Prefill:
    """A dispatched prefill whose first token is still on the device:
    its sequence, that token and the prompt's routing counts (read back
    by the pull), when it was dispatched, submitted and pulled, and
    what its ``generate.prefill`` record carries."""

    __slots__ = ("seq", "first", "counts", "t0", "t_submit", "t_pulled",
                 "args")

    def __init__(self, seq, first, counts, t0, t_submit, args):
        self.seq, self.first, self.counts = seq, first, counts
        self.t0, self.t_submit, self.t_pulled = t0, t_submit, None
        self.args = args


class DecodeEngine:
    """The prefill/decode continuous-batching loop over one model.

    ``model`` exposes the :class:`~deeplearning4j_tpu.models.decoder.
    DecoderLM` contract (``prefill`` / ``decode_step`` / ``conf``);
    ``params`` is the (possibly resident-sharded) tree the jitted
    programs consume, ``view_fn`` the in-jit params adapter
    (``serving.residency.serving_param_view`` partial, or None for
    dense). One compiled program per prompt bucket (prefill+commit)
    and per decode bucket; ``warmup()`` compiles them all so the guard
    count freezes before the first real request."""

    def __init__(self, model, params, pool: KVBlockPool, *,
                 view_fn=None, name: str = "model",
                 prompt_buckets: Sequence[int] = (16, 64),
                 decode_buckets: Sequence[int] = (4, 8),
                 max_seq_len: Optional[int] = None,
                 paged: Optional[bool] = None,
                 guard: Optional[RetraceGuard] = None,
                 rng_seed: int = 0):
        self.model = model
        self.params = params
        self.pool = pool
        self.view_fn = view_fn
        self.name = name
        self.prompt_buckets = tuple(sorted(int(b)
                                           for b in set(prompt_buckets)))
        self.decode_buckets = tuple(sorted(int(b)
                                           for b in set(decode_buckets)))
        cap = pool.usable_blocks * pool.block_size
        self.max_seq_len = int(min(max_seq_len or model.conf.max_len,
                                   model.conf.max_len, cap))
        #: fixed block-table width — part of every decode signature
        self.max_blocks = pool.blocks_for(self.max_seq_len)
        self.guard = guard if guard is not None else RetraceGuard(
            f"generate:{name}",
            threshold=len(self.prompt_buckets)
            + 2 * len(self.decode_buckets) + 2)
        self._paged = paged
        self._seq_ids = itertools.count(1)
        self._pending: "_queue.Queue" = _queue.Queue()
        #: the head of the queue while it waits for a state slot
        self._held = None
        self._live: Dict[int, _Sequence] = {}
        #: the dispatched steps whose ids were not pulled yet
        self._inflight: "collections.deque[_Step]" = collections.deque()
        #: the prefills dispatched since the last step: the next step
        #: takes them (``_Step.prefills``), or a landing pulls them
        self._parked: List[_Prefill] = []
        #: the ``generate.admit`` records whose prefills wait for the
        #: step that will take or land them, and whether the last pass
        #: built no step
        self._admissions: List[dict] = []
        self._skipped = False
        self._t_landed = 0.0
        #: the admission episode that no decode step dispatched after
        #: it has closed yet: what ``generate.stall`` will carry, the
        #: ids of the sequences of the step before it (``before``) and,
        #: once that step's tokens are out, when (``t0``)
        self._stall: Optional[dict] = None
        self._lock = threading.Lock()
        self._worker: Optional[threading.Thread] = None
        #: the worker shutdown() swapped out, until it finishes its
        #: drain — _ensure_worker joins it so two workers never touch
        #: _pending/_live concurrently
        self._draining: Optional[threading.Thread] = None
        self._work = threading.Event()
        self._shutdown = False
        self._step = 0
        self._iter = 0              # engine iterations, the spans' id
        self._meters_of = None      # the registry _meters() bound to
        self._bound = None          # ... and what it bound there
        self._warmed = False
        self.warm_signatures = 0
        self._jits: dict = {}
        #: who reads which cache in a step, where the pool holds rings
        self._reads = model.cache_reads() if pool.window_kinds else None
        #: names of the int32 the model's programs return last (what
        #: its routing did), () for a model without sparse experts
        self._counted = tuple(getattr(model, "step_counts", ()))
        import jax
        self._rng = jax.random.PRNGKey(rng_seed)

    # -- compiled programs ---------------------------------------------
    def _paged_now(self) -> bool:
        """Resolve the paged-vs-dense decode backend once per compile
        (trace-time, like every kernel_select decision)."""
        if self._paged is not None:
            return bool(self._paged)
        from deeplearning4j_tpu.ops.attention_pallas import \
            select_paged_backend
        backend, _ = select_paged_backend(1, self.max_blocks)
        return backend == "paged"

    def _view(self, params):
        return self.view_fn(params) if self.view_fn is not None \
            else params

    def _prefill_jit(self):
        import jax
        if "prefill" not in self._jits:
            def fn(params, tokens, length):
                return self.model.prefill(self._view(params), tokens,
                                          length)
            self._jits["prefill"] = jax.jit(fn)
        return self._jits["prefill"]

    def _commit_jit(self):
        import jax
        import jax.numpy as jnp
        if "commit" not in self._jits:
            def fn(cache, new, blocks, *state_slot):
                (kp, vp, *state), (k, v, *new_state) = cache, new
                bs = kp.shape[2]
                # every layer named block by block, not taken whole
                # with ``[:, blocks]``: a scatter whose window spans
                # the layers may make XLA transpose the pool into a
                # layout of its own and back (two copies of each pool)
                layer = jnp.arange(kp.shape[0])[:, None]

                def tiles(a, pool):
                    # [layers, 1, t, heads, d] -> [layers, blocks,
                    # block, heads * d]: a position's heads side by
                    # side, the bucket cut into the pool's blocks (and
                    # padded up to whole ones); low-precision pools
                    # (kv_dtype=bf16) take the write in their own dtype
                    a = a[:, 0].reshape(a.shape[0], a.shape[2], -1)
                    a = jnp.pad(a, ((0, 0), (0, blocks.size * bs
                                             - a.shape[1]), (0, 0)))
                    return a.reshape(a.shape[0], -1, bs,
                                     a.shape[-1]).astype(pool.dtype)
                # the prompt's blocks of every layer into the donated
                # pools, whole: what lies past the prompt in its last
                # block is not live until a decode step writes it, and
                # the bucket's other blocks land in scratch block 0
                kp = kp.at[layer, blocks].set(tiles(k, kp))
                vp = vp.at[layer, blocks].set(tiles(v, vp))
                # recurrent state: the prompt's last-token state into
                # the sequence's slot, every layer at once
                # (a ring arrives [layers, 1, window, lanes] and is
                # stored in the pool's blocks: same bytes, same order)
                state = [a.at[:, state_slot[0]].set(
                    n[:, 0].reshape(a.shape[:1] + a.shape[2:])
                    .astype(a.dtype)) for a, n in zip(state, new_state)]
                return (kp, vp, *state)
            # the cache is donated: the pool's arrays are updated in
            # place, never written a second time
            self._jits["commit"] = jax.jit(fn, donate_argnums=(0,))
        return self._jits["commit"]

    def _sample_jit(self):
        import jax
        if "sample" not in self._jits:
            from deeplearning4j_tpu.ops.sampling import sample_logits
            self._jits["sample"] = jax.jit(sample_logits)
        return self._jits["sample"]

    def _decode_jit(self):
        import jax

        from deeplearning4j_tpu.ops.sampling import sample_logits
        if "decode" not in self._jits:
            paged = self._paged_now()

            def fn(params, cache, tokens, positions, tables, key,
                   temps, topks, *state_slots):
                logits, *cache = self.model.decode_step(
                    self._view(params), tokens, positions, *cache,
                    tables, *state_slots, paged=paged)
                counts = (cache.pop(),) if self._counted else ()
                ids = sample_logits(logits, key, temps, topks)
                return (ids, tuple(cache), *counts)
            self._jits["decode"] = jax.jit(fn, donate_argnums=(1,))
        return self._jits["decode"]

    def _merge_jit(self):
        import jax
        if "merge" not in self._jits:
            def fn(ids, row, first):
                # a joiner's first token, sampled by its prefill, as
                # the token of the row it takes in the next step
                return ids.at[row].set(first[0])
            self._jits["merge"] = jax.jit(fn)
        return self._jits["merge"]

    # -- warmup --------------------------------------------------------
    def warmup(self) -> float:
        """Compile every prompt bucket's prefill+commit and every
        decode bucket's fused step and first-token merge (dummy data,
        blocked to completion). The guard count freezes here — any
        later new signature is a bucket miss."""
        import jax
        t0 = time.perf_counter()
        for t in self.prompt_buckets:
            tokens = np.zeros((1, t), np.int32)
            length = np.asarray([1], np.int32)
            self.guard.record(tokens, length)
            last, *new = self._prefill_jit()(self.params, tokens,
                                             length)
            if self._counted:
                new.pop()
            blocks = np.zeros((self.pool.blocks_for(t),), np.int32)
            self.guard.record(new[0], blocks)
            cache = self._commit_jit()(
                self.pool.arrays, tuple(new), blocks,
                *self._state_arg(np.int32(0)))
            # the first-token sampler compiles once here (its [1,
            # vocab] signature never varies with the prompt bucket)
            first = self._sample_jit()(
                last, jax.random.fold_in(self._rng, 0),
                np.zeros((1,), np.float32), np.zeros((1,), np.int32))
            jax.block_until_ready((last, cache, first))
            # scratch-block writes only: pool arrays unchanged where
            # it matters, but keep the functional update discipline
            self.pool.update_arrays(*cache)
        for b in self.decode_buckets:
            tokens = np.zeros((b,), np.int32)
            positions = np.zeros((b,), np.int32)
            tables = np.zeros((b, self.max_blocks), np.int32)
            temps = np.zeros((b,), np.float32)
            topks = np.zeros((b,), np.int32)
            self.guard.record(tokens, positions, tables, temps, topks)
            import jax as _jax
            key = _jax.random.fold_in(self._rng, 0)
            rest = (positions, tables, key, temps, topks,
                    *self._state_arg(np.zeros((b,), np.int32)))
            ids, cache, *_ = self._decode_jit()(
                self.params, self.pool.arrays, tokens, *rest)
            self.pool.update_arrays(*cache)
            # and with its tokens as every step built on the one before
            # gets them: that step's ids, still on the device, with a
            # joiner's first token merged in
            row = np.int32(0)
            self.guard.record(ids, row, first)
            ids = self._merge_jit()(ids, row, first)
            ids, cache, *_ = self._decode_jit()(
                self.params, self.pool.arrays, ids, *rest)
            self.pool.update_arrays(*cache)
            jax.block_until_ready(ids)
        self._warmed = True
        self.warm_signatures = self.guard.n_signatures
        return time.perf_counter() - t0

    def _state_arg(self, slots) -> tuple:
        """The state-slot argument of the commit and decode programs:
        there only for a pool that holds recurrent state."""
        return (slots,) if self.pool.state else ()

    def retraces_since_warmup(self) -> int:
        """Distinct signatures compiled after warmup — must stay 0 in
        steady state across any join/leave churn (the zero-retrace
        proof for the decode loop)."""
        return self.guard.n_signatures - self.warm_signatures

    # -- request intake ------------------------------------------------
    def generate_cost(self, prompt_len: int, max_tokens: int = 0
                      ) -> int:
        """Admission cost of a generate request: the KV blocks its
        prompt occupies (token-cost admission — a long prompt spends
        the AIMD budget many short ones would)."""
        return self.pool.blocks_for(int(prompt_len) + int(max_tokens))

    def submit(self, prompt, max_tokens: int, *,
               temperature: float = 0.0, top_k: int = 0,
               deadline: Optional[float] = None,
               ctx=None) -> TokenStream:
        """Enqueue a generate request. Allocates the prompt's KV
        blocks synchronously — :class:`~deeplearning4j_tpu.serving.
        kvcache.PoolExhausted` (HTTP 429 upstream) raises HERE, before
        the caller starts streaming. Returns the token stream. ``ctx``
        (the request's TraceContext) rides the pending entry so the
        engine thread can attribute queue/device phases and per-token
        instants back onto the request timeline."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must carry at least one token")
        if prompt.size >= self.max_seq_len:
            raise ValueError(
                f"prompt of {prompt.size} tokens >= max_seq_len "
                f"{self.max_seq_len}")
        if deadline is not None and time.monotonic() >= deadline:
            raise DeadlineExceeded(
                "deadline already expired at generate submit")
        max_tokens = int(min(max_tokens,
                             self.max_seq_len - prompt.size))
        seq_id = next(self._seq_ids)
        # reserve the prompt's blocks NOW: exhaustion is a synchronous
        # shed, not a mid-stream surprise
        self.pool.alloc(seq_id, int(prompt.size))
        stream = TokenStream(seq_id, int(prompt.size))
        with self._lock:
            self._ensure_worker()
            self._pending.put((seq_id, prompt, max_tokens,
                               float(temperature), int(top_k),
                               deadline, stream, time.perf_counter(),
                               ctx))
        self._work.set()
        return stream

    def _ensure_worker(self):
        if self._worker is not None:
            return
        prev, self._draining = self._draining, None
        if prev is not None:
            # the old worker drains _pending/_live single-threaded;
            # it never takes this lock, so waiting here cannot deadlock
            prev.join()
        self._shutdown = False
        # caller (submit) holds self._lock: worker startup and the
        # queue insertion that wakes it stay atomic
        # dl4j-lint: disable=lock-discipline
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name=f"dl4j-generate-"
                                             f"{self.name}")
        self._worker.start()

    def shutdown(self, timeout: float = 30.0):
        """Stop the engine worker after it drains every admitted and
        pending sequence (bounded by ``timeout``). A concurrent submit
        either reaches the old worker's drain, or sees ``_worker``
        None and starts a fresh one — it can no longer enqueue onto a
        joined worker and strand its stream."""
        with self._lock:
            self._shutdown = True
            w, self._worker = self._worker, None
            if w is not None:
                self._draining = w
        self._work.set()
        if w is not None:
            w.join(timeout)

    # -- the continuous loop -------------------------------------------
    def _loop(self):
        me = threading.current_thread()
        while True:
            # Clear BEFORE looking: a submit that lands after the
            # look re-sets the event, so the wait below returns
            # immediately instead of losing the wake-up.
            self._work.clear()
            if self._pending.empty() and not self._live \
                    and self._held is None:
                self._inflight.clear()  # nothing but holes is in them
                self._parked = []
                self._settle(False)
                self._stall = None      # and nobody waits for a token
                # Idle — and only exit on shutdown/supersession while
                # idle: every pending request was admitted and every
                # admitted sequence retired, so no stream is stranded.
                if self._shutdown or self._worker is not me:
                    return
                # Block until a submit wakes us (bounded so queued
                # deadline/cancel checks still tick over).
                self._work.wait(0.05)
                continue
            self._iter += 1
            with telemetry.span("generate.iteration", model=self.name,
                                iter=self._iter):
                build = True
                if not self._pending.empty() or self._held is not None:
                    with telemetry.span("generate.admit") as admit:
                        prefills = self._admit_pending(admit)
                    if admit["behind"]:
                        self._admissions.append(admit)
                        # a pass whose host turn went on prefills queued
                        # behind the steps in flight builds no step (the
                        # next one does: never two passes in a row), so
                        # its pull keeps the device's beat, which a build
                        # and a dispatch besides would make late for
                        # every row
                        build = self._skipped
                    elif prefills:
                        _admissions_counter().inc(
                            prefills, model=self.name, path="idle")
                self._skipped = not build
                self._settle(self._decode_iteration(build))

    def _settle(self, queued: Optional[bool]) -> None:
        """Whether the step built after the admissions of the last
        passes took their prefills into the queue (``queued``; None:
        no step was built): their records (the ring holds their args)
        and the counter say so once it is known."""
        if queued is None:
            return
        for admit in self._admissions:
            admit["joined"] = admit["behind"] if queued else 0
            _admissions_counter().inc(
                admit["behind"], model=self.name,
                path="joined" if queued else "landed")
        self._admissions.clear()

    def _admit_pending(self, args: dict) -> int:
        """Prefill every queued request (each its own bucket-padded
        pass) behind whatever is in flight, and join it to the live
        sequences. Puts into ``args``, what ``generate.admit`` carries,
        how many left the queue (``admitted``), the prefills dispatched
        while steps were in flight (``behind``) and, for now, none of
        them taken into a step's holes (``joined``: set once the step
        after them is built, :meth:`_settle`). Returns how many
        prefills it dispatched."""
        in_flight = bool(self._inflight)
        admitted = prefills = 0
        while True:
            item, self._held = self._held, None
            if item is None:
                try:
                    item = self._pending.get_nowait()
                except _queue.Empty:
                    break
            (seq_id, prompt, max_tokens, temperature, top_k, deadline,
             stream, t_submit, ctx) = item
            if stream.cancelled or (deadline is not None
                                    and time.monotonic() >= deadline):
                admitted += 1
                reason = "cancelled" if stream.cancelled else "deadline"
                self.pool.free(seq_id)
                if ctx is not None:
                    ctx.phase_at("queue", t_submit, time.perf_counter())
                self._finish(stream, reason)
                continue
            if self.pool.state and self.pool.alloc_slot(seq_id) is None:
                # every state slot is live: the head of the queue
                # waits for a retirement, like one that finds no row
                self._held = item
                break
            admitted += 1
            try:
                self._prefill_one(seq_id, prompt, max_tokens,
                                  temperature, top_k, deadline, stream,
                                  t_submit, ctx)
            except BaseException as e:      # noqa: BLE001
                self.pool.free(seq_id)
                self._finish(stream, "error", e)
                continue
            prefills += 1
        args.update(admitted=admitted,
                    behind=prefills if in_flight else 0, joined=0)
        return prefills

    def _prompt_bucket(self, n: int) -> int:
        for b in self.prompt_buckets:
            if n <= b:
                return b
        return n                    # oversized prompt: cold compile

    def _prefill_one(self, seq_id, prompt, max_tokens, temperature,
                     top_k, deadline, stream, t_submit, ctx=None):
        """Dispatch the prompt's prefill, its commit into the pool and
        its first token's sample behind whatever is in flight, and make
        the sequence live with that token still on the device: the
        host reads it when it is the oldest thing in flight
        (:meth:`_hand_out`)."""
        import jax
        t_prefill = time.perf_counter()
        t = self._prompt_bucket(prompt.size)
        args = {"parent": "generate.admit", "iter": self._iter,
                "model": self.name, "tokens": int(prompt.size),
                "bucket": t,
                "queue_ms": round((t_prefill - t_submit) * 1e3, 3),
                "seq": seq_id}
        if ctx:
            # engine-side queue phase: submit -> prefill start
            ctx.phase_at("queue", t_submit, t_prefill)
            args["trace"] = ctx.trace_id
        if self.pool.state:
            args["state_slot"] = self.pool.slot(seq_id)
        if hasattr(self.model, "prefill_layer_positions"):
            done, dense = self.model.prefill_layer_positions(t)
            args.update(positions=t, layer_positions=done,
                        layer_positions_dense=dense)
        temps = np.asarray([temperature], np.float32)
        topks = np.asarray([top_k], np.int32)
        args.update(self._name_sample(temps, topks))
        self._stall_grows(int(prompt.size))
        tokens = np.zeros((1, t), np.int32)
        tokens[0, :prompt.size] = prompt
        length = np.asarray([prompt.size], np.int32)
        self._record(tokens, length)
        last, *new = self._prefill_jit()(self.params, tokens, length)
        counts = new.pop() if self._counted else None
        # the bucket's K/V into the prompt's pool blocks (the blocks of
        # the bucket past the prompt's last land in scratch block 0)
        blocks = self.pool.padded_table(seq_id, self.pool.blocks_for(t))
        self._record(new[0], blocks)
        self.pool.update_arrays(*self._commit_jit()(
            self.pool.arrays, tuple(new), blocks,
            *self._state_arg(np.int32(self.pool.slot(seq_id)))))
        self._step += 1
        key = jax.random.fold_in(self._rng, self._step)
        first = self._sample_jit()(last, key, temps, topks)
        if ctx is not None:
            ctx.note(kv_blocks=len(self.pool.table(seq_id)),
                     prompt_tokens=int(prompt.size))
        seq = self._live[seq_id] = _Sequence(
            seq_id, stream, 0, int(prompt.size), max_tokens, temperature,
            top_k, deadline, t_prefill, ctx)
        seq.first = first
        self._parked.append(
            _Prefill(seq, first, counts, t_prefill, t_submit, args))
        _live_gauge().set(len(self._live), model=self.name)

    def _hand_out(self, done: _Prefill) -> int:
        """A prefill's first token, pulled, to its stream: the
        ``generate.prefill`` record, time-to-first-token, the request's
        device phase, and retirement on EOS or ``max_tokens``. Returns
        1 where the sequence retired (0 where it had already left: a
        disconnect or a deadline before its first token came back)."""
        seq, tok = done.seq, done.first
        telemetry.span_at(
            "generate.prefill", telemetry.us_of(done.t0) * 1e-6,
            done.t_pulled - done.t0, **done.args,
            **self._name_counts(done.counts))
        if self._live.get(seq.seq_id) is not seq:
            return 0
        now = time.perf_counter()
        _ttft_hist().observe(now - done.t_submit, model=self.name)
        if seq.ctx is not None:
            # the steps queued ahead, the prefill forward, commit and
            # first-token sample are this request's device phase
            # (decode steps are shared across the live batch,
            # attributed as instants instead)
            seq.ctx.phase_at("device", done.t0, done.t_pulled)
        seq.stream._put(tok)
        _tokens_counter().inc(model=self.name)
        seq.next_token, seq.first, seq.t_last = tok, None, now
        eos = self.model.conf.eos_id
        if tok == eos or seq.max_tokens <= 1:
            self._retire(seq, "eos" if tok == eos else "max_tokens")
            return 1
        return 0

    def _decode_bucket(self, n: int) -> int:
        for b in self.decode_buckets:
            if n <= b:
                return b
        return self.decode_buckets[-1]

    def _retire(self, seq: _Sequence, reason: str,
                error: Optional[BaseException] = None) -> None:
        self._live.pop(seq.seq_id, None)
        self.pool.free(seq.seq_id)
        self._finish(seq.stream, reason, error)
        _live_gauge().set(len(self._live), model=self.name)

    def _finish(self, stream: TokenStream, reason: str,
                error: Optional[BaseException] = None) -> None:
        stream._close(reason, error)
        if reason == "cancelled":
            _disconnects_counter().inc(model=self.name)
        _requests_counter().inc(model=self.name, outcome=reason)

    def _meters(self):
        """The per-step and per-token meters with this engine's labels
        resolved, bound once (again only if the registry is replaced):
        (decode-step seconds, occupancy, tokens, inter-token gap, the
        sampler's executions by rung, the routed pairs on held experts
        and elsewhere and the idle held experts)."""
        reg = telemetry.MetricsRegistry.get()
        if self._meters_of is not reg:
            from deeplearning4j_tpu.ops.sampling import PATHS
            self._meters_of = reg
            self._bound = (
                _decode_step_hist().bind(model=self.name),
                _occupancy_hist().bind(model=self.name,
                                       policy="decode"),
                _tokens_counter().bind(model=self.name),
                _intertoken_hist().bind(model=self.name),
                [_sample_path_counter().bind(model=self.name, path=p)
                 for p in PATHS],
                (_moe_rows_counter().bind(model=self.name, where="held"),
                 _moe_rows_counter().bind(model=self.name,
                                          where="elsewhere"),
                 _moe_idle_counter().bind(model=self.name)))
        return self._bound

    def _name_sample(self, temps, topks) -> dict:
        """What the sampler's program will do with these per-row
        arguments, as span attributes, and counted: the rung it takes
        (the program's own rule, on the host's copy of the arrays),
        the rows it sees, and for how many of them it orders the
        vocabulary."""
        from deeplearning4j_tpu.ops.sampling import PATHS, sample_rung
        rung = int(sample_rung(temps, topks))
        sampled = self._meters()[4]
        sampled[rung].inc()
        rows = len(temps)
        return {"sample_path": PATHS[rung], "sample_rows": rows,
                "sample_ordered":
                    rows if rung >= PATHS.index("top_k") else 0}

    def _name_counts(self, counts) -> dict:
        """What a program's routing did (the model's ``step_counts``,
        pulled with its ids), as span attributes, and counted."""
        if counts is None:
            return {}
        named = dict(zip(self._counted, (int(c) for c in np.asarray(counts))))
        if "moe_rows" in named:
            held, elsewhere, idle = self._meters()[5]
            held.inc(named["moe_rows"])
            elsewhere.inc(named["moe_rows_all"] - named["moe_rows"])
            idle.inc(named["moe_experts_held"] - named["moe_experts_hit"])
        return named

    def _decode_iteration(self, build: bool = True) -> Optional[bool]:
        """ONE fused step over all live sequences (the iteration of
        iteration-level scheduling), dispatched behind the steps in
        flight; the oldest program in flight (a step, or the prefills
        queued before it) is pulled and emitted once ``RUN_AHEAD`` are
        queued behind it. Returns whether the step was built on the ones
        in flight, with no landing; with ``build`` False it only pulls
        what is due and returns None."""
        flying = self._inflight
        if not self._live:
            flying.clear()
            self._parked = []
            self._stall = None
            return False
        if not build:
            if self._queued() > RUN_AHEAD:
                self._emit_step(*self._pull_one())
            return None
        step = None
        if flying:
            with telemetry.span("generate.build"):
                step = self._build_step(flying[-1])
        queued = step is not None
        if step is None:
            # no step in flight (an admission into an idle engine), or
            # its rows will not do for another (a joiner without a
            # hole, a sequence without a row, a smaller bucket): land
            # what is in flight, then pack the rows afresh
            self._land()
            if self._live:
                with telemetry.span("generate.build"):
                    step = self._build_step()
            if step is None:
                self._stall = None      # nobody is left waiting
                return False
        rows, b, inputs, sample = step
        pool = self.pool
        got = None
        with telemetry.span(
                "generate.decode_step", model=self.name,
                live=sum(seq is not None for seq in rows),
                bucket=b, grid_blocks=b * self.max_blocks,
                pool_usable=pool.usable_blocks,
                pool_live=pool.usable_blocks - pool.free_blocks,
                **sample):
            t0 = time.perf_counter()
            with telemetry.span("generate.dispatch",
                                program="decode_step"):
                ids, cache, *routed = self._decode_jit()(
                    self.params, pool.arrays, *inputs)
                # a donated cache is gone once dispatched: the pool
                # holds the step's own arrays from here on
                pool.update_arrays(*cache)
            for seq in rows:
                if seq is not None:
                    seq.flying += 1
            dispatched = _Step(rows, b, ids, t0, *routed)
            # the prefills run before it; the first step after an
            # admission episode's prefills closes it
            dispatched.prefills, self._parked = self._parked, []
            dispatched.closes, self._stall = self._stall, None
            flying.append(dispatched)
            if self._queued() > RUN_AHEAD:
                got = self._pull_one()
        if got is not None:
            self._emit_step(*got)
        return queued

    def _queued(self) -> int:
        """The programs in flight: the decode steps, and the prefills
        (each with its commit and sample) not pulled yet."""
        return len(self._inflight) + len(self._parked) \
            + sum(len(s.prefills) for s in self._inflight)

    def _pull_one(self):
        """The oldest in flight, read back (:meth:`_pull`): the prefills
        queued before the oldest step while any are left, else that
        step."""
        oldest = self._inflight[0]
        if oldest.prefills:
            prefills, oldest.prefills = oldest.prefills, ()
            return self._pull(prefills)
        self._inflight.popleft()
        return self._pull((), oldest)

    def _pull(self, prefills, step: Optional[_Step] = None):
        """Dispatched prefills and the step queued behind them (if
        any), read back to the host in that order: ``(prefills, step,
        ids, step_s)``, each prefill with its first token read, the
        step's ids, and the seconds the device had for the step (since
        it was dispatched or what ran before it landed, whichever came
        later)."""
        with telemetry.span("generate.pull"):
            for p in prefills:
                p.first = int(np.asarray(p.first)[0])
                if p.counts is not None:
                    p.counts = np.asarray(p.counts)
                p.t_pulled = self._t_landed = time.perf_counter()
            if step is None:
                return prefills, None, None, None
            ids = np.asarray(step.ids)
            if step.counts is not None:
                step.counts = np.asarray(step.counts)
        now = time.perf_counter()
        step_s = now - max(step.t0, self._t_landed)
        self._t_landed = now
        return prefills, step, ids, step_s

    def _still_live(self, rows) -> list:
        """``rows`` with None for every sequence that has retired."""
        return [seq if seq is not None
                and self._live.get(seq.seq_id) is seq else None
                for seq in rows]

    def _emit_step(self, prefills, step: Optional[_Step], ids,
                   step_s) -> None:
        """Hand out what one pull brought: the prefills' first tokens,
        then the step's tokens."""
        with telemetry.span("generate.emit") as args:
            retired = sum(self._hand_out(p) for p in prefills)
            # what the step computed for a row that retired meanwhile
            # (EOS seen some steps late, a disconnect) is dropped
            rows = self._still_live(step.rows) if step is not None else ()
            args["tokens"] = sum(seq is not None for seq in rows)
            if step is not None:
                retired += self._emit(step, rows, ids, step_s)
                args.update(self._name_counts(step.counts))
            args["retired"] = retired

    def _land(self) -> None:
        """Pull and hand out everything in flight, oldest first,
        without dispatching another step."""
        while self._inflight:
            step = self._inflight.popleft()
            self._emit_step(*self._pull(step.prefills, step))
        if self._parked:
            parked, self._parked = self._parked, []
            self._emit_step(*self._pull(parked))

    def _stall_grows(self, prompt_tokens: int) -> None:
        """A prefill is about to be dispatched behind the steps in
        flight and to hold the decoding rows up: it opens an admission
        episode at the last step dispatched, or joins the one that no
        step dispatched since has closed. The rows that wait are that
        step's; with no step in flight, as in an idle engine, nobody
        waits: no episode, and no ``generate.stall``."""
        stall = self._stall
        if stall is None:
            prev = self._inflight[-1] if self._inflight else None
            before = prev is not None and frozenset(
                seq.seq_id for seq in self._still_live(prev.rows)
                if seq is not None)
            if not before:
                return
            stall = self._stall = prev.opens = {
                "before": before, "prefills": 0, "prompt_tokens": 0}
        stall["prefills"] += 1
        stall["prompt_tokens"] += prompt_tokens

    def _stall_ends(self, stall: dict, rows, now: float) -> None:
        """The tokens of the first step after an admission episode are
        going out: what a row that was decoding waited, as
        ``generate.stall`` and in the histogram."""
        t0, before = stall.pop("t0"), stall.pop("before")
        telemetry.span_at(
            "generate.stall", telemetry.us_of(t0) * 1e-6, now - t0,
            model=self.name, iter=self._iter,
            rows=sum(seq is not None and seq.seq_id in before
                     for seq in rows), **stall)
        _stall_hist().observe(now - t0, model=self.name)

    def _build_step(self, prev: Optional[_Step] = None):
        """Everything the host does before a step can be dispatched:
        pre-step retirement, one more token slot for every row, the
        padded inputs, the step's key and what its sampler will do
        (:meth:`_name_sample`). With ``prev``, the last
        step dispatched and still in flight, the rows keep their
        places, stand as many positions further as steps in flight
        carry them, and take its ids as their tokens; each joiner (a
        sequence admitted since, its first token still on the device)
        takes a hole at its prompt length, that token merged into its
        row. None when no row is left, or when the rows of ``prev``
        will not do: a sequence without a row that is no joiner, more
        joiners than holes, or, with no joiner, a smaller bucket."""
        import jax
        now = time.monotonic()
        # pre-step retirement: cancelled / deadline sequences leave
        # and their blocks free before we spend device time
        for seq in list(self._live.values()):
            if seq.stream.cancelled:
                self._retire(seq, "cancelled")
            elif seq.deadline is not None and now >= seq.deadline:
                self._retire(seq, "deadline")
        if prev is None:
            # grow every sequence by one token slot; a pool with no
            # free block sheds THAT sequence mid-batch, the rest keep
            # decoding
            for seq in list(self._live.values()):
                try:
                    self.pool.extend(seq.seq_id, 1)
                except PoolExhausted as e:
                    self._retire(seq, "kv_pool", e)
            rows = list(self._live.values())[:self.decode_buckets[-1]]
            b = self._decode_bucket(len(rows))
            joined = ()
        else:
            rows, rowless = self._rows_after(prev)
            holes = [i for i, seq in enumerate(rows) if seq is None]
            b = prev.bucket
            if any(seq.first is None for seq in rowless) \
                    or len(rowless) > len(holes) or not rowless \
                    and self._decode_bucket(b - len(holes)) != b:
                return None
            # a step with joiners keeps its bucket: the next one packs
            # the rows into a smaller one if they fit
            for i, seq in zip(holes, rowless):
                rows[i] = seq
            for i, seq in enumerate(rows):
                if seq is not None:
                    try:
                        self.pool.extend(seq.seq_id, 1)
                    except PoolExhausted as e:
                        self._retire(seq, "kv_pool", e)
                        rows[i] = None
            joined = [(i, seq) for i, seq in zip(holes, rowless)
                      if rows[i] is seq]
        if not any(seq is not None for seq in rows):
            return None
        tokens = np.zeros((b,), np.int32)
        positions = np.zeros((b,), np.int32)
        tables = np.zeros((b, self.max_blocks), np.int32)
        temps = np.zeros((b,), np.float32)
        topks = np.zeros((b,), np.int32)
        state_slots = np.zeros((b,), np.int32)      # dead rows: scratch
        for i, seq in enumerate(rows):
            if seq is None:
                continue
            tokens[i] = seq.next_token
            positions[i] = seq.position + seq.flying
            tables[i] = self.pool.padded_table(seq.seq_id,
                                               self.max_blocks)
            temps[i] = seq.temperature
            topks[i] = seq.top_k
            state_slots[i] = self.pool.slot(seq.seq_id)
        self._record(tokens, positions, tables, temps, topks)
        self._step += 1
        key = jax.random.fold_in(self._rng, self._step)
        ids = tokens if prev is None else prev.ids
        for i, seq in joined:
            row = np.int32(i)
            self._record(ids, row, seq.first)
            ids, seq.first = self._merge_jit()(ids, row, seq.first), None
        inputs = (ids, positions, tables, key, temps, topks,
                  *self._state_arg(state_slots))
        return rows, b, inputs, {**self._name_sample(temps, topks),
                                 **self._cache_reads(rows, positions)}

    def _rows_after(self, prev: _Step):
        """The rows of a step built on ``prev``, padded to its bucket,
        with a hole (None) where a sequence retired or a step in flight
        brings its last token; and the live sequences that need a step
        and have no row there (joiners, or sequences that wait)."""
        rows = [seq if seq is not None and seq.generated
                + seq.flying < seq.max_tokens else None
                for seq in self._still_live(prev.rows)]
        rows += [None] * (prev.bucket - len(rows))
        placed = {seq.seq_id for seq in rows if seq is not None}
        rowless = [seq for seq in self._live.values()
                   if seq.generated + seq.flying < seq.max_tokens
                   and seq.seq_id not in placed]
        return rows, rowless

    def _cache_reads(self, rows, positions) -> dict:
        """The K/V tokens this step's live rows hold and its layers
        read, as span attributes: nothing for a model whose layers all
        read one growing cache."""
        reads = self._reads
        if reads is None:
            return {}
        live = np.asarray([seq is not None for seq in rows])
        ctx = positions[:len(rows)][live].astype(np.int64) + 1
        kv = int(ctx.sum())
        ring = int(np.minimum(ctx, reads["window"]).sum())
        window = reads["window_layers"] * ring
        out = {"kv_tokens": kv, "ring_tokens": ring,
               "window_read_tokens": window,
               "kv_read_tokens": reads["kv_readers"] * kv + window}
        if "kv_token_bytes" in reads:
            # a ring token and a pool token differ in width
            ring_bytes = window * reads["window_token_bytes"]
            out.update(window_read_bytes=ring_bytes,
                       kv_read_bytes=reads["kv_readers"] * kv
                       * reads["kv_token_bytes"] + ring_bytes)
        return out

    def _emit(self, step: _Step, rows, ids, step_s) -> int:
        """Hand every row of ``step`` still live (``rows``) its token:
        meters, the stream's queue, the request's ``inter_token``
        instant, and retirement on EOS or ``max_tokens``; and the ends
        of the admission episodes the step bounds. Returns how many
        rows retired."""
        step_hist, occupancy, tokens, gap_hist, *_ = self._meters()
        step_hist.observe(step_s)
        occupancy.observe(sum(seq is not None for seq in rows)
                          / max(1, step.bucket))
        now = time.perf_counter()
        if step.closes is not None:
            self._stall_ends(step.closes, rows, now)
        if step.opens is not None:
            step.opens["t0"] = now
        eos = self.model.conf.eos_id
        retired = 0
        for i, seq in enumerate(rows):
            if seq is None:
                continue
            tok = int(ids[i])
            seq.stream._put(tok)
            tokens.inc()
            if seq.ctx is not None:
                seq.ctx.instant(
                    "inter_token", index=seq.generated,
                    gap_ms=round((now - seq.t_last) * 1e3, 3))
            gap_hist.observe(now - seq.t_last)
            seq.t_last = now
            seq.position += 1
            seq.next_token = tok
            seq.generated += 1
            seq.flying -= 1
            if tok == eos or seq.generated >= seq.max_tokens:
                self._retire(seq, "eos" if tok == eos else "max_tokens")
                retired += 1
        return retired

    def _record(self, *arrays) -> None:
        hit = self.guard.record(*arrays)
        if self._warmed and not hit:
            telemetry.counter(
                "dl4j_serving_bucket_miss_total",
                "post-warmup flushes whose padded signature no warm "
                "bucket covered — a cold XLA compile on the serving "
                "path (shape/dtype drift, or grow the bucket set)"
            ).inc(model=self.name)
