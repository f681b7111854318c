"""Per-model dynamic batcher with shape-bucketed flushes.

Reuses the ``ParallelInference`` submit/flush discipline (background
worker drains a queue and aggregates requests) with two
serving-critical changes. First, every flush is padded UP to the
nearest *warm bucket* — a batch size whose XLA program was compiled at
warmup — so steady-state requests never retrace (TVM's ahead-of-time
compilation discipline, PAPERS.md 1802.04799). A per-version
``RetraceGuard`` counts signatures; after warmup its count must not
move. Second, the default flush trigger is **continuous** (Orca-style
iteration-level scheduling): the worker flushes the moment the device
is free and takes whatever is waiting — occupancy-driven, not
clock-driven. A request never waits out a fixed window behind an idle
device; under load, queue depth alone fills the buckets. The classic
fixed ``batch_window_ms`` behavior stays available as
``flush_policy="window"``. Realized fill lands in the
``dl4j_serving_batch_occupancy`` histogram (live rows / padded rows).

Two model surfaces:

- MLN/ComputationGraph: the jitted sharded forward inherited from
  ``ParallelInference`` (params replicated over the mesh, batch
  sharded over ``data``) — or, with ``mode="sharded"``/``"fsdp"``,
  the ZeRO-layout resident placement from ``serving.residency``:
  params live 1/N-sharded between requests and are gathered inside
  the jitted forward, bitwise-equal to the dense path. The sharded
  tree lives on the *batcher* (``_serve_params``), never on the model,
  so ``model.output`` and training paths stay untouched.
- generic (``SameDiff`` adapters, ONNX importers): any object whose
  ``output(batch) -> array`` is signature-cached internally — bucket
  padding keeps *its* cache to one entry per bucket too (dense only).

Requests carry an optional ``time.monotonic()`` deadline: a request
whose deadline expires while queued is cancelled at flush time with
:class:`~deeplearning4j_tpu.serving.admission.DeadlineExceeded` —
never computed (counted under
``dl4j_serving_deadline_shed_total{where="queue"}``).
"""
from __future__ import annotations

import concurrent.futures
import queue as _queue
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.common import telemetry
from deeplearning4j_tpu.common.compilecache import RetraceGuard
from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                   ParallelInference)
from deeplearning4j_tpu.serving.admission import (DeadlineExceeded,
                                                  _deadline_shed_counter)

_LATENCY_HELP = ("serving request latency by stage: queue "
                 "(submit->flush), compute (flush forward), total "
                 "(submit->result), warmup (per-bucket pre-compile) "
                 "(seconds)")

#: flush triggers: continuous = flush whenever the device frees and
#: requests wait (iteration-level scheduling); window = hold the first
#: request up to batch_window_ms hoping for batch-mates (the PR-3 seed)
FLUSH_POLICIES = ("continuous", "window")


def _latency() -> telemetry.Histogram:
    return telemetry.histogram("dl4j_serving_latency_seconds",
                               _LATENCY_HELP)


class ServingBatcher(ParallelInference):
    """A ``ParallelInference`` whose flushes land on warm buckets."""

    def __init__(self, model, buckets: Sequence[int] = (8, 32),
                 mesh=None, *, name: str = "model",
                 batch_window_ms: float = 2.0,
                 queue_limit: int = 256,
                 guard: Optional[RetraceGuard] = None,
                 flush_policy: str = "continuous",
                 mode: str = "dense",
                 tensor_parallel: Optional[int] = None,
                 generate: Optional[dict] = None,
                 param_dtype=None):
        #: generic path: no MLN `_forward` funnel — serve through the
        #: model's own `output(batch)` (SameDiff/ONNX adapters)
        self._generic = None if hasattr(model, "_forward") \
            else model.output
        #: generative path: a model exposing the prefill/decode_step
        #: contract gets a DecodeEngine beside the predict path
        self._generative = (hasattr(model, "prefill")
                            and hasattr(model, "decode_step"))
        self.generate_config = dict(generate or {})
        self.engine = None
        if not buckets:
            raise ValueError("need at least one warmup bucket")
        if flush_policy not in FLUSH_POLICIES:
            raise ValueError(f"flush_policy must be one of "
                             f"{FLUSH_POLICIES}, got {flush_policy!r}")
        from deeplearning4j_tpu.serving.residency import (
            assert_mode, resolve_param_dtype)
        assert_mode(mode)
        self.param_dtype = resolve_param_dtype(param_dtype)
        if self.param_dtype is not None and mode == "dense":
            raise ValueError(
                f"param_dtype={self.param_dtype!r} needs a sharded "
                f"residency mode ('sharded'/'fsdp'); dense serving "
                f"keeps the model's own float32 tree")
        if mode != "dense" and self._generic is not None \
                and not self._generative:
            raise ValueError(
                f"residency mode {mode!r} needs a param-tree model "
                f"(MLN/ComputationGraph); generic output() models "
                f"serve dense only")
        super().__init__(model, mesh,
                         inference_mode=InferenceMode.BATCHED,
                         batch_limit=max(int(b) for b in buckets),
                         queue_limit=queue_limit,
                         batch_window_ms=batch_window_ms)
        if self._generic is None:
            # sharded forward: buckets must be shard multiples, or the
            # place-time pad would silently shift them to a new shape
            w = self.n_workers
            buckets = {-(-int(b) // w) * w for b in buckets}
        self.buckets = tuple(sorted(int(b) for b in set(buckets)))
        self.batch_limit = self.buckets[-1]
        self.name = name
        self.flush_policy = flush_policy
        self.mode = mode
        self.tensor_parallel = tensor_parallel
        self.guard = guard if guard is not None else RetraceGuard(
            f"serving:{name}", threshold=len(self.buckets) + 1)
        self._warmed = False
        #: the resident-sharded serving layout (mode != dense); lives
        #: here — never on the model — so model.output stays dense
        self._serve_params = None
        self._serve_states = None
        self._fsdp_specs = None
        self._serve_tp_specs = None

    # ------------------------------------------------------------------
    @property
    def params(self):
        """What this batcher actually holds resident — the sharded
        serving layout when one is placed, else the model's own tree
        (the ``memory_report`` attribution surface)."""
        if self._serve_params is not None:
            return self._serve_params
        return getattr(self.model, "params", None)

    def _ensure(self):
        if self._generic is not None:
            return
        if self.mode == "dense":
            super()._ensure()
            return
        m = self.model
        if not m._initialized:
            m.init()
        if not self._placed:
            from deeplearning4j_tpu.parallel.mesh import replicate_tree
            from deeplearning4j_tpu.serving.residency import \
                serving_layouts
            (self._serve_params, self._fsdp_specs,
             self._serve_tp_specs) = serving_layouts(
                self.mesh, m.params, self.mode, self.tensor_parallel,
                name=self.name, param_dtype=self.param_dtype)
            self._serve_states = replicate_tree(self.mesh, m.states)
            self._placed = True
        if self._fwd is None:
            import jax

            from deeplearning4j_tpu.nn.graph import ComputationGraph
            from deeplearning4j_tpu.serving.residency import \
                serving_param_view
            is_graph = isinstance(m, ComputationGraph)
            mesh, mode = self.mesh, self.mode
            specs, tp_specs = self._fsdp_specs, self._serve_tp_specs
            pd = self.param_dtype

            def fwd(params, states, x):
                view = serving_param_view(params, specs, mesh,
                                          tp_specs, mode,
                                          param_dtype=pd)
                if is_graph:
                    acts, _ = m._forward(view, states, [x],
                                         training=False, rng=None,
                                         want_logits=False)
                    return acts[m.conf.network_outputs[0]]
                out, _ = m._forward(view, states, x, training=False,
                                    rng=None, want_logits=False)
                return out

            self._fwd = jax.jit(fwd)

    def _bucket_for(self, n: int) -> Optional[int]:
        for b in self.buckets:
            if n <= b:
                return b
        return None

    def _pad_to_bucket(self, chunk: np.ndarray) -> np.ndarray:
        """Pad the chunk's batch dim up to the nearest warm bucket by
        repeating the final row (sliced back off after the forward).
        Chunks are pre-capped at the largest bucket, so a bucket
        always exists."""
        n = chunk.shape[0]
        b = self._bucket_for(n)
        if b is None or b == n:
            return chunk
        reps = np.repeat(chunk[-1:], b - n, axis=0)
        return np.concatenate([chunk, reps], axis=0)

    def _record(self, sig_array) -> None:
        """Guard bookkeeping for one dispatch: a NEW signature after
        warmup finished is a bucket miss — the request paid the cold
        compile the warmup set was supposed to cover (feature-shape/
        dtype drift, or a bucket the set is missing)."""
        hit = self.guard.record(sig_array)
        if self._warmed and not hit:
            telemetry.counter(
                "dl4j_serving_bucket_miss_total",
                "post-warmup flushes whose padded signature no warm "
                "bucket covered — a cold XLA compile on the serving "
                "path (shape/dtype drift, or grow the bucket set)"
            ).inc(model=self.name)

    def _forward_padded(self, padded: np.ndarray, orig: int
                        ) -> np.ndarray:
        if self._generic is not None:
            self._record(padded)
            return np.asarray(self._generic(padded))[:orig]
        placed, _ = self._place_chunk(padded)
        self._record(placed)
        if self._serve_params is not None:
            out = self._run_fwd(self._serve_params, self._serve_states,
                                placed)
        else:
            out = self._run_fwd(self.model.params, self.model.states,
                                placed)
        return np.asarray(out)[:orig]

    # ------------------------------------------------------------------
    def warmup(self, input_shape: Sequence[int],
               dtype=np.float32) -> float:
        """Pre-compile every bucket's program (one forward per bucket,
        blocked to completion) so the first real request hits a warm
        signature. ``input_shape`` is one request's shape WITHOUT the
        batch dim. Returns total warmup seconds."""
        self._ensure()
        lat = _latency()
        t_all = time.perf_counter()
        for b in self.buckets:
            x = np.zeros((b,) + tuple(input_shape), dtype)
            t0 = time.perf_counter()
            with telemetry.span("serving.warmup", model=self.name,
                                bucket=b):
                # _forward_padded's np.asarray is the sync point: the
                # bucket's program has fully compiled AND run once by
                # the time this returns
                self._forward_padded(x, b)
            lat.observe(time.perf_counter() - t0, model=self.name,
                        stage="warmup")
        self._warmed = True
        return time.perf_counter() - t_all

    # -- generative path (ISSUE 16) ------------------------------------
    @property
    def is_generative(self) -> bool:
        return self._generative

    def _ensure_generate(self):
        """Build the KV pool + DecodeEngine on first use. Residency
        modes compose: under ``sharded``/``fsdp`` the model's params
        are placed resident-sharded (``serving.residency``) and the
        engine's jitted programs consume them through the serving
        param view — the KV pool itself stays dense-replicated (every
        chip decodes every sequence, classifier-serving style)."""
        if not self._generative:
            raise ValueError(f"model {self.name!r} has no "
                             f"prefill/decode_step surface")
        if self.engine is not None:
            return self.engine
        import functools

        from deeplearning4j_tpu.serving.generative import DecodeEngine
        from deeplearning4j_tpu.serving.kvcache import KVBlockPool
        cfg = self.generate_config
        m = self.model
        if getattr(m, "params", None) is None:
            m.init()
        c = m.conf
        from deeplearning4j_tpu.common.dtypes import to_jnp_dtype
        kv_dtype = cfg.get("kv_dtype")
        if kv_dtype is None:
            # fleet-wide default; per-model generate={'kv_dtype': ...}
            # overrides it
            import os
            kv_dtype = os.environ.get("DL4J_TPU_KV_DTYPE", "").strip() \
                or "float32"
        if isinstance(kv_dtype, str):
            kv_dtype = to_jnp_dtype(
                "bfloat16" if kv_dtype in ("bf16", "bfloat16")
                else kv_dtype)
        decode_buckets = cfg.get("decode_buckets", (4, 8))
        # a model with recurrent state (state-space layers) names its
        # per-sequence state arrays; every row of the largest decode
        # bucket needs a slot of its own beside the scratch slot
        state = m.state_shapes() if hasattr(m, "state_shapes") else None
        need = max(decode_buckets) + 1
        state_slots = int(cfg.get("state_slots", need)) if state else 0
        if state and state_slots < need:
            raise ValueError(
                f"state_slots {state_slots} < largest decode bucket + 1 "
                f"= {need} (slot 0 is scratch)")
        pool = KVBlockPool(
            # the layers whose K/V grows with the context: all of them,
            # unless the model keeps windows or shares one cache
            getattr(m, "kv_layers", c.n_layers),
            int(cfg.get("kv_blocks", 64)),
            int(cfg.get("kv_block_size", 16)),
            getattr(c, "n_kv_heads", c.n_heads), c.head_dim,
            dtype=kv_dtype, name=self.name,
            state=state, state_slots=state_slots,
            v_head_dim=getattr(c, "v_head_dim", None))
        params, view_fn = m.params, None
        if self.mode != "dense":
            from deeplearning4j_tpu.serving.residency import (
                serving_layouts, serving_param_view)
            placed, fsdp_specs, tp_specs = serving_layouts(
                self.mesh, m.params, self.mode, self.tensor_parallel,
                name=self.name, param_dtype=self.param_dtype)
            self._serve_params = placed
            self._fsdp_specs = fsdp_specs
            self._serve_tp_specs = tp_specs
            params = placed
            view_fn = functools.partial(
                serving_param_view, fsdp_specs=fsdp_specs,
                mesh=self.mesh, tp_specs=tp_specs, mode=self.mode,
                param_dtype=self.param_dtype)
        self.engine = DecodeEngine(
            m, params, pool, view_fn=view_fn, name=self.name,
            prompt_buckets=cfg.get("prompt_buckets", (16, 64)),
            decode_buckets=decode_buckets,
            max_seq_len=cfg.get("max_seq_len"),
            paged=cfg.get("paged"), guard=self.guard,
            rng_seed=int(cfg.get("rng_seed", 0)))
        return self.engine

    def warmup_generate(self) -> float:
        """Compile every prefill/commit/decode bucket program before
        the first real generate request (the generative half of
        :meth:`warmup`). Returns warmup seconds."""
        engine = self._ensure_generate()
        lat = _latency()
        t0 = time.perf_counter()
        with telemetry.span("serving.warmup_generate",
                            model=self.name):
            secs = engine.warmup()
        lat.observe(secs, model=self.name, stage="warmup")
        self._warmed = True
        return time.perf_counter() - t0

    def generate_cost(self, prompt_len: int, max_tokens: int = 0
                      ) -> int:
        """Token-cost of a generate admission (KV blocks)."""
        return self._ensure_generate().generate_cost(prompt_len,
                                                     max_tokens)

    def submit_generate(self, prompt, max_tokens: int, *,
                        temperature: float = 0.0, top_k: int = 0,
                        deadline: Optional[float] = None,
                        ctx=None):
        """Enqueue a generate request; returns the
        :class:`~deeplearning4j_tpu.serving.generative.TokenStream`.
        Raises PoolExhausted synchronously when the KV pool cannot
        hold the prompt (shed upstream as 429 + Retry-After).
        ``ctx`` (the request's TraceContext) rides the pending entry
        into the engine for cross-thread phase attribution."""
        engine = self._ensure_generate()
        telemetry.counter(
            "dl4j_inference_requests_total",
            "requests submitted to ParallelInference").inc(
                mode="generate")
        return engine.submit(prompt, max_tokens,
                             temperature=temperature, top_k=top_k,
                             deadline=deadline, ctx=ctx)

    def shutdown(self, *a, **kw):
        if self.engine is not None:
            self.engine.shutdown()
        return super().shutdown(*a, **kw)

    # ------------------------------------------------------------------
    def output_batched(self, requests: List) -> List[np.ndarray]:
        """Aggregate ``requests`` into bucket-padded flushes. Unlike
        the base class this never compiles an odd shape in steady
        state: total rows are chunked by the largest bucket and each
        chunk padded to its nearest bucket."""
        if not requests:
            return []
        self._ensure()
        arrays = [np.asarray(r) for r in requests]
        sizes = [a.shape[0] for a in arrays]
        big = np.concatenate(arrays, axis=0) if len(arrays) > 1 \
            else arrays[0]
        cap = self.buckets[-1]
        outs = []
        for i in range(0, big.shape[0], cap):
            chunk = np.asarray(big[i:i + cap])
            n = chunk.shape[0]
            outs.append(self._forward_padded(
                self._pad_to_bucket(chunk), n))
        flat = np.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
        result, off = [], 0
        for s in sizes:
            result.append(flat[off:off + s])
            off += s
        return result

    # ------------------------------------------------------------------
    def submit(self, x,
               deadline: Optional[float] = None,
               ctx=None) -> "concurrent.futures.Future":
        """Enqueue one request; ``deadline`` is an absolute
        ``time.monotonic()`` instant past which the request must not
        be computed (its Future then raises DeadlineExceeded).
        ``ctx`` is the request's
        :class:`~deeplearning4j_tpu.common.tracectx.TraceContext`:
        the flush worker runs on its own thread, so the context rides
        the Future and phase intervals are attributed back with
        ``phase_at``."""
        fut: concurrent.futures.Future = concurrent.futures.Future()
        if deadline is not None:
            fut._serving_deadline = float(deadline)
        if ctx is not None:
            fut._trace_ctx = ctx
        telemetry.counter(
            "dl4j_inference_requests_total",
            "requests submitted to ParallelInference").inc(
                mode=self.inference_mode)
        # same locking discipline as the base class: the put happens
        # under the lock shutdown() takes to enqueue its sentinel
        with self._lock:
            self._ensure_worker()
            self._requests.put((x, fut, time.monotonic()))
        return fut

    def _ensure_worker(self):
        """Start the flush worker (caller holds ``self._lock``).

        ``window`` policy keeps the base loop: hold the first request
        up to ``batch_window_ms`` collecting batch-mates. The
        ``continuous`` loop never arms a clock — it blocks for ONE
        request, greedily drains whatever else is already queued (up
        to ``batch_limit``), and flushes immediately. Batch formation
        comes from device busy time alone: while a flush computes,
        arrivals accumulate in the queue and the next iteration takes
        them all. An idle device therefore gives a lone request
        zero added latency, and a saturated one fills buckets — the
        fixed window's latency floor is gone in both regimes."""
        if self.flush_policy != "continuous":
            super()._ensure_worker()
            return
        if self._worker is not None:
            return
        self._requests = _queue.Queue(self.queue_limit)
        self._shutdown = False
        q = self._requests                       # bind THIS queue

        def loop():
            while True:
                try:
                    first = q.get(timeout=0.1)
                except _queue.Empty:
                    if self._shutdown:
                        return
                    continue
                if first is None:
                    return
                batch = [first]
                while len(batch) < self.batch_limit:
                    try:
                        nxt = q.get_nowait()
                    except _queue.Empty:
                        break
                    if nxt is None:
                        self._flush(batch)
                        return
                    batch.append(nxt)
                self._flush(batch)

        self._worker = threading.Thread(target=loop, daemon=True,
                                        name="dl4j-tpu-serving")
        self._worker.start()

    def _padded_rows(self, rows: int) -> int:
        """Rows the device actually computes for ``rows`` live rows
        after chunking by the largest bucket and padding each chunk
        up — the occupancy denominator."""
        cap, total = self.buckets[-1], 0
        while rows > 0:
            take = min(rows, cap)
            total += self._bucket_for(take) or take
            rows -= take
        return total

    def _flush(self, batch):
        now = time.monotonic()
        live = []
        for x, f, t in batch:
            dl = getattr(f, "_serving_deadline", None)
            if dl is not None and now >= dl:
                # expired while queued: cancel, never compute
                telemetry.counter(
                    "dl4j_serving_deadline_expired_total",
                    "requests whose deadline passed while queued — "
                    "cancelled before compute").inc(model=self.name)
                _deadline_shed_counter().inc(model=self.name,
                                             where="queue")
                if f.set_running_or_notify_cancel():
                    f.set_exception(DeadlineExceeded(
                        f"deadline passed {now - dl:.3f}s before "
                        f"flush"))
                continue
            if f.set_running_or_notify_cancel():
                live.append((x, f, t))
        if not live:
            return
        lat = _latency()
        if telemetry.enabled():
            for _, _, t in live:
                lat.observe(now - t, model=self.name, stage="queue")
            telemetry.histogram(
                "dl4j_inference_batch_occupancy",
                "aggregated-batch fill fraction per flush "
                "(requests / batch_limit)",
                buckets=telemetry.RATIO_BUCKETS).observe(
                    len(live) / max(1, self.batch_limit))
            rows = sum(int(np.asarray(x).shape[0])
                       for x, _, _ in live)
            telemetry.histogram(
                "dl4j_serving_batch_occupancy",
                "live rows / bucket-padded rows per serving flush — "
                "how full the warm buckets actually run (1.0 = no "
                "padding waste; continuous batching should push this "
                "up under load)",
                buckets=telemetry.RATIO_BUCKETS).observe(
                    rows / max(1, self._padded_rows(rows)),
                    model=self.name, policy=self.flush_policy)
        t0 = time.perf_counter()
        t_dev0 = time.monotonic()
        try:
            with telemetry.span("serving.flush", model=self.name,
                                requests=len(live)):
                outs = self.output_batched([x for x, _, _ in live])
        except BaseException as e:           # noqa: BLE001
            for _, f, _ in live:
                f.set_exception(e)
            return
        t_dev1 = time.monotonic()
        lat.observe(time.perf_counter() - t0, model=self.name,
                    stage="compute")
        end = time.monotonic()
        occ = None
        for (_, f, t), o in zip(live, outs):
            lat.observe(end - t, model=self.name, stage="total")
            ctx = getattr(f, "_trace_ctx", None)
            if ctx is not None:
                # request timeline: queue (submit -> this flush),
                # batch_wait (deadline/occupancy bookkeeping before
                # the device dispatch), device (the flush forward)
                if occ is None:
                    r = sum(int(np.asarray(x).shape[0])
                            for x, _, _ in live)
                    occ = round(r / max(1, self._padded_rows(r)), 3)
                ctx.phase_at("queue", t, now)
                ctx.phase_at("batch_wait", now, t_dev0)
                ctx.phase_at("device", t_dev0, t_dev1)
                ctx.note(batch=len(live), occupancy=occ)
            f.set_result(o)
