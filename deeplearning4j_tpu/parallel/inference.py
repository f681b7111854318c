"""ParallelInference: batched multi-device inference.

Reference parity: ``org.deeplearning4j.parallelism.ParallelInference``
(SURVEY.md P6) — request batching across threads with per-device model
workers and observable round-trips.

TPU-first design: one jitted forward, batch sharded over the mesh
``data`` axis; XLA splits the work across devices. `BATCHED` mode's
request aggregation becomes a `batch_limit`-sized queue flushed through
the sharded program; `SEQUENTIAL` mode is a plain single call.
"""
from __future__ import annotations

import concurrent.futures
import logging
import queue as _queue
import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common import telemetry
from deeplearning4j_tpu.parallel.mesh import (DEFAULT_DATA_AXIS,
                                              data_sharding, make_mesh,
                                              pad_batch_to_multiple,
                                              replicate_tree)

log = logging.getLogger("deeplearning4j_tpu")


class InferenceMode:
    #: run each request directly on the shared jitted forward — no
    #: queue, lowest latency (reference: InferenceMode.INPLACE)
    INPLACE = "INPLACE"
    SEQUENTIAL = "SEQUENTIAL"
    #: aggregate requests into up-to-batch_limit batches (reference:
    #: InferenceMode.BATCHED via the observable queue)
    BATCHED = "BATCHED"


class ParallelInference:
    def __init__(self, model, mesh=None, *,
                 inference_mode: str = InferenceMode.BATCHED,
                 batch_limit: int = 32,
                 queue_limit: int = 64,
                 batch_window_ms: float = 2.0):
        self.model = model
        self.mesh = mesh if mesh is not None else make_mesh()
        self.inference_mode = inference_mode
        self.batch_limit = batch_limit
        self.queue_limit = queue_limit
        #: how long the batching worker waits for more requests once
        #: it holds at least one (the latency/throughput knob)
        self.batch_window_ms = batch_window_ms
        self._fwd = None
        self._placed = False
        self._worker = None
        self._requests = None
        self._shutdown = False
        self._lock = threading.Lock()

    class Builder:
        def __init__(self, model):
            self._model = model
            self._mesh = None
            self._mode = InferenceMode.BATCHED
            self._batch_limit = 32
            self._queue_limit = 64
            self._workers = None
            self._batch_window_ms = 2.0

        def inference_mode(self, mode: str):
            self._mode = mode
            return self

        def batch_limit(self, n: int):
            self._batch_limit = n
            return self

        def queue_limit(self, n: int):
            self._queue_limit = n
            return self

        def workers(self, n: int):
            self._workers = n
            return self

        def batch_window_ms(self, ms: float):
            self._batch_window_ms = float(ms)
            return self

        def build(self) -> "ParallelInference":
            mesh = self._mesh
            if mesh is None:
                devs = jax.devices()
                if self._workers:
                    devs = devs[:self._workers]
                mesh = make_mesh({DEFAULT_DATA_AXIS: len(devs)}, devs)
            return ParallelInference(self._model, mesh,
                                     inference_mode=self._mode,
                                     batch_limit=self._batch_limit,
                                     queue_limit=self._queue_limit,
                                     batch_window_ms=
                                     self._batch_window_ms)

    # ------------------------------------------------------------------
    @property
    def n_workers(self) -> int:
        return self.mesh.shape[DEFAULT_DATA_AXIS]

    def _ensure(self):
        m = self.model
        if not m._initialized:
            m.init()
        if not self._placed:
            m.params = replicate_tree(self.mesh, m.params)
            m.states = replicate_tree(self.mesh, m.states)
            self._placed = True
        if self._fwd is None:
            from deeplearning4j_tpu.nn.graph import ComputationGraph
            is_graph = isinstance(m, ComputationGraph)

            def fwd(params, states, x):
                if is_graph:
                    acts, _ = m._forward(params, states, [x],
                                         training=False, rng=None,
                                         want_logits=False)
                    return acts[m.conf.network_outputs[0]]
                out, _ = m._forward(params, states, x, training=False,
                                    rng=None, want_logits=False)
                return out

            # idempotent lazy init: racing callers both build the same
            # jitted fn and the last assignment wins — no torn state
            # dl4j-lint: disable=lock-discipline
            self._fwd = jax.jit(fwd)

    def _run_fwd(self, params, states, placed):
        """The jitted forward, traced under the mesh's partition mark
        (``kernel_select.partitioned``): on a multi-device mesh the
        program is GSPMD-partitioned, which Mosaic kernels cannot be."""
        from deeplearning4j_tpu.ops import kernel_select
        with kernel_select.partitioned(self.mesh.size):
            return self._fwd(params, states, placed)

    def _place_chunk(self, x):
        """Pad to a shard multiple and device_put sharded over the mesh
        (an async dispatch — the H2D DMA proceeds in the background).
        Returns (placed, original_batch)."""
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(self.model._dtype)
        padded, orig = pad_batch_to_multiple(x, self.n_workers)
        placed = jax.device_put(
            padded, data_sharding(self.mesh, padded.ndim))
        return placed, orig

    def output(self, x) -> np.ndarray:
        """Run inference on ``x``; pads the batch to a shard multiple and
        slices the padding back off (padding is safe for inference,
        unlike training — mesh.py note)."""
        self._ensure()
        placed, orig = self._place_chunk(x)
        out = self._run_fwd(self.model.params, self.model.states,
                            placed)
        return np.asarray(out[:orig])

    def output_batched(self, requests: List) -> List[np.ndarray]:
        """BATCHED mode: aggregate many small requests into shard-wide
        batches (the reference's observable queue, synchronously).

        Chunks are double-buffered: chunk i+1's sharded ``device_put``
        is dispatched BEFORE the host blocks on chunk i's result, so
        the next H2D DMA overlaps the current forward + D2H — the
        DevicePrefetcher discipline applied to the serving path
        (``DL4J_TPU_DEVICE_PREFETCH=0`` reverts to serial placement)."""
        if not requests:
            return []
        self._ensure()
        from deeplearning4j_tpu.common.environment import Environment
        arrays = [np.asarray(r) for r in requests]
        sizes = [a.shape[0] for a in arrays]
        big = np.concatenate(arrays, axis=0)
        chunks = [big[i:i + self.batch_limit]
                  for i in range(0, big.shape[0], self.batch_limit)]
        overlap = Environment.get().device_prefetch
        outs = []
        placed = self._place_chunk(chunks[0]) if chunks else None
        for i in range(len(chunks)):
            cur, orig = placed
            # device compute for the current chunk: dispatched async
            out = self._run_fwd(self.model.params, self.model.states,
                                cur)
            if i + 1 < len(chunks):
                if overlap:
                    # stage chunk i+1 while chunk i computes/transfers
                    placed = self._place_chunk(chunks[i + 1])
                    outs.append(np.asarray(out[:orig]))   # sync point
                else:
                    outs.append(np.asarray(out[:orig]))
                    placed = self._place_chunk(chunks[i + 1])
            else:
                outs.append(np.asarray(out[:orig]))
        flat = np.concatenate(outs, axis=0)
        result, off = [], 0
        for s in sizes:
            result.append(flat[off:off + s])
            off += s
        return result

    # -- async observable serving (reference: ParallelInference's
    # request queue + worker batching; output(Observable) round) -------
    def submit(self, x) -> "concurrent.futures.Future":
        """Enqueue one request; returns a Future resolving to its
        result. In BATCHED mode a background worker drains the queue,
        aggregates up to ``batch_limit`` requests (or whatever is
        waiting after ``batch_window_ms``) into ONE forward, and
        distributes the slices — the reference's observable BATCHED
        serving loop. INPLACE/SEQUENTIAL run the request directly
        (no queue, no cross-request aggregation)."""
        fut: concurrent.futures.Future = concurrent.futures.Future()
        telemetry.counter(
            "dl4j_inference_requests_total",
            "requests submitted to ParallelInference").inc(
                mode=self.inference_mode)
        if self.inference_mode != InferenceMode.BATCHED:
            if fut.set_running_or_notify_cancel():
                try:
                    fut.set_result(self.output(x))
                except BaseException as e:       # noqa: BLE001
                    fut.set_exception(e)
            return fut
        # the put happens UNDER the lock shutdown() takes to enqueue
        # its sentinel: a racing submit can therefore never land behind
        # the sentinel on a dead queue (which would strand its Future
        # forever). A submit that wins the lock AFTER shutdown sees
        # _worker None and _ensure_worker restarts the service. The
        # put can block briefly when the queue is full; the worker
        # never takes this lock, so it keeps draining and the put
        # always completes.
        with self._lock:
            self._ensure_worker()
            self._requests.put((x, fut, time.monotonic()))
        return fut

    def _ensure_worker(self):
        """Start the batching worker (caller holds ``self._lock``)."""
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
                deadline = time.monotonic() + self.batch_window_ms / 1e3
                while len(batch) < self.batch_limit:
                    left = deadline - time.monotonic()
                    try:
                        nxt = q.get(timeout=max(left, 0) or 0.0001)
                    except _queue.Empty:
                        break
                    if nxt is None:
                        self._flush(batch)
                        return
                    batch.append(nxt)
                self._flush(batch)

        # caller holds self._lock (see docstring) — submit's
        # queue-bind and the worker start stay atomic
        # dl4j-lint: disable=lock-discipline
        self._worker = threading.Thread(target=loop, daemon=True,
                                        name="dl4j-tpu-serving")
        self._worker.start()

    def _flush(self, batch):
        # a caller may have cancelled its future while queued (client
        # timeout) — skip those; one cancelled request must not kill
        # the worker or starve its batch-mates
        live = [(x, f, t) for x, f, t in batch
                if f.set_running_or_notify_cancel()]
        if not live:
            return
        if telemetry.enabled():
            now = time.monotonic()
            lat = telemetry.histogram(
                "dl4j_inference_queue_seconds",
                "submit-to-flush latency of a queued request "
                "(seconds)")
            for _, _, t in live:
                lat.observe(now - t)
            telemetry.histogram(
                "dl4j_inference_batch_occupancy",
                "aggregated-batch fill fraction per flush "
                "(requests / batch_limit)",
                buckets=telemetry.RATIO_BUCKETS).observe(
                    len(live) / max(1, self.batch_limit))
        try:
            with telemetry.span("inference.flush", requests=len(live)):
                outs = self.output_batched([x for x, _, _ in live])
        except BaseException as e:           # noqa: BLE001
            for _, f, _ in live:
                f.set_exception(e)
            return
        for (_, f, _), o in zip(live, outs):
            f.set_result(o)

    def shutdown(self):
        """Stop the batching worker (pending requests are flushed).

        After the worker exits, any requests still sitting in the queue
        (possible when the worker died abnormally, or raced its idle
        timeout against a submit) have their futures CANCELLED — no
        caller may block forever on a Future nobody will resolve
        (ADVICE.md round 5)."""
        with self._lock:
            worker, self._worker = self._worker, None
            if worker is None:
                return
            self._shutdown = True
            q = self._requests               # bind THIS queue
            q.put(None)
        worker.join()
        while True:
            try:
                item = q.get_nowait()
            except _queue.Empty:
                break
            if item is not None:
                item[1].cancel()
