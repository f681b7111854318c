"""MultiLayerNetwork: a sequential layer stack compiled to one jitted step.

Reference parity: ``org.deeplearning4j.nn.multilayer.MultiLayerNetwork``
(SURVEY.md D2, call stack section 3.1) — ``init/fit/output/score/evaluate``
with listeners, per-layer updaters, gradient normalization, l1/l2.

TPU-first mapping of the reference's fit() loop (section 3.1):
- fwd/bwd/updater orchestration per minibatch -> ONE ``jax.jit`` function
  (value_and_grad over the whole stack + pure updater transforms), traced
  once per input signature, buffers donated so XLA reuses them
  (donation replaces the reference's workspace machinery D8/J6);
- the flattened param/gradient views -> params stay a pytree; flattening
  exists only as a serialization order (utils.ModelSerializer);
- cuDNN helper dispatch -> nothing: layers lower to XLA ops directly.
"""
from __future__ import annotations

import logging
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common import layerprof
from deeplearning4j_tpu.common.dtypes import to_jnp_dtype
from deeplearning4j_tpu.nn.conf.constraints import apply_constraints
from deeplearning4j_tpu.nn.conf.builders import (BackpropType,
                                                 MultiLayerConfiguration)
from deeplearning4j_tpu.nn.conf.layers import BaseOutputLayer
from deeplearning4j_tpu.nn.ladder import TrainingLadder
from deeplearning4j_tpu.ops import kernel_select
from deeplearning4j_tpu.optimize.listeners import TrainingListener

log = logging.getLogger("deeplearning4j_tpu")


def _as_jnp(x, dtype=None):
    from deeplearning4j_tpu.ndarray.ndarray import INDArray
    if isinstance(x, INDArray):
        x = x.data
    arr = jnp.asarray(x)
    if dtype is not None and jnp.issubdtype(arr.dtype, jnp.floating):
        arr = arr.astype(dtype)
    return arr


class MultiLayerNetwork(TrainingLadder):
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.params: dict = {}
        self.states: dict = {}
        self.updater_states: dict = {}
        self.listeners: List[TrainingListener] = []
        self.iteration_count = 0
        self.epoch_count = 0
        self.last_batch_size = 0
        self._score = float("nan")
        self._rng = jax.random.PRNGKey(conf.seed)
        self._initialized = False
        self._dtype = to_jnp_dtype(conf.dtype)
        self._retrace_guard = None
        self._init_ladder()

    # ------------------------------------------------------------------
    def init(self) -> "MultiLayerNetwork":
        if self._initialized:
            return self
        conf = self.conf
        conf.resolve_shapes()
        key = jax.random.PRNGKey(conf.seed)
        cur = conf.input_type
        for i, layer in enumerate(conf.layers):
            if i in conf.input_preprocessors and cur is not None:
                cur = conf.input_preprocessors[i].get_output_type(cur)
            key, sub = jax.random.split(key)
            self.params[f"layer_{i}"] = layer.init_params(
                sub, cur, self._dtype) if layer.has_params() else {}
            self.states[f"layer_{i}"] = layer.init_state(
                cur, self._dtype) if layer.has_state() else {}
            if cur is not None:
                cur = layer.get_output_type(cur)
        for i, layer in enumerate(conf.layers):
            up = layer.updater or conf.updater
            self.updater_states[f"layer_{i}"] = up.init_state(
                self.params[f"layer_{i}"])
        self._initialized = True
        return self

    # ------------------------------------------------------------------
    def set_listeners(self, *listeners: TrainingListener):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners: TrainingListener):
        self.listeners.extend(listeners)
        return self

    # ------------------------------------------------------------------
    @property
    def output_layer_conf(self) -> BaseOutputLayer:
        last = self.conf.layers[-1]
        if not isinstance(last, BaseOutputLayer):
            raise ValueError("last layer is not an output layer")
        return last

    def n_layers(self) -> int:
        return len(self.conf.layers)

    # ------------------------------------------------------------------
    def _forward(self, params, states, x, *, training: bool, rng,
                 stop_at: Optional[int] = None, want_logits: bool,
                 mask=None, start_at: int = 0):
        """Walk the stack. ``mask`` is the per-timestep features mask,
        passed to layers that accept one (recurrent/pooling).
        ``start_at``/``stop_at`` bound the walk to ``[start_at,
        stop_at)`` — the pipeline-stage slice (parallel/pipeline.py);
        ``x`` is then the incoming stage activation, and per-layer RNG
        stays folded on the ABSOLUTE layer index so a sliced walk
        reproduces the whole-stack random stream.
        Returns (out, new_states)."""
        conf = self.conf
        if conf.compute_dtype:
            # mixed precision: compute in (usually) bfloat16, master
            # params stay float32; the cast transposes to a cast-back,
            # so gradients/updates remain float32 (SURVEY.md section 7
            # "bfloat16 on the MXU" design stance). States (BN running
            # stats) are NOT cast: their (1-decay)*delta updates would
            # round to zero at bf16 ulp — normalization statistics stay
            # f32, the standard mixed-precision rule.
            from deeplearning4j_tpu.common.dtypes import cast_floats
            cd = conf.compute_dtype
            # an FsdpParamView casts per-layer post-gather (gathering
            # the master dtype then casting would defeat nothing, but
            # the view must stay a view to keep gathers just-in-time)
            params = (params.cast(cd) if hasattr(params, "cast")
                      else cast_floats(params, cd))
            x = cast_floats(x, cd)
        new_states = {}
        h = x
        n = len(conf.layers)

        def run_layer(i, h, lrng):
            # layer-attribution scope (common.layerprof): every op this
            # layer traces — forward AND its autodiff transpose —
            # carries dl4j.layer_<i> in compiled-HLO metadata; both the
            # remat-segmented and the plain walk funnel through here
            with layerprof.scope(f"layer_{i}"):
                return _run_layer(i, h, lrng)

        def _run_layer(i, h, lrng):
            layer = conf.layers[i]
            if i in conf.input_preprocessors:
                h = conf.input_preprocessors[i].pre_process(h)
            lp = params.get(f"layer_{i}", {})
            ls = states.get(f"layer_{i}", {})
            if training and layer.weight_noise is not None and \
                    lrng is not None and lp:
                # reference: conf.weightnoise — params perturbed per
                # forward pass; gradients flow to the clean params
                lrng, wn_rng = jax.random.split(lrng)
                lp = layer.weight_noise.apply(lp, wn_rng)
            kw = {}
            if mask is not None and layer.accepts_mask():
                kw["mask"] = mask
            is_last = i == n - 1
            if is_last and want_logits and isinstance(layer,
                                                      BaseOutputLayer) \
                    and layer.wants_logits():
                h, ns = layer.forward_logits(lp, h, training=training,
                                             rng=lrng, state=ls or None)
            else:
                h, ns = layer.forward(lp, h, training=training, rng=lrng,
                                      state=ls or None, **kw)
            return h, ns if ns is not None else {}

        if training and stop_at is None and start_at == 0 and \
                conf.remat_segments > 1 and n > 1:
            # sqrt(N) checkpointing: only segment-boundary activations
            # are stored for backward; interiors are recomputed.
            # Per-layer RNG is fold_in(rng, layer index) — the SAME
            # derivation as the plain path below, so toggling
            # remat_segments does not change the dropout/weight-noise
            # stream (it used to: pre-split here vs sequential split
            # there)
            from deeplearning4j_tpu.common.remat import segment_plan
            keys = ([jax.random.fold_in(rng, j) for j in range(n)]
                    if rng is not None else [None] * n)

            def make_seg(lo, hi):
                def seg_fn(h, seg_keys):
                    ns = {}
                    for j in range(lo, hi):
                        h, s = run_layer(j, h, seg_keys[j - lo])
                        ns[f"layer_{j}"] = s
                    return h, ns
                return seg_fn

            for lo, hi, wrap in segment_plan(n, conf.remat_segments):
                seg_fn = make_seg(lo, hi)
                if wrap:
                    seg_fn = jax.checkpoint(seg_fn)
                h, ns = seg_fn(h, list(keys[lo:hi]))
                new_states.update(ns)
        else:
            for i in range(start_at, n):
                if stop_at is not None and i >= stop_at:
                    break
                # fold_in(rng, layer index), matching the segmented
                # path: the random stream is a function of the layer,
                # not of how the walk is segmented
                lrng = (jax.random.fold_in(rng, i)
                        if rng is not None else None)
                h, ns = run_layer(i, h, lrng)
                new_states[f"layer_{i}"] = ns
        if conf.compute_dtype:
            from deeplearning4j_tpu.common.dtypes import cast_floats
            h = cast_floats(h, self._dtype)          # f32 loss/output
            new_states = cast_floats(new_states, self._dtype)
        return h, new_states

    def _recurrent_keys(self):
        return [f"layer_{i}" for i, l in enumerate(self.conf.layers)
                if l.is_recurrent()]

    def _with_zero_rnn_states(self, states, batch: int):
        """states for a fresh sequence: persistent (BN) entries kept,
        recurrent entries zeroed for this batch size."""
        out = dict(states)
        for i, layer in enumerate(self.conf.layers):
            if layer.is_recurrent():
                out[f"layer_{i}"] = layer.zero_state(batch, self._dtype)
        return out

    def _strip_rnn_states(self, states):
        out = dict(states)
        for k in self._recurrent_keys():
            out[k] = {}
        return out

    def _regularization(self, params):
        """Score-side l1/l2 (reference: applied to weights, not biases)."""
        reg = 0.0
        for i, layer in enumerate(self.conf.layers):
            if getattr(layer, "is_frozen", lambda: False)():
                # regularizing frozen weights would un-freeze them:
                # the l1/l2 gradient bypasses forward's stop_gradient
                continue
            l1 = layer.l1 or 0.0
            l2 = layer.l2 or 0.0
            if l1 == 0.0 and l2 == 0.0:
                continue
            for name, p in params.get(f"layer_{i}", {}).items():
                if name not in ("W",):   # weights only, like the reference
                    continue
                if l1:
                    reg = reg + l1 * jnp.sum(jnp.abs(p))
                if l2:
                    reg = reg + 0.5 * l2 * jnp.sum(p * p)
        return reg

    # ------------------------------------------------------------------
    def _build_train_step(self):
        conf = self.conf
        out_layer = self.output_layer_conf
        want_logits = out_layer.wants_logits()
        layers = {f"layer_{i}": layer
                  for i, layer in enumerate(conf.layers)}
        view = self._param_view(layers)

        def loss_fn(params, states, x, y, fmask, lmask, rng):
            # fmask: per-timestep features mask (recurrent/pooling hold);
            # lmask: labels mask (loss exclusion) — distinct, as in the
            # reference (featuresMaskArray vs labelsMaskArray)
            params = view(params)
            out, new_states = self._forward(params, states, x,
                                            training=True, rng=rng,
                                            want_logits=True, mask=fmask)
            # attribution scope: loss + regularization are real step
            # work but belong to no layer — name them instead of
            # letting them fall into the _unattributed bucket
            with layerprof.scope("loss"):
                data_loss = out_layer.compute_loss(
                    y, out, from_logits=want_logits, mask=lmask)
                return (data_loss + self._regularization(params),
                        new_states)

        self._build_steps(loss_fn, layers)

    # ------------------------------------------------------------------
    @kernel_select.marks_partitions
    def fit(self, data, labels=None, *, n_epochs: int = 1):
        """fit(x, y) | fit(DataSet) | fit(iterator[, n_epochs])."""
        if not self._initialized:
            self.init()
        self._sync_updater_layout()
        self._sync_param_layout()
        if self._train_step is None:
            self._build_train_step()
        if labels is not None:
            for _ in range(n_epochs):
                self._fit_batch(data, labels, None, None)
            return self
        if hasattr(data, "features") and hasattr(data, "labels"):
            for _ in range(n_epochs):
                self._fit_batch(data.features, data.labels,
                                getattr(data, "features_mask", None),
                                getattr(data, "labels_mask", None))
            return self
        # iterator protocol: stage batches device-side ahead of the
        # step loop (no-op when DL4J_TPU_DEVICE_PREFETCH=0 or the
        # stream is not a resettable iterator)
        from deeplearning4j_tpu.datasets.prefetch import \
            maybe_device_prefetch
        data = maybe_device_prefetch(data, dtype=self._dtype)
        for _ in range(n_epochs):
            for lis in self.listeners:
                lis.on_epoch_start(self)
            if hasattr(data, "reset"):
                data.reset()
            for ds in data:
                self._fit_batch(ds.features, ds.labels,
                                getattr(ds, "features_mask", None),
                                getattr(ds, "labels_mask", None))
            # a partial accumulation window does not leak across epochs
            self.flush_accumulated()
            # epochs-completed count advances BEFORE listeners fire:
            # an epoch-end checkpoint then serializes the true count
            # (a resumed job must not retrain a finished epoch)
            self.epoch_count += 1
            for lis in self.listeners:
                lis.on_epoch_end(self)
        return self

    # ------------------------------------------------------------------
    @kernel_select.marks_partitions
    def fit_steps(self, ds, steps: int):
        """Run ``steps`` train iterations on one device-resident batch
        in ONE jit dispatch (lax.fori_loop over the compiled step; the
        Keras steps_per_execution idea — see ComputationGraph.fit_steps).
        Masks unsupported on this fast path; listeners fire once per
        group with the final loss."""
        if not self._initialized:
            self.init()
        self._sync_updater_layout()
        self._sync_param_layout()
        if self._train_step is None:
            self._build_train_step()
        if getattr(ds, "features_mask", None) is not None or \
                getattr(ds, "labels_mask", None) is not None:
            raise ValueError(
                "fit_steps does not support masked DataSets — padded "
                "timesteps would train as real data; use fit()")
        x = _as_jnp(ds.features, self._dtype)
        y = _as_jnp(ds.labels, self._dtype)

        if not hasattr(self, "_multi_steps"):
            self._multi_steps = {}
        if steps not in self._multi_steps:
            step_fn = self._step_fn

            def multi(params, states, upd, x, y, it0, rng):
                def body(i, carry):
                    p, s, u, _, _ = carry
                    r = jax.random.fold_in(rng, i)
                    return step_fn(p, s, u, x, y, None, None, it0 + i, r)

                # loss carry must match step_fn's loss dtype (bf16 nets
                # produce a bf16 loss); grad-norm carry is f32
                zero = jnp.zeros((), self._dtype)
                gz = jnp.zeros((), jnp.float32)
                return jax.lax.fori_loop(0, steps, body,
                                         (params, states, upd, zero, gz))

            self._multi_steps[steps] = jax.jit(multi,
                                               donate_argnums=(0, 1, 2))

        states_in = self._with_zero_rnn_states(self.states,
                                               int(x.shape[0]))
        self._rng, rng = jax.random.split(self._rng)
        from deeplearning4j_tpu.common import diagnostics, telemetry
        with telemetry.step_span("MultiLayerNetwork", steps=steps) as sp:
            self.params, new_states, self.updater_states, loss, gnorm = \
                self._multi_steps[steps](self.params, states_in,
                                         self.updater_states, x, y,
                                         jnp.asarray(
                                             self.iteration_count),
                                         rng)
        self.states = self._strip_rnn_states(new_states)
        self._score = loss
        self.last_batch_size = int(x.shape[0])
        self.iteration_count += steps
        # one record per group: the final step's loss/grad norm stand
        # in for the window (the fori_loop body is opaque to the host)
        diagnostics.after_step(
            self, "MultiLayerNetwork", self.iteration_count - 1, loss,
            sp, grad_norm=gnorm if self._step_gnorm else None,
            params=self.params, steps=steps)
        for lis in self.listeners:
            lis.iteration_done(self, self.iteration_count - 1,
                               self.epoch_count)
        return self

    # ------------------------------------------------------------------
    def pretrain(self, data, *, n_epochs: int = 1):
        """Layerwise unsupervised pretraining (reference:
        MultiLayerNetwork.pretrain(DataSetIterator) — fits every
        pretrainable layer (AutoEncoder/VAE) in stack order on the
        activations of the layers below it)."""
        from deeplearning4j_tpu.nn.pretrain_util import materialize_once
        data = materialize_once(data)
        for i, layer in enumerate(self.conf.layers):
            if getattr(layer, "is_pretrainable", lambda: False)():
                self.pretrain_layer(i, data, n_epochs=n_epochs)
        return self

    def pretrain_layer(self, idx: int, data, *, n_epochs: int = 1):
        """Fit one pretrainable layer (reference: pretrainLayer(int,
        iter)). The layer's ``pretrain_loss`` + its updater compile into
        one jitted step; layers below run in inference mode."""
        if not self._initialized:
            self.init()
        self._sync_updater_layout()
        # pretrain reads/writes per-layer dense params directly; leave
        # the flat layout (a later fit() re-enters it)
        self._densify_params_inplace()
        layer = self.conf.layers[idx]
        if not getattr(layer, "is_pretrainable", lambda: False)():
            raise ValueError(f"layer {idx} is not pretrainable")
        up = layer.updater or self.conf.updater
        key = f"layer_{idx}"
        upd_state = self.updater_states[key]

        if not hasattr(self, "_pretrain_steps"):
            self._pretrain_steps = {}
        if idx not in self._pretrain_steps:
            def step(lp, below_params, states, us, x, iteration, rng):
                r_in, r_loss = jax.random.split(rng)
                h = x
                if idx > 0:
                    h, _ = self._forward(below_params, states, x,
                                         training=False, rng=r_in,
                                         stop_at=idx, want_logits=False)
                # _forward(stop_at=idx) stops before layer idx's own
                # preprocessor; apply it (auto-inserted CnnToFeedForward
                # etc.) so pretrain sees the same input as supervised fit
                if idx in self.conf.input_preprocessors:
                    h = self.conf.input_preprocessors[idx].pre_process(h)
                loss, g = jax.value_and_grad(layer.pretrain_loss)(
                    lp, h, r_loss)
                updates, new_us = up.apply(g, us, iteration)
                new_lp = jax.tree_util.tree_map(lambda p, u: p - u, lp,
                                                updates)
                new_lp = apply_constraints(layer, new_lp)
                return new_lp, new_us, loss

            self._pretrain_steps[idx] = jax.jit(step,
                                                donate_argnums=(0, 3))
        jit_step = self._pretrain_steps[idx]
        below = {f"layer_{j}": self.params[f"layer_{j}"]
                 for j in range(idx)}

        from deeplearning4j_tpu.nn.pretrain_util import (
            feature_batches, materialize_once)
        data = materialize_once(data)

        for _ in range(n_epochs):
            for x in feature_batches(data):
                x = _as_jnp(x, self._dtype)
                self._rng, rng = jax.random.split(self._rng)
                states_in = self._with_zero_rnn_states(self.states,
                                                       int(x.shape[0]))
                self.params[key], upd_state, loss = jit_step(
                    self.params[key], below, states_in, upd_state,
                    x, jnp.asarray(self.iteration_count), rng)
                self._score = loss
                self.iteration_count += 1
        self.updater_states[key] = upd_state
        return self

    def _fit_batch(self, x, y, fmask, lmask):
        x = _as_jnp(x, self._dtype)
        y = _as_jnp(y, self._dtype)
        fmask = _as_jnp(fmask) if fmask is not None else None
        lmask = _as_jnp(lmask) if lmask is not None else None
        if self._retrace_guard is None:
            from deeplearning4j_tpu.common.compilecache import RetraceGuard
            self._retrace_guard = RetraceGuard(
                f"{type(self).__name__} train step")
        self._retrace_guard.record(x, y, fmask, lmask)
        # layer_report() with no batch re-lowers at the last fit shape
        self._layerprof_shapes = ((x.shape, x.dtype), (y.shape, y.dtype))
        if self.conf.backprop_type is BackpropType.TRUNCATED_BPTT and \
                x.ndim == 3:
            return self._fit_tbptt(x, y, fmask, lmask)
        if self._accum_steps > 1:
            return self._fit_batch_accum(x, y, fmask, lmask)
        self._rng, rng = jax.random.split(self._rng)
        states_in = self._with_zero_rnn_states(self.states,
                                               int(x.shape[0]))
        from deeplearning4j_tpu.common import diagnostics, telemetry
        with telemetry.step_span("MultiLayerNetwork") as sp:
            self.params, new_states, self.updater_states, loss, gnorm = \
                self._train_step(self.params, states_in,
                                 self.updater_states, x, y, fmask, lmask,
                                 jnp.asarray(self.iteration_count), rng)
        # standard BPTT: recurrent state resets every minibatch
        # (reference: fit() clears rnn state); BN stats persist
        self.states = self._strip_rnn_states(new_states)
        self._score = loss          # device scalar; float() on read
        self.last_batch_size = int(x.shape[0])
        # grads never leave the fused step, so a trip attributes the
        # first bad leaf in the (poisoned) post-update params
        diagnostics.after_step(
            self, "MultiLayerNetwork", self.iteration_count, loss, sp,
            grad_norm=gnorm if self._step_gnorm else None,
            params=self.params)
        self.iteration_count += 1
        for lis in self.listeners:
            lis.iteration_done(self, self.iteration_count - 1,
                               self.epoch_count)

    def _fit_batch_accum(self, x, y, fmask, lmask):
        """Accumulation micro-step: backward + gradient add only; the
        updater fires once per ``_accum_steps`` window on the mean
        gradient, with the updater iteration = number of updates
        APPLIED (so Adam bias correction sees update indices, not
        micro-batch indices)."""
        self._rng, rng = jax.random.split(self._rng)
        states_in = self._with_zero_rnn_states(self.states,
                                               int(x.shape[0]))
        from deeplearning4j_tpu.common import diagnostics, telemetry
        with telemetry.step_span("MultiLayerNetwork",
                                 accumulating=self._accum_steps) as sp:
            grads, new_states, loss, gnorm = self._grad_step(
                self.params, states_in, x, y, fmask, lmask, rng)
            # watchdog check BEFORE accumulate/apply: the first
            # micro-batch's grads become _accum_grads, whose buffers
            # the apply step donates — after that the scan target is
            # gone
            diagnostics.check_numerics(
                self, "MultiLayerNetwork", self.iteration_count, loss,
                grad_norm=gnorm if self._step_gnorm else None,
                grads=grads)
            self._accum_grads = (grads if self._accum_grads is None
                                 else self._accum_add(self._accum_grads,
                                                      grads))
            self._accum_count += 1
            if self._accum_count >= self._accum_steps:
                self._apply_accumulated()
        self.states = self._strip_rnn_states(new_states)
        self._score = loss          # device scalar; float() on read
        self.last_batch_size = int(x.shape[0])
        diagnostics.record_step(
            self, "MultiLayerNetwork", self.iteration_count, loss, sp,
            grad_norm=gnorm if self._step_gnorm else None)
        self.iteration_count += 1
        for lis in self.listeners:
            lis.iteration_done(self, self.iteration_count - 1,
                               self.epoch_count)

    def _fit_tbptt(self, x, y, fmask, lmask):
        """Truncated BPTT (SURVEY.md section 5.7): the time axis splits
        into tbptt_fwd_length segments; recurrent state carries across
        segments (no gradient flow between step calls = truncation), and
        resets at the batch boundary — reference tBPTT semantics."""
        L = self.conf.tbptt_fwd_length
        T = x.shape[1]

        def seg(m, t0):
            return m[:, t0:t0 + L] if m is not None and m.ndim >= 2 else m

        from deeplearning4j_tpu.common import diagnostics
        states = self._with_zero_rnn_states(self.states, int(x.shape[0]))
        for t0 in range(0, T, L):
            seg_x = x[:, t0:t0 + L]
            seg_y = y[:, t0:t0 + L] if y.ndim >= 3 else y
            self._rng, rng = jax.random.split(self._rng)
            self.params, states, self.updater_states, loss, gnorm = \
                self._train_step(self.params, states,
                                 self.updater_states, seg_x, seg_y,
                                 seg(fmask, t0), seg(lmask, t0),
                                 jnp.asarray(self.iteration_count), rng)
            self._score = loss          # device scalar; float() on read
            diagnostics.after_step(
                self, "MultiLayerNetwork", self.iteration_count, loss,
                None, grad_norm=gnorm if self._step_gnorm else None,
                params=self.params, tbptt_segment=t0 // L)
            self.iteration_count += 1
        self.states = self._strip_rnn_states(states)
        self.last_batch_size = int(x.shape[0])
        for lis in self.listeners:
            lis.iteration_done(self, self.iteration_count - 1,
                               self.epoch_count)

    # -- stateful streaming inference (SURVEY.md section 5.7) -----------
    def rnn_time_step(self, x):
        """Feed one step (or a chunk) of a sequence, carrying hidden
        state across calls (reference: rnnTimeStep)."""
        from deeplearning4j_tpu.nn.conf.layers_recurrent import Bidirectional
        if any(isinstance(l, Bidirectional) for l in self.conf.layers):
            # reference throws too: the backward direction needs future
            # timesteps, which streaming cannot provide
            raise ValueError(
                "rnnTimeStep is not supported on networks with "
                "Bidirectional layers")
        if not self._initialized:
            self.init()
        x = _as_jnp(x, self._dtype)
        single_step = x.ndim == 2
        if single_step:
            x = x[:, None, :]
        if getattr(self, "_rnn_stream_states", None) is None:
            self._rnn_stream_states = self._with_zero_rnn_states(
                self.states, int(x.shape[0]))
            self._rnn_stream_batch = int(x.shape[0])
        elif int(x.shape[0]) != self._rnn_stream_batch:
            raise ValueError(
                f"rnnTimeStep batch size {int(x.shape[0])} != stored "
                f"state batch size {self._rnn_stream_batch}; call "
                f"rnn_clear_previous_state() first")
        out, new_states = self._forward(
            self.dense_params(), self._rnn_stream_states, x,
            training=False, rng=None, want_logits=False)
        # keep persistent (BN) states as-is; update only the rnn carries
        merged = dict(self._rnn_stream_states)
        for k in self._recurrent_keys():
            merged[k] = new_states[k]
        self._rnn_stream_states = merged
        if single_step and out.ndim == 3:
            out = out[:, -1]
        return out

    def rnn_clear_previous_state(self):
        self._rnn_stream_states = None

    def rnn_get_previous_state(self, layer_idx: int):
        if getattr(self, "_rnn_stream_states", None) is None:
            return None
        return self._rnn_stream_states.get(f"layer_{layer_idx}")

    # ------------------------------------------------------------------
    @kernel_select.marks_partitions
    def output(self, x, train: bool = False, mask=None):
        """Inference forward pass (reference: ``output(INDArray)``)."""
        if not self._initialized:
            self.init()
        x = _as_jnp(x, self._dtype)
        mask = _as_jnp(mask) if mask is not None else None
        out, _ = self._forward(self.dense_params(), self.states, x,
                               training=train, rng=None,
                               want_logits=False, mask=mask)
        return out

    @kernel_select.marks_partitions
    def feed_forward(self, x, train: bool = False) -> list:
        """All layer activations (reference: feedForward)."""
        if not self._initialized:
            self.init()
        x = _as_jnp(x, self._dtype)
        params = self.dense_params()
        if self.conf.compute_dtype:
            # same dtype path as fit()/output() — per-layer activations
            # must match what the trained/predicted path computes
            from deeplearning4j_tpu.common.dtypes import cast_floats
            cd = self.conf.compute_dtype
            params = cast_floats(params, cd)
            x = cast_floats(x, cd)
        acts = [x]
        h = x
        rng = None
        for i, layer in enumerate(self.conf.layers):
            if i in self.conf.input_preprocessors:
                h = self.conf.input_preprocessors[i].pre_process(h)
            h, _ = layer.forward(params.get(f"layer_{i}", {}), h,
                                 training=train, rng=rng,
                                 state=self.states.get(f"layer_{i}") or
                                 None)
            acts.append(h)
        return acts

    def predict(self, x) -> np.ndarray:
        """Argmax class predictions (reference: predict)."""
        return np.asarray(jnp.argmax(self.output(x), axis=-1))

    @kernel_select.marks_partitions
    def score(self, dataset=None) -> float:
        """Latest minibatch score, or score of a given DataSet."""
        if dataset is None:
            return float(self._score)
        x = _as_jnp(dataset.features, self._dtype)
        y = _as_jnp(dataset.labels, self._dtype)
        mask = getattr(dataset, "labels_mask", None)
        mask = _as_jnp(mask) if mask is not None else None
        out_layer = self.output_layer_conf
        want_logits = out_layer.wants_logits()
        params = self.dense_params()
        out, _ = self._forward(params, self.states, x, training=False,
                               rng=None, want_logits=True)
        loss = out_layer.compute_loss(y, out, from_logits=want_logits,
                                      mask=mask)
        return float(loss + self._regularization(params))

    # ------------------------------------------------------------------
    def evaluate(self, iterator):
        """Classification evaluation (reference: evaluate(DataSetIterator))."""
        from deeplearning4j_tpu.evaluation import Evaluation
        ev = Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(ds.features,
                              mask=getattr(ds, "features_mask", None))
            ev.eval(ds.labels, out,
                    mask=getattr(ds, "labels_mask", None))
        return ev

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.evaluation import RegressionEvaluation
        ev = RegressionEvaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(ds.features)
            ev.eval(ds.labels, out,
                    mask=getattr(ds, "labels_mask", None))
        return ev

    # ------------------------------------------------------------------
    def num_params(self) -> int:
        return int(sum(np.prod(p.shape) for p in
                       jax.tree_util.tree_leaves(self.dense_params())))

    def param_table(self) -> dict:
        """{"0_W": array, ...} — reference paramTable naming."""
        out = {}
        params = self.dense_params()
        for i in range(self.n_layers()):
            for name, p in params.get(f"layer_{i}", {}).items():
                out[f"{i}_{name}"] = p
            for name, s in (self.states.get(f"layer_{i}") or {}).items():
                out[f"{i}_{name}"] = s
        return out

    def get_param(self, key: str):
        i, name = key.split("_", 1)
        return self.dense_params()[f"layer_{i}"][name]

    def set_params_from_table(self, table: dict):
        self._densify_params_inplace()
        for k, v in table.items():
            i, name = k.split("_", 1)
            lk = f"layer_{i}"
            if name in self.params.get(lk, {}):
                if isinstance(v, dict):   # wrapper sub-trees (fwd/bwd)
                    for sub, a in v.items():
                        self.params[lk][name][sub] = jnp.asarray(a)
                else:
                    self.params[lk][name] = jnp.asarray(v)
            elif name in (self.states.get(lk) or {}):
                self.states[lk][name] = jnp.asarray(v)

    def clone(self) -> "MultiLayerNetwork":
        import copy
        net = MultiLayerNetwork(copy.deepcopy(self.conf))
        if self._initialized:
            net.init()
            net.params = jax.tree_util.tree_map(lambda a: a,
                                                self.dense_params())
            net.states = jax.tree_util.tree_map(lambda a: a, self.states)
            net.updater_states = jax.tree_util.tree_map(
                lambda a: a, self.updater_states)
        return net

    @kernel_select.marks_partitions
    def layer_report(self, data=None, labels=None, **roofline_kw):
        """Per-layer flops/bytes/roofline attribution of the compiled
        train step (common.layerprof): lowers the jitted step at the
        given batch (or the last fitted batch's shapes), partitions
        ``cost_analysis()`` by the ``dl4j.layer_<i>`` scopes, and joins
        the kernel-select decisions recorded at trace time.  Also
        published to ``GET /api/layers`` and the ``dl4j_layer_*``
        metrics.  Lowering only — nothing executes, buffers are not
        donated."""
        if not self._initialized:
            self.init()
        self._sync_updater_layout()
        self._sync_param_layout()
        if self._train_step is None:
            self._build_train_step()
        if data is not None and hasattr(data, "features"):
            labels = data.labels
            data = data.features
        if data is None:
            shapes = getattr(self, "_layerprof_shapes", None)
            if shapes is None:
                raise ValueError(
                    "layer_report needs a batch: pass (data, labels) "
                    "or fit at least one batch first")
            (xs, xd), (ys, yd) = shapes
            data = np.zeros(xs, dtype=xd)
            labels = np.zeros(ys, dtype=yd)
        x = _as_jnp(data, self._dtype)
        y = _as_jnp(labels, self._dtype)
        states_in = self._with_zero_rnn_states(self.states,
                                               int(x.shape[0]))
        lowered = self._train_step.lower(
            self.params, states_in, self.updater_states, x, y, None,
            None, jnp.asarray(0), jax.random.PRNGKey(0))
        types = {f"layer_{i}": type(l).__name__
                 for i, l in enumerate(self.conf.layers)}
        return layerprof.attribute_compiled(
            lowered.compile(), model_name=type(self).__name__,
            layer_types=types, **roofline_kw)

    def summary(self) -> str:
        lines = [f"{'idx':<4} {'type':<24} {'nIn->nOut':<14} {'params':<10}"]
        total = 0
        params = self.dense_params()
        for i, layer in enumerate(self.conf.layers):
            n = int(sum(np.prod(p.shape) for p in
                        params.get(f"layer_{i}", {}).values()))
            total += n
            lines.append(f"{i:<4} {type(layer).__name__:<24} "
                         f"{layer.n_in}->{layer.n_out:<10} {n:<10}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)
