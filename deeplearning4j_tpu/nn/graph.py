"""ComputationGraph: DAG model compiled to one jitted step.

Reference parity: ``org.deeplearning4j.nn.graph.ComputationGraph``
(SURVEY.md D3, call stack 3.2): topo-ordered vertex execution,
multi-input/multi-output, same fit/output/score/evaluate surface as
MultiLayerNetwork. The reference's reverse-topo epsilon accumulation
(fan-out vertices sum incoming gradients) is what reverse-mode autodiff
does by construction — ``jax.value_and_grad`` over the whole DAG replaces
the hand-written backprop orchestration.
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common import layerprof
from deeplearning4j_tpu.common.dtypes import to_jnp_dtype
from deeplearning4j_tpu.nn.conf.graph_conf import \
    ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf.constraints import apply_constraints
from deeplearning4j_tpu.nn.conf.layers import BaseOutputLayer
from deeplearning4j_tpu.nn.ladder import TrainingLadder
from deeplearning4j_tpu.nn.multilayer import _as_jnp
from deeplearning4j_tpu.ops import kernel_select
from deeplearning4j_tpu.optimize.listeners import TrainingListener

log = logging.getLogger("deeplearning4j_tpu")


class ComputationGraph(TrainingLadder):
    def __init__(self, conf: ComputationGraphConfiguration):
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
        self._topo = conf.topo_order()
        self._retrace_guard = None
        self._init_ladder()

    # ------------------------------------------------------------------
    def init(self) -> "ComputationGraph":
        if self._initialized:
            return self
        conf = self.conf
        conf.resolve_shapes()
        types = getattr(conf, "_resolved_types", {})
        key = jax.random.PRNGKey(conf.seed)
        for name in self._topo:
            v = conf.vertices[name]
            if not v.is_layer:
                self.params[name] = {}
                self.states[name] = {}
                continue
            in_type = types.get(v.inputs[0]) if types else None
            if v.preprocessor is not None and in_type is not None:
                in_type = v.preprocessor.get_output_type(in_type)
            key, sub = jax.random.split(key)
            self.params[name] = v.content.init_params(
                sub, in_type, self._dtype) if v.content.has_params() else {}
            self.states[name] = v.content.init_state(
                in_type, self._dtype) if v.content.has_state() else {}
        for name in self._topo:
            v = conf.vertices[name]
            up = (v.content.updater if v.is_layer and v.content.updater
                  else conf.updater)
            self.updater_states[name] = up.init_state(self.params[name])
        self._initialized = True
        return self

    # ------------------------------------------------------------------
    def set_listeners(self, *listeners: TrainingListener):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners: TrainingListener):
        self.listeners.extend(listeners)
        return self

    def output_layer_confs(self) -> Dict[str, BaseOutputLayer]:
        out = {}
        for name in self.conf.network_outputs:
            layer = self.conf.vertices[name].content
            if isinstance(layer, BaseOutputLayer):
                out[name] = layer
        return out

    # ------------------------------------------------------------------
    def _forward(self, params, states, inputs: Sequence, *,
                 training: bool, rng, want_logits: bool, fmask=None,
                 upto: Optional[str] = None, start_acts=None,
                 topo_slice=None):
        """Topo walk. inputs: list matching conf.network_inputs order.
        ``fmask`` is the per-timestep features mask (first input's), passed
        to mask-aware layers — multi-input graphs with per-input masks can
        attach masks via PreprocessorVertex if they diverge.
        ``upto``: walk only the ancestor subgraph of this vertex
        (inclusive) — the pretrain path, where downstream vertices must
        not even be traced (their params are held out of the step).
        ``topo_slice``: ``(lo, hi)`` — walk only ``self._topo[lo:hi]``,
        the pipeline-stage slice (parallel/pipeline.py), with
        ``start_acts`` seeding the activations handed over from earlier
        stages; per-vertex RNG stays folded on the FULL-topo layer
        position, so a sliced walk reproduces the whole-graph stream.
        Returns ({vertex: activation} for outputs, new_states)."""
        conf = self.conf
        if conf.compute_dtype:
            # mixed precision: bfloat16 math, float32 master params —
            # the entry cast's transpose gives float32 gradients.
            # States (BN running stats) stay f32: bf16 ulp would
            # swallow their (1-decay)*delta updates.
            from deeplearning4j_tpu.common.dtypes import cast_floats
            cd = conf.compute_dtype
            # an FsdpParamView casts per-vertex post-gather, keeping
            # the just-in-time gather schedule
            params = (params.cast(cd) if hasattr(params, "cast")
                      else cast_floats(params, cd))
            inputs = [cast_floats(x, cd) for x in inputs]
            if start_acts is not None:
                start_acts = cast_floats(start_acts, cd)
        def run_vertex(name, acts, lrng):
            """Execute one vertex against the live activation dict;
            returns (activation, layer_state).  The layer-attribution
            scope (common.layerprof) tags every op the vertex traces —
            forward AND its autodiff transpose — with
            ``dl4j.<vertex name>``; both the remat-segmented and the
            plain walk funnel through here."""
            with layerprof.scope(name):
                return _run_vertex(name, acts, lrng)

        def _run_vertex(name, acts, lrng):
            v = conf.vertices[name]
            xs = [acts[i] for i in v.inputs]
            if not v.is_layer:
                return v.content.forward(xs, training=training), {}
            h = xs[0]
            if v.preprocessor is not None:
                h = v.preprocessor.pre_process(h)
            layer = v.content
            lp = params.get(name, {})
            if training and layer.weight_noise is not None and \
                    lrng is not None and lp:
                # reference: conf.weightnoise — params perturbed
                # per forward; gradients flow to the clean params
                lrng, wn_rng = jax.random.split(lrng)
                lp = layer.weight_noise.apply(lp, wn_rng)
            ls = states.get(name, {})
            kw = {}
            if fmask is not None and layer.accepts_mask():
                kw["mask"] = fmask
            if want_logits and name in conf.network_outputs and \
                    isinstance(layer, BaseOutputLayer) and \
                    layer.wants_logits():
                h, ns = layer.forward_logits(
                    lp, h, training=training,
                    rng=lrng, state=ls or None)
            else:
                h, ns = layer.forward(
                    lp, h, training=training,
                    rng=lrng, state=ls or None, **kw)
            return h, ns if ns is not None else {}

        if training and conf.remat_segments > 1 and \
                len(self._topo) > 1 and \
                start_acts is None and topo_slice is None:
            acts, new_states = self._forward_segmented(run_vertex, rng,
                                                       inputs)
        else:
            topo = self._topo
            if topo_slice is not None:
                topo = topo[topo_slice[0]:topo_slice[1]]
            if upto is not None:
                need = {upto}
                for n in reversed(self._topo):
                    if n in need:
                        need.update(conf.vertices[n].inputs)
                topo = [n for n in topo if n in need]
            acts = dict(zip(conf.network_inputs, inputs))
            if start_acts is not None:
                acts.update(start_acts)
            new_states = {}
            # fold_in by layer position IN THE FULL TOPO — same
            # derivation as _forward_segmented, so neither toggling
            # remat_segments nor an upto-restricted walk changes the
            # dropout/weight-noise stream
            layer_pos = {n: i for i, n in enumerate(
                n for n in self._topo if conf.vertices[n].is_layer)}
            for name in topo:
                lrng = None
                if rng is not None and conf.vertices[name].is_layer:
                    lrng = jax.random.fold_in(rng, layer_pos[name])
                h, ns = run_vertex(name, acts, lrng)
                acts[name] = h
                new_states[name] = ns
        if self.conf.compute_dtype:
            from deeplearning4j_tpu.common.dtypes import cast_floats
            for out in self.conf.network_outputs:
                if out in acts:          # absent under a partial walk
                    acts[out] = cast_floats(acts[out], self._dtype)
            new_states = cast_floats(new_states, self._dtype)
        return acts, new_states

    def _forward_segmented(self, run_vertex, rng, inputs):
        """Training forward in ``conf.remat_segments`` contiguous
        ``jax.checkpoint`` segments of the topo walk: only the
        activations LIVE at a segment boundary are stored for the
        backward pass; everything inside a segment is recomputed
        (sqrt(N) checkpointing — trades recompute FLOPs for HBM
        activation traffic, usually a win on bandwidth-bound TPUs).
        Per-vertex RNG is ``fold_in(rng, layer position)`` — the same
        derivation as the plain walk, so the random stream is invariant
        to segmentation (and to remat on/off)."""
        from deeplearning4j_tpu.common.remat import segment_plan
        conf = self.conf
        topo = self._topo
        plan = segment_plan(len(topo), conf.remat_segments)

        layer_names = [n for n in topo if conf.vertices[n].is_layer]
        if rng is not None and layer_names:
            rng_for = {n: jax.random.fold_in(rng, i)
                       for i, n in enumerate(layer_names)}
        else:
            rng_for = {}

        # liveness: an activation must cross a segment boundary iff a
        # later vertex consumes it or it is a network output
        consumers: Dict[str, list] = {}
        for name in topo:
            for src in conf.vertices[name].inputs:
                consumers.setdefault(src, []).append(name)
        pos = {n: i for i, n in enumerate(topo)}

        def needed_after(idx_end):
            keep = set(conf.network_outputs)
            for src, cons in consumers.items():
                if any(pos[c] >= idx_end for c in cons):
                    keep.add(src)
            return keep

        live: Dict[str, jnp.ndarray] = dict(zip(conf.network_inputs,
                                                inputs))
        new_states: dict = {}
        for lo, hi, wrap in plan:
            seg = topo[lo:hi]
            produced = set(seg)
            refs = {src for n in seg
                    for src in conf.vertices[n].inputs}
            seg_in = sorted(refs - produced)
            keep = needed_after(hi)
            seg_out = sorted(produced & keep)
            seg_rngs = {n: rng_for[n] for n in seg if n in rng_for}

            def seg_fn(in_acts, seg_rngs, seg=seg, seg_out=seg_out):
                acts = dict(in_acts)
                ns = {}
                for name in seg:
                    h, s = run_vertex(name, acts,
                                      seg_rngs.get(name))
                    acts[name] = h
                    ns[name] = s
                return {k: acts[k] for k in seg_out}, ns

            if wrap:
                # the LAST segment (wrap=False) holds the loss head;
                # checkpointing it buys nothing
                seg_fn = jax.checkpoint(seg_fn)
            outs, ns = seg_fn({k: live[k] for k in seg_in}, seg_rngs)
            live.update(outs)
            new_states.update(ns)
            # prune dead activations so they do not stay resident
            # (reuses this segment's liveness set from above)
            live = {k: v for k, v in live.items() if k in keep}
        return live, new_states

    # -- recurrent state lifecycle (mirrors MultiLayerNetwork) ----------
    def _recurrent_names(self):
        return [n for n in self._topo
                if self.conf.vertices[n].is_layer and
                self.conf.vertices[n].content.is_recurrent()]

    def _with_zero_rnn_states(self, states, batch: int):
        out = dict(states)
        for n in self._recurrent_names():
            out[n] = self.conf.vertices[n].content.zero_state(
                batch, self._dtype)
        return out

    def _strip_rnn_states(self, states):
        out = dict(states)
        for n in self._recurrent_names():
            out[n] = {}
        return out

    def _regularization(self, params):
        reg = 0.0
        for name in self._topo:
            v = self.conf.vertices[name]
            if not v.is_layer:
                continue
            l1 = v.content.l1 or 0.0
            l2 = v.content.l2 or 0.0
            if l1 == 0.0 and l2 == 0.0:
                continue
            W = params.get(name, {}).get("W")
            if W is None:
                continue
            if l1:
                reg = reg + l1 * jnp.sum(jnp.abs(W))
            if l2:
                reg = reg + 0.5 * l2 * jnp.sum(W * W)
        return reg

    # ------------------------------------------------------------------
    def _build_train_step(self):
        conf = self.conf
        out_confs = self.output_layer_confs()
        layers = {name: (conf.vertices[name].content
                         if conf.vertices[name].is_layer else None)
                  for name in self._topo}
        view = self._param_view(layers)

        def loss_fn(params, states, inputs, labels, fmask, lmasks, rng):
            params = view(params)
            acts, new_states = self._forward(params, states, inputs,
                                             training=True, rng=rng,
                                             want_logits=True,
                                             fmask=fmask)
            # attribution scope: loss + regularization are real step
            # work but belong to no vertex — name them instead of
            # letting them fall into the _unattributed bucket
            with layerprof.scope("loss"):
                loss = self._regularization(params)
                for i, out_name in enumerate(conf.network_outputs):
                    layer = out_confs.get(out_name)
                    if layer is None:
                        continue
                    loss = loss + layer.compute_loss(
                        labels[i], acts[out_name],
                        from_logits=layer.wants_logits(),
                        mask=lmasks[i] if lmasks is not None else None)
                return loss, new_states

        self._build_steps(loss_fn, layers)

    # ------------------------------------------------------------------
    @kernel_select.marks_partitions
    def fit(self, data, labels=None, *, n_epochs: int = 1):
        """fit(x, y) | fit(DataSet/MultiDataSet) | fit(iterator)."""
        if not self._initialized:
            self.init()
        self._sync_updater_layout()
        self._sync_param_layout()
        if self._train_step is None:
            self._build_train_step()
        if labels is not None:
            for _ in range(n_epochs):
                self._fit_batch(
                    [data] if not isinstance(data, (list, tuple))
                    else list(data),
                    [labels] if not isinstance(labels, (list, tuple))
                    else list(labels), None, None)
            return self
        if hasattr(data, "features") and hasattr(data, "labels"):
            for _ in range(n_epochs):
                self._fit_dataset(data)
            return self
        # stage batches device-side ahead of the step loop (no-op when
        # DL4J_TPU_DEVICE_PREFETCH=0 or not a resettable iterator)
        from deeplearning4j_tpu.datasets.prefetch import \
            maybe_device_prefetch
        data = maybe_device_prefetch(data, dtype=self._dtype)
        for _ in range(n_epochs):
            for lis in self.listeners:
                lis.on_epoch_start(self)
            if hasattr(data, "reset"):
                data.reset()
            for ds in data:
                self._fit_dataset(ds)
            # a partial accumulation window does not leak across epochs
            self.flush_accumulated()
            # epochs-completed advances BEFORE listeners (see
            # MultiLayerNetwork.fit: checkpoint-resume correctness)
            self.epoch_count += 1
            for lis in self.listeners:
                lis.on_epoch_end(self)
        return self

    def pretrain(self, data, *, n_epochs: int = 1):
        """Greedy layerwise unsupervised pretraining (reference:
        ComputationGraph.pretrain(DataSetIterator) — SURVEY.md D3):
        every pretrainable vertex (AutoEncoder/VAE) is fit in topo
        order on the activations of the subgraph feeding it, with the
        rest of the graph held fixed."""
        from deeplearning4j_tpu.nn.pretrain_util import materialize_once
        data = materialize_once(data)
        for name in self._topo:
            v = self.conf.vertices[name]
            if v.is_layer and getattr(v.content, "is_pretrainable",
                                      lambda: False)():
                self.pretrain_vertex(name, data, n_epochs=n_epochs)
        return self

    def pretrain_vertex(self, name: str, data, *, n_epochs: int = 1):
        """Fit one pretrainable vertex (reference:
        ComputationGraph.pretrainLayer(String, iter)). The vertex's
        ``pretrain_loss`` + its updater compile into ONE jitted step;
        upstream vertices run in inference mode, and XLA dead-code
        eliminates everything downstream of the vertex's input (the
        walk is traced whole, only ``acts[src]`` is consumed)."""
        if not self._initialized:
            self.init()
        self._sync_updater_layout()
        # pretrain reads/writes per-vertex dense params directly; leave
        # the flat layout (a later fit() re-enters it)
        self._densify_params_inplace()
        v = self.conf.vertices[name]
        layer = v.content if v.is_layer else None
        if layer is None or not getattr(layer, "is_pretrainable",
                                        lambda: False)():
            raise ValueError(f"vertex {name!r} is not pretrainable")
        up = layer.updater or self.conf.updater
        upd_state = self.updater_states[name]

        if not hasattr(self, "_pretrain_steps"):
            self._pretrain_steps = {}
        if name not in self._pretrain_steps:
            src = v.inputs[0]

            def step(lp, frozen_params, states, us, inputs, iteration,
                     rng):
                acts, _ = self._forward(frozen_params, states, inputs,
                                        training=False, rng=None,
                                        want_logits=False, upto=src)
                h = acts[src]
                if v.preprocessor is not None:
                    h = v.preprocessor.pre_process(h)
                loss, g = jax.value_and_grad(layer.pretrain_loss)(
                    lp, h, rng)
                updates, new_us = up.apply(g, us, iteration)
                new_lp = jax.tree_util.tree_map(
                    lambda p, u: p - u, lp, updates)
                new_lp = apply_constraints(layer, new_lp)
                return new_lp, new_us, loss

            self._pretrain_steps[name] = jax.jit(step,
                                                 donate_argnums=(0, 3))
        jit_step = self._pretrain_steps[name]

        from deeplearning4j_tpu.nn.pretrain_util import (
            feature_batches, materialize_once)
        data = materialize_once(data)

        for _ in range(n_epochs):
            for inputs in feature_batches(data, as_list=True):
                inputs = [_as_jnp(x, self._dtype) for x in inputs]
                rng = self._next_rng()
                states_in = self._with_zero_rnn_states(
                    self.states, int(inputs[0].shape[0]))
                frozen = {k: p for k, p in self.params.items()
                          if k != name}
                self.params[name], upd_state, loss = jit_step(
                    self.params[name], frozen, states_in, upd_state,
                    inputs, jnp.asarray(self.iteration_count), rng)
                self._score = loss
                self.iteration_count += 1
        self.updater_states[name] = upd_state
        return self

    def _next_rng(self):
        """Pooled rng keys: one eager threefry split per 64 iterations
        instead of one per step (the eager split showed up as ~3ms of
        host time per step in the ResNet-50 profile)."""
        pool = getattr(self, "_rng_pool", None)
        if not pool:
            keys = jax.random.split(self._rng, 65)
            self._rng = keys[0]
            self._rng_pool = list(keys[1:])
            pool = self._rng_pool
        return pool.pop()

    # ------------------------------------------------------------------
    @kernel_select.marks_partitions
    def fit_steps(self, ds, steps: int):
        """Run ``steps`` train iterations on one device-resident batch
        in ONE jit dispatch (lax.fori_loop over the compiled step — the
        Keras ``steps_per_execution`` idea). Removes the per-step host
        dispatch gap entirely; BN stats/updater state/iteration advance
        exactly as ``steps`` calls of fit() would. Listeners fire once
        per group with the final loss. Masks are not supported on this
        fast path — use fit() for masked data."""
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
        feats = ds.features if isinstance(ds.features, list) \
            else [ds.features]
        labs = ds.labels if isinstance(ds.labels, list) else [ds.labels]
        inputs = [_as_jnp(x, self._dtype) for x in feats]
        labels = [_as_jnp(y, self._dtype) for y in labs]

        if not hasattr(self, "_multi_steps"):
            self._multi_steps = {}
        if steps not in self._multi_steps:
            step_fn = self._step_fn

            def multi(params, states, upd, inputs, labels, it0, rng):
                def body(i, carry):
                    p, s, u, _, _ = carry
                    r = jax.random.fold_in(rng, i)
                    return step_fn(p, s, u, inputs, labels, None, None,
                                   it0 + i, r)

                # loss carry must match step_fn's loss dtype (bf16 nets
                # produce a bf16 loss); grad-norm carry is f32
                zero = jnp.zeros((), self._dtype)
                gz = jnp.zeros((), jnp.float32)
                return jax.lax.fori_loop(
                    0, steps, body,
                    (params, states, upd, zero, gz))

            self._multi_steps[steps] = jax.jit(multi,
                                               donate_argnums=(0, 1, 2))

        states_in = self._with_zero_rnn_states(self.states,
                                               int(inputs[0].shape[0]))
        rng = self._next_rng()
        from deeplearning4j_tpu.common import diagnostics, telemetry
        with telemetry.step_span("ComputationGraph", steps=steps) as sp:
            self.params, new_states, self.updater_states, loss, gnorm = \
                self._multi_steps[steps](self.params, states_in,
                                         self.updater_states, inputs,
                                         labels,
                                         jnp.asarray(
                                             self.iteration_count),
                                         rng)
        self.states = self._strip_rnn_states(new_states)
        self._score = loss
        self.last_batch_size = int(inputs[0].shape[0])
        self.iteration_count += steps
        # one record per group: the final step's loss/grad norm stand
        # in for the window (the fori_loop body is opaque to the host)
        diagnostics.after_step(
            self, "ComputationGraph", self.iteration_count - 1, loss,
            sp, grad_norm=gnorm if self._step_gnorm else None,
            params=self.params, steps=steps)
        for lis in self.listeners:
            lis.iteration_done(self, self.iteration_count - 1,
                               self.epoch_count)
        return self

    def _fit_dataset(self, ds):
        feats = ds.features if isinstance(ds.features, list) \
            else [ds.features]
        labs = ds.labels if isinstance(ds.labels, list) else [ds.labels]
        self._fit_batch(feats, labs, self._ds_fmask(ds),
                        self._ds_lmasks(ds))

    def _fit_batch(self, inputs: list, labels: list, fmask, lmasks):
        inputs = [_as_jnp(x, self._dtype) for x in inputs]
        labels = [_as_jnp(y, self._dtype) for y in labels]
        fmask = _as_jnp(fmask) if fmask is not None else None
        if lmasks is not None:
            lmasks = [(_as_jnp(m) if m is not None else None)
                      for m in lmasks]
        # the host's whole turn for one step: ``train_step`` (the
        # jitted call) is its child, the rest is its self time
        from deeplearning4j_tpu.common import telemetry
        with telemetry.span("fit.batch", iter=self.iteration_count):
            self._fit_step(inputs, labels, fmask, lmasks)

    def _fit_step(self, inputs: list, labels: list, fmask, lmasks):
        if self._retrace_guard is None:
            from deeplearning4j_tpu.common.compilecache import RetraceGuard
            self._retrace_guard = RetraceGuard(
                f"{type(self).__name__} train step")
        self._retrace_guard.record(inputs, labels, fmask, lmasks)
        # layer_report() with no batch re-lowers at the last fit shape
        self._layerprof_shapes = (
            [(x.shape, x.dtype) for x in inputs],
            [(y.shape, y.dtype) for y in labels])
        from deeplearning4j_tpu.nn.conf.builders import BackpropType
        if self.conf.backprop_type is BackpropType.TRUNCATED_BPTT and \
                inputs[0].ndim == 3:
            return self._fit_tbptt(inputs, labels, fmask, lmasks)
        if self._accum_steps > 1:
            return self._fit_batch_accum(inputs, labels, fmask, lmasks)
        rng = self._next_rng()
        states_in = self._with_zero_rnn_states(self.states,
                                               int(inputs[0].shape[0]))
        from deeplearning4j_tpu.common import diagnostics, telemetry
        with telemetry.step_span("ComputationGraph") as sp:
            self.params, new_states, self.updater_states, loss, gnorm = \
                self._train_step(self.params, states_in,
                                 self.updater_states, inputs, labels,
                                 fmask, lmasks,
                                 jnp.asarray(self.iteration_count), rng)
        self.states = self._strip_rnn_states(new_states)
        self._score = loss          # device scalar; float() on read
        self.last_batch_size = int(inputs[0].shape[0])
        # grads never leave the fused step; a trip attributes the first
        # bad leaf in the (poisoned) post-update params
        diagnostics.after_step(
            self, "ComputationGraph", self.iteration_count, loss, sp,
            grad_norm=gnorm if self._step_gnorm else None,
            params=self.params)
        self.iteration_count += 1
        for lis in self.listeners:
            lis.iteration_done(self, self.iteration_count - 1,
                               self.epoch_count)

    def _fit_batch_accum(self, inputs: list, labels: list, fmask,
                         lmasks):
        """Accumulation micro-step: backward + gradient add only; the
        updater fires once per ``_accum_steps`` window on the mean
        gradient with updater iteration = number of updates APPLIED
        (Adam bias correction must see update indices)."""
        rng = self._next_rng()
        states_in = self._with_zero_rnn_states(self.states,
                                               int(inputs[0].shape[0]))
        from deeplearning4j_tpu.common import diagnostics, telemetry
        with telemetry.step_span("ComputationGraph",
                                 accumulating=self._accum_steps) as sp:
            grads, new_states, loss, gnorm = self._grad_step(
                self.params, states_in, inputs, labels, fmask, lmasks,
                rng)
            # watchdog check BEFORE accumulate/apply: the apply step
            # donates the accumulated-grad buffers this micro-batch's
            # grads may alias
            diagnostics.check_numerics(
                self, "ComputationGraph", self.iteration_count, loss,
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
        self.last_batch_size = int(inputs[0].shape[0])
        diagnostics.record_step(
            self, "ComputationGraph", self.iteration_count, loss, sp,
            grad_norm=gnorm if self._step_gnorm else None)
        self.iteration_count += 1
        for lis in self.listeners:
            lis.iteration_done(self, self.iteration_count - 1,
                               self.epoch_count)

    def _fit_tbptt(self, inputs: list, labels: list, fmask, lmasks):
        """tBPTT segmentation over the time axis (SURVEY.md section 5.7);
        same carry/truncation semantics as MultiLayerNetwork._fit_tbptt."""
        L = self.conf.tbptt_fwd_length
        T = inputs[0].shape[1]
        states = self._with_zero_rnn_states(self.states,
                                            int(inputs[0].shape[0]))
        for t0 in range(0, T, L):
            seg_in = [x[:, t0:t0 + L] if x.ndim >= 3 else x
                      for x in inputs]
            seg_lab = [y[:, t0:t0 + L] if y.ndim >= 3 else y
                       for y in labels]
            seg_f = fmask[:, t0:t0 + L] if fmask is not None and \
                fmask.ndim >= 2 else fmask
            seg_l = None
            if lmasks is not None:
                seg_l = [m[:, t0:t0 + L] if m is not None and
                         m.ndim >= 2 else m for m in lmasks]
            self._rng, rng = jax.random.split(self._rng)
            self.params, states, self.updater_states, loss, gnorm = \
                self._train_step(self.params, states,
                                 self.updater_states, seg_in, seg_lab,
                                 seg_f, seg_l,
                                 jnp.asarray(self.iteration_count), rng)
            self._score = loss          # device scalar; float() on read
            from deeplearning4j_tpu.common import diagnostics
            diagnostics.after_step(
                self, "ComputationGraph", self.iteration_count, loss,
                None, grad_norm=gnorm if self._step_gnorm else None,
                params=self.params, tbptt_segment=t0 // L)
            self.iteration_count += 1
        self.states = self._strip_rnn_states(states)
        self.last_batch_size = int(inputs[0].shape[0])
        for lis in self.listeners:
            lis.iteration_done(self, self.iteration_count - 1,
                               self.epoch_count)

    # ------------------------------------------------------------------
    @kernel_select.marks_partitions
    def output(self, *inputs, train: bool = False, mask=None):
        """Returns list of output activations (single array if one
        output) — reference: ComputationGraph.output(INDArray...)."""
        if not self._initialized:
            self.init()
        xs = [_as_jnp(x, self._dtype) for x in inputs]
        mask = _as_jnp(mask) if mask is not None else None
        acts, _ = self._forward(self.dense_params(), self.states, xs,
                                training=train, rng=None,
                                want_logits=False, fmask=mask)
        outs = [acts[n] for n in self.conf.network_outputs]
        return outs[0] if len(outs) == 1 else outs

    def outputs(self, *inputs, train: bool = False, mask=None) -> list:
        """Always-a-list variant (reference: ComputationGraph.output
        returns INDArray[] regardless of output count)."""
        out = self.output(*inputs, train=train, mask=mask)
        return out if isinstance(out, list) else [out]

    def predict(self, *inputs) -> np.ndarray:
        out = self.output(*inputs)
        if isinstance(out, list):
            out = out[0]
        return np.asarray(jnp.argmax(out, axis=-1))

    # -- stateful streaming inference (SURVEY.md section 5.7;
    #    reference: ComputationGraph.rnnTimeStep) -----------------------
    def rnn_time_step(self, *inputs):
        """Feed one step (2D inputs) or a chunk (3D inputs) of a
        sequence through the DAG, carrying every recurrent vertex's
        hidden state across calls (reference: rnnTimeStep).  2D
        inputs get 2D outputs (the last timestep); 3D chunks return
        full per-step activations."""
        from deeplearning4j_tpu.nn.conf.layers_recurrent import (
            Bidirectional)
        for n in self._topo:
            v = self.conf.vertices[n]
            if v.is_layer and isinstance(v.content, Bidirectional):
                # reference throws too: the backward direction needs
                # future timesteps, which streaming cannot provide
                raise ValueError(
                    "rnnTimeStep is not supported on graphs with "
                    "Bidirectional layers")
        if not self._initialized:
            self.init()
        xs = [_as_jnp(x, self._dtype) for x in inputs]
        # only RECURRENT inputs get the step-dim treatment: a graph
        # can also carry genuinely feed-forward inputs (e.g. static
        # metadata merged after LastTimeStep) that must pass through
        # 2D, exactly as output() passes them
        from deeplearning4j_tpu.nn.conf.inputs import InputTypeRecurrent
        rec = [isinstance(t, InputTypeRecurrent)
               for t in self.conf.input_types] or [True] * len(xs)
        if len(rec) != len(xs):
            raise ValueError(
                f"rnnTimeStep got {len(xs)} inputs for "
                f"{len(rec)} declared network inputs")
        single_step = all(x.ndim == 2 for x, r in zip(xs, rec) if r)
        xs = [x[:, None, :] if r and x.ndim == 2 else x
              for x, r in zip(xs, rec)]
        batch = int(xs[0].shape[0])
        if getattr(self, "_rnn_stream_states", None) is None:
            self._rnn_stream_states = self._with_zero_rnn_states(
                self.states, batch)
            self._rnn_stream_batch = batch
        elif batch != self._rnn_stream_batch:
            raise ValueError(
                f"rnnTimeStep batch size {batch} != stored state "
                f"batch size {self._rnn_stream_batch}; call "
                f"rnn_clear_previous_state() first")
        acts, new_states = self._forward(
            self.dense_params(), self._rnn_stream_states, xs,
            training=False, rng=None, want_logits=False)
        # keep persistent (BN) states as-is; update only rnn carries
        merged = dict(self._rnn_stream_states)
        for k in self._recurrent_names():
            merged[k] = new_states[k]
        self._rnn_stream_states = merged
        outs = [acts[n] for n in self.conf.network_outputs]
        if single_step:
            outs = [o[:, -1] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self):
        self._rnn_stream_states = None

    def rnn_get_previous_state(self, vertex_name: str):
        """Stored streaming state of one recurrent vertex, by name
        (reference: rnnGetPreviousState(String))."""
        if getattr(self, "_rnn_stream_states", None) is None:
            return None
        return self._rnn_stream_states.get(vertex_name)

    def rnn_set_previous_state(self, vertex_name: str, state: dict):
        """Overwrite one vertex's streaming state (reference:
        rnnSetPreviousState).  Works on a fresh network too: the
        batch size is inferred from the provided state arrays."""
        if not self._initialized:
            self.init()
        if vertex_name not in self._recurrent_names():
            raise ValueError(
                f"'{vertex_name}' is not a recurrent vertex "
                f"(recurrent: {self._recurrent_names()})")
        leaves = jax.tree_util.tree_leaves(state)
        if not leaves:
            raise ValueError("cannot infer batch size from an "
                             "empty state dict")
        batch = int(leaves[0].shape[0])
        if getattr(self, "_rnn_stream_states", None) is None:
            self._rnn_stream_states = self._with_zero_rnn_states(
                self.states, batch)
            self._rnn_stream_batch = batch
        elif batch != self._rnn_stream_batch:
            raise ValueError(
                f"rnnSetPreviousState batch size {batch} != stored "
                f"state batch size {self._rnn_stream_batch}; call "
                f"rnn_clear_previous_state() first")
        self._rnn_stream_states = dict(self._rnn_stream_states)
        self._rnn_stream_states[vertex_name] = state

    @staticmethod
    def _ds_fmask(ds):
        """First features mask, honoring both the MultiDataSet plural
        (features_masks) and DataSet singular (features_mask) attrs —
        same lookup order as _fit_dataset."""
        ms = getattr(ds, "features_masks", None)
        if ms:
            return ms[0]
        return getattr(ds, "features_mask", None)

    @staticmethod
    def _ds_lmasks(ds):
        ms = getattr(ds, "labels_masks", None)
        if ms is not None:
            return ms
        lm = getattr(ds, "labels_mask", None)
        return [lm] if lm is not None else None

    @kernel_select.marks_partitions
    def score(self, dataset=None) -> float:
        if dataset is None:
            return float(self._score)
        feats = dataset.features if isinstance(dataset.features, list) \
            else [dataset.features]
        labs = dataset.labels if isinstance(dataset.labels, list) \
            else [dataset.labels]
        xs = [_as_jnp(x, self._dtype) for x in feats]
        ys = [_as_jnp(y, self._dtype) for y in labs]
        lmasks = self._ds_lmasks(dataset)
        fmask = self._ds_fmask(dataset)
        params = self.dense_params()
        acts, _ = self._forward(
            params, self.states, xs, training=False, rng=None,
            want_logits=True,
            fmask=_as_jnp(fmask) if fmask is not None else None)
        loss = self._regularization(params)
        out_confs = self.output_layer_confs()
        for i, out_name in enumerate(self.conf.network_outputs):
            layer = out_confs.get(out_name)
            if layer is None:
                continue
            loss = loss + layer.compute_loss(
                ys[i], acts[out_name], from_logits=layer.wants_logits(),
                mask=lmasks[i] if lmasks is not None else None)
        return float(loss)

    def evaluate(self, iterator):
        from deeplearning4j_tpu.evaluation import Evaluation
        ev = Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            feats = ds.features if isinstance(ds.features, list) \
                else [ds.features]
            out = self.output(*feats, mask=self._ds_fmask(ds))
            if isinstance(out, list):
                out = out[0]
            lmasks = self._ds_lmasks(ds)
            ev.eval(ds.labels if not isinstance(ds.labels, list)
                    else ds.labels[0], out,
                    mask=lmasks[0] if lmasks else None)
        return ev

    # ------------------------------------------------------------------
    def num_params(self) -> int:
        return int(sum(np.prod(p.shape) for p in
                       jax.tree_util.tree_leaves(self.dense_params())))

    def param_table(self) -> dict:
        out = {}
        params = self.dense_params()
        for name in self._topo:
            for pname, p in params.get(name, {}).items():
                out[f"{name}_{pname}"] = p
        return out

    @kernel_select.marks_partitions
    def lower_train_step(self, data=None, labels=None):
        """The jitted train step lowered at the given batch (or the
        last fitted batch's shapes) — the one home of the coupling to
        the step's argument list (``layer_report``, the benchmarks'
        cost analysis and ``chip_smoke.py`` read the compiled program
        through it).  Lowering only — nothing executes, buffers are
        not donated."""
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
                    "lowering the train step needs a batch: pass "
                    "(data, labels) or fit at least one batch first")
            xs, ys = shapes
            data = [np.zeros(s, dtype=d) for s, d in xs]
            labels = [np.zeros(s, dtype=d) for s, d in ys]
        if not isinstance(data, list):
            data = [data]
        if not isinstance(labels, list):
            labels = [labels]
        inputs = [_as_jnp(x, self._dtype) for x in data]
        labs = [_as_jnp(y, self._dtype) for y in labels]
        states_in = self._with_zero_rnn_states(
            self.states, int(inputs[0].shape[0]))
        return self._train_step.lower(
            self.params, states_in, self.updater_states, inputs, labs,
            None, None, jnp.asarray(0), jax.random.PRNGKey(0))

    def layer_report(self, data=None, labels=None, **roofline_kw):
        """Per-vertex flops/bytes/roofline attribution of the compiled
        train step (common.layerprof): lowers the jitted step at the
        given batch (or the last fitted batch's shapes), partitions
        ``cost_analysis()`` by the ``dl4j.<vertex>`` scopes, and joins
        the kernel-select decisions recorded at trace time.  Also
        published to ``GET /api/layers`` and the ``dl4j_layer_*``
        metrics."""
        lowered = self.lower_train_step(data, labels)
        types = {layerprof.sanitize(n):
                 type(self.conf.vertices[n].content).__name__
                 for n in self._topo}
        return layerprof.attribute_compiled(
            lowered.compile(), model_name=type(self).__name__,
            layer_types=types, **roofline_kw)

    def summary(self) -> str:
        lines = [f"{'vertex':<28} {'type':<22} {'inputs':<28} {'params':<10}"]
        total = 0
        params = self.dense_params()
        for name in self._topo:
            v = self.conf.vertices[name]
            n = int(sum(np.prod(p.shape)
                        for p in params.get(name, {}).values()))
            total += n
            lines.append(f"{name:<28} {type(v.content).__name__:<22} "
                         f"{','.join(v.inputs):<28} {n:<10}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)
