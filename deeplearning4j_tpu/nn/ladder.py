"""What ``MultiLayerNetwork`` and ``ComputationGraph`` share of the
training ladder: the mesh and exchange mode a model's step specializes
on, the jitted step scaffolding round ``parallel.zero.apply_update``,
gradient accumulation, and the layout sync that moves params and
updater state between the dense, ZeRO-1 flat, encoded and fsdp-resident
layouts.

A model class supplies what is its own — its entries in update order
with their layer confs (``_build_train_step`` hands them to
:meth:`TrainingLadder._build_steps`) and a loss over
``(params, states, inputs, labels, fmask, lmasks, rng)``. Which tail
runs for which mode is ``parallel.zero``'s decision, not this
module's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common import layerprof
from deeplearning4j_tpu.learning.updaters import (TP_KEY, has_tp,
                                                  is_dp_sharded,
                                                  is_encoded, is_fsdp)
from deeplearning4j_tpu.nn.conf.constraints import apply_constraints
from deeplearning4j_tpu.parallel import zero
from deeplearning4j_tpu.parallel.zero import UpdateExchange


class TrainingLadder:
    """Mixin: ``params`` / ``updater_states`` are ``{entry: subtree}``
    dicts (an entry is a layer or a vertex) and ``conf`` carries the
    default updater and the gradient normalization."""

    def _init_ladder(self):
        self._train_step = None
        self._step_gnorm = False    # step emits a real grad norm
        # the (possibly 2D) mesh the step tail specializes on and the
        # resolved exchange over its data axis (parallel.zero): DENSE
        # with no mesh, and with one under dense×tp, where the step
        # needs the mesh for the tp pins only
        self._dp_mesh = None
        self._dp_axis = "data"
        self._dp_mode = UpdateExchange.DENSE
        # the static EncodingSpec of the ENCODED exchange
        self._dp_encoding = None
        # FSDP: params live as 1/N flat shards ({FSDP_KEY: {dtype:
        # flat}} per entry), gathered per entry just-in-time in the
        # forward; the per-entry DpFlatSpec is what densifies them
        self._fsdp_specs = {}
        # tensor parallelism (parallel.speclayout): per-entry
        # {name: TpLeafSpec} for model-axis sharded leaves
        self._tp_model_axis = None
        self._tp_specs = {}
        # gradient accumulation (reference: GradientsAccumulator)
        self._accum_steps = 1
        self._accum_grads = None
        self._accum_count = 0
        self._updates_applied = 0

    # ------------------------------------------------------------------
    def _param_view(self, order):
        """What the loss applies to ``params`` before the forward walk:
        under fsdp a lazy view over the 1/N flat shards, so each
        entry's all-gather is emitted at its point of use in the walk
        (``order`` is the walk's, for the next-entry prefetch); with tp
        specs on a dense layout (2D mode) the tp leaves pinned to their
        compute spec — the custom-vjp pin sends the cotangent to the
        resident spec, so dp grad collectives stay on the data axis."""
        mesh, axis = self._dp_mesh, self._dp_axis
        tp_specs = dict(self._tp_specs)     # empty without a mesh
        if self._dp_mode is UpdateExchange.FSDP:
            from deeplearning4j_tpu.common.environment import Environment
            fsdp_specs = dict(self._fsdp_specs)
            prefetch = Environment.get().fsdp_prefetch
            order = list(order)
            return lambda params: zero.FsdpParamView(
                params, fsdp_specs, mesh, axis, order=order,
                prefetch=prefetch, tp_specs=tp_specs)
        if tp_specs:
            return lambda params: zero.pin_tp_params(params, mesh,
                                                     tp_specs)
        return lambda params: params

    def _build_steps(self, loss_fn, layers):
        """Compile the fused step, the accumulation micro-step and the
        apply step round ``loss_fn``. ``layers`` maps every entry, in
        update order, to its layer conf (None for an entry that is no
        layer): the conf names the entry's updater (the model's default
        otherwise) and its post-update constraints."""
        conf = self.conf
        updaters = {k: (layer.updater if layer is not None
                        and layer.updater else conf.updater)
                    for k, layer in layers.items()}
        normalization = (conf.gradient_normalization,
                         conf.gradient_normalization_threshold)
        mesh, axis, mode = self._dp_mesh, self._dp_axis, self._dp_mode
        encoding = self._dp_encoding
        tp_specs = dict(self._tp_specs)     # empty without a mesh

        # numerics watchdog (common.diagnostics): when armed, the step
        # also emits the global grad norm — computed in-jit, fused into
        # the backward, so the host check is one extra scalar read.
        # When off it is a free zeros constant and XLA dead-code
        # eliminates the reduction; the step keeps ONE output shape.
        from deeplearning4j_tpu.common.diagnostics import watchdog_enabled
        want_gnorm = watchdog_enabled()
        self._step_gnorm = want_gnorm

        def grad_norm(grads):
            if not want_gnorm:
                return jnp.zeros((), jnp.float32)
            sq = [jnp.sum(jnp.square(g.astype(jnp.float32)))
                  for g in jax.tree_util.tree_leaves(grads)]
            return jnp.sqrt(sum(sq)) if sq else jnp.zeros((),
                                                          jnp.float32)

        def update_tail(params, upd_states, grads, iteration):
            """Grads -> (new_params, new_upd), entry by entry through
            ``zero.apply_update``; shared by the fused step and the
            accumulation apply step."""
            new_params, new_upd = {}, {}
            for k, up in updaters.items():
                g = grads.get(k, {})
                if not g:
                    new_params[k] = params.get(k, {})
                    new_upd[k] = upd_states.get(k, ())
                    continue
                new_p, new_upd[k] = zero.apply_update(
                    up, g, params[k], upd_states[k], iteration,
                    mesh=mesh, axis=axis, mode=mode,
                    tp_specs=tp_specs.get(k), encoding=encoding,
                    normalization=normalization)
                # post-update projection (reference: constraints are
                # applied after the updater, inside the same step).
                # Not under fsdp: it needs full tensors, and the
                # resolver refuses fsdp when any layer has constraints
                if layers[k] is not None and \
                        mode is not UpdateExchange.FSDP:
                    new_p = apply_constraints(layers[k], new_p)
                new_params[k] = new_p
            return new_params, new_upd

        def step(params, states, upd_states, inputs, labels, fmask,
                 lmasks, iteration, rng):
            (loss, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, states, inputs, labels,
                                       fmask, lmasks, rng)
            gnorm = grad_norm(grads)
            # attribution scope: the updater sweep reads/writes every
            # parameter — substantial byte traffic that is not any
            # entry's compute
            with layerprof.scope("optimizer"):
                new_params, new_upd = update_tail(params, upd_states,
                                                  grads, iteration)
            return new_params, new_states, new_upd, loss, gnorm

        def grad_step(params, states, inputs, labels, fmask, lmasks,
                      rng):
            # accumulation micro-step: backward only, no update (params
            # NOT donated — the apply step still reads them)
            (loss, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, states, inputs, labels,
                                       fmask, lmasks, rng)
            return grads, new_states, loss, grad_norm(grads)

        def apply_step(params, upd_states, grads, scale, iteration):
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            with layerprof.scope("optimizer"):
                new_params, new_upd = update_tail(params, upd_states,
                                                  grads, iteration)
            return new_params, new_upd

        # donate params/states/updater-state buffers: XLA reuses them
        # in place of the reference's workspaces
        self._step_fn = step        # unjitted (multi-step path reuses)
        self._train_step = jax.jit(step, donate_argnums=(0, 1, 2))
        self._grad_step = jax.jit(grad_step, donate_argnums=(1,))
        self._apply_step = jax.jit(apply_step, donate_argnums=(1, 2))
        self._accum_add = jax.jit(
            lambda acc, g: jax.tree_util.tree_map(
                lambda a, b: a + b, acc, g),
            donate_argnums=(0,))

    # ------------------------------------------------------------------
    def set_dp_mesh(self, mesh, axis: str = "data", mode=None, *,
                    model_axis=None, tp_specs=None, encoding=None):
        """Install (or clear, with ``mesh=None``) the (possibly 2D)
        mesh the jitted step tail specializes on (``parallel.zero``).
        ``mode="fsdp"`` selects the ZeRO-3 tail: params convert to the
        1/N flat resident layout here (the model owns both param and
        updater-state conversion under fsdp); ``mode="dense"`` installs
        the mesh WITHOUT the ZeRO-1 tail (dense×tp: the step needs the
        mesh for tensor-parallel pins only); ``mode="encoded"`` selects
        the compressed-collective tail (``encoding=`` takes an
        ``EncodingSpec`` or scheme string; the ENCODED_KEY
        error-feedback state is injected at the next layout sync); any
        other mode with a mesh is the ZeRO-1 tail, for which callers
        still own converting/placing ``updater_states``.
        ``model_axis``/``tp_specs`` (``parallel.speclayout``) add the
        tensor-parallel dimension: spec'd leaves pin to the model axis
        in-step and never enter the dp flats. Invalidates compiled
        steps."""
        mode_s = str(getattr(mode, "value", mode) or "").lower()
        if mesh is None or mode_s == "dense":
            mode = UpdateExchange.DENSE
        elif mode_s in ("fsdp", "encoded"):
            mode = UpdateExchange(mode_s)
        else:
            mode = UpdateExchange.SHARDED
        if mode is UpdateExchange.ENCODED:
            from deeplearning4j_tpu.parallel.encoding import \
                resolve_encoding
            encoding = resolve_encoding(encoding)
        else:
            encoding = None
        tp_specs = dict(tp_specs or {}) if mesh is not None else {}
        model_axis = model_axis if tp_specs else None
        if mesh is self._dp_mesh and axis == self._dp_axis and \
                mode is self._dp_mode and \
                encoding == self._dp_encoding and \
                model_axis == self._tp_model_axis and \
                tp_specs == self._tp_specs:
            return self
        self.flush_accumulated()
        self._dp_mesh = mesh
        self._dp_axis = axis
        self._dp_mode = mode
        self._dp_encoding = encoding
        self._tp_model_axis = model_axis
        self._tp_specs = tp_specs
        self._train_step = None
        self._step_fn = None
        self._grad_step = None
        self._apply_step = None
        self._accum_add = None
        if hasattr(self, "_multi_steps"):
            del self._multi_steps
        self._sync_param_layout()
        return self

    def set_accumulation_steps(self, n: int):
        """Apply the updater once every ``n`` fit() micro-batches on the
        mean of their gradients (the reference's GradientsAccumulator):
        effective batch = n x micro-batch with no extra activation HBM."""
        n = max(int(n), 1)
        if n != self._accum_steps:
            self.flush_accumulated()
            self._accum_steps = n
        return self

    def flush_accumulated(self):
        """Apply a partial accumulation window now (epoch end / mode
        change); no-op when nothing is pending."""
        if self._accum_count:
            self._apply_accumulated()
        return self

    def _apply_accumulated(self):
        k = self._accum_count
        scale = jnp.asarray(1.0 / k, jnp.float32)
        self.params, self.updater_states = self._apply_step(
            self.params, self.updater_states, self._accum_grads, scale,
            jnp.asarray(self._updates_applied))
        self._accum_grads = None
        self._accum_count = 0
        self._updates_applied += 1

    def _sync_updater_layout(self):
        """A checkpoint restored from a ZeRO-1 run carries flat sharded
        updater state; on a plain (no-mesh) model — or under the
        dense×tp tail, which consumes dense state — convert it back to
        the dense per-entry layout before stepping (ENCODED_KEY
        error-feedback state is stripped there: the residual belongs
        to the compressed exchange). Under ``mode="encoded"`` the
        inverse sync runs: entries missing their ENCODED_KEY state
        (first fit, or a dense/sharded checkpoint restored into an
        encoded run — on any device count) get it injected and placed."""
        if self._dp_mode is not UpdateExchange.DENSE:
            if self._dp_mode is UpdateExchange.ENCODED:
                n = self._dp_mesh.shape[self._dp_axis]
                states = self.updater_states
                new = zero.ensure_encoded_states(
                    self.dense_params(), states, n, self._dp_encoding,
                    tp_specs=self._tp_specs)
                if any(new[k] is not states.get(k) for k in new):
                    self.updater_states = zero.place_updater_states(
                        self._dp_mesh, new, self._dp_axis,
                        tp_specs=self._tp_specs)
            return
        if any(is_dp_sharded(s) or has_tp(s) or is_encoded(s)
               for s in self.updater_states.values()):
            self.updater_states = zero.strip_encoded_states(
                zero.states_to_dense(self.params, self.updater_states))

    def _params_are_fsdp(self) -> bool:
        return any(is_fsdp(p) for p in self.params.values()
                   if isinstance(p, dict))

    def _sync_param_layout(self):
        """Enter/leave the fsdp flat resident param layout
        (parallel.zero). Entering converts updater state to the ZeRO-1
        flat layout too (the fsdp tail consumes it) and places both at
        1/N per replica; leaving densifies params (gather timed into
        ``dl4j_fsdp_gather_seconds``).  Elastic re-mesh: flats resident
        for a DIFFERENT world size (resume onto a new mesh) round-trip
        through the dense layout and re-enter — params via
        ``params_to_dense`` -> ``place_fsdp_params``, updater state via
        its ``DpFlatSpec`` re-ravel inside ``states_to_sharded``."""
        flat = self._params_are_fsdp()
        if self._dp_mode is UpdateExchange.FSDP:
            n = self._dp_mesh.shape[self._dp_axis]
            if flat:
                if zero.fsdp_spec_shards(self._fsdp_specs) == n and \
                        self._tp_layout_matches():
                    # already resident; placement happened on entry
                    return
                # raveled for another world size (or another tp
                # partition): densify and re-enter
                self._densify_params_inplace()
            self.updater_states = zero.states_to_sharded(
                self.params, self.updater_states, n,
                tp_specs=self._tp_specs)
            self.params, self._fsdp_specs = zero.params_to_fsdp(
                self.params, n, tp_specs=self._tp_specs)
            self.params = zero.place_fsdp_params(
                self._dp_mesh, self.params, self._dp_axis,
                tp_specs=self._tp_specs)
            self.updater_states = zero.place_updater_states(
                self._dp_mesh, self.updater_states, self._dp_axis,
                tp_specs=self._tp_specs)
        elif flat:
            self._densify_params_inplace()

    def _tp_layout_matches(self) -> bool:
        """True when the resident fsdp entries' TP_KEY split matches
        the installed tp specs (an fsdp×tp checkpoint restored onto a
        mesh with a different tp degree must densify and re-enter)."""
        want = {k: set(v) for k, v in (self._tp_specs or {}).items()}
        for k, sub in self.params.items():
            if not isinstance(sub, dict) or not is_fsdp(sub):
                continue
            got = set(sub.get(TP_KEY, {}))
            if got != want.get(k, set()):
                return False
        return True

    def _densify_params_inplace(self):
        if self._params_are_fsdp():
            self.params = zero.params_to_dense(self.params,
                                               self._fsdp_specs)
            # specs kept: a later _sync_param_layout re-entry recomputes
            if any(zero.on_2d_mesh(a)
                   for a in jax.tree_util.tree_leaves(self.params)):
                # leaving a 2D (data, model) residency: the densified
                # leaves still carry the old mesh's shardings, and
                # re-raveling them through XLA SPMD hits the same
                # concatenate-lowering bug worked around in
                # zero.apply_update_sharded — re-enter from host copies
                self.params = jax.device_get(self.params)
                self.updater_states = jax.device_get(self.updater_states)

    def dense_params(self) -> dict:
        """Params in the dense per-entry layout regardless of residency
        (non-mutating; under fsdp this is a full host-side all-gather —
        checkpoint/inference/introspection consumers only)."""
        return zero.params_to_dense(self.params, self._fsdp_specs)
