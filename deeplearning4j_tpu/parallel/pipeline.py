"""Pipeline parallelism — microbatch schedules over the ``pipe`` axis.

The reference has NO pipeline parallelism (SURVEY.md §2.6 P8: ABSENT).
Two engines live here:

1. The scan engine (``pipeline_apply`` / ``pipeline_loss``): one
   homogeneous stage fn, ONE ``lax.scan`` over clock ticks inside
   ``shard_map`` with ``lax.ppermute`` neighbor handoffs that ride ICI.
   XLA sees a static loop (compiles once, overlaps the permute with the
   next tick's compute), the VJP of the scan IS the backward pipeline,
   and ``jax.checkpoint`` on the stage fn gives remat-per-microbatch.
   This is the all-forward-then-backward **GPipe reference schedule**
   (transformer block stacks still train through it).
2. The promoted real fit path (ISSUE 18): ``StagePartition`` splits an
   MLN layer stack / graph topology into contiguous byte-balanced
   stages, ``build_schedule`` emits an explicit GPipe or 1F1B tick
   table, and ``PipelineTrainer`` executes it stage by stage on the
   ``pipe`` axis of a 3D ``(data, model, pipe)`` mesh. Each backward
   re-runs its stage forward under ``jax.vjp`` inside the jit —
   remat-per-microbatch by construction, so only the stage *input* of
   each in-flight microbatch stays resident. 1F1B bounds that
   residency at ``min(M, S-s)`` microbatches per stage versus GPipe's
   ``M``; the bubble fraction ``(S-1)/(M+S-1)`` is identical.

Layout-axis ownership (the PR-12 cross-link convention): this module
owns the ``pipe`` mesh axis — which stage holds which contiguous slice
of the network, and the microbatch schedule that streams activations
between stages. ``parallel/speclayout.py`` owns the ``model``-axis
parameter specs (column/row tensor-parallel placement plus the fsdp
``data`` residency axis) and per-stage spec restriction;
``parallel/tensor.py`` owns the column/row sharded matmul math on the
``model`` axis. The three compose into the 3D mesh built by
``ParallelWrapper.Builder.pipeline_stages`` (parallel/wrapper.py).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

PIPE_AXIS = "pipe"


def to_microbatches(x, n_micro: int):
    """[b, ...] -> [n_micro, b/n_micro, ...] (leading-dim split)."""
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])


def from_microbatches(x):
    return x.reshape((-1,) + x.shape[2:])


def pipeline_apply(stage_fn: Callable, stage_params, x_micro,
                   axis: str = PIPE_AXIS,
                   remat: bool = False,
                   with_aux: bool = False,
                   varying_axes: Optional[tuple] = None):
    """Run microbatches through the stage pipeline (inside shard_map).

    stage_fn(params, x) -> y with ``y.shape == x.shape`` (transformer
    blocks preserve [mb, t, d], so stacks satisfy this naturally).
    ``stage_params`` are THIS device's stage weights. ``x_micro`` is
    [n_micro, mb, ...], same on every stage (only stage 0 reads it).
    Returns [n_micro, mb, ...]; rows are valid on the LAST stage.

    With ``with_aux`` the stage fn returns ``(y, aux_scalar)`` (e.g. a
    MoE load-balancing loss); returns ``(outputs, aux_sum)`` where
    ``aux_sum`` accumulates only *valid* ticks — warm-up/drain bubble
    ticks compute on garbage activations and must not contribute.
    """
    n_st = _axis_size_concrete(axis)
    stage = lax.axis_index(axis)
    n_micro = x_micro.shape[0]
    raw = stage_fn if with_aux else (
        lambda p, x: (stage_fn(p, x), jnp.zeros((), x.dtype)))
    fn = jax.checkpoint(raw) if remat else raw
    perm = [(i, (i + 1) % n_st) for i in range(n_st)]

    def tick(carry, t):
        state, outputs, aux_acc = carry
        inject = x_micro[jnp.clip(t, 0, n_micro - 1)]
        x_in = jnp.where(stage == 0, inject, state)
        y, aux = fn(stage_params, x_in)
        valid = (t >= stage) & (t - stage < n_micro)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        out_idx = jnp.clip(t - (n_st - 1), 0, n_micro - 1)
        collect = (stage == n_st - 1) & (t >= n_st - 1)
        outputs = jnp.where(collect, outputs.at[out_idx].set(y), outputs)
        state = lax.ppermute(y, axis, perm)
        return (state, outputs, aux_acc), None

    vaxes = tuple(varying_axes) if varying_axes else (axis,)
    state0 = _varying(jnp.zeros_like(x_micro[0]), vaxes)
    out0 = _varying(jnp.zeros_like(x_micro), vaxes)
    aux0 = _varying(jnp.zeros((), x_micro.dtype), vaxes)
    (_, outputs, aux_sum), _ = lax.scan(
        tick, (state0, out0, aux0), jnp.arange(n_micro + n_st - 1))
    if with_aux:
        return outputs, aux_sum
    return outputs


def _varying(x, axes):
    """Mark x as device-varying over ``axes`` (shard_map VMA typing —
    the scan carry differs per stage even though it starts as zeros;
    with MoE/DP inside the stage fn it also varies over those axes)."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    have = jax.typeof(x).vma
    axes = tuple(a for a in axes if a not in have)
    if not axes:
        return x
    return lax.pcast(x, axes, to="varying")


from .mesh import axis_size as _axis_size_concrete  # shared helper


def last_stage_only(value, axis: str = PIPE_AXIS):
    """Zero ``value`` except on the last pipeline stage, then psum —
    every stage ends up holding the last stage's value (the way a
    pipelined loss becomes a global scalar)."""
    n_st = lax.psum(1, axis)
    stage = lax.axis_index(axis)
    keep = (stage == n_st - 1).astype(value.dtype)
    return lax.psum(value * keep, axis)


def pipeline_loss(stage_fn: Callable, loss_fn: Callable, stage_params,
                  x_micro, y_micro, axis: str = PIPE_AXIS,
                  remat: bool = False):
    """Forward the pipeline and reduce a mean loss on the last stage.

    loss_fn(outputs_mb, labels_mb) -> scalar mean loss per microbatch.
    Returns the same scalar on every stage (safe to grad through).
    """
    outs = pipeline_apply(stage_fn, stage_params, x_micro, axis, remat)
    n_micro = x_micro.shape[0]
    per_mb = jax.vmap(loss_fn)(outs, y_micro)
    return last_stage_only(jnp.mean(per_mb), axis)


def init_stage_params(init_fn: Callable, axis: str = PIPE_AXIS):
    """Build THIS stage's params inside shard_map:
    ``init_fn(stage_index) -> params pytree`` (use lax.switch or
    index-folded RNG keys inside)."""
    return init_fn(lax.axis_index(axis))


# ======================================================================
# ISSUE 18 — the promoted real fit path: explicit schedule tables,
# contiguous stage partitioning, and the host-level stage executor.
# ======================================================================
import logging
import time

import numpy as np

log = logging.getLogger("deeplearning4j_tpu")

#: microbatch schedules the real fit path understands
SCHEDULES = ("gpipe", "1f1b")


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe/1F1B pipeline bubble: ``(S-1)/(M+S-1)`` of the schedule's
    ticks are idle on some stage (warm-up + drain). Identical for both
    schedules — 1F1B trades activation residency, not bubble."""
    s, m = int(n_stages), int(n_micro)
    return (s - 1) / float(m + s - 1)


def build_schedule(n_stages: int, n_micro: int, kind: str = "1f1b"):
    """The explicit tick table for ``kind`` — a list of ticks, each a
    tuple of per-stage ops: ``("F", m)``, ``("B", m)`` or ``None``
    (idle/bubble).

    GPipe: every stage runs all ``M`` forwards, then backwards in
    reverse microbatch order (matching the scan engine's VJP).
    1F1B: after a ``S-s-1``-deep warm-up, stage ``s`` alternates one
    backward per forward, so at most ``min(M, S-s)`` microbatches are
    ever in flight (forwarded but not yet backwarded) on it.
    """
    s_n, m_n = int(n_stages), int(n_micro)
    if kind not in SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {kind!r} "
                         f"(know {SCHEDULES})")
    if s_n < 1 or m_n < 1:
        raise ValueError(f"need n_stages >= 1 and n_micro >= 1, got "
                         f"({s_n}, {m_n})")
    fwd = [0] * s_n            # forwards committed per stage
    bwd = [0] * s_n            # backwards committed per stage
    ticks = []
    while any(b < m_n for b in bwd):
        ops = []
        for s in range(s_n):
            op = None
            f_ready = fwd[s] < m_n and (s == 0 or fwd[s - 1] > fwd[s])
            if kind == "gpipe":
                if f_ready:
                    op = ("F", fwd[s])
                elif fwd[s] == m_n and bwd[s] < m_n:
                    m = m_n - 1 - bwd[s]     # reverse microbatch order
                    if s == s_n - 1 or bwd[s + 1] >= m_n - m:
                        op = ("B", m)
            else:                            # 1f1b, in-order backward
                in_flight = fwd[s] - bwd[s]
                prefer_b = fwd[s] == m_n or in_flight > s_n - s - 1
                b_ready = bwd[s] < m_n and fwd[s] > bwd[s] and \
                    (s == s_n - 1 or bwd[s + 1] > bwd[s])
                if prefer_b:
                    # no forward fallback: falling forward here is what
                    # would let residency grow past S-s
                    op = ("B", bwd[s]) if b_ready else None
                elif f_ready:
                    op = ("F", fwd[s])
            ops.append(op)
        for s, op in enumerate(ops):          # commit AFTER the tick
            if op is not None:
                if op[0] == "F":
                    fwd[s] += 1
                else:
                    bwd[s] += 1
        if not any(ops):
            raise RuntimeError("pipeline schedule deadlocked "
                               f"(kind={kind}, S={s_n}, M={m_n})")
        ticks.append(tuple(ops))
    return ticks


def peak_residency(schedule, n_stages: int):
    """Per-stage max in-flight microbatches (forwarded, backward still
    pending) over a tick table — the activation-stash bound. GPipe
    peaks at ``M`` on stage 0; 1F1B at ``min(M, S-s)``."""
    live = [0] * n_stages
    peak = [0] * n_stages
    for ops in schedule:
        for s, op in enumerate(ops):
            if op is None:
                continue
            live[s] += 1 if op[0] == "F" else -1
            peak[s] = max(peak[s], live[s])
    return peak


def schedule_idle_ticks(schedule, n_stages: int):
    """Per-stage count of bubble ticks (no op scheduled)."""
    return [sum(1 for ops in schedule if ops[s] is None)
            for s in range(n_stages)]


def stage_submesh(mesh, stage: int, pipe_axis: str = PIPE_AXIS):
    """The (data[, model]) submesh holding pipeline stage ``stage`` —
    the pipe axis is dropped, every other axis keeps its extent, so the
    existing dp/ZeRO-1/tp machinery runs unchanged *within* a stage."""
    from jax.sharding import Mesh
    names = list(mesh.axis_names)
    if pipe_axis not in names:
        raise ValueError(f"mesh axes {tuple(names)} have no "
                         f"{pipe_axis!r} axis")
    k = names.index(pipe_axis)
    devs = np.moveaxis(np.asarray(mesh.devices), k, -1)[..., stage]
    rest = tuple(n for n in names if n != pipe_axis)
    if not rest:                       # pp-only mesh: 1-device stages
        from .mesh import DEFAULT_DATA_AXIS
        return Mesh(devs.reshape((1,)), (DEFAULT_DATA_AXIS,))
    return Mesh(devs, rest)


def _entry_param_bytes(entry) -> int:
    total = 0
    for a in jax.tree_util.tree_leaves(entry):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            total += int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
    return total


class StagePartition:
    """Contiguous split of an ordered entry list (MLN ``layer_i`` keys,
    graph topo vertex names) into ``n_stages`` stages, greedily
    balanced by parameter bytes. Contiguity is what makes the handoff
    a single activation-edge cut per boundary."""

    def __init__(self, entries, boundaries):
        self.entries = list(entries)
        self.boundaries = list(boundaries)
        self.n_stages = len(self.boundaries) - 1

    @classmethod
    def build(cls, entries, params, n_stages: int) -> "StagePartition":
        entries = list(entries)
        s_n = int(n_stages)
        if s_n < 1:
            raise ValueError(f"n_stages must be >= 1, got {s_n}")
        if len(entries) < s_n:
            raise ValueError(
                f"cannot split {len(entries)} layers/vertices into "
                f"{s_n} pipeline stages — need at least one per stage")
        sizes = [float(_entry_param_bytes((params or {}).get(e, {})))
                 for e in entries]
        if not sum(sizes):
            sizes = [1.0] * len(entries)
        total = sum(sizes)
        bounds, acc = [0], 0.0
        for i, sz in enumerate(sizes):
            if len(bounds) == s_n:
                break
            acc += sz
            left = len(entries) - (i + 1)
            need = s_n - len(bounds)
            if left == need or (acc >= total / s_n and left >= need):
                bounds.append(i + 1)
                acc = 0.0
        bounds.append(len(entries))
        return cls(entries, bounds)

    def stage_entries(self, s: int):
        return self.entries[self.boundaries[s]:self.boundaries[s + 1]]

    def stage_of(self, entry) -> int:
        i = self.entries.index(entry)
        for s in range(self.n_stages):
            if self.boundaries[s] <= i < self.boundaries[s + 1]:
                return s
        raise ValueError(entry)

    def stage_param_bytes(self, params):
        return [sum(_entry_param_bytes(params.get(e, {}))
                    for e in self.stage_entries(s))
                for s in range(self.n_stages)]


def _tree_bytes(tree) -> int:
    return sum(int(getattr(a, "nbytes", 0))
               for a in jax.tree_util.tree_leaves(tree))


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


# -- model adapters ---------------------------------------------------------
# The trainer is model-shape-agnostic; these adapters map MLN's linear
# layer stack and the graph's topo order onto the common contract:
# ordered entries, a per-stage forward, a last-stage loss, and the
# per-entry updater/constraint/regularization dispatch of the model's
# own dense train step.

class _MlnStages:
    def __init__(self, model):
        self.model = model
        self.part = None
        conf = model.conf
        self.n_layers = len(conf.layers)
        self.out_layer = conf.layers[-1]
        self.want_logits = self.out_layer.wants_logits()

    def entries(self):
        return [f"layer_{i}" for i in range(self.n_layers)]

    def finalize(self):
        pass

    def fwd_fn(self, s: int):
        lo = self.part.boundaries[s]
        hi = self.part.boundaries[s + 1]
        model = self.model

        def fwd(stage_params, states, h, fmask, rng):
            return model._forward(stage_params, states, h,
                                  training=True, rng=rng,
                                  want_logits=False, mask=fmask,
                                  start_at=lo, stop_at=hi)
        return fwd

    def loss_fn(self, s: int):
        lo = self.part.boundaries[s]
        model, out_layer = self.model, self.out_layer
        wl = self.want_logits

        def fn(stage_params, states, h, y, lmask, fmask, rng):
            out, ns = model._forward(stage_params, states, h,
                                     training=True, rng=rng,
                                     want_logits=True, mask=fmask,
                                     start_at=lo)
            loss = out_layer.compute_loss(y, out, from_logits=wl,
                                          mask=lmask)
            return loss, ns
        return fn

    def _layer(self, entry):
        return self.model.conf.layers[int(entry.split("_")[1])]

    def updater_for(self, entry):
        return self._layer(entry).updater or self.model.conf.updater

    def gn_threshold(self):
        c = self.model.conf
        return (c.gradient_normalization,
                c.gradient_normalization_threshold)

    def constrain(self, entry, new_p):
        from deeplearning4j_tpu.nn.conf.constraints import \
            apply_constraints
        return apply_constraints(self._layer(entry), new_p)

    def has_regularization(self, names) -> bool:
        return any(getattr(self._layer(n), "l1", 0.0) or
                   getattr(self._layer(n), "l2", 0.0) for n in names)

    def microbatch_views(self, ds, n_micro: int):
        model = self.model
        dt = getattr(model, "_dtype", jnp.float32)
        x, y = ds.features, ds.labels
        fm = getattr(ds, "features_mask", None)
        lm = getattr(ds, "labels_mask", None)
        xm = to_microbatches(jnp.asarray(x, dt), n_micro)
        ym = to_microbatches(jnp.asarray(y, dt), n_micro)
        fmm = (to_microbatches(jnp.asarray(fm), n_micro)
               if fm is not None else None)
        lmm = (to_microbatches(jnp.asarray(lm), n_micro)
               if lm is not None else None)
        return _MicroViews(
            batch_size=int(x.shape[0]),
            inject=lambda m: xm[m],
            labels=lambda m: ym[m],
            lmask=(lambda m: lmm[m]) if lmm is not None else None,
            fmask=(lambda m: fmm[m]) if fmm is not None else None)


class _GraphStages:
    def __init__(self, model):
        self.model = model
        self.part = None
        self.topo = list(model._topo)
        self.out_confs = model.output_layer_confs()

    def entries(self):
        return list(self.topo)

    def finalize(self):
        """Handoff sets per stage boundary: an activation produced at
        stage ``p`` and consumed at stage ``c > p`` (or by the loss)
        rides every boundary in between — including network inputs
        consumed past stage 0, which flow through like any other
        activation (honest wire accounting)."""
        conf = self.model.conf
        part = self.part
        s_n = part.n_stages
        slice_of = {}
        for s in range(s_n):
            for nm in part.stage_entries(s):
                slice_of[nm] = s
        for inp in conf.network_inputs:
            slice_of.setdefault(inp, 0)
        need = [set() for _ in range(s_n + 1)]

        def consume(name, s):
            ss = slice_of.get(name)
            if ss is None or ss >= s:
                return
            for t in range(ss + 1, s + 1):
                need[t].add(name)

        for s in range(s_n):
            for nm in part.stage_entries(s):
                for src in conf.vertices[nm].inputs:
                    consume(src, s)
        for out in conf.network_outputs:
            consume(out, s_n - 1)
        self.incoming = [sorted(need[s]) for s in range(s_n)]
        self.outgoing = [sorted(need[s + 1]) for s in range(s_n)]

    def fwd_fn(self, s: int):
        lo = self.part.boundaries[s]
        hi = self.part.boundaries[s + 1]
        model = self.model
        outs = self.outgoing[s]
        first = s == 0

        def fwd(stage_params, states, h, fmask, rng):
            acts, ns = model._forward(
                stage_params, states, h if first else [],
                training=True, rng=rng, want_logits=False,
                fmask=fmask, start_acts=None if first else h,
                topo_slice=(lo, hi))
            return {n: acts[n] for n in outs}, ns
        return fwd

    def loss_fn(self, s: int):
        lo = self.part.boundaries[s]
        hi = self.part.boundaries[s + 1]
        model, out_confs = self.model, self.out_confs
        conf = model.conf
        first = s == 0

        def fn(stage_params, states, h, labels, lmasks, fmask, rng):
            acts, ns = model._forward(
                stage_params, states, h if first else [],
                training=True, rng=rng, want_logits=True,
                fmask=fmask, start_acts=None if first else h,
                topo_slice=(lo, hi))
            loss = jnp.zeros((), jnp.float32)
            for i, out_name in enumerate(conf.network_outputs):
                layer = out_confs.get(out_name)
                if layer is None:
                    continue
                loss = loss + layer.compute_loss(
                    labels[i], acts[out_name],
                    from_logits=layer.wants_logits(),
                    mask=lmasks[i] if lmasks is not None else None)
            return loss, ns
        return fn

    def updater_for(self, entry):
        v = self.model.conf.vertices[entry]
        if v.is_layer and v.content.updater:
            return v.content.updater
        return self.model.conf.updater

    def gn_threshold(self):
        c = self.model.conf
        return (c.gradient_normalization,
                c.gradient_normalization_threshold)

    def constrain(self, entry, new_p):
        v = self.model.conf.vertices[entry]
        if not v.is_layer:
            return new_p
        from deeplearning4j_tpu.nn.conf.constraints import \
            apply_constraints
        return apply_constraints(v.content, new_p)

    def has_regularization(self, names) -> bool:
        for n in names:
            v = self.model.conf.vertices[n]
            if v.is_layer and (getattr(v.content, "l1", 0.0) or
                               getattr(v.content, "l2", 0.0)):
                return True
        return False

    def microbatch_views(self, ds, n_micro: int):
        model = self.model
        dt = getattr(model, "_dtype", jnp.float32)
        feats, labels = ds.features, ds.labels
        fl = list(feats) if isinstance(feats, (list, tuple)) else [feats]
        ll = list(labels) if isinstance(labels, (list, tuple)) else [labels]
        lm = getattr(ds, "labels_mask", None)
        fm = getattr(ds, "features_mask", None)
        fm0 = fm[0] if isinstance(fm, (list, tuple)) else fm
        lml = ((list(lm) if isinstance(lm, (list, tuple)) else [lm])
               if lm is not None else None)
        xm = [to_microbatches(jnp.asarray(a, dt), n_micro) for a in fl]
        ym = [to_microbatches(jnp.asarray(a, dt), n_micro) for a in ll]
        lmm = ([to_microbatches(jnp.asarray(a), n_micro)
                if a is not None else None for a in lml]
               if lml is not None else None)
        fmm = (to_microbatches(jnp.asarray(fm0), n_micro)
               if fm0 is not None else None)
        return _MicroViews(
            batch_size=int(fl[0].shape[0]),
            inject=lambda m: [a[m] for a in xm],
            labels=lambda m: [a[m] for a in ym],
            lmask=((lambda m: [a[m] if a is not None else None
                               for a in lmm])
                   if lmm is not None else None),
            fmask=(lambda m: fmm[m]) if fmm is not None else None)


class _MicroViews:
    """Per-microbatch accessors for one training batch."""

    def __init__(self, batch_size, inject, labels, lmask, fmask):
        self.batch_size = batch_size
        self.inject = inject
        self.labels = labels
        self.lmask = lmask
        self.fmask = fmask


def make_stage_adapter(model):
    """The stage adapter for a model — MLN layer stacks and graph
    topologies are the supported pipeline substrates."""
    if hasattr(model, "_topo"):
        return _GraphStages(model)
    if hasattr(model, "conf") and hasattr(model.conf, "layers"):
        return _MlnStages(model)
    raise ValueError(
        f"pipeline_stages: unsupported model type "
        f"{type(model).__name__} (need MultiLayerNetwork or "
        f"ComputationGraph)")


class PipelineTrainer:
    """Host-level stage executor: the promoted pipeline fit path.

    Walks the explicit tick table from :func:`build_schedule`, running
    each stage's forward/backward as its own jit on that stage's
    ``(data[, model])`` submesh of a 3D mesh, handing activations (and
    backward cotangents) across the ``pipe`` boundary with
    ``jax.device_put`` — the accounted pipe-axis wire traffic. Backward
    ops re-run their stage forward under ``jax.vjp`` inside the jit, so
    the only per-(stage, microbatch) residency is the stage *input*
    stash — exactly what :func:`peak_residency` bounds.

    Each stage applies its own update tail (``zero.apply_update``,
    dense or per-stage ZeRO-1, with the tp split when stage specs
    exist), so updater flats stay
    local to the stage's pipe group (``parallel/zero.py``). Microbatch
    grads are summed and scaled by ``1/M`` — with mean losses this is
    bit-for-tolerance the full-batch gradient, which is what makes the
    pp trajectory match the dp-only dense one (tests/test_pipeline.py).
    """

    def __init__(self, model, mesh, *, n_micro=None, schedule="1f1b",
                 mode="dense", pipe_axis=PIPE_AXIS, data_axis=None,
                 model_axis=None):
        from .mesh import DEFAULT_DATA_AXIS, DEFAULT_MODEL_AXIS
        if schedule not in SCHEDULES:
            raise ValueError(f"unknown pipeline schedule {schedule!r} "
                             f"(know {SCHEDULES})")
        self.model = model
        self.mesh = mesh
        self.pipe_axis = pipe_axis
        self.data_axis = data_axis or DEFAULT_DATA_AXIS
        self.model_axis = model_axis or DEFAULT_MODEL_AXIS
        self.n_stages = int(dict(mesh.shape).get(pipe_axis, 1))
        if self.n_stages < 2:
            raise ValueError(
                f"pipeline training needs a {pipe_axis!r} mesh axis of "
                f">= 2 stages, got {self.n_stages}")
        self.schedule = schedule
        self.n_micro = int(n_micro) if n_micro else 2 * self.n_stages
        if self.n_micro < 1:
            raise ValueError(f"n_micro must be >= 1, got {self.n_micro}")
        mode_s = str(getattr(mode, "value", mode) or "dense").lower()
        if mode_s == "auto":
            mode_s = "sharded"
        if mode_s == "fsdp":
            # fsdp param residency needs whole-model gather scheduling;
            # per-stage ZeRO-1 already keeps every updater flat local
            # to its stage's pipe group, which is the locality the 3D
            # design asks of zero.py — params stay dense per stage.
            log.info("pipeline x fsdp: downgrading the update tail to "
                     "per-stage ZeRO-1 (updater flats local to each "
                     "stage's pipe group; stage params stay dense)")
            mode_s = "sharded"
        self.mode = mode_s
        self.dp = int(dict(mesh.shape).get(self.data_axis, 1))
        self.tp = int(dict(mesh.shape).get(self.model_axis, 1))
        self._tail = "sharded" if (mode_s == "sharded" and
                                   self.dp > 1) else "dense"
        if not model._initialized:
            model.init()
        self.adapter = make_stage_adapter(model)
        self._sched = build_schedule(self.n_stages, self.n_micro,
                                     schedule)
        self.part = None
        self.submeshes = None
        self._placed = False
        self._jits = None
        self.last_report = None

    # -- placement ----------------------------------------------------
    def place(self):
        """Partition the (densified) model over the stages and place
        each stage's params/states/updater-state on its submesh."""
        from .mesh import replicate_tree
        from .speclayout import SpecLayout
        from deeplearning4j_tpu.parallel import zero
        m = self.model
        if hasattr(m, "set_dp_mesh"):
            # densify any prior sharded/fsdp layout and invalidate the
            # model's own compiled steps — the trainer owns this fit
            m.set_dp_mesh(None, self.data_axis)
        if hasattr(m, "_sync_updater_layout"):
            m._sync_updater_layout()
        self.part = StagePartition.build(self.adapter.entries(),
                                         m.params, self.n_stages)
        self.adapter.part = self.part
        self.adapter.finalize()
        self.submeshes = [stage_submesh(self.mesh, s, self.pipe_axis)
                          for s in range(self.n_stages)]
        if self.tp > 1:
            layout = SpecLayout(self.mesh, model_axis=self.model_axis,
                                data_axis=self.data_axis,
                                stage_axis=self.pipe_axis)
            self._tp_specs = layout.infer_stages(m.params, self.part,
                                                 shard_over_data=False)
        else:
            self._tp_specs = [{} for _ in range(self.n_stages)]
        for s in range(self.n_stages):
            sub = self.submeshes[s]
            names = self.part.stage_entries(s)
            sp = {k: m.params[k] for k in names if k in m.params}
            specs = self._tp_specs[s]
            if specs:
                sp = zero.place_tp_params(sub, sp, specs)
            else:
                sp = replicate_tree(sub, sp)
            m.params.update(sp)
            st = {k: m.states[k] for k in names if k in m.states}
            m.states.update(replicate_tree(sub, st))
            us = {k: m.updater_states[k] for k in names
                  if k in m.updater_states}
            us = zero.states_to_dense(sp, us)
            if self._tail == "sharded":
                us = zero.states_to_sharded(sp, us, self.dp,
                                            tp_specs=specs or None)
                us = zero.place_updater_states(sub, us, self.data_axis,
                                               tp_specs=specs or None)
            else:
                us = replicate_tree(sub, us)
            m.updater_states.update(us)
        self._jits = None
        self._placed = True

    # -- jit construction ---------------------------------------------
    def _make_pin(self, s: int):
        specs = self._tp_specs[s]
        if not specs:
            return lambda p: p
        from deeplearning4j_tpu.parallel import zero
        sub = self.submeshes[s]
        return lambda params: zero.pin_tp_params(params, sub, specs)

    def _make_apply(self, s: int):
        from deeplearning4j_tpu.parallel import zero
        ad = self.adapter
        names = list(self.part.stage_entries(s))
        ups = {k: ad.updater_for(k) for k in names}
        normalization = ad.gn_threshold()
        sub = self.submeshes[s]
        specs_all = self._tp_specs[s]
        mode = zero.UpdateExchange(self._tail)
        model = self.model
        data_axis = self.data_axis
        has_reg = ad.has_regularization(names)

        def apply_fn(stage_params, upd_states, gsum, scale, iteration):
            g_all = jax.tree_util.tree_map(lambda a: a * scale, gsum)
            reg = jnp.zeros((), jnp.float32)
            if has_reg:
                # regularization is per-batch, not per-microbatch: its
                # grad rides the apply step once, like the dense path
                reg_val, rg = jax.value_and_grad(
                    model._regularization)(stage_params)
                reg = jnp.asarray(reg_val, jnp.float32)
                g_all = _tree_add(g_all, rg)
            new_params, new_upd = {}, {}
            for k in names:
                g = g_all.get(k, {})
                p = stage_params.get(k, {})
                if not g or not p:
                    new_params[k] = p
                    new_upd[k] = upd_states.get(k, ())
                    continue
                new_p, new_upd[k] = zero.apply_update(
                    ups[k], g, p, upd_states[k], iteration, mesh=sub,
                    axis=data_axis, mode=mode,
                    tp_specs=specs_all.get(k), normalization=normalization)
                new_params[k] = ad.constrain(k, new_p)
            return new_params, new_upd, reg
        return jax.jit(apply_fn)

    def _build(self):
        s_n = self.n_stages
        ad = self.adapter
        pins = [self._make_pin(s) for s in range(s_n)]
        self._fwd_jit, self._bwd_jit = [], []
        for s in range(s_n - 1):
            fwd = ad.fwd_fn(s)
            pin = pins[s]

            def make_f(fwd=fwd, pin=pin):
                def f(stage_params, states, h, fmask, rng):
                    return fwd(pin(stage_params), states, h, fmask, rng)
                return jax.jit(f)

            def make_b(fwd=fwd, pin=pin):
                def b(stage_params, states, h, g_out, fmask, rng):
                    def core(p, hh):
                        out, _ = fwd(pin(p), states, hh, fmask, rng)
                        return out
                    _, vjp = jax.vjp(core, stage_params, h)
                    gp, gh = vjp(g_out)
                    return gp, gh
                return jax.jit(b)

            self._fwd_jit.append(make_f())
            self._bwd_jit.append(make_b())
        loss_fn = ad.loss_fn(s_n - 1)
        pin = pins[s_n - 1]

        def last(stage_params, states, h, y, lmask, fmask, rng):
            def core(p, hh):
                return loss_fn(pin(p), states, hh, y, lmask, fmask,
                               rng)
            (loss, ns), (gp, gh) = jax.value_and_grad(
                core, argnums=(0, 1), has_aux=True)(stage_params, h)
            return loss, ns, gp, gh
        self._last_jit = jax.jit(last)
        self._apply_jit = [self._make_apply(s) for s in range(s_n)]
        self._jits = True

    # -- execution ----------------------------------------------------
    def _put(self, s: int, tree):
        """Place a microbatch payload on stage ``s``'s submesh, sharded
        over the data axis (the pipe-boundary handoff)."""
        from .mesh import data_sharding
        sub = self.submeshes[s]

        def put_one(a):
            if not hasattr(a, "ndim") or a.ndim == 0:
                return a
            return jax.device_put(
                a, data_sharding(sub, a.ndim, self.data_axis))
        return jax.tree_util.tree_map(put_one, tree)

    def fit_batch(self, ds):
        """One training step over ``ds`` — schedule-driven microbatch
        pipeline, per-stage apply, model bookkeeping to match
        ``_fit_batch`` (score, iteration count, listeners, telemetry,
        step-breakdown ``pipeline`` phase)."""
        from deeplearning4j_tpu.common import diagnostics, stepstats
        from deeplearning4j_tpu.common import telemetry
        m = self.model
        if not self._placed:
            self.place()
        if self._jits is None:
            self._build()
        s_n, m_n = self.n_stages, self.n_micro
        views = self.adapter.microbatch_views(ds, m_n)
        mb = views.batch_size // m_n
        if self.dp > 1 and mb % self.dp:
            raise ValueError(
                f"microbatch of {mb} rows not divisible by {self.dp} "
                f"data-parallel shards; pick n_micro/batch so that "
                f"batch/n_micro is a multiple of dp")
        with telemetry.step_span(type(m).__name__) as sp:
            report = self._run_schedule(views)
            loss = report.pop("_loss")
            new_states = report.pop("_states")
            stepstats.collector().note_in_step(
                "pipeline", report["bubble_seconds"])
            if telemetry.enabled():
                telemetry.histogram(
                    "dl4j_pipeline_bubble_seconds",
                    "measured per-step pipeline bubble (sum of stage "
                    "idle time while peers compute)").observe(
                    report["bubble_seconds"], schedule=self.schedule,
                    stages=str(s_n))
                h = telemetry.histogram(
                    "dl4j_pipeline_stage_seconds",
                    "per-stage busy seconds inside one pipeline step")
                for s in range(s_n):
                    h.observe(report["stage_busy_seconds"][s],
                              stage=str(s))
            m.states.update(new_states)
            if hasattr(m, "_strip_rnn_states"):
                m.states = m._strip_rnn_states(m.states)
            m._score = loss
            m.last_batch_size = views.batch_size
            self.last_report = report
            diagnostics.record_step(m, type(m).__name__,
                                    m.iteration_count, loss, sp)
        m.iteration_count += 1
        for lis in getattr(m, "listeners", []) or []:
            lis.iteration_done(m, m.iteration_count - 1,
                               getattr(m, "epoch_count", 0))
        return loss

    def _run_schedule(self, views):
        m = self.model
        s_n, m_n = self.n_stages, self.n_micro
        part = self.part
        sp = []
        st = []
        for s in range(s_n):
            names = part.stage_entries(s)
            sp.append({k: m.params[k] for k in names if k in m.params})
            st.append({k: m.states[k] for k in names if k in m.states})
        rng = None
        if hasattr(m, "_rng"):
            m._rng, rng = jax.random.split(m._rng)
        else:
            rng = jax.random.PRNGKey(0)
        # the SAME per-microbatch key feeds forward and recompute-
        # backward of every stage — remat needs identical dropout masks
        rngs = [jax.random.fold_in(rng, mi) for mi in range(m_n)]
        inject = [self._put(0, views.inject(mi)) for mi in range(m_n)]
        y_put = [self._put(s_n - 1, views.labels(mi))
                 for mi in range(m_n)]
        lm_put = ([self._put(s_n - 1, views.lmask(mi))
                   for mi in range(m_n)]
                  if views.lmask is not None else [None] * m_n)
        fmask_put = None
        if views.fmask is not None:
            fmask_put = {(s, mi): self._put(s, views.fmask(mi))
                         for s in range(s_n) for mi in range(m_n)}

        def fm(s, mi):
            return fmask_put[(s, mi)] if fmask_put is not None else None

        h_store, h_next, g_next = {}, {}, {}
        stash_bytes = {}
        live = [0] * s_n
        live_b = [0] * s_n
        peak = [0] * s_n
        peak_b = [0] * s_n
        grads = [None] * s_n
        ns_by_stage = {}
        losses = []
        wire_fwd = 0
        wire_bwd = 0
        tick_durs = []
        for ops in self._sched:
            durs = [0.0] * s_n
            for s, op in enumerate(ops):
                if op is None:
                    continue
                kind, mi = op
                t0 = time.perf_counter()
                if kind == "F":
                    h_in = inject[mi] if s == 0 else h_next.pop((s, mi))
                    h_store[(s, mi)] = h_in
                    stash_bytes[(s, mi)] = _tree_bytes(h_in)
                    live[s] += 1
                    live_b[s] += stash_bytes[(s, mi)]
                    peak[s] = max(peak[s], live[s])
                    peak_b[s] = max(peak_b[s], live_b[s])
                    if s < s_n - 1:
                        h_out, ns = self._fwd_jit[s](
                            sp[s], st[s], h_in, fm(s, mi), rngs[mi])
                        jax.block_until_ready(h_out)
                        ns_by_stage[s] = ns
                        wire_fwd += _tree_bytes(h_out)
                        h_next[(s + 1, mi)] = self._put(s + 1, h_out)
                    # last stage: forward is fused into its backward
                    # (remat) — the F op only stashes the handoff
                else:
                    h_in = h_store.pop((s, mi))
                    if s == s_n - 1:
                        loss, ns, gp, gh = self._last_jit(
                            sp[s], st[s], h_in, y_put[mi], lm_put[mi],
                            fm(s, mi), rngs[mi])
                        losses.append(loss)
                    else:
                        gp, gh = self._bwd_jit[s](
                            sp[s], st[s], h_in, g_next.pop((s, mi)),
                            fm(s, mi), rngs[mi])
                        ns = None
                    jax.block_until_ready(gp)
                    live[s] -= 1
                    live_b[s] -= stash_bytes.pop((s, mi))
                    grads[s] = gp if grads[s] is None else \
                        _tree_add(grads[s], gp)
                    if s > 0:
                        wire_bwd += _tree_bytes(gh)
                        g_next[(s - 1, mi)] = self._put(s - 1, gh)
                    if ns is not None:
                        ns_by_stage[s] = ns
                durs[s] = time.perf_counter() - t0
            tick_durs.append(durs)
        # apply: one update per batch per stage, like the dense step
        it = jnp.asarray(m.iteration_count)
        scale = jnp.asarray(1.0 / m_n, jnp.float32)
        reg_total = 0.0
        new_states = {}
        for s in range(s_n):
            names = part.stage_entries(s)
            us = {k: m.updater_states.get(k, ()) for k in names}
            new_p, new_u, reg = self._apply_jit[s](
                sp[s], us, grads[s], scale, it)
            m.params.update(new_p)
            m.updater_states.update(new_u)
            reg_total += float(reg)
            if s in ns_by_stage:
                new_states.update(ns_by_stage[s])
        data_loss = sum(float(l) for l in losses) / m_n
        loss = data_loss + reg_total
        stage_busy = [sum(d[s] for d in tick_durs) for s in range(s_n)]
        stage_idle = [sum(max(d) - d[s] for d in tick_durs)
                      for s in range(s_n)]
        return {
            "_loss": loss,
            "_states": new_states,
            "schedule": self.schedule,
            "n_stages": s_n,
            "n_micro": m_n,
            "bubble_fraction": bubble_fraction(s_n, m_n),
            "bubble_seconds": sum(stage_idle),
            "stage_busy_seconds": stage_busy,
            "stage_idle_seconds": stage_idle,
            "peak_residency_microbatches": peak,
            "peak_residency_bytes": peak_b,
            "pipe_wire_fwd_bytes": wire_fwd,
            "pipe_wire_bwd_bytes": wire_bwd,
            "pipe_wire_bytes": wire_fwd + wire_bwd,
            "stage_param_bytes": part.stage_param_bytes(m.params),
        }
