"""Flagship distributed Transformer LM — every parallelism strategy in
one jitted train step.

The reference's only distribution story is whole-model data parallelism
(SURVEY.md §2.6 P1–P4: `ParallelWrapper` replicas + gradient sharing
over Aeron; P7–P10 ABSENT). This model is the TPU-native superset: one
``jax.sharding.Mesh`` with axes

- ``data``  — DP: batch sharded; non-expert gradients psum over ICI.
              Also hosts **EP**: MoE expert weights are sharded over
              ``data`` (DeepSpeed-style — expert params replace DP
              replication) and tokens reach their experts via two
              ``all_to_all``s.
- ``pipe``  — PP: contiguous stages of transformer blocks; GPipe
              microbatch schedule via ``lax.ppermute`` + ``lax.scan``
              (:mod:`..parallel.pipeline`), differentiable end-to-end.
- ``model`` — TP: megatron column/row sharding of QKV/out-proj and
              MLP up/down (:mod:`..parallel.tensor`), vocab-parallel
              embedding + cross-entropy. Also hosts **SP** in megatron
              form: norm/residual regions keep activations
              time-sharded over ``model`` (all_gather in,
              reduce_scatter out of each TP region).
- ``seq``   — optional dedicated CP axis: activations time-sharded,
              attention via ring attention (:mod:`..parallel.sequence`,
              K/V blocks rotating over ICI). When present it replaces
              the megatron-SP layout.

The whole step — fwd, bwd, gradient reduction, updater — is ONE
``shard_map`` over the mesh inside ONE ``jax.jit``, so XLA compiles a
single SPMD program with all collectives visible to its scheduler
(overlap with compute), exactly the design SURVEY.md §7 prescribes.

Gradient reduction rule: a parameter leaf's gradient is psum'd over
every mesh axis that does NOT appear in its PartitionSpec, except
``model`` (TP weight grads are complete locally via collective
transposes, and model-replicated leaves compute identical grads on
every TP rank). Expert weights (sharded over ``data``) are complete
via the all_to_all transpose; stage-stacked leaves (sharded over
``pipe``) are local to their stage.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..learning.updaters import IUpdater, Sgd
from ..parallel.expert import init_moe_params, moe_ffn
from ..parallel.pipeline import (from_microbatches, pipeline_apply,
                                 to_microbatches)
from ..parallel.mesh import shard_map as _shard_map
from ..parallel.sequence import ring_attention
from ..parallel.tensor import (init_tp_block_params, layer_norm,
                               row_parallel_dense, sp_all_gather,
                               tp_mlp, tp_self_attention)


@dataclass
class TransformerLMConfig:
    vocab_size: int = 256
    max_len: int = 128
    d_model: int = 64
    n_heads: int = 4
    d_ff: int = 128
    layers_per_stage: int = 2
    n_experts: int = 0          # 0 = dense MLP everywhere (no MoE)
    moe_top_k: int = 2
    moe_capacity: Optional[int] = None   # None = capacity_factor rule
    moe_capacity_factor: float = 1.25
    aux_coef: float = 0.01
    dtype: object = jnp.float32


class DistributedTransformerLM:
    """dp/pp/tp/sp/ep-sharded causal LM with a single-jit train step.

    ``mesh`` must have axes ``data``, ``pipe``, ``model``; an optional
    ``seq`` axis (size>1) switches sequence handling from megatron-SP
    (time sharded over ``model``) to ring-attention CP (time sharded
    over ``seq``).
    """

    def __init__(self, conf: TransformerLMConfig, mesh,
                 updater: Optional[IUpdater] = None, n_micro: int = 4):
        self.conf = conf
        self.mesh = mesh
        self.updater = updater if updater is not None else Sgd(0.1)
        self.n_micro = n_micro
        ax = dict(zip(mesh.axis_names, mesh.devices.shape))
        for need in ("data", "pipe", "model"):
            if need not in ax:
                raise ValueError(f"mesh needs axis '{need}', has {ax}")
        self.dp = ax["data"]
        self.pp = ax["pipe"]
        self.tp = ax["model"]
        self.sp = ax.get("seq", 1)
        self.ring = self.sp > 1
        if conf.n_heads % self.tp:
            raise ValueError("n_heads must divide by tp")
        if conf.n_experts and conf.n_experts % self.dp:
            raise ValueError("n_experts must divide by dp (EP axis)")
        self._step = None

    # -- parameter structure ------------------------------------------
    def _moe_layer(self, l: int) -> bool:
        """Static MoE placement: last block of every stage is MoE."""
        return (self.conf.n_experts > 0
                and l == self.conf.layers_per_stage - 1)

    def init_global_params(self, seed: int = 0):
        """Full (unsharded) parameter pytree; stage-stacked leaves get
        a leading [n_stages] dim. Same math as the sharded runtime —
        shards are slices of these arrays."""
        c = self.conf
        key = jax.random.PRNGKey(seed)
        k_emb, k_pos, k_head, k_blk = jax.random.split(key, 4)
        stages = []
        for l in range(c.layers_per_stage):
            per_stage = []
            for s in range(self.pp):
                bk = jax.random.fold_in(k_blk,
                                        s * c.layers_per_stage + l)
                p = init_tp_block_params(bk, c.d_model, c.n_heads,
                                         c.d_ff, tp=1, tp_rank=0,
                                         dtype=c.dtype)
                if self._moe_layer(l):
                    del p["mlp"]
                    p["moe"] = init_moe_params(
                        jax.random.fold_in(bk, 7), c.d_model, c.d_ff,
                        c.n_experts, ep=1, ep_rank=0, dtype=c.dtype)
                per_stage.append(p)
            stages.append(jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *per_stage))
        return {
            "embed": jax.random.normal(
                k_emb, (c.vocab_size, c.d_model), c.dtype) * 0.02,
            "pos": jax.random.normal(
                k_pos, (c.max_len, c.d_model), c.dtype) * 0.02,
            "stages": stages,
            "ln_f_g": jnp.ones((c.d_model,), c.dtype),
            "ln_f_b": jnp.zeros((c.d_model,), c.dtype),
            "head": jax.random.normal(
                k_head, (c.d_model, c.vocab_size), c.dtype)
            * (c.d_model ** -0.5),
        }

    def param_specs(self):
        # The 5D flagship STACKS per-stage blocks on a leading stage
        # dimension and shards that dimension over `pipe` — the one
        # deliberate exception to the "pipe never appears in a
        # PartitionSpec" invariant (the 1F1B fit path keeps stages as
        # stage-local arrays instead; see parallel/speclayout.py).
        # dl4j-lint: disable-file=spec-invariants
        col = P("pipe", None, "model")
        row = P("pipe", "model", None)
        rep = P("pipe", None)
        blk = {
            "ln1_g": rep, "ln1_b": rep, "ln2_g": rep, "ln2_b": rep,
            "attn": {"Wq": col, "Wk": col, "Wv": col, "Wo": row,
                     "bo": rep},
        }
        dense = dict(blk)
        dense["mlp"] = {"Wi": col, "bi": P("pipe", "model"),
                        "Wo": row, "bo": rep}
        moe = dict(blk)
        moe["moe"] = {"Wg": P("pipe", None, None),
                      "Wi": P("pipe", "data", None, None),
                      "Wo": P("pipe", "data", None, None)}
        stages = [moe if self._moe_layer(l) else dense
                  for l in range(self.conf.layers_per_stage)]
        return {
            "embed": P("model", None),     # vocab-parallel rows
            "pos": P(),
            "stages": stages,
            "ln_f_g": P(), "ln_f_b": P(),
            "head": P(None, "model"),      # column-parallel
        }

    def init(self, seed: int = 0):
        """(params, opt_state) placed on the mesh with their specs."""
        params = self.init_global_params(seed)
        opt_state = self.updater.init_state(params)
        specs = self.param_specs()
        ospecs = _state_specs(opt_state, specs)
        put = lambda tree, sp: _zip_map(
            lambda a, s: jax.device_put(a, NamedSharding(self.mesh, s)),
            tree, sp)
        return put(params, specs), put(opt_state, ospecs)

    # -- sharded math (inside shard_map) ------------------------------
    def _embed(self, p, ids):
        """Vocab-parallel embedding + positions. Returns the
        time-LOCAL activation [b, t_local, d]."""
        table = p["embed"]                  # [V/tp, d] local
        vl = table.shape[0]
        rank = lax.axis_index("model")
        loc = ids - rank * vl
        ok = (loc >= 0) & (loc < vl)
        emb = jnp.take(table, jnp.clip(loc, 0, vl - 1), axis=0)
        emb = jnp.where(ok[..., None], emb, 0.0)   # partial per rank
        t = ids.shape[1]
        if self.ring:
            emb = lax.psum(emb, "model")
            off = lax.axis_index("seq") * t
            return emb + lax.dynamic_slice_in_dim(p["pos"], off, t, 0)
        # megatron-SP: reduce the vocab-partial sums AND scatter time
        # over the model axis in one collective. (The transpose is an
        # all_gather of the cotangent, which keeps the vocab-sharded
        # table's gradients local-complete.)
        emb = lax.psum_scatter(emb, "model", scatter_dimension=1,
                               tiled=True)         # [b, t/tp, d]
        tl = t // self.tp
        off = lax.axis_index("model") * tl
        return emb + lax.dynamic_slice_in_dim(p["pos"], off, tl, 0)

    def _attention(self, h, ap, n_heads_local):
        if not self.ring:
            t = h.shape[1] * self.tp        # global length
            mask = jnp.tril(jnp.ones((t, t), bool))[None, None]
            return tp_self_attention(h, ap, n_heads_local,
                                     mask=mask, sequence_parallel=True)
        b, tl, _ = h.shape
        dh = ap["Wq"].shape[-1] // n_heads_local
        hd = lambda a: a.reshape(b, tl, n_heads_local, dh) \
            .transpose(0, 2, 1, 3)
        # use_flash: per-shard Pallas kernels + exact lse merge —
        # measured 320x over the differentiated blockwise ring for a
        # causal seq-8192 train step on v5e (BENCH_notes_r04.md); on
        # CPU backends it runs the exact dense-with-lse reference
        o = ring_attention(hd(h @ ap["Wq"]), hd(h @ ap["Wk"]),
                           hd(h @ ap["Wv"]), "seq", causal=True,
                           use_flash=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, tl, n_heads_local * dh)
        return row_parallel_dense(o, ap["Wo"], ap["bo"], "model")

    def _block(self, p, x, n_heads_local):
        """One transformer block on the local activation layout.
        Returns (x, aux)."""
        c = self.conf
        h = layer_norm(x, p["ln1_g"], p["ln1_b"])
        x = x + self._attention(h, p["attn"], n_heads_local)
        h = layer_norm(x, p["ln2_g"], p["ln2_b"])
        if "moe" in p:
            # each rank routes its LOCAL tokens (time-sharded under
            # megatron-SP, seq-sharded under ring); EP all_to_all over
            # `data`. Expert grads: complete over data (a2a
            # transpose), partial over the time-sharding axis — the
            # reduction rule psums them there.
            y, aux = moe_ffn(h, p["moe"], axis="data",
                             k=c.moe_top_k, capacity=c.moe_capacity,
                             capacity_factor=c.moe_capacity_factor)
            if not self.ring:
                # make the loss (hence every rank's cotangent scale)
                # identical across model ranks
                aux = lax.pmean(aux, "model")
            return x + y, aux
        return (x + tp_mlp(h, p["mlp"], "model",
                           sequence_parallel=not self.ring),
                jnp.zeros((), x.dtype))

    def _loss_local(self, params, ids, labels):
        """Scalar loss (replicated across the mesh) from local shards.
        ids/labels: [b_local, t_local] int32."""
        c = self.conf
        hl = c.n_heads // self.tp
        x = self._embed(params, ids)
        xm = to_microbatches(x, self.n_micro)

        def stage_fn(stage_params, xx):
            aux_t = jnp.zeros((), xx.dtype)
            for l in range(c.layers_per_stage):
                bp = jax.tree_util.tree_map(lambda a: a[0],
                                            stage_params[l])
                xx, aux = self._block(bp, xx, hl)
                aux_t = aux_t + aux
            return xx, aux_t

        outs, aux_sum = pipeline_apply(
            stage_fn, params["stages"], xm, with_aux=True,
            varying_axes=tuple(self.mesh.axis_names))
        h = from_microbatches(outs)            # [b_local, t_local, d]
        h = layer_norm(h, params["ln_f_g"], params["ln_f_b"])
        if not self.ring:
            h = sp_all_gather(h, "model")      # [b_local, t, d]
        logits = h @ params["head"]            # [.., t, V/tp] local
        ce = _vocab_parallel_xent(logits, labels)
        ce_mean = jnp.mean(ce)

        stage = lax.axis_index("pipe")
        last = (stage == self.pp - 1).astype(ce_mean.dtype)
        local = ce_mean * last + c.aux_coef * aux_sum / self.n_micro
        loss = lax.psum(local, "pipe")
        loss = lax.pmean(loss, "data")
        if self.ring:
            loss = lax.pmean(loss, "seq")
        return loss

    # -- public API ----------------------------------------------------
    def data_specs(self):
        if self.ring:
            return P("data", "seq")
        return P("data", None)

    def build_train_step(self):
        specs = self.param_specs()
        # opt-state specs mirror param specs leaf-for-leaf
        ospecs = _state_specs(
            jax.eval_shape(self.updater.init_state,
                           jax.eval_shape(self.init_global_params)),
            specs)
        dsp = self.data_specs()

        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

        def objective(params, ids, labels):
            loss = self._loss_local(params, ids, labels)
            # the scalar is numerically replicated over its remaining
            # varying axes (e.g. `model`: every TP rank stitches the
            # same CE). Autodiff sums all rank-copies through the
            # collective transposes, so each rank must contribute
            # loss/n_copies for the grads to come out exactly dL/dθ
            # (verified leaf-for-leaf in test_transformer_5d): the
            # copy count is the product of the loss's varying axes.
            scale = int(np.prod([sizes.get(a, 1)
                                 for a in jax.typeof(loss).vma])) or 1
            return loss / scale, loss

        def body(params, opt_state, ids, labels, it):
            # no manual gradient reduction: under VMA-typed shard_map
            # every implicit unvarying->varying cast in the forward (a
            # replicated param meeting a data/seq/time-sharded
            # activation) transposes to a psum over exactly the right
            # axes, so the grads arrive complete — verified
            # leaf-for-leaf against a single-device reference in
            # test_transformer_5d
            grads, loss = jax.grad(objective, has_aux=True)(
                params, ids, labels)
            upd, new_state = self.updater.apply(grads, opt_state, it)
            new_params = jax.tree_util.tree_map(
                lambda p_, u: p_ - u, params, upd)
            return new_params, new_state, _unvary(loss, self.mesh)

        fn = _shard_map(body, self.mesh,
                        in_specs=(specs, ospecs, dsp, dsp, P()),
                        out_specs=(specs, ospecs, P()))
        self._step = jax.jit(fn, donate_argnums=(0, 1))
        return self._step

    def train_step(self, params, opt_state, ids, labels, it=0):
        if self._step is None:
            self.build_train_step()
        it = jnp.asarray(it, jnp.int32)
        return self._step(params, opt_state, ids, labels, it)


def _axsize(mesh, ax):
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(ax, 1)


def _zip_map(f, tree, specs):
    """tree_map over (array-tree, spec-tree) that treats PartitionSpec
    entries as leaves regardless of their pytree registration."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    s_flat = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    assert len(flat) == len(s_flat), (len(flat), len(s_flat))
    return jax.tree_util.tree_unflatten(
        treedef, [f(a, s) for a, s in zip(flat, s_flat)])


def _state_specs(state, specs):
    """Opt-state spec tree: every state leaf mirrors its param leaf
    (updater states are {name: param-shaped tree} maps, or ())."""
    if isinstance(state, tuple) and state == ():
        return ()
    return {k: specs for k in state}


def _vocab_parallel_xent(logits_local, labels, axis: str = "model"):
    """Per-token cross-entropy with the vocab dim sharded over
    ``axis`` (megatron): max/sum/target-logit stitched by pmax/psum."""
    vl = logits_local.shape[-1]
    rank = lax.axis_index(axis)
    # the stabilizer is mathematically a constant — stop_gradient both
    # dodges pmax's missing diff rule and skips a useless backward op
    m = lax.pmax(jnp.max(lax.stop_gradient(logits_local), -1), axis)
    e = jnp.sum(jnp.exp(logits_local - m[..., None]), -1)
    lse = jnp.log(lax.psum(e, axis)) + m
    loc = labels - rank * vl
    ok = (loc >= 0) & (loc < vl)
    tgt = jnp.take_along_axis(
        logits_local, jnp.clip(loc, 0, vl - 1)[..., None], -1)[..., 0]
    tgt = lax.psum(jnp.where(ok, tgt, 0.0), axis)
    return lse - tgt


def _unvary(x, mesh):
    """Type a numerically-replicated scalar as unvarying on every mesh
    axis (needed for out_specs=P() under shard_map VMA checking).
    psum over the still-varying axes multiplies the value by their
    total size, so divide it back out — numerically a no-op that gives
    the checker the collective it wants."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = tuple(jax.typeof(x).vma) or tuple(mesh.axis_names)
    n = int(np.prod([sizes.get(a, 1) for a in axes]))
    return lax.psum(x, axes) / n
