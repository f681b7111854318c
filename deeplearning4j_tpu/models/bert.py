"""BERT family — the framework's native transformer encoder.

Reference parity: the reference has no native BERT *model*; BERT-base
arrives via SameDiff TF import (`TensorflowFrameworkImporter`,
SURVEY.md S6, BASELINE config #4) and is fed by
``org.deeplearning4j.iterator.BertIterator`` (D16). Here the encoder is
a first-class model built TPU-first:

- **stacked-layer scan**: all L encoder layers live in ONE stacked
  params pytree and run under ``lax.scan`` — compile time is O(1) in
  depth and XLA sees a single fused layer body.
- **remat**: optional ``jax.checkpoint`` over the layer body trades
  FLOPs for HBM (activation memory O(sqrt) trick is XLA's choice).
- **attention**: dense fused attention by default
  (`ops.attention.dot_product_attention` over split heads), or the
  Pallas flash kernel (`parallel.sequence.flash_attention`, key-mask
  aware) for long sequences.
- **bf16-ready**: ``compute_dtype=bfloat16`` keeps params fp32 and
  casts activations, the standard TPU mixed-precision recipe (MXU
  native bf16).

Weight layout follows the TF/HF BERT conventions (q/k/v/output dense
per layer, gelu intermediate, post-LN) so TF-checkpoint import can map
1:1 onto these pytrees.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.common import layerprof
from deeplearning4j_tpu.learning.updaters import Adam, IUpdater
from deeplearning4j_tpu.ops.attention import (dot_product_attention,
                                              merge_heads, split_heads)


def _raw_step(loss_fn, updater):
    """Functional train step shared by every model head:
    (params, opt_state, iteration, batch, rng) -> (params', state',
    loss)."""

    def step(params, opt_state, iteration, batch, rng):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, rng))(params)
        # attribution scope (common.layerprof): the updater sweep is
        # real step work that belongs to no functional block
        with layerprof.scope("optimizer"):
            updates, new_state = updater.apply(grads, opt_state,
                                               iteration)
            # apply the (possibly f32) updater math at full precision
            # but keep each param's own dtype — bf16 params would
            # otherwise silently promote to f32 after one step
            new_params = jax.tree_util.tree_map(
                lambda p, u: (p - u).astype(p.dtype), params, updates)
        return new_params, new_state, loss

    return step


def _make_train_step(loss_fn, updater):
    """Jitted train step; params/opt-state buffers are donated (XLA
    reuses them)."""
    return jax.jit(_raw_step(loss_fn, updater), donate_argnums=(0, 1))


class _Trainable:
    """fit_batch/score plumbing over a jitted `_make_train_step`."""

    updater: IUpdater
    params: dict

    def _loss_fn(self, params, batch, rng):
        raise NotImplementedError

    def _ensure_step(self):
        if getattr(self, "_compiled_updater", None) is not None and \
                self._compiled_updater is not self.updater:
            # updater reassigned after compile: the cached programs
            # bake the OLD update rule (and the opt state's moments
            # belong to it) — evict everything, like SameDiff's
            # set_training_config eviction of train_multi
            self._step = None
            self._multi_step = None
            self._opt_state = None
        if getattr(self, "_step", None) is None:
            self._compiled_updater = self.updater
            self._step = _make_train_step(self._loss_fn, self.updater)
            self._opt_state = self.updater.init_state(self.params)
            self._iteration = 0

    def fit_batch(self, batch) -> float:
        self._ensure_step()
        batch = {k: jnp.asarray(v) for k, v in batch.items()
                 if v is not None}
        rng = jax.random.PRNGKey(np.random.randint(0, 2 ** 31))
        self.params, self._opt_state, loss = self._step(
            self.params, self._opt_state, self._iteration, batch, rng)
        self._iteration += 1
        self.score_value = float(loss)
        return self.score_value

    def fit_steps(self, batch, n_steps: int) -> float:
        """``n_steps`` updates on ONE device-resident batch inside a
        single ``lax.fori_loop`` dispatch, syncing on the final loss
        once — the benchmark-grade loop (same recipe as
        ``MultiLayerNetwork.fit_steps``: per-step host dispatch + loss
        sync is a fixed tax that a fori-loop amortizes). Per-step RNG is ``fold_in(rng, i)``."""
        self._ensure_step()
        if getattr(self, "_multi_step", None) is None:
            raw = _raw_step(self._loss_fn, self.updater)

            def multi(params, opt_state, it0, batch, rng, n):
                def body(i, carry):
                    p, s, _ = carry
                    p, s, l = raw(p, s, it0 + i, batch,
                                  jax.random.fold_in(rng, i))
                    return p, s, jnp.float32(l)

                return lax.fori_loop(
                    0, n, body,
                    (params, opt_state, jnp.float32(0)))

            self._multi_step = jax.jit(multi, static_argnums=(5,),
                                       donate_argnums=(0, 1))
        batch = {k: jnp.asarray(v) for k, v in batch.items()
                 if v is not None}
        rng = jax.random.PRNGKey(np.random.randint(0, 2 ** 31))
        self.params, self._opt_state, loss = self._multi_step(
            self.params, self._opt_state, self._iteration, batch,
            rng, n_steps)
        self._iteration += n_steps
        self.score_value = float(loss)
        return self.score_value

    def score(self) -> float:
        return self.score_value

    def layer_report(self, batch, **roofline_kw):
        """Per-functional-block flops/bytes/roofline attribution of
        the compiled train step (common.layerprof): lowers the jitted
        step at ``batch``, partitions ``cost_analysis()`` by the
        ``dl4j.*`` scopes (embeddings / encoder.attention /
        encoder.ffn / pooler / mlm_head / nsp_head for BERT), and
        joins the kernel-select decisions recorded at trace time.
        Lowering only — nothing executes, buffers are not donated."""
        self._ensure_step()
        batch = {k: jnp.asarray(v) for k, v in batch.items()
                 if v is not None}
        lowered = self._step.lower(
            self.params, self._opt_state, self._iteration, batch,
            jax.random.PRNGKey(0))
        return layerprof.attribute_compiled(
            lowered.compile(), model_name=type(self).__name__,
            **roofline_kw)


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    # TPU-first knobs
    compute_dtype: str = "float32"        # "bfloat16" for MXU-native
    # remat: False = store all activations; True/"full" = per-layer
    # jax.checkpoint saving nothing (max recompute, min HBM);
    # "dots" = jax.checkpoint(policy=dots_saveable) — matmul outputs
    # are SAVED, only elementwise/softmax recompute (the r4 MFU-sweep
    # winner candidate: recompute cost drops from ~1 fwd to ~0)
    remat: object = False
    # Pallas kernel (t % 128 == 0). Key masks are supported in-kernel;
    # attention-prob dropout is not (needs materialized weights), so
    # training with attention_probs_dropout_prob > 0 uses the dense
    # path — set it to 0.0 to train through the flash kernel.
    use_flash_attention: bool = False
    #: compute q/k/v with ONE [H, 3H] GEMM instead of three [H, H]
    #: GEMMs. Param layout is unchanged (Wq/Wk/Wv stay separate for
    #: the TF-checkpoint 1:1 mapping). Measured NULL on v5e: the
    #: concat sits inside the stacked-layer scan body, is rebuilt on
    #: every remat pass, and cost 8% at the headline config
    #: (BENCH_notes_r04.md) — kept for the record, default off
    fused_qkv: bool = False
    # MLM head on at most this many gathered positions per sequence
    # (the reference TF BERT pretraining knob of the same name);
    # 0 = decode every position. Rows with more masked positions than
    # this train on the first max_predictions_per_seq of them.
    max_predictions_per_seq: int = 0

    @staticmethod
    def base():
        return BertConfig()

    @staticmethod
    def tiny(**kw):
        """Test-scale config (layers=2, hidden=128)."""
        d = dict(vocab_size=1000, hidden_size=128, num_hidden_layers=2,
                 num_attention_heads=4, intermediate_size=256,
                 max_position_embeddings=128)
        d.update(kw)
        return BertConfig(**d)


def _norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _dropout(x, rate, rng, training):
    if not training or rng is None or rate <= 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


class Bert(_Trainable):
    """BERT encoder with MLM/NSP pretraining heads.

    params pytree:
      embeddings: word/position/token_type [.,hidden], ln {g,b}
      encoder:    STACKED over layers: each leaf [L, ...]
      pooler:     {W, b}
      mlm:        transform {W, b, ln_g, ln_b}, output bias (decoder
                  weights tied to word embeddings)
      nsp:        {W, b}
    """

    def __init__(self, config: BertConfig,
                 updater: Optional[IUpdater] = None, seed: int = 0):
        self.conf = config
        self.updater = updater or Adam(1e-4)
        self.seed = seed
        self.params = None
        self._opt_state = None
        self._step = None
        self._encode_jit = None
        self.score_value = float("nan")

    # -- init ------------------------------------------------------------
    def init(self) -> "Bert":
        c = self.conf
        key = jax.random.PRNGKey(self.seed)
        ks = iter(jax.random.split(key, 32))
        sd = c.initializer_range
        H, L = c.hidden_size, c.num_hidden_layers

        def tn(k, shape):
            return sd * jax.random.truncated_normal(k, -2, 2, shape,
                                                    jnp.float32)

        def stacked(shape):
            return tn(next(ks), (L,) + shape)

        self.params = {
            "embeddings": {
                "word": tn(next(ks), (c.vocab_size, H)),
                "position": tn(next(ks),
                               (c.max_position_embeddings, H)),
                "token_type": tn(next(ks), (c.type_vocab_size, H)),
                "ln_g": jnp.ones((H,)), "ln_b": jnp.zeros((H,)),
            },
            "encoder": {
                "Wq": stacked((H, H)), "bq": jnp.zeros((L, H)),
                "Wk": stacked((H, H)), "bk": jnp.zeros((L, H)),
                "Wv": stacked((H, H)), "bv": jnp.zeros((L, H)),
                "Wo": stacked((H, H)), "bo": jnp.zeros((L, H)),
                "attn_ln_g": jnp.ones((L, H)),
                "attn_ln_b": jnp.zeros((L, H)),
                "Wi": stacked((H, c.intermediate_size)),
                "bi": jnp.zeros((L, c.intermediate_size)),
                "Wout": stacked((c.intermediate_size, H)),
                "bout": jnp.zeros((L, H)),
                "out_ln_g": jnp.ones((L, H)),
                "out_ln_b": jnp.zeros((L, H)),
            },
            "pooler": {"W": tn(next(ks), (H, H)), "b": jnp.zeros((H,))},
            "mlm": {"W": tn(next(ks), (H, H)), "b": jnp.zeros((H,)),
                    "ln_g": jnp.ones((H,)), "ln_b": jnp.zeros((H,)),
                    "out_b": jnp.zeros((c.vocab_size,))},
            "nsp": {"W": tn(next(ks), (H, 2)), "b": jnp.zeros((2,))},
        }
        return self

    # -- encoder ---------------------------------------------------------
    def _attention(self, lp, x, key_mask, rng, training):
        c = self.conf
        h = c.num_attention_heads
        b, t, H = x.shape
        r_attn = r_out = None
        if rng is not None:
            r_attn, r_out = jax.random.split(rng)

        if c.fused_qkv:
            w = jnp.concatenate([lp["Wq"], lp["Wk"], lp["Wv"]], 1)
            bias = jnp.concatenate([lp["bq"], lp["bk"], lp["bv"]])
            qkv = x @ w + bias
            q, k, v = (split_heads(t, h)
                       for t in jnp.split(qkv, 3, axis=-1))
        else:
            q = split_heads(x @ lp["Wq"] + lp["bq"], h)
            k = split_heads(x @ lp["Wk"] + lp["bk"], h)
            v = split_heads(x @ lp["Wv"] + lp["bv"], h)
        attn_drop = (c.attention_probs_dropout_prob
                     if training and r_attn is not None else 0.0)
        if c.use_flash_attention and attn_drop == 0.0:
            from deeplearning4j_tpu.parallel.sequence import \
                flash_attention
            o = flash_attention(q, k, v, False, 128, 128, key_mask)
        else:
            m = None
            if key_mask is not None:
                m = key_mask[:, None, None, :]
            o = dot_product_attention(q, k, v, m,
                                      dropout_rng=r_attn,
                                      dropout_rate=attn_drop)
        o = merge_heads(o)
        o = o @ lp["Wo"] + lp["bo"]
        return _dropout(o, c.hidden_dropout_prob, r_out, training)

    def _layer(self, lp, x, key_mask, rng, training):
        c = self.conf
        r1 = r2 = None
        if rng is not None:
            r1, r2 = jax.random.split(rng)
        # functional-block attribution scopes (common.layerprof): the
        # encoder is a lax.scan over stacked layer params — one traced
        # body for all L layers — so per-layer-index scopes cannot
        # exist; attention vs FFN is the finest static split
        with layerprof.scope("encoder.attention"):
            a = self._attention(lp, x, key_mask, r1, training)
            x = _norm(x + a, lp["attn_ln_g"], lp["attn_ln_b"],
                      c.layer_norm_eps)
        with layerprof.scope("encoder.ffn"):
            i = jax.nn.gelu(x @ lp["Wi"] + lp["bi"])
            o = _dropout(i @ lp["Wout"] + lp["bout"],
                         c.hidden_dropout_prob, r2, training)
            return _norm(x + o, lp["out_ln_g"], lp["out_ln_b"],
                         c.layer_norm_eps)

    def encode(self, params, input_ids, token_type_ids=None,
               attention_mask=None, *, training=False, rng=None):
        """input_ids [b, t] int32 -> (sequence_output [b, t, H],
        pooled_output [b, H])."""
        c = self.conf
        dt = jnp.dtype(c.compute_dtype)
        b, t = input_ids.shape
        if t > c.max_position_embeddings:
            raise ValueError(
                f"sequence length {t} exceeds max_position_embeddings "
                f"{c.max_position_embeddings} (JAX gather would "
                "silently clamp to the last position)")
        r_emb = None
        if rng is not None:
            rng, r_emb = jax.random.split(rng)
        with layerprof.scope("embeddings"):
            e = params["embeddings"]
            x = e["word"][input_ids]
            x = x + e["position"][jnp.arange(t)][None]
            if token_type_ids is None:
                token_type_ids = jnp.zeros_like(input_ids)
            x = x + e["token_type"][token_type_ids]
            x = _norm(x, e["ln_g"], e["ln_b"], c.layer_norm_eps)
            x = _dropout(x, c.hidden_dropout_prob, r_emb, training)
            x = x.astype(dt)

        key_mask = None
        if attention_mask is not None:
            key_mask = attention_mask.astype(dt)

        L = c.num_hidden_layers
        enc = jax.tree_util.tree_map(lambda a: a.astype(dt),
                                     params["encoder"])

        def body(carry, layer_in):
            x, rng = carry
            lp, i = layer_in
            r = None
            if rng is not None:
                r = jax.random.fold_in(rng, i)
            y = self._layer(lp, x, key_mask, r, training)
            return (y, rng), None

        if not c.remat:
            layer_fn = body
        elif c.remat in (True, "full"):
            layer_fn = jax.checkpoint(body)
        elif c.remat == "dots":
            layer_fn = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_saveable)
        else:
            raise ValueError(f"remat={c.remat!r}: use False, True, "
                             f"'full', or 'dots'")
        (x, _), _ = lax.scan(layer_fn, (x, rng),
                             (enc, jnp.arange(L)))

        x = x.astype(jnp.float32)
        with layerprof.scope("pooler"):
            p = params["pooler"]
            pooled = jnp.tanh(x[:, 0] @ p["W"] + p["b"])
        return x, pooled

    # -- heads -----------------------------------------------------------
    def mlm_logits(self, params, sequence_output):
        with layerprof.scope("mlm_head"):
            m = params["mlm"]
            h = jax.nn.gelu(sequence_output @ m["W"] + m["b"])
            h = _norm(h, m["ln_g"], m["ln_b"],
                      self.conf.layer_norm_eps)
            # decoder tied to word embeddings (TF/HF convention)
            return h @ params["embeddings"]["word"].T + m["out_b"]

    def nsp_logits(self, params, pooled_output):
        with layerprof.scope("nsp_head"):
            n = params["nsp"]
            return pooled_output @ n["W"] + n["b"]

    def pretrain_loss(self, params, batch, rng=None, training=True):
        """Masked-LM + next-sentence loss.

        batch keys: input_ids, token_type_ids, attention_mask,
        mlm_labels ([b, t], -1 = unmasked/ignore), nsp_labels ([b]
        int, optional).
        """
        seq, pooled = self.encode(
            params, batch["input_ids"],
            batch.get("token_type_ids"), batch.get("attention_mask"),
            training=training, rng=rng)
        labels = batch["mlm_labels"]
        k = self.conf.max_predictions_per_seq
        if k and k < labels.shape[1]:
            # Gather the (at most k) masked positions per sequence and
            # run the vocab-sized decoder on [b, k, H] instead of
            # [b, t, H] — the reference TF BERT's
            # max_predictions_per_seq design. With ~15% masking the
            # decoder matmul is the single largest head cost; rows
            # with more than k masked positions train on the first k
            # (identical to the reference's truncation).
            masked = labels >= 0
            # stable argsort of "not masked": masked positions first,
            # original order preserved within each group
            pos = jnp.argsort(~masked, axis=1, stable=True)[:, :k]
            labels = jnp.take_along_axis(labels, pos, axis=1)
            seq_sel = jnp.take_along_axis(seq, pos[..., None], axis=1)
        else:
            seq_sel = seq
        logits = self.mlm_logits(params, seq_sel)
        with layerprof.scope("loss"):
            w = (labels >= 0).astype(jnp.float32)
            safe = jnp.maximum(labels, 0)
            logp = jax.nn.log_softmax(logits, -1)
            nll = -jnp.take_along_axis(logp, safe[..., None],
                                       -1)[..., 0]
            mlm = jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
            loss = mlm
        if "nsp_labels" in batch and batch["nsp_labels"] is not None:
            nlogits = self.nsp_logits(params, pooled)
            with layerprof.scope("loss"):
                nlogp = jax.nn.log_softmax(nlogits, -1)
                nsp = -jnp.mean(jnp.take_along_axis(
                    nlogp, batch["nsp_labels"][:, None], -1)[:, 0])
                loss = loss + nsp
        return loss

    # -- training (fit_batch from _Trainable) ----------------------------
    def _loss_fn(self, params, batch, rng):
        return self.pretrain_loss(params, batch, rng)

    def output(self, input_ids, token_type_ids=None,
               attention_mask=None):
        """Inference forward: (sequence_output, pooled_output)."""
        if self._encode_jit is None:
            self._encode_jit = jax.jit(functools.partial(
                self.encode, training=False, rng=None))
        return self._encode_jit(
            self.params, jnp.asarray(input_ids),
            None if token_type_ids is None
            else jnp.asarray(token_type_ids),
            None if attention_mask is None
            else jnp.asarray(attention_mask))


class BertForSequenceClassification(_Trainable):
    """Fine-tuning head over a (pretrained) encoder — the reference's
    BERT fine-tune flow (BertIterator supervised mode, D16)."""

    def __init__(self, bert: Bert, num_labels: int,
                 updater: Optional[IUpdater] = None, seed: int = 1):
        self.bert = bert
        self.num_labels = num_labels
        self.updater = updater or Adam(2e-5)
        key = jax.random.PRNGKey(seed)
        H = bert.conf.hidden_size
        # COPY the encoder params: the jitted train step donates its
        # param buffers, so sharing them with `bert` would invalidate
        # the encoder's arrays on the first fine-tune step. fit_batch
        # re-syncs bert.params to the fine-tuned weights.
        self.params = {
            "bert": jax.tree_util.tree_map(jnp.array, bert.params),
            "cls": {"W": bert.conf.initializer_range *
                    jax.random.truncated_normal(key, -2, 2,
                                                (H, num_labels)),
                    "b": jnp.zeros((num_labels,))},
        }
        self._step = None
        self._opt_state = None
        self._logits_jit = None
        self.score_value = float("nan")

    def logits(self, params, input_ids, token_type_ids=None,
               attention_mask=None, training=False, rng=None):
        _, pooled = self.bert.encode(params["bert"], input_ids,
                                     token_type_ids, attention_mask,
                                     training=training, rng=rng)
        return pooled @ params["cls"]["W"] + params["cls"]["b"]

    def _loss_fn(self, params, batch, rng):
        lg = self.logits(params, batch["input_ids"],
                         batch.get("token_type_ids"),
                         batch.get("attention_mask"),
                         training=True, rng=rng)
        logp = jax.nn.log_softmax(lg, -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, batch["labels"][:, None], -1)[:, 0])

    def fit_batch(self, batch) -> float:
        loss = super().fit_batch(batch)
        # keep the encoder object consistent with the fine-tuned weights
        self.bert.params = self.params["bert"]
        return loss

    def predict(self, input_ids, token_type_ids=None,
                attention_mask=None):
        if self._logits_jit is None:
            self._logits_jit = jax.jit(functools.partial(
                self.logits, training=False, rng=None))
        lg = self._logits_jit(
            self.params, jnp.asarray(input_ids),
            None if token_type_ids is None
            else jnp.asarray(token_type_ids),
            None if attention_mask is None
            else jnp.asarray(attention_mask))
        return np.asarray(jnp.argmax(lg, -1))
