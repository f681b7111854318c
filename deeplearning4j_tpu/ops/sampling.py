"""Token sampling — the one implementation behind every decode loop.

Greedy / temperature / top-k next-token selection factored out of the
benchmark decoders so the generative serving engine
(:mod:`serving.generative`) and ``benchmarks/bench_charrnn.py`` sample
through identical math. Everything here is jit-friendly: pure
functions of ``(logits, key, temperature, top_k)``, and one compiled
decode step serves greedy and stochastic sequences side by side in the
same batch. The program does the work its batch asks for:
:func:`sample_logits` branches on the device (``lax.switch``, inside
the caller's jit) on what the ``temperature`` and ``top_k`` arrays it
is handed need, cheapest rung first (:data:`PATHS`), and every rung
gives the ids the last one would.

Conventions:

- ``logits`` is ``[batch, vocab]`` (a single decode step's last-token
  logits). ``temperature`` and ``top_k`` are per-row arrays (or
  scalars broadcast to the batch), so heterogeneous requests batch
  together without retracing.
- ``temperature == 0`` means greedy (argmax) for that row — resolved
  with ``jnp.where`` inside a sampling batch, so rows share a program.
- ``top_k == 0`` means "no top-k filter" (full distribution).
- The PRNG key is threaded explicitly; callers split per step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: additive score for filtered logits — matches ops.attention.NEG_INF
#: (finite, so masked-everything rows degrade to uniform, not NaN)
NEG_INF = -1e9

#: the largest ``top_k`` the partial selection (``lax.top_k`` with this
#: static k) answers; a batch with a larger one orders the whole
#: vocabulary. Hugging Face's default is 50, common settings 20-100.
TOP_K_CAP = 128

#: the rungs of :func:`sample_logits`, cheapest first, indexed by
#: :func:`sample_rung`: ``argmax`` (no row samples), ``categorical``
#: (some row samples, none of them filters), ``top_k`` (a sampling
#: row filters, none beyond ``TOP_K_CAP``), ``sort`` (any ``top_k``
#: up to the vocabulary)
PATHS = ("argmax", "categorical", "top_k", "sort")


def sample_rung(temperature, top_k, xp=np):
    """Index into :data:`PATHS` of the rung a batch with these per-row
    ``temperature`` and ``top_k`` takes, from two reductions: does any
    row sample, and the largest ``top_k`` among the rows that do (a
    greedy row's filter is never read). ``xp`` is ``numpy`` on the
    host (the engine names the rung its step will take) and
    ``jax.numpy`` inside the program: one rule for both."""
    samples = temperature > 0
    k_max = xp.max(xp.where(samples, top_k, 0))
    return xp.any(samples) * (1 + (k_max > 0) + (k_max > TOP_K_CAP))


def _kth_by_sort(scaled, kc):
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    return jnp.take_along_axis(sorted_desc, kc[..., None] - 1, axis=-1)


def _kth_by_top_k(scaled, kc):
    cap = min(TOP_K_CAP, scaled.shape[-1])
    largest, _ = lax.top_k(scaled, cap)
    return jnp.take_along_axis(
        largest, jnp.minimum(kc, cap)[..., None] - 1, axis=-1)


def top_k_filter(logits, top_k, kth=_kth_by_sort):
    """Keep each row's ``top_k`` largest logits (ties at the threshold
    kept), push the rest to ``NEG_INF``. ``top_k`` is a per-row int
    array (0 = keep all). ``kth`` finds the threshold, the k-th largest
    value of a row (k clamped into [1, vocab]): by a whole sort, exact
    for any k, or by ``lax.top_k`` for k up to ``TOP_K_CAP``."""
    logits = jnp.asarray(logits)
    vocab = logits.shape[-1]
    k = jnp.asarray(top_k, jnp.int32)
    k = jnp.broadcast_to(k, logits.shape[:-1])
    thresh = kth(logits, jnp.clip(k, 1, vocab))
    filtered = jnp.where(logits >= thresh, logits, NEG_INF)
    return jnp.where(k[..., None] > 0, filtered, logits)


def sample_logits(logits, key, temperature=1.0, top_k=0):
    """Next-token ids ``[batch]`` from ``[batch, vocab]`` logits.

    Per-row ``temperature`` (0 = greedy argmax) and ``top_k``
    (0 = unfiltered). One program whatever the rows ask for — the
    property the continuous decode batch depends on (no per-request
    retrace) — that runs one rung of :data:`PATHS`: an all-greedy
    batch is an argmax and nothing else, and the vocabulary is ordered
    only for a sampling row with a ``top_k``. Greedy rows inside a
    sampling batch ride along through ``jnp.where``. Where both
    arguments are concrete (Python scalars, numpy) the rung is chosen
    while tracing and the others are not compiled."""
    logits = jnp.asarray(logits)
    rows = logits.shape[:-1]

    def draw(kth):
        temp = jnp.broadcast_to(
            jnp.asarray(temperature, logits.dtype), rows)
        # guard the 0 rows — their result is discarded by the where
        safe_temp = jnp.where(temp > 0, temp, 1.0)
        scaled = logits / safe_temp[..., None]
        if kth is not None:
            scaled = top_k_filter(scaled, top_k, kth)
        # the Gumbel trick over the scaled, filtered logits
        sampled_ids = jax.random.categorical(
            key, scaled, axis=-1).astype(jnp.int32)
        return jnp.where(temp > 0, sampled_ids, greedy(logits))

    rungs = (lambda: greedy(logits), lambda: draw(None),
             lambda: draw(_kth_by_top_k), lambda: draw(_kth_by_sort))
    if not any(isinstance(a, jax.core.Tracer)
               for a in (temperature, top_k)):
        return rungs[int(sample_rung(np.asarray(temperature),
                                     np.asarray(top_k)))]()
    return lax.switch(
        sample_rung(jnp.asarray(temperature), jnp.asarray(top_k), jnp),
        rungs)


def greedy(logits):
    """Pure argmax ids ``[batch]`` — the deterministic reference the
    conformance gate compares paged decode against."""
    return jnp.argmax(jnp.asarray(logits), axis=-1).astype(jnp.int32)
