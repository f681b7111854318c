"""Persistent XLA compilation cache + retrace guard for the jit funnels.

The reference pays JVM warmup once per process; our analogue is XLA
compile latency, which every fresh process pays in full at the first
``fit``/``output`` call — minutes at ResNet/BERT scale on TPU. jax ships
a content-addressed on-disk compilation cache (the TVM compile-cache
idea): keyed by (HLO, compile options, backend version), so a second
process compiling the SAME network loads the serialized executable
instead of re-running XLA.

Where the cache lives is decided ONCE, by :func:`configure`, which the
package ``__init__`` calls — the one place every entry path passes, and
early enough that the process has not compiled anything yet (jax
decides "is there a cache?" at its first compile and never asks again):

* ``JAX_COMPILATION_CACHE_DIR`` set: jax itself uses that directory and
  this package sets none (the operator placed the cache; e.g. a machine
  that keeps one across runs).
* unset: ``<checkout>/.jax_cache``, computed from this file's path —
  never ``$HOME``, a temp name, a pid or the time, because the path is
  part of where a later run looks. The directory is git-ignored.
* a process asked for the CPU platform gets no cache unless
  ``DL4J_TPU_COMPILE_CACHE=1`` says so explicitly (see
  :func:`resolve_cache_dir`); ``DL4J_TPU_COMPILE_CACHE=0`` opts out
  everywhere.

:class:`RetraceGuard` is the other half of compile-latency hygiene: the
cache cannot help a process that keeps compiling NEW programs. jit
retraces per input signature, so ragged minibatches or unbucketed
sequence lengths silently turn one network into dozens of compiled
programs. The guard counts distinct signatures per network and warns
once past a threshold, pointing at padding/bucketing.
"""
from __future__ import annotations

import logging
import os
from typing import Mapping, Optional

from deeplearning4j_tpu.common import telemetry
from deeplearning4j_tpu.common.environment import Environment

log = logging.getLogger("deeplearning4j_tpu")

#: jax's own variable; when it is set this package sets no directory
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_TRUE = ("1", "true", "True", "yes")


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — next to the package directory."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def resolve_cache_dir(environ: Mapping[str, str],
                      platforms: Optional[str]) -> Optional[str]:
    """The directory this package sets, or None when it sets none.

    ``platforms`` is what the process was ASKED to run on
    (``jax.config.jax_platforms``: ``JAX_PLATFORMS`` or an earlier
    ``config.update``) — asking the backend itself would initialise it,
    and take the chip, at import. A process pinned to ``cpu`` gets no
    cache without an explicit ``DL4J_TPU_COMPILE_CACHE=1``: cpu
    ``device_get``/``np.asarray`` return zero-copy views of XLA
    buffers, and a cache-loaded executable honors buffer donation that
    a freshly-compiled CPU one may not — code holding views across a
    donating step (a pattern CPU-only tests get away with) would see
    its arrays mutate."""
    if environ.get(JAX_CACHE_ENV):
        return None
    flag = environ.get("DL4J_TPU_COMPILE_CACHE")
    if flag is not None and flag not in _TRUE:
        return None
    on_cpu = (platforms or "").split(",")[0].strip() == "cpu"
    if on_cpu and flag is None:
        return None
    return checkout_cache_dir()


def configure() -> Optional[str]:
    """Apply :func:`resolve_cache_dir` to jax's config; returns the
    directory set here (None: jax's own configuration stands). Called
    from the package ``__init__``; touches no file and no backend —
    jax creates the directory at its first cache write, and an
    unwritable one fails there, loudly."""
    import jax
    d = resolve_cache_dir(os.environ, jax.config.jax_platforms)
    if d is None:
        return None
    jax.config.update("jax_compilation_cache_dir", d)
    # cache unconditionally: the default gates (>=1s compile, min
    # entry size) exist for shared-filesystem TPU pods; here losing
    # sub-second entries would skip exactly the programs unit-scale
    # users compile, and make "a warm run adds no entries" untestable
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log.debug("persistent XLA compilation cache at %s", d)
    return d


def signature_of(*xs) -> tuple:
    """Hashable (shape, dtype) signature of a batch's arrays; None
    passes through, lists/tuples recurse (graph multi-input)."""
    out = []
    for x in xs:
        if x is None:
            out.append(None)
        elif isinstance(x, (list, tuple)):
            out.append(signature_of(*x))
        elif hasattr(x, "shape"):
            out.append((tuple(x.shape), str(getattr(x, "dtype", ""))))
        else:
            out.append(type(x).__name__)
    return tuple(out)


class RetraceGuard:
    """Counts the distinct input signatures one network has compiled
    and warns ONCE when the count exceeds the threshold — each new
    signature is a full XLA recompile (shape churn defeats both the
    in-process jit cache and the persistent cache's amortization)."""

    def __init__(self, name: str, threshold: Optional[int] = None):
        self.name = name
        self.threshold = (threshold if threshold is not None
                          else Environment.get().retrace_warn_threshold)
        self._sigs: set = set()
        self._warned = False
        # bound once: record() runs every step, and the hit path must
        # not pay a registry lookup + label-key build per step
        self._hits = telemetry.counter(
            "dl4j_compile_cache_hits_total",
            "steps whose input signature matched an "
            "already-compiled program (no retrace)").bind(
                network=self.name)

    def record(self, *batch_arrays) -> bool:
        """Record one dispatch; returns True when the signature was
        already known (no retrace) — callers gate their own
        cold-compile accounting on it (serving bucket misses)."""
        sig = signature_of(*batch_arrays)
        if sig in self._sigs:
            # known signature: the in-process executable is reused
            self._hits.inc()
            return True
        self._sigs.add(sig)
        # new signature: jit traces + compiles (the persistent on-disk
        # cache may still serve the binary — this counts compiles the
        # PROCESS had to go through, i.e. retrace pressure)
        telemetry.counter(
            "dl4j_compile_cache_misses_total",
            "steps whose input signature was new to this process "
            "(trace + XLA compile or persistent-cache load)"
        ).inc(network=self.name)
        if len(self._sigs) > 1:
            telemetry.counter(
                "dl4j_retrace_total",
                "recompiles past a network's first signature "
                "(shape/dtype churn)").inc(network=self.name)
            telemetry.instant("retrace", network=self.name,
                              signature=repr(sig),
                              n_signatures=len(self._sigs))
        if not self._warned and len(self._sigs) > self.threshold:
            self._warned = True
            log.warning(
                "%s has now compiled %d distinct input signatures — "
                "every new batch shape/dtype recompiles the whole XLA "
                "program. Pad minibatches to a fixed batch size (or "
                "bucket sequence lengths) so the step compiles once.",
                self.name, len(self._sigs))
        return False

    @property
    def n_signatures(self) -> int:
        return len(self._sigs)
