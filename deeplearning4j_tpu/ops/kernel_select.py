"""Unified kernel-selection ladder for the hand-written Pallas kernels.

Before this module every fused kernel carried its own ad-hoc gate —
``DL4J_TPU_FLASH_ATTENTION`` in attention_pallas, ``DL4J_TPU_FUSED_BN_BWD``
in bn_pallas, and now ``DL4J_TPU_FUSED_CONV`` for the conv-epilogue
family — each re-implementing the same three rungs in slightly
different shapes.  The ladder is the cuDNN-helper dispatch discipline
(SURVEY.md D9: the helper seam decides, the layer never does):

  1. **structural gate** — dominates everything.  A site the kernel
     cannot express (dense additive bias, unaligned channels, wrong
     dtype/rank, inference-mode BN asked for a batch-stats pass) is
     demoted to the dense lowering no matter what the env says; the
     demotion reason is logged and counted.
  2. **force / kill override** — the tri-state env var (``=1`` force
     on anywhere, ``=0`` kill switch, unset auto), with the
     ``Environment.extra`` key taking precedence over the process env
     so tests and embedding apps can flip gates without touching
     ``os.environ``.
  3. **measured auto-heuristic** — kernel-specific, supplied by the
     caller as a thunk returning ``(fused, reason)``. The rule: a
     family's auto rung is on for the TPU only where a chip run
     recorded in PERF.md shows the whole program faster with it
     (``paged_attention`` and ``ssm_state`` by the served cells,
     section 6, PR 27-32; FLASH_MIN_SEQ still by BENCH_notes_r03).
     ``bn_bwd``, ``bn_fwd`` and ``conv_epilogue`` are off since
     PR 33, and their reason strings name the reading.

Every decision increments ``dl4j_kernel_select_total{kernel,decision}``
so a profile that shows a dense conv where a fused one was expected is
answerable from telemetry instead of print-debugging trace code.
Decisions happen at trace time (inside ``jit`` tracing), so the counter
counts compiled-program dispatch choices, not per-step executions.
"""
from __future__ import annotations

import contextlib
import contextvars
import logging
import os
from dataclasses import dataclass
from typing import Callable, Optional, Tuple, Union

from deeplearning4j_tpu.common import telemetry

log = logging.getLogger(__name__)

#: kernel family -> (Environment.extra key, env var) for the tri-state
#: force/kill override.  The conv epilogue and the BN forward
#: reduction ride the same DL4J_TPU_FUSED_CONV gate: they are one
#: family (the epilogue writes what the stats pass reads).
GATES = {
    "conv_epilogue": ("fused_conv", "DL4J_TPU_FUSED_CONV"),
    "bn_fwd": ("fused_conv", "DL4J_TPU_FUSED_CONV"),
    "bn_bwd": ("fused_bn_bwd", "DL4J_TPU_FUSED_BN_BWD"),
    "attention": ("flash_attention", "DL4J_TPU_FLASH_ATTENTION"),
    "paged_attention": ("paged_attention", "DL4J_TPU_PAGED_ATTENTION"),
    "ssm_state": ("ssm_state", "DL4J_TPU_SSM_STATE"),
    "moe_grouped": ("moe_grouped", "DL4J_TPU_MOE_GROUPED"),
}

_select_total = telemetry.counter(
    "dl4j_kernel_select_total",
    "kernel-dispatch ladder decisions by kernel family and rung "
    "(structural / forced / killed / auto_fused / auto_dense)")


def platform() -> str:
    """The platform every auto rung and every ``pallas_call`` keys on
    — jax's default backend, the one idiom for "are we on the chip"."""
    import jax
    return jax.default_backend()


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in interpret mode: False on a
    TPU backend (Mosaic compiles them), True everywhere else (so CPU
    tests exercise the same kernel code). The platform alone decides
    — no env var, argument or caught exception can put a kernel into
    interpret mode on the chip."""
    return platform() != "tpu"


#: devices the program being traced is partitioned over by GSPMD (1 =
#: a single-device program); set by whoever places a model on a mesh
_partitions: contextvars.ContextVar = contextvars.ContextVar(
    "dl4j_kernel_select_partitions", default=1)


@contextlib.contextmanager
def partitioned(n_devices: int):
    """Mark programs traced inside the block as GSPMD-partitioned over
    ``n_devices``. jax refuses to lower a Mosaic kernel into such a
    program ("Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map"), so while ``n_devices > 1``
    every family's structural gate demotes to the dense lowering —
    with that reason, counted like any other. The mesh owners
    (ParallelWrapper, ParallelInference) wrap their jitted calls in
    this and the model funnels mark themselves
    (:func:`marks_partitions`); the decision lands at trace time, i.e.
    on the first call. Fully-manual ``shard_map`` bodies are
    single-device programs to Mosaic and need no mark."""
    token = _partitions.set(max(int(n_devices), 1))
    try:
        yield
    finally:
        _partitions.reset(token)


def devices_spanned(tree) -> int:
    """Devices the arrays of ``tree`` are laid out over — read off the
    first device-array leaf (a model's params share one placement);
    1 for host arrays and empty trees."""
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            return len(sharding.device_set)
    return 1


def marks_partitions(method):
    """Decorator for a model's public funnels (``fit``, ``output``,
    ...): run the method under :func:`partitioned` with the device
    count ``self.params`` span, so a model that a mesh owner placed on
    several devices keeps tracing partition-safe programs when the
    user calls it directly afterwards (``net.output(x)`` after a
    data-parallel fit)."""
    import functools

    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        with partitioned(devices_spanned(self.params)):
            return method(self, *args, **kwargs)
    return wrapper


@dataclass(frozen=True)
class Selection:
    """One dispatch decision: which lowering a site gets and why."""

    kernel: str          #: kernel family (a GATES key)
    fused: bool          #: True = hand kernel, False = dense lowering
    decision: str        #: ladder rung that decided (counter label)
    reason: str          #: human-readable justification

    def __bool__(self) -> bool:  # ``if select(...):`` reads naturally
        return self.fused


def gate_override(kernel: str) -> Optional[bool]:
    """The tri-state force/kill override for a kernel family:
    True (force on) / False (kill switch) / None (auto heuristic).
    ``Environment.extra[<key>]`` overrides the env var."""
    from deeplearning4j_tpu.common.environment import Environment
    extra_key, env_var = GATES[kernel]
    flag = Environment.get().extra.get(extra_key)
    if flag is None:
        flag = os.environ.get(env_var)
    if flag is None or str(flag) == "":
        return None
    return str(flag) in ("1", "true", "True", "yes")


_UNSET = object()


def select(kernel: str, *,
           structural: Optional[str] = None,
           auto: Union[Tuple[bool, str],
                       Callable[[], Tuple[bool, str]]] = (False, "auto"),
           override=_UNSET,
           use_env_override: bool = True,
           record: bool = True) -> Selection:
    """Run the ladder for one dispatch site.

    ``structural`` — a demotion reason when the site fails the
    kernel's structural gate, or None when it is admissible.
    ``auto`` — the measured heuristic: either a ``(fused, reason)``
    pair or a thunk returning one (thunks keep device probes like
    free-HBM lookups off the structural/override fast paths).
    ``override``/``use_env_override`` exist for tests — by default the
    live ``gate_override(kernel)`` tri-state is consulted.
    """
    env_var = GATES[kernel][1]
    n_part = _partitions.get()
    if structural is None and n_part > 1:
        structural = (f"program is partitioned over {n_part} devices: "
                      "Mosaic kernels cannot be automatically "
                      "partitioned")
    if structural is not None:
        sel = Selection(kernel, False, "structural", structural)
    else:
        if override is _UNSET:
            override = gate_override(kernel) if use_env_override else None
        if override is False:
            sel = Selection(kernel, False, "killed",
                            f"{env_var}=0 kill switch")
        elif override is True:
            sel = Selection(kernel, True, "forced",
                            f"{env_var}=1 forced")
        else:
            fused, reason = auto() if callable(auto) else auto
            sel = Selection(kernel, bool(fused),
                            "auto_fused" if fused else "auto_dense",
                            reason)
    if record:
        _select_total.inc(kernel=kernel, decision=sel.decision)
        # layer-attribution join: selection happens at trace time,
        # inside the layer's attribution scope — record which layer's
        # trace made this decision (lazy import: layerprof imports
        # telemetry, keep this module light at import time)
        from deeplearning4j_tpu.common import layerprof
        layerprof.note_selection(sel)
        log.debug("kernel_select %s -> %s (%s: %s)", kernel,
                  "fused" if sel.fused else "dense", sel.decision,
                  sel.reason)
    return sel


def decisions(kernel: str) -> dict:
    """Counter readback for tests/diagnostics: decision -> count."""
    return {d: _select_total.value(kernel=kernel, decision=d)
            for d in ("structural", "forced", "killed", "auto_fused",
                      "auto_dense")}
