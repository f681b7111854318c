"""Training diagnostics: device-level attribution on the telemetry spine.

PR 2's spine (``common.telemetry``) instruments host paths — queues,
steps, caches.  This layer pushes observability down to the device, in
the cost-attribution spirit of Xu et al. (PAPERS.md 2004.13336: the
XLA memory/collective accounting that steered the sharded-update work)
and TVM's measure-then-tune loop (PAPERS.md 1802.04799).  Four pieces:

- **HBM accounting** — :func:`update_hbm_gauges` reads jax device
  memory stats into ``dl4j_hbm_live_bytes`` / ``dl4j_hbm_peak_bytes``
  gauges; :func:`memory_report` adds per-buffer attribution (params /
  updater state / model states / prefetch staging / an activations+
  workspace residual) for every model the fit funnels have touched.
  Exported on ``/api/memory`` (UIServer), refreshed on every
  ``/metrics`` scrape (UIServer AND the serving ``InferenceServer``),
  and landed in ``bench.py`` JSON as the ``memory`` block.
- **Per-collective tracing** — :func:`collective_span` generalizes the
  ``dp.update_exchange`` span pattern: one context manager that emits
  a ``collective.<kind>`` chrome-trace span plus
  ``dl4j_collective_seconds{kind,axis}`` /
  ``dl4j_collective_bytes_total{kind,axis}``.  Used by
  ``parallel.wrapper`` (update exchange), ``parallel.zero`` (sharded
  state placement) and ``parallel.sharedtraining`` (global batch
  assembly).
- **Numerics watchdog** — opt-in (``DL4J_TPU_NUMERICS_WATCHDOG=1``),
  sampled (``DL4J_TPU_NUMERICS_SAMPLE=N``) non-finite check on the
  loss and the in-step global grad norm inside the fit funnels.  A
  trip raises a structured :class:`NumericsEvent` carrying the step,
  tensor group, and the first bad leaf — located by a cheap per-dtype
  flat-segment scan reusing ``learning.updaters.DpFlatSpec`` — instead
  of silently training on NaNs.
- **Flight recorder** — :class:`FlightRecorder`, a bounded ring of
  per-step records (step time, loss, grad norm, retrace count,
  collective bytes, HBM gauges) that dumps a JSONL artifact plus a
  chrome trace of the last window on crash (sys.excepthook), on
  SIGTERM (the preemption signal), or on a watchdog trip — the black
  box elastic training (ROADMAP item 5) debugs from.

Gates (``common.environment``): ``DL4J_TPU_FLIGHT_RECORDER`` (default
on), ``DL4J_TPU_FLIGHT_RECORDER_STEPS``/``_DIR``,
``DL4J_TPU_NUMERICS_WATCHDOG`` (default off),
``DL4J_TPU_HBM_SAMPLE_STEPS``.  The whole layer shares PR 2's <1%
step-overhead budget — ``benchmarks/bench_telemetry.py`` has the
diagnostics leg that measures it.
"""
from __future__ import annotations

import json
import logging
import math
import os
import signal
import sys
import threading
import time
import weakref
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

import numpy as np

from deeplearning4j_tpu.common import stepstats, telemetry
from deeplearning4j_tpu.common.environment import Environment

log = logging.getLogger("deeplearning4j_tpu")

#: flight-recorder / memory-report schema version, stamped into every
#: artifact and the bench.py ``meta`` block
SCHEMA_VERSION = 1


# ----------------------------------------------------------------------
# HBM accounting
def _tree_bytes(tree) -> int:
    """Total buffer bytes of a pytree (global logical bytes — a
    replicated array counts once, matching how dp_ravel sizes it)."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape, dtype=np.int64) *
                         np.dtype(leaf.dtype).itemsize)
    return total


def _leaf_resident_bytes(leaf) -> int:
    """Bytes of one leaf actually resident on a single device.  For a
    replicated array this equals the full logical bytes; for a
    dp-sharded flat (ZeRO-1 state, fsdp params) it is the 1/N shard
    the device really holds — which is what an HBM budget cares
    about."""
    if not (hasattr(leaf, "shape") and hasattr(leaf, "dtype")):
        return 0
    itemsize = np.dtype(leaf.dtype).itemsize
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        try:
            shard_shape = sharding.shard_shape(leaf.shape)
            return int(np.prod(shard_shape, dtype=np.int64) * itemsize)
        except Exception:       # noqa: BLE001 — exotic sharding types
            pass
    return int(np.prod(leaf.shape, dtype=np.int64) * itemsize)


def _tree_resident_bytes(tree) -> int:
    """Per-device resident bytes of a pytree (sharding-aware: a
    dp-sharded leaf counts its shard, a replicated leaf its full
    size)."""
    import jax
    return sum(_leaf_resident_bytes(leaf)
               for leaf in jax.tree_util.tree_leaves(tree))


def device_memory_stats() -> List[dict]:
    """Per-device allocator stats from jax (``device.memory_stats()``).
    Empty on backends that expose none (CPU)."""
    import jax
    out = []
    for d in jax.devices():
        try:
            st = d.memory_stats()
        except Exception:           # noqa: BLE001 — backend-dependent
            st = None
        if not st:
            continue
        out.append({
            "id": int(d.id),
            "kind": str(getattr(d, "device_kind", d.platform)),
            "bytes_in_use": int(st.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(st.get("peak_bytes_in_use",
                                            st.get("bytes_in_use", 0))),
            "bytes_limit": int(st.get("bytes_limit", 0)),
        })
    return out


def update_hbm_gauges(stats: Optional[List[dict]] = None) -> List[dict]:
    """Refresh ``dl4j_hbm_live_bytes``/``dl4j_hbm_peak_bytes`` from the
    device allocator (``stats`` injectable for tests / CPU rigs where
    jax reports none).  Called per sampled step by the flight recorder
    and on every ``/metrics`` scrape."""
    if stats is None:
        stats = device_memory_stats()
    if stats and telemetry.enabled():
        live = telemetry.gauge(
            "dl4j_hbm_live_bytes",
            "device allocator bytes currently in use, per device")
        peak = telemetry.gauge(
            "dl4j_hbm_peak_bytes",
            "device allocator high-water mark, per device")
        for s in stats:
            live.set(s["bytes_in_use"], device=str(s["id"]))
            peak.set(s["peak_bytes_in_use"], device=str(s["id"]))
    return stats


#: models the fit funnels have stepped, for attribution — weak so a
#: dropped model does not leak through the diagnostics layer
_tracked_models: "weakref.WeakValueDictionary[str, object]" = \
    weakref.WeakValueDictionary()
_tracked_lock = threading.Lock()


def track_model(model, name: Optional[str] = None) -> None:
    """Register a model for :func:`memory_report` attribution (the fit
    funnels do this on every recorded step; idempotent and weak)."""
    key = f"{name or type(model).__name__}@{id(model):x}"
    if key not in _tracked_models:
        with _tracked_lock:
            try:
                _tracked_models[key] = model
            except TypeError:       # non-weakrefable exotic model
                pass


def _model_attribution(model) -> dict:
    """Bytes by buffer family for one model.  Works for MLN/graph
    (params/states/updater_states) and SameDiff (_arrays /
    _updater_state)."""
    params = getattr(model, "params", None)
    if params is None:
        params = getattr(model, "_arrays", {})
    upd = getattr(model, "updater_states", None)
    if upd is None:
        upd = getattr(model, "_updater_state", None) or {}
    states = getattr(model, "states", {}) or {}
    # resident = what one device actually holds (a ZeRO-1 sharded
    # state or fsdp param flat counts its 1/N shard, not the logical
    # size); equal to the plain bytes when everything is replicated
    return {
        "params_bytes": _tree_bytes(params),
        "updater_state_bytes": _tree_bytes(upd),
        "model_state_bytes": _tree_bytes(states),
        "params_resident_bytes": _tree_resident_bytes(params),
        "updater_state_resident_bytes": _tree_resident_bytes(upd),
    }


def memory_report(model=None) -> dict:
    """The per-buffer HBM attribution report: device allocator stats
    (live/peak/limit), per-model params / updater-state / model-state
    bytes, prefetch staging bytes, and the residual the allocator holds
    beyond what those account for (activations, XLA workspace,
    fragmentation).  ``model`` narrows attribution to one model;
    default covers every tracked model.  This is the instrument that
    makes the FSDP work (ROADMAP item 1) measurable: it shows where
    the 93.5%-of-peak HBM actually goes."""
    devices = update_hbm_gauges()
    if model is not None:
        items = [(type(model).__name__, model)]
    else:
        with _tracked_lock:
            items = [(k, m) for k, m in _tracked_models.items()]
    models = {name: _model_attribution(m) for name, m in items}
    staging = telemetry.gauge(
        "dl4j_prefetch_staged_bytes",
        "bytes of device-prefetched batches currently staged ahead of "
        "the step loop").value()
    # account per-device residency (shard-aware), not logical bytes —
    # under fsdp a model's params_bytes exceeds what any chip holds
    accounted = int(staging) + sum(
        v["params_resident_bytes"] + v["updater_state_resident_bytes"] +
        v["model_state_bytes"] for v in models.values())
    # paged KV-cache pools are their own resident class: preallocated
    # generation state, not params and not activations (sys.modules
    # lookup: near-free, and no import edge from diagnostics to
    # serving)
    kvc = sys.modules.get("deeplearning4j_tpu.serving.kvcache")
    kv_pools = kvc.pool_report() if kvc is not None else []
    kv_bytes = kvc.pool_resident_bytes() if kvc is not None else 0
    accounted += int(kv_bytes)
    report = {
        "schema_version": SCHEMA_VERSION,
        "devices": devices,
        "live_bytes_total": sum(d["bytes_in_use"] for d in devices),
        "peak_bytes_total": sum(d["peak_bytes_in_use"]
                                for d in devices),
        "models": models,
        "prefetch_staging_bytes": int(staging),
        "kv_pools": kv_pools,
        "kv_pool_bytes": int(kv_bytes),
        "accounted_bytes": accounted,
    }
    if devices:
        # what the allocator holds beyond the buffers we can name:
        # activations kept for backward, XLA scratch, fragmentation
        report["activations_and_workspace_bytes_est"] = max(
            report["live_bytes_total"] - accounted, 0)
    return report


#: published single-chip peaks, keyed by jax ``device_kind`` — THE
#: table (layerprof, bench.py and the benchmarks read it). A device
#: that is not here gets no percent-of-peak number from anything.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "tflops": 197.0,        # bf16
        "hbm_gbps": 819.0,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def device_peaks(device_kind: Optional[str] = None) -> Optional[dict]:
    """The :data:`DEVICE_PEAKS` row of ``device_kind`` (default: the
    process's first device), or None when the device is not in the
    table — callers then report no ``pct_of_roof`` /
    ``pct_compute_peak`` / ``pct_hbm_peak`` instead of assuming a
    chip."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    return DEVICE_PEAKS.get(device_kind)


def roofline(flops: float, bytes_moved: float, step_seconds: float,
             peak_tflops: Optional[float] = None,
             peak_hbm_gbps: Optional[float] = None) -> dict:
    """Automatic roofline classification from an XLA cost analysis
    (``benchmarks.cost_util``) plus a measured step time: achieved
    TFLOP/s and GB/s, arithmetic intensity vs the machine ridge point,
    which roof binds, and %-of-that-roof — the one number that says
    whether fused kernels (ROADMAP item 3) or more MXU work is the
    next lever."""
    tf = flops / step_seconds / 1e12
    gbps = bytes_moved / step_seconds / 1e9
    out = {
        "tflops": round(tf, 2),
        "hbm_gbps": round(gbps, 1),
        "arithmetic_intensity_flops_per_byte": round(
            flops / max(bytes_moved, 1.0), 2),
    }
    if peak_tflops and peak_hbm_gbps:
        ridge = peak_tflops * 1e12 / (peak_hbm_gbps * 1e9)
        ai = out["arithmetic_intensity_flops_per_byte"]
        out["ridge_flops_per_byte"] = round(ridge, 1)
        out["bound"] = "compute" if ai >= ridge else "hbm"
        out["pct_compute_peak"] = round(100 * tf / peak_tflops, 1)
        out["pct_hbm_peak"] = round(100 * gbps / peak_hbm_gbps, 1)
        out["pct_of_roof"] = (out["pct_compute_peak"]
                              if out["bound"] == "compute"
                              else out["pct_hbm_peak"])
    return out


def bench_meta() -> dict:
    """Provenance block stamped into every bench JSON so BENCH_r*.json
    trajectories are comparable run-to-run: schema version, git rev,
    jax version, device kind/count, and the ``DL4J_TPU_*`` env that
    shapes the run."""
    import jax
    meta = {
        "schema_version": SCHEMA_VERSION,
        "time": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "jax_version": jax.__version__,
        "python_version": sys.version.split()[0],
    }
    try:
        devs = jax.devices()
        meta["device_count"] = len(devs)
        meta["device_kind"] = str(getattr(devs[0], "device_kind",
                                          devs[0].platform))
        meta["platform"] = devs[0].platform
    except Exception as e:          # noqa: BLE001
        meta["device_error"] = repr(e)
    try:
        import subprocess
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))))
        if rev.returncode == 0:
            meta["git_rev"] = rev.stdout.strip()
    except Exception:               # noqa: BLE001 — no git, no rev
        pass
    meta["env"] = {k: v for k, v in sorted(os.environ.items())
                   if k.startswith("DL4J_TPU_") or k == "JAX_PLATFORMS"}
    return meta


# ----------------------------------------------------------------------
# per-collective tracing
_COLLECTIVE_SECONDS_HELP = (
    "host-observed wall time of one collective exchange — update "
    "exchange (AllReduce | ReduceScatter+AllGather), sharded-state "
    "placement, cross-process batch assembly (seconds)")
_COLLECTIVE_BYTES_HELP = (
    "estimated per-replica bytes moved by collective exchanges, by "
    "kind and mesh axis")


@contextmanager
def collective_span(kind: str, axis: str, nbytes: int = 0, **attrs):
    """The general form of the ``dp.update_exchange`` span pattern: a
    chrome-trace span ``collective.<kind>`` plus
    ``dl4j_collective_seconds{kind,axis}`` and
    ``dl4j_collective_bytes_total{kind,axis}``.  ``kind`` names the
    exchange (``update_exchange``, ``state_placement``,
    ``global_assembly``, ...), ``axis`` the mesh axis it rides.  Wraps
    host dispatch of the jitted program that CONTAINS the collective —
    on-device overlap means this bounds, not isolates, the wire time;
    the bytes counter is what makes a scaling-efficiency claim
    falsifiable per PR."""
    if not telemetry.enabled():
        yield
        return
    t0 = time.perf_counter()
    with telemetry.span(f"collective.{kind}", axis=axis,
                        bytes=int(nbytes), **attrs):
        yield
    dt = time.perf_counter() - t0
    telemetry.histogram(
        "dl4j_collective_seconds",
        _COLLECTIVE_SECONDS_HELP).observe(dt, kind=kind, axis=axis)
    if nbytes:
        telemetry.counter(
            "dl4j_collective_bytes_total",
            _COLLECTIVE_BYTES_HELP).inc(int(nbytes), kind=kind,
                                        axis=axis)
    # fold into the scaling observatory's step breakdown
    stepstats.note_collective(kind, dt)


# ----------------------------------------------------------------------
# numerics watchdog
class NumericsEvent(RuntimeError):
    """A non-finite value surfaced in training.  Structured: ``step``,
    ``model``, ``tensor_group`` (``loss``/``gradients``/``params``),
    ``value`` (the offending scalar when there is one), ``first_bad``
    ({leaf, dtype, flat_index} from the DpFlatSpec segment scan)."""

    def __init__(self, model: str, step: int, tensor_group: str,
                 first_bad: Optional[dict] = None, value=None):
        self.model = model
        self.step = int(step)
        self.tensor_group = tensor_group
        self.first_bad = first_bad
        self.value = value
        loc = f" first bad leaf: {first_bad}" if first_bad else ""
        super().__init__(
            f"non-finite {tensor_group} (={value}) in {model} at step "
            f"{step};{loc} — training halted by the numerics watchdog "
            f"(DL4J_TPU_NUMERICS_WATCHDOG=0 disables)")

    def to_dict(self) -> dict:
        return {"model": self.model, "step": self.step,
                "tensor_group": self.tensor_group,
                "first_bad": self.first_bad,
                "value": (None if self.value is None
                          else float(self.value))}


def first_nonfinite(tree) -> Optional[dict]:
    """Locate the first non-finite leaf element via the per-dtype flat
    segment layout (``learning.updaters.DpFlatSpec``): one fused
    ``isfinite``+``argmax`` reduction per float dtype bucket instead of
    a per-leaf host loop, then the flat index maps back through the
    spec's (dtype, offset, shape) segments to a named leaf.  Returns
    ``{leaf, dtype, flat_index}`` or None when every element is
    finite."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.learning.updaters import dp_ravel
    leaves_with_path = jax.tree_util.tree_flatten_with_path(tree)[0]
    if not leaves_with_path:
        return None
    paths = [jax.tree_util.keystr(p) for p, _ in leaves_with_path]
    flats, spec = dp_ravel(tree, 1)
    for dt, flat in flats.items():
        if not jnp.issubdtype(flat.dtype, jnp.floating):
            continue
        bad = ~jnp.isfinite(flat)
        if not bool(jnp.any(bad)):
            continue
        idx = int(jnp.argmax(bad))
        for (d, off, shape), label in zip(spec.infos, paths):
            if d != dt:
                continue
            size = int(np.prod(shape)) if shape else 1
            if off <= idx < off + size:
                return {"leaf": label, "dtype": dt,
                        "flat_index": idx - off}
        return {"leaf": "<padding>", "dtype": dt, "flat_index": idx}
    return None


def check_numerics(model, model_name: str, step: int, loss,
                   grad_norm=None, grads=None, params=None,
                   recorded: bool = False) -> None:
    """The fit-funnel watchdog hook.  No-op unless
    ``DL4J_TPU_NUMERICS_WATCHDOG=1``; checks every
    ``DL4J_TPU_NUMERICS_SAMPLE``-th step.  ``loss`` (and ``grad_norm``
    when the step computes one) are device scalars — the check is the
    one host sync.  On a trip the first bad leaf is located in
    ``grads`` (preferred) or ``params``, the flight recorder dumps
    with ``reason="numerics"``, and a :class:`NumericsEvent` raises."""
    env = Environment.get()
    if not env.numerics_watchdog:
        return
    if env.numerics_sample > 1 and step % env.numerics_sample:
        return
    lf = float(loss)
    gf = None if grad_norm is None else float(grad_norm)
    if math.isfinite(lf) and (gf is None or math.isfinite(gf)):
        return
    if not math.isfinite(lf):
        group, value = "loss", lf
    else:
        group, value = "gradients", gf
    first_bad = None
    scan = grads if grads is not None else params
    if scan is not None:
        try:
            first_bad = first_nonfinite(scan)
        except Exception as e:      # noqa: BLE001 — diagnosis must not
            log.warning("numerics attribution scan failed: %r", e)
    telemetry.counter(
        "dl4j_numerics_trips_total",
        "numerics-watchdog trips (non-finite loss or grad norm), by "
        "model and tensor group").inc(model=model_name, group=group)
    telemetry.instant("numerics_trip", model=model_name, step=step,
                      group=group)
    event = NumericsEvent(model_name, step, group, first_bad, value)
    rec = FlightRecorder.get()
    if rec.enabled:
        if not recorded:
            # the poisoned step itself belongs in the black box
            rec.record(model, model_name, step, lf, None,
                       grad_norm=gf)
        rec.dump("numerics", event=event.to_dict())
    raise event


# ----------------------------------------------------------------------
# flight recorder
class FlightRecorder:
    """Bounded ring of per-step structured records, dumped to
    ``flightrec_<pid>_<reason>.jsonl`` (+ a chrome trace of the span
    buffer's last window) on crash, SIGTERM, or watchdog trip.

    Loss/grad-norm enter the ring as device scalars and are
    materialized only at dump time, so recording never forces a step
    sync.  HBM gauges refresh every ``DL4J_TPU_HBM_SAMPLE_STEPS``
    records.  Gate: ``DL4J_TPU_FLIGHT_RECORDER`` (default on)."""

    _instance: Optional["FlightRecorder"] = None
    _instance_lock = threading.Lock()

    def __init__(self):
        env = Environment.get()
        self.enabled = bool(env.flight_recorder)
        self.max_steps = max(int(env.flight_recorder_steps), 1)
        self.dir = env.flight_recorder_dir or "flightrec"
        self.keep = max(int(env.flight_recorder_keep), 1)
        self.hbm_sample = max(int(env.hbm_sample_steps), 1)
        self._ring: "deque[dict]" = deque()
        self._lock = threading.Lock()
        self._installed = False
        self._prev_excepthook = None
        self._prev_sigterm = None
        self._n_records = 0
        self._last_hbm: List[dict] = []
        self._dumped_reasons: set = set()

    @classmethod
    def get(cls) -> "FlightRecorder":
        inst = cls._instance
        if inst is not None:
            return inst
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def _reset_for_tests(cls):
        with cls._instance_lock:
            if cls._instance is not None:
                cls._instance.uninstall()
            cls._instance = None
        with _tracked_lock:
            _tracked_models.clear()

    # -- crash / preemption hooks --------------------------------------
    def install(self) -> None:
        """Wrap ``sys.excepthook`` (crash) and the SIGTERM handler
        (preemption).  Idempotent; called lazily on the first recorded
        step so importing the library never touches process-global
        handlers."""
        if self._installed:
            return
        self._installed = True
        self._prev_excepthook = sys.excepthook

        def _hook(tp, val, tb):
            try:
                self.dump("crash", event={"error": repr(val)})
            finally:
                (self._prev_excepthook or sys.__excepthook__)(
                    tp, val, tb)

        sys.excepthook = _hook
        try:
            self._prev_sigterm = signal.signal(signal.SIGTERM,
                                               self._on_sigterm)
        except ValueError:
            # not the main thread — excepthook coverage only
            self._prev_sigterm = None

    def uninstall(self) -> None:
        if not self._installed:
            return
        self._installed = False
        if self._prev_excepthook is not None:
            sys.excepthook = self._prev_excepthook
        if self._prev_sigterm is not None:
            try:
                signal.signal(signal.SIGTERM, self._prev_sigterm)
            except ValueError:
                pass

    def _on_sigterm(self, signum, frame):
        self.dump("sigterm")
        prev = self._prev_sigterm
        if callable(prev):
            prev(signum, frame)
        else:
            # re-deliver with the default disposition so the exit
            # status still says "terminated by SIGTERM"
            try:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
            except ValueError:
                pass
            os.kill(os.getpid(), signal.SIGTERM)

    # -- recording ------------------------------------------------------
    @staticmethod
    def _counter_total(name: str) -> float:
        reg = telemetry.MetricsRegistry.get()
        m = reg._metrics.get(name)
        if m is None:
            return 0.0
        return float(sum(m._series.values()))

    def record(self, model, model_name: str, step: int, loss,
               span=None, grad_norm=None, **extra) -> None:
        """Append one step record.  ``loss``/``grad_norm`` may be
        device scalars (kept lazy); ``span`` is the
        ``telemetry.step_span`` whose ``duration`` just closed."""
        if not self.enabled:
            return
        if not self._installed:
            self.install()
        track_model(model, model_name)
        self._n_records += 1
        if self._n_records % self.hbm_sample == 1:
            try:
                self._last_hbm = update_hbm_gauges()
            except Exception:       # noqa: BLE001
                self._last_hbm = []
        rec = {
            "step": int(step),
            "t": time.time(),
            "model": model_name,
            "step_seconds": getattr(span, "duration", None),
            "loss": loss,
            "grad_norm": grad_norm,
            "retraces": self._counter_total("dl4j_retrace_total"),
            "collective_bytes": (
                self._counter_total("dl4j_collective_bytes_total") +
                self._counter_total(
                    "dl4j_dp_update_exchange_bytes_total")),
            "hbm_live_bytes": sum(d["bytes_in_use"]
                                  for d in self._last_hbm),
            "hbm_peak_bytes": sum(d["peak_bytes_in_use"]
                                  for d in self._last_hbm),
        }
        # heaviest layer of the last layerprof report, when one was
        # computed (sys.modules lookup: near-free, and no import edge
        # from diagnostics to layerprof)
        lp = sys.modules.get("deeplearning4j_tpu.common.layerprof")
        if lp is not None:
            top = lp.top_layer()
            if top is not None:
                rec["top_layer"] = top
        if extra:
            rec.update(extra)
        with self._lock:
            self._ring.append(rec)
            while len(self._ring) > self.max_steps:
                self._ring.popleft()

    def records(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    # -- dumping --------------------------------------------------------
    @staticmethod
    def _materialize(v):
        if v is None:
            return None
        try:
            return float(v)
        except Exception as e:      # noqa: BLE001 — a dead buffer must
            return f"<unreadable: {e!r}>"   # not lose the record

    def dump(self, reason: str, event: Optional[dict] = None
             ) -> Optional[str]:
        """Write the ring as JSONL plus a chrome trace of the span
        buffer; returns the JSONL path.  One dump per reason per
        process (a crashing step must not stampede artifacts)."""
        if not self.enabled:
            return None
        with self._lock:
            if reason in self._dumped_reasons:
                return None
            self._dumped_reasons.add(reason)
            ring = list(self._ring)
        base = os.path.join(self.dir,
                            f"flightrec_{os.getpid()}_{reason}")
        path = base + ".jsonl"
        try:
            os.makedirs(self.dir, exist_ok=True)
            with open(path, "w") as f:
                f.write(json.dumps({
                    "record": "meta",
                    "schema_version": SCHEMA_VERSION,
                    "reason": reason,
                    "time": time.time(),
                    "pid": os.getpid(),
                    "n_steps": len(ring),
                    "ring_capacity": self.max_steps,
                    "event": event,
                }) + "\n")
                for rec in ring:
                    out = dict(rec)
                    out["loss"] = self._materialize(rec["loss"])
                    out["grad_norm"] = self._materialize(
                        rec["grad_norm"])
                    f.write(json.dumps(out) + "\n")
            trace = telemetry.export_chrome_trace(base + ".trace.json")
        except Exception as e:      # noqa: BLE001 — dumping is best-
            log.warning("flight recorder dump failed: %r", e)
            return None
        telemetry.counter(
            "dl4j_flightrec_dumps_total",
            "flight-recorder dumps, by trigger reason").inc(
                reason=reason)
        log.warning("flight recorder: dumped %d step records to %s "
                    "(+ %s) reason=%s", len(ring), path, trace, reason)
        self._prune()
        return path

    def _prune(self) -> None:
        """Bounded retention: keep the newest ``keep`` dump pairs in
        the dump directory, delete older ones (a week of preemptions
        must not fill the disk with black boxes)."""
        try:
            dumps = sorted(
                (p for p in os.listdir(self.dir)
                 if p.startswith("flightrec_")
                 and p.endswith(".jsonl")),
                key=lambda p: os.path.getmtime(
                    os.path.join(self.dir, p)))
        except OSError:
            return
        for p in dumps[:-self.keep]:
            for victim in (p, p[:-len(".jsonl")] + ".trace.json"):
                try:
                    os.remove(os.path.join(self.dir, victim))
                except OSError:
                    pass


# ----------------------------------------------------------------------
# the calls the fit funnels make per step
def _close_breakdown(model_name: str, step: int, span,
                     extra: dict) -> None:
    """Close the scaling-observatory breakdown for this step and embed
    its phase decomposition into the flight-recorder record."""
    try:
        bd = stepstats.close_step(model_name, step, span)
    except Exception as e:  # noqa: BLE001 — observability must never
        log.warning("stepstats close failed: %r", e)
        return
    if bd is not None:
        extra.setdefault("phases", bd["phases"])


def record_step(model, model_name: str, step: int, loss, span=None,
                grad_norm=None, **extra) -> None:
    """Flight-recorder append only — for funnels that already ran
    :func:`check_numerics` mid-step (the accumulation path must check
    grads BEFORE the apply step donates their buffers)."""
    from deeplearning4j_tpu.common import faults
    faults.chaos_step()
    _close_breakdown(model_name, step, span, extra)
    rec = FlightRecorder.get()
    if rec.enabled:
        rec.record(model, model_name, step, loss, span,
                   grad_norm=grad_norm, **extra)


def after_step(model, model_name: str, step: int, loss, span=None,
               grad_norm=None, grads=None, params=None,
               **extra) -> None:
    """Record the step into the flight recorder, then run the numerics
    watchdog (which may raise :class:`NumericsEvent`).  Near-free when
    both gates are off: two attribute checks."""
    from deeplearning4j_tpu.common import faults
    faults.chaos_step()
    _close_breakdown(model_name, step, span, extra)
    rec = FlightRecorder.get()
    if rec.enabled:
        rec.record(model, model_name, step, loss, span,
                   grad_norm=grad_norm, **extra)
    check_numerics(model, model_name, step, loss, grad_norm=grad_norm,
                   grads=grads, params=params, recorded=rec.enabled)


def watchdog_enabled() -> bool:
    return Environment.get().numerics_watchdog
