"""Unified telemetry spine: metrics registry + one-timeline tracing.

The reference stack's training-health story (StatsListener,
PerformanceListener, the Vert.x UI — SURVEY.md D7/D17) observes the
train loop only.  The perf-critical subsystems grown since (device
prefetcher, compile cache, batched serving) were invisible outside
one-off benchmarks; this module is the process-wide instrument panel
they all report into — the TVM "measure, then tune" discipline
(PAPERS.md 1802.04799) applied to the runtime itself.

Three pieces:

- :class:`MetricsRegistry` — a thread-safe, process-wide registry of
  labeled :class:`Counter`/:class:`Gauge`/:class:`Histogram` metrics.
  Every hot path (prefetch feeder, fit funnels, serving queue,
  checkpoint writer) records into it; it renders as a Prometheus
  text-format page (``UIServer`` serves it at ``/metrics``), folds into
  ``ui.stats`` reports via :class:`MetricsReporterListener`, and lands
  in ``bench.py`` JSON via :meth:`MetricsRegistry.summary`.
- :func:`span` — a context manager recording spans into a shared
  chrome-trace event buffer, in the SAME format
  ``ui.profiling.ProfilingListener`` emits, so host spans, feeder-
  thread spans, and ``jax.profiler`` TPU traces load into one
  chrome://tracing / Perfetto timeline.  :func:`export_chrome_trace`
  writes the buffer; :func:`merge_chrome_traces` folds several trace
  files (ours or jax.profiler's) into one.  Every stamp comes from ONE
  clock, :func:`now_us`; while a ``jax.profiler`` trace is being taken
  each span also lies in its ``/host:CPU`` plane, above the device
  lines.
- ``DL4J_TPU_TELEMETRY`` gate (default on) — when off, every record
  call is a single attribute check and spans don't allocate
  (``benchmarks/bench_telemetry.py`` is the overhead microbench).

Metric names follow Prometheus conventions (``dl4j_`` namespace,
``_seconds``/``_bytes``/``_total`` unit suffixes); the catalog lives in
README "Observability" and ``scripts/check_telemetry_catalog.py`` keeps
code and catalog honest.
"""
from __future__ import annotations

import gzip
import json
import logging
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.common.environment import Environment
from deeplearning4j_tpu.optimize.listeners import TrainingListener

log = logging.getLogger("deeplearning4j_tpu")

#: default latency buckets (seconds) — microseconds (counter overhead,
#: queue pops) up to tens of seconds (BERT-scale compiles, checkpoints)
DEFAULT_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
                   1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)

#: buckets for 0..1 ratios (batch occupancy)
RATIO_BUCKETS = (0.1, 0.25, 0.5, 0.75, 0.9, 1.0)

_LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: dict) -> _LabelKey:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _render_labels(key: _LabelKey, extra: str = "") -> str:
    parts = [f'{k}="{v}"' for k, v in key]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class _Metric:
    """Base: name, help text, per-registry enabled flag shared by
    reference (the registry flips ``_state['on']`` for all metrics at
    once — record calls check one dict slot, no lock)."""

    kind = "untyped"

    def __init__(self, name: str, help: str, state: dict):
        self.name = name
        self.help = help
        self._state = state        # {'on': bool}, shared with registry
        self._lock = threading.Lock()
        self._series: Dict[_LabelKey, object] = {}


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount: float = 1, **labels) -> None:
        if not self._state["on"]:
            return
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0) + amount

    def bind(self, **labels) -> "_BoundCounter":
        """Pre-resolve a label set for per-step hot paths (see
        Histogram.bind)."""
        return _BoundCounter(self, _label_key(labels))

    def value(self, **labels) -> float:
        return self._series.get(_label_key(labels), 0)

    def _render(self) -> List[str]:
        return [f"{self.name}{_render_labels(k)} {v:g}"
                for k, v in sorted(self._series.items())]

    def _snapshot(self):
        return {";".join(f"{k}={v}" for k, v in key) or "": val
                for key, val in self._series.items()}


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        if not self._state["on"]:
            return
        with self._lock:
            self._series[_label_key(labels)] = value

    def inc(self, amount: float = 1, **labels) -> None:
        if not self._state["on"]:
            return
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0) + amount

    def dec(self, amount: float = 1, **labels) -> None:
        self.inc(-amount, **labels)

    def value(self, **labels) -> float:
        return self._series.get(_label_key(labels), 0)

    def _render(self) -> List[str]:
        return [f"{self.name}{_render_labels(k)} {v:g}"
                for k, v in sorted(self._series.items())]

    def _snapshot(self):
        return {";".join(f"{k}={v}" for k, v in key) or "": val
                for key, val in self._series.items()}


class _HistSeries:
    __slots__ = ("counts", "sum", "count", "exemplar")

    def __init__(self, n_buckets: int):
        self.counts = [0] * (n_buckets + 1)     # last slot = +Inf
        self.sum = 0.0
        self.count = 0
        #: most recent (value, {label: value}, unix_ts) exemplar — a
        #: concrete request (trace id) behind the aggregate, OpenMetrics
        #: style, so a bad bucket links to a timeline
        self.exemplar: Optional[Tuple[float, dict, float]] = None


class Histogram(_Metric):
    """Fixed-bucket histogram (Prometheus classic): per-label-set
    bucket counts + sum + count; rendering is cumulative with the
    ``le`` label, as scrapers expect."""

    kind = "histogram"

    def __init__(self, name, help, state,
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help, state)
        self.buckets = tuple(sorted(buckets))

    def observe(self, value: float, **labels) -> None:
        if not self._state["on"]:
            return
        self._observe_key(_label_key(labels), value)

    def observe_with_exemplar(self, value: float, exemplar: dict,
                              **labels) -> None:
        """Observe ``value`` and attach ``exemplar`` (e.g.
        ``{"trace_id": ...}``) to the series — the latest exemplar is
        kept per label set and rendered OpenMetrics-style on the +Inf
        bucket, so a latency spike on a dashboard links to the concrete
        request timeline that produced it."""
        if not self._state["on"]:
            return
        self._observe_key(_label_key(labels), value,
                          exemplar=dict(exemplar))

    def _observe_key(self, key: _LabelKey, value: float,
                     exemplar: Optional[dict] = None) -> None:
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = _HistSeries(len(self.buckets))
            i = 0
            for b in self.buckets:          # linear scan: ~20 buckets,
                if value <= b:              # cheaper than bisect setup
                    break
                i += 1
            s.counts[i] += 1
            s.sum += value
            s.count += 1
            if exemplar is not None:
                s.exemplar = (float(value), exemplar, time.time())

    def exemplar_of(self, **labels) -> Optional[dict]:
        """The latest exemplar attached to a series, as
        ``{"value", "labels", "ts"}`` (None when the series has never
        seen one)."""
        s = self._series.get(_label_key(labels))
        if s is None or s.exemplar is None:
            return None
        value, ex_labels, ts = s.exemplar
        return {"value": value, "labels": dict(ex_labels), "ts": ts}

    def bind(self, **labels) -> "_BoundHistogram":
        """Pre-resolve a label set: the returned handle's ``observe``
        skips per-call label-key construction — for per-step hot
        paths (step_span caches one per model name)."""
        return _BoundHistogram(self, _label_key(labels))

    @contextmanager
    def time(self, **labels):
        """Observe the wall-clock duration of the with-block."""
        if not self._state["on"]:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(time.perf_counter() - t0, **labels)

    def quantile(self, q: float, **labels) -> float:
        """Estimated ``q``-quantile (0..1) from the cumulative bucket
        counts — the ``histogram_quantile`` discipline: linear
        interpolation inside the winning bucket, +Inf observations
        clamp to the top finite edge. NaN with no observations (a
        quantile of an empty series is undefined; 0.0 would read as
        "everything was instant" on a dashboard).
        An estimate bounded by bucket resolution, not an exact order
        statistic — serving benchmarks report p50/p95/p99 from the
        live registry with it."""
        s = self._series.get(_label_key(labels))
        if s is None or s.count == 0:
            return float("nan")
        target = q * s.count
        cum, lo = 0.0, 0.0
        for edge, c in zip(self.buckets, s.counts):
            if c and cum + c >= target:
                return lo + (edge - lo) * (target - cum) / c
            cum += c
            lo = edge
        return self.buckets[-1]

    def count_of(self, **labels) -> int:
        s = self._series.get(_label_key(labels))
        return s.count if s else 0

    def sum_of(self, **labels) -> float:
        s = self._series.get(_label_key(labels))
        return s.sum if s else 0.0

    def _render(self) -> List[str]:
        out = []
        for key, s in sorted(self._series.items()):
            cum = 0
            for b, c in zip(self.buckets, s.counts):
                cum += c
                le = 'le="%g"' % b
                out.append(f"{self.name}_bucket"
                           f"{_render_labels(key, le)} {cum}")
            inf = 'le="+Inf"'
            inf_line = (f"{self.name}_bucket"
                        f"{_render_labels(key, inf)} {s.count}")
            if s.exemplar is not None:
                # OpenMetrics exemplar syntax on the terminal bucket;
                # plain-text scrapers that stop at the value ignore it
                value, ex_labels, ts = s.exemplar
                ex = ",".join(f'{k}="{v}"'
                              for k, v in sorted(ex_labels.items()))
                inf_line += f" # {{{ex}}} {value:g} {ts:.3f}"
            out.append(inf_line)
            out.append(f"{self.name}_sum{_render_labels(key)} {s.sum:g}")
            out.append(f"{self.name}_count{_render_labels(key)}"
                       f" {s.count}")
        return out

    def _snapshot(self):
        return {";".join(f"{k}={v}" for k, v in key) or "": {
                    "count": s.count, "sum": s.sum,
                    "mean": (s.sum / s.count if s.count else 0.0)}
                for key, s in self._series.items()}


class _BoundHistogram:
    __slots__ = ("_h", "_key")

    def __init__(self, h: Histogram, key: _LabelKey):
        self._h = h
        self._key = key

    def observe(self, value: float) -> None:
        if self._h._state["on"]:
            self._h._observe_key(self._key, value)


class _BoundCounter:
    __slots__ = ("_c", "_key")

    def __init__(self, c: Counter, key: _LabelKey):
        self._c = c
        self._key = key

    def inc(self, amount: float = 1) -> None:
        c = self._c
        if c._state["on"]:
            with c._lock:
                c._series[self._key] = \
                    c._series.get(self._key, 0) + amount


class MetricsRegistry:
    """Process-wide, thread-safe metric registry.  Registration is
    idempotent: ``counter(name, ...)`` returns the existing metric when
    ``name`` is already registered (instrument sites in different
    modules share series by name), and raises on a kind mismatch."""

    _instance: Optional["MetricsRegistry"] = None
    _instance_lock = threading.Lock()

    def __init__(self, enabled: Optional[bool] = None):
        if enabled is None:
            enabled = Environment.get().telemetry
        self._state = {"on": bool(enabled)}
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    @classmethod
    def get(cls) -> "MetricsRegistry":
        inst = cls._instance
        if inst is not None:
            return inst
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def _reset_for_tests(cls):
        """Drop the singleton (and the trace buffer) so a test sees a
        clean panel; the next ``get()`` re-reads the env gate."""
        with cls._instance_lock:
            cls._instance = None
        _trace_buffer.clear()
        from deeplearning4j_tpu.common import faults, stepstats
        stepstats.StepStats._reset_for_tests()
        faults._reset_for_tests()
        for hook in list(_reset_hooks):
            hook()

    # -- gate ----------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._state["on"]

    def set_enabled(self, on: bool) -> None:
        self._state["on"] = bool(on)

    # -- registration --------------------------------------------------
    def _register(self, cls, name, help, **kw) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{m.kind}, not {cls.kind}")
                return m
            m = cls(name, help, self._state, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._register(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._register(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_BUCKETS
                  ) -> Histogram:
        return self._register(Histogram, name, help, buckets=buckets)

    # -- export --------------------------------------------------------
    def render_prometheus(self) -> str:
        """Prometheus text exposition format (0.0.4)."""
        lines: List[str] = []
        with self._lock:
            metrics = sorted(self._metrics.values(),
                             key=lambda m: m.name)
        for m in metrics:
            lines.append(f"# HELP {m.name} {m.help}")
            lines.append(f"# TYPE {m.name} {m.kind}")
            lines.extend(m._render())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """{name: {labelkey: value-or-hist-summary}} — the raw panel,
        JSON-serializable (MetricsReporterListener report payload)."""
        with self._lock:
            metrics = list(self._metrics.values())
        return {m.name: m._snapshot() for m in metrics}

    def summary(self) -> dict:
        """Compact snapshot for bench.py JSON: drops empty metrics."""
        return {k: v for k, v in self.snapshot().items() if v}


# ----------------------------------------------------------------------
# module-level conveniences: instrument sites call these; they resolve
# the singleton and are idempotent per metric name
def counter(name: str, help: str = "") -> Counter:
    return MetricsRegistry.get().counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return MetricsRegistry.get().gauge(name, help)


def histogram(name: str, help: str = "",
              buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
    return MetricsRegistry.get().histogram(name, help, buckets=buckets)


def enabled() -> bool:
    return MetricsRegistry.get().enabled


#: callables invoked by MetricsRegistry._reset_for_tests — modules
#: holding their own process-wide singletons (serving.slo,
#: serving.reqrec, common.tracectx) register here at import time so the
#: existing autouse test fixtures reset them too, without this module
#: having to import upward into the serving package
_reset_hooks: List = []


def on_reset(hook) -> None:
    """Register a zero-arg callable to run on every
    ``MetricsRegistry._reset_for_tests()`` (idempotent per hook)."""
    if hook not in _reset_hooks:
        _reset_hooks.append(hook)


# ----------------------------------------------------------------------
# one-timeline tracing: a shared chrome-trace event buffer, same event
# schema as ui.profiling.ProfilingListener so everything merges
class _TraceBuffer:
    """RING buffer: past ``max_events`` the OLDEST events are evicted,
    so a week-long run keeps the most recent window (the flight
    recorder's dump-on-crash wants the end of the run, not the start)
    at bounded host memory.  Evictions count into ``dropped`` (exported
    in trace metadata) and the
    ``dl4j_trace_events_dropped_total`` counter."""

    def __init__(self, max_events: int = 200_000):
        self.max_events = int(os.environ.get(
            "DL4J_TPU_TELEMETRY_MAX_EVENTS", str(max_events)))
        self._lock = threading.Lock()
        self.events: "deque[dict]" = deque()
        self.dropped = 0

    def append(self, ev: dict) -> None:
        n_evicted = 0
        with self._lock:
            self.events.append(ev)
            # max_events is a plain attribute (tests resize it live),
            # so ring capacity is enforced here, not via deque(maxlen)
            while len(self.events) > self.max_events:
                self.events.popleft()
                self.dropped += 1
                n_evicted += 1
        if n_evicted:
            counter("dl4j_trace_events_dropped_total",
                    "chrome-trace span-buffer ring evictions (oldest "
                    "events displaced once the buffer is full)"
                    ).inc(n_evicted)

    def clear(self) -> None:
        with self._lock:
            self.events = deque()
            self.dropped = 0


_trace_buffer = _TraceBuffer()


# ----------------------------------------------------------------------
# the one clock.  Every span, instant and request phase is stamped in
# epoch microseconds derived from ``time.perf_counter_ns()`` through a
# single anchor pair read once, here: the ring's ``ts`` stays on the
# epoch axis the chrome-trace exporters and the flight recorder expect,
# never runs backwards when the wall clock is stepped, and converts
# back to the ``perf_counter`` a caller stamped its own events with.
# (``time.monotonic`` is the same clock as ``time.perf_counter`` on
# Linux, CLOCK_MONOTONIC: phase instants taken with either convert.)
_ANCHOR_WALL_NS = time.time_ns()
_ANCHOR_PERF_NS = time.perf_counter_ns()
_EPOCH_LESS_PERF_NS = _ANCHOR_WALL_NS - _ANCHOR_PERF_NS

#: the anchor: (``time.time()``, ``time.perf_counter()``) of one instant
CLOCK_ANCHOR_S = (_ANCHOR_WALL_NS * 1e-9, _ANCHOR_PERF_NS * 1e-9)


_perf_ns = time.perf_counter_ns


def now_us() -> int:
    """Epoch microseconds on the monotonic clock."""
    return (_perf_ns() + _EPOCH_LESS_PERF_NS) // 1000


def us_of(perf_s: float) -> int:
    """Epoch microseconds of a ``time.perf_counter()`` instant."""
    return (int(perf_s * 1e9) + _EPOCH_LESS_PERF_NS) // 1000


def perf_counter_of(ts_us: float) -> float:
    """The ``time.perf_counter()`` reading (seconds) of a ring ``ts``
    — the inverse of :func:`now_us`."""
    return (ts_us * 1000 - _EPOCH_LESS_PERF_NS) * 1e-9


#: ``args`` keys a span hands down to the spans it causes: the
#: identifier their work shares (an engine or fit iteration, a request)
SHARED_IDS = ("iter", "seq", "trace")

#: this process's id, for the ring's records.  Read once and again in a
#: forked child, not in every span: ``os.getpid()`` is a system call,
#: 5 us in a loop on the sealed machines the chip is reached through
#: and ten times that after the thread has slept, and one in each of an
#: engine iteration's seven spans was most of what the spans cost there
#: (PERF.md section 6, PR 26)
_PID = os.getpid()


def _reread_pid() -> None:
    global _PID
    _PID = os.getpid()


os.register_at_fork(after_in_child=_reread_pid)

#: ``.stack``: (name, args) of the spans open on THIS thread, outermost
#: first — what names a new span's ``parent``
_open_spans = threading.local()


def _import_annotation():
    global _TraceAnnotation
    from jax.profiler import TraceAnnotation
    _TraceAnnotation = TraceAnnotation
    return TraceAnnotation


#: ``jax.profiler.TraceAnnotation``, imported lazily and once: a span's
#: twin on the profiler's clock (one flag check while no profile is
#: being taken)
_TraceAnnotation = None


class _Span:
    """One with-block's span: a ring record and its twin in a running
    ``jax.profiler`` trace.  Hand-rolled (slots, no generator) because
    the decode engine opens seven of these an iteration."""

    __slots__ = ("name", "args", "t0", "t1", "_twin")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.args = attrs

    def __enter__(self) -> dict:
        if not MetricsRegistry.get()._state["on"]:
            self._twin = None
            return self.args
        try:
            stack = _open_spans.stack
        except AttributeError:
            stack = _open_spans.stack = []
        args = self.args
        if stack:
            # caused by the span open round it: its name, and the
            # identifiers it shares where this span gives none
            parent, held = stack[-1]
            args = {"parent": parent}
            for k in SHARED_IDS:
                if k in held:
                    args[k] = held[k]
            args.update(self.args)
            self.args = args
        stack.append((self.name, args))
        # now_us(), inlined: seven spans an engine iteration
        self.t0 = (_perf_ns() + _EPOCH_LESS_PERF_NS) // 1000
        self._twin = (_TraceAnnotation or _import_annotation())(
            self.name, **args)
        return args

    def __exit__(self, *exc):
        twin = self._twin
        if twin is None:
            return False
        self.t1 = t1 = (_perf_ns() + _EPOCH_LESS_PERF_NS) // 1000
        twin.__exit__(None, None, None)
        _open_spans.stack.pop()
        _trace_buffer.append({
            "name": self.name, "ph": "X", "pid": _PID,
            "tid": threading.get_ident() & 0xFFFF,
            "ts": self.t0, "dur": t1 - self.t0, "args": self.args})
        return False


def span(name: str, **attrs) -> _Span:
    """Record a chrome-trace span ("X" event) for the with-block onto
    THIS thread's timeline row, and mirror it into a running
    ``jax.profiler`` trace.  Near-free when telemetry is off.  Attrs
    land in the event's ``args`` beside ``parent`` (the span open round
    this one on this thread) and the identifiers inherited from it
    (:data:`SHARED_IDS`).  ``with span(...) as args`` gives the
    ``args`` dict: what is only known when the block ends (a count of
    what it did) may be added to it."""
    return _Span(name, attrs)


def instant(name: str, **attrs) -> None:
    """Record a zero-duration chrome-trace instant event (retraces,
    cache evictions — things with a WHEN but no duration)."""
    if not MetricsRegistry.get().enabled:
        return
    _trace_buffer.append({
        "name": name, "ph": "i", "s": "p", "pid": _PID,
        "tid": threading.get_ident() & 0xFFFF,
        "ts": now_us(), "args": attrs})


def span_at(name: str, t_wall: float, dur_s: float, **attrs) -> None:
    """Record a chrome-trace span with EXPLICIT start/duration — for
    phases measured by another thread (a batcher flush attributing
    queue wait back to each request) where a with-block cannot wrap
    the interval. ``t_wall`` is epoch seconds on the one clock:
    ``now_us() * 1e-6``, or ``us_of(perf_counter instant) * 1e-6``."""
    if not MetricsRegistry.get().enabled:
        return
    _trace_buffer.append({
        "name": name, "ph": "X", "pid": _PID,
        "tid": threading.get_ident() & 0xFFFF,
        "ts": int(round(t_wall * 1e6)), "dur": max(0, int(dur_s * 1e6)),
        "args": attrs})


def trace_events() -> List[dict]:
    return list(_trace_buffer.events)


def export_chrome_trace(path: str,
                        metadata: Optional[dict] = None) -> str:
    """Write the shared span buffer as chrome://tracing JSON (the
    format ProfilingListener and jax.profiler also emit).  ``metadata``
    keys (e.g. ``host`` / ``clock_offset_s`` stamped by a scaling-
    observatory worker) merge into the document metadata, where
    :func:`merge_host_traces` reads them back."""
    with _trace_buffer._lock:
        events = list(_trace_buffer.events)
        dropped = _trace_buffer.dropped
    meta = {"dropped_events": dropped}
    if metadata:
        meta.update(metadata)
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "metadata": meta}
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def _load_trace(path: str) -> dict:
    op = gzip.open if str(path).endswith(".gz") else open
    with op(path, "rt") as f:
        doc = json.load(f)
    return doc if isinstance(doc, dict) else {"traceEvents": doc}


def merge_chrome_traces(output_path: str, *paths: str) -> str:
    """Concatenate the traceEvents of several chrome-trace files —
    telemetry spans, ProfilingListener iteration spans, and a
    ``jax.profiler`` trace (``.trace.json.gz`` under its log dir) —
    into ONE file whose timeline shows host and device side by side.
    Events already share the epoch-microsecond clock; pids/tids keep
    the sources on separate rows."""
    events: List[dict] = []
    meta: dict = {}
    for p in paths:
        doc = _load_trace(p)
        events.extend(doc.get("traceEvents", []))
        meta.update(doc.get("metadata", {}))
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "metadata": meta}
    with open(output_path, "w") as f:
        json.dump(doc, f)
    return output_path


def merge_host_traces(output_path: str, *sources) -> str:
    """Fold per-HOST trace files into one clock-corrected timeline.

    Each source is either a path (no correction) or a dict::

        {"path": ..., "host": "worker3", "clock_offset_s": 0.012}

    ``clock_offset_s`` is how far that host's clock runs AHEAD of the
    reference (leader) clock — the value ``StepStatsClient`` estimates
    in its connect handshake — so every event timestamp is shifted by
    ``-offset`` to express it on the leader clock; a source omitting it
    falls back to a ``clock_offset_s`` key in its own trace metadata
    (what :func:`export_chrome_trace` stamps on workers).  Pids are
    remapped per source so same-pid workers on different hosts land on
    separate rows, each labeled with its host via ``process_name``
    metadata events."""
    events: List[dict] = []
    meta: dict = {"hosts": []}
    for idx, src in enumerate(sources):
        if isinstance(src, (str, os.PathLike)):
            src = {"path": src}
        doc = _load_trace(src["path"])
        doc_meta = doc.get("metadata", {}) or {}
        host = src.get("host") or doc_meta.get("host") \
            or f"host{idx}"
        offset_s = src.get("clock_offset_s")
        if offset_s is None:
            offset_s = doc_meta.get("clock_offset_s", 0.0)
        shift_us = int(float(offset_s) * 1e6)
        pid_map: Dict[object, int] = {}
        for ev in doc.get("traceEvents", []):
            ev = dict(ev)
            pid = pid_map.get(ev.get("pid"))
            if pid is None:
                pid = 1000 * (idx + 1) + len(pid_map)
                pid_map[ev.get("pid")] = pid
            ev["pid"] = pid
            if "ts" in ev:
                ev["ts"] = int(ev["ts"]) - shift_us
            events.append(ev)
        for pid in sorted(pid_map.values()):
            events.append({
                "name": "process_name", "ph": "M", "pid": pid,
                "tid": 0, "args": {"name": host}})
        meta["hosts"].append({"host": host,
                              "clock_offset_s": float(offset_s),
                              "events": len(doc.get("traceEvents",
                                                    []))})
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "metadata": meta}
    with open(output_path, "w") as f:
        json.dump(doc, f)
    return output_path


_STEP_HELP = ("host-observed train-step wall time: dispatch plus "
              "whatever sync the funnel performs (seconds)")


class _StepSpan(_Span):
    """The fit-funnel instrumentation point: times the with-block into
    the ``dl4j_train_step_seconds`` histogram (labeled by model class)
    AND records a ``train_step`` span — one call site per funnel keeps
    MLN/graph/SameDiff step timing comparable.  The bound histogram is
    cached per model name: this runs once per train step, and the <1%
    overhead budget is measured against millisecond steps."""

    __slots__ = ("duration",)

    def __init__(self, model: str, attrs: dict):
        self.name = "train_step"
        attrs["model"] = model
        self.args = attrs

    def __enter__(self):
        # the clock always runs (even when telemetry is off): the
        # flight recorder reads ``duration`` after the with-block,
        # independent of the metrics gate
        self.duration = 0.0
        _Span.__enter__(self)
        if self._twin is None:
            self.t0 = now_us()
        return self

    def __exit__(self, *exc):
        if self._twin is None:
            self.duration = (now_us() - self.t0) * 1e-6
            return False
        _Span.__exit__(self, *exc)
        self.duration = dt = (self.t1 - self.t0) * 1e-6
        reg = MetricsRegistry.get()
        cache = reg.__dict__.setdefault("_step_bound", {})
        model = self.args["model"]
        b = cache.get(model)
        if b is None:
            b = cache[model] = histogram(
                "dl4j_train_step_seconds",
                _STEP_HELP).bind(model=model)
        b.observe(dt)
        return False


def step_span(model: str, **attrs) -> _StepSpan:
    return _StepSpan(model, attrs)


def observe_feed_stall(seconds: float, source: str) -> None:
    """Time a consumer spent blocked waiting for its next batch —
    non-zero buckets here mean the input pipeline, not the device, is
    the bottleneck (the ladder `benchmarks/bench_input_pipeline.py`
    measures, now visible in production runs)."""
    histogram("dl4j_feed_stall_seconds",
              "time the step loop waited on the input pipeline for "
              "its next batch (seconds)").observe(seconds,
                                                  source=source)
    # route into the scaling observatory's per-step breakdown as
    # data_wait (lazy import: stepstats imports this module)
    from deeplearning4j_tpu.common import stepstats
    stepstats.note_data_wait(seconds, source)


# ----------------------------------------------------------------------
class MetricsReporterListener(TrainingListener):
    """Folds registry snapshots into ``ui.stats`` reports every
    ``frequency`` iterations, so the dashboard (and anything tailing a
    FileStatsStorage JSONL) charts runtime metrics — queue depths,
    cache hits, step-time quantiles — alongside score curves.  Attach
    like any TrainingListener; reports carry a ``telemetry`` key."""

    def __init__(self, storage=None, frequency: int = 10):
        if storage is None:
            from deeplearning4j_tpu.ui.stats import InMemoryStatsStorage
            storage = InMemoryStatsStorage()
        self.storage = storage
        self.frequency = max(1, int(frequency))

    def iteration_done(self, model, iteration: int, epoch: int):
        if iteration % self.frequency:
            return
        self.storage.put_report({
            "iteration": iteration,
            "epoch": epoch,
            "time": time.time(),
            "score": float(model.score()),
            "layers": {},
            "telemetry": MetricsRegistry.get().summary()})
