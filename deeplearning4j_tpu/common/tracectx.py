"""Request-scoped trace context for the serving path.

The serving observatory's propagation layer: a trace id is minted at
ingress (or adopted from an incoming ``X-Dl4j-Trace-Id`` header) and a
:class:`TraceContext` rides the request through
``ServingRouter`` → ``InferenceServer`` → ``AdmissionController`` →
``ServingBatcher``/``DecodeEngine``. Each hop stamps *phase* spans —
``req.admit``, ``req.queue``, ``req.batch_wait``, ``req.device``,
``req.serialize``, ``req.stream`` (plus ``req.ttft`` /
``req.inter_token`` instants for generate) — into the shared
chrome-trace ring with the trace id in ``args``, so one request's life
renders as a single connected timeline under its ``request`` root span
in Perfetto, next to the ``serving.flush`` / ``generate.*`` spans that
already existed.

Two propagation mechanisms, on purpose:

- **ambient** (:func:`bind` / :func:`current`): a ``contextvars``
  slot for code on the request's own handler thread (the access log
  reads it). Handler threads are reused across keep-alive requests, so
  ``bind`` always restores the previous value — the leakage hazard the
  test suite pins.
- **explicit**: cross-thread hops (the batcher's flush worker, the
  decode engine loop) carry the context object itself (on the Future /
  pending tuple) and use :meth:`TraceContext.phase_at` to attribute
  intervals they measured back onto the request's timeline.

Clocks: phase intervals are measured on the process's monotonic clock
(``time.perf_counter``; ``time.monotonic`` is the same clock on Linux)
and put on the unix-epoch microsecond axis chrome-trace uses through
the telemetry spine's ONE anchor (``telemetry.us_of``), so a request's
phases line up with every other span of the process, whichever thread
measured them.

Gate: ``DL4J_TPU_REQUEST_TRACE`` (default ON, and also off whenever
the telemetry spine is off). When off, :func:`start` returns the
falsy :data:`NULL` context whose methods are no-ops — call sites stay
uniform and ``benchmarks/bench_serving.py``'s ``serving_observatory``
leg measures the ≤1% p50 overhead claim of leaving it on.
"""
from __future__ import annotations

import contextvars
import os
import threading
import time
import uuid
from contextlib import contextmanager
from typing import List, Optional, Tuple

from deeplearning4j_tpu.common import telemetry

#: the end-to-end trace id header (request and response direction)
TRACE_HEADER = "X-Dl4j-Trace-Id"
#: stamped by the router: which replica actually served the request
REPLICA_HEADER = "X-Dl4j-Replica"

#: canonical per-request phase names (span name = "req.<phase>")
PHASES = ("admit", "queue", "batch_wait", "device", "serialize",
          "stream")

_MAX_ID_LEN = 64

_enabled_override: Optional[bool] = None

_current: "contextvars.ContextVar[Optional[TraceContext]]" = \
    contextvars.ContextVar("dl4j_trace_ctx", default=None)


def request_trace_enabled() -> bool:
    """The ``DL4J_TPU_REQUEST_TRACE`` gate (AND the telemetry spine's
    own gate — a span with no ring to land in is pure cost)."""
    if not telemetry.enabled():
        return False
    if _enabled_override is not None:
        return _enabled_override
    return os.environ.get("DL4J_TPU_REQUEST_TRACE", "1") not in (
        "0", "false", "False", "no")


def set_enabled(on: Optional[bool]) -> None:
    """Override the env gate in-process (None restores it) — the bench
    leg's on/off A-B without re-execing."""
    global _enabled_override
    _enabled_override = None if on is None else bool(on)


def _reset_for_tests() -> None:
    set_enabled(None)


telemetry.on_reset(_reset_for_tests)


def mint_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def _clean_id(header_value: Optional[str]) -> Optional[str]:
    """An adopted trace id, sanitized: printable, bounded, no
    whitespace — a hostile header must not pollute logs or traces."""
    if not header_value:
        return None
    tid = header_value.strip()
    if not tid or len(tid) > _MAX_ID_LEN:
        return None
    if not all(c.isalnum() or c in "-_." for c in tid):
        return None
    return tid


class TraceContext:
    """One request's identity + timeline. Truthy (the disabled path
    returns the falsy :data:`NULL` instead), thread-safe for the
    cross-thread ``phase_at``/``note`` calls."""

    __slots__ = ("trace_id", "model", "kind", "t0", "phases", "attrs",
                 "verdict", "closed", "_lock")

    def __init__(self, model: str, kind: str,
                 trace_id: Optional[str] = None):
        self.trace_id = trace_id or mint_trace_id()
        self.model = model
        self.kind = kind                    # "predict" | "generate"
        self.t0 = time.perf_counter()       # ingress, monotonic
        #: (phase, start_mono, dur_s) — the recorder's phase breakdown
        self.phases: List[Tuple[str, float, float]] = []
        self.attrs: dict = {}
        self.verdict: Optional[str] = None
        self.closed = False
        self._lock = threading.Lock()

    def __bool__(self) -> bool:
        return True

    # -- clock mapping -------------------------------------------------
    def wall(self, mono_t: float) -> float:
        """A monotonic-clock instant in epoch seconds, through the
        telemetry spine's one anchor."""
        return telemetry.us_of(mono_t) * 1e-6

    # -- phases --------------------------------------------------------
    @contextmanager
    def phase(self, name: str):
        """Time the with-block as phase ``name`` of this request."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phase_at(name, t0, time.perf_counter())

    def phase_at(self, name: str, mono_t0: float,
                 mono_t1: float) -> None:
        """Attribute an already-measured ``[mono_t0, mono_t1]``
        interval to this request as phase ``name`` — the cross-thread
        spelling (batcher flush, decode engine)."""
        dur = max(0.0, mono_t1 - mono_t0)
        with self._lock:
            self.phases.append((name, mono_t0, dur))
        telemetry.span_at(f"req.{name}", self.wall(mono_t0), dur,
                          trace=self.trace_id, model=self.model)

    def instant(self, name: str, **attrs) -> None:
        telemetry.instant(f"req.{name}", trace=self.trace_id,
                          model=self.model, **attrs)

    def note(self, **attrs) -> None:
        """Attach request facts (queue depth, KV blocks, batch
        occupancy) — they land in the root span's args and the flight
        recorder's record."""
        with self._lock:
            self.attrs.update(attrs)

    # -- completion ----------------------------------------------------
    def elapsed_s(self) -> float:
        return time.perf_counter() - self.t0

    def finish(self, verdict) -> float:
        """Close the request: emit the ``request`` root span covering
        ingress→now with the verdict (HTTP status or reason) in args.
        Idempotent — error paths may race the normal path. Returns
        total seconds."""
        with self._lock:
            if self.closed:
                return 0.0
            self.closed = True
            self.verdict = str(verdict)
            attrs = dict(self.attrs)
        dur = self.elapsed_s()
        telemetry.span_at("request", self.wall(self.t0), dur,
                          trace=self.trace_id, model=self.model,
                          kind=self.kind, verdict=self.verdict,
                          **attrs)
        return dur

    def phase_ms(self) -> dict:
        """{phase: total milliseconds} — repeated phases (per-chunk
        device spans) sum."""
        out: dict = {}
        with self._lock:
            for name, _, dur in self.phases:
                out[name] = out.get(name, 0.0) + dur * 1e3
        return out


class _NullContext:
    """Falsy no-op stand-in when request tracing is off: call sites
    keep one shape, the disabled path costs one truthiness check."""

    __slots__ = ()
    trace_id = None
    model = None
    kind = None
    verdict = None
    closed = True

    def __bool__(self) -> bool:
        return False

    @contextmanager
    def phase(self, name: str):
        yield

    def phase_at(self, *a, **kw) -> None:
        pass

    def instant(self, *a, **kw) -> None:
        pass

    def note(self, **kw) -> None:
        pass

    def finish(self, verdict) -> float:
        return 0.0

    def phase_ms(self) -> dict:
        return {}

    def wall(self, mono_t: float) -> float:
        return mono_t

    def elapsed_s(self) -> float:
        return 0.0


NULL = _NullContext()


def start(model: str, kind: str,
          incoming_header: Optional[str] = None):
    """Mint (or adopt, when the ``X-Dl4j-Trace-Id`` request header
    carries a well-formed id) a request trace context — the ingress
    call. Returns :data:`NULL` when the gate is off."""
    if not request_trace_enabled():
        return NULL
    return TraceContext(model, kind,
                        trace_id=_clean_id(incoming_header))


def current():
    """The context bound to this thread of control (None outside a
    request)."""
    return _current.get()


@contextmanager
def bind(ctx):
    """Make ``ctx`` the ambient context for the with-block. ALWAYS
    restores the previous value — handler threads are reused across
    keep-alive requests, and a leaked binding is exactly the
    cross-request contamination the observatory exists to rule out."""
    token = _current.set(ctx if ctx else None)
    try:
        yield ctx
    finally:
        _current.reset(token)
