"""Multi-replica serving router: least-loaded dispatch, health-gated
routing, and fleet-wide warm-then-drain rollouts.

The scale-out face of the serving stack. A :class:`ServingRouter`
owns N in-process replicas — each a full
(:class:`~deeplearning4j_tpu.serving.registry.ModelRegistry`,
:class:`~deeplearning4j_tpu.serving.admission.AdmissionController`,
:class:`~deeplearning4j_tpu.serving.server.InferenceServer`) stack on
its own port — and fronts them with one HTTP listener:

- ``POST /v1/models/<name>:predict`` — proxied to the healthy replica
  with the fewest outstanding router-dispatched requests
  (least-loaded). Connection-level failures mark the replica unhealthy
  and the request retries on the next one; application-level statuses
  (429/503/504, with ``Retry-After`` / ``X-Model-Version`` headers)
  relay untouched — shedding is the *replica's* verdict, not a router
  failure.
- ``POST /v1/models/<name>:generate`` — same dispatch; a chunked
  (streaming) replica response is relayed chunk-by-chunk, so each
  token reaches the client the moment the replica emits it.
- ``GET /v1/replicas`` — per-replica health/outstanding/url.
- ``GET /v1/models`` — the first healthy replica's catalog.
- ``GET /healthz`` / ``GET /readyz`` — the fleet answers (ready when
  ≥1 replica is ready).
- ``GET /metrics`` — this process's telemetry registry (replica and
  router metrics share it when replicas are in-process).

:meth:`ServingRouter.rollout` is the fleet version of the registry's
hot-swap protocol: replicas are re-registered **one at a time**, and
each replica warms the new version fully before its live pointer
flips — so at every instant every replica serves *some* warm version
and the fleet never drops or colds a request (warm-then-drain,
fleet-wide).

A background thread polls each replica's ``/healthz`` every
``health_interval_s`` (``dl4j_serving_router_healthy`` mirrors the
verdict); a replica marked down by a failed proxy re-enters rotation
on its next successful poll. Liveness, not readiness, gates rotation:
a live replica with no model yet stays routable (readiness is
answered in-process from its registry), while a dead socket is out.
"""
from __future__ import annotations

import http.client
import json
import re
import threading
import time
from pathlib import Path
from typing import List, Optional

from deeplearning4j_tpu.common import telemetry, tracectx
from deeplearning4j_tpu.common.httputil import (QuietHandler,
                                                start_http_server)
from deeplearning4j_tpu.serving import reqrec
from deeplearning4j_tpu.serving.admission import AdmissionController
from deeplearning4j_tpu.serving.registry import ModelRegistry
from deeplearning4j_tpu.serving.server import InferenceServer
from deeplearning4j_tpu.serving.slo import SLOTracker

_ROUTE_RE = re.compile(r"^/v1/models/([^/:]+):(predict|generate)$")

#: end-to-end headers the proxy relays verbatim in each direction —
#: the trace id crosses BOTH ways, so the replica adopts the router's
#: id and the client reads it back off the response
_RELAY_REQ = ("Content-Type", "X-Deadline-Ms",
              tracectx.TRACE_HEADER)
_RELAY_RESP = ("Content-Type", "Retry-After", "X-Model-Version",
               tracectx.TRACE_HEADER)


def _healthy_gauge() -> telemetry.Gauge:
    return telemetry.gauge(
        "dl4j_serving_router_healthy",
        "router's live health verdict per replica (1 = in rotation, "
        "0 = out after a failed readyz poll or connection error)")


class Replica:
    """One in-process serving stack plus the router's bookkeeping."""

    def __init__(self, name: str, registry: ModelRegistry,
                 admission: AdmissionController,
                 server: InferenceServer):
        self.name = name
        self.registry = registry
        self.admission = admission
        self.server = server
        self.healthy = True
        self._outstanding = 0
        self._lock = threading.Lock()

    @property
    def outstanding(self) -> int:
        return self._outstanding

    def begin(self):
        with self._lock:
            self._outstanding += 1

    def end(self):
        with self._lock:
            self._outstanding = max(0, self._outstanding - 1)

    def set_healthy(self, ok: bool):
        self.healthy = ok
        _healthy_gauge().set(1 if ok else 0, replica=self.name)

    def host_port(self):
        httpd = self.server._httpd
        if httpd is None:       # stopped/crashed replica: connection-
            raise OSError("replica server is not running")  # level fail
        host, port = httpd.server_address[0], httpd.server_address[1]
        if host in ("0.0.0.0", "::"):
            host = "127.0.0.1"
        return host, port

    def describe(self) -> dict:
        return {"name": self.name, "url": self.server.url,
                "healthy": self.healthy,
                "outstanding": self.outstanding,
                "ready": self.registry.ready()
                and not self.admission.draining}


class ServingRouter:
    """N serving replicas behind one least-loaded HTTP front."""

    def __init__(self, n_replicas: int = 2, *, mesh=None,
                 default_buckets=(8, 32),
                 flush_policy: str = "continuous",
                 queue_limit: int = 256,
                 batch_window_ms: float = 2.0,
                 admission_factory=None,
                 request_timeout_s: float = 60.0,
                 health_interval_s: float = 1.0):
        if n_replicas < 1:
            raise ValueError("need at least one replica")
        self.replicas: List[Replica] = []
        for i in range(n_replicas):
            registry = ModelRegistry(
                mesh, default_buckets=default_buckets,
                batch_window_ms=batch_window_ms,
                queue_limit=queue_limit, flush_policy=flush_policy)
            admission = (admission_factory() if admission_factory
                         else AdmissionController())
            server = InferenceServer(
                registry, admission,
                request_timeout_s=request_timeout_s)
            self.replicas.append(
                Replica(f"replica-{i}", registry, admission, server))
        self.health_interval_s = health_interval_s
        self._httpd = None
        self._thread: Optional[threading.Thread] = None
        self._health_thread: Optional[threading.Thread] = None
        self._stopping = False
        self.port: Optional[int] = None

    # ------------------------------------------------------------------
    def start(self, port: int = 0) -> "ServingRouter":
        """Start every replica's server (each on a free port), then
        the router front, then the health poller. Idempotent."""
        if self._httpd is not None:
            return self
        for r in self.replicas:
            r.server.start(0)
            r.set_healthy(True)
        router = self

        class Handler(QuietHandler):
            def do_GET(self):               # noqa: N802
                if self.path == "/v1/replicas":
                    self.send_json({"replicas":
                                    [r.describe()
                                     for r in router.replicas]})
                elif self.path == "/v1/models":
                    rep = router._pick() or router.replicas[0]
                    self.send_json({"models":
                                    rep.registry.describe()})
                elif self.path == "/healthz":
                    self.send_body(b"ok\n", "text/plain")
                elif self.path == "/readyz":
                    ok = any(r.healthy and r.registry.ready()
                             and not r.admission.draining
                             for r in router.replicas)
                    self.send_body(b"ready\n" if ok
                                   else b"not ready\n",
                                   "text/plain", 200 if ok else 503)
                elif self.path == "/metrics":
                    self.send_metrics()
                elif self.path == "/api/slo":
                    # replicas are in-process: the tracker is the
                    # shared process singleton
                    self.send_json(SLOTracker.get().report())
                else:
                    self.send_json({"error": "not found"}, 404)

            def do_POST(self):              # noqa: N802
                m = _ROUTE_RE.match(self.path)
                if not m:
                    if self.path == "/api/reqrec/dump":
                        path = reqrec.get().dump("api")
                        self.send_json({"path": path},
                                       200 if path else 503)
                        return
                    self.send_json({"error": "not found"}, 404)
                    return
                router._proxy(self)

        self._httpd, self._thread = start_http_server(Handler, port)
        # lifecycle transition: assigned before the health thread
        # starts (happens-before), and start/stop are owner-serialized
        # dl4j-lint: disable=lock-discipline
        self.port = self._httpd.server_address[1]
        self._stopping = False
        self._health_thread = threading.Thread(
            target=self._health_loop, daemon=True,
            name="dl4j-tpu-router-health")
        self._health_thread.start()
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0):
        """Stop the front, then every replica (draining by default)."""
        self._stopping = True
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
            self._thread = None
            # lifecycle transition, owner-serialized with start()
            # dl4j-lint: disable=lock-discipline
            self.port = None
        for r in self.replicas:
            r.server.stop(drain=drain, timeout=timeout)
            r.registry.shutdown()

    @property
    def url(self) -> Optional[str]:
        return f"http://127.0.0.1:{self.port}" if self.port else None

    # ------------------------------------------------------------------
    def rollout(self, name: str, model, **register_kw) -> List:
        """Register (or hot-swap) ``name`` across the fleet,
        warm-then-drain one replica at a time.

        ``model`` is a zero-arg factory (called once per replica — the
        safe spelling for in-memory models, since each replica needs
        its own instance), an artifact path (each replica loads its
        own copy), or a single object (shared across replicas; fine
        for read-only serving of small models). ``register_kw`` passes
        through to :meth:`ModelRegistry.register` (warmup_shape, mode,
        latency_slo_ms, ...). Returns the new ModelVersions."""
        versions = []
        for r in self.replicas:
            m = model
            if callable(m) and not hasattr(m, "output") \
                    and not hasattr(m, "_forward"):
                m = m()
            elif isinstance(m, (str, Path)):
                m = str(m)
            # register() warms the new version fully BEFORE flipping
            # this replica's live pointer; the other replicas keep
            # serving their current warm version meanwhile
            versions.append(r.registry.register(name, m,
                                                **register_kw))
        telemetry.counter(
            "dl4j_serving_rollouts_total",
            "fleet-wide warm-then-drain version rollouts completed "
            "per model (every replica re-registered sequentially, "
            "each warmed before its live pointer flipped)"
        ).inc(model=name)
        return versions

    # ------------------------------------------------------------------
    def _pick(self, exclude=()) -> Optional[Replica]:
        """The healthy replica with the fewest outstanding
        router-dispatched requests."""
        alive = [r for r in self.replicas
                 if r.healthy and r not in exclude]
        if not alive:
            return None
        return min(alive, key=lambda r: r.outstanding)

    def _health_loop(self):
        while not self._stopping:
            for r in self.replicas:
                if self._stopping:
                    return
                try:
                    host, port = r.host_port()
                    conn = http.client.HTTPConnection(host, port,
                                                      timeout=2.0)
                    conn.request("GET", "/healthz")
                    ok = conn.getresponse().status == 200
                    conn.close()
                except OSError:
                    ok = False
                r.set_healthy(ok)
            time.sleep(self.health_interval_s)

    # ------------------------------------------------------------------
    def _proxy(self, handler: QuietHandler):
        counted = telemetry.counter(
            "dl4j_serving_router_requests_total",
            "requests dispatched by the router per replica and "
            "relayed HTTP status (replica=none -> no replica could "
            "take the request, 502)")
        # trace id minted at the fleet ingress (or adopted from the
        # client); _RELAY_REQ carries it into the replica, which
        # adopts it — the replica's `request` root span nests inside
        # the router's `req.route` envelope under one id
        tid = tracectx._clean_id(
            handler.headers.get(tracectx.TRACE_HEADER))
        if tid is None and tracectx.request_trace_enabled():
            tid = tracectx.mint_trace_id()
        handler._trace_id = tid
        t0 = time.perf_counter()

        def route_span(replica: str, status) -> None:
            if tid:
                telemetry.span_at(
                    "req.route", telemetry.us_of(t0) * 1e-6,
                    time.perf_counter() - t0, trace=tid,
                    replica=replica, status=str(status))

        body = handler.read_body()
        req_headers = {h: handler.headers[h] for h in _RELAY_REQ
                       if handler.headers.get(h)}
        if tid:
            req_headers[tracectx.TRACE_HEADER] = tid
        tried = []
        while True:
            rep = self._pick(exclude=tried)
            if rep is None:
                counted.inc(replica="none", code="502")
                handler.send_json(
                    {"error": "no healthy replica available"}, 502,
                    {tracectx.TRACE_HEADER: tid} if tid else None)
                route_span("none", 502)
                return
            tried.append(rep)
            rep.begin()
            try:
                host, port = rep.host_port()
                conn = http.client.HTTPConnection(host, port,
                                                  timeout=120.0)
                conn.request("POST", handler.path, body=body,
                             headers=req_headers)
                resp = conn.getresponse()
                chunked = (resp.getheader("Transfer-Encoding", "")
                           .lower() == "chunked")
                resp_headers = {h: resp.getheader(h)
                                for h in _RELAY_RESP
                                if resp.getheader(h)}
                # which replica served is part of the verdict
                resp_headers[tracectx.REPLICA_HEADER] = rep.name
                if tid:
                    resp_headers.setdefault(tracectx.TRACE_HEADER,
                                            tid)
                status = resp.status
                if chunked:
                    # token stream: relay incrementally so the client
                    # sees each token the moment the replica emits it
                    # (no retry past this point — bytes are out)
                    self._relay_stream(handler, rep, resp,
                                       resp_headers, status, counted)
                    conn.close()
                    route_span(rep.name, status)
                    return
                payload = resp.read()
                conn.close()
            except OSError:
                # connection-level failure: out of rotation until the
                # next successful poll; the request retries elsewhere
                rep.set_healthy(False)
                continue
            finally:
                rep.end()
            counted.inc(replica=rep.name, code=str(status))
            ctype = resp_headers.pop("Content-Type",
                                     "application/json")
            handler.send_body(payload, ctype, status,
                              headers=resp_headers)
            route_span(rep.name, status)
            return

    def _relay_stream(self, handler, rep, resp, resp_headers, status,
                      counted):
        """Relay a chunked replica response (the :generate token
        stream) piece by piece. ``http.client`` de-chunks the replica
        side (``read1`` returns each frame as it lands); the router
        re-chunks toward the client. A replica failure mid-stream
        truncates the client's stream (``abort_chunks``); a client
        disconnect just stops the relay — the replica's own disconnect
        handling frees the sequence."""
        ctype = resp_headers.pop("Content-Type",
                                 "application/x-ndjson")
        counted.inc(replica=rep.name, code=str(status))
        handler.begin_chunks(ctype, status, headers=resp_headers)
        try:
            while True:
                piece = resp.read1(65536)
                if not piece:
                    break
                handler.send_chunk(piece)
        except OSError:
            # replica died mid-stream, or the client went away —
            # either way the stream cannot complete cleanly
            handler.abort_chunks()
            return
        handler.end_chunks()
