"""HTTP front end: stdlib ``ThreadingHTTPServer`` over the repository.

Endpoints (KFServing-style verbs, stdlib-only implementation):

* ``POST /v1/models/{name}:predict``  — ``{"inputs": [tensor, ...],
  "timeout_ms": n?}`` where each tensor is a nested JSON list shaped
  like the exported input minus its leading batch dim.  Responds
  ``{"outputs": [...], "timing": {"queue_ms":, "compute_ms":}}``.
* ``GET  /healthz``   — liveness + per-model vitals (the serving twin
  of PR 2's kvstore ``heartbeat`` probe: cheap, never touches the
  device, and reports queue depths so a scheduler can drain early);
  503 while draining.
* ``GET  /metrics``   — Prometheus text exposition.
* ``POST /v1/models/{name}:load``    — ``{"path":, "version"?:,
  "warmup"?:}`` admin verbs; ``:unload``; ``:reload`` (atomic swap,
  in-flight requests finish on the old version).

Each handler thread blocks inside ``DynamicBatcher.submit`` while its
request rides a coalesced batch — ThreadingHTTPServer gives us the
per-request threads, the batcher turns them into bucket-sized device
launches.
"""
from __future__ import annotations

import json
import queue as _queue
import select
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as onp

from ..base import get_env
from .. import fault, flightrec, trace
from ..error import SessionExpiredError, SessionLostError
from .admission import (Admission, BadRequest, ClientDisconnected,
                        ServingError, retry_after_s)
from .metrics import ServingMetrics
from .model_repository import ModelRepository

__all__ = ["InferenceServer", "health_body", "main"]


def health_body(repository, t_start=None, sessions=None):
    """Build the structured ``/healthz`` response: ``(code, body)``.

    Per-model ``state`` is the probe contract the fleet layer routes
    on (docs/serving.md):

    * ``loading``  — a build (initial load, or a reload's replacement)
      is warming; the name is not serving yet (or still serving the
      old version).  A prober must NOT admit a replica on this.
    * ``ready``    — loaded, warmed, taking traffic.
    * ``draining`` — admission stopped; in-flight work finishing.

    Queue depth rides along per model (and summed at the top level) so
    schedulers can shed load before the 429 bound bites.  Per-model
    ``device`` says where the model's parameters live — platform and
    kind as JAX reports them — so a replica serving from the wrong
    platform is visible to whoever probes it.  Shared by
    the HTTP handler and the in-process fleet replicas, so the two
    probe paths can never disagree on shape."""
    draining = repository.admission.draining
    models = {}
    total_depth = 0
    for name, d in repository.models().items():
        total_depth += d["queue_depth"]
        models[name] = {
            "state": "draining" if draining else "ready",
            "version": d["version"],
            "queue_depth": d["queue_depth"],
            "compile_count": d["compile_count"],
            # how expensive this replica's readiness was, and whether
            # the AOT artifact layer carried it (compile_count 0 with
            # aot_buckets = cold start was deserialization) — the
            # numbers an autoscaler sizes spawn lead time from
            "cold_start_ms": d["cold_start_ms"],
            "aot_buckets": d["aot_buckets"],
            "aot_load_failures": d["aot_load_failures"],
            "device": d["device"],
        }
    for name in repository.loading_names():
        if name not in models:
            models[name] = {"state": "loading", "version": None,
                            "queue_depth": 0, "compile_count": None,
                            "cold_start_ms": None, "aot_buckets": [],
                            "aot_load_failures": 0, "device": None}
    body = {
        "status": "draining" if draining else "ok",
        "uptime_s": (round(time.monotonic() - t_start, 3)
                     if t_start is not None else None),
        "queue_depth": total_depth,
        "models": models,
    }
    # stateful sessions ride along (additively — probers that pin the
    # per-model predict shape never see the key unless session models
    # are actually registered): per session model the pinned describe
    # dict, docs/serving.md "Sessions"
    if sessions is not None and sessions.names():
        body["sessions"] = sessions.describe()
        body["queue_depth"] += sum(
            d["queue_depth"] for d in body["sessions"].values())
    # request-scoped tracing rides along additively too: the key only
    # appears while tracing is observably on (sampling enabled or
    # spans recorded), so bare deployments keep their pinned shape
    if trace.active():
        body["trace"] = trace.health_block()
    # same additive discipline for the always-on flight recorder:
    # present only once events were actually recorded
    if flightrec.active():
        body["flight"] = flightrec.health_block()
    return (503 if draining else 200), body


class ServingHTTPServer(ThreadingHTTPServer):
    """Shared listener for the single-server and fleet front ends."""
    daemon_threads = True
    allow_reuse_address = True
    # stdlib default backlog is 5: a burst of >5 concurrent connects
    # overflows the SYN queue and the extras stall a full ~1s TCP
    # retransmit — measured as a 1023ms p99 on an 8-client volley
    request_queue_size = 128


class JSONRequestHandler(BaseHTTPRequestHandler):
    """Shared handler plumbing (JSON send/parse, quiet logging) for
    the single-server and fleet-router front ends — one place to fix
    Content-Length/encoding/backpressure behaviour for both."""

    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        if get_env("MXNET_SERVING_VERBOSE", False, bool):
            BaseHTTPRequestHandler.log_message(self, fmt, *args)

    @property
    def app(self):
        return self.server.app

    def _send(self, code, body, content_type="application/json",
              extra_headers=None):
        data = (body if isinstance(body, bytes)
                else json.dumps(body).encode())
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def _body(self):
        length = int(self.headers.get("Content-Length") or 0)
        if length == 0:
            return {}
        try:
            return json.loads(self.rfile.read(length) or b"{}")
        except (ValueError, UnicodeDecodeError) as e:
            raise BadRequest(f"request body is not JSON: {e}")

    def _query(self):
        """Query-string params of the current request (stdlib-only,
        no cgi): ``?a=b&c=d`` → ``{"a": "b", "c": "d"}``."""
        qs = self.path.partition("?")[2]
        out = {}
        for pair in qs.split("&"):
            k, sep, v = pair.partition("=")
            if sep and k:
                out[k] = v
        return out

    def _trace_dump(self, service):
        """``GET /v1/trace[?trace_id=...]`` — this process's span ring
        as Chrome trace-event JSON (tools/traceview.py merges several
        of these into one cross-process timeline)."""
        tid = self._query().get("trace_id") or None
        self._send(200, trace.export(tid, service=service))

    def _flight_dump(self, service):
        """``GET /v1/flight`` — this process's flight-recorder ring as
        a dump (tools/postmortem.py merges several of these, plus any
        crash/SIGUSR2 dump files, into one incident timeline)."""
        self._send(200, flightrec.export(service=service,
                                         reason="http"))

    @staticmethod
    def parse_session_path(path):
        """``/v1/sessions/{model}:create`` or
        ``/v1/sessions/{model}/{sid}:{verb}`` →
        ``(model, sid_or_None, verb)``; ``None`` for anything else.
        One parser for both front ends — the server and the fleet
        router must never grow different session URL surfaces."""
        if not (path.startswith("/v1/sessions/") and ":" in path):
            return None
        target, _, verb = path[len("/v1/sessions/"):].rpartition(":")
        model, _, sid = target.partition("/")
        if not model or not verb:
            return None
        return model, (sid or None), verb

    # -- client-liveness + chunked streaming --------------------------

    def _client_gone(self):
        """True when the client hung up (EOF/reset on its socket).

        Non-consuming: the byte is MSG_PEEKed, so a keep-alive
        client's *next* pipelined request is left intact.  Used while
        a request is queued — a dead client's request is cancelled so
        it stops consuming device time (``PendingResult.cancel``).

        Known tradeoff (nginx's 499 makes the same call): a client
        that half-closes (``shutdown(SHUT_WR)``) after sending its
        request also reads as EOF here and gets cancelled, even
        though its read side could still take the response.
        Half-closing HTTP clients are vanishingly rare; dead clients
        burning device time are not — the wire optimizes for the
        latter."""
        try:
            r, _, _ = select.select([self.connection], [], [], 0)
            if not r:
                return False
            return self.connection.recv(1, socket.MSG_PEEK) == b""
        except OSError:
            return True

    def _await_pending(self, pending, name, deadline_ms=None,
                       poll_s=0.05):
        """Block on a :class:`~.batcher.PendingResult` while watching
        the client socket; a disconnect cancels the queued request
        (counted in ``mxnet_serving_cancelled_total``) and raises
        :class:`~.admission.ClientDisconnected`."""
        backstop = time.monotonic() + (
            (deadline_ms or 120000.0) / 1000.0 + 10.0)
        while not pending._req.event.wait(poll_s):
            if self._client_gone():
                pending.cancel()
                raise ClientDisconnected(
                    f"client of {name!r} disconnected while queued")
            if time.monotonic() > backstop:
                break
        return pending.result()

    def _start_chunked(self, code=200, extra_headers=None):
        """Begin a ``Transfer-Encoding: chunked`` response (streamed
        session decode): headers out now, body arrives one
        ``_write_chunk`` per decode step."""
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Transfer-Encoding", "chunked")
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()

    def _write_chunk(self, obj):
        """One JSON line as one HTTP chunk.  ``serving.stream_write``
        fires per chunk — an injected fault here is a client-side
        connection loss and must cancel the stream, not wedge it."""
        fault.inject("serving.stream_write")
        data = json.dumps(obj).encode() + b"\n"
        self.wfile.write(b"%x\r\n" % len(data) + data + b"\r\n")
        self.wfile.flush()

    def _end_chunked(self):
        self.wfile.write(b"0\r\n\r\n")
        self.wfile.flush()


class _Handler(JSONRequestHandler):

    # -- routes -------------------------------------------------------

    def do_GET(self):
        path = self.path.split("?", 1)[0]
        if path == "/healthz":
            return self._healthz()
        if path == "/metrics":
            return self._send(200, self.app.metrics.render().encode(),
                              content_type="text/plain; version=0.0.4")
        if path == "/v1/models":
            return self._send(200, {"models": self.app.repository.models()})
        if path == "/v1/trace":
            return self._trace_dump("server")
        if path == "/v1/flight":
            return self._flight_dump("server")
        self._send(404, {"error": "NotFound", "message": path})

    def do_POST(self):
        path = self.path.split("?", 1)[0]
        if path.startswith("/v1/models/") and ":" in path:
            name, _, verb = path[len("/v1/models/"):].rpartition(":")
            handler = {"predict": self._predict, "load": self._load,
                       "unload": self._unload,
                       "reload": self._reload}.get(verb)
            if handler is not None and name:
                return handler(name)
        parsed = self.parse_session_path(path)
        if parsed is not None:
            model, sid, verb = parsed
            if verb == "create" and sid is None:
                return self._session_create(model)
            if sid is not None:
                handler = {"step": self._session_step,
                           "close": self._session_close,
                           "adopt": self._session_adopt}.get(verb)
                if handler is not None:
                    return handler(model, sid)
        self._send(404, {"error": "NotFound", "message": path})

    # -- handlers -----------------------------------------------------

    def _healthz(self):
        code, body = health_body(self.app.repository, self.app.t_start,
                                 sessions=self.app.sessions)
        self._send(code, body)

    def _predict(self, name):
        t0 = time.monotonic()
        code, timing, payload, hdrs = 500, {}, None, None
        # born here, or adopted from the router's hop span via the
        # X-MXNET-TRACE header (garbled → ignored, absent → the local
        # head-sampling decision).  None for unsampled requests — the
        # whole per-request cost of tracing-off is this one call.
        tspan = trace.from_header(self.headers.get(trace.HEADER),
                                  "server.request", model=name)
        try:
            with trace.activate(tspan):
                code, timing, payload = self._predict_inner(name)
        except ClientDisconnected:
            code = 499   # counted, never sent — the socket is gone
            payload = None
        except ServingError as e:
            code = e.http_status
            hdrs = (self.app.retry_headers(name)
                    if code in (429, 503) else None)
            payload = e.payload()
        except fault.TransientFault as e:
            code = 503   # injected front-end fault: client may retry
            payload = {"error": "TransientFault", "message": str(e)}
            hdrs = self.app.retry_headers(name)
            flightrec.note_error("server", e)
        except Exception as e:  # mxlint: allow-broad-except(HTTP boundary: any error becomes a 500 response)
            code = 500
            payload = {"error": type(e).__name__, "message": str(e)}
            # a framework error crossed the server's top boundary: the
            # black box dumps (rate-limited, best-effort) — the 500
            # below still carries the original error
            flightrec.note_error("server", e)
        # record BEFORE sending: the moment the response bytes go out,
        # the client may scrape /metrics, and its own request must
        # already be counted.  Unknown-model 404s are not attributed
        # per-model: arbitrary client-supplied names must not grow the
        # metrics registry.
        if code != 404:
            e2e = (time.monotonic() - t0) * 1000.0
            self.app.metrics.record_request(
                name, code, e2e_ms=e2e,
                compute_ms=timing.get("compute_ms"),
                queue_ms=timing.get("queue_ms"),
                trace_id=tspan.trace_id if tspan is not None else None)
        if tspan is not None:
            tspan.set(code=code,
                      queue_ms=timing.get("queue_ms"),
                      compute_ms=timing.get("compute_ms"))
            tspan.finish(
                outcome="ok" if code == 200 else f"http_{code}")
            # echo the id so the client (and the router's hop span)
            # can fetch /v1/trace for exactly this request
            hdrs = dict(hdrs or {})
            hdrs[trace.HEADER] = trace.header_value(tspan)
        if payload is not None:
            self._send(code, payload, extra_headers=hdrs)

    def _predict_inner(self, name):
        """The predict body proper, run under the request's trace
        context; returns ``(code, timing, payload)`` — errors
        propagate to :meth:`_predict`'s HTTP mapping."""
        # resolve the model FIRST: every later error (400/5xx) is
        # then attributed to a registry-backed name, so arbitrary
        # client-supplied names cannot grow the metrics registry
        entry = self.app.repository.get(name)
        body = self._body()
        if "inputs" not in body or not isinstance(body["inputs"],
                                                  list):
            raise BadRequest('body needs "inputs": [tensor, ...]')
        specs = entry.predictor.meta["inputs"]
        if len(body["inputs"]) != len(specs):
            raise BadRequest(
                f"model {name!r} takes {len(specs)} inputs, got "
                f"{len(body['inputs'])}")
        try:
            arrs = tuple(
                onp.asarray(x, dtype=spec["dtype"])
                for x, spec in zip(body["inputs"], specs))
        except (TypeError, ValueError) as e:
            raise BadRequest(f"malformed input tensor: {e}")
        for a, spec in zip(arrs, specs):
            want = tuple(spec["shape"][1:])
            if tuple(a.shape) != want:
                raise BadRequest(
                    f"instance shape {tuple(a.shape)} != exported "
                    f"instance shape {want}")
        # async submit + disconnect-aware wait: a client that
        # hangs up while its request is queued gets it CANCELLED
        # (the flush worker drops the row before it costs device
        # time) instead of computing into a dead socket
        deadline = body.get("timeout_ms")
        pending = self.app.repository.predict_async(
            name, arrs, deadline)
        out, timing = self._await_pending(pending, name, deadline)
        import jax
        outputs = [o.tolist()
                   for o in jax.tree_util.tree_leaves(out)]
        payload = {"outputs": outputs,
                   "timing": {k: round(v, 3)
                              for k, v in timing.items()
                              if v is not None}}
        return 200, timing, payload

    def _admin(self, name, fn):
        # errors attribute to the name only when it names a loaded
        # model (a failed :load of an arbitrary name must not mint a
        # metrics entry); successes always do — :load just created it
        try:
            result = fn(self._body())
            self.app.metrics.record_request(name, 200)
            self._send(200, result)
        except ServingError as e:
            if e.http_status != 404 and self.app.repository.has(name):
                self.app.metrics.record_request(name, e.http_status)
            self._send(e.http_status, e.payload())
        except Exception as e:  # mxlint: allow-broad-except(HTTP boundary: any error becomes a 500 response)
            if self.app.repository.has(name):
                self.app.metrics.record_request(name, 500)
            self._send(500, {"error": type(e).__name__,
                             "message": str(e)})

    def _load(self, name):
        def fn(body):
            if "path" not in body:
                raise BadRequest('load needs {"path": artifact-prefix}')
            return self.app.repository.load(
                name, body["path"], version=body.get("version"),
                warmup=body.get("warmup"), slo=body.get("slo"))
        self._admin(name, fn)

    def _unload(self, name):
        self._admin(name, lambda body:
                    self.app.repository.unload(name))

    def _reload(self, name):
        def fn(body):
            return self.app.repository.reload(
                name, path=body.get("path"),
                version=body.get("version"),
                warmup=body.get("warmup"), slo=body.get("slo"))
        self._admin(name, fn)

    # -- stateful sessions (docs/serving.md "Sessions") ---------------

    def _session_guarded(self, model, fn):
        """Error→HTTP mapping for the session verbs: eviction/loss are
        410 Gone (typed, terminal for that id — retrying can never
        succeed), overload/drain keep the live-derived Retry-After."""
        code = 500
        tspan = trace.from_header(self.headers.get(trace.HEADER),
                                  "server.session", model=model)
        try:
            with trace.activate(tspan):
                fn()
            code = 200
        except ClientDisconnected:
            code = 499               # counted, nothing sendable
        except (SessionExpiredError, SessionLostError) as e:
            code = 410
            self._send(410, {"error": type(e).__name__,
                             "message": str(e)})
        except ServingError as e:
            code = e.http_status
            hdrs = (self.app.retry_headers(model)
                    if code in (429, 503) else None)
            self._send(code, e.payload(), extra_headers=hdrs)
        except fault.TransientFault as e:
            code = 503
            self._send(503, {"error": "TransientFault",
                             "message": str(e)},
                       extra_headers=self.app.retry_headers(model))
        except Exception as e:  # mxlint: allow-broad-except(HTTP boundary: any error becomes a 500 response)
            code = 500
            self._send(500, {"error": type(e).__name__,
                             "message": str(e)})
        if tspan is not None:
            tspan.set(code=code)
            tspan.finish(
                outcome="ok" if code == 200 else f"http_{code}")
        if model in self.app.sessions.names():
            self.app.metrics.record_request(model, code)

    def _session_create(self, model):
        def fn():
            body = self._body()
            mgr = self.app.sessions.get(model)
            self._send(200, mgr.create(body.get("session_id")))
        self._session_guarded(model, fn)

    def _session_close(self, model, sid):
        def fn():
            self._send(200, self.app.sessions.get(model).close(sid))
        self._session_guarded(model, fn)

    def _session_adopt(self, model, sid):
        """Adopt a session from its latest snapshot (the migration
        verb the fleet router drives after a replica death)."""
        def fn():
            self._send(200, self.app.sessions.get(model).restore(sid))
        self._session_guarded(model, fn)

    def _session_step(self, model, sid):
        def fn():
            body = self._body()
            if "inputs" not in body or not isinstance(body["inputs"],
                                                      list):
                raise BadRequest('body needs "inputs": [tensor, ...]')
            mgr = self.app.sessions.get(model)
            arrs = tuple(body["inputs"])  # dtypes land in check_inputs
            steps = body.get("steps", 1)
            deadline = body.get("timeout_ms")
            if body.get("stream"):
                return self._session_stream(mgr, sid, arrs, steps,
                                            deadline)
            chunks, timing = mgr.step(sid, arrs, steps=steps,
                                      deadline_ms=deadline)
            self._send(200, {
                "session_id": sid, "steps": timing["steps"],
                "outputs": [[onp.asarray(leaf).tolist()
                             for leaf in chunk] for chunk in chunks],
                "timing": {k: round(v, 3)
                           for k, v in timing.items()
                           if v is not None}})
        self._session_guarded(model, fn)

    def _session_stream(self, mgr, sid, arrs, steps, deadline):
        """Chunked-response decode: one JSON line per decode step the
        moment it lands, a final ``done`` (or in-band ``error``) line,
        then the terminating chunk.  Concatenating the per-line
        outputs is bitwise-identical to the non-streamed response
        (the streaming-parity contract).  A broken pipe cancels the
        stream at the next step boundary — dead clients must not keep
        riding the batch."""
        handle = mgr.step(sid, arrs, steps=steps, deadline_ms=deadline,
                          stream=True)
        budget_s = ((deadline or 120000.0) / 1000.0 + 10.0)
        self._start_chunked(200)
        try:
            while True:
                try:
                    kind, payload = handle.chunk_queue.get(
                        timeout=budget_s)
                except _queue.Empty:
                    handle.cancel()
                    self._write_chunk({
                        "error": "DeadlineExceeded",
                        "message": "decode loop stalled",
                        "steps": handle.steps_done})
                    break
                if kind == "chunk":
                    self._write_chunk({
                        "session_id": sid,
                        "outputs": [onp.asarray(leaf).tolist()
                                    for leaf in payload]})
                elif kind == "done":
                    self._write_chunk({
                        "done": True, "session_id": sid,
                        "steps": payload["steps"],
                        "timing": {k: round(v, 3)
                                   for k, v in payload.items()
                                   if v is not None}})
                    break
                else:   # in-band typed error: stream ends, no restart
                    self._write_chunk({
                        "error": type(payload).__name__,
                        "message": str(payload),
                        "steps": handle.steps_done})
                    break
            self._end_chunked()
        except OSError as e:
            # broken pipe / reset / injected serving.stream_write
            # fault: the client is gone — stop decoding for it
            handle.cancel()
            raise ClientDisconnected(
                f"stream client of {mgr.name!r}/{sid} vanished: "
                f"{type(e).__name__}") from e


class InferenceServer:
    """Own the repository + metrics + HTTP listener as one unit."""

    def __init__(self, repository=None, host="127.0.0.1", port=0,
                 metrics=None):
        # adopt the repository's metrics when it already has one, so
        # handler-side counters and batcher-side counters land in the
        # same instance; otherwise rebind the repository (and its live
        # batchers) to ours
        if metrics is None and repository is not None:
            metrics = repository.metrics
        self.metrics = metrics or ServingMetrics()
        self.repository = repository or ModelRepository(
            metrics=self.metrics)
        if self.repository.metrics is not self.metrics:
            self.repository.set_metrics(self.metrics)
        else:
            self.metrics.attach_repository(self.repository)
        # stateful sessions share the repository's admission policy
        # (one drain drains both) and the server's metrics instance
        from .sessions import SessionHost
        self.sessions = SessionHost(
            metrics=self.metrics,
            admission=self.repository.admission,
            snapshot_dir=get_env("MXNET_SERVING_SESSION_DIR", None))
        self.metrics.register_with_profiler()
        self.host = host
        self.port = int(port)
        self.t_start = time.monotonic()
        self._httpd = None
        self._thread = None

    def retry_headers(self, model=None):
        """Live-state ``Retry-After`` for 429/503 responses: current
        queue depth times the observed per-request service time."""
        from .admission import retry_after_s
        depth = sum(self.repository.queue_depths().values())
        depth += sum(self.sessions.queue_depths().values())
        return {"Retry-After": retry_after_s(
            depth, self.metrics.service_ms_estimate(model))}

    def start(self):
        """Bind + serve on a background thread; returns the bound port
        (ephemeral when constructed with port=0)."""
        self._httpd = ServingHTTPServer((self.host, self.port),
                                        _Handler)
        self._httpd.app = self
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="serving-http",
            daemon=True)
        self._thread.start()
        return self.port

    def shutdown(self, drain=True, timeout=30.0):
        """Graceful stop: drain queues first so queued requests get
        real responses (session streams truncate typed and every
        session snapshots, so migration after a drain is lossless),
        then close the listener."""
        if drain:
            self.repository.drain_all(timeout)
            self.sessions.drain_all(timeout)
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        self.metrics.unregister_from_profiler()


def main(argv=None):
    import argparse
    import signal

    p = argparse.ArgumentParser(
        description="mxnet-tpu dynamic-batching inference server")
    p.add_argument("--model", action="append", default=[],
                   metavar="NAME=PREFIX",
                   help="load artifact PREFIX as model NAME at startup")
    p.add_argument("--session-model", action="append", default=[],
                   metavar="NAME=SPEC",
                   help="register a stateful session model from the "
                        "sessions.SESSION_MODELS registry (e.g. "
                        "toy_decoder:dim=16,max_len=32)")
    p.add_argument("--session-dir", default=None,
                   help="shared CRC'd snapshot directory (overrides "
                        "MXNET_SERVING_SESSION_DIR); required for "
                        "cross-replica session migration")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int,
                   default=get_env("MXNET_SERVING_PORT", 8080, int))
    p.add_argument("--no-warmup", action="store_true",
                   help="skip per-bucket warmup compiles at load")
    args = p.parse_args(argv)

    # black box: name this process in flight dumps and arm the SIGUSR2
    # wedge-dump path (docs/observability.md "Flight recorder")
    flightrec.install_signal_handler(proc="server")
    server = InferenceServer(host=args.host, port=args.port)
    if args.session_dir:
        server.sessions.snapshot_dir = args.session_dir
    for spec in args.model:
        name, sep, path = spec.partition("=")
        if not sep:
            p.error(f"--model wants NAME=PREFIX, got {spec!r}")
        server.repository.load(name, path,
                               warmup=not args.no_warmup)
        print(f"[serving] loaded {name} from {path}", flush=True)
    for spec in args.session_model:
        name, sep, model_spec = spec.partition("=")
        if not sep:
            p.error(f"--session-model wants NAME=SPEC, got {spec!r}")
        server.sessions.add(name, model_spec,
                            warmup=not args.no_warmup)
        print(f"[serving] session model {name} = {model_spec}",
              flush=True)
    port = server.start()
    flightrec.record(flightrec.LIFECYCLE, "server.started", port=port,
                     models=sorted(server.repository.models()))
    print(f"[serving] listening on {args.host}:{port}", flush=True)

    done = threading.Event()

    def stop(signum, frame):
        print(f"[serving] signal {signum}: draining", flush=True)
        done.set()

    signal.signal(signal.SIGTERM, stop)
    signal.signal(signal.SIGINT, stop)
    done.wait()
    server.shutdown(drain=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
