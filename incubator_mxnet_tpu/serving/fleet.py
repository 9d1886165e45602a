"""Replica fleet: N inference replicas behind one lifecycle manager.

The single-process server (PR 3) dies whole: one crash, one stuck
compile, one reload takes 100% of traffic down.  This module is the
replica layer under the fleet router (:mod:`.router`): it spawns or
adopts N replicas of the same model set, tracks each through an
explicit state machine, probes their health, and walks them one at a
time through zero-downtime rolling reloads.

State machine (per replica)::

    starting ──► warming ──► ready ◄──► draining
        │            │         │            │
        └────────────┴────┬────┴────────────┘
                          ▼
                        dead

* ``starting``  constructed, worker not yet loading
* ``warming``   models loading + per-bucket warmup compiling
* ``ready``     serving; routable iff also probe-``healthy``
* ``draining``  out of rotation (rolling reload / shutdown);
                in-flight requests finish
* ``dead``      killed or exited; never re-admitted

Two replica backends share one interface:

* :class:`ThreadReplica` — an in-process ``ModelRepository`` (its own
  predictors, batchers, compile caches).  Cheap to spawn, the default
  for tests and single-host fleets; a *kill* makes every subsequent
  call raise ``ConnectionResetError``, exactly what a crashed process
  looks like to the router.
* :class:`ProcessReplica` — a real ``python -m ...serving.server``
  subprocess on an ephemeral port, spoken to over HTTP.  True isolation
  (own GIL, own device client, killable with SIGKILL); the backend the
  scaling bench and production use.

Health is double-sourced: an **active prober** hits each ready
replica's ``/healthz`` every ``MXNET_SERVING_FLEET_PROBE_MS`` and
demands structured per-model ``ready`` state (a warming model is not
routable), while the router feeds **passive** per-request outcomes
into the same consecutive-failure budget
(``MXNET_SERVING_FLEET_PROBE_FAILS``).  One success from either source
re-admits.

Fault points: ``serving.probe`` fires before each active probe;
``serving.replica_exec`` fires as a replica accepts a routed request
(both docs/fault_tolerance.md).
"""
from __future__ import annotations

import json
import os
import queue as _queue
import subprocess
import sys
import threading
import time

import numpy as onp

from ..base import get_env
from ..context import child_tpu_chips
from .. import fault, flightrec, trace
from ..error import ReplicaUnavailableError
from ..locks import named_lock
from .admission import (BadRequest, DeadlineExceeded, ModelNotFound,
                        QueueFullError, ServingError, ShuttingDown)

__all__ = ["ReplicaFleet", "ThreadReplica", "ProcessReplica",
           "STARTING", "WARMING", "READY", "DRAINING", "DEAD"]

STARTING = "starting"
WARMING = "warming"
READY = "ready"
DRAINING = "draining"
DEAD = "dead"


class _ReplicaBase:
    """Shared lifecycle + health bookkeeping for both backends."""

    backend = "?"
    chip = None     # the TPU chip a process replica owns (fleet-assigned)

    def __init__(self, rid, models, probe_fails=None):
        self.rid = rid
        self.models = dict(models)          # name -> artifact prefix
        self.state = STARTING
        self._killed = False
        self._healthy = True
        self._fails = 0                     # consecutive probe/request
        self._probe_fails = int(
            probe_fails if probe_fails is not None
            else get_env("MXNET_SERVING_FLEET_PROBE_FAILS", 3, int))
        self._inflight = 0
        self._lock = named_lock("fleet.replica")

    def _to(self, new_state):
        """One state-machine transition, recorded in the flight ring —
        the replica lifecycle IS the story a dead-fleet postmortem
        reconstructs.  No-op (and no event) when the state is already
        ``new_state``."""
        old = self.state
        if old == new_state:
            return
        self.state = new_state
        flightrec.record(flightrec.LIFECYCLE, "replica.state",
                         severity="warn" if new_state == DEAD
                         else "info",
                         replica=self.rid, frm=old, to=new_state)

    # -- routing view -------------------------------------------------

    @property
    def healthy(self):
        return self._healthy

    @property
    def inflight(self):
        return self._inflight

    def routable(self):
        return self.state == READY and self._healthy

    def track(self):
        """Context manager bumping the inflight gauge around one hop."""
        return _Inflight(self)

    # -- health accounting (active probe + passive request outcomes) --

    def note_success(self):
        with self._lock:
            self._fails = 0
            readmitted = not self._healthy
            self._healthy = True
        if readmitted:
            flightrec.record(flightrec.HEALTH, "replica.readmitted",
                             replica=self.rid)

    def note_failure(self):
        """One failed probe or failed routed request.  Returns True
        when this failure crossed the consecutive-failure budget and
        quarantined the replica."""
        with self._lock:
            self._fails += 1
            crossed = self._healthy and self._fails >= self._probe_fails
            if crossed:
                self._healthy = False
        if crossed:
            flightrec.record(flightrec.HEALTH, "replica.quarantined",
                             severity="warn", replica=self.rid,
                             fails=self._fails)
        return crossed

    # -- lifecycle ----------------------------------------------------

    def begin_drain(self):
        if self.state in (READY, WARMING, STARTING):
            self._to(DRAINING)

    def readmit(self):
        """Back into rotation after a drain (rolling reload step done).
        A dead replica stays dead."""
        if self.state == DRAINING and not self._killed:
            self._to(READY)
            self.note_success()

    def kill(self):
        """Simulate/perform a crash: the replica answers nothing ever
        again.  In-flight behaviour is backend-specific (a killed
        process resets its sockets; a killed thread replica lets
        already-executing batches finish — admission dies either way)."""
        self._killed = True
        self._to(DEAD)

    def has_model(self, name):
        """True when this replica serves ``name`` (multi-tenant
        routing filter: replicas no longer all hold the same set)."""
        return name in self.models

    def describe(self):
        return {"state": self.state, "healthy": self._healthy,
                "inflight": self._inflight, "backend": self.backend,
                "models": sorted(self.models)}

    # -- autoscaler signals (defaults; backends refine) ----------------

    def vitals(self):
        """One combined load probe: ``{"queues": {model: depth},
        "sessions": live-session-count, "streams": active-stream-
        count}``.  The autoscaler calls this ONCE per replica per
        tick — for a process replica it is a single ``/healthz``
        round trip, and splitting it per-signal would multiply the
        control loop's I/O.  A dead/unreachable replica reports
        empty."""
        return {"queues": {}, "sessions": 0, "streams": 0}

    def active_streams(self):
        """Streams currently riding this replica's decode loops —
        re-probed fresh each time the shrink path re-checks quiesce
        (a shrink only closes a replica once they reach a step
        boundary).  Queue depths and session counts ride the same
        :meth:`vitals` probe and have no separate accessor: the
        autoscaler consumes the combined sweep."""
        return self.vitals()["streams"]

    # -- interface the backends implement -----------------------------

    def start(self):
        raise NotImplementedError

    def predict(self, name, inputs, deadline_ms=None, inputs_json=None):
        raise NotImplementedError

    def healthz(self):
        raise NotImplementedError

    def admin(self, verb, name, path=None, version=None, warmup=None):
        raise NotImplementedError

    def model_meta(self, name):
        raise NotImplementedError

    def close(self, timeout=30.0):
        raise NotImplementedError

    # stateful sessions (docs/serving.md "Sessions"): the replica owns
    # the carry; the router owns which replica that is (affinity)

    def session_create(self, model, sid=None):
        raise NotImplementedError

    def session_step(self, model, sid, inputs, steps=1,
                     deadline_ms=None, on_chunk=None):
        raise NotImplementedError

    def session_close(self, model, sid):
        raise NotImplementedError

    def session_adopt(self, model, sid):
        raise NotImplementedError


class _Inflight:
    __slots__ = ("_r",)

    def __init__(self, replica):
        self._r = replica

    def __enter__(self):
        with self._r._lock:
            self._r._inflight += 1
        return self._r

    def __exit__(self, *exc):
        with self._r._lock:
            self._r._inflight -= 1
        return False


def _check_replica_exec(rid, name):
    """``serving.replica_exec`` fault hook: a transient fault here is a
    replica-side crash/stall the router's failover must absorb."""
    fault.inject("serving.replica_exec", f"{rid}:{name}")


class ThreadReplica(_ReplicaBase):
    """In-process replica: its own repository, predictors and batchers.

    No HTTP hop — the router calls straight into the repository.  Each
    replica still owns separate compile caches and queues, so fleet
    semantics (independent warmup, independent drain, per-replica
    load) are faithful; only the failure domain is shared."""

    backend = "thread"

    def __init__(self, rid, models, buckets=None, warmup=None,
                 probe_fails=None, session_models=None,
                 session_dir=None):
        super().__init__(rid, models, probe_fails=probe_fails)
        from .model_repository import ModelRepository
        from .sessions import SessionHost
        self.repository = ModelRepository(buckets=buckets)
        self.sessions = SessionHost(
            admission=self.repository.admission,
            snapshot_dir=session_dir, buckets=buckets)
        self._session_models = dict(session_models or {})
        self._warmup = warmup
        self._t_start = time.monotonic()

    def start(self):
        self._to(WARMING)
        try:
            for name, path in self.models.items():
                self.repository.load(name, path, warmup=self._warmup)
            for name, spec in self._session_models.items():
                self.sessions.add(
                    name, spec,
                    warmup=self._warmup is not False)
        except Exception:
            self._to(DEAD)
            raise
        if self.state == WARMING:   # a racing kill()/drain wins
            self._to(READY)
        return self

    def _gone(self):
        if self._killed:
            raise ConnectionResetError(
                f"replica {self.rid} is dead")

    def predict(self, name, inputs, deadline_ms=None, inputs_json=None):
        # in-process hop: typed arrays only — a JSON fallback would
        # lose the exported dtypes (json floats decode as f64)
        self._gone()
        _check_replica_exec(self.rid, name)
        with self.track():
            out, timing = self.repository.predict(name, inputs,
                                                  deadline_ms)
            import jax
            return jax.tree_util.tree_leaves(out), timing

    def healthz(self):
        self._gone()
        from .server import health_body
        return health_body(self.repository, self._t_start,
                           sessions=self.sessions)

    def session_create(self, model, sid=None):
        self._gone()
        return self.sessions.get(model).create(sid)

    def session_step(self, model, sid, inputs, steps=1,
                     deadline_ms=None, on_chunk=None):
        self._gone()
        _check_replica_exec(self.rid, f"{model}/{sid}")
        with self.track():
            mgr = self.sessions.get(model)
            if on_chunk is None:
                return mgr.step(sid, inputs, steps=steps,
                                deadline_ms=deadline_ms)
            handle = mgr.step(sid, inputs, steps=steps,
                              deadline_ms=deadline_ms, stream=True)
            budget_s = ((deadline_ms or 120000.0) / 1000.0 + 10.0)
            chunks = []
            try:
                while True:
                    try:
                        kind, payload = handle.chunk_queue.get(
                            timeout=budget_s)
                    except _queue.Empty:
                        raise DeadlineExceeded(
                            f"stream {model}/{sid} on replica "
                            f"{self.rid} stalled") from None
                    if kind == "chunk":
                        chunks.append(payload)
                        on_chunk(payload)
                    elif kind == "done":
                        return chunks, payload
                    else:
                        raise payload
            except BaseException:
                # covers a RAISING on_chunk relay too (client gone):
                # the decode loop must drop this stream at the next
                # boundary instead of decoding into the void
                handle.cancel()
                raise

    def session_close(self, model, sid):
        self._gone()
        return self.sessions.get(model).close(sid)

    def session_adopt(self, model, sid):
        self._gone()
        return self.sessions.get(model).restore(sid)

    def kill(self):
        """Crash simulation, session edition: the decode loops die
        with the "process" — active streams break typed at the next
        step boundary and NO parting snapshots are written (graceful
        snapshots are ``close()``'s job; a crash only has whatever
        the periodic snapshotter already made durable)."""
        super().kill()
        for name in self.sessions.names():
            self.sessions.get(name).batcher.drain(timeout=5.0)

    def admin(self, verb, name, path=None, version=None, warmup=None,
              slo=None):
        self._gone()
        if verb == "load":
            out = self.repository.load(name, path, version=version,
                                       warmup=warmup, slo=slo)
            self.models[name] = path
            return out
        if verb == "reload":
            out = self.repository.reload(name, path=path,
                                         version=version, warmup=warmup,
                                         slo=slo)
            if path is not None:
                self.models[name] = path
            return out
        if verb == "unload":
            out = self.repository.unload(name)
            self.models.pop(name, None)
            return out
        raise ValueError(f"unknown admin verb {verb!r}")

    def vitals(self):
        if self._killed:
            return {"queues": {}, "sessions": 0, "streams": 0}
        return {"queues": self.repository.queue_depths(),
                "sessions": self.sessions.active_sessions(),
                "streams": self.sessions.active_streams()}

    def model_meta(self, name):
        self._gone()
        return self.repository.get(name).predictor.meta["inputs"]

    def close(self, timeout=30.0):
        self._to(DEAD)
        self.repository.drain_all(timeout)
        # final sync snapshots: a post-drain migration is lossless
        self.sessions.drain_all(timeout)


class ProcessReplica(_ReplicaBase):
    """Subprocess replica: a real ``serving.server`` on an ephemeral
    port, isolated down to its own interpreter and device client."""

    backend = "process"

    def __init__(self, rid, models, warmup=None, probe_fails=None,
                 startup_timeout_s=300.0, session_models=None,
                 session_dir=None, chip=None):
        super().__init__(rid, models, probe_fails=probe_fails)
        self._warmup = warmup
        self.chip = chip   # None where subprocesses run on no TPU
        self._session_models = dict(session_models or {})
        for name, spec in self._session_models.items():
            if not isinstance(spec, str):
                raise ValueError(
                    f"process replicas rebuild session models from "
                    f"registry spec strings; got {type(spec).__name__} "
                    f"for {name!r}")
        self._session_dir = session_dir
        self._startup_timeout_s = float(startup_timeout_s)
        self._proc = None
        self._port = None
        self._port_event = threading.Event()
        self._log_tail: list = []

    @property
    def port(self):
        return self._port

    def start(self):
        self._to(WARMING)
        cmd = [sys.executable, "-m",
               "incubator_mxnet_tpu.serving.server",
               "--host", "127.0.0.1", "--port", "0"]
        for name, path in self.models.items():
            cmd += ["--model", f"{name}={path}"]
        for name, spec in self._session_models.items():
            cmd += ["--session-model", f"{name}={spec}"]
        if self._session_dir is not None:
            cmd += ["--session-dir", str(self._session_dir)]
        if self._warmup is False:
            cmd.append("--no-warmup")
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        # the replica inherits this process's environment — platform
        # (JAX_PLATFORMS), compile-cache directory and all: it runs
        # where the operator pointed the fleet, never on a default
        env = dict(os.environ)
        if self.chip is not None:
            # one process per chip: without these a subprocess claims
            # every chip of the host and the next replica finds none
            env.update(TPU_VISIBLE_CHIPS=str(self.chip),
                       TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                       TPU_PROCESS_BOUNDS="1,1,1")
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        self._proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)
        threading.Thread(target=self._read_stdout,
                         name=f"replica-{self.rid}-log",
                         daemon=True).start()
        if (not self._port_event.wait(self._startup_timeout_s)
                or self._port is None):
            # timed out, or the child exited before binding (the
            # stdout reader sets the event at EOF so a dead child
            # cannot hang the spawn — but it must not look READY)
            self.kill()
            raise ReplicaUnavailableError(
                f"replica {self.rid} did not come up within "
                f"{self._startup_timeout_s:.0f}s: "
                f"{' | '.join(self._log_tail[-5:])}")
        # server.main loads + warms every model BEFORE binding the
        # listener, so "listening" implies warm
        if self.state == WARMING:
            self._to(READY)
        return self

    def _read_stdout(self):
        for line in self._proc.stdout:
            line = line.rstrip()
            self._log_tail.append(line)
            del self._log_tail[:-50]
            if "] listening on " in line and not self._port_event.is_set():
                try:
                    self._port = int(line.rsplit(":", 1)[1])
                except ValueError:
                    continue
                self._port_event.set()
        self._port_event.set()   # EOF: unblock start() to report death

    def _gone(self):
        if self._killed or self._port is None:
            raise ConnectionResetError(f"replica {self.rid} is dead")
        if self._proc is not None and self._proc.poll() is not None:
            if self.state != DEAD:
                # an UNEXPECTED subprocess exit (vs kill()/close(),
                # which transition first) — the event a postmortem
                # anchors a replica death on
                flightrec.record(flightrec.LIFECYCLE, "replica.exited",
                                 severity="error", replica=self.rid,
                                 rc=self._proc.returncode)
            self._to(DEAD)
            raise ConnectionResetError(
                f"replica {self.rid} exited rc={self._proc.returncode}")

    def _http(self, method_path, body=None, timeout_s=30.0,
              headers=None):
        import http.client
        import urllib.error
        import urllib.request
        self._gone()
        method, path = method_path.split(" ", 1)
        hdrs = {"Content-Type": "application/json"}
        if headers:
            hdrs.update(headers)
        req = urllib.request.Request(
            f"http://127.0.0.1:{self._port}{path}", data=body,
            headers=hdrs, method=method)
        try:
            with urllib.request.urlopen(req, timeout=timeout_s) as resp:
                status, raw = resp.status, resp.read()
        except urllib.error.HTTPError as e:
            try:
                payload = json.loads(e.read())
            except ValueError:
                payload = {"error": "HTTPError", "message": str(e)}
            return e.code, payload
        except (urllib.error.URLError, http.client.HTTPException,
                TimeoutError, OSError) as e:
            # ANY transport-level failure on the hop — refused socket,
            # reset or truncated mid-response (a SIGKILLed replica
            # raises IncompleteRead, an HTTPException, NOT a
            # ConnectionError), socket timeout — means this replica is
            # unavailable for this request; typed so the router fails
            # over instead of surfacing a 500
            raise ReplicaUnavailableError(
                f"replica {self.rid}: {type(e).__name__}: {e}") from e
        try:
            return status, json.loads(raw)
        except ValueError as e:
            raise ReplicaUnavailableError(
                f"replica {self.rid}: garbled response body: "
                f"{e}") from e

    @staticmethod
    def _raise_for(code, payload, rid, name):
        msg = f"replica {rid} [{name}]: {payload.get('message', payload)}"
        if code == 429:
            raise QueueFullError(msg)
        if code == 503:
            raise ShuttingDown(msg)
        if code == 504:
            raise DeadlineExceeded(msg,
                                   queue_ms=payload.get("queue_ms"),
                                   compute_ms=payload.get("compute_ms"))
        if code == 404:
            raise ModelNotFound(msg)
        if code == 400:
            raise BadRequest(msg)
        raise ServingError(msg)

    def predict(self, name, inputs, deadline_ms=None, inputs_json=None):
        _check_replica_exec(self.rid, name)
        if inputs_json is None:
            inputs_json = json.dumps(
                [onp.asarray(x).tolist() for x in inputs])
        body = ('{"inputs": %s%s}' % (
            inputs_json,
            f', "timeout_ms": {float(deadline_ms)}' if deadline_ms
            else "")).encode()
        # socket budget trails the request deadline slightly so the
        # server's typed 504 (with its queue/compute split) beats the
        # socket timeout
        timeout_s = (deadline_ms / 1000.0 + 2.0 if deadline_ms
                     else 120.0)
        # propagate the active trace across the process hop: the hop
        # span's id becomes the replica-side parent, so one timeline
        # covers router AND replica (a replica that predates the
        # header just ignores it — single-process trace)
        hval = trace.header_value(trace.current_span())
        with self.track():
            code, payload = self._http(
                f"POST /v1/models/{name}:predict", body, timeout_s,
                headers={trace.HEADER: hval} if hval else None)
        if code != 200:
            self._raise_for(code, payload, self.rid, name)
        return payload["outputs"], payload.get("timing", {})

    def healthz(self):
        return self._http("GET /healthz", timeout_s=10.0)

    def admin(self, verb, name, path=None, version=None, warmup=None,
              slo=None):
        body = {}
        if path is not None:
            body["path"] = path
        if version is not None:
            body["version"] = version
        if warmup is not None:
            body["warmup"] = warmup
        if slo is not None:
            body["slo"] = getattr(slo, "name", slo)
        code, payload = self._http(
            f"POST /v1/models/{name}:{verb}",
            json.dumps(body).encode(), timeout_s=600.0)
        if code != 200:
            self._raise_for(code, payload, self.rid, name)
        if verb == "load" or (verb == "reload" and path is not None):
            self.models[name] = path
        elif verb == "unload":
            self.models.pop(name, None)
        return payload

    def vitals(self):
        empty = {"queues": {}, "sessions": 0, "streams": 0}
        try:
            code, body = self.healthz()
        except (ConnectionError, ServingError):
            return empty
        if code not in (200, 503) or not isinstance(body, dict):
            return empty
        sessions = (body.get("sessions") or {}).values()
        return {
            "queues": {name: int(m.get("queue_depth") or 0)
                       for name, m in (body.get("models")
                                       or {}).items()},
            "sessions": sum(int(s.get("active_sessions") or 0)
                            for s in sessions),
            "streams": sum(int(s.get("active_streams") or 0)
                           for s in sessions),
        }

    def model_meta(self, name):
        code, payload = self._http("GET /v1/models", timeout_s=30.0)
        if code != 200:
            self._raise_for(code, payload, self.rid, name)
        if name not in payload.get("models", {}):
            raise ModelNotFound(f"model {name!r} not on replica "
                                f"{self.rid}")
        return payload["models"][name]["inputs"]

    # -- sessions over the wire ---------------------------------------

    @classmethod
    def _raise_session(cls, code, payload, rid, what):
        """Session errors carry their type in-band; 410 resolves back
        to the typed eviction/loss error the contract names."""
        from ..error import SessionExpiredError, SessionLostError
        err = payload.get("error")
        msg = (f"replica {rid} [{what}]: "
               f"{payload.get('message', payload)}")
        if err == "SessionLostError":
            raise SessionLostError(msg)
        if err == "SessionExpiredError" or code == 410:
            raise SessionExpiredError(msg)
        # in-band stream errors arrive under HTTP 200: resolve the
        # typed class by name, not status
        by_name = {"DeadlineExceeded": DeadlineExceeded,
                   "ShuttingDown": ShuttingDown,
                   "QueueFullError": QueueFullError,
                   "BadRequest": BadRequest,
                   "ModelNotFound": ModelNotFound,
                   "SessionNotFound": ModelNotFound}.get(err)
        if by_name is not None and code == 200:
            raise by_name(msg)
        cls._raise_for(code, payload, rid, what)

    def session_create(self, model, sid=None):
        body = {"session_id": sid} if sid else {}
        code, payload = self._http(
            f"POST /v1/sessions/{model}:create",
            json.dumps(body).encode(), timeout_s=60.0)
        if code != 200:
            self._raise_session(code, payload, self.rid, model)
        return payload

    def session_step(self, model, sid, inputs, steps=1,
                     deadline_ms=None, on_chunk=None):
        _check_replica_exec(self.rid, f"{model}/{sid}")
        body = {"inputs": [onp.asarray(x).tolist() for x in inputs],
                "steps": int(steps)}
        if deadline_ms:
            body["timeout_ms"] = float(deadline_ms)
        timeout_s = (deadline_ms / 1000.0 + 5.0 if deadline_ms
                     else 120.0)
        hval = trace.header_value(trace.current_span())
        with self.track():
            if on_chunk is None:
                code, payload = self._http(
                    f"POST /v1/sessions/{model}/{sid}:step",
                    json.dumps(body).encode(), timeout_s,
                    headers={trace.HEADER: hval} if hval else None)
                if code != 200:
                    self._raise_session(code, payload, self.rid,
                                        f"{model}/{sid}")
                return payload["outputs"], payload.get("timing", {})
            return self._session_stream(model, sid, body, timeout_s,
                                        on_chunk)

    def _session_stream(self, model, sid, body, timeout_s, on_chunk):
        """Streamed hop: relay each chunked JSON line as it arrives.
        A mid-stream transport loss (SIGKILLed replica) surfaces typed
        ``ReplicaUnavailableError`` — with chunks already delivered the
        router must NOT transparently re-run the stream (chunks cannot
        be unsent); the session itself recovers on the next step."""
        import http.client
        import urllib.error
        import urllib.request
        self._gone()
        body = dict(body)
        body["stream"] = True
        hdrs = {"Content-Type": "application/json"}
        hval = trace.header_value(trace.current_span())
        if hval:
            hdrs[trace.HEADER] = hval
        req = urllib.request.Request(
            f"http://127.0.0.1:{self._port}/v1/sessions/{model}/"
            f"{sid}:step", data=json.dumps(body).encode(),
            headers=hdrs)
        chunks = []
        try:
            with urllib.request.urlopen(req, timeout=timeout_s) as resp:
                for line in resp:
                    msg = json.loads(line)
                    if "outputs" in msg:
                        chunks.append(msg["outputs"])
                        on_chunk(msg["outputs"])
                    elif "error" in msg:
                        self._raise_session(
                            200, msg, self.rid, f"{model}/{sid}")
                    else:
                        return chunks, msg.get("timing", {})
            raise ReplicaUnavailableError(
                f"replica {self.rid}: stream for {model}/{sid} ended "
                "without a done line")
        except urllib.error.HTTPError as e:
            try:
                payload = json.loads(e.read())
            except ValueError:
                payload = {"error": "HTTPError", "message": str(e)}
            self._raise_session(e.code, payload, self.rid,
                                f"{model}/{sid}")
        except (urllib.error.URLError, http.client.HTTPException,
                TimeoutError, ValueError, OSError) as e:
            raise ReplicaUnavailableError(
                f"replica {self.rid}: stream for {model}/{sid} broke "
                f"after {len(chunks)} chunk(s): "
                f"{type(e).__name__}: {e}") from e

    def session_close(self, model, sid):
        code, payload = self._http(
            f"POST /v1/sessions/{model}/{sid}:close", b"{}",
            timeout_s=60.0)
        if code != 200:
            self._raise_session(code, payload, self.rid,
                                f"{model}/{sid}")
        return payload

    def session_adopt(self, model, sid):
        code, payload = self._http(
            f"POST /v1/sessions/{model}/{sid}:adopt", b"{}",
            timeout_s=120.0)
        if code != 200:
            self._raise_session(code, payload, self.rid,
                                f"{model}/{sid}")
        return payload

    def kill(self):
        super().kill()
        if self._proc is not None and self._proc.poll() is None:
            self._proc.kill()

    def close(self, timeout=30.0):
        self._to(DEAD)
        self._killed = True
        if self._proc is not None and self._proc.poll() is None:
            self._proc.terminate()
            try:
                self._proc.wait(timeout)
            except subprocess.TimeoutExpired:
                self._proc.kill()
                self._proc.wait(10.0)


_UNPROBED = object()


class ReplicaFleet:
    """Spawn/adopt N replicas; own their lifecycle, health and rolls.

    ``models`` maps model name -> artifact prefix; every replica loads
    the same set.  ``spawn()`` brings all replicas up concurrently and
    starts the active prober.  The router consumes :meth:`pick`
    (least-loaded routable replica) and :meth:`states` (gauges)."""

    def __init__(self, models, n=None, backend="thread", buckets=None,
                 warmup=None, probe_ms=None, probe_fails=None,
                 metrics=None, session_models=None, session_dir=None):
        self.models = dict(models)
        # name -> registry spec string; every replica hosts the same
        # session models, snapshotting into the SHARED session_dir so
        # any survivor can adopt a dead replica's sessions
        self.session_models = dict(session_models or {})
        self.session_dir = (
            session_dir if session_dir is not None
            else get_env("MXNET_SERVING_SESSION_DIR", None))
        self.n = int(n if n is not None
                     else get_env("MXNET_SERVING_FLEET_REPLICAS", 2, int))
        if self.n < 1:
            raise ValueError(f"fleet size must be >= 1, got {self.n}")
        if backend not in ("thread", "process"):
            raise ValueError(
                f"backend must be thread|process, got {backend!r}")
        self.backend = backend
        self.metrics = metrics            # FleetMetrics or None
        self._buckets = buckets
        self._warmup = warmup
        self._probe_ms = float(
            probe_ms if probe_ms is not None
            else get_env("MXNET_SERVING_FLEET_PROBE_MS", 500.0, float))
        self._probe_fails = probe_fails
        self._replicas: list = []
        self._next_rid = 0
        # process backend on a TPU host: chips a subprocess finds
        # (None: its backend is no TPU), probed once at first spawn
        self._tpu_chips = _UNPROBED
        self._meta_cache: dict = {}       # name -> input specs
        self._lock = named_lock("fleet.state")
        self._stop = threading.Event()
        self._prober = None
        # the router-HA membership layer, when one is attached: this
        # fleet's summary() rides every lease beat, so every router in
        # the tier shares one view of every fleet (routerha.fleet_view)
        self.membership = None

    # -- shared membership view ---------------------------------------

    def attach_membership(self, membership):
        """Wire a :class:`~.routerha.RouterHA` to this fleet: the HA
        lease then publishes :meth:`summary` each beat, making this
        fleet part of the router tier's shared membership view."""
        self.membership = membership
        return self

    def summary(self):
        """Compact cross-router fleet view (published in the HA lease
        entry — small on purpose: it is re-written every beat)."""
        states = self.states()
        return {
            "backend": self.backend,
            "replicas": len(states),
            "ready": sum(1 for st in states.values()
                         if st["state"] == "ready" and st["healthy"]),
            "models": sorted(self.models),
            "session_models": sorted(self.session_models),
        }

    # -- lifecycle ----------------------------------------------------

    def _free_chip(self, pending=()):
        """The chip a new process replica will own, or None where
        replica subprocesses run on no TPU.  One process per chip: a
        process replica beyond the host's chips is an error here, at
        spawn — several replicas share a chip through the thread
        backend."""
        if self._tpu_chips is _UNPROBED:
            self._tpu_chips = child_tpu_chips()
        if self._tpu_chips is None:
            return None
        with self._lock:
            busy = {r.chip for r in self._replicas if r.state != DEAD}
        busy.update(r.chip for r in pending)
        free = [c for c in range(self._tpu_chips) if c not in busy]
        if not free:
            raise ValueError(
                f"--backend process needs one TPU chip per replica and "
                f"all {self._tpu_chips} of this host are taken "
                f"({len(busy)} live process replica(s)); use --backend "
                f"thread to let several replicas share a chip")
        return free[0]

    def _new_replica(self, models=None, pending=()):
        with self._lock:
            rid = f"r{self._next_rid}"
            self._next_rid += 1
        models = self.models if models is None else models
        if self.backend == "process":
            return ProcessReplica(rid, models, warmup=self._warmup,
                                  probe_fails=self._probe_fails,
                                  session_models=self.session_models,
                                  session_dir=self.session_dir,
                                  chip=self._free_chip(pending))
        return ThreadReplica(rid, models, buckets=self._buckets,
                             warmup=self._warmup,
                             probe_fails=self._probe_fails,
                             session_models=self.session_models,
                             session_dir=self.session_dir)

    def spawn(self):
        """Bring up all N replicas concurrently; raises if any failed
        to reach ``ready``.  Starts the prober.  Returns ``self``."""
        fresh = []
        for _ in range(self.n):
            fresh.append(self._new_replica(pending=fresh))
        with self._lock:
            self._replicas.extend(fresh)
        errors = []

        def up(r):
            try:
                r.start()
            except Exception as e:  # mxlint: allow-broad-except(collected and re-raised below — a failed replica must not strand the spawn barrier)
                errors.append((r.rid, e))

        threads = [threading.Thread(target=up, args=(r,),
                                    name=f"spawn-{r.rid}", daemon=True)
                   for r in fresh]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            self.shutdown()
            rid, e = errors[0]
            raise ReplicaUnavailableError(
                f"{len(errors)}/{self.n} replicas failed to start "
                f"(first: {rid}: {type(e).__name__}: {e})") from e
        self.start_prober()
        return self

    def spawn_one(self, models=None):
        """Bring up ONE additional replica (the autoscaler's grow
        verb), optionally with its own model subset — ``models=None``
        loads the fleet default set, ``{}`` spawns an empty replica
        the bin-packer then places models onto.  Blocks through load +
        warmup; a failed start leaves the replica out of the list and
        raises."""
        r = self._new_replica(models=None if models is None
                              else dict(models))
        try:
            r.start()
        except Exception as e:
            raise ReplicaUnavailableError(
                f"replica {r.rid} failed to start: "
                f"{type(e).__name__}: {e}") from e
        with self._lock:
            self._replicas.append(r)
        return r

    def remove(self, rid, timeout=30.0):
        """Drain + close one replica and drop it from the fleet (the
        autoscaler's shrink verb — the caller has already waited out
        sessions/in-flight work; ``close`` still snapshots whatever
        remains so a post-shrink migration is lossless)."""
        r = self.get(rid)
        r.begin_drain()
        try:
            r.close(timeout)
        finally:
            with self._lock:
                try:
                    self._replicas.remove(r)
                except ValueError:
                    pass
        return r

    def adopt(self, replica):
        """Take ownership of an externally-built replica (custom
        backend, pre-warmed process) — it is probed and routed like a
        spawned one."""
        with self._lock:
            self._replicas.append(replica)
        return replica

    def shutdown(self, timeout=30.0):
        self.stop_prober()
        with self._lock:
            replicas = list(self._replicas)
        for r in replicas:
            r.begin_drain()
        for r in replicas:
            try:
                r.close(timeout)
            except Exception:  # mxlint: allow-broad-except(best-effort teardown: one broken replica must not leak the rest)
                pass

    # -- routing view -------------------------------------------------

    @property
    def replicas(self):
        with self._lock:
            return list(self._replicas)

    def get(self, rid):
        for r in self.replicas:
            if r.rid == rid:
                return r
        raise KeyError(f"no replica {rid!r}")

    def routable(self, name=None):
        """Routable replicas; with ``name``, only those serving that
        model (multi-tenant packing means replicas differ)."""
        return [r for r in self.replicas
                if r.routable() and (name is None or r.has_model(name))]

    def ready_count(self):
        return len(self.routable())

    def all_draining(self):
        """True when every live replica is draining — the whole fleet
        is going away and new work must get 503 + Retry-After."""
        live = [r for r in self.replicas if r.state != DEAD]
        return bool(live) and all(r.state == DRAINING for r in live)

    def pick(self, exclude=frozenset(), name=None):
        """Least-loaded routable replica, preferring ones not in
        ``exclude`` (already-failed hops).  With ``name``, only
        replicas serving that model are candidates.  When every
        routable replica has been tried, fall back to the least-loaded
        one anyway — a transient double-fault on a 2-replica fleet
        should burn the remaining failover budget, not strand the
        request.

        Last resort: with nothing healthy, READY-but-quarantined
        replicas are still offered.  Quarantine demotes a replica
        below its healthy peers; it must not blackhole a fleet whose
        every survivor is mid-probe-window (a killed peer plus one
        unlucky probe burst used to 503 live requests for up to a
        probe interval).  A successful hop re-admits the replica
        (passive health note); a failed one costs what the immediate
        503 would have cost anyway."""
        candidates = self.routable(name)
        if not candidates:
            candidates = [r for r in self.replicas
                          if r.state == READY
                          and (name is None or r.has_model(name))]
        if not candidates:
            return None
        fresh = [r for r in candidates if r.rid not in exclude]
        pool = fresh or candidates
        return min(pool, key=lambda r: (r.inflight, r.rid))

    def states(self):
        """{rid: {state, healthy, inflight, backend}} — the gauges
        :class:`.metrics.FleetMetrics` exports."""
        return {r.rid: r.describe() for r in self.replicas}

    def kill(self, rid):
        """Chaos verb: hard-kill one replica (process: SIGKILL)."""
        self.get(rid).kill()

    def model_meta(self, name):
        """Input specs for ``name`` from any live replica (the router
        validates requests against these before routing).  Cached —
        for process replicas this is an HTTP hop, and it must not ride
        along on every predict; admin verbs and rolling reloads
        invalidate (a reload may point at a different artifact)."""
        cached = self._meta_cache.get(name)
        if cached is not None:
            return cached
        last = None
        claimants = [r for r in self.replicas
                     if r.state != DEAD and r.has_model(name)]
        if not claimants:
            # nobody is assigned the model.  On a classic fleet (every
            # replica loads self.models) that is an authoritative 404;
            # under autoscaling the router consults the control plane
            # (scale-from-zero) before surfacing it.
            raise ModelNotFound(f"model {name!r} not loaded on any "
                                "replica")
        for r in claimants:
            try:
                specs = r.model_meta(name)
                self._meta_cache[name] = specs
                return specs
            except ModelNotFound:
                if r.state == READY:
                    raise     # authoritative: a serving replica says no
                last = ModelNotFound(f"model {name!r} not loaded")
            except (ConnectionError, ServingError) as e:
                last = e
        raise ReplicaUnavailableError(
            f"no replica could describe model {name!r}") from last

    # -- fleet-wide admin ---------------------------------------------

    def load_everywhere(self, name, path, version=None, warmup=None,
                        slo=None):
        return self._admin_everywhere("load", name, path=path,
                                      version=version, warmup=warmup,
                                      slo=slo)

    def unload_everywhere(self, name):
        return self._admin_everywhere("unload", name)

    def _admin_everywhere(self, verb, name, **kw):
        # control-plane verbs get the same observability as requests
        # (PR 14 traced requests; admin verbs record into the flight
        # ring with their latency, so a slow :load is attributable)
        t0 = time.monotonic()
        out = {}
        try:
            for r in self.replicas:
                if r.state == DEAD:
                    continue
                out[r.rid] = r.admin(verb, name, **kw)
        except BaseException as e:
            flightrec.record(flightrec.SCALING, f"fleet.{verb}",
                             severity="error", model=name,
                             error=type(e).__name__,
                             replicas=len(out),
                             ms=round((time.monotonic() - t0) * 1e3, 3))
            raise
        self._meta_cache.pop(name, None)
        if verb == "load":
            self.models[name] = kw.get("path")
        elif verb == "unload":
            self.models.pop(name, None)
        flightrec.record(flightrec.SCALING, f"fleet.{verb}",
                         model=name, replicas=len(out),
                         ms=round((time.monotonic() - t0) * 1e3, 3))
        return out

    # -- zero-downtime rolling reload ---------------------------------

    def rolling_reload(self, name, path=None, version=None,
                       drain_timeout_s=30.0):
        """Reload ``name`` on every replica in rotation, one at a
        time: drain (out of rotation, in-flight finishes), reload (the
        repository's atomic swap + warmup), re-admit.  Ready capacity
        never drops below ``len(ready) - 1``; a reload failure
        re-admits the replica on its old version and surfaces, leaving
        a mixed-version fleet rather than a smaller one.

        "In rotation" means state READY including probe-quarantined
        replicas: quarantine is temporary, and a skipped unhealthy
        replica would re-admit itself later still serving the OLD
        version with nothing reporting the mixed fleet."""
        targets = [r for r in self.replicas if r.state == READY]
        if not targets:
            raise ReplicaUnavailableError(
                f"no replica in rotation to reload {name!r} on")
        self._meta_cache.pop(name, None)   # new version, new specs
        report = {"model": name, "replicas": [],
                  "min_ready": self.ready_count()}

        def note_ready():
            report["min_ready"] = min(report["min_ready"],
                                      self.ready_count())

        for r in targets:
            t0 = time.monotonic()
            r.begin_drain()
            note_ready()
            deadline = t0 + drain_timeout_s
            while r.inflight > 0 and time.monotonic() < deadline:
                time.sleep(0.002)
            try:
                info = r.admin("reload", name, path=path,
                               version=version)
            except BaseException as e:
                # old version still swapped in (the repository only
                # replaces after a successful build) — re-admit rather
                # than shrink the fleet
                flightrec.record(
                    flightrec.SCALING, "fleet.rolling_reload",
                    severity="error", model=name, replica=r.rid,
                    error=type(e).__name__)
                r.readmit()
                note_ready()
                raise
            r.readmit()
            note_ready()
            report["replicas"].append({
                "replica": r.rid,
                "version": info.get("version"),
                "ms": round((time.monotonic() - t0) * 1000.0, 3)})
        # a meta lookup that raced the roll may have cached the OLD
        # version's specs; drop it so the next one sees the new fleet
        self._meta_cache.pop(name, None)
        flightrec.record(
            flightrec.SCALING, "fleet.rolling_reload", model=name,
            replicas=len(report["replicas"]),
            min_ready=report["min_ready"],
            ms=round(sum(r["ms"] for r in report["replicas"]), 3))
        return report

    # -- active health probing ----------------------------------------

    def start_prober(self):
        if self._prober is not None and self._prober.is_alive():
            return
        self._stop.clear()
        self._prober = threading.Thread(target=self._probe_loop,
                                        name="fleet-prober",
                                        daemon=True)
        self._prober.start()

    def stop_prober(self):
        self._stop.set()
        if self._prober is not None:
            self._prober.join(5.0)
            self._prober = None

    def probe_once(self):
        """One active probe sweep (the prober loop body; callable
        directly from tests).  Only replicas in rotation are scored —
        warming and draining are lifecycle states, not health
        failures."""
        for r in self.replicas:
            if r.state not in (READY,):
                continue
            ok = False
            try:
                fault.inject("serving.probe", r.rid)
                code, body = r.healthz()
                models = body.get("models", {})
                # the contract is per-REPLICA: a multi-tenant replica
                # only owes the models packed onto it, not the fleet
                # union (on a classic fleet r.models == self.models)
                ok = (code == 200
                      and set(r.models) <= set(models)
                      and all(m.get("state") == "ready"
                              for m in models.values()))
            except Exception:  # mxlint: allow-broad-except(a probe that cannot complete IS the failure signal being counted)
                ok = False
            if ok:
                r.note_success()
            else:
                r.note_failure()
                if self.metrics is not None:
                    self.metrics.record_probe_failure(r.rid)

    def _probe_loop(self):
        while not self._stop.wait(self._probe_ms / 1000.0):
            self.probe_once()
