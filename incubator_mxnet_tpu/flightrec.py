"""Always-on flight recorder: the per-process operational black box.

Request-scoped tracing (:mod:`.trace`, PR 14) answers "where did THIS
request spend its time" — but it is head-sampled and request-shaped.
When a replica dies, the autoscaler makes a bad eviction, or a chaos
run leaves a wedged fleet, what explains the incident is the
*control-plane history* that preceded it: state transitions, scaling
ticks, quarantines, evictions, membership changes, compile storms.
This module is that record — the aviation black box next to the
cockpit voice recorder:

* **Always on** — a bounded ring of structured events
  ``(t, category, name, severity, fields, trace_id?)`` per process
  (``MXNET_FLIGHT_RING``, default 2048; ``0`` disables).  Emitters
  fire only on *operationally interesting* transitions (a healthy
  request appends nothing), so the steady-state cost is zero and the
  emit cost itself is one deque append (microbenched by
  ``serving_bench --flight-check``).
* **Categories** — ``lifecycle`` (process/replica/model state),
  ``scaling`` (autoscaler decisions + admin verbs), ``placement``
  (reservations/evictions under the HBM budget), ``health``
  (quarantine/readmit, failed hops, failover, hedging), ``fault``
  (every fired injection, mirroring the span event so chaos artifacts
  are self-explaining in BOTH systems), ``compile`` (executor builds,
  sentinel storms), ``checkpoint``, ``membership`` (PS join/leave/
  evict, trainer evict/rejoin, and the HA router tier's lease
  lifecycle: ``router.lease.acquired/renewed/expired``,
  ``router.lease.beat_lost``, ``router.takeover.started/completed``,
  ``router.forwarded``, ``router.exited`` — the chain
  ``router.lease.expired → router.takeover.started →
  session.restored`` is what ``tools/postmortem.py --gate`` asserts
  after a router kill), ``session``.
* **Monotonic-anchored** — event timestamps are monotonic
  (MX-TIME001); export places them on a shared cross-process timeline
  via :func:`.trace.anchor`, the ONE wall-clock anchor this process
  captured — flight dumps and trace dumps therefore merge onto the
  same timeline (``tools/postmortem.py``).
* **Dump triggers** — (a) a typed framework error crossing a server/
  router/trainer top-level boundary writes
  ``MXNET_FLIGHT_DIR/<proc>-<pid>.flight.json`` (rate-limited by
  ``MXNET_FLIGHT_DUMP_MIN_S``, best-effort, and NEVER masks the
  original error); (b) ``SIGUSR2`` dumps ring + all thread stacks +
  a metrics snapshot + recent trace ids — the "the process is wedged,
  tell me why" path; (c) ``GET /v1/flight`` on server and router for
  live inspection.

``tools/postmortem.py`` (stdlib, jax-free) merges any number of
flight + trace dumps into one causal timeline and reconstructs an
incident across processes (docs/observability.md "Flight recorder").
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque

from .base import get_env
from . import trace as _trace
from .locks import named_lock

__all__ = [
    "CATEGORIES", "EVENTS", "EVENT_PREFIXES",
    "LIFECYCLE", "SCALING", "PLACEMENT", "HEALTH",
    "FAULT", "COMPILE", "CHECKPOINT", "MEMBERSHIP", "SESSION",
    "Event", "enabled", "active", "configure", "reset", "record",
    "events", "stats", "health_block", "export", "export_json",
    "dump", "note_error", "install_signal_handler", "proc_name",
    "ring_capacity", "flight_dir", "dump_path",
]

LIFECYCLE = "lifecycle"
SCALING = "scaling"
PLACEMENT = "placement"
HEALTH = "health"
FAULT = "fault"
COMPILE = "compile"
CHECKPOINT = "checkpoint"
MEMBERSHIP = "membership"
SESSION = "session"

#: The closed category vocabulary — :func:`record` rejects anything
#: else (a typo'd category would silently shear the postmortem views).
CATEGORIES = (LIFECYCLE, SCALING, PLACEMENT, HEALTH, FAULT, COMPILE,
              CHECKPOINT, MEMBERSHIP, SESSION)
_CATEGORY_SET = frozenset(CATEGORIES)

#: The registered event-NAME vocabulary (mxlint MX-FLIGHT001).  Names
#: were free strings until a ``postmortem --gate`` list drifted from
#: its emitter and the mismatch surfaced only at chaos-stage runtime —
#: exactly the failure mode fault.POINTS closed for inject sites.  Now
#: every static ``record(category, "name")`` call in the linted
#: surface must name an entry here, and every gate string
#: (``postmortem --gate ev1,ev2`` argv or ``Incident(gate=...)``) must
#: too.  Keep the tuple sorted; an emitter with a new name adds its
#: row in the same PR.
EVENTS = (
    "bench.emit",
    "boundary.error",
    "checkpoint.fallback",
    "checkpoint.reshard",
    "checkpoint.restored",
    "checkpoint.save",
    "checkpoint.unrecoverable",
    "checkpoint.write_failed",
    "compile.storm",
    "executor.created",
    "fleet.rolling_reload",
    "jit.compiled",
    "lock.order_violation",
    "model.loaded",
    "model.unloaded",
    "model.unplaceable",
    "placer.blocked",
    "placer.evict",
    "replica.exited",
    "replica.quarantined",
    "replica.readmitted",
    "replica.state",
    "router.exited",
    "router.failover",
    "router.forwarded",
    "router.hedge_launched",
    "router.hedge_won",
    "router.hop_failed",
    "router.lease.acquired",
    "router.lease.beat_lost",
    "router.lease.expired",
    "router.lease.renewed",
    "router.scale_from_zero",
    "router.started",
    "router.takeover.completed",
    "router.takeover.started",
    "scale.apply",
    "scale.decide",
    "scale.dropped",
    "scale.failed",
    "scale.from_zero",
    "server.started",
    "session.created",
    "session.evicted",
    "session.lost",
    "session.migrated",
    "session.restored",
    "sigusr2.dump",
    "trainer.evicted",
    "trainer.rejoined",
    "worker.evicted",
    "worker.joined",
    "worker.left",
)

#: Prefix families for dynamically-formed names: ``fault.{point}``
#: (suffix validated against ``fault.POINTS`` — the two registries
#: compose) and ``fleet.{verb}`` (admin verbs fan out per call site).
EVENT_PREFIXES = ("fault.", "fleet.")

_SEVERITIES = frozenset(("info", "warn", "error"))


class Event:
    """One flight-recorder entry.  Immutable after construction; the
    ring stores these directly (no serialization on the emit path)."""

    __slots__ = ("t", "category", "name", "severity", "fields",
                 "trace_id")

    def __init__(self, t, category, name, severity, fields, trace_id):
        self.t = t                   # monotonic seconds
        self.category = category
        self.name = name
        self.severity = severity
        self.fields = fields         # dict or None
        self.trace_id = trace_id     # 16-hex id or None

    def __repr__(self):
        return (f"Event({self.category}:{self.name} "
                f"sev={self.severity} t={self.t:.3f})")


# ---------------------------------------------------------------------------
# configuration + ring
# ---------------------------------------------------------------------------

_lock = named_lock("flightrec.cfg")
_cfg = {"ring": None, "dir": None, "dump_min_s": None, "proc": None}
_provider_registered = False


def ring_capacity():
    n = _cfg["ring"]
    if n is None:
        n = _cfg["ring"] = max(
            0, get_env("MXNET_FLIGHT_RING", 2048, int))
    return n


def flight_dir():
    d = _cfg["dir"]
    if d is None:
        d = _cfg["dir"] = get_env("MXNET_FLIGHT_DIR", "") or ""
    return d or None


def dump_min_s():
    s = _cfg["dump_min_s"]
    if s is None:
        s = _cfg["dump_min_s"] = max(
            0.0, get_env("MXNET_FLIGHT_DUMP_MIN_S", 10.0, float))
    return s


def proc_name():
    """Process label in dumps/exports ("router", "server", ...).  Set
    by the serving CLIs (and :func:`configure`); defaults to the
    executable's role-agnostic ``proc``."""
    return _cfg["proc"] or "proc"


def enabled():
    """Recording on (``MXNET_FLIGHT_RING`` > 0 — the default)."""
    return ring_capacity() > 0


class _Ring:
    """Bounded event store.  The append path is deliberately LOCK-FREE:
    one ``deque.append`` (atomic under the GIL, maxlen evicts
    oldest-first in the same op) plus one counter bump.  No lock may
    sit on this path — the SIGUSR2 handler records too, and a handler
    blocking on a lock its interrupted thread holds would wedge the
    process the signal exists to diagnose.  The tradeoff: concurrent
    ``pushed += 1`` bumps can interleave at a bytecode boundary, so
    under heavy multi-thread emission the counter may run slightly
    LOW; eviction is derived (``pushed - len(ring)``, clamped at 0) —
    exact single-threaded, at-most-under concurrent."""

    __slots__ = ("cap", "_d", "pushed")

    def __init__(self, cap):
        self.cap = int(cap)
        self._d = deque(maxlen=self.cap)
        self.pushed = 0

    def push(self, ev):
        self._d.append(ev)
        self.pushed += 1

    def snapshot(self):
        return list(self._d)

    @property
    def evicted(self):
        return max(0, self.pushed - len(self._d))


_ring_obj = None

# dump bookkeeping (process-wide; reset() clears)
_dump_state = {
    "written": 0, "rate_limited": 0, "failures": 0,
    "sigusr2": 0, "sigusr2_dropped": 0,
    "last_dump_mono": None, "dumping": False,
}


def _ring():
    # LOCK-FREE first-use init (signal-path constraint, see _Ring):
    # two threads racing here build two rings and the first GIL-atomic
    # global assignment wins — the loser's ring (holding at most the
    # loser's own first event) is discarded.  Benign next to a handler
    # deadlocking on the module lock.
    global _ring_obj
    r = _ring_obj
    if r is None:
        r = _Ring(max(1, ring_capacity()))
        if _ring_obj is None:
            _ring_obj = r
        r = _ring_obj
    return r


def configure(ring=None, dir=None, proc=None, dump_min_s=None):
    """Programmatic override of the env knobs (tests, CLIs).  ``None``
    keeps the current value; changing ``ring`` re-allocates an empty
    ring (``0`` disables recording)."""
    global _ring_obj
    with _lock:
        if ring is not None:
            _cfg["ring"] = max(0, int(ring))
            _ring_obj = _Ring(max(1, _cfg["ring"]))
        if dir is not None:
            _cfg["dir"] = str(dir)
        if proc is not None:
            _cfg["proc"] = str(proc)
        if dump_min_s is not None:
            _cfg["dump_min_s"] = max(0.0, float(dump_min_s))


def reset():
    """Forget overrides, recorded events and dump counters; next use
    re-reads the env (test isolation)."""
    global _ring_obj
    with _lock:
        for k in _cfg:
            _cfg[k] = None
        _ring_obj = None
        _dump_state.update(written=0, rate_limited=0, failures=0,
                           sigusr2=0, sigusr2_dropped=0,
                           last_dump_mono=None, dumping=False)


def active():
    """Recording is observably on: enabled AND at least one event
    landed.  Gates the additive ``"flight"`` block in /healthz +
    describe() — a process that recorded nothing keeps its bare
    pinned shape."""
    return (enabled() and _ring_obj is not None
            and _ring_obj.pushed > 0)


def _ensure_provider():
    global _provider_registered
    if _provider_registered:
        return
    _provider_registered = True
    from . import profiler
    profiler.register_stats_provider("flight", stats)


# ---------------------------------------------------------------------------
# the emitter API
# ---------------------------------------------------------------------------

def record(category, name, severity="info", **fields):
    """Append one event to the ring — THE emitter call.

    Near-zero cost and exception-free by contract: emitters sit inside
    state machines (probe sweeps, PS command handlers, the autoscaler
    loop) that must never be broken by their own observability.  The
    category/severity vocabulary IS validated (a typo would silently
    shear every postmortem view), but that check is deterministic —
    any test that exercises the emitter catches it.

    ``trace_id`` may be passed explicitly in ``fields``; otherwise the
    active request trace (if any) is stamped on, linking the black box
    to the request-scoped layer."""
    if not enabled():
        return
    if category not in _CATEGORY_SET:
        raise ValueError(
            f"flightrec.record: unknown category {category!r} "
            f"(known: {', '.join(CATEGORIES)})")
    if severity not in _SEVERITIES:
        raise ValueError(
            f"flightrec.record: severity must be info|warn|error, "
            f"got {severity!r}")
    tid = fields.pop("trace_id", None) or _trace.current_trace_id()
    _ring().push(Event(time.monotonic(), category, name, severity,
                       fields or None, tid))
    _ensure_provider()


def events(category=None, name=None, severity=None):
    """Recorded events, oldest first, optionally filtered."""
    out = _ring().snapshot()
    if category is not None:
        out = [e for e in out if e.category == category]
    if name is not None:
        out = [e for e in out if e.name == name]
    if severity is not None:
        out = [e for e in out if e.severity == severity]
    return out


def stats():
    """The ``flight`` profiler stats provider."""
    r = _ring()
    return {
        "enabled": enabled(),
        "ring_capacity": ring_capacity(),
        "events_recorded": r.pushed,
        "events_in_ring": len(r._d),
        "events_evicted": r.evicted,
        "dumps_written": _dump_state["written"],
        "dumps_rate_limited": _dump_state["rate_limited"],
        "dump_failures": _dump_state["failures"],
        "sigusr2_dumps": _dump_state["sigusr2"],
        "sigusr2_dropped": _dump_state["sigusr2_dropped"],
    }


def health_block():
    """The additive ``"flight"`` block for /healthz + describe() —
    present only while :func:`active` (bare processes keep their
    pinned shape).  ``dumps`` counts dump FILES written (crash and
    SIGUSR2 alike — both go through :func:`dump`, which owns the
    counter; a stderr-fallback SIGUSR2 dump is not a file)."""
    r = _ring()
    return {"ring": ring_capacity(), "events": r.pushed,
            "evictions": r.evicted,
            "dumps": _dump_state["written"]}


# ---------------------------------------------------------------------------
# export + dumps
# ---------------------------------------------------------------------------

def _wall_us(t_mono):
    aw, am = _trace.anchor()
    return int((aw + (t_mono - am)) * 1e6)


def export(service=None, reason="inspect"):
    """The ring as one JSON-ready dict.  Event timestamps are exported
    in wall microseconds via the shared per-process anchor, so dumps
    from several processes merge onto one timeline
    (``tools/postmortem.py``)."""
    evs = []
    for e in _ring().snapshot():
        evs.append({
            "ts_us": _wall_us(e.t),
            "category": e.category,
            "name": e.name,
            "severity": e.severity,
            "fields": e.fields,
            "trace_id": e.trace_id,
        })
    r = _ring()
    return {
        "flight": 1,
        "proc": service or proc_name(),
        "pid": os.getpid(),
        "reason": reason,
        "dumped_ts_us": _wall_us(time.monotonic()),
        "ring": ring_capacity(),
        "recorded": r.pushed,
        "evicted": r.evicted,
        "events": evs,
    }


def export_json(service=None, reason="inspect"):
    return json.dumps(export(service, reason))


def dump_path(suffix=""):
    """``MXNET_FLIGHT_DIR/<proc>-<pid>[suffix].flight.json`` — or
    ``None`` when no dump directory is configured."""
    d = flight_dir()
    if d is None:
        return None
    return os.path.join(
        d, f"{proc_name()}-{os.getpid()}{suffix}.flight.json")


def dump(path=None, reason="manual", extra=None):
    """Write the ring to ``path`` (default :func:`dump_path`).
    Best-effort: ANY failure is swallowed and counted — a flight dump
    exists to explain errors, it must never add one.  Returns the
    path written, or ``None``."""
    path = path or dump_path()
    if path is None:
        return None
    payload = export(reason=reason)
    if extra:
        payload.update(extra)
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)   # a reader never sees a torn dump
    except Exception:  # mxlint: allow-broad-except(best-effort black-box write: a failed dump is counted, never surfaced — it must not mask the error being dumped)
        _dump_state["failures"] += 1
        return None
    _dump_state["written"] += 1
    return path


def note_error(boundary, error, message="", dump_now=True):
    """A typed framework error crossed a top-level boundary
    (server/router/trainer): record it, and write a rate-limited crash
    dump so the pre-error control-plane history survives the process.

    Never raises — the caller is about to surface the ORIGINAL error
    and nothing here may mask it."""
    try:
        err_name = (error if isinstance(error, str)
                    else type(error).__name__)
        record(LIFECYCLE, "boundary.error", severity="error",
               boundary=boundary, error=err_name,
               message=(message or (str(error)
                                    if not isinstance(error, str)
                                    else ""))[:200])
        if not dump_now or flight_dir() is None:
            return None
        now = time.monotonic()
        with _lock:
            last = _dump_state["last_dump_mono"]
            if last is not None and now - last < dump_min_s():
                _dump_state["rate_limited"] += 1
                return None
            _dump_state["last_dump_mono"] = now
        return dump(reason=f"error:{err_name}")
    except Exception:  # mxlint: allow-broad-except(the black box must never mask the typed error the caller is surfacing; a broken recorder is counted and ignored)
        _dump_state["failures"] += 1
        return None


# ---------------------------------------------------------------------------
# SIGUSR2: "the process is wedged, tell me why"
# ---------------------------------------------------------------------------

def _thread_stacks():
    """All thread stacks, formatted — the wedge diagnosis payload."""
    import sys
    import traceback
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in sys._current_frames().items():
        label = f"{names.get(ident, '?')} ({ident})"
        out[label] = traceback.format_stack(frame)
    return out


def _recent_trace_ids(limit=32):
    seen, out = set(), []
    for s in reversed(_trace.spans()):
        if s.trace_id not in seen:
            seen.add(s.trace_id)
            out.append(s.trace_id)
        if len(out) >= limit:
            break
    return out


def sigusr2_dump():
    """One wedge dump: ring + all thread stacks + a metrics snapshot +
    the recent trace ids.  Re-entrant-safe — a second signal while a
    dump is in flight is dropped and counted, never queued into a
    dump storm.

    Signal-path lock discipline: the handler runs on the main thread
    BETWEEN bytecodes of whatever it interrupted.  If that was a
    ``with _lock:`` section of this module (note_error's rate-limit
    window, configure()), a blocking acquire here would deadlock the
    process on its own diagnosis signal — so the acquire is
    non-blocking and a contended lock counts as a dropped signal."""
    if not _lock.acquire(blocking=False):
        # the interrupted thread (or a concurrent caller) holds the
        # module lock: bail out the same way a mid-dump signal does
        _dump_state["sigusr2_dropped"] += 1
        return None
    try:
        if _dump_state["dumping"]:
            _dump_state["sigusr2_dropped"] += 1
            return None
        _dump_state["dumping"] = True
    finally:
        _lock.release()
    try:
        extra = {"threads": _thread_stacks(),
                 "active_traces": _recent_trace_ids()}
        try:
            from . import profiler
            extra["metrics"] = json.loads(profiler.dumps(format="json"))
        except Exception:  # mxlint: allow-broad-except(a stats provider crashing must not lose the ring+stacks half of the wedge dump)
            extra["metrics"] = None
        record(LIFECYCLE, "sigusr2.dump",
               threads=len(extra["threads"]))
        path = dump_path(".sigusr2")
        if path is None:
            # no dump dir: the wedge report goes to stderr — losing it
            # entirely would defeat the signal's purpose
            import sys
            payload = export(reason="sigusr2")
            payload.update(extra)
            try:
                print(json.dumps(payload), file=sys.stderr, flush=True)
            except Exception:  # mxlint: allow-broad-except(stderr may be gone in a daemonized process; the dump is best-effort by contract)
                _dump_state["failures"] += 1
                return None
            _dump_state["sigusr2"] += 1
            return "<stderr>"
        written = dump(path, reason="sigusr2", extra=extra)
        if written is not None:
            # the FILE is counted by dump() ("written"); this counter
            # tracks sigusr2 dumps performed, file or stderr
            _dump_state["sigusr2"] += 1
        return written
    finally:
        # plain GIL-atomic store — no lock on the signal path
        _dump_state["dumping"] = False


def _handle_sigusr2(signum, frame):
    sigusr2_dump()


def install_signal_handler(proc=None):
    """Install the ``SIGUSR2`` wedge-dump handler (main thread only —
    the CLIs call this at startup).  Returns True when installed."""
    if proc is not None:
        configure(proc=proc)
    import signal
    if not hasattr(signal, "SIGUSR2"):
        return False
    if threading.current_thread() is not threading.main_thread():
        return False
    signal.signal(signal.SIGUSR2, _handle_sigusr2)
    return True
