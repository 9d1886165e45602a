"""Request-scoped distributed tracing: spans across router → replica →
batcher → device (docs/observability.md).

The stack has deep *aggregate* observability — Prometheus counters,
latency histograms, a dozen profiler stats providers — but none of it
answers "where did THIS slow request spend its time?".  This module is
the request-scoped layer, and the package's one span source: a
monotonic-clock span recorder with context propagation, near-zero off
cost, and Chrome trace-event export, threaded through every stage a
request crosses:

* **Birth / adoption** — a trace is born at a front end (router or
  server) by a head-sampling decision (``MXNET_TRACE_SAMPLE``, default
  0 ⇒ the hot path pays one branch), or adopted from an
  ``X-MXNET-TRACE`` header (``traceid-spanid-sampled``).  The header's
  sampled flag is authoritative: an upstream "1" records even when
  local sampling is off; a garbled header is ignored, never a 500.
* **Propagation** — within a process the active span rides a
  ``contextvars.ContextVar``; across process-replica HTTP hops it
  rides the header (the hop span's id becomes the replica-side
  parent).  A replica that predates the header simply records nothing
  — the trace degrades to the router's single-process view.
* **Storage** — a bounded per-process ring (``MXNET_TRACE_RING``
  spans); overflow evicts oldest-first whole spans, counted, so a
  wrapped ring can never splice spans from two different traces into
  one record.
* **Export** — Chrome trace-event JSON via :func:`export` (served at
  ``GET /v1/trace`` on server and router), a ``trace`` profiler stats
  provider, and ``tools/traceview.py`` which merges router + replica
  dumps into one timeline by trace id.  Span timestamps are monotonic
  (mxlint MX-TIME001); export places them on a shared timeline via
  ONE wall-clock anchor captured per process.
* **The profiler's clock** — the body of every :class:`span`, sampled
  or not, also runs under a ``jax.profiler.TraceAnnotation`` of its
  name: nothing without a profiler session, and with one
  (``profiler.set_config(xprof_dir=...)``) the span is written into the
  session's ``.xplane.pb``, on its host plane, beside the device's
  operations (``chipbench/scope_reduce.py`` reads both).

Span vocabulary (what the instrumented call sites record):
``router.request`` / ``server.request`` roots; ``router.hop`` /
``router.hedge`` per physical attempt (each retry and hedge is its own
span, finishing with a typed ``outcome``); ``batch.queue`` /
``batch.execute`` (admission wait vs device compute, with the chosen
padding bucket); ``session.queue`` / ``session.decode_step``
(continuous batching); ``executor.build`` vs ``trace_cache.hit``
(compile-vs-cache on the Executor choke point); ``model.load``;
``train.epoch`` / ``train.chunk`` / ``prefetch.fill`` /
``prefetch.drain`` on the training side; ``fused_step.call`` around
one ``FusedTrainStep.__call__`` with its children
``fused_step.key_split`` (the PRNG split, a jitted program of its
own), ``fused_step.analyses`` (while a lint latch is open) and
``executor.call`` (``Executor.__call__``: the jitted call itself, with
its ``site``).  Process spans: ``process.import``;
``gluon.param_init`` (one leaf) under ``gluon.initialize`` or
``gluon.first_forward`` (the eager pass that resolves deferred
shapes); ``amp.convert_block``; ``fused_step.build`` with its children
``fused_step.state_copy``, ``fused_step.place`` and
``fused_step.program``; ``fused_step.first_call`` around the first
``fused_step.call``; ``jit.compile``, one a compile of the process,
recorded when its backend compile ends.  ``fault.py`` injections add
a ``fault.<point>`` event to the active span, so a chaos-run artifact
shows the injected fault and the recovery path in one timeline.  The
HA router tier adds ``router.forwarded`` events (mis-hashed session
request proxied to its ring owner, ``serving/routerha.py``) — the
``X-MXNET-ROUTER`` hop propagates the trace header, so a forwarded
request stays ONE trace across both routers.
"""
from __future__ import annotations

import sys
import time

# ONE clock anchor per process, read before anything else is imported:
# the package's ``__init__`` imports this module first, so the anchor is
# the start of the package's import and the process trace's ``t0``.
# Every span timestamp is monotonic (durations can never jump on an NTP
# step — the MX-TIME001 contract); export maps them onto a shared
# cross-process timeline by adding the delta-to-anchor to this single
# wall reading, :func:`process_spans` onto ``time.perf_counter()``'s
# clock by the same delta.
_ANCHOR_WALL = time.time()  # mxlint: allow-wall-clock(single per-process anchor aligning monotonic span times across processes at export; all arithmetic stays monotonic)
_ANCHOR_MONO = time.monotonic()
_ANCHOR_PERF = time.perf_counter()
#: whether the process had imported JAX before this package: if not,
#: ``process.import`` holds JAX's import too
JAX_PRELOADED = "jax" in sys.modules

import contextvars
import json
import os
import random
import threading
from collections import deque

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .base import get_env
from .locks import named_lock

__all__ = [
    "HEADER", "Span", "enabled", "active", "sample_rate", "configure",
    "reset", "start_trace", "record_span", "from_header",
    "parse_header", "header_value", "current_span", "current_trace_id",
    "activate", "span", "process_span", "record_process_span",
    "add_event", "export", "spans", "process_spans", "process_summary",
    "stats", "health_block", "slow_k", "JAX_PRELOADED",
]

#: The propagation header: ``traceid(16 hex)-spanid(8 hex)-sampled``.
HEADER = "X-MXNET-TRACE"

_HEX = set("0123456789abcdef")

_current: contextvars.ContextVar = contextvars.ContextVar(
    "mxnet_trace_span", default=None)

_lock = named_lock("trace.cfg")
_cfg = {"sample": None, "ring": None, "slow_k": None}  # None = env
_rng = random.Random()
_provider_registered = False


def _new_id(nibbles):
    return "%0*x" % (nibbles, _rng.getrandbits(4 * nibbles))


class Span:
    """One timed region of one trace.  Created by the helpers below;
    recorded into the ring at :meth:`finish` (never before — a crashed
    holder simply never lands, it cannot half-record)."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "t0",
                 "t1", "args", "events", "tid", "_done")

    def __init__(self, name, /, trace_id, parent_id=None, t0=None,
                 **args):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_id(8)
        self.parent_id = parent_id
        self.t0 = time.monotonic() if t0 is None else float(t0)
        self.t1 = None
        self.args = dict(args)
        self.events = []           # [(t_mono, name, args), ...]
        self.tid = threading.get_ident()
        self._done = False

    def set(self, **args):
        self.args.update(args)
        return self

    def event(self, name, **args):
        """Timestamped instant event on this span (fault injections,
        cache hits, failover notes)."""
        self.events.append((time.monotonic(), name, args))

    def child(self, name, /, **args):
        return Span(name, self.trace_id, parent_id=self.span_id,
                    **args)

    def finish(self, outcome=None, t1=None):
        """Close the span and push it into the ring (the process
        trace's spans into its own store).  Idempotent — double-finish
        records once.  ``outcome`` defaults to ``"ok"``; error paths
        pass the typed error's class name."""
        if self._done:
            return self
        self._done = True
        self.t1 = time.monotonic() if t1 is None else float(t1)
        self.args.setdefault("outcome", outcome or "ok")
        if self.trace_id == _PROCESS.trace_id:
            _process_store.push(self)
        else:
            _ring().push(self)
            _ensure_provider()
        return self

    @property
    def done(self):
        return self._done


# ---------------------------------------------------------------------------
# configuration + ring
# ---------------------------------------------------------------------------

def sample_rate():
    s = _cfg["sample"]
    if s is None:
        s = _cfg["sample"] = get_env("MXNET_TRACE_SAMPLE", 0.0, float)
    return s


def ring_capacity():
    n = _cfg["ring"]
    if n is None:
        n = _cfg["ring"] = max(
            1, get_env("MXNET_TRACE_RING", 4096, int))
    return n


def slow_k():
    """K for the slow-request exemplars the latency histograms keep
    (metrics.py); lives here so one module owns the trace knobs."""
    k = _cfg["slow_k"]
    if k is None:
        k = _cfg["slow_k"] = max(
            0, get_env("MXNET_TRACE_SLOW_K", 4, int))
    return k


def enabled():
    """Head sampling on (``MXNET_TRACE_SAMPLE`` > 0)."""
    return sample_rate() > 0.0


class _Ring:
    """Bounded span store.  Eviction is whole-span oldest-first, so a
    wrapped ring drops complete spans (counted) — it can never splice
    two traces into one record."""

    __slots__ = ("cap", "_d", "_lock", "pushed", "dropped")

    def __init__(self, cap):
        self.cap = int(cap)
        self._d = deque()
        self._lock = named_lock("trace.ring")
        self.pushed = 0
        self.dropped = 0

    def push(self, span_obj):
        with self._lock:
            self.pushed += 1
            self._d.append(span_obj)
            while len(self._d) > self.cap:
                self._d.popleft()
                self.dropped += 1

    def snapshot(self, trace_id=None):
        with self._lock:
            out = list(self._d)
        if trace_id is not None:
            out = [s for s in out if s.trace_id == trace_id]
        return out

    def clear(self):
        with self._lock:
            self._d.clear()
            self.pushed = 0
            self.dropped = 0


_ring_obj = None

#: spans the process trace's store holds: one a leaf and one a compile,
#: a few hundred a net — a constant, not a knob
_PROCESS_CAP = 8192
#: the process trace's root: never finished, so never in a store;
#: export closes it at the last process span's end
_PROCESS = Span("process", _new_id(16), t0=_ANCHOR_MONO)
_process_store = _Ring(_PROCESS_CAP)


def _ring():
    global _ring_obj
    if _ring_obj is None:
        with _lock:
            if _ring_obj is None:
                _ring_obj = _Ring(ring_capacity())
    return _ring_obj


def configure(sample=None, ring=None, slow=None):
    """Programmatic override of the env knobs (tests, benches).  Any
    argument left ``None`` keeps its current value; changing the ring
    capacity re-allocates an empty ring."""
    global _ring_obj
    with _lock:
        if sample is not None:
            _cfg["sample"] = float(sample)
        if slow is not None:
            _cfg["slow_k"] = int(slow)
        if ring is not None:
            _cfg["ring"] = max(1, int(ring))
            _ring_obj = _Ring(_cfg["ring"])
    if sample is not None and sample > 0:
        _ensure_provider()


def reset():
    """Forget overrides and recorded spans, the process trace's too;
    next use re-reads the env (test isolation)."""
    global _ring_obj
    with _lock:
        _cfg["sample"] = None
        _cfg["ring"] = None
        _cfg["slow_k"] = None
        _ring_obj = None
    _process_store.clear()


def active():
    """Tracing is observably on: sampling enabled, or spans already
    recorded (an adopted forced-sample header counts).  Gates the
    additive ``"trace"`` block in /healthz + describe()."""
    return enabled() or (_ring_obj is not None and _ring_obj.pushed > 0)


def _ensure_provider():
    global _provider_registered
    if _provider_registered:
        return
    _provider_registered = True
    from . import profiler
    profiler.register_stats_provider("trace", stats)


# ---------------------------------------------------------------------------
# creation + context propagation
# ---------------------------------------------------------------------------

def start_trace(name, **args):
    """Head-sampled root span: returns a :class:`Span` or ``None``
    (the per-request sampling branch — when ``MXNET_TRACE_SAMPLE`` is
    0 this is one float compare)."""
    rate = sample_rate()
    if rate <= 0.0:
        return None
    if rate < 1.0 and _rng.random() >= rate:
        return None
    return Span(name, _new_id(16), **args)


def record_span(name, /, parent, t0, t1, **args):
    """Create AND finish a child span with explicit monotonic
    timestamps — for recorders that learn about a region after the
    fact (the batcher's queue-wait split)."""
    if parent is None:
        return None
    s = parent.child(name, t0=t0, **args)
    return s.finish(t1=t1)


def current_span():
    return _current.get()


def current_trace_id():
    s = _current.get()
    return s.trace_id if s is not None else None


class activate:
    """``with trace.activate(span):`` — install ``span`` as the
    context's current span (``None`` ⇒ no-op passthrough, so callers
    need no branch)."""

    __slots__ = ("_span", "_token")

    def __init__(self, span_obj):
        self._span = span_obj
        self._token = None

    def __enter__(self):
        if self._span is not None:
            self._token = _current.set(self._span)
        return self._span

    def __exit__(self, *exc):
        if self._token is not None:
            _current.reset(self._token)
        return False


class span:
    """``with trace.span("router.hop", replica=rid):`` — child of the
    current span, activated for the body, finished on exit with
    ``outcome`` = the escaping exception's class name (or "ok").
    No current span ⇒ nothing reaches the ring.

    Sampled or not, the body also runs under a
    ``jax.profiler.TraceAnnotation`` of the same name: JAX's own no-op
    without a profiler session; with one (``profiler.set_config(
    xprof_dir=...)``, ``jax.profiler.start_trace``) the span is written
    into the session's ``.xplane.pb``, on the clock of its host plane,
    beside the device's operations."""

    __slots__ = ("_name", "_args", "_span", "_token", "_annotation")

    def __init__(self, name, /, **args):
        self._name = name
        self._args = args
        self._span = None
        self._token = None
        self._annotation = _TraceAnnotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        parent = _current.get()
        if parent is not None:
            self._span = parent.child(self._name, **self._args)
            self._token = _current.set(self._span)
        return self._span

    def __exit__(self, etype, evalue, tb):
        if self._token is not None:
            _current.reset(self._token)
        if self._span is not None:
            self._span.finish(
                outcome=etype.__name__ if etype is not None else None)
        self._annotation.__exit__(etype, evalue, tb)
        return False


class process_span(span):
    """``with trace.process_span("fused_step.build", site=s):`` — a
    :class:`span` that always records: it marks something that happens
    once a process, once a net or once a compiled signature, never once
    a step or a request, so it needs no sampling decision and no
    profiler session.  Child of the current span where there is one (a
    model loaded by a sampled request stays in that request's trace),
    of the ``process`` root where there is none; current for its body,
    so the ordinary spans inside it record as its children."""

    __slots__ = ()

    def __enter__(self):
        self._annotation.__enter__()
        parent = _current.get() or _PROCESS
        self._span = parent.child(self._name, **self._args)
        self._token = _current.set(self._span)
        return self._span


def record_process_span(name, /, seconds=None, **args):
    """A process span learnt about after the fact: it ended now and
    lasted ``seconds`` (``None``: since the process trace began, the
    start of the package's import)."""
    t1 = time.monotonic()
    t0 = _ANCHOR_MONO if seconds is None else t1 - seconds
    return record_span(name, _current.get() or _PROCESS, t0, t1, **args)


def add_event(name, **args):
    """Instant event on the active span, if any — the hook fault.py
    fires on every injection (one contextvar read when untraced)."""
    s = _current.get()
    if s is not None:
        s.event(name, **args)


# ---------------------------------------------------------------------------
# header propagation
# ---------------------------------------------------------------------------

def parse_header(text):
    """``traceid-spanid-sampled`` → ``(trace_id, span_id, sampled)``;
    ``None`` for anything malformed (a garbled header is ignored, not
    an error — the request must still serve)."""
    if not text or not isinstance(text, str):
        return None
    parts = text.strip().lower().split("-")
    if len(parts) != 3:
        return None
    tid, sid, flag = parts
    if len(tid) != 16 or not set(tid) <= _HEX:
        return None
    if len(sid) != 8 or not set(sid) <= _HEX:
        return None
    if flag not in ("0", "1"):
        return None
    return tid, sid, flag == "1"


def header_value(span_obj):
    """The ``X-MXNET-TRACE`` value carrying ``span_obj`` downstream
    (its id becomes the callee-side parent); ``None`` span ⇒ ``None``
    (caller sends no header)."""
    if span_obj is None:
        return None
    return f"{span_obj.trace_id}-{span_obj.span_id}-1"


def from_header(text, name, **args):
    """Adopt a propagated trace, or fall back to the local sampling
    decision.  A valid header is AUTHORITATIVE either way: sampled=1
    records regardless of local sampling (the head decision was
    upstream's), sampled=0 suppresses recording entirely (the
    upstream already decided not to trace this request); only a
    garbled/absent header degrades to :func:`start_trace`."""
    parsed = parse_header(text)
    if parsed is None:
        return start_trace(name, **args)
    tid, parent_sid, sampled = parsed
    if not sampled:
        return None
    s = Span(name, tid, parent_id=parent_sid, **args)
    s.args["adopted"] = True
    return s


# ---------------------------------------------------------------------------
# export + stats
# ---------------------------------------------------------------------------

def anchor():
    """The per-process ``(wall, monotonic)`` anchor pair.  Captured
    ONCE per process (the MX-TIME001 contract) and shared by every
    exporter that needs to place monotonic timestamps on a cross-
    process timeline — this module's span export and the flight
    recorder's event dumps both use it, so their merged timelines can
    never disagree about when "now" was."""
    return _ANCHOR_WALL, _ANCHOR_MONO


def _wall_us(t_mono):
    return int((_ANCHOR_WALL + (t_mono - _ANCHOR_MONO)) * 1e6)


def spans(trace_id=None):
    """The ring's recorded spans, newest last (optionally one trace's);
    the process trace is :func:`process_spans`."""
    return _ring().snapshot(trace_id)


def _process_trace(trace_id=None):
    """The process trace's spans, its root first, closed at the last
    one's end; nothing while the store is empty."""
    held = _process_store.snapshot(trace_id)
    if not held:
        return []
    _PROCESS.t1 = max(s.t1 for s in held)
    return [_PROCESS] + held


def process_spans():
    """The process trace as plain records, in the order the spans
    ended: ``name``, ``parent`` (the parent span's name; None where it
    has left the store), ``t0`` and ``t1`` on ``time.perf_counter()``'s
    clock (that of ``executor_cache.compile_log()``'s ``at``), ``args``,
    and the ``span_id`` / ``parent_id`` the tree is rebuilt from."""
    held = _process_trace()
    names = {s.span_id: s.name for s in held}
    shift = _ANCHOR_PERF - _ANCHOR_MONO
    return [{"name": s.name, "parent": names.get(s.parent_id),
             "t0": s.t0 + shift, "t1": s.t1 + shift, "args": dict(s.args),
             "span_id": s.span_id, "parent_id": s.parent_id}
            for s in held[1:]]


def process_summary():
    """The process trace folded by span name: ``count``, ``total_s``,
    ``first_start_s`` and ``last_end_s`` (seconds after the start of
    the package's import), and the store's ``dropped`` count — what the
    ``cold_start`` provider reports under ``"setup"``."""
    by_name = {}
    for s in _process_store.snapshot():
        into = by_name.setdefault(s.name, {
            "count": 0, "total_s": 0.0,
            "first_start_s": float("inf"), "last_end_s": 0.0})
        into["count"] += 1
        into["total_s"] += s.t1 - s.t0
        into["first_start_s"] = min(into["first_start_s"],
                                    s.t0 - _ANCHOR_MONO)
        into["last_end_s"] = max(into["last_end_s"], s.t1 - _ANCHOR_MONO)
    for into in by_name.values():
        for key in ("total_s", "first_start_s", "last_end_s"):
            into[key] = round(into[key], 6)
    return {"spans": by_name, "cap": _process_store.cap,
            "dropped": _process_store.dropped}


def export(trace_id=None, service=None):
    """Chrome trace-event JSON (``chrome://tracing`` /
    ``ui.perfetto.dev`` loadable): one ``ph:"X"`` complete event per
    span — the process trace's first, under its ``process`` root, then
    the ring's — one ``ph:"i"`` instant per span event.  ``service`` labels
    the process (router/replica) for merged views."""
    pid = os.getpid()
    svc = service or f"pid:{pid}"
    events = []
    for s in _process_trace(trace_id) + _ring().snapshot(trace_id):
        t1 = s.t1 if s.t1 is not None else s.t0
        args = dict(s.args)
        args.update(trace_id=s.trace_id, span_id=s.span_id,
                    parent_id=s.parent_id, service=svc)
        events.append({
            "name": s.name, "cat": "trace", "ph": "X",
            "ts": _wall_us(s.t0),
            "dur": max(0, _wall_us(t1) - _wall_us(s.t0)),
            "pid": pid, "tid": s.tid, "args": args,
        })
        for t_ev, ev_name, ev_args in s.events:
            ia = dict(ev_args)
            ia.update(trace_id=s.trace_id, span_id=s.span_id,
                      service=svc)
            events.append({
                "name": ev_name, "cat": "trace_event", "ph": "i",
                "ts": _wall_us(t_ev), "s": "t",
                "pid": pid, "tid": s.tid, "args": ia,
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_json(trace_id=None, service=None):
    return json.dumps(export(trace_id, service))


def stats():
    """The ``trace`` profiler stats provider."""
    r = _ring()
    with r._lock:
        in_ring = len(r._d)
        pushed, dropped = r.pushed, r.dropped
        traces = len({s.trace_id for s in r._d})
    return {
        "enabled": enabled(),
        "sample": sample_rate(),
        "ring_capacity": r.cap,
        "spans_recorded": pushed,
        "spans_dropped": dropped,
        "spans_in_ring": in_ring,
        "traces_in_ring": traces,
        "slow_k": slow_k(),
    }


def health_block():
    """The additive ``"trace"`` block for /healthz + describe() —
    present only while :func:`active` (bare deployments keep their
    pinned shape)."""
    st = stats()
    return {"sample": st["sample"], "ring": st["ring_capacity"],
            "spans": st["spans_recorded"],
            "dropped": st["spans_dropped"], "slow_k": st["slow_k"]}
