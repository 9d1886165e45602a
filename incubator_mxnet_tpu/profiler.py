"""Profiler: same Python API as the reference over JAX/XLA tracing.

Reference: src/profiler/ + python/mxnet/profiler.py — chrome://tracing
JSON dumps, aggregate tables, scoped tasks/counters (§5.1 of SURVEY.md).
TPU design: ``jax.profiler`` produces xprof/perfetto traces of device
execution; this module adds (a) the reference's set_config/start/stop/
dumps API, (b) host-side scoped events collected into chrome-trace JSON,
(c) aggregate duration tables.
"""
from __future__ import annotations

import json
import os
import threading
import time

import jax

from .locks import named_lock

__all__ = ["set_config", "set_state", "start", "stop", "dump", "dumps",
           "pause", "resume", "Task", "Frame", "Counter", "Marker", "scope",
           "dump_memory_allocations", "bulk_stats", "reset_bulk_stats",
           "record_bulk_flush", "record_eager_dispatch",
           "register_stats_provider", "unregister_stats_provider",
           "provider_stats"]

_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": True,
    "profile_api": True,
    "aggregate_stats": False,
    "xprof_dir": None,
}
_state = {"running": False, "xprof_active": False}
_events: list[dict] = []
_events_lock = named_lock("profiler.events")
_aggregate: dict[str, list[float]] = {}


def set_config(**kwargs):
    _config.update(kwargs)


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        start()
    else:
        stop()


def start(profile_process="worker"):
    _state["running"] = True
    xdir = _config.get("xprof_dir")
    if xdir:
        try:
            jax.profiler.start_trace(xdir)
            _state["xprof_active"] = True
        except Exception:  # mxlint: allow-broad-except(xprof is best-effort: already tracing or unsupported platform)
            _state["xprof_active"] = False
    if _config.get("profile_memory"):
        _start_memory_sampler()
        global _alloc_tracking
        _alloc_tracking = True
        _state["alloc_session"] = True
        with _events_lock:
            _alloc_records.clear()   # each session starts fresh


def stop(profile_process="worker"):
    global _alloc_tracking
    _state["running"] = False
    _alloc_tracking = False
    _state["alloc_session"] = False
    _stop_memory_sampler()
    if _state.get("xprof_active"):
        try:
            jax.profiler.stop_trace()
        finally:
            _state["xprof_active"] = False


# -- imperative op-bulking counters (ops/bulking.py): segments flushed,
#    ops-per-segment histogram, trace-cache hit rate, and the per-op
#    eager dispatch count for comparison — the observability half of the
#    reference's bulk-exec engine segments (graph_executor.cc InitOpSegs) --

_bulk_lock = named_lock("profiler.bulk")


def _fresh_bulk_stats():
    return {"segments_flushed": 0, "ops_bulked": 0,
            "trace_cache_hits": 0, "trace_cache_misses": 0,
            "eager_dispatches": 0, "ops_per_segment": {}}


_bulk = _fresh_bulk_stats()


def record_bulk_flush(n_ops, cache_hit):
    """One segment flushed as a single compiled program of ``n_ops`` ops."""
    with _bulk_lock:
        _bulk["segments_flushed"] += 1
        _bulk["ops_bulked"] += n_ops
        _bulk["trace_cache_hits" if cache_hit else "trace_cache_misses"] += 1
        h = _bulk["ops_per_segment"]
        h[n_ops] = h.get(n_ops, 0) + 1
    if _state["running"]:
        with _events_lock:
            _events.append({"name": "bulk_segment", "cat": "bulking",
                            "ph": "C", "ts": time.perf_counter_ns() // 1000,
                            "pid": os.getpid(),
                            "args": {"ops": n_ops,
                                     "cache_hit": int(cache_hit)}})


def record_eager_dispatch():
    """One per-op jitted dispatch on the eager path (bulking off or op
    not bulkable) — the denominator for launches-vs-ops comparisons."""
    _bulk["eager_dispatches"] += 1  # GIL-atomic enough for a counter


def bulk_stats(reset=False):
    """Snapshot of the bulking counters plus derived rates.

    ``segments_flushed`` is the number of compiled-program launches the
    bulked path made; ``ops_bulked / segments_flushed`` is the mean
    segment length (reference target: > 5 ops per engine segment)."""
    global _bulk
    with _bulk_lock:
        out = {k: (dict(v) if isinstance(v, dict) else v)
               for k, v in _bulk.items()}
        if reset:
            # rebind (not clear-in-place): record_eager_dispatch increments
            # without the lock and must never see a half-reset dict
            _bulk = _fresh_bulk_stats()
    segs = out["segments_flushed"]
    lookups = out["trace_cache_hits"] + out["trace_cache_misses"]
    out["ops_per_segment_mean"] = (out["ops_bulked"] / segs) if segs else 0.0
    out["trace_cache_hit_rate"] = (
        out["trace_cache_hits"] / lookups) if lookups else 0.0
    return out


def reset_bulk_stats():
    bulk_stats(reset=True)


# -- pluggable subsystem stats (serving/metrics.py registers here so
#    profiler dumps carry the serving counters alongside bulk_stats) --

_stats_providers: dict = {}


def register_stats_provider(name, fn):
    """Register ``fn() -> dict`` folded into :func:`dumps` output under
    ``name`` (idempotent: re-registering replaces the provider)."""
    _stats_providers[name] = fn


def unregister_stats_provider(name, fn=None):
    """Drop a provider so a torn-down subsystem stops being reported
    (and stops being kept alive by the registry).  With ``fn`` given,
    only removes it while it is still the registered provider — a later
    registration under the same name wins and is left in place."""
    cur = _stats_providers.get(name)
    if fn is None or cur == fn:
        _stats_providers.pop(name, None)


def provider_stats():
    """{provider: stats-dict} for every registered provider; a provider
    that raises is reported as an error string, never propagated."""
    out = {}
    for name, fn in list(_stats_providers.items()):
        try:
            out[name] = fn()
        except Exception as e:  # mxlint: allow-broad-except(a broken stats provider is reported as an error entry, never breaks dumps)
            out[name] = {"error": f"{type(e).__name__}: {e}"}
    return out


# -- per-allocation attribution (reference storage_profiler.cc
#    GpuMemoryProfiler: allocations tagged with the active profiler
#    scope and dumped as CSV) --

_scope_stack = threading.local()
_alloc_tracking = False          # checked inline by _Chunk.__init__
_alloc_records: list[tuple] = []
_ALLOC_CAP = 200_000             # hard cap: profiling must not OOM the host


def _current_scope_name():
    stack = getattr(_scope_stack, "names", None)
    return ":".join(stack) if stack else "<unk>"


def record_alloc(nbytes, shape, dtype, device):
    """Called from NDArray chunk creation while allocation tracking is
    on (reference storage_profiler.cc:OnAlloc)."""
    if len(_alloc_records) >= _ALLOC_CAP:
        return
    with _events_lock:
        _alloc_records.append((_current_scope_name(), int(nbytes),
                               tuple(shape), str(dtype), str(device)))


def dump_memory_allocations(path=None, reset=False):
    """CSV of recorded allocations, one row per chunk, grouped totals at
    the end (the reference's gpu_memory_profile.csv role).  Returns the
    CSV text; writes it to ``path`` when given."""
    with _events_lock:
        records = list(_alloc_records)
        if reset:
            _alloc_records.clear()
    lines = ["Attribute name,Requested size,Shape,Dtype,Device"]
    totals: dict[str, int] = {}
    for name, nbytes, shape, dtype, dev in records:
        lines.append(f"\"{name}\",{nbytes},\"{shape}\",{dtype},{dev}")
        totals[name] = totals.get(name, 0) + nbytes
    lines.append("")
    lines.append("Scope,Total bytes")
    for name, tot in sorted(totals.items(), key=lambda kv: -kv[1]):
        lines.append(f"\"{name}\",{tot}")
    text = "\n".join(lines) + "\n"
    if path:
        with open(path, "w") as f:
            f.write(text)
    return text


# -- device/host memory counters (reference storage_profiler.cc +
#    profiler.h counter events; §2.1 "storage manager profiler hooks") --

def _memory_snapshot():
    """One sample: PJRT HBM stats per device + the native host pool."""
    samples = {}
    for dev, st in device_memory_profile().items():
        if st.get("bytes_in_use") is not None:
            samples[f"hbm:{dev}"] = {"bytes_in_use": st["bytes_in_use"]}
    try:
        from . import native
        if native.available():
            import ctypes
            allocated = ctypes.c_uint64()
            pooled = ctypes.c_uint64()
            native.check_call(native.lib.MXTStorageStats(
                ctypes.byref(allocated), ctypes.byref(pooled)))
            samples["host_pool"] = {"bytes_allocated": allocated.value,
                                    "bytes_pooled": pooled.value}
    except Exception:  # mxlint: allow-broad-except(memory sampling is best-effort; a failed probe skips the sample)
        pass
    return samples


def _sampler_loop(stop_evt, interval_s):
    while not stop_evt.wait(interval_s):
        if not _state["running"]:
            continue  # pause() suppresses memory samples like events
        ts = time.perf_counter_ns() // 1000
        for name, args in _memory_snapshot().items():
            with _events_lock:
                _events.append({"name": name, "cat": "memory", "ph": "C",
                                "ts": ts, "pid": os.getpid(), "args": args})


def _start_memory_sampler():
    if _state.get("mem_thread") is not None:
        return
    interval = float(os.environ.get("MXNET_PROFILER_MEM_INTERVAL_MS",
                                    "50")) / 1000.0
    evt = threading.Event()
    t = threading.Thread(target=_sampler_loop, args=(evt, interval),
                         daemon=True)
    _state["mem_stop"] = evt
    _state["mem_thread"] = t
    t.start()


def _stop_memory_sampler():
    t = _state.pop("mem_thread", None)
    evt = _state.pop("mem_stop", None)
    if evt is not None:
        evt.set()
    if t is None:
        return  # sampler never ran (profile_memory off) — emit nothing,
                # and never touch the backend from a bare stop()
    t.join(timeout=2)
    # one final sample so even a zero-duration profile window records
    # the memory state
    ts = time.perf_counter_ns() // 1000
    for name, args in _memory_snapshot().items():
        with _events_lock:
            _events.append({"name": name, "cat": "memory", "ph": "C",
                            "ts": ts, "pid": os.getpid(), "args": args})


def pause(profile_process="worker"):
    global _alloc_tracking
    _state["running"] = False
    _alloc_tracking = False   # allocations are suppressed while paused


def resume(profile_process="worker"):
    global _alloc_tracking
    _state["running"] = True
    _alloc_tracking = bool(_state.get("alloc_session"))


def _emit(name, category, start_us, dur_us, args=None):
    with _events_lock:
        _events.append({
            "name": name, "cat": category, "ph": "X",
            "ts": start_us, "dur": dur_us,
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": args or {},
        })
        _aggregate.setdefault(name, []).append(dur_us)


class scope:
    """``with profiler.scope('fwd'):`` — host-side chrome-trace event +
    a jax.profiler.TraceAnnotation so the region shows up in xprof too."""

    def __init__(self, name, category="operation"):
        self.name = name
        self.category = category
        self._jax_ctx = None

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        stack = getattr(_scope_stack, "names", None)
        if stack is None:
            stack = _scope_stack.names = []
        stack.append(self.name)
        try:
            self._jax_ctx = jax.profiler.TraceAnnotation(self.name)
            self._jax_ctx.__enter__()
        except Exception:  # mxlint: allow-broad-except(TraceAnnotation is cosmetic; scope timing works without it)
            self._jax_ctx = None
        return self

    def __exit__(self, *exc):
        stack = getattr(_scope_stack, "names", None)
        if stack:
            stack.pop()
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(*exc)
        if _state["running"]:
            t1 = time.perf_counter_ns()
            _emit(self.name, self.category, self._t0 // 1000,
                  (t1 - self._t0) // 1000)


class Task:
    """User-scoped profiler task (reference profiler.h:557 ProfileTask)."""

    def __init__(self, domain=None, name="task"):
        self.name = name
        self._scope = None

    def start(self):
        self._scope = scope(self.name, "task")
        self._scope.__enter__()

    def stop(self):
        if self._scope is not None:
            self._scope.__exit__(None, None, None)
            self._scope = None


Frame = Task
Marker = Task


class Counter:
    """Named counter (reference profiler.h:768 ProfileCounter)."""

    def __init__(self, domain=None, name="counter", value=0):
        self.name = name
        self.value = value

    def set_value(self, value):
        self.value = value
        if _state["running"]:
            with _events_lock:
                _events.append({"name": self.name, "ph": "C",
                                "ts": time.perf_counter_ns() // 1000,
                                "pid": os.getpid(),
                                "args": {"value": value}})

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)


def dumps(reset=False, format="table"):
    """Aggregate stats as a printable table (reference profiler.py:316),
    followed by one section per registered subsystem stats provider
    (``bulk_stats`` for op bulking, ``serving`` for the inference
    server) so one dump answers both halves of the perf story.

    ``format="json"`` returns the same content machine-readable (one
    JSON object: ``{"aggregate": {name: {calls, total_us, mean_us}},
    "providers": {provider: stats}}``) so CI gates and
    ``tools/traceview.py`` consume provider stats without screen-
    scraping the table."""
    if format not in ("table", "json"):
        raise ValueError(
            f'dumps format must be "table" or "json", got {format!r}')
    with _events_lock:
        agg = {name: {"calls": len(durs),
                      "total_us": round(sum(durs), 1),
                      "mean_us": round(sum(durs) / len(durs), 1)}
               for name, durs in sorted(_aggregate.items())}
        if reset:
            _aggregate.clear()
    sections = {"bulk_stats": bulk_stats()}
    sections.update(provider_stats())
    if format == "json":
        return json.dumps({"aggregate": agg, "providers": sections},
                          default=str)
    lines = [f"{'Name':<40} {'Calls':>8} {'Total(us)':>12} {'Mean(us)':>12}"]
    for name, a in agg.items():
        lines.append(f"{name:<40} {a['calls']:>8} {a['total_us']:>12.1f} "
                     f"{a['mean_us']:>12.1f}")
    for name, stats in sections.items():
        if not stats:
            continue
        lines.append("")
        lines.append(f"[{name}]")
        for k, v in sorted(stats.items()):
            lines.append(f"{k:<40} {v}")
    return "\n".join(lines)


def dump(finished=True, profile_process="worker"):
    """Write chrome://tracing JSON to the configured filename."""
    with _events_lock:
        payload = {"traceEvents": list(_events), "displayTimeUnit": "ms"}
    with open(_config["filename"], "w") as f:
        json.dump(payload, f)
    return _config["filename"]


def device_memory_profile():
    """HBM allocation snapshot (reference storage_profiler.cc analog)."""
    stats = {}
    for d in jax.devices():
        try:
            ms = d.memory_stats()
            if ms:
                stats[str(d)] = {"bytes_in_use": ms.get("bytes_in_use"),
                                 "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                                 "bytes_limit": ms.get("bytes_limit")}
        except Exception:  # mxlint: allow-broad-except(per-device stats probe; an unsupported device is skipped)
            continue
    return stats
