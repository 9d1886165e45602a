"""Serving observability: Prometheus-text counters + latency quantiles.

Pure stdlib — no prometheus_client.  One :class:`ServingMetrics`
instance is shared by the repository, batcher, admission layer and HTTP
front end; ``render()`` is the ``GET /metrics`` body and ``snapshot()``
the dict the profiler folds into its dumps (alongside ``bulk_stats``)
and the serving bench emits as JSON.

The load-bearing counter is ``mxnet_serving_compile_total``: the sum of
every loaded predictor's jit-cache size.  After warmup it must
flatline — growth under steady traffic means a request paid a cold XLA
compile, which on TPU is the difference between microseconds and
seconds.
"""
from __future__ import annotations

import threading
import time

from .. import trace
from ..locks import named_lock

__all__ = ["ServingMetrics", "FleetMetrics", "Histogram",
           "SlowExemplars"]


def _esc(label_value):
    """Prometheus label-value escaping (exposition format 0.0.4):
    one unescaped quote/backslash/newline in a model name would
    invalidate the whole /metrics page for every model."""
    return (str(label_value).replace("\\", "\\\\")
            .replace('"', '\\"').replace("\n", "\\n"))

# defaults chosen for ms-scale serving latencies: sub-ms through 10s
_LATENCY_BUCKETS = (1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                    500.0, 1000.0, 2500.0, 5000.0, 10000.0)
_RESERVOIR = 2048   # ring buffer per histogram for quantile estimates


class Histogram:
    """Fixed-bucket histogram + ring-buffer quantiles (p50/p95/p99).

    Prometheus histograms are cumulative-bucket counters; quantiles are
    computed host-side from the last ``_RESERVOIR`` observations, which
    is the summary-style view the bench and profiler dumps want."""

    def __init__(self, buckets=_LATENCY_BUCKETS):
        self.buckets = tuple(sorted(buckets))
        self.counts = [0] * (len(self.buckets) + 1)   # +Inf tail
        self.total = 0
        self.sum = 0.0
        self._ring = [0.0] * _RESERVOIR
        self._lock = named_lock("metrics.histogram")

    def observe(self, value):
        value = float(value)
        with self._lock:
            i = 0
            for i, edge in enumerate(self.buckets):
                if value <= edge:
                    break
            else:
                i = len(self.buckets)
            self.counts[i] += 1
            self._ring[self.total % _RESERVOIR] = value
            self.total += 1
            self.sum += value

    def quantile(self, q):
        with self._lock:
            n = min(self.total, _RESERVOIR)
            if n == 0:
                return 0.0
            data = sorted(self._ring[:n])
        idx = min(n - 1, max(0, int(q * n)))
        return data[idx]

    def snapshot(self):
        with self._lock:
            total, s = self.total, self.sum
        return {"count": total, "sum": round(s, 3),
                "p50": round(self.quantile(0.50), 3),
                "p95": round(self.quantile(0.95), 3),
                "p99": round(self.quantile(0.99), 3)}

    def prom_lines(self, name, labels=""):
        lab = f"{{{labels}}}" if labels else ""
        out = []
        cum = 0
        with self._lock:
            counts, total, s = list(self.counts), self.total, self.sum
        for edge, c in zip(self.buckets, counts):
            cum += c
            sep = "," if labels else ""
            out.append(f'{name}_bucket{{{labels}{sep}le="{edge:g}"}} {cum}')
        sep = "," if labels else ""
        out.append(f'{name}_bucket{{{labels}{sep}le="+Inf"}} {total}')
        out.append(f"{name}_sum{lab} {s:.6f}")
        out.append(f"{name}_count{lab} {total}")
        return out


class SlowExemplars:
    """Trace-id exemplars for a latency histogram: the K slowest
    requests per observation window (``MXNET_TRACE_SLOW_K``).

    Histograms tell you THAT p99 spiked; an exemplar names a concrete
    trace id to pull from ``/v1/trace`` and see WHERE the time went.
    Windowing (default 512 observations) keeps the set current — a
    one-off stall from an hour ago ages out instead of squatting on
    the top-K forever.  The previous window is kept so a scrape right
    after rollover still sees exemplars."""

    __slots__ = ("_k", "_window", "_cur", "_prev", "_count", "_lock")

    def __init__(self, k=None, window=512):
        self._k = k
        self._window = int(window)
        self._cur: list = []     # [(ms, trace_id)] sorted desc
        self._prev: list = []
        self._count = 0
        self._lock = named_lock("metrics.slowk")

    def note(self, ms, trace_id):
        """Record one traced observation (untraced requests never get
        here — the caller gates on trace_id)."""
        if trace_id is None:
            return
        k = self._k if self._k is not None else trace.slow_k()
        if k <= 0:
            return
        with self._lock:
            self._count += 1
            if self._count % self._window == 0:
                self._prev, self._cur = self._cur, []
            cur = self._cur
            cur.append((float(ms), str(trace_id)))
            cur.sort(key=lambda t: -t[0])
            del cur[k:]

    def exemplars(self):
        """Top-K ``[{"ms", "trace_id"}]`` over the current + previous
        window, slowest first."""
        k = self._k if self._k is not None else trace.slow_k()
        with self._lock:
            merged = sorted(self._cur + self._prev,
                            key=lambda t: -t[0])[:max(0, k)]
        return [{"ms": round(ms, 3), "trace_id": tid}
                for ms, tid in merged]


class _ModelMetrics:
    __slots__ = ("requests", "errors", "batches", "batch_hist",
                 "e2e_ms", "compute_ms", "queue_ms", "padded_rows",
                 "cancelled", "t_last_request", "slow")

    def __init__(self):
        self.requests = {}       # {http-code: count}
        self.errors = 0
        self.batches = 0
        self.padded_rows = 0
        self.cancelled = 0
        # monotonic stamp of the last request (None until one lands):
        # the idle-seconds gauge the autoscaler's scale-to-zero /
        # idle-unload decision reads
        self.t_last_request = None
        self.batch_hist = Histogram(buckets=(1, 2, 4, 8, 16, 32, 64, 128))
        self.e2e_ms = Histogram()
        self.compute_ms = Histogram()
        self.queue_ms = Histogram()
        self.slow = SlowExemplars()   # K slowest traced requests


class ServingMetrics:
    """Process-wide serving counters, shared across models."""

    def __init__(self):
        self._models: dict[str, _ModelMetrics] = {}
        self._lock = named_lock("metrics.serving")
        self._started = time.monotonic()
        # callbacks the repository installs: () -> int / dict
        self._compile_count_fn = None
        self._queue_depth_fn = None
        self._memory_fn = None
        # cold-start observability (ROADMAP item 2): per-model load →
        # ready duration, process-start → ready, and AOT-executable
        # load outcomes, recorded by ModelRepository._build_entry
        self._cold_start: dict[str, dict] = {}
        # stateful sessions (SessionHost callbacks): per-model gauges
        # + the per-session-model compile counts folded into the
        # compile_total flatline invariant
        self._session_stats_fn = None
        self._session_hists_fn = None
        self._session_compile_fn = None

    def attach_repository(self, repository):
        """Wire gauges that live in the repository (compile counts per
        predictor, live queue depths per batcher, export-time memory
        plans per model)."""
        self._compile_count_fn = repository.compile_counts
        self._queue_depth_fn = repository.queue_depths
        self._memory_fn = getattr(repository, "memory_summaries", None)

    def attach_sessions(self, host):
        """Wire the session-host gauges (active sessions, steps,
        snapshots, stream latency) — and fold the session models'
        decode-step compile counts into ``mxnet_serving_compile_total``
        so the flatline-after-warmup invariant covers continuous
        batching: a session join/leave that cost an XLA compile moves
        the same counter a cold predict would."""
        self._session_stats_fn = host.stats
        self._session_hists_fn = host.stream_hists
        self._session_compile_fn = host.compile_counts

    def _model(self, name):
        with self._lock:
            m = self._models.get(name)
            if m is None:
                m = self._models[name] = _ModelMetrics()
            return m

    # -- recording hooks ----------------------------------------------

    def record_request(self, model, code, e2e_ms=None, compute_ms=None,
                       queue_ms=None, trace_id=None):
        m = self._model(model)
        with self._lock:
            m.requests[code] = m.requests.get(code, 0) + 1
            m.t_last_request = time.monotonic()
            if code >= 400:
                m.errors += 1
        if e2e_ms is not None:
            m.e2e_ms.observe(e2e_ms)
            if trace_id is not None:
                # exemplar: the histogram bucket gets a concrete trace
                # to name when someone asks "which request was that?"
                m.slow.note(e2e_ms, trace_id)
        if compute_ms is not None:
            m.compute_ms.observe(compute_ms)
        if queue_ms is not None:
            m.queue_ms.observe(queue_ms)

    def record_batch(self, model, batch_size, padded_to):
        m = self._model(model)
        with self._lock:
            m.batches += 1
            m.padded_rows += max(0, padded_to - batch_size)
        m.batch_hist.observe(batch_size)

    def record_cancel(self, model):
        """One request/stream withdrawn before (or between) device
        steps — client disconnects and lost hedge races land here."""
        m = self._model(model)
        with self._lock:
            m.cancelled += 1

    def record_cold_start(self, model, cold_start_ms, aot_loads=0,
                          aot_load_failures=0, compile_count=0):
        """One model version reached ready: how long load + warmup
        took, when after process start it happened, and whether the
        AOT executables carried it (``compile_count`` 0 with nonzero
        ``aot_loads`` = cold start was deserialization, not
        compilation)."""
        from .. import executor_cache as _xc
        with self._lock:
            prev = self._cold_start.get(model, {})
            self._cold_start[model] = {
                # gauges: the LIVE version's load cost
                "cold_start_ms": round(float(cold_start_ms), 3),
                "time_to_ready_ms": _xc.since_import_ms(),
                "compile_count_at_ready": int(compile_count),
                # counters: monotonic across reloads — a v2 exported
                # without AOT must not make the Prometheus series drop
                # (a decrease reads as a counter reset and fabricates
                # rate() deltas)
                "aot_loads": prev.get("aot_loads", 0) + int(aot_loads),
                "aot_load_failures": (prev.get("aot_load_failures", 0)
                                      + int(aot_load_failures)),
            }

    # -- exposition ---------------------------------------------------

    def compile_count(self):
        total = 0
        if self._compile_count_fn is not None:
            total += sum(self._compile_count_fn().values())
        if self._session_compile_fn is not None:
            total += sum(self._session_compile_fn().values())
        return total

    def idle_seconds(self, model=None):
        """Seconds since the model's last request — the autoscaler's
        idle-unload input signal.  A model that has never seen a
        request reports its full metrics-instance age (idle since
        "forever" as far as scale-to-zero is concerned).  With
        ``model=None`` returns the ``{name: idle_s}`` dict."""
        now = time.monotonic()
        with self._lock:
            if model is not None:
                m = self._models.get(model)
                last = (m.t_last_request if m is not None else None)
                return now - (last if last is not None
                              else self._started)
            return {name: now - (m.t_last_request
                                 if m.t_last_request is not None
                                 else self._started)
                    for name, m in self._models.items()}

    def last_request_uptime_s(self, model):
        """Monotonic stamp of the model's last request, expressed as
        seconds after this metrics instance started (``None`` until a
        request lands).  Monotonic by design — wall-clock timestamps
        are banned repo-wide (mxlint MX-TIME001); operators correlate
        via ``mxnet_serving_uptime_seconds`` on the same scrape."""
        with self._lock:
            m = self._models.get(model)
            if m is None or m.t_last_request is None:
                return None
            return m.t_last_request - self._started

    def service_ms_estimate(self, model):
        """Recent p50 end-to-end latency for ``model`` (None until
        observed) — the live term the derived ``Retry-After`` uses."""
        with self._lock:
            m = self._models.get(model)
        if m is None or m.e2e_ms.total == 0:
            return None
        return m.e2e_ms.quantile(0.5)

    def render(self):
        """Prometheus text exposition format (version 0.0.4)."""
        L = []
        L.append("# HELP mxnet_serving_uptime_seconds Server uptime.")
        L.append("# TYPE mxnet_serving_uptime_seconds gauge")
        L.append(f"mxnet_serving_uptime_seconds "
                 f"{time.monotonic() - self._started:.3f}")
        compiles = dict(self._compile_count_fn()
                        if self._compile_count_fn else {})
        if self._session_compile_fn is not None:
            for model, n in self._session_compile_fn().items():
                compiles[model] = compiles.get(model, 0) + n
        L.append("# HELP mxnet_serving_compile_total Distinct XLA "
                 "executables per model (must flatline after warmup).")
        L.append("# TYPE mxnet_serving_compile_total counter")
        for model, n in sorted(compiles.items()):
            L.append(f'mxnet_serving_compile_total'
                     f'{{model="{_esc(model)}"}} {n}')
        with self._lock:
            cold = {k: dict(v) for k, v in self._cold_start.items()}
        L.append("# HELP mxnet_serving_cold_start_ms Load + warmup "
                 "duration of the live model version.")
        L.append("# TYPE mxnet_serving_cold_start_ms gauge")
        for model, c in sorted(cold.items()):
            L.append(f'mxnet_serving_cold_start_ms'
                     f'{{model="{_esc(model)}"}} {c["cold_start_ms"]}')
        L.append("# HELP mxnet_serving_time_to_ready_ms Process start "
                 "to model ready.")
        L.append("# TYPE mxnet_serving_time_to_ready_ms gauge")
        for model, c in sorted(cold.items()):
            L.append(f'mxnet_serving_time_to_ready_ms'
                     f'{{model="{_esc(model)}"}} {c["time_to_ready_ms"]}')
        L.append("# HELP mxnet_serving_aot_loads_total AOT executables "
                 "deserialized per model (cache hits that skipped XLA).")
        L.append("# TYPE mxnet_serving_aot_loads_total counter")
        for model, c in sorted(cold.items()):
            L.append(f'mxnet_serving_aot_loads_total'
                     f'{{model="{_esc(model)}"}} {c["aot_loads"]}')
        L.append("# HELP mxnet_serving_aot_load_failures_total AOT "
                 "blobs refused (compat mismatch/corruption) per model "
                 "— each one recompiled instead.")
        L.append("# TYPE mxnet_serving_aot_load_failures_total counter")
        for model, c in sorted(cold.items()):
            L.append(f'mxnet_serving_aot_load_failures_total'
                     f'{{model="{_esc(model)}"}} '
                     f'{c["aot_load_failures"]}')
        depths = (self._queue_depth_fn() if self._queue_depth_fn else {})
        L.append("# HELP mxnet_serving_queue_depth In-flight + queued "
                 "requests per model.")
        L.append("# TYPE mxnet_serving_queue_depth gauge")
        for model, n in sorted(depths.items()):
            L.append(f'mxnet_serving_queue_depth'
                     f'{{model="{_esc(model)}"}} {n}')
        mem = (self._memory_fn() if self._memory_fn else {})
        L.append("# HELP mxnet_serving_model_peak_hbm_bytes Static "
                 "peak-HBM estimate of the exported forward (memlint).")
        L.append("# TYPE mxnet_serving_model_peak_hbm_bytes gauge")
        for model, m in sorted(mem.items()):
            if m.get("peak_hbm_bytes") is not None:
                L.append(f'mxnet_serving_model_peak_hbm_bytes'
                         f'{{model="{_esc(model)}"}} '
                         f'{m["peak_hbm_bytes"]}')
        L.append("# HELP mxnet_serving_model_donated_bytes_reclaimed "
                 "Input bytes XLA reuses for outputs via buffer "
                 "donation (memlint plan).")
        L.append("# TYPE mxnet_serving_model_donated_bytes_reclaimed "
                 "gauge")
        for model, m in sorted(mem.items()):
            if m.get("donated_bytes_reclaimed") is not None:
                L.append(f'mxnet_serving_model_donated_bytes_reclaimed'
                         f'{{model="{_esc(model)}"}} '
                         f'{m["donated_bytes_reclaimed"]}')
        with self._lock:
            models = dict(self._models)
        L.append("# HELP mxnet_serving_requests_total Requests by "
                 "model and HTTP code.")
        L.append("# TYPE mxnet_serving_requests_total counter")
        for name, m in sorted(models.items()):
            with self._lock:
                codes = dict(m.requests)
            for code, n in sorted(codes.items()):
                L.append(f'mxnet_serving_requests_total'
                         f'{{model="{_esc(name)}",code="{code}"}} {n}')
        L.append("# HELP mxnet_serving_errors_total 4xx/5xx responses.")
        L.append("# TYPE mxnet_serving_errors_total counter")
        for name, m in sorted(models.items()):
            L.append(f'mxnet_serving_errors_total'
                     f'{{model="{_esc(name)}"}} {m.errors}')
        L.append("# HELP mxnet_serving_batches_total Coalesced batches "
                 "executed.")
        L.append("# TYPE mxnet_serving_batches_total counter")
        for name, m in sorted(models.items()):
            L.append(f'mxnet_serving_batches_total'
                     f'{{model="{_esc(name)}"}} {m.batches}')
        L.append("# HELP mxnet_serving_padded_rows_total Wasted rows "
                 "from bucket padding.")
        L.append("# TYPE mxnet_serving_padded_rows_total counter")
        for name, m in sorted(models.items()):
            L.append(f'mxnet_serving_padded_rows_total'
                     f'{{model="{_esc(name)}"}} {m.padded_rows}')
        L.append("# HELP mxnet_serving_cancelled_total Requests/"
                 "streams withdrawn before execution (client "
                 "disconnects, lost hedge races).")
        L.append("# TYPE mxnet_serving_cancelled_total counter")
        for name, m in sorted(models.items()):
            L.append(f'mxnet_serving_cancelled_total'
                     f'{{model="{_esc(name)}"}} {m.cancelled}')
        L.append("# HELP mxnet_serving_model_idle_seconds Seconds "
                 "since the model's last request (the autoscaler's "
                 "idle-unload signal).")
        L.append("# TYPE mxnet_serving_model_idle_seconds gauge")
        idle = self.idle_seconds()
        for name in sorted(models):
            L.append(f'mxnet_serving_model_idle_seconds'
                     f'{{model="{_esc(name)}"}} {idle[name]:.3f}')
        L.append("# HELP mxnet_serving_model_last_request_uptime_"
                 "seconds Last request's monotonic stamp as seconds "
                 "after metrics start (-1 until a request lands; "
                 "correlate with mxnet_serving_uptime_seconds).")
        L.append("# TYPE mxnet_serving_model_last_request_uptime_"
                 "seconds gauge")
        for name in sorted(models):
            last = self.last_request_uptime_s(name)
            L.append(f'mxnet_serving_model_last_request_uptime_seconds'
                     f'{{model="{_esc(name)}"}} '
                     f'{-1 if last is None else round(last, 3)}')
        sess = (self._session_stats_fn() if self._session_stats_fn
                else {})
        for metric, key, kind, help_ in (
                ("mxnet_serving_session_active", "active_sessions",
                 "gauge", "Live sessions per session model."),
                ("mxnet_serving_session_steps_total", "steps_total",
                 "counter", "Decode steps executed."),
                ("mxnet_serving_session_snapshots_total",
                 "snapshots_total", "counter",
                 "Carry snapshots written (CRC'd shard format)."),
                ("mxnet_serving_session_snapshot_failures_total",
                 "snapshot_failures_total", "counter",
                 "Snapshot attempts that failed (stream unaffected)."),
                ("mxnet_serving_session_evictions_total",
                 "evictions_total", "counter",
                 "Sessions evicted (idle TTL / session cap)."),
                ("mxnet_serving_session_restored_total",
                 "restored_total", "counter",
                 "Sessions adopted from a snapshot (migrations in)."),
                ("mxnet_serving_session_snapshot_age_s",
                 "snapshot_age_s", "gauge",
                 "Oldest live session's seconds since last snapshot "
                 "(the migration re-base window).")):
            L.append(f"# HELP {metric} {help_}")
            L.append(f"# TYPE {metric} {kind}")
            for name, st in sorted(sess.items()):
                L.append(f'{metric}{{model="{_esc(name)}"}} '
                         f'{st[key]}')
        hists = (self._session_hists_fn() if self._session_hists_fn
                 else {})
        L.append("# HELP mxnet_serving_session_stream_ms Per-chunk "
                 "decode-step latency of session streams.")
        L.append("# TYPE mxnet_serving_session_stream_ms histogram")
        for name, h in sorted(hists.items()):
            L.extend(h.prom_lines("mxnet_serving_session_stream_ms",
                                  f'model="{_esc(name)}"'))
        L.append("# HELP mxnet_serving_batch_size Coalesced batch sizes.")
        L.append("# TYPE mxnet_serving_batch_size histogram")
        for name, m in sorted(models.items()):
            L.extend(m.batch_hist.prom_lines("mxnet_serving_batch_size",
                                             f'model="{_esc(name)}"'))
        for metric, attr, help_ in (
                ("mxnet_serving_latency_ms", "e2e_ms",
                 "End-to-end request latency."),
                ("mxnet_serving_compute_ms", "compute_ms",
                 "Device compute time per request."),
                ("mxnet_serving_queue_ms", "queue_ms",
                 "Queue wait per request.")):
            L.append(f"# HELP {metric} {help_}")
            L.append(f"# TYPE {metric} histogram")
            for name, m in sorted(models.items()):
                L.extend(getattr(m, attr).prom_lines(
                    metric, f'model="{_esc(name)}"'))
        # slow-request exemplars as comments (docs/observability.md):
        # the trace ids of the K slowest traced requests per window —
        # text-format-legal ('#' lines), so a plain scraper ignores
        # them while a human (or traceview) reads the ids right off
        # the /metrics page
        for name, m in sorted(models.items()):
            for ex in m.slow.exemplars():
                L.append(f'# exemplar mxnet_serving_latency_ms'
                         f'{{model="{_esc(name)}"}} '
                         f'trace_id={ex["trace_id"]} ms={ex["ms"]}')
        return "\n".join(L) + "\n"

    def snapshot(self):
        """Flat dict view: profiler dumps + serving bench JSON."""
        with self._lock:
            models = dict(self._models)
        out = {"compile_total": self.compile_count()}
        if self._queue_depth_fn is not None:
            out["queue_depth"] = sum(self._queue_depth_fn().values())
        with self._lock:
            for name, c in self._cold_start.items():
                out[f"{name}.cold_start_ms"] = c["cold_start_ms"]
                out[f"{name}.time_to_ready_ms"] = c["time_to_ready_ms"]
                out[f"{name}.aot_loads"] = c["aot_loads"]
                out[f"{name}.aot_load_failures"] = c["aot_load_failures"]
        if self._memory_fn is not None:
            for name, m in self._memory_fn().items():
                if m.get("peak_hbm_bytes") is not None:
                    out[f"{name}.peak_hbm_bytes"] = m["peak_hbm_bytes"]
                if m.get("donated_bytes_reclaimed") is not None:
                    out[f"{name}.donated_bytes_reclaimed"] = \
                        m["donated_bytes_reclaimed"]
        if self._session_stats_fn is not None:
            for name, st in self._session_stats_fn().items():
                for k, v in st.items():
                    out[f"{name}.session.{k}"] = v
        for name, m in models.items():
            with self._lock:
                reqs = sum(m.requests.values())
                errs, batches = m.errors, m.batches
                padded, cancelled = m.padded_rows, m.cancelled
            out[f"{name}.requests"] = reqs
            out[f"{name}.idle_s"] = round(self.idle_seconds(name), 3)
            out[f"{name}.errors"] = errs
            out[f"{name}.batches"] = batches
            out[f"{name}.padded_rows"] = padded
            out[f"{name}.cancelled"] = cancelled
            out[f"{name}.batch_size"] = m.batch_hist.snapshot()
            out[f"{name}.e2e_ms"] = m.e2e_ms.snapshot()
            out[f"{name}.compute_ms"] = m.compute_ms.snapshot()
            out[f"{name}.queue_ms"] = m.queue_ms.snapshot()
            slow = m.slow.exemplars()
            if slow:
                out[f"{name}.slow_traces"] = slow
        return out

    def register_with_profiler(self):
        """Fold the serving counters into ``profiler.dumps()`` output
        alongside ``bulk_stats``."""
        from .. import profiler
        profiler.register_stats_provider("serving", self.snapshot)

    def unregister_from_profiler(self):
        """Detach at server shutdown: a dead server must not keep its
        repository (predictors, weights) alive through the profiler's
        provider registry nor report stale counters in later dumps."""
        from .. import profiler
        profiler.unregister_stats_provider("serving", self.snapshot)


class _RouteModel:
    """Per-model router-side counters (the autoscaler's load signal)."""

    __slots__ = ("requests", "e2e_ms", "t_last", "inflight", "slow")

    def __init__(self):
        self.requests = {}       # {final-http-code: count}
        self.e2e_ms = Histogram()
        self.t_last = None       # monotonic stamp of last route
        self.inflight = 0
        self.slow = SlowExemplars()   # K slowest traced routes


class FleetMetrics:
    """Fleet-level observability: the router + replica-lifecycle view.

    Per-replica serving counters (batches, compile counts, latency
    histograms) live on each replica's own :class:`ServingMetrics`;
    this class carries what only the fleet layer can see — replica
    states and inflight load, active-probe failures, failovers, and
    the hedging win rate.  Rendered into the router's ``/metrics``
    page and folded into ``profiler.dumps()`` as ``serving_fleet``."""

    def __init__(self):
        self._lock = named_lock("metrics.fleet")
        self._started = time.monotonic()
        self._codes: dict = {}            # {http-code: count}
        self._probe_failures: dict = {}   # {replica-id: count}
        self.failovers = 0
        self.hedges_launched = 0
        self.hedges_won = 0
        self.migrations = 0               # session carries re-homed
        self.session_losses = 0           # typed SessionLostError out
        self.route_cancels = 0            # client gone mid-route
        self.route_ms = Histogram()
        self.slow = SlowExemplars()       # fleet-level slow exemplars
        # per-model router view: the autoscaler's input signal (queue
        # depth rides on replica healthz; p99 / inflight / idle live
        # here, where every routed request passes)
        self._by_model: dict = {}         # {model: _RouteModel}
        self._fleet_states_fn = None      # () -> {rid: state-dict}
        self._session_count_fn = None     # () -> live affinity entries
        self._autoscale_fn = None         # () -> autoscaler.describe()

    def attach_fleet(self, fleet):
        """Wire the live replica-state gauge callback."""
        self._fleet_states_fn = fleet.states

    def attach_session_count(self, fn):
        """Wire the router's session-affinity gauge (sessions the
        fleet currently tracks, wherever their carry lives)."""
        self._session_count_fn = fn

    def attach_autoscaler(self, fn):
        """Wire the autoscaler's describe callback so desired-vs-
        actual replica counts and scale-decision counters render on
        the router's ``/metrics`` page."""
        self._autoscale_fn = fn

    def _route_model(self, model):
        with self._lock:
            m = self._by_model.get(model)
            if m is None:
                m = self._by_model[model] = _RouteModel()
            return m

    # -- recording hooks ----------------------------------------------

    def record_route(self, code, ms=None, model=None, trace_id=None):
        with self._lock:
            self._codes[code] = self._codes.get(code, 0) + 1
        if ms is not None:
            self.route_ms.observe(ms)
            if trace_id is not None:
                self.slow.note(ms, trace_id)
        if model is not None:
            m = self._route_model(model)
            with self._lock:
                m.requests[code] = m.requests.get(code, 0) + 1
                m.t_last = time.monotonic()
            if ms is not None:
                m.e2e_ms.observe(ms)
                if trace_id is not None:
                    m.slow.note(ms, trace_id)

    def note_model_inflight(self, model, delta):
        """Routed-requests-in-flight gauge per model (bumped around
        each route; part of the autoscaler's load signal)."""
        m = self._route_model(model)
        with self._lock:
            m.inflight = max(0, m.inflight + int(delta))

    def model_idle_s(self, model):
        """Seconds since the last routed request for ``model``; a
        model never routed reports this instance's full age."""
        with self._lock:
            m = self._by_model.get(model)
            last = m.t_last if m is not None else None
            return time.monotonic() - (last if last is not None
                                       else self._started)

    def model_stats(self):
        """{model: {requests, dropped, p50_ms, p99_ms, inflight,
        idle_s}} — the router-side half of the autoscaler's signal."""
        now = time.monotonic()
        with self._lock:
            items = list(self._by_model.items())
        out = {}
        for name, m in items:
            with self._lock:
                reqs = dict(m.requests)
                inflight = m.inflight
                last = m.t_last
            out[name] = {
                "requests": sum(reqs.values()),
                "dropped": sum(n for c, n in reqs.items()
                               if c in (429, 503)),
                "p50_ms": m.e2e_ms.quantile(0.50),
                "p99_ms": m.e2e_ms.quantile(0.99),
                "inflight": inflight,
                "idle_s": round(now - (last if last is not None
                                       else self._started), 3),
            }
        return out

    def record_failover(self):
        with self._lock:
            self.failovers += 1

    def record_hedge(self, won=False):
        with self._lock:
            if won:
                self.hedges_won += 1
            else:
                self.hedges_launched += 1

    def record_probe_failure(self, replica_id):
        with self._lock:
            self._probe_failures[replica_id] = (
                self._probe_failures.get(replica_id, 0) + 1)

    def record_migration(self):
        """One session adopted onto a new replica from its snapshot."""
        with self._lock:
            self.migrations += 1

    def record_session_loss(self):
        """One session surfaced typed ``SessionLostError`` — the
        failover contract's explicit failure arm, never a hang."""
        with self._lock:
            self.session_losses += 1

    def record_route_cancel(self):
        """Client disconnected while its request was still between
        hops — abandoned before more device time was spent."""
        with self._lock:
            self.route_cancels += 1

    # -- exposition ---------------------------------------------------

    def _replica_states(self):
        return self._fleet_states_fn() if self._fleet_states_fn else {}

    def render(self):
        """Prometheus text exposition for the router's ``/metrics``."""
        L = []
        states = self._replica_states()
        L.append("# HELP mxnet_serving_fleet_replica_state Replica "
                 "lifecycle state (1 for the current state).")
        L.append("# TYPE mxnet_serving_fleet_replica_state gauge")
        for rid, st in sorted(states.items()):
            L.append(f'mxnet_serving_fleet_replica_state'
                     f'{{replica="{_esc(rid)}",'
                     f'state="{_esc(st["state"])}"}} 1')
        L.append("# HELP mxnet_serving_fleet_replica_inflight Routed "
                 "requests currently on each replica.")
        L.append("# TYPE mxnet_serving_fleet_replica_inflight gauge")
        for rid, st in sorted(states.items()):
            L.append(f'mxnet_serving_fleet_replica_inflight'
                     f'{{replica="{_esc(rid)}"}} {st["inflight"]}')
        L.append("# HELP mxnet_serving_fleet_replica_healthy Probe "
                 "verdict: 1 routable, 0 quarantined.")
        L.append("# TYPE mxnet_serving_fleet_replica_healthy gauge")
        for rid, st in sorted(states.items()):
            L.append(f'mxnet_serving_fleet_replica_healthy'
                     f'{{replica="{_esc(rid)}"}} '
                     f'{1 if st["healthy"] else 0}')
        ready = sum(1 for st in states.values()
                    if st["state"] == "ready" and st["healthy"])
        L.append("# HELP mxnet_serving_fleet_ready_replicas Replicas "
                 "ready and healthy (routable).")
        L.append("# TYPE mxnet_serving_fleet_ready_replicas gauge")
        L.append(f"mxnet_serving_fleet_ready_replicas {ready}")
        with self._lock:
            codes = dict(self._codes)
            probe_failures = dict(self._probe_failures)
            failovers = self.failovers
            launched, won = self.hedges_launched, self.hedges_won
            migrations, losses = self.migrations, self.session_losses
            route_cancels = self.route_cancels
        L.append("# HELP mxnet_serving_fleet_sessions Sessions the "
                 "router currently tracks affinity for.")
        L.append("# TYPE mxnet_serving_fleet_sessions gauge")
        L.append(f"mxnet_serving_fleet_sessions "
                 f"{self._session_count_fn() if self._session_count_fn else 0}")
        L.append("# HELP mxnet_serving_fleet_session_migrations_total "
                 "Sessions re-homed from a snapshot after replica "
                 "death or drain.")
        L.append("# TYPE mxnet_serving_fleet_session_migrations_total "
                 "counter")
        L.append(f"mxnet_serving_fleet_session_migrations_total "
                 f"{migrations}")
        L.append("# HELP mxnet_serving_fleet_session_losses_total "
                 "Sessions that surfaced typed SessionLostError (no "
                 "recoverable snapshot).")
        L.append("# TYPE mxnet_serving_fleet_session_losses_total "
                 "counter")
        L.append(f"mxnet_serving_fleet_session_losses_total {losses}")
        L.append("# HELP mxnet_serving_fleet_route_cancels_total "
                 "Routed requests abandoned between hops because the "
                 "client disconnected.")
        L.append("# TYPE mxnet_serving_fleet_route_cancels_total "
                 "counter")
        L.append(f"mxnet_serving_fleet_route_cancels_total "
                 f"{route_cancels}")
        L.append("# HELP mxnet_serving_fleet_requests_total Routed "
                 "requests by final HTTP code.")
        L.append("# TYPE mxnet_serving_fleet_requests_total counter")
        for code, n in sorted(codes.items()):
            L.append(f'mxnet_serving_fleet_requests_total'
                     f'{{code="{code}"}} {n}')
        with self._lock:
            by_model = dict(self._by_model)
        L.append("# HELP mxnet_serving_fleet_model_requests_total "
                 "Routed requests by model and final HTTP code.")
        L.append("# TYPE mxnet_serving_fleet_model_requests_total "
                 "counter")
        for name, m in sorted(by_model.items()):
            with self._lock:
                mcodes = dict(m.requests)
            for code, n in sorted(mcodes.items()):
                L.append(f'mxnet_serving_fleet_model_requests_total'
                         f'{{model="{_esc(name)}",code="{code}"}} {n}')
        L.append("# HELP mxnet_serving_fleet_model_inflight Routed "
                 "requests currently in flight per model.")
        L.append("# TYPE mxnet_serving_fleet_model_inflight gauge")
        for name, m in sorted(by_model.items()):
            L.append(f'mxnet_serving_fleet_model_inflight'
                     f'{{model="{_esc(name)}"}} {m.inflight}')
        L.append("# HELP mxnet_serving_model_idle_seconds Seconds "
                 "since the model's last routed request (the "
                 "autoscaler's idle-unload signal).")
        L.append("# TYPE mxnet_serving_model_idle_seconds gauge")
        for name in sorted(by_model):
            L.append(f'mxnet_serving_model_idle_seconds'
                     f'{{model="{_esc(name)}"}} '
                     f'{self.model_idle_s(name):.3f}')
        scale = (self._autoscale_fn() if self._autoscale_fn else None)
        if scale is not None:
            L.append("# HELP mxnet_serving_autoscale_desired_replicas "
                     "Replica copies the control loop wants per model.")
            L.append("# TYPE mxnet_serving_autoscale_desired_replicas "
                     "gauge")
            for name, st in sorted(scale.get("models", {}).items()):
                L.append(f'mxnet_serving_autoscale_desired_replicas'
                         f'{{model="{_esc(name)}"}} {st["desired"]}')
            L.append("# HELP mxnet_serving_autoscale_actual_replicas "
                     "Replica copies currently serving per model.")
            L.append("# TYPE mxnet_serving_autoscale_actual_replicas "
                     "gauge")
            for name, st in sorted(scale.get("models", {}).items()):
                L.append(f'mxnet_serving_autoscale_actual_replicas'
                         f'{{model="{_esc(name)}"}} {st["actual"]}')
            L.append("# HELP mxnet_serving_autoscale_decisions_total "
                     "Scale decisions applied, by action.")
            L.append("# TYPE mxnet_serving_autoscale_decisions_total "
                     "counter")
            for action, n in sorted(
                    scale.get("decisions", {}).items()):
                L.append(f'mxnet_serving_autoscale_decisions_total'
                         f'{{action="{_esc(action)}"}} {n}')
            L.append("# HELP mxnet_serving_autoscale_evictions_total "
                     "Models evicted from a replica by the HBM "
                     "bin-packer (LRU), by model.")
            L.append("# TYPE mxnet_serving_autoscale_evictions_total "
                     "counter")
            for name, n in sorted(
                    scale.get("evictions", {}).items()):
                L.append(f'mxnet_serving_autoscale_evictions_total'
                         f'{{model="{_esc(name)}"}} {n}')
            L.append("# HELP mxnet_serving_autoscale_replica_seconds_"
                     "total Integrated live-replica time (the fleet-"
                     "economics number the autoscale bench gates).")
            L.append("# TYPE mxnet_serving_autoscale_replica_seconds_"
                     "total counter")
            L.append(f"mxnet_serving_autoscale_replica_seconds_total "
                     f"{scale.get('replica_seconds', 0.0):.3f}")
        L.append("# HELP mxnet_serving_fleet_failovers_total Request "
                 "hops retried on a different replica.")
        L.append("# TYPE mxnet_serving_fleet_failovers_total counter")
        L.append(f"mxnet_serving_fleet_failovers_total {failovers}")
        L.append("# HELP mxnet_serving_fleet_probe_failures_total "
                 "Active health-probe failures per replica.")
        L.append("# TYPE mxnet_serving_fleet_probe_failures_total "
                 "counter")
        for rid, n in sorted(probe_failures.items()):
            L.append(f'mxnet_serving_fleet_probe_failures_total'
                     f'{{replica="{_esc(rid)}"}} {n}')
        L.append("# HELP mxnet_serving_fleet_hedges_total Hedged "
                 "second requests launched / won the race.")
        L.append("# TYPE mxnet_serving_fleet_hedges_total counter")
        L.append(f'mxnet_serving_fleet_hedges_total'
                 f'{{event="launched"}} {launched}')
        L.append(f'mxnet_serving_fleet_hedges_total'
                 f'{{event="won"}} {won}')
        L.append("# HELP mxnet_serving_fleet_route_ms End-to-end "
                 "routed request latency (all hops + hedges).")
        L.append("# TYPE mxnet_serving_fleet_route_ms histogram")
        L.extend(self.route_ms.prom_lines("mxnet_serving_fleet_route_ms"))
        # slow-route exemplars: trace ids to feed tools/traceview.py
        # (fleet-wide, then per model) — comment lines, scraper-inert
        for ex in self.slow.exemplars():
            L.append(f'# exemplar mxnet_serving_fleet_route_ms '
                     f'trace_id={ex["trace_id"]} ms={ex["ms"]}')
        for name, m in sorted(by_model.items()):
            for ex in m.slow.exemplars():
                L.append(f'# exemplar mxnet_serving_fleet_route_ms'
                         f'{{model="{_esc(name)}"}} '
                         f'trace_id={ex["trace_id"]} ms={ex["ms"]}')
        return "\n".join(L) + "\n"

    def snapshot(self):
        """Flat dict view for profiler dumps and the fleet bench."""
        states = self._replica_states()
        with self._lock:
            out = {
                "replicas": {rid: dict(st)
                             for rid, st in sorted(states.items())},
                "ready": sum(1 for st in states.values()
                             if st["state"] == "ready"
                             and st["healthy"]),
                "requests": dict(self._codes),
                "failovers": self.failovers,
                "hedges_launched": self.hedges_launched,
                "hedges_won": self.hedges_won,
                "migrations": self.migrations,
                "session_losses": self.session_losses,
                "route_cancels": self.route_cancels,
                "sessions": (self._session_count_fn()
                             if self._session_count_fn else 0),
                "probe_failures": dict(self._probe_failures),
            }
        out["route_ms"] = self.route_ms.snapshot()
        out["models"] = self.model_stats()
        slow = self.slow.exemplars()
        if slow:
            out["slow_traces"] = slow
        if self._autoscale_fn is not None:
            out["autoscale"] = self._autoscale_fn()
        return out

    def register_with_profiler(self):
        from .. import profiler
        profiler.register_stats_provider("serving_fleet", self.snapshot)

    def unregister_from_profiler(self):
        """Detach at router shutdown — mirrors
        :meth:`ServingMetrics.unregister_from_profiler`: a dead fleet
        must not be kept alive by the provider registry."""
        from .. import profiler
        profiler.unregister_stats_provider("serving_fleet",
                                           self.snapshot)
