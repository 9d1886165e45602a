"""Versioned model registry over ``deploy.load_predictor`` artifacts.

The reference framework's predict runtime loads one symbol+params pair
per process; a server needs a *repository*: several named models, each
with a live version, loadable/unloadable/reloadable while traffic
flows.  Three properties are load-bearing:

* **Warmup at load time** — ``warmup(bucket_sizes)`` pushes one zeros
  batch per padding bucket through the predictor, so every executable
  the batcher can request is compiled before the model is visible to
  traffic.  No user request ever pays a cold XLA compile (on TPU those
  are seconds, not microseconds).
* **Atomic reload** — the replacement version is fully loaded *and
  warmed* off to the side, then swapped in under the lock; the old
  version's batcher drains (in-flight requests finish on the weights
  they started with) and only then is it dropped.
* **Shared observability** — the repository feeds compile counts and
  queue depths to :class:`.metrics.ServingMetrics`, which is where the
  "compile count flatlines after warmup" invariant is scraped from.
"""
from __future__ import annotations

import contextlib
import threading

from ..base import get_env
from .. import trace
from ..locks import named_lock
from .admission import (Admission, ModelNotFound, ServingError,
                        checked_enqueue, slo_class)
from .batcher import DynamicBatcher, WeightedFairGate, parse_buckets

__all__ = ["ModelRepository", "ModelEntry"]


class ModelEntry:
    """One live (name, version) binding: predictor + its batcher."""

    __slots__ = ("name", "version", "path", "predictor", "batcher",
                 "cold_start_ms", "slo")

    def __init__(self, name, version, path, predictor, batcher,
                 slo=None):
        self.name = name
        self.version = version
        self.path = path
        self.predictor = predictor
        self.batcher = batcher
        self.cold_start_ms = None      # set once load + warmup finishes
        self.slo = slo_class(slo)      # SLO class (admission + WFQ)

    def describe(self):
        return {
            "version": self.version,
            "path": self.path,
            "slo": self.slo.name,
            "buckets": list(self.batcher.buckets),
            "max_batch": self.batcher.max_batch,
            "batch_polymorphic": self.predictor.batch_polymorphic,
            "cold_start_ms": self.cold_start_ms,
            "aot_buckets": self.predictor.aot_buckets,
            "aot_load_failures": self.predictor.aot_load_failures,
            "device": {"platform": self.predictor.device.platform,
                       "kind": self.predictor.device.device_kind},
            "compile_count": self.predictor.compile_count,
            "queue_depth": self.batcher.depth,
            "inputs": self.predictor.meta["inputs"],
            "outputs": self.predictor.meta["outputs"],
            "graphlint_findings": (self.predictor.meta.get("graphlint")
                                   or {}).get("findings"),
            "memlint": self.memory_summary(),
        }

    def memory_summary(self):
        """Export-time memory plan (deploy._export_memlint): the
        per-model peak-HBM estimate and donation accounting the
        /metrics gauges report."""
        ml = self.predictor.meta.get("memlint") or {}
        return {
            "peak_hbm_bytes": ml.get("peak_hbm_bytes"),
            "donated_bytes_reclaimed": ml.get("donated_bytes_reclaimed"),
            "undonated_bytes": ml.get("undonated_bytes"),
            "donate_argnums": self.predictor.meta.get("donate_argnums"),
        }


class ModelRepository:
    def __init__(self, metrics=None, admission=None, buckets=None,
                 warmup=None):
        self.metrics = metrics
        self.admission = admission or Admission()
        self._buckets = (list(buckets) if buckets is not None
                         else parse_buckets())
        self._warmup_default = (
            warmup if warmup is not None
            else get_env("MXNET_SERVING_WARMUP", True, bool))
        self._models: dict[str, ModelEntry] = {}
        self._retired: list[ModelEntry] = []
        self._loading: dict[str, int] = {}   # name -> in-flight builds
        # one WFQ gate per repository: batches of co-packed models are
        # admitted to the device in SLO-weighted fair order
        self.exec_gate = WeightedFairGate()
        self._lock = named_lock("models.repository")
        if self.metrics is not None:
            self.metrics.attach_repository(self)

    def set_metrics(self, metrics):
        """Rebind the repository (and every live batcher) to a metrics
        instance — the server calls this when adopting a repository
        that was constructed without one, so batch counters don't
        silently vanish."""
        self.metrics = metrics
        with self._lock:
            entries = list(self._models.values()) + list(self._retired)
        for e in entries:
            e.batcher.metrics = metrics
        if metrics is not None:
            metrics.attach_repository(self)

    # -- build/teardown ----------------------------------------------

    @contextlib.contextmanager
    def _loading_state(self, name):
        """Track that ``name`` is being built (load + warmup): health
        probes report it as ``loading`` so a fleet prober / rolling
        reload can tell "warming, admit later" from "never heard of
        it".  Counted, not flagged — a reload racing a load must not
        clear the other's marker."""
        with self._lock:
            self._loading[name] = self._loading.get(name, 0) + 1
        try:
            yield
        finally:
            with self._lock:
                n = self._loading.get(name, 1) - 1
                if n <= 0:
                    self._loading.pop(name, None)
                else:
                    self._loading[name] = n

    def loading_names(self):
        """Names with a build (load or reload replacement) in flight."""
        with self._lock:
            return sorted(self._loading)

    def _build_entry(self, name, path, version, warmup, slo=None):
        import time
        from ..deploy import load_predictor
        slo = slo_class(slo)
        t0 = time.monotonic()
        # a load paid inside a request trace (scale-from-zero, cold
        # admin verbs) shows up as its own span — the cold-start cost
        # attributed to exactly the request that paid it
        with trace.span("model.load", model=name, version=version):
            predictor = load_predictor(path)
            # the artifact carries its export-time IR bill of health
            # (deploy._export_graphlint, docs/graph_analysis.md); the
            # deserialized executable is opaque to re-linting, so
            # surface the recorded findings at the serving boundary
            gl = predictor.meta.get("graphlint") or {}
            if gl.get("findings"):
                import warnings
                warnings.warn(
                    f"model {name!r} ({path}) exported with "
                    f"{gl['findings']} graphlint finding(s) "
                    f"{gl.get('by_rule')} — see its meta.json for "
                    "details")
            batcher = DynamicBatcher(name, predictor,
                                     metrics=self.metrics,
                                     buckets=self._buckets,
                                     exec_gate=self.exec_gate,
                                     weight=slo.weight)
            entry = ModelEntry(name, version, path, predictor, batcher,
                               slo=slo)
            do_warmup = (self._warmup_default if warmup is None
                         else warmup)
            if do_warmup:
                try:
                    self.warmup_entry(entry)
                except Exception:
                    # a failed warmup must not leak the worker thread
                    # (and through its closure the predictor's weights)
                    entry.batcher.drain()
                    raise
            # cold start = load (deserialize weights/graph + AOT
            # blobs) + warmup (executes every bucket); with a full AOT
            # bucket set this is deserialization, not compilation, and
            # compile_count at ready is 0 from process start
            entry.cold_start_ms = round(
                (time.monotonic() - t0) * 1000.0, 3)
        if self.metrics is not None:
            self.metrics.record_cold_start(
                name, entry.cold_start_ms,
                aot_loads=len(entry.predictor.aot_buckets),
                aot_load_failures=entry.predictor.aot_load_failures,
                compile_count=entry.predictor.compile_count)
        from .. import flightrec
        flightrec.record(flightrec.LIFECYCLE, "model.loaded",
                         model=name, version=version,
                         ms=entry.cold_start_ms,
                         compiles=entry.predictor.compile_count)
        return entry

    def warmup_entry(self, entry, bucket_sizes=None):
        if bucket_sizes is None:
            # the batcher's compile universe: every bucket a batch of
            # 1..max_batch requests can pad to.  That is the buckets
            # below the flush cap PLUS the bucket covering max_batch
            # itself — when the cap sits between buckets (max_batch=20,
            # buckets ...16,32) a 17..20-request batch pads to 32, which
            # must be warm too or the flatline invariant breaks
            b = entry.batcher
            sizes = sorted({s for s in b.buckets if s <= b.max_batch}
                           | {b._bucket_for(b.max_batch)})
        else:
            sizes = list(bucket_sizes)
        return entry.predictor.warmup(sizes)

    def load(self, name, path, version=None, warmup=None, slo=None):
        """Load a new model under ``name``; errors if it exists
        (``reload`` is the replace verb).  The entry only becomes
        visible after a successful load + warmup.  ``slo`` names the
        model's :class:`~.admission.SloClass` (admission shed order +
        WFQ weight); default ``standard``."""
        with self._loading_state(name):
            entry = self._build_entry(
                name, path, 1 if version is None else int(version),
                warmup, slo=slo)
        with self._lock:
            if name in self._models:
                entry.batcher.close()
                raise ServingError(
                    f"model {name!r} already loaded (v"
                    f"{self._models[name].version}); use reload")
            self._models[name] = entry
        return entry.describe()

    def reload(self, name, path=None, version=None, warmup=None,
               slo=None):
        """Atomic swap: build + warm the replacement, then swap the
        name binding; in-flight requests finish on the old version,
        whose batcher drains in the background.  ``slo`` defaults to
        the old version's class (a reload is not a policy change)."""
        with self._lock:
            old = self._models.get(name)
        if old is None:
            raise ModelNotFound(f"model {name!r} is not loaded")
        with self._loading_state(name):
            entry = self._build_entry(
                name, path or old.path,
                old.version + 1 if version is None else int(version),
                warmup, slo=slo if slo is not None else old.slo)
        with self._lock:
            old = self._models.get(name)   # re-read: racing reload/unload
            if old is not None:
                self._models[name] = entry
                self._retired.append(old)
        if old is None:
            # lost the race to an unload while building: tear down the
            # replacement (outside the lock — drain joins the worker)
            entry.batcher.drain()
            raise ModelNotFound(
                f"model {name!r} was unloaded during reload")
        threading.Thread(target=self._retire, args=(old,),
                         daemon=True).start()
        return entry.describe()

    def _retire(self, entry):
        entry.batcher.drain()
        with self._lock:
            try:
                self._retired.remove(entry)
            except ValueError:
                pass

    def unload(self, name):
        with self._lock:
            entry = self._models.pop(name, None)
        if entry is None:
            raise ModelNotFound(f"model {name!r} is not loaded")
        entry.batcher.drain()
        self.exec_gate.forget(name)
        from .. import flightrec
        flightrec.record(flightrec.LIFECYCLE, "model.unloaded",
                         model=name, version=entry.version)
        return {"unloaded": name, "version": entry.version}

    def drain_all(self, timeout=30.0):
        """Graceful shutdown: stop admission, flush every queue."""
        self.admission.begin_drain()
        with self._lock:
            entries = list(self._models.values()) + list(self._retired)
        for e in entries:
            e.batcher.drain(timeout)

    # -- request path -------------------------------------------------

    def get(self, name):
        with self._lock:
            entry = self._models.get(name)
        if entry is None:
            raise ModelNotFound(f"model {name!r} is not loaded")
        return entry

    def has(self, name):
        with self._lock:
            return name in self._models

    def _submit_current(self, name, submit):
        """Resolve the live entry and run ``submit(entry)``, chasing a
        concurrent reload: between ``get`` and the batcher enqueue the
        name can be swapped to a new version and the OLD batcher begin
        draining — such a request is neither in-flight (it never
        enqueued) nor misaddressed (the model still serves), so it
        must land on the replacement, not die 503.  A genuine drain
        (server shutdown) or unload still surfaces typed."""
        from .admission import ShuttingDown
        entry = self.get(name)
        checked_enqueue(name)
        while True:
            try:
                return submit(entry)
            except ShuttingDown:
                if self.admission.draining:
                    raise              # whole-server drain: real 503
                fresh = self.get(name)  # unloaded -> ModelNotFound
                if fresh is entry:
                    raise              # draining for its own reasons
                entry = fresh          # reload swapped: retry on new

    def predict(self, name, inputs, deadline_ms=None):
        """Admission-gated batched predict; the server's hot path.
        The depth bound runs under the batcher's queue lock
        (``Admission.gate``) so concurrent arrivals cannot race past
        it; the ``serving.enqueue`` fault point fires outside the lock
        (an injected delay must not stall the flush worker)."""
        return self._submit_current(name, lambda entry:
            entry.batcher.submit(
                inputs, self.admission.deadline_ms(deadline_ms),
                admit=self.admission.gate(name, slo=entry.slo)))

    def predict_async(self, name, inputs, deadline_ms=None):
        """Admission-gated ``submit_async``: returns a
        :class:`~.batcher.PendingResult` so one caller thread can keep
        many single requests in flight."""
        return self._submit_current(name, lambda entry:
            entry.batcher.submit_async(
                inputs, self.admission.deadline_ms(deadline_ms),
                admit=self.admission.gate(name, slo=entry.slo)))

    # -- introspection ------------------------------------------------

    def models(self):
        with self._lock:
            entries = dict(self._models)
        return {name: e.describe() for name, e in entries.items()}

    def compile_counts(self):
        with self._lock:
            entries = dict(self._models)
        return {name: e.predictor.compile_count
                for name, e in entries.items()}

    def queue_depths(self):
        with self._lock:
            entries = dict(self._models)
        return {name: e.batcher.depth for name, e in entries.items()}

    def memory_summaries(self):
        """Per-model export-time memory plans for the /metrics gauges
        (peak-HBM estimate, donated-bytes-reclaimed)."""
        with self._lock:
            entries = dict(self._models)
        return {name: e.memory_summary() for name, e in entries.items()}
