"""Unified Executor: the single compile choke point, and the cold-start
caches stacked on top of it.

The framework has four separately-grown compile surfaces — the Gluon
``CachedOp`` (gluon/block.py), bulked eager segments (ops/bulking.py),
the fused train step (fuse.py) and the deploy ``Predictor`` (deploy.py).
Each used to wire the same three cross-cutting concerns by hand: the
recompile sentinel's ``instrument``, graphlint's ``check_traced`` and
memlint's ``check_memory``, plus its own ad-hoc trace-cache dict.  This
module is the one place all of that lives now:

* :class:`Executor` — wraps the python function a surface hands to
  ``jax.jit``: sentinel instrumentation, donation/sharding options, and
  the jit object itself, with a ``compile_count`` probe shared by the
  serving metrics.  Creating an Executor is also the point where the
  persistent compilation cache is switched on (below), so *every*
  compile surface rides it without per-surface wiring.
* :func:`run_analyses` — THE build-time graphlint/memlint wiring.  A
  surface states its contract (donation, allowed-undonated positions,
  ignored rules); the gating on ``MXNET_GRAPH_LINT`` /
  ``MXNET_GRAPH_MEMLINT`` and the calls into the analysis passes happen
  here, once.
* :class:`TraceCache` — the shared trace-cache shape (lock, hit/miss
  counters, stats) behind ``CachedOp._cache`` and the bulking segment
  cache, so "did a steady-state loop retrace" is answerable uniformly.

Cold-start persistence (ROADMAP item 2 — replica cold-start from
minutes to seconds) stacks two layers on this choke point:

* **Persistent XLA compilation cache** — always on: where
  ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
  code names no directory at all; otherwise the cache lives at the
  fixed path ``<checkout>/.jax_cache`` (the path is part of the cache
  key, so it never moves).  A second process on the same host (a
  serving replica spawn, an elastic worker join, a rolling reload, the
  next phase of ``chip_smoke.py``) skips XLA compilation for every
  graph the first process built.  Decided at ONE init point
  (:func:`ensure_compile_cache`), called by every Executor
  construction, with min-entry-size / min-compile-time thresholds so
  tiny graphs don't churn the directory.
* **AOT-serialized executables** — :func:`serialize_executable` /
  :func:`deserialize_executable` wrap
  ``jax.experimental.serialize_executable`` with a versioned
  compatibility envelope (jax/jaxlib versions + platform), so deploy
  artifacts can ship per-bucket *compiled* executables and a loader can
  refuse — loudly, with a recompile fallback — a blob built by a
  different toolchain instead of crashing inside an unpickler.

Observability: a ``cold_start`` profiler stats provider reports time
since this module's import, per-site build counts, the persistent-cache
configuration, AOT load hits/failures, under ``"setup"`` the process
trace folded by span name (:func:`trace.process_summary`: the import,
each leaf's initialisation, the step's build and first call, every
compile, with when each began and ended) and -- under ``"jit"`` -- what
JAX itself reports of every compile
(``jax.monitoring``): seconds tracing, lowering, in backend compile
and retrieving from the persistent cache, and counts of compiles and
of cache hits and misses, per jitted function (the calling
:class:`Executor`'s site where one is calling, else the function's
name).  The listeners run only when something compiles; each compile
is also the process span ``jit.compile`` and the flight recorder's
``jit.compiled``, with its time of day.
``Executor.__call__`` times the call as the ``executor.call`` span of
:mod:`.trace`.
"""
from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time

import jax
from jax import monitoring as _monitoring
from jax.experimental.compilation_cache import compilation_cache as _cc

from . import trace as _trace
from .base import get_env
from .locks import named_lock

__all__ = ["Executor", "TraceCache", "run_analyses", "lint_active",
           "memlint_active", "ensure_compile_cache",
           "compile_cache_bypassed",
           "serialize_executable", "deserialize_executable", "aot_compat",
           "AOTCompatError", "record_aot_load", "since_import_ms",
           "compile_log", "stats", "reset_stats"]

_IMPORT_T0 = time.monotonic()   # this module's import, not process start

_lock = named_lock("executor.state")
_state = {
    "cache_init_done": False,
    "cache_dir": None,
    "aot_loads": 0,
    "aot_load_failures": 0,
    "analyses": 0,
}
_sites: dict[str, dict] = {}       # site -> {"executors": n}
_provider_registered = False

# what jax.monitoring reports of each compile, folded per jitted function
_JIT_FIELDS = ("compiles", "trace_s", "lower_s", "backend_compile_s",
               "cache_retrieval_s", "cache_hits", "cache_misses")
_jit: dict[str, dict] = {}         # site or function name -> the fields
_jit_log = collections.deque(maxlen=4096)   # one record per compile


class _Compiling(threading.local):
    """What this thread is compiling: the calling Executor's site, the
    last traced function ``(name, seconds)``, the open compile record."""
    site = trace = record = None


_compiling = _Compiling()


class AOTCompatError(RuntimeError):
    """An AOT-serialized executable was built by an incompatible
    toolchain (jax/jaxlib version or platform mismatch) or the blob is
    malformed.  Loaders catch this and fall back to recompilation."""


# ---------------------------------------------------------------------------
# persistent compilation cache — the one shared init point
# ---------------------------------------------------------------------------

_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def ensure_compile_cache():
    """The one rule for where JAX's persistent compilation cache lives.

    * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself;
      no directory is set in code (nothing else, this package's own
      variables included, can override it).
    * unset: ``<checkout>/.jax_cache`` — derived from this package's
      path, so every process of one checkout (trainer, server,
      replicas, ``chip_smoke.py`` children) shares it and a re-run
      finds what the last run compiled.

    Idempotent and cheap after the first call; every Executor
    construction routes through here, so any process that compiles
    anything gets the cache without per-surface wiring.  Returns the
    directory in effect.  ``JAX_ENABLE_COMPILATION_CACHE=false``
    switches the cache off (cold-start measurements, the test suite).

    Thresholds (both default to "cache everything" because cold start
    is what the cache exists to kill; raise them on hosts where the
    cache directory competes with real data):

    * ``MXNET_COMPILE_CACHE_MIN_ENTRY_BYTES`` — skip persisting
      executables smaller than this.
    * ``MXNET_COMPILE_CACHE_MIN_COMPILE_SECS`` — skip persisting
      compilations faster than this.
    """
    with _lock:
        if _state["cache_init_done"]:
            return _state["cache_dir"]
        _state["cache_init_done"] = True
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          get_env("MXNET_COMPILE_CACHE_MIN_ENTRY_BYTES",
                                  0, int))
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          get_env("MXNET_COMPILE_CACHE_MIN_COMPILE_SECS",
                                  0.0, float))
        # the key holds the instructions' metadata too: by default jax
        # strips it, and a program that differs only in its
        # ``jax.named_scope`` names would be served an executable whose
        # instructions carry the older names -- a profile of it would
        # attribute device time to scopes that are gone
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not d:
            d = _DEFAULT_CACHE_DIR
            jax.config.update("jax_compilation_cache_dir", d)
        # jax's cache module latches its enabled/disabled state at
        # the first compile; anything compiled before this init
        # (eager op dispatch during import) would leave it stuck
        # disabled — drop the latch so the settings take effect
        _cc.reset_cache()
        _state["cache_dir"] = d
        return d


@contextlib.contextmanager
def compile_cache_bypassed():
    """Compile with the persistent cache out of the way (neither read
    nor written).  For compiles whose product is itself serialized —
    an executable *served from* the cache can re-serialize
    incompletely — and for described-topology compiles, whose entries
    no attached device can read back."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    _cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        _cc.reset_cache()


def _reset_compile_cache_for_tests():
    """Allow a test to re-run ensure_compile_cache with a fresh env."""
    with _lock:
        _state["cache_init_done"] = False
        _state["cache_dir"] = None


# ---------------------------------------------------------------------------
# the choke point
# ---------------------------------------------------------------------------

def _ensure_provider():
    global _provider_registered
    if _provider_registered:
        return
    _provider_registered = True
    from . import profiler
    profiler.register_stats_provider("cold_start", stats)
    _monitoring.register_event_duration_secs_listener(_on_jit_duration)
    _monitoring.register_event_listener(_on_jit_event)


def _compile_record(module_name, **fields):
    """A compile's record, every counter 0 but ``fields``, for the module
    ``jit(step)`` (what jax names it) under its function's name ``step``."""
    fun = module_name.partition("(")[2].rstrip(")") or module_name
    return dict(dict.fromkeys(_JIT_FIELDS, 0), fun=fun, **fields)


def _on_jit_duration(event, seconds, fun_name=None, **_):
    """``jax.monitoring`` duration listener.  A compile reports, on the
    thread that makes it and in this order: the tracing of every jitted
    function nested in the program and then of the program itself (the
    last before lowering), its lowering, the persistent cache's events,
    and the backend compile that encloses them.  One record a compile is
    opened at the lowering and folded at the backend compile; nested
    traces are inside the program's own and are not added to it."""
    if event == "/jax/core/compile/jaxpr_trace_duration":
        _compiling.trace = (fun_name, seconds)
    elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        traced, trace_s = _compiling.trace or (None, 0.0)
        _compiling.trace = None
        record = _compiling.record = _compile_record(fun_name,
                                                     lower_s=seconds)
        if traced == record["fun"]:
            record["trace_s"] = trace_s
    elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
        record = _compiling.record
        if record is not None:
            record["cache_retrieval_s"] += seconds
    elif event == "/jax/core/compile/backend_compile_duration":
        fresh = _compile_record(fun_name)
        record = _compiling.record
        if record is None or record["fun"] != fresh["fun"]:
            record = fresh                           # lowered elsewhere
        _compiling.record = None
        record.update(compiles=1, backend_compile_s=seconds,
                      at=time.perf_counter(),
                      site=_compiling.site)
        with _lock:
            into = _jit.setdefault(record["site"] or record["fun"],
                                   dict.fromkeys(_JIT_FIELDS, 0))
            for field in _JIT_FIELDS:
                into[field] += record[field]
            _jit_log.append(record)
        # every compile of the process with its time of day, sampling on
        # or off: in the process trace (under the span that paid it) and
        # on the flight ring, where a postmortem looks for recompiles
        spent = {f: record[f] for f in (
            "trace_s", "lower_s", "backend_compile_s", "cache_retrieval_s")}
        _trace.record_process_span(
            "jit.compile", record["trace_s"] + record["lower_s"] + seconds,
            fun=record["fun"], site=record["site"],
            cache_hit=record["cache_hits"] > 0, **spent)
        from . import flightrec as _flightrec
        _flightrec.record(_flightrec.COMPILE, "jit.compiled",
                          fun=record["fun"], site=record["site"], **spent)


def _on_jit_event(event, **_):
    """``jax.monitoring`` event listener: the persistent cache's hit or
    miss of the compile this thread is making."""
    record = _compiling.record
    if record is not None:
        if event == "/jax/compilation_cache/cache_hits":
            record["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            record["cache_misses"] += 1


class Executor:
    """One jitted entry point built through the unified choke point.

    ``Executor(fn, site)`` is the replacement for a bare
    ``jax.jit(_recompile.instrument(fn, site), ...)``: persistent-cache
    init, sentinel instrumentation and the jit options live here; the
    surface keeps only its calling convention.  ``executor.jfn`` is the
    jitted callable; :attr:`compile_count` probes the jit executable
    cache (the serving "must flatline after warmup" counter).
    """

    __slots__ = ("site", "fn", "jfn", "donate_argnums")

    def __init__(self, fn, site, donate_argnums=(), in_shardings=None,
                 static_argnums=None, static_argnames=None,
                 instrument=True):
        from .analysis import recompile as _recompile
        ensure_compile_cache()
        _ensure_provider()
        self.site = site
        self.fn = fn
        self.donate_argnums = tuple(donate_argnums)
        kwargs = {}
        if self.donate_argnums:
            kwargs["donate_argnums"] = self.donate_argnums
        if in_shardings is not None:
            kwargs["in_shardings"] = in_shardings
        if static_argnums is not None:
            kwargs["static_argnums"] = static_argnums
        if static_argnames is not None:
            kwargs["static_argnames"] = static_argnames
        # instrument=False is for surfaces that detect their own cache
        # misses and report a richer compile signature themselves (the
        # bulking trace cache) via recompile.record_compile
        wrapped = _recompile.instrument(fn, site) if instrument else fn
        self.jfn = jax.jit(wrapped, **kwargs)  # mxlint: disable=MX-DONATE001(donation is threaded via kwargs — every Executor caller states its donate_argnums contract at construction, and () means caller-held inputs)
        # an Executor built while a request trace is active means that
        # request is paying a build the warm path would not — stamp it
        # on the trace (the XLA compile itself lands inside whatever
        # span is timing the call; this event names the site) AND on
        # the always-on flight ring, where a postmortem can see a
        # compile burst precede an incident even with tracing off
        _trace.add_event("executor.created", site=site)
        from . import flightrec as _flightrec
        _flightrec.record(_flightrec.COMPILE, "executor.created",
                          site=site)
        with _lock:
            _sites.setdefault(site, {"executors": 0})["executors"] += 1

    def __call__(self, *args, **kwargs):
        """The jitted call, timed as the ``executor.call`` span; what it
        compiles is counted under this executor's site."""
        was = _compiling.site
        _compiling.site = self.site
        try:
            with _trace.span("executor.call", site=self.site):
                return self.jfn(*args, **kwargs)
        finally:
            _compiling.site = was

    def lower(self, *args, **kwargs):
        return self.jfn.lower(*args, **kwargs)

    @property
    def compile_count(self):
        """Distinct executables this entry point compiled (jit cache
        probe; AOT-loaded executables never appear here — that is the
        point).  A probe that cannot be read raises: 0 would say
        "compiled nothing"."""
        return int(self.jfn._cache_size())

    def analyze(self, args, graphlint=None, memlint=None,
                shardlint=None):
        """Run the build-time analyses over the *uninstrumented* fn with
        this executor's donation contract pre-applied (a surface can
        still override per-call)."""
        gl = dict(graphlint) if graphlint is not None else None
        ml = dict(memlint) if memlint is not None else None
        sl = dict(shardlint) if shardlint is not None else None
        if gl is not None:
            gl.setdefault("donate_argnums", self.donate_argnums)
        if ml is not None:
            ml.setdefault("donate_argnums", self.donate_argnums)
        if sl is not None:
            sl.setdefault("donate_argnums", self.donate_argnums)
        return run_analyses(self.fn, args, name=self.site,
                            graphlint=gl, memlint=ml, shardlint=sl)


def lint_active():
    """Whether build-time graphlint is on (``MXNET_GRAPH_LINT`` /
    ``graphlint.set_lint_mode``) — for frontends that gate expensive
    argument prep or manage an analyzed-once latch."""
    from .analysis import graphlint
    return graphlint.lint_mode() is not None


def memlint_active():
    """Whether build-time memlint is on (``MXNET_GRAPH_MEMLINT`` /
    ``memlint.set_mem_mode``)."""
    from .analysis import memlint
    return memlint.mem_mode() is not None


def shardlint_active():
    """Whether build-time shardlint is on (``MXNET_GRAPH_SHARDLINT`` /
    ``shardlint.set_shard_mode``)."""
    from .analysis import shardlint
    return shardlint.shard_mode() is not None


def latch_train_analyses(executor, args, lint_done, memlint_done):
    """One-shot build-time graphlint/memlint for a donated train
    program (the fused step and the chunked loop share this exact
    discipline): each latch sets only once its mode is on, so
    enabling a mode after step 1 still analyzes; GL-DEAD001 is
    ignored by documented scope limit (AD transposition leaves dead
    primal eqns in every value_and_grad trace — straight-line or
    scanned); donation is REQUIRED (the train-state carry contracts
    to donate).  Returns the updated ``(lint_done, memlint_done)``."""
    do_lint = not lint_done and lint_active()
    do_mem = not memlint_done and memlint_active()
    if do_lint or do_mem:
        from .analysis import graphlint as _graphlint
        executor.analyze(
            args,
            graphlint=dict(
                check_donation=True,
                config=_graphlint.Config(ignore={"GL-DEAD001"}),
            ) if do_lint else None,
            memlint=dict(require_donation=True) if do_mem else None)
    return lint_done or do_lint, memlint_done or do_mem


def run_analyses(fn, args, name, graphlint=None, memlint=None,
                 shardlint=None):
    """THE graphlint/memlint/shardlint build-time wiring (previously
    copied at every compile surface).  ``graphlint``/``memlint``/
    ``shardlint`` are kwarg dicts for
    :func:`analysis.graphlint.check_traced` /
    :func:`analysis.memlint.check_memory` /
    :func:`analysis.shardlint.check_sharding` — pass ``None`` to skip a
    pass entirely, ``{}`` for the defaults.  Inert (three cached env
    reads) unless the respective mode is on.  Returns
    ``(findings, mem_report)``; the shard report is recorded in the
    ``shardlint`` profiler provider's per-site stats.
    """
    findings = rep = None
    if graphlint is not None:
        from .analysis import graphlint as _graphlint
        if _graphlint.lint_mode() is not None:
            findings = _graphlint.check_traced(fn, args, name=name,
                                               **graphlint)
    if memlint is not None:
        from .analysis import memlint as _memlint
        if _memlint.mem_mode() is not None:
            rep = _memlint.check_memory(fn, args, name=name, **memlint)
    srep = None
    if shardlint is not None:
        from .analysis import shardlint as _shardlint
        if _shardlint.shard_mode() is not None:
            srep = _shardlint.check_sharding(fn, args, name=name,
                                             **shardlint)
    if findings is not None or rep is not None or srep is not None:
        with _lock:
            _state["analyses"] += 1
    return findings, rep


class TraceCache:
    """Keyed executable cache with hit/miss accounting — the shared
    shape behind CachedOp's per-signature cache and the bulking segment
    cache.  Keys are the caller's business (op sequence / Block
    signature / bucket + shapes/dtypes/statics); this class owns the
    lock and the counters so cache behavior is observable uniformly."""

    __slots__ = ("name", "_d", "_lock", "hits", "misses")

    def __init__(self, name):
        self.name = name
        self._d: dict = {}
        self._lock = named_lock("executor.cache")
        self.hits = 0
        self.misses = 0

    def get(self, key):
        with self._lock:
            entry = self._d.get(key)
            if entry is None:
                self.misses += 1
            else:
                self.hits += 1
            return entry

    def put(self, key, value):
        with self._lock:
            self._d[key] = value
        return value

    def get_or_create(self, key, factory):
        """Atomic lookup-or-build: ``factory()`` runs under the cache
        lock, so two threads racing on one key can never build (and
        report to the sentinel) twice.  Returns ``(entry, hit)``.

        Build-vs-cache-hit is trace-visible: a hit adds an instant
        event to the active request span, a miss times ``factory()``
        as an ``executor.build`` span — the difference between "paid a
        compile" and "replayed an executable" for exactly the request
        that paid it (docs/observability.md)."""
        with self._lock:
            entry = self._d.get(key)
            if entry is not None:
                self.hits += 1
                _trace.add_event("trace_cache.hit", cache=self.name)
                return entry, True
            self.misses += 1
            with _trace.span("executor.build", cache=self.name):
                entry = self._d[key] = factory()
            return entry, False

    def peek(self, key):
        """Lookup without touching the hit/miss counters (re-checks
        after a race, stats probes)."""
        with self._lock:
            return self._d.get(key)

    def clear(self):
        with self._lock:
            n = len(self._d)
            self._d.clear()
        return n

    def __len__(self):
        with self._lock:
            return len(self._d)

    def stats(self):
        with self._lock:
            return {"entries": len(self._d), "hits": self.hits,
                    "misses": self.misses}


# ---------------------------------------------------------------------------
# AOT executable serialization (versioned envelope over jax.experimental)
# ---------------------------------------------------------------------------

_AOT_MAGIC = b"MXTAOT1\n"


def aot_compat():
    """The compatibility claim stamped into (and checked against) every
    AOT blob: serialized executables are jax/jaxlib/platform-exact."""
    import jaxlib
    backend = jax.default_backend()
    return {"format": "mxtpu_aot_v1",
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": backend}


def serialize_executable(compiled):
    """Envelope + payload for a ``jax.stages.Compiled`` (from
    ``jax.jit(...).lower(...).compile()``).  The envelope is a JSON
    header checked BEFORE the pickle payload is touched — an
    incompatible or corrupted blob must be rejected by a version
    string comparison, not by whatever an unpickler does with garbage.
    """
    from jax.experimental.serialize_executable import serialize
    payload, in_tree, out_tree = serialize(compiled)
    header = dict(aot_compat())
    # the devices the program was compiled for: the loader must hand
    # jax exactly these, or it loads onto every device of the backend
    header["devices"] = [
        d.id for d in compiled.runtime_executable().local_devices()]
    blob_header = json.dumps(header, sort_keys=True).encode()
    import pickle
    trees = pickle.dumps((in_tree, out_tree))
    parts = [_AOT_MAGIC,
             len(blob_header).to_bytes(8, "little"), blob_header,
             len(trees).to_bytes(8, "little"), trees,
             len(payload).to_bytes(8, "little"), payload]
    return b"".join(parts)


def deserialize_executable(blob, record=True):
    """Load an AOT blob back into a callable executable, onto the
    devices (by id) it was compiled for — a one-device program loads
    onto one device whether this process has 1, 4 or 8.

    Raises :class:`AOTCompatError` on any mismatch or corruption — the
    caller's contract is to catch it, warn loudly, and recompile.  The
    compat check runs before the pickle payload is deserialized.
    ``record=False`` keeps the load out of the ``cold_start``
    aot_loads/failure counters (export-time self-checks are
    validation, not cold-start cache traffic)."""
    try:
        if not blob.startswith(_AOT_MAGIC):
            raise AOTCompatError(
                "not an mxtpu AOT executable (bad magic); the artifact "
                "is corrupted or from an incompatible exporter")
        off = len(_AOT_MAGIC)

        def take(n):
            nonlocal off
            piece = blob[off:off + n]
            if len(piece) != n:
                raise AOTCompatError("truncated AOT executable blob")
            off += n
            return piece

        hlen = int.from_bytes(take(8), "little")
        header = json.loads(take(hlen).decode())
        want = aot_compat()
        mismatched = {k: (header.get(k), want[k]) for k in want
                      if header.get(k) != want[k]}
        if mismatched:
            raise AOTCompatError(
                "AOT executable was built by an incompatible toolchain: "
                + "; ".join(f"{k}: artifact={a!r} runtime={b!r}"
                            for k, (a, b) in sorted(mismatched.items()))
                + " — falling back to recompilation is required")
        import pickle
        tlen = int.from_bytes(take(8), "little")
        in_tree, out_tree = pickle.loads(take(tlen))
        plen = int.from_bytes(take(8), "little")
        payload = take(plen)
        by_id = {d.id: d for d in jax.devices()}
        ids = header.get("devices", [jax.devices()[0].id])
        if not all(i in by_id for i in ids):
            raise AOTCompatError(
                f"AOT executable was compiled for devices {ids}; this "
                f"process has {sorted(by_id)}")
        from jax.experimental.serialize_executable import \
            deserialize_and_load
        loaded = deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in ids])
        if record:
            record_aot_load(ok=True)
        return loaded
    except AOTCompatError:
        if record:
            record_aot_load(ok=False)
        raise
    except Exception as e:  # mxlint: allow-broad-except(any decode/unpickle failure of a foreign blob must surface as the typed compat error the fallback path catches)
        if record:
            record_aot_load(ok=False)
        raise AOTCompatError(
            f"AOT executable blob unusable ({type(e).__name__}: {e}); "
            "falling back to recompilation is required") from e


def record_aot_load(ok=True):
    """Count an AOT executable load (success/failure) for the
    ``cold_start`` stats provider and the serving gauges."""
    _ensure_provider()
    with _lock:
        _state["aot_loads" if ok else "aot_load_failures"] += 1


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

def since_import_ms():
    """Milliseconds since this module was imported (with the package, so
    a little after process start)."""
    return round((time.monotonic() - _IMPORT_T0) * 1000.0, 3)


def compile_log():
    """One record per compile since the counters were registered (the
    newest 4096): ``fun``, ``site`` (the calling Executor's, or None),
    ``at`` (``time.perf_counter()`` when the backend compile ended) and
    the fields of ``stats()["jit"]`` -- for a reader that has to cut the
    sums at a point in time."""
    with _lock:
        return [dict(r) for r in _jit_log]


def stats():
    """The ``cold_start`` profiler stats provider."""
    with _lock:
        # per-op eager sites (op:*) number in the hundreds — count them
        # but keep the detail table to the structural surfaces
        per_site = {k: dict(v) for k, v in _sites.items()
                    if not k.startswith("op:")}
        jit = {field: sum(f[field] for f in _jit.values())
               for field in _JIT_FIELDS}
        jit["per_function"] = {k: dict(v) for k, v in _jit.items()}
        out = {
            "since_import_ms": since_import_ms(),
            "persistent_cache_dir": _state["cache_dir"],
            "aot_loads": _state["aot_loads"],
            "aot_load_failures": _state["aot_load_failures"],
            "analyses": _state["analyses"],
            "sites": len(_sites),
            "op_sites": sum(1 for k in _sites if k.startswith("op:")),
            "per_site": per_site,
            "jit": jit,
        }
    out["setup"] = _trace.process_summary()
    return out


def reset_stats():
    """Drop per-site state (tests).  The persistent-cache init latch is
    deliberately kept — re-pointing a live process's cache dir is not a
    supported operation (use _reset_compile_cache_for_tests)."""
    with _lock:
        _sites.clear()
        _jit.clear()
        _jit_log.clear()
        _state["aot_loads"] = 0
        _state["aot_load_failures"] = 0
        _state["analyses"] = 0
