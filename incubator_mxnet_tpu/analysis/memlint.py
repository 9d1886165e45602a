"""memlint — liveness-based static HBM planning/analysis over traced graphs.

The reference framework's NNVM layer wins its memory leanness from a
*static memory-planning pass* (PAPER.md: shape inference → gradient →
memory planning → fusion): buffer lifetimes are computed on the graph,
in-place/identity ops alias their inputs, and outputs reuse dead
buffers.  XLA does its own planning at compile time, but the framework
above it decides the two things XLA cannot: **which input buffers are
donated** (``donate_argnums``) and **which traced outputs escape the
executable at all**.  memlint is the analyzer for both:

* a **liveness walk** over the same ``ClosedJaxpr``\\ s graphlint visits
  (recursing into pjit/scan/while/cond sub-jaxprs) computing a
  peak-HBM *estimate* per compiled graph — buffer sizes from avals,
  backward liveness over eqn outvars, donation and view-aliasing
  credited against the peak;
* a **per-buffer lifetime report** (birth eqn → last use, kind, bytes)
  naming the buffers that dominate the peak;
* **enforced donation findings**: the donation advisory graphlint
  emits as opt-in GL-DONATE001 graduates here to error-severity
  ``ML-DONATE001`` — at a surface that contracts to donate (the fused
  train step, CachedOp ``static_alloc``), an undonated input whose
  shape/dtype matches an output FAILS strict mode instead of merely
  advising.

Rules (docs/graph_analysis.md):

=============  ==========================================================
ML-DONATE001   an undonated input buffer shape/dtype-matches an output —
               XLA must hold input AND output alive together where
               ``donate_argnums`` would alias them.  Error severity at a
               surface that demands donation (fused step, static_alloc
               CachedOp), advisory elsewhere
ML-PEAK001     the peak-HBM estimate exceeds
               ``MXNET_MEMLINT_PEAK_BYTES`` (opt-in budget gate, off
               unless the env var is set)
=============  ==========================================================

Enforcement is the ``MXNET_GRAPH_MEMLINT`` env var (``warn``/``strict``,
same grammar as ``MXNET_GRAPH_LINT``) read by :func:`check_memory`, the
choke point wired at all four compile surfaces: the fused train step
(``fuse.py``), CachedOp builds (``gluon/block.py``), bulked-segment
flushes (``ops/bulking.py``) and the deploy/export path (``deploy.py``
records the summary in ``meta.json``; the serving repository surfaces
it).  Each analysis records per-site stats — peak-HBM estimate,
donated-bytes-reclaimed — exposed through the ``memlint`` profiler
stats provider (``profiler.dumps()``) and the serving ``/metrics``
gauges.

Estimator model and its known slack vs. real XLA allocation are
documented in docs/graph_analysis.md — the estimate is an upper bound
on *planned* buffers (XLA fusion eliminates many temporaries; scratch
space and layout padding are not modeled).
"""
from __future__ import annotations

import threading
import warnings as _warnings

import jax
from jax.extend import core as _jcore
import numpy as _onp

from ..base import get_env
from .graphlint import Finding, render

__all__ = ["RULES", "Config", "MemReport", "analyze_jaxpr", "analyze_fn",
           "analyze_block", "check_memory", "mem_mode", "set_mem_mode",
           "mem_scope", "record_bulk_reclaim", "segment_alias_credit",
           "record_segment_alias_credit", "stats", "reset_stats",
           "Finding", "render"]

RULES = {
    "ML-DONATE001": "undonated input shape/dtype-matches an output at a "
                    "donating surface",
    "ML-PEAK001": "peak-HBM estimate exceeds MXNET_MEMLINT_PEAK_BYTES",
}

#: jaxpr primitives whose single output XLA can alias onto the first
#: input's buffer (bitcast-compatible views).  Deliberately small:
#: transpose/broadcast change layout or size and get no credit.
_ALIAS_PRIMS = {"reshape", "bitcast_convert_type", "stop_gradient",
                "squeeze", "copy"}


class Config:
    """Thresholds for the memory passes.

    ``peak_bytes`` gates ML-PEAK001 (0 = off; defaults from
    ``MXNET_MEMLINT_PEAK_BYTES``); ``donate_min_bytes`` is the floor
    below which an undonated match is not worth a finding;
    ``top_buffers`` bounds the lifetime report; ``ignore`` silences
    whole rules for one analysis (the graphlint Config contract)."""

    __slots__ = ("peak_bytes", "donate_min_bytes", "top_buffers", "ignore")

    def __init__(self, peak_bytes=None, donate_min_bytes=1024,
                 top_buffers=10, ignore=()):
        if peak_bytes is None:
            peak_bytes = get_env("MXNET_MEMLINT_PEAK_BYTES", 0, int)
        self.peak_bytes = int(peak_bytes)
        self.donate_min_bytes = int(donate_min_bytes)
        self.top_buffers = int(top_buffers)
        self.ignore = frozenset(ignore)


def _aval(v):
    return getattr(v, "aval", None)


def _is_var(v):
    return not hasattr(v, "val")


def _nbytes(av):
    try:
        n = 1
        for d in av.shape:
            n *= int(d)
        return n * _onp.dtype(av.dtype).itemsize
    except (TypeError, ValueError, AttributeError):
        return 0


def _sig(av):
    return (tuple(getattr(av, "shape", ())), str(getattr(av, "dtype", "?")))


def _source_of(eqn):
    try:
        from jax._src import source_info_util as _siu
        return _siu.summarize(eqn.source_info)
    except Exception:  # mxlint: allow-broad-except(private jax API probe; a buffer without a source line is still accounted)
        return None


class _Buffer:
    """One planned allocation, possibly shared by several vars (view
    aliasing) or planned onto a donated input (donation reuse)."""

    __slots__ = ("nbytes", "shape", "dtype", "kind", "birth", "last",
                 "escapes", "alias_donated", "source")

    def __init__(self, nbytes, shape, dtype, kind, birth, source=None):
        self.nbytes = nbytes
        self.shape = shape
        self.dtype = dtype
        self.kind = kind          # const | input | donated_input | temp
        self.birth = birth        # -1 for entry buffers, else eqn index
        self.last = birth         # last eqn index that reads any member
        self.escapes = False      # some member is a graph output
        self.alias_donated = False  # output planned onto a donated input
        self.source = source

    @property
    def freeable(self):
        """May be released after its last use (vs. pinned to scope end:
        undonated inputs belong to the caller, consts to the
        executable, escaping buffers to the outputs)."""
        return not self.escapes and self.kind in ("temp", "donated_input")

    def as_dict(self):
        return {"nbytes": self.nbytes, "shape": list(self.shape),
                "dtype": self.dtype, "kind": self.kind,
                "birth": self.birth, "last_use": self.last,
                "escapes": self.escapes,
                "alias_donated": self.alias_donated,
                "source": self.source}


class MemReport:
    """Result of one analysis: the peak estimate, the credit breakdown,
    the dominant buffer lifetimes, and any findings."""

    __slots__ = ("where", "peak_bytes", "peak_eqn", "input_bytes",
                 "output_bytes", "const_bytes", "donated_bytes",
                 "donated_reclaimed_bytes", "undonated_bytes",
                 "alias_credit_bytes", "buffers", "findings", "n_eqns",
                 "donation_coverage")

    def __init__(self):
        self.where = None
        self.peak_bytes = 0
        self.peak_eqn = None
        self.input_bytes = 0
        self.output_bytes = 0
        self.const_bytes = 0
        self.donated_bytes = 0             # bytes of donated input buffers
        self.donated_reclaimed_bytes = 0   # output bytes planned onto them
        self.undonated_bytes = 0           # donatable-but-not-donated bytes
        self.alias_credit_bytes = 0        # view-aliased bytes not re-counted
        self.buffers = []                  # top-N lifetime dicts
        self.findings = []
        self.n_eqns = 0
        self.donation_coverage = None      # matched donated leaves / donated

    def as_dict(self):
        return {
            "where": self.where,
            "peak_hbm_bytes": self.peak_bytes,
            "peak_eqn": self.peak_eqn,
            "input_bytes": self.input_bytes,
            "output_bytes": self.output_bytes,
            "const_bytes": self.const_bytes,
            "donated_bytes": self.donated_bytes,
            "donated_bytes_reclaimed": self.donated_reclaimed_bytes,
            "undonated_bytes": self.undonated_bytes,
            "alias_credit_bytes": self.alias_credit_bytes,
            "donation_coverage": self.donation_coverage,
            "n_eqns": self.n_eqns,
            "buffers": self.buffers,
            "findings": [f.as_dict() for f in self.findings],
        }


def _inner_jaxprs(params):
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, _jcore.ClosedJaxpr):
                yield item.jaxpr, tuple(item.consts)
            elif isinstance(item, _jcore.Jaxpr):
                yield item, ()


# ---------------------------------------------------------------------------
# the plan: liveness + aliasing + donation over one jaxpr scope
# ---------------------------------------------------------------------------

class _Plan:
    __slots__ = ("var2buf", "bufs", "peak", "peak_t", "alias_credit",
                 "reclaimed", "n_eqns")


def _plan(jaxpr, consts, donated_ids):
    """Build the allocation plan for one jaxpr scope and compute its
    peak via an event sweep (O(n log n) in eqns + buffers)."""
    p = _Plan()
    var2buf: dict[int, _Buffer] = {}
    out_ids = {id(v) for v in jaxpr.outvars if _is_var(v)}

    for var, c in zip(jaxpr.constvars, consts):
        av = _aval(var)
        var2buf[id(var)] = _Buffer(
            _nbytes(av), tuple(getattr(av, "shape", ())),
            str(getattr(av, "dtype", "?")), "const", -1)
    for var in jaxpr.invars:
        av = _aval(var)
        kind = "donated_input" if id(var) in donated_ids else "input"
        var2buf[id(var)] = _Buffer(
            _nbytes(av), tuple(getattr(av, "shape", ())),
            str(getattr(av, "dtype", "?")), kind, -1)

    alias_credit = 0
    inner_extra: dict[int, int] = {}   # eqn index -> transient call peak
    for t, eqn in enumerate(jaxpr.eqns):
        # sub-jaxpr transient: the inner scope's own peak minus the
        # operand bytes already counted live here (documented slack:
        # inner donation/aliasing across the call boundary is not
        # modeled — pjit donated_invars would tighten this)
        inner_peak = 0
        for inner, iconsts in _inner_jaxprs(eqn.params):
            ip = _plan(inner, iconsts, set())
            inner_peak = max(inner_peak, ip.peak)
        if inner_peak:
            operand_bytes = sum(
                var2buf[id(v)].nbytes for v in eqn.invars
                if _is_var(v) and id(v) in var2buf)
            extra = inner_peak - operand_bytes
            if extra > 0:
                inner_extra[t] = extra

        src = None
        aliased = (eqn.primitive.name in _ALIAS_PRIMS
                   and len(eqn.outvars) == 1
                   and eqn.invars and _is_var(eqn.invars[0])
                   and id(eqn.invars[0]) in var2buf)
        for v in eqn.outvars:
            av = _aval(v)
            if av is None:
                continue
            if aliased and _nbytes(av) == var2buf[id(eqn.invars[0])].nbytes:
                base = var2buf[id(eqn.invars[0])]
                var2buf[id(v)] = base     # view: same planned buffer
                base.last = max(base.last, t)
                if id(v) in out_ids:
                    base.escapes = True
                alias_credit += base.nbytes
                continue
            if src is None:
                src = _source_of(eqn)
            b = _Buffer(_nbytes(av), tuple(av.shape), str(av.dtype),
                        "temp", t, src)
            if id(v) in out_ids:
                b.escapes = True
            var2buf[id(v)] = b
        for v in eqn.invars:
            if _is_var(v) and id(v) in var2buf:
                b = var2buf[id(v)]
                b.last = max(b.last, t)

    for v in jaxpr.outvars:
        if _is_var(v) and id(v) in var2buf:
            var2buf[id(v)].escapes = True

    bufs = list({id(b): b for b in var2buf.values()}.values())

    # -- donation planning: plan escaping buffers ONTO donated inputs
    # (the jax/XLA input_output_aliases contract: equal shape+dtype).
    # A matched output allocates nothing — it reuses the donated
    # buffer, which in turn stays live to scope end.
    reclaimed = 0
    by_sig: dict[tuple, list[_Buffer]] = {}
    for b in bufs:
        if b.escapes and b.kind == "temp" and not b.alias_donated:
            by_sig.setdefault((b.shape, b.dtype), []).append(b)
    for b in bufs:
        if b.kind != "donated_input":
            continue
        cands = by_sig.get((b.shape, b.dtype))
        if cands:
            out = cands.pop()
            out.alias_donated = True
            b.escapes = True          # carries the output to scope end
            reclaimed += b.nbytes

    # -- event sweep for the peak ---------------------------------------
    n = len(jaxpr.eqns)
    delta: dict[int, int] = {}
    for b in bufs:
        if b.alias_donated or b.nbytes == 0:
            continue                  # reuses another buffer / abstract
        delta[b.birth] = delta.get(b.birth, 0) + b.nbytes
        end = (b.last + 1) if b.freeable else (n + 1)
        delta[end] = delta.get(end, 0) - b.nbytes
    live, peak, peak_t = 0, 0, None
    for t in sorted(set(delta) | set(inner_extra)):
        live += delta.get(t, 0)
        at_t = live + inner_extra.get(t, 0)
        if at_t > peak:
            peak, peak_t = at_t, t

    p.var2buf = var2buf
    p.bufs = bufs
    p.peak = peak
    p.peak_t = peak_t
    p.alias_credit = alias_credit
    p.reclaimed = reclaimed
    p.n_eqns = n
    return p


def _arg_slices(jaxpr, args):
    """Map argument positions onto flattened invar slices (one leaf per
    invar when ``args`` is None)."""
    if args is not None:
        sizes = [len(jax.tree_util.tree_leaves(a)) for a in args]
    else:
        sizes = [1] * len(jaxpr.invars)
    slices, pos = [], 0
    for n in sizes:
        slices.append(jaxpr.invars[pos:pos + n])
        pos += n
    return slices


def _report_of(closed, where, donate_argnums, args, config):
    jaxpr = closed.jaxpr
    slices = _arg_slices(jaxpr, args)
    donated_ids = {id(v) for i in donate_argnums
                   if 0 <= i < len(slices) for v in slices[i]}
    p = _plan(jaxpr, tuple(closed.consts), donated_ids)

    rep = MemReport()
    rep.where = where
    rep.n_eqns = p.n_eqns
    rep.peak_bytes = p.peak
    if p.peak_t is not None and 0 <= p.peak_t < p.n_eqns:
        eqn = jaxpr.eqns[p.peak_t]
        rep.peak_eqn = {"index": p.peak_t,
                        "primitive": eqn.primitive.name,
                        "source": _source_of(eqn)}
    elif p.peak_t is not None:
        rep.peak_eqn = {"index": int(p.peak_t), "primitive": "entry",
                        "source": None}
    rep.const_bytes = sum(b.nbytes for b in p.bufs if b.kind == "const")
    rep.input_bytes = sum(b.nbytes for b in p.bufs
                          if b.kind in ("input", "donated_input"))
    # each output STORAGE once: a donation-matched output lives in the
    # donated input's buffer (marked escaping), so the alias_donated
    # twin would double-count it
    rep.output_bytes = sum(b.nbytes for b in p.bufs
                           if b.escapes and not b.alias_donated)
    rep.donated_bytes = sum(b.nbytes for b in p.bufs
                            if b.kind == "donated_input")
    rep.donated_reclaimed_bytes = p.reclaimed
    rep.alias_credit_bytes = p.alias_credit
    rep.buffers = [b.as_dict() for b in
                   sorted(p.bufs, key=lambda b: -b.nbytes)
                   [:config.top_buffers]]
    return rep, slices, p


def analyze_jaxpr(closed, where="graph", donate_argnums=(), args=None,
                  config=None):
    """Memory analysis of a ``ClosedJaxpr``.  ``args`` (the pytree call
    arguments) map ``donate_argnums`` positions onto flattened invars,
    exactly like the graphlint calling-convention pass; without them
    each invar is its own argument position."""
    config = config or Config()
    rep, _, _ = _report_of(closed, where, tuple(donate_argnums), args,
                           config)
    return rep


def _donation_findings(rep, plan, slices, donate_argnums,
                       allow_undonated, require_donation, where, config):
    """ML-DONATE001 over the entry calling convention, plus the
    donation-coverage figure the CI gate consumes."""
    donated_total = donated_matched = 0
    for i in donate_argnums:
        if 0 <= i < len(slices):
            for v in slices[i]:
                b = plan.var2buf.get(id(v))
                if b is None:
                    continue
                donated_total += 1
                if b.escapes:     # matched to an output (or passthrough)
                    donated_matched += 1
    rep.donation_coverage = (
        donated_matched / donated_total if donated_total else None)

    if "ML-DONATE001" in config.ignore:
        return
    # unclaimed escaping slots by signature (donation matching already
    # consumed its slots inside the plan — a step that donates params
    # is not re-flagged for the gradient buffer sharing the shape)
    out_slots: dict[tuple, int] = {}
    for b in plan.bufs:
        if b.escapes and b.kind == "temp" and not b.alias_donated:
            k = (b.shape, b.dtype)
            out_slots[k] = out_slots.get(k, 0) + 1
    matched, nbytes, argpos = 0, 0, []
    for i, leaves in enumerate(slices):
        if i in donate_argnums or i in allow_undonated:
            continue
        hit = False
        for v in leaves:
            av = _aval(v)
            if av is None or _nbytes(av) < config.donate_min_bytes:
                continue
            k = _sig(av)
            if out_slots.get(k, 0) > 0:
                out_slots[k] -= 1
                matched += 1
                nbytes += _nbytes(av)
                hit = True
        if hit:
            argpos.append(i)
    if matched:
        rep.undonated_bytes = nbytes
        if require_donation:
            msg = (f"{matched} undonated input buffer(s) ({nbytes} bytes, "
                   f"argument position(s) {argpos}) shape/dtype-match "
                   "outputs — this surface contracts to donate: pass "
                   "them in donate_argnums so XLA aliases input and "
                   "output instead of holding both alive")
        else:
            msg = (f"{matched} undonated input buffer(s) ({nbytes} bytes, "
                   f"argument position(s) {argpos}) shape/dtype-match "
                   "outputs — donate_argnums would reclaim the bytes")
        rep.findings.append(Finding(
            "ML-DONATE001", where, "", None, None, msg,
            severity="error" if require_donation else "advisory"))


def analyze_fn(fn, *args, where=None, donate_argnums=(),
               allow_undonated=(), require_donation=False, config=None):
    """Trace ``fn(*args)`` (arrays or ShapeDtypeStructs) and run the
    full memory analysis; returns a :class:`MemReport` with findings.

    ``donate_argnums`` are the positions the surface actually donates;
    ``require_donation=True`` makes an undonated shape-matching input
    an error-severity ML-DONATE001 (the enforced invariant) instead of
    an advisory.  ``allow_undonated`` declares argument positions the
    caller legitimately keeps (an inference CachedOp's params)."""
    config = config or Config()
    where = where or getattr(fn, "__name__", "fn")
    closed = jax.make_jaxpr(fn)(*args)
    rep, slices, plan = _report_of(closed, where, tuple(donate_argnums),
                                   args, config)
    _donation_findings(rep, plan, slices, tuple(donate_argnums),
                       tuple(allow_undonated), require_donation, where,
                       config)
    if config.peak_bytes and rep.peak_bytes > config.peak_bytes \
            and "ML-PEAK001" not in config.ignore:
        rep.findings.append(Finding(
            "ML-PEAK001", where, "", None, None,
            f"peak-HBM estimate {rep.peak_bytes} bytes exceeds the "
            f"budget MXNET_MEMLINT_PEAK_BYTES={config.peak_bytes} — "
            "the dominant buffers are in the lifetime report "
            "(report.buffers)", severity="error"))
    return rep


def analyze_block(block, *example, training=False, where=None,
                  config=None, donate_argnums=()):
    """Memory analysis of a gluon Block's forward — the same pure
    function ``hybridize``/``export_model`` compile (params passed as
    argument 0, inputs from 1)."""
    from ..ndarray import NDArray
    params, apply_fn = block.functional()
    ex = tuple(x.data if isinstance(x, NDArray) else x for x in example)

    def fwd(p, *inputs):
        return apply_fn(p, *inputs, training=training)

    return analyze_fn(fwd, params, *ex,
                      where=where or f"block:{type(block).__name__}",
                      donate_argnums=donate_argnums, config=config)


# ---------------------------------------------------------------------------
# the executable-build choke point (MXNET_GRAPH_MEMLINT)
# ---------------------------------------------------------------------------

_mem_mode: "str | None | bool" = False    # False = read env at first use


def _env_mem_mode():
    raw = str(get_env("MXNET_GRAPH_MEMLINT", "0")).strip().lower()
    if raw in ("", "0", "off", "false", "none"):
        return None
    if raw in ("2", "strict", "raise"):
        return "strict"
    return "warn"


def mem_mode() -> "str | None":
    """``None`` (off, default), ``"warn"`` or ``"strict"`` — read once
    from ``MXNET_GRAPH_MEMLINT``; runtime toggles via
    :func:`set_mem_mode`."""
    global _mem_mode
    if _mem_mode is False:
        _mem_mode = _env_mem_mode()
        if _mem_mode is not None:
            _ensure_provider()
    return _mem_mode


def set_mem_mode(mode):
    """Set the build-time memory-lint mode (``None``/``"warn"``/
    ``"strict"``); returns the previous mode."""
    global _mem_mode
    if mode not in (None, "warn", "strict"):
        raise ValueError(f"memlint mode must be None/'warn'/'strict', "
                         f"got {mode!r}")
    prev = mem_mode()
    _mem_mode = mode
    if mode is not None:
        _ensure_provider()
    return prev


class mem_scope:
    """``with mem_scope("strict"): ...`` — tests/CI."""

    def __init__(self, mode):
        self._mode = mode
        self._prev = None

    def __enter__(self):
        self._prev = set_mem_mode(self._mode)
        return self

    def __exit__(self, *exc):
        set_mem_mode(self._prev)
        return False


def check_memory(fn, args, name=None, donate_argnums=(),
                 allow_undonated=(), require_donation=False, config=None):
    """Run the memory analysis over ``fn(*args)`` at executable-build
    time.  Inert (one cached env read) unless ``MXNET_GRAPH_MEMLINT``
    is on: ``warn`` warns per finding; ``strict`` raises
    :class:`~..error.MemLintError` on error-severity findings.  The
    analysis itself is best-effort — a crash warns and never breaks
    the build.  Records per-site stats for the ``memlint`` profiler
    provider on every run.  Returns the report (or None when off)."""
    mode = mem_mode()
    if mode is None:
        return None
    name = name or getattr(fn, "__name__", "traced")
    try:
        rep = analyze_fn(fn, *args, where=name,
                         donate_argnums=donate_argnums,
                         allow_undonated=allow_undonated,
                         require_donation=require_donation, config=config)
    except Exception as e:  # mxlint: allow-broad-except(the analysis is best-effort at build time; a memlint crash must never break the executable build)
        _warnings.warn(f"memlint could not analyze {name!r} ({e})")
        return None
    _record_site(name, rep)
    for f in rep.findings:
        _warnings.warn(f"memlint: {f!r}")
    errors = [f for f in rep.findings if f.severity == "error"]
    if mode == "strict" and errors:
        from ..error import MemLintError
        raise MemLintError(
            f"memlint: {len(errors)} finding(s) in {name!r}:\n"
            + render(errors))
    return rep


# ---------------------------------------------------------------------------
# per-site stats (profiler provider + serving /metrics feed)
# ---------------------------------------------------------------------------

_stats_lock = threading.Lock()
_sites: dict[str, dict] = {}
_bulk_reclaimed = {"bytes": 0, "buffers": 0, "alias_credit_bytes": 0}
_provider_registered = False


def _ensure_provider():
    global _provider_registered
    if _provider_registered:
        return
    _provider_registered = True
    from .. import profiler
    profiler.register_stats_provider("memlint", stats)


def _record_site(name, rep):
    with _stats_lock:
        st = _sites.setdefault(name, {"analyses": 0})
        st["analyses"] += 1
        st["peak_hbm_bytes"] = rep.peak_bytes
        st["donated_bytes_reclaimed"] = rep.donated_reclaimed_bytes
        st["undonated_bytes"] = rep.undonated_bytes
        st["alias_credit_bytes"] = rep.alias_credit_bytes
        st["donation_coverage"] = rep.donation_coverage
        st["findings"] = len(rep.findings)
    _ensure_provider()


def record_bulk_reclaim(nbytes, nbuffers=1):
    """A bulking flush dropped ``nbytes`` of dead segment-internal
    temporaries from the compiled program's outputs (ops/bulking.py):
    XLA frees them inside the program instead of materializing them.
    Always-on counter (integer adds), folded into :func:`stats`."""
    with _stats_lock:
        _bulk_reclaimed["bytes"] += int(nbytes)
        _bulk_reclaimed["buffers"] += int(nbuffers)
    _ensure_provider()


def record_segment_alias_credit(nbytes):
    """Fold one segment's op-level identity-alias credit
    (:func:`segment_alias_credit`) into the provider counters."""
    if not nbytes:
        return
    with _stats_lock:
        _bulk_reclaimed["alias_credit_bytes"] += int(nbytes)
    _ensure_provider()


def segment_alias_credit(nodes):
    """Bytes of bulked-segment node outputs that alias an input per the
    op-level identity table (``ops.ref_aliases.IDENTITY_ALIASES`` — the
    reference's FInplaceIdentity registrations): planned by XLA as
    views, not fresh allocations."""
    from ..ops.ref_aliases import IDENTITY_ALIASES
    credit = 0
    for node in nodes:
        idx = IDENTITY_ALIASES.get(node.op.name)
        if idx is None or idx >= len(node.args):
            continue
        if node.outs:         # identity aliases exactly one output
            credit += node.outs[0].nbytes
    return credit


def stats():
    """Counters for the profiler's ``memlint`` stats provider."""
    with _stats_lock:
        per_site = {k: dict(v) for k, v in _sites.items()}
        bulk = dict(_bulk_reclaimed)
    return {
        "sites": len(per_site),
        "peak_hbm_bytes_max": max(
            (s.get("peak_hbm_bytes", 0) for s in per_site.values()),
            default=0),
        "donated_bytes_reclaimed": sum(
            s.get("donated_bytes_reclaimed", 0)
            for s in per_site.values()),
        "undonated_bytes": sum(
            s.get("undonated_bytes", 0) for s in per_site.values()),
        "bulk_temp_reclaimed_bytes": bulk["bytes"],
        "bulk_temp_reclaimed_buffers": bulk["buffers"],
        "bulk_alias_credit_bytes": bulk["alias_credit_bytes"],
        "per_site": per_site,
    }


def reset_stats():
    """Drop all per-site state (tests)."""
    with _stats_lock:
        _sites.clear()
        _bulk_reclaimed["bytes"] = 0
        _bulk_reclaimed["buffers"] = 0
        _bulk_reclaimed["alias_credit_bytes"] = 0
