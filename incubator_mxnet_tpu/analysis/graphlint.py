"""graphlint — IR-level static analysis of traced graphs (jaxpr passes).

mxlint (sibling module) reads Python *source*; graphlint reads the
*traced computation* — the jaxpr a framework entry point lowers to
before XLA sees it.  Everything the reference framework expressed as
NNVM graph passes (quantize-aware checks, AMP casts, memory planning
hints) has its analysis analog here: one walk over the IR that every
frontend (eager op, bulked segment, hybridized block, Symbol executor,
fused train step, deploy export) funnels through.

Rules (docs/graph_analysis.md):

=============  ==========================================================
GL-DTYPE001    a float64/complex128 value in the graph — TPUs have no
               f64 ALU (emulated, order-of-magnitude slow); almost
               always a leaked numpy double under ``JAX_ENABLE_X64``
GL-DTYPE002    mixed-precision promotion: a bf16/f16 operand is widened
               (``convert_element_type``) to feed an elementwise op
               whose other operand is natively f32 — jax's silent
               promotion upcasts the whole intermediate (2x the HBM)
               when a f32 array meets a low-precision activation; cast
               the wide side down where the mix is unintended
GL-PREC001     low-precision accumulation: a ``reduce_sum``-family
               primitive accumulating ≥ ``accum_elems`` elements in
               bf16/f16/f8 — VPU reductions accumulate in the operand
               dtype, and bf16 has 8 mantissa bits (relative error grows
               with the reduction length); accumulate in f32
               (``dtype=jnp.float32`` / cast first)
GL-CONST001    an oversized constant baked into the graph (a closed-over
               weight captured at trace time): bloats the executable,
               re-compiles on every value change, and can never be
               donated — pass it as an argument
GL-DEAD001     dead computation: an equation (with no effects) none of
               whose outputs reach the graph outputs — traced work the
               caller dropped, usually a forgotten output or an aux
               update nobody applies
GL-HOST001     a host callback inside the graph (``pure_callback``/
               ``io_callback``/``debug_callback``): every execution
               round-trips device→host→device — fatal in a serving or
               fused-train-step graph
GL-TILE001     degenerate trailing-dim layout: a large rank-2
               intermediate shaped ``(big, ≤8)`` — TPU tiles are
               ``(sublane, 128)`` lanes minor, so a tiny trailing dim
               wastes > 90% of every vector register and HBM tile;
               keep the long axis minor (transpose, or fold the pair)
GL-DONATE001   *advisory*: an undonated input whose shape/dtype matches
               an output — the classic params-in/params-out update step
               where ``donate_argnums`` would let XLA alias the buffers
               instead of holding both alive (the memory-planning
               analog of the reference's in-place flags).  The
               ENFORCED form lives in :mod:`.memlint` as ML-DONATE001
               (``MXNET_GRAPH_MEMLINT``): error severity at surfaces
               that contract to donate, with the reclaimed bytes
               measured
=============  ==========================================================

``GL-DEAD001`` also covers **unused arguments** at the entry point
(advisory): an input traced into the signature that no equation ever
reads — dead weight in the calling convention (callers declare
intentional slack, like an inference CachedOp's unused RNG key, via
``allow_unused_args``).

Every jit surface can run the whole catalog at executable-build time
through one choke point, :func:`check_traced`, inert unless
``MXNET_GRAPH_LINT`` is set (``1``/``warn`` → one warning per finding;
``2``/``strict`` → :class:`~..error.GraphLintError` on error-severity
findings).  CachedOp builds, bulked-segment flushes, fused-step first
calls and deploy exports are wired through it.

The walker recurses into sub-jaxprs (``pjit``/``scan``/``while``/
``cond`` branches, custom-vjp calls), so a rule fires no matter how
deeply a loop body buries the offending equation.  Each finding carries
the entry-point label, the nesting path (``/pjit/while:body``), the
primitive, and a best-effort user source line from jax's eqn
source-info.

This module needs jax (it traces), unlike mxlint — it is loaded
lazily by ``analysis/__init__``; importing the analysis package alone
stays jax-free for the mxlint CLI.
"""
from __future__ import annotations

import warnings as _warnings

import jax
from jax.extend import core as _jcore
import numpy as _onp

from ..base import get_env

__all__ = ["RULES", "Config", "Finding", "lint_jaxpr", "lint_fn",
           "lint_op", "lint_block", "lint_symbol", "check_traced",
           "lint_mode", "set_lint_mode", "render"]

RULES = {
    "GL-DTYPE001": "float64/complex128 in the graph (no TPU f64 ALU)",
    "GL-DTYPE002": "mixed-precision promotion widens a low-float "
                   "operand in an elementwise op",
    "GL-PREC001": "long low-precision accumulation (bf16/f16 reduce)",
    "GL-CONST001": "oversized constant baked into the graph",
    "GL-DEAD001": "dead computation (outputs never used)",
    "GL-HOST001": "host callback inside the graph",
    "GL-TILE001": "degenerate trailing-dim layout for TPU tiling",
    "GL-DONATE001": "undonated input shape/dtype-matches an output "
                    "(advisory)",
}


class Config:
    """Thresholds for the size-gated rules.

    ``ignore`` silences whole rules for one lint run — the IR analog of
    an mxlint pragma (jaxprs have no comment to hang a pragma on, so
    suppression is per entry point, justified at the call site).
    ``const_bytes`` defaults from ``MXNET_GRAPHLINT_CONST_BYTES``.
    """

    __slots__ = ("const_bytes", "accum_elems", "tile_min_elems",
                 "donate_min_bytes", "ignore")

    def __init__(self, const_bytes=None, accum_elems=512,
                 tile_min_elems=1 << 16, donate_min_bytes=1024,
                 ignore=()):
        if const_bytes is None:
            const_bytes = get_env("MXNET_GRAPHLINT_CONST_BYTES",
                                  1 << 20, int)
        self.const_bytes = int(const_bytes)
        self.accum_elems = int(accum_elems)
        self.tile_min_elems = int(tile_min_elems)
        self.donate_min_bytes = int(donate_min_bytes)
        self.ignore = frozenset(ignore)


class Finding:
    """One IR finding, located by (entry label, nesting path, source).

    ``severity`` is ``"error"`` (gates CI / strict mode) or
    ``"advisory"`` (reported, never gates) — same contract as the
    source-level findings in :mod:`.findings`.  Baseline identity is
    ``(rule, where+path, message)`` via ``key``, so graphlint findings
    flow through the shared ``apply_baseline`` machinery unchanged.
    """

    __slots__ = ("rule", "where", "path", "primitive", "source",
                 "message", "severity")

    def __init__(self, rule, where, path, primitive, source, message,
                 severity="error"):
        self.rule = rule
        self.where = where
        self.path = path or "/"
        self.primitive = primitive
        self.source = source
        self.message = message
        self.severity = severity

    @property
    def key(self):
        return (self.rule, f"{self.where}{self.path}", self.message)

    def as_dict(self):
        return {"rule": self.rule, "where": self.where, "path": self.path,
                "primitive": self.primitive, "source": self.source,
                "message": self.message, "severity": self.severity}

    def __repr__(self):
        src = f" [{self.source}]" if self.source else ""
        adv = " (advisory)" if self.severity != "error" else ""
        return (f"{self.where}{self.path}: {self.rule}{adv} "
                f"({self.primitive}){src}: {self.message}")


def render(findings):
    return "\n".join(repr(f) for f in findings)


# ---------------------------------------------------------------------------
# helpers over jax internals
# ---------------------------------------------------------------------------

_WIDE_FLOATS = ("float64", "complex128")
_LOW_FLOATS = ("bfloat16", "float16", "float8_e4m3fn", "float8_e5m2",
               "float8_e4m3b11_fnuz", "float8_e4m3fnuz", "float8_e5m2fnuz")
_ELEMWISE = {"add", "sub", "mul", "div", "max", "min", "pow", "rem",
             "atan2", "nextafter", "add_any"}
_REDUCE_SUM = {"reduce_sum", "reduce_window_sum", "cumsum"}
_CALLBACKS = {"pure_callback", "io_callback", "debug_callback", "callback"}


def _source_of(eqn):
    """Best-effort ``file:line`` of the user frame that traced ``eqn``."""
    try:
        from jax._src import source_info_util as _siu
        return _siu.summarize(eqn.source_info)
    except Exception:  # mxlint: allow-broad-except(private jax API probe; a finding without a source line is still a finding)
        return None


def _aval(v):
    return getattr(v, "aval", None)


def _is_var(v):
    # Literals carry .val; Vars (incl. DropVar) do not
    return not hasattr(v, "val")


def _float_name(dtype):
    name = str(dtype)
    return name if ("float" in name or "complex" in name) else None


def _size(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _const_nbytes(c):
    try:
        return int(c.size) * _onp.dtype(c.dtype).itemsize
    except (TypeError, ValueError, AttributeError):
        return 0


def _iter_subjaxprs(params):
    """Yield (tag, jaxpr-or-closed) for every inner jaxpr an eqn carries
    (pjit: ``jaxpr``; scan: ``jaxpr``; while: ``cond_jaxpr``/
    ``body_jaxpr``; cond: ``branches``; custom_*: ``call_jaxpr``...).
    Generic over param names so new primitives keep working."""
    for name, v in params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for i, item in enumerate(vals):
            if isinstance(item, (_jcore.Jaxpr, _jcore.ClosedJaxpr)):
                tag = name.replace("_jaxpr", "").replace("jaxpr", "")
                tag = tag.strip("_") or None
                idx = f"#{i}" if len(vals) > 1 else ""
                yield (f":{tag}{idx}" if tag else idx), item


# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------

def lint_jaxpr(closed, where="graph", config=None):
    """Run every pass over a ``ClosedJaxpr`` (or raw ``Jaxpr``);
    returns deduplicated, sorted Findings."""
    config = config or Config()
    findings: list[Finding] = []
    if isinstance(closed, _jcore.ClosedJaxpr):
        _walk(closed.jaxpr, tuple(closed.consts), "", where, config,
              findings)
    else:
        _walk(closed, (), "", where, config, findings)
    return _finish(findings)


def _finish(findings):
    seen = set()
    out = []
    for f in sorted(findings, key=lambda f: (f.rule, f.path, f.message)):
        if f.key not in seen:
            seen.add(f.key)
            out.append(f)
    return out


def _walk(jaxpr, consts, path, where, config, findings):
    ign = config.ignore

    def emit(rule, eqn, message, prim=None):
        if rule not in ign:
            findings.append(Finding(
                rule, where, path,
                prim or (eqn.primitive.name if eqn is not None else None),
                _source_of(eqn) if eqn is not None else None, message))

    # -- GL-CONST001: closed-over constants ------------------------------
    for var, c in zip(jaxpr.constvars, consts):
        nbytes = _const_nbytes(c)
        if nbytes >= config.const_bytes:
            av = _aval(var)
            emit("GL-CONST001", None,
                 f"constant {tuple(getattr(av, 'shape', ()))} "
                 f"{getattr(av, 'dtype', '?')} ({nbytes} bytes) is baked "
                 "into the graph — a closed-over array captured at trace "
                 "time; pass it as an argument so it can be donated and "
                 "updated without recompiling", prim="const")

    # producer map for the promotion pattern (GL-DTYPE002): jnp never
    # hands a primitive mixed dtypes — promotion materializes as a
    # convert_element_type feeding the op, so the rule looks one
    # producer upstream
    producers = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            producers[id(ov)] = eqn

    # taint: wide values DERIVED from a widened low-float (a deliberate
    # f32 compute region — layer_norm's mean over x.astype(f32)) are not
    # "natively wide"; meeting them is not a promotion bug
    tainted = set()
    for eqn in jaxpr.eqns:
        src_low = False
        if eqn.primitive.name == "convert_element_type" and eqn.invars:
            sav = _aval(eqn.invars[0])
            src_low = sav is not None and str(sav.dtype) in _LOW_FLOATS
        if not src_low:
            src_low = any(_is_var(v) and id(v) in tainted
                          for v in eqn.invars)
        if src_low:
            for ov in eqn.outvars:
                av = _aval(ov)
                if av is not None \
                        and _float_name(getattr(av, "dtype", "")) \
                        and str(av.dtype) not in _LOW_FLOATS:
                    tainted.add(id(ov))

    def _widened_from(v):
        """Source low-float dtype if ``v`` is a fresh widening of one."""
        p = producers.get(id(v))
        if p is None or p.primitive.name != "convert_element_type":
            return None
        src_av = _aval(p.invars[0])
        out_av = _aval(v)
        if (src_av is not None and out_av is not None
                and str(src_av.dtype) in _LOW_FLOATS
                and _float_name(out_av.dtype)
                and str(out_av.dtype) not in _LOW_FLOATS):
            return str(src_av.dtype)
        return None

    # -- liveness for GL-DEAD001 (per jaxpr scope) ------------------------
    live = {id(v) for v in jaxpr.outvars if _is_var(v)}
    dead_eqns = []
    for eqn in reversed(jaxpr.eqns):
        is_live = (bool(eqn.effects)
                   or any(id(v) in live for v in eqn.outvars))
        if is_live:
            for v in eqn.invars:
                if _is_var(v):
                    live.add(id(v))
        else:
            dead_eqns.append(eqn)

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name

        # -- recurse into sub-jaxprs ------------------------------------
        for tag, inner in _iter_subjaxprs(eqn.params):
            sub_path = f"{path}/{prim}{tag}"
            if isinstance(inner, _jcore.ClosedJaxpr):
                _walk(inner.jaxpr, tuple(inner.consts), sub_path, where,
                      config, findings)
            else:
                _walk(inner, (), sub_path, where, config, findings)

        # -- GL-HOST001 --------------------------------------------------
        if prim in _CALLBACKS:
            emit("GL-HOST001", eqn,
                 f"{prim} inside the traced graph: every execution "
                 "round-trips device->host->device and serializes the "
                 "pipeline — hoist the host work out of the compiled "
                 "section")

        # -- GL-DTYPE001 -------------------------------------------------
        for v in eqn.outvars:
            av = _aval(v)
            if av is not None and str(getattr(av, "dtype", "")) \
                    in _WIDE_FLOATS:
                emit("GL-DTYPE001", eqn,
                     f"{av.dtype} value of shape {tuple(av.shape)}: TPUs "
                     "have no f64 unit (emulated, ~10x slow) — a numpy "
                     "double leaked into the trace under JAX_ENABLE_X64; "
                     "cast to float32 at the boundary")
                break

        # -- GL-DTYPE002 -------------------------------------------------
        if prim in _ELEMWISE and len(eqn.invars) >= 2:
            for v in eqn.invars:
                if not _is_var(v):
                    continue
                low = _widened_from(v)
                if low is None:
                    continue
                out_av = _aval(eqn.outvars[0]) if eqn.outvars else None
                # the other operand must be natively wide (not itself a
                # widening, not a weak python scalar) — that is the
                # promotion, not a deliberate lone upcast
                other_wide = any(
                    o is not v and _is_var(o)
                    and not getattr(_aval(o), "weak_type", False)
                    and _float_name(getattr(_aval(o), "dtype", ""))
                    and str(_aval(o).dtype) not in _LOW_FLOATS
                    and _widened_from(o) is None
                    and id(o) not in tainted
                    for o in eqn.invars)
                if other_wide:
                    emit("GL-DTYPE002", eqn,
                         f"a {low} operand is widened to "
                         f"{getattr(out_av, 'dtype', 'float32')} to meet "
                         f"a natively-wide operand of {prim}: the whole "
                         "intermediate is upcast (2x HBM) — if the mix "
                         "is unintended, cast the wide operand down "
                         "instead")
                    break

        # -- GL-PREC001 --------------------------------------------------
        if prim in _REDUCE_SUM and eqn.invars:
            av = _aval(eqn.invars[0])
            if av is not None and str(getattr(av, "dtype", "")) \
                    in _LOW_FLOATS:
                n = _accum_count(eqn, av)
                if n >= config.accum_elems:
                    emit("GL-PREC001", eqn,
                         f"{prim} accumulates {n} elements in {av.dtype}: "
                         "reductions accumulate in the operand dtype and "
                         f"{av.dtype} has few mantissa bits — accumulate "
                         "in float32 (dtype=jnp.float32, or cast before "
                         "the reduction)")

        # -- GL-TILE001 --------------------------------------------------
        for v in eqn.outvars:
            av = _aval(v)
            shape = tuple(getattr(av, "shape", ()) or ())
            if (len(shape) == 2 and shape[-1] <= 8 and shape[0] >= 128
                    and _size(shape) >= config.tile_min_elems):
                emit("GL-TILE001", eqn,
                     f"intermediate shaped {shape}: TPU tiles are "
                     "(sublane, 128) with the LAST dim on lanes, so a "
                     f"trailing dim of {shape[-1]} wastes "
                     f"{100 * (1 - shape[-1] / 128):.0f}% of every "
                     "register and HBM tile — keep the long axis minor "
                     "(transpose or reshape)")

    # -- GL-DEAD001 ------------------------------------------------------
    for eqn in dead_eqns:
        outs = [f"{tuple(_aval(v).shape)} {_aval(v).dtype}"
                for v in eqn.outvars if _aval(v) is not None]
        emit("GL-DEAD001", eqn,
             f"{eqn.primitive.name} -> {', '.join(outs) or 'no outputs'} "
             "is computed but never reaches a graph output — traced work "
             "the caller drops (forgotten return value or unapplied aux "
             "update); XLA will DCE it, but the trace says the Python "
             "code asked for it")


def _accum_count(eqn, av):
    """Elements accumulated per output for a reduce-sum-family eqn."""
    p = eqn.params
    shape = tuple(av.shape)
    if "window_dimensions" in p:               # reduce_window_sum
        return _size(p["window_dimensions"])
    if "axes" in p:                            # reduce_sum
        return _size(shape[a] for a in p["axes"])
    if "axis" in p:                            # cumsum
        return int(shape[p["axis"]])
    out_av = _aval(eqn.outvars[0]) if eqn.outvars else None
    out_n = _size(getattr(out_av, "shape", ())) if out_av is not None else 1
    return max(1, _size(shape) // max(1, out_n))


# ---------------------------------------------------------------------------
# calling-convention passes (top-level invars only)
# ---------------------------------------------------------------------------

def _aval_bytes(av):
    try:
        return _size(av.shape) * _onp.dtype(av.dtype).itemsize
    except (TypeError, ValueError, AttributeError):
        return 0


def _lint_calling_convention(closed, args, where, config,
                             donate_argnums, allow_unused_args,
                             check_donation):
    """Unused-argument (GL-DEAD001, advisory) and donation-opportunity
    (GL-DONATE001, advisory) analysis over the ENTRY jaxpr's invars."""
    jaxpr = closed.jaxpr
    out: list[Finding] = []
    ignore = config.ignore
    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if _is_var(v):
                used.add(id(v))
    for v in jaxpr.outvars:
        if _is_var(v):
            used.add(id(v))

    sizes = [len(jax.tree_util.tree_leaves(a)) for a in args]
    invars = jaxpr.invars
    slices, pos = [], 0
    for n in sizes:
        slices.append(invars[pos:pos + n])
        pos += n

    if "GL-DEAD001" not in ignore:
        for i, leaves in enumerate(slices):
            if i in allow_unused_args or not leaves:
                continue
            if all(id(v) not in used for v in leaves):
                av = _aval(leaves[0])
                out.append(Finding(
                    "GL-DEAD001", where, "", None, None,
                    f"argument {i} ({len(leaves)} leaf/leaves, e.g. "
                    f"{tuple(getattr(av, 'shape', ()))} "
                    f"{getattr(av, 'dtype', '?')}) is traced into the "
                    "signature but never read — dead weight in the "
                    "calling convention (declare intentional slack via "
                    "allow_unused_args)", severity="advisory"))

    if check_donation and "GL-DONATE001" not in ignore:
        out_counts: dict[tuple, int] = {}
        for v in jaxpr.outvars:
            av = _aval(v)
            if av is not None and _aval_bytes(av) >= config.donate_min_bytes:
                k = (tuple(av.shape), str(av.dtype))
                out_counts[k] = out_counts.get(k, 0) + 1
        # donated inputs claim their matching output slots FIRST — a
        # step that already donates params must not be advised again
        # for the gradient buffer that merely shares the shape
        for i in donate_argnums:
            if 0 <= i < len(slices):
                for v in slices[i]:
                    av = _aval(v)
                    if av is None:
                        continue
                    k = (tuple(av.shape), str(av.dtype))
                    if out_counts.get(k, 0) > 0:
                        out_counts[k] -= 1
        matched, nbytes = 0, 0
        for i, leaves in enumerate(slices):
            if i in donate_argnums:
                continue
            for v in leaves:
                av = _aval(v)
                if av is None:
                    continue
                k = (tuple(av.shape), str(av.dtype))
                if out_counts.get(k, 0) > 0:
                    out_counts[k] -= 1
                    matched += 1
                    nbytes += _aval_bytes(av)
        if matched:
            out.append(Finding(
                "GL-DONATE001", where, "", None, None,
                f"{matched} undonated input buffer(s) "
                f"({nbytes} bytes) shape/dtype-match outputs — "
                "donate_argnums would let XLA alias them instead of "
                "holding input and output alive together (params-in/"
                "params-out update steps are the classic case)",
                severity="advisory"))
    return out


# ---------------------------------------------------------------------------
# entry points — one per framework graph surface
# ---------------------------------------------------------------------------

def lint_fn(fn, *args, where=None, config=None, donate_argnums=(),
            allow_unused_args=(), check_donation=False):
    """Trace ``fn(*args)`` (arrays or ShapeDtypeStructs) and lint the
    jaxpr.  The universal entry the others reduce to.

    ``donate_argnums``/``check_donation`` drive the GL-DONATE001
    advisory (donation only means something for step-like entry points,
    so it is opt-in); ``allow_unused_args`` declares argument positions
    intentionally unused (an inference CachedOp's RNG key).
    """
    closed = jax.make_jaxpr(fn)(*args)
    where = where or getattr(fn, "__name__", "fn")
    config = config or Config()
    findings = lint_jaxpr(closed, where, config)
    findings += _lint_calling_convention(
        closed, args, where, config, tuple(donate_argnums),
        tuple(allow_unused_args), check_donation)
    return _finish(findings)


def lint_op(op, *specs, config=None, **kwargs):
    """Lint one registered operator at the given input specs.

    ``specs`` are arrays or ``(shape, dtype)`` tuples; ``kwargs`` are
    the op's static parameters.
    """
    from ..ops import registry as _registry
    if isinstance(op, str):
        op = _registry.get_op(op)
    args = tuple(
        jax.ShapeDtypeStruct(tuple(s[0]), s[1]) if isinstance(s, tuple)
        else s for s in specs)

    def run(*arrs):
        return op.fn(*arrs, **kwargs)

    return lint_fn(run, *args, where=f"op:{op.name}", config=config)


def lint_block(block, *example, training=False, where=None, config=None):
    """Lint a gluon Block's forward — the same pure function
    ``hybridize``/``export_model`` compile (params passed as arguments,
    so weights can never trip GL-CONST001 unless genuinely baked)."""
    from ..ndarray import NDArray
    params, apply_fn = block.functional()
    ex = tuple(x.data if isinstance(x, NDArray) else x for x in example)

    def fwd(p, *inputs):
        return apply_fn(p, *inputs, training=training)

    return lint_fn(fwd, params, *ex,
                   where=where or f"block:{type(block).__name__}",
                   config=config)


def lint_symbol(symbol, shapes, training=False, config=None):
    """Lint a Symbol graph: ``shapes`` maps every argument (and aux
    state) name to a shape (dtype float32, matching ``simple_bind``)."""
    import jax.numpy as jnp
    names = symbol.list_arguments() + symbol.list_auxiliary_states()
    missing = [n for n in names if n not in shapes]
    if missing:
        raise ValueError(f"lint_symbol needs shapes for {missing}")
    specs = [jax.ShapeDtypeStruct(tuple(shapes[n]), jnp.float32)
             for n in names]

    def fwd(*vals):
        bindings = dict(zip(names, vals))
        if training:
            return tuple(symbol._evaluate(bindings, training=True,
                                          aux_updates={}))
        return tuple(symbol._evaluate(bindings))

    return lint_fn(fwd, *specs, where=f"symbol:{symbol.name}",
                   config=config)


# ---------------------------------------------------------------------------
# the executable-build choke point (MXNET_GRAPH_LINT)
# ---------------------------------------------------------------------------

_lint_mode: "str | None | bool" = False    # False = read env at first use


def _env_lint_mode():
    raw = str(get_env("MXNET_GRAPH_LINT", "0")).strip().lower()
    if raw in ("", "0", "off", "false", "none"):
        return None
    if raw in ("2", "strict", "raise"):
        return "strict"
    return "warn"


def lint_mode() -> "str | None":
    """``None`` (off, default), ``"warn"`` or ``"strict"`` — read once
    from ``MXNET_GRAPH_LINT``; runtime toggles via :func:`set_lint_mode`."""
    global _lint_mode
    if _lint_mode is False:
        _lint_mode = _env_lint_mode()
    return _lint_mode


def set_lint_mode(mode):
    """Set the build-time lint mode (``None``/``"warn"``/``"strict"``);
    returns the previous mode."""
    global _lint_mode
    if mode not in (None, "warn", "strict"):
        raise ValueError(f"lint mode must be None/'warn'/'strict', "
                         f"got {mode!r}")
    prev = lint_mode()
    _lint_mode = mode
    return prev


def check_traced(fn, args, name=None, config=None, donate_argnums=(),
                 allow_unused_args=(), check_donation=False):
    """Run the whole catalog over ``fn(*args)`` at executable-build
    time.  Inert (one cached env read) unless ``MXNET_GRAPH_LINT`` is
    on: ``warn`` emits one warning per finding; ``strict`` raises
    :class:`~..error.GraphLintError` on error-severity findings (a
    strict advisory still only warns).  A failure of the lint trace
    itself warns and never breaks the build.  Returns the findings (or
    None when off)."""
    mode = lint_mode()
    if mode is None:
        return None
    name = name or getattr(fn, "__name__", "traced")
    try:
        findings = lint_fn(fn, *args, where=name, config=config,
                           donate_argnums=donate_argnums,
                           allow_unused_args=allow_unused_args,
                           check_donation=check_donation)
    except Exception as e:  # mxlint: allow-broad-except(the lint is best-effort at build time; a lint crash must never break the executable build)
        _warnings.warn(f"graphlint could not analyze {name!r} ({e})")
        return None
    for f in findings:
        _warnings.warn(f"graphlint: {f!r}")
    errors = [f for f in findings if f.severity == "error"]
    if mode == "strict" and errors:
        from ..error import GraphLintError
        raise GraphLintError(
            f"graphlint: {len(errors)} finding(s) in {name!r}:\n"
            + render(errors))
    return findings
