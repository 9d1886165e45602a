"""mxlint — framework-aware static analysis (pure stdlib, AST-based).

Generic linters know Python; this one knows *this framework's*
invariants — the contracts that hold the engine/serving/kvstore layers
together and that a silent violation turns into a production incident:

=============  ==========================================================
MX-ENV001      ``MXNET_*`` env var read in code (``base.get_env``,
               ``os.environ``/``os.getenv``) but missing from
               ``docs/env_vars.md`` — an undocumented knob
MX-ENV002      env var documented in ``docs/env_vars.md`` but never read
               anywhere in the scanned code — a dead doc entry
MX-FAULT001    ``fault.inject("point")`` call site names a point not
               declared in the central ``fault.POINTS`` registry — a
               typo'd point silently never fires
MX-FAULT002    point declared in ``fault.POINTS`` but never wired to an
               ``inject`` call site — dead chaos coverage
MX-FLIGHT001   flight-recorder event name not registered: a static
               ``flightrec.record(cat, "name")`` emit names something
               missing from ``flightrec.EVENTS``, or a ``postmortem
               --gate ev1,ev2`` string (subprocess argv or
               ``gate=``/``--gate`` in ``tests/``, ``ci/``,
               ``benchmark/``) names an event no emitter registers —
               gate-string drift used to fail only at chaos-stage
               runtime.  Dynamic names must fall in an
               ``EVENT_PREFIXES`` family; ``fault.*`` gate entries are
               additionally checked against ``fault.POINTS``
MX-TIME001     wall-clock ``time.time()`` — timeout/deadline/duration
               arithmetic must use ``time.monotonic()`` (an NTP step
               fires spurious timeouts); genuinely wall-clock sites
               carry ``# mxlint: allow-wall-clock(<reason>)``
MX-BULK001     an op registered as bulkable calls a host-effect function
               (``asnumpy``, ``np.asarray``, ``print``, file IO) in its
               impl — deferring it into a bulked segment would reorder
               the side effect
MX-LOCK001     inconsistent lock acquisition order: a cycle in the
               static per-module lock-order graph (nested ``with``
               acquisitions plus same-module call resolution)
MX-EXC001      broad ``except Exception``/``BaseException``/bare
               ``except`` whose handler never re-raises — it can swallow
               the typed errors (``PSTimeoutError``,
               ``CheckpointCorruptError``, ...) the caller contracts on;
               annotate ``# mxlint: allow-broad-except(<reason>)``
MX-DONATE001   a ``jax.jit``/``pjit`` call site inside
               ``incubator_mxnet_tpu/`` that passes no
               ``donate_argnums``/``donate_argnames`` — every jitted
               entry point must either donate its reusable input
               buffers or carry a
               ``# mxlint: disable=MX-DONATE001(<why the inputs are
               caller-held>)`` pragma, so undonated HBM is a decision,
               never an accident (the AST half of memlint's enforced
               donation — docs/graph_analysis.md)
MX-AST000      file failed to parse
=============  ==========================================================

Suppression:

* **Pragmas** — a trailing comment on the flagged line:
  ``# mxlint: allow-broad-except(reason)``,
  ``# mxlint: allow-wall-clock(reason)``, or the generic
  ``# mxlint: disable=MX-XXXNNN(reason)``.  The reason is mandatory —
  an empty pragma does not suppress.
* **Baseline** — a JSON file of known findings
  (``{"findings": [{"rule", "file", "message", "reason"}]}``) so CI
  fails only on regressions.  Matching ignores line numbers (they
  drift); the (rule, file, message) triple is the identity.

Whole-surface rules (ENV001/002, FAULT002) need to see the entire
package to be meaningful, so they only run when at least one scanned
path is a directory.

This module is deliberately import-light (stdlib only): the CLI
``tools/mxlint.py`` loads it straight from the file so linting never
pays — or requires — the framework's jax import.  The finding/baseline
machinery is shared with graphlint via :mod:`.findings` (same identity
contract, same written-reason rule), loaded by file when this module
itself was loaded standalone.
"""
from __future__ import annotations

import ast
import os
import re

try:
    from .findings import (Finding, load_baseline, apply_baseline,
                           prune_stale_baseline, render)
except ImportError:   # standalone file-load (tools/mxlint.py, no package)
    import importlib.util as _ilu
    _p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "findings.py")
    _spec = _ilu.spec_from_file_location("_mxlint_findings", _p)
    _mod = _ilu.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    Finding = _mod.Finding
    load_baseline = _mod.load_baseline
    apply_baseline = _mod.apply_baseline
    prune_stale_baseline = _mod.prune_stale_baseline
    render = _mod.render

__all__ = ["RULES", "Finding", "lint_paths", "load_baseline",
           "apply_baseline", "prune_stale_baseline", "render"]

RULES = {
    "MX-ENV001": "env var read in code but not documented in env_vars.md",
    "MX-ENV002": "env var documented in env_vars.md but never read in code",
    "MX-FAULT001": "fault.inject names a point not declared in fault.POINTS",
    "MX-FAULT002": "fault point declared in fault.POINTS but never wired",
    "MX-FLIGHT001": "flight event name not registered in flightrec.EVENTS "
                    "(emit site or postmortem gate string)",
    "MX-TIME001": "wall-clock time.time(); use time.monotonic() "
                  "(pragma allow-wall-clock for true wall-clock needs)",
    "MX-BULK001": "bulkable op impl calls a host-effect function",
    "MX-LOCK001": "lock-order cycle (inconsistent acquisition order)",
    "MX-EXC001": "broad except swallows typed errors without a pragma",
    "MX-DONATE001": "jax.jit/pjit call site passes no donate_argnums",
    "MX-SHARD001": "shard_map/pjit call site passes no explicit "
                   "mesh/sharding argument",
    "MX-AST000": "file failed to parse",
}

_ENV_RE = re.compile(r"MXNET_[A-Z0-9_]+$")
_DOC_VAR_RE = re.compile(r"`(MXNET_[A-Z0-9_]+)`")
_LOCK_ATTR_RE = re.compile(r"(?:^|_)(lock|cv|cond|mutex)$")
_PRAGMA_RE = re.compile(
    r"#\s*mxlint:\s*"
    r"(allow-broad-except|allow-wall-clock|disable=(MX-[A-Z]+\d+))"
    r"\((.+)\)")  # greedy: reasons may themselves contain parens
_PRAGMA_KEYS = {"allow-broad-except": "MX-EXC001",
                "allow-wall-clock": "MX-TIME001"}


class _File:
    """One parsed source file plus its pragma map."""

    def __init__(self, path, rel):
        self.path = path
        self.rel = rel
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            self.src = f.read()
        self.tree = None
        self.parse_error = None
        try:
            self.tree = ast.parse(self.src, filename=path)
        except SyntaxError as e:
            self.parse_error = e
        # line -> set of rule ids suppressed there (reason mandatory)
        self.pragmas: dict[int, set] = {}
        for i, line in enumerate(self.src.splitlines(), 1):
            for m in _PRAGMA_RE.finditer(line):
                kind, disabled_rule, reason = m.groups()
                if not reason.strip():
                    continue
                rule = disabled_rule or _PRAGMA_KEYS[kind]
                self.pragmas.setdefault(i, set()).add(rule)

    def suppressed(self, rule, node) -> bool:
        """A pragma suppresses when it sits on any physical line of the
        flagged statement/handler header (multi-line calls included).
        For block nodes (``except`` handlers) only the header lines
        count — a pragma inside the body belongs to the body's own
        statements, not the enclosing handler."""
        body = getattr(node, "body", None)
        if isinstance(body, list) and body:
            last = max(node.lineno, body[0].lineno - 1)
        else:
            last = getattr(node, "end_lineno", node.lineno) or node.lineno
        return any(rule in self.pragmas.get(ln, ())
                   for ln in range(node.lineno, last + 1))

    def suppressed_at(self, rule, line) -> bool:
        return rule in self.pragmas.get(line, ())


# ---------------------------------------------------------------------------
# small AST helpers
# ---------------------------------------------------------------------------

def _const_str(node):
    return (node.value if isinstance(node, ast.Constant)
            and isinstance(node.value, str) else None)


def _is_environ(node):
    """Matches ``os.environ`` or a bare ``environ`` name."""
    return ((isinstance(node, ast.Attribute) and node.attr == "environ")
            or (isinstance(node, ast.Name) and node.id == "environ"))


def _call_name(func):
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _env_var_of(call: ast.Call):
    """The MXNET_* literal a call reads, or None.

    Recognizes ``get_env("X", ...)`` / ``base.get_env`` /
    ``os.getenv("X")`` / ``os.environ.get("X")``."""
    f = call.func
    name = _call_name(f)
    if name == "get" and isinstance(f, ast.Attribute) \
            and not _is_environ(f.value):
        return None  # some other dict's .get
    if name not in ("get_env", "getenv", "get"):
        return None
    if not call.args:
        return None
    v = _const_str(call.args[0])
    return v if v and _ENV_RE.match(v) else None


def _env_reads(tree):
    """Yield (var, lineno) for every env-var read in the tree."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            v = _env_var_of(node)
            if v:
                yield v, node.lineno
        elif isinstance(node, ast.Subscript) and _is_environ(node.value):
            v = _const_str(node.slice)
            if v and _ENV_RE.match(v):
                yield v, node.lineno


def _documented_vars(docs_path):
    """{var: lineno} for every MXNET_* named in the first cell of an
    env_vars.md table row.  Prose mentions (meaning columns, section
    text) do not count — only the variable column declares a knob."""
    out = {}
    with open(docs_path, "r", encoding="utf-8") as f:
        for i, line in enumerate(f, 1):
            if not line.lstrip().startswith("|"):
                continue
            first_cell = line.lstrip().lstrip("|").split("|", 1)[0]
            for var in _DOC_VAR_RE.findall(first_cell):
                out.setdefault(var, i)
    return out


def _fault_points(fault_file: "_File"):
    """Parse the POINTS tuple literal out of fault.py: {name: lineno}."""
    if fault_file.tree is None:
        return {}
    for node in ast.walk(fault_file.tree):
        if isinstance(node, ast.Assign) \
                and any(isinstance(t, ast.Name) and t.id == "POINTS"
                        for t in node.targets) \
                and isinstance(node.value, (ast.Tuple, ast.List)):
            out = {}
            for elt in node.value.elts:
                v = _const_str(elt)
                if v:
                    out[v] = elt.lineno
            return out
    return {}


def _inject_calls(tree):
    """Yield (point_or_None, lineno) for fault.inject(...) call sites.
    ``None`` means the point argument is not a string literal."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        is_inject = (
            (isinstance(f, ast.Attribute) and f.attr == "inject"
             and isinstance(f.value, ast.Name)
             and f.value.id in ("fault", "_fault"))
            or (isinstance(f, ast.Name) and f.id == "inject"))
        if not is_inject or not node.args:
            continue
        yield _const_str(node.args[0]), node.lineno


def _flight_vocab(flight_file: "_File"):
    """Parse ``EVENTS`` and ``EVENT_PREFIXES`` tuple literals out of
    flightrec.py: ({name: lineno}, (prefix, ...)) — or (None, ()) when
    the vocabulary is absent (older tree)."""
    if flight_file is None or flight_file.tree is None:
        return None, ()
    events, prefixes = None, ()
    for node in ast.walk(flight_file.tree):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, (ast.Tuple, ast.List))):
            continue
        names = {t.id for t in node.targets if isinstance(t, ast.Name)}
        if "EVENTS" in names:
            events = {}
            for elt in node.value.elts:
                v = _const_str(elt)
                if v:
                    events[v] = elt.lineno
        elif "EVENT_PREFIXES" in names:
            prefixes = tuple(v for v in map(_const_str, node.value.elts)
                             if v)
    return events, prefixes


def _record_calls(tree):
    """Yield (name, prefix, lineno) for flightrec.record(...) emit
    sites.  Exactly one of name/prefix is non-None: a string-literal
    second argument gives ``name``; an f-string gives its static
    leading ``prefix`` (may be ``""``)."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr == "record"
                and isinstance(f.value, ast.Name)
                and f.value.id in ("flightrec", "_flightrec")):
            continue
        if len(node.args) < 2:
            continue
        n = node.args[1]
        name = _const_str(n)
        if name is not None:
            yield name, None, n.lineno
        elif isinstance(n, ast.JoinedStr):
            first = n.values[0] if n.values else None
            prefix = (first.value if isinstance(first, ast.Constant)
                      and isinstance(first.value, str) else "")
            yield None, prefix, n.lineno
        # a plain variable name stays unchecked (runtime territory)


def _gate_strings(tree):
    """Yield (gate_string, lineno) for postmortem gate sites — both
    shapes: a ``"--gate"`` argv constant followed by the gate list in
    the same ``list`` literal (subprocess calls in tests), and a
    ``gate="ev1,ev2"`` keyword argument (soak_bench Incidents)."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.List, ast.Tuple)):
            elts = node.elts
            for i, elt in enumerate(elts[:-1]):
                if _const_str(elt) == "--gate":
                    gate = _const_str(elts[i + 1])
                    if gate:
                        yield gate, elts[i + 1].lineno
        elif isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "gate":
                    gate = _const_str(kw.value)
                    if gate:
                        yield gate, kw.value.lineno


# ---------------------------------------------------------------------------
# per-file rules
# ---------------------------------------------------------------------------

def _check_time(fobj: "_File", findings):
    """MX-TIME001: any time.time() call (or ``from time import time``)."""
    aliases = set()
    for node in ast.walk(fobj.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for a in node.names:
                if a.name == "time":
                    aliases.add(a.asname or "time")
    for node in ast.walk(fobj.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = ((isinstance(f, ast.Attribute) and f.attr == "time"
                and isinstance(f.value, ast.Name) and f.value.id == "time")
               or (isinstance(f, ast.Name) and f.id in aliases))
        if hit and not fobj.suppressed("MX-TIME001", node):
            findings.append(Finding(
                "MX-TIME001", fobj.rel, node.lineno,
                "time.time() is wall-clock: an NTP step skews "
                "timeout/deadline/duration math — use time.monotonic() "
                "(or pragma allow-wall-clock with a reason)"))


_BROAD_NAMES = ("Exception", "BaseException")


def _is_broad_handler(type_node):
    if type_node is None:
        return True  # bare except
    nodes = (type_node.elts if isinstance(type_node, ast.Tuple)
             else [type_node])
    for n in nodes:
        if isinstance(n, ast.Name) and n.id in _BROAD_NAMES:
            return True
        if isinstance(n, ast.Attribute) and n.attr in _BROAD_NAMES:
            return True
    return False


def _handler_raises(handler):
    """True when a ``raise`` executes as part of the handler body —
    raises inside nested defs/lambdas run later (if ever), so they do
    not make the handler propagate."""
    stack = list(handler.body)
    while stack:
        n = stack.pop()
        if isinstance(n, ast.Raise):
            return True
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))
    return False


def _check_broad_except(fobj: "_File", findings):
    """MX-EXC001: broad handler with no raise anywhere in its body."""
    for node in ast.walk(fobj.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _is_broad_handler(node.type):
            continue
        if _handler_raises(node):
            continue  # propagates (possibly wrapped) — typed errors survive
        if fobj.suppressed("MX-EXC001", node):
            continue
        findings.append(Finding(
            "MX-EXC001", fobj.rel, node.lineno,
            "broad except swallows typed errors (PSTimeoutError, "
            "CheckpointCorruptError, ...) — narrow it, re-raise, or "
            "pragma allow-broad-except with a reason"))


_DONATE_KWARGS = ("donate_argnums", "donate_argnames")


def _is_jit_ref(f):
    """A reference to ``jax.jit``/``jit``/``pjit`` (the callee of a
    call site, or a bare ``@jax.jit`` decorator).

    Attribute receivers are restricted to the conventional module
    names so ``self.jit()`` methods do not false-positive."""
    if isinstance(f, ast.Name):
        return f.id in ("jit", "pjit")
    if isinstance(f, ast.Attribute) and f.attr in ("jit", "pjit"):
        v = f.value
        return isinstance(v, ast.Name) and v.id in ("jax", "pjit",
                                                    "_pjit", "jax_pjit")
    return False


def _check_donate(fobj: "_File", findings):
    """MX-DONATE001: framework jit/pjit sites must decide donation.

    Only applies inside ``incubator_mxnet_tpu/`` — tools, benchmarks
    and scripts jit throwaway closures where donation is noise.  The
    keyword's *presence* satisfies the rule (a conditional value like
    ``donate_argnums=(1,) if static else ()`` is still a decision).
    Covers both spellings: ``jax.jit(fn, ...)`` call sites and the
    bare ``@jax.jit`` decorator (which can never carry the keyword —
    it must become ``@jax.jit(donate_argnums=...)`` wrapping, wire
    donation at the call site, or carry the pragma)."""
    rel = fobj.rel.replace(os.sep, "/")
    if "incubator_mxnet_tpu/" not in rel \
            and not rel.startswith("incubator_mxnet_tpu"):
        return

    def emit(node):
        findings.append(Finding(
            "MX-DONATE001", fobj.rel, node.lineno,
            "jax.jit/pjit site passes no donate_argnums — input "
            "buffers this entry point could reuse stay live alongside "
            "the outputs; donate them, or pragma "
            "disable=MX-DONATE001(reason) stating why the inputs are "
            "caller-held"))

    for node in ast.walk(fobj.tree):
        if isinstance(node, ast.Call) and _is_jit_ref(node.func):
            if any(kw.arg in _DONATE_KWARGS for kw in node.keywords):
                continue
            if fobj.suppressed("MX-DONATE001", node):
                continue
            emit(node)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # bare @jax.jit decorator: no way to carry the keyword
            for dec in node.decorator_list:
                if _is_jit_ref(dec) \
                        and not fobj.suppressed_at("MX-DONATE001",
                                                   dec.lineno):
                    emit(dec)


_SHARD_CALLEES = ("shard_map", "pjit")
_SHARD_RECEIVERS = ("jax", "pjit", "_pjit", "base", "_base",
                    "shard_map")
_SHARD_KWARGS = ("mesh", "in_specs", "out_specs", "in_shardings",
                 "out_shardings")


def _is_shard_ref(f):
    """A reference to ``shard_map``/``pjit`` as a
    call-site callee.  Attribute receivers are restricted to the
    conventional module names (``jax.shard_map``,
    ``shard_map.shard_map``) so unrelated methods do not
    false-positive."""
    if isinstance(f, ast.Name):
        return f.id in _SHARD_CALLEES
    if isinstance(f, ast.Attribute) and f.attr in _SHARD_CALLEES:
        v = f.value
        return isinstance(v, ast.Name) and v.id in _SHARD_RECEIVERS
    return False


def _check_shard(fobj: "_File", findings):
    """MX-SHARD001: framework shard_map/pjit sites must say where the
    computation lands.

    Only applies inside ``incubator_mxnet_tpu/`` (the MX-DONATE001
    scope rule: tools and benchmarks map throwaway closures).  A
    ``mesh=``/``in_specs=``/``in_shardings=``-family keyword satisfies
    the rule, as do two or more positional arguments (the
    ``shard_map(fn, mesh, ...)`` positional spelling) — the
    point is that the mesh/sharding decision is VISIBLE at the call
    site, where shardlint (analysis/shardlint.py) can hold the declared
    specs against the propagated ones, not inherited from ambient
    context."""
    rel = fobj.rel.replace(os.sep, "/")
    if "incubator_mxnet_tpu/" not in rel \
            and not rel.startswith("incubator_mxnet_tpu"):
        return
    for node in ast.walk(fobj.tree):
        if not (isinstance(node, ast.Call) and _is_shard_ref(node.func)):
            continue
        if any(kw.arg in _SHARD_KWARGS for kw in node.keywords):
            continue
        if len(node.args) >= 2:
            continue
        if fobj.suppressed("MX-SHARD001", node):
            continue
        findings.append(Finding(
            "MX-SHARD001", fobj.rel, node.lineno,
            "shard_map/pjit site passes no explicit mesh/sharding "
            "argument — the placement decision is invisible here and "
            "unanalyzable by shardlint; pass mesh=/in_specs= (or "
            "in_shardings=), or pragma disable=MX-SHARD001(reason) "
            "stating where the mesh comes from"))


_HOST_NS = ("onp", "np", "numpy", "_onp")
_HOST_NS_FNS = ("asarray", "array", "save", "load", "fromfile")
_HOST_NAME_FNS = ("print", "open", "input")


def _host_effect_of(call: ast.Call):
    """Name of the host-effect a call performs inside an op impl."""
    f = call.func
    if isinstance(f, ast.Name) and f.id in _HOST_NAME_FNS:
        return f.id
    if isinstance(f, ast.Attribute):
        if f.attr == "asnumpy":
            return ".asnumpy()"
        if f.attr == "tofile":
            return ".tofile()"
        if (f.attr in _HOST_NS_FNS and isinstance(f.value, ast.Name)
                and f.value.id in _HOST_NS):
            return f"{f.value.id}.{f.attr}"
    return None


def _register_meta(dec: ast.Call):
    """(is_register, effective_bulkable) for an op decorator call.

    Mirrors ops/registry.py defaulting: ``bulkable`` defaults to
    ``jittable`` (itself default True).  Non-literal values are treated
    as opted-out (no static claim to check)."""
    if _call_name(dec.func) != "register":
        return False, False

    def _flag(name, default):
        for kw in dec.keywords:
            if kw.arg == name:
                if isinstance(kw.value, ast.Constant):
                    return bool(kw.value.value)
                return None  # dynamic: unknowable statically
        return default

    jittable = _flag("jittable", True)
    bulkable = _flag("bulkable", None if jittable is None else jittable)
    return True, bool(bulkable)


def _check_bulkable_purity(fobj: "_File", findings):
    """MX-BULK001: host effects inside a bulkable op's implementation."""
    for node in ast.walk(fobj.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        bulkable = False
        for dec in node.decorator_list:
            if isinstance(dec, ast.Call):
                is_reg, eff = _register_meta(dec)
                if is_reg:
                    bulkable = eff
                    break
        if not bulkable:
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                effect = _host_effect_of(sub)
                if effect and not fobj.suppressed("MX-BULK001", sub):
                    findings.append(Finding(
                        "MX-BULK001", fobj.rel, sub.lineno,
                        f"op {node.name!r} is registered bulkable but "
                        f"calls {effect} — deferring it into a bulked "
                        "segment reorders the host effect; register "
                        "with bulkable=False (or jittable=False)"))


# ---------------------------------------------------------------------------
# lock-order graph (per module, with same-module call resolution)
# ---------------------------------------------------------------------------

def _lock_key(expr, modname, classname):
    """Canonical node for a lock-guard expression, or None.

    ``self.X`` resolves to ``module:Class.X``; any other receiver
    collapses to ``module:*.X`` (same attribute, unknown holder class —
    Var._lock acquired through a parameter, for instance)."""
    if not isinstance(expr, ast.Attribute):
        return None
    if not _LOCK_ATTR_RE.search(expr.attr):
        return None
    if isinstance(expr.value, ast.Name) and expr.value.id == "self" \
            and classname:
        return f"{modname}:{classname}.{expr.attr}"
    return f"{modname}:*.{expr.attr}"


class _FuncInfo:
    __slots__ = ("key", "direct_locks", "calls", "edges")

    def __init__(self, key):
        self.key = key
        self.direct_locks = set()   # locks acquired anywhere in the body
        self.calls = set()          # resolvable same-module callees
        # (held_lock, callee_or_lock, line): deferred edge material
        self.edges = []


def _collect_lock_info(fobj: "_File", modname):
    """Per-function lock acquisitions, nested-with edges, and calls made
    while holding a lock.  A ``disable=MX-LOCK001`` pragma on an
    acquisition or call line removes that site from the graph (both its
    edges and its contribution to transitive acquire-sets)."""
    funcs = {}

    class V(ast.NodeVisitor):
        def __init__(self):
            self.cls = None
            self.fn = None
            self.held = []   # stack of (lockkey, line)

        def visit_ClassDef(self, node):
            prev, self.cls = self.cls, node.name
            self.generic_visit(node)
            self.cls = prev

        def _fn_key(self, name):
            return (modname, self.cls, name)

        def visit_FunctionDef(self, node):
            prev_fn, prev_held = self.fn, self.held
            key = self._fn_key(node.name)
            self.fn = funcs.setdefault(key, _FuncInfo(key))
            self.held = []   # a nested def runs later: fresh hold stack
            self.generic_visit(node)
            self.fn, self.held = prev_fn, prev_held

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_With(self, node):
            acquired = []
            for item in node.items:
                lk = _lock_key(item.context_expr, modname, self.cls)
                if lk and fobj.suppressed_at("MX-LOCK001",
                                             item.context_expr.lineno):
                    lk = None
                if lk and self.fn is not None:
                    self.fn.direct_locks.add(lk)
                    for held, _ in self.held:
                        self.fn.edges.append(
                            (held, ("lock", lk), item.context_expr.lineno))
                    acquired.append((lk, item.context_expr.lineno))
                    self.held.append((lk, item.context_expr.lineno))
                else:
                    # a guard-call item (``with make_guard():``) runs
                    # while earlier items' locks are held — its call
                    # edges (transitive acquires) belong in the graph
                    self.visit(item.context_expr)
            for stmt in node.body:
                self.visit(stmt)
            for _ in acquired:
                self.held.pop()

        visit_AsyncWith = visit_With

        def visit_Call(self, node):
            if self.fn is not None \
                    and not fobj.suppressed_at("MX-LOCK001", node.lineno):
                callee = None
                f = node.func
                if isinstance(f, ast.Name):
                    callee = (modname, None, f.id)
                elif isinstance(f, ast.Attribute) \
                        and isinstance(f.value, ast.Name) \
                        and f.value.id == "self" and self.cls:
                    callee = (modname, self.cls, f.attr)
                if callee is not None:
                    self.fn.calls.add(callee)
                    for held, _ in self.held:
                        self.fn.edges.append(
                            (held, ("call", callee), node.lineno))
            self.generic_visit(node)

    V().visit(fobj.tree)
    return funcs


def _check_lock_order(files, findings):
    """MX-LOCK001: cycles in the static lock-order graph.

    Nodes are canonical lock names; an edge A→B means some code path
    acquires B while holding A (lexically nested ``with``, or a call —
    resolved within the module for ``self.m()``/bare ``f()`` — to a
    function whose transitive acquisitions include B)."""
    funcs = {}
    file_of_mod = {}
    for fobj in files:
        if fobj.tree is None:
            continue
        # key by relative path, not basename: two same-named modules
        # (every __init__.py, tools/x.py vs pkg/x.py) must not merge
        # into one lock graph — a cross-file merge fabricates cycles
        # and collides (modname, cls, name) function keys
        modname = os.path.splitext(fobj.rel)[0].replace(os.sep, "/")
        file_of_mod.setdefault(modname, fobj.rel)
        funcs.update(_collect_lock_info(fobj, modname))

    # transitive acquire-sets (fixpoint over the same-module call graph)
    summary = {k: set(fi.direct_locks) for k, fi in funcs.items()}
    changed = True
    while changed:
        changed = False
        for k, fi in funcs.items():
            for callee in fi.calls:
                target = summary.get(callee)
                if target is None and callee[1] is not None:
                    # self.m() may resolve to a module-level name too
                    target = summary.get((callee[0], None, callee[2]))
                if target and not target <= summary[k]:
                    summary[k] |= target
                    changed = True

    edges = {}   # (A, B) -> (file, line)
    for (modname, _cls, _name), fi in funcs.items():
        rel = file_of_mod.get(modname, modname)
        for held, target, line in fi.edges:
            if target[0] == "lock":
                locks = (target[1],)
            else:
                callee = target[1]
                s = summary.get(callee) or (
                    summary.get((callee[0], None, callee[2]))
                    if callee[1] is not None else None) or ()
                locks = tuple(s)
            for lk in locks:
                edges.setdefault((held, lk), (rel, line))

    graph = {}
    for (a, b) in edges:
        graph.setdefault(a, set()).add(b)

    # cycle detection (iterative DFS, each cycle reported once)
    seen_cycles = set()
    WHITE, GREY, BLACK = 0, 1, 2
    color = {n: WHITE for n in graph}

    def dfs(start):
        stack = [(start, iter(graph.get(start, ())))]
        path = [start]
        color[start] = GREY
        while stack:
            node, it = stack[-1]
            advanced = False
            for nxt in it:
                if color.get(nxt, WHITE) == GREY:
                    i = path.index(nxt)
                    cyc = tuple(sorted(path[i:]))
                    if cyc not in seen_cycles:
                        seen_cycles.add(cyc)
                        rel, line = edges[(node, nxt)]
                        order = " -> ".join(path[i:] + [nxt])
                        findings.append(Finding(
                            "MX-LOCK001", rel, line,
                            f"lock-order cycle: {order} — some path "
                            "acquires these locks in the opposite order; "
                            "pick one global order"))
                elif color.get(nxt, WHITE) == WHITE:
                    color[nxt] = GREY
                    stack.append((nxt, iter(graph.get(nxt, ()))))
                    path.append(nxt)
                    advanced = True
                    break
            if not advanced:
                stack.pop()
                path.pop()
                color[node] = BLACK

    for n in list(graph):
        if color.get(n, WHITE) == WHITE:
            dfs(n)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _discover(paths):
    out = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d != "__pycache__" and not d.startswith(".")]
                for n in sorted(names):
                    if n.endswith(".py"):
                        out.append(os.path.join(root, n))
        elif p.endswith(".py"):
            out.append(p)
    return out


def lint_paths(paths, repo_root=None, docs_path=None, fault_points=None):
    """Lint ``paths`` (files and/or directories); returns Findings.

    ``docs_path`` defaults to ``<repo_root>/docs/env_vars.md``;
    ``repo_root`` defaults to the current directory.  ``fault_points``
    overrides the registry parsed from a scanned ``fault.py`` (tests).
    Whole-surface rules (ENV001/002, FAULT002) run only when at least
    one path is a directory.
    """
    repo_root = os.path.abspath(repo_root or os.getcwd())
    whole_surface = any(os.path.isdir(p) for p in paths)
    if docs_path is None:
        cand = os.path.join(repo_root, "docs", "env_vars.md")
        docs_path = cand if os.path.exists(cand) else None

    files = []
    findings: list[Finding] = []
    for path in _discover(paths):
        fobj = _File(path, os.path.relpath(os.path.abspath(path), repo_root))
        if fobj.parse_error is not None:
            findings.append(Finding("MX-AST000", fobj.rel,
                                    fobj.parse_error.lineno or 1,
                                    f"syntax error: {fobj.parse_error.msg}"))
            continue
        files.append(fobj)

    # -- per-file rules --------------------------------------------------
    for fobj in files:
        _check_time(fobj, findings)
        _check_broad_except(fobj, findings)
        _check_bulkable_purity(fobj, findings)
        _check_donate(fobj, findings)
        _check_shard(fobj, findings)

    # -- lock-order graph --------------------------------------------------
    _check_lock_order(files, findings)

    # -- env-var <-> docs sync ---------------------------------------------
    env_reads = {}
    for fobj in files:
        for var, line in _env_reads(fobj.tree):
            env_reads.setdefault(var, (fobj, line))
    if docs_path is not None and whole_surface:
        documented = _documented_vars(docs_path)
        docs_rel = os.path.relpath(os.path.abspath(docs_path), repo_root)
        for var, (fobj, line) in sorted(env_reads.items()):
            if var not in documented \
                    and not fobj.suppressed_at("MX-ENV001", line):
                findings.append(Finding(
                    "MX-ENV001", fobj.rel, line,
                    f"{var} is read here but has no row in {docs_rel} — "
                    "document the knob (variable column of a table)"))
        for var, line in sorted(documented.items()):
            if var not in env_reads:
                findings.append(Finding(
                    "MX-ENV002", docs_rel, line,
                    f"{var} is documented but never read in the scanned "
                    "code — remove the row or wire the knob"))

    # -- fault-point registry ------------------------------------------------
    fault_file = next((f for f in files
                       if os.path.basename(f.path) == "fault.py"), None)
    declared = dict(fault_points) if fault_points is not None else (
        _fault_points(fault_file) if fault_file is not None else None)
    if declared is not None:
        wired = set()
        for fobj in files:
            if fobj is fault_file:
                continue
            for point, line in _inject_calls(fobj.tree):
                if point is None:
                    continue  # dynamic point name: runtime guard covers it
                wired.add(point)
                if point not in declared \
                        and not fobj.suppressed_at("MX-FAULT001", line):
                    findings.append(Finding(
                        "MX-FAULT001", fobj.rel, line,
                        f"fault.inject({point!r}) names an undeclared "
                        "point — add it to fault.POINTS (it can never "
                        "fire otherwise)"))
        if whole_surface and fault_file is not None:
            for point, line in sorted(declared.items()):
                if point not in wired:
                    findings.append(Finding(
                        "MX-FAULT002", fault_file.rel, line,
                        f"fault point {point!r} is declared in "
                        "fault.POINTS but no inject() call site names it "
                        "— dead chaos coverage"))

    # -- flight-event registry ------------------------------------------------
    flight_file = next((f for f in files
                        if os.path.basename(f.path) == "flightrec.py"
                        and "analysis" not in f.rel.split(os.sep)), None)
    events, prefixes = _flight_vocab(flight_file)

    def _flight_name_ok(tok):
        if tok in events:
            return True
        for pfx in prefixes:
            if tok.startswith(pfx) and len(tok) > len(pfx):
                # the fault.* family composes with the fault-point
                # registry: the suffix must be a declared point
                if pfx == "fault." and declared is not None:
                    return tok[len(pfx):] in declared
                return True
        return False

    def _check_flight_gates(fobj):
        for gate, line in _gate_strings(fobj.tree):
            if fobj.suppressed_at("MX-FLIGHT001", line):
                continue
            for tok in gate.split(","):
                tok = tok.strip()
                if tok and not _flight_name_ok(tok):
                    findings.append(Finding(
                        "MX-FLIGHT001", fobj.rel, line,
                        f"postmortem gate names {tok!r} but no emitter "
                        "registers it in flightrec.EVENTS — this gate "
                        "can only fail at chaos-stage runtime"))

    if events is not None:
        for fobj in files:
            if fobj is flight_file:
                continue
            for name, prefix, line in _record_calls(fobj.tree):
                if fobj.suppressed_at("MX-FLIGHT001", line):
                    continue
                if name is not None and not _flight_name_ok(name):
                    findings.append(Finding(
                        "MX-FLIGHT001", fobj.rel, line,
                        f"flightrec.record emits {name!r} which is not "
                        "registered in flightrec.EVENTS — add the row "
                        "(postmortem gates can only name registered "
                        "events)"))
                elif prefix is not None and not any(
                        p.startswith(prefix) or prefix.startswith(p)
                        for p in prefixes):
                    findings.append(Finding(
                        "MX-FLIGHT001", fobj.rel, line,
                        f"flightrec.record emits a dynamic name with "
                        f"static prefix {prefix!r} outside every "
                        "flightrec.EVENT_PREFIXES family"))
            _check_flight_gates(fobj)
        # gate strings also live in tests/ (subprocess postmortem
        # runs), which the lint surface does not otherwise scan —
        # sweep them for gate sites only when linting whole-surface
        tests_dir = os.path.join(repo_root, "tests")
        if whole_surface and os.path.isdir(tests_dir):
            scanned = {f.path for f in files}
            for name in sorted(os.listdir(tests_dir)):
                path = os.path.join(tests_dir, name)
                if not name.endswith(".py") or path in scanned:
                    continue
                tobj = _File(path, os.path.relpath(path, repo_root))
                if tobj.parse_error is None:
                    _check_flight_gates(tobj)

    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    return findings


# baseline machinery: shared with graphlint — see .findings
# (load_baseline / apply_baseline / render imported at the top)
