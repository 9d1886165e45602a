"""shardlint — SPMD sharding lint, collective-cost model, and per-shard
HBM plans (build-time, pre-XLA).

Where memlint answers "does this graph fit ONE chip", shardlint answers
the mesh-era questions: does each SHARD fit its chip, what does one step
pay in collective traffic, and do the declared shardings actually agree
with each other?  It propagates sharding specs from the entry-point
declarations (``NamedSharding``/``PartitionSpec``) through the equation
graph — ``shard_map`` ``in_names``/``out_names``, pjit
``in_shardings``/``out_shardings``, ``sharding_constraint`` — recursing
into sub-jaxprs the graphlint way, and produces per compiled graph:

* a **per-shard HBM plan**: memlint's liveness sweep with every buffer
  divided by its shard factor on the declared mesh (replicated buffers
  are charged full-size to every shard), reported as
  ``peak_hbm_bytes_per_shard`` and gated by **SL-SHARD-PEAK001**
  against the per-chip budget ``MXNET_SHARDLINT_CHIP_BYTES`` (0 = off);
* a **collective-cost model**: every explicit collective (``psum``,
  ``all_gather``, ``psum_scatter``, ``all_to_all``, ``ppermute``) and
  every implied resharding priced in bytes on its mesh axis and summed
  into ``comm_bytes_per_step`` (collectives inside a ``scan`` body are
  multiplied by the trip count);
* **spec-conformance rules**:

  ============== =====================================================
  SL-SHARD-PEAK001 per-shard peak exceeds ``MXNET_SHARDLINT_CHIP_BYTES``
  SL-RESHARD001  producer and consumer declare incompatible shardings
                 on the same value — an avoidable mid-graph reshard
  SL-REPL001     a large (>= ``MXNET_SHARDLINT_REPL_BYTES``, default
                 8 MiB) entry buffer declared fully replicated when a
                 mesh axis could shard it
  SL-SPEC001     a declared sharding names a mesh axis the mesh does
                 not have
  SL-DONATE001   a donated input whose signature-matched output has a
                 different sharding — the aliasing the donation paid
                 for is silently defeated by a reshard
  ============== =====================================================

Known slack (documented, deliberate): spec propagation is
declaration-driven — a value nobody declared is *untracked* and charged
full-size to every shard (a conservative upper bound, never an
undercount); pjit sub-graph transients are charged unscaled;
``while`` trip counts are unknown so body collectives are charged once;
the reshard cost model prices a spec change at one full payload copy
(the true all-to-all may be cheaper).

Build-time wiring is the memlint contract exactly: inert unless
``MXNET_GRAPH_SHARDLINT`` (or :func:`set_shard_mode`) turns it on,
``warn`` warns per finding, ``strict`` raises
:class:`~..error.ShardLintError` on error-severity findings, and an
analyzer crash warns but never breaks a build.  Findings reuse
graphlint's :class:`Finding` so they flow through the shared
``findings.py`` baseline machinery; ``tools/shardlint.py`` is the CLI.
"""
import math
import threading
import warnings as _warnings

import jax
from jax.extend import core as _jcore

from ..base import get_env
from .graphlint import Finding, render, _source_of
from .memlint import (_plan as _mem_plan, _nbytes, _arg_slices,
                      _inner_jaxprs, _aval, _is_var, _sig)

__all__ = [
    "Config", "ShardReport", "analyze_fn", "check_sharding",
    "shard_mode", "set_shard_mode", "shard_scope", "sweep_parallel",
    "render", "Finding", "stats", "reset_stats",
]

RULES = {
    "SL-SHARD-PEAK001": "per-shard peak HBM exceeds the per-chip budget",
    "SL-RESHARD001": "incompatible declared shardings on the same value",
    "SL-REPL001": "large entry buffer left fully replicated",
    "SL-SPEC001": "declared sharding names an axis absent from the mesh",
    "SL-DONATE001": "donated input resharded before reuse",
}

# resharding / donation-mismatch findings below this payload are noise
# (a handful of scalars crossing a spec boundary costs nothing)
_RESHARD_MIN_BYTES = 1024


class Config:
    """Thresholds for the sharding passes.

    ``chip_bytes`` gates SL-SHARD-PEAK001 (0 = off; defaults from
    ``MXNET_SHARDLINT_CHIP_BYTES``); ``repl_bytes`` is the floor above
    which a fully replicated entry buffer draws SL-REPL001 (defaults
    from ``MXNET_SHARDLINT_REPL_BYTES``, 8 MiB); ``ignore`` silences
    whole rules for one analysis (the graphlint Config contract)."""

    __slots__ = ("chip_bytes", "repl_bytes", "ignore")

    def __init__(self, chip_bytes=None, repl_bytes=None, ignore=()):
        if chip_bytes is None:
            chip_bytes = get_env("MXNET_SHARDLINT_CHIP_BYTES", 0, int)
        if repl_bytes is None:
            repl_bytes = get_env("MXNET_SHARDLINT_REPL_BYTES",
                                 8 << 20, int)
        self.chip_bytes = int(chip_bytes)
        self.repl_bytes = int(repl_bytes)
        self.ignore = frozenset(ignore)


# ---------------------------------------------------------------------------
# spec plumbing: a spec is a tuple (one entry per dim) of tuples of mesh
# axis names; () = replicated on that dim.  None = untracked (nobody
# declared anything reaching this value).
# ---------------------------------------------------------------------------

def _mesh_axis_sizes(mesh):
    """``{axis_name: size}`` from a jax Mesh, a dict, or None."""
    if mesh is None:
        return {}
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    shape = getattr(mesh, "shape", None)
    if shape is not None:
        return {str(k): int(v) for k, v in dict(shape).items()}
    return {}


def _norm_spec(spec, ndim):
    """Normalize a PartitionSpec / tuple / None into the internal
    per-dim tuple-of-axis-names form, padded to ``ndim``."""
    if spec is None:
        return tuple(() for _ in range(ndim))
    out = []
    for entry in tuple(spec)[:ndim]:
        if entry is None:
            out.append(())
        elif isinstance(entry, str):
            out.append((entry,))
        else:
            out.append(tuple(str(a) for a in entry))
    while len(out) < ndim:
        out.append(())
    return tuple(out)


def _names_to_spec(names, ndim):
    """shard_map ``in_names``/``out_names`` dict ({dim: (axis, ...)})
    into the internal form."""
    return tuple(tuple(names.get(d, ())) for d in range(ndim))


def _spec_axes(spec):
    axes = []
    for entry in spec:
        axes.extend(entry)
    return axes


def _spec_str(spec):
    if spec is None:
        return "untracked"
    parts = []
    for entry in spec:
        if not entry:
            parts.append("None")
        elif len(entry) == 1:
            parts.append(f"'{entry[0]}'")
        else:
            parts.append("(" + ",".join(f"'{a}'" for a in entry) + ")")
    return "P(" + ", ".join(parts) + ")"


def _shard_factor(spec, axis_sizes):
    """How many ways this buffer is split on the mesh (1 = replicated
    or untracked — charged full-size, the conservative upper bound)."""
    if spec is None:
        return 1
    n = 1
    for entry in spec:
        for a in entry:
            n *= int(axis_sizes.get(a, 1))
    return max(1, n)


def _declared_spec(sharding, ndim):
    """NamedSharding -> internal spec; UnspecifiedValue/other -> None."""
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None
    return _norm_spec(spec, ndim)


def _replicated(ndim):
    return tuple(() for _ in range(ndim))


def _shape_of(v):
    return tuple(getattr(_aval(v), "shape", ()))


# ---------------------------------------------------------------------------
# collective cost model
# ---------------------------------------------------------------------------

def _axis_names(params):
    raw = params.get("axes", params.get("axis_name", ()))
    if raw is None:
        raw = ()
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))

# bytes moved per participant for payload p on an n-device axis
_COLLECTIVES = {
    "psum": lambda p, n: 2 * p * (n - 1) // n,           # all-reduce
    "pmax": lambda p, n: 2 * p * (n - 1) // n,
    "pmin": lambda p, n: 2 * p * (n - 1) // n,
    "all_gather": lambda p, n: p * (n - 1),              # p = per-shard in
    "all_gather_invariant": lambda p, n: p * (n - 1),
    "reduce_scatter": lambda p, n: p * (n - 1) // n,
    "psum_scatter": lambda p, n: p * (n - 1) // n,
    "all_to_all": lambda p, n: p * (n - 1) // n,
    "ppermute": lambda p, n: p,                          # one hop
}


def _record_collective(collectives, kind, axes, n, payload, scale,
                       path, source):
    comm = _COLLECTIVES[kind](payload, n) if n > 1 else 0
    collectives.append({
        "kind": kind, "axis": "+".join(axes) if axes else None,
        "axis_size": n, "payload_bytes": payload,
        "comm_bytes": comm * scale, "count": scale,
        "path": path or "/", "source": source,
    })


# ---------------------------------------------------------------------------
# the walk: propagate specs, price collectives, flag reshards
# ---------------------------------------------------------------------------

def _emit_reshard(findings, collectives, where, path, prim, eqn, v,
                  prop, decl, what):
    nb = _nbytes(_aval(v))
    if nb < _RESHARD_MIN_BYTES:
        return
    src = _source_of(eqn)
    findings.append(Finding(
        "SL-RESHARD001", where, path, prim, src,
        f"{what}: value {_shape_of(v)} arrives as {_spec_str(prop)} but "
        f"is declared {_spec_str(decl)} here — the partitioner inserts "
        f"a reshard ({nb} bytes); align the producer's declared "
        "sharding with the consumer's (or drop the redundant "
        "constraint)", severity="error"))
    collectives.append({
        "kind": "reshard", "axis": None, "axis_size": 0,
        "payload_bytes": nb, "comm_bytes": nb, "count": 1,
        "path": path or "/", "source": src,
    })


def _walk(jaxpr, var2spec, axis_sizes, where, path, findings,
          collectives, scale):
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        params = eqn.params

        if prim in _COLLECTIVES:
            axes = _axis_names(params)
            n = 1
            for a in axes:
                n *= int(axis_sizes.get(a, 1))
            payload = sum(_nbytes(_aval(v)) for v in eqn.invars
                          if _is_var(v))
            if axes and payload:
                _record_collective(collectives, prim, axes, n, payload,
                                   scale, path, _source_of(eqn))

        elif prim == "shard_map":
            sm_sizes = _mesh_axis_sizes(params.get("mesh"))
            in_names = params.get("in_names", ())
            out_names = params.get("out_names", ())
            for v, names in zip(eqn.invars, in_names):
                ndim = len(_shape_of(v))
                decl = _names_to_spec(names, ndim)
                if not _is_var(v):
                    continue
                prop = var2spec.get(id(v))
                if prop is not None and prop != decl:
                    _emit_reshard(findings, collectives, where, path,
                                  prim, eqn, v, prop, decl,
                                  "shard_map in_specs disagree with the "
                                  "producer")
            inner = params.get("jaxpr")
            if inner is not None:
                body = getattr(inner, "jaxpr", inner)
                for cv in body.constvars:
                    var2spec[id(cv)] = _replicated(len(_shape_of(cv)))
                for iv in body.invars:
                    # the body sees its own shard: locally replicated
                    var2spec[id(iv)] = _replicated(len(_shape_of(iv)))
                _walk(body, var2spec, sm_sizes, where,
                      f"{path}/shard_map", findings, collectives, scale)
            for v, names in zip(eqn.outvars, out_names):
                var2spec[id(v)] = _names_to_spec(names,
                                                 len(_shape_of(v)))

        elif prim == "pjit":
            closed = params.get("jaxpr")
            body = getattr(closed, "jaxpr", closed)
            in_sh = params.get("in_shardings") or ()
            out_sh = params.get("out_shardings") or ()
            for cv in body.constvars:
                var2spec[id(cv)] = _replicated(len(_shape_of(cv)))
            for i, iv in enumerate(body.invars):
                decl = None
                if i < len(in_sh):
                    decl = _declared_spec(in_sh[i], len(_shape_of(iv)))
                src_v = eqn.invars[i] if i < len(eqn.invars) else None
                prop = (var2spec.get(id(src_v))
                        if src_v is not None and _is_var(src_v) else None)
                if decl is not None and prop is not None and decl != prop:
                    _emit_reshard(findings, collectives, where, path,
                                  prim, eqn, src_v, prop, decl,
                                  "pjit in_shardings disagree with the "
                                  "producer")
                var2spec[id(iv)] = decl if decl is not None else prop
            _walk(body, var2spec, axis_sizes, where, f"{path}/pjit",
                  findings, collectives, scale)
            for i, ov in enumerate(eqn.outvars):
                ndim = len(_shape_of(ov))
                decl = None
                if i < len(out_sh):
                    decl = _declared_spec(out_sh[i], ndim)
                body_ov = (body.outvars[i]
                           if i < len(body.outvars) else None)
                prop = (var2spec.get(id(body_ov))
                        if body_ov is not None and _is_var(body_ov)
                        else None)
                var2spec[id(ov)] = decl if decl is not None else prop

        elif prim == "sharding_constraint":
            v = eqn.invars[0] if eqn.invars else None
            ndim = len(_shape_of(v)) if v is not None else 0
            decl = _declared_spec(params.get("sharding"), ndim)
            prop = (var2spec.get(id(v))
                    if v is not None and _is_var(v) else None)
            if decl is not None and prop is not None and decl != prop:
                _emit_reshard(findings, collectives, where, path, prim,
                              eqn, v, prop, decl,
                              "sharding_constraint disagrees with the "
                              "producer")
            for ov in eqn.outvars:
                var2spec[id(ov)] = decl if decl is not None else prop

        else:
            subs = list(_iter_subjaxprs_tagged(params))
            if subs:
                # collectives in a scan body run once per step; while
                # trip counts are unknown — charged once (slack)
                sub_scale = scale * int(params.get("length", 1) or 1) \
                    if prim == "scan" else scale
                for tag, sub in subs:
                    body = getattr(sub, "jaxpr", sub)
                    for cv in body.constvars:
                        var2spec[id(cv)] = _replicated(
                            len(_shape_of(cv)))
                    for iv in body.invars:
                        if id(iv) not in var2spec:
                            var2spec[id(iv)] = None
                    _walk(body, var2spec, axis_sizes, where,
                          f"{path}/{prim}{tag}", findings, collectives,
                          sub_scale)
            _structural_specs(eqn, prim, params, var2spec)

        # shape-match fallback for anything still unmapped: an output
        # the same shape as a tracked input keeps its layout (covers
        # elementwise, convert_element_type, collectives' results, ...)
        for ov in eqn.outvars:
            if id(ov) in var2spec:
                continue
            shape = _shape_of(ov)
            spec = None
            for iv in eqn.invars:
                if _is_var(iv) and var2spec.get(id(iv)) is not None \
                        and _shape_of(iv) == shape:
                    spec = var2spec[id(iv)]
                    break
            var2spec[id(ov)] = spec


def _iter_subjaxprs_tagged(params):
    for name, v in params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for i, item in enumerate(vals):
            if isinstance(item, (_jcore.Jaxpr, _jcore.ClosedJaxpr)):
                tag = name.replace("_jaxpr", "").replace("jaxpr", "")
                tag = tag.strip("_") or ""
                idx = f"#{i}" if len(vals) > 1 else ""
                yield f":{tag}{idx}" if (tag or idx) else "", item


def _structural_specs(eqn, prim, params, var2spec):
    """Exact spec transfer for the shape-changing primitives we can
    reason about; everything else falls through to the shape-match
    heuristic (or untracked)."""
    if not eqn.invars or not _is_var(eqn.invars[0]):
        return
    spec = var2spec.get(id(eqn.invars[0]))
    if spec is None or len(eqn.outvars) != 1:
        return
    ov = eqn.outvars[0]
    if prim == "transpose":
        perm = params.get("permutation")
        if perm is not None and len(perm) == len(spec):
            var2spec[id(ov)] = tuple(spec[p] for p in perm)
    elif prim == "broadcast_in_dim":
        bdims = params.get("broadcast_dimensions", ())
        in_shape = _shape_of(eqn.invars[0])
        out_shape = _shape_of(ov)
        out = [() for _ in out_shape]
        for i, d in enumerate(bdims):
            if i < len(spec) and i < len(in_shape) \
                    and d < len(out_shape) \
                    and in_shape[i] == out_shape[d]:
                out[d] = spec[i]
        var2spec[id(ov)] = tuple(out)
    elif prim in ("reduce_sum", "reduce_max", "reduce_min",
                  "reduce_prod", "reduce_and", "reduce_or",
                  "argmax", "argmin"):
        axes = set(params.get("axes", ()))
        var2spec[id(ov)] = tuple(e for i, e in enumerate(spec)
                                 if i not in axes)


# ---------------------------------------------------------------------------
# the per-shard plan: memlint's liveness sweep, bytes / shard factor
# ---------------------------------------------------------------------------

def _sharded_peak(jaxpr, plan, var2spec, axis_sizes):
    """Re-run memlint's event sweep with each buffer scaled by its
    shard factor.  A buffer reachable through several vars takes the
    SMALLEST factor (largest per-shard bytes — conservative)."""
    buf_factor = {}
    for vid, b in plan.var2buf.items():
        f = _shard_factor(var2spec.get(vid), axis_sizes)
        prev = buf_factor.get(id(b))
        buf_factor[id(b)] = f if prev is None else min(prev, f)

    def scaled(b):
        return int(math.ceil(b.nbytes / buf_factor.get(id(b), 1)))

    # inner-scope transients: a shard_map body's avals are already
    # per-shard; pjit/scan bodies are charged unscaled (upper bound)
    n = len(jaxpr.eqns)
    inner_extra = {}
    for t, eqn in enumerate(jaxpr.eqns):
        inner_peak = 0
        for inner, iconsts in _inner_jaxprs(eqn.params):
            inner_peak = max(inner_peak,
                             _mem_plan(inner, iconsts, set()).peak)
        if inner_peak:
            operand = sum(scaled(plan.var2buf[id(v)])
                          for v in eqn.invars
                          if _is_var(v) and id(v) in plan.var2buf)
            extra = inner_peak - operand
            if extra > 0:
                inner_extra[t] = extra

    delta = {}
    for b in plan.bufs:
        nb = scaled(b)
        if b.alias_donated or nb == 0:
            continue
        delta[b.birth] = delta.get(b.birth, 0) + nb
        end = (b.last + 1) if b.freeable else (n + 1)
        delta[end] = delta.get(end, 0) - nb
    live, peak, peak_t = 0, 0, None
    for t in sorted(set(delta) | set(inner_extra)):
        live += delta.get(t, 0)
        at_t = live + inner_extra.get(t, 0)
        if at_t > peak:
            peak, peak_t = at_t, t
    return peak, peak_t


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

class ShardReport:
    """Result of one analysis: the per-shard peak, the collective bill,
    the sharding-spec tree of the entry arguments, and any findings."""

    __slots__ = ("where", "mesh_axes", "peak_hbm_bytes_per_shard",
                 "peak_hbm_bytes", "peak_eqn", "comm_bytes_per_step",
                 "collectives", "spec_tree", "findings", "n_eqns")

    def __init__(self):
        self.where = None
        self.mesh_axes = {}
        self.peak_hbm_bytes_per_shard = 0
        self.peak_hbm_bytes = 0            # whole-graph (memlint parity)
        self.peak_eqn = None
        self.comm_bytes_per_step = 0
        self.collectives = []
        self.spec_tree = {}                # argpos -> [spec strings]
        self.findings = []
        self.n_eqns = 0

    def as_dict(self):
        return {
            "where": self.where,
            "mesh_axes": dict(self.mesh_axes),
            "peak_hbm_bytes_per_shard": self.peak_hbm_bytes_per_shard,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "peak_eqn": self.peak_eqn,
            "comm_bytes_per_step": self.comm_bytes_per_step,
            "collectives": list(self.collectives),
            "spec_tree": {str(k): list(v)
                          for k, v in self.spec_tree.items()},
            "n_eqns": self.n_eqns,
            "findings": [f.as_dict() for f in self.findings],
        }


def _flat_specs(in_specs, args, slices):
    """Align the caller's ``in_specs`` with the flattened invars.
    Each position may be a PartitionSpec (broadcast over the arg's
    leaves), None (untracked), or a pytree of PartitionSpecs matching
    the arg's structure."""
    from jax.sharding import PartitionSpec
    out = {}
    if in_specs is None:
        return out
    for i, spec_i in enumerate(tuple(in_specs)):
        if i >= len(slices):
            break
        leaves_v = slices[i]
        if spec_i is None:
            continue
        if isinstance(spec_i, PartitionSpec):
            leaf_specs = [spec_i] * len(leaves_v)
        else:
            leaf_specs = jax.tree_util.tree_leaves(
                spec_i, is_leaf=lambda x: x is None
                or isinstance(x, PartitionSpec))
            if len(leaf_specs) != len(leaves_v):
                raise ValueError(
                    f"in_specs[{i}] has {len(leaf_specs)} leaves but "
                    f"argument {i} has {len(leaves_v)}")
        for v, sp in zip(leaves_v, leaf_specs):
            if sp is not None:
                out[id(v)] = _norm_spec(sp, len(_shape_of(v)))
    return out


def analyze_fn(fn, *args, mesh=None, in_specs=None, where=None,
               donate_argnums=(), allow_replicated=(), config=None):
    """Trace ``fn(*args)`` and run the full sharding analysis against
    ``mesh`` (a jax Mesh or an ``{axis: size}`` dict); returns a
    :class:`ShardReport` with findings.

    ``in_specs`` declares the entry shardings, one entry per argument
    position: a ``PartitionSpec`` (applied to every leaf of that
    argument), ``None`` (untracked), or a pytree of PartitionSpecs
    matching the argument.  ``allow_replicated`` names argument
    positions legitimately kept replicated (SL-REPL001 escape, the
    memlint ``allow_undonated`` convention); ``donate_argnums`` powers
    SL-DONATE001."""
    config = config or Config()
    where = where or getattr(fn, "__name__", "fn")
    closed = jax.make_jaxpr(fn)(*args)
    jaxpr = closed.jaxpr
    axis_sizes = _mesh_axis_sizes(mesh)
    slices = _arg_slices(jaxpr, args)
    donate_argnums = tuple(donate_argnums)
    allow_replicated = tuple(allow_replicated)

    findings: list[Finding] = []
    collectives: list[dict] = []
    var2spec: dict[int, tuple] = {}

    declared = _flat_specs(in_specs, args, slices)
    for vid, spec in declared.items():
        missing = sorted({a for a in _spec_axes(spec)
                          if a not in axis_sizes})
        if missing:
            findings.append(Finding(
                "SL-SPEC001", where, "", None, None,
                f"declared sharding {_spec_str(spec)} names mesh "
                f"ax{'is' if len(missing) == 1 else 'es'} "
                f"{missing} absent from the mesh "
                f"(axes: {sorted(axis_sizes) or 'none'}) — the "
                "partitioner would reject or silently replicate this",
                severity="error"))
    var2spec.update(declared)
    for cv in jaxpr.constvars:
        var2spec[id(cv)] = _replicated(len(_shape_of(cv)))

    _walk(jaxpr, var2spec, axis_sizes, where, "", findings, collectives,
          1)

    # -- SL-REPL001: big declared-replicated entry leaves -----------------
    shardable = sorted(a for a, s in axis_sizes.items() if s > 1)
    for i, leaves in enumerate(slices):
        if i in allow_replicated or not shardable:
            continue
        for v in leaves:
            spec = declared.get(id(v))
            if spec is None or any(spec):
                continue          # untracked or already sharded somewhere
            nb = _nbytes(_aval(v))
            if nb < config.repl_bytes:
                continue
            shape = _shape_of(v)
            cands = sorted(a for a in shardable
                           if any(d % axis_sizes[a] == 0 and d > 1
                                  for d in shape))
            if not cands:
                continue
            findings.append(Finding(
                "SL-REPL001", where, "", None, None,
                f"argument {i} leaf {shape} ({nb} bytes) is declared "
                f"fully replicated but mesh ax{'is' if len(cands) == 1 else 'es'} "
                f"{cands} divide(s) it — every chip holds a full copy; "
                "shard it (or list the position in allow_replicated)",
                severity="error"))

    # -- memlint plan + per-shard sweep -----------------------------------
    donated_ids = {id(v) for i in donate_argnums
                   if 0 <= i < len(slices) for v in slices[i]}
    plan = _mem_plan(jaxpr, tuple(closed.consts), donated_ids)
    peak_shard, peak_t = _sharded_peak(jaxpr, plan, var2spec, axis_sizes)

    # -- SL-DONATE001: donated leaf vs its signature-matched output -------
    out_by_sig: dict[tuple, list] = {}
    seen_out = set()
    for ov in jaxpr.outvars:
        if _is_var(ov) and id(ov) not in seen_out:
            seen_out.add(id(ov))
            out_by_sig.setdefault(_sig(_aval(ov)), []).append(ov)
    for i in donate_argnums:
        if not (0 <= i < len(slices)):
            continue
        for v in slices[i]:
            cands = out_by_sig.get(_sig(_aval(v)))
            if not cands:
                continue
            ov = cands.pop()
            in_spec = var2spec.get(id(v))
            out_spec = var2spec.get(id(ov))
            nb = _nbytes(_aval(v))
            if in_spec is not None and out_spec is not None \
                    and in_spec != out_spec and nb >= _RESHARD_MIN_BYTES:
                findings.append(Finding(
                    "SL-DONATE001", where, "", None, None,
                    f"donated argument {i} leaf {_shape_of(v)} is "
                    f"{_spec_str(in_spec)} but its matched output is "
                    f"{_spec_str(out_spec)} — XLA cannot alias buffers "
                    "with different layouts, so the donation is "
                    "silently dropped and both copies stay live; "
                    "align the output sharding with the donated input",
                    severity="error"))

    # -- SL-SHARD-PEAK001 --------------------------------------------------
    if config.chip_bytes and peak_shard > config.chip_bytes:
        findings.append(Finding(
            "SL-SHARD-PEAK001", where, "", None, None,
            f"per-shard peak-HBM estimate {peak_shard} bytes exceeds "
            f"the per-chip budget "
            f"MXNET_SHARDLINT_CHIP_BYTES={config.chip_bytes} on mesh "
            f"{dict(axis_sizes)} — shard more of the dominant buffers "
            "or grow the mesh", severity="error"))

    rep = ShardReport()
    rep.where = where
    rep.mesh_axes = dict(axis_sizes)
    rep.n_eqns = plan.n_eqns
    rep.peak_hbm_bytes_per_shard = int(peak_shard)
    rep.peak_hbm_bytes = int(plan.peak)
    if peak_t is not None and 0 <= peak_t < len(jaxpr.eqns):
        eqn = jaxpr.eqns[peak_t]
        rep.peak_eqn = {"index": int(peak_t),
                        "primitive": eqn.primitive.name,
                        "source": _source_of(eqn)}
    elif peak_t is not None:
        rep.peak_eqn = {"index": int(peak_t), "primitive": "entry",
                        "source": None}
    rep.collectives = collectives
    rep.comm_bytes_per_step = int(sum(c["comm_bytes"]
                                      for c in collectives))
    for i, leaves in enumerate(slices):
        rep.spec_tree[i] = [_spec_str(declared.get(id(v)))
                            for v in leaves]

    kept, seen = [], set()
    for f in findings:
        if f.rule in config.ignore or f.key in seen:
            continue
        seen.add(f.key)
        kept.append(f)
    kept.sort(key=lambda f: (f.rule, f.path, f.message))
    rep.findings = kept
    return rep


# ---------------------------------------------------------------------------
# the executable-build choke point (MXNET_GRAPH_SHARDLINT)
# ---------------------------------------------------------------------------

_shard_mode: "str | None | bool" = False   # False = read env at first use


def _env_shard_mode():
    raw = str(get_env("MXNET_GRAPH_SHARDLINT", "0")).strip().lower()
    if raw in ("", "0", "off", "false", "none"):
        return None
    if raw in ("2", "strict", "raise"):
        return "strict"
    return "warn"


def shard_mode() -> "str | None":
    """``None`` (off, default), ``"warn"`` or ``"strict"`` — read once
    from ``MXNET_GRAPH_SHARDLINT``; runtime toggles via
    :func:`set_shard_mode`."""
    global _shard_mode
    if _shard_mode is False:
        _shard_mode = _env_shard_mode()
        if _shard_mode is not None:
            _ensure_provider()
    return _shard_mode


def set_shard_mode(mode):
    """Set the build-time sharding-lint mode (``None``/``"warn"``/
    ``"strict"``); returns the previous mode."""
    global _shard_mode
    if mode not in (None, "warn", "strict"):
        raise ValueError(f"shardlint mode must be None/'warn'/'strict', "
                         f"got {mode!r}")
    prev = shard_mode()
    _shard_mode = mode
    if mode is not None:
        _ensure_provider()
    return prev


class shard_scope:
    """``with shard_scope("strict"): ...`` — tests/CI."""

    def __init__(self, mode):
        self._mode = mode
        self._prev = None

    def __enter__(self):
        self._prev = set_shard_mode(self._mode)
        return self

    def __exit__(self, *exc):
        set_shard_mode(self._prev)
        return False


def check_sharding(fn, args, name=None, mesh=None, in_specs=None,
                   donate_argnums=(), allow_replicated=(), config=None):
    """Run the sharding analysis over ``fn(*args)`` at executable-build
    time.  Inert (one cached env read) unless ``MXNET_GRAPH_SHARDLINT``
    is on: ``warn`` warns per finding; ``strict`` raises
    :class:`~..error.ShardLintError` on error-severity findings.  The
    analysis itself is best-effort — a crash warns and never breaks
    the build.  Records per-site stats for the ``shardlint`` profiler
    provider on every run.  Returns the report (or None when off)."""
    mode = shard_mode()
    if mode is None:
        return None
    name = name or getattr(fn, "__name__", "traced")
    try:
        rep = analyze_fn(fn, *args, mesh=mesh, in_specs=in_specs,
                         where=name, donate_argnums=donate_argnums,
                         allow_replicated=allow_replicated,
                         config=config)
    except Exception as e:  # mxlint: allow-broad-except(the analysis is best-effort at build time; a shardlint crash must never break the executable build)
        _warnings.warn(f"shardlint could not analyze {name!r} ({e})")
        return None
    _record_site(name, rep)
    for f in rep.findings:
        _warnings.warn(f"shardlint: {f!r}")
    errors = [f for f in rep.findings if f.severity == "error"]
    if mode == "strict" and errors:
        from ..error import ShardLintError
        raise ShardLintError(
            f"shardlint: {len(errors)} finding(s) in {name!r}:\n"
            + render(errors))
    return rep


# ---------------------------------------------------------------------------
# the parallel-stack sweep (CLI --check, CI, and the zero-finding pins)
# ---------------------------------------------------------------------------

def sweep_parallel(config=None):
    """Analyze every surface of the ``parallel/`` stack (plus the
    kvstore compressed all-reduce) on the 8-device dryrun mesh; returns
    ``[(name, ShardReport)]``.  The contract — pinned per-module by
    tests/test_shardlint.py and gated by ``tools/shardlint.py --check``
    — is ZERO error findings."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import make_mesh, mesh_rules
    from ..parallel.pipeline import pipeline_forward
    from ..parallel.ulysses import ulysses_attention
    from ..parallel.ring_attention import ring_attention
    from ..parallel.moe import moe_forward, init_moe_params, MoELayer
    from ..kvstore.gradient_compression import make_compressed_allreduce

    config = config or Config()
    out = []
    key = jax.random.PRNGKey(0)

    # -- mesh.py: the canonical rule table on a dp/tp mesh ----------------
    mesh = make_mesh(dp=4, tp=2)
    emb = jax.random.normal(key, (64, 32), jnp.float32)
    tok = jax.random.normal(key, (8, 16, 32), jnp.float32)

    def embed_matmul(w, x):
        return jnp.einsum("btd,vd->btv", x, w)

    out.append(("parallel.mesh", analyze_fn(
        embed_matmul, emb, tok, mesh=mesh,
        in_specs=(mesh_rules("embed"), mesh_rules("activation")),
        where="parallel.mesh", config=config)))

    # -- pipeline ----------------------------------------------------------
    npp, d, B, n_micro = 8, 8, 16, 4
    mesh = make_mesh(pp=npp)
    pp_params = {"w": jax.random.normal(key, (npp, d, d), jnp.float32),
                 "b": jax.random.normal(key, (npp, d), jnp.float32)}
    x = jax.random.normal(key, (B, d), jnp.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def pipe(params, xin):
        return pipeline_forward(params, xin, stage_fn, mesh,
                                n_micro=n_micro)

    out.append(("parallel.pipeline", analyze_fn(
        pipe, pp_params, x, mesh=mesh,
        in_specs=({"w": P("pp", None, None), "b": P("pp", None)}, None),
        where="parallel.pipeline", config=config)))

    # -- ulysses -----------------------------------------------------------
    mesh = make_mesh(dp=2, sp=4)
    q = jax.random.normal(key, (2, 4, 16, 8), jnp.float32)
    qkv_spec = P("dp", None, "sp", None)

    def ulysses(qq, kk, vv):
        return ulysses_attention(qq, kk, vv, mesh, axis_name="sp",
                                 causal=True)

    out.append(("parallel.ulysses", analyze_fn(
        ulysses, q, q, q, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        where="parallel.ulysses", config=config)))

    # -- ring_attention ----------------------------------------------------
    def ring(qq, kk, vv):
        return ring_attention(qq, kk, vv, mesh, axis_name="sp",
                              causal=True)

    out.append(("parallel.ring_attention", analyze_fn(
        ring, q, q, q, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        where="parallel.ring_attention", config=config)))

    # -- moe ---------------------------------------------------------------
    mesh = make_mesh(ep=4, dp=2)
    moe_params = init_moe_params(key, 16, 32, 4)
    xm = jax.random.normal(key, (4, 8, 16), jnp.float32)
    specs = MoELayer(16, 32, 4).partition_specs()
    out.append(("parallel.moe", analyze_fn(
        moe_forward, moe_params, xm, mesh=mesh,
        in_specs=({k: specs[k] for k in moe_params},
                  P("dp", None, None)),
        where="parallel.moe", config=config)))

    # -- kvstore.gradient_compression -------------------------------------
    mesh = make_mesh(dp=8)
    allreduce = make_compressed_allreduce(mesh)
    g = jax.random.normal(key, (64, 8), jnp.float32)
    resid = jnp.zeros_like(g)
    out.append(("kvstore.gradient_compression", analyze_fn(
        allreduce, g, resid, mesh=mesh, in_specs=(P("dp"), P("dp")),
        where="kvstore.gradient_compression", config=config)))

    for name, rep in out:
        _record_site(name, rep)
    return out


# ---------------------------------------------------------------------------
# per-site stats (profiler provider)
# ---------------------------------------------------------------------------

_stats_lock = threading.Lock()
_sites: dict[str, dict] = {}
_provider_registered = False


def _ensure_provider():
    global _provider_registered
    if _provider_registered:
        return
    _provider_registered = True
    from .. import profiler
    profiler.register_stats_provider("shardlint", stats)


def _record_site(name, rep):
    with _stats_lock:
        st = _sites.setdefault(name, {"analyses": 0})
        st["analyses"] += 1
        st["peak_hbm_bytes_per_shard"] = rep.peak_hbm_bytes_per_shard
        st["peak_hbm_bytes"] = rep.peak_hbm_bytes
        st["comm_bytes_per_step"] = rep.comm_bytes_per_step
        st["collectives"] = len(rep.collectives)
        st["findings"] = len(rep.findings)
    _ensure_provider()


def stats():
    """Counters for the profiler's ``shardlint`` stats provider."""
    with _stats_lock:
        per_site = {k: dict(v) for k, v in _sites.items()}
    return {
        "sites": len(per_site),
        "peak_hbm_bytes_per_shard_max": max(
            (s.get("peak_hbm_bytes_per_shard", 0)
             for s in per_site.values()), default=0),
        "comm_bytes_per_step_total": sum(
            s.get("comm_bytes_per_step", 0) for s in per_site.values()),
        "findings": sum(s.get("findings", 0) for s in per_site.values()),
        "per_site": per_site,
    }


def reset_stats():
    """Drop all per-site state (tests)."""
    with _stats_lock:
        _sites.clear()
