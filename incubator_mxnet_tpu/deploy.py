"""Deploy/predict surface (reference include/mxnet/c_predict_api.h +
src/c_api/c_predict_api.cc).

Export side: ``export_model`` compiles a Block (or jittable fn) forward
to StableHLO and writes three artifacts:

* ``{prefix}.stablehlo.mlir``  — human-inspectable StableHLO text of the
  compiled forward (the TPU-era analog of ``prefix-symbol.json``)
* ``{prefix}.jaxport``         — jax.export serialized executable
  (StableHLO + calling convention), reloadable without any model code
* ``{prefix}.params``          — weights in the reference TLV format
* ``{prefix}.meta.json``       — input names/shapes/dtypes

Predict side: ``load_predictor`` rebuilds a callable from the artifacts
alone — no Python model code, mirroring the reference's predict-only API
that loads symbol+params without the training stack.  The C ABI in
src/predict.cc drives exactly this loader through an embedded
interpreter, the same layering as the reference where c_predict_api.cc
is a thin C shim over the full libmxnet runtime.

Serving side: ``export_model`` additionally attempts a **batch-
polymorphic** export (``{prefix}.batch.jaxport``, symbolic leading
dim), so a loaded :class:`Predictor` accepts any batch size — the
substrate the dynamic batcher (serving/batcher.py) pads its buckets
against.  On TPU every distinct input shape is a fresh XLA compile, so
the predictor also exposes :meth:`Predictor.warmup` (pre-compile a set
of bucket sizes) and :attr:`Predictor.compile_count` (executable-cache
probe: must flatline once traffic only replays warmed shapes).
"""
from __future__ import annotations

import json
import os

import numpy as onp

import jax
import jax.export  # noqa: F401  (jax.export is a lazily-bound submodule)
import jax.numpy as jnp

from . import executor_cache as _xc

__all__ = ["export_model", "load_predictor"]


def _tuples_to_lists(tree):
    if isinstance(tree, tuple):
        return [_tuples_to_lists(t) for t in tree]
    if isinstance(tree, list):
        return [_tuples_to_lists(t) for t in tree]
    if isinstance(tree, dict):
        return {k: _tuples_to_lists(v) for k, v in tree.items()}
    return tree


def _block_forward_fn(block):
    params, apply_fn = block.functional()

    def fwd(params, *inputs):
        # keep multi-output forwards intact: the predictor exposes
        # indexed outputs (MXTPredGetOutput), so no truncation here
        return apply_fn(params, *inputs, training=False)

    return params, fwd


def export_model(model, example_inputs, prefix, params=None,
                 donate_argnums=(), aot_buckets=None,
                 sharding_rule=None, sharding_mesh=None):
    """Compile + serialize a model's forward for deployment.

    model: a gluon Block (uses ``functional()``) or a pure
    ``fn(params, *inputs)``; example_inputs: tuple of arrays fixing the
    traced shapes (static-shape contract, like the reference predictor's
    input-shape binding at MXPredCreate time).

    ``donate_argnums`` positions refer to the compiled signature
    ``fwd(params, *inputs)``: position 0 is the params pytree (never
    donatable — the predictor reuses it across calls), positions 1..n
    are the user inputs.  Donated positions are recorded in
    ``meta.json`` and re-applied by the loaded :class:`Predictor`, so
    serving executions let XLA reuse the request's input buffers for
    outputs — callers hand over the donated arrays (the batcher builds
    each padded batch fresh, so the serving path is donation-safe by
    construction).

    ``aot_buckets`` (or ``MXNET_EXPORT_AOT_BUCKETS``) additionally
    serializes one *compiled* executable per batch-bucket size next to
    the artifact (``{prefix}.aot.b{n}``), so a loading process executes
    instead of compiling — the cold-start killer for serving replicas.
    The blobs are jax/jaxlib/platform-exact (a loud versioned compat
    check falls back to recompilation on mismatch).

    ``sharding_rule`` (with ``sharding_mesh``) declares how the params
    are laid out on a mesh: either ``rule_fn(name, leaf) ->
    PartitionSpec`` (the :func:`~.parallel.mesh.shard_params`
    convention) or a pytree of PartitionSpecs matching ``params``.
    When given, the sharding analysis (``analysis/shardlint.py``) runs
    over the exported forward and meta.json gains a ``"shardlint"``
    entry: the sharding-spec tree, the per-shard HBM plan
    (``peak_hbm_bytes_per_shard``), the collective bill and any
    findings — which ``serving/placement.py`` reads as the per-shard
    footprint when placing the artifact on a mesh-sharded replica.
    """
    from .ndarray import NDArray, save as nd_save

    if hasattr(model, "functional"):
        params, fwd = _block_forward_fn(model)
    else:
        fwd = model
        if params is None:
            raise ValueError("pure-function export needs params=")
    donate_argnums = tuple(sorted(set(int(i) for i in donate_argnums)))
    if any(i == 0 for i in donate_argnums):
        raise ValueError(
            "donate_argnums position 0 is the params pytree — the "
            "predictor holds it across calls; only input positions "
            "(1..n) are donatable")
    if any(not 0 < i <= len(example_inputs) for i in donate_argnums):
        raise ValueError(
            f"donate_argnums {donate_argnums} out of range for "
            f"{len(example_inputs)} example input(s)")
    # normalize containers so the traced pytree matches what
    # _unflatten_keystr reconstructs at load time (tuples → lists;
    # keystr cannot distinguish them)
    params = _tuples_to_lists(params)

    example = tuple(
        x.data if isinstance(x, NDArray) else jnp.asarray(x)
        for x in example_inputs)
    # everything the export lowers and compiles is for the accelerator
    # (the default backend's first device): a Block's parameters and
    # NDArray examples live on the host CPU, and committed arguments
    # would otherwise make the shipped executables CPU programs
    params, example = jax.device_put((params, example), jax.devices()[0])

    # through the unified choke point: the export trace is a compile
    # surface like any other (sentinel site export:<name>, persistent
    # compile cache enabled at Executor construction)
    jitted = _xc.Executor(
        fwd, f"export:{os.path.basename(prefix)}",
        donate_argnums=donate_argnums).jfn
    lowered = jitted.lower(params, *example)
    with open(prefix + ".stablehlo.mlir", "w") as f:
        f.write(lowered.as_text())

    # IR lint of the forward being shipped (docs/graph_analysis.md): a
    # baked-in constant, f64 leak or host callback found NOW is one
    # found before it serves traffic.  MXNET_EXPORT_GRAPHLINT=warn
    # (default) | raise | 0.
    graphlint_summary = _export_graphlint(fwd, params, example, prefix)
    # memory plan of the same forward (analysis/memlint.py): peak-HBM
    # estimate, donated-bytes-reclaimed and the dominant buffer
    # lifetimes ride along in meta.json so the serving layer can report
    # per-model HBM without re-tracing the (opaque) deserialized graph
    memlint_summary = _export_memlint(fwd, params, example,
                                      donate_argnums, prefix)
    # sharding plan of the same forward (analysis/shardlint.py): the
    # declared spec tree, the per-shard peak and the collective bill
    # ride along so a mesh-sharded serving tier charges each replica
    # its SHARD, not the whole graph
    shardlint_summary = _export_shardlint(fwd, params, example,
                                          donate_argnums, prefix,
                                          sharding_rule, sharding_mesh)

    exported = jax.export.export(jitted)(params, *example)
    with open(prefix + ".jaxport", "wb") as f:
        f.write(exported.serialize())

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    names, wire = [], {}
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        names.append(name)
        wire[name] = NDArray(leaf)
    nd_save(prefix + ".params", wire)

    meta = {
        "format": "mxtpu_predict_v1",
        "param_names": names,
        "inputs": [{"shape": list(x.shape), "dtype": jnp.dtype(x.dtype).name}
                   for x in example],
        "outputs": [{"shape": list(s.shape), "dtype": jnp.dtype(s.dtype).name}
                    for s in jax.tree_util.tree_leaves(
                        jax.eval_shape(fwd, params, *example))],
    }
    meta["batch_export"] = _write_batch_export(jitted, params, example,
                                               prefix)
    meta["donate_argnums"] = list(donate_argnums)
    aot = _write_aot_buckets(jitted, params, example, prefix, aot_buckets)
    if aot is not None:
        meta["aot"] = aot
    if graphlint_summary is not None:
        meta["graphlint"] = graphlint_summary
    if memlint_summary is not None:
        meta["memlint"] = memlint_summary
    if shardlint_summary is not None:
        meta["shardlint"] = shardlint_summary
    with open(prefix + ".meta.json", "w") as f:
        json.dump(meta, f, indent=1)
    _write_pjrt_sidecar(prefix, params, meta)
    return meta


def _export_graphlint(fwd, params, example, prefix):
    """Lint the traced forward at export time (jaxpr passes,
    ``analysis/graphlint.py``); returns the meta.json summary or None
    when disabled.  ``warn`` mode (default) warns and records; ``raise``
    fails the export with :class:`~.error.GraphLintError`."""
    from .base import get_env
    mode = str(get_env("MXNET_EXPORT_GRAPHLINT", "warn")).strip().lower()
    if mode in ("", "0", "off", "none", "false"):
        return None
    from .analysis import graphlint
    try:
        findings = graphlint.lint_fn(
            fwd, params, *example,
            where=f"export:{os.path.basename(prefix)}")
    except Exception as e:  # mxlint: allow-broad-except(the lint is advisory in warn mode; a lint crash must never block an export)
        import warnings
        if mode == "raise":
            raise
        warnings.warn(f"export graphlint could not run ({e}); exporting "
                      "without IR analysis")
        return {"error": f"{type(e).__name__}: {e}"}
    # advisories never gate (same contract as check_traced and the
    # CLI): "findings"/"by_rule" count error severity only, so
    # raise-mode and the serving load-time warning fire only on real
    # violations and the counts agree with the breakdown
    errors = [f for f in findings if f.severity == "error"]
    by_rule: dict[str, int] = {}
    adv_by_rule: dict[str, int] = {}
    for f in findings:
        tgt = by_rule if f.severity == "error" else adv_by_rule
        tgt[f.rule] = tgt.get(f.rule, 0) + 1
    summary = {"findings": len(errors),
               "advisories": len(findings) - len(errors),
               "by_rule": by_rule,
               "advisories_by_rule": adv_by_rule,
               "details": [f.as_dict() for f in findings[:25]]}
    if errors:
        msg = (f"graphlint: {len(errors)} finding(s) in the exported "
               f"forward of {prefix!r}:\n"
               + graphlint.render(errors[:10]))
        if mode == "raise":
            from .error import GraphLintError
            raise GraphLintError(msg)
        import warnings
        warnings.warn(msg)
    return summary


def _export_memlint(fwd, params, example, donate_argnums, prefix):
    """Static memory plan of the exported forward (liveness-based
    peak-HBM estimate + donation accounting, ``analysis/memlint.py``);
    returns the meta.json summary or None when export analysis is
    disabled (same ``MXNET_EXPORT_GRAPHLINT`` gate — it is the
    export-time IR-analysis switch)."""
    from .base import get_env
    mode = str(get_env("MXNET_EXPORT_GRAPHLINT", "warn")).strip().lower()
    if mode in ("", "0", "off", "none", "false"):
        return None
    from .analysis import memlint
    try:
        rep = memlint.analyze_fn(
            fwd, params, *example,
            where=f"export:{os.path.basename(prefix)}",
            donate_argnums=donate_argnums,
            allow_undonated=(0,))   # params are held across calls
    except Exception as e:  # mxlint: allow-broad-except(the memory plan is advisory at export; a memlint crash must never block an export)
        import warnings
        warnings.warn(f"export memlint could not run ({e}); exporting "
                      "without a memory summary")
        return {"error": f"{type(e).__name__}: {e}"}
    d = rep.as_dict()
    d["buffers"] = d["buffers"][:5]
    d["findings"] = [f.as_dict() for f in rep.findings]
    return d


def _export_shardlint(fwd, params, example, donate_argnums, prefix,
                      sharding_rule, sharding_mesh):
    """Sharding analysis of the exported forward
    (``analysis/shardlint.py``); returns the meta.json summary or None
    when no sharding was declared / export analysis is disabled (same
    ``MXNET_EXPORT_GRAPHLINT`` gate as its siblings)."""
    if sharding_rule is None or sharding_mesh is None:
        return None
    from .base import get_env
    mode = str(get_env("MXNET_EXPORT_GRAPHLINT", "warn")).strip().lower()
    if mode in ("", "0", "off", "none", "false"):
        return None
    from .analysis import shardlint
    try:
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        if callable(sharding_rule):
            leaf_specs = [sharding_rule(jax.tree_util.keystr(p), leaf)
                          for p, leaf in flat]
            spec_tree = jax.tree_util.tree_unflatten(treedef, leaf_specs)
        else:
            spec_tree = sharding_rule
            leaf_specs = jax.tree_util.tree_leaves(
                spec_tree, is_leaf=lambda x: x is None or isinstance(
                    x, jax.sharding.PartitionSpec))
        rep = shardlint.analyze_fn(
            fwd, params, *example, mesh=sharding_mesh,
            in_specs=(spec_tree,) + (None,) * len(example),
            where=f"export:{os.path.basename(prefix)}",
            donate_argnums=donate_argnums)
    except Exception as e:  # mxlint: allow-broad-except(the sharding plan is advisory at export; a shardlint crash must never block an export)
        import warnings
        warnings.warn(f"export shardlint could not run ({e}); exporting "
                      "without a sharding summary")
        return {"error": f"{type(e).__name__}: {e}"}
    d = rep.as_dict()
    d["collectives"] = d["collectives"][:10]
    d["sharding_spec_tree"] = {
        jax.tree_util.keystr(p): str(s if s is not None else "P()")
        for (p, _), s in zip(flat, leaf_specs)}
    return d


def _write_batch_export(jitted, params, example, prefix):
    """Shape-polymorphic twin of the static export: the leading axis of
    every input becomes one shared symbolic dim ``b``, so the serving
    batcher can execute any padding-bucket size from the same artifact
    (each concrete size still compiles once — see Predictor.warmup).
    Models that constrain the batch dim (e.g. a reshape folding it into
    a static size) can't be exported this way; the predictor then falls
    back to chunked static-batch execution."""
    path = prefix + ".batch.jaxport"
    try:
        if not all(x.ndim >= 1 for x in example):
            raise ValueError("all inputs need a leading batch axis")
        b, = jax.export.symbolic_shape("b")
        specs = [jax.ShapeDtypeStruct((b,) + tuple(x.shape[1:]), x.dtype)
                 for x in example]
        exported = jax.export.export(jitted)(params, *specs)
        blob = exported.serialize()   # serialize before open(): a failed
        with open(path, "wb") as f:   # export must not truncate the file
            f.write(blob)
        return True
    except Exception as e:  # mxlint: allow-broad-except(polymorphic export is an optional artifact; failure degrades to per-shape compilation with a warning)
        import warnings
        if os.path.exists(path):
            os.remove(path)  # no stale polymorphic artifact
        warnings.warn(
            f"batch-polymorphic export unavailable ({e}); the predictor "
            "will serve non-exported batch sizes by chunking to the "
            "traced batch size")
        return False


def _parse_aot_buckets(aot_buckets):
    """Resolve the bucket list: explicit arg wins, else the
    ``MXNET_EXPORT_AOT_BUCKETS`` env (``default``/``true`` = the
    serving batcher's padding buckets, a comma list = exactly those
    sizes — ``1`` means the single bucket [1], it is a valid size and
    must not be hijacked as a boolean — empty/``0``/``off`` = off)."""
    from .base import get_env
    if aot_buckets is None:
        raw = str(get_env("MXNET_EXPORT_AOT_BUCKETS", "")).strip().lower()
        if raw in ("", "0", "off", "none", "false"):
            return None
        if raw in ("default", "true"):
            from .serving.batcher import parse_buckets
            aot_buckets = parse_buckets()
        else:
            aot_buckets = [int(t) for t in raw.split(",") if t.strip()]
    buckets = sorted({int(b) for b in aot_buckets})
    if any(b < 1 for b in buckets):
        raise ValueError(f"AOT bucket sizes must be >= 1, got {buckets}")
    return buckets or None


def _write_aot_buckets(jitted, params, example, prefix, aot_buckets):
    """AOT layer of the artifact: one *compiled* executable per batch
    bucket, serialized with a versioned compat envelope
    (``executor_cache.serialize_executable``) as ``{prefix}.aot.b{n}``.
    ``ModelRepository.load`` + warmup then deserialize instead of
    compiling — XLA never runs in the serving replica.  Executables are
    jax/jaxlib/platform-exact; the loader's compat check falls back to
    recompilation (loudly) rather than crash on a foreign blob.
    AOT buckets that were asked for and cannot be built fail the
    export.  Returns the meta.json ``"aot"`` entry or None when off."""
    buckets = _parse_aot_buckets(aot_buckets)
    if buckets is None:
        return None
    if not all(x.ndim >= 1 for x in example):
        raise ValueError(
            "AOT buckets need a leading batch axis on every input")
    written = []
    files = {}
    try:
        for n in buckets:
            specs = [jax.ShapeDtypeStruct((n,) + tuple(x.shape[1:]),
                                          x.dtype) for x in example]
            # compiled with the persistent cache out of the way: an
            # executable *served from* the cache re-serializes without
            # its kernels' object code (XLA:CPU: "Function dot_kernel
            # not found" when the loaded blob first runs), and with the
            # cache on by default that would be every second export
            with _xc.compile_cache_bypassed():
                compiled = jitted.lower(params, *specs).compile()
            blob = _xc.serialize_executable(compiled)
            # round-trip self-check BEFORE shipping: a blob that does
            # not load and run in the exporting environment can never
            # serve anywhere, and must fail the export here, not a
            # serving replica later.  record=False: validation, not
            # cold-start cache traffic
            loaded = _xc.deserialize_executable(blob, record=False)
            jax.block_until_ready(loaded(params, *(
                jnp.zeros(s.shape, s.dtype) for s in specs)))
            path = f"{prefix}.aot.b{n}"
            with open(path, "wb") as f:
                f.write(blob)
            written.append(path)
            files[str(n)] = os.path.basename(path)
    except BaseException:
        for path in written:   # no partial bucket set: all-or-nothing
            if os.path.exists(path):
                os.remove(path)
        raise
    return {"buckets": buckets, "files": files,
            "compat": _xc.aot_compat()}


def _write_pjrt_sidecar(prefix, params, meta):
    """Artifacts for the PURE-C++ PJRT predictor (src/pjrt_predict.cc):
    no Python at serving time, so everything the C runtime needs is
    spelled out flat —
    * ``{prefix}.pjrt.json``: the mlir main's argument list in calling
      order (param leaves in tree-flatten order, then user inputs) with
      dtype/shape, and byte offsets into
    * ``{prefix}.pjrt_params.bin``: concatenated little-endian raw
      param bytes, and
    * ``{prefix}.compile_options.pb``: a serialized CompileOptionsProto
      for PJRT_Client_Compile (generated here because C has no proto
      library).
    """
    import numpy as onp
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    args, offset = [], 0
    with open(prefix + ".pjrt_params.bin", "wb") as f:
        for path, leaf in flat:
            arr = onp.asarray(leaf)
            raw = arr.tobytes()
            args.append({"kind": "param",
                         "name": jax.tree_util.keystr(path),
                         "dtype": jnp.dtype(arr.dtype).name,
                         "shape": list(arr.shape),
                         "offset": offset, "nbytes": len(raw)})
            f.write(raw)
            offset += len(raw)
    for spec in meta["inputs"]:
        args.append({"kind": "input", "dtype": spec["dtype"],
                     "shape": spec["shape"]})
    with open(prefix + ".pjrt.json", "w") as f:
        json.dump({"format": "mxtpu_pjrt_v1", "args": args,
                   "outputs": meta["outputs"]}, f, indent=1)
    # line-oriented twin of pjrt.json for the C runtime (no JSON parser
    # in C): "arg {param|input} dtype offset nbytes ndim d0 d1 ..." /
    # "out dtype ndim d0 d1 ..."
    with open(prefix + ".pjrt.txt", "w") as f:
        for a in args:
            dims = " ".join(str(d) for d in a["shape"])
            off = a.get("offset", -1)
            nb = a.get("nbytes", -1)
            f.write(f"arg {a['kind']} {a['dtype']} {off} {nb} "
                    f"{len(a['shape'])} {dims}".rstrip() + "\n")
        for o in meta["outputs"]:
            dims = " ".join(str(d) for d in o["shape"])
            f.write(f"out {o['dtype']} {len(o['shape'])} {dims}".rstrip()
                    + "\n")
    try:
        from jaxlib import xla_client
        blob = xla_client.CompileOptions().SerializeAsString()  # before open():
        # a failed serialization must not leave a truncated file behind
    except Exception as e:  # mxlint: allow-broad-except(compile-options blob is an optional artifact; failure warns and the PJRT-direct path recompiles)
        import warnings
        if os.path.exists(prefix + ".compile_options.pb"):
            os.remove(prefix + ".compile_options.pb")  # no stale lies
        warnings.warn(
            f"could not serialize CompileOptions ({e}); the PJRT-direct "
            "C predictor will refuse this artifact (python Predictor "
            "unaffected)")
        return
    with open(prefix + ".compile_options.pb", "wb") as f:
        f.write(blob)


class Predictor:
    """Loaded deploy artifact: ``pred(inputs) -> outputs`` (numpy).

    Mirrors MXPredCreate/SetInput/Forward/GetOutput
    (reference c_predict_api.h) as a single callable; the C ABI wraps
    this object 1:1.
    """

    def __init__(self, prefix):
        with open(prefix + ".meta.json") as f:
            self.meta = json.load(f)
        if self.meta.get("format") != "mxtpu_predict_v1":
            raise ValueError(f"{prefix}: not a mxtpu predict artifact")
        with open(prefix + ".jaxport", "rb") as f:
            self._exported = jax.export.deserialize(f.read())
        from .ndarray import load as nd_load
        loaded = nd_load(prefix + ".params")
        # rebuild the params pytree from flattened keystr names, and
        # commit it to the accelerator: nd_load lands arrays on the
        # host CPU (the default context), and committed params decide
        # where every call runs — left there, a TPU host would serve
        # from its CPU without a word
        self.device = jax.devices()[0]
        self._params = jax.device_put(
            _unflatten_keystr({k: v.data for k, v in loaded.items()}),
            self.device)
        # both entry points go through the unified choke point
        # (executor_cache.Executor): jit's executable cache keyed on
        # concrete input shapes is (a) the warm-path dispatch and (b)
        # the compile counter the serving metrics watch
        tag = os.path.basename(prefix)
        # donation does not survive serialization: jax.export records
        # the aliasing in the module, but the re-jitted call needs its
        # own donate_argnums for the caller-side buffers to be freed —
        # re-apply the positions export_model recorded in meta.json
        # (position 0 = params, held across calls, never donated)
        self._donate = tuple(self.meta.get("donate_argnums") or ())
        self._call_ex = _xc.Executor(
            self._exported.call, f"predictor:{tag}",
            donate_argnums=self._donate)
        self._call = self._call_ex.jfn
        self._batch_call_ex = None
        self._batch_call = None
        bpath = prefix + ".batch.jaxport"
        if self.meta.get("batch_export", os.path.exists(bpath)):
            try:
                with open(bpath, "rb") as f:
                    self._batch_exported = jax.export.deserialize(f.read())
                self._batch_call_ex = _xc.Executor(
                    self._batch_exported.call, f"predictor:{tag}:batch",
                    donate_argnums=self._donate)
                self._batch_call = self._batch_call_ex.jfn
            except (OSError, ValueError) as e:
                # an artifact set copied without the polymorphic twin
                # (older tooling, partial copy) must still serve — the
                # static export fully supports the chunk/pad fallback
                import warnings
                warnings.warn(
                    f"batch-polymorphic artifact {bpath} unusable "
                    f"({e}); serving non-exported batch sizes by "
                    "chunking to the traced batch size")
        self._static_shapes = [tuple(s["shape"])
                               for s in self.meta["inputs"]]
        self._static_dtypes = [s["dtype"] for s in self.meta["inputs"]]
        # AOT layer: per-bucket *compiled* executables shipped in the
        # artifact — executing one is pure deserialization + run, no
        # XLA, so a replica that serves only AOT-covered buckets keeps
        # compile_count at ZERO from process start.  A mismatched or
        # corrupted blob is refused by the versioned compat check and
        # that bucket falls back to the traced path (recompile), loudly.
        self._aot: dict = {}
        self.aot_load_failures = 0
        for n in (self.meta.get("aot") or {}).get("buckets") or ():
            # blob paths derive from THIS prefix (like .jaxport/.params),
            # so a renamed/copied artifact set loads its own blobs — the
            # manifest's "files" entry is informational
            path = f"{prefix}.aot.b{int(n)}"
            try:
                with open(path, "rb") as f:
                    blob = f.read()
                self._aot[int(n)] = _xc.deserialize_executable(blob)
            except (OSError, _xc.AOTCompatError) as e:
                self.aot_load_failures += 1
                import warnings
                warnings.warn(
                    f"AOT executable for bucket {n} of {prefix} "
                    f"unusable ({e}); this bucket recompiles at warmup")

    def __call__(self, *inputs):
        arrs = tuple(jnp.asarray(x) for x in inputs)
        n = self._aot_batch(arrs) if self._aot else None
        if n is not None:
            out = self._aot[n](self._params, *arrs)
        elif [tuple(a.shape) for a in arrs] == self._static_shapes:
            out = self._call(self._params, *arrs)
        else:
            out = self._flex_call(arrs)
        return jax.tree_util.tree_map(onp.asarray, out)

    # -- batched serving surface -------------------------------------

    def _aot_batch(self, arrs):
        """The batch size when ``arrs`` exactly matches the exported
        signature at an AOT-covered bucket (shared leading dim, same
        trailing shape and dtype); else None."""
        if len(arrs) != len(self._static_shapes):
            return None
        n = None
        for a, ref, dt in zip(arrs, self._static_shapes,
                              self._static_dtypes):
            if (a.ndim != len(ref) or tuple(a.shape[1:]) != tuple(ref[1:])
                    or jnp.dtype(a.dtype) != jnp.dtype(dt)):
                return None
            if n is None:
                n = int(a.shape[0])
            elif int(a.shape[0]) != n:
                return None
        return n if n in self._aot else None

    def _flex_call(self, arrs):
        """Execute at a batch size other than the traced one: the
        polymorphic export when available, else chunk/pad to the traced
        batch size (correct but pays traced-batch compute per chunk)."""
        n = self._check_batched(arrs)
        if self._batch_call is not None:
            return self._batch_call(self._params, *arrs)
        b0 = self._static_shapes[0][0]
        # each chunk is exactly b0 rows — if the artifact ships an AOT
        # executable for that size, run it instead of compiling one
        chunk_call = self._aot.get(b0, None) or self._call
        chunks = []
        for lo in range(0, n, b0):
            part = tuple(a[lo:lo + b0] for a in arrs)
            take = int(part[0].shape[0])
            if take < b0:
                part = tuple(jnp.concatenate(
                    [p, jnp.zeros((b0 - take,) + tuple(p.shape[1:]),
                                  p.dtype)]) for p in part)
            out = chunk_call(self._params, *part)
            chunks.append(jax.tree_util.tree_map(
                lambda o, k=take: o[:k], out))
        return jax.tree_util.tree_map(
            lambda *parts: jnp.concatenate(parts, axis=0), *chunks)

    def _check_batched(self, arrs):
        """Validate that inputs are the exported signature with a
        (shared) different leading dim; returns that batch size."""
        if len(arrs) != len(self._static_shapes):
            raise ValueError(
                f"model takes {len(self._static_shapes)} inputs, got "
                f"{len(arrs)}")
        n = None
        for a, ref in zip(arrs, self._static_shapes):
            if a.ndim != len(ref) or tuple(a.shape[1:]) != tuple(ref[1:]):
                raise ValueError(
                    f"input shape {tuple(a.shape)} does not match the "
                    f"exported signature {tuple(ref)} (only the leading "
                    "batch dim may differ)")
            if n is None:
                n = int(a.shape[0])
            elif int(a.shape[0]) != n:
                raise ValueError(
                    "all inputs must share one leading batch dim, got "
                    f"{[int(x.shape[0]) for x in arrs]}")
        return n

    @property
    def batch_polymorphic(self):
        return self._batch_call is not None

    @property
    def aot_buckets(self):
        """Batch sizes served by AOT-deserialized executables (no XLA
        compile in this process, ever, for these sizes)."""
        return sorted(self._aot)

    @property
    def compile_count(self):
        """Distinct executables traced so far (the executors' jit cache
        sizes; AOT executions never appear — deserialization is not
        compilation).  After ``warmup`` this must not grow while
        traffic replays warmed shapes — the serving /metrics counter
        asserts exactly that, and an all-AOT artifact keeps it at zero
        from process start."""
        return sum(ex.compile_count
                   for ex in (self._call_ex, self._batch_call_ex)
                   if ex is not None)

    def warmup(self, batch_sizes):
        """Pre-build one executable per batch size so no user request
        pays a cold XLA compile (TPU: every shape is a fresh compile).
        AOT-covered sizes execute their deserialized executable once
        (validation, not compilation)."""
        for n in batch_sizes:
            args = tuple(
                jnp.zeros((int(n),) + tuple(ref[1:]), dtype)
                for ref, dtype in zip(self._static_shapes,
                                      self._static_dtypes))
            self(*args)   # __call__ materializes to numpy: compile+run
        return self.compile_count


def _unflatten_keystr(flat: dict):
    """Invert jax.tree_util.keystr for pytrees of nested dicts, lists
    and tuples (keys look like ``['a'][0]['b']``; tuples come back as
    lists, which jax treats as the same pytree shape for calling)."""
    import re
    token = re.compile(r"\['([^']+)'\]|\[(\d+)\]")
    root: dict | list | None = None

    def ensure(container, key, make):
        if isinstance(key, int):
            while len(container) <= key:
                container.append(None)
            if container[key] is None:
                container[key] = make()
            return container[key]
        if key not in container:
            container[key] = make()
        return container[key]

    for keystr, val in flat.items():
        parts = [(m.group(1) if m.group(1) is not None else int(m.group(2)))
                 for m in token.finditer(keystr)]
        if not parts:
            parts = [keystr]
        kinds = [list if isinstance(p, int) else dict for p in parts]
        if root is None:
            root = kinds[0]()
        node = root
        for i, p in enumerate(parts[:-1]):
            node = ensure(node, p, kinds[i + 1])
        last = parts[-1]
        if isinstance(last, int):
            while len(node) <= last:
                node.append(None)
            node[last] = val
        else:
            node[last] = val
    return root if root is not None else {}


def load_predictor(prefix):
    return Predictor(prefix)
