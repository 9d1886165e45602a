"""Block / HybridBlock: the define-by-run API with whole-graph compilation.

TPU-native re-design of the reference Gluon core
(python/mxnet/gluon/block.py — Block :251, HybridBlock :854, hybridize
:1172 → _build_cache :985 → CachedOp; C++ side src/imperative/cached_op.h).

The reference's CachedOp traces the block into an NNVM graph and replays
it through the engine.  Here ``hybridize()`` compiles the *entire* block
into one XLA executable via ``jax.jit``:

* Tracing: parameters are temporarily mapped to tracer-backed NDArrays
  (see parameter._TraceParams), the block's ``forward`` runs once under
  ``jax.jit`` tracing, and the jaxpr is compiled.  This is the analog of
  deferred-compute tracing (reference block.py:1340) + whole-graph bind.
* Autograd: when recording, the compiled forward runs under ``jax.vjp``
  and lands on the tape as a *single* node — backward through the block
  is one compiled XLA call (the CachedOp::Backward analog).
* Mutable state (BatchNorm moving stats): collected during tracing as
  extra outputs and written back after execution, replacing the
  reference's in-place aux-state mutation with a functional round-trip.
* static_alloc → XLA buffer donation of input activations;
  static_shape → cache keyed on input shapes (shape buckets).
"""
from __future__ import annotations

import contextlib
import threading

import jax
import numpy as onp

from .. import autograd
from .. import executor_cache as _xc
from .. import random as _random
from .. import trace
from ..context import current_context
from ..ndarray import NDArray
from .parameter import Parameter, ParameterDict, _TraceParams, \
    _trace_map, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "CachedOp"]

_state_updates = threading.local()
_first_forward_open = threading.local()     # .on inside gluon.first_forward


def register_state_update(param: Parameter, new_value):
    """BatchNorm-style aux-state update: defer if tracing, else apply."""
    collector = getattr(_state_updates, "stack", None)
    if collector:
        collector[-1].append((param, new_value))
    else:
        with autograd.pause():
            param._check_and_get()._set_data(
                new_value.data if isinstance(new_value, NDArray) else new_value)


# lists that a forward appends traced values to (``nn.record_routing``), as
# callables that give the live list or None: what a block under recompute()
# appends leaves its checkpointed region as further outputs of it
_trace_sinks = []


def register_trace_sink(live):
    _trace_sinks.append(live)


class _CollectStateUpdates:
    def __enter__(self):
        if not hasattr(_state_updates, "stack"):
            _state_updates.stack = []
        self.updates = []
        _state_updates.stack.append(self.updates)
        return self.updates

    def __exit__(self, *exc):
        _state_updates.stack.pop()


class Block:
    """Base building block (reference gluon/block.py:251)."""

    def __init__(self, prefix=None, params=None):
        self._prefix = prefix or ""
        self._children: dict[str, Block] = {}
        self._reg_params: dict[str, Parameter] = {}
        self._forward_hooks: list = []
        self._forward_pre_hooks: list = []
        self._shared_params = params

    # -- registration -----------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            self.__dict__.setdefault("_children", {})[name] = value
            value.__dict__["_scope_key"] = name
        elif isinstance(value, Parameter):
            shared = self.__dict__.get("_shared_params")
            if shared is not None:
                # parameter sharing (reference Block(params=...) semantics):
                # an existing parameter of the same name is reused
                if name in shared:
                    value = shared[name]
                else:
                    suffix = [p for k, p in shared.items()
                              if k.endswith("." + name)]
                    if len(suffix) == 1:
                        value = suffix[0]
                    elif len(suffix) > 1:
                        raise ValueError(
                            f"shared params have multiple candidates for "
                            f"{name!r}: pass an unambiguous params dict "
                            "(e.g. layer.collect_params(), not the whole "
                            "net's)")
            self.__dict__.setdefault("_reg_params", {})[name] = value
            if not value.name or value.name == "param":
                value.name = name
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        name = name or str(len(self._children))
        self._children[name] = block
        block.__dict__["_scope_key"] = name
        return block

    def _scope(self):
        """The ``jax.named_scope`` this block is traced under: the key it
        is registered under in its parent (``features``, ``0``, ``conv1``
        ...), so an instruction's ``op_name`` reads
        ``forward/features/4/0/body/conv1/...``.  Not ``self.name``: the
        prefix counter differs between two nets built in one process.  A
        root block has no key and adds no segment."""
        key = self.__dict__.get("_scope_key")
        return contextlib.nullcontext() if key is None \
            else jax.named_scope(key)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix.rstrip("_") or type(self).__name__.lower()

    def name_scope(self):
        """Compat no-op scope (the reference used it for name prefixes)."""
        from ..name import Prefix
        return Prefix(self._prefix)

    @property
    def params(self) -> ParameterDict:
        d = ParameterDict(self._prefix)
        for name, p in self._reg_params.items():
            d._add(p.name if p.name != "param" else name, p)
        return d

    def collect_params(self, select=None) -> ParameterDict:
        """All params of self + descendants, qualified names
        (reference block.py collect_params)."""
        out = ParameterDict(self._prefix)
        self._collect_params_into(out, prefix="")
        if select is not None:
            import re
            pat = re.compile(select)
            filtered = ParameterDict(self._prefix)
            for k, v in out.items():
                if pat.match(k):
                    filtered._add(k, v)
            return filtered
        return out

    def _collect_params_into(self, out: ParameterDict, prefix: str):
        for name, p in self._reg_params.items():
            out._add(prefix + name, p)
        for cname, child in self._children.items():
            child._collect_params_into(out, prefix + cname + ".")

    # -- lifecycle --------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init=init, ctx=ctx,
                                         force_reinit=force_reinit)
        return self

    def cast(self, dtype):
        for p in self.collect_params().values():
            p.cast(dtype)
        for child in self._children.values():
            pass  # params already collected recursively
        self._cast_hook(dtype)
        return self

    def _cast_hook(self, dtype):
        for child in self._children.values():
            child._cast_hook(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def reset_ctx(self, ctx):
        self.collect_params().reset_ctx(ctx)

    # -- persistence (reference block.py:440 save_parameters / :496 load) -
    def save_parameters(self, filename, deduplicate=False):
        from .. import ndarray as nd
        arrays = {}
        for name, p in self.collect_params().items():
            arrays[name] = p.data()
        nd.save(filename, arrays)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        from .. import ndarray as nd
        loaded = nd.load(filename)
        if isinstance(loaded, list):
            raise ValueError("expected dict-of-arrays params file")
        params = self.collect_params()
        for name, p in params.items():
            if name in loaded:
                if p._data is None:
                    p.shape = loaded[name].shape
                    p.initialize(ctx=ctx)
                p.set_data(loaded[name])
            elif not allow_missing:
                raise KeyError(f"parameter {name} missing in {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(params.keys())
            if extra:
                raise KeyError(f"extra params in file: {sorted(extra)}")

    save_params = save_parameters
    load_params = load_parameters

    # -- hooks ------------------------------------------------------------
    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return _HookHandle(self._forward_hooks, hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return _HookHandle(self._forward_pre_hooks, hook)

    # -- call -------------------------------------------------------------
    # the latch of ``gluon.first_forward``: open until the block's first
    # call of any kind, so that every later call pays this one test
    _first_forward_pending = True

    def _first_forward(self, args, kwargs):
        """The block's first call, which ``__call__`` hands over and which
        calls it again with the latch closed.  Where it is eager (no parameters
        mapped to tracers, no traced argument) and outermost (no other
        block's first call open on this thread), it is the pass that
        resolves the net's deferred shapes, one small program an op, and
        runs as the process span ``gluon.first_forward`` (trace.py) with
        the leaves that got their shape and value inside it as
        ``deferred``; it closes the latch of every block below, called or
        not.  Any other first call only closes this block's latch."""
        self._first_forward_pending = False
        if getattr(_first_forward_open, "on", False) \
                or _trace_map() is not None or any(
                    isinstance(getattr(a, "data", a), jax.core.Tracer)
                    for a in args):
            return self(*args, **kwargs)

        def deferred():
            return sum(p._deferred_init_args is not None
                       for p in self.collect_params().values())

        waiting = deferred()
        _first_forward_open.on = True
        try:
            with trace.process_span("gluon.first_forward",
                                    block=type(self).__name__) as sp:
                out = self(*args, **kwargs)
                sp.set(deferred=waiting - deferred())
        finally:
            _first_forward_open.on = False
            self.apply(Block._close_first_forward)
        return out

    def _close_first_forward(self):
        self._first_forward_pending = False

    def __call__(self, *args, **kwargs):
        if self._first_forward_pending:
            return self._first_forward(args, kwargs)
        for hook in self._forward_pre_hooks:
            hook(self, args)
        policy = getattr(self, "_amp_policy", None)
        forward = self.forward
        if self.__dict__.get("_recompute") and _trace_map() is not None:
            forward = self._recomputed_forward
        with self._scope():
            if policy is not None:
                from ..amp import amp as _amp
                with _amp.policy_scope(policy):
                    out = forward(*args, **kwargs)
            else:
                out = forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    # -- recomputation ----------------------------------------------------
    def recompute(self, active=True):
        """Ask that, wherever this block is traced into a compiled program
        (a fused train step, a hybridized parent, ``functional()``), its
        ``forward`` run under ``jax.checkpoint``: the backward pass keeps
        the block's inputs and parameters and runs everything inside it
        again, so that a stack of such blocks holds one block's activations
        at a time.  Eager calls are as before.  Aux-state updates that the
        block registers (BatchNorm's moving statistics, a router's bias)
        leave the checkpointed region as further outputs of it and are
        registered outside, once: they carry no gradient, so the run in the
        backward pass does not compute them again.  In a trace, the
        instructions run again carry ``rematted_computation`` in their
        ``op_name`` (docs/observability.md)."""
        self.__dict__["_recompute"] = bool(active)
        return self

    def _recomputed_forward(self, *args):
        outer = _trace_map()
        mine = [p for p in self.collect_params().values() if p in outer]

        updated = []        # the parameters whose new values ``pure`` returns
        sinks = [sink for sink in (live() for live in _trace_sinks)
                 if sink is not None]
        held = [len(sink) for sink in sinks]

        def pure(values, inputs):
            inner = dict(outer)
            inner.update(zip(mine, map(NDArray, values)))
            with _TraceParams(inner), _CollectStateUpdates() as updates:
                out = self.forward(*map(NDArray, inputs))
            updated[:] = [p for p, _ in updates]
            sunk = [sink[n:] for sink, n in zip(sinks, held)]
            for sink, n in zip(sinks, held):
                del sink[n:]
            return (jax.tree_util.tree_map(lambda o: o.data, out),
                    [jax.lax.stop_gradient(getattr(v, "data", v))
                     for _, v in updates], sunk)

        out, new_values, sunk = jax.checkpoint(pure)(
            [outer[p].data for p in mine], [a.data for a in args])
        for param, value in zip(updated, new_values):
            register_state_update(param, NDArray(value))
        for sink, values in zip(sinks, sunk):
            sink.extend(values)
        return jax.tree_util.tree_map(NDArray, out)

    def summary(self, *inputs):
        """Print per-block output shapes (reference block.py summary)."""
        rows = []

        def add_hooks(block, prefix):
            def hook(blk, ins, out):
                outs = out if isinstance(out, (list, tuple)) else [out]
                shapes = [tuple(o.shape) for o in outs if isinstance(o, NDArray)]
                nparams = sum(int(onp.prod(p.shape)) for p in
                              blk._reg_params.values()
                              if p._shape_complete())
                rows.append((prefix or type(blk).__name__, shapes, nparams))
            handles.append(block.register_forward_hook(hook))
            for name, c in block._children.items():
                add_hooks(c, f"{prefix}.{name}" if prefix else name)

        handles: list = []
        add_hooks(self, "")
        try:
            self(*inputs)
        finally:
            for h in handles:
                h.detach()
        print(f"{'Layer':<40} {'Output shape':<24} {'Params':>12}")
        print("-" * 78)
        for name, shapes, nparams in rows:
            print(f"{name:<40} {str(shapes):<24} {nparams:>12}")
        total = sum(int(onp.prod(p.shape)) for p in
                    self.collect_params().values() if p._shape_complete())
        print("-" * 78)
        print(f"Total params: {total}")

    def __repr__(self):
        lines = [type(self).__name__ + "("]
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {child_repr}")
        lines.append(")")
        return "\n".join(lines)


class _HookHandle:
    def __init__(self, hook_list, hook):
        self._list = hook_list
        self._hook = hook

    def detach(self):
        if self._hook in self._list:
            self._list.remove(self._hook)


class CachedOp:
    """Whole-block compiled executable (reference src/imperative/cached_op.h:365).

    One instance per hybridized block; caches one compiled program per
    (input shapes, dtypes, training-mode) signature — the TPU analog of
    the reference's per-bucket executors.
    """

    def __init__(self, block: "HybridBlock", static_alloc=False,
                 static_shape=False):
        self.block = block
        self.static_alloc = static_alloc
        self.static_shape = static_shape
        self._site = f"cachedop:{type(block).__name__}"
        self._cache = _xc.TraceCache(self._site)

    def _ordered_params(self):
        return list(self.block.collect_params().values())

    def _build(self, sig, params, training):
        entry = {"single": True, "su_params": []}

        def pure(param_vals, input_vals, key):
            mapping = {p: NDArray(v) for p, v in zip(params, param_vals)}
            with _TraceParams(mapping), _random.key_scope(key), \
                    autograd._scope(None, training), _CollectStateUpdates() as su:
                outs = self.block.forward(*[NDArray(v) for v in input_vals])
            if isinstance(outs, (list, tuple)):
                entry["single"] = False
                out_vals = tuple(o.data for o in outs)
            else:
                out_vals = (outs.data,)
            entry["su_params"] = [p for p, _ in su]
            upd_vals = tuple(v.data if isinstance(v, NDArray) else v
                             for _, v in su)
            return out_vals, upd_vals

        # the unified choke point (executor_cache.Executor) owns the
        # sentinel instrumentation and the jit: one trace of `pure` ==
        # one XLA compile of this CachedOp; a varying input signature
        # shows up as churn at this site.  The uninstrumented fn rides
        # on the executor for the build-time IR lint, whose extra trace
        # must not count as a compile.
        entry["executor"] = _xc.Executor(
            pure, self._site,
            donate_argnums=(1,) if self.static_alloc else ())
        entry["jfn"] = entry["executor"].jfn
        return entry

    def __call__(self, *inputs):
        params = self._ordered_params()
        # deferred shape inference: fall back to one eager pass
        for p in params:
            if p._data is None and p._deferred_init_args is not None:
                return self.block.forward(*inputs)
        raw_params = [p._check_and_get().data for p in params]
        raw_inputs = [x.data for x in inputs]
        training = autograd.is_training()
        # param shapes/dtypes are part of the signature: a re-initialized
        # or reshaped/recast parameter must rebuild, not silently reuse a
        # stale executable entry
        sig = (tuple((tuple(a.shape), str(a.dtype)) for a in raw_inputs),
               training,
               tuple((tuple(a.shape), str(a.dtype)) for a in raw_params))
        # atomic against concurrent first calls with the same signature:
        # two threads must not double-build (and double-report to the
        # sentinel) one executable
        entry, hit = self._cache.get_or_create(
            sig, lambda: self._build(sig, params, training))
        if not hit:
            # build-time analyses through the unified choke point
            # (executor_cache.run_analyses; inert by default): the
            # exact pure fn this executable compiles, with the RNG key
            # declared intentionally-unused (deterministic nets ignore
            # it).  static_alloc contracts to donate the input
            # activations; without it the params and inputs are
            # caller-held (allow_undonated), so memlint only records
            # the peak-HBM estimate and lifetime stats.
            if _xc.lint_active() or _xc.memlint_active() \
                    or _xc.shardlint_active():
                entry["executor"].analyze(
                    (raw_params, raw_inputs, jax.random.PRNGKey(0)),
                    graphlint=dict(allow_unused_args=(2,),
                                   check_donation=self.static_alloc),
                    memlint=dict(
                        allow_undonated=(0,) if self.static_alloc
                        else (0, 1),
                        require_donation=self.static_alloc),
                    # no declared entry specs here (a hybridized block
                    # is single-chip unless export/fused-step paths say
                    # otherwise): shardlint still prices any collectives
                    # and records the per-site per-shard stats
                    shardlint=dict(allow_replicated=(0, 1, 2)))
        jfn = entry["jfn"]
        key = _random.next_key()

        recording = autograd.is_recording()
        grad_params = [p for p in params if p.grad_req != "null"]
        need_grad = recording and (
            grad_params or any(x._in_graph() for x in inputs))
        if need_grad:
            out_vals, vjp_fn, upd_vals = jax.vjp(
                lambda ps, xs: jfn(ps, xs, key), raw_params, raw_inputs,
                has_aux=True)
        else:
            out_vals, upd_vals = jfn(raw_params, raw_inputs, key)

        out_nds = tuple(NDArray(v, ctx=inputs[0].ctx if inputs else current_context())
                        for v in out_vals)
        # apply collected state updates (moving stats)
        for p, v in zip(entry["su_params"], upd_vals):
            with autograd.pause():
                p._check_and_get()._set_data(v)

        if need_grad:
            nd_inputs = [p._data for p in params] + \
                [x for x in inputs if isinstance(x, NDArray)]

            def tape_vjp(seed):
                if not isinstance(seed, tuple):
                    seed = (seed,)
                grad_ps, grad_xs = vjp_fn(seed)
                return tuple(grad_ps) + tuple(grad_xs)

            autograd._record(None, tape_vjp, inputs, nd_inputs,
                             list(range(len(nd_inputs))), out_nds)
        return out_nds[0] if entry["single"] else out_nds


class HybridBlock(Block):
    """Block that can compile to a single XLA program
    (reference gluon/block.py:854)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cached_op: CachedOp | None = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Enable whole-graph compilation (reference block.py:1172)."""
        self._active = active
        self._flags = {"static_alloc": static_alloc,
                       "static_shape": static_shape}
        self._cached_op = None
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                child._active = False  # only the outermost block compiles
        return self

    def _get_cached_op(self):
        if self._cached_op is None:
            self._cached_op = CachedOp(self, **self._flags)
        return self._cached_op

    def __call__(self, *args, **kwargs):
        if self._first_forward_pending:
            return self._first_forward(args, kwargs)
        if self._active and args and all(
                isinstance(a, NDArray) and
                not isinstance(a.data, jax.core.Tracer) for a in args):
            for hook in self._forward_pre_hooks:
                hook(self, args)
            policy = getattr(self, "_amp_policy", None)
            if policy is not None:
                # the CachedOp trace replays forward() via invoke, so the
                # policy must be active around it exactly as in the eager
                # path (the casts bake into the compiled graph)
                from ..amp import amp as _amp
                with _amp.policy_scope(policy):
                    out = self._get_cached_op()(*args)
            else:
                out = self._get_cached_op()(*args)
            for hook in self._forward_hooks:
                hook(self, args, out)
            return out
        return super().__call__(*args, **kwargs)

    # -- reference hybrid_forward compatibility ---------------------------
    def forward(self, *args, **kwargs):
        if type(self).hybrid_forward is not HybridBlock.hybrid_forward:
            from .. import ndarray as F
            param_kwargs = {name: p.data() for name, p in
                            self._reg_params.items()}
            return self.hybrid_forward(F, *args, **param_kwargs, **kwargs)
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward() or "
            f"hybrid_forward()")

    def hybrid_forward(self, F, *args, **kwargs):
        raise NotImplementedError

    # -- functional bridge (TPU-first: feeds pjit/shard_map) --------------
    def functional(self):
        """Return ``(params_dict, apply_fn)`` for pure-functional use.

        ``apply_fn(params_dict, *inputs, training=False, key=None)`` is a
        pure function suitable for ``jax.jit``/``pjit``/``shard_map`` —
        the bridge from the imperative Gluon API to SPMD training (used
        by the parallel layer; no reference equivalent, SURVEY.md §7
        stage 10).
        """
        named = list(self.collect_params().items())
        params_dict = {name: p.data().data for name, p in named}
        name2param = {name: p for name, p in named}
        param2name = {p: name for name, p in named}

        def apply_fn(pvals, *input_vals, training=False, key=None,
                     with_updates=False):
            # key=None stays None: key_scope derives PRNGKey(0) lazily,
            # so a deterministic forward traces no dead PRNG equations
            # (graphlint GL-DEAD001 on every inference graph otherwise)
            mapping = {name2param[n]: NDArray(v) for n, v in pvals.items()}
            policy = getattr(self, "_amp_policy", None)
            if policy is not None:
                from ..amp import amp as _amp
                pol_ctx = _amp.policy_scope(policy)
            else:
                pol_ctx = contextlib.nullcontext()
            with _TraceParams(mapping), _random.key_scope(key), \
                    autograd._scope(None, training), \
                    _CollectStateUpdates() as su, pol_ctx:
                outs = self.forward(*[NDArray(v) for v in input_vals])
            if isinstance(outs, (list, tuple)):
                out = tuple(o.data for o in outs)
            else:
                out = outs.data
            if with_updates:
                updates = {param2name[p]: (v.data if isinstance(v, NDArray)
                                           else v)
                           for p, v in su if p in param2name}
                return out, updates
            return out

        return params_dict, apply_fn

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes by abstract evaluation."""
        self.forward(*args)  # eager pass performs deferred init

    def export(self, path, epoch=0, remove_amp_cast=True, example_inputs=None):
        """Serialize graph + params (reference block.py:1248 export).

        TPU re-design of the symbol.json deployment format: the traced
        forward is serialized as a portable StableHLO program
        (``jax.export``) in ``path-symbol.stablehlo`` with a JSON
        manifest in ``path-symbol.json``, plus ``path-%04d.params``.
        This is the deploy artifact the reference's C predict API loaded
        (SURVEY.md §2.1 "C API": predict maps to serialized StableHLO).
        """
        import json as _json
        from jax import export as jax_export
        from .. import ndarray as nd

        if example_inputs is None:
            raise ValueError(
                "export needs example_inputs=(x, ...) to trace the graph")
        params = self.collect_params()
        named = list(params.items())
        pvals = [p.data().data for _, p in named]
        ivals = [x.data if isinstance(x, NDArray) else x
                 for x in example_inputs]

        def pure(param_vals, input_vals):
            mapping = {p: NDArray(v)
                       for (_, p), v in zip(named, param_vals)}
            with _TraceParams(mapping), autograd._scope(None, False), \
                    _CollectStateUpdates():
                outs = self.forward(*[NDArray(v) for v in input_vals])
            if isinstance(outs, (list, tuple)):
                return tuple(o.data for o in outs)
            return outs.data

        exported = jax_export.export(jax.jit(pure))(pvals, ivals)  # mxlint: disable=MX-DONATE001(export-time trace over the block's live parameter values — serving-side donation is deploy.export_model's donate_argnums contract)
        with open(f"{path}-symbol.stablehlo", "wb") as f:
            f.write(exported.serialize())
        manifest = {
            "format": "stablehlo",
            "inputs": [{"shape": list(v.shape), "dtype": str(v.dtype)}
                       for v in ivals],
            "params": [name for name, _ in named],
        }
        with open(f"{path}-symbol.json", "w") as f:
            _json.dump(manifest, f, indent=2)
        arrays = {f"arg:{k}": p.data() for k, p in params.items()}
        nd.save(f"{path}-{epoch:04d}.params", arrays)
        return f"{path}-symbol.json", f"{path}-{epoch:04d}.params"


class SymbolBlock(HybridBlock):
    """Run a Symbol graph as a Block (reference block.py:1410).

    Construct with ``SymbolBlock(outputs, inputs)`` or
    ``SymbolBlock.imports(symbol_file, input_names, param_file)``.
    """

    def __init__(self, outputs, inputs, params=None):
        super().__init__()
        self._symbol_outputs = outputs
        self._symbol_inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        input_names = {s.name for s in self._symbol_inputs}
        out0 = outputs[0] if isinstance(outputs, list) else outputs
        arg_names = out0.list_arguments()
        aux_names = out0.list_auxiliary_states()
        for name in arg_names + aux_names:
            if name not in input_names:
                p = Parameter(name, allow_deferred_init=True,
                              grad_req="null" if name in aux_names
                              else "write")
                if params and name in params:
                    data = params[name]
                    p.shape = data.shape
                    p.initialize(ctx=current_context())
                    p.set_data(data)
                self._reg_params[name] = p

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        import json as _json
        from .. import symbol as sym_mod
        from .. import ndarray as nd
        with open(symbol_file) as f:
            manifest = _json.load(f)
        if manifest.get("format") == "stablehlo":
            # HybridBlock.export deploy artifact: portable StableHLO
            # program + params (the predict-API path, SURVEY.md §2.1)
            return _StableHLOBlock(symbol_file, manifest, param_file)
        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        params = None
        if param_file:
            loaded = nd.load(param_file)
            params = {k.split(":", 1)[-1]: v for k, v in loaded.items()}
        return SymbolBlock(sym, inputs, params=params)

    def forward(self, *args):
        bindings = {s.name: a for s, a in zip(self._symbol_inputs, args)}
        for name, p in self._reg_params.items():
            bindings[name] = p.data()
        return self._symbol_outputs.eval_with(bindings)


class _StableHLOBlock(HybridBlock):
    """Deserialized ``HybridBlock.export`` artifact, runnable as a Block.

    The TPU analog of loading prefix-symbol.json into the reference's
    C predict API (c_predict_api.cc): the graph arrives as a compiled
    StableHLO program, so inference needs no Python model definition.
    """

    def __init__(self, symbol_file, manifest, param_file):
        super().__init__()
        from jax import export as jax_export
        path = symbol_file[:-len("-symbol.json")] \
            if symbol_file.endswith("-symbol.json") else symbol_file
        with open(f"{path}-symbol.stablehlo", "rb") as f:
            self._exported = jax_export.deserialize(f.read())
        self._param_names = manifest["params"]
        params = {}
        if param_file:
            from .. import ndarray as nd
            loaded = nd.load(param_file)
            params = {k.split(":", 1)[-1]: v for k, v in loaded.items()}
        for name in self._param_names:
            p = Parameter(name, allow_deferred_init=True)
            if name in params:
                data = params[name]
                p.shape = data.shape
                p.initialize(ctx=current_context())
                p.set_data(data)
            self._reg_params[name] = p

    def forward(self, *args):
        pvals = [self._reg_params[n].data().data for n in self._param_names]
        ivals = [x.data if isinstance(x, NDArray) else x for x in args]
        out = self._exported.call(pvals, ivals)
        if isinstance(out, (list, tuple)):
            outs = [NDArray(o) for o in out]
            return outs[0] if len(outs) == 1 else outs
        return NDArray(out)
