"""Whole-step compilation: forward + backward + optimizer in ONE XLA program.

The TPU analog of the reference's op-bulking + static_alloc CachedOp
(graph_executor.cc:1422 InitOpSegs; cached_op.h static paths): instead of
pushing hundreds of small ops per step, the entire train step — loss,
gradients, optimizer update, BatchNorm moving-stat updates — compiles to
a single donated-buffer XLA executable.  This is the framework's
performance path for benchmarks and large-scale training; the eager
Trainer remains the flexible path.

Optimizer math is shared with ``optimizer/optimizer.py`` by construction:
the fused updates below implement the same formulas (SGD+momentum, NAG,
Adam, AdamW) as pure pytree transforms.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import executor_cache as _xc
from . import trace
from .base import resolve_chunk_steps
from .ndarray import NDArray
from .ops.pallas_kernels import gspmd_trace

__all__ = ["FusedTrainStep", "make_fused_train_step", "sgd_init", "adam_init"]


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def sgd_init(params):
    return {"mom": _tree_map(jnp.zeros_like, params)}


def adam_init(params):
    return {"m": _tree_map(jnp.zeros_like, params),
            "v": _tree_map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def _sgd_update(grads, state, params, lr, momentum, wd):
    new_mom = _tree_map(
        lambda p, g, m: momentum * m - lr * (g + wd * p),
        params, grads, state["mom"])
    new_params = _tree_map(lambda p, m2: (p + m2).astype(p.dtype),
                           params, new_mom)
    return new_params, {"mom": new_mom}


def _nag_update(grads, state, params, lr, momentum, wd):
    """Nesterov momentum, same formula as optimizer.py NAG.update."""
    new_mom = _tree_map(lambda p, g, m: momentum * m + g + wd * p,
                        params, grads, state["mom"])
    new_params = _tree_map(
        lambda p, g, m2: (p - lr * (g + wd * p + momentum * m2)).astype(p.dtype),
        params, grads, new_mom)
    return new_params, {"mom": new_mom}


def _adam_update(grads, state, params, lr, b1, b2, eps, wd):
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    corr = jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
    new_m = _tree_map(lambda g, m, p: b1 * m + (1 - b1) * (g + wd * p),
                      grads, state["m"], params)
    new_v = _tree_map(lambda g, v, p: b2 * v + (1 - b2) * jnp.square(g + wd * p),
                      grads, state["v"], params)
    new_params = _tree_map(
        lambda p, m2, v2: (p - lr * corr * m2 /
                           (jnp.sqrt(v2) + eps)).astype(p.dtype),
        params, new_m, new_v)
    return new_params, {"m": new_m, "v": new_v, "t": t}


def _adamw_update(grads, state, params, lr, b1, b2, eps, wd):
    """Decoupled weight decay, same formula as optimizer.py AdamW.update."""
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    corr = jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
    new_m = _tree_map(lambda g, m: b1 * m + (1 - b1) * g, grads, state["m"])
    new_v = _tree_map(lambda g, v: b2 * v + (1 - b2) * jnp.square(g),
                      grads, state["v"])
    new_params = _tree_map(
        lambda p, m2, v2: (p - lr * corr * m2 / (jnp.sqrt(v2) + eps)
                           - lr * wd * p).astype(p.dtype),
        params, new_m, new_v)
    return new_params, {"m": new_m, "v": new_v, "t": t}


class FusedTrainStep:
    """Compiled train step over a gluon block.

    Usage::

        step = make_fused_train_step(net, loss_fn, "sgd",
                                     {"learning_rate": 0.1, "momentum": 0.9})
        for batch in data:
            loss = step(x, y)     # one XLA program; params live on device
        step.write_back()          # sync updated params into the Block
    """

    def __init__(self, block, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, batch_spec=None, donate=True, chunk_steps=None):
        self.block = block
        self.loss_block = loss_fn
        opt_params = dict(optimizer_params or {})
        self.lr = opt_params.get("learning_rate", 0.01)
        self.momentum = opt_params.get("momentum", 0.0)
        self.wd = opt_params.get("wd", 0.0)
        self.optimizer = optimizer
        # chunk budget for the whole-loop compilation path (fuse_loop):
        # K == 1 stays on this per-step program, K > 1 lets a
        # ChunkedTrainLoop scan K steps per dispatch
        self.chunk_steps = resolve_chunk_steps(chunk_steps)
        self._site = f"fused_step:{type(block).__name__}"
        # from a Block's parameters to a committed train state and a
        # jitted function, as process spans (trace.py: always recorded)
        with trace.process_span("fused_step.build", site=self._site):
            self._init_state(block, optimizer, mesh)
            # kept for the chunked loop (fuse_loop): the scanned program
            # re-applies the same batch sharding to its (K, batch, ...)
            # blocks, with the scan axis unsharded
            self._mesh = mesh
            self._batch_spec = batch_spec
            self._lint_done = False
            self._memlint_done = False
            self._shardlint_done = False
            with trace.process_span("fused_step.program"):
                self._step_fn = self._build(mesh, batch_spec, donate)
        self._first_call_pending = True
        self._last = None

    def _init_state(self, block, optimizer, mesh):
        params_all, apply_fn = block.functional()
        self._apply = apply_fn
        # split trainable vs aux (grad_req null → moving stats etc.)
        named = list(block.collect_params().items())
        self._trainable_names = [n for n, p in named if p.grad_req != "null"]
        self._aux_names = [n for n, p in named if p.grad_req == "null"]
        with trace.process_span("fused_step.state_copy") as sp:
            # copy the initial values: the step donates its param buffers,
            # and donating the Block's live arrays would delete them out
            # from under any eval pass on the block itself
            self.params = {n: jnp.array(params_all[n])
                           for n in self._trainable_names}
            self.aux = {n: jnp.array(params_all[n]) for n in self._aux_names}
            if optimizer in ("sgd", "nag"):
                self.opt_state = sgd_init(self.params)
            elif optimizer in ("adam", "adamw"):
                self.opt_state = adam_init(self.params)
            else:
                raise ValueError(
                    f"fused step supports sgd/nag/adam/adamw; got "
                    f"{optimizer!r} (use the eager Trainer for others)")
            self._key = jax.random.PRNGKey(0)
            state = (self.params, self.aux, self.opt_state, self._key)
            leaves = jax.tree_util.tree_leaves(state)
            nbytes = sum(leaf.nbytes for leaf in leaves)
            sp.set(leaves=len(leaves), bytes=nbytes)
        # commit the whole train state to where it will run, up front:
        # jit outputs are committed arrays, so an uncommitted first
        # call would compile one executable for step 1 and a second —
        # the real steady-state one — for step 2+ (one program per
        # batch shape, from the first dispatch); and a Block's
        # parameters live on the host CPU, which must not decide where
        # the step runs.  One device without a mesh; replicated over
        # the mesh with one (data parallelism: GSPMD all-reduces the
        # gradients of replicated parameters).
        if mesh is None:
            placement = jax.devices()[0]
            devices = 1
        else:
            from jax.sharding import NamedSharding, PartitionSpec
            placement = NamedSharding(mesh, PartitionSpec())
            devices = mesh.size
        with trace.process_span("fused_step.place", bytes=nbytes,
                                devices=devices):
            self.params, self.aux, self.opt_state, self._key = \
                jax.device_put(state, placement)

    def _build(self, mesh, batch_spec, donate):
        loss_block = self.loss_block
        apply = self._apply
        lr, momentum, wd = self.lr, self.momentum, self.wd
        optimizer = self.optimizer

        # the phases carry names into every instruction's op_name
        # (docs/observability.md): under value_and_grad the forward
        # pass reads jvp(forward)/..., the backward pass
        # transpose(jvp(forward))/... with no scope of its own, the
        # update optimizer/...; child blocks add their registration
        # keys below the phase (gluon/block.py)
        def loss_of(params, aux, x, y, key):
            with jax.named_scope("forward"):
                out, updates = apply({**params, **aux}, x, training=True,
                                     key=key, with_updates=True)
                # a net's first output is what a loss sees, unless the loss
                # says it weighs all of them (main and extra heads)
                outs = out if isinstance(out, tuple) else (out,)
                if not getattr(loss_block, "takes_all_outputs", False):
                    outs = outs[:1]
                with jax.named_scope("loss"):
                    loss = loss_block(*map(NDArray, outs), NDArray(y))
                return jnp.mean(loss.data), updates

        def step(params, aux, opt_state, x, y, key):
            # this body runs while tracing, whoever traces it (the jit
            # call, a lower(), the analyses, the chunked loop's scan):
            # over a mesh, GSPMD partitions the program, and it cannot
            # partition a Mosaic kernel
            with gspmd_trace(mesh is not None):
                (loss, updates), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params, aux, x, y, key)
            with jax.named_scope("optimizer"):
                if optimizer == "sgd":
                    new_params, new_state = _sgd_update(
                        grads, opt_state, params, lr, momentum, wd)
                elif optimizer == "nag":
                    new_params, new_state = _nag_update(
                        grads, opt_state, params, lr, momentum, wd)
                elif optimizer == "adamw":
                    new_params, new_state = _adamw_update(
                        grads, opt_state, params, lr, 0.9, 0.999, 1e-8, wd)
                else:
                    new_params, new_state = _adam_update(
                        grads, opt_state, params, lr, 0.9, 0.999, 1e-8, wd)
                new_aux = {**aux, **{k: v for k, v in updates.items()
                                     if k in aux}}
            return new_params, new_aux, new_state, loss

        donate_argnums = (0, 1, 2) if donate else ()
        # the unified choke point owns sentinel instrumentation + jit
        # (the executor keeps the raw uninstrumented step as .fn for
        # the build-time analyses — its lint trace must not count as a
        # sentinel compile):
        # a fused step should compile ONCE per batch shape — churn here
        # (varying batch, a dtype flip) is the single most expensive
        # recompile in the framework
        in_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            bspec = NamedSharding(mesh, batch_spec or P("dp"))
            in_shardings = (None, None, None, bspec, bspec, None)
        self._executor = _xc.Executor(
            step, self._site,
            donate_argnums=donate_argnums, in_shardings=in_shardings)
        # called through the Executor, not its bare jfn: the one choke
        # point times every jitted entry point (``executor.call``)
        return self._executor

    def __call__(self, x, y):
        if self._first_call_pending:
            return self._first_call(x, y)
        # the host's side of a step, span by span (trace.py; written
        # into the profiler's trace when a session is on): the key
        # split is a jitted program of its own, the analyses' latches
        # are checked while one is open, the rest is the jitted call
        with trace.span("fused_step.call"):
            xv = x.data if isinstance(x, NDArray) else x
            yv = y.data if isinstance(y, NDArray) else y
            with trace.span("fused_step.key_split"):
                self._key, sub = jax.random.split(self._key)
            if not (self._lint_done and self._memlint_done
                    and self._shardlint_done):
                with trace.span("fused_step.analyses"):
                    self._analyze(xv, yv, sub)
            self.params, self.aux, self.opt_state, loss = self._step_fn(
                self.params, self.aux, self.opt_state, xv, yv, sub)
        self._last = loss
        return loss

    def _first_call(self, x, y):
        """Until a call has returned: the key split, the analyses, trace,
        lower, compile or cache read, the executable's load and the dispatch
        of step 1, as one process span around the call's ordinary ones."""
        self._first_call_pending = False
        try:
            with trace.process_span("fused_step.first_call",
                                    site=self._site):
                return self(x, y)
        except BaseException:
            self._first_call_pending = True
            raise

    def _analyze(self, xv, yv, sub):
        if not (self._lint_done and self._memlint_done):
            # build-time analyses of the whole train step through the
            # unified choke point (MXNET_GRAPH_LINT/MXNET_GRAPH_MEMLINT).
            # An undonated step (donate=False) earns its GL-DONATE001
            # advisory and is an error-severity ML-DONATE001 — the
            # fused step CONTRACTS to donate params/aux/optimizer
            # state.  Latch/exemption discipline lives in
            # latch_train_analyses (shared with ChunkedTrainLoop).
            self._lint_done, self._memlint_done = \
                _xc.latch_train_analyses(
                    self._executor,
                    (self.params, self.aux, self.opt_state, xv, yv, sub),
                    self._lint_done, self._memlint_done)
        if not self._shardlint_done and _xc.shardlint_active():
            # one-shot shardlint over the same step: the batch args
            # carry the declared dp spec when a mesh was given; the
            # train state is legitimately replicated (dp), so only
            # the collective bill and per-shard peak are of interest
            from jax.sharding import PartitionSpec as P
            bspec = (self._batch_spec or P("dp")) \
                if self._mesh is not None else None
            self._executor.analyze(
                (self.params, self.aux, self.opt_state, xv, yv, sub),
                shardlint=dict(
                    mesh=self._mesh,
                    in_specs=(None, None, None, bspec, bspec, None),
                    allow_replicated=(0, 1, 2, 5)))
            self._shardlint_done = True

    @property
    def step_fn(self):
        """The raw (uninstrumented) pure step function
        ``(params, aux, opt_state, x, y, key) -> (params, aux,
        opt_state, loss)`` — the body a :class:`~.fuse_loop.
        ChunkedTrainLoop` scans over."""
        return self._executor.fn

    def chunked_loop(self, chunk_steps=None):
        """A :class:`~.fuse_loop.ChunkedTrainLoop` over this step
        (state stays shared: the loop reads and writes this step's
        params/aux/opt_state/key, so tail batches and ``write_back``
        keep working unchanged)."""
        from .fuse_loop import ChunkedTrainLoop
        return ChunkedTrainLoop(self, chunk_steps=chunk_steps)

    def write_back(self):
        """Copy updated params back into the Block's Parameters."""
        all_params = dict(self.block.collect_params().items())
        for name, val in {**self.params, **self.aux}.items():
            all_params[name]._check_and_get()._set_data(val)


def make_fused_train_step(block, loss_fn, optimizer="sgd",
                          optimizer_params=None, **kwargs):
    return FusedTrainStep(block, loss_fn, optimizer, optimizer_params,
                          **kwargs)
