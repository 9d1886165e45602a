"""Whole-training-loop compilation: one XLA program per epoch chunk.

The fused train step (fuse.py) collapsed the *step* — forward +
backward + optimizer — into one XLA program, but the *loop* still pays
Python once per step: dispatch the program, round-trip the loss handle
to the host, loop.  At small batch sizes that per-step overhead
dominates step time (ROADMAP item 4: the largest CPU-measurable
step-time lever left, and exactly what the 70%-MFU on-chip target
cannot afford).

:class:`ChunkedTrainLoop` fuses the loop itself: ``lax.scan`` over K
fused steps inside one jitted program —

* **carry** = (params, aux, opt_state, PRNG key, loss accumulator),
  donated end to end (memlint's donation-coverage gate applies to the
  scan carry exactly as it does to the per-step program);
* **xs** = a K-step batch block shaped ``(K, batch, ...)`` fed by the
  dataloader's :class:`~.gluon.data.dataloader.DevicePrefetchRing`
  (the next block's host→device transfer overlaps the current chunk's
  compute);
* **metrics** accumulate in-carry and emit once per chunk, so the host
  sees ONE dispatch + one scalar transfer per K steps instead of K.

The PRNG key is threaded through the carry with the *same*
``jax.random.split`` schedule the sequential step uses, so dropout and
any other in-graph randomness see identical keys step for step.

The loop builds through :class:`~.executor_cache.Executor` (site
``fused_loop:{Block}``) — graphlint/memlint/recompile-sentinel wiring
inherited from the unified choke point.  The block shape ``(K, batch,
...)`` is part of the jit trace key, so a bucket-boundary retrace is a
sentinel-visible event; the tail of an epoch that does not fill K runs
through the *existing* per-step fused program instead of compiling a
second, shorter loop (one loop executable per bucket, ever).

State is shared with the wrapped :class:`~.fuse.FusedTrainStep`
(params/aux/opt_state/key live on the step object), so mixing chunked
epochs, per-step tail batches, and ``write_back`` needs no copying.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import executor_cache as _xc
from . import trace
from .base import resolve_chunk_steps as _resolve_chunk_steps
from .gluon.data.dataloader import DevicePrefetchRing

__all__ = ["ChunkedTrainLoop"]


class ChunkedTrainLoop:
    """Scan K fused train steps per XLA dispatch.

    Usage::

        step = make_fused_train_step(net, loss_fn, "sgd", opt_params,
                                     chunk_steps=16)
        loop = step.chunked_loop()          # or ChunkedTrainLoop(step)
        for epoch in range(epochs):
            records = loop.run_epoch(batches)   # iterable of (x, y)
        step.write_back()

    ``chunk_steps == 1`` deliberately degenerates to the existing
    per-step fused path — no scan program is ever built, so the
    default (``MXNET_TRAIN_CHUNK_STEPS=1``) is bit-for-bit the
    pre-chunking behavior.
    """

    def __init__(self, step, chunk_steps=None):
        self.step = step
        self.chunk_steps = _resolve_chunk_steps(
            chunk_steps if chunk_steps is not None else step.chunk_steps)
        self.chunks_run = 0
        self.tail_steps_run = 0
        self._lint_done = False
        self._memlint_done = False
        self._executor = None
        if self.chunk_steps > 1:
            self._executor = self._build()

    def _build(self):
        step_fn = self.step.step_fn

        def loop(params, aux, opt_state, key, xs, ys):
            def body(carry, xy):
                params, aux, opt_state, key, loss_sum = carry
                x, y = xy
                # the EXACT split schedule of the sequential step
                # (FusedTrainStep.__call__): next-key first, step key
                # second — dropout parity is bitwise, not statistical
                key, sub = jax.random.split(key)
                params, aux, opt_state, loss = step_fn(
                    params, aux, opt_state, x, y, sub)
                return (params, aux, opt_state, key,
                        loss_sum + loss.astype(jnp.float32)), None
            carry0 = (params, aux, opt_state, key,
                      jnp.zeros((), jnp.float32))
            (params, aux, opt_state, key, loss_sum), _ = jax.lax.scan(
                body, carry0, (xs, ys))
            return (params, aux, opt_state, key,
                    loss_sum / xs.shape[0])

        # a mesh-built step shards its per-step batch; the scanned
        # blocks carry the same spec shifted one axis right (scan axis
        # K unsharded) — dropping it would silently replicate every
        # block across the mesh
        in_shardings = None
        if self.step._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            bspec = self.step._batch_spec or P("dp")
            block = NamedSharding(self.step._mesh, P(None, *bspec))
            in_shardings = (None, None, None, None, block, block)
        # the whole carry is donated: params/aux/opt_state like the
        # per-step program, plus the PRNG key (consumed and re-emitted
        # every chunk).  xs/ys stay caller-held — the prefetch ring
        # may still be uploading the NEXT block from the same pool
        return _xc.Executor(
            loop, f"fused_loop:{type(self.step.block).__name__}",
            donate_argnums=(0, 1, 2, 3), in_shardings=in_shardings)

    # -- observability -------------------------------------------------

    @property
    def compile_count(self):
        """Distinct loop executables compiled — must equal the number
        of distinct (K, bucket) block shapes driven (the bench's
        one-compile-per-bucket flatline gate)."""
        return self._executor.compile_count if self._executor else 0

    @property
    def steps_run(self):
        return self.chunks_run * self.chunk_steps + self.tail_steps_run

    # -- execution -----------------------------------------------------

    def _analyze(self, args):
        """Build-time graphlint/memlint over the scanned program, the
        same latch discipline as the fused step (shared
        :func:`~.executor_cache.latch_train_analyses`).  The
        GL-DEAD001 exemption carries into the sub-jaxpr walk because
        rule suppression is per lint run, not per nesting level."""
        self._lint_done, self._memlint_done = _xc.latch_train_analyses(
            self._executor, args, self._lint_done, self._memlint_done)

    def run_chunk(self, xs, ys):
        """One full K-step chunk: ``xs``/``ys`` are device blocks
        shaped ``(K, batch, ...)``.  Returns the chunk's mean loss (a
        device scalar — the one small transfer per K steps)."""
        if self._executor is None:
            raise RuntimeError(
                "chunk_steps == 1 has no loop program; drive the "
                "per-step FusedTrainStep (run_epoch does this for you)")
        if xs.shape[0] != self.chunk_steps:
            raise ValueError(
                f"block carries {xs.shape[0]} steps, loop compiled for "
                f"chunks of {self.chunk_steps}")
        s = self.step
        if not (self._lint_done and self._memlint_done):
            args = (s.params, s.aux, s.opt_state, s._key, xs, ys)
            self._analyze(args)
        # one span per chunk dispatch (K steps, one XLA program):
        # dispatch is async, so the span measures host-side cost — the
        # thing chunking exists to amortize (no-op without a trace)
        with trace.span("train.chunk", steps=self.chunk_steps,
                        chunk=self.chunks_run):
            s.params, s.aux, s.opt_state, s._key, loss = \
                self._executor(s.params, s.aux, s.opt_state,
                               s._key, xs, ys)
        s._last = loss
        self.chunks_run += 1
        return loss

    def run_epoch(self, batches, on_chunk=None):
        """Drive one epoch: group ``batches`` (an iterable of ``(x,
        y)`` pairs — a DataLoader works as is) into K-step blocks
        through a :class:`DevicePrefetchRing`, dispatch one program
        per block, and fall back to the per-step fused path for the
        tail that does not fill a chunk.  ``on_chunk(record)`` runs at
        every chunk boundary (after the tail too) — the hook elastic
        checkpoint/eviction logic keys on.  Returns the per-chunk
        records ``[{"steps", "loss", "kind"}, ...]`` where ``loss`` is
        always the per-step mean over the record's steps."""
        # an epoch gets its own trace when sampling is on and nothing
        # upstream started one — the training-side analog of a request
        # trace: chunk dispatches and prefetch fill/drain land as
        # spans on one timeline (docs/observability.md)
        root = (trace.start_trace("train.epoch",
                                  chunk_steps=self.chunk_steps)
                if trace.current_span() is None else None)
        try:
            with trace.activate(root):
                return self._run_epoch(batches, on_chunk)
        finally:
            if root is not None:
                root.finish()

    def _run_epoch(self, batches, on_chunk):
        records = []
        if self.chunk_steps == 1:
            # degenerate case: the existing fused step IS the loop
            for x, y in batches:
                loss = self.step(x, y)
                self.tail_steps_run += 1
                rec = {"steps": 1, "loss": loss, "kind": "step"}
                records.append(rec)
                if on_chunk is not None:
                    on_chunk(rec)
            return records
        ring = DevicePrefetchRing(batches, self.chunk_steps)
        for block in ring:
            if block[0] == "chunk":
                _, xs, ys = block
                loss = self.run_chunk(xs, ys)
                rec = {"steps": self.chunk_steps, "loss": loss,
                       "kind": "chunk"}
            else:
                # epoch tail: reuse the per-step program — a partial
                # chunk must never compile a second loop executable
                tail = block[1]
                loss_sum = None
                for x, y in tail:
                    loss = self.step(x, y)
                    loss_sum = loss if loss_sum is None else loss_sum + loss
                    self.tail_steps_run += 1
                # per-step mean, same semantics as a chunk record
                rec = {"steps": len(tail), "loss": loss_sum / len(tail),
                       "kind": "tail"}
            records.append(rec)
            if on_chunk is not None:
                on_chunk(rec)
        return records

    def write_back(self):
        self.step.write_back()
