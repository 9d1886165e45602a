"""Pipeline parallelism: collective-permute microbatch pipeline.

New capability vs the reference (its closest analog is group2ctx coarse
layer placement, symbol.py:1608).  GPipe-style schedule inside
``shard_map`` over the 'pp' axis: each rank holds one stage's params;
microbatch activations flow stage→stage via ``ppermute``; ranks idle on
the bubble steps (output masked), exactly the standard TPU pipeline
recipe (scaling-book pipelining chapter).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["pipeline_forward"]


def _pipeline_sharded(stage_params, microbatches, stage_fn, axis_name,
                      strip_stage_axis):
    """Run inside shard_map over 'pp'.

    stage_params: this rank's stage parameters (leading pp axis stripped).
    microbatches: (n_micro, mb_size, ...) — replicated input; rank 0
    feeds the pipeline, the last rank's outputs are collected.
    """
    npp = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    if strip_stage_axis:
        # one layer per stage: drop the local (size-1) slice axis so
        # stage_fn sees per-stage params; multi-layer stages keep the
        # stacked slice and stage_fn iterates it
        stage_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
    n_micro = microbatches.shape[0]
    total_steps = n_micro + npp - 1
    mb_shape = microbatches.shape[1:]

    state = jnp.zeros(mb_shape, microbatches.dtype)  # activation in flight
    outputs = jnp.zeros((n_micro,) + mb_shape, microbatches.dtype)

    def step(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (if in range)
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        injected = jnp.where(rank == 0,
                             microbatches[mb_idx],
                             state)
        out = stage_fn(stage_params, injected)
        # last stage emits result for microbatch t-(npp-1)
        emit_idx = t - (npp - 1)
        valid = jnp.logical_and(rank == npp - 1,
                                jnp.logical_and(emit_idx >= 0,
                                                emit_idx < n_micro))
        outputs = lax.cond(
            valid,
            lambda o: lax.dynamic_update_index_in_dim(
                o, out, jnp.clip(emit_idx, 0, n_micro - 1), axis=0),
            lambda o: o,
            outputs)
        # shift activations to next stage
        perm = [(i, (i + 1) % npp) for i in range(npp)]
        state = lax.ppermute(out, axis_name, perm)
        return (state, outputs), None

    (state, outputs), _ = lax.scan(step, (state, outputs),
                                   jnp.arange(total_steps))
    # broadcast last-stage outputs to all pp ranks so out_specs can be
    # replicated over pp
    outputs = lax.psum(
        jnp.where(rank == npp - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)
    return outputs


def pipeline_forward(stacked_params, x, stage_fn, mesh: Mesh, n_micro=4,
                     axis_name="pp",
                     x_spec=P("dp"), param_spec=P("pp")):
    """Run ``stage_fn`` as an npp-stage pipeline.

    stacked_params: pytree whose leaves have leading axis = npp (one
    slice per stage).  x: (batch, ...) — reshaped into n_micro
    microbatches.  Returns stage-npp output with batch restored.
    """
    B = x.shape[0]
    assert B % n_micro == 0
    micro = x.reshape(n_micro, B // n_micro, *x.shape[1:])

    npp = mesh.shape[axis_name]
    leading = {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(
        stacked_params)}
    assert len(leading) == 1, "stacked_params leaves must share the stage axis"
    stack = leading.pop()
    assert stack % npp == 0, \
        f"layer stack ({stack}) must divide the pp axis ({npp})"
    fn = functools.partial(_pipeline_sharded, stage_fn=stage_fn,
                           axis_name=axis_name,
                           strip_stage_axis=(stack == npp))
    param_specs = jax.tree_util.tree_map(lambda _: param_spec, stacked_params)
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False)
    out = mapped(stacked_params, micro)
    return out.reshape(B, *out.shape[2:])
