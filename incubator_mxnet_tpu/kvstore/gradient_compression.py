"""2-bit gradient compression with error feedback.

Reference: src/kvstore/gradient_compression.h:38-131 (.cc/.cu kernels).
TPU re-design: the quantize/dequantize round-trip is a fused XLA kernel;
residual (error-feedback) state is kept per-key on device; and for
data-parallel sync the compressed codes actually cross the wire —
``make_compressed_allreduce`` packs four 2-bit codes per uint8 and
all-gathers the uint8 buffer over the mesh axis (16× less collective
traffic than fp32), dequantizing after the collective.  The reference
packs 16 codes per float32 on the push path (gradient_compression.cc
Quantize2BitKernel); same 2 bits/element density, same
{-threshold, 0, +threshold} codebook, same error-feedback recurrence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


__all__ = ["GradientCompression", "make_compressed_allreduce"]


class GradientCompression:
    def __init__(self, type="2bit", threshold=0.5):
        if type not in ("2bit", "1bit", "none"):
            raise ValueError(f"unsupported compression type {type}")
        self.type = type
        self.threshold = float(threshold)
        self._residual: dict = {}

        @jax.jit  # mxlint: disable=MX-DONATE001(grad is the caller's live gradient and the residual read from self._residual stays bound until the returned one replaces it)
        def _round_trip_2bit(grad, residual, threshold):
            acc = grad + residual
            q = jnp.where(acc >= threshold, threshold,
                          jnp.where(acc <= -threshold, -threshold, 0.0))
            return q, acc - q

        @jax.jit  # mxlint: disable=MX-DONATE001(grad is the caller's live gradient and the residual read from self._residual stays bound until the returned one replaces it)
        def _round_trip_1bit(grad, residual, threshold):
            acc = grad + residual
            q = jnp.where(acc >= 0, threshold, -threshold)
            return q, acc - q

        self._rt2 = _round_trip_2bit
        self._rt1 = _round_trip_1bit

    def compress_decompress(self, grad, key=None):
        """Quantize-then-dequantize with error feedback (what the wire
        round trip computes end-to-end)."""
        if self.type == "none":
            return grad
        k = key if key is not None else (grad.shape, str(grad.dtype))
        residual = self._residual.get(k)
        if residual is None:
            residual = jnp.zeros_like(grad)
        fn = self._rt2 if self.type == "2bit" else self._rt1
        q, new_residual = fn(grad, residual, self.threshold)
        self._residual[k] = new_residual
        return q


def _quantize_2bit(acc, threshold):
    """(n,) float → packed uint8 codes, 4 per byte.

    Codebook (reference gradient_compression.cc Quantize2BitKernel):
    0 → 0, 1 → +threshold, 2 → -threshold.
    """
    codes = jnp.where(acc >= threshold, 1,
                      jnp.where(acc <= -threshold, 2, 0)).astype(jnp.uint8)
    n = codes.shape[0]
    pad = (-n) % 4
    codes = jnp.pad(codes, (0, pad))
    codes = codes.reshape(-1, 4)
    shifts = jnp.array([0, 2, 4, 6], jnp.uint8)
    return jnp.sum(codes << shifts, axis=1).astype(jnp.uint8)


def _dequantize_2bit(packed, n, threshold, dtype):
    shifts = jnp.array([0, 2, 4, 6], jnp.uint8)
    codes = (packed[:, None] >> shifts) & jnp.uint8(3)
    codes = codes.reshape(-1)[:n]
    return jnp.where(codes == 1, threshold,
                     jnp.where(codes == 2, -threshold, 0.0)).astype(dtype)


def make_compressed_allreduce(mesh, axis_name="dp", threshold=0.5):
    """Build ``fn(grad, residual) -> (mean_grad, new_residual)`` whose
    cross-device traffic is 2-bit-packed uint8 (16× less than fp32).

    Runs under ``shard_map`` over ``axis_name``: each rank quantizes its
    local gradient (+residual carry-over), the **packed uint8 codes**
    are all-gathered over the mesh axis — that is the only collective,
    so the wire dtype really is uint8 — and every rank dequantizes and
    averages the gathered codes.  Error feedback keeps what quantization
    dropped for the next step (reference gradient_compression.h:38-131
    semantics, re-laid onto an ICI collective instead of a PS push).

    Works on any pytree of equal-sharded (replicated over axis_name)
    gradients.
    """
    nranks = mesh.shape[axis_name]

    def _one(grad, residual):
        shape, dtype = grad.shape, grad.dtype
        flat = grad.reshape(-1).astype(jnp.float32)
        acc = flat + residual.reshape(-1).astype(jnp.float32)
        packed = _quantize_2bit(acc, threshold)
        q_local = _dequantize_2bit(packed, flat.shape[0], threshold,
                                   jnp.float32)
        new_residual = (acc - q_local).reshape(shape).astype(dtype)
        gathered = lax.all_gather(packed, axis_name)      # uint8 on wire
        total = jnp.zeros_like(flat)
        for r in range(nranks):
            total = total + _dequantize_2bit(gathered[r], flat.shape[0],
                                             threshold, jnp.float32)
        return (total / nranks).reshape(shape).astype(dtype), new_residual

    def body(grads, residuals):
        # leaves arrive as (1, ...): this rank's slice of the stacked
        # per-rank gradient/residual trees
        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_r = treedef.flatten_up_to(residuals)
        outs = [_one(g[0], r[0]) for g, r in zip(flat_g, flat_r)]
        mean = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
        res = jax.tree_util.tree_unflatten(treedef,
                                           [o[1][None] for o in outs])
        return mean, res

    from jax.sharding import PartitionSpec as P
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(), P(axis_name)),
        check_vma=False)
    return jax.jit(mapped)  # mxlint: disable=MX-DONATE001(grad/residual trees are caller-held — callers re-run the sync on the same gradients; the donating surface is the compressed dp train step below)


def make_compressed_dp_train_step(loss_fn, mesh, lr=0.1, axis_name="dp",
                                  threshold=0.5):
    """Data-parallel SGD step whose gradient sync is 2-bit compressed.

    ``step(params, residuals, batch) -> (params, residuals, loss)``:
    batch sharded over ``axis_name``; each rank computes its local
    gradient, quantizes (+error feedback), all-gathers **uint8** codes
    (the only cross-rank traffic), dequantizes, averages, and applies
    SGD.  Params replicated; residuals carry a leading per-rank axis
    sharded over ``axis_name``.
    """
    nranks = mesh.shape[axis_name]

    def body(params, residuals, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_r = treedef.flatten_up_to(residuals)
        new_params_flat = []
        new_res_flat = []
        for g, r, p in zip(flat_g, flat_r,
                           jax.tree_util.tree_leaves(params)):
            shape, dtype = g.shape, g.dtype
            flat = g.reshape(-1).astype(jnp.float32)
            acc = flat + r[0].reshape(-1).astype(jnp.float32)
            packed = _quantize_2bit(acc, threshold)
            q_local = _dequantize_2bit(packed, flat.shape[0], threshold,
                                       jnp.float32)
            new_res_flat.append((acc - q_local).reshape(shape)
                                .astype(dtype)[None])
            gathered = lax.all_gather(packed, axis_name)  # uint8 on wire
            total = jnp.zeros_like(flat)
            for i in range(nranks):
                total = total + _dequantize_2bit(
                    gathered[i], flat.shape[0], threshold, jnp.float32)
            mean_g = (total / nranks).reshape(shape)
            new_params_flat.append(
                (p.astype(jnp.float32) - lr * mean_g).astype(p.dtype))
        new_params = jax.tree_util.tree_unflatten(treedef, new_params_flat)
        new_res = jax.tree_util.tree_unflatten(treedef, new_res_flat)
        loss_mean = lax.pmean(loss, axis_name)
        return new_params, new_res, loss_mean

    from jax.sharding import PartitionSpec as P
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axis_name), P(axis_name)),
        out_specs=(P(), P(axis_name), P()),
        check_vma=False)
    # params and residuals are pure carry state (`params, residuals,
    # loss = step(params, residuals, batch)`): donate both so the
    # update aliases them in place; the batch (arg 2) is caller-held
    return jax.jit(mapped, donate_argnums=(0, 1))
