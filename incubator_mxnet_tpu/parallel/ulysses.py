"""Ulysses-style sequence parallelism: all-to-all head redistribution.

Alternative to ring attention for long sequences (DeepSpeed-Ulysses
pattern; see PAPERS.md): activations arrive sequence-sharded; an
all-to-all converts them to head-sharded (full sequence per device),
plain attention runs locally, and a second all-to-all restores sequence
sharding.  On TPU the all-to-alls ride ICI and cost ~2×activation size
— cheaper than ring when heads ≥ sp degree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["ulysses_attention"]


def _ulysses_sharded(q, k, v, axis_name, causal):
    """q,k,v: (B, H, T_local, D) with H full, T sharded."""
    nsp = lax.axis_size(axis_name)
    B, H, T, D = q.shape
    assert H % nsp == 0, "heads must divide sp degree for Ulysses"

    def seq2head(x):
        # (B,H,Tl,D) → split heads into nsp groups, all-to-all so each
        # rank gets H/nsp heads with the FULL sequence.  The received
        # source-rank axis must land BEFORE T (chunk-major) so that
        # merging (nsp, T) reconstructs the global sequence order —
        # head2seq then splits S the same chunk-major way, making the
        # two transforms exact inverses.
        x = x.reshape(B, nsp, H // nsp, T, D)
        x = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                           tiled=False)           # (B, H/nsp, nsp, T, D)
        return x.reshape(B, H // nsp, nsp * T, D)

    def head2seq(x):
        x = x.reshape(B, H // nsp, nsp, T, D)
        x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                           tiled=False)           # (B, nsp, H/nsp, T, D)
        return x.reshape(B, H, T, D)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    scale = 1.0 / (D ** 0.5)
    logits = jnp.einsum("bhtd,bhsd->bhts", qh, kh,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        S = logits.shape[-1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(qh.dtype)
    out = jnp.einsum("bhts,bhsd->bhtd", probs, vh)
    return head2seq(out)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name="sp", causal=False,
                      qkv_spec=P("dp", None, "sp", None)):
    fn = functools.partial(_ulysses_sharded, axis_name=axis_name,
                           causal=causal)
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False)
    return mapped(q, k, v)
