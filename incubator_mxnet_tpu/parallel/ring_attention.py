"""Ring attention: exact attention over sequence-sharded inputs.

New capability vs the reference (SURVEY.md §5.7: it has none — max
sequence length bounded by one device's memory).  Design follows the
blockwise-ring formulation (Liu et al., ring attention; see PAPERS.md):
Q stays put per sp-shard; K/V blocks rotate around the sp ring via
``ppermute`` while each rank accumulates the streaming-softmax partial
(max, sum, weighted values).  ICI makes the rotation overlap with the
local attention block — the collective cost hides behind the matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ["ring_attention", "_ring_attention_sharded"]


def _local_block(q, k, v, m_prev, l_prev, o_prev, scale, mask=None):
    """One streaming-softmax accumulation step (flash-attention algebra)."""
    logits = jnp.einsum("bhtd,bhsd->bhts", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    m_cur = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    correction = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = correction * l_prev + jnp.sum(p, axis=-1)
    o_new = correction[..., None] * o_prev + \
        jnp.einsum("bhts,bhsd->bhtd", p, v.astype(p.dtype))
    return m_new, l_new, o_new


def _ring_attention_sharded(q, k, v, axis_name, causal=False):
    """Body run inside shard_map: q,k,v are (B, H, T_local, D) shards."""
    nsp = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    B, H, T, D = q.shape

    m = jnp.full((B, H, T), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, H, T), jnp.float32)
    o = jnp.zeros((B, H, T, D), jnp.float32)

    def step(carry, i):
        k_blk, v_blk, m_c, l_c, o_c = carry
        src_idx = (my_idx - i) % nsp  # which shard this K/V block came from
        if causal:
            q_pos = my_idx * T + jnp.arange(T)[:, None]
            k_pos = src_idx * T + jnp.arange(T)[None, :]
            mask = (q_pos >= k_pos)[None, None]
        else:
            mask = None
        m_c, l_c, o_c = _local_block(q, k_blk, v_blk, m_c, l_c, o_c, scale,
                                     mask)
        perm = [(j, (j + 1) % nsp) for j in range(nsp)]
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m_c, l_c, o_c), None

    (k, v, m, l, o), _ = lax.scan(step, (k, v, m, l, o), jnp.arange(nsp))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis_name="sp", causal=False,
                   qkv_spec=P("dp", None, "sp", None)):
    """Exact attention with sequence sharded over `axis_name`.

    q,k,v: (B, H, T, D) global arrays (sharded or not); returns same
    shape, sequence-sharded layout preserved.
    """
    fn = functools.partial(_ring_attention_sharded, axis_name=axis_name,
                           causal=causal)
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False)
    return mapped(q, k, v)
