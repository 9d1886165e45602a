"""State-space layers of the Mamba-2 kind: the selective recurrence in its
chunked form, with a backward pass of its own, and the short causal
convolution that stands before it.  Two registered ops:

* ``causal_conv1d`` — depthwise causal convolution over the sequence with a
  bias and SiLU; zero state at the start of a sequence.
* ``ssd_scan``      — ``S_t = exp(Δ_t A) · S_{t−1} + Δ_t · x_t B_tᵀ``,
  ``y_t = S_t C_t + D · x_t``: a ``P × N`` state a head, a scalar decay a
  head, ``B`` and ``C`` shared by the heads of a group.

``ssd_scan`` never runs the recurrence a position at a time.  The sequence
is cut into chunks of ``chunk`` positions.  With ``a_t = Δ_t A`` and ``cum``
its running sum inside a chunk (float32, every exponent ≤ 0):

* inside a chunk ``Y = (L ⊙ C Bᵀ)(Δ ⊙ x)`` with ``L_ts = exp(cum_t −
  cum_s)`` for ``t ≥ s``, else 0;
* a chunk's own state ``Σ_s exp(cum_end − cum_s) Δ_s x_s B_sᵀ``;
* between chunks ``S_c = exp(cum_end) S_{c−1} + own_c`` (a ``lax.scan`` over
  the chunks: elementwise on one ``(heads, P, N)`` state);
* ``Y += exp(cum_t) · S_{c−1} C_t``, and ``D · x``.

Every product is a batched matmul over (batch, chunk, group).  The backward
pass is the mirror recurrence over the chunks in reverse.  It keeps the
inputs and each chunk's *entry state* (float32, ``T / chunk`` states of
``heads · P · N``) and nothing of the inside of a chunk: the decay matrices
and the scores are computed again from the inputs, which costs the
intra-chunk products (a fifth of the forward pass) once more and saves
two ``(heads, chunk, chunk)`` float32 tensors a chunk.  A sequence that is
no multiple of the chunk is padded with ``Δ = 0`` rows, which leave the
state alone.

Both ops run behind :func:`pallas_kernels.dispatch` under the kernel scopes
``ssd_scan`` and ``causal_conv1d`` as compositions that have no kernel yet
(``xla:no_kernel`` in ``kernel_routes``); ``ssm_plans`` says what each traced
signature of the scan was cut into (docs/observability.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import profiler as _profiler
from ..locks import named_lock
from . import pallas_kernels as pk
from .moe_ops import _precision
from .registry import register

__all__ = ["causal_conv1d", "ssd_scan", "ssm_plans"]

F32 = jnp.float32


def _einsum(spec, a, b):
    """Both operands in ``a``'s dtype, accumulated in float32."""
    return jnp.einsum(spec, a, b.astype(a.dtype), preferred_element_type=F32,
                      precision=_precision(a.dtype))


# ======================================================================
# the short convolution
# ======================================================================

@register("causal_conv1d")
def causal_conv1d(x, weight, bias):
    """``silu(bias + Σ_k weight[:, k] · x_{t−K+1+k})`` for ``x`` (B, T, C),
    ``weight`` (C, K) and ``bias`` (C,): each channel its own ``K`` taps
    over its own past, zeros before the first position.  Summed in
    float32; the result has ``x``'s dtype."""
    def causal_conv1d(x, weight, bias):
        taps, t = weight.shape[1], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(F32)
        w = weight.astype(F32)
        acc = bias.astype(F32) + sum(
            padded[:, k:k + t] * w[:, k] for k in range(taps))
        return jax.nn.silu(acc).astype(x.dtype)

    return pk.dispatch(causal_conv1d, causal_conv1d, x, weight, bias,
                       unless="no_kernel")


# ======================================================================
# the chunked scan.  Inside, everything is cut into chunks and the heads
# into (group, head of the group): x (b, c, q, g, j, p), dt (b, c, q, g, j)
# float32, a and d (g, j), B and C (b, c, q, g, n), states (b, c, g, j, p, n).
# ======================================================================

def _chunk_states(x, dt, a, b_):
    """Each chunk's own state ``Σ_s exp(cum_end − cum_s) Δ_s x_s B_sᵀ``,
    float32, and its whole decay ``exp(cum_end)`` (b, c, g, j)."""
    cum = jnp.cumsum(dt * a, axis=2)
    total = cum[:, :, -1]
    weight = jnp.exp(total[:, :, None] - cum) * dt
    xw = (x.astype(F32) * weight[..., None]).astype(x.dtype)
    return _einsum("bcqgjp,bcqgn->bcgjpn", xw, b_), jnp.exp(total)


def _entry_states(own, decay):
    """``S_c = decay_c · S_{c−1} + own_c`` from ``S = 0``: the state each
    chunk starts from (the state after the last chunk has no reader)."""
    def step(state, chunk):
        own_c, decay_c = chunk
        return decay_c[..., None, None] * state + own_c, state

    _, entry = jax.lax.scan(step, jnp.zeros_like(own[:, 0]),
                            (jnp.moveaxis(own, 1, 0),
                             jnp.moveaxis(decay, 1, 0)))
    return jnp.moveaxis(entry, 0, 1)


def _chunk_outputs(x, dt, a, b_, c_, d, entry):
    """``y`` of every chunk from its inputs and its entry state."""
    q = x.shape[2]
    cum = jnp.cumsum(dt * a, axis=2)
    by_head = jnp.moveaxis(cum, 2, -1)                      # (b, c, g, j, q)
    ahead = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        ahead, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    scores = _einsum("bcqgn,bcsgn->bcgqs", c_, b_)
    mixed = (scores[:, :, :, None] * decay).astype(x.dtype)
    xdt = (x.astype(F32) * dt[..., None]).astype(x.dtype)
    y = (_einsum("bcgjqs,bcsgjp->bcqgjp", mixed, xdt)
         + _einsum("bcqgn,bcgjpn->bcqgjp", c_, entry)
         * jnp.exp(cum)[..., None]
         + d[..., None] * x.astype(F32))
    return y.astype(x.dtype)


@jax.custom_vjp
def _ssd_chunked(x, dt, a, b_, c_, d):
    return _ssd_fwd(x, dt, a, b_, c_, d)[0]


def _ssd_fwd(x, dt, a, b_, c_, d):
    entry = _entry_states(*_chunk_states(x, dt, a, b_))
    return (_chunk_outputs(x, dt, a, b_, c_, d, entry),
            (x, dt, a, b_, c_, d, entry))


def _ssd_bwd(res, dy):
    x, dt, a, b_, c_, d, entry = res
    _, pull_outputs = jax.vjp(_chunk_outputs, *res)
    dx, ddt, da, db, dc, dd, d_entry = pull_outputs(dy)
    (_, decay), pull_states = jax.vjp(_chunk_states, x, dt, a, b_)

    # the mirror recurrence: what the loss feels of the state a chunk
    # leaves behind is what it feels of the next chunk's entry state,
    # directly and through that chunk's decay
    def step(felt, chunk):
        d_entry_c, decay_c = chunk
        return d_entry_c + decay_c[..., None, None] * felt, felt

    _, d_own = jax.lax.scan(step, jnp.zeros_like(entry[:, 0]),
                            (jnp.moveaxis(d_entry, 1, 0),
                             jnp.moveaxis(decay, 1, 0)), reverse=True)
    d_own = jnp.moveaxis(d_own, 0, 1)
    d_decay = jnp.sum(entry * d_own, axis=(-2, -1))
    dx2, ddt2, da2, db2 = pull_states((d_own, d_decay))
    return dx + dx2, ddt + ddt2, da + da2, db + db2, dc, dd


_ssd_chunked.defvjp(_ssd_fwd, _ssd_bwd)


_plans = {}
_plans_lock = named_lock("ops.ssm_plans")


def ssm_plans(reset=False):
    """``{signature: plan}`` of every ``ssd_scan`` call traced so far:
    ``chunk``, ``chunks`` a sequence, ``heads_a_step`` (the composition
    takes all heads of a chunk in one batched product), ``state_bytes_saved``
    (the entry states the backward pass keeps) and ``padded_rows`` (the
    ``Δ = 0`` rows that fill the last chunk).  Counts signatures, not calls.
    The ``ssm_plans`` provider of ``profiler.dumps()``."""
    with _plans_lock:
        out = {sig: dict(plan) for sig, plan in sorted(_plans.items())}
        if reset:
            _plans.clear()
    return out


_profiler.register_stats_provider("ssm_plans", ssm_plans)


@register("ssd_scan")
def ssd_scan(x, dt, A, B, C, D, chunk=128):
    """The selective state-space recurrence (module docstring) over ``x``
    (batch, T, heads, P) with ``dt`` (batch, T, heads) the step sizes ``Δ ≥
    0``, ``A`` (heads,) negative, ``B`` and ``C`` (batch, T, groups, N) and
    the skip ``D`` (heads,); returns ``y`` like ``x``.  ``dt``, ``A`` and
    ``D``, the decays and the running state are float32 whatever ``x`` is;
    the products take ``x``'s dtype and accumulate in float32."""
    batch, t, heads, p = x.shape
    groups, n = B.shape[-2:]
    if heads % groups:
        raise ValueError(f"ssd_scan: {heads} heads do not divide into "
                         f"{groups} groups")
    chunks = -(-t // chunk)
    padded = chunks * chunk - t
    with _plans_lock:
        _plans[f"b{batch} t{t} h{heads}x{p} g{groups} n{n} {x.dtype}"] = {
            "chunk": chunk, "chunks": chunks, "heads_a_step": heads,
            "state_bytes_saved": 4 * batch * chunks * heads * p * n,
            "padded_rows": padded}

    def ssd_scan(x, dt, a, b_, c_, d):
        def cut(v, *tail):
            v = jnp.pad(v, ((0, 0), (0, padded)) + ((0, 0),) * (v.ndim - 2))
            return v.reshape((batch, chunks, chunk) + (tail or v.shape[2:]))

        by_group = (groups, heads // groups)
        y = _ssd_chunked(cut(x, *by_group, p),
                         cut(dt.astype(F32), *by_group),
                         a.astype(F32).reshape(by_group), cut(b_), cut(c_),
                         d.astype(F32).reshape(by_group))
        return y.reshape((batch, chunks * chunk, heads, p))[:, :t]

    return pk.dispatch(ssd_scan, ssd_scan, x, dt, A, B, C, D,
                       unless="no_kernel")
