"""Routed feed-forward layers for a chip that holds a share of the experts
(expert parallelism), and the rotary embedding of the decoders that use
them.  Three registered ops:

* ``rope``       — rotary embedding over interleaved pairs, or over the
  two halves.
* ``moe_route``  — the router: float32 sigmoid scores over *all* experts,
  the ``top_k`` largest of score + bias, normalised gates, and the
  gradient-free bias update from this chip's load.
* ``moe_ffn``    — the held experts' feed-forward (SwiGLU, or ungated with
  ``relu(·)²``) over the rows routed to them.  The layer is told which
  experts it holds (``first``, ``count``); what the absent experts would
  have added is left out.

``moe_ffn`` drops nothing and its device work does not follow the routing.
All ``T·k`` assignments are ranked by expert; the rows of held experts are
gathered into a buffer of *static* size in which every expert's group is
padded to the row tile; every tile of the buffer is computed, real rows or
zeros, by one grouped matmul (``moe_experts``: a Pallas kernel whose weight
block is picked by a prefetched tile → expert map, or its XLA composition
— :func:`pallas_kernels.dispatch` routes), and the results are combined by
a gather.  So every shape, grid and trip count of a step is a function of
the configuration and the traffic alone.  If a step's rows outgrow the
buffer, further passes over the same code compute the rest (a
``while_loop`` whose body runs only then); the op counts such steps.  No
``(tokens, experts, capacity)`` tensor exists.

Trace names (docs/observability.md): kernel scopes ``rope``, ``moe_route``,
``moe_dispatch`` (ranking, gathers, combine) and ``moe_experts``;
``moe_plans`` says what each traced signature of ``moe_ffn`` was cut into.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import profiler as _profiler
from ..locks import named_lock
from . import pallas_kernels as pk
from .registry import register

__all__ = ["rope", "moe_route", "moe_ffn", "moe_experts", "buffer_rows",
           "moe_plans", "TILE"]

# Rows of one tile of the buffer: every held expert's group is padded to
# it, so a tile belongs to one expert.
TILE = 128
F32 = jnp.float32


def _precision(dtype):
    """float32 operands multiply at full precision (the float32 reference
    comparisons); bfloat16 ones as they are."""
    return jax.lax.Precision.HIGHEST if dtype == F32 else None


def _scoped(fn, *args):
    """``fn(*args)`` under its own name as a kernel scope, counted in
    ``kernel_routes`` as a composition that has no kernel."""
    return pk.dispatch(fn, fn, *args, unless="no_kernel")


# ======================================================================
# rotary embedding
# ======================================================================

@register("rope")
def rope(x, theta=10000.0, interleaved=True):
    """Rotary position embedding of ``x`` (B, T, heads, D) over interleaved
    pairs: the pair ``(x[2i], x[2i+1])`` of position ``t`` turns by the
    angle ``t · theta^(-2i/D)`` and stays where it was.  With
    ``interleaved=False`` the pairs are ``(x[i], x[i + D/2])``, the two
    halves (``rotate_half``).  The angles are float32; the result has
    ``x``'s dtype."""
    def rope(x):
        t, d = x.shape[1], x.shape[-1]
        inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
        angle = jnp.arange(t, dtype=F32)[:, None] * inv_freq
        if not interleaved:
            cos, sin = (jnp.tile(f(angle), 2)[None, :, None, :]
                        for f in (jnp.cos, jnp.sin))
            # (x1, x2) -> (-x2, x1): the halves change places
            turned = jnp.roll(x, d // 2, axis=-1).astype(F32) * jnp.where(
                jnp.arange(d) < d // 2, -1.0, 1.0)
            return (x.astype(F32) * cos + turned * sin).astype(x.dtype)
        cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)[None, :, None, :]
        sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)[None, :, None, :]
        # (x[2i], x[2i+1]) -> (-x[2i+1], x[2i]) as a signed permutation
        # matrix: exact in any dtype, and no lane-strided access
        even = jnp.arange(0, d, 2)
        turn = jnp.zeros((d, d), x.dtype).at[even + 1, even].set(-1) \
            .at[even, even + 1].set(1)
        turned = jnp.dot(x, turn,
                         preferred_element_type=F32,
                         precision=_precision(x.dtype))
        return (x.astype(F32) * cos + turned * sin).astype(x.dtype)

    return _scoped(rope, x)


# ======================================================================
# the router
# ======================================================================

@register("moe_route")
def moe_route(x, weight, bias, top_k=8, scale=1.0, gamma=0.0):
    """Sigmoid router with a selection bias (no auxiliary loss).

    ``x`` (T, H), ``weight`` (n_experts, H), ``bias`` (n_experts,) float32.
    Scores ``s = sigmoid(x·Wᵀ)`` are float32; the ``top_k`` experts with the
    largest ``s + bias`` are chosen (the bias takes part in the choice
    only); gates ``scale · s_sel / sum(s_sel)``.  Returns ``(idx (T, k)
    int32, gates (T, k) float32, new_bias, load)``: ``load`` counts this
    chip's assignments to every expert, and ``new_bias = bias + gamma ·
    sign(mean(load) − load)`` carries no gradient."""
    def moe_route(x, weight, bias):
        logits = jax.lax.dot_general(
            x, weight.astype(x.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=F32, precision=_precision(x.dtype))
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(jax.lax.stop_gradient(s) + bias, top_k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        gates = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        load = jnp.sum(idx.reshape(-1, 1) == jnp.arange(weight.shape[0]),
                       axis=0, dtype=F32)
        new_bias = bias + gamma * jnp.sign(jnp.mean(load) - load)
        return (idx.astype(jnp.int32), gates,
                jax.lax.stop_gradient(new_bias), load)

    return _scoped(moe_route, x, weight, bias.astype(F32))


# ======================================================================
# moe_experts: the grouped matmul, every tile computed
# ======================================================================

_NN = (((1,), (0,)), ((), ()))      # rows · W[e]
_NT = (((1,), (1,)), ((), ()))      # rows · W[e]ᵀ
_TN = (((0,), (0,)), ((), ()))      # rowsᵀ · rows, summed into its expert


def _col_block(n):
    """Columns of one block: the whole width where it is not whole lanes,
    else the largest of 512, 384, 256, 128 that divides it."""
    if n % 128:
        return n
    return max(b for b in (512, 384, 256, 128) if n % b == 0)


def _rows_kernel(te_ref, x_ref, w_ref, o_ref, *, dims):
    del te_ref                      # the index maps read it
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], dims, preferred_element_type=F32,
        precision=_precision(x_ref.dtype)).astype(o_ref.dtype)


def _weights_kernel(te_ref, x_ref, y_ref, o_ref, acc_ref):
    """Grid (column blocks, row tiles): the tiles of one expert follow one
    another, their products add up in ``acc_ref``, and the expert's block
    is written at its last tile."""
    i, n = pl.program_id(1), pl.num_programs(1)
    mine = te_ref[i]
    first = (i == 0) | (te_ref[jnp.maximum(i - 1, 0)] != mine)
    last = (i == n - 1) | (te_ref[jnp.minimum(i + 1, n - 1)] != mine)
    part = jax.lax.dot_general(
        x_ref[...], y_ref[...], _TN, preferred_element_type=F32,
        precision=_precision(x_ref.dtype))

    @pl.when(first)
    def _():
        acc_ref[...] = part

    @pl.when(jnp.logical_not(first))
    def _():
        acc_ref[...] += part

    @pl.when(last)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


_MOE_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=64 * 1024 * 1024,
    dimension_semantics=("parallel", "arbitrary"))


def moe_experts(a, b, tile_expert, kind, n_experts=None):
    """The grouped matmul over a buffer of ``TILE``-row tiles, tile ``t``
    belonging to expert ``tile_expert[t]`` (non-decreasing), as a Pallas
    kernel; every tile is computed.

    * ``kind="nn"``: rows ``a`` (R, K) · ``b[e]`` (E, K, N) → (R, N);
    * ``kind="nt"``: rows ``a`` (R, N) · ``b[e]ᵀ`` (E, K, N) → (R, K);
    * ``kind="tn"``: ``a`` (R, K)ᵀ · ``b`` (R, N), summed over each
      expert's tiles → (``n_experts``, K, N); the block of an expert with
      no tile is left as it was allocated (the caller zeroes it).
    """
    rows = a.shape[0]
    tiles = rows // TILE
    if kind == "tn":
        k, n = a.shape[1], b.shape[1]
        tn = _col_block(n)
        return pl.pallas_call(
            _weights_kernel,
            out_shape=jax.ShapeDtypeStruct((n_experts, k, n), a.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(n // tn, tiles),
                in_specs=[
                    pl.BlockSpec((TILE, k), lambda j, i, te: (i, 0)),
                    pl.BlockSpec((TILE, tn), lambda j, i, te: (i, j))],
                out_specs=pl.BlockSpec((None, k, tn),
                                       lambda j, i, te: (te[i], 0, j)),
                scratch_shapes=[pltpu.VMEM((k, tn), F32)]),
            interpret=pk.interpret_mode(), compiler_params=_MOE_PARAMS,
            name="moe_experts_bwd_dw",
        )(tile_expert, a, b)
    if kind == "nn":
        width, n = b.shape[1:]
        tn = _col_block(n)
        w_spec = pl.BlockSpec((None, width, tn),
                              lambda j, i, te: (te[i], 0, j))
        dims, name = _NN, "moe_experts_fwd"
    else:
        n, width = b.shape[1:]       # the result is b's first width wide
        tn = _col_block(n)
        w_spec = pl.BlockSpec((None, tn, width),
                              lambda j, i, te: (te[i], j, 0))
        dims, name = _NT, "moe_experts_bwd_dx"
    return pl.pallas_call(
        functools.partial(_rows_kernel, dims=dims),
        out_shape=jax.ShapeDtypeStruct((rows, n), a.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // tn, tiles),
            in_specs=[pl.BlockSpec((TILE, width), lambda j, i, te: (i, 0)),
                      w_spec],
            out_specs=pl.BlockSpec((TILE, tn), lambda j, i, te: (i, j))),
        interpret=pk.interpret_mode(), compiler_params=_MOE_PARAMS,
        name=name,
    )(tile_expert, a, b)


def _experts_xla(a, b, tile_expert, kind, n_experts=None):
    """The same contract in plain XLA: each tile against its expert's
    gathered weights; experts without a tile get zeros."""
    tiles = a.shape[0] // TILE
    at = a.reshape(tiles, TILE, a.shape[1])
    prec = _precision(a.dtype)
    if kind == "tn":
        parts = jnp.einsum("tmk,tmn->tkn", at,
                           b.reshape(tiles, TILE, b.shape[1]),
                           preferred_element_type=F32, precision=prec)
        return jax.ops.segment_sum(parts, tile_expert,
                                   num_segments=n_experts).astype(a.dtype)
    spec = "tmk,tkn->tmn" if kind == "nn" else "tmn,tkn->tmk"
    out = jnp.einsum(spec, at, b[tile_expert], preferred_element_type=F32,
                     precision=prec)
    return out.reshape(a.shape[0], -1).astype(a.dtype)


def _experts(a, b, tile_expert, kind, n_experts=None):
    return pk.dispatch(
        functools.partial(moe_experts, kind=kind, n_experts=n_experts),
        functools.partial(_experts_xla, kind=kind, n_experts=n_experts),
        a, b, tile_expert)


# ======================================================================
# moe_ffn: rank, gather, grouped feed-forward, combine — in passes
# ======================================================================

def buffer_rows(tokens, top_k, n_experts, count, factor):
    """Rows of the held experts' buffer: ``factor`` times the rows a
    balanced router sends to ``count`` of ``n_experts`` experts, in whole
    tiles, plus one tile of padding for every held expert."""
    mean = tokens * top_k * count / n_experts
    return (-(-int(factor * mean) // TILE) + count) * TILE


def _plan(idx, first, count, cap):
    """Rank all ``T·k`` assignments by held expert, with two sorts and no
    scatter.  Returns, as int32: ``order`` (assignments sorted by expert,
    those of absent experts last), ``row_of`` (every assignment's buffer
    row, counted over all passes; a row no pass reaches for absent
    experts), ``sizes`` (real rows of every held expert) and ``passes``
    (how many buffers the padded groups fill)."""
    n = idx.size
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    rank = jnp.arange(n, dtype=jnp.int32)
    sorted_key, order = jax.lax.sort((key, rank), num_keys=1, is_stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    starts, padded_starts, ends = _groups(sizes)
    group = jnp.minimum(sorted_key, count - 1)
    dest = jnp.where(sorted_key < count,
                     padded_starts[group] + rank - starts[group],
                     jnp.int32(2 ** 30))
    _, row_of = jax.lax.sort((order, dest), num_keys=1)
    passes = jnp.maximum(1, -(-ends[-1] // cap))
    return order, row_of, sizes, passes


def _groups(sizes):
    """Where every expert's rows start among the sorted assignments, and
    where its group, padded to whole tiles, starts and ends in the
    buffer."""
    padded = -(-sizes // TILE) * TILE
    ends = jnp.cumsum(padded)
    return jnp.cumsum(sizes) - sizes, ends - padded, ends


def _pass_rows(p, plan, cap):
    """Pass ``p``'s buffer: the assignment in every row (−1 in a padding
    row) and every tile's expert (the last one's for tiles past the last
    group, which hold no row)."""
    order, _, sizes = plan[:3]
    count = sizes.shape[0]
    starts, padded_starts, ends = _groups(sizes)
    row = p * cap + jnp.arange(cap, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, row[::TILE], side="right"),
        count - 1).astype(jnp.int32)
    mine = jnp.repeat(tile_expert, TILE)
    within = row - padded_starts[mine]
    src = jnp.where(
        within < sizes[mine],
        order[jnp.minimum(starts[mine] + within, order.shape[0] - 1)], -1)
    return src, tile_expert


def _gather_rows(rows, at, valid):
    """``rows[at]`` where ``valid``, zeros elsewhere."""
    return jnp.where(valid[:, None], rows[jnp.where(valid, at, 0)], 0)


def _combine(buffer, plan, p, cap, shape, weights=None):
    """Every token's sum over its assignments of their buffer rows in pass
    ``p`` (times ``weights``): a gather, no scatter."""
    at = plan[1].reshape(shape) - p * cap
    valid = (at >= 0) & (at < cap)
    picked = jnp.where(valid[..., None], buffer[jnp.where(valid, at, 0)],
                       0).astype(F32)                  # (T, k, width)
    if weights is not None:
        picked = picked * weights[..., None]
    return jnp.sum(picked, axis=1)


def _swiglu(h):
    gate, up = jnp.split(h.astype(F32), 2, axis=-1)
    sig = jax.nn.sigmoid(gate)
    return gate, up, sig, gate * sig


def _activate(h, activation):
    """The experts' hidden activation, float32, from their first product
    ``h``: ``silu(gate) · up`` of the two halves, or ``relu(h)²``."""
    if activation == "relu2":
        return jnp.square(jnp.maximum(h.astype(F32), 0.0))
    _, up, _, silu = _swiglu(h)
    return silu * up


def _activate_bwd(h, dact, activation):
    """``(act, dh)``: the activation again and the gradient by ``h`` from
    the gradient by the activation, both float32."""
    if activation == "relu2":
        pos = jnp.maximum(h.astype(F32), 0.0)
        return pos * pos, 2.0 * pos * dact
    gate, up, sig, silu = _swiglu(h)
    return silu * up, jnp.concatenate(
        [dact * up * sig * (1.0 + gate * (1.0 - sig)), dact * silu], axis=-1)


def _forward_pass(p, plan, x, gates, w_in, w_out, cap, activation):
    k = gates.shape[1]
    with jax.named_scope("moe_dispatch"):
        src, te = _pass_rows(p, plan, cap)
        xb = _gather_rows(x, src // k, src >= 0)
    h = _experts(xb, w_in, te, "nn")
    with jax.named_scope("moe_experts"):
        act = _activate(h, activation).astype(x.dtype)
    yb = _experts(act, w_out, te, "nn")
    with jax.named_scope("moe_dispatch"):
        return _combine(yb, plan, p, cap, gates.shape, gates)


def _backward_pass(p, plan, x, gates, w_in, w_out, dy, cap, activation):
    """Pass ``p``'s part of every gradient; the hidden activations are
    computed again, so a pass keeps nothing between forward and backward."""
    count, k = w_in.shape[0], gates.shape[1]
    dt = x.dtype
    with jax.named_scope("moe_dispatch"):
        src, te = _pass_rows(p, plan, cap)
        valid, tok = src >= 0, src // k
        xb = _gather_rows(x, tok, valid)
        dyb = _gather_rows(dy, tok, valid)
        gb = jnp.where(valid, gates.reshape(-1)[jnp.maximum(src, 0)], 0.0)
        present = jnp.any(te[None, :] == jnp.arange(count)[:, None],
                          axis=1)[:, None, None]
    h = _experts(xb, w_in, te, "nn")
    dact_unit = _experts(dyb, w_out, te, "nt")      # before the gate
    with jax.named_scope("moe_experts"):
        act, dh = _activate_bwd(h, dact_unit.astype(F32) * gb[:, None],
                                activation)
        dgate_rows = jnp.sum(dact_unit.astype(F32) * act, axis=-1)
        dh = dh.astype(dt)
        act_gated = (act * gb[:, None]).astype(dt)
    dw_out = jnp.where(present, _experts(act_gated, dyb, te, "tn", count), 0)
    dw_in = jnp.where(present, _experts(xb, dh, te, "tn", count), 0)
    dxb = _experts(dh, w_in, te, "nt")
    with jax.named_scope("moe_dispatch"):
        dx = _combine(dxb, plan, p, cap, gates.shape)
        at = plan[1].reshape(gates.shape) - p * cap
        ok = (at >= 0) & (at < cap)
        dgates = jnp.where(ok, dgate_rows[jnp.where(ok, at, 0)], 0.0)
    return dx, dgates, dw_in.astype(w_in.dtype), dw_out.astype(w_out.dtype)


def _over_passes(one_pass, passes):
    """``one_pass(0)``, and the sum with ``one_pass(p)`` for every further
    pass: a loop whose body runs only when rows outgrew the buffer."""
    def more(carry):
        p, total = carry
        return p + 1, jax.tree_util.tree_map(
            lambda t, u: t + u.astype(t.dtype), total, one_pass(p))

    return jax.lax.while_loop(lambda c: c[0] < passes, more,
                              (jnp.int32(1), one_pass(jnp.int32(0))))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _routed(x, gates, w_in, w_out, plan, cap, activation):
    return _routed_fwd(x, gates, w_in, w_out, plan, cap, activation)[0]


def _routed_fwd(x, gates, w_in, w_out, plan, cap, activation):
    y = _over_passes(
        lambda p: _forward_pass(p, plan, x, gates, w_in, w_out, cap,
                                activation),
        plan[3])
    return y.astype(x.dtype), (x, gates, w_in, w_out, plan)


def _routed_bwd(cap, activation, res, dy):
    x, gates, w_in, w_out, plan = res
    dx, dgates, dw_in, dw_out = _over_passes(
        lambda p: _backward_pass(p, plan, x, gates, w_in, w_out,
                                 dy.astype(x.dtype), cap, activation),
        plan[3])
    return (dx.astype(x.dtype), dgates.astype(gates.dtype), dw_in, dw_out,
            None)


_routed.defvjp(_routed_fwd, _routed_bwd)


_plans = {}
_plans_lock = named_lock("ops.moe_plans")


def moe_plans(reset=False):
    """``{signature: plan}`` of every ``moe_ffn`` call traced so far:
    ``tokens``, ``top_k``, ``n_experts``, ``held``, ``assignments`` (``tokens
    · top_k``, all of them ranked), ``buffer_rows`` and ``tile`` (every tile
    of the buffer is computed), ``width`` (what the experts read and write),
    ``hidden`` (their own) and ``activation``.  Counts signatures, not
    calls.  The ``moe_plans`` provider of ``profiler.dumps()``."""
    with _plans_lock:
        out = {sig: dict(plan) for sig, plan in sorted(_plans.items())}
        if reset:
            _plans.clear()
    return out


_profiler.register_stats_provider("moe_plans", moe_plans)


@register("moe_ffn")
def moe_ffn(x, idx, gates, w_in, w_out, n_experts=None, first=0,
            capacity_factor=1.5, activation="swiglu"):
    """The held experts' part of a routed feed-forward layer.

    ``x`` (T, H); ``idx`` (T, k) int32 over all ``n_experts``; ``gates``
    (T, k); ``w_in`` (count, H, 2·I) with gate and up projections side by
    side for ``activation="swiglu"``, (count, H, I) for the ungated
    ``"relu2"`` (``relu(x W_in)² W_out``); ``w_out`` (count, I, H): the
    weights of experts ``first`` … ``first + count − 1``.  Returns ``(y (T,
    H), stats)`` with ``y[t] = Σ_j gates[t, j] · Expert_{idx[t, j]}(x[t])``
    over held experts only and ``stats`` float32 ``[real rows held, rows of
    the buffer, passes taken, largest held expert's rows / a balanced
    expert's]``."""
    if activation not in ("swiglu", "relu2"):
        raise ValueError(f"moe_ffn: activation {activation!r} is neither "
                         "'swiglu' nor 'relu2'")
    count = w_in.shape[0]
    n_experts = n_experts or count
    cap = buffer_rows(x.shape[0], idx.shape[1], n_experts, count,
                      capacity_factor)
    with _plans_lock:
        _plans[f"t{x.shape[0]} k{idx.shape[1]} e{count}/{n_experts} "
               f"w{x.shape[1]} i{w_out.shape[1]} {activation} {x.dtype}"] = {
            "tokens": x.shape[0], "top_k": idx.shape[1],
            "n_experts": n_experts, "held": count, "assignments": idx.size,
            "buffer_rows": cap, "tile": TILE, "width": x.shape[1],
            "hidden": w_out.shape[1], "activation": activation}
    with jax.named_scope("moe_dispatch"):
        plan = _plan(idx, first, count, cap)
    y = _routed(x, gates, w_in, w_out, plan, cap, activation)
    sizes = plan[2].astype(F32)
    balanced = idx.size / n_experts
    stats = jnp.stack([jnp.sum(sizes), jnp.float32(cap),
                       plan[3].astype(F32), jnp.max(sizes) / balanced])
    return y, jax.lax.stop_gradient(stats)
