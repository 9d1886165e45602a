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
``ssd_scan`` and ``causal_conv1d``, and both have two sides: the composition
(the CPU, a mesh, shapes the kernels do not take, and the tests' parity
reference) and a Pallas pair.  The scan's, ``ssd_scan_fwd`` and
``ssd_scan_bwd``, computes the same products with the same casts and keeps
the same float32 entry states, but never writes a chunk's decay matrix, its
scores or their cotangents to HBM.  The convolution's, ``causal_conv1d_fwd``
and ``causal_conv1d_bwd``, reads ``x`` (and ``dy``) once and writes ``y`` (or
``dx``) once: no padded float32 copy, no sum before SiLU kept for the
backward, no product of a tap in HBM; the taps' and the bias's gradients are
summed in the core.  Which side a call takes goes by its shape and dtype
alone (:class:`_ScanPlan`, :class:`_ConvPlan`); ``ssm_plans`` says what each
traced signature of either op got (docs/observability.md).
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
from .moe_ops import _precision
from .registry import register

__all__ = ["causal_conv1d", "ssd_scan", "ssm_plans"]

F32 = jnp.float32


def _einsum(spec, a, b):
    """Both operands in ``a``'s dtype, accumulated in float32."""
    return jnp.einsum(spec, a, b.astype(a.dtype), preferred_element_type=F32,
                      precision=_precision(a.dtype))


_plans = {}
_plans_lock = named_lock("ops.ssm_plans")


def ssm_plans(reset=False):
    """``{signature: plan}`` of every ``ssd_scan`` and ``causal_conv1d``
    call traced so far.  A scan's (``b1 t4096 h128x64 g8 n128 bfloat16``):
    ``chunk``, ``chunks`` a sequence, ``route`` (``kernel``, or the
    ``xla:<why>`` that :func:`pallas_kernels.dispatch` counted),
    ``heads_a_step`` (a group's heads in a step of the kernels; the
    composition takes all heads of a chunk in one batched product),
    ``grid_steps_fwd`` / ``grid_steps_bwd`` and ``vmem_bytes`` (the larger
    bill of the two kernels; 0 on the composition), ``state_bytes_saved``
    (the entry states the backward pass keeps, on either route) and
    ``padded_rows`` (the ``Δ = 0`` rows that fill the last chunk).  A
    convolution's (``conv b1 t4096 c10240 k4 bfloat16``): ``route``,
    ``taps``, ``channels_a_step`` (a lane tile in a step of the kernels, all
    of them on the composition), ``grid_steps_fwd`` / ``grid_steps_bwd``,
    ``vmem_bytes`` and ``padded_rows`` (the zero rows that fill the last
    turn of a step's walk).  Counts signatures, not calls.  The
    ``ssm_plans`` provider of ``profiler.dumps()``."""
    with _plans_lock:
        out = {sig: dict(plan) for sig, plan in sorted(_plans.items())}
        if reset:
            _plans.clear()
    return out


_profiler.register_stats_provider("ssm_plans", ssm_plans)


def _note_plan(pn):
    """The ``ssm_plans`` entry of the signature being traced."""
    with _plans_lock:
        _plans[pn.signature()] = pn.stats(pk.route(pn.why_not))


# ======================================================================
# the short convolution.  Its kernel pair takes one lane tile of channels a
# grid step over its whole sequence, grid (batch, tile): ``x`` (b, T, C) is
# read as it lies, a block of (T rows, 128 lanes).  A step walks its block
# ``_CONV_ROWS`` positions a turn over a float32 copy of the block in VMEM
# with ``_CONV_HALO`` rows of zeros before it, so that tap ``k`` of a turn
# is a plain load ``K − 1 − k`` rows up (Mosaic addresses VMEM by the row;
# only a block one lane tile wide may be read at an unaligned row, which is
# why a step is no wider).  Backward the walk runs from the last turn to the
# first over a second copy, what the loss feels of the sum before SiLU, with
# the zeros after it.
# ======================================================================

_CONV_VMEM = 16 * 1024 * 1024
_CONV_VMEM_MOST = 48 * 1024 * 1024
_CONV_VMEM_OWN = 2 * 1024 * 1024
_CONV_LANES = 128       # channels a grid step: one lane tile
_CONV_ROWS = 128        # positions a turn of a step's walk over the sequence
_CONV_HALO = 8          # float32 rows of zeros before (and after) a sequence
_CONV_TAPS_MOST = 7     # taps and the bias are the rows of one (8, C) block


class _ConvPlan:
    """What one ``causal_conv1d`` signature is cut into, and whether the
    kernel pair takes it (``why_not`` is None) or the composition does
    (``"shape"`` or ``"dtype"``: the ``unless=`` of the dispatch).  The pair
    takes channels that are whole lane tiles, at most ``_CONV_TAPS_MOST``
    taps, bfloat16 or float32, and a sequence whose blocks — the whole
    sequence of ``_CONV_LANES`` channels a step, pipelined, and its float32
    copies — fit the VMEM bill."""

    def __init__(self, batch, t, channels, taps, dtype):
        self.batch, self.t, self.channels, self.taps = batch, t, channels, taps
        self.dtype = jnp.dtype(dtype)
        self.rows = -(-t // _CONV_ROWS) * _CONV_ROWS
        self.padded = self.rows - t
        wide = self.rows * _CONV_LANES * self.dtype.itemsize
        copy = 4 * (self.rows + _CONV_HALO) * _CONV_LANES
        # a kernel's bill: its pipelined blocks twice, its float32 copies of
        # a block (x; in the backward also what the loss feels of the sum
        # before SiLU) once, and the step's own values
        self.vmem_fwd = 2 * 2 * wide + copy + _CONV_VMEM_OWN
        self.vmem_bwd = 2 * 3 * wide + 2 * copy + _CONV_VMEM_OWN
        if self.dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
            self.why_not = "dtype"
        elif (channels % _CONV_LANES or not 1 <= taps <= _CONV_TAPS_MOST
              or self.vmem_bwd > _CONV_VMEM_MOST):
            self.why_not = "shape"
        else:
            self.why_not = None

    def __hash__(self):
        return hash(self.signature())

    def __eq__(self, other):
        return self.signature() == other.signature()

    def signature(self):
        return (f"conv b{self.batch} t{self.t} c{self.channels} "
                f"k{self.taps} {self.dtype}")

    def fill(self, v):
        """(b, T, C) → (b, whole turns, C): zero rows after the sequence."""
        return jnp.pad(v, ((0, 0), (0, self.padded), (0, 0)))

    def stats(self, route):
        """The ``ssm_plans`` entry of this signature on ``route``."""
        kernel = route == "kernel"
        steps = self.batch * (self.channels // _CONV_LANES) if kernel else 0
        return {"route": route, "taps": self.taps,
                "channels_a_step": _CONV_LANES if kernel else self.channels,
                "padded_rows": self.padded if kernel else 0,
                "grid_steps_fwd": steps, "grid_steps_bwd": steps,
                "vmem_bytes": max(self.vmem_fwd, self.vmem_bwd)
                if kernel else 0}


def _conv_turn(i):
    """The first row of turn ``i`` of a step's walk over its block."""
    return pl.multiple_of(i * _CONV_ROWS, _CONV_ROWS)


def _conv_sum(wb_ref, xf, at, taps, now=None):
    """The sum before SiLU of ``_CONV_ROWS`` positions from ``at``, float32:
    the bias and tap ``k`` times the rows ``K − 1 − k`` earlier in the float32
    copy ``xf`` (whose first ``_CONV_HALO`` rows are the zeros before the
    sequence); also the rows each tap read."""
    past = [xf[pl.ds(_CONV_HALO + at - (taps - 1 - k), _CONV_ROWS), :]
            for k in range(taps - 1)] + [
        xf[pl.ds(_CONV_HALO + at, _CONV_ROWS), :] if now is None else now]
    acc = wb_ref[taps:taps + 1, :] + wb_ref[0:1, :] * past[0]
    for k in range(1, taps):
        acc += wb_ref[k:k + 1, :] * past[k]
    return acc, past


def _conv_fwd_kernel(wb_ref, x_ref, y_ref, xf, *, taps):
    """One lane tile of channels over its whole sequence, ``_CONV_ROWS``
    positions a turn: each turn writes its rows' float32 copy behind the
    earlier turns' and reads the taps' rows back at their offsets."""
    xf[0:_CONV_HALO, :] = jnp.zeros((_CONV_HALO, xf.shape[1]), F32)

    def turn(i, _):
        at = _conv_turn(i)
        now = x_ref[pl.ds(at, _CONV_ROWS), :].astype(F32)
        xf[pl.ds(_CONV_HALO + at, _CONV_ROWS), :] = now
        acc, _ = _conv_sum(wb_ref, xf, at, taps, now)
        y_ref[pl.ds(at, _CONV_ROWS), :] = (
            acc / (1.0 + jnp.exp(-acc))).astype(y_ref.dtype)

    jax.lax.fori_loop(0, x_ref.shape[0] // _CONV_ROWS, turn, None)


def _conv_bwd_kernel(wb_ref, x_ref, dy_ref, dx_ref, dwb_ref, xf, felt, *,
                     taps):
    """The same block backward in time.  ``felt`` holds what the loss feels
    of the sum before SiLU, float32, with zeros after the last position: a
    turn writes its rows there and reads the later turns' for ``dx``.  The
    taps' and the bias's gradients are summed over the turns in registers
    and written once a step, as the rows of an (8, lanes) block."""
    rows, lanes = x_ref.shape
    turns = rows // _CONV_ROWS
    xf[0:_CONV_HALO, :] = jnp.zeros((_CONV_HALO, lanes), F32)
    felt[rows:rows + _CONV_HALO, :] = jnp.zeros((_CONV_HALO, lanes), F32)

    def copy(i, _):
        at = _conv_turn(i)
        xf[pl.ds(_CONV_HALO + at, _CONV_ROWS), :] = x_ref[
            pl.ds(at, _CONV_ROWS), :].astype(F32)

    jax.lax.fori_loop(0, turns, copy, None)
    fold = lambda v: jnp.sum(v.reshape(-1, 8, lanes), axis=0)

    def turn(i, sums):
        at = _conv_turn(turns - 1 - i)
        acc, past = _conv_sum(wb_ref, xf, at, taps)
        sig = 1.0 / (1.0 + jnp.exp(-acc))
        d_acc = (dy_ref[pl.ds(at, _CONV_ROWS), :].astype(F32)
                 * (sig * (1.0 + acc * (1.0 - sig))))
        felt[pl.ds(at, _CONV_ROWS), :] = d_acc
        dx = wb_ref[taps - 1:taps, :] * d_acc
        for k in range(taps - 1):
            dx += wb_ref[k:k + 1, :] * felt[
                pl.ds(at + taps - 1 - k, _CONV_ROWS), :]
        dx_ref[pl.ds(at, _CONV_ROWS), :] = dx.astype(dx_ref.dtype)
        return tuple(s + fold(d_acc * v) for s, v in zip(sums, past)) + (
            sums[taps] + fold(d_acc),)

    sums = jax.lax.fori_loop(
        0, turns, turn, (jnp.zeros((8, lanes), F32),) * (taps + 1))
    dwb_ref[...] = jnp.concatenate(
        [jnp.sum(s, axis=0, keepdims=True) for s in sums]
        + [jnp.zeros((8 - len(sums), lanes), F32)], axis=0)


def _conv_call(kernel, name, pn, vmem, ins, outs, copies, operands):
    """The ``pallas_call`` of one kernel of the pair over grid (batch, lane
    tile of channels).  ``ins`` and ``outs`` name each operand's kind of
    block: ``x`` the whole (padded) sequence of a lane tile, ``wb`` the taps,
    the bias or their gradients as the rows of an (8, C) float32 array."""
    kinds = {
        "x": ((pn.batch, pn.rows, pn.channels), pn.dtype,
              (None, pn.rows, _CONV_LANES), lambda b, c: (b, 0, c)),
        "wb": ((8, pn.channels), F32, (8, _CONV_LANES), lambda b, c: (0, c)),
        "dwb": ((pn.batch, 8, pn.channels), F32, (None, 8, _CONV_LANES),
                lambda b, c: (b, 0, c)),
    }
    spec = lambda kind: pl.BlockSpec(*kinds[kind][2:],
                                     memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(kernel, taps=pn.taps),
        out_shape=[jax.ShapeDtypeStruct(*kinds[o][:2]) for o in outs],
        grid=(pn.batch, pn.channels // _CONV_LANES),
        in_specs=[spec(i) for i in ins], out_specs=[spec(o) for o in outs],
        scratch_shapes=[pltpu.VMEM((pn.rows + _CONV_HALO, _CONV_LANES), F32)
                        ] * copies,
        interpret=pk.interpret_mode(),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(_CONV_VMEM, vmem),
            dimension_semantics=("parallel", "parallel")),
        name=name,
    )(*operands)


def _taps_and_bias(pn, weight, bias):
    """``weight`` (C, K) and ``bias`` (C,) as the kernels read them: the
    rows of an (8, C) float32 array, tap ``k`` row ``k``, the bias row K."""
    return jnp.concatenate(
        [weight.astype(F32).T, bias.astype(F32)[None],
         jnp.zeros((_CONV_TAPS_MOST - pn.taps, pn.channels), F32)], axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv_kernels(pn, x, weight, bias):
    return _conv_kernels_fwd(pn, x, weight, bias)[0]


def _conv_kernels_fwd(pn, x, weight, bias):
    (y,) = _conv_call(_conv_fwd_kernel, "causal_conv1d_fwd", pn, pn.vmem_fwd,
                      ("wb", "x"), ("x",), 1,
                      (_taps_and_bias(pn, weight, bias), pn.fill(x)))
    return y[:, :pn.t], (x, weight, bias)


def _conv_kernels_bwd(pn, res, dy):
    x, weight, bias = res
    dx, dwb = _conv_call(
        _conv_bwd_kernel, "causal_conv1d_bwd", pn, pn.vmem_bwd,
        ("wb", "x", "x"), ("x", "dwb"), 2,
        (_taps_and_bias(pn, weight, bias), pn.fill(x),
         pn.fill(dy.astype(x.dtype))))
    dwb = jnp.sum(dwb, axis=0)
    return (dx[:, :pn.t], dwb[:pn.taps].T.astype(weight.dtype),
            dwb[pn.taps].astype(bias.dtype))


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


@register("causal_conv1d")
def causal_conv1d(x, weight, bias):
    """``silu(bias + Σ_k weight[:, k] · x_{t−K+1+k})`` for ``x`` (B, T, C),
    ``weight`` (C, K) and ``bias`` (C,): each channel its own ``K`` taps
    over its own past, zeros before the first position.  Summed in
    float32; the result has ``x``'s dtype."""
    pn = _ConvPlan(*x.shape, weight.shape[1], x.dtype)

    def causal_conv1d_xla(x, weight, bias):
        taps, t = weight.shape[1], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(F32)
        w = weight.astype(F32)
        acc = bias.astype(F32) + sum(
            padded[:, k:k + t] * w[:, k] for k in range(taps))
        return jax.nn.silu(acc).astype(x.dtype)

    def causal_conv1d(x, weight, bias):
        return _conv_kernels(pn, x, weight, bias)

    _note_plan(pn)
    return pk.dispatch(causal_conv1d, causal_conv1d_xla, x, weight, bias,
                       unless=pn.why_not)


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


# ======================================================================
# the kernel pair.  One grid step takes one chunk of one group's heads:
# grid (batch, group, chunk), the chunk axis sequential — forward in order
# with the running state in VMEM scratch, backward in reverse with what the
# loss feels of the outgoing state there.  Nothing is cut in HBM: ``x``
# (b, T, heads·P) is read as it lies, a block of (chunk rows, the group's
# heads on the lanes); ``B`` and ``C`` (b, T, groups·N) likewise.  Only Δ,
# a sixty-fourth of ``x`` or less, is laid out twice outside, a group's
# heads on the lanes (b, g, T, j) and on the sublanes (b, g, j, T): a decay
# ``exp(cum_t − cum_s)`` needs ``cum`` down the rows and along them, and
# both running sums are products with a triangle of ones on the MXU.
#
# Inside a step the lanes are walked a tile at a time (``_ScanPlan.tile``:
# a head of 128 lanes or more, or the 128 // P heads that share 128 lanes,
# so every slice of a block is whole lane tiles).  A head's (Q, Q) decay
# matrix, its decay-weighted scores and their cotangents never leave the
# core; what goes to HBM is ``y`` and the float32 entry state of each chunk
# (the residual the composition keeps too), and from the backward ``dx``,
# ``dΔ``, ``dB`` and ``dC`` (summed over the group's heads in the step)
# once, ``dA`` and ``dD`` as each chunk's share (XLA adds them up).
# ======================================================================

_SCAN_VMEM = 32 * 1024 * 1024
_SCAN_VMEM_MOST = 64 * 1024 * 1024
_SCAN_VMEM_OWN = 6 * 1024 * 1024
# The backward kernel takes a group whose float32 state is at most this.
# At Falcon-H1's shape (16 heads x 128 x 256: 2 MiB) it runs alone, at any
# address, and inside a step of two blocks, but the cell's whole step of four
# blocks does not come back from its first call with it, nor with 8 heads a
# step (my chip runs, PR 37: seven attempts; unexplained, PERF.md section 7).
# So that shape keeps the composition's backward under the forward kernel;
# Nemotron-H's (16 x 64 x 128: 0.5 MiB) takes the pair.
_SCAN_BWD_STATE_MOST = 1024 * 1024


class _ScanPlan:
    """What one ``ssd_scan`` signature is cut into, and whether the kernel
    pair takes it (``why_not`` is None) or the composition does (``"shape"``
    or ``"dtype"``: the ``unless=`` of the dispatch).  The pair takes chunks
    of whole lane tiles, a state of whole lane tiles, heads of whole lane
    tiles or that share one evenly, bfloat16 or float32, and a group's
    heads a step if their blocks fit the VMEM bill; ``backward_kernel`` says
    whether the backward is the pair's or the composition's from the
    forward kernel's residuals (``_SCAN_BWD_STATE_MOST``)."""

    def __init__(self, batch, t, heads, p, groups, n, chunk, dtype):
        self.batch, self.t, self.heads, self.p = batch, t, heads, p
        self.groups, self.n, self.chunk = groups, n, chunk
        self.dtype = jnp.dtype(dtype)
        self.chunks = -(-t // chunk)
        self.padded = self.chunks * chunk - t
        self.per_group = j = heads // groups
        self.tile = max(p, 128)
        item = self.dtype.itemsize
        wide, state = chunk * j * p * item, 4 * j * p * n
        small = 2 * chunk * n * item + 8 * chunk * max(j, 8)
        # a kernel's bill: its pipelined blocks twice, its scratch once, and
        # the step's own values (a tile's float32 temporaries, a few (Q, Q)
        # float32 matrices)
        own = _SCAN_VMEM_OWN + 32 * chunk * chunk
        self.vmem_fwd = 2 * (2 * wide + small + state) + state + own
        self.backward_kernel = state <= _SCAN_BWD_STATE_MOST
        self.vmem_bwd = (2 * (3 * wide + 2 * small + state) + state + own
                         if self.backward_kernel else 0)
        if self.dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
            self.why_not = "dtype"
        elif (chunk % 128 or n % 128 or p % 8 or self.tile % p
              or j % (self.tile // p)
              or max(self.vmem_fwd, self.vmem_bwd) > _SCAN_VMEM_MOST):
            self.why_not = "shape"
        else:
            self.why_not = None

    def __hash__(self):
        return hash((self.signature(), self.chunk))

    def __eq__(self, other):
        return (self.signature(), self.chunk) == (other.signature(),
                                                  other.chunk)

    def signature(self):
        return (f"b{self.batch} t{self.t} h{self.heads}x{self.p} "
                f"g{self.groups} n{self.n} {self.dtype}")

    def cut(self, v, *tail):
        """(b, whole chunks, ...) → (b, chunks, chunk) + ``tail`` (or the
        axes ``v`` has after the positions): the composition's view."""
        return v.reshape((self.batch, self.chunks, self.chunk)
                         + (tail or v.shape[2:]))

    def stats(self, route):
        """The ``ssm_plans`` entry of this signature on ``route``."""
        steps = self.batch * self.groups * self.chunks
        kernel = route == "kernel"
        return {"chunk": self.chunk, "chunks": self.chunks,
                "heads_a_step": self.per_group if kernel else self.heads,
                "state_bytes_saved": 4 * self.batch * self.chunks
                * self.heads * self.p * self.n,
                "padded_rows": self.padded, "route": route,
                "grid_steps_fwd": steps if kernel else 0,
                "grid_steps_bwd": steps if kernel and self.backward_kernel
                else 0,
                "vmem_bytes": max(self.vmem_fwd, self.vmem_bwd)
                if kernel else 0}


def _dot(a, b, dims):
    return pk._attn_dot(a, b.astype(a.dtype), dims)


def _chunk_decays(dtc_ref, dtr_ref, ar_ref, ac_ref):
    """Δ (Q, j), the triangle ``t ≥ s`` and the running sum of ``Δ A``
    inside the chunk in both layouts, (Q, j) and (j, Q): products with the
    triangle's ones at full float32 precision."""
    dt = dtc_ref[...]
    q = dt.shape[0]
    ahead = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    ones = ahead.astype(F32)
    cum = _dot(ones, dt * ar_ref[...], pk._NN)
    cum_r = _dot(dtr_ref[...] * ac_ref[...], ones, pk._NT)
    return dt, ahead, cum, cum_r


def _whole_decay(dtr_ref, ac_ref, n):
    """(j, N): a head's ``exp(cum_end)`` along a whole row, as the state's
    rows take it (a sum on the MXU again: Mosaic broadcasts one value along
    the lanes or down the sublanes, not both)."""
    a_r = dtr_ref[...] * ac_ref[...]
    return jnp.exp(_dot(a_r, jnp.ones((a_r.shape[1], n), F32), pk._NN))


def _decay_of(head, ahead, cum, cum_r):
    """A head's (Q, Q) float32 ``exp(cum_t − cum_s)`` for ``t ≥ s``, else 0:
    the difference first, so no exponent is above 0."""
    return jnp.exp(jnp.where(
        ahead, cum[:, head:head + 1] - cum_r[head:head + 1, :], -jnp.inf))


class _Tile:
    """Lane tile ``k`` of a step: its lanes in a (Q, j·P) block, which are
    its rows in a (j·P, N) state, and the heads that share it."""

    def __init__(self, k, q, tile, p):
        self.q, self.tile, self.p = q, tile, p
        self.lanes = slice(k * tile, (k + 1) * tile)
        self.heads = range(k * (tile // p), (k + 1) * (tile // p))
        self.lane = (jax.lax.broadcasted_iota(jnp.int32, (q, tile), 1)
                     if len(self.heads) > 1 else None)

    def rows(self, head, of_block=False):
        """A head's rows in this tile's (tile, N) slice of a state, or in
        the whole block's."""
        at = head * self.p - (0 if of_block else self.lanes.start)
        return slice(at, at + self.p)

    def only(self, head, v):
        """``v`` (Q, tile) with the other heads' lanes at zero."""
        if self.lane is None:
            return v
        mine = self.rows(head)
        return jnp.where((self.lane >= mine.start) & (self.lane < mine.stop),
                         v, jnp.zeros_like(v))

    def spread(self, v):
        """(Q, j), a value a head → (Q, tile): a head's column over its own
        lanes."""
        out = None
        for head in reversed(self.heads):
            col = jnp.broadcast_to(v[:, head:head + 1], (self.q, self.tile))
            out = col if out is None else jnp.where(
                self.lane < self.rows(head).stop, col, out)
        return out

    def sums(self, head, v):
        """(Q, 1): ``v`` (Q, tile) summed over a head's lanes."""
        return jnp.sum(self.only(head, v), axis=1, keepdims=True)


def _scan_fwd_kernel(dtc_ref, dtr_ref, ar_ref, ac_ref, d_ref, x_ref, b_ref,
                     c_ref, y_ref, entry_ref, state, *, p, tile):
    q, lanes = x_ref.shape
    n, dtype = b_ref.shape[1], x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, F32)

    dt, ahead, cum, cum_r = _chunk_decays(dtc_ref, dtr_ref, ar_ref, ac_ref)
    grow = jnp.exp(cum)
    keep = jnp.exp(cum[q - 1:q] - cum) * dt
    whole = _whole_decay(dtr_ref, ac_ref, n)
    b_, c_ = b_ref[...], c_ref[...]
    scores = _dot(c_, b_, pk._NT)
    for k in range(lanes // tile):
        tl = _Tile(k, q, tile, p)
        xf = x_ref[:, tl.lanes].astype(F32)
        entry = state[tl.lanes, :]
        entry_ref[tl.lanes, :] = entry
        y = (_dot(c_, entry.astype(dtype), pk._NT) * tl.spread(grow)
             + d_ref[:, tl.lanes] * xf)
        xdt = (xf * tl.spread(dt)).astype(dtype)
        for head in tl.heads:
            mixed = (scores * _decay_of(head, ahead, cum, cum_r)).astype(dtype)
            y += _dot(mixed, tl.only(head, xdt), pk._NN)
        y_ref[:, tl.lanes] = y.astype(dtype)
        own = _dot((xf * tl.spread(keep)).astype(dtype), b_, pk._TN)
        for head in tl.heads:
            mine = tl.rows(head)
            state[tl.rows(head, of_block=True), :] = (
                whole[head:head + 1] * entry[mine] + own[mine])


def _scan_bwd_kernel(dtc_ref, dtr_ref, ar_ref, ac_ref, d_ref, x_ref, b_ref,
                     c_ref, entry_ref, dy_ref, dx_ref, db_ref, dc_ref,
                     ddt_ref, da_ref, dd_ref, felt, *, p, tile):
    """One chunk of the mirror recurrence; ``felt`` is what the loss feels
    of the state this chunk leaves behind."""
    q, lanes = x_ref.shape
    n, dtype, j = b_ref.shape[1], x_ref.dtype, dtc_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        felt[...] = jnp.zeros(felt.shape, F32)

    dt, ahead, cum, cum_r = _chunk_decays(dtc_ref, dtr_ref, ar_ref, ac_ref)
    grow = jnp.exp(cum)
    tail = jnp.exp(cum[q - 1:q] - cum)
    whole = _whole_decay(dtr_ref, ac_ref, n)
    b_, c_ = b_ref[...], c_ref[...]
    scores = _dot(c_, b_, pk._NT)
    d_scores = jnp.zeros((q, q), F32)
    d_b = jnp.zeros((q, n), F32)
    d_c = jnp.zeros((q, n), F32)
    d_cum = jnp.zeros((q, j), F32)
    d_cum_r = jnp.zeros((j, q), F32)
    d_dt = jnp.zeros((q, j), F32)
    d_keep = jnp.zeros((q, j), F32)
    d_total = jnp.zeros((1, j), F32)
    head_of = jax.lax.broadcasted_iota(jnp.int32, (q, j), 1)
    head_of_r = jax.lax.broadcasted_iota(jnp.int32, (j, q), 0)
    for k in range(lanes // tile):
        tl = _Tile(k, q, tile, p)
        dy = dy_ref[:, tl.lanes]
        xf, dyf = x_ref[:, tl.lanes].astype(F32), dy.astype(F32)
        entry, out = entry_ref[tl.lanes, :], felt[tl.lanes, :]
        entry_low, out_low = entry.astype(dtype), out.astype(dtype)
        dt_w, tail_w = tl.spread(dt), tl.spread(tail)
        xdt = (xf * dt_w).astype(dtype)
        d_inner = dyf * tl.spread(grow)
        d_inner_low = d_inner.astype(dtype)
        through = d_inner * _dot(c_, entry_low, pk._NT)
        d_c += _dot(d_inner_low, entry_low, pk._NN)
        d_entry = _dot(d_inner_low, c_, pk._TN)
        d_b += _dot((xf * (tail_w * dt_w)).astype(dtype), out_low, pk._NN)
        d_xdt = jnp.zeros((q, tile), F32)
        for head in tl.heads:
            decay = _decay_of(head, ahead, cum, cum_r)
            mixed = (scores * decay).astype(dtype)
            dy_h = tl.only(head, dy)
            d_sc = _dot(dy_h, xdt, pk._NT) * decay
            d_xdt += _dot(mixed, dy_h, pk._TN)
            d_scores += d_sc
            # what cum feels through the decays: as cum_t along a row, as
            # −cum_s down a column, both from the one float32 matrix (the
            # two nearly cancel in dA, and must to the last bit)
            pull = d_sc * scores
            d_cum = jnp.where(
                head_of == head, jnp.sum(pull, axis=1, keepdims=True)
                + tl.sums(head, through), d_cum)
            d_cum_r = jnp.where(head_of_r == head,
                                jnp.sum(pull, axis=0, keepdims=True), d_cum_r)
        kept = _dot(b_, out_low, pk._NT)
        z = d_xdt + kept * tail_w
        dx_ref[:, tl.lanes] = (dt_w * z + d_ref[:, tl.lanes] * dyf
                               ).astype(dtype)
        dd_ref[:, tl.lanes] = jnp.sum(dyf * xf, axis=0, keepdims=True)
        xz, xkept = xf * z, xf * kept
        for head in tl.heads:
            d_dt = jnp.where(head_of == head, tl.sums(head, xz), d_dt)
            d_keep = jnp.where(head_of == head, tl.sums(head, xkept), d_keep)
            mine = tl.rows(head)
            passed = whole[head:head + 1] * out[mine]
            felt[tl.rows(head, of_block=True), :] = passed + d_entry[mine]
            d_total = jnp.where(
                head_of[:1] == head,
                jnp.sum(jnp.sum(passed * entry[mine], axis=0, keepdims=True),
                        axis=1, keepdims=True), d_total)
    # the chunk's own state holds exp(cum_end − cum_s) Δ_s x_s: cum_end
    # feels those weights' pull and the entry state's decay, −cum_s its own
    # weight's.  Then back through the running sum (Σ over t ≥ s), cum_end's
    # share to every position.
    pull = dt * tail * d_keep
    behind = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
              <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)).astype(F32)
    d_a = (_dot(behind, d_cum - pull, pk._NN) - _dot(behind, d_cum_r, pk._NT)
           + d_total + jnp.sum(pull, axis=0, keepdims=True))
    ddt_ref[...] = d_dt + ar_ref[...] * d_a
    da_ref[...] = jnp.sum(d_a * dt, axis=0, keepdims=True)
    d_scores_low = d_scores.astype(dtype)
    db_ref[...] = (d_b + _dot(d_scores_low, c_, pk._TN)).astype(dtype)
    dc_ref[...] = (d_c + _dot(d_scores_low, b_, pk._NN)).astype(dtype)


def _scan_call(kernel, name, pn, vmem, reverse, ins, outs, scratch, operands):
    """The ``pallas_call`` of one kernel of the pair over grid (batch,
    group, chunk), chunks in reverse for the backward.  ``ins`` and ``outs``
    name each operand's kind of block."""
    q, j, n, lanes = pn.chunk, pn.per_group, pn.n, pn.per_group * pn.p
    at = (lambda c: pn.chunks - 1 - c) if reverse else (lambda c: c)
    t = pn.chunks * q
    # kind: (array shape, dtype, block, index map)
    kinds = {
        # a chunk's rows of a group: its heads' lanes, the state's width
        "x": ((pn.batch, t, pn.heads * pn.p), pn.dtype, (None, q, lanes),
              lambda b, g, c: (b, at(c), g)),
        "n": ((pn.batch, t, pn.groups * n), pn.dtype, (None, q, n),
              lambda b, g, c: (b, at(c), g)),
        "state": ((pn.batch, pn.chunks, pn.heads * pn.p, n), F32,
                  (None, None, lanes, n), lambda b, g, c: (b, at(c), g, 0)),
        # Δ with the group's heads on the lanes, and on the sublanes
        "dtc": ((pn.batch, pn.groups, t, j), F32, (None, None, q, j),
                lambda b, g, c: (b, g, at(c), 0)),
        "dtr": ((pn.batch, pn.groups, j, t), F32, (None, None, j, q),
                lambda b, g, c: (b, g, 0, at(c))),
        # a value a head: A both ways, D over the head's lanes
        "ar": ((pn.groups, 1, j), F32, (None, 1, j),
               lambda b, g, c: (g, 0, 0)),
        "ac": ((pn.groups, j, 1), F32, (None, j, 1),
               lambda b, g, c: (g, 0, 0)),
        "d": ((pn.groups, 1, lanes), F32, (None, 1, lanes),
              lambda b, g, c: (g, 0, 0)),
        # a chunk's share of a sum over positions (XLA adds them up)
        "sum_j": ((pn.batch, pn.groups, pn.chunks, 1, j), F32,
                  (None, None, None, 1, j), lambda b, g, c: (b, g, c, 0, 0)),
        "sum_x": ((pn.batch, pn.groups, pn.chunks, 1, lanes), F32,
                  (None, None, None, 1, lanes),
                  lambda b, g, c: (b, g, c, 0, 0)),
    }
    spec = lambda kind: pl.BlockSpec(*kinds[kind][2:],
                                     memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(kernel, p=pn.p, tile=pn.tile),
        out_shape=[jax.ShapeDtypeStruct(*kinds[o][:2]) for o in outs],
        grid=(pn.batch, pn.groups, pn.chunks),
        in_specs=[spec(i) for i in ins], out_specs=[spec(o) for o in outs],
        scratch_shapes=scratch,
        interpret=pk.interpret_mode(),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(_SCAN_VMEM_MOST, max(_SCAN_VMEM, vmem)),
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name,
    )(*operands)


_DECAYS = ("dtc", "dtr", "ar", "ac", "d")


def _decay_operands(pn, dt, a, d):
    """Δ (b, T, heads), ``A`` and ``D`` (heads,) as the kernels read them."""
    by_group = dt.reshape(pn.batch, -1, pn.groups, pn.per_group)
    return (by_group.transpose(0, 2, 1, 3), by_group.transpose(0, 2, 3, 1),
            a.reshape(pn.groups, 1, -1), a.reshape(pn.groups, -1, 1),
            jnp.repeat(d, pn.p).reshape(pn.groups, 1, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ssd_kernels(pn, x, dt, a, b_, c_, d):
    """The scan over whole chunks: ``x`` (b, T, heads·P), ``dt`` (b, T,
    heads) float32, ``a`` and ``d`` (heads,) float32, ``b_`` and ``c_``
    (b, T, groups·N)."""
    return _ssd_kernels_fwd(pn, x, dt, a, b_, c_, d)[0]


def _ssd_kernels_fwd(pn, x, dt, a, b_, c_, d):
    lanes = pn.per_group * pn.p
    y, entry = _scan_call(
        _scan_fwd_kernel, "ssd_scan_fwd", pn, pn.vmem_fwd, False,
        _DECAYS + ("x", "n", "n"), ("x", "state"),
        [pltpu.VMEM((lanes, pn.n), F32)],
        _decay_operands(pn, dt, a, d) + (x, b_, c_))
    return y, (x, dt, a, b_, c_, d, entry)


def _ssd_kernels_bwd(pn, res, dy):
    x, dt, a, b_, c_, d, entry = res
    if not pn.backward_kernel:
        # the composition's backward from the same residuals, cut its way
        by_group = (pn.groups, pn.per_group)
        grads = _ssd_bwd(
            (pn.cut(x, *by_group, pn.p), pn.cut(dt, *by_group),
             a.reshape(by_group), pn.cut(b_, pn.groups, pn.n),
             pn.cut(c_, pn.groups, pn.n), d.reshape(by_group),
             entry.reshape((pn.batch, pn.chunks) + by_group + (pn.p, pn.n))),
            pn.cut(dy.astype(x.dtype), *by_group, pn.p))
        return tuple(g.reshape(v.shape) for g, v in zip(grads, res))
    lanes = pn.per_group * pn.p
    dx, db, dc, ddt, da, dd = _scan_call(
        _scan_bwd_kernel, "ssd_scan_bwd", pn, pn.vmem_bwd, True,
        _DECAYS + ("x", "n", "n", "state", "x"),
        ("x", "n", "n", "dtc", "sum_j", "sum_x"),
        [pltpu.VMEM((lanes, pn.n), F32)],
        _decay_operands(pn, dt, a, d) + (x, b_, c_, entry,
                                         dy.astype(x.dtype)))
    return (dx, ddt.transpose(0, 2, 1, 3).reshape(dt.shape),
            jnp.sum(da, axis=(0, 2)).reshape(a.shape), db, dc,
            jnp.sum(dd, axis=(0, 2)).reshape(pn.heads, pn.p).sum(axis=1))


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


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
    pn = _ScanPlan(batch, t, heads, p, groups, n, chunk, x.dtype)
    chunks, padded = pn.chunks, pn.padded

    def fill(v):
        """Whole chunks: ``Δ = 0`` rows after the sequence."""
        return jnp.pad(v, ((0, 0), (0, padded)) + ((0, 0),) * (v.ndim - 2))

    def ssd_scan_xla(x, dt, a, b_, c_, d):
        cut = lambda v, *tail: pn.cut(fill(v), *tail)
        by_group = (groups, heads // groups)
        y = _ssd_chunked(cut(x, *by_group, p),
                         cut(dt.astype(F32), *by_group),
                         a.astype(F32).reshape(by_group), cut(b_), cut(c_),
                         d.astype(F32).reshape(by_group))
        return y.reshape((batch, chunks * chunk, heads, p))[:, :t]

    def ssd_scan(x, dt, a, b_, c_, d):
        flat = lambda v: fill(v).reshape(batch, chunks * chunk, -1)
        y = _ssd_kernels(pn, flat(x), flat(dt.astype(F32)), a.astype(F32),
                         flat(b_.astype(x.dtype)), flat(c_.astype(x.dtype)),
                         d.astype(F32))
        return y.reshape((batch, chunks * chunk, heads, p))[:, :t]

    _note_plan(pn)
    return pk.dispatch(ssd_scan, ssd_scan_xla, x, dt, A, B, C, D,
                       unless=pn.why_not)
