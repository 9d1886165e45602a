"""Decoder-era layers over the registered ops: ``RMSNorm``, the
feed-forwards ``SwiGLU`` (gated) and ``ReLU2MLP`` (ungated), ``RoutedFFN``,
a mixture-of-experts layer that is told which experts it holds
(``ops/moe_ops.py``), and the two sequence mixers the hybrid decoders
build: ``Mamba2Mixer`` (``ops/ssm_ops.py``) and ``GroupedQueryAttention``.
TPU-era additions; the reference has no counterpart."""
from __future__ import annotations

import contextlib
import math
import threading
import weakref

import jax
import jax.numpy as jnp

from ... import initializer as init_mod
from ... import profiler as _profiler
from ... import random as _random
from ...ndarray import NDArray
from ...ops.registry import invoke
from ..block import (HybridBlock, register_state_update,
                     register_trace_sink)
from ..parameter import Parameter
from .basic_layers import Dense

__all__ = ["RMSNorm", "SwiGLU", "ReLU2MLP", "RoutedFFN", "Mamba2Mixer",
           "GroupedQueryAttention", "moe_stats", "record_routing"]


def _dense(units, in_units):
    return Dense(units, use_bias=False, flatten=False, in_units=in_units)


class RMSNorm(HybridBlock):
    """``x · rsqrt(mean(x²) + eps) · gamma`` over the last axis."""

    def __init__(self, in_channels, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=init_mod.One())

    def forward(self, x):
        return invoke("RMSNorm", x, self.gamma.data(), eps=self._epsilon)


class SwiGLU(HybridBlock):
    """``down(silu(gate(x)) · up(x))`` without biases; the gate and up
    projections are one matrix, side by side."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        self._hidden = hidden_size
        self.gate_up = Dense(2 * hidden_size, use_bias=False, flatten=False,
                             in_units=units)
        self.down = Dense(units, use_bias=False, flatten=False,
                          in_units=hidden_size)

    def forward(self, x):
        h = self.gate_up(x)
        gate, up = h[..., :self._hidden], h[..., self._hidden:]
        return self.down(invoke("silu", gate) * up)


class ReLU2MLP(HybridBlock):
    """``down(relu(up(x))²)`` without biases: the ungated feed-forward."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        self.up = _dense(hidden_size, units)
        self.down = _dense(units, hidden_size)

    def forward(self, x):
        return self.down(invoke("relu", self.up(x)).square())


class _ZeroState(Parameter):
    """Aux state (no gradient) that starts at zero whatever initializer
    the net is given."""

    def __init__(self, name, shape):
        super().__init__(name, grad_req="null", shape=shape)

    def _finish_init(self, init, ctx, default_init=None):
        super()._finish_init(init_mod.Zero(), ctx)


# the live routed layers, for the ``moe`` stats provider
_routed_layers = weakref.WeakSet()

STATS = ("rows_held", "buffer_rows", "passes", "load_max_over_mean",
         "overflow_steps")


_routing = threading.local()


@contextlib.contextmanager
def record_routing():
    """Inside, every :class:`RoutedFFN` forward appends the experts it
    chose, ``(rows, top_k)`` int32, to the list this yields, in the order
    the layers run (a comparison with a reference needs the program's own
    choice where two scores nearly tie).  Used around a traced forward, the
    list holds tracers: return them from the traced function."""
    _routing.sink = sink = []
    try:
        yield sink
    finally:
        _routing.sink = None


register_trace_sink(lambda: getattr(_routing, "sink", None))


class RoutedFFN(HybridBlock):
    """A routed feed-forward layer on a chip that holds ``held = (first,
    count)`` of ``n_experts`` experts (expert parallelism), with an optional
    shared expert that every chip computes alike.  The experts, and the shared
    expert with them, are SwiGLU (``activation="swiglu"``) or ungated
    ``relu(·)²`` (``"relu2"``).

    With ``latent_size`` the routed experts live in a narrower latent: the
    router and the shared expert read the layer's input at its full width,
    the tokens are projected down once (``latent_down``, kernel scope
    ``moe_latent_down``), every routed expert reads and writes the latent,
    and this chip's gated sum is projected back (``latent_up``, scope
    ``moe_latent_up``).

    The router scores all ``n_experts`` (float32 sigmoid), picks the
    ``top_k`` largest of score + selection bias, and normalises the chosen
    scores to gates that sum to ``scale``.  This chip adds its own
    experts' part, ``Σ g_e · Expert_e(x)`` over held ``e``; what absent
    experts would have added is left out, and nothing stands in for the
    exchange.  No token is dropped, whatever the routing, and the step's
    device work does not follow it (``ops/moe_ops.py``).

    While training, the selection bias moves with no gradient, ``b_e +=
    gamma · sign(mean load − load_e)`` over this chip's tokens, as an aux
    state like BatchNorm's moving statistics; the aux state ``moe_stats``
    carries the last step's ``STATS`` (``overflow_steps`` counts the steps
    that needed a further pass), read after a run by :func:`moe_stats`."""

    def __init__(self, units, hidden_size, n_experts, held=None, top_k=2,
                 scale=1.0, gamma=0.0, capacity_factor=1.5,
                 shared_hidden_size=0, latent_size=0, activation="swiglu",
                 **kwargs):
        super().__init__(**kwargs)
        self._first, count = held or (0, n_experts)
        self._n_experts, self._top_k = n_experts, top_k
        self._scale, self._gamma = scale, gamma
        self._factor, self._activation = capacity_factor, activation
        width = latent_size or units
        feed_forward = {"swiglu": SwiGLU, "relu2": ReLU2MLP}
        self.router_weight = Parameter("router_weight",
                                       shape=(n_experts, units))
        self.score_bias = _ZeroState("score_bias", (n_experts,))
        self.moe_stats = _ZeroState("moe_stats", (len(STATS),))
        self.experts_in = Parameter(
            "experts_in", shape=(count, width, hidden_size
                                 * (2 if activation == "swiglu" else 1)))
        self.experts_out = Parameter("experts_out",
                                     shape=(count, hidden_size, width))
        if latent_size:
            self.latent_down = _dense(latent_size, units)
            self.latent_up = _dense(units, latent_size)
        self.shared = feed_forward[activation](
            units, shared_hidden_size) if shared_hidden_size else None
        self._latent = bool(latent_size)
        _routed_layers.add(self)

    def forward(self, x):
        from ... import autograd
        rows = x.reshape((-1, x.shape[-1]))
        training = autograd.is_training()
        idx, gates, new_bias, _ = invoke(
            "moe_route", rows, self.router_weight.data(),
            self.score_bias.data(), top_k=self._top_k, scale=self._scale,
            gamma=self._gamma if training else 0.0)
        if getattr(_routing, "sink", None) is not None:
            _routing.sink.append(idx.data)
        if self._latent:
            with jax.named_scope("moe_latent_down"):
                rows = self.latent_down(rows)
        y, stats = invoke(
            "moe_ffn", rows, idx, gates, self.experts_in.data(),
            self.experts_out.data(), n_experts=self._n_experts,
            first=self._first, capacity_factor=self._factor,
            activation=self._activation)
        if self._latent:
            with jax.named_scope("moe_latent_up"):
                y = self.latent_up(y)
        if training:
            before = self.moe_stats.data()
            overflow = before[4:5] + (stats[2:3] > 1.0)
            register_state_update(self.score_bias, new_bias)
            register_state_update(
                self.moe_stats, invoke("concat", stats, overflow, dim=0))
        y = y.reshape(x.shape)
        return y if self.shared is None else y + self.shared(x)


# ======================================================================
# the sequence mixers of the hybrid decoders
# ======================================================================

def _times(x, c):
    """``x · c`` for a constant ``c`` (a number, or a vector over the last
    axis), multiplied in float32 and rounded once to ``x``'s dtype."""
    if not isinstance(c, float):
        c = NDArray(jnp.asarray(c, jnp.float32))
    elif c == 1.0:
        return x
    return (x.astype("float32") * c).astype(x.dtype)


class _FromUniform(init_mod.Initializer):
    """``transform(u)`` of ``u`` uniform in ``[low, high)``, whatever the
    parameter is called (the base class reads a name's ending)."""

    def __init__(self, low, high, transform=None):
        super().__init__(low=low, high=high)
        self._range, self._transform = (low, high), transform

    def __call__(self, name, arr=None):
        arr = name if arr is None else arr
        value = jax.random.uniform(_random.next_key(), arr.shape,
                                   jnp.float32, *self._range)
        if self._transform:
            value = self._transform(value)
        arr._set_data(value.astype(arr.data.dtype))


class _OwnInit(Parameter):
    """A parameter that starts from its own initializer whatever the net
    is given: Mamba-2's ``A``, ``Δ`` bias, skip and convolution."""

    def _finish_init(self, init, ctx, default_init=None):
        super()._finish_init(self.init, ctx)


class _GroupedRMSNorm(RMSNorm):
    """The mixer's gated norm: the mean square over each of ``groups`` equal
    parts of the channels apart, under the one gain — the op ``RMSNorm``
    over ``x`` as ``(..., groups, width)`` with the gain as ``(groups,
    width)``."""

    def __init__(self, in_channels, epsilon, groups, **kwargs):
        super().__init__(in_channels, epsilon, **kwargs)
        self._groups = groups

    def forward(self, x):
        parts = (self._groups, x.shape[-1] // self._groups)
        return invoke("RMSNorm", x.reshape(x.shape[:-1] + parts),
                      self.gamma.data().reshape(parts), eps=self._epsilon
                      ).reshape(x.shape)


class Mamba2Mixer(HybridBlock):
    """The Mamba-2 mixer: ``d_ssm = heads · head_dim`` channels, a
    ``head_dim × d_state`` state a head, ``B`` and ``C`` shared by the heads
    of a group.  Starts as Mamba-2 does: ``A`` uniform in [1, 16], ``Δ``'s
    bias so that ``softplus`` of it is log-uniform in [0.001, 0.1], ``D`` 1,
    the convolution uniform within ``d_conv ** -0.5``."""

    def __init__(self, units, d_ssm, heads, d_state, groups, d_conv=4,
                 chunk=128, multipliers=(1.0,) * 5, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._d, self._heads, self._n, self._groups = (d_ssm, heads, d_state,
                                                       groups)
        self._chunk = chunk
        bc = groups * d_state
        z, x, b, c, dt = multipliers
        self._mup = None if set(multipliers) == {1.0} else \
            [z] * d_ssm + [x] * d_ssm + [b] * bc + [c] * bc + [dt] * heads
        self.in_proj = _dense(2 * d_ssm + 2 * bc + heads, units)
        bound = d_conv ** -0.5
        self.conv_weight = _OwnInit("conv_weight", shape=(d_ssm + 2 * bc,
                                                          d_conv),
                                    init=_FromUniform(-bound, bound))
        self.conv_bias = _OwnInit("conv_bias", shape=(d_ssm + 2 * bc,),
                                  init=_FromUniform(-bound, bound))
        self.a_log = _OwnInit("a_log", shape=(heads,),
                              init=_FromUniform(1.0, 16.0, jnp.log))
        self.dt_bias = _OwnInit(
            "dt_bias", shape=(heads,),
            init=_FromUniform(math.log(1e-3), math.log(1e-1),
                              lambda u: jnp.log(jnp.expm1(jnp.exp(u)))))
        self.d_skip = _OwnInit("d_skip", shape=(heads,), init=init_mod.One())
        self.norm = _GroupedRMSNorm(d_ssm, epsilon, groups)
        self.out_proj = _dense(units, d_ssm)

    def forward(self, m):
        b, t, _ = m.shape
        d, heads, groups, n = self._d, self._heads, self._groups, self._n
        proj = self.in_proj(m)
        if self._mup:
            proj = _times(proj, self._mup)
        wide = d + 2 * groups * n
        xbc = invoke("causal_conv1d", proj[..., d:d + wide],
                     self.conv_weight.data(), self.conv_bias.data())
        delta = invoke("softplus", proj[..., d + wide:].astype("float32")
                       + self.dt_bias.data())
        y = invoke(
            "ssd_scan", xbc[..., :d].reshape((b, t, heads, d // heads)),
            delta, -invoke("exp", self.a_log.data()),
            xbc[..., d:d + groups * n].reshape((b, t, groups, n)),
            xbc[..., d + groups * n:].reshape((b, t, groups, n)),
            self.d_skip.data(), chunk=self._chunk)
        return self.out_proj(self.gated(y.reshape((b, t, d)), proj[..., :d]))

    def gated(self, y, z):
        """The gate before the norm: ``RMSNorm_grouped(y ⊙ silu(z))``."""
        return self.norm(y * invoke("silu", z))


class GroupedQueryAttention(HybridBlock):
    """Causal attention of ``num_heads`` query heads over ``num_kv_heads``
    key/value heads, rotary in the two-halves form over the whole head; with
    ``rope_theta=None`` no position embedding at all (a stack whose other
    layers carry the position)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope_theta,
                 key_multiplier=1.0, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv, self._dim = num_heads, num_kv_heads, head_dim
        self._theta = None if rope_theta is None else float(rope_theta)
        self._key_mult = float(key_multiplier)
        self.q = _dense(num_heads * head_dim, units)
        self.k = _dense(num_kv_heads * head_dim, units)
        self.v = _dense(num_kv_heads * head_dim, units)
        self.o = _dense(units, num_heads * head_dim)

    def forward(self, x):
        b, t, _ = x.shape

        def heads(y, n):
            return y.reshape((b, t, n, self._dim))

        def turned(y):
            if self._theta is not None:
                y = invoke("rope", y, theta=self._theta, interleaved=False)
            return y.transpose((0, 2, 1, 3))

        out = invoke(
            "dot_product_attention", turned(heads(self.q(x), self._heads)),
            turned(heads(_times(self.k(x), self._key_mult), self._kv)),
            heads(self.v(x), self._kv).transpose((0, 2, 1, 3)), causal=True)
        return self.o(out.transpose((0, 2, 1, 3)).reshape(
            (b, t, self._heads * self._dim)))


def moe_stats(values=None):
    """``{parameter name: {rows_held, buffer_rows, passes,
    load_max_over_mean, overflow_steps}}`` of routed layers.  ``values`` is
    a name → array dict that holds ``moe_stats`` leaves (a fused step's
    ``step.aux``: the counters travel as aux state and are read after the
    run, never inside a step); without it, the live layers' own parameters,
    which a fused step fills at ``write_back()``."""
    import numpy as onp
    if values is None:
        values = {f"{type(layer).__name__}@{id(layer):x}.moe_stats":
                  layer.moe_stats.data().data
                  for layer in list(_routed_layers)
                  if layer.moe_stats._data is not None}
    return {name: dict(zip(STATS, (float(v) for v in onp.asarray(value))))
            for name, value in values.items() if name.endswith("moe_stats")}


_profiler.register_stats_provider("moe", moe_stats)
