"""Decoder-era layers over the registered ops: ``RMSNorm``, the gated
feed-forward ``SwiGLU``, and ``RoutedFFN``, a mixture-of-experts layer
that is told which experts it holds (``ops/moe_ops.py``).  TPU-era
additions; the reference has no counterpart."""
from __future__ import annotations

import contextlib
import threading
import weakref

from ... import initializer as init_mod
from ... import profiler as _profiler
from ...ops.registry import invoke
from ..block import HybridBlock, register_state_update
from ..parameter import Parameter
from .basic_layers import Dense

__all__ = ["RMSNorm", "SwiGLU", "RoutedFFN", "moe_stats", "record_routing"]


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


class RoutedFFN(HybridBlock):
    """A routed SwiGLU layer on a chip that holds ``held = (first, count)``
    of ``n_experts`` experts (expert parallelism), with an optional shared
    expert that every chip computes alike.

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
                 shared_hidden_size=0, **kwargs):
        super().__init__(**kwargs)
        self._first, count = held or (0, n_experts)
        self._n_experts, self._top_k = n_experts, top_k
        self._scale, self._gamma = scale, gamma
        self._factor = capacity_factor
        self.router_weight = Parameter("router_weight",
                                       shape=(n_experts, units))
        self.score_bias = _ZeroState("score_bias", (n_experts,))
        self.moe_stats = _ZeroState("moe_stats", (len(STATS),))
        self.experts_in = Parameter("experts_in",
                                    shape=(count, units, 2 * hidden_size))
        self.experts_out = Parameter("experts_out",
                                     shape=(count, hidden_size, units))
        self.shared = SwiGLU(units, shared_hidden_size) \
            if shared_hidden_size else None
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
        y, stats = invoke(
            "moe_ffn", rows, idx, gates, self.experts_in.data(),
            self.experts_out.data(), n_experts=self._n_experts,
            first=self._first, capacity_factor=self._factor)
        if training:
            before = self.moe_stats.data()
            overflow = before[4:5] + (stats[2:3] > 1.0)
            register_state_update(self.score_bias, new_bias)
            register_state_update(
                self.moe_stats, invoke("concat", stats, overflow, dim=0))
        y = y.reshape(x.shape)
        return y if self.shared is None else y + self.shared(x)


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
