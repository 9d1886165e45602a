"""Causal decoder whose every block runs a Mamba-2 state-space mixer and
grouped-query attention side by side on one normed input, then a gated MLP:
the layer equations of the Falcon-H1 family with its fixed multipliers, as
Gluon blocks over the registered ops, so that ``amp.convert_block``,
``fuse.make_fused_train_step``, the block scopes and
``pallas_kernels.dispatch`` apply as they do to every other model.

Model: ``h_0 = embedding_multiplier · E[t]``; ``logits = lm_head_multiplier ·
W_head RMSNorm(h_L)``.

Block (``u`` its input): ``n = RMSNorm(u)``; ``x = u + ssm_out_multiplier ·
Mixer(ssm_in_multiplier · n) + attention_out_multiplier ·
Attn(attention_in_multiplier · n)``; ``u' = x + MLP(RMSNorm(x))``.  Both
branches read the same ``n``; their sum joins the residual once.

Attention: ``q = W_q n`` (heads × D), ``k = key_multiplier · W_k n`` and ``v =
W_v n`` (kv heads × D); rotary over the whole ``D`` in the two-halves form;
query head ``i`` reads key/value head ``i // (heads / kv heads)``; causal.

MLP: ``down_mult · W_down(silu(gate_mult · W_gate y) ⊙ W_up y)``.

Mixer: ``[z ; xBC ; dt] = W_in m``, each part times its constant of
``ssm_multipliers`` (z, x, B, C, dt); ``xBC ← silu(conv1d_causal(xBC))``;
``Δ = softplus(dt + dt_bias)`` and ``A = −exp(A_log)`` a head, float32;
``y = ssd_scan(x, Δ, A, B, C, D)`` (``ops/ssm_ops.py``); ``y ←
RMSNorm_grouped(y ⊙ silu(z))``, the mean square over each group's channels
apart under one gain; ``W_out y``.  No bias but the convolution's.

A multiplier is arithmetic on an activation, applied in float32 and rounded
once to the activation's dtype; none is folded into a weight.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import initializer as init_mod
from .. import random as _random
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray import NDArray
from ..ops.registry import invoke

__all__ = ["FalconH1Mixer", "GroupedQueryAttention", "FalconH1Block",
           "FalconH1Decoder"]


def _dense(units, in_units):
    return nn.Dense(units, use_bias=False, flatten=False, in_units=in_units)


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


class _GroupedRMSNorm(nn.RMSNorm):
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


class FalconH1Mixer(HybridBlock):
    """The Mamba-2 branch: ``d_ssm = heads · head_dim`` channels, a
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
        return self.out_proj(self.norm(
            y.reshape((b, t, d)) * invoke("silu", proj[..., :d])))


class GroupedQueryAttention(HybridBlock):
    """Causal attention of ``num_heads`` query heads over ``num_kv_heads``
    key/value heads, rotary in the two-halves form over the whole head."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope_theta,
                 key_multiplier=1.0, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv, self._dim = num_heads, num_kv_heads, head_dim
        self._theta, self._key_mult = float(rope_theta), float(key_multiplier)
        self.q = _dense(num_heads * head_dim, units)
        self.k = _dense(num_kv_heads * head_dim, units)
        self.v = _dense(num_kv_heads * head_dim, units)
        self.o = _dense(units, num_heads * head_dim)

    def forward(self, x):
        b, t, _ = x.shape

        def heads(y, n):
            return y.reshape((b, t, n, self._dim))

        def turned(y):
            return invoke("rope", y, theta=self._theta,
                          interleaved=False).transpose((0, 2, 1, 3))

        out = invoke(
            "dot_product_attention", turned(heads(self.q(x), self._heads)),
            turned(heads(_times(self.k(x), self._key_mult), self._kv)),
            heads(self.v(x), self._kv).transpose((0, 2, 1, 3)), causal=True)
        return self.o(out.transpose((0, 2, 1, 3)).reshape(
            (b, t, self._heads * self._dim)))


class _GatedMLP(nn.SwiGLU):
    """``nn.SwiGLU`` with the family's two constants around it."""

    def __init__(self, units, hidden_size, multipliers=(1.0, 1.0), **kwargs):
        super().__init__(units, hidden_size, **kwargs)
        self._gate_mult, self._down_mult = map(float, multipliers)

    def forward(self, x):
        h = self.gate_up(x)
        gate, up = h[..., :self._hidden], h[..., self._hidden:]
        return _times(self.down(
            invoke("silu", _times(gate, self._gate_mult)) * up),
            self._down_mult)


class FalconH1Block(HybridBlock):
    """``mamba`` and ``attn`` side by side on one normed input, then
    ``ffn``."""

    def __init__(self, mamba, attn, ffn, units, epsilon=1e-5,
                 ssm_in=1.0, ssm_out=1.0, attention_in=1.0,
                 attention_out=1.0, **kwargs):
        super().__init__(**kwargs)
        self._mults = tuple(map(float, (ssm_in, ssm_out, attention_in,
                                        attention_out)))
        self.input_norm = nn.RMSNorm(units, epsilon)
        self.mamba = mamba
        self.attn = attn
        self.ffn_norm = nn.RMSNorm(units, epsilon)
        self.ffn = ffn

    def forward(self, u):
        ssm_in, ssm_out, attention_in, attention_out = self._mults
        n = self.input_norm(u)
        x = (u + _times(self.mamba(_times(n, ssm_in)), ssm_out)
             + _times(self.attn(_times(n, attention_in)), attention_out))
        return x + self.ffn(self.ffn_norm(x))


class FalconH1Decoder(HybridBlock):
    """``num_layers`` blocks between an embedding and an untied head over
    ``vocab_size`` ids (the slice of the vocabulary this chip holds).
    ``forward(tokens)`` takes ``(B, T)`` ids and returns ``(B, T, vocab)``
    logits.  The multipliers are the configuration's keys; every one
    defaults to 1.  With ``recompute`` every block asks to be run again in
    the backward pass (``HybridBlock.recompute``): a compiled train step
    then holds the blocks' inputs and one block's activations at a time."""

    def __init__(self, vocab_size, units, num_layers, num_heads,
                 num_kv_heads, head_dim, rope_theta, hidden_size, d_ssm,
                 ssm_heads, d_state, n_groups, d_conv=4, chunk_size=128,
                 epsilon=1e-5, embedding_multiplier=1.0,
                 lm_head_multiplier=1.0, ssm_in_multiplier=1.0,
                 ssm_out_multiplier=1.0, attention_in_multiplier=1.0,
                 attention_out_multiplier=1.0, key_multiplier=1.0,
                 mlp_multipliers=(1.0, 1.0), ssm_multipliers=(1.0,) * 5,
                 recompute=False, **kwargs):
        super().__init__(**kwargs)
        self._embed_mult = float(embedding_multiplier)
        self._head_mult = float(lm_head_multiplier)
        self.embed = nn.Embedding(vocab_size, units)
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(FalconH1Block(
                FalconH1Mixer(units, d_ssm, ssm_heads, d_state, n_groups,
                              d_conv, chunk_size,
                              tuple(map(float, ssm_multipliers)), epsilon),
                GroupedQueryAttention(units, num_heads, num_kv_heads,
                                      head_dim, rope_theta, key_multiplier),
                _GatedMLP(units, hidden_size, mlp_multipliers), units,
                epsilon, ssm_in_multiplier, ssm_out_multiplier,
                attention_in_multiplier, attention_out_multiplier
            ).recompute(recompute))
        self.norm = nn.RMSNorm(units, epsilon)
        self.head = _dense(vocab_size, units)

    def forward(self, tokens):
        hidden = self.layers(_times(self.embed(tokens), self._embed_mult))
        return _times(self.head(self.norm(hidden)), self._head_mult)
