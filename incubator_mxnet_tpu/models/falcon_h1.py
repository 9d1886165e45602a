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

Mixer (``gluon.nn.Mamba2Mixer`` with the family's constants): ``[z ; xBC ;
dt] = W_in m``, each part times its constant of
``ssm_multipliers`` (z, x, B, C, dt); ``xBC ← silu(conv1d_causal(xBC))``;
``Δ = softplus(dt + dt_bias)`` and ``A = −exp(A_log)`` a head, float32;
``y = ssd_scan(x, Δ, A, B, C, D)`` (``ops/ssm_ops.py``); ``y ←
RMSNorm_grouped(y ⊙ silu(z))``, the mean square over each group's channels
apart under one gain; ``W_out y``.  No bias but the convolution's.

A multiplier is arithmetic on an activation, applied in float32 and rounded
once to the activation's dtype; none is folded into a weight.
"""
from __future__ import annotations

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.nn.transformer_layers import (  # noqa: F401
    GroupedQueryAttention, Mamba2Mixer, _GroupedRMSNorm, _dense, _times)
from ..ops.registry import invoke

__all__ = ["GroupedQueryAttention", "FalconH1Block", "FalconH1Decoder"]


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
                Mamba2Mixer(units, d_ssm, ssm_heads, d_state, n_groups,
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
