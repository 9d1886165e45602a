"""Causal decoder with latent attention, routed feed-forward layers and a
multi-token-prediction module: the layer equations of the DeepSeek-V3
family, as Gluon blocks over the registered ops, so that
``amp.convert_block``, ``fuse.make_fused_train_step``, the block scopes and
``pallas_kernels.dispatch`` apply as they do to every other model.

Block: ``x = h + MLA(RMSNorm(h))``, ``h' = x + FFN(RMSNorm(x))``; the first
``first_dense`` layers' FFN is a dense SwiGLU, every other layer's a
:class:`~..gluon.nn.RoutedFFN` with one shared expert.  No bias anywhere.

MLA: ``c_q = RMSNorm(W_qa u)``; ``q = W_qb c_q`` → heads × (nope + rope);
``[c_kv ; k_r] = W_kva u``; ``[k_nope ; v] = W_kvb RMSNorm(c_kv)`` → heads ×
(nope + v); rotary (interleaved pairs) on ``q``'s last ``rope`` columns and
on ``k_r``, which all heads share; causal softmax of ``q·k / sqrt(nope +
rope)``; ``W_o`` over heads × v.  v is narrower than q and k, which
``dot_product_attention`` takes as it is.

MTP (one module): ``h' = W_eh [RMSNorm(h_L) ; RMSNorm(E[t_{i+1}])]``, one
more block, the shared final norm, embedding and head; it predicts
``t_{i+2}``.  The model takes ``T + 1`` tokens a sequence and returns the
two heads' logits over ``T`` positions each, ``(main, mtp)``; the loss that
weights them is ``gluon.loss.WeightedHeadsSoftmaxCELoss``.
"""
from __future__ import annotations

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import concat
from ..ops.registry import invoke

__all__ = ["MLAttention", "DecoderBlock", "MLAMoEDecoder"]


def _dense(units, in_units):
    return nn.Dense(units, use_bias=False, flatten=False, in_units=in_units)


class MLAttention(HybridBlock):
    """Multi-head latent attention, training form (no cache)."""

    def __init__(self, units, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._rank = num_heads, kv_lora_rank
        self._nope, self._rope, self._v = (qk_nope_head_dim,
                                           qk_rope_head_dim, v_head_dim)
        self._theta = rope_theta
        self.q_a = _dense(q_lora_rank, units)
        self.q_norm = nn.RMSNorm(q_lora_rank, epsilon)
        self.q_b = _dense(num_heads * (qk_nope_head_dim + qk_rope_head_dim),
                          q_lora_rank)
        self.kv_a = _dense(kv_lora_rank + qk_rope_head_dim, units)
        self.kv_norm = nn.RMSNorm(kv_lora_rank, epsilon)
        self.kv_b = _dense(num_heads * (qk_nope_head_dim + v_head_dim),
                           kv_lora_rank)
        self.o = _dense(units, num_heads * v_head_dim)

    def forward(self, x):
        b, t, _ = x.shape
        h, nope, rope = self._heads, self._nope, self._rope
        q = self.q_b(self.q_norm(self.q_a(x))).reshape((b, t, h, nope + rope))
        kv = self.kv_a(x)
        k_rot = invoke("rope", kv[..., self._rank:].reshape((b, t, 1, rope)),
                       theta=self._theta)
        kv = self.kv_b(self.kv_norm(kv[..., :self._rank])).reshape(
            (b, t, h, nope + self._v))
        q = concat(q[..., :nope],
                   invoke("rope", q[..., nope:], theta=self._theta), dim=-1)
        k = concat(kv[..., :nope],
                   invoke("broadcast_to", k_rot, shape=(b, t, h, rope)),
                   dim=-1)
        out = invoke("dot_product_attention",
                     q.transpose((0, 2, 1, 3)), k.transpose((0, 2, 1, 3)),
                     kv[..., nope:].transpose((0, 2, 1, 3)), causal=True)
        return self.o(out.transpose((0, 2, 1, 3)).reshape(
            (b, t, h * self._v)))


class DecoderBlock(HybridBlock):
    """Pre-norm block: ``attn`` (MLA) and ``ffn`` (dense SwiGLU or routed)."""

    def __init__(self, attention, ffn, units, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self.attn_norm = nn.RMSNorm(units, epsilon)
        self.attn = attention
        self.ffn_norm = nn.RMSNorm(units, epsilon)
        self.ffn = ffn

    def forward(self, x):
        x = x + self.attn(self.attn_norm(x))
        return x + self.ffn(self.ffn_norm(x))


class _MTP(HybridBlock):
    """The multi-token-prediction module's own parameters: two norms, the
    4,096 → 2,048-style projection and one more block."""

    def __init__(self, block, units, epsilon, **kwargs):
        super().__init__(**kwargs)
        self.hidden_norm = nn.RMSNorm(units, epsilon)
        self.embed_norm = nn.RMSNorm(units, epsilon)
        self.proj = _dense(units, 2 * units)
        self.block = block

    def forward(self, hidden, next_embedded):
        return self.block(self.proj(concat(
            self.hidden_norm(hidden), self.embed_norm(next_embedded),
            dim=-1)))


class MLAMoEDecoder(HybridBlock):
    """``num_layers`` blocks (the first ``first_dense`` dense, the rest
    routed) and one multi-token-prediction module.

    ``held = (first, count)`` are the routed experts this chip holds of
    ``n_experts`` in every routed layer; ``vocab_size`` is the slice of the
    vocabulary it holds.  ``forward(tokens)`` takes ``(B, T + 1)`` ids
    and returns ``(main, mtp)`` logits ``(B, T, vocab)``: position ``i`` of
    ``main`` predicts ``t_{i+1}``, of ``mtp`` ``t_{i+2}``."""

    def __init__(self, vocab_size, units, num_layers, num_heads,
                 q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, rope_theta, dense_hidden_size,
                 expert_hidden_size, n_experts, held, top_k, scale,
                 gamma=0.001, capacity_factor=1.5, first_dense=1,
                 n_shared=1, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)

        def block(dense):
            attention = MLAttention(
                units, num_heads, q_lora_rank, kv_lora_rank,
                qk_nope_head_dim, qk_rope_head_dim, v_head_dim, rope_theta,
                epsilon)
            ffn = nn.SwiGLU(units, dense_hidden_size) if dense else \
                nn.RoutedFFN(units, expert_hidden_size, n_experts, held,
                             top_k, scale, gamma, capacity_factor,
                             n_shared * expert_hidden_size)
            return DecoderBlock(attention, ffn, units, epsilon)

        self.embed = nn.Embedding(vocab_size, units)
        self.layers = nn.HybridSequential()
        for i in range(num_layers):
            self.layers.add(block(i < first_dense))
        self.mtp = _MTP(block(False), units, epsilon)
        self.norm = nn.RMSNorm(units, epsilon)
        self.head = _dense(vocab_size, units)

    def forward(self, tokens):
        embedded = self.embed(tokens)
        hidden = self.layers(embedded[:, :-1])
        main = self.head(self.norm(hidden))
        return main, self.head(self.norm(self.mtp(hidden, embedded[:, 1:])))
