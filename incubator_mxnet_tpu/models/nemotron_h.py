"""Causal decoder whose layers differ in kind: every layer is one pre-normed
residual branch — a Mamba-2 mixer, a latent mixture of experts or attention
alone — chosen by a character of a pattern, with a multi-token-prediction
module: the layer equations of the Nemotron-H family (``model_type``
``nemotron_h``), as Gluon blocks over the registered ops, so that
``amp.convert_block``, ``fuse.make_fused_train_step``, the block scopes and
``pallas_kernels.dispatch`` apply as they do to every other model.

Model: ``h_0 = E[t]``; layer ``i`` of kind ``c = pattern[i]``: ``h ← h +
Branch_c(RMSNorm_i(h))``; ``logits = W_head RMSNorm_f(h_L)``, the head
untied.

* ``M`` — ``gluon.nn.Mamba2Mixer`` with every multiplier 1 (block key
  ``mamba``): ``[z ; xBC ; dt] = W_in u``; ``xBC ← silu(conv1d_causal(xBC))``;
  ``Δ = softplus(dt + dt_bias)``, ``A = −exp(A_log)``; ``y = ssd_scan(x, Δ,
  A, B, C, D)``; ``y ← RMSNorm_grouped(y ⊙ silu(z))`` (gate before norm);
  ``W_out y``.
* ``*`` — ``gluon.nn.GroupedQueryAttention`` without a position embedding
  (block key ``attn``): ``q = W_q u``, ``k, v = W_k u, W_v u``, causal softmax
  of ``q·k / sqrt(head_dim)``, query head ``i`` reading key head ``i //
  (heads / kv heads)``; ``W_o``.  Position comes through the Mamba layers.
* ``E`` — ``gluon.nn.RoutedFFN`` with a latent (block key ``moe``): scores
  ``s = sigmoid(W_r u)`` float32 over all experts; the ``top_k`` largest of
  ``s + b``; gates ``scale · s_e / Σ s``; ``ℓ = W_down u``; ``r = Σ g_e ·
  relu(ℓ W1_e)² W2_e`` over the experts held here; ``W_up r + relu(u V1)²
  V2``.  No bias anywhere but the mixer's convolution.

MTP (``mla_moe._MTP`` with a body of ``mtp_pattern``'s layers and a final
norm of its own): ``h' = W_eh [RMSNorm(h_L) ; RMSNorm(E[t_{i+1}])]``, the
body, then the main model's own head; it predicts ``t_{i+2}``.  The model
takes ``T + 1`` tokens a sequence and returns the two heads' logits over
``T`` positions each, ``(main, mtp)``.
"""
from __future__ import annotations

from ..gluon import nn
from ..gluon.block import HybridBlock
from .mla_moe import _MTP, _dense

__all__ = ["layer_kinds", "HybridLayer", "NemotronHDecoder"]

KINDS = {"M": "mamba", "*": "attn", "E": "moe"}


def layer_kinds(pattern):
    """``'*EM'`` → ``['attn', 'moe', 'mamba']``: the block key of every
    layer's branch; a character that names no kind raises."""
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown or not pattern:
        raise ValueError(
            f"layer pattern {pattern!r}: {unknown or 'no layer'} is none of "
            f"{sorted(KINDS)} (M Mamba-2 mixer, * attention, E experts)")
    return [KINDS[c] for c in pattern]


class HybridLayer(HybridBlock):
    """``h + branch(RMSNorm(h))``; the branch is registered under its kind
    (``mamba``, ``attn`` or ``moe``), which is its block key in a trace."""

    def __init__(self, kind, branch, units, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._kind = kind
        self.norm = nn.RMSNorm(units, epsilon)
        setattr(self, kind, branch)

    def forward(self, h):
        return h + getattr(self, self._kind)(self.norm(h))


class NemotronHDecoder(HybridBlock):
    """The layers of ``pattern`` between an embedding and an untied head
    over ``vocab_size`` ids (the slice of the vocabulary this chip holds),
    and one multi-token-prediction module whose body is ``mtp_pattern``.

    ``held = (first, count)`` are the routed experts this chip holds of
    ``n_experts`` in every ``E`` layer.  ``forward(tokens)`` takes ``(B, T +
    1)`` ids and returns ``(main, mtp)`` logits ``(B, T, vocab)``: position
    ``i`` of ``main`` predicts ``t_{i+1}``, of ``mtp`` ``t_{i+2}``.  With
    ``recompute`` every layer asks to be run again in the backward pass
    (``HybridBlock.recompute``)."""

    def __init__(self, vocab_size, units, pattern, mtp_pattern, num_heads,
                 num_kv_heads, head_dim, d_ssm, ssm_heads, d_state, n_groups,
                 d_conv, chunk_size, expert_hidden_size, latent_size,
                 shared_hidden_size, n_experts, held, top_k, scale,
                 gamma=0.001, capacity_factor=1.5, epsilon=1e-5,
                 recompute=False, **kwargs):
        super().__init__(**kwargs)

        def layer(kind):
            if kind == "mamba":
                branch = nn.Mamba2Mixer(units, d_ssm, ssm_heads, d_state,
                                        n_groups, d_conv, chunk_size,
                                        epsilon=epsilon)
            elif kind == "attn":
                branch = nn.GroupedQueryAttention(
                    units, num_heads, num_kv_heads, head_dim, rope_theta=None)
            else:
                branch = nn.RoutedFFN(
                    units, expert_hidden_size, n_experts, held, top_k, scale,
                    gamma, capacity_factor, shared_hidden_size, latent_size,
                    activation="relu2")
            return HybridLayer(kind, branch, units, epsilon
                               ).recompute(recompute)

        self.embed = nn.Embedding(vocab_size, units)
        self.layers = nn.HybridSequential()
        for kind in layer_kinds(pattern):
            self.layers.add(layer(kind))
        body = nn.HybridSequential()
        for kind in layer_kinds(mtp_pattern):
            body.add(layer(kind))
        body.add(nn.RMSNorm(units, epsilon))
        self.mtp = _MTP(body, units, epsilon)
        self.norm = nn.RMSNorm(units, epsilon)
        self.head = _dense(vocab_size, units)

    def forward(self, tokens):
        embedded = self.embed(tokens)
        hidden = self.layers(embedded[:, :-1])
        return (self.head(self.norm(hidden)),
                self.head(self.mtp(hidden, embedded[:, 1:])))
