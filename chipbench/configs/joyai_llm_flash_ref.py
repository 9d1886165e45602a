"""The plain reference of the ``joyai_llm_flash`` configuration: forward
pass, loss and gradients of the cut model in straightforward ``jax.numpy``
float32 at the highest matmul precision.  No kernel, no Gluon, no sorting:
the routed layer is a loop over the held experts with a dense mask.  It
imports nothing of the program under test; the benchmark's runner and the
tests both compare the program with it.

Layer equations (DeepSeek-V3 family; keys are the configuration's):

* block: ``x = h + MLA(RMSNorm(h))``, ``h' = x + FFN(RMSNorm(x))``; the
  first ``first_k_dense_replace`` layers' FFN is dense SwiGLU of width
  ``intermediate_size``, every other layer's is routed;
* MLA: ``c_q = RMSNorm(W_qa u)``, ``q = W_qb c_q``, ``[c_kv; k_r] = W_kva
  u``, ``[k_nope; v] = W_kvb RMSNorm(c_kv)``, rotary over interleaved pairs
  (``rope_theta``) on q's last ``qk_rope_head_dim`` columns and on ``k_r``,
  which all heads share, causal softmax of ``q·k / sqrt(qk_head_dim)``,
  ``W_o``;
* router: ``s = sigmoid(W_r x)``; the ``num_experts_per_tok`` largest of
  ``s + b``; gates ``routed_scaling_factor · s_sel / Σ s_sel``; ``y =
  Shared(x) + Σ_e g_e Expert_e(x)`` over the experts held here
  (``held_experts = [first, count]``): what the absent experts would have
  added is left out; the bias moves by ``gamma · sign(mean load − load)``;
* MTP: ``W_eh [RMSNorm(h_L); RMSNorm(E[t_{i+1}])]``, one more routed block,
  the shared final norm and head; loss ``CE(main, t_{i+1}) + lambda ·
  CE(mtp, t_{i+2})``.

Parameters come as a dict under the net's own names (``layers.0.attn.
q_a.weight`` ...; dense weights are ``(out, in)``), any dtype; they are
used as float32.
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _mm(a, b, round_to):
    """``a @ b`` in float32; ``round_to`` rounds both operands to a lower
    precision first (the probe that shows the comparison's limits would
    catch one)."""
    if round_to is not None:
        a, b = (v.astype(round_to).astype(F32) for v in (a, b))
    return jnp.matmul(a, b)


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def rotary(x, theta):
    """``x`` (T, heads, D): the pair ``(x[2i], x[2i+1])`` of position ``t``
    turns by ``t · theta^(-2i/D)``."""
    t, d = x.shape[0], x.shape[-1]
    angle = (jnp.arange(t, dtype=F32)[:, None]
             * theta ** (-jnp.arange(0, d, 2, dtype=F32) / d))[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                      even * jnp.sin(angle) + odd * jnp.cos(angle)],
                     axis=-1).reshape(x.shape)


def swiglu(x, w_gate_up, w_down, mm):
    """``w_gate_up`` (in, 2·width) gate and up side by side; ``w_down``
    (width, in)."""
    gate, up = jnp.split(mm(x, w_gate_up), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w_down)


def attention(p, prefix, u, cfg, mm):
    """One sequence ``u`` (T, hidden)."""
    heads, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, vd, rank = (cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                      cfg["kv_lora_rank"])
    eps, t = cfg["rms_norm_eps"], u.shape[0]
    w = lambda name: p[f"{prefix}.{name}.weight"].astype(F32).T
    c_q = rms_norm(mm(u, w("q_a")), p[f"{prefix}.q_norm.gamma"], eps)
    q = mm(c_q, w("q_b")).reshape(t, heads, nope + rope)
    kv = mm(u, w("kv_a"))
    k_rot = rotary(kv[:, None, rank:], cfg["rope_theta"])
    kvb = mm(rms_norm(kv[:, :rank], p[f"{prefix}.kv_norm.gamma"], eps),
             w("kv_b")).reshape(t, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope],
                         rotary(q[..., nope:], cfg["rope_theta"])], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_rot, (t, heads, rope))], -1)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):                      # one head at a time: (T, T) scores
        q, k, v = qkv
        scores = mm(q, k.T) * (nope + rope) ** -0.5
        return mm(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1), v)

    out = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1)
                                  for a in (q, k, kvb[..., nope:])))
    return mm(jnp.swapaxes(out, 0, 1).reshape(t, heads * vd), w("o"))


def route(p, prefix, x, cfg, forced=None, margin=0.0):
    """Scores, the chosen experts and their gates for rows ``x`` (N,
    hidden).  Rows whose 8th and 9th largest ``s + b`` lie within
    ``margin`` of one another take ``forced``'s choice where one is given
    (the program's own: a near tie may fall either way on rounding).
    Returns ``(idx, gates, near_tie, own_idx)``."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.matmul(x, p[f"{prefix}.router_weight"]
                                  .astype(F32).T))
    biased = jax.lax.stop_gradient(s) + p[f"{prefix}.score_bias"].astype(F32)
    ranked = jnp.argsort(-biased, axis=-1, stable=True)
    own = ranked[:, :k]
    top = jnp.take_along_axis(biased, ranked[:, k - 1:k + 1], axis=-1)
    near_tie = (top[:, 0] - top[:, 1]) < margin
    idx = own if forced is None else jnp.where(near_tie[:, None], forced, own)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    gates = cfg["routed_scaling_factor"] * chosen / jnp.sum(
        chosen, -1, keepdims=True)
    return idx, gates, near_tie, own


def routed_ffn(p, prefix, x, cfg, mm, held=None, forced=None, margin=0.0,
               shared=True):
    """Rows ``x`` (N, hidden) → this share's part of the layer, and what
    the router did.  ``held = (first, count)`` defaults to the
    configuration's ``held_experts``; the expert weights in ``p`` are
    those of the held experts, in order."""
    first, count = held or cfg["held_experts"]
    idx, gates, near_tie, own = route(p, prefix, x, cfg, forced, margin)
    w_in = p[f"{prefix}.experts_in"].astype(F32)
    w_out = p[f"{prefix}.experts_out"].astype(F32)

    @jax.checkpoint
    def add_expert(y, e):               # a dense mask: every row, weight 0
        weight = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        return y + weight[:, None] * swiglu(x, w_in[e], w_out[e], mm), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), jnp.arange(count))
    if shared:
        y = y + swiglu(x, p[f"{prefix}.shared.gate_up.weight"].astype(F32).T,
                       p[f"{prefix}.shared.down.weight"].astype(F32).T, mm)
    load = jnp.sum(idx.reshape(-1, 1) == jnp.arange(
        cfg["router_outputs"]), axis=0).astype(F32)
    return y, {"idx": idx, "near_tie": near_tie, "own_idx": own,
               "load": load}


def block(p, prefix, h, cfg, mm, dense, forced=None, margin=0.0):
    """``h`` (B, T, hidden) through one block; also what its router did
    (None for a dense block)."""
    eps = cfg["rms_norm_eps"]
    attend = jax.checkpoint(lambda u: attention(
        p, f"{prefix}.attn", u, cfg, mm))
    x = h + jax.lax.map(attend, rms_norm(
        h, p[f"{prefix}.attn_norm.gamma"], eps))
    normed = rms_norm(x, p[f"{prefix}.ffn_norm.gamma"], eps)
    if dense:
        return x + swiglu(
            normed, p[f"{prefix}.ffn.gate_up.weight"].astype(F32).T,
            p[f"{prefix}.ffn.down.weight"].astype(F32).T, mm), None
    y, did = routed_ffn(
        p, f"{prefix}.ffn", normed.reshape(-1, normed.shape[-1]), cfg, mm,
        forced=(forced or {}).get(f"{prefix}.ffn"), margin=margin)
    return x + y.reshape(x.shape), did


def forward(p, tokens, cfg, forced=None, margin=0.0, round_to=None):
    """``tokens`` (B, T + 1) → ``(main, mtp)`` logits (B, T, vocab) and the
    routing of every routed layer by its name.  ``forced`` maps such names
    to ``(B·T, k)`` choices (see :func:`route`)."""
    mm = functools.partial(_mm, round_to=round_to)
    eps, routing = cfg["rms_norm_eps"], {}
    embedded = p["embed.weight"].astype(F32)[tokens]
    h = embedded[:, :-1]
    for i in range(cfg["num_hidden_layers"]):
        h, did = jax.checkpoint(lambda p, h, i=i: block(
            p, f"layers.{i}", h, cfg, mm, i < cfg["first_k_dense_replace"],
            forced, margin))(p, h)
        if did is not None:
            routing[f"layers.{i}.ffn"] = did
    head = lambda h: mm(rms_norm(h, p["norm.gamma"], eps),
                        p["head.weight"].astype(F32).T)
    main = head(h)
    joined = jnp.concatenate(
        [rms_norm(h, p["mtp.hidden_norm.gamma"], eps),
         rms_norm(embedded[:, 1:], p["mtp.embed_norm.gamma"], eps)], -1)
    h_mtp, routing["mtp.block.ffn"] = block(
        p, "mtp.block", mm(joined, p["mtp.proj.weight"].astype(F32).T),
        cfg, mm, False, forced, margin)
    return main, head(h_mtp), routing


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def loss(p, tokens, labels, cfg, **kwargs):
    """``labels`` (B, 2, T): ``t_{i+1}`` and ``t_{i+2}``.  Returns the loss
    and ``(main, mtp, routing)``."""
    main, mtp, routing = forward(p, tokens, cfg, **kwargs)
    value = (cross_entropy(main, labels[:, 0])
             + cfg["mtp_loss_weight"] * cross_entropy(mtp, labels[:, 1]))
    return value, (main, mtp, routing)


def loss_and_grads(p, tokens, labels, cfg, **kwargs):
    """``((loss, (main, mtp, routing)), gradients)`` with a gradient for
    every float parameter but the router's bias, which has none."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss(p, tokens, labels, cfg, **kwargs),
            has_aux=True)(p)


def bias_after_step(bias, load, cfg):
    """The router's bias after one training step on this chip's tokens."""
    return bias + cfg["bias_update_gamma"] * jnp.sign(jnp.mean(load) - load)
