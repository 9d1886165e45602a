"""The plain reference of the ``falcon_h1_34b`` configuration: forward pass,
loss and gradients of the cut model in straightforward ``jax.numpy`` float32
at the highest matmul precision.  No kernel, no Gluon, no chunking: the
state-space scan is its *quadratic form* over the whole sequence, a head at
a time; attention repeats its key heads and masks densely.  It imports
nothing of the program under test; the benchmark's runner and the tests both
compare the program with it.

Layer equations (Falcon-H1 family; keys are the configuration's):

* model: ``h_0 = embedding_multiplier · E[t]``; ``logits =
  lm_head_multiplier · W_head RMSNorm(h_L)``; the loss is the mean
  cross-entropy of position ``i``'s logits against token ``i + 1``;
* block: ``n = RMSNorm(u)``; ``x = u + ssm_out_multiplier ·
  Mixer(ssm_in_multiplier · n) + attention_out_multiplier ·
  Attn(attention_in_multiplier · n)``; ``u' = x + MLP(RMSNorm(x))``;
* attention: ``q = W_q n``, ``k = key_multiplier · W_k n``, ``v = W_v n``;
  rotary over the whole head in the two-halves form (``rope_theta``); query
  head ``i`` reads key head ``i // (heads / kv heads)``; causal softmax of
  ``q·k / sqrt(head_dim)``; ``W_o``;
* MLP: ``mlp_multipliers[1] · W_down(silu(mlp_multipliers[0] · W_gate y) ⊙
  W_up y)``;
* mixer: ``[z ; xBC ; dt] = W_in m``, each part times its constant of
  ``ssm_multipliers`` (z, x, B, C, dt); ``xBC ← silu(conv(xBC))``, depthwise,
  causal, ``mamba_d_conv`` taps and a bias; ``Δ = softplus(dt + dt_bias)``,
  ``A = −exp(A_log)``; the recurrence ``S_t = exp(Δ_t A) S_{t−1} + Δ_t x_t
  B_tᵀ``, ``y_t = S_t C_t + D x_t`` as ``y = ((C Bᵀ) ⊙ L)(Δ ⊙ x) + D x`` with
  ``L_ts = exp(Σ_{s<r≤t} Δ_r A)`` for ``t ≥ s``, else 0; ``y ←
  RMSNorm_grouped(y ⊙ silu(z))`` over each group's channels apart;
  ``W_out y``.

Parameters come as a dict under the net's own names (``layers.0.mamba.
in_proj.weight`` ...; dense weights are ``(out, in)``, the MLP's gate and up
side by side in ``gate_up``), any dtype; they are used as float32, *a block
at a time*: ``loss_and_grads`` keeps each block's float32 input, then runs
the head's and each block's ``jax.vjp`` in reverse with only that block's
float32 weights alive, so that the published widths fit one chip.
``adamw_first_step`` is the optimizer's first step on one weight, for the
comparison with the state the timed program's first call leaves.
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
    """``x`` (T, heads, D): the pair ``(x[i], x[i + D/2])`` of position ``t``
    turns by ``t · theta^(-2i/D)``."""
    t, d = x.shape[0], x.shape[-1]
    angle = (jnp.arange(t, dtype=F32)[:, None]
             * float(theta) ** (-jnp.arange(0, d, 2, dtype=F32) / d))[:, None, :]
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [first * jnp.cos(angle) - second * jnp.sin(angle),
         second * jnp.cos(angle) + first * jnp.sin(angle)], axis=-1)


def attention(p, n, cfg, mm):
    """One sequence ``n`` (T, hidden), already times its multiplier."""
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    t = n.shape[0]
    w = lambda name: p[f"attn.{name}.weight"].T
    q = rotary(mm(n, w("q")).reshape(t, heads, d), cfg["rope_theta"])
    k = rotary((cfg["key_multiplier"] * mm(n, w("k"))).reshape(t, kv, d),
               cfg["rope_theta"])
    v = mm(n, w("v")).reshape(t, kv, d)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):                      # one head at a time: (T, T) scores
        q, k, v = qkv
        scores = mm(q, k.T) * d ** -0.5
        return mm(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1), v)

    out = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return mm(jnp.swapaxes(out, 0, 1).reshape(t, heads * d), w("o"))


def conv_silu(x, weight, bias):
    """``x`` (T, C), ``weight`` (C, K): tap ``k`` reads ``K − 1 − k``
    positions back; zeros before the sequence."""
    t, taps = x.shape[0], weight.shape[1]
    acc = jnp.broadcast_to(bias, x.shape)
    for k in range(taps):
        back = taps - 1 - k
        acc = acc + weight[:, k] * jnp.pad(x, ((back, 0), (0, 0)))[:t]
    return jax.nn.silu(acc)


def scan_quadratic(x, delta, a, b, c, d_skip, mm):
    """``x`` (T, H, P), ``delta`` (T, H), ``a`` (H,), ``b`` and ``c`` (T, G,
    N), ``d_skip`` (H,): the recurrence's closed form with the full ``T × T``
    decay matrix, a head at a time."""
    t, heads = delta.shape
    per_group = heads // b.shape[1]
    since = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(args):
        x, delta, a, b, c, d_skip = args
        total = jnp.cumsum(delta * a)               # Σ_{r≤t} Δ_r A
        decay = jnp.exp(jnp.where(since, total[:, None] - total[None, :],
                                  -jnp.inf))
        return mm(mm(c, b.T) * decay, delta[:, None] * x) + d_skip * x

    by_head = lambda v: jnp.repeat(jnp.swapaxes(v, 0, 1), per_group, axis=0)
    y = jax.lax.map(head, (jnp.swapaxes(x, 0, 1), delta.T, a, by_head(b),
                           by_head(c), d_skip))
    return jnp.swapaxes(y, 0, 1)


def mixer(p, m, cfg, mm):
    """One sequence ``m`` (T, hidden), already times its multiplier."""
    d, heads, n, groups = (cfg["mamba_d_ssm"], cfg["mamba_n_heads"],
                           cfg["mamba_d_state"], cfg["mamba_n_groups"])
    t, bc = m.shape[0], groups * n
    mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
    proj = mm(m, p["mamba.in_proj.weight"].T)
    z = mz * proj[:, :d]
    xbc = jnp.concatenate([mx * proj[:, d:2 * d],
                           mb * proj[:, 2 * d:2 * d + bc],
                           mc * proj[:, 2 * d + bc:2 * d + 2 * bc]], -1)
    dt = mdt * proj[:, 2 * d + 2 * bc:]
    xbc = conv_silu(xbc, p["mamba.conv_weight"], p["mamba.conv_bias"])
    y = scan_quadratic(
        xbc[:, :d].reshape(t, heads, d // heads),
        jax.nn.softplus(dt + p["mamba.dt_bias"]),
        -jnp.exp(p["mamba.a_log"]), xbc[:, d:d + bc].reshape(t, groups, n),
        xbc[:, d + bc:].reshape(t, groups, n), p["mamba.d_skip"][:, None],
        mm)
    gated = (y.reshape(t, d) * jax.nn.silu(z)).reshape(t, groups, d // groups)
    normed = rms_norm(gated, p["mamba.norm.gamma"].reshape(groups, -1),
                      cfg["rms_norm_eps"])
    return mm(normed.reshape(t, d), p["mamba.out_proj.weight"].T)


def block(p, u, cfg, round_to=None):
    """``u`` (B, T, hidden) through one block whose parameters ``p`` come
    under their names inside the block (``mamba.in_proj.weight`` ...)."""
    mm = functools.partial(_mm, round_to=round_to)
    p = {name: value.astype(F32) for name, value in p.items()}
    eps = cfg["rms_norm_eps"]
    n = rms_norm(u, p["input_norm.gamma"], eps)
    x = (u
         + cfg["ssm_out_multiplier"] * jax.lax.map(
             lambda m: mixer(p, m, cfg, mm), cfg["ssm_in_multiplier"] * n)
         + cfg["attention_out_multiplier"] * jax.lax.map(
             lambda s: attention(p, s, cfg, mm),
             cfg["attention_in_multiplier"] * n))
    gate_mult, down_mult = cfg["mlp_multipliers"]
    gate, up = jnp.split(mm(rms_norm(x, p["ffn_norm.gamma"], eps),
                            p["ffn.gate_up.weight"].T), 2, axis=-1)
    return x + down_mult * mm(jax.nn.silu(gate_mult * gate) * up,
                              p["ffn.down.weight"].T)


def embed(p, tokens, cfg):
    return cfg["embedding_multiplier"] * p["embed.weight"].astype(F32)[tokens]


def head_loss(p, h, labels, cfg, round_to=None):
    """The loss and the logits from the last block's output; ``labels`` (B,
    1, T) hold token ``i + 1`` at position ``i``."""
    mm = functools.partial(_mm, round_to=round_to)
    logits = cfg["lm_head_multiplier"] * mm(
        rms_norm(h, p["norm.gamma"].astype(F32), cfg["rms_norm_eps"]),
        p["head.weight"].astype(F32).T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, labels[:, 0, :, None], -1)), logits


def adamw_first_step(w, g, learning_rate, wd, b1=0.9, b2=0.999, eps=1e-8):
    """``(first moment, new weight)`` after AdamW's first step from zero
    moments on the float32 weight ``w`` with the gradient ``g``: ``m = (1 −
    b1) g``, ``v = (1 − b2) g²``, ``w' = w − lr · (sqrt(1 − b2) / (1 − b1))
    · m / (sqrt(v) + eps) − lr · wd · w`` (decoupled decay; ``eps`` beside
    the uncorrected ``sqrt(v)``, as the configuration's departures say).
    Where ``|g|`` is well over ``eps / sqrt(1 − b2)`` = 3.2e-7 the step is
    ``lr · sign(g)``."""
    m, v = (1.0 - b1) * g, (1.0 - b2) * g * g
    step = (1.0 - b2) ** 0.5 / (1.0 - b1) * m / (jnp.sqrt(v) + eps)
    return m, w - learning_rate * step - learning_rate * wd * w


def of_block(p, i):
    """Block ``i``'s parameters under their names inside the block."""
    prefix = f"layers.{i}."
    return {name[len(prefix):]: value for name, value in p.items()
            if name.startswith(prefix)}


def loss_and_grads(p, tokens, labels, cfg, round_to=None, fold=None):
    """``((loss, logits), gradients)`` with a float32 gradient for every
    parameter, computed a block at a time (module docstring).  With
    ``fold``, each part's gradients (a dict by name) are handed to it as
    soon as they exist and are not kept: the dict returned is then empty."""
    grads = {}
    keep = fold or grads.update
    with jax.default_matmul_precision("highest"):
        forward = jax.jit(lambda q, h: block(q, h, cfg, round_to))
        backward = jax.jit(lambda q, h, g: jax.vjp(
            lambda q, h: block(q, h, cfg, round_to),
            {n: v.astype(F32) for n, v in q.items()}, h)[1](g))
        inputs = [jax.jit(lambda w, t: embed({"embed.weight": w}, t, cfg))(
            p["embed.weight"], tokens)]
        for i in range(cfg["num_hidden_layers"]):
            inputs.append(forward(of_block(p, i), inputs[-1]))
        def top(q, h):
            q = {n: v.astype(F32) for n, v in q.items()}
            loss, pull, logits = jax.vjp(
                lambda q, h: head_loss(q, h, labels, cfg, round_to), q, h,
                has_aux=True)
            return (loss, logits) + pull(jnp.ones((), F32))

        loss, logits, d_top, felt = jax.jit(top)(
            {n: p[n] for n in ("norm.gamma", "head.weight")}, inputs.pop())
        keep(d_top)
        del d_top
        for i in reversed(range(cfg["num_hidden_layers"])):
            d_block, felt = backward(of_block(p, i), inputs.pop(), felt)
            keep({f"layers.{i}.{n}": g for n, g in d_block.items()})
            del d_block
        keep({"embed.weight": jax.jit(
            lambda g: cfg["embedding_multiplier"] * jnp.zeros(
                p["embed.weight"].shape, F32).at[tokens].add(g))(felt)})
    return (loss, logits), grads
