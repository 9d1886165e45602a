"""The plain reference of the ``nemotron3_super_120b`` configuration: forward
pass, both heads' losses and gradients of the cut model in straightforward
``jax.numpy`` float32 at the highest matmul precision.  No kernel, no Gluon,
no chunking, no sorting: the state-space scan is its *quadratic form* over
the whole sequence, a head at a time; attention repeats its key heads and
masks densely; the routed layer is a loop over the held experts with a dense
mask.  It imports nothing of the program under test; the benchmark's runner
and the tests both compare the program with it.  What it shares with the two
older references (the norm, the convolution, the quadratic scan, the router,
AdamW's first step) it imports from them.

Layer equations (``model_type`` ``nemotron_h``; keys are the
configuration's).  ``h ← h + Branch_c(RMSNorm(h))`` for the layer's kind
``c`` of ``hybrid_override_pattern``, ``eps`` ``layer_norm_epsilon``; after
the last layer a final norm and an untied head:

* ``M``: ``[z | xBC | dt] = u W_in``; ``xBC ← silu(conv(xBC))``, depthwise,
  causal, ``conv_kernel`` taps and a bias; ``Δ = softplus(dt + dt_bias)``,
  ``A = −exp(A_log)``; ``S_t = exp(Δ_t A) S_{t−1} + Δ_t x_t B_tᵀ``, ``y_t =
  S_t C_t + D x_t`` as ``y = ((C Bᵀ) ⊙ L)(Δ ⊙ x) + D x``; ``y ←
  RMSNorm_grouped(y ⊙ silu(z))`` over ``n_groups`` groups apart (gate before
  norm); ``y W_out``.
* ``*``: ``q = u W_q``, ``k, v = u W_k, u W_v``; **no rotary embedding**;
  query head ``i`` reads key head ``i // (heads / kv heads)``; causal
  softmax of ``q·k / sqrt(head_dim)``; ``W_o``.
* ``E``: ``s = sigmoid(u W_r)`` over all ``router_outputs``; the
  ``num_experts_per_tok`` largest of ``s + b``; gates
  ``routed_scaling_factor · s_e / Σ s``; ``ℓ = u W_down``; ``r = Σ g_e ·
  relu(ℓ W1_e)² W2_e`` over the experts held here (``held_experts = [first,
  count]``; what absent experts would have added is left out); ``r W_up +
  relu(u V1)² V2``.
* MTP: ``h' = [RMSNorm(h_L) ; RMSNorm(E[t_{i+1}])] W_eh``, the layers of
  ``mtp_hybrid_override_pattern``, a final norm of its own, the main head;
  loss ``CE(main, t_{i+1}) + mtp_loss_weight · CE(mtp, t_{i+2})``.

Parameters come as a dict under the net's own names (``layers.0.attn.
q.weight`` ...; dense weights are ``(out, in)``), any dtype; they are used as
float32, *a layer at a time*: ``loss_and_grads`` keeps each layer's float32
input, then runs the heads' and each layer's ``jax.vjp`` in reverse with only
that layer's float32 weights alive, so that the published widths fit one
chip.
"""
import functools
import json

import jax
import jax.numpy as jnp

from chipbench.configs.falcon_h1_34b_ref import (  # noqa: F401
    adamw_first_step, conv_silu, rms_norm, scan_quadratic)
from chipbench.configs.joyai_llm_flash_ref import cross_entropy, route

F32 = jnp.float32
KINDS = {"M": "mamba", "*": "attn", "E": "moe"}
PROBE = jnp.float8_e4m3fn   # what ``round_to`` is where the caller names none


def _mm(a, b, low=False, probe=PROBE):
    """``a @ b`` in float32; where ``low`` (which may be traced: the probe
    and the reference proper then share one compiled program) both operands
    are rounded to ``probe`` first, the lower precision that shows the
    comparison's limits would catch one."""
    if low is not False:
        a, b = (jnp.where(low, v.astype(probe).astype(F32), v)
                for v in (a, b))
    return jnp.matmul(a, b)


def relu2_mlp(x, w_up, w_down, mm):
    """``relu(x W_up)² W_down``; ``w_up`` (in, width), ``w_down`` (width,
    out)."""
    return mm(jnp.square(jax.nn.relu(mm(x, w_up))), w_down)


def attention(p, u, cfg, mm):
    """One sequence ``u`` (T, hidden); no position embedding."""
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    t = u.shape[0]
    w = lambda name: p[f"attn.{name}.weight"].T
    q = mm(u, w("q")).reshape(t, heads, d)
    k, v = (jnp.repeat(mm(u, w(name)).reshape(t, kv, d), heads // kv, axis=1)
            for name in ("k", "v"))
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):                      # one head at a time: (T, T) scores
        q, k, v = qkv
        scores = mm(q, k.T) * d ** -0.5
        return mm(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1), v)

    out = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return mm(jnp.swapaxes(out, 0, 1).reshape(t, heads * d), w("o"))


def mixer(p, u, cfg, mm):
    """One sequence ``u`` (T, hidden)."""
    heads, n, groups = (cfg["mamba_num_heads"], cfg["ssm_state_size"],
                        cfg["n_groups"])
    d = heads * cfg["mamba_head_dim"]
    t, bc = u.shape[0], groups * n
    proj = mm(u, p["mamba.in_proj.weight"].T)
    z, dt = proj[:, :d], proj[:, 2 * d + 2 * bc:]
    xbc = conv_silu(proj[:, d:2 * d + 2 * bc], p["mamba.conv_weight"],
                    p["mamba.conv_bias"])
    y = scan_quadratic(
        xbc[:, :d].reshape(t, heads, d // heads),
        jax.nn.softplus(dt + p["mamba.dt_bias"]),
        -jnp.exp(p["mamba.a_log"]), xbc[:, d:d + bc].reshape(t, groups, n),
        xbc[:, d + bc:].reshape(t, groups, n), p["mamba.d_skip"][:, None],
        mm)
    gated = (y.reshape(t, d) * jax.nn.silu(z)).reshape(t, groups, d // groups)
    normed = rms_norm(gated, p["mamba.norm.gamma"].reshape(groups, -1),
                      cfg["layer_norm_epsilon"])
    return mm(normed.reshape(t, d), p["mamba.out_proj.weight"].T)


def latent_moe(p, x, cfg, mm, held=None, forced=None, margin=0.0,
               shared=True):
    """Rows ``x`` (N, hidden) → this share's part of the layer, and what the
    router did.  ``held = (first, count)`` defaults to the configuration's
    ``held_experts``; the expert weights in ``p`` are those of the held
    experts, in order.  This share's gated sum goes through ``W_up``; the
    shared expert, which every share computes alike, is added where
    ``shared``."""
    first, count = held or cfg["held_experts"]
    idx, gates, near_tie, own = route(p, "moe", x, cfg, forced, margin)
    latent = mm(x, p["moe.latent_down.weight"].T)
    w_in, w_out = p["moe.experts_in"], p["moe.experts_out"]

    @jax.checkpoint
    def add_expert(r, e):               # a dense mask: every row, weight 0
        weight = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        return r + weight[:, None] * relu2_mlp(latent, w_in[e], w_out[e],
                                               mm), None

    r, _ = jax.lax.scan(add_expert, jnp.zeros_like(latent),
                        jnp.arange(count))
    y = mm(r, p["moe.latent_up.weight"].T)
    if shared:
        y = y + relu2_mlp(x, p["moe.shared.up.weight"].T,
                          p["moe.shared.down.weight"].T, mm)
    return y, {"idx": idx, "near_tie": near_tie, "own_idx": own}


def layer(p, kind, h, cfg, mm=_mm, forced=None, margin=0.0):
    """``h`` (B, T, hidden) through one layer of ``kind`` (``mamba``,
    ``attn`` or ``moe``) whose parameters ``p`` come under their names
    inside the layer (``norm.gamma``, ``mamba.in_proj.weight`` ...); also
    what its router did (None for the other kinds)."""
    p = {name: value.astype(F32) for name, value in p.items()}
    u = rms_norm(h, p["norm.gamma"], cfg["layer_norm_epsilon"])
    if kind == "moe":
        y, did = latent_moe(p, u.reshape(-1, u.shape[-1]), cfg, mm,
                            forced=forced, margin=margin)
        return h + y.reshape(h.shape), did
    branch = mixer if kind == "mamba" else attention
    return h + jax.lax.map(lambda s: branch(p, s, cfg, mm), u), None


def mtp_input(p, hidden, next_embedded, cfg, mm=_mm):
    """``[RMSNorm(h) ; RMSNorm(E[t_{i+1}])] W_eh``; ``p`` under the names
    inside ``mtp``."""
    p = {name: value.astype(F32) for name, value in p.items()}
    eps = cfg["layer_norm_epsilon"]
    return mm(jnp.concatenate(
        [rms_norm(hidden, p["hidden_norm.gamma"], eps),
         rms_norm(next_embedded, p["embed_norm.gamma"], eps)], -1),
        p["proj.weight"].T)


def heads_loss(p, hidden, mtp_hidden, labels, cfg, mm=_mm):
    """The loss and ``(main, mtp)`` logits from the last layer's output and
    the MTP body's; ``labels`` (B, 2, T) hold ``t_{i+1}`` and ``t_{i+2}``."""
    p = {name: value.astype(F32) for name, value in p.items()}
    eps, head = cfg["layer_norm_epsilon"], p["head.weight"].T
    main = mm(rms_norm(hidden, p["norm.gamma"], eps), head)
    mtp = mm(rms_norm(mtp_hidden, p[_mtp_norm(cfg)], eps), head)
    value = (cross_entropy(main, labels[:, 0])
             + cfg["mtp_loss_weight"] * cross_entropy(mtp, labels[:, 1]))
    return value, (main, mtp)


def _mtp_norm(cfg):
    """The MTP body's own final norm follows its layers."""
    return f"mtp.block.{len(cfg['mtp_hybrid_override_pattern'])}.gamma"


def stages(cfg):
    """``[(prefix, kind)]`` of every layer in the order it runs: the trunk's,
    then the MTP body's."""
    trunk = [(f"layers.{i}", KINDS[c])
             for i, c in enumerate(cfg["hybrid_override_pattern"])]
    body = [(f"mtp.block.{i}", KINDS[c])
            for i, c in enumerate(cfg["mtp_hybrid_override_pattern"])]
    return trunk, body


def under(p, prefix):
    """The parameters below ``prefix.``, under their names inside it."""
    prefix += "."
    return {name[len(prefix):]: value for name, value in p.items()
            if name.startswith(prefix)}


def _as_f32(q):
    return {n: v.astype(F32) for n, v in q.items()}


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, margin, probe):
    """The jitted pieces ``loss_and_grads`` is made of, one a kind of layer
    and a direction whatever the layer, compiled once a configuration: each
    takes ``low`` (a traced flag: round every matmul operand to ``probe``)
    first, and a routed layer the program's own choice of experts (or None)
    as an argument."""
    cfg, probe = json.loads(cfg_json), jnp.dtype(probe)
    mm = lambda low: functools.partial(_mm, low=low, probe=probe)

    def run(kind):
        return lambda low, q, h, chosen: layer(q, kind, h, cfg, mm(low),
                                               chosen, margin)

    def pull(kind):
        return lambda low, q, fixed, h, chosen, felt: jax.vjp(
            lambda q, h: run(kind)(low, {**q, **fixed}, h, chosen)[0],
            _as_f32(q), h)[1](felt)

    def top(low, q, h, m, labels):
        value, back, logits = jax.vjp(
            lambda q, h, m: heads_loss(q, h, m, labels, cfg, mm(low)),
            _as_f32(q), h, m, has_aux=True)
        return (value, logits) + back(jnp.ones((), F32))

    def embed_grad(shape, tokens, felt, felt_next):
        return jnp.zeros(shape, F32).at[tokens[:, :-1]].add(felt) \
            .at[tokens[:, 1:]].add(felt_next)

    return {
        "forward": {k: jax.jit(run(k)) for k in KINDS.values()},
        "backward": {k: jax.jit(pull(k)) for k in KINDS.values()},
        "embed": jax.jit(lambda w, t: w.astype(F32)[t]),
        "mtp_input": jax.jit(lambda low, q, h, e: mtp_input(
            q, h, e, cfg, mm(low))),
        "mtp_input_back": jax.jit(lambda low, q, h, e, g: jax.vjp(
            lambda q, h, e: mtp_input(q, h, e, cfg, mm(low)),
            _as_f32(q), h, e)[1](g)),
        "top": jax.jit(top),
        "embed_grad": jax.jit(embed_grad, static_argnums=0),
    }


def loss_and_grads(p, tokens, labels, cfg, forced=None, margin=0.0,
                   round_to=None, fold=None):
    """``((loss, (main, mtp, routing)), gradients)`` with a float32 gradient
    for every parameter but the routers' bias and counters, which have none,
    computed a layer at a time (module docstring).  ``forced`` maps a routed
    layer's name (``layers.1.moe``) to the ``(B·T, k)`` experts to take where
    the scores nearly tie (:func:`joyai_llm_flash_ref.route`); ``routing``
    says by the same names what each router did.  ``round_to`` rounds every
    matmul operand to that dtype first.  With ``fold``, each part's gradients
    (a dict by name) are handed to it as soon as they exist and are not
    kept: the dict returned is then empty."""
    grads, routing = {}, {}
    keep = fold or grads.update
    forced = forced or {}
    run = _programs(json.dumps(cfg, sort_keys=True), margin,
                    jnp.dtype(round_to or PROBE).name)
    low = jnp.bool_(round_to is not None)
    state = ("score_bias", "moe_stats")

    def forward(stage, h):
        prefix, kind = stage
        out, did = run["forward"][kind](low, under(p, prefix), h,
                                        forced.get(f"{prefix}.moe"))
        if did is not None:
            routing[f"{prefix}.moe"] = did
        return out

    def backward(stage, h, felt):
        prefix, kind = stage
        q = under(p, prefix)
        d_q, felt = run["backward"][kind](
            low, {n: v for n, v in q.items() if not n.endswith(state)},
            {n: v for n, v in q.items() if n.endswith(state)}, h,
            forced.get(f"{prefix}.moe"), felt)
        keep({f"{prefix}.{n}": g for n, g in d_q.items()})
        return felt

    with jax.default_matmul_precision("highest"):
        trunk, body = stages(cfg)
        embedded = run["embed"](p["embed.weight"], tokens)
        inputs = [embedded[:, :-1]]
        for stage in trunk:
            inputs.append(forward(stage, inputs[-1]))
        hidden = inputs.pop()
        mtp_p = {n: v for n, v in under(p, "mtp").items()
                 if not n.startswith("block.")}
        mtp_inputs = [run["mtp_input"](low, mtp_p, hidden, embedded[:, 1:])]
        for stage in body:
            mtp_inputs.append(forward(stage, mtp_inputs[-1]))
        loss, (main, mtp), d_top, felt, felt_mtp = run["top"](
            low, {n: p[n] for n in ("norm.gamma", "head.weight",
                                    _mtp_norm(cfg))},
            hidden, mtp_inputs.pop(), labels)
        keep(d_top)
        del d_top
        for stage in reversed(body):
            felt_mtp = backward(stage, mtp_inputs.pop(), felt_mtp)
        d_mtp, d_hidden, d_next = run["mtp_input_back"](
            low, mtp_p, hidden, embedded[:, 1:], felt_mtp)
        keep({f"mtp.{n}": g for n, g in d_mtp.items()})
        felt = felt + d_hidden
        del d_mtp, d_hidden, hidden
        for stage in reversed(trunk):
            felt = backward(stage, inputs.pop(), felt)
        keep({"embed.weight": run["embed_grad"](
            p["embed.weight"].shape, tokens, felt, d_next)})
    return (loss, (main, mtp, routing)), grads
