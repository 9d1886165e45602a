"""Flagship TPU-native transformer LM with 5-axis parallelism.

Pure-functional JAX model (params pytree + apply fn) designed mesh-first:

* dp — batch sharding; gradient psum fused into backward by GSPMD
* tp — attention heads + FFN hidden column/row parallel (Megatron split:
  qkv col-parallel, out-proj row-parallel → one psum per block)
* sp — sequence sharding with ring attention (collective-permute KV
  rotation, parallel/ring_attention.py) or GSPMD-gathered attention
* pp — layer-stack axis sharded over 'pp' (stage placement); an explicit
  microbatch ppermute pipeline lives in parallel/pipeline.py
* ep — optional MoE FFN with experts over 'ep' (parallel/moe.py)

No reference equivalent (SURVEY.md §2.3: TP/PP/SP/EP absent in MXNet 1.x)
— this is the "beyond reference" capability layer the TPU build requires.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.nn_ops import dot_product_attention
from ..ops.pallas_kernels import gspmd_trace
from ..parallel.ring_attention import ring_attention
from ..parallel.moe import init_moe_params, moe_forward

__all__ = ["TransformerConfig", "TransformerLM"]

_attend = functools.partial(dot_product_attention.fn, causal=True)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_len: int = 2048
    dtype: str = "bfloat16"
    use_moe: bool = False
    n_experts: int = 8
    attention: str = "gspmd"  # under a mesh: 'gspmd' | 'ring' | 'flash' (shard_map)

    @property
    def head_dim(self):
        return self.d_model // self.n_heads


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


class TransformerLM:
    """init/apply container (functional; no gluon dependency on purpose —
    this model feeds pjit/shard_map directly)."""

    def __init__(self, config: TransformerConfig):
        self.cfg = config

    # -- parameters -------------------------------------------------------
    def init(self, key):
        cfg = self.cfg
        dt = _dtype(cfg)
        keys = jax.random.split(key, 6)
        D, H, F, L = cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.n_layers
        s = lambda k, shape, scale: (jax.random.normal(k, shape, jnp.float32)
                                     * scale).astype(dt)
        params = {
            "embed": s(keys[0], (cfg.vocab_size, D), 0.02),
            "pos_embed": s(keys[1], (cfg.max_len, D), 0.02),
            "layers": {
                "wqkv": s(keys[2], (L, D, 3 * D), D ** -0.5),
                "wo": s(keys[3], (L, D, D), D ** -0.5),
                "ln1": jnp.ones((L, D), dt),
                "ln2": jnp.ones((L, D), dt),
                "w1": s(keys[4], (L, D, F), D ** -0.5),
                "w2": s(keys[5], (L, F, D), F ** -0.5),
            },
            "ln_f": jnp.ones((D,), dt),
        }
        if cfg.use_moe:
            params["moe"] = init_moe_params(
                jax.random.fold_in(key, 99), D, F, cfg.n_experts, dt)
        return params

    def partition_rules(self):
        """path-substring → PartitionSpec (consumed by shard_params)."""
        return [
            ("embed", P(None, "tp")),
            ("pos_embed", P(None, None)),
            ("wqkv", P("pp", None, "tp")),
            ("wo", P("pp", "tp", None)),
            ("ln1", P("pp", None)),
            ("ln2", P("pp", None)),
            ("w1", P("pp", None, "tp")),
            ("w2", P("pp", "tp", None)),
            ("ln_f", P(None)),
            ("moe/gate", P(None, None)),
            ("moe/w_in", P("ep", None, None)),
            ("moe/w_out", P("ep", None, None)),
        ]

    def spec_for(self, path):
        for frag, spec in self.partition_rules():
            if frag in path.replace("'", "").replace("][", "/"):
                return spec
        return P()

    def shard_params(self, params, mesh: Mesh):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = []
        for path, leaf in flat:
            spec = self.spec_for(jax.tree_util.keystr(path))
            out.append(jax.device_put(leaf, NamedSharding(mesh, spec)))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- forward ----------------------------------------------------------
    def _rmsnorm(self, x, g):
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                      keepdims=True)
        return (x.astype(jnp.float32) * lax.rsqrt(ms + 1e-6)).astype(x.dtype) * g

    def _attention(self, q, k, v, mesh):
        """Causal attention through the one entry point,
        ``dot_product_attention`` (its ``dispatch`` picks the blockwise
        kernel or the XLA composition); ``cfg.attention`` only says how
        it meets a mesh."""
        cfg = self.cfg
        if mesh is None:
            return _attend(q, k, v)
        if cfg.attention == "ring" or (
                cfg.attention == "flash" and mesh.shape.get("sp", 1) > 1):
            # the kernel is per-(b,h); sequence sharding needs the ring
            # schedule instead of an all-gather of K/V
            return ring_attention(q, k, v, mesh, axis_name="sp", causal=True)
        if cfg.attention == "flash":
            # keep batch/head shards local: inside shard_map a Mosaic
            # kernel is legal and GSPMD doesn't all-gather q/k/v
            spec = P("dp", "tp", None, None)
            return jax.shard_map(_attend, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec)(q, k, v)
        with gspmd_trace():     # GSPMD partitions the composition
            return _attend(q, k, v)

    def _layer(self, lp, x, mesh):
        cfg = self.cfg
        B, T, D = x.shape
        H, dh = cfg.n_heads, cfg.head_dim
        h = self._rmsnorm(x, lp["ln1"])
        qkv = jnp.einsum("btd,de->bte", h, lp["wqkv"])
        if mesh is not None:
            qkv = lax.with_sharding_constraint(
                qkv, NamedSharding(mesh, P("dp", "sp", "tp")))
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, T, H, dh).transpose(0, 2, 1, 3)

        att = self._attention(heads(q), heads(k), heads(v), mesh)
        att = att.transpose(0, 2, 1, 3).reshape(B, T, D)
        x = x + jnp.einsum("btd,de->bte", att, lp["wo"])
        h = self._rmsnorm(x, lp["ln2"])
        ff = jax.nn.gelu(jnp.einsum("btd,df->btf", h, lp["w1"]))
        if mesh is not None:
            ff = lax.with_sharding_constraint(
                ff, NamedSharding(mesh, P("dp", "sp", "tp")))
        x = x + jnp.einsum("btf,fd->btd", ff, lp["w2"])
        return x

    def apply(self, params, tokens, mesh: Mesh | None = None):
        """tokens (B, T) int32 → logits (B, T, V)."""
        cfg = self.cfg
        B, T = tokens.shape
        x = params["embed"][tokens] + params["pos_embed"][:T][None]
        if mesh is not None:
            x = lax.with_sharding_constraint(
                x, NamedSharding(mesh, P("dp", "sp", None)))

        L = cfg.n_layers

        def body(x, lp):
            return self._layer(lp, x, mesh), None

        # lax.scan over the layer stack; the leading (L) axis of every
        # layer param is sharded over 'pp' (stage placement)
        x, _ = lax.scan(lambda carry, lp: (self._layer(lp, carry, mesh), None),
                        x, params["layers"])
        return self._head(params, x, mesh)

    def _head(self, params, x, mesh):
        cfg = self.cfg
        if cfg.use_moe:
            moe_out, aux = moe_forward(params["moe"], x)
            x = x + moe_out
        x = self._rmsnorm(x, params["ln_f"])
        logits = jnp.einsum("btd,vd->btv", x, params["embed"])
        if mesh is not None:
            logits = lax.with_sharding_constraint(
                logits, NamedSharding(mesh, P("dp", "sp", None)))
        return logits

    # -- pipelined forward (real pp schedule) -----------------------------
    def apply_pipelined(self, params, tokens, mesh: Mesh, n_micro: int):
        """tokens (B, T) → logits, via a microbatched circular pipeline.

        The GSPMD collective-permute pipelining pattern (GSPMD paper §3.4;
        scaling-book pipelining chapter): the layer stack is reshaped to
        (npp, L/npp, ...) with the stage axis sharded over 'pp'; a
        per-stage activation buffer advances one stage per step via
        ``jnp.roll`` on the stage-sharded axis, which XLA lowers to a
        collective-permute over the pp ring.  All stages compute every
        step (vmapped over the stage axis → SPMD over 'pp'); bubble-step
        garbage is never collected.  Because the schedule is plain
        scan+roll, ``jax.grad`` differentiates it into the reverse
        pipeline automatically — backward microbatches flow last→first
        stage with the transposed permute.  Replaces the reference's
        coarse group2ctx placement (graph_executor.cc:2048) with an
        actual overlap schedule.
        """
        cfg = self.cfg
        npp = mesh.shape["pp"]
        B, T = tokens.shape
        if B % n_micro:
            raise ValueError(
                f"n_micro ({n_micro}) must divide the batch size ({B})")
        L = cfg.n_layers
        if L % npp:
            raise ValueError(
                f"pp degree ({npp}) must divide n_layers ({L})")
        mb = B // n_micro

        x = params["embed"][tokens] + params["pos_embed"][:T][None]
        micro = x.reshape(n_micro, mb, T, cfg.d_model)
        micro = lax.with_sharding_constraint(
            micro, NamedSharding(mesh, P(None, "dp", "sp", None)))

        # (L, ...) → (npp, L/npp, ...), stage axis sharded over pp
        layers = jax.tree_util.tree_map(
            lambda a: lax.with_sharding_constraint(
                a.reshape(npp, L // npp, *a.shape[1:]),
                NamedSharding(mesh, P("pp", *([None] * a.ndim)))),
            params["layers"])

        def stage_apply(lp_stage, xb):
            """Run this stage's L/npp layers (no per-op sharding
            constraints here: specs can't follow the vmapped stage axis;
            GSPMD propagates tp/sp sharding from the param shardings)."""
            out, _ = lax.scan(
                lambda c, lp: (self._layer(lp, c, None), None), xb, lp_stage)
            return out

        buf = jnp.zeros((npp, mb, T, cfg.d_model), micro.dtype)
        outputs = jnp.zeros((n_micro, mb, T, cfg.d_model), micro.dtype)
        buf = lax.with_sharding_constraint(
            buf, NamedSharding(mesh, P("pp", "dp", "sp", None)))

        def step(carry, t):
            buf, outputs = carry
            inject = micro[jnp.clip(t, 0, n_micro - 1)]
            slot0 = jnp.where(t < n_micro, inject, buf[0])
            buf = lax.dynamic_update_index_in_dim(buf, slot0, 0, axis=0)
            new_buf = jax.vmap(stage_apply)(layers, buf)
            emit = t - (npp - 1)
            out_last = new_buf[npp - 1]
            outputs = jnp.where(
                (emit >= 0) & (emit < n_micro),
                lax.dynamic_update_index_in_dim(
                    outputs, out_last, jnp.clip(emit, 0, n_micro - 1), axis=0),
                outputs)
            # advance: stage i's output becomes stage i+1's input
            # (roll on the pp-sharded axis → collective-permute on ICI)
            buf = jnp.roll(new_buf, 1, axis=0)
            return (buf, outputs), None

        (buf, outputs), _ = lax.scan(step, (buf, outputs),
                                     jnp.arange(n_micro + npp - 1))
        x = outputs.reshape(B, T, cfg.d_model)
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", "sp", None)))
        return self._head(params, x, mesh)

    # -- training ---------------------------------------------------------
    def loss_fn(self, params, tokens, mesh=None, n_micro=None):
        if n_micro is not None and mesh is not None:
            logits = self.apply_pipelined(params, tokens[:, :-1], mesh,
                                          n_micro)
        else:
            logits = self.apply(params, tokens[:, :-1], mesh)
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll)

    def make_train_step(self, mesh: Mesh, lr=1e-3, n_micro=None,
                        donate=False):
        """SGD train step jitted over the mesh; GSPMD inserts the dp-psum
        for gradients and tp/sp/ep collectives for the sharded math.

        When the mesh has pp > 1, the forward (and its transposed
        backward) run the microbatched circular pipeline
        (``apply_pipelined``) instead of the scan-with-sharded-params
        stage fetch; n_micro defaults to 2*pp (bubble fraction
        (pp-1)/(2*pp+pp-1)) clamped to divide the batch at call time.

        ``donate=True`` donates the params (arg 0) so XLA writes the
        update in place — HBM for one param copy instead of two.  Only
        for callers that follow the ``params, loss = step(params,
        tokens)`` rebinding contract: ``shard_params`` may alias its
        input (``device_put`` is a no-op for already-placed arrays), so
        the pre-shard tree dies with the donated one.
        """
        pp = dict(mesh.shape).get("pp", 1)

        def step(params, tokens):
            nm = n_micro
            if pp > 1 and nm is None:
                # default 2*pp microbatches, clamped to a divisor of the
                # (statically known) batch so the pipeline always traces
                nm = min(2 * pp, tokens.shape[0])
                while tokens.shape[0] % nm:
                    nm -= 1
            loss, grads = jax.value_and_grad(
                lambda p: self.loss_fn(p, tokens, mesh,
                                       nm if pp > 1 else None))(params)
            new_params = jax.tree_util.tree_map(
                lambda p, g: (p.astype(jnp.float32)
                              - lr * g.astype(jnp.float32)).astype(p.dtype),
                params, grads)
            return new_params, loss

        token_sharding = NamedSharding(mesh, P("dp", None))
        return jax.jit(step, in_shardings=(None, token_sharding),
                       donate_argnums=(0,) if donate else ()), \
            token_sharding
