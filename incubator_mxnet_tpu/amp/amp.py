"""AMP entry points (reference contrib/amp/amp.py:47-389).

Two conversion mechanisms, both driven by the op lists in ``lists.py``:

* **Eager / Gluon path** — ``convert_block`` casts parameters and
  attaches a ``CastPolicy`` to the block; every op executed under the
  block's forward (eager, hybridized, or via ``Block.functional``) has
  its floating inputs cast per-op inside ``ops.registry.invoke``.  This
  is the analog of the reference's ``convert_hybrid_block``
  (contrib/amp/amp.py:550) where the casts live in the converted graph.
* **Symbolic path** — ``convert_symbol`` rewrites the Symbol DAG,
  inserting explicit ``amp_cast``/``amp_multicast`` nodes
  (reference amp.py:389 convert_symbol → C++ ReducePrecision pass,
  src/nnvm/low_precision_pass.cc).  ``convert_model`` additionally casts
  the parameter dict.
"""
from __future__ import annotations

import contextlib
import threading

import jax.numpy as jnp

from .. import trace
from ..base import dtype_from_any
from .loss_scaler import LossScaler
from . import lists

_state = {"initialized": False, "dtype": None, "scaler": None}

_tls = threading.local()


def init(target_dtype="bfloat16"):
    """Enable mixed precision (reference amp.py:47 init).

    bfloat16 (TPU native): params stay fp32-master-on-demand, compute in
    bf16 via block casting; no loss scaling needed.  float16: enables the
    dynamic LossScaler.
    """
    _state["initialized"] = True
    _state["dtype"] = dtype_from_any(target_dtype)
    if target_dtype in ("float16", "fp16"):
        _state["scaler"] = LossScaler()
    return _state


def init_trainer(trainer):
    """Attach the loss scaler to a Trainer (reference amp.py init_trainer)."""
    trainer._amp_loss_scaler = _state.get("scaler")
    return trainer


# ---------------------------------------------------------------------------
# CastPolicy: list-driven per-op input casting on the eager invoke path
# ---------------------------------------------------------------------------

class CastPolicy:
    """Per-op dtype decisions compiled from the amp lists.

    ``cast_args(op_name, arrays)`` returns the arrays with floating
    inputs cast per the op's class: lp16 ops to the low-precision target,
    fp32 ops to float32, widest-type ops to the widest floating dtype
    among the inputs.  Non-floating arrays (int labels, bool masks) pass
    through untouched, as do ops in no list.
    """

    def __init__(self, target_dtype="bfloat16", target_dtype_ops=None,
                 fp32_ops=None, widest_dtype_ops=None, excluded_ops=None):
        self.target_dtype = dtype_from_any(target_dtype)
        lp16, fp32, widest = lists.get_lists(target_dtype)
        self.lp16 = set(lp16 if target_dtype_ops is None else target_dtype_ops)
        self.fp32 = set(fp32 if fp32_ops is None else fp32_ops)
        self.widest = set(widest if widest_dtype_ops is None
                          else widest_dtype_ops)
        self.excluded = set(excluded_ops or ())
        overlap = self.lp16 & self.fp32
        if overlap:
            raise ValueError(
                f"ops cannot be in both the target-dtype and fp32 lists: "
                f"{sorted(overlap)}")

    def op_class(self, op_name):
        if op_name in self.excluded:
            return None
        if op_name in self.lp16:
            return "lp16"
        if op_name in self.fp32:
            return "fp32"
        if op_name in self.widest:
            return "widest"
        return None

    def cast_args(self, op_name, arrays):
        cls = self.op_class(op_name)
        if cls is None:
            return arrays

        def is_float(a):
            return hasattr(a, "dtype") and jnp.issubdtype(a.dtype,
                                                          jnp.floating)

        if cls == "lp16":
            tgt = self.target_dtype
            return [a.astype(tgt) if is_float(a) and a.dtype != tgt else a
                    for a in arrays]
        if cls == "fp32":
            return [a.astype(jnp.float32)
                    if is_float(a) and a.dtype != jnp.float32 else a
                    for a in arrays]
        floats = [a.dtype for a in arrays if is_float(a)]
        if not floats:
            return arrays
        widest = max(floats, key=lambda d: jnp.finfo(d).bits)
        return [a.astype(widest) if is_float(a) and a.dtype != widest else a
                for a in arrays]


def current_policy():
    return getattr(_tls, "policy", None)


@contextlib.contextmanager
def policy_scope(policy):
    prev = getattr(_tls, "policy", None)
    _tls.policy = policy
    try:
        yield policy
    finally:
        _tls.policy = prev


# ---------------------------------------------------------------------------
# Block conversion (eager path)
# ---------------------------------------------------------------------------

_KEEP_FP32_SUFFIXES = ("gamma", "beta", "running_mean", "running_var",
                       "moving_mean", "moving_var",
                       # a router's selection bias and counters (aux state)
                       "score_bias", "moe_stats",
                       # a state-space mixer's decay, step bias and skip
                       "a_log", "dt_bias", "d_skip")


def convert_block(block, target_dtype="bfloat16", target_dtype_ops=None,
                  fp32_ops=None, widest_dtype_ops=None, excluded_ops=None):
    """Convert a Block to mixed precision (reference convert_hybrid_block).

    Casts the block's parameters to ``target_dtype`` (norm-layer
    scale/offset, moving statistics, a router's aux state and a state-space
    mixer's decay, step bias and skip stay fp32) and attaches a
    ``CastPolicy`` built from the amp lists — honored per-op on every
    forward through the block, so ``fp32_ops=['softmax']`` really does
    run softmax in fp32 on bf16 activations.
    """
    policy = CastPolicy(target_dtype, target_dtype_ops=target_dtype_ops,
                        fp32_ops=fp32_ops, widest_dtype_ops=widest_dtype_ops,
                        excluded_ops=excluded_ops)
    with trace.process_span("amp.convert_block",
                            dtype=str(target_dtype)) as sp:
        cast = [p for name, p in block.collect_params().items()
                if not name.endswith(_KEEP_FP32_SUFFIXES)]
        for p in cast:
            p.cast(target_dtype)
        sp.set(leaves=len(cast), bytes=sum(
            p._nbytes() for p in cast if p._shape_complete()))
    block._amp_policy = policy
    return block


@contextlib.contextmanager
def scale_loss(loss, trainer):
    """``with amp.scale_loss(loss, trainer) as scaled: scaled.backward()``."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None:
        yield loss
        return
    scaled = loss * scaler.loss_scale
    trainer._scale = 1.0 / scaler.loss_scale
    yield scaled
    overflow = scaler.has_overflow(trainer._params)
    scaler.update_scale(overflow)
    trainer._amp_skip_update = overflow


def unscale(trainer):
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is not None:
        trainer._scale = 1.0


# ---------------------------------------------------------------------------
# Symbol conversion (graph rewrite, reference amp.py:389 convert_symbol)
# ---------------------------------------------------------------------------

def convert_symbol(sym, target_dtype="bfloat16", target_dtype_ops=None,
                   fp32_ops=None, widest_dtype_ops=None, excluded_sym_names=None,
                   data_names=None):
    """Rewrite a Symbol graph with explicit amp_cast/amp_multicast nodes.

    Every op in the target-dtype list gets its floating inputs wrapped in
    ``amp_cast(dtype=target)``; fp32-list ops get ``amp_cast(float32)``;
    widest-list ops with mixed-precision inputs get one ``amp_multicast``
    over all inputs.  Ops named in ``excluded_sym_names`` are left alone.
    Returns a new Symbol; the input symbol is not mutated.
    """
    from ..symbol import Symbol, _SymNode

    policy = CastPolicy(target_dtype, target_dtype_ops=target_dtype_ops,
                        fp32_ops=fp32_ops, widest_dtype_ops=widest_dtype_ops)
    excluded = set(excluded_sym_names or ())
    tgt_name = jnp.dtype(policy.target_dtype).name

    old2new: dict[int, _SymNode] = {}
    cast_cache: dict[tuple, _SymNode] = {}

    def cast_edge(entry, dtype_name):
        """Wrap an input edge in an amp_cast node.

        Aux-state variables (BatchNorm moving stats) are never cast: the
        reference's ReducePrecision pass leaves aux inputs alone, and the
        executor identifies aux updates by matching direct variable
        inputs.  Casts dedup per (producer edge, dtype) so a tensor
        feeding N listed ops is cast once, with a unique node name.
        """
        if entry.op_name is None and entry.attrs.get("__aux__"):
            return entry
        key = (entry.key, entry.output_index, dtype_name)
        cast = cast_cache.get(key)
        if cast is None:
            cast = _SymNode("amp_cast",
                            f"{entry.name}_amp_cast_{dtype_name}"
                            + (f"_{entry.output_index}"
                               if entry.output_index else ""),
                            [entry], {"dtype": dtype_name})
            cast_cache[key] = cast
        return cast

    order = sym._topo_order()
    for node in order:
        if node.op_name is None:
            old2new[node.key] = _SymNode(None, node.name, [], {},
                                         attrs=dict(node.attrs))
            continue
        new_inputs = [old2new[i.key].clone_for_output(i.output_index)
                      for i in node.inputs]
        cls = None if node.name in excluded else policy.op_class(node.op_name)
        if cls == "lp16":
            new_inputs = [cast_edge(e, tgt_name) for e in new_inputs]
        elif cls == "fp32":
            new_inputs = [cast_edge(e, "float32") for e in new_inputs]
        elif cls == "widest" and len(new_inputs) > 1:
            multi = _SymNode("amp_multicast", f"{node.name}_amp_multicast",
                             new_inputs, {"num_outputs": len(new_inputs)},
                             num_outputs=len(new_inputs))
            new_inputs = [multi.clone_for_output(i)
                          for i in range(len(new_inputs))]
        old2new[node.key] = _SymNode(node.op_name, node.name, new_inputs,
                                     dict(node.kwargs),
                                     attrs=dict(node.attrs),
                                     num_outputs=node.num_outputs)

    heads = [old2new[n.key].clone_for_output(n.output_index)
             for n in sym._head_entries()]
    return Symbol(heads)


def convert_model(sym, arg_params, aux_params, target_dtype="bfloat16",
                  target_dtype_ops=None, fp32_ops=None, widest_dtype_ops=None,
                  excluded_sym_names=None, cast_optional_params=False):
    """convert_symbol + cast the parameter dicts (reference amp.py:477).

    Parameters feeding only lp16 ops may be stored in the low-precision
    dtype when ``cast_optional_params`` (saves checkpoint bytes); by
    default params stay fp32 and the graph's amp_cast nodes downcast at
    runtime, matching the reference default.
    """
    new_sym = convert_symbol(sym, target_dtype, target_dtype_ops, fp32_ops,
                             widest_dtype_ops, excluded_sym_names)
    tgt = dtype_from_any(target_dtype)
    arg_params = dict(arg_params)
    aux_params = dict(aux_params)
    if cast_optional_params:
        policy = CastPolicy(target_dtype, target_dtype_ops=target_dtype_ops,
                            fp32_ops=fp32_ops,
                            widest_dtype_ops=widest_dtype_ops)
        # a param may be cast when every consumer is an lp16-class op
        # that is not excluded by name (an excluded op stays fp32, so its
        # params must too)
        excluded = set(excluded_sym_names or ())
        ok: dict[str, bool] = {}
        for node in sym._topo_order():
            if node.op_name is None:
                continue
            is_lp16 = (node.name not in excluded
                       and policy.op_class(node.op_name) == "lp16")
            for i in node.inputs:
                if i.op_name is None:
                    ok[i.name] = ok.get(i.name, True) and is_lp16
        for name, val in list(arg_params.items()):
            if ok.get(name, False):
                arg_params[name] = val.astype(tgt)
    return new_sym, arg_params, aux_params
