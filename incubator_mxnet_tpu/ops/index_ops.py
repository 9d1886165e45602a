"""Indexing / gather / scatter ops (reference src/operator/tensor/indexing_op*)."""
import jax
import jax.numpy as jnp
from jax import lax

from .. import profiler as _profiler
from ..locks import named_lock
from .nn_ops import _zero_cotangent
from .registry import register


@register("take", num_inputs=2)
def take(x, indices, axis=0, mode="clip"):
    return jnp.take(x, indices.astype(jnp.int32), axis=axis, mode=mode)


# ---------------------------------------------------------------------------
# Embedding: a gather; its gradient by the table is routed by shape
# ---------------------------------------------------------------------------
#
# The constants below are the two sweeps of the bare gradient (`jnp.take`
# forward + `vjp`, device time of the program) on one TPU v5e, 65 signatures
# (PERF.md section 6, PR 35).  XLA's TPU compiler emits one of three scatters
# for the transpose of the gather, and which one is a rule in elements that
# the described compiles show (tests/test_tpu_compile.py):
#
# * at most one id for eight table rows, or rows of more than 7,808 elements:
#   updates in place into the zero-filled table.  Linear to 3 % over 14
#   points: the table filled at 680 GB/s, the updates moved at 22.5 GB/s,
#   0.0575 us an id.
# * otherwise the ids are sorted and the table is passed over once; rows of
#   more than 4,224 elements are gathered into the sorted order by a fusion
#   of their own first.  34.7 ms a 10^9 updated elements, and a pass whose
#   cost an element depends on the row's width in 128-lane tiles as on
#   nothing else (+-10 % over 49 points, float32 as bfloat16): 8.9 ms a
#   10^9 table elements at 768, 335 at 5,120 -- 1.72 us a table row,
#   however few ids there are.  No formula was found for it, so the
#   measured widths are a table and an unmeasured width gets the lowest
#   entry, which keeps the scatter.
#
# The matmul form ran at 157-194 TFLOP/s in bfloat16 (median 181.5 of 62
# points) and at 60.2-64.4 in float32 at `HIGHEST`.  The package keeps no copy
# of the chip's peak and needs none: two measured rates are compared.
_MATMUL_FLOPS_PER_S = {"bfloat16": 1.8e14, "float32": 6.0e13}
_IN_PLACE_MS = (1.47e-9, 44.4e-9, 5.75e-5)  # a table byte, an updated one, an id
_SORTED_MS_PER_G_UPDATES = 34.7
_SORTED_MS_PER_G_TABLE = {          # row width in elements -> ms
    768: 8.9, 1024: 10.2, 1280: 13.1, 1536: 20.9, 1792: 49.8, 2048: 21.0,
    2304: 38.3, 2560: 147.3, 2816: 16.2, 3072: 20.9, 3328: 30.4, 3584: 51.5,
    3840: 138.1, 4096: 9.1, 4608: 74.3, 5120: 335.0, 6144: 36.9, 7168: 98.0}


def embedding_grad_route(ids, table_rows, width, dtype):
    """How the gradient of ``Embedding`` by its ``[table_rows, width]``
    table is computed for ``ids`` looked-up rows: the counter's entry for
    that signature, ``route`` first.  A comparison of two estimated times on
    one TPU v5e -- the matmul ``one_hot(ids)^T . g`` at its measured rate
    against a model of whichever scatter XLA compiles for these shapes
    (the comment above) -- and of nothing else: no flag, no model's name.
    Off the TPU the route is the scatter whatever the estimates say: the
    CPU's scatter has no such cliff and its matmul no such rate."""
    dtype = jnp.dtype(dtype)
    flops = 2 * ids * table_rows * width
    rate = _MATMUL_FLOPS_PER_S.get(dtype.name)
    est_matmul = 1e3 * flops / rate if rate else None   # never measured
    if 8 * ids <= table_rows or width > 7808:
        form = "in_place"
        a_table_byte, an_updated_byte, an_id = _IN_PLACE_MS
        est_scatter = (a_table_byte * table_rows * width * dtype.itemsize
                       + an_updated_byte * ids * width * dtype.itemsize
                       + an_id * ids)
    else:
        form = "sorted_gathered" if width > 4224 else "sorted"
        a_pass = _SORTED_MS_PER_G_TABLE.get(
            width, min(_SORTED_MS_PER_G_TABLE.values()))
        est_scatter = (_SORTED_MS_PER_G_UPDATES * ids * width
                       + a_pass * table_rows * width) / 1e9
    matmul = (jax.default_backend() == "tpu" and est_matmul is not None
              and est_matmul < est_scatter)
    return {"route": "matmul" if matmul else "scatter", "ids": ids,
            "table_rows": table_rows, "width": width,
            "matmul_flops": flops,
            "est_matmul_ms": est_matmul and round(est_matmul, 3),
            "est_scatter_ms": round(est_scatter, 3), "scatter_form": form}


_grads = {}
_grads_lock = named_lock("ops.embedding_grads")


def embedding_grads(reset=False):
    """``{signature: entry}`` of every :func:`embedding` call traced so far
    (``"4096 -> 32640x5120 bfloat16"``: ids, table, dtype), each
    :func:`embedding_grad_route`'s answer: the ``route`` its gradient takes
    and the two estimates that chose it.  Counts signatures, not calls.
    The ``embedding_grads`` provider of ``profiler.dumps()``."""
    with _grads_lock:
        out = {sig: dict(entry) for sig, entry in sorted(_grads.items())}
        if reset:
            _grads.clear()
    return out


_profiler.register_stats_provider("embedding_grads", embedding_grads)


def _take_rows(weight, ids):
    return jnp.take(weight, ids.astype(jnp.int32), axis=0, mode="clip")


@jax.custom_vjp
def _take_rows_matmul_grad(weight, ids):
    return _take_rows(weight, ids)


def _take_rows_fwd(weight, ids):
    # the table rides along for its shape and dtype: nothing reads it
    return _take_rows(weight, ids), (weight, ids)


def _take_rows_bwd(res, g):
    """``one_hot(ids)^T . g``, the ids clipped as the forward clips them.
    The compare stays the producer of the convolution's operand, which XLA
    fuses into it: no ``[ids, rows]`` array is written.  Duplicates of an id
    are summed in float32 and rounded once (the scatter adds in the table's
    dtype, one rounding a duplicate)."""
    weight, ids = res
    rows, width = weight.shape
    flat = jnp.clip(ids.astype(jnp.int32), 0, rows - 1).reshape(-1)
    hot = flat[:, None] == jnp.arange(rows, dtype=jnp.int32)
    grad = lax.dot_general(
        hot.astype(g.dtype), g.reshape(-1, width), (((0,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST if g.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)
    return grad.astype(weight.dtype), _zero_cotangent(ids)


_take_rows_matmul_grad.defvjp(_take_rows_fwd, _take_rows_bwd)


@register("Embedding", num_inputs=2, aliases=("embedding",))
def embedding(data, weight, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False):
    """Embedding lookup (reference src/operator/tensor/indexing_op.h Embedding).

    On TPU this is a gather from an HBM-resident table (ids clipped into
    it), a dynamic-gather where the reference had its own kernels.  The
    gradient by the table, the reference's AddTakeGrad, is routed by the
    shapes the op is traced with (:func:`embedding_grad_route`): where
    XLA's scatter-add is the cheaper it is the plain transpose of the
    gather, and this op is bare ``jnp.take``; where the scatter falls off
    its fast path (Falcon-H1's 4,096 ids into ``[32640, 5120]``: 57 ms) it
    is one matmul with the one-hot of the ids, accumulated in float32 (7.4
    ms).  Off the TPU always the former.  ``embedding_grads`` records the
    choice a signature."""
    if weight.ndim != 2:            # not a table: the gather alone
        return _take_rows(weight, data)
    plan = embedding_grad_route(data.size, *weight.shape, weight.dtype)
    with _grads_lock:
        _grads[f"{data.size} -> {weight.shape[0]}x{weight.shape[1]} "
               f"{weight.dtype}"] = plan
    if plan["route"] == "matmul":
        return _take_rows_matmul_grad(weight, data)
    return _take_rows(weight, data)


@register("one_hot", num_inputs=1, differentiable=False)
def one_hot(indices, depth=1, on_value=1.0, off_value=0.0, dtype="float32"):
    from ..base import dtype_from_any
    dt = dtype_from_any(dtype)
    eye = jnp.equal(
        indices.astype(jnp.int32)[..., None],
        jnp.arange(depth, dtype=jnp.int32))
    return jnp.where(eye, jnp.asarray(on_value, dt), jnp.asarray(off_value, dt))


@register("gather_nd", num_inputs=2)
def gather_nd(data, indices):
    """Reference semantics: indices[0..M-1] index the first M dims of data."""
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return data[idx]


@register("scatter_nd", num_inputs=2)
def scatter_nd(data, indices, shape=None):
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return jnp.zeros(shape, data.dtype).at[idx].set(data)


@register("index_add_nd", num_inputs=3,
          aliases=("index_add", "_npx_index_add"))
def index_add_nd(base, indices, updates):
    """Coordinate-row scatter-add (reference _npx_index_add,
    src/operator/contrib/index_add.cc): indices is (K, N) — K leading
    coordinates for N update sites."""
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return base.at[idx].add(updates)


@register("index_update_nd", num_inputs=3,
          aliases=("index_update", "_npx_index_update", "_scatter_set_nd"))
def index_update_nd(base, indices, updates):
    """Coordinate-row scatter-assign (reference _npx_index_update)."""
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return base.at[idx].set(updates)


@register("pick", num_inputs=2)
def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    idx = jnp.expand_dims(index.astype(jnp.int32), axis)
    out = jnp.take_along_axis(data, idx, axis=axis, mode=mode)
    if not keepdims:
        out = jnp.squeeze(out, axis)
    return out


@register("take_along_axis", num_inputs=2)
def take_along_axis(data, indices, axis=0):
    return jnp.take_along_axis(data, indices.astype(jnp.int32), axis=axis)


@register("where_index", num_inputs=1, differentiable=False)
def where_index(cond, size=None, fill_value=-1):
    """Static-shape nonzero: returns `size` indices padded with fill_value.

    TPU-first replacement for dynamic-shape np.where(cond): the output
    length must be static under XLA, so callers pass an upper bound.
    """
    flat = cond.reshape(-1).astype(bool)
    n = flat.shape[0] if size is None else size
    idx = jnp.nonzero(flat, size=n, fill_value=fill_value)[0]
    return idx.astype(jnp.int32)


@register("masked_fill", num_inputs=2)
def masked_fill(data, mask, value=0.0):
    return jnp.where(mask.astype(bool), jnp.asarray(value, data.dtype), data)


@register("index_array", num_inputs=1, differentiable=False)
def index_array(x, axes=None):
    shape = x.shape
    axes = axes or tuple(range(len(shape)))
    grids = jnp.meshgrid(*[jnp.arange(shape[a]) for a in axes], indexing="ij")
    return jnp.stack(grids, axis=-1).astype(jnp.int64 if False else jnp.int32)


@register("batch_take", num_inputs=2, differentiable=False)
def batch_take(a, indices):
    """Row-wise pick: out[i] = a[i, indices[i]] (reference
    indexing_op.cc batch_take; flattens leading dims like the
    reference)."""
    a2 = a.reshape(-1, a.shape[-1])
    idx = indices.reshape(-1).astype(jnp.int32)
    idx = jnp.clip(idx, 0, a2.shape[1] - 1)
    return jnp.take_along_axis(a2, idx[:, None], axis=1)[:, 0] \
        .reshape(indices.shape)


@register("argmax_channel", num_inputs=1, differentiable=False)
def argmax_channel(x):
    """argmax over axis 1 returned as float (reference
    broadcast_reduce_op_index.cc argmax_channel)."""
    return jnp.argmax(x, axis=1).astype(jnp.float32)


@register("ravel_multi_index", num_inputs=1, differentiable=False,
          aliases=("_ravel_multi_index",))
def ravel_multi_index(data, shape=None):
    """(ndim, n) coordinates -> flat indices (reference ravel.cc)."""
    coords = tuple(data[i].astype(jnp.int32)
                   for i in range(data.shape[0]))
    strides = []
    acc = 1
    for d in reversed(shape):
        strides.append(acc)
        acc *= d
    strides = list(reversed(strides))
    out = sum(c * s for c, s in zip(coords, strides))
    return out.astype(jnp.float32) if data.dtype == jnp.float32 else out


@register("unravel_index", num_inputs=1, differentiable=False,
          aliases=("_unravel_index",))
def unravel_index(data, shape=None):
    """flat indices -> (ndim, n) coordinates (reference ravel.cc)."""
    idx = data.astype(jnp.int32)
    coords = []
    for d in reversed(shape):
        coords.append(idx % d)
        idx = idx // d
    out = jnp.stack(list(reversed(coords)))
    return out.astype(jnp.float32) if data.dtype == jnp.float32 else out
