"""NDArray core tests (reference tests/python/unittest/test_ndarray.py)."""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.test_utils import assert_almost_equal


def test_creation_and_numpy_roundtrip():
    a = nd.array([[1, 2], [3, 4]])
    assert a.shape == (2, 2)
    assert a.dtype == onp.float32
    assert_almost_equal(a, onp.array([[1, 2], [3, 4]], "float32"))
    b = nd.array(onp.arange(6).reshape(2, 3), dtype="int32")
    assert b.dtype == onp.int32
    assert b.asnumpy().tolist() == [[0, 1, 2], [3, 4, 5]]


def test_creation_helpers():
    assert nd.zeros((2, 3)).asnumpy().sum() == 0
    assert nd.ones((2, 3)).asnumpy().sum() == 6
    assert nd.full((2,), 7).asnumpy().tolist() == [7, 7]
    assert nd.arange(0, 5).asnumpy().tolist() == [0, 1, 2, 3, 4]
    assert nd.eye(3).asnumpy().trace() == 3


def test_arithmetic_broadcast():
    a = nd.array([[1.0, 2.0], [3.0, 4.0]])
    b = nd.array([10.0, 20.0])
    assert_almost_equal(a + b, onp.array([[11, 22], [13, 24]], "float32"))
    assert_almost_equal(a - 1, onp.array([[0, 1], [2, 3]], "float32"))
    assert_almost_equal(2 * a, onp.array([[2, 4], [6, 8]], "float32"))
    assert_almost_equal(a / b, onp.array([[0.1, 0.1], [0.3, 0.2]], "float32"))
    assert_almost_equal(a ** 2, onp.array([[1, 4], [9, 16]], "float32"))
    assert_almost_equal(-a, -a.asnumpy())


def test_comparison_ops():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([2.0, 2.0, 2.0])
    assert (a < b).asnumpy().tolist() == [1, 0, 0]
    assert (a == b).asnumpy().tolist() == [0, 1, 0]
    assert (a >= b).asnumpy().tolist() == [0, 1, 1]


def test_inplace_ops_mutate_chunk():
    a = nd.ones((3,))
    version0 = a._chunk.var.version
    a += 2
    assert a.asnumpy().tolist() == [3, 3, 3]
    assert a._chunk.var.version > version0
    a *= 2
    assert a.asnumpy().tolist() == [6, 6, 6]


def test_slice_view_semantics():
    """Views share the chunk: writes through either side are visible
    (reference NDArray slice-view semantics, ndarray.h views)."""
    a = nd.zeros((4, 4))
    v = a[1:3]
    v[:] = 7.0
    assert a.asnumpy()[1:3].tolist() == [[7] * 4, [7] * 4]
    a[2] = 3.0
    assert v.asnumpy()[1].tolist() == [3] * 4


def test_setitem_basic_and_advanced():
    a = nd.zeros((3, 3))
    a[0, 0] = 5
    a[1] = nd.ones((3,))
    assert a.asnumpy()[0, 0] == 5
    assert a.asnumpy()[1].tolist() == [1, 1, 1]


def test_reshape_view():
    a = nd.arange(0, 6).reshape((2, 3))
    r = a.reshape((3, 2))
    assert r.shape == (3, 2)
    r2 = a.reshape((-1,))
    assert r2.shape == (6,)
    # reshape with 0 (copy dim) and -1
    b = nd.zeros((2, 3, 4))
    assert b.reshape((0, -1)).shape == (2, 12)


def test_reductions_and_methods():
    a = nd.array([[1.0, 2.0], [3.0, 4.0]])
    assert a.sum().asscalar() == 10
    assert a.mean().asscalar() == 2.5
    assert a.max(axis=0).asnumpy().tolist() == [3, 4]
    assert a.argmax(axis=1).asnumpy().tolist() == [1, 1]
    assert abs(a.norm().asscalar() - onp.sqrt(30)) < 1e-5


def test_dtype_cast_and_context():
    a = nd.ones((2, 2))
    b = a.astype("float16")
    assert b.dtype == onp.float16
    c = a.as_in_context(mx.cpu())
    assert c.ctx.device_type == "cpu"
    bf = a.astype("bfloat16")
    assert "bfloat16" in str(bf.data.dtype)


def test_save_load_roundtrip(tmp_path):
    fname = str(tmp_path / "arrays.params")
    d = {"w": nd.ones((2, 3)), "b": nd.arange(0, 4, dtype="int32")}
    nd.save(fname, d)
    loaded = nd.load(fname)
    assert set(loaded) == {"w", "b"}
    assert_almost_equal(loaded["w"], d["w"])
    assert loaded["b"].asnumpy().tolist() == [0, 1, 2, 3]
    # list form
    nd.save(fname, [nd.zeros((2,)), nd.ones((3,))])
    lst = nd.load(fname)
    assert isinstance(lst, list) and len(lst) == 2


def test_concat_split_stack():
    a, b = nd.ones((2, 3)), nd.zeros((2, 3))
    c = nd.concat(a, b, dim=0)
    assert c.shape == (4, 3)
    s = nd.stack(a, b, axis=0)
    assert s.shape == (2, 2, 3)
    parts = nd.split(c, num_outputs=2, axis=0)
    assert parts[0].shape == (2, 3)


def test_wait_to_read_and_waitall():
    a = nd.ones((4,)) * 3
    a.wait_to_read()
    nd.waitall()
    assert a.asnumpy().tolist() == [3, 3, 3, 3]


def test_scalar_conversions():
    a = nd.array([3.5])
    assert float(a) == 3.5
    assert a.asscalar() == 3.5
    assert int(nd.array([7])) == 7
    with pytest.raises(ValueError):
        nd.ones((2,)).asscalar()


def test_sparse_row_sparse():
    from incubator_mxnet_tpu.ndarray import sparse
    dense = nd.array([[0, 0], [1, 2], [0, 0], [3, 4]])
    rs = sparse.cast_storage(dense, "row_sparse")
    assert rs.stype == "row_sparse"
    assert rs.indices.asnumpy().tolist() == [1, 3]
    assert_almost_equal(rs.todense(), dense)
    back = rs.tostype("default")
    assert back.stype == "default"


def test_sparse_csr():
    from incubator_mxnet_tpu.ndarray import sparse
    dense = nd.array([[0, 1.0], [2.0, 0]])
    csr = sparse.cast_storage(dense, "csr")
    assert csr.stype == "csr"
    assert_almost_equal(csr.todense(), dense)


def test_one_hot_take_pick():
    idx = nd.array([0, 2], dtype="int32")
    oh = nd.one_hot(idx, depth=3)
    assert oh.asnumpy().tolist() == [[1, 0, 0], [0, 0, 1]]
    data = nd.array([[1.0, 2, 3], [4, 5, 6]])
    assert nd.take(data, nd.array([1], dtype="int32"),
                   axis=1).asnumpy().ravel().tolist() == [2, 5]
    assert nd.pick(data, nd.array([0, 2]), axis=1).asnumpy().tolist() == [1, 6]


def test_np_grad_with_leading_scalar():
    """Cotangent slot routing when non-arrays precede NDArrays
    (round-3 review regression: np.subtract(1.0, x) handed x the
    scalar's gradient)."""
    import numpy as onp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd
    x = mx.np.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = mx.np.subtract(1.0, x)
        loss = (y * y).sum()
    loss.backward()
    onp.testing.assert_allclose(
        x.grad.asnumpy(), -2.0 * (1.0 - onp.array([1., 2., 3.])),
        rtol=1e-6)


def test_array_function_protocol():
    """onp.mean/concatenate/stack on NDArray dispatch to the framework
    numpy namespace and stay NDArray (reference
    test_numpy_interoperability.py / numpy_dispatch_protocol.py)."""
    import numpy as onp
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([4.0, 5.0, 6.0])
    m = onp.mean(a)
    assert isinstance(m, nd.NDArray) and float(m.asnumpy()) == 2.0
    c = onp.concatenate([a, b])
    assert isinstance(c, nd.NDArray)
    assert c.asnumpy().tolist() == [1, 2, 3, 4, 5, 6]
    s = onp.stack([a, b])
    assert isinstance(s, nd.NDArray) and s.shape == (2, 3)


@pytest.mark.parametrize("kind", ["tpu", "gpu", "cpu"])
def test_context_device_id_out_of_range_raises(kind):
    """A device_id beyond the devices present is an error, never a wrap
    onto device ``id % n`` (``mx.tpu(3)`` on a one-chip host used to land
    on chip 0); in range, tpu()/gpu() resolve to the default backend."""
    import jax
    n = len(jax.devices())          # the harness's 8 virtual CPU devices
    assert mx.Context(kind, n - 1).jax_device == jax.devices()[n - 1]
    with pytest.raises(ValueError, match="out of range"):
        mx.Context(kind, n + 1).jax_device
    with pytest.raises(ValueError, match="out of range"):
        nd.ones((2,), ctx=mx.Context(kind, 9))


def test_num_tpus_counts_tpu_devices_only():
    assert mx.context.num_tpus() == 0     # CPU harness: not "any non-CPU"
    assert not mx.tpu_context_available()
