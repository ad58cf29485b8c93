"""NDArray semantics tests (reference model: tests/python/unittest/test_ndarray.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def test_creation():
    a = nd.zeros((2, 3))
    assert a.shape == (2, 3)
    assert a.dtype == np.float32
    assert (a.asnumpy() == 0).all()

    b = nd.ones((4,), dtype="int32")
    assert b.dtype == np.int32

    c = nd.full((2, 2), 7.5)
    assert (c.asnumpy() == 7.5).all()

    d = nd.array([[1, 2], [3, 4]])
    assert d.shape == (2, 2)
    np.testing.assert_array_equal(d.asnumpy(), [[1, 2], [3, 4]])

    e = nd.arange(1, 7, 2)
    np.testing.assert_allclose(e.asnumpy(), [1, 3, 5])


def test_elementwise_arith():
    a = nd.array([[1.0, 2.0], [3.0, 4.0]])
    b = nd.array([[4.0, 3.0], [2.0, 1.0]])
    np.testing.assert_allclose((a + b).asnumpy(), np.full((2, 2), 5.0))
    np.testing.assert_allclose((a - b).asnumpy(), [[-3, -1], [1, 3]])
    np.testing.assert_allclose((a * b).asnumpy(), [[4, 6], [6, 4]])
    np.testing.assert_allclose((a / b).asnumpy(), [[0.25, 2 / 3], [1.5, 4]])
    np.testing.assert_allclose((a + 1).asnumpy(), [[2, 3], [4, 5]])
    np.testing.assert_allclose((1 - a).asnumpy(), [[0, -1], [-2, -3]])
    np.testing.assert_allclose((2 / a).asnumpy(), [[2, 1], [2 / 3, 0.5]])
    np.testing.assert_allclose((a ** 2).asnumpy(), [[1, 4], [9, 16]], rtol=1e-5)
    np.testing.assert_allclose((-a).asnumpy(), [[-1, -2], [-3, -4]])


def test_inplace_mutation():
    a = nd.zeros((2, 3))
    a[:] = 5
    assert (a.asnumpy() == 5).all()
    a += 1
    assert (a.asnumpy() == 6).all()
    a *= 2
    assert (a.asnumpy() == 12).all()
    a[0, 1] = 99
    assert a.asnumpy()[0, 1] == 99
    a[1] = nd.array([7.0, 8.0, 9.0])
    np.testing.assert_allclose(a.asnumpy()[1], [7, 8, 9])


def test_indexing():
    a = nd.array(np.arange(24).reshape(2, 3, 4))
    np.testing.assert_array_equal(a[1].asnumpy(), np.arange(12, 24).reshape(3, 4))
    np.testing.assert_array_equal(a[1, 2].asnumpy(), [20, 21, 22, 23])
    np.testing.assert_array_equal(a[:, 1].asnumpy(), [[4, 5, 6, 7], [16, 17, 18, 19]])
    sl = a[0:1]
    assert sl.shape == (1, 3, 4)


def test_copy_semantics():
    a = nd.ones((3,))
    b = a.copy()
    b[:] = 2
    assert (a.asnumpy() == 1).all()

    c = nd.zeros((3,))
    a.copyto(c)
    assert (c.asnumpy() == 1).all()

    d = a.as_in_context(mx.cpu(0))
    assert d.context.device_type == "cpu"


def test_scalar_conversion():
    a = nd.array([3.5])
    assert a.asscalar() == 3.5
    assert float(a) == 3.5
    with pytest.raises(Exception):
        nd.zeros((2,)).asscalar()


def test_reshape_transpose():
    a = nd.array(np.arange(6).reshape(2, 3))
    assert a.reshape((3, 2)).shape == (3, 2)
    assert a.reshape((-1,)).shape == (6,)
    assert a.T.shape == (3, 2)
    assert a.transpose().shape == (3, 2)
    assert a.expand_dims(0).shape == (1, 2, 3)
    assert nd.moveaxis(a, 0, 1).shape == (3, 2)


def test_reduce_methods():
    a = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    assert a.sum().asscalar() == 15
    np.testing.assert_allclose(a.sum(axis=0).asnumpy(), [3, 5, 7])
    np.testing.assert_allclose(a.mean(axis=1).asnumpy(), [1, 4])
    assert a.max().asscalar() == 5
    assert a.min().asscalar() == 0
    assert a.argmax().asscalar() == 5
    assert a.norm().asscalar() == pytest.approx(np.sqrt(np.sum(np.arange(6) ** 2)))


def test_comparison():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([3.0, 2.0, 1.0])
    np.testing.assert_array_equal((a == b).asnumpy(), [0, 1, 0])
    np.testing.assert_array_equal((a > b).asnumpy(), [0, 0, 1])
    np.testing.assert_array_equal((a >= 2).asnumpy(), [0, 1, 1])


def test_dtype_cast():
    a = nd.array([1.5, 2.5])
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = nd.cast(a, dtype="float16")
    assert c.dtype == np.float16


def test_save_load(tmp_path):
    fname = str(tmp_path / "arrs.npz")
    arrs = {"w": nd.ones((2, 2)), "b": nd.zeros((3,))}
    nd.save(fname, arrs)
    loaded = nd.load(fname)
    assert set(loaded) == {"w", "b"}
    np.testing.assert_array_equal(loaded["w"].asnumpy(), np.ones((2, 2)))

    lst = [nd.ones((2,)), nd.zeros((1,))]
    fname2 = str(tmp_path / "lst.npz")
    nd.save(fname2, lst)
    loaded2 = nd.load(fname2)
    assert isinstance(loaded2, list) and len(loaded2) == 2


def test_context():
    assert mx.cpu(0).device_type == "cpu"
    with mx.Context("cpu", 0):
        assert mx.current_context().device_type == "cpu"
    a = nd.zeros((2,), ctx=mx.cpu(0))
    assert a.context.device_type == "cpu"
    a.wait_to_read()


def test_requested_accelerator_is_that_chip_or_an_error(monkeypatch):
    """tpu(i)/gpu(i) on a host WITH accelerators is device i or an
    MXNetError, never i % n on another chip; with none (this CPU mesh)
    the MXNet-compat mapping onto the virtual devices stays."""
    from mxnet_tpu import context

    class Chip:
        platform = "tpu"

    chips = [Chip(), Chip()]
    cpus = context._jax_devices("cpu")
    assert mx.gpu(9).jax_device is cpus[9 % len(cpus)]      # compat mapping
    monkeypatch.setattr(
        context, "_jax_devices",
        lambda kind: cpus if kind == "cpu" else chips)
    assert mx.tpu(1).jax_device is chips[1]
    assert mx.gpu(0).jax_device is chips[0]
    with pytest.raises(mx.MXNetError, match="2 accelerator"):
        mx.tpu(2).jax_device
    assert mx.cpu(0).jax_device is cpus[0]


def test_concat_split():
    a = nd.ones((2, 3))
    b = nd.zeros((2, 3))
    c = nd.concatenate([a, b], axis=0)
    assert c.shape == (4, 3)
    parts = c.split(2, axis=0)
    assert len(parts) == 2
    np.testing.assert_array_equal(parts[0].asnumpy(), np.ones((2, 3)))


def test_broadcast():
    a = nd.array([[1.0], [2.0]])
    b = a.broadcast_to((2, 3))
    assert b.shape == (2, 3)
    np.testing.assert_allclose(b.asnumpy(), [[1, 1, 1], [2, 2, 2]])


def test_getitem_bounds_checked_under_record():
    import mxnet_tpu as mx
    x = mx.nd.array(np.array([1., 2., 3.], np.float32))
    with mx.autograd.record():
        with pytest.raises(IndexError):
            x[5]
        with pytest.raises(IndexError):
            x[-5]


def test_scalar_tuple_index_grad():
    import mxnet_tpu as mx
    x = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    x.attach_grad()
    with mx.autograd.record():
        y = x[0, 1] * 3
    y.backward()
    g = x.grad.asnumpy()
    exp = np.zeros((2, 3), np.float32)
    exp[0, 1] = 3
    np.testing.assert_allclose(g, exp)


def test_T_property_grad():
    import mxnet_tpu as mx
    x = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    x.attach_grad()
    with mx.autograd.record():
        loss = (x.T * 2).sum()
    loss.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), np.full((2, 3), 2.0))


def test_zero_size_indexing():
    import mxnet_tpu as mx
    x = mx.nd.zeros((5, 0))
    with mx.autograd.record():
        y = x[2]
    assert y.shape == (0,)


def test_bool_and_empty_slice_indexing_under_record():
    import mxnet_tpu as mx
    x = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    x.attach_grad()
    with mx.autograd.record():
        b = x[True]
        e = x[0, 1:1]
        loss = (b * 2).sum()
    assert b.shape == (1, 2, 3)  # numpy semantics: new leading axis
    assert e.shape == (0,)
    loss.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), np.full((2, 3), 2.0))
