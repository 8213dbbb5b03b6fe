"""Custom op framework tests (modeled on the reference
tests/python/unittest/test_operator.py::test_custom_op cases)."""
import numpy as np
import pytest


import mxnet_tpu as mx
from mxnet_tpu import nd, autograd


@mx.operator.register("sqr_t")
class SqrProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=True)

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def create_operator(self, ctx, shapes, dtypes):
        return Sqr()


class Sqr(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        self.assign(out_data[0], req[0], in_data[0] * in_data[0])

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.assign(in_grad[0], req[0], 2 * in_data[0] * out_grad[0])


@mx.operator.register("mult_t")
class MultProp(mx.operator.CustomOpProp):
    def list_arguments(self):
        return ["lhs", "rhs"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def create_operator(self, ctx, shapes, dtypes):
        return Mult()


class Mult(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        self.assign(out_data[0], req[0], in_data[0] * in_data[1])

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.assign(in_grad[0], req[0], in_data[1] * out_grad[0])
        self.assign(in_grad[1], req[1], in_data[0] * out_grad[0])


@mx.operator.register("no_input_op_t")
class NoInputProp(mx.operator.CustomOpProp):
    def __init__(self, length, depth):
        super().__init__(need_top_grad=False)
        self.length = int(length)
        self.depth = int(depth)

    def list_arguments(self):
        return []

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return [], [(self.length, self.depth)], []

    def infer_type(self, in_type):
        return [], [np.float32], []

    def create_operator(self, ctx, shapes, dtypes):
        return NoInputOp(self.length, self.depth)


class NoInputOp(mx.operator.CustomOp):
    def __init__(self, length, depth):
        self.output = np.arange(length * depth, dtype=np.float32) \
            .reshape(length, depth)

    def forward(self, is_train, req, in_data, out_data, aux):
        self.assign(out_data[0], req[0], mx.nd.array(self.output))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        pass


def test_custom_forward_eager():
    x = nd.array(np.array([[1.0, 2.0], [3.0, 4.0]], np.float32))
    y = nd.Custom(x, op_type="sqr_t")
    np.testing.assert_allclose(y.asnumpy(), x.asnumpy() ** 2)


def test_custom_backward():
    x = nd.array(np.array([1.0, 2.0, 3.0], np.float32))
    x.attach_grad()
    with autograd.record():
        y = nd.Custom(x, op_type="sqr_t")
        loss = y.sum()
    loss.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), 2 * x.asnumpy())


def test_custom_two_inputs_kwargs():
    a = nd.array(np.random.rand(3, 2).astype(np.float32))
    b = nd.array(np.random.rand(3, 2).astype(np.float32))
    a.attach_grad()
    b.attach_grad()
    with autograd.record():
        y = nd.Custom(lhs=a, rhs=b, op_type="mult_t")
        y.backward()
    np.testing.assert_allclose(y.asnumpy(), a.asnumpy() * b.asnumpy(),
                               rtol=1e-6)
    np.testing.assert_allclose(a.grad.asnumpy(), b.asnumpy(), rtol=1e-6)
    np.testing.assert_allclose(b.grad.asnumpy(), a.asnumpy(), rtol=1e-6)


def test_custom_no_input():
    out = nd.Custom(length=4, depth=3, op_type="no_input_op_t")
    np.testing.assert_allclose(
        out.asnumpy(), np.arange(12, dtype=np.float32).reshape(4, 3))


def test_custom_in_hybrid_block_trains():
    """A numpy-implemented op training inside a hybridized block."""

    class Net(mx.gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.dense = mx.gluon.nn.Dense(2)

        def hybrid_forward(self, F, x):
            h = self.dense(x)
            return F.Custom(h, op_type="sqr_t")

    net = Net()
    net.initialize(mx.init.Uniform(0.5))
    net.hybridize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    x = nd.array(np.random.rand(4, 3).astype(np.float32))
    losses = []
    for _ in range(5):
        with autograd.record():
            y = net(x)
            loss = y.sum()
        loss.backward()
        trainer.step(4)
        losses.append(float(loss.asscalar()))
    assert losses[-1] < losses[0]  # squared outputs shrink under descent


def test_custom_symbol_executor():
    data = mx.sym.var("data")
    out = mx.sym.Custom(data=data, op_type="sqr_t", name="sqr")
    x = nd.array(np.array([2.0, 3.0], np.float32))
    gx = nd.array(np.zeros(2, np.float32))
    ex = out.bind(args={"data": x}, args_grad={"data": gx})
    np.testing.assert_allclose(
        ex.forward(is_train=True)[0].asnumpy(), [4.0, 9.0])
    ex.backward(nd.array(np.ones(2, np.float32)))
    np.testing.assert_allclose(gx.asnumpy(), [4.0, 6.0])
