"""cpu <-> tpu cross-backend consistency sweep.

Reference parity: tests/python/gpu/test_operator_gpu.py — the reference's
signature accelerator-test move is running every op on both backends and
comparing outputs AND gradients with check_consistency
(python/mxnet/test_utils.py:1224).  Here the two backends are the host CPU
and the real TPU chip in the same process; each case binds the same symbol
with identical inputs on both contexts and cross-checks forward outputs
and input gradients.

Opt-in: requires MXNET_TEST_PLATFORM=tpu and a real accelerator —
skipped silently otherwise (the default suite is CPU-pinned).

Design notes (TPU-native):
- ops with the same input domain are grouped into one multi-output
  Symbol so one executor bind (one XLA compile) covers many ops
  instead of one compile per op
- fp32 matmuls run at highest precision (set by conftest in this mode)
  so tolerances stay near fp32; test_default_matmul_precision_bf16
  separately covers the shipped bf16-multiply default with bf16-aware
  tolerances
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal, check_consistency

pytestmark = pytest.mark.skipif(
    os.environ.get("MXNET_TEST_PLATFORM") != "tpu"
    or mx.context.num_tpus() == 0,
    reason="cross-backend sweep needs MXNET_TEST_PLATFORM=tpu and a chip")


def _ctxs(**shapes):
    return [dict(ctx=mx.cpu(), **shapes), dict(ctx=mx.tpu(0), **shapes)]


def _group(ops):
    d = mx.sym.var("data")
    return mx.sym.Group([fn(d) for fn in ops])


# --- elementwise vocabulary, grouped by input domain -----------------

UNARY_ANY = [
    lambda d: mx.sym.relu(d),
    lambda d: mx.sym.sigmoid(d),
    lambda d: mx.sym.tanh(d),
    lambda d: mx.sym.exp(d),
    lambda d: mx.sym.sin(d),
    lambda d: mx.sym.cos(d),
    lambda d: mx.sym.arctan(d),
    lambda d: mx.sym.square(d),
    lambda d: mx.sym.expm1(d),
    lambda d: mx.sym.Activation(d, act_type="softrelu"),
    lambda d: mx.sym.LeakyReLU(d, act_type="leaky", slope=0.1),
    lambda d: mx.sym.LeakyReLU(d, act_type="elu", slope=1.0),
    lambda d: mx.sym.softsign(d),
    lambda d: mx.sym.erf(d),
]

UNARY_POS = [
    lambda d: mx.sym.log(d),
    lambda d: mx.sym.log2(d),
    lambda d: mx.sym.log10(d),
    lambda d: mx.sym.log1p(d),
    lambda d: mx.sym.sqrt(d),
    lambda d: mx.sym.rsqrt(d),
    lambda d: mx.sym.cbrt(d),
    lambda d: mx.sym.gamma(d),
    lambda d: mx.sym.gammaln(d),
    lambda d: mx.sym.reciprocal(d),
]

UNARY_UNIT = [
    lambda d: mx.sym.arcsin(d),
    lambda d: mx.sym.arccos(d),
    lambda d: mx.sym.arctanh(d * 0.9),
    lambda d: mx.sym.tan(d),
    lambda d: mx.sym.sinh(d),
    lambda d: mx.sym.cosh(d),
    lambda d: mx.sym.arcsinh(d),
]

REDUCTIONS = [
    lambda d: mx.sym.sum(d, axis=1),
    lambda d: mx.sym.mean(d, axis=0),
    lambda d: mx.sym.max(d, axis=1),
    lambda d: mx.sym.min(d),
    lambda d: mx.sym.prod(d * 0.5 + 1.0, axis=1),
    lambda d: mx.sym.norm(d, ord=2, axis=1),
    lambda d: mx.sym.sum(d, axis=1, keepdims=True),
]

SHAPES_OPS = [
    lambda d: mx.sym.transpose(d),
    lambda d: mx.sym.reshape(d, shape=(-1,)),
    lambda d: mx.sym.flip(d, axis=1),
    lambda d: mx.sym.slice(d, begin=(1, 0), end=(4, 3)),
    lambda d: mx.sym.clip(d, -0.5, 0.5),
    lambda d: mx.sym.tile(d, reps=(2, 1)),
    lambda d: mx.sym.expand_dims(d, axis=0),
    lambda d: mx.sym.pad(mx.sym.reshape(d, shape=(1, 1, 5, 4)),
                         mode="constant", pad_width=(0, 0, 0, 0, 1, 1, 2, 2)),
    lambda d: mx.sym.softmax(d, axis=-1),
    lambda d: mx.sym.log_softmax(d, axis=-1),
]


@pytest.mark.parametrize("name,ops,lo,hi", [
    ("unary_any", UNARY_ANY, -2.0, 2.0),
    ("unary_pos", UNARY_POS, 0.1, 2.0),
    ("unary_unit", UNARY_UNIT, -0.9, 0.9),
    ("reductions", REDUCTIONS, -2.0, 2.0),
    ("shape_ops", SHAPES_OPS, -2.0, 2.0),
])
def test_elementwise_groups(name, ops, lo, hi):
    sym = _group(ops)
    data = np.random.uniform(lo, hi, size=(5, 4))
    check_consistency(sym, _ctxs(data=(5, 4)),
                      arg_params={"data": data}, tol=1e-4)


# --- binary / broadcasting -------------------------------------------

def test_binary_broadcast():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    sym = mx.sym.Group([
        mx.sym.broadcast_add(a, b), mx.sym.broadcast_sub(a, b),
        mx.sym.broadcast_mul(a, b), mx.sym.broadcast_div(a, b),
        mx.sym.broadcast_maximum(a, b), mx.sym.broadcast_minimum(a, b),
        mx.sym.broadcast_power(mx.sym.abs(a) + 0.5, b),
        mx.sym.broadcast_hypot(a, b),
    ])
    check_consistency(
        sym, _ctxs(a=(4, 1, 3), b=(1, 5, 3)),
        arg_params={"a": np.random.uniform(0.5, 2, (4, 1, 3)),
                    "b": np.random.uniform(0.5, 2, (1, 5, 3))}, tol=1e-4)


# --- the MXU ops: dense / conv / pooling / norm ----------------------

def test_fully_connected():
    d = mx.sym.var("data")
    sym = mx.sym.FullyConnected(d, num_hidden=16, name="fc")
    check_consistency(sym, _ctxs(data=(8, 12)), tol=1e-3)


def test_dot_and_batch_dot():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    sym = mx.sym.dot(a, b)
    check_consistency(sym, _ctxs(a=(6, 5), b=(5, 7)), tol=1e-3)
    sym = mx.sym.batch_dot(mx.sym.var("a"), mx.sym.var("b"))
    check_consistency(sym, _ctxs(a=(3, 4, 5), b=(3, 5, 6)), tol=1e-3)


@pytest.mark.parametrize("kwargs,ishape", [
    (dict(num_filter=8, kernel=(3, 3)), (2, 3, 10, 10)),
    (dict(num_filter=8, kernel=(3, 3), stride=(2, 2), pad=(1, 1)),
     (2, 3, 10, 10)),
    (dict(num_filter=6, kernel=(3, 3), num_group=3), (2, 6, 8, 8)),
    (dict(num_filter=8, kernel=(3, 3), dilate=(2, 2)), (2, 3, 12, 12)),
    (dict(num_filter=8, kernel=(3,)), (2, 3, 12)),
])
def test_convolution(kwargs, ishape):
    sym = mx.sym.Convolution(mx.sym.var("data"), name="conv", **kwargs)
    check_consistency(sym, _ctxs(data=ishape), scale=0.3, tol=1e-3)


def test_deconvolution():
    sym = mx.sym.Deconvolution(mx.sym.var("data"), num_filter=4,
                               kernel=(3, 3), stride=(2, 2), name="dc")
    check_consistency(sym, _ctxs(data=(2, 3, 6, 6)), scale=0.3, tol=1e-3)


@pytest.mark.parametrize("kwargs", [
    dict(pool_type="max", kernel=(2, 2), stride=(2, 2)),
    dict(pool_type="avg", kernel=(3, 3), stride=(2, 2), pad=(1, 1)),
    dict(pool_type="max", global_pool=True, kernel=(2, 2)),
])
def test_pooling(kwargs):
    sym = mx.sym.Pooling(mx.sym.var("data"), **kwargs)
    check_consistency(sym, _ctxs(data=(2, 3, 8, 8)), tol=1e-4)


def test_batchnorm_and_layernorm():
    d = mx.sym.var("data")
    sym = mx.sym.BatchNorm(d, fix_gamma=False, name="bn")
    check_consistency(sym, _ctxs(data=(4, 3, 6, 6)), tol=1e-3)
    sym = mx.sym.LayerNorm(d, name="ln")
    check_consistency(sym, _ctxs(data=(4, 12)), tol=1e-3)


def test_softmax_output_and_embedding():
    d = mx.sym.var("data")
    sym = mx.sym.SoftmaxOutput(d, mx.sym.var("label"), name="sm")
    # label is an argument: supply integer classes via arg_params
    check_consistency(
        sym, _ctxs(data=(6, 10), label=(6,)),
        arg_params={"label": np.random.randint(0, 10, (6,)).astype(np.float32)},
        tol=1e-4)
    emb = mx.sym.Embedding(mx.sym.var("idx"), input_dim=20, output_dim=8,
                           name="emb")
    check_consistency(
        emb, _ctxs(idx=(5,)),
        arg_params={"idx": np.random.randint(0, 20, (5,)).astype(np.float32)},
        tol=1e-4)


# --- indexing / ordering ---------------------------------------------

def test_take_and_ordering():
    d = mx.sym.var("data")
    sym = mx.sym.Group([mx.sym.sort(d, axis=1),
                        mx.sym.argsort(d, axis=1),
                        mx.sym.argmax(d, axis=1),
                        mx.sym.argmin(d, axis=1),
                        mx.sym.topk(d, k=3, axis=1, ret_typ="value")])
    check_consistency(sym, _ctxs(data=(4, 7)), grad_req="null", tol=1e-5)


def test_concat_split_stack():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    sym = mx.sym.Group([mx.sym.concat(a, b, dim=1),
                        mx.sym.stack(a, b, axis=0),
                        mx.sym.broadcast_add(a, b)])
    check_consistency(sym, _ctxs(a=(3, 4), b=(3, 4)), tol=1e-5)


# --- eager on-chip checks --------------------------------------------

def test_eager_ops_on_chip_match_cpu():
    """Eager NDArray ops dispatched to the chip match the cpu backend."""
    x = np.random.randn(16, 16).astype(np.float32)
    with mx.tpu(0):
        t = nd.array(x)
        out_t = (nd.dot(t, t.T) + nd.relu(t) * 2).asnumpy()
        assert t.context.device_type == "tpu"
    with mx.cpu():
        c = nd.array(x)
        out_c = (nd.dot(c, c.T) + nd.relu(c) * 2).asnumpy()
    assert_almost_equal(out_t, out_c, rtol=1e-4, atol=1e-4)


def test_default_matmul_precision_bf16():
    """The shipped default (bf16 multiplies on the MXU) stays within
    bf16-aware tolerance of the fp32 host result."""
    import jax

    x = np.random.randn(64, 64).astype(np.float32)
    y = np.random.randn(64, 64).astype(np.float32)
    ref = x @ y
    with jax.default_matmul_precision("default"):
        with mx.tpu(0):
            out = nd.dot(nd.array(x), nd.array(y)).asnumpy()
    # bf16 has ~8 mantissa bits -> relative error up to ~1e-2
    assert_almost_equal(out, ref, rtol=2e-2, atol=2e-2 * np.abs(ref).max())


def test_mixed_precision_cast_chain_on_chip():
    """astype round-trips and bf16 compute run on the chip."""
    x = np.random.randn(8, 8).astype(np.float32)
    with mx.tpu(0):
        a = nd.array(x).astype("bfloat16")
        out = (a * 2 + 1).astype("float32").asnumpy()
    assert_almost_equal(out, x.astype(np.float32) * 2 + 1, rtol=2e-2,
                        atol=2e-2)
