"""Model zoo smoke tests (modeled on tests/python/unittest/
test_gluon_model_zoo.py — tiny inputs, shape checks)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo import vision


@pytest.mark.parametrize("name", ["resnet18_v1", "resnet18_v2"])
def test_resnet18(name):
    net = vision.get_model(name, classes=10)
    net.initialize()
    out = net(nd.array(np.random.rand(1, 3, 32, 32).astype(np.float32)))
    assert out.shape == (1, 10)


def test_resnet50_v1_shape():
    net = vision.resnet50_v1(classes=7)
    net.initialize()
    out = net(nd.array(np.random.rand(1, 3, 64, 64).astype(np.float32)))
    assert out.shape == (1, 7)


def test_mobilenet():
    net = vision.mobilenet0_25(classes=5)
    net.initialize()
    out = net(nd.array(np.random.rand(1, 3, 32, 32).astype(np.float32)))
    assert out.shape == (1, 5)


def test_alexnet():
    net = vision.alexnet(classes=8)
    net.initialize()
    out = net(nd.array(np.random.rand(1, 3, 224, 224).astype(np.float32)))
    assert out.shape == (1, 8)


def test_vgg11():
    net = vision.vgg11(classes=6)
    net.initialize()
    out = net(nd.array(np.random.rand(1, 3, 32, 32).astype(np.float32)))
    assert out.shape == (1, 6)


def test_get_model_unknown():
    with pytest.raises(ValueError):
        vision.get_model("nonexistent_model")


def test_resnet_hybridize_and_train_step():
    from mxnet_tpu import gluon, autograd

    net = vision.resnet18_v1(classes=4)
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = nd.array(np.random.rand(2, 3, 32, 32).astype(np.float32))
    y = nd.array(np.array([0, 1], dtype=np.float32))
    with autograd.record():
        out = net(x)
        loss = loss_fn(out, y)
    loss.backward()
    trainer.step(2)
    assert np.isfinite(loss.asnumpy()).all()


def test_eager_resnet50_forward_is_fast():
    """The per-op jit cache must keep un-hybridized (eager) dispatch usable:
    one warm bs1 ResNet-50 forward in well under a second (the round-1
    regression recompiled every op on every forward)."""
    import time

    net = vision.resnet50_v1(classes=10)
    net.initialize()
    x = nd.array(np.random.rand(1, 3, 224, 224).astype(np.float32))
    out = net(x)          # cold: fills the per-op cache
    out.wait_to_read()
    t0 = time.time()
    out = net(x)
    out.wait_to_read()
    warm = time.time() - t0
    assert warm < 5.0, "warm eager ResNet-50 forward took %.2fs" % warm
