"""The system under test for the ``resnet_v1`` family: the model zoo's
network, as ``bench.build_trainer`` builds it (its dozen lines copied, so
that its CPU shrink branch can never be taken), with the benchmark's
weights put in."""


def build_net(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.vision.resnet import _ResNet

    if cfg["units"] == [3, 4, 6, 3] and cfg["channels"][-1] == 2048:
        from mxnet_tpu.gluon.model_zoo import vision

        net = vision.resnet50_v1(classes=cfg["classes"])
    else:  # the CPU rehearsal's tiny layout, same unit code
        net = _ResNet(1, True, cfg["units"], cfg["channels"],
                      classes=cfg["classes"])
    # Zero costs nothing for the deferred shapes; set_weights fills all
    net.initialize(mx.init.Zero())
    return net


def loss_fn(cfg):
    from mxnet_tpu import gluon

    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    return lambda out, label: ce(out, label)
