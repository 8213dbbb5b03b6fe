"""On-chip serving correctness guard.

Runs ONLY against the real accelerator (MXNET_TEST_PLATFORM=tpu): a
numeric spot-check of the uint8+preprocess serving path on the chip.
Serving throughput has no guard here: it is not measured on the current
machine, and a floor belongs with the benchmark that defines the metric.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx

pytestmark = pytest.mark.skipif(
    os.environ.get("MXNET_TEST_PLATFORM") != "tpu"
    or mx.context.num_tpus() == 0,
    reason="on-chip serving guard needs MXNET_TEST_PLATFORM=tpu")


def test_predictor_correct_on_chip():
    """Numeric spot-check of the uint8+preprocess serving path on the
    accelerator (not just throughput)."""
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.serving import Predictor, uint8_normalizer

    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1), nn.GlobalAvgPool2D(),
            nn.Dense(5))
    net.initialize()
    prep = uint8_normalizer(mean=(0., 0., 0.), std=(255., 255., 255.),
                            dtype="float32")
    raw = np.random.randint(0, 255, (4, 3, 16, 16), np.uint8)
    pred, _ = Predictor.from_block(net, raw, chain=2, preprocess=prep)
    outs = list(pred.predict([raw] * 3))
    ref = net(nd.array(raw.astype(np.float32) / 255.0)).asnumpy()
    np.testing.assert_allclose(outs[0], ref, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(outs[2], ref, rtol=2e-2, atol=2e-2)
