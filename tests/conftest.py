"""Test config (SURVEY §4).

Default: run on a virtual 8-device CPU mesh — the reference's distributed
tests fork local processes; here a forced host device count exercises the
same sharding paths without TPU hardware.

Opt-in on-device pass (reference tests/python/gpu/test_operator_gpu.py:1,
which re-runs the whole unittest suite on the accelerator):

    MXNET_TEST_PLATFORM=tpu python -m pytest tests/test_operator.py ...

leaves the real accelerator as the default jax backend so every eager op,
executor bind and gluon block in the suite actually runs on the chip, and
enables the cpu<->tpu cross-backend consistency sweep
(tests/test_tpu_consistency.py).  Modules that hard-require the 8-device
CPU mesh are skipped in this mode.  fp32 matmuls are pinned to highest
precision so results stay comparable with the suite's numpy-derived
tolerances; the consistency sweep separately covers the default
(bf16-multiply) path with bf16-aware tolerances.
"""
import os

TEST_PLATFORM = os.environ.get("MXNET_TEST_PLATFORM", "cpu")

if TEST_PLATFORM == "tpu":
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
else:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")

# modules whose tests need the multi-device CPU mesh (sharding/collectives
# over 8 virtual devices) or CPU-pinned subprocesses; meaningless or
# unrunnable against the single real chip
_NEEDS_CPU_MESH = {
    "test_parallel", "test_kvstore", "test_engine",
}


def pytest_collection_modifyitems(config, items):
    if TEST_PLATFORM != "tpu":
        return
    skip = pytest.mark.skip(
        reason="needs the 8-device CPU mesh (run without "
               "MXNET_TEST_PLATFORM=tpu)")
    for item in items:
        mod = item.module.__name__ if item.module else ""
        if mod in _NEEDS_CPU_MESH:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import mxnet_tpu as mx

    mx.random.seed(0)
    yield
