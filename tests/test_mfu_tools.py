"""The MFU probes (tools/bench_mfu.py) must stay runnable, and the peak
they and the MFU gauge divide by must come from the published device
table."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_matmul_and_hbm_probes_run_tiny():
    import bench_mfu

    res = bench_mfu.matmul_ceiling(sizes=(128,), iters=4)
    assert res[0]["tflops"] > 0
    cv = bench_mfu.conv_ceiling(batch=2, hw=8, ch=8, iters=2)
    assert cv["tflops"] > 0
    bw = bench_mfu.hbm_bandwidth(mb=4, iters=4)
    assert bw["gb_per_s"] > 0


def test_mfu_peak_comes_from_the_device_table_or_not_at_all(monkeypatch):
    """The MFU peak is a published number keyed by device_kind with its
    source; a device that is not listed (this CPU harness) yields no peak
    — and so no MFU — never a default."""
    import jax

    from mxnet_tpu import telemetry

    flops, hbm, source = telemetry.DEVICE_PEAKS["TPU v5 lite"]
    assert (flops, hbm) == (197e12, 819e9) and "TPU v5e" in source
    monkeypatch.delenv("MXNET_PEAK_TFLOPS", raising=False)
    telemetry.set_peak_flops(None)
    assert jax.devices()[0].device_kind not in telemetry.DEVICE_PEAKS
    assert telemetry.peak_flops() is None
    # the explicit overrides still win
    monkeypatch.setenv("MXNET_PEAK_TFLOPS", "2.5")
    telemetry.set_peak_flops(None)          # drop the cached resolution
    assert telemetry.peak_flops() == 2.5e12
    telemetry.set_peak_flops(1e12)
    assert telemetry.peak_flops() == 1e12
    telemetry.set_peak_flops(None)
