"""Measure this chip's attainable compute ceiling and do the MFU
accounting for bench.py (VERDICT r4 weak #1 / next-round #1).

Two forced-compute probes, each a chain of iterations inside one jit
program through donated state, timed to ``jax.block_until_ready``:

1. matmul ceiling — bf16 square matmul chains at several MXU-friendly
   sizes; the peak is the chip's practical TF/s for pure MXU work.
2. conv ceiling — a chained 3x3 same-channel convolution (the ResNet-50
   hot shape class) at bf16; convs lower to implicit GEMM on the MXU
   but pay layout/im2col overheads, so this is the fairer ceiling for
   a conv net.

Then computes MFU for the bench.py headline (img/s x FLOPs/img) against
(a) the measured matmul ceiling, (b) the measured conv ceiling, and
(c) the published peak of the device kind (telemetry.DEVICE_PEAKS; a
device that is not listed gets no such ratio).

Run on an idle chip:
    python tools/bench_mfu.py --bench-img-per-sec N [--json out.json]
"""
import argparse
import json
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# ResNet-50 v1 @224: ~4.1 GFLOP forward per image; training fwd+bwd+update
# is conventionally 3x forward (the reference's own accounting in
# docs/faq/perf.md benchmarks uses images/sec on the same model).
RESNET50_TRAIN_GFLOP_PER_IMG = 12.3


def log(msg):
    print("[mfu %6.1fs] %s" % (time.time() - T0, msg), file=sys.stderr,
          flush=True)


def _timed_chain(fn, state, repeats=3):
    """Run fn (a jitted donated-state chain) `repeats` times; return
    (best_seconds, final_state)."""
    import jax

    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        state = jax.block_until_ready(fn(state))
        best = min(best, time.perf_counter() - t0)
    return best, state


def matmul_ceiling(sizes=(2048, 4096, 8192), iters=256):
    import jax
    import jax.numpy as jnp
    from jax import lax

    results = []
    for n in sizes:
        flops_per = 2.0 * n * n * n

        @partial(jax.jit, donate_argnums=0)
        def chain(y, w):
            def body(_, y):
                # the 0.03 scale keeps bf16 activations bounded; it
                # fuses into the matmul epilogue (no extra HBM pass)
                return (y @ w) * jnp.asarray(0.03, jnp.bfloat16)

            return lax.fori_loop(0, iters, body, y)

        rng = np.random.RandomState(0)
        y = jnp.asarray(rng.randn(n, n), jnp.bfloat16)
        w = jnp.asarray(rng.randn(n, n) / np.sqrt(n), jnp.bfloat16)

        log("matmul %d: compiling" % n)
        # warm-up + compile outside the clock
        y = jax.block_until_ready(chain(y, w))
        secs, y = _timed_chain(lambda s: chain(s, w), y)
        tflops = iters * flops_per / secs / 1e12
        log("matmul %d: %.1f TF/s (%.2fs / %d iters)"
            % (n, tflops, secs, iters))
        results.append({"n": n, "iters": iters, "seconds": secs,
                        "tflops": tflops})
    return results


def conv_ceiling(batch=256, hw=28, ch=256, iters=128):
    import jax
    import jax.numpy as jnp
    from jax import lax

    flops_per = 2.0 * batch * hw * hw * ch * ch * 9

    @partial(jax.jit, donate_argnums=0)
    def chain(x, w):
        def body(_, x):
            y = lax.conv_general_dilated(
                x, w, window_strides=(1, 1), padding="SAME",
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            return y * jnp.asarray(0.03, jnp.bfloat16)

        return lax.fori_loop(0, iters, body, x)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, ch, hw, hw), jnp.bfloat16)
    w = jnp.asarray(rng.randn(ch, ch, 3, 3) / (3 * np.sqrt(ch)),
                    jnp.bfloat16)

    log("conv %dx%dx%dx%d: compiling" % (batch, ch, hw, hw))
    x = jax.block_until_ready(chain(x, w))
    secs, x = _timed_chain(lambda s: chain(s, w), x)
    tflops = iters * flops_per / secs / 1e12
    log("conv: %.1f TF/s (%.2fs / %d iters)" % (tflops, secs, iters))
    return {"batch": batch, "hw": hw, "ch": ch, "iters": iters,
            "seconds": secs, "tflops": tflops}


def hbm_bandwidth(mb=512, iters=64):
    """Forced elementwise chain: one read + one write of `mb` MB per
    iteration -> effective HBM GB/s (the memory roofline)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = mb * 1024 * 1024 // 2  # bf16 elements
    bytes_per_iter = 2.0 * n * 2  # read + write

    @partial(jax.jit, donate_argnums=0)
    def chain(y):
        def body(_, y):
            return y * jnp.asarray(1.0001, jnp.bfloat16) \
                + jnp.asarray(0.0001, jnp.bfloat16)

        return lax.fori_loop(0, iters, body, y)

    y = jnp.ones((n,), jnp.bfloat16)

    log("hbm %dMB: compiling" % mb)
    y = jax.block_until_ready(chain(y))
    secs, y = _timed_chain(chain, y, repeats=6)
    gbs = iters * bytes_per_iter / secs / 1e9
    log("hbm: %.0f GB/s (%.2fs / %d iters)" % (gbs, secs, iters))
    return {"mb": mb, "iters": iters, "seconds": secs, "gb_per_s": gbs}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--json", default=None)
    p.add_argument("--bench-img-per-sec", type=float, default=None,
                   help="the bench.py img/s to account for (none = "
                        "ceilings only, no MFU ratios)")
    args = p.parse_args()

    import jax

    from mxnet_tpu import telemetry

    kind = jax.devices()[0].device_kind
    log("devices: %s" % jax.devices())
    peak = telemetry.DEVICE_PEAKS.get(kind)
    if peak is None:
        log("device_kind %r has no published peak in "
            "telemetry.DEVICE_PEAKS: no MFU against it" % kind)

    mm = matmul_ceiling()
    cv = conv_ceiling()
    bw = hbm_bandwidth()

    img_s = args.bench_img_per_sec
    bench_tflops = (img_s or 0) * RESNET50_TRAIN_GFLOP_PER_IMG / 1e3

    mm_peak = max(r["tflops"] for r in mm)
    out = {
        "platform": jax.devices()[0].platform,
        "device_kind": kind,
        "device_count": len(jax.devices()),
        "matmul": mm,
        "conv": cv,
        "hbm": bw,
        "bench_img_per_sec": img_s,
        "bench_tflops": bench_tflops,
        "mfu_vs_matmul_ceiling": bench_tflops / mm_peak if img_s else None,
        "mfu_vs_conv_ceiling": bench_tflops / cv["tflops"]
        if img_s else None,
        "mfu_vs_published_peak": bench_tflops / (peak[0] / 1e12)
        if img_s and peak else None,
        "published_peak_tflops": peak[0] / 1e12 if peak else None,
        "published_peak_source": peak[2] if peak else None,
        "resnet50_train_gflop_per_img": RESNET50_TRAIN_GFLOP_PER_IMG,
    }
    print(json.dumps(out, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        log("wrote %s" % args.json)


T0 = time.time()

if __name__ == "__main__":
    main()
