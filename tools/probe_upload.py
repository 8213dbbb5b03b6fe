"""Host->device upload bandwidth vs transfer size and dtype.

Maps the upload surface (size x dtype, incl. ml_dtypes bf16) so every
upload consumer (serving, IO pipeline) can be built on measured numbers.
Each timed ``device_put`` ends in ``jax.block_until_ready``.

Usage: python tools/probe_upload.py [--json out.json]
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--json", default=None)
    p.add_argument("--max-mb", type=int, default=256)
    args = p.parse_args()

    import jax

    dev = jax.devices()[0]
    print("device:", dev, dev.device_kind)

    def timed_upload(x, reps=3):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.device_put(x, dev))
            best = min(best, time.perf_counter() - t0)
        return best

    sizes = [2 ** k for k in range(10, 48)
             if 2 ** k <= args.max_mb * 2 ** 20]
    if len(sizes) > 8:
        big = sizes[-1]
        sizes = sizes[::2]
        if sizes[-1] != big:
            sizes.append(big)
    try:
        import ml_dtypes

        bf16 = np.dtype(ml_dtypes.bfloat16)
    except ImportError:
        bf16 = None
    dtypes = [("float32", np.float32), ("uint8", np.uint8)]
    if bf16 is not None:
        dtypes.append(("bfloat16(ml_dtypes)", bf16))

    rows = []
    print("%8s  %-20s %10s %12s" % ("bytes", "dtype", "time", "GB/s"))
    for name, dt in dtypes:
        for nbytes in sizes:
            n = nbytes // np.dtype(dt).itemsize
            if n == 0:
                continue
            x = (np.random.rand(n) * 100).astype(np.float32).astype(dt)
            t = timed_upload(x)
            gbs = nbytes / t / 1e9
            rows.append({"dtype": name, "bytes": nbytes,
                         "seconds": round(t, 6), "GBps": round(gbs, 4)})
            print("%8.1fM %-20s %9.4fs %10.3f GB/s"
                  % (nbytes / 2 ** 20, name, t, gbs))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print("wrote", args.json)


if __name__ == "__main__":
    main()
