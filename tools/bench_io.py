"""ImageRecordIter throughput microbench.

Generates a synthetic ImageNet-like .rec (224x224 JPEGs) and measures
end-to-end pipeline throughput (read -> JPEG decode -> augment -> batch
-> device upload).  The number to beat is the training consumption rate
from bench.py (ResNet-50 img/s per chip): the pipeline must exceed it or
the chip starves.

Host pipeline and end-to-end rates: not measured on the current
machine.  Decode scales with preprocess_threads (PIL/numpy and the native
decoder release the GIL), and the batch uploads as uint8 (0.15 MB/img).

Usage: python tools/bench_io.py [n_images] [threads]
"""
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import recordio  # noqa: E402


def ledger_records(host_rate, e2e_rate, n_images, threads):
    """perf_ledger record(s) for one run: the host pipeline rate and
    the end-to-end (incl. device upload) rate — both must clear the
    training consumption rate or the chip starves.  The tier-1 schema
    guard calls this with canned rates."""
    from mxnet_tpu import perf_ledger

    fields = {"n_images": n_images, "threads": threads}
    return [
        perf_ledger.make_record("io_pipeline_host_img_s", host_rate,
                                "images/sec", **fields),
        perf_ledger.make_record("io_pipeline_e2e_img_s", e2e_rate,
                                "images/sec", **fields),
    ]


def make_rec(path, n, size=224):
    rng = np.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
    t0 = time.time()
    for i in range(n):
        img = rng.randint(0, 255, (size, size, 3), np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 1000), i, 0), img, quality=90))
    w.close()
    print("wrote %d records in %.1fs" % (n, time.time() - t0))


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
    threads = int(sys.argv[2]) if len(sys.argv) > 2 else os.cpu_count()
    with tempfile.TemporaryDirectory() as d:
        base = os.path.join(d, "synth")
        make_rec(base, n)
        it = mx.io.ImageRecordIter(
            path_imgrec=base + ".rec", path_imgidx=base + ".idx",
            batch_size=128, data_shape=(3, 224, 224), shuffle=True,
            rand_crop=True, rand_mirror=True, resize=256,
            mean_r=123.68, mean_g=116.78, mean_b=103.94,
            preprocess_threads=threads, prefetch_buffer=8)
        # warm epoch to populate thread-local readers + compile normalize
        for _ in it:
            pass
        it.reset()

        # (a) host pipeline rate: read -> decode -> augment -> batch,
        # futures drained without device work
        t0 = time.time()
        imgs = 0
        for _ in range(len(it._order) // 128):
            fut = it._pending.popleft()
            it._submit()
            data, _, pad = fut.result()
            imgs += data.shape[0] - pad
        host_rate = imgs / (time.time() - t0)
        print("host decode+augment+batch: %.0f img/s "
              "(%d imgs, %d threads, bs128)" % (host_rate, imgs, threads))

        # (b) end-to-end including uint8 device upload + fused
        # on-device normalize (blocks on the last batch only, like a
        # training consumer whose step consumes the previous upload)
        it.reset()
        t0 = time.time()
        imgs = 0
        last = None
        for batch in it:
            last = batch.data[0]
            imgs += batch.data[0].shape[0] - batch.pad
        last.asnumpy()  # drain the async queue
        e2e_rate = imgs / (time.time() - t0)
        print("end-to-end w/ device upload: %.0f img/s" % e2e_rate)

        from mxnet_tpu import perf_ledger

        for rec in ledger_records(round(host_rate, 1),
                                  round(e2e_rate, 1), n, threads):
            perf_ledger.emit(rec)


if __name__ == "__main__":
    main()
