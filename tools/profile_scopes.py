"""Where a small decode engine's device time goes, by named scope: the
walk-through of docs/observability.md ("Where the device's time goes").

    python tools/profile_scopes.py [--out DIR] [--steps 8]

Builds a small expert decoder (``MoEDecoderLM`` at widths of whole lane
tiles, so that its expert layers run the grouped kernels on a TPU) behind
a ``PagedGenerationEngine``, warms its two programs up, and then

1. records one prefill chunk and one decode step under
   ``jax.profiler`` with the Python tracer off (``DIR/record``) and keeps
   the device's planes of it as ``DIR/small_engine.xplane.pb`` (what
   ``tests/data/small_engine.xplane.pb`` is, read by
   ``tests/test_device_scopes.py``);
2. runs ``--steps`` decode steps between ``mx.profiler.start()`` and
   ``stop()`` and prints ``mx.profiler.dumps()``, whose "Device time by
   scope" table is read from that session's trace.

On a CPU the table says that the trace holds no device plane.
"""
import argparse
import glob
import os
import shutil
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import generate  # noqa: E402


def small_engine():
    """Two layers of 4 experts (top 2) of width 128 on a stream of 128,
    4 query and 2 key/value heads of 32; 4 slots of 64 positions in
    pages of 16, prefill chunks of 16; bfloat16 under ``bf16_mixed``."""
    from mxnet_tpu.gluon.model_zoo.language import MoEDecoderLM

    mx.random.seed(0)
    net = MoEDecoderLM(
        vocab_size=256, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
        d_head=32, n_experts=4, top_k=2, d_expert=128, max_len=64,
        dtype="bfloat16")
    net.initialize(mx.init.Normal(0.02))
    return generate.PagedGenerationEngine(
        net, slots=4, cache_len=64, page_size=16, prefill_chunk=16,
        prefix_share=False, dtype_policy="bf16_mixed",
        sampling=generate.SamplingConfig(greedy=True))


def keep_device_planes(src, dst):
    """Copy the trace ``src`` to ``dst`` without the host's planes (an
    ``XSpace`` is its planes one after another, field 1, each behind
    its length, and a plane's name its field 2: a plane is kept or
    dropped whole)."""
    from mxnet_tpu.profiler import _fields

    def varint(n):
        out = bytearray()
        while n >= 0x80:
            out.append(n & 0x7F | 0x80)
            n >>= 7
        return bytes(out + bytes([n]))

    with open(src, "rb") as f:
        space = memoryview(f.read())
    with open(dst, "wb") as f:
        for number, plane in _fields(space):
            names = [bytes(v) for n, v in _fields(plane) if n == 2]
            if number == 1 and names and names[0].startswith(
                    b"/device:TPU:"):
                f.write(b"\x0a" + varint(len(plane)) + bytes(plane))


def prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 256, n).astype(np.int32)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/device_scopes")
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args(argv)
    import jax

    eng = small_engine()
    eng.admit(prompt(12, 0))               # a chunk, and its first token
    eng.admit(prompt(9, 1))
    for _ in range(3):                      # a step, launched ahead
        eng.decode_step()
    eng.drain()

    record = os.path.join(args.out, "record")
    shutil.rmtree(record, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(record, profiler_options=opts)
    eng.admit(prompt(10, 2))                # one chunk
    eng.decode_step()                       # one step
    eng.drain()
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(record, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    kept = os.path.join(args.out, "small_engine.xplane.pb")
    keep_device_planes(found[0], kept)
    print("recorded %s (%d bytes), %d device operations" % (
        kept, os.path.getsize(kept), len(mx.profiler.device_table(kept))))

    mx.profiler.set_config(
        filename=os.path.join(args.out, "profile.json"))
    mx.profiler.start()
    for _ in range(args.steps):
        eng.decode_step()
    eng.drain()
    mx.profiler.stop()
    print(mx.profiler.dumps())
    return 0


if __name__ == "__main__":
    sys.exit(main())
