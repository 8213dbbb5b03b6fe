#!/usr/bin/env python
"""Time the chunkwise gated delta rule of one linear-attention layer
(``ops.gated_delta.gated_delta_chunk``, the Pallas kernel a prefill
chunk of a KDA or GDN layer of ``HybridDecoderLM`` runs) in a loop of
its own, on the chip, at the widths of the serving cells that run it.

    python tools/bench_delta_chunk.py [--shapes ling_chunk,olmo_chunk] \
        [--heads 2,4,8] [--layers 6] [--reps 10]

(``ling_long`` and ``olmo_long`` are chunks of 4096 positions, eight of
the kernel's segments.)

Prints one JSON line a (shape, ``--heads`` entry): milliseconds a layer
(the median of ``--reps`` timed calls of a program of ``--layers``
layers of distinct inputs, each a chunk of one slot), the share of the
least time that ``gdn_chunk_flops`` and ``gdn_chunk_bytes`` of
``benchmark/lib/reference/olmo_hybrid.py`` give a layer at the shape's
widths (at Ling's the per-channel decay sums are not counted, so the
share is a floor there), and the largest distance of the outputs and
the final state from the recurrence a token (``gated_delta_step`` in a
``lax.scan``).  ``--heads`` sets ``gated_delta.HEADS``, the most heads
a grid step of the kernel holds.  Refuses to run without an
accelerator: a time from a CPU is no device number.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name: slots, positions, heads, d_k, d_v, a decay a head, the bound
# below of a decay a key channel (KDA's, the range the inputs are drawn
# in); the long shapes walk the kernel's segments of 512
SHAPES = {
    "ling_chunk": (1, 512, 32, 128, 128, False, -5.0),
    "olmo_chunk": (1, 512, 30, 96, 192, True, None),
    "ling_long": (1, 4096, 32, 128, 128, False, -5.0),
    "olmo_long": (1, 4096, 30, 96, 192, True, None),
    "tiny": (2, 80, 3, 16, 24, False, -5.0),
}


def inputs(key, L, B, T, H, dk, dv, per_head, lower):
    """``L`` layers' operands, drawn as the mixers make them: unit keys,
    queries of norm ``d_k^-1/2``, a decay a key channel in [lower, 0]
    with ``beta`` in [0, 1], or one a head from none to ``exp(-12)`` a
    token with ``beta`` in [0, 2]."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (L, B, T, H, dk))
    k = jax.random.normal(ks[1], (L, B, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (L, B, T, H, dv))
    if per_head:
        g = -jnp.exp(jax.random.uniform(ks[3], (L, B, T, H),
                                        minval=-6.9, maxval=2.48))
        beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (L, B, T, H)))
    else:
        g = lower * jax.nn.sigmoid(
            3 * jax.random.normal(ks[3], (L, B, T, H, dk)))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (L, B, T, H)))
    s0 = jax.random.normal(ks[5], (L, B, H, dk, dv))
    return q, k, v, g, beta, s0


def recurrence(q, k, v, g, beta, s0):
    """The rule a token at a time: the outputs and the final state."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.gated_delta import gated_delta_step

    def step(s, x):
        o, s = gated_delta_step(*x, s)
        return s, o

    s, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def least_seconds(B, T, H, dk, dv, peaks):
    """The least time of one layer's chunk by the benchmark's own count
    (``gdn_chunk_flops`` and ``gdn_chunk_bytes`` at these widths)."""
    from benchmark.lib.reference import olmo_hybrid

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        cfg = json.load(f)
    cfg.update(linear_num_key_heads=H, linear_key_head_dim=dk,
               linear_value_head_dim=dv, layers_held=[0])
    return B * max(olmo_hybrid.gdn_chunk_flops(cfg, T)
                   / peaks["bf16_flops_per_s"],
                   olmo_hybrid.gdn_chunk_bytes(cfg, T)
                   / peaks["hbm_bytes_per_s"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="ling_chunk,olmo_chunk")
    ap.add_argument("--heads", default="4",
                    help="most heads a grid step holds, a comma list")
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run without an accelerator, to try the tool; "
                         "its times mean nothing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.peaks import peaks_for
    from mxnet_tpu.ops import gated_delta

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        sys.exit("bench_delta_chunk: no accelerator; a CPU time is no "
                 "device number")
    peaks = None if dev.platform == "cpu" else peaks_for(dev.device_kind)

    def timed(f, *a):
        jax.block_until_ready(f(*a))
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(f(*a))
            times.append(time.perf_counter() - t)
        return float(np.median(times)) * 1e3 / args.layers

    kept = gated_delta.HEADS
    for name in args.shapes.split(","):
        B, T, H, dk, dv, per_head, lower = SHAPES[name]
        ops = inputs(jax.random.PRNGKey(args.seed), args.layers, B, T, H,
                     dk, dv, per_head, lower)
        valid = jnp.full((B,), T, jnp.int32)
        want = jax.jit(recurrence)(*(a[0] for a in ops))
        for heads in (int(h) for h in args.heads.split(",")):
            gated_delta.HEADS = heads

            def layers(q, k, v, g, beta, s0):
                out = 0.0
                for l in range(args.layers):
                    o, s = gated_delta.gated_delta_chunk(
                        q[l], k[l], v[l], g[l], beta[l], s0[l], valid)
                    out = out + o.sum() + s.sum()
                return out

            def first(q, k, v, g, beta, s0):
                return gated_delta.gated_delta_chunk(
                    q[0], k[0], v[0], g[0], beta[0], s0[0], valid)

            o, s = jax.jit(first)(*ops)
            ms = timed(jax.jit(layers), *ops)
            line = {"shape": name, "device": dev.device_kind,
                    "heads_per_step": heads, "positions": T,
                    "widths": [H, dk, dv], "ms_per_layer": ms,
                    "out_gap_max": float(jnp.abs(o - want[0]).max()),
                    "state_gap_max": float(jnp.abs(s - want[1]).max()),
                    "out_abs_max": float(jnp.abs(want[0]).max())}
            if peaks is not None:
                line["roofline_share_pct"] = 100.0 * least_seconds(
                    B, T, H, dk, dv, peaks) / (ms / 1e3)
            print(json.dumps(line), flush=True)
        del ops
    gated_delta.HEADS = kept


if __name__ == "__main__":
    main()
