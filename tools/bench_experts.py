#!/usr/bin/env python
"""Time one expert layer's held experts in a loop of their own, on the
chip: ``parallel.moe.routed_experts`` (rows grouped by expert) against
the dense product it replaced (every held expert multiplies every row),
at the shapes the serving cells run it in.

    python tools/bench_experts.py [--shapes ling_step,...] [--tile 16,32]

Prints one JSON line a shape: milliseconds a layer for each form (the
median of ``--reps`` timed calls of a program of ``--layers`` layers of
distinct weights, the rows chained through them), the largest distance
between the two forms' results on the first layer alone (chained, a
rounding in one layer flips a near-tie of the next layer's router), and
the experts some row chose.  Refuses to
run without an accelerator: a time from a CPU is no device number.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: rows, d_model, d_expert, experts of the router, held, first, top_k
SHAPES = {
    "ling_step": (32, 2560, 768, 512, 128, 0, 8),
    "ling_chunk": (512, 2560, 768, 512, 128, 0, 8),
    "sdar_pass": (128, 2048, 768, 128, 128, 0, 8),
    "sdar_chunk": (256, 2048, 768, 128, 128, 0, 8),
    "giga_step": (64, 7168, 2048, 256, 16, 0, 8),
    "giga_chunk": (512, 7168, 2048, 256, 16, 0, 8),
    "tiny": (24, 128, 128, 16, 4, 4, 2),
}


def dense_experts(x, router_w, w_gate, w_up, w_down, top_k, d_expert,
                  first=0, norm_topk=True, select=None):
    """Every held expert multiplies every row; a row's weight for an
    expert it was not routed to is zero: ``routed_experts`` before it
    grouped its rows, with its signature (``tests/
    test_grouped_experts.py`` takes it for its oracle)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_exp = router_w.shape[-1]
    T, F = x.shape[0], int(d_expert)
    held = w_down.shape[0] // F
    f32 = jnp.float32
    logits = jnp.dot(x, router_w, preferred_element_type=f32)
    if select is not None:
        top_p, top_i = select(logits, top_k)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = lax.top_k(probs, top_k)
        if norm_topk:
            top_p = top_p / top_p.sum(-1, keepdims=True)
    chosen = top_i[:, :, None] == jnp.arange(n_exp, dtype=top_i.dtype)
    counts = chosen.sum((0, 1)).astype(jnp.int32)
    combine = jnp.where(chosen, top_p[:, :, None], 0.0).sum(1)
    mine = lax.dynamic_slice_in_dim(combine, first, held, axis=1)
    g = jnp.dot(x, w_gate, preferred_element_type=f32)
    u = jnp.dot(x, w_up, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).reshape((T, held, F)) * mine[:, :, None]
    out = jnp.dot(h.reshape((T, held * F)).astype(x.dtype), w_down,
                  preferred_element_type=f32)
    return out.astype(x.dtype), counts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(
        s for s in SHAPES if s != "tiny"))
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tile", default="",
                    help="row-tile heights to try besides the rule's")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="",
                    help="a directory: trace three calls of the grouped "
                         "form a shape there and print its device "
                         "operations")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel import moe

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        sys.exit("bench_experts: no accelerator; a CPU time is no "
                 "device number")

    def layers_of(fn, F, first, k):
        def run(x, weights):
            loads = []
            for rw, wg, wu, wd in weights:
                y, counts = fn(x, rw, wg, wu, wd, k, F, first=first)
                x = (x + y).astype(x.dtype)
                loads.append(counts)
            return x, jnp.stack(loads)
        return jax.jit(run)

    def timed(f, *a):
        jax.block_until_ready(f(*a))
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(f(*a))
            times.append(time.perf_counter() - t)
        return float(np.median(times)) * 1e3 / args.layers

    for name in args.shapes.split(","):
        T, D, F, E, held, first, k = SHAPES[name]
        key = jax.random.PRNGKey(args.seed)
        bf = jnp.bfloat16
        weights = []
        for _ in range(args.layers):
            key, a, b, c, d = jax.random.split(key, 5)
            weights.append((
                (jax.random.normal(a, (D, E), jnp.float32) * 0.02).astype(bf),
                (jax.random.normal(b, (D, held * F), jnp.float32)
                 * 0.02).astype(bf),
                (jax.random.normal(c, (D, held * F), jnp.float32)
                 * 0.02).astype(bf),
                (jax.random.normal(d, (held * F, D), jnp.float32)
                 * 0.02).astype(bf)))
        x = jax.random.normal(key, (T, D), jnp.float32).astype(bf)
        line = {"shape": name, "device": dev.device_kind,
                "rows": T, "tile": moe.expert_row_tile(T * k, E)}
        dense = layers_of(dense_experts, F, first, k)
        _chained, loads = dense(x, weights)
        want = jax.jit(lambda x, w: dense_experts(
            x, *w, k, F, first=first)[0])(x, weights[0])
        line["dense_ms_per_layer"] = timed(dense, x, weights)
        loads = np.asarray(loads)[:, first:first + held]
        line["held_touched_per_layer"] = float((loads > 0).sum(1).mean())
        line["held_pairs_per_layer"] = float(loads.sum(1).mean())
        line["held_load_max"] = int(loads.max())
        rule = moe.expert_row_tile
        for tile in [None] + [int(t) for t in args.tile.split(",") if t]:
            if tile is not None:
                moe.expert_row_tile = lambda _p, _e, tile=tile: tile
            grouped = layers_of(moe.routed_experts, F, first, k)
            got = jax.jit(lambda x, w: moe.routed_experts(
                x, *w, k, F, first=first)[0])(x, weights[0])
            tag = "grouped" if tile is None else "grouped_tile%d" % tile
            line[tag + "_ms_per_layer"] = timed(grouped, x, weights)
            line[tag + "_gap_max"] = float(jnp.abs(
                got.astype(jnp.float32) - want.astype(jnp.float32)).max())
            moe.expert_row_tile = rule
        line["out_abs_max"] = float(jnp.abs(want.astype(jnp.float32)).max())
        if args.trace:
            from benchmark.lib import xplane

            where = os.path.join(args.trace, name)
            with jax.profiler.trace(where):
                for _ in range(3):
                    jax.block_until_ready(grouped(x, weights))
            line["grouped_device_ops_3_calls"] = xplane.top_ops(
                xplane.load(xplane.find_trace(where)), n=30)
        print(json.dumps(line), flush=True)
        del weights, x, want


if __name__ == "__main__":
    main()
