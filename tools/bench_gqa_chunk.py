#!/usr/bin/env python
"""Time the core of a prefill chunk's grouped-query attention (scores,
softmax and context of a full ``"gqa"`` layer of ``HybridDecoderLM``
over a slot's gathered ``[K | V]`` rows) in a loop of its own, on the
chip: all the rows a slot holds (``ops.attention_rows
.chunk_attention_rows``) against blocks of
``ops.attention_rows.CACHE_BLOCK_ROWS`` rows up to ``start``
(``chunk_attention_blocks``), at the widths of the serving cells that
run such layers.

    python tools/bench_gqa_chunk.py [--shapes kexaone_chunk,...] \
        [--blocks 256,512,1024] [--starts 0,512,1536,3584,8192]

Prints one JSON line a (shape, start): milliseconds a layer of each
form (the median of ``--reps`` timed calls of a program of ``--layers``
layers of distinct rows and queries), the blocked form at each
``--blocks`` size with each block's K and V sliced and transposed as it
is read (what the model runs) and with the rows transposed whole once
before the loop (``once``), and the largest distance of each from the
whole form's context.  A ``start`` past the slot's capacity less the
chunk is cut to it.  Refuses to run without an accelerator: a time from
a CPU is no device number.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: slots, query positions a slot, query heads, key/value heads,
# head size, rows a slot
SHAPES = {
    "kexaone_chunk": (1, 512, 64, 8, 128, 9216),
    "olmo_chunk": (1, 512, 30, 30, 128, 5120),
    "tiny": (2, 16, 4, 2, 16, 64),
}


def whole(q, k, v, rows, start, H, Hkv):
    """What a chunk ran before: every row a slot holds multiplied, those
    at or above ``start`` masked, the K and V halves sliced off the
    gathered rows."""
    from mxnet_tpu.ops.attention_rows import chunk_attention_rows

    w = k.shape[2]
    return chunk_attention_rows(q, k, v, rows[..., :w], rows[..., w:2 * w],
                                start, H, Hkv)


def once(q, k, v, rows, start, H, Hkv):
    """The blocked form with the cached K and V transposed whole to
    (B, Hkv, dh, S) before the loop, each block a slice of the
    transposed rows."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention_rows import _dot, cache_block_rows

    B, C, _ = q.shape
    S = rows.shape[1]
    G, dh = H // Hkv, q.shape[2] // H
    w = Hkv * dh
    K = cache_block_rows(S)
    scale = dh ** -0.5

    def heads(a):
        return jnp.swapaxes(a, 1, 2).reshape((B, Hkv, dh, a.shape[1]))

    over_d = (((3,), (2,)), ((0, 1), (0, 1)))
    over_s = (((3,), (3,)), ((0, 1), (0, 1)))
    qh = q.reshape((B, C, Hkv, G, dh)).transpose(
        (0, 2, 3, 1, 4)).reshape((B, Hkv, G * C, dh))
    c_idx = jnp.arange(C, dtype=jnp.int32)
    causal = jnp.tile(c_idx[:, None] >= c_idx[None, :], (G, 1))
    s = jnp.where(causal, _dot(qh, heads(k), over_d) * scale, -1e30)
    m = s.max(-1, keepdims=True)
    e = jnp.exp(s - m)
    den = e.sum(-1, keepdims=True)
    ctx = _dot(e.astype(v.dtype), heads(v), over_s)
    kt, vt = heads(rows[..., :w]), heads(rows[..., w:2 * w])

    def block(j, carry):
        m, den, ctx = carry
        first = j * K
        at = jnp.minimum(first, S - K)
        kb = jax.lax.dynamic_slice(kt, (0, 0, 0, at), (B, Hkv, dh, K))
        vb = jax.lax.dynamic_slice(vt, (0, 0, 0, at), (B, Hkv, dh, K))
        s = _dot(qh, kb, over_d) * scale
        idx = at + jnp.arange(K, dtype=jnp.int32)
        ok = (idx >= first)[None, :] & (idx[None, :] < start[:, None])
        s = jnp.where(ok[:, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        shrink = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)
        den = den * shrink + e.sum(-1, keepdims=True)
        ctx = ctx * shrink + _dot(e.astype(rows.dtype), vb, over_s)
        return m_new, den, ctx

    blocks = (jnp.max(start) + K - 1) // K
    _m, den, ctx = jax.lax.fori_loop(0, blocks, block, (m, den, ctx))
    out = (ctx / den).reshape((B, Hkv, G, C, dh)).transpose((0, 3, 1, 2, 4))
    return out.reshape((B, C, H * dh)).astype(q.dtype)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="kexaone_chunk,olmo_chunk")
    ap.add_argument("--blocks", default="256,512,1024")
    ap.add_argument("--starts", default="0,512,1536,3584,8192")
    ap.add_argument("--once", type=int, default=1,
                    help="also time the rows transposed whole once")
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

    from mxnet_tpu.ops import attention_rows

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        sys.exit("bench_gqa_chunk: no accelerator; a CPU time is no "
                 "device number")
    bf = jnp.float32 if dev.platform == "cpu" else jnp.bfloat16

    def layers_of(core, H, Hkv):
        def run(qs, ks, vs, rows, start):
            out = 0.0
            for q, k, v, r in zip(qs, ks, vs, rows):
                out = out + core(q, k, v, r, start, H, Hkv).astype(
                    jnp.float32)
            return out
        return jax.jit(run)

    def timed(f, *a):
        jax.block_until_ready(f(*a))
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(f(*a))
            times.append(time.perf_counter() - t)
        return float(np.median(times)) * 1e3 / args.layers

    def blocked(q, k, v, rows, start, H, Hkv):
        return attention_rows.chunk_attention_blocks(q, k, v, rows, start,
                                                     H, Hkv)

    for name in args.shapes.split(","):
        B, C, H, Hkv, dh, S = SHAPES[name]
        w = Hkv * dh
        key = jax.random.PRNGKey(args.seed)
        key, a, b, c, d = jax.random.split(key, 5)
        L = args.layers
        qs = jax.random.normal(a, (L, B, C, H * dh), bf)
        ks = jax.random.normal(b, (L, B, C, w), bf)
        vs = jax.random.normal(c, (L, B, C, w), bf)
        rows = jax.random.normal(d, (L, B, S, 2 * w), bf)
        forms = {"whole": layers_of(whole, H, Hkv)}
        for st in (int(s) for s in args.starts.split(",")):
            st = min(st, S - C)
            start = jnp.full((B,), st, jnp.int32)
            line = {"shape": name, "device": dev.device_kind, "start": st,
                    "rows": S, "heads": [H, Hkv, dh]}
            want = forms["whole"](qs, ks, vs, rows, start)
            line["whole_ms_per_layer"] = timed(forms["whole"], qs, ks, vs,
                                               rows, start)
            kept = attention_rows.CACHE_BLOCK_ROWS
            for K in (int(k) for k in args.blocks.split(",")):
                attention_rows.CACHE_BLOCK_ROWS = K
                cores = [("blocks", blocked)] + (
                    [("once", once)] if args.once else [])
                for tag, core in cores:
                    # (traced at its first call, under this K)
                    f = forms.setdefault((tag, K), layers_of(core, H, Hkv))
                    got = f(qs, ks, vs, rows, start)
                    line["%s%d_ms_per_layer" % (tag, K)] = timed(
                        f, qs, ks, vs, rows, start)
                    line["%s%d_gap_max" % (tag, K)] = float(jnp.abs(
                        got - want).max())
            attention_rows.CACHE_BLOCK_ROWS = kept
            line["out_abs_max"] = float(jnp.abs(want).max())
            print(json.dumps(line), flush=True)
        del qs, ks, vs, rows


if __name__ == "__main__":
    main()
