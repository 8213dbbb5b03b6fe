#!/usr/bin/env python
"""Time the core of absorbed latent attention (scores, softmax and
context of ``HybridDecoderLM._mla`` over a slot's cached rows) in a loop
of its own, on the chip: the whole-``S`` products against the blocks of
``ops.attention_rows.CACHE_BLOCK_ROWS`` rows up to ``start``, at the
shapes the serving cells dispatch.

    python tools/bench_mla_chunk.py [--shapes giga_chunk,...] \
        [--blocks 256,512,1024] [--starts 0,512,1024,3584]

Prints one JSON line a (shape, start): milliseconds a layer of each
form (the median of ``--reps`` timed calls of a program of ``--layers``
layers of distinct rows and queries), the blocked form at each
``--blocks`` size one pass (a carried context, rescaled a block: what
the model runs) and two passes (maximum and denominator first, the
scores computed twice, no rescale), and the largest distance of each
from the whole form's context.  A step shape (many slots) draws its
``start`` a slot between a quarter of ``--starts``' value and the value
itself.  Refuses to run without an accelerator: a time from a CPU is no
device number.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: slots, query positions a slot, heads, rows a slot, latent, rope
SHAPES = {
    "giga_chunk": (1, 512, 64, 6144, 512, 64),
    "ling_chunk": (1, 512, 32, 9216, 512, 64),
    "giga_verify": (32, 2, 64, 6144, 512, 64),
    "ling_step": (32, 1, 32, 9216, 512, 64),
    "tiny": (2, 16, 4, 64, 32, 8),
}


def whole(q, rows, start, scale, s_own, own, keep):
    """``_mla``'s whole-``S`` form: every row a slot holds multiplied,
    those at or above ``start`` masked."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention_rows import _softmax_pair

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)

    ok = (jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :]
          < start[:, None])[:, None, None, :]
    s_old = dot("bchw,bsw->bhcs", q, rows) * scale
    p_old, p_own = _softmax_pair(s_old, s_own, ok, True, rows.dtype)
    return dot("bhcs,bsw->bchw", p_old, rows)[..., :keep] \
        + dot("bhcs,bsw->bchw", p_own, own)[..., :keep]


def two_pass(q, rows, start, scale, s_own, own, keep):
    """The blocked form without a carried context: the maximum and the
    denominator over the blocks first, then the scores once more and
    the context, each block's probabilities final as they are made."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention_rows import cache_block_rows

    f32, act = jnp.float32, rows.dtype
    B, S, lanes = rows.shape
    K = cache_block_rows(S)

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, preferred_element_type=f32)

    def scores(j):
        first = j * K
        at = jnp.minimum(first, S - K)
        blk = jax.lax.dynamic_slice(rows, (0, at, 0), (B, K, lanes))
        idx = at + jnp.arange(K, dtype=jnp.int32)
        ok = (idx >= first)[None, :] & (idx[None, :] < start[:, None])
        return jnp.where(ok[:, None, None, :],
                         dot("bchw,bsw->bhcs", q, blk) * scale, -1e30), blk

    def norm(j, carry):
        m, den = carry
        s, _blk = scores(j)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        return m_new, den * jnp.exp(m - m_new) \
            + jnp.exp(s - m_new).sum(-1, keepdims=True)

    m = s_own.max(-1, keepdims=True)
    den = jnp.exp(s_own - m).sum(-1, keepdims=True)
    blocks = (jnp.max(start) + K - 1) // K
    m, den = jax.lax.fori_loop(0, blocks, norm, (m, den))

    def context(j, ctx):
        s, blk = scores(j)
        return ctx + dot("bhcs,bsw->bhcw", jnp.exp(s - m).astype(act),
                         blk)[..., :keep]

    ctx = dot("bhcs,bsw->bhcw", jnp.exp(s_own - m).astype(act),
              own)[..., :keep]
    ctx = jax.lax.fori_loop(0, blocks, context, ctx)
    return jnp.swapaxes(ctx / den, 1, 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="giga_chunk,ling_chunk,giga_verify")
    ap.add_argument("--blocks", default="256,512,1024,2048")
    ap.add_argument("--starts", default="0,512,1024,3584")
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

    from mxnet_tpu.gluon.model_zoo.language import hybrid_decoder
    from mxnet_tpu.ops import attention_rows

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        sys.exit("bench_mla_chunk: no accelerator; a CPU time is no "
                 "device number")
    bf = jnp.float32 if dev.platform == "cpu" else jnp.bfloat16

    def layers_of(core, scale, keep):
        def run(qs, rows, owns, start):
            C = owns.shape[2]
            causal = jnp.tril(jnp.ones((C, C), bool))[None, None]
            out = 0.0
            for q, r, own in zip(qs, rows, owns):
                s_own = jnp.where(causal, jnp.einsum(
                    "bchw,bsw->bhcs", q[..., :own.shape[-1]], own,
                    preferred_element_type=jnp.float32) * scale, -1e30)
                out = out + core(q, r, start, scale, s_own, own, keep)
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

    for name in args.shapes.split(","):
        B, C, H, S, dl, dr = SHAPES[name]
        lanes = -(-(dl + dr) // 128) * 128
        scale = (128 + dr) ** -0.5
        key = jax.random.PRNGKey(args.seed)
        key, a, b, c = jax.random.split(key, 4)
        L = args.layers
        qs = jnp.pad(jax.random.normal(a, (L, B, C, H, dl + dr), bf),
                     [(0, 0)] * 4 + [(0, lanes - dl - dr)])
        rows = jnp.pad(jax.random.normal(b, (L, B, S, dl + dr), bf),
                       [(0, 0)] * 3 + [(0, lanes - dl - dr)])
        owns = jax.random.normal(c, (L, B, C, dl + dr), bf)
        forms = {"whole": layers_of(whole, scale, dl)}
        for st in (int(s) for s in args.starts.split(",")):
            st = min(st, S - C)
            start = np.full((B,), st, np.int32)
            if B > 1:
                start = np.random.default_rng(args.seed).integers(
                    st // 4, st + 1, B).astype(np.int32)
            start = jnp.asarray(start)
            line = {"shape": name, "device": dev.device_kind,
                    "start_max": int(start.max()),
                    "start_mean": float(start.mean())}
            want = forms["whole"](qs, rows, owns, start)
            line["whole_ms_per_layer"] = timed(
                forms["whole"], qs, rows, owns, start)
            kept = attention_rows.CACHE_BLOCK_ROWS
            for K in (int(k) for k in args.blocks.split(",")):
                attention_rows.CACHE_BLOCK_ROWS = K
                for tag, core in (("blocks", hybrid_decoder._attend_in_blocks),
                                  ("two_pass", two_pass)):
                    # (traced at its first call, under this K)
                    f = forms.setdefault((tag, K),
                                         layers_of(core, scale, dl))
                    got = f(qs, rows, owns, start)
                    line["%s%d_ms_per_layer" % (tag, K)] = timed(
                        f, qs, rows, owns, start)
                    line["%s%d_gap_max" % (tag, K)] = float(jnp.abs(
                        got - want).max())
            attention_rows.CACHE_BLOCK_ROWS = kept
            line["out_abs_max"] = float(jnp.abs(want).max())
            print(json.dumps(line), flush=True)
        del qs, rows, owns


if __name__ == "__main__":
    main()
