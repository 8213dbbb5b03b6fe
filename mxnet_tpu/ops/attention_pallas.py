"""Flash attention as a Pallas TPU kernel.

The reference has no fused attention op at all (SURVEY §5: attention
only via composed ops) — this is a TPU-first addition: a blockwise
online-softmax kernel that never materializes the (T, T) score matrix.
Scores are computed tile-by-tile in VMEM, carried through running
max / denominator f32 scratch, and the MXU sees two matmuls per tile
(QKᵀ and PV) with fp32 accumulation.

Returns the normalized output and the per-row logsumexp, so callers
can merge partial results exactly — `parallel.ring_attention` can use
the same online-softmax identity to combine per-device blocks, making
this kernel the local engine of the sequence-parallel path.

Backward runs as recompute in plain jax under `custom_vjp` (no stored
score matrix reaches the residuals; XLA re-fuses the recomputation); a
hand-written Pallas backward is a further optimization, not a semantic
change.

``interpret=None`` resolves from the devices present: compiled through
Mosaic on a TPU, the Pallas interpreter elsewhere (slow — the CPU test
harness); pass ``interpret=False`` to refuse the interpreter outright.
"""
from __future__ import annotations

import functools

__all__ = ["flash_attention", "flash_attention_with_lse"]


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
            *, blk_k, blk_q, scale, causal, n_kblk):
    """Grid (bh, qi, ki): one K/V tile per step, accumulators persist in
    VMEM scratch across the (sequential, innermost) ki axis."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: tiles fully above the diagonal contribute nothing
    q_last = (qi + 1) * blk_q - 1
    live = (ki * blk_k <= q_last) if causal else True

    @pl.when(live)
    def _tile():
        q = q_ref[0].astype(jnp.float32) * scale       # (blk_q, D)
        k_blk = k_ref[0].astype(jnp.float32)           # (blk_k, D)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * blk_q + lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            k_pos = ki * blk_k + lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        m = m_ref[...]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        pv = jax.lax.dot_general(p, v_blk, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(ki == n_kblk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l_safe)).astype(jnp.float32)


def _flash_fwd_raw(q, k, v, scale, causal, blk_q, blk_k, interpret):
    """q, k, v: (B, H, T, D) -> (o (B,H,T,D), lse (B,H,T))."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    blk_q = min(blk_q, Tq)
    blk_k = min(blk_k, Tk)
    if Tq % blk_q or Tk % blk_k:
        raise ValueError("flash_attention: seq lengths (%d, %d) must be "
                         "multiples of the block sizes (%d, %d)"
                         % (Tq, Tk, blk_q, blk_k))
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)
    n_kblk = Tk // blk_k

    grid = (B * H, Tq // blk_q, n_kblk)
    kern = functools.partial(_kernel, blk_k=blk_k, blk_q=blk_q,
                             scale=scale, causal=causal, n_kblk=n_kblk)
    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, blk_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, blk_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            # lse rides as (..., blk_q, 1): the trailing singleton keeps
            # the block within TPU tile rules (last dim == array dim)
            pl.BlockSpec((1, blk_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return o.reshape(B, H, Tq, D), lse.reshape(B, H, Tq)


def _ref_attention_lse(q, k, v, scale, causal):
    """Reference (f32, unblocked) producing (o, lse) — the backward
    recompute target whose vjp defines the kernel's gradients."""
    import jax.numpy as jnp

    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p / l, v.astype(jnp.float32))
    return o, (m + jnp.log(l))[..., 0]


@functools.lru_cache(maxsize=None)
def _flash_vjp_fn(scale, causal, blk_q, blk_k, interpret):
    """One custom_vjp function per static config — repeat calls hit
    jax's function-identity dispatch cache instead of retracing."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def fwd(qt, kt, vt):
        return _flash_fwd_raw(qt, kt, vt, scale, causal, blk_q, blk_k,
                              interpret)

    def fwd_fwd(qt, kt, vt):
        return fwd(qt, kt, vt), (qt, kt, vt)

    def fwd_bwd(res, g):
        qt, kt, vt = res
        g_o, g_lse = g
        _, vjp = jax.vjp(
            lambda a, b, c: _ref_attention_lse(a, b, c, scale, causal),
            qt, kt, vt)
        dq, dk, dv = vjp((g_o.astype(jnp.float32),
                          g_lse.astype(jnp.float32)))
        return (dq.astype(qt.dtype), dk.astype(kt.dtype),
                dv.astype(vt.dtype))

    fwd.defvjp(fwd_fwd, fwd_bwd)
    return fwd


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             blk_q=128, blk_k=128, interpret=None):
    """(B, T, H, D) attention via the Pallas kernel.

    Returns (out (B,T,H,D), lse (B,T,H)) — lse is the per-row softmax
    log-normalizer, the quantity needed to merge partial attention
    blocks exactly (ring/sequence parallelism)."""
    import jax
    import jax.numpy as jnp

    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5

    qt = jnp.swapaxes(q, 1, 2)   # (B, H, T, D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    fwd = _flash_vjp_fn(scale, bool(causal), int(blk_q), int(blk_k),
                        bool(interpret))
    o, lse = fwd(qt, kt, vt)
    return jnp.swapaxes(o, 1, 2), jnp.swapaxes(lse, 1, 2)


def flash_attention(q, k, v, causal=False, scale=None, blk_q=128,
                    blk_k=128, interpret=None):
    """(B, T, H, D) -> (B, T, H, D) fused attention output."""
    o, _lse = flash_attention_with_lse(q, k, v, causal=causal,
                                       scale=scale, blk_q=blk_q,
                                       blk_k=blk_k, interpret=interpret)
    return o
