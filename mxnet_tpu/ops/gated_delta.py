"""The gated delta rule, as the serving path runs it: the recurrence
itself for one token a slot, and its chunkwise form for a chunk of many
as one Pallas TPU kernel.  Two kinds of decay: one a key channel (Kimi
Delta Attention, arXiv:2510.26692, section 3) or one scalar a head
(Gated DeltaNet, Yang, Kautz and Hatamizadeh, arXiv:2412.06464).

A head keeps a state ``S`` of ``(d_k, d_v)`` in float32.  A token with
query ``q``, key ``k`` (both ``d_k``), value ``v`` (``d_v``), log-decay
``g <= 0`` (a key channel, or the head's one) and write strength
``beta`` moves it by

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(with one decay a head ``Diag(exp g_t)`` is the scalar ``exp g_t``, which
commutes with the rest).  :func:`gated_delta_step` is that line.
:func:`gated_delta_chunk` gives the same outputs and final state for
``T`` tokens without a step a token: in sub-chunks of ``SUB`` (64)
positions, with ``G`` the running sum of ``g`` inside a sub-chunk, the
changes ``d_t = beta_t (v_t - S_{t-1}^T (exp g_t . k_t))`` of one
sub-chunk solve one unit lower-triangular system

    (I + Diag(beta) A) D = Diag(beta) (V - (K . exp G) S_0),
    A[t, i] = sum_c k[t, c] k[i, c] exp(G[t, c] - G[i, c])   (i < t)

whose inverse does not depend on the state, and only the state is
carried from one sub-chunk to the next.

**The kernel** (:func:`_chunk_kernel`).  A grid step holds a few heads
of one slot (``HEADS`` at most, a divisor of the heads) and their
states, in the chip's fast memory, for up to ``SEGMENT`` (512)
positions of the chunk; a longer chunk is walked a segment a grid step
along a last grid axis, the state kept in fast memory from one to the
next, so that what a step holds does not grow with the chunk.  It reads
the operands as the mixers lay them out, ``(B, T, H, d)`` seen as ``(B,
T, H d)``, a head a block of lanes, and writes the outputs the same
way, so nothing is transposed around it.  It walks a segment's
sub-chunks twice: first every sub-chunk's ``A`` and ``Q K^T`` with the
decay applied, the inverse, ``w = T (K . exp G)`` and ``u = T V`` (``T
= (I + Diag(beta) A)^-1 Diag(beta)``), what is left of a key at the
sub-chunk's end and the decay of the state, none of which needs the
state; then the state from sub-chunk to sub-chunk, three products a
step: ``D = u - w S``, ``O = (Q . exp G) S + (Q K^T) D`` and ``S = exp
G_end S + (K . exp(G_end - G))^T D``.  Only the outputs and the final
state leave the kernel.  Widths that are not whole lane tiles (Olmo's
96 and 192) are zero-padded before it: a zero key channel and a zero
row of the state change nothing.

The inverse is taken by blocks, doubling their size: with ``D_s`` the
block-diagonal matrix of the inverted diagonal blocks of size ``s`` and
``C_s`` the blocks of ``A`` just below them inside blocks of ``2 s``,
``D_2s = D_s - D_s C_s D_s`` (``[[a, 0], [c, b]]^-1 = [[a^-1, 0], [-b^-1
c a^-1, b^-1]]``): six rounds for 64 rows, the first elementwise and
two matrix products each after it, and no loop over rows.  Every
product is a block of the true inverse, whose entries stay of size 1 to 4 (``|k| = 1``, ``beta <= 2``, a decay ``<=
1``), so nothing large cancels.  The Neumann series ``(I - L)(I +
L^2)(I + L^4)...`` is as exact on paper and not in float32: where the
keys of a sub-chunk resemble each other (a stream with a common
component under a SiLU) its powers reach 1e9 and more before they
cancel, and the state came out wrong by its own size on some weights.

**The decay.**  With one decay a head (``g`` of shape (B, T, H)) ``A``
is ``(K K^T) * exp(min(G_t - G_i, 0))``: one matrix product and one
(C, C) mask a head.  With one a key channel the sum over channels is
written as matrix products by blocks of ``BLOCK`` (16) rows of a
sub-chunk.  A diagonal block stays elementwise, ``sum_c k_t,c k_i,c
exp(G_t,c - G_i,c)``, whose exponent is never positive.  The rows of
block ``a`` against all earlier positions are one product, the decay
factored through the last position ``r`` of block ``a - 1``:
``(k_t . exp(G_t - G_r)) . (k_i . exp(G_r - G_i))^T``.  Both factors
lie in (0, 1], so nothing overflows whatever the decay; where one
underflows, the pair's own decay ``exp(G_t - G_i)``, their product, is
smaller still, and the sum loses nothing float32 could hold.  (KDA's
decay is bounded below, ``-5`` a token, which keeps the first factor
at least ``exp(-80)``, a normal float32; no bound is needed for the
argument.)

``beta`` may lie in [0, 2] (negative eigenvalues, Grazzi et al.,
arXiv:2411.12537): ``I - beta k k^T`` then has eigenvalues in [-1, 1]
and still moves no state away from zero.

A position at or past ``valid`` (padding after a prompt's last tokens,
an idle slot) is given ``g = 0`` and ``beta = 0``, which leaves the
state as it was, to the bit where nothing is valid.  Everything here
is float32 and every product is taken at ``HIGHEST`` precision: the
state is the model's memory of the whole sequence.  Traced under a
named scope the caller gives (``kda.scan`` by default), the layout
changes around the kernel included.  Off the TPU the kernel runs in
the TPU interpreter of Pallas (``paged_rows_pallas._interpret``).
"""
from __future__ import annotations

__all__ = ["gated_delta_step", "gated_delta_chunk", "SUB", "BLOCK",
           "HEADS", "SEGMENT"]

SUB = 64
# rows of a sub-chunk a product of the per-channel decay sums covers
BLOCK = 16
# most heads a grid step of the kernel holds
HEADS = 4
# most positions a grid step of the kernel holds (whole sub-chunks)
SEGMENT = 8 * SUB

_LANES = 128


def gated_delta_step(q, k, v, g, beta, state, scope="kda.scan"):
    """One token a row.  ``q``, ``k`` (..., d_k); ``g`` (..., d_k), a
    decay a key channel, or (...,), one a head; ``v`` (..., d_v);
    ``beta`` (...,); ``state`` (..., d_k, d_v) float32.  Returns ``(o
    (..., d_v), state)``.  Traced under the named scope ``scope``."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(scope):
        f32 = jnp.float32
        q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
        if g.ndim < k.ndim:              # one decay a head
            g = g[..., None]
        s = state * jnp.exp(g)[..., None]
        delta = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
        s = s + k[..., None] * delta[..., None, :]
        return jnp.sum(s * q[..., None], axis=-2), s


def gated_delta_chunk(q, k, v, g, beta, state, valid=None,
                      scope="kda.scan"):
    """``T`` tokens a row.  ``q``, ``k`` (B, T, H, d_k); ``g`` (B, T, H,
    d_k), a decay a key channel, or (B, T, H), one a head; ``v`` (B, T,
    H, d_v); ``beta`` (B, T, H); ``state`` (B, H, d_k, d_v) float32;
    ``valid`` (B,) int32, the leading positions of each row that count
    (all of them without it).  Returns ``(o (B, T, H, d_v) float32,
    state)``.  Traced under the named scope ``scope``: the kernel and
    the layout changes around it."""
    import jax

    with jax.named_scope(scope):
        return _gated_delta_chunk(q, k, v, g, beta, state, valid)


def _gated_delta_chunk(q, k, v, g, beta, state, valid):
    import jax.numpy as jnp

    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    # sub-chunks of SUB positions, fewer for a short dispatch (a power
    # of two and whole blocks, for the inverse and the decay sums); a
    # chunk longer than a SEGMENT is walked a SEGMENT a grid step
    C = min(SUB, max(BLOCK, 1 << max(T - 1, 0).bit_length()))
    seg = min(SEGMENT, -(-T // C) * C)
    Tp = -(-T // seg) * seg
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    per_head = g.ndim == 3
    if valid is None:
        valid = jnp.full((B,), T, jnp.int32)
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < valid[:, None]
    dkp, dvp = (-(-d // _LANES) * _LANES for d in (dk, dv))

    def lanes(a, width):                 # (B, T, H, x) -> (B, Tp, H width)
        if a.shape[1:] != (Tp, H, width):
            # zeros past T and past each width's lane tiles
            a = jnp.pad(a, [(0, 0), (0, Tp - T), (0, 0),
                            (0, width - a.shape[-1])])
        return a.reshape((B, Tp, H * width))

    def rows(a):                         # (B, T, H) -> (B, H, Tp / C, C)
        a = jnp.pad(jnp.where(live[:, :, None], a, 0.0),
                    [(0, 0), (0, Tp - T), (0, 0)])
        return a.reshape((B, Tp // C, C, H)).transpose((0, 3, 1, 2))

    if per_head:
        # the running sum inside each sub-chunk
        decay = jnp.cumsum(rows(g), axis=-1)
    else:                                # masked past valid in the kernel
        decay = lanes(g, dkp)
    if state.shape[2:] != (dkp, dvp):
        state = jnp.pad(state, [(0, 0), (0, 0), (0, dkp - dk), (0, dvp - dv)])
    o, state = _chunk_kernel(
        valid.astype(jnp.int32), lanes(q, dkp), lanes(k, dkp), lanes(v, dvp),
        rows(beta), decay, state.astype(f32), H, C, seg)
    o = o.reshape((B, Tp, H, dvp))
    return o[:, :T, :, :dv], state[:, :, :dk, :dv]


def _chunk_kernel(valid, q, k, v, beta, decay, state, H, C, seg):
    """The chunkwise rule over operands as the mixers lay them out, each
    head a block of lanes: ``q``, ``k`` (B, Tp, H dk); ``v`` (B, Tp, H
    dv); ``beta`` (B, H, N, C), each sub-chunk a row; ``decay`` (B, H,
    N, C), a head's running sums inside each sub-chunk, or (B, Tp, H
    dk), the log-decays a key channel; ``state`` (B, H, dk, dv); dk and
    dv whole lane tiles, ``Tp = N C`` a multiple of ``seg``, the
    positions a grid step walks; ``valid`` (B,) int32, the positions of
    a row that count (``beta`` and a head's decay already zero past
    them).  Returns ``(o (B, Tp, H dv), state)``, float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .paged_rows_pallas import _interpret

    f32 = jnp.float32
    B, Tp, _ = q.shape
    dk, dv = q.shape[2] // H, v.shape[2] // H
    N = seg // C                         # sub-chunks a grid step
    hb = max(d for d in range(1, min(HEADS, H) + 1) if H % d == 0)
    per_head = decay.ndim == 4
    NN, NT, TN = ((2,), (1,)), ((2,), (2,)), ((1,), (1,))

    def mm(a, b, contract):              # batched over the leading axis
        return lax.dot_general(a, b, (contract, ((0,), (0,))),
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=f32)

    def kernel(valid_ref, q_ref, k_ref, v_ref, b_ref, g_ref, s_ref, o_ref,
               so_ref, w_s, u_s, qin_s, qk_s, kout_s, end_s, st_s):
        first = pl.program_id(2) * seg   # the step's first position
        count = valid_ref[pl.program_id(0)]
        last = pl.program_id(2) == pl.num_programs(2) - 1
        row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
        ones = jnp.broadcast_to(jnp.where(row >= col, 1.0, 0.0), (hb, C, C))
        # the blocks of size 2^s just below the diagonal ones, a round of
        # the inverse each
        belows = [((row >> (s + 1)) == (col >> (s + 1)))
                  & ((row >> s) & 1 == 1) & ((col >> s) & 1 == 0)
                  for s in range(C.bit_length() - 1)]

        def column(r):                   # (hb, 1, C) -> (hb, C, 1)
            return jnp.sum(jnp.where(row == col, r, 0.0), -1, keepdims=True)

        def inverse(a):
            """``(I + a)^-1`` for strictly lower ``a`` (hb, C, C), by
            blocks doubling their size."""
            d = jnp.where(row == col, 1.0, 0.0) - jnp.where(belows[0], a, 0.0)
            for below in belows[1:]:
                d = d - mm(d, mm(jnp.where(below, a, 0.0), d, NN), NN)
            return d

        def sums(q_, k_, G):
            """``Q K^T`` and ``K K^T`` (hb, C, C) with a decay a key
            channel, on and below the diagonal: the diagonal blocks of
            ``BLOCK`` rows elementwise, a column of each block at a
            time; then the rows of each later block against all earlier
            positions as one product, the decay factored through the
            last position before the block."""
            start = (row // BLOCK) * BLOCK
            kk = qk = jnp.zeros((hb, C, C), f32)

            def spread(a, j):            # row j of each block, its rows
                blocks = (hb * (C // BLOCK), BLOCK, dk)
                return jnp.broadcast_to(a.reshape(blocks)[:, j:j + 1],
                                        blocks).reshape((hb, C, dk))

            for j in range(BLOCK):
                kj = spread(k_, j) * jnp.exp(jnp.minimum(G - spread(G, j),
                                                         0.0))
                at = col == start + j
                kk = jnp.where(at, jnp.sum(k_ * kj, -1, keepdims=True), kk)
                qk = jnp.where(at, jnp.sum(q_ * kj, -1, keepdims=True), qk)
            for a in range(1, C // BLOCK):
                e = G[:, a * BLOCK - 1:a * BLOCK]
                fall = jnp.exp(jnp.minimum(G - e, 0.0))
                rise = k_ * jnp.exp(jnp.minimum(e - G, 0.0))
                strip = (row // BLOCK == a) & (col < a * BLOCK)
                kk = jnp.where(strip, mm(k_ * fall, rise, NT), kk)
                qk = jnp.where(strip, mm(q_ * fall, rise, NT), qk)
            return qk, kk

        def prepare(n, carry):
            """What sub-chunk ``n`` needs that does not depend on the
            state, the ``hb`` heads batched."""
            r = pl.ds(pl.multiple_of(n * C, C), C)

            def heads(ref, width):       # (C, hb width) -> (hb, C, width)
                return jnp.stack([ref[r, j * width:(j + 1) * width]
                                  for j in range(hb)])

            q_, k_ = heads(q_ref, dk), heads(k_ref, dk)
            bt = column(b_ref[:, pl.ds(n, 1)])              # (hb, C, 1)
            if per_head:
                Gr = g_ref[:, pl.ds(n, 1)]                   # (hb, 1, C)
                G = column(Gr)                               # (hb, C, 1)
                dec = jnp.where(row >= col,
                                jnp.exp(jnp.minimum(G - Gr, 0.0)), 0.0)
                kk = mm(k_, k_, NT) * dec
                qk = mm(q_, k_, NT) * dec
            else:
                live = first + n * C \
                    + lax.broadcasted_iota(jnp.int32, (C, 1), 0) < count
                G = mm(ones, jnp.where(live, heads(g_ref, dk), 0.0), NN)
                qk, kk = sums(q_, k_, G)
            # the last row, by a sum: a row sliced at its place would
            # keep its place in the value's tiles, and the chip's
            # compiler fails on a store of it (jax 0.9, v5e)
            end = jnp.sum(jnp.where(
                lax.broadcasted_iota(jnp.int32, G.shape, 1) == C - 1, G, 0.0),
                1, keepdims=True)                            # (hb, 1, 1|dk)
            t = inverse(jnp.where(row > col, kk, 0.0) * bt)
            into = jnp.exp(G)
            w_s[:, r] = mm(t, bt * k_ * into, NN)
            u_s[:, r] = mm(t, bt * heads(v_ref, dv), NN)
            qin_s[:, r] = q_ * into
            qk_s[:, r] = jnp.where(row >= col, qk, 0.0)
            kout_s[:, r] = k_ * jnp.exp(end - G)
            end_s[n] = jnp.broadcast_to(jnp.exp(end), (hb, 1, dk))
            return carry

        def carry_state(n, st):                              # (hb, dv, dk)
            r = pl.ds(pl.multiple_of(n * C, C), C)
            d = u_s[:, r] - mm(w_s[:, r], st, NT)            # (hb, C, dv)
            o = mm(qin_s[:, r], st, NT) + mm(qk_s[:, r], d, NN)
            for j in range(hb):
                o_ref[r, j * dv:(j + 1) * dv] = o[j]
            return st * end_s[n] + mm(d, kout_s[:, r], TN)

        @pl.when(pl.program_id(2) == 0)
        def _():
            st_s[...] = jnp.swapaxes(s_ref[...], 1, 2)

        lax.fori_loop(0, N, prepare, 0)
        st_s[...] = lax.fori_loop(0, N, carry_state, st_s[...])

        @pl.when(last)
        def _():
            so_ref[...] = jnp.swapaxes(st_s[...], 1, 2)

    def lane_block(width):
        return pl.BlockSpec((None, seg, hb * width),
                            lambda b, h, s, *_: (b, s, h))

    def head_block(*shape):
        return pl.BlockSpec((None, hb) + shape,
                            lambda b, h, s, *_: (b, h) + (0,) * len(shape))

    # the state stays in the step's fast memory across a row's segments:
    # read at the first, written at the last
    state_spec = head_block(dk, dv)
    rows_spec = pl.BlockSpec((None, hb, N, C), lambda b, h, s, *_: (b, h, s, 0))
    decay_spec = rows_spec if per_head else lane_block(dk)
    # two buffers of each block, the scratch, and room for the
    # temporaries of a sub-chunk: none of it grows with the chunk
    blocks = seg * (3 * dk + 2 * dv) + 2 * seg + 2 * dk * dv
    scratch = seg * (3 * dk + dv + _LANES) + 2 * dk * dv
    vmem = 4 * hb * (2 * blocks + scratch) + (24 << 20)
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((B, Tp, H * dv), f32),
                   jax.ShapeDtypeStruct((B, H, dk, dv), f32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // hb, Tp // seg),
            in_specs=[lane_block(dk), lane_block(dk), lane_block(dv),
                      rows_spec, decay_spec, state_spec],
            out_specs=[lane_block(dv), state_spec],
            scratch_shapes=[pltpu.VMEM((hb, seg, x), f32)
                            for x in (dk, dv, dk, C, dk)]
            + [pltpu.VMEM((N, hb, 1, dk), f32),
               pltpu.VMEM((hb, dv, dk), f32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        name="gated_delta_chunk", interpret=_interpret())(
            valid, q, k, v, beta, decay, state)
