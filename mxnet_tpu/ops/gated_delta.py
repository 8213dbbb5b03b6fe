"""The gated delta rule, as the serving path runs it: the recurrence
itself for one token a slot, and its chunkwise form for a chunk of many.
Two kinds of decay: one a key channel (Kimi Delta Attention,
arXiv:2510.26692, section 3) or one scalar a head (Gated DeltaNet, Yang,
Kautz and Hatamizadeh, arXiv:2412.06464).

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

whose inverse does not depend on the state, so it is taken once for
all sub-chunks together (by blocks, doubling their size six times: no
loop over rows; :func:`_unit_lower_inverse`), and only the state is
carried from one sub-chunk to the next (a ``lax.scan`` of ``T / SUB``
steps of small matrix products).  ``exp(G[t] - G[i])`` is formed as it
stands for ``i <= t``: its exponent is never positive, so a channel
that decays by ``exp(-5)`` a token (KDA's bound) for 64 tokens
underflows to an exact 0 where the factored form ``exp(G[t]) *
exp(-G[i])`` would overflow; a decay a head has no bound below and is
formed the same way.  With one decay a head ``A`` is ``(K K^T) * exp(G_t
- G_i)``, a matrix product and a (C, C) mask a head: no decay a channel
is ever formed.

``beta`` may lie in [0, 2] (negative eigenvalues, Grazzi et al.,
arXiv:2411.12537): ``I - beta k k^T`` then has eigenvalues in [-1, 1]
and still moves no state away from zero.

A position at or past ``valid`` (padding after a prompt's last tokens,
an idle slot) is given ``g = 0`` and ``beta = 0``, which leaves the
state as it was.  Everything here is float32 with ``highest``
precision: the state is the model's memory of the whole sequence.
Traced under a named scope the caller gives (``kda.scan`` by default).
"""
from __future__ import annotations

__all__ = ["gated_delta_step", "gated_delta_chunk", "SUB"]

SUB = 64


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


def _unit_lower_inverse(low):
    """``(I + low)^-1`` for strictly lower-triangular ``low``
    (..., n, n), by blocks: with the diagonal blocks of size ``s``
    inverted, two neighbours ``A`` (upper) and ``B`` (lower) and the
    block ``C`` of ``low`` between them give the block of size ``2 s``,

        [[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]],

    so ``log2(n)`` rounds of two batched products each, no loop over
    rows.  Every product is a block of the true inverse, whose entries
    stay of size 1 to 4 (``|k| = 1``, ``beta <= 2``, a decay ``<= 1``:
    the entries of ``(I + low)^-1 Diag(beta)`` are ``beta_t k_t^T``
    times a product of transitions ``I - beta k k^T`` of norm at most 1
    times ``k_i beta_i``), so nothing large cancels.  (The Neumann
    series ``(I - L)(I + L^2)(I + L^4)...`` is as exact on paper and
    not in float32: where the keys of a sub-chunk resemble each other, a
    stream with a common component under a SiLU, its powers reach 1e9
    and more before they cancel, and the state came out wrong by its own
    size on some weights.)"""
    import jax.numpy as jnp
    from jax import lax

    given = low.shape[-1]
    n = 1 << max(given - 1, 0).bit_length()
    lead = low.shape[:-2]
    if n != given:                       # more rows of the identity
        low = jnp.pad(low, [(0, 0)] * len(lead) + [(0, n - given)] * 2)

    def mm(a, b):
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)

    inv, s = jnp.ones(lead + (n, 1, 1), low.dtype), 1
    while s < n:
        m = n // (2 * s)
        # C of the i-th pair: rows of its lower half, columns of its upper
        c = jnp.moveaxis(jnp.diagonal(
            low.reshape(lead + (m, 2, s, m, 2, s))[..., :, 1, :, :, 0, :],
            axis1=-4, axis2=-2), -1, len(lead))          # (.., m, s, s)
        a, b = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        inv = jnp.concatenate([
            jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
            jnp.concatenate([-mm(mm(b, c), a), b], axis=-1)], axis=-2)
        s *= 2
    return inv[..., 0, :given, :given]


def gated_delta_chunk(q, k, v, g, beta, state, valid=None,
                      scope="kda.scan"):
    """``T`` tokens a row (padded here to whole sub-chunks).  ``q``,
    ``k`` (B, T, H, d_k); ``g`` (B, T, H, d_k), a decay a key channel,
    or (B, T, H), one a head; ``v`` (B, T, H, d_v); ``beta`` (B, T, H);
    ``state`` (B, H, d_k, d_v) float32; ``valid`` (B,) int32, the
    leading positions of each row that count (all of them without it).
    Returns ``(o (B, T, H, d_v) float32, state)``.  Traced under the
    named scope ``scope``: the decay sums, the block inverse and the
    scan over sub-chunks."""
    import jax

    with jax.named_scope(scope):
        return _gated_delta_chunk(q, k, v, g, beta, state, valid)


def _gated_delta_chunk(q, k, v, g, beta, state, valid):
    import jax.numpy as jnp
    from jax import lax

    f32, hi = jnp.float32, lax.Precision.HIGHEST
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(SUB, T)
    N, given = -(-T // C), T
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    per_head = g.ndim == 3
    if valid is not None:
        live = jnp.arange(T, dtype=jnp.int32)[None, :] < valid[:, None]
        g = jnp.where(live[:, :, None] if per_head else live[:, :, None, None],
                      g, 0.0)
        beta = jnp.where(live[:, :, None], beta, 0.0)
    if N * C != T:                       # zeros: g = 0 and beta = 0
        T = N * C
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, T - given)]
                                    + [(0, 0)] * (a.ndim - 2))
                            for a in (q, k, v, g, beta))

    def split(a):                        # (B, T, H, x) -> (B, H, N, C, x)
        return a.reshape((B, N, C, H) + a.shape[3:]).transpose(
            (0, 3, 1, 2) + tuple(range(4, a.ndim + 1)))

    q, k, v, g = split(q), split(k), split(v), split(g)
    beta = split(beta)                                   # (B, H, N, C)
    G = jnp.cumsum(g, axis=3)                # (B, H, N, C[, dk])
    keep = jnp.tril(jnp.ones((C, C), bool))
    if per_head:
        # decay from position i to position t of a sub-chunk, i <= t, one
        # (C, C) mask a head beside two matrix products
        decay = jnp.where(keep, jnp.exp(jnp.minimum(
            G[..., :, None] - G[..., None, :], 0.0)), 0.0)   # (.., C, C)
        k_t = jnp.swapaxes(k, -1, -2)
        kk = jnp.matmul(k, k_t, precision=hi) * decay
        qk = jnp.matmul(q, k_t, precision=hi) * decay
        G = G[..., None]                     # the same for every channel
    else:
        # decay from position i to position t of a sub-chunk, i <= t
        diff = G[:, :, :, :, None, :] - G[:, :, :, None, :, :]
        decay = jnp.where(keep[:, :, None],
                          jnp.exp(jnp.minimum(diff, 0.0)),
                          0.0)                           # (.., C, C, dk)
        # sums of products, not matrix products: written so, the
        # compiler forms the decay inside the reduction and never
        # stores it
        k_from = k[:, :, :, None, :, :] * decay
        kk = jnp.sum(k[:, :, :, :, None, :] * k_from, axis=-1)  # (.., C, C)
        qk = jnp.sum(q[:, :, :, :, None, :] * k_from, axis=-1)
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    inv = _unit_lower_inverse(
        jnp.where(strict, kk, 0.0) * beta[..., None])    # (.., C, C)
    inv = inv * beta[..., None, :]                       # T Diag(beta)
    k_in = k * jnp.exp(G)                # what a key reads of S_0
    q_in = q * jnp.exp(G)
    w = jnp.matmul(inv, k_in, precision=hi)              # (.., C, dk)
    u = jnp.matmul(inv, v, precision=hi)                 # (.., C, dv)
    # what is left of a key's write at the sub-chunk's end
    k_out = k * jnp.exp(G[:, :, :, -1:, :] - G)
    end = jnp.exp(G[:, :, :, -1, :])                     # (B, H, N, dk | 1)

    def sub_chunk(s, xs):
        w_n, u_n, qin_n, qk_n, kout_n, end_n = xs
        d = u_n - jnp.matmul(w_n, s, precision=hi)       # (B, H, C, dv)
        o = jnp.matmul(qin_n, s, precision=hi) \
            + jnp.matmul(qk_n, d, precision=hi)
        s = s * end_n[..., None] + jnp.einsum(
            "bhtc,bhtv->bhcv", kout_n, d, precision=hi)
        return s, o

    def by_sub(a):                       # N leads, for the scan
        return jnp.moveaxis(a, 2, 0)

    state, o = lax.scan(sub_chunk, state.astype(f32), tuple(
        by_sub(a) for a in (w, u, q_in, qk, k_out, end)))
    # (N, B, H, C, dv) -> (B, T, H, dv)
    return o.transpose(1, 0, 3, 2, 4).reshape((B, T, H, dv))[:, :given], \
        state
