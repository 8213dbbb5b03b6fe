"""Attention of a chunk of queries against K/V rows as the paged pool
holds them: one row a position, every head side by side,
``(B, S, kv_heads * d_head)``.

The decode engine (``generate.PagedGenerationEngine``) gathers such rows
a layer at a time for a model that does not declare ``layer_caches``.  A
head size under the TPU's 128 lanes must never become the minor-most
dimension of anything as large as the cache: splitting the rows into
``(B, heads, S, d_head)`` makes the compiler re-tile all of them, every
layer, every step.  So for the decode and verify shapes (few query rows
a slot) the rows are read as they lie:

* the queries are expanded **block-diagonally**: row ``(c, h)`` of
  ``(B, C * heads, kv_heads * d_head)`` holds head ``h``'s ``d_head``
  values at the lanes of its key/value head and zeros elsewhere;
* the scores are ``q_bd @ k_rows^T``, contracted over the full row (the
  zeros add exactly 0), the output ``p @ v_rows``, of which each head
  keeps its own lanes.

Both are plain matrix products; the arithmetic is ``kv_heads`` times the
head-split products', which the MXU has to spare while the cache's
bytes set the pace.  With many query rows a slot (a prefill chunk) the
arithmetic outgrows the bytes saved, and the head-split products are
the faster, on rows transposed whole to ``(B, kv_heads * d_head, S)``
(lane-dense on both sides) so that the heads split off a major
dimension: :func:`attends_in` says which form a dispatch's shape gets,
and nothing else decides it.

Either way the operands stay in the dtype they arrive in, the products
accumulate in float32, and the softmax over the cached and the chunk's
own positions together is float32.

**How much of the cache a dispatch attends** is a second rule on the
same static shape, :func:`cached_rows_in`, for a model that attends its
own rows (``HybridDecoderLM``'s absorbed latent attention and its full
``"gqa"`` layers; the products above attend all ``S`` rows in every
shape, and a layer that declares ``"attended": "whole"`` keeps them).  A
slot's rows are gathered at its whole capacity ``S`` and those at or
above ``start`` are masked, so products over all of them cost the
capacity whatever the sequence has written.  A **prefill chunk** (many
query positions of one slot, compute-bound on them) attends in
**blocks** of :data:`CACHE_BLOCK_ROWS` rows, a loop whose trip count the
program computes from ``start``: it multiplies the blocks the sequence
has reached and never reads the rest (``HybridDecoderLM._mla`` for
latent rows, :func:`chunk_attention_blocks` for grouped-query ``[K |
V]`` rows).  A **decode or verify step** (one or a few query positions
of every slot) keeps the whole-``S`` products: over 32 slots the bound
is the longest sequence's, the products are bound by the rows' bytes
and small, and a loop in every layer of every step costs its edges (no
operation is scheduled across them, weights are not fetched ahead over
them) more than the masked rows it would skip.  A layer whose engine
hands a step its pages instead (``"attended": "pages"``:
``HybridDecoderLM``'s full ``"gqa"`` layers) reads each slot's
**written pages in place**, :func:`paged_attention_rows` over the
Pallas kernel of ``ops.paged_rows_pallas``: no gather, no row past a
slot's ``start`` copied, the cost the sum of the slots' lengths and not
the longest's; its chunk is gathered and attended in blocks.
:func:`attended_cache_rows` is the count a dispatch reads and
multiplies, which the engine writes on its ``engine.prefill`` and
``engine.decode`` spans.

**Rows kept in a ring.**  A sliding-window layer attends the last
``window`` positions (itself included) and nothing before them, so what
it keeps of a sequence is a ring a slot, ``(B, R, lanes)``, and no page:
position ``p`` lies in ring row ``p mod R`` and the positions are never
stored, :func:`ring_positions` computes them from ``start``.  A
dispatch **attends before it writes**: :func:`window_attention_rows`
attends the ring as the last dispatch left it, each row by the position
it holds, and the chunk's own rows under the band, in the two forms
above; :func:`ring_write` then puts the chunk's rows that count in
their places.  A row written for a draft that was rejected lies at or
above the next ``start``; by position it reads as ``R`` earlier, which
no query's band reaches in a ring of :func:`ring_rows` rows, and the
next dispatch overwrites it: nothing is copied to roll back.  A chunk
longer than the ring leaves its last ``R`` rows.  A windowed layer
multiplies its ring's rows in every shape, and that is what
:func:`attended_cache_rows` counts for it.
"""
from __future__ import annotations

__all__ = ["chunk_attention_rows", "chunk_attention_blocks",
           "paged_attention_rows", "attends_in",
           "cached_rows_in", "cache_block_rows", "attended_cache_rows",
           "ring_rows",
           "ring_positions", "window_attention_rows", "ring_write"]

# query rows a slot (chunk positions x query heads) up to which the
# block-diagonal products beat the head-split ones.  Timed on a TPU v5e
# at OPT-1.3B's widths, 8 slots of 1024 positions (PERF.md, PR 32): a
# decode step (32 rows a slot) 14.8 against 26.5 ms a program, a verify
# step of 4 tokens (128) 16.2 against 21.6, a prefill chunk of 32 (1024)
# 7.3 against 4.8
BLOCK_DIAGONAL_MAX_QUERY_ROWS = 256


def attends_in(chunk, n_heads):
    """The form :func:`chunk_attention_rows` attends in for ``chunk``
    query positions a slot of ``n_heads`` query heads: ``"rows"`` (the
    block-diagonal products on the rows as they lie) or ``"heads"`` (the
    head-split products)."""
    return "rows" if chunk * n_heads <= BLOCK_DIAGONAL_MAX_QUERY_ROWS \
        else "heads"


# rows of cache a block of the blocked form holds: whole pages and whole
# sublane tiles.  Timed on a TPU v5e in a loop of its own
# (tools/bench_mla_chunk.py; PERF.md, PR 38), one layer's scores,
# softmax and context of a chunk of 512 at GigaChat's 64 heads against
# 6144 rows, milliseconds at start 0 | 512 | 1536 | 3584: blocks of 256
# 0.96 | 1.23 | 1.73 | 2.78, of 512 0.77 | 1.11 | 1.78 | 3.11, of 1024
# 0.98 | 1.69 | 2.42 | 3.82, of 2048 0.95 | 2.35 | 2.36 | 3.74, all the
# rows 5.55-5.57; at Ling's 32 heads against 9216 rows 512 is the
# fastest at every start (0.58 at 512, 1.28 at 3584; all rows 3.88).
# Half the serving cells' chunks start at or under 512.  The grouped-query
# blocks (chunk_attention_blocks; tools/bench_gqa_chunk.py, PERF.md), a
# chunk of 512 at start 0 | 512 | 1536 | 3584 | 8192: K-EXAONE's 64 / 8
# heads of 128 against 9216 rows, blocks of 256 0.48 | 0.53 | 0.73 |
# 1.14 | 1.98, of 512 0.52 | 0.56 | 0.71 | 1.07 | 1.75, of 1024 0.48 |
# 1.01 | 1.58 | 2.71 | 4.94, all the rows 5.76-5.80; Olmo-Hybrid's 30 /
# 30 against 5120 rows (start 4608 for 8192), of 256 0.76 | 0.82 | 0.98
# | 1.27 | 1.41, of 512 0.76 | 0.80 | 0.92 | 1.13 | 1.24, of 1024 0.76 |
# 0.85 | 0.93 | 1.12 | 1.19, all the rows 2.21-2.23: one size serves both
CACHE_BLOCK_ROWS = 512

# query positions a slot from which a dispatch is a chunk and attends
# its cache in blocks; under it (a decode step's 1, a verify step's
# 1 + spec_k) the whole-S products stay.  The same timing, a layer of a
# (32, 2) verify step at GigaChat's widths with the longest slot at
# 501 | 1503 | 3506 rows: all the rows 0.88, blocks of 512 0.98 | 1.10 |
# 1.33; a (32, 1) step at Ling's: 1.19 against 1.31 | 1.43 | 1.65
BLOCKED_CACHE_MIN_QUERY_POSITIONS = 16


def cached_rows_in(chunk, attended=None):
    """How a dispatch of ``chunk`` query positions a slot attends the
    rows its model caches: ``"blocks"`` (blocks of
    :data:`CACHE_BLOCK_ROWS` rows up to the longest ``start`` of the
    dispatch, a loop inside the one program), ``"whole"`` (all ``S``
    rows a slot holds, masked) or ``"pages"`` (each slot's written
    pages read where they lie in the pool, :func:`paged_attention_rows`).
    ``attended`` is what the layer declares (``layer_caches``): None for
    a model that attends the rows it is handed itself
    (``HybridDecoderLM._mla``: blocks in a chunk, whole in a step),
    ``"whole"`` for a layer that multiplies all a slot holds in every
    shape, ``"pages"`` for a layer that a step reads in place and a
    chunk, gathered, in blocks (:func:`chunk_attention_blocks`).  The
    static shape and the declaration decide and nothing else."""
    if attended == "whole":
        return "whole"
    if chunk >= BLOCKED_CACHE_MIN_QUERY_POSITIONS:
        return "blocks"
    return "pages" if attended == "pages" else "whole"


def cache_block_rows(held):
    """Rows a block of the blocked form holds when a slot holds
    ``held``: :data:`CACHE_BLOCK_ROWS`, or all of a smaller cache."""
    return min(CACHE_BLOCK_ROWS, int(held))


def attended_cache_rows(chunk, start, held, rule=None, page_size=16):
    """Cached rows a slot a dispatch of ``chunk`` query positions reads
    and multiplies in one layer of ``held`` rows a slot, by
    :func:`cached_rows_in` on ``chunk`` and the layer's declared
    ``rule`` (``"whole"`` for a layer that multiplies all it holds in
    every shape, attention through :func:`chunk_attention_rows` and a
    windowed layer, whose ``held`` is its ring's rows; ``"pages"`` for
    one a step reads in place and a chunk in blocks).  ``start`` is the
    rows each slot of the dispatch has written (a sequence), or the
    longest's: ``held`` under ``"whole"``; whole blocks up to the
    longest under ``"blocks"`` (a chunk, whether the layer says None or
    ``"pages"``); under ``"pages"`` each slot's whole pages of
    ``page_size`` rows up to its own ``start``, summed over the slots
    and divided by their number (what the kernel copies; not the
    longest's)."""
    import numpy as np

    starts = [int(s) for s in np.atleast_1d(start)]
    form = cached_rows_in(chunk, rule)
    if form == "whole":
        return int(held)
    if form == "pages":
        page = int(page_size)
        return sum(min(-(-s // page) * page, int(held))
                   for s in starts) / max(len(starts), 1)
    k = cache_block_rows(held)
    return min(-(-max(starts, default=0) // k) * k, int(held))


def _softmax_pair(s_cache, s_chunk, cache_ok, chunk_ok, dtype):
    """The softmax over the cached and the chunk's own positions
    together, float32, of scores given apart: ``s_cache`` (..., Q, S)
    and ``s_chunk`` (..., Q, C) with the positions a query may attend
    (``cache_ok``, ``chunk_ok``, broadcastable).  Returns the two parts
    of the probabilities in ``dtype``.  Every query attends at least
    itself, so the shared maximum is finite and a masked score's
    ``exp`` is exactly 0."""
    import jax.numpy as jnp

    neg = jnp.float32(-1e30)
    s_cache = jnp.where(cache_ok, s_cache, neg)
    s_chunk = jnp.where(chunk_ok, s_chunk, neg)
    m = jnp.maximum(s_cache.max(-1, keepdims=True),
                    s_chunk.max(-1, keepdims=True))
    e_cache, e_chunk = jnp.exp(s_cache - m), jnp.exp(s_chunk - m)
    denom = e_cache.sum(-1, keepdims=True) + e_chunk.sum(-1, keepdims=True)
    return (e_cache / denom).astype(dtype), (e_chunk / denom).astype(dtype)


def _dot(a, b, dims):
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def chunk_attention_rows(q, k_chunk, v_chunk, k_rows, v_rows, start,
                         n_heads, n_kv_heads=None, scale=None,
                         block_length=1):
    """Causal (or block-causal) attention of a chunk against its
    sequence's cached rows.

    ``q`` (B, C, n_heads * d_head): the chunk's queries, at positions
    ``start_b .. start_b + C - 1``; ``k_chunk`` / ``v_chunk``
    (B, C, n_kv_heads * d_head): its own keys and values (not in the
    cache yet); ``k_rows`` / ``v_rows`` (B, S, n_kv_heads * d_head): the
    cache, of which positions ``s < start_b`` are attended and the rest
    (unwritten, stale or the trash page's) are not; ``start`` (B,)
    int32.  Query head ``h`` reads key/value head ``h // (n_heads /
    n_kv_heads)``.  Chunk position ``c`` attends every cached position
    below ``start_b`` and chunk positions ``c'`` with ``c' // B <= c //
    B`` for ``block_length`` B: ``c' <= c`` at 1; bidirectional inside
    a block and causal across blocks above it (a chunk starts on a block
    boundary).  Returns (B, C, n_heads * d_head) in ``q``'s dtype.
    Traced under the named scope ``attn.core``
    (``mxnet_tpu.profiler.device_table``).
    """
    import jax
    import jax.numpy as jnp

    C, S = q.shape[1], k_rows.shape[1]
    with jax.named_scope("attn.core"):
        cache_ok = (jnp.arange(S, dtype=jnp.int32)[None, :]
                    < start.astype(jnp.int32)[:, None])         # (B, S)
        c_idx = jnp.arange(C, dtype=jnp.int32)
        if block_length > 1:
            c_idx = c_idx // int(block_length)
        causal = c_idx[:, None] >= c_idx[None, :]               # (C, C')
        return _attend_rows(q, k_chunk, v_chunk, k_rows, v_rows,
                            cache_ok[:, None, :], causal, n_heads,
                            n_kv_heads, scale)


def chunk_attention_blocks(q, k_chunk, v_chunk, rows, start, n_heads,
                           n_kv_heads=None, scale=None):
    """:func:`chunk_attention_rows` of a prefill chunk that multiplies
    the cached rows its sequence has written, not all ``S`` a slot
    holds: ``q``, ``k_chunk``, ``v_chunk`` and ``start`` as that takes
    them, ``rows`` (B, S, lanes) the cache as the pool holds it, a
    row's ``[K | V]`` (``2 * n_kv_heads * d_head`` values) first and
    whatever lanes the pool pads with after them.

    Head-split as :func:`chunk_attention_rows` splits a chunk: the G
    query heads of a key/value head side by side, the rows transposed
    so that the heads split off a major dimension.  The softmax runs
    online (a running maximum, denominator and context, float32),
    started from the chunk's own causal block, whose maximum is finite:
    every query attends at least itself.  The cached rows follow in a
    loop over blocks of :func:`cache_block_rows` rows, each block's K
    and V sliced and transposed as it is read, for ``j <
    ceil(max(start) / block)``, a trip count the program computes: a
    block past the longest sequence's rows is never read, one partly
    live (and every block of a shorter sequence of the dispatch) is
    masked by ``s < start_b``.  The last block of a cache that is not
    whole blocks is read ending at ``S`` and its rows under the block's
    own first are masked.  The same mathematics in another order of
    summation.  Traced under the named scope ``attn.core``."""
    import jax
    import jax.numpy as jnp

    B, C, _ = q.shape
    S = rows.shape[1]
    H = int(n_heads)
    Hkv = int(n_kv_heads) if n_kv_heads else H
    G, dh = H // Hkv, q.shape[2] // H
    w = Hkv * dh
    K = cache_block_rows(S)
    if scale is None:
        scale = dh ** -0.5
    start = start.astype(jnp.int32)

    def heads(a):       # (B, T, Hkv * dh) -> (B, Hkv, dh, T)
        return jnp.swapaxes(a, 1, 2).reshape((B, Hkv, dh, a.shape[1]))

    over_d = (((3,), (2,)), ((0, 1), (0, 1)))
    over_s = (((3,), (3,)), ((0, 1), (0, 1)))
    with jax.named_scope("attn.core"):
        # the G query heads of a key/value head side by side: row (g, c)
        qh = q.reshape((B, C, Hkv, G, dh)).transpose(
            (0, 2, 3, 1, 4)).reshape((B, Hkv, G * C, dh))
        c_idx = jnp.arange(C, dtype=jnp.int32)
        causal = jnp.tile(c_idx[:, None] >= c_idx[None, :], (G, 1))
        s = _dot(qh, heads(k_chunk), over_d) * scale        # (B,Hkv,GC,C)
        s = jnp.where(causal[None, None], s, jnp.float32(-1e30))
        m = s.max(-1, keepdims=True)
        e = jnp.exp(s - m)
        den = e.sum(-1, keepdims=True)
        ctx = _dot(e.astype(v_chunk.dtype), heads(v_chunk), over_s)

        def block(j, carry):
            m, den, ctx = carry
            first = j * K
            at = jnp.minimum(first, S - K)
            blk = jax.lax.dynamic_slice(rows, (0, at, 0),
                                        (B, K, rows.shape[2]))
            s = _dot(qh, heads(blk[..., :w]), over_d) * scale   # (.., K)
            idx = at + jnp.arange(K, dtype=jnp.int32)
            ok = (idx >= first)[None, :] & (idx[None, :] < start[:, None])
            s = jnp.where(ok[:, None, None, :], s, jnp.float32(-1e30))
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            shrink = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new)          # a masked score's is exactly 0
            den = den * shrink + e.sum(-1, keepdims=True)
            ctx = ctx * shrink + _dot(e.astype(rows.dtype),
                                      heads(blk[..., w:2 * w]), over_s)
            return m_new, den, ctx

        blocks = (jnp.max(start) + K - 1) // K
        _m, den, ctx = jax.lax.fori_loop(0, blocks, block, (m, den, ctx))
        out = (ctx / den).reshape((B, Hkv, G, C, dh)).transpose(
            (0, 3, 1, 2, 4))
        return out.reshape((B, C, H * dh)).astype(q.dtype)


def paged_attention_rows(q, k_chunk, v_chunk, paged, n_heads,
                         n_kv_heads=None, scale=None):
    """:func:`chunk_attention_rows` of a decode or verify step whose
    cached rows are read where they lie in the pool: ``paged`` is
    ``ops.paged_rows_pallas.PagedRows`` (the pool's pages, the
    dispatch's page table and ``start``), whose written pages
    ``paged_rows_attention`` walks slot by slot, returning the online
    softmax's partials; the chunk's own rows (causal among themselves,
    not in the pool yet) are added here by the identity
    :func:`_softmax_pair` and ``parallel.ring_attention`` use: one
    shared maximum, the two parts' exponentials rescaled to it, one
    denominator.  The same float32 scores and softmax on the same
    operands as :func:`chunk_attention_rows` over the gathered rows,
    summed in another order.  Traced under the named scope
    ``attn.core``, the kernel included."""
    import jax
    import jax.numpy as jnp

    from .paged_rows_pallas import paged_rows_attention

    B, C, _ = q.shape
    H = int(n_heads)
    Hkv = int(n_kv_heads) if n_kv_heads else H
    G, dh = H // Hkv, q.shape[2] // H
    if scale is None:
        scale = dh ** -0.5
    with jax.named_scope("attn.core"):
        # row c * G + j of key/value head g: query head g * G + j at
        # chunk position c
        qh = q.reshape((B, C, Hkv, G, dh)).transpose((0, 2, 1, 3, 4)) \
            .reshape((B, Hkv, C * G, dh))
        acc, m_old, l_old = paged_rows_attention(
            qh, paged.pages, paged.table, paged.start, scale)
        kh = k_chunk.reshape((B, C, Hkv, dh)).transpose((0, 2, 1, 3))
        vh = v_chunk.reshape((B, C, Hkv, dh)).transpose((0, 2, 1, 3))
        s = _dot(qh, kh, (((3,), (3,)), ((0, 1), (0, 1)))) * scale
        r = jnp.arange(C * G, dtype=jnp.int32) // G
        causal = r[:, None] >= jnp.arange(C, dtype=jnp.int32)[None, :]
        s = jnp.where(causal, s, jnp.float32(-1e30))     # (B, Hkv, CG, C)
        # every query attends itself: the shared maximum is finite and
        # a slot with nothing cached (m -1e30, l 0) adds exactly 0
        m = jnp.maximum(m_old, s.max(-1, keepdims=True))
        e = jnp.exp(s - m)
        alpha = jnp.exp(m_old - m)
        denom = l_old * alpha + e.sum(-1, keepdims=True)
        ctx = acc * alpha + _dot(e.astype(v_chunk.dtype), vh,
                                 (((3,), (2,)), ((0, 1), (0, 1))))
        out = (ctx / denom).reshape((B, Hkv, C, G, dh)).transpose(
            (0, 2, 1, 3, 4))
        return out.reshape((B, C, H * dh)).astype(q.dtype)


def ring_rows(window, spec_k=0, itemsize=2):
    """Rows of the ring a windowed layer keeps a slot: ``window +
    spec_k``, up to whole sublane tiles (8 rows of 32 bits: 16 of
    bfloat16).  A query attends the ``window - 1`` positions before it;
    the dispatch before may have written ``spec_k`` rows past the
    position this one starts at (drafts that were rejected), each of
    which displaced the row ``R`` positions earlier.  Attending before
    writing, ``window - 1 + spec_k`` rows would do; one more keeps a
    whole window readable beside them (``PagedGenerationEngine.cached``)
    and would let a step write before it attends."""
    tile = 8 * max(1, 4 // int(itemsize))
    return -(-(int(window) + int(spec_k)) // tile) * tile


def ring_positions(start, rows):
    """The position each of a ring's ``rows`` rows holds before a
    dispatch that starts at ``start`` (B,): the largest ``p < start_b``
    with ``p mod rows == r``, (B, rows) int32; negative where the
    sequence has not reached the row (whatever lies there is another
    sequence's, or nothing)."""
    import jax.numpy as jnp

    last = start.astype(jnp.int32)[:, None] - 1
    r = jnp.arange(rows, dtype=jnp.int32)[None, :]
    return last - jnp.mod(last - r, rows)


def window_attention_rows(q, k_chunk, v_chunk, ring, start, window,
                          n_heads, n_kv_heads=None, scale=None):
    """Attention of a chunk in a sliding window of ``window`` positions
    (position ``i`` attends ``j`` with ``0 <= i - j < window``) against
    the ring its sequence's last rows lie in.

    ``q``, ``k_chunk``, ``v_chunk`` and ``start`` as
    :func:`chunk_attention_rows` takes them; ``ring`` (B, R, lanes) with
    a row's ``[K | V]`` (``2 * n_kv_heads * d_head`` values) first and
    whatever lanes the engine pads with after them, as the dispatch
    before left it: row ``r`` holds position :func:`ring_positions`
    ``[b, r]``.  Chunk position ``c`` attends the ring's rows whose
    position is not negative and more than ``start_b + c - window``, and
    chunk positions ``c - window < c' <= c``.  Returns (B, C, n_heads *
    d_head) in ``q``'s dtype.  Traced under the named scope
    ``attn.window``."""
    import jax
    import jax.numpy as jnp

    B, C, _ = q.shape
    R, w = ring.shape[1], k_chunk.shape[2]
    with jax.named_scope("attn.window"):
        held = ring_positions(start, R)                         # (B, R)
        at = start.astype(jnp.int32)[:, None] \
            + jnp.arange(C, dtype=jnp.int32)[None, :]            # (B, C)
        ring_ok = (held[:, None, :] >= 0) \
            & (at[:, :, None] - held[:, None, :] < window)      # (B, C, R)
        c_idx = jnp.arange(C, dtype=jnp.int32)
        ahead = c_idx[:, None] - c_idx[None, :]
        band = (ahead >= 0) & (ahead < window)                  # (C, C')
        return _attend_rows(q, k_chunk, v_chunk, ring[..., :w],
                            ring[..., w:2 * w], ring_ok, band, n_heads,
                            n_kv_heads, scale)


def ring_write(ring, rows, start, valid):
    """The ring after a dispatch: ``rows`` (B, C, lanes), the chunk's
    rows of positions ``start_b ..``, of which the first ``valid_b``
    count, put at ``position mod R``; every other row of ``ring`` (B, R,
    lanes) stays.  A chunk no longer than the ring scatters its rows in
    place (a row that does not count is dropped); a longer one gathers,
    for every ring row, the last chunk row that lands on it."""
    import jax.numpy as jnp

    B, R, _ = ring.shape
    C = rows.shape[1]
    start = start.astype(jnp.int32)[:, None]
    valid = valid.astype(jnp.int32)[:, None]
    rows = rows.astype(ring.dtype)
    if C <= R:
        c = jnp.arange(C, dtype=jnp.int32)[None, :]
        to = jnp.where(c < valid, jnp.mod(start + c, R), R)     # (B, C)
        return ring.at[jnp.arange(B)[:, None], to].set(rows, mode="drop")
    r = jnp.arange(R, dtype=jnp.int32)[None, :]
    last = valid - 1                                            # (B, 1)
    c = last - jnp.mod(start + last - r, R)                     # (B, R)
    new = jnp.take_along_axis(rows, jnp.maximum(c, 0)[:, :, None], axis=1)
    return jnp.where((c >= 0)[:, :, None], new, ring)


def _attend_rows(q, k_chunk, v_chunk, k_rows, v_rows, cache_ok, chunk_ok,
                 n_heads, n_kv_heads, scale):
    """The two forms on masks given: ``cache_ok`` (B, 1 or C, S) the
    cached rows a chunk position attends, ``chunk_ok`` (C, C') the
    chunk's own."""
    import jax.numpy as jnp

    B, C, _ = q.shape
    H = int(n_heads)
    Hkv = int(n_kv_heads) if n_kv_heads else H
    G, dh = H // Hkv, q.shape[2] // H
    if scale is None:
        scale = dh ** -0.5
    causal = chunk_ok
    by_query = cache_ok.shape[1] != 1

    if attends_in(C, H) == "rows":
        # row (c, h) = head h's values at the lanes of its key/value
        # head, zeros at the others
        own = (jnp.arange(H)[:, None] // G
               == jnp.arange(Hkv)[None, :])                     # (H, Hkv)
        q_bd = jnp.where(own[None, None, :, :, None],
                         q.reshape((B, C, H, 1, dh)),
                         jnp.zeros((), q.dtype)).reshape(
                             (B, C * H, Hkv * dh))
        rows_t = (((2,), (2,)), ((0,), (0,)))   # contract the full row
        s_cache = _dot(q_bd, k_rows, rows_t) * scale            # (B, CH, S)
        s_chunk = _dot(q_bd, k_chunk, rows_t) * scale           # (B, CH, C)
        p_cache, p_chunk = _softmax_pair(
            s_cache, s_chunk,
            jnp.repeat(cache_ok, H, axis=1) if by_query else cache_ok,
            jnp.repeat(causal, H, axis=0)[None], k_rows.dtype)
        over_s = (((2,), (1,)), ((0,), (0,)))
        o_bd = _dot(p_cache, v_rows, over_s) + _dot(p_chunk, v_chunk,
                                                    over_s)
        # every head keeps the lanes of its key/value head: summed over
        # the key/value heads the rows belong to, lanes (kv', d) hold
        # head (kv', g)
        o_bd = o_bd.reshape((B, C, Hkv, G, Hkv, dh))
        mine = jnp.eye(Hkv, dtype=bool)[None, None, :, None, :, None]
        out = jnp.where(mine, o_bd, 0.0).sum(axis=2)     # (B, C, G, Hkv, dh)
        out = out.transpose((0, 1, 3, 2, 4))
    else:
        def heads(a):       # (B, T, Hkv * dh) -> (B, Hkv, dh, T)
            # positions minor-most: the rows are transposed whole, lane
            # dense on both sides, and the heads split off a major
            # dimension, which moves nothing
            return jnp.swapaxes(a, 1, 2).reshape((B, Hkv, dh, a.shape[1]))

        # the G query heads of a key/value head side by side: row (g, c)
        qh = q.reshape((B, C, Hkv, G, dh)).transpose(
            (0, 2, 3, 1, 4)).reshape((B, Hkv, G * C, dh))
        over_d = (((3,), (2,)), ((0, 1), (0, 1)))
        s_cache = _dot(qh, heads(k_rows), over_d) * scale   # (B,Hkv,GC,S)
        s_chunk = _dot(qh, heads(k_chunk), over_d) * scale
        p_cache, p_chunk = _softmax_pair(
            s_cache, s_chunk,
            (jnp.tile(cache_ok, (1, G, 1)) if by_query else cache_ok)[
                :, None],
            jnp.tile(causal, (G, 1))[None, None], k_rows.dtype)
        over_s = (((3,), (3,)), ((0, 1), (0, 1)))
        out = _dot(p_cache, heads(v_rows), over_s) + _dot(
            p_chunk, heads(v_chunk), over_s)             # (B, Hkv, GC, dh)
        out = out.reshape((B, Hkv, G, C, dh)).transpose((0, 3, 1, 2, 4))
    return out.reshape((B, C, H * dh)).astype(q.dtype)
