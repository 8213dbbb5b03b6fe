"""``ops.attention_rows.chunk_attention_rows`` (ISSUE 32): the attention
the decode engine's row pool is read by, in both of its forms, held to a
plain head-split attention in float32; the rule that picks the form
from the dispatch's shape; and the second rule on that shape (ISSUE 38):
whether a model that attends its own rows reads them in blocks up to
``start`` or over all a slot holds, and the count the engine reports;
and ``chunk_attention_blocks``, a grouped-query chunk's cached rows in
blocks up to ``start``, held to the whole form and the reference; the
block-causal mask of decoding by diffusion over blocks."""
import numpy as np
import pytest

from mxnet_tpu.ops import attention_rows

S = 24


def _reference(q, k_chunk, v_chunk, k_rows, v_rows, start, H, Hkv):
    """Head by head, slot by slot, over the attended positions only:
    numpy, float64."""
    B, C, _ = q.shape
    dh = q.shape[2] // H
    out = np.zeros((B, C, H * dh))
    for b in range(B):
        n = int(start[b])
        for h in range(H):
            kv = h // (H // Hkv)
            qs = slice(h * dh, (h + 1) * dh)
            ks = slice(kv * dh, (kv + 1) * dh)
            for c in range(C):
                keys = np.concatenate([k_rows[b, :n, ks],
                                       k_chunk[b, :c + 1, ks]])
                vals = np.concatenate([v_rows[b, :n, ks],
                                       v_chunk[b, :c + 1, ks]])
                s = keys @ q[b, c, qs] * dh ** -0.5
                p = np.exp(s - s.max())
                out[b, c, qs] = (p / p.sum()) @ vals
    return out


@pytest.mark.parametrize("form", ["rows", "heads"])
@pytest.mark.parametrize("chunk", [1, 3, 8], ids=["decode", "verify",
                                                  "prefill_chunk"])
@pytest.mark.parametrize("heads", [(4, 4, 16), (4, 4, 64), (8, 2, 16)],
                         ids=["mha_4x16", "mha_4x64", "gqa_8over2"])
def test_rows_attention_matches_head_split_f32(monkeypatch, heads, chunk,
                                               form):
    """Three slots in one call: nothing cached (the chunk attends
    itself alone), a full cache, and one part-filled whose rows from
    ``start`` on hold what the trash page or an evicted request left
    (large values that must reach no query)."""
    import jax.numpy as jnp

    H, Hkv, dh = heads
    monkeypatch.setattr(attention_rows, "BLOCK_DIAGONAL_MAX_QUERY_ROWS",
                        10 ** 6 if form == "rows" else 0)
    assert attention_rows.attends_in(chunk, H) == form
    rs = np.random.RandomState(H * 100 + dh + chunk)
    start = np.asarray([0, S, 7], np.int32)
    q = rs.standard_normal((3, chunk, H * dh)).astype(np.float32)
    k_c, v_c = rs.standard_normal((2, 3, chunk, Hkv * dh)).astype(np.float32)
    k_r, v_r = rs.standard_normal((2, 3, S, Hkv * dh)).astype(np.float32)
    for b, n in enumerate(start):
        k_r[b, n:] = 1e3
        v_r[b, n:] = -1e3
    got = attention_rows.chunk_attention_rows(
        *(jnp.asarray(a) for a in (q, k_c, v_c, k_r, v_r, start)), H, Hkv)
    assert got.shape == (3, chunk, H * dh) and got.dtype == jnp.float32
    want = _reference(q, k_c, v_c, k_r, v_r, start, H, Hkv)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("form", ["rows", "heads"])
@pytest.mark.parametrize("chunk", [4, 8], ids=["pass", "prefill_chunk"])
def test_block_causal_rows_match_a_dense_reference_f32(monkeypatch, chunk,
                                                       form):
    """``block_length`` 4 (decoding by diffusion over blocks): chunk
    position ``c`` attends ``c'`` iff ``c' // 4 <= c // 4``, in both
    forms, held to a dense float32 attention over the cached rows and
    the chunk's under that mask, grouped-query (8 query heads over 2).
    Every ``start`` is a block boundary, as the engine's are."""
    import jax.numpy as jnp

    H, Hkv, dh, Bl = 8, 2, 16, 4
    monkeypatch.setattr(attention_rows, "BLOCK_DIAGONAL_MAX_QUERY_ROWS",
                        10 ** 6 if form == "rows" else 0)
    assert attention_rows.attends_in(chunk, H) == form
    rs = np.random.RandomState(chunk)
    start = np.asarray([0, S, 8], np.int32)
    q = rs.standard_normal((3, chunk, H * dh)).astype(np.float32)
    k_c, v_c = rs.standard_normal((2, 3, chunk, Hkv * dh)).astype(np.float32)
    k_r, v_r = rs.standard_normal((2, 3, S, Hkv * dh)).astype(np.float32)
    got = attention_rows.chunk_attention_rows(
        *(jnp.asarray(a) for a in (q, k_c, v_c, k_r, v_r, start)), H, Hkv,
        block_length=Bl)
    assert got.shape == (3, chunk, H * dh) and got.dtype == jnp.float32
    # dense: (B, H, C, S + C) scores, one mask, one softmax
    G = H // Hkv
    qh = q.reshape((3, chunk, H, dh)).transpose((0, 2, 1, 3))
    keys = np.concatenate([k_r, k_c], 1).reshape((3, S + chunk, Hkv, dh))
    vals = np.concatenate([v_r, v_c], 1).reshape((3, S + chunk, Hkv, dh))
    keys = np.repeat(keys.transpose((0, 2, 1, 3)), G, axis=1)
    vals = np.repeat(vals.transpose((0, 2, 1, 3)), G, axis=1)
    s = np.einsum("bhcd,bhsd->bhcs", qh, keys) * np.float32(dh ** -0.5)
    blk = np.arange(chunk) // Bl
    mask = np.concatenate([
        np.broadcast_to(np.arange(S)[None, None, :] < start[:, None, None],
                        (3, chunk, S)),
        np.broadcast_to(blk[None, None, :] <= blk[None, :, None],
                        (3, chunk, chunk))], -1)[:, None]
    s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhcs,bhsd->bhcd", p, vals).transpose(
        (0, 2, 1, 3)).reshape((3, chunk, H * dh))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-5)
    # the mask is the block's, not the causal one: a position that
    # attends a later one of its block reads otherwise
    causal = attention_rows.chunk_attention_rows(
        *(jnp.asarray(a) for a in (q, k_c, v_c, k_r, v_r, start)), H, Hkv)
    assert not np.allclose(np.asarray(causal), want, atol=1e-3)


def test_form_follows_the_query_rows_a_slot():
    """The one rule: chunk positions x query heads against a constant.
    At OPT-1.3B's 32 heads a decode step and a verify step of 4 attend
    the rows as they lie, a prefill chunk of 32 splits them by head."""
    limit = attention_rows.BLOCK_DIAGONAL_MAX_QUERY_ROWS
    assert attention_rows.attends_in(1, 32) == "rows"
    assert attention_rows.attends_in(4, 32) == "rows"
    assert attention_rows.attends_in(32, 32) == "heads"
    assert attention_rows.attends_in(limit, 1) == "rows"
    assert attention_rows.attends_in(limit + 1, 1) == "heads"


@pytest.mark.parametrize("chunk,form", [
    (1, "whole"), (2, "whole"), (8, "whole"), (16, "blocks"),
    (24, "blocks"), (512, "blocks")])
def test_cached_rows_are_attended_in_blocks_by_a_chunk_alone(chunk, form):
    """The second rule on the static shape (ISSUE 38): a decode step's
    one position a slot and a verify step's few attend all the rows a
    slot holds, a prefill chunk blocks of them up to ``start``.  A layer
    that a step reads in place (``"pages"``) attends a chunk in blocks
    too; one declared ``"whole"`` attends all it holds in every shape."""
    assert attention_rows.cached_rows_in(chunk) == form
    paged = "pages" if form == "whole" else "blocks"
    assert attention_rows.cached_rows_in(chunk, "pages") == paged
    assert attention_rows.cached_rows_in(chunk, "whole") == "whole"
    limit = attention_rows.BLOCKED_CACHE_MIN_QUERY_POSITIONS
    assert attention_rows.cached_rows_in(limit - 1) == "whole"
    assert attention_rows.cached_rows_in(limit) == "blocks"
    assert attention_rows.cached_rows_in(limit - 1, "pages") == "pages"
    assert attention_rows.cached_rows_in(limit, "pages") == "blocks"


@pytest.mark.parametrize("start,held,block,want", [
    (0, 6144, 512, 0), (1, 6144, 512, 512), (512, 6144, 512, 512),
    (513, 6144, 512, 1024), (3584, 6144, 512, 3584),
    (3584, 6144, 1024, 4096), (6000, 6144, 1024, 6144),
    (9000, 9216, 512, 9216), (30, 128, 512, 128), (0, 128, 512, 0),
    (33, 40, 16, 40), (32, 40, 16, 32)])
def test_rows_a_dispatch_multiplies(monkeypatch, start, held, block, want):
    """``attended_cache_rows``: whole blocks up to the longest ``start``
    under ``"blocks"`` (a block is all of a smaller cache; the last
    block of a cache that is not whole blocks ends with it), all a slot
    holds under ``"whole"`` whatever is written."""
    monkeypatch.setattr(attention_rows, "CACHE_BLOCK_ROWS", block)
    assert attention_rows.cache_block_rows(held) == min(block, held)
    assert attention_rows.attended_cache_rows(512, start, held) == want
    assert attention_rows.attended_cache_rows(2, start, held) == held
    # a layer read in place by a step counts its chunk the same
    assert attention_rows.attended_cache_rows(512, start, held,
                                              "pages") == want
    assert attention_rows.attended_cache_rows(512, start, held,
                                              "whole") == held


def test_a_block_is_whole_pages_and_whole_sublane_tiles():
    """The constant as committed: a multiple of the serving cells' page
    (16 rows) and of a bfloat16 sublane tile (16 rows), so that a
    block's first row is a tile's first row."""
    assert attention_rows.CACHE_BLOCK_ROWS % 16 == 0
    assert attention_rows.CACHE_BLOCK_ROWS >= 128


def test_operands_keep_their_dtype_and_accumulate_in_f32():
    """bfloat16 in, bfloat16 out, and no narrower inside than a float32
    softmax over products accumulated in float32: within bfloat16's
    rounding of the float32 result on the same (rounded) operands."""
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(5)
    H, dh, C = 4, 16, 2
    arrs = [jnp.asarray(rs.standard_normal(s), jnp.bfloat16) for s in
            [(2, C, H * dh)] * 3 + [(2, S, H * dh)] * 2]
    start = jnp.asarray([5, S], jnp.int32)
    got = attention_rows.chunk_attention_rows(*arrs, start, H)
    assert got.dtype == jnp.bfloat16
    want = attention_rows.chunk_attention_rows(
        *(a.astype(jnp.float32) for a in arrs), start, H)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want), atol=2e-2, rtol=2e-2)
    text = jax.jit(attention_rows.chunk_attention_rows,
                   static_argnums=(6,)).lower(*arrs, start, H).as_text()
    assert "exponential" in text and "xf32>" in text
    assert "-> tensor<2x8x24xf32>" in text, "scores are not float32"


# the blocked form of a grouped-query chunk: blocks of 16 cached rows,
# chunks of 16 queries a slot
BLOCK, CHUNK = 16, 16


def _chunk_operands(H, Hkv, dh, held, start, seed, pad=16):
    """A chunk of ``CHUNK`` queries a slot and its own keys and values,
    and the cache as the pool holds it, ``[K | V]`` a row and ``pad``
    lanes after them; rows from a slot's ``start`` on hold what the
    trash page or an evicted request left (large values that must reach
    no query), the padding lanes too."""
    rs = np.random.RandomState(seed)
    B, w = len(start), Hkv * dh
    q = rs.standard_normal((B, CHUNK, H * dh)).astype(np.float32)
    k_c, v_c = rs.standard_normal((2, B, CHUNK, w)).astype(np.float32)
    rows = rs.standard_normal((B, held, 2 * w + pad)).astype(np.float32)
    rows[..., 2 * w:] = 1e3
    for b, n in enumerate(start):
        rows[b, n:] = 1e3
    return q, k_c, v_c, rows, np.asarray(start, np.int32)


@pytest.mark.parametrize("heads", [(8, 1, 16), (4, 4, 32), (8, 2, 16)],
                         ids=["gqa_8over1", "mha_4x32", "gqa_8over2"])
@pytest.mark.parametrize("held,start", [
    (64, (0, 7, BLOCK, 63)),            # nothing cached beside a block's
    (64, (0, 0, 0, 0)),                 # inside, a boundary and S - 1
    (64, (BLOCK - 1, BLOCK + 1, 2 * BLOCK, 48)),
    (40, (0, 33, 39, 17)),              # the last block ends at S = 40
    (24, (23, 0, 5, 16))],              # a cache under two blocks
    ids=["ragged", "nothing_cached", "boundaries", "ragged_last_block",
         "short_cache"])
def test_a_chunk_attends_grouped_rows_in_blocks_what_it_attends_whole(
        monkeypatch, heads, held, start):
    """``chunk_attention_blocks`` against ``chunk_attention_rows`` over
    the same gathered rows and against the plain reference, float32: one
    mathematics in another order of summation.  Four slots of one
    dispatch at ragged ``start`` values, G query heads a key/value head
    from 8 to 1.  The rows from the end of the bound's last block on are
    never read: filled with NaN they leave the result the same."""
    import jax.numpy as jnp

    H, Hkv, dh = heads
    w = Hkv * dh
    monkeypatch.setattr(attention_rows, "CACHE_BLOCK_ROWS", BLOCK)
    assert attention_rows.cached_rows_in(CHUNK, "pages") == "blocks"
    q, k_c, v_c, rows, start = _chunk_operands(H, Hkv, dh, held, start,
                                               H * held + dh)
    args = [jnp.asarray(a) for a in (q, k_c, v_c)]
    got = np.asarray(attention_rows.chunk_attention_blocks(
        *args, jnp.asarray(rows), jnp.asarray(start), H, Hkv))
    whole = np.asarray(attention_rows.chunk_attention_rows(
        *args, jnp.asarray(rows[..., :w]), jnp.asarray(rows[..., w:2 * w]),
        jnp.asarray(start), H, Hkv))
    assert got.shape == (len(start), CHUNK, H * dh)
    np.testing.assert_allclose(got, whole, atol=1e-5, rtol=1e-5)
    want = _reference(q, k_c, v_c, rows[..., :w], rows[..., w:2 * w],
                      start, H, Hkv)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    bound = min(-(-int(start.max()) // BLOCK) * BLOCK, held)
    if bound < held:
        poisoned = rows.copy()
        poisoned[:, bound:] = np.nan
        again = np.asarray(attention_rows.chunk_attention_blocks(
            *args, jnp.asarray(poisoned), jnp.asarray(start), H, Hkv))
        assert np.array_equal(again, got)


def test_blocked_operands_keep_their_dtype_and_one_loop_a_call():
    """bfloat16 in, bfloat16 out, within bfloat16's rounding of the
    float32 result on the same (rounded) operands; scores float32; the
    cached half one ``while`` whose trip count is computed from
    ``start``, not unrolled by the cache's length."""
    import jax
    import jax.numpy as jnp

    H, Hkv, dh = 4, 2, 16
    q, k_c, v_c, rows, start = _chunk_operands(H, Hkv, dh, 64, (5, 40), 3)
    arrs = [jnp.asarray(a, jnp.bfloat16) for a in (q, k_c, v_c, rows)]
    start = jnp.asarray(start)
    got = attention_rows.chunk_attention_blocks(*arrs, start, H, Hkv)
    assert got.dtype == jnp.bfloat16
    want = attention_rows.chunk_attention_blocks(
        *(a.astype(jnp.float32) for a in arrs), start, H, Hkv)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want), atol=3e-2, rtol=3e-2)
    text = jax.jit(attention_rows.chunk_attention_blocks,
                   static_argnums=(5, 6)).lower(*arrs, start, H,
                                                Hkv).as_text()
    assert text.count("stablehlo.while") == 1
    assert "exponential" in text and "xf32>" in text
