"""``ops.attention_rows.chunk_attention_rows`` (ISSUE 32): the attention
the decode engine's row pool is read by, in both of its forms, held to a
plain head-split attention in float32; the rule that picks the form
from the dispatch's shape; and the second rule on that shape (ISSUE 38):
whether a model that attends its own rows reads them in blocks up to
``start`` or over all a slot holds, and the count the engine reports."""
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
    slot holds, a prefill chunk blocks of them up to ``start``."""
    assert attention_rows.cached_rows_in(chunk) == form
    limit = attention_rows.BLOCKED_CACHE_MIN_QUERY_POSITIONS
    assert attention_rows.cached_rows_in(limit - 1) == "whole"
    assert attention_rows.cached_rows_in(limit) == "blocks"


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
