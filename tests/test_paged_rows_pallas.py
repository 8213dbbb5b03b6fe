"""``ops.paged_rows_pallas.paged_rows_attention`` (ISSUE 40): a decode or
verify step's cached rows read where they lie in the page pool, slot by
slot through the page table, run here under the TPU interpreter of
Pallas (copies and semaphores included), and the combining step
``ops.attention_rows.paged_attention_rows`` that adds the chunk's own
rows: held to a plain float64 attention and to
``chunk_attention_rows`` over the same rows gathered, with the slots'
pages scattered out of order, the trash page in the table, and garbage
(NaN, large values) where no query may look."""
import numpy as np
import pytest

from mxnet_tpu.ops import attention_rows, paged_rows_pallas
from mxnet_tpu.ops.paged_rows_pallas import PagedRows

PAGE, P, DH = 16, 3, 16
S = PAGE * P
# rows written a slot: nothing, part of a page, a page less one, a page,
# a page and one, every row the slot holds
STARTS = [0, 1, 15, 16, 17, S]


def _case(C, H, Hkv, dtype, seed, dh=DH):
    """Queries, the chunk's own rows, a pool and a page table: slot
    ``b``'s pages scattered over the pool, its unwritten pages the trash
    page 0 (NaN), the rows at or above its ``start`` in its last written
    page NaN or 1e4, its pages past the written ones large values; heads
    of ``dh``."""
    rs = np.random.RandomState(seed)
    B, w = len(STARTS), Hkv * dh
    lanes = -(-2 * w // 128) * 128
    n_pages = 1 + B * P
    pool = rs.standard_normal((n_pages, PAGE, lanes)).astype(np.float32)
    pool[..., 2 * w:] = 0.0
    pool[0] = np.nan                                  # the trash page
    ids = rs.permutation(np.arange(1, n_pages)).reshape(B, P)
    table = np.zeros((B, P), np.int32)
    start = np.asarray(STARTS, np.int32)
    for b, n in enumerate(start):
        written = -(-int(n) // PAGE)
        table[b, :written] = ids[b, :written]
        if b % 2:       # pages the slot holds but has not reached
            table[b, written:] = ids[b, written:]
            pool[ids[b, written:], :, :2 * w] = 1e4
        if n % PAGE:
            pool[table[b, written - 1], n % PAGE:, :2 * w] = \
                np.nan if b % 2 else -1e4
    q = rs.standard_normal((B, C, H * dh)).astype(np.float32)
    k_c, v_c = rs.standard_normal((2, B, C, w)).astype(np.float32)
    cast = (lambda a: a.astype(dtype))
    return cast(q), cast(k_c), cast(v_c), cast(pool), table, start


def _gathered(pool, table, start):
    """The rows as the engine gathered them before: every page of a
    slot, what lies at or above ``start`` zeroed here (the reference
    must not meet the NaN either)."""
    B = table.shape[0]
    rows = pool[table].reshape(B, S, pool.shape[2]).astype(np.float32)
    ok = np.arange(S)[None, :, None] < start[:, None, None]
    return np.where(ok, rows, 0.0)


def _reference(q, k_c, v_c, rows, start, H, Hkv, DH=DH):
    """Head by head, slot by slot, over the attended positions alone:
    numpy, float64."""
    B, C, _ = q.shape
    w = Hkv * DH
    out = np.zeros((B, C, H * DH))
    for b in range(B):
        n = int(start[b])
        for h in range(H):
            g = h // (H // Hkv)
            ks = slice(g * DH, (g + 1) * DH)
            vs = slice(w + g * DH, w + (g + 1) * DH)
            for c in range(C):
                keys = np.concatenate([rows[b, :n, ks], k_c[b, :c + 1, ks]])
                vals = np.concatenate([rows[b, :n, vs], v_c[b, :c + 1, ks]])
                s = keys @ q[b, c, h * DH:(h + 1) * DH] * DH ** -0.5
                p = np.exp(s - s.max())
                out[b, c, h * DH:(h + 1) * DH] = (p / p.sum()) @ vals
    return out


@pytest.mark.parametrize("per_block", [1, 2, 16])
@pytest.mark.parametrize("heads", [(16, 2), (2, 2)], ids=["G8", "G1"])
@pytest.mark.parametrize("chunk", [1, 2], ids=["decode", "verify"])
def test_pages_in_place_match_the_plain_attention_f32(monkeypatch, chunk,
                                                      heads, per_block):
    """float32: the kernel and the combining step against float64, with
    one page a block (every block's copies overlapping the products of
    the one before), two, and all of a slot's pages in one block."""
    import jax.numpy as jnp

    monkeypatch.setattr(paged_rows_pallas, "PAGES_PER_BLOCK", per_block)
    H, Hkv = heads
    q, k_c, v_c, pool, table, start = _case(chunk, H, Hkv, np.float32,
                                            chunk * 10 + H + per_block)
    got = attention_rows.paged_attention_rows(
        jnp.asarray(q), jnp.asarray(k_c), jnp.asarray(v_c),
        PagedRows(jnp.asarray(pool), jnp.asarray(table),
                  jnp.asarray(start)), H, Hkv)
    assert got.shape == q.shape and got.dtype == jnp.float32
    got = np.asarray(got)
    assert np.isfinite(got).all()
    want = _reference(q, k_c, v_c, _gathered(pool, table, start), start,
                      H, Hkv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("heads", [(16, 2), (2, 2)], ids=["G8", "G1"])
@pytest.mark.parametrize("chunk", [1, 2], ids=["decode", "verify"])
def test_pages_in_place_are_the_gathered_form_bf16(chunk, heads):
    """bfloat16 operands, as on the chip: the same scores and softmax as
    ``chunk_attention_rows`` over the gathered rows, summed in another
    order and rounded at another point (the probabilities before the
    division by the denominator): within a unit or two of bfloat16 at
    the outputs' size."""
    import jax.numpy as jnp
    import ml_dtypes

    H, Hkv = heads
    w = Hkv * DH
    bf = ml_dtypes.bfloat16
    q, k_c, v_c, pool, table, start = _case(chunk, H, Hkv, bf, chunk + H)
    got = np.asarray(attention_rows.paged_attention_rows(
        jnp.asarray(q), jnp.asarray(k_c), jnp.asarray(v_c),
        PagedRows(jnp.asarray(pool), jnp.asarray(table),
                  jnp.asarray(start)), H, Hkv)).astype(np.float32)
    rows = jnp.asarray(_gathered(pool, table, start).astype(bf))
    want = np.asarray(attention_rows.chunk_attention_rows(
        jnp.asarray(q), jnp.asarray(k_c), jnp.asarray(v_c),
        rows[..., :w], rows[..., w:2 * w], jnp.asarray(start), H,
        Hkv)).astype(np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=2e-2)


def test_the_partials_of_a_slot_with_nothing_written():
    """A slot the step does not step (the engine hands it ``start`` 0)
    or whose sequence has written nothing: no page is read, whatever its
    table names (here the NaN trash page and pages of large values), and
    its partials are the empty softmax's: maximum -1e30, denominator 0,
    context 0; a written slot's denominator is at least 1."""
    import jax.numpy as jnp

    q, _k, _v, pool, table, start = _case(2, 16, 2, np.float32, 3)
    table[0] = table[-1]               # pages another slot has written
    qh = q.reshape(6, 2, 2, 8, DH).transpose(0, 2, 1, 3, 4).reshape(
        6, 2, 16, DH)
    acc, m, l = (np.asarray(a) for a in paged_rows_pallas
                 .paged_rows_attention(jnp.asarray(qh), jnp.asarray(pool),
                                       jnp.asarray(table),
                                       jnp.asarray(start), DH ** -0.5))
    assert acc.shape == (6, 2, 16, DH) and m.shape == l.shape == (6, 2, 16, 1)
    assert (acc[0] == 0).all() and (l[0] == 0).all() and (m[0] == -1e30).all()
    assert np.isfinite(acc).all() and (l[1:] >= 1).all()


@pytest.mark.parametrize("per_block", [2, 16])
def test_one_query_row_a_head_of_a_width_of_128(monkeypatch, per_block):
    """Olmo-Hybrid-7B's full layers as a decode step hands them over: as
    many key/value heads as query heads (one query row a key/value head)
    of 128, a head count that is no multiple of 8 (6 here, 30 in the
    model), rows of 2 x 6 x 128 = 1536 lanes; float32 against float64."""
    import jax.numpy as jnp

    monkeypatch.setattr(paged_rows_pallas, "PAGES_PER_BLOCK", per_block)
    dh, H = 128, 6
    q, k_c, v_c, pool, table, start = _case(1, H, H, np.float32, per_block,
                                            dh)
    assert pool.shape[-1] == 2 * H * dh
    got = np.asarray(attention_rows.paged_attention_rows(
        jnp.asarray(q), jnp.asarray(k_c), jnp.asarray(v_c),
        PagedRows(jnp.asarray(pool), jnp.asarray(table),
                  jnp.asarray(start)), H, H))
    assert np.isfinite(got).all()
    want = _reference(q, k_c, v_c, _gathered(pool, table, start), start,
                      H, H, dh)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
