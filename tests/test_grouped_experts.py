"""``parallel.moe.routed_experts`` groups its rows by expert (ISSUE 36):
the chosen (row, expert) pairs sorted by expert, each held expert's rows
padded up to whole row tiles, one grouped product a matrix.  Held here
to the dense product it replaced (every held expert multiplies every
row, unrouted weights zero: ``tools/bench_experts.py``'s
``dense_experts``, the parent's arithmetic, which that tool times it
against on the chip)
and to a plain loop over rows, on the CPU: through ``jax.lax.ragged_dot``
as the package runs it off the TPU, and through the Pallas kernels under
the interpreter at widths of whole lane tiles."""
import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import moe
from mxnet_tpu.parallel.moe import routed_experts, sigmoid_group_select

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
from bench_experts import dense_experts  # noqa: E402


def row_by_row(x, router_w, w_gate, w_up, w_down, top_k, F, first,
               norm_topk=True):
    """The layer as its definition reads, a row and an expert at a time
    (NumPy, float64; softmax routing)."""
    x, router_w, w_gate, w_up, w_down = (
        np.asarray(a, np.float64) for a in (x, router_w, w_gate, w_up,
                                            w_down))
    held = w_down.shape[0] // F
    logits = x @ router_w
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-p[t], kind="stable")[:top_k]
        w = p[t, top] / (p[t, top].sum() if norm_topk else 1.0)
        for e, we in zip(top, w):
            if first <= e < first + held:
                cols = slice((e - first) * F, (e - first + 1) * F)
                g, u = x[t] @ w_gate[:, cols], x[t] @ w_up[:, cols]
                out[t] += we * ((g / (1 + np.exp(-g)) * u) @ w_down[cols])
    return out


def layer(seed, T, D, F, E, held, scale=0.3):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    return (jnp.asarray(rng.normal(size=(T, D)), f32),
            jnp.asarray(rng.normal(size=(D, E)), f32),
            jnp.asarray(rng.normal(size=(D, held * F)) * scale, f32),
            jnp.asarray(rng.normal(size=(D, held * F)) * scale, f32),
            jnp.asarray(rng.normal(size=(held * F, D)) * scale, f32))


def close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


# rows, d_model, d_expert, experts, held, first, top_k
CASES = {
    "top1": (40, 64, 32, 16, 16, 0, 1),
    "top2": (40, 64, 32, 16, 16, 0, 2),
    "top8": (24, 64, 32, 16, 16, 0, 8),
    "share_first_8_of_16": (37, 64, 32, 16, 4, 8, 8),
    "share_last_quarter": (33, 64, 32, 16, 4, 12, 2),
    "pairs_not_a_multiple_of_the_tile": (7, 64, 32, 8, 8, 0, 3),
    "one_row": (1, 64, 32, 8, 4, 2, 2),
    "more_tiles_than_pairs": (2, 64, 32, 64, 64, 0, 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_matches_dense_and_row_by_row(case):
    """Softmax routing over ``top_k`` 1, 2 and 8, whole layers and
    shares with ``first`` != 0, pair counts that fill no whole tile: the
    grouped product gives the dense oracle's and the plain loop's
    result, and the counts over all experts of the layer."""
    T, D, F, E, held, first, k = CASES[case]
    args = layer(sorted(CASES).index(case), T, D, F, E, held)
    with jax.default_matmul_precision("highest"):
        got, counts = jax.jit(functools.partial(
            routed_experts, top_k=k, d_expert=F, first=first))(*args)
        want, want_counts = dense_experts(*args, k, F, first=first)
    close(got, want)
    close(got, row_by_row(*args, k, F, first))
    assert counts.dtype == jnp.int32 and counts.shape == (E,)
    assert (np.asarray(counts) == np.asarray(want_counts)).all()
    assert int(counts.sum()) == T * k


@pytest.mark.parametrize("norm_topk", [True, False])
def test_norm_topk_is_kept(norm_topk):
    args = layer(5, 20, 64, 32, 8, 8)
    with jax.default_matmul_precision("highest"):
        got, _ = routed_experts(*args, 2, 32, norm_topk=norm_topk)
    close(got, row_by_row(*args, 2, 32, 0, norm_topk=norm_topk))


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4), (8, 8)])
@pytest.mark.parametrize("norm_topk", [True, False])
def test_sigmoid_group_select_goes_through(first, held, norm_topk):
    """The selection rule is the caller's: a sigmoid, group-limited
    router with a bias and a scaling gives the oracle's result too."""
    T, D, F, E, k = 30, 64, 32, 16, 4
    x, rw, wg, wu, wd = layer(11, T, D, F, E, held)
    bias = jnp.asarray(np.random.default_rng(2).normal(size=(E,)) * 0.1,
                       jnp.float32)
    select = sigmoid_group_select(bias, n_group=4, topk_group=2,
                                  scaling=2.5, norm_topk=norm_topk)
    with jax.default_matmul_precision("highest"):
        got, counts = routed_experts(x, rw, wg, wu, wd, k, F, first=first,
                                     select=select)
        want, want_counts = dense_experts(x, rw, wg, wu, wd, k, F,
                                          first=first, select=select)
    close(got, want)
    assert (np.asarray(counts) == np.asarray(want_counts)).all()


def skewed(which, T=48, D=64, F=32, E=8, held=8):
    """Rows with positive entries and a router that sends them all to
    one expert (``all_on_one``: top 1 on expert 3) or never to expert 3
    (``one_never``)."""
    x, rw, wg, wu, wd = layer(7, T, D, F, E, held)
    x = jnp.abs(x)
    rw = rw.at[:, 3].set(5.0 if which == "all_on_one" else -5.0)
    return x, rw, wg, wu, wd


@pytest.mark.parametrize("which,top_k", [("all_on_one", 1),
                                         ("all_on_one", 2),
                                         ("one_never", 2)])
def test_skewed_routing(which, top_k):
    """Every row on one expert (several tiles of one expert, the others
    none), and an expert no row chose (no tile): the same result."""
    args = skewed(which)
    with jax.default_matmul_precision("highest"):
        got, counts = routed_experts(*args, top_k, 32)
        want, _ = dense_experts(*args, top_k, 32)
    close(got, want)
    assert int(counts[3]) == (48 if which == "all_on_one" else 0)


def test_no_pair_on_the_held_experts_gives_zeros():
    """A share none of whose experts any row chose: no tile in use, a
    result of zeros (and nothing of the never-written rows in it)."""
    x, rw, wg, wu, wd = skewed("one_never")
    col = slice(3 * 32, 4 * 32)
    with jax.default_matmul_precision("highest"):
        got, counts = routed_experts(x, rw, wg[:, col], wu[:, col], wd[col],
                                     2, 32, first=3)
    assert int(counts[3]) == 0 and int(counts.sum()) == 48 * 2
    assert np.asarray(got).shape == (48, 64)
    assert (np.asarray(got) == 0).all()


def test_shares_add_up_to_the_whole_layer():
    T, D, F, E, k = 29, 64, 32, 16, 8
    x, rw, wg, wu, wd = layer(13, T, D, F, E, E)
    with jax.default_matmul_precision("highest"):
        whole, counts = routed_experts(x, rw, wg, wu, wd, k, F)
        parts = []
        for first in (0, 4, 8, 12):
            cut = slice(first * F, (first + 4) * F)
            part, c = routed_experts(x, rw, wg[:, cut], wu[:, cut], wd[cut],
                                     k, F, first=first)
            assert (np.asarray(c) == np.asarray(counts)).all()
            parts.append(np.asarray(part, np.float64))
    close(sum(parts), whole)


# -- the Pallas kernels, under the interpreter --------------------------------

KERNEL_CASES = {
    "whole_layer_top2": (40, 128, 128, 8, 8, 0, 2),
    "share_top8": (20, 256, 128, 16, 4, 4, 8),
    "several_column_blocks": (9, 128, 256, 8, 2, 2, 2),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_read_each_expert_in_place(case, monkeypatch):
    """At widths of whole lane tiles the grouped product is the two
    Pallas kernels, which take the ``(d_model, held x width)`` matrices
    as they are and find expert ``e`` by block index; under the
    interpreter they give the oracle's result."""
    T, D, F, E, held, first, k = KERNEL_CASES[case]
    args = layer(3, T, D, F, E, held, scale=0.1)
    monkeypatch.setattr(moe, "_grouped_experts", functools.partial(
        moe._grouped_experts, interpret=True))
    with jax.default_matmul_precision("highest"):
        got, counts = jax.jit(functools.partial(
            routed_experts, top_k=k, d_expert=F, first=first))(*args)
        want, want_counts = dense_experts(*args, k, F, first=first)
    close(got, want)
    assert (np.asarray(counts) == np.asarray(want_counts)).all()


def test_kernels_skip_what_no_row_chose(monkeypatch):
    """Expert 3's matrices are NaN and no row is routed to it: the
    kernels never read them (the dense product would give NaN)."""
    from mxnet_tpu.ops import grouped_ffn_pallas

    T, D, F, E = 16, 128, 128, 8
    x, rw, wg, wu, wd = layer(4, T, D, F, E, E, scale=0.1)
    x = jnp.abs(x)
    rw = rw.at[:, 3].set(-5.0)
    col = slice(3 * F, 4 * F)
    wg, wu = wg.at[:, col].set(jnp.nan), wu.at[:, col].set(jnp.nan)
    wd = wd.at[col].set(jnp.nan)
    assert grouped_ffn_pallas.fits(D, F, 64)
    monkeypatch.setattr(moe, "_grouped_experts", functools.partial(
        moe._grouped_experts, interpret=True))
    with jax.default_matmul_precision("highest"):
        got, counts = routed_experts(x, rw, wg, wu, wd, 2, F)
    assert int(counts[3]) == 0
    assert np.isfinite(np.asarray(got)).all()
    clean = [a.at[:, col].set(0.0) for a in (wg, wu)] + [wd.at[col].set(0.0)]
    with jax.default_matmul_precision("highest"):
        want, _ = dense_experts(x, rw, *clean, 2, F)
    close(got, want)


# -- the rule on static shapes, and the counter's arithmetic ------------------

@pytest.mark.parametrize("pairs,n_experts,tile", [
    (32 * 8, 512, 16),      # Ling's (32, 1) step
    (512 * 8, 512, 16),     # Ling's (1, 512) chunk: 8 pairs an expert
    (128 * 8, 128, 16),     # SDAR's (32, 4) pass
    (256 * 8, 128, 32),     # SDAR's (1, 256) chunk: 16 an expert
    (64 * 8, 256, 16),      # GigaChat's (32, 2) verify step
    (512 * 8, 256, 32),     # GigaChat's (1, 512) chunk
    (1, 8, 16),             # never under a bfloat16 sublane tile
    (1 << 20, 8, 128),      # never over the MXU's rows
])
def test_row_tile_follows_the_static_shapes(pairs, n_experts, tile):
    assert moe.expert_row_tile(pairs, n_experts) == tile


@pytest.mark.parametrize("d_model,d_expert,rows,fits", [
    (2560, 768, 6144, True), (2048, 768, 3072, True),
    (7168, 2048, 4608, True), (64, 32, 64, False), (128, 96, 64, False),
    (192, 128, 64, False), (2560, 768, 1 << 17, False)])
def test_kernels_want_whole_lane_tiles_and_rows_in_scalar_memory(
        d_model, d_expert, rows, fits):
    """The rule on static shapes that says whether a call's grouped
    product is the kernels' (on a TPU) or the plain one's: widths of
    whole lane tiles, as the three configurations have and the tiny
    rehearsal and test sizes have not, and padded rows whose
    destinations and weights fit the chip's scalar memory."""
    from mxnet_tpu.ops import grouped_ffn_pallas

    assert grouped_ffn_pallas.fits(d_model, d_expert, rows) is fits


def test_rows_multiplied_pads_each_expert_to_whole_tiles():
    counts = np.array([[0, 1, 16, 17], [40, 0, 0, 3]])
    assert moe.expert_rows_multiplied(counts, 16) == \
        (0 + 16 + 16 + 32) + (48 + 0 + 0 + 16)
    assert moe.expert_rows_multiplied(counts[:, :0], 16) == 0
