"""The held experts' gated feed-forward on rows grouped by expert, read
from the expert matrices where they lie (Pallas TPU kernels).

``parallel.moe.routed_experts`` lays the chosen (row, expert) pairs out
in **row tiles** of ``tm`` rows, every tile of one expert (an expert's
rows padded up to whole tiles), and says per tile which expert it is
(``tile_expert``) and how many tiles are in use (``n_tiles``).  The two
kernels here multiply a tile by its expert's matrices and nothing else:

* :func:`gate_up`: ``silu(rows @ gate_e) * (rows @ up_e)``, where expert
  ``e``'s columns are the column blocks ``[e * F, (e + 1) * F)`` of the
  ``(d_model, held * F)`` matrices: the block index of a weight block is
  ``(0, e * (F / tn) + j)``;
* :func:`down_combine`: ``h @ down_e``, expert ``e``'s rows being the row
  block ``e`` of height ``F`` of the ``(held * F, d_model)`` matrix, each
  product row weighted and added to its token's row of the result, which
  stays in fast memory while the tiles pass under it.

No expert matrix is reshaped, transposed or copied: ``F`` and
``d_model`` are whole lane tiles (:func:`fits`), so a block is whole
tiles of the matrix as the TPU stores it.  The grid is ``(column blocks,
tiles in use)`` with the tiles innermost: consecutive tiles of one
expert name the same weight block, which is then fetched once, so every
expert some row chose is read once a call and an expert no row chose is
never read.  The number of tiles in use is a scalar computed by the
program (a dynamic grid bound), so one compiled program serves any
routing; ``tile_expert`` and the rows' destinations and weights are
prefetched into scalar memory.  Operands stay in the dtype they arrive
in, products accumulate in float32, and so does the sum over a token's
experts.

Modelled on ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (which
takes ``(groups, k, n)`` weights and lets tiles straddle groups).
"""
from __future__ import annotations

__all__ = ["fits", "gate_up", "down_combine"]

# bytes of one weight block a kernel keeps in flight (each is double
# buffered, gate and up side by side): blocks of 3-4 MB are fetched in
# 4-5 us, ten times a grid step's fixed cost
_BLOCK_BYTES = 4 << 20
# bytes of scalar memory (1 MiB on a v5e) the padded rows' destinations
# and weights may take, 8 a row
_SCALAR_BYTES = 768 << 10


def fits(d_model, d_expert, padded_rows):
    """Whether the kernels can run a call: both widths whole 128-lane
    tiles (an expert is then whole tiles of the matrices as stored), and
    the padded rows' destinations and weights within scalar memory."""
    return d_model % 128 == 0 and d_expert % 128 == 0 \
        and 8 * padded_rows <= _SCALAR_BYTES


def _col_tile(width, column_bytes):
    """The widest column block that is whole lane tiles, divides
    ``width`` and keeps ``column_bytes`` a column under
    ``_BLOCK_BYTES``."""
    best = 128
    for tn in range(128, width + 1, 128):
        if width % tn == 0 and tn * column_bytes <= _BLOCK_BYTES:
            best = tn
    return best


def _params(vmem_bytes):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=int(vmem_bytes) + (8 << 20))


def gate_up(rows, w_gate, w_up, tile_expert, n_tiles, d_expert, tm,
            interpret=False):
    """``silu(rows @ gate_e) * (rows @ up_e)`` tile by tile.

      rows: (R, d_model), ``R`` a multiple of ``tm``; tile ``t`` is rows
        ``[t * tm, (t + 1) * tm)``
      w_gate, w_up: (d_model, held * d_expert) as the model holds them
      tile_expert: (R / tm,) int32, the held expert of each tile
      n_tiles: int32 scalar, the tiles in use (the leading ones)
    Returns (R, d_expert) in ``rows``' dtype; rows of tiles not in use
    are left as they were allocated."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, D = rows.shape
    F = int(d_expert)
    size = jnp.dtype(w_gate.dtype).itemsize
    tn = _col_tile(F, D * size)
    per = F // tn

    def kernel(_te, x_ref, g_ref, u_ref, o_ref):
        x = x_ref[...]
        g = jnp.dot(x, g_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = (jax.nn.silu(g) * u).astype(o_ref.dtype)

    def weight(j, t, te):
        return 0, te[t] * per + j

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((R, F), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(per, n_tiles),
            in_specs=[pl.BlockSpec((tm, D), lambda j, t, te: (t, 0)),
                      pl.BlockSpec((D, tn), weight),
                      pl.BlockSpec((D, tn), weight)],
            out_specs=pl.BlockSpec((tm, tn), lambda j, t, te: (t, j))),
        compiler_params=_params(4 * D * tn * size + 4 * tm * (D + tn) * 4),
        name="grouped_ffn_gate_up", interpret=interpret)(
            tile_expert, rows, w_gate, w_up)


def down_combine(h, w_down, tile_expert, n_tiles, row_of, row_weight,
                 n_rows, tm, interpret=False):
    """``out[row_of[r]] += row_weight[r] * (h[r] @ down_e)`` over the
    rows of the tiles in use, float32: the experts' down product and the
    weighted sum over each token's experts in one kernel, so the product
    rows never go back to memory.

      h: (R, d_expert), what :func:`gate_up` returned
      w_down: (held * d_expert, d_model) as the model holds it
      row_of: (R,) int32, the row of ``out`` each padded row adds to
      row_weight: (R,) float32, its weight there (0 on padding)
      n_rows: rows of ``out``
    Returns (n_rows, d_model) float32, zeros where nothing was added
    (all of it when no tile is in use)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, F = h.shape
    D = w_down.shape[1]
    size = jnp.dtype(w_down.dtype).itemsize
    # a column block of the weights, and of ``out`` (twice the room)
    tn = _col_tile(D, max(F * size, 2 * n_rows))

    def kernel(_te, n_ref, row_ref, weight_ref, h_ref, w_ref, o_ref, y_ref):
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(t < n_ref[0])
        def _():
            y_ref[...] = jnp.dot(h_ref[...], w_ref[...],
                                 preferred_element_type=jnp.float32)

            def add(r, _):
                at = pl.ds(row_ref[t * tm + r], 1)
                o_ref[at, :] = o_ref[at, :] \
                    + weight_ref[t * tm + r] * y_ref[pl.ds(r, 1), :]

            jax.lax.fori_loop(0, tm, add, None)

    n = jnp.reshape(n_tiles, (1,)).astype(jnp.int32)
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((n_rows, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # (one step at least: it zeroes the result)
            num_scalar_prefetch=4, grid=(D // tn, jnp.maximum(n[0], 1)),
            in_specs=[
                pl.BlockSpec((tm, F), lambda j, t, *_: (t, 0)),
                pl.BlockSpec((F, tn), lambda j, t, te, *_: (te[t], j))],
            out_specs=pl.BlockSpec((n_rows, tn), lambda j, t, *_: (0, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=_params(2 * F * tn * size + 2 * n_rows * tn * 4
                                + 4 * tm * (F + tn) * 4),
        name="grouped_ffn_down_combine", interpret=interpret)(
            tile_expert, n, row_of, row_weight, h, w_down)
