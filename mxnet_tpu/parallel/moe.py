"""Expert parallelism: mixture-of-experts with all-to-all dispatch.

The reference has NO expert parallelism (SURVEY §2.3) — like ring
attention and the GPipe pipeline, this is a TPU-first capability the
mesh design makes natural: experts live one-per-device along an 'ep'
mesh axis, tokens are routed by a learned gate, exchanged with
`lax.all_to_all` over ICI, processed by the local expert FFN, and
returned by the inverse all_to_all.

Static shapes throughout: each device sends exactly `capacity` tokens
to every expert (over-capacity tokens are dropped, under-capacity slots
are masked padding — the standard switch-routing discipline), so one
compiled program serves every step.

Routing follows the switch-transformer family: ``top_k=1`` is the
Switch layer (gate = raw top-1 probability), ``top_k=2`` the GShard
layer (combine weights renormalized over the chosen pair, second
choices take capacity slots after all first choices).  Both return the
load-balancing auxiliary loss  ``E * sum_e f_e * P_e``  (f_e = fraction
of tokens whose first choice is expert e, P_e = mean router probability
for e, pmean'd over the mesh axis) that training adds to the task loss
to keep the router from collapsing onto few experts.
"""
from __future__ import annotations

import functools

__all__ = ["moe_ffn", "moe_ffn_sharded", "routed_experts",
           "sigmoid_group_select", "expert_row_tile",
           "expert_rows_multiplied"]


def _check_top_k(top_k, n_experts):
    """Loud early validation (make_mesh convention): a bad ``top_k``
    must not surface as an opaque lax.top_k shape error mid-trace."""
    import numpy as np

    if isinstance(top_k, bool) or \
            not isinstance(top_k, (int, np.integer)) or \
            top_k < 1 or top_k > n_experts:
        raise ValueError(
            "moe: top_k must be an int in [1, n_experts=%d], got %r"
            % (n_experts, top_k))


def moe_ffn(x, gate_w, w_in, w_out, axis_name="ep", capacity_factor=1.25,
            top_k=1):
    """Top-k switch FFN over experts sharded along `axis_name`.

    Per-device arguments (inside shard_map/pmap):
      x: (tokens, d_model) this device's token shard
      gate_w: (d_model, n_experts) router weights (replicated)
      w_in: (1, d_model, d_hidden) THIS device's expert up-projection
      w_out: (1, d_hidden, d_model) THIS device's expert down-projection
    Returns ``(out, aux_loss)``:
      out: (tokens, d_model) expert outputs scaled by the gate weight
        (dropped tokens contribute zero, residual-style)
      aux_loss: scalar load-balancing loss, identical on every device.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    _check_top_k(top_k, gate_w.shape[-1])
    n_exp = lax.psum(1, axis_name)
    T, D = x.shape
    capacity = max(1, int(capacity_factor * top_k * T / n_exp))

    # --- route: top_k experts per token
    logits = x @ gate_w                      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_probs, topk_idx = lax.top_k(probs, top_k)   # (T, k)
    if top_k == 1:
        combine = topk_probs                 # Switch: raw probability
    else:
        combine = topk_probs / topk_probs.sum(-1, keepdims=True)

    # --- load-balancing aux loss (Switch eq. 4, global over the axis)
    f_local = jnp.mean(jax.nn.one_hot(topk_idx[:, 0], n_exp,
                                      dtype=probs.dtype), axis=0)
    p_local = jnp.mean(probs, axis=0)
    f = lax.pmean(f_local, axis_name)
    p = lax.pmean(p_local, axis_name)
    aux = n_exp * jnp.sum(f * p)

    # --- capacity slots in rank-priority order: every token's first
    # choice is seated before any second choice (GShard discipline)
    slots, keeps = [], []
    counts = jnp.zeros((n_exp,), jnp.int32)
    for r in range(top_k):
        oh = jax.nn.one_hot(topk_idx[:, r], n_exp, dtype=jnp.int32)
        pos = jnp.cumsum(oh, axis=0) - 1 + counts          # (T, E)
        slot = jnp.take_along_axis(pos, topk_idx[:, r:r + 1],
                                   axis=1)[:, 0]
        counts = counts + oh.sum(axis=0)
        slots.append(slot)
        keeps.append(slot < capacity)

    # --- scatter tokens into (E, capacity, D) send buffers
    send = jnp.zeros((n_exp, capacity, D), x.dtype)
    for r in range(top_k):
        send = send.at[topk_idx[:, r],
                       jnp.clip(slots[r], 0, capacity - 1)].add(
            jnp.where(keeps[r][:, None], x, 0))

    # --- exchange: device i's row e goes to device e (all_to_all over
    # ICI); afterwards this device holds every peer's tokens for ITS
    # expert: (E_src, capacity, D)
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)

    # --- local expert FFN (one matmul pair on the MXU)
    h = jax.nn.relu(jnp.einsum("scd,dh->sch", recv, w_in[0]))
    y = jnp.einsum("sch,hd->scd", h, w_out[0])

    # --- return trip + un-scatter back to token order
    back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)                     # (E, cap, D)
    out = jnp.zeros_like(x)
    for r in range(top_k):
        got = back[topk_idx[:, r], jnp.clip(slots[r], 0, capacity - 1)]
        got = jnp.where(keeps[r][:, None], got, 0)
        out = out + got * combine[:, r:r + 1].astype(out.dtype)
    return out, aux


def moe_ffn_sharded(mesh, x, gate_w, w_in, w_out, axis_name="ep",
                    capacity_factor=1.25, top_k=1):
    """Convenience wrapper: shard tokens and experts over `mesh`.

    x: (total_tokens, d_model) — token dim sharded over axis_name
    w_in: (n_experts, d_model, d_hidden), w_out: (n_experts, d_hidden,
    d_model) — expert dim sharded; gate_w replicated.
    Returns ``(out, aux_loss)`` like :func:`moe_ffn`.

    Declares its mesh consumption: the ``axis_name`` axis (default
    'ep') must exist on ``mesh`` — composing with a dp/fsdp/tp training
    mesh means building ONE mesh carrying all the axes and handing each
    engine its own (loud :func:`mesh.require_axes` failure otherwise,
    not a shard_map placement error three layers deep)."""
    from jax.sharding import PartitionSpec as P

    from .mesh import shard_map, require_axes
    from .. import telemetry as _telemetry

    require_axes(mesh, axis_name, who="moe_ffn_sharded")
    if _telemetry.enabled():
        # dispatch + return all_to_all, each ~ the routed token payload
        # (capacity_factor bounds it; host-side estimate, docs/
        # observability.md "collective bytes")
        _telemetry.COLLECTIVE_BYTES.inc(
            2 * int(x.nbytes * capacity_factor), axis=axis_name,
            op="all_to_all")
    _check_top_k(top_k, gate_w.shape[-1])
    fn = shard_map(
        functools.partial(moe_ffn, axis_name=axis_name,
                          capacity_factor=capacity_factor, top_k=top_k),
        mesh=mesh,
        in_specs=(P(axis_name, None), P(None, None),
                  P(axis_name, None, None), P(axis_name, None, None)),
        out_specs=(P(axis_name, None), P()),
        check_vma=False)
    return fn(x, gate_w, w_in, w_out)


def sigmoid_group_select(bias, n_group, topk_group, scaling=1.0,
                         norm_topk=True):
    """The selection rule of a sigmoid, group-limited router with a
    selection bias (DeepSeek-V3, arXiv:2412.19437, section 2.1.2,
    ``noaux_tc``), for :func:`routed_experts`' ``select``.

    A token's score for an expert is ``s = sigmoid(logit)``.  Who is
    chosen goes by ``s + bias`` (``bias`` (n_experts,) float32, kept
    out of the weights): the experts lie in ``n_group`` groups side by
    side, a group's score is the sum of its two highest ``s + bias``,
    the ``topk_group`` best groups are kept, and the ``top_k`` highest
    ``s + bias`` inside them are the token's experts.  What each adds
    is weighted by its ``s`` alone, over the sum of the chosen ones'
    ``s`` with ``norm_topk``, times ``scaling``."""
    def select(logits, top_k):
        import jax
        import jax.numpy as jnp
        from jax import lax

        T, E = logits.shape
        score = jax.nn.sigmoid(logits)
        biased = score + bias.astype(score.dtype)
        by_group = biased.reshape((T, n_group, E // n_group))
        group = lax.top_k(by_group, 2)[0].sum(-1)          # (T, groups)
        kept = lax.top_k(group, topk_group)[1]             # (T, kept)
        open_ = (kept[:, :, None] == jnp.arange(
            n_group, dtype=kept.dtype)).any(1)             # (T, groups)
        biased = jnp.where(jnp.repeat(open_, E // n_group, axis=1),
                           biased, -jnp.inf)
        top_i = lax.top_k(biased, top_k)[1]
        top_w = jnp.take_along_axis(score, top_i, axis=1)
        if norm_topk:
            top_w = top_w / top_w.sum(-1, keepdims=True)
        return top_w * scaling, top_i

    return select


# rows a tile of the grouped product holds at most and at least: the
# MXU takes up to 128 rows past a weight tile for the price of one, and
# a bfloat16 tile is 16 sublanes
_ROW_TILE_MAX, _ROW_TILE_MIN = 128, 16


def expert_row_tile(pairs, n_experts):
    """The row-tile height of the grouped product for ``pairs`` chosen
    (row, expert) pairs a call over a router of ``n_experts``: twice the
    pairs an expert expects, in whole 16-row sublane tiles, between 16
    and 128.  An expert's rows are padded up to whole tiles, so the
    height trades rows multiplied in vain against experts that take a
    second pass of the MXU over their matrices; static shapes alone
    decide it."""
    want = -(-2 * pairs // n_experts)
    tile = -(-want // _ROW_TILE_MIN) * _ROW_TILE_MIN
    return max(_ROW_TILE_MIN, min(_ROW_TILE_MAX, tile))


def expert_rows_multiplied(counts, tile):
    """The rows the grouped product multiplies for experts that
    ``counts`` (..., experts) rows fell on: each expert's rows padded up
    to whole tiles of ``tile`` rows, summed (NumPy, for the engine's
    spans)."""
    import numpy as np

    counts = np.asarray(counts)
    return int((-(-counts // tile) * tile).sum())


def _plain_grouped_ffn(rows, w_gate, w_up, w_down, tiles, d_expert, tm):
    """The grouped product without a kernel (any platform, any widths):
    ``jax.lax.ragged_dot`` over the experts' matrices seen three
    dimensional, which copies them, so for sizes at which that is
    nothing."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    D, F = rows.shape[1], d_expert
    held = w_down.shape[0] // F
    f32 = jnp.float32
    sizes = (tiles * tm).astype(jnp.int32)

    def by_expert(w):                      # (D, held * F) -> (held, D, F)
        return w.reshape((D, held, F)).transpose((1, 0, 2))

    g = lax.ragged_dot(rows, by_expert(w_gate), sizes,
                       preferred_element_type=f32)
    u = lax.ragged_dot(rows, by_expert(w_up), sizes,
                       preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(rows.dtype)
    return lax.ragged_dot(h, w_down.reshape((held, F, D)), sizes,
                          preferred_element_type=f32)


def _grouped_experts(x, top_p, top_i, counts, w_gate, w_up, w_down,
                     d_expert, first, interpret=False):
    """``sum_k w_k * ffn_{e_k}(x)`` over the held experts among each
    row's chosen ones, float32 (T, D): the chosen pairs sorted by
    expert, laid out in row tiles of one expert each, one grouped
    product a matrix: the Pallas kernels on a TPU where they fit
    (``ops.grouped_ffn_pallas.fits``), else the plain product;
    ``interpret`` runs the kernels under the Pallas interpreter
    wherever (the tests).  A function jitted once: the layers of a
    program, alike in their shapes, share one trace and one lowering of
    it (the kernels' lowering is most of an expert layer's); its named
    scopes, ``experts.route`` round the sort, the tile map and the
    padded rows' gather and ``experts.ffn`` round the grouped product,
    are inside it, so the shared trace carries them."""
    return _jitted_grouped_experts()(
        x, top_p, top_i, counts, w_gate, w_up, w_down, first,
        d_expert=int(d_expert), interpret=bool(interpret))


@functools.lru_cache(maxsize=None)
def _jitted_grouped_experts():
    import jax

    return jax.jit(_grouped_experts_traced,
                   static_argnames=("d_expert", "interpret"))


def _grouped_experts_traced(x, top_p, top_i, counts, w_gate, w_up, w_down,
                            first, d_expert, interpret):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ops import grouped_ffn_pallas as kernels

    T, D = x.shape
    k = top_i.shape[1]
    P, F = T * k, int(d_expert)
    held = w_down.shape[0] // F
    i32, f32 = jnp.int32, jnp.float32
    tm = expert_row_tile(P, counts.shape[0])
    # an expert's rows end in a tile of their own: at most one tile
    # more than the pairs fill for every expert that has any
    n_tiles_max = -(-P // tm) + min(held, P)
    R = n_tiles_max * tm

    with jax.named_scope("experts.route"):
        local = top_i.reshape((P,)).astype(i32) - first
        key = jnp.where((local >= 0) & (local < held), local, held)
        # the pairs by expert, those on experts not held here behind the
        # others, each with its row of ``x`` and its weight
        place = jnp.arange(P, dtype=i32)
        skey, srow, sw = lax.sort(
            (key, place // k, top_p.reshape((P,)).astype(f32)), num_keys=1,
            is_stable=True)
        c = lax.dynamic_slice_in_dim(counts, first, held)     # rows an expert
        tiles = -(-c // tm)
        tile_end = jnp.cumsum(tiles)
        # a sorted pair's padded row: its expert's first tile, then its
        # place among its expert's rows (a pair not held: past the end)
        shift = jnp.concatenate([(tile_end - tiles) * tm - (jnp.cumsum(c) - c),
                                 jnp.full((1,), R, i32)])
        at = place + shift[skey]
        packed = jnp.zeros((R, 2), i32).at[at].set(
            jnp.stack([srow, lax.bitcast_convert_type(sw, i32)], axis=1),
            mode="drop", indices_are_sorted=True, unique_indices=True)
        # (padding rows: a copy of row 0 at weight 0)
        row_of = packed[:, 0]
        row_weight = lax.bitcast_convert_type(packed[:, 1], f32)
        tile_expert = jnp.minimum(
            (tile_end[None, :] <= jnp.arange(n_tiles_max, dtype=i32)[:, None])
            .sum(1), held - 1).astype(i32)
        rows = jnp.take(x, row_of, axis=0, mode="clip")

    with jax.named_scope("experts.ffn"):
        def by_kernel(rows, w_gate, w_up, w_down, tile_expert, tiles, row_of,
                      row_weight):
            n_tiles = jnp.sum(tiles).astype(i32)
            h = kernels.gate_up(rows, w_gate, w_up, tile_expert, n_tiles,
                                F, tm, interpret=interpret)
            return kernels.down_combine(
                h, w_down, tile_expert, n_tiles, row_of, row_weight, T, tm,
                interpret=interpret)

        def plain(rows, w_gate, w_up, w_down, tile_expert, tiles, row_of,
                  row_weight):
            y = _plain_grouped_ffn(rows, w_gate, w_up, w_down, tiles, F, tm)
            return jnp.zeros((T, D), f32).at[row_of].add(
                y * row_weight[:, None])

        args = (rows, w_gate, w_up, w_down, tile_expert, tiles, row_of,
                row_weight)
        if interpret:
            return by_kernel(*args)
        if kernels.fits(D, F, R):
            return lax.platform_dependent(*args, tpu=by_kernel, default=plain)
        return plain(*args)


def routed_experts(x, router_w, w_gate, w_up, w_down, top_k, d_expert,
                   first=0, norm_topk=True, select=None):
    """The part that the experts held here add to a gated top-k expert
    layer, with no capacity and no token dropped: what the serving path
    calls (``gluon.model_zoo.language.MoEDecoderLM``,
    ``HybridDecoderLM``).

    The router is over ALL experts; this caller holds the experts
    ``[first, first + held)`` (``held`` = the matrices' width over
    ``d_expert``) and computes their part of the result for
    the tokens routed to them.  The parts of all holders add up to the
    whole layer's output (on one chip that holds every expert, the part
    is the whole).  **A held expert multiplies only the rows routed to
    it**: the ``tokens * top_k`` chosen (row, expert) pairs are sorted
    by expert (pairs on experts not held here behind the others), each
    held expert's rows padded up to whole row tiles
    (:func:`expert_row_tile`), and one grouped product a matrix runs
    over the tiles in use, so an expert no row chose is neither
    multiplied nor read.  Shapes are static (the pair list, the most
    tiles there can be); how many tiles are in use and which expert
    each is are computed in the program, so the one compiled program
    serves any routing.  On a TPU, at widths of whole lane tiles, the
    product is ``ops.grouped_ffn_pallas``'s kernels, which read an
    expert where it lies in the matrices as handed over; elsewhere
    (the CPU; small test widths) the same tiles go through
    ``jax.lax.ragged_dot``.  The kernels have no gradient.

      x: (tokens, d_model)
      router_w: (d_model, n_experts), every expert's column
      w_gate, w_up: (d_model, held * d_expert), expert by expert
      w_down: (held * d_expert, d_model)
    (two dimensions each: the TPU lays a (d_model, held, d_expert) array
    out in tiles over its last two dimensions, and a program that takes
    it so copies every expert matrix to multiply by it; with
    ``d_expert`` whole lane tiles, expert ``e`` is the column blocks
    ``[e * d_expert, (e + 1) * d_expert)`` of the one and the row block
    ``e`` of the other)
    Returns ``(out, counts)``:
      out: (tokens, d_model), ``sum_e w_e * down_e(silu(gate_e x) *
        up_e x)`` over the held experts among each token's ``top_k``
        (who is chosen, and with what ``w``, is ``select``'s to say:
        ``select(logits (tokens, n_experts) float32, top_k)`` ->
        ``(w, index)``, each (tokens, top_k), as
        :func:`sigmoid_group_select` makes one; without it the ``top_k``
        highest of the router's softmax, over float32, renormalised
        over the chosen ones with ``norm_topk``); operands in ``x``'s
        dtype, products accumulated, weighted by ``w`` and summed over
        a token's experts in float32
      counts: (n_experts,) int32, the tokens routed to each expert of
        the whole layer in this call.
    Traced under the named scopes ``experts.route`` (router, selection,
    sort, tile map, the padded rows' gather) and ``experts.ffn`` (the
    grouped product), which ``profiler.device_table`` reads the device's
    time by.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_exp = router_w.shape[-1]
    _check_top_k(top_k, n_exp)
    f32 = jnp.float32
    with jax.named_scope("experts.route"):
        logits = jnp.dot(x, router_w, preferred_element_type=f32)
        if select is not None:
            top_p, top_i = select(logits, top_k)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            top_p, top_i = lax.top_k(probs, top_k)            # (T, k)
            if norm_topk:
                top_p = top_p / top_p.sum(-1, keepdims=True)
        chosen = top_i[:, :, None] == jnp.arange(n_exp, dtype=top_i.dtype)
        counts = chosen.sum((0, 1)).astype(jnp.int32)         # (E,)
    out = _grouped_experts(x, top_p, top_i, counts, w_gate, w_up, w_down,
                           d_expert, first)
    return out.astype(x.dtype), counts
