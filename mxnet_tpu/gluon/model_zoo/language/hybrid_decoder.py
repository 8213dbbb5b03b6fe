"""A hybrid decoder-only language model: a layer mixes tokens by a
linear-attention recurrence (Kimi Delta Attention, arXiv:2510.26692, or
Gated DeltaNet, arXiv:2412.06464), by softmax attention over a cached
latent (multi-head latent attention, DeepSeek-V2, arXiv:2405.04434,
section 2.1), or by grouped-query softmax attention over cached keys
and values, over the whole sequence or in a sliding window (EXAONE 4.0,
arXiv:2507.11407, section 2), and the feed-forward of a layer is a
dense gated-SiLU MLP or a sparse expert layer with a sigmoid,
group-limited router and a shared expert (DeepSeek-V3,
arXiv:2412.19437, section 2.1.2).

    h = x + Mix_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))

or, with ``post_norm`` (OLMo 2's reordered block, arXiv:2501.00656,
section 3.2: the norm on each sub-layer's output),

    h = x + RMSNorm(Mix_l(x));  y = h + RMSNorm(FFN_l(h))

``mixers[l]`` is ``"kda"``, ``"gdn"``, ``"mla"``, ``"gqa"`` or
``"swa"`` and ``ffns[l]`` ``"dense"`` or ``"moe"``; no bias anywhere,
an untied output head.

**KDA** (``n_heads`` heads of ``d_k`` key and ``d_v`` value channels):
``q, k, v = SiLU(conv(W_qkv x))``, a causal depthwise convolution of
``conv_kernel`` taps a channel; ``q = l2norm(q) / sqrt(d_k)``, ``k =
l2norm(k)`` a head; a log-decay a key channel ``g = lower_bound *
sigmoid(exp(A_log_h) * (W_f x + dt_bias))``; ``beta = sigmoid(W_b x)``
a head; the state ``S`` (d_k, d_v) a head, float32, moved by the gated
delta rule (``ops.gated_delta``: its one-token form for a decode step,
its chunkwise form for a chunk); ``out = W_o (RMSNorm_head(o) *
sigmoid(W_g x))`` with one gate a head.  No positions.  What a KDA
layer carries from one dispatch to the next is per sequence, not per
token: ``S`` and the last ``conv_kernel - 1`` inputs of the convolution.

**GDN** (Gated DeltaNet with one decay a head, as FLA's
``GatedDeltaNet`` builds it): ``q = W_q x``, ``k = W_k x`` (``n_heads``
heads of ``d_k``) and ``v = W_v x`` (of ``d_v``), three products, then
KDA's convolution, SiLU and l2 norms; a log-decay a HEAD ``g =
-exp(A_log_h) softplus(W_a x + dt_bias_h)``, with no bound below;
``beta = sigmoid(W_b x)`` a head, times 2 with ``neg_eigval`` (the
transition ``I - beta k k^T`` then has eigenvalues down to -1, Grazzi
et al., arXiv:2411.12537); the same state and rule; ``out = W_o
(RMSNorm_head(o) * SiLU(W_g x))`` with a gate a CHANNEL.  It keeps what
a KDA layer keeps.

**MLA**: ``q = W_q x`` as heads of ``(d_nope | d_rope)``, or with a
query latent (``q_latent``; DeepSeek-V3, arXiv:2412.19437, section
2.1.1) ``q = W_qb RMSNorm(W_qa x)``; ``[c | k_r] = W_dkv x``
(``d_latent | d_rope``), ``c~ = RMSNorm(c)``; ``[k_nope | v]_h = W_ukv
c~`` with values of ``d_v_mla`` channels a head (``d_v`` without it);
rotary positions (interleaved pairs) on ``q_r`` and on the one ``k_r``
all heads share, at plain frequencies ``theta^(-2i/d_rope)`` or, with
``rope_scaling`` of type ``yarn``, at YaRN's (:func:`yarn_corners`:
frequency ``i`` is ``f_i (1 - ramp_i) + f_i / factor * ramp_i``, the
ramp rising from pair ``lo`` to pair ``hi``);
``softmax((q_nope . k_nope + q_r . k_r) * s) v``, causal, ``s = (d_nope
+ d_rope)^-1/2``, times YaRN's ``m^2`` (``m = 0.1 mscale_all_dim
ln(factor) + 1``) under ``rope_scaling``; the same head-wise sigmoid
gate (``mla_gate``; DeepSeek-V3 has none); ``W_o``.  **Cached a token:
the row ``[c~ | rope(k_r)]``, ``d_latent + d_rope`` values, and nothing
else.**  The up-projection is absorbed: ``q_nope W_uk`` (a query in
the latent space) beside ``rope(q_r)`` attends the cached rows as they
lie and the result goes through ``W_uv``, so no key or value of a
cached position is ever rebuilt, in a decode step or in a chunk (on
the chip a chunk of 512 against 9216 rows takes 46.0 ms absorbed and
48.0 with the rows expanded to per-head keys and values: PERF.md, PR
33).  A chunk attends the cached rows a block at a time up to the rows
its sequence has written, a step all the rows a slot holds
(``ops.attention_rows.cached_rows_in``; PERF.md, PR 38).

**GQA** (``"gqa"``, full) and **SWA** (``"swa"``, windowed): ``q = W_q
x`` as ``n_heads`` heads of ``d_head``, ``k = W_k x`` and ``v = W_v x``
as ``n_kv_heads`` heads; with ``qk_norm`` an RMSNorm with a learned
weight over the ``d_head`` channels of every q and k head, or with
``qk_norm="whole"`` over all of q's and all of k's channels at once
(OLMo 2's); rotate-half
rotary positions at ``theta^(-2i/d_head)`` on the kinds named in
``rotary`` (by default the windowed layers alone: a full layer then
carries no positions at all); ``softmax(q . k * d_head^-1/2) v``,
causal, ``n_heads / n_kv_heads`` query heads sharing a key/value head;
in a ``"swa"`` layer position ``i`` attends ``j`` with ``0 <= i - j <
window``; ``W_o``.  **Cached a token: the row ``[K | V]``, ``2 *
n_kv_heads * d_head`` values, normed and where the layer rotates
rotated.**  A ``"gqa"`` layer keeps every position's row in pages; a
chunk attends the slot's gathered rows a block at a time up to the rows
its sequence has written (``ops.attention_rows.chunk_attention_blocks``),
and a decode or verify step reads each slot's written pages where they
lie in the pool (a Pallas kernel, ``ops.paged_rows_pallas``;
``ops.attention_rows.cached_rows_in``); a
``"swa"`` layer keeps the last rows of a
sequence in a ring a slot and attends them by the positions they hold
(``ops.attention_rows.window_attention_rows``), whatever the sequence's
length.

**The expert layer** is ``parallel.moe.routed_experts`` with
``sigmoid_group_select``; told which experts it holds
(``experts_held``) it routes over all of them and computes its own
experts' part.  The shared expert is the block's own: every token
takes it, whoever holds which routed expert.

**The draft block** (``draft_layers`` = 1; DeepSeek-V3's
multi-token-prediction module, section 2.2): for position ``i`` with
the trunk's last block output ``h_i`` (before the final norm) and the
token that follows, ``t_{i+1}``: ``u_i = W_eh [RMSNorm_h(h_i) ;
RMSNorm_e(Emb(t_{i+1}))]`` (``2 D -> D``), one more block of the kind of
the trunk's last (its MLA or GQA layer caching rows of its own, at
position ``i``), its own final norm, the trunk's embedding and head:
the logits of position ``i + 2``.  :meth:`chunk_forward` hands the trunk's ``h``
back (``extras["hidden"]``) and :meth:`draft_forward` runs the block,
so that a serving engine can feed it the tokens its own sampling chose
in the same program (``generate.PagedGenerationEngine``,
"self-drafting").

The model speaks the chunk protocol of ``mxnet_tpu.generate`` and
declares, a layer, what it caches (``config["layer_caches"]``): a KDA
or GDN layer per-slot state (``S`` and the convolution's tail), an MLA layer
paged rows of ``d_latent + d_rope`` values, a GQA layer paged rows of
``2 * n_kv_heads * d_head`` (of which a step reads each slot's written
pages in place and a chunk multiplies, gathered, the blocks up to its
``start``: ``"attended": "pages"``), an SWA layer a window:
``{"window": (window, 2 * n_kv_heads * d_head)}``, rows a slot in a
ring; the draft block's entry follows the trunk's (``config["draft_layers"]`` says how many there
are).  Parameters are registered in one flat list, layer by layer, the
draft block's after the head; every matrix is stored ``(out, in)``,
the routed experts side by side as ``MoEDecoderLM`` stores them.
``dtype`` is the type the embedding and the matrices are stored in;
norm weights, ``A_log``, ``dt_bias``, the router's selection bias and
the output head are always float32.  A GDN layer's convolution is one
``(conv_kernel, 2 n_heads d_k + n_heads d_v)`` weight over ``[q | k |
v]``, as KDA's is.
"""
from __future__ import annotations

from ...block import HybridBlock
from .moe_decoder import _rms, _rope

__all__ = ["HybridDecoderLM", "yarn_corners", "yarn_mscale"]


def yarn_corners(theta, d_rope, original_max, beta_fast, beta_slow):
    """The pairs between which YaRN's ramp rises (Peng et al.,
    arXiv:2309.00071, as DeepSeek-V3 applies it): pair ``i`` of
    ``d_rope / 2`` turns ``original_max f_i / 2 pi`` times over the
    original context, and ``d(r) = d_rope ln(original_max / (2 pi r)) /
    (2 ln theta)`` is the pair that turns ``r`` times.  Pairs below
    ``lo = floor(d(beta_fast))`` keep their frequency, pairs above
    ``hi = ceil(d(beta_slow))`` are slowed by ``factor``; both clipped
    to ``[0, d_rope / 2 - 1]``."""
    import math

    def pair(turns):
        return d_rope * math.log(original_max / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    last = d_rope // 2 - 1
    lo = min(max(math.floor(pair(beta_fast)), 0), last)
    hi = min(max(math.ceil(pair(beta_slow)), 0), last)
    return lo, hi


def yarn_mscale(factor, mscale):
    """``0.1 mscale ln(factor) + 1`` (1 for no scaling)."""
    import math

    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_pairs(x, pos, theta, yarn=None):
    """Rotary positions over interleaved pairs ``(x[2i], x[2i+1])``.
    x (B, C, ..., d) float32, pos (B, C) int32.  ``yarn`` = ``(factor,
    lo, hi, gain)``: the frequencies of pairs from ``lo`` to ``hi``
    ramp down to ``1 / factor`` of their own, cos and sin times
    ``gain``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if yarn is not None:
        factor, lo, hi, gain = yarn
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo)
                        / max(hi - lo, 1e-3), 0.0, 1.0)
        inv = inv * (1.0 - ramp) + inv / factor * ramp
    ang = pos.astype(jnp.float32)[:, :, None] * inv           # (B, C, d/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None and gain != 1.0:
        cos, sin = cos * gain, sin * gain
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def _mm(a, w):
    """a (..., in) x w (out, in), in the weight's dtype."""
    import jax.numpy as jnp

    return jnp.dot(a.astype(w.dtype), w.T)


def _gated_mlp(x, wg, wu, wd):
    import jax

    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wu), wd)


def _raw(params):
    return [q.data()._data for q in params]


def _delta_rule(q, k, v, g, beta, state, valid, scope):
    """The gated delta rule over a dispatch (``ops.gated_delta``): its
    one-token form for a decode step (a row past ``valid`` keeps its
    state), its chunkwise form for a chunk.  ``g`` (B, C, H, dk) a key
    channel or (B, C, H) a head.  Returns (o (B, C, H, dv), state)."""
    import jax.numpy as jnp

    from ....ops.gated_delta import gated_delta_chunk, gated_delta_step

    if q.shape[1] > 1:
        return gated_delta_chunk(q, k, v, g, beta, state, valid, scope)
    live = valid > 0
    o, state = gated_delta_step(
        q[:, 0], k[:, 0], v[:, 0],
        jnp.where(live.reshape((-1,) + (1,) * (g.ndim - 2)), g[:, 0], 0.0),
        jnp.where(live[:, None], beta[:, 0], 0.0), state, scope)
    return o[:, None], state


def _attend_in_blocks(q, rows, start, scale, s_own, own, keep):
    """The context of absorbed latent attention over the cached rows a
    dispatch's sequences have written and over the chunk's own, the
    cached ones a block of ``ops.attention_rows.CACHE_BLOCK_ROWS`` at a
    time: ``q`` (B, C, H, lanes) the queries in the latent space (zeros
    under the rows' zero lanes), ``rows`` (B, S, lanes) the cache, of
    which a sequence attends positions ``< start_b``; ``s_own`` (B, H,
    C, C) float32 the chunk's own scores, scaled and causally masked,
    ``own`` (B, C, >= keep) its rows.  Returns (B, C, H, keep) float32.

    The softmax runs online (a running maximum, denominator and
    context, float32), started from the chunk's own block, whose
    maximum is finite: every query attends at least itself.  The loop's
    trip count is ``ceil(max(start) / block)``, computed by the program:
    a block past the longest sequence's rows is never read, one partly
    live (and every block of a shorter sequence of the dispatch) is
    masked by ``s < start_b`` as the whole-``S`` products mask.  The
    last block of a cache that is not whole blocks is read ending at
    ``S`` and its rows under the block's own first are masked."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention_rows import cache_block_rows

    f32, act = jnp.float32, rows.dtype
    B, S, lanes = rows.shape
    K = cache_block_rows(S)

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, preferred_element_type=f32)

    m = s_own.max(-1, keepdims=True)                        # (B, H, C, 1)
    e = jnp.exp(s_own - m)
    den = e.sum(-1, keepdims=True)
    ctx = dot("bhcs,bsw->bhcw", e.astype(act), own[..., :keep])

    def block(j, carry):
        m, den, ctx = carry
        first = j * K
        at = jnp.minimum(first, S - K)
        blk = jax.lax.dynamic_slice(rows, (0, at, 0), (B, K, lanes))
        s = dot("bchw,bsw->bhcs", q, blk) * scale           # (B, H, C, K)
        idx = at + jnp.arange(K, dtype=jnp.int32)
        ok = (idx >= first)[None, :] & (idx[None, :] < start[:, None])
        s = jnp.where(ok[:, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        shrink = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)              # a masked score's is exactly 0
        den = den * shrink + e.sum(-1, keepdims=True)
        # (the block's latent lanes are sliced off before the product,
        # where the whole form multiplies the row whole and slices the
        # result: a block is small to copy, and the product then adds
        # into the carried context without a pass over a wider result;
        # on a v5e, GigaChat's chunk at start 512: 1.11 against 1.58 ms
        # a layer, tools/bench_mla_chunk.py, PERF.md PR 38)
        ctx = ctx * shrink \
            + dot("bhcs,bsw->bhcw", e.astype(act), blk[..., :keep])
        return m_new, den, ctx

    blocks = (jnp.max(start) + K - 1) // K
    _m, den, ctx = jax.lax.fori_loop(0, blocks, block, (m, den, ctx))
    return jnp.swapaxes(ctx / den, 1, 2)


class HybridDecoderLM(HybridBlock):
    """Token ids (batch, seq) -> logits (batch, seq, vocab).

    ``mixers`` / ``ffns`` give every layer's kind; the sizes of a kind
    no layer has may be left out.  ``experts_held`` = ``(first,
    count)`` makes this instance hold only those routed experts of
    every expert layer (the chip's share of an expert-parallel
    deployment); the shared expert is always held.  ``q_latent``,
    ``d_v_mla``, ``mla_gate`` and ``rope_scaling`` (a published
    ``rope_scaling`` group of type ``yarn``) shape the MLA layers;
    ``n_kv_heads``, ``d_head``, ``qk_norm`` (True or ``"head"``: a
    head at a time; ``"whole"``: the whole projection) and ``rotary``
    (the kinds among ``"gqa"`` and ``"swa"`` that rotate) the
    grouped-query layers, ``window`` the ``"swa"`` ones; ``d_k``,
    ``d_v``, ``conv_kernel`` and ``neg_eigval`` (``beta`` in [0, 2])
    the ``"gdn"`` ones; ``post_norm`` puts every block's two norms on
    its sub-layers' outputs; ``draft_layers`` = 1 adds the draft block.
    """

    def __init__(self, vocab_size, d_model, mixers, ffns, n_heads,
                 d_k=None, d_v=None, conv_kernel=None, kda_lower_bound=None,
                 d_nope=None, d_rope=None, d_latent=None, d_ff=None,
                 n_experts=None, top_k=None, d_expert=None, n_group=None,
                 topk_group=None, routed_scaling=1.0,
                 norm_topk=True, max_len=262144, rope_theta=6e6,
                 rms_eps=1e-6, experts_held=None, dtype="float32",
                 q_latent=None, d_v_mla=None, mla_gate=True,
                 rope_scaling=None, draft_layers=0, n_kv_heads=None,
                 d_head=None, window=None, qk_norm=True, rotary=("swa",),
                 neg_eigval=False, post_norm=False, **kwargs):
        super().__init__(**kwargs)
        mixers, ffns = list(mixers), list(ffns)
        if len(mixers) != len(ffns) or not mixers:
            raise ValueError("mixers and ffns give one kind a layer each")
        for kinds, known in ((mixers, ("kda", "gdn", "mla", "gqa",
                                       "swa")),
                             (ffns, ("dense", "moe"))):
            if set(kinds) - set(known):
                raise ValueError("layer kinds %r are not of %r"
                                 % (kinds, known))
        needs = {"kda": dict(d_k=d_k, d_v=d_v, conv_kernel=conv_kernel,
                             kda_lower_bound=kda_lower_bound),
                 "gdn": dict(d_k=d_k, d_v=d_v, conv_kernel=conv_kernel),
                 "mla": dict(d_nope=d_nope, d_rope=d_rope, d_latent=d_latent,
                             d_v=d_v_mla if d_v_mla is not None else d_v),
                 "gqa": dict(n_kv_heads=n_kv_heads, d_head=d_head),
                 "swa": dict(n_kv_heads=n_kv_heads, d_head=d_head,
                             window=window),
                 "dense": dict(d_ff=d_ff),
                 "moe": dict(n_experts=n_experts, top_k=top_k,
                             d_expert=d_expert, n_group=n_group,
                             topk_group=topk_group)}
        for kind in set(mixers) | set(ffns):
            lacks = [k for k, v in needs[kind].items() if v is None]
            if lacks:
                raise ValueError("a %r layer needs %s" % (kind, lacks))
        draft_layers = int(draft_layers)
        if draft_layers not in (0, 1) or (
                draft_layers and mixers[-1] not in ("mla", "gqa")):
            raise ValueError(
                "draft_layers is 0 or 1, a block of the kind of the trunk's "
                "last, whose mixer must cache paged rows (mla, gqa), got "
                "%r after %r" % (draft_layers, mixers[-1]))
        if {"gqa", "swa"} & set(mixers) and n_heads % n_kv_heads:
            raise ValueError("n_heads (%d) must divide by n_kv_heads (%d)"
                             % (n_heads, n_kv_heads))
        if qk_norm not in (True, False, "head", "whole"):
            raise ValueError("qk_norm is True or 'head' (a head at a time), "
                             "'whole' (the whole projection) or False, got %r"
                             % (qk_norm,))
        moe = "moe" in ffns
        if moe:
            first, held = experts_held if experts_held is not None \
                else (0, n_experts)
            if not (0 <= first and held >= 1
                    and first + held <= n_experts):
                raise ValueError("experts_held %r outside [0, %d)"
                                 % (experts_held, n_experts))
            if n_experts % n_group:
                raise ValueError(
                    "n_experts (%d) must divide by n_group (%d)"
                    % (n_experts, n_group))
        else:
            first, held = 0, 0
        self._mixers, self._ffns = mixers, ffns
        self._first = int(first)
        self._theta, self._eps = float(rope_theta), float(rms_eps)
        self._lower = None if kda_lower_bound is None \
            else float(kda_lower_bound)
        self._route = dict(n_group=n_group, topk_group=topk_group,
                           scaling=float(routed_scaling),
                           norm_topk=bool(norm_topk))
        D, H, F = d_model, n_heads, d_expert
        K = None if conv_kernel is None else int(conv_kernel)
        # a KDA or GDN layer's q, k, v
        wide = None if d_k is None else H * (2 * d_k + d_v)
        dvm = d_v_mla if d_v_mla is not None else d_v
        self._sizes = dict(H=H, dk=d_k, dv=d_v, K=K, wide=wide,
                           dn=d_nope, dr=d_rope, dl=d_latent, dvm=dvm,
                           ql=q_latent, Hkv=n_kv_heads, dh=d_head,
                           window=None if window is None else int(window))
        self._qk_norm = "head" if qk_norm is True else qk_norm
        self._rotary = tuple(rotary)
        self._neg_eigval, self._post_norm = bool(neg_eigval), bool(post_norm)
        self._gate = bool(mla_gate)
        # the frequency table and the score scale of the MLA layers
        self._yarn, self._scale = None, None
        if "mla" in mixers:
            self._scale = (d_nope + d_rope) ** -0.5
            if rope_scaling is not None:
                kind = rope_scaling.get("rope_type",
                                        rope_scaling.get("type"))
                if kind != "yarn":
                    raise ValueError("rope_scaling of type %r is not built"
                                     % (kind,))
                factor = float(rope_scaling["factor"])
                lo, hi = yarn_corners(
                    self._theta, d_rope,
                    rope_scaling["original_max_position_embeddings"],
                    rope_scaling.get("beta_fast", 32),
                    rope_scaling.get("beta_slow", 1))
                all_dim = rope_scaling.get("mscale_all_dim", 0)
                self._yarn = (factor, lo, hi, yarn_mscale(
                    factor, rope_scaling.get("mscale", 1))
                    / yarn_mscale(factor, all_dim))
                if all_dim:
                    self._scale *= yarn_mscale(factor, all_dim) ** 2
        # what a layer keeps between dispatches: the engine allocates
        # it (generate.PagedGenerationEngine, "layer_caches")
        def keeps(m):
            if m in ("kda", "gdn"):
                return {"state": [((H, d_k, d_v), "float32"),
                                  ((K - 1, wide), None)]}
            if m == "mla":
                return {"rows": d_latent + d_rope}
            kv = 2 * n_kv_heads * d_head        # a position's [K | V]
            if m == "gqa":
                return {"rows": kv, "attended": "pages"}
            return {"window": (int(window), kv)}

        caches = [keeps(m) for m in mixers + mixers[-1:] * draft_layers]
        self._cfg = dict(
            vocab_size=vocab_size, d_model=D, n_heads=H,
            n_layers=len(mixers), max_len=max_len, layer_caches=caches)
        if moe:
            self._cfg.update(n_experts=n_experts, top_k=top_k, d_expert=F,
                             experts_held=(int(first), int(held)))

        def get(name, shape, stored=dtype):
            return self.params.get(name, shape=shape, dtype=stored)

        def block(h, mix, ffn):
            """One block's parameters, registered under the prefix
            ``h``: (mixer norm, mixer, feed-forward norm, feed-forward)."""
            norm_mix = get(h + "attn_norm_gamma", (D,), "float32")
            if mix == "kda":
                mixer = [
                    get(h + "proj_qkv_weight", (wide, D)),
                    get(h + "conv_weight", (K, wide)),
                    get(h + "decay_weight", (H * d_k, D)),
                    get(h + "decay_a_log", (H,), "float32"),
                    get(h + "decay_dt_bias", (H * d_k,), "float32"),
                    get(h + "beta_weight", (H, D)),
                    get(h + "gate_weight", (H, D)),
                    get(h + "o_norm_gamma", (d_v,), "float32"),
                    get(h + "attn_out_weight", (D, H * d_v))]
            elif mix == "gdn":
                mixer = [
                    get(h + "proj_q_weight", (H * d_k, D)),
                    get(h + "proj_k_weight", (H * d_k, D)),
                    get(h + "proj_v_weight", (H * d_v, D)),
                    get(h + "conv_weight", (K, wide)),
                    get(h + "decay_weight", (H, D)),
                    get(h + "decay_a_log", (H,), "float32"),
                    get(h + "decay_dt_bias", (H,), "float32"),
                    get(h + "beta_weight", (H, D)),
                    get(h + "gate_weight", (H * d_v, D)),
                    get(h + "o_norm_gamma", (d_v,), "float32"),
                    get(h + "attn_out_weight", (D, H * d_v))]
            elif mix in ("gqa", "swa"):
                mixer = [get(h + "proj_q_weight", (H * d_head, D)),
                         get(h + "proj_k_weight", (n_kv_heads * d_head, D)),
                         get(h + "proj_v_weight", (n_kv_heads * d_head, D))]
                if self._qk_norm:
                    whole = self._qk_norm == "whole"
                    mixer += [get(h + "q_norm_gamma",
                                  (H * d_head if whole else d_head,),
                                  "float32"),
                              get(h + "k_norm_gamma",
                                  (n_kv_heads * d_head if whole else d_head,),
                                  "float32")]
                mixer.append(get(h + "attn_out_weight", (D, H * d_head)))
            else:
                q_in = D
                mixer = []
                if q_latent is not None:
                    mixer += [get(h + "q_down_weight", (q_latent, D)),
                              get(h + "q_norm_gamma", (q_latent,),
                                  "float32")]
                    q_in = q_latent
                mixer += [
                    get(h + "proj_q_weight", (H * (d_nope + d_rope), q_in)),
                    get(h + "kv_down_weight", (d_latent + d_rope, D)),
                    get(h + "kv_norm_gamma", (d_latent,), "float32"),
                    get(h + "kv_up_weight", (H * (d_nope + dvm), d_latent))]
                if self._gate:
                    mixer.append(get(h + "gate_weight", (H, D)))
                mixer.append(get(h + "attn_out_weight", (D, H * dvm)))
            norm_ffn = get(h + "ffn_norm_gamma", (D,), "float32")
            if ffn == "dense":
                feed = [get(h + "ffn_gate_weight", (d_ff, D)),
                        get(h + "ffn_up_weight", (d_ff, D)),
                        get(h + "ffn_down_weight", (D, d_ff))]
            else:
                feed = [
                    get(h + "router_weight", (n_experts, D)),
                    get(h + "router_bias", (n_experts,), "float32"),
                    get(h + "experts_gate_weight", (D, held * F)),
                    get(h + "experts_up_weight", (D, held * F)),
                    get(h + "experts_down_weight", (held * F, D)),
                    get(h + "shared_gate_weight", (F, D)),
                    get(h + "shared_up_weight", (F, D)),
                    get(h + "shared_down_weight", (D, F))]
            return norm_mix, mixer, norm_ffn, feed

        with self.name_scope():
            self._embed = get("embed_weight", (vocab_size, D))
            self._layers = [block("h%d_" % i, mix, ffn)
                            for i, (mix, ffn) in enumerate(zip(mixers, ffns))]
            self._final = get("final_norm_gamma", (D,), "float32")
            self._head = get("head_weight", (vocab_size, D), "float32")
            registered = len(self.params.keys())
            self._draft = None
            if draft_layers:
                self._draft = (
                    get("mtp_hnorm_gamma", (D,), "float32"),
                    get("mtp_enorm_gamma", (D,), "float32"),
                    get("mtp_proj_weight", (D, 2 * D)),
                    block("mtp_", mixers[-1], ffns[-1]),
                    get("mtp_final_norm_gamma", (D,), "float32"))
            # the draft block and how many of the parameters, the last
            # registered, are its own
            self._cfg.update(
                draft_layers=draft_layers,
                draft_params=len(self.params.keys()) - registered)

    @property
    def config(self):
        return dict(self._cfg)

    # -- the mixers --------------------------------------------------------
    def _kda(self, n, p, cache, valid):
        """The KDA mixer on normed states ``n`` (B, C, D).  ``cache``
        is ``(S (B, H, dk, dv) float32, tail (B, K-1, wide))``;
        ``valid`` (B,) the leading positions of each row that count.
        Returns (out (B, C, D), (S, tail) after the valid positions).
        Traced under the named scope ``kda.proj`` (the products and the
        convolution's tail); the delta rule opens ``kda.scan``."""
        import jax
        import jax.numpy as jnp

        wqkv, wc, wf, a_log, dt_bias, wb, wgate, g_o, wo = p
        z = self._sizes
        H, dk, dv = z["H"], z["dk"], z["dv"]
        B, C, _D = n.shape
        state, tail = cache
        f32 = jnp.float32
        with jax.named_scope("kda.proj"):
            q, k, v, tail = self._qkv_conv(_mm(n, wqkv), tail, wc, valid)
            gate_in = _mm(n, wf).astype(f32).reshape((B, C, H, dk)) \
                + dt_bias.reshape((H, dk))
            g = self._lower * jax.nn.sigmoid(
                jnp.exp(a_log)[:, None] * gate_in)              # in [lower, 0]
            beta = jax.nn.sigmoid(_mm(n, wb).astype(f32))       # (B, C, H)
        o, state = _delta_rule(q, k, v, g, beta, state, valid, "kda.scan")
        with jax.named_scope("kda.proj"):
            o = _rms(o, g_o, self._eps) \
                * jax.nn.sigmoid(_mm(n, wgate).astype(f32))[..., None]
            return _mm(o.reshape((B, C, H * dv)), wo), (state, tail)

    def _qkv_conv(self, x, tail, wc, valid):
        """A KDA or GDN layer's causal depthwise convolution over the
        projected ``x`` = ``[q | k | v]`` (B, C, wide) after the layer's
        ``tail`` (B, K-1, wide), SiLU, and l2 norms a head (``q`` also
        scaled by ``d_k^-1/2``).  Returns (q, k (B, C, H, dk), v (B, C,
        H, dv), float32; the tail after the valid positions)."""
        import jax
        import jax.numpy as jnp

        z = self._sizes
        H, dk, dv, K = z["H"], z["dk"], z["dv"], z["K"]
        B, C, _w = x.shape
        f32 = jnp.float32
        seen = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        # the tail after this dispatch: the last K-1 inputs that count
        # (with valid = 0 the tail it came with)
        keep = valid[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        tail = jnp.take_along_axis(seen, keep[:, :, None], axis=1)
        y = sum(seen[:, j:j + C].astype(f32) * wc[j].astype(f32)
                for j in range(K))
        y = jax.nn.silu(y)
        q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)
        q = q.reshape((B, C, H, dk))
        k = k.reshape((B, C, H, dk))
        v = v.reshape((B, C, H, dv))

        def l2(a):
            return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True)
                                     + 1e-6)

        return l2(q) * dk ** -0.5, l2(k), v, tail

    def _gdn(self, n, p, cache, valid):
        """The Gated DeltaNet mixer on the states ``n`` (B, C, D), as
        :meth:`_kda` takes them and with what it returns.  Traced under
        the named scope ``gdn.proj`` (the products, the convolution, the
        output norm and gate); the delta rule opens ``gdn.scan``."""
        import jax
        import jax.numpy as jnp

        wq, wk, wv, wc, wa, a_log, dt_bias, wb, wgate, g_o, wo = p
        z = self._sizes
        H, dv = z["H"], z["dv"]
        B, C, _D = n.shape
        state, tail = cache
        f32 = jnp.float32
        with jax.named_scope("gdn.proj"):
            q, k, v, tail = self._qkv_conv(jnp.concatenate(
                [_mm(n, wq), _mm(n, wk), _mm(n, wv)], axis=-1), tail, wc,
                valid)
            # one log-decay a head, <= 0 and unbounded below
            g = -jnp.exp(a_log) * jax.nn.softplus(
                _mm(n, wa).astype(f32) + dt_bias)               # (B, C, H)
            beta = jax.nn.sigmoid(_mm(n, wb).astype(f32))       # (B, C, H)
            if self._neg_eigval:
                beta = 2.0 * beta
        o, state = _delta_rule(q, k, v, g, beta, state, valid, "gdn.scan")
        with jax.named_scope("gdn.proj"):
            o = _rms(o, g_o, self._eps) * jax.nn.silu(
                _mm(n, wgate).astype(f32)).reshape((B, C, H, dv))
            return _mm(o.reshape((B, C, H * dv)), wo), (state, tail)

    def _mla(self, n, p, rows, start, pos):
        """The MLA mixer on normed states ``n`` (B, C, D) at positions
        ``pos`` (B, C).  ``rows`` (B, S, >= dl + dr) are the cached
        positions' ``[c~ | rope(k_r)]`` (those under ``start`` count),
        with whatever zero lanes the pool keeps after them, or None.
        Returns (out (B, C, D), the chunk's rows (B, C, dl+dr)).
        Traced under the named scopes ``attn.proj`` (every product with
        a weight, the rotary positions) and ``attn.core`` (scores,
        softmax and context over the rows).

        How much of ``rows`` is multiplied follows the dispatch's static
        shape (``ops.attention_rows.cached_rows_in``, nothing else): a
        **prefill chunk** (many query positions of one slot) attends
        blocks of cached rows up to its longest ``start``
        (:func:`_attend_in_blocks`: a loop in the one program, its trip
        count computed from ``start``), so it costs the rows its
        sequence has written and not the slot's capacity ``S``; a
        **decode or verify step** (one or two positions of every slot)
        multiplies all ``S`` rows and masks: over 32 slots the longest
        sequence bounds the loop, its products are small and bound by
        the rows' bytes, and a loop a layer a step costs more at its
        edges than the masked rows it would skip (a v5e, GigaChat's
        ``(32, 2)``: 0.88 ms a layer whole, 0.98-1.33 in blocks; PERF.md,
        PR 38).  One mathematics either way: operands in the dtype they
        arrive in, products accumulated in float32, a float32 softmax
        over the cached and the chunk's own positions together."""
        import jax
        import jax.numpy as jnp

        from ....ops.attention_rows import _softmax_pair, cached_rows_in

        z = self._sizes
        H, dn, dr, dl, dv = z["H"], z["dn"], z["dr"], z["dl"], z["dvm"]
        p = list(p)
        wo = p.pop()
        wgate = p.pop() if self._gate else None
        wdkv, g_kv, wukv = p[-3:]
        wq = p[-4]
        B, C, _D = n.shape
        f32, act = jnp.float32, wq.dtype
        with jax.named_scope("attn.proj"):
            if z["ql"] is not None:       # the query's own latent, normed
                wqa, g_q = p[:2]
                n_q = _rms(_mm(n, wqa), g_q, self._eps).astype(act)
            else:
                n_q = n
            q = _mm(n_q, wq).reshape((B, C, H, dn + dr))
            q_nope = q[..., :dn]
            q_r = _rope_pairs(q[..., dn:].astype(f32), pos, self._theta,
                              self._yarn).astype(act)
            down = _mm(n, wdkv)                             # (B, C, dl+dr)
            c = _rms(down[..., :dl], g_kv, self._eps).astype(act)
            k_r = _rope_pairs(down[..., dl:].astype(f32), pos, self._theta,
                              self._yarn).astype(act)
            new = jnp.concatenate([c, k_r], axis=-1)        # (B, C, dl+dr)
            up = wukv.reshape((H, dn + dv, dl))
            w_uk, w_uv = up[:, :dn], up[:, dn:]             # (H, dn|dv, dl)
        scale = self._scale
        causal = jnp.tril(jnp.ones((C, C), bool))[None, None]
        cached = rows is not None
        if cached:
            rows = rows.astype(act)

        def dot(spec, a, b):
            return jnp.einsum(spec, a, b, preferred_element_type=f32)

        # absorbed: the queries go to the latent space and attend the
        # rows as they lie; the row is contracted whole and read whole
        # (the lanes after the latent are dropped from the result), so
        # no slice of the cache is ever copied
        with jax.named_scope("attn.proj"):
            q_lat = dot("bchd,hdl->bchl", q_nope, w_uk).astype(act)
            q_cat = jnp.concatenate([q_lat, q_r], axis=-1)      # (B,C,H,dl+dr)
        with jax.named_scope("attn.core"):
            s_new = dot("bchw,bsw->bhcs", q_cat, new) * scale
            if not cached:
                p_new = jax.nn.softmax(
                    jnp.where(causal, s_new, -1e30), -1).astype(act)
                ctx = dot("bhcs,bsw->bchw", p_new, new)[..., :dl]
            else:
                # (zeros under the pool's zero lanes)
                q_old = jnp.pad(q_cat, [(0, 0)] * 3 + [
                    (0, rows.shape[-1] - dl - dr)])
                if cached_rows_in(C) == "whole":
                    cache_ok = (
                        jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :]
                        < start[:, None])[:, None, None, :]
                    s_old = dot("bchw,bsw->bhcs", q_old, rows) * scale
                    p_old, p_new = _softmax_pair(s_old, s_new, cache_ok,
                                                 causal, act)
                    ctx = dot("bhcs,bsw->bchw", p_old, rows)[..., :dl] \
                        + dot("bhcs,bsw->bchw", p_new, new)[..., :dl]
                else:
                    ctx = _attend_in_blocks(
                        q_old, rows, start, scale,
                        jnp.where(causal, s_new, -1e30), new, dl)
        with jax.named_scope("attn.proj"):
            o = dot("bchl,hdl->bchd", ctx.astype(act), w_uv)
            if wgate is not None:
                o = o * jax.nn.sigmoid(_mm(n, wgate).astype(f32))[..., None]
            return _mm(o.reshape((B, C, H * dv)), wo), new

    def _gqa(self, n, p, kept, start, pos, mix, attended=None):
        """The grouped-query mixer on normed states ``n`` (B, C, D) at
        positions ``pos`` (B, C): ``mix`` ``"gqa"`` attends ``kept`` (B,
        S, >= 2 w), the cached positions' ``[K | V]`` rows (those under
        ``start`` count), gathered for a chunk, or for a decode or
        verify step ``ops.paged_rows_pallas.PagedRows``, the pool's
        pages and each slot's page table, whose written pages are read
        where they lie (``ops.attention_rows.paged_attention_rows``);
        ``"swa"`` the ring ``kept`` (B, R, >= 2 w) its sequence's last
        rows lie in, inside the window; None: a whole sequence from
        nothing.  Returns (out (B, C, D), the chunk's rows (B, C, 2 w)).
        Traced under the named scopes ``attn.proj`` (the products with a
        weight, the norms of q and k, the rotary positions) and
        ``attn.core`` (scores, softmax and context over a slot's rows,
        gathered or in place) or ``attn.window`` (the same over a
        ring).

        How much of the gathered rows a ``"gqa"`` layer multiplies
        follows the dispatch's static shape and what the layer declares
        (``attended``; ``ops.attention_rows.cached_rows_in``): a
        **prefill chunk** attends blocks of cached rows up to its longest
        ``start`` (``ops.attention_rows.chunk_attention_blocks``, a loop
        whose trip count the program computes), so it costs the rows its
        sequence has written and not the slot's capacity; a layer
        declared ``"whole"``, and a step handed gathered rows, multiply
        all ``S`` and mask (``chunk_attention_rows``)."""
        import jax
        import jax.numpy as jnp

        from ....ops.attention_rows import (cached_rows_in,
                                            chunk_attention_blocks,
                                            chunk_attention_rows,
                                            paged_attention_rows,
                                            window_attention_rows)
        from ....ops.paged_rows_pallas import PagedRows

        z = self._sizes
        H, Hkv, dh = z["H"], z["Hkv"], z["dh"]
        wq, wk, wv = p[:3]
        wo = p[-1]
        B, C, _D = n.shape
        f32, act = jnp.float32, wq.dtype
        with jax.named_scope("attn.proj"):
            q = _mm(n, wq).reshape((B, C, H, dh)).astype(f32)
            k = _mm(n, wk).reshape((B, C, Hkv, dh)).astype(f32)
            v = _mm(n, wv)
            if self._qk_norm == "whole":      # over all of q's channels
                q = _rms(q.reshape((B, C, H * dh)), p[3], self._eps) \
                    .reshape((B, C, H, dh))
                k = _rms(k.reshape((B, C, Hkv * dh)), p[4], self._eps) \
                    .reshape((B, C, Hkv, dh))
            elif self._qk_norm:
                q, k = _rms(q, p[3], self._eps), _rms(k, p[4], self._eps)
            if mix in self._rotary:
                q, k = _rope(q, pos, self._theta), _rope(k, pos, self._theta)
            q = q.astype(act).reshape((B, C, H * dh))
            k = k.astype(act).reshape((B, C, Hkv * dh))
            new = jnp.concatenate([k, v.astype(act)], axis=-1)
        w = Hkv * dh
        if isinstance(kept, PagedRows):     # a step: its pages in place
            ctx = paged_attention_rows(q, k, new[..., w:], kept, H, Hkv)
            with jax.named_scope("attn.proj"):
                return _mm(ctx, wo), new
        if kept is None:        # one row no query attends
            kept = jnp.zeros((B, 1, 2 * w), act)
        kept = kept.astype(act)
        if mix == "swa":
            ctx = window_attention_rows(q, k, new[..., w:], kept, start,
                                        z["window"], H, Hkv)
        elif cached_rows_in(C, attended) == "blocks":
            ctx = chunk_attention_blocks(q, k, new[..., w:], kept, start,
                                         H, Hkv)
        else:
            ctx = chunk_attention_rows(q, k, new[..., w:], kept[..., :w],
                                       kept[..., w:2 * w], start, H, Hkv)
        with jax.named_scope("attn.proj"):
            return _mm(ctx, wo), new

    def _moe(self, m, p):
        """The expert layer on normed states ``m`` (N, D): (the held
        routed experts' part + the shared expert, counts (E,))."""
        from ....parallel.moe import routed_experts, sigmoid_group_select

        import jax

        wr, bias, wg, wu, wd, sg, su, sd = p
        c = self._cfg
        y, counts = routed_experts(
            m, wr.T, wg, wu, wd, c["top_k"], c["d_expert"],
            first=self._first,
            select=sigmoid_group_select(bias, **self._route))
        with jax.named_scope("ffn"):
            return y + _gated_mlp(m, sg, su, sd).astype(y.dtype), counts

    # -- the one forward ---------------------------------------------------

    def _block(self, x, params, mix, ffn, cache, start, valid, pos,
               attended=None):
        """One block on the stream ``x`` (B, C, D): (the stream after
        it, what its mixer keeps, its expert layer's counts or None).
        ``cache`` None: a whole sequence from nothing; ``attended`` what
        the layer declares in ``layer_caches``.  Every part is traced
        under a named scope (``kda.proj``, ``kda.scan``,
        ``gdn.proj``, ``gdn.scan``, ``attn.proj``, ``attn.core``,
        ``attn.window``, ``ffn``, ``experts.route``, ``experts.ffn``),
        all layers' work under the one name, which
        ``profiler.device_table`` reads a device trace by; a norm under
        its sub-layer's, before it or, with ``post_norm``, after it."""
        import jax
        import jax.numpy as jnp

        z = self._sizes
        B, C, _D = x.shape
        act = x.dtype
        norm_mix, mixer, norm_ffn, feed = params
        proj = {"kda": "kda.proj", "gdn": "gdn.proj"}.get(mix, "attn.proj")
        post = self._post_norm
        if post:
            n = x
        else:
            with jax.named_scope(proj):
                n = _rms(x, norm_mix.data()._data, self._eps).astype(act)
        if mix in ("kda", "gdn"):
            if cache is None:
                cache = (
                    jnp.zeros((B, z["H"], z["dk"], z["dv"]), jnp.float32),
                    jnp.zeros((B, z["K"] - 1, z["wide"]), act))
            out, kept = (self._kda if mix == "kda" else self._gdn)(
                n, _raw(mixer), cache, valid)
        elif mix == "mla":
            out, kept = self._mla(n, _raw(mixer), cache, start, pos)
        else:
            out, kept = self._gqa(n, _raw(mixer), cache, start, pos, mix,
                                  attended)
        if post:
            with jax.named_scope(proj):
                out = _rms(out, norm_mix.data()._data, self._eps)
        x = x + out.astype(act)
        if post:
            m = x
        else:
            with jax.named_scope("ffn"):
                m = _rms(x, norm_ffn.data()._data, self._eps).astype(act)
        counts = None
        if ffn == "dense":
            with jax.named_scope("ffn"):
                y = _gated_mlp(m, *_raw(feed))
        else:
            y, counts = self._moe(m.reshape((B * C, -1)), _raw(feed))
            y = y.reshape((B, C, -1))
        if post:
            with jax.named_scope("ffn"):
                y = _rms(y, norm_ffn.data()._data, self._eps)
        return x + y.astype(act), kept, counts

    def _run(self, tokens, caches, start, valid):
        """tokens (B, C) int; caches a list with, a layer, its state
        ``(S, tail)`` (KDA, GDN), its cached rows (B, S, lanes) (MLA, GQA)
        or its ring (B, R, lanes) (SWA), or None (a whole sequence from
        nothing); start, valid (B,) int32.
        Returns (logits raw (B, C, V), a layer's new state or the
        chunk's new rows, expert load (expert layers, E) int32, the
        last block's output (B, C, D))."""
        import jax
        import jax.numpy as jnp

        C = tokens.shape[1]
        pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)
        with jax.named_scope("embed"):
            x = jnp.take(self._embed.data()._data, tokens, axis=0)
        new, loads = [], []
        kinds = self._cfg["layer_caches"]
        for li, (mix, ffn) in enumerate(zip(self._mixers, self._ffns)):
            x, kept, counts = self._block(
                x, self._layers[li], mix, ffn,
                caches[li] if caches is not None else None, start, valid,
                pos, kinds[li].get("attended"))
            new.append(kept)
            if counts is not None:
                loads.append(counts)
        with jax.named_scope("head"):
            head = self._head.data()._data
            h = _rms(x, self._final.data()._data,
                     self._eps).astype(head.dtype)
            logits = jnp.dot(h, head.T)
        load = jnp.stack(loads) if loads else None
        return logits, new, load, x

    def hybrid_forward(self, F, tokens, **_registered):
        import jax.numpy as jnp

        from ....ndarray import NDArray

        ids = tokens._data.astype(jnp.int32)
        B, T = ids.shape
        logits, _new, _load, _x = self._run(
            ids, None, jnp.zeros((B,), jnp.int32),
            jnp.full((B,), T, jnp.int32))
        return NDArray(logits)

    def chunk_forward(self, tokens, caches, start, valid):
        """C positions a sequence against what each layer cached (the
        chunk protocol of ``generate.PagedGenerationEngine`` for a model
        that declares ``layer_caches``): ``tokens`` raw (B, C) int32 at
        positions ``start_b ..``, of which the first ``valid_b`` count
        (the rest is padding; 0 for a row no one is in).  ``caches[l]``
        is, for a layer with state, the tuple of its arrays ``(B, ...)``
        as the sequence left them, and for a layer with paged rows the
        rows of positions ``< start_b``, (B, S, lanes) with ``lanes``
        the declared width padded with zeros to whole tiles of 128, for
        a layer with a window its ring (B, R, lanes), row ``r`` holding
        the last position under ``start_b`` that is ``r`` modulo ``R``
        (entries past the trunk's layers, the draft block's, are left
        alone).
        Returns
        ``(logits NDArray (B, C, V), a list with, a layer, the state
        after the valid positions or the chunk's rows (B, C, width; a
        windowed layer's too: the engine puts them in the ring),
        {"expert_load": (expert layers, experts) int32})``; a model
        with a draft block adds ``"hidden"``, the last block's output
        (B, C, D), which :meth:`draft_forward` takes."""
        import jax.numpy as jnp

        from ....ndarray import NDArray

        logits, new, load, x = self._run(
            tokens.astype(jnp.int32), caches, start.astype(jnp.int32),
            valid.astype(jnp.int32))
        extras = {} if load is None else {"expert_load": load}
        if self._draft is not None:
            extras["hidden"] = x
        return NDArray(logits), new, extras

    def draft_forward(self, hidden, follow, rows, start, valid):
        """The draft block on the trunk's ``hidden`` (B, C, D) of
        positions ``start_b ..`` and ``follow`` (B, C) int32, the token
        that follows each (position ``i``'s is token ``i + 1``), against
        the block's own cached ``rows`` (as :meth:`chunk_forward` takes
        a layer's; None: a whole sequence from nothing).  Returns
        ``(logits NDArray (B, C, V) of positions i + 2, the chunk's rows
        (B, C, width), {"expert_load": (1, experts)})``.  Traced under
        the named scope ``draft``, the block's parts nested inside it."""
        import jax
        import jax.numpy as jnp

        from ....ndarray import NDArray

        if self._draft is None:
            raise ValueError("the model was built without a draft block "
                             "(draft_layers=0)")
        g_h, g_e, w_eh, block, g_out = self._draft
        start = start.astype(jnp.int32)
        C = follow.shape[1]
        pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)
        with jax.named_scope("draft"):
            with jax.named_scope("embed"):
                emb = jnp.take(self._embed.data()._data,
                               follow.astype(jnp.int32), axis=0)
            act = emb.dtype
            u = _mm(jnp.concatenate([
                _rms(hidden, g_h.data()._data, self._eps).astype(act),
                _rms(emb, g_e.data()._data, self._eps).astype(act)], -1),
                w_eh.data()._data)
            x, kept, counts = self._block(
                u, block, self._mixers[-1], self._ffns[-1], rows, start,
                valid.astype(jnp.int32), pos,
                self._cfg["layer_caches"][len(self._mixers)].get("attended"))
            with jax.named_scope("head"):
                head = self._head.data()._data
                h = _rms(x, g_out.data()._data,
                         self._eps).astype(head.dtype)
                logits = jnp.dot(h, head.T)
        extras = {} if counts is None else {"expert_load": counts[None]}
        return NDArray(logits), kept, extras
