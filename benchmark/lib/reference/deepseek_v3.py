"""Plain float32 reference of the ``deepseek_v3`` decoder as
ai-sage/GigaChat3.1-702B-A36B's ``config.json`` sizes it (DeepSeek-V3,
arXiv:2412.19437, sections 2.1.1, 2.1.2 and 2.2): pre-norm residual
blocks, RMSNorm, no bias, an untied head;

    h = x + MLA(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))

``FFN_l`` is a dense gated-SiLU MLP of ``intermediate_size`` for ``l <
first_k_dense_replace`` and else the expert layer.  ``l`` is the
PUBLISHED index of a layer: the configuration's ``layers_held`` says
which ones are here.

* **MLA with a query latent**, expanded to per-head keys and values over
  the whole sequence (no absorption, no cache): ``c_q = RMSNorm(W_qa
  x)``; ``q = W_qb c_q`` as heads of ``(nope | rope)``; ``[c | k_r] =
  W_kva x``; ``[k_nope | v]_h = W_kvb RMSNorm(c)`` (values of
  ``v_head_dim``, which is not the key's width); rotary positions on
  interleaved pairs of ``q_r`` and of the one ``k_r``, at YaRN's
  frequencies (:func:`yarn_inv_freq`); ``softmax((q_nope . k_nope + q_r
  . k_r) s) v``, causal, ``s = (nope + rope)^-1/2 m^2`` with ``m = 0.1
  mscale_all_dim ln(factor) + 1``; no output gate; ``W_o``.  The scores
  are taken in blocks of ``ROW_BLOCK`` query rows.
* **The expert layer** (section 2.1.2): ``s = sigmoid(W_r x)`` over ALL
  published experts; selection on ``s + b``: ``n_group`` groups side by
  side, a group's score the sum of its two highest ``s + b``, the best
  ``topk_group`` groups kept, the ``num_experts_per_tok`` highest ``s +
  b`` inside them chosen; weights ``s_i / sum_chosen s``
  (``norm_topk_prob``) times ``routed_scaling_factor``; ``y = sum_i w_i
  E_i(x) + E_shared(x)``.  Only the experts ``[experts_first,
  experts_first + n_routed_experts)`` are here: what the others would
  add is left out, as it is in the program.  Every held expert
  multiplies every row, weighted 0 where it was not chosen, in groups of
  ``EXPERT_GROUP``.
* **The multi-token-prediction module** (section 2.2, one): for position
  ``i`` with the trunk's last block output ``h_i`` (before the final
  norm) and the token that follows, ``t_{i+1}``: ``u_i = W_eh
  [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))]``, one more block of the
  trunk's kind over ``u``, ``Head(RMSNorm_mtp(.))`` with the trunk's
  embedding and head: the logits of position ``i + 2``.

What the published config does not spell out is listed under
``assumed`` in the configuration's file and mirrored here and in the
program (``mxnet_tpu.gluon.model_zoo.language.HybridDecoderLM``).
Nothing here imports the program.  Parameters are a flat list in the
order of :func:`param_specs`, in the shapes the program registers them
in (every matrix ``(out, in)``; the routed experts side by side).
``quant`` (None for the reference) is the control's hook, on both
operands of every matrix product.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
EXPERT_GROUP = 4
ROW_BLOCK = 512
# what the query latent's norm weight is drawn as (``assumed.weights``)
Q_GAIN = 2.0


def sizes(cfg):
    pub = cfg["published"]
    return dict(
        D=cfg["hidden_size"], H=cfg["num_attention_heads"],
        ql=cfg["q_lora_rank"], dl=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], Fd=cfg["intermediate_size"],
        F=cfg["moe_intermediate_size"], E=pub["n_routed_experts"],
        held=cfg["n_routed_experts"], first=cfg["experts_first"],
        k=cfg["num_experts_per_tok"], V=cfg["vocab_size"],
        draft=cfg["num_nextn_predict_layers"])


def layer_kinds(cfg):
    """The feed-forward kind of the layers held, by their published
    index (every mixer is MLA)."""
    return ["dense" if l < cfg["first_k_dense_replace"] else "moe"
            for l in cfg["layers_held"]]


def block_specs(cfg, h, ffn):
    """One block's leaves under the prefix ``h``."""
    z = sizes(cfg)
    D, H = z["D"], z["H"]
    specs = [(h + "attn_norm_gamma", (D,), "gamma"),
             (h + "q_down_weight", (z["ql"], D), "matrix"),
             (h + "q_norm_gamma", (z["ql"],), "q_gamma"),
             (h + "proj_q_weight", (H * (z["dn"] + z["dr"]), z["ql"]),
              "matrix"),
             (h + "kv_down_weight", (z["dl"] + z["dr"], D), "matrix"),
             (h + "kv_norm_gamma", (z["dl"],), "gamma"),
             (h + "kv_up_weight", (H * (z["dn"] + z["dv"]), z["dl"]),
              "matrix"),
             (h + "attn_out_weight", (D, H * z["dv"]), "matrix"),
             (h + "ffn_norm_gamma", (D,), "gamma")]
    if ffn == "dense":
        return specs + [(h + "ffn_gate_weight", (z["Fd"], D), "matrix"),
                        (h + "ffn_up_weight", (z["Fd"], D), "matrix"),
                        (h + "ffn_down_weight", (D, z["Fd"]), "matrix")]
    wide = z["held"] * z["F"]
    return specs + [(h + "router_weight", (z["E"], D), "matrix"),
                    (h + "router_bias", (z["E"],), "bias"),
                    (h + "experts_gate_weight", (D, wide), "matrix"),
                    (h + "experts_up_weight", (D, wide), "matrix"),
                    (h + "experts_down_weight", (wide, D), "matrix"),
                    (h + "shared_gate_weight", (z["F"], D), "matrix"),
                    (h + "shared_up_weight", (z["F"], D), "matrix"),
                    (h + "shared_down_weight", (D, z["F"]), "matrix")]


def per_block(ffn):
    """Leaves of one block in :func:`block_specs`' order."""
    return 9 + (3 if ffn == "dense" else 8)


def param_specs(cfg):
    z = sizes(cfg)
    D = z["D"]
    specs = [("embed_weight", (z["V"], D), "matrix")]
    for i, ffn in enumerate(layer_kinds(cfg)):
        specs += block_specs(cfg, "h%d_" % i, ffn)
    specs += [("final_norm_gamma", (D,), "gamma"),
              ("head_weight", (z["V"], D), "head")]
    if z["draft"]:
        specs += [("mtp_hnorm_gamma", (D,), "gamma"),
                  ("mtp_enorm_gamma", (D,), "gamma"),
                  ("mtp_proj_weight", (D, 2 * D), "matrix")]
        specs += block_specs(cfg, "mtp_", layer_kinds(cfg)[-1])
        specs.append(("mtp_final_norm_gamma", (D,), "gamma"))
    return specs


def draft_leaves(cfg):
    """How many of the leaves, the last ones, are the draft module's."""
    if not sizes(cfg)["draft"]:
        return 0
    return 4 + per_block(layer_kinds(cfg)[-1])


def init_leaf(key, shape, kind):
    """``matrix``: normal, std 0.02, rounded to bfloat16 once and kept
    so (the type the program is handed it in); ``head`` the same values
    in float32 (``bf16_mixed`` keeps the head float32); norm weights
    ones, but the query latent's (``q_gamma``) ``Q_GAIN``: every mixer
    here is softmax attention, and at weights of std 0.02 its scores
    over thousands of positions would spread so little that a layer
    averages its values away and adds a hundredth of the residual
    stream (PERF.md, PR 33); with the queries twice as large the scores
    spread by about 3 and a layer adds about a third; the router's
    selection ``bias`` normal, std 0.02 like the matrices, float32:
    non-zero, so that selection and weighting differ."""
    if kind == "gamma":
        return jnp.ones(shape, F32)
    if kind == "q_gamma":
        return jnp.full(shape, Q_GAIN, F32)
    if kind == "bias":
        return 0.02 * jax.random.normal(key, shape, F32)
    w = (0.02 * jax.random.normal(key, shape, F32)).astype(jnp.bfloat16)
    return w.astype(F32) if kind == "head" else w


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _q(quant, *xs):
    return xs if quant is None else tuple(quant(x) for x in xs)


def _mm(x, w, quant):
    """x (..., in) times w (out, in) transposed."""
    x, w = _q(quant, x, w.astype(F32))
    return jnp.matmul(x, w.T, precision=HI)


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg):
    """The ``qk_rope_head_dim / 2`` frequencies the rotary pairs turn
    at, and the factor on cos and sin: ``f_i = theta^(-2i/d)``; ``d(r) =
    d ln(original_max / (2 pi r)) / (2 ln theta)`` is the pair that
    turns ``r`` times over the original context; between ``lo =
    floor(d(beta_fast))`` and ``hi = ceil(d(beta_slow))`` (clipped to
    the pairs there are) a ramp goes from 0 to 1, and the frequency used
    is ``f_i (1 - ramp_i) + f_i / factor * ramp_i``."""
    d, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    sc = cfg["rope_scaling"]
    f = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    if sc is None:
        return jnp.asarray(f, F32), 1.0

    def pair(turns):
        return d * math.log(sc["original_max_position_embeddings"]
                            / (2 * math.pi * turns)) / (2 * math.log(theta))

    lo = min(max(math.floor(pair(sc["beta_fast"])), 0), d // 2 - 1)
    hi = min(max(math.ceil(pair(sc["beta_slow"])), 0), d // 2 - 1)
    ramp = [min(max((i - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
            for i in range(d // 2)]
    inv = [fi * (1 - r) + fi / sc["factor"] * r for fi, r in zip(f, ramp)]
    gain = yarn_mscale(sc["factor"], sc["mscale"]) \
        / yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return jnp.asarray(inv, F32), gain


def score_scale(cfg):
    """``(nope + rope)^-1/2``, times ``m^2`` under YaRN."""
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg["rope_scaling"]
    if sc is not None and sc["mscale_all_dim"]:
        s *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return s


def _rope_pairs(cfg, x, pos):
    """x (B, T, ..., d), pos (T,): interleaved pairs (x[2i], x[2i+1])."""
    d = x.shape[-1]
    inv, gain = yarn_inv_freq(cfg)
    ang = pos.astype(F32)[:, None] * inv                      # (T, d/2)
    ang = ang.reshape((1, ang.shape[0]) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def mla(cfg, n, p, quant=None):
    """The mixer's output (B, T, D) and what the layer caches a token:
    ``[RMSNorm(c) | rope(k_r)]`` (B, T, latent + rope)."""
    wqa, g_q, wq, wdkv, g_kv, wukv, wo = p
    z = sizes(cfg)
    H, dn, dr, dl, dv = z["H"], z["dn"], z["dr"], z["dl"], z["dv"]
    B, T, _D = n.shape
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(T)
    c_q = _rms(_mm(n, wqa, quant), g_q, eps)
    q = _mm(c_q, wq, quant).reshape(B, T, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope_pairs(cfg, q[..., dn:], pos)
    down = _mm(n, wdkv, quant)
    c = _rms(down[..., :dl], g_kv, eps)
    k_r = _rope_pairs(cfg, down[..., dl:], pos)               # (B, T, dr)
    kv = _mm(c, wukv, quant).reshape(B, T, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    # every head's key: its own k_nope beside the one k_r
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, dr))], -1)
    qq = jnp.concatenate([q_n, q_r], -1)
    qq, k = _q(quant, qq, k)
    block = min(ROW_BLOCK, T)
    if T % block:
        raise ValueError("%d positions are no multiple of %d" % (T, block))
    scale = score_scale(cfg)

    def rows(i):
        at = i * block + jnp.arange(block)
        s = jnp.einsum("bthd,bshd->bhts", qq[:, at], k, precision=HI) * scale
        s = jnp.where(pos[None, :] <= at[:, None], s, -1e30)
        att, vv = _q(quant, jax.nn.softmax(s, axis=-1), v)
        return jnp.einsum("bhts,bshd->bthd", att, vv, precision=HI)

    o = lax.map(rows, jnp.arange(T // block))          # (blocks, B, block, ..)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * dv)
    return _mm(o, wo, quant), jnp.concatenate([c, k_r], -1)


def route(cfg, logits, bias):
    """(weight (N, E) of every published expert for every row, 0 where
    it was not chosen; chosen (N, k) indices) from the router's logits
    (N, E)."""
    z = sizes(cfg)
    N, E = logits.shape
    groups, kept = cfg["n_group"], cfg["topk_group"]
    s = jax.nn.sigmoid(logits)
    biased = s + bias
    per_group = biased.reshape(N, groups, E // groups)
    group_score = lax.top_k(per_group, 2)[0].sum(-1)
    best = lax.top_k(group_score, kept)[1]                    # (N, kept)
    open_ = jnp.zeros((N, groups), bool).at[
        jnp.arange(N)[:, None], best].set(True)
    masked = jnp.where(jnp.repeat(open_, E // groups, axis=1), biased,
                       -jnp.inf)
    chosen = lax.top_k(masked, z["k"])[1]                     # (N, k)
    w = jnp.take_along_axis(s, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    weight = jnp.zeros_like(s).at[jnp.arange(N)[:, None], chosen].set(w)
    return weight, chosen


def _gated_mlp(n, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(n, wg, quant)) * _mm(n, wu, quant), wd,
               quant)


def experts(cfg, n, p, quant=None, held=None, shared=True):
    """The expert layer on normed states n (N, D).  ``held`` = (first,
    count) of the routed experts whose matrices ``p`` carries (the
    configuration's own without it); ``shared`` adds the shared expert
    (the share test counts it once)."""
    wr, bias, wg, wu, wd, sg, su, sd = p
    z = sizes(cfg)
    D, F = z["D"], z["F"]
    first, count = held if held is not None else (z["first"], z["held"])
    weight, _chosen = route(cfg, _mm(n, wr, quant), bias)
    out = jnp.zeros_like(n)
    for e0 in range(0, count, EXPERT_GROUP):
        e1 = min(e0 + EXPERT_GROUP, count)
        # the experts lie side by side: held expert e is columns (rows,
        # for the down matrix) [e * F, (e + 1) * F)
        here = slice(e0 * F, e1 * F)
        g, u, d = (wg[:, here].astype(F32).reshape(D, e1 - e0, F),
                   wu[:, here].astype(F32).reshape(D, e1 - e0, F),
                   wd[here].astype(F32).reshape(e1 - e0, F, D))
        nq, gq, uq = _q(quant, n, g, u)
        a = jnp.einsum("nd,def->nef", nq, gq, precision=HI)
        b = jnp.einsum("nd,def->nef", nq, uq, precision=HI)
        hq, dq = _q(quant, jax.nn.silu(a) * b, d)
        out = out + jnp.einsum(
            "nef,efd->nd", hq * weight[:, first + e0:first + e1, None], dq,
            precision=HI)
    if shared:
        out = out + _gated_mlp(n, sg, su, sd, quant)
    return out


def block(cfg, x, p, ffn, quant=None):
    """One block on the stream x (B, T, D): (the stream after it, the
    rows its MLA layer caches)."""
    eps = cfg["rms_norm_eps"]
    B, T, D = x.shape
    out, rows = mla(cfg, _rms(x, p[0], eps), p[1:8], quant)
    x = x + out
    m = _rms(x, p[8], eps)
    if ffn == "dense":
        return x + _gated_mlp(m, *p[9:], quant), rows
    return x + experts(cfg, m.reshape(B * T, D), p[9:],
                       quant).reshape(B, T, D), rows


def trunk(cfg, params, tokens, quant=None, kept=None):
    """tokens (B, T) int32 -> the last block's output (B, T, D), before
    the final norm.  Every layer's cached rows are appended to
    ``kept``."""
    params = list(params)
    x = params[0][tokens].astype(F32)
    at = 1
    for ffn in layer_kinds(cfg):
        p = params[at:at + per_block(ffn)]
        at += len(p)
        x, rows = block(cfg, x, p, ffn, quant)
        if kept is not None:
            kept.append(rows)
    return x


def draft_hidden(cfg, params, tokens, x, quant=None, kept=None):
    """The draft module on the trunk's output ``x`` (B, T, D) of
    ``tokens`` (B, T): its final-normed states (B, T, D), of which
    position ``i`` (fed ``h_i`` and token ``i + 1``) gives the logits of
    position ``i + 2``; the last position is fed token 0 and means
    nothing."""
    params = list(params)
    eps = cfg["rms_norm_eps"]
    p = params[len(params) - draft_leaves(cfg):]
    follow = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    u = _mm(jnp.concatenate(
        [_rms(x, p[0], eps), _rms(params[0][follow].astype(F32), p[1], eps)],
        -1), p[2], quant)
    g, rows = block(cfg, u, p[3:-1], layer_kinds(cfg)[-1], quant)
    if kept is not None:
        kept.append(rows)
    return _rms(g, p[-1], eps)


def _final(cfg, params):
    """(final norm weight, head) of the trunk."""
    params = list(params)
    at = len(params) - draft_leaves(cfg)
    return params[at - 2], params[at - 1]


def caches(cfg, params, tokens, upto, quant=None):
    """What every block held keeps of a sequence, as a serving engine's
    caches hold it once they are fed: the trunk's layers' and then the
    draft module's latent rows (B, T, latent + rope).  Of the trunk's
    the first ``upto`` count; of the draft module's too, and its row
    ``upto - 1`` is of token ``upto`` of ``tokens``: hand that one over
    as well."""
    del upto            # rows are a position each: nothing to cut here
    kept = []
    x = trunk(cfg, params, tokens, quant, kept)
    if sizes(cfg)["draft"]:
        draft_hidden(cfg, params, tokens, x, quant, kept)
    return kept


def logits_at(cfg, params, tokens, positions, quant=None):
    """Logits (B, n, V) at the given positions (n,) of each row only."""
    g, head = _final(cfg, params)
    h = _rms(trunk(cfg, params, tokens, quant), g,
             cfg["rms_norm_eps"])[:, positions]
    return _mm(h, head, quant)


def both_logits_at(cfg, params, tokens, positions, quant=None):
    """(the trunk's logits, the draft module's) at ``positions``, each
    (B, n, V): the trunk's of the token after a position, the draft
    module's of the one after that."""
    g, head = _final(cfg, params)
    x = trunk(cfg, params, tokens, quant)
    h = _rms(x, g, cfg["rms_norm_eps"])[:, positions]
    d = draft_hidden(cfg, params, tokens, x, quant)[:, positions]
    return _mm(h, head, quant), _mm(d, head, quant)


def forward(cfg, params, tokens):
    """Logits of the whole sequence (B, T, V)."""
    return logits_at(cfg, params, tokens, jnp.arange(tokens.shape[1]))


# -- counts, from the configuration alone ------------------------------------

def mixer_params(cfg):
    """Multiplied parameters of one MLA mixer."""
    z = sizes(cfg)
    D, H = z["D"], z["H"]
    return D * z["ql"] + z["ql"] * H * (z["dn"] + z["dr"]) \
        + D * (z["dl"] + z["dr"]) + z["dl"] * H * (z["dn"] + z["dv"]) \
        + H * z["dv"] * D


def ffn_params(cfg, ffn, routed):
    """Multiplied parameters of one feed-forward with ``routed`` routed
    experts counted: the router and the shared expert beside them."""
    z = sizes(cfg)
    if ffn == "dense":
        return 3 * z["D"] * z["Fd"]
    return z["D"] * z["E"] + 3 * z["D"] * z["F"] \
        + routed * 3 * z["D"] * z["F"]


def blocks(cfg):
    """The feed-forward kind of every block a step runs: the trunk's
    layers and then the draft module's."""
    kinds = layer_kinds(cfg)
    return kinds + kinds[-1:] * sizes(cfg)["draft"]


def matmul_params(cfg):
    """Every parameter a forward multiplies by, as held here: mixers,
    feed-forwards with all held experts, the draft module's projection,
    the head."""
    z = sizes(cfg)
    return z["V"] * z["D"] + 2 * z["D"] * z["D"] * z["draft"] + sum(
        mixer_params(cfg) + ffn_params(cfg, ffn, z["held"])
        for ffn in blocks(cfg))


def serve_flops_per_token(cfg, context):
    """Forward of one position at ``context`` cached positions, the
    algorithm's operations: every block's mixer, dense layer, router and
    shared expert and, of a token's ``num_experts_per_tok`` routed
    experts, the share that falls on the experts held here (``held /
    published``); the draft module once a token (its projection, its
    block, the head a second time); MLA's scores and values over the
    live positions, expanded (2 x 2 x heads x (nope + rope | v) a
    position a block).  The second row of a verify step, which a
    rejected draft wastes, is not the algorithm's and is not counted."""
    z = sizes(cfg)
    routed = z["k"] * z["held"] / z["E"]
    total = z["V"] * z["D"] * (1 + z["draft"]) \
        + 2 * z["D"] * z["D"] * z["draft"]
    per_pos = 0
    for ffn in blocks(cfg):
        total += mixer_params(cfg) + ffn_params(cfg, ffn, routed)
        per_pos += 2 * z["H"] * (z["dn"] + z["dr"] + z["dv"])
    return 2 * total + per_pos * int(context)


def forward_min_bytes(cfg, live_positions, slots, experts_touched):
    """The least bytes one verify step moves through HBM whatever
    implements it: every weight it multiplies once in the type it is
    stored in (the head float32, the rest bfloat16), the draft module's
    included, and of the routed experts those that some row of the step
    chose: ``experts_touched`` is the number of (block, held expert)
    pairs at least one row fell on, the draft module's block among them
    (the program counts them); the latent rows of the live positions of
    every block once, bfloat16.  ``slots`` is part of the reader's call
    and counts nothing here: no block keeps state a slot."""
    del slots
    z = sizes(cfg)
    weights = 4 * z["V"] * z["D"] + 2 * 2 * z["D"] * z["D"] * z["draft"]
    rows = 0
    for ffn in blocks(cfg):
        weights += 2 * (mixer_params(cfg) + ffn_params(cfg, ffn, 0))
        rows += 2 * (z["dl"] + z["dr"])
    weights += 2 * 3 * z["D"] * z["F"] * experts_touched
    return weights + rows * live_positions
