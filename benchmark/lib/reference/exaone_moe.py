"""Plain float32 reference of the ``exaone_moe`` decoder as
LGAI-EXAONE/K-EXAONE-236B-A23B's ``config.json`` sizes it (the block of
EXAONE 4.0, arXiv:2507.11407, as K-EXAONE keeps it; DeepSeek-V3's
router and multi-token-prediction module, arXiv:2412.19437, sections
2.1.2 and 2.2): pre-norm residual blocks, RMSNorm, no bias, an untied
head;

    h = x + Attn_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))

``l`` is the PUBLISHED index of a layer: the configuration's
``layers_held`` says which ones are here, ``sliding_windows[l]`` the
window of its attention (0: full) and ``mlp_layer_types[l]`` its
feed-forward.

* **Attention**: ``q = W_q x`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = W_k x`` and ``v = W_v x`` as
  ``num_key_value_heads``; RMSNorm with a learned weight over the
  ``head_dim`` channels of every q and k head (QK-norm); rotate-half
  rotary positions at ``rope_theta^(-2i/head_dim)`` on the layers with
  a window ALONE (a full layer, and the draft module's, carry no
  positions); ``softmax(q . k head_dim^-1/2) v``, float32, causal and,
  with a window ``w``, position ``i`` attending ``j`` with ``0 <= i - j
  < w``; query head ``h`` reads key/value head ``h // (heads /
  kv_heads)``; ``W_o``.  Every layer, windowed or not, is a full score
  matrix under its mask, taken in blocks of ``ROW_BLOCK`` query rows:
  no cache, no ring.
* **The expert layer** (``mlp_layer_types[l] == "sparse"``): ``s =
  sigmoid(W_r x)`` over ALL published experts; selection on ``s + b``
  within ``n_group`` groups of which ``topk_group`` are kept (both 1
  here: every expert is open), the ``num_experts_per_tok`` highest
  chosen; weights ``s_i / sum_chosen s`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``y = sum_i w_i E_i(x) + E_shared(x)``
  with gated-SiLU experts.  Only the experts ``[experts_first,
  experts_first + num_experts)`` are here: what the others would add is
  left out, as it is in the program.  Every held expert multiplies
  every row, weighted 0 where it was not chosen, in groups of
  ``EXPERT_GROUP``.
* **The multi-token-prediction module** (one): for position ``i`` with
  the trunk's last block output ``h_i`` (before the final norm) and the
  token that follows, ``t_{i+1}``: ``u_i = W_eh [RMSNorm_h(h_i) ;
  RMSNorm_e(Emb(t_{i+1}))]``, one more block over ``u`` (attention of
  ``mtp_layer_types[0]``, the expert feed-forward), ``Head(RMSNorm_mtp
  (.))`` with the trunk's embedding and head: the logits of position
  ``i + 2``.

What the published config does not spell out is listed under
``assumed`` in the configuration's file and mirrored here and in the
program (``mxnet_tpu.gluon.model_zoo.language.HybridDecoderLM``).
Nothing here imports the program.  Parameters are a flat list in the
order of :func:`param_specs`, in the shapes the program registers them
in (every matrix ``(out, in)``; the routed experts side by side).
``quant`` (None for the reference) is the control's hook, on both
operands of every matrix product.
"""
import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
EXPERT_GROUP = 4
ROW_BLOCK = 256
# what the query head's norm weight is drawn as (``assumed.weights``)
Q_GAIN = 2.0


def sizes(cfg):
    pub = cfg["published"]
    return dict(
        D=cfg["hidden_size"], H=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
        Fd=cfg["intermediate_size"], F=cfg["moe_intermediate_size"],
        E=pub["num_experts"], held=cfg["num_experts"],
        first=cfg["experts_first"], k=cfg["num_experts_per_tok"],
        V=cfg["vocab_size"], draft=cfg["num_nextn_predict_layers"])


def layer_kinds(cfg):
    """``(window, feed-forward)`` of the layers held, by their published
    index: the window is 0 for a full layer, the feed-forward ``"dense"``
    or ``"moe"``."""
    return [(int(cfg["sliding_windows"][l]),
             "moe" if cfg["mlp_layer_types"][l] == "sparse" else "dense")
            for l in cfg["layers_held"]]


def draft_kind(cfg):
    """The draft module's block: attention of ``mtp_sliding_windows``,
    the expert feed-forward (``assumed.mtp``)."""
    return int(cfg["mtp_sliding_windows"][0]), "moe"


def blocks(cfg):
    """``(window, feed-forward)`` of every block a step runs: the
    trunk's layers and then the draft module's."""
    return layer_kinds(cfg) + [draft_kind(cfg)] * sizes(cfg)["draft"]


def block_specs(cfg, h, ffn):
    """One block's leaves under the prefix ``h``."""
    z = sizes(cfg)
    D, H, Hkv, dh = z["D"], z["H"], z["Hkv"], z["dh"]
    specs = [(h + "attn_norm_gamma", (D,), "gamma"),
             (h + "proj_q_weight", (H * dh, D), "matrix"),
             (h + "proj_k_weight", (Hkv * dh, D), "matrix"),
             (h + "proj_v_weight", (Hkv * dh, D), "matrix"),
             (h + "q_norm_gamma", (dh,), "q_gamma"),
             (h + "k_norm_gamma", (dh,), "gamma"),
             (h + "attn_out_weight", (D, H * dh), "matrix"),
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
    return 8 + (3 if ffn == "dense" else 8)


def param_specs(cfg):
    z = sizes(cfg)
    D = z["D"]
    specs = [("embed_weight", (z["V"], D), "matrix")]
    for i, (_window, ffn) in enumerate(layer_kinds(cfg)):
        specs += block_specs(cfg, "h%d_" % i, ffn)
    specs += [("final_norm_gamma", (D,), "gamma"),
              ("head_weight", (z["V"], D), "head")]
    if z["draft"]:
        specs += [("mtp_hnorm_gamma", (D,), "gamma"),
                  ("mtp_enorm_gamma", (D,), "gamma"),
                  ("mtp_proj_weight", (D, 2 * D), "matrix")]
        specs += block_specs(cfg, "mtp_", draft_kind(cfg)[1])
        specs.append(("mtp_final_norm_gamma", (D,), "gamma"))
    return specs


def draft_leaves(cfg):
    """How many of the leaves, the last ones, are the draft module's."""
    if not sizes(cfg)["draft"]:
        return 0
    return 4 + per_block(draft_kind(cfg)[1])


def init_leaf(key, shape, kind):
    """``matrix``: normal, std 0.02, rounded to bfloat16 once and kept
    so (the type the program is handed it in); ``head`` the same values
    in float32 (``bf16_mixed`` keeps the head float32); norm weights
    ones, but the query heads' (``q_gamma``) ``Q_GAIN``: under QK-norm a
    score is a product of two unit-RMS vectors of ``head_dim`` over its
    root, which spreads by about 1 whatever the matrices hold, and over
    thousands of positions a full layer would then average its values
    to a twentieth of the stream; with the queries twice as large the
    scores spread by about 2 and a full layer attends a few hundred
    positions' worth, a windowed one a handful (``gigachat3.1-702b-a36b``
    draws its query latent's norm the same, PERF.md, PR 35); the
    router's selection ``bias`` normal, std 0.02 like the matrices,
    float32: non-zero, so that selection and weighting differ."""
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


def _rope_half(cfg, x, pos):
    """x (B, T, heads, d), pos (T,): ``x cos + rotate_half(x) sin`` with
    ``rotate_half(x) = [-x2 | x1]`` over the two halves of a head and
    frequency ``i`` of ``d / 2`` at ``theta^(-2i/d)``, both halves
    turning alike."""
    d = x.shape[-1]
    theta = cfg["rope_parameters"]["rope_theta"]
    inv = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)], F32)
    ang = pos.astype(F32)[:, None] * inv                      # (T, d/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(cfg, n, p, window, quant=None):
    """The mixer's output (B, T, D) on normed states ``n`` and what the
    layer caches a token: ``[K | V]``, normed and with a window rotated,
    (B, T, 2 x kv_heads x head_dim)."""
    wq, wk, wv, g_q, g_k, wo = p
    z = sizes(cfg)
    H, Hkv, dh = z["H"], z["Hkv"], z["dh"]
    G = H // Hkv
    B, T, _D = n.shape
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(T)
    q = _rms(_mm(n, wq, quant).reshape(B, T, H, dh), g_q, eps)
    k = _rms(_mm(n, wk, quant).reshape(B, T, Hkv, dh), g_k, eps)
    v = _mm(n, wv, quant).reshape(B, T, Hkv, dh)
    if window:
        q, k = _rope_half(cfg, q, pos), _rope_half(cfg, k, pos)
    kept = jnp.concatenate([k.reshape(B, T, Hkv * dh),
                            v.reshape(B, T, Hkv * dh)], -1)
    qq, kk = _q(quant, q.reshape(B, T, Hkv, G, dh), k)
    block = min(ROW_BLOCK, T)
    if T % block:
        raise ValueError("%d positions are no multiple of %d" % (T, block))
    scale = dh ** -0.5

    def rows(i):
        at = i * block + jnp.arange(block)
        s = jnp.einsum("btkgd,bskd->bkgts", qq[:, at], kk,
                       precision=HI) * scale
        ahead = at[:, None] - pos[None, :]
        ok = (ahead >= 0) & ((ahead < window) if window else True)
        s = jnp.where(ok, s, -1e30)
        att, vv = _q(quant, jax.nn.softmax(s, axis=-1), v)
        return jnp.einsum("bkgts,bskd->btkgd", att, vv, precision=HI)

    o = lax.map(rows, jnp.arange(T // block))          # (blocks, B, block, ..)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * dh)
    return _mm(o, wo, quant), kept


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


def block(cfg, x, p, kind, quant=None):
    """One block on the stream x (B, T, D): (the stream after it, the
    rows its attention caches)."""
    window, ffn = kind
    eps = cfg["rms_norm_eps"]
    B, T, D = x.shape
    out, rows = attention(cfg, _rms(x, p[0], eps), p[1:7], window, quant)
    x = x + out
    m = _rms(x, p[7], eps)
    if ffn == "dense":
        return x + _gated_mlp(m, *p[8:], quant), rows
    return x + experts(cfg, m.reshape(B * T, D), p[8:],
                       quant).reshape(B, T, D), rows


def trunk(cfg, params, tokens, quant=None, kept=None):
    """tokens (B, T) int32 -> the last block's output (B, T, D), before
    the final norm.  Every layer's cached rows are appended to
    ``kept``."""
    params = list(params)
    x = params[0][tokens].astype(F32)
    at = 1
    for kind in layer_kinds(cfg):
        p = params[at:at + per_block(kind[1])]
        at += len(p)
        x, rows = block(cfg, x, p, kind, quant)
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
    g, rows = block(cfg, u, p[3:-1], draft_kind(cfg), quant)
    if kept is not None:
        kept.append(rows)
    return _rms(g, p[-1], eps)


def _final(cfg, params):
    """(final norm weight, head) of the trunk."""
    params = list(params)
    at = len(params) - draft_leaves(cfg)
    return params[at - 2], params[at - 1]


def caches(cfg, params, tokens, upto, quant=None):
    """What every block held keeps of a sequence: the trunk's layers'
    and then the draft module's ``[K | V]`` rows (B, T, 2 x kv_heads x
    head_dim), a row a position.  A windowed layer's are all here too:
    a serving engine keeps the last of them, and says from which
    position on.  Of the draft module's, row ``upto - 1`` is of token
    ``upto`` of ``tokens``: hand that one over as well."""
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
    """Multiplied parameters of one attention layer, windowed or full."""
    z = sizes(cfg)
    return 2 * z["D"] * z["H"] * z["dh"] + 2 * z["D"] * z["Hkv"] * z["dh"]


def ffn_params(cfg, ffn, routed):
    """Multiplied parameters of one feed-forward with ``routed`` routed
    experts counted: the router and the shared expert beside them."""
    z = sizes(cfg)
    if ffn == "dense":
        return 3 * z["D"] * z["Fd"]
    return z["D"] * z["E"] + 3 * z["D"] * z["F"] \
        + routed * 3 * z["D"] * z["F"]


def matmul_params(cfg):
    """Every parameter a forward multiplies by, as held here: mixers,
    feed-forwards with all held experts, the draft module's projection,
    the head."""
    z = sizes(cfg)
    return z["V"] * z["D"] + 2 * z["D"] * z["D"] * z["draft"] + sum(
        mixer_params(cfg) + ffn_params(cfg, ffn, z["held"])
        for _window, ffn in blocks(cfg))


def kv_row(cfg):
    """Values of the row a layer caches a token: ``[K | V]``."""
    z = sizes(cfg)
    return 2 * z["Hkv"] * z["dh"]


def serve_flops_per_token(cfg, context):
    """Forward of one position at ``context`` cached positions, the
    algorithm's operations: every block's mixer, dense layer, router and
    shared expert and, of a token's ``num_experts_per_tok`` routed
    experts, the share that falls on the experts held here (``held /
    published``); the draft module once a token (its projection, its
    block, the head a second time); scores and values over the live
    positions of a full layer and over at most the window's of a
    windowed one (2 x 2 x heads x head_dim a position).  The second row
    of a verify step, which a rejected draft wastes, is not the
    algorithm's and is not counted."""
    z = sizes(cfg)
    routed = z["k"] * z["held"] / z["E"]
    total = z["V"] * z["D"] * (1 + z["draft"]) \
        + 2 * z["D"] * z["D"] * z["draft"]
    attended = 0
    for window, ffn in blocks(cfg):
        total += mixer_params(cfg) + ffn_params(cfg, ffn, routed)
        attended += min(int(context), window) if window else int(context)
    return 2 * total + 4 * z["H"] * z["dh"] * attended


def forward_min_bytes(cfg, live_positions, slots, experts_touched):
    """The least bytes one verify step moves through HBM whatever
    implements it: every weight it multiplies once in the type it is
    stored in (the head float32, the rest bfloat16), the draft module's
    included, and of the routed experts those that some row of the step
    chose: ``experts_touched`` is the number of (block, held expert)
    pairs at least one row fell on, the draft module's block among them
    (the program counts them); ``[K | V]`` of the live positions
    (``live_positions``, summed over the ``slots`` active slots) of
    every full layer once, bfloat16, and of a windowed layer of at most
    its window's positions a slot (the sum is all the reader has: where
    some slots hold fewer positions than the window and others more,
    ``min(live, window x slots)`` counts a little over the least; every
    prompt of the cell's traffic but one in twenty is longer than the
    window)."""
    z = sizes(cfg)
    weights = 4 * z["V"] * z["D"] + 2 * 2 * z["D"] * z["D"] * z["draft"]
    rows = 0
    for window, ffn in blocks(cfg):
        weights += 2 * (mixer_params(cfg) + ffn_params(cfg, ffn, 0))
        rows += 2 * kv_row(cfg) * (
            min(live_positions, window * slots) if window
            else live_positions)
    weights += 2 * 3 * z["D"] * z["F"] * experts_touched
    return weights + rows
