"""Plain float32 reference of the ``bailing_hybrid`` decoder
(inclusionAI/Ling-3.0-flash ``config.json``): pre-norm residual blocks,
RMSNorm, no bias, an untied head;

    h = x + Mix_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))

``Mix_l`` is multi-head latent attention where ``(l + 1) %
layer_group_size == 0`` and Kimi Delta Attention elsewhere; ``FFN_l`` a
dense gated-SiLU MLP for ``l < first_k_dense_replace`` and else the
expert layer (sigmoid scores, a selection bias, group-limited top k, a
scaling factor, a shared expert).  ``l`` is the PUBLISHED index of a
layer: the configuration's ``layers_held`` says which ones are here.

* **KDA**, token by token (a ``lax.scan`` over the sequence, no
  chunking; arXiv:2510.26692, section 3): ``q, k, v = SiLU(conv(W_qkv
  x))``, a causal depthwise convolution of ``short_conv_kernel_size``
  taps; ``q = l2norm(q) / sqrt(d_k)``, ``k = l2norm(k)`` a head; ``g =
  kda_lower_bound * sigmoid(exp(A_log_h) * (W_f x + dt_bias))`` a key
  channel; ``beta = sigmoid(W_b x)`` a head; ``S_t = (I - beta_t k_t
  k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T
  q_t``; ``out = W_o (RMSNorm_head(o) * sigmoid(W_g x))``, one gate a
  head.
* **MLA**, expanded to per-head keys and values over the whole sequence
  (no absorption, no cache; arXiv:2405.04434, section 2.1, no query
  latent): ``q = W_q x`` as heads of ``(nope | rope)``; ``[c | k_r] =
  W_dkv x``; ``[k_nope | v]_h = W_ukv RMSNorm(c)``; rotary positions on
  interleaved pairs of ``q_r`` and of the one ``k_r``; ``softmax((q_nope
  . k_nope + q_r . k_r) / sqrt(nope + rope)) v``, causal; the same
  head-wise gate; ``W_o``.  The scores are taken in blocks of
  ``ROW_BLOCK`` query rows, so that 9216 positions fit.
* **The expert layer** (arXiv:2412.19437, section 2.1.2): ``s =
  sigmoid(W_r x)`` over ALL published experts; selection on ``s + b``:
  ``n_group`` groups side by side, a group's score the sum of its two
  highest ``s + b``, the best ``topk_group`` groups kept, the
  ``num_experts_per_tok`` highest ``s + b`` inside them chosen; weights
  ``s_i / sum_chosen s`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``y = sum_i w_i E_i(x) + E_shared(x)``.
  Only the experts ``held`` = ``[experts_first, experts_first +
  num_experts)`` are here: what the others would add is left out, as it
  is in the program (the share test adds the shares up).  Every held
  expert multiplies every row, weighted 0 where it was not chosen, in
  groups of ``EXPERT_GROUP`` so that a layer's float32 copy never
  exists whole.

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
EXPERT_GROUP = 16
ROW_BLOCK = 512
L2_EPS = 1e-6


def sizes(cfg):
    H = cfg["num_attention_heads"]
    pub = cfg["published"]
    return dict(
        D=cfg["hidden_size"], H=H, dk=cfg["head_dim"], dv=cfg["head_dim"],
        K=cfg["short_conv_kernel_size"],
        wide=3 * H * cfg["head_dim"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dl=cfg["kv_lora_rank"], dvm=cfg["v_head_dim"],
        Fd=cfg["intermediate_size"], F=cfg["moe_intermediate_size"],
        Fs=cfg["moe_shared_expert_intermediate_size"],
        E=pub["num_experts"], held=cfg["num_experts"],
        first=cfg["experts_first"], k=cfg["num_experts_per_tok"],
        V=cfg["vocab_size"])


def layer_kinds(cfg):
    """[(mixer, ffn)] of the layers held, by their published index."""
    return [("mla" if (l + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if l < cfg["first_k_dense_replace"] else "moe")
            for l in cfg["layers_held"]]


def param_specs(cfg):
    z = sizes(cfg)
    D, H, dk, dv = z["D"], z["H"], z["dk"], z["dv"]
    specs = [("embed_weight", (z["V"], D), "matrix")]
    for i, (mix, ffn) in enumerate(layer_kinds(cfg)):
        h = "h%d_" % i
        specs.append((h + "attn_norm_gamma", (D,), "gamma"))
        if mix == "kda":
            specs += [(h + "proj_qkv_weight", (z["wide"], D), "matrix"),
                      (h + "conv_weight", (z["K"], z["wide"]), "conv"),
                      (h + "decay_weight", (H * dk, D), "matrix"),
                      (h + "decay_a_log", (H,), "a_log"),
                      (h + "decay_dt_bias", (H * dk,), "dt_bias"),
                      (h + "beta_weight", (H, D), "matrix"),
                      (h + "gate_weight", (H, D), "matrix"),
                      (h + "o_norm_gamma", (dv,), "gamma"),
                      (h + "attn_out_weight", (D, H * dv), "matrix")]
        else:
            specs += [(h + "proj_q_weight",
                       (H * (z["dn"] + z["dr"]), D), "matrix"),
                      (h + "kv_down_weight", (z["dl"] + z["dr"], D),
                       "matrix"),
                      (h + "kv_norm_gamma", (z["dl"],), "gamma"),
                      (h + "kv_up_weight",
                       (H * (z["dn"] + z["dvm"]), z["dl"]), "matrix"),
                      (h + "gate_weight", (H, D), "matrix"),
                      (h + "attn_out_weight", (D, H * z["dvm"]), "matrix")]
        specs.append((h + "ffn_norm_gamma", (D,), "gamma"))
        if ffn == "dense":
            specs += [(h + "ffn_gate_weight", (z["Fd"], D), "matrix"),
                      (h + "ffn_up_weight", (z["Fd"], D), "matrix"),
                      (h + "ffn_down_weight", (D, z["Fd"]), "matrix")]
        else:
            wide = z["held"] * z["F"]
            specs += [(h + "router_weight", (z["E"], D), "matrix"),
                      (h + "router_bias", (z["E"],), "bias"),
                      (h + "experts_gate_weight", (D, wide), "matrix"),
                      (h + "experts_up_weight", (D, wide), "matrix"),
                      (h + "experts_down_weight", (wide, D), "matrix"),
                      (h + "shared_gate_weight", (z["Fs"], D), "matrix"),
                      (h + "shared_up_weight", (z["Fs"], D), "matrix"),
                      (h + "shared_down_weight", (D, z["Fs"]), "matrix")]
    specs += [("final_norm_gamma", (D,), "gamma"),
              ("head_weight", (z["V"], D), "head")]
    return specs


def per_layer(mix, ffn):
    """Leaves of one layer in :func:`param_specs`' order."""
    return (10 if mix == "kda" else 7) + (4 if ffn == "dense" else 9)


def init_leaf(key, shape, kind):
    """``matrix``: normal, std 0.02, rounded to bfloat16 once and kept
    so (the type the program is handed it in); ``head`` the same values
    in float32 (``bf16_mixed`` keeps the head float32); ``conv``: normal,
    std 1 / sqrt(taps), bfloat16; norm weights ones.  ``a_log`` =
    log(uniform(1, 16)) a head and ``dt_bias`` the inverse softplus of a
    step drawn log-uniformly from [0.001, 0.1] a channel (the KDA
    layer's own initialisation), so that channels decay at rates from
    none to ``exp(kda_lower_bound)`` a token; the router's selection
    ``bias`` normal, std 0.02 like the matrices, float32: non-zero, so
    that selection and weighting differ."""
    if kind == "gamma":
        return jnp.ones(shape, F32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "bias":
        return 0.02 * jax.random.normal(key, shape, F32)
    std = shape[0] ** -0.5 if kind == "conv" else 0.02
    w = (std * jax.random.normal(key, shape, F32)).astype(jnp.bfloat16)
    return w.astype(F32) if kind == "head" else w


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _q(quant, *xs):
    return xs if quant is None else tuple(quant(x) for x in xs)


def _mm(x, w, quant):
    """x (..., in) times w (out, in) transposed."""
    x, w = _q(quant, x, w.astype(F32))
    return jnp.matmul(x, w.T, precision=HI)


def _rope_pairs(x, pos, theta):
    """x (B, T, ..., d), pos (T,): interleaved pairs (x[2i], x[2i+1])."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv                      # (T, d/2)
    ang = ang.reshape((1, ang.shape[0]) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def kda_inputs(cfg, n, p, quant=None):
    """From normed states n (B, T, D): q, k (B, T, H, dk), v (B, T, H,
    dv), log-decay g (B, T, H, dk), beta (B, T, H), output gate (B, T,
    H), each as the recurrence takes it."""
    wqkv, wc, wf, a_log, dt_bias, wb, wgate, _g_o, _wo = p
    z = sizes(cfg)
    H, dk, dv, K = z["H"], z["dk"], z["dv"], z["K"]
    B, T, _D = n.shape
    x = _mm(n, wqkv, quant)
    seen = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))          # causal
    y = sum(seen[:, j:j + T] * wc[j].astype(F32) for j in range(K))
    y = jax.nn.silu(y)
    q = y[..., :H * dk].reshape(B, T, H, dk)
    k = y[..., H * dk:2 * H * dk].reshape(B, T, H, dk)
    v = y[..., 2 * H * dk:].reshape(B, T, H, dv)

    def l2(a):
        return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    gate_in = _mm(n, wf, quant).reshape(B, T, H, dk) \
        + dt_bias.reshape(H, dk)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * gate_in)
    beta = jax.nn.sigmoid(_mm(n, wb, quant))
    return l2(q) / math.sqrt(dk), l2(k), v, g, beta, \
        jax.nn.sigmoid(_mm(n, wgate, quant))


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, a token a step.  q, k, g (B, T, H, dk); v (B, T,
    H, dv); beta (B, T, H).  Returns (o (B, T, H, dv), the last state
    (B, H, dk, dv))."""
    B, _T, H, dk = q.shape
    if state is None:
        state = jnp.zeros((B, H, dk, v.shape[-1]), F32)

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]                       # decay
        # (I - beta k k^T) s + beta k v^T
        s = s + k_t[..., None] * (b_t[..., None] * (
            v_t - jnp.einsum("bhc,bhcv->bhv", k_t, s,
                             precision=HI)))[..., None, :]
        return s, jnp.einsum("bhc,bhcv->bhv", q_t, s, precision=HI)

    state, o = lax.scan(token, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def kda(cfg, n, p, quant=None, upto=None):
    """The mixer's output (B, T, D) and, with ``upto``, what the layer
    keeps of a sequence's first ``upto`` positions: the state after them
    (B, H, dk, dv) and the convolution's last taps - 1 inputs (B, K - 1,
    3 H dk).  Past ``upto`` the recurrence is handed ``g = 0`` and
    ``beta = 0``, which leave the state as it is, so the scan's last
    state is the one asked for (the outputs past ``upto`` mean nothing
    then)."""
    z = sizes(cfg)
    B, T, _D = n.shape
    q, k, v, g, beta, gate = kda_inputs(cfg, n, p, quant)
    if upto is not None:
        counts = jnp.arange(T) < upto
        g = jnp.where(counts[None, :, None, None], g, 0.0)
        beta = jnp.where(counts[None, :, None], beta, 0.0)
    o, state = delta_rule(q, k, v, g, beta)
    o = _rms(o, p[7], cfg["rms_norm_eps"]) * gate[..., None]
    out = _mm(o.reshape(B, T, z["H"] * z["dv"]), p[8], quant)
    if upto is None:
        return out, None
    seen = jnp.pad(_mm(n, p[0], quant), ((0, 0), (z["K"] - 1, 0), (0, 0)))
    return out, (state, lax.dynamic_slice_in_dim(seen, upto, z["K"] - 1, 1))


def mla(cfg, n, p, quant=None, upto=None):
    """The mixer's output (B, T, D) and, with ``upto``, what the layer
    caches a token: ``[RMSNorm(c) | rope(k_r)]`` (B, T, latent + rope)."""
    wq, wdkv, g_kv, wukv, wgate, wo = p
    z = sizes(cfg)
    H, dn, dr, dl, dv = z["H"], z["dn"], z["dr"], z["dl"], z["dvm"]
    B, T, _D = n.shape
    pos = jnp.arange(T)
    q = _mm(n, wq, quant).reshape(B, T, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope_pairs(q[..., dn:], pos, cfg["rope_theta"])
    down = _mm(n, wdkv, quant)
    c = _rms(down[..., :dl], g_kv, cfg["rms_norm_eps"])
    k_r = _rope_pairs(down[..., dl:], pos, cfg["rope_theta"])  # (B, T, dr)
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

    def rows(i):
        at = i * block + jnp.arange(block)
        s = jnp.einsum("bthd,bshd->bhts", qq[:, at], k, precision=HI) \
            / math.sqrt(dn + dr)
        s = jnp.where(pos[None, :] <= at[:, None], s, -1e30)
        att, vv = _q(quant, jax.nn.softmax(s, axis=-1), v)
        return jnp.einsum("bhts,bshd->bthd", att, vv, precision=HI)

    o = lax.map(rows, jnp.arange(T // block))          # (blocks, B, block, ..)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H, dv)
    o = o * jax.nn.sigmoid(_mm(n, wgate, quant))[..., None]
    return _mm(o.reshape(B, T, H * dv), wo, quant), \
        None if upto is None else jnp.concatenate([c, k_r], -1)


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
        # sum_e w_e down_e(h_e), the weight put on h_e: a (rows,
        # experts, hidden) array of every expert's output is 1.5 GB a
        # group at 9216 rows
        out = out + jnp.einsum(
            "nef,efd->nd", hq * weight[:, first + e0:first + e1, None], dq,
            precision=HI)
    if shared:
        out = out + _gated_mlp(n, sg, su, sd, quant)
    return out


def hidden(cfg, params, tokens, quant=None, upto=None, kept=None):
    """tokens (B, T) int32 -> final-RMSNorm states (B, T, D).  With
    ``upto`` every layer's cache of the first ``upto`` positions is
    appended to ``kept`` (:func:`caches`)."""
    params = list(params)
    eps = cfg["rms_norm_eps"]
    x = params[0][tokens].astype(F32)
    B, T, D = x.shape
    at = 1
    for mix, ffn in layer_kinds(cfg):
        p = params[at:at + per_layer(mix, ffn)]
        at += len(p)
        n_mix = 10 if mix == "kda" else 7
        n = _rms(x, p[0], eps)
        out, cache = (kda if mix == "kda" else mla)(
            cfg, n, p[1:n_mix], quant, upto)
        x = x + out
        if kept is not None:
            kept.append(cache)
        m = _rms(x, p[n_mix], eps)
        if ffn == "dense":
            x = x + _gated_mlp(m, *p[n_mix + 1:], quant)
        else:
            x = x + experts(cfg, m.reshape(B * T, D), p[n_mix + 1:],
                            quant).reshape(B, T, D)
    return _rms(x, params[-2], eps)


def caches(cfg, params, tokens, upto, quant=None):
    """What every layer held keeps of a sequence's first ``upto``
    positions, as a serving engine's caches hold it once they are fed:
    a KDA layer ``(S (B, H, dk, dv), the convolution's tail (B, K - 1,
    3 H dk))``, an MLA layer its latent rows (B, T, latent + rope), of
    which the first ``upto`` count."""
    kept = []
    hidden(cfg, params, tokens, quant, upto, kept)
    return kept


def logits_at(cfg, params, tokens, positions, quant=None):
    """Logits (B, n, V) at the given positions (n,) of each row only:
    the head is the largest product, and serving compares a few
    positions."""
    h = hidden(cfg, params, tokens, quant)[:, positions]
    return _mm(h, list(params)[-1], quant)


def forward(cfg, params, tokens):
    """Logits of the whole sequence (B, T, V)."""
    return logits_at(cfg, params, tokens, jnp.arange(tokens.shape[1]))


# -- counts, from the configuration alone ------------------------------------

def mixer_params(cfg, mix):
    """Multiplied parameters of one mixer."""
    z = sizes(cfg)
    D, H = z["D"], z["H"]
    if mix == "kda":                 # qkv, decay gate, beta, gate, out
        return D * z["wide"] + D * H * z["dk"] + 2 * D * H \
            + H * z["dv"] * D
    return D * H * (z["dn"] + z["dr"]) + D * (z["dl"] + z["dr"]) \
        + z["dl"] * H * (z["dn"] + z["dvm"]) + D * H + H * z["dvm"] * D


def ffn_params(cfg, ffn, routed):
    """Multiplied parameters of one feed-forward with ``routed`` routed
    experts counted: the router and the shared expert beside them."""
    z = sizes(cfg)
    if ffn == "dense":
        return 3 * z["D"] * z["Fd"]
    return z["D"] * z["E"] + 3 * z["D"] * z["Fs"] \
        + routed * 3 * z["D"] * z["F"]


def serve_flops_per_token(cfg, context):
    """Forward of one position at ``context`` cached positions, the
    algorithm's operations: every mixer's and dense layer's matrices,
    the router, the shared expert and, of a token's
    ``num_experts_per_tok`` routed experts, the share that falls on the
    experts held here (``held / published``: what this chip computes;
    the others' work is on other chips), the head; a KDA layer's state
    update and read (decay, k^T S, the rank-one write, S^T q: 4 passes
    of 2 operations over H x dk x dv) and its convolution; an MLA
    layer's scores and values over the live positions, expanded (2 x 2
    x heads x (nope + rope | v) a position)."""
    z = sizes(cfg)
    routed = z["k"] * z["held"] / z["E"]
    total = z["V"] * z["D"]
    per_pos = 0
    for mix, ffn in layer_kinds(cfg):
        total += mixer_params(cfg, mix) + ffn_params(cfg, ffn, routed)
        if mix == "kda":
            total += 4 * z["H"] * z["dk"] * z["dv"] + z["K"] * z["wide"]
        else:
            per_pos += 2 * z["H"] * (z["dn"] + z["dr"] + z["dvm"])
    return 2 * total + per_pos * int(context)


def forward_min_bytes(cfg, live_positions, slots, experts_touched):
    """The least bytes one decode forward moves through HBM whatever
    implements it: every weight it multiplies once in the type it is
    stored in (the head float32, the rest bfloat16), and of the routed
    experts those that some row of the step chose:
    ``experts_touched`` is the number of (layer, held expert) pairs at
    least one row fell on, over all expert layers (the program counts
    them; 32 rows of 8 choices over 512 experts fall on about 50 of a
    layer's 128 held, fewer where the selection bias gathers them);
    the state of the ``slots`` active slots read and written once
    (float32 S, the convolution's tail in bfloat16); the latent rows of
    the live positions once, bfloat16."""
    z = sizes(cfg)
    weights = 4 * z["V"] * z["D"]
    state = rows = 0
    for mix, ffn in layer_kinds(cfg):
        weights += 2 * (mixer_params(cfg, mix) + ffn_params(cfg, ffn, 0))
        if mix == "kda":
            weights += 2 * z["K"] * z["wide"]
            state += 2 * (4 * z["H"] * z["dk"] * z["dv"]
                          + 2 * (z["K"] - 1) * z["wide"])
        else:
            rows += 2 * (z["dl"] + z["dr"])
    weights += 2 * 3 * z["D"] * z["F"] * experts_touched
    return weights + state * slots + rows * live_positions
