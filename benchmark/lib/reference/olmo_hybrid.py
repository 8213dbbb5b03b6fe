"""Plain float32 reference of the ``olmo_hybrid`` decoder
(allenai/Olmo-Hybrid-7B ``config.json``): OLMo 2's reordered post-norm
blocks (arXiv:2501.00656, section 3.2), RMSNorm, no bias, an untied
head;

    h = x + RMSNorm(Mix_l(x));  y = h + RMSNorm(MLP(h))

``Mix_l`` is Gated DeltaNet where ``layer_types[l]`` is
``linear_attention`` and multi-head softmax attention where it is
``full_attention``; ``MLP`` a dense gated-SiLU MLP of
``intermediate_size``.  ``l`` is the PUBLISHED index of a layer: the
configuration's ``layers_held`` says which ones are here.

* **Gated DeltaNet** (Yang, Kautz and Hatamizadeh, arXiv:2412.06464, as
  FLA's ``GatedDeltaNet`` builds it), token by token (a ``lax.scan``
  over the sequence, no chunking): ``q = W_q x``, ``k = W_k x``
  (``linear_num_key_heads`` heads of ``linear_key_head_dim``), ``v = W_v
  x`` (heads of ``linear_value_head_dim``); each through a causal
  depthwise convolution of ``linear_conv_kernel_dim`` taps and SiLU;
  ``q = l2norm(q) / sqrt(d_k)``, ``k = l2norm(k)`` a head; one
  log-decay a head ``g = -exp(A_log) softplus(W_a x + dt_bias)``;
  ``beta = 2 sigmoid(W_b x)`` (``linear_allow_neg_eigval``: eigenvalues
  of the transition down to -1, Grazzi et al., arXiv:2411.12537);

      S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  ``out = W_o (RMSNorm_{d_v}(o) * SiLU(W_g x))``, a gate a channel.
* **Full attention**: ``q = RMSNorm(W_q x)`` and ``k = RMSNorm(W_k x)``
  over the whole projection (OLMo 2's QK-norm), ``v = W_v x``; 30 heads
  of ``hidden_size / num_attention_heads`` and as many key/value heads;
  ``softmax(q . k / sqrt(d_head)) v``, causal, no positions
  (``rope_parameters.rope_theta`` null); ``W_o``.  The scores are taken
  in blocks of ``ROW_BLOCK`` query rows.

What the published config does not spell out is listed under
``assumed`` in the configuration's file and mirrored here and in the
program (``mxnet_tpu.gluon.model_zoo.language.HybridDecoderLM`` with
``"gdn"`` and ``"gqa"`` mixers, ``post_norm`` and ``qk_norm="whole"``).
Nothing here imports the program.  Parameters are a flat list in the
order of :func:`param_specs`, in the shapes the program registers them
in (every matrix ``(out, in)``).  ``quant`` (None for the reference) is
the control's hook, on both operands of every matrix product.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 512
L2_EPS = 1e-6
# what the query projection's norm weight is drawn as (``assumed.weights``)
Q_GAIN = 2.0
# the sub-chunk the chunkwise form's least work is counted at
SUB = 64


def sizes(cfg):
    H = cfg["num_attention_heads"]
    Hl = cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return dict(
        D=cfg["hidden_size"], H=H, Hkv=cfg["num_key_value_heads"],
        dh=cfg["hidden_size"] // H, Hl=Hl, dk=dk, dv=dv,
        K=cfg["linear_conv_kernel_dim"], wide=Hl * (2 * dk + dv),
        Fd=cfg["intermediate_size"], V=cfg["vocab_size"])


def layer_kinds(cfg):
    """The mixer (``"gdn"`` or ``"gqa"``) of the layers held, by their
    published index."""
    return ["gdn" if cfg["layer_types"][l] == "linear_attention" else "gqa"
            for l in cfg["layers_held"]]


def block_specs(cfg, h, mix):
    """One block's leaves under the prefix ``h``: the mixer's output norm,
    the mixer, the MLP's output norm, the MLP."""
    z = sizes(cfg)
    D, Hl, dk, dv = z["D"], z["Hl"], z["dk"], z["dv"]
    specs = [(h + "attn_norm_gamma", (D,), "gamma")]
    if mix == "gdn":
        specs += [(h + "proj_q_weight", (Hl * dk, D), "matrix"),
                  (h + "proj_k_weight", (Hl * dk, D), "matrix"),
                  (h + "proj_v_weight", (Hl * dv, D), "matrix"),
                  (h + "conv_weight", (z["K"], z["wide"]), "conv"),
                  (h + "decay_weight", (Hl, D), "matrix"),
                  (h + "decay_a_log", (Hl,), "a_log"),
                  (h + "decay_dt_bias", (Hl,), "dt_bias"),
                  (h + "beta_weight", (Hl, D), "matrix"),
                  (h + "gate_weight", (Hl * dv, D), "matrix"),
                  (h + "o_norm_gamma", (dv,), "gamma"),
                  (h + "attn_out_weight", (D, Hl * dv), "matrix")]
    else:
        H, Hkv, dh = z["H"], z["Hkv"], z["dh"]
        specs += [(h + "proj_q_weight", (H * dh, D), "matrix"),
                  (h + "proj_k_weight", (Hkv * dh, D), "matrix"),
                  (h + "proj_v_weight", (Hkv * dh, D), "matrix"),
                  (h + "q_norm_gamma", (H * dh,), "q_gamma"),
                  (h + "k_norm_gamma", (Hkv * dh,), "gamma"),
                  (h + "attn_out_weight", (D, H * dh), "matrix")]
    return specs + [(h + "ffn_norm_gamma", (D,), "gamma"),
                    (h + "ffn_gate_weight", (z["Fd"], D), "matrix"),
                    (h + "ffn_up_weight", (z["Fd"], D), "matrix"),
                    (h + "ffn_down_weight", (D, z["Fd"]), "matrix")]


def per_block(mix):
    """Leaves of one block in :func:`block_specs`' order."""
    return (12 if mix == "gdn" else 7) + 4


def param_specs(cfg):
    z = sizes(cfg)
    specs = [("embed_weight", (z["V"], z["D"]), "matrix")]
    for i, mix in enumerate(layer_kinds(cfg)):
        specs += block_specs(cfg, "h%d_" % i, mix)
    return specs + [("final_norm_gamma", (z["D"],), "gamma"),
                    ("head_weight", (z["V"], z["D"]), "head")]


def init_leaf(key, shape, kind):
    """``matrix``: normal, std 0.02, rounded to bfloat16 once and kept
    so (the type the program is handed it in); ``head`` the same values
    in float32 (``bf16_mixed`` keeps the head float32); ``conv``: normal,
    std 1 / sqrt(taps), bfloat16; norm weights ones, but the query
    projection's (``q_gamma``) ``Q_GAIN``: under a QK-norm a score is a
    product of two unit-RMS vectors of ``d_head`` over its root, which
    spreads by about 1 whatever the matrices hold, and over thousands of
    positions a full layer would then average its values; with the
    queries twice as large the scores spread by about 2 (as
    ``k-exaone-236b-a23b`` draws them).  ``a_log`` =
    log(uniform(1, 16)) a head and ``dt_bias`` the inverse softplus of a
    step drawn log-uniformly from [0.001, 0.1] a head (Gated DeltaNet's
    own initialisation, as FLA's ``GatedDeltaNet`` draws it)."""
    if kind == "gamma":
        return jnp.ones(shape, F32)
    if kind == "q_gamma":
        return jnp.full(shape, Q_GAIN, F32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
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


def gdn_inputs(cfg, x, p, quant=None):
    """From the block's input x (B, T, D): q, k (B, T, H, dk), v (B, T,
    H, dv), log-decay g (B, T, H), beta (B, T, H), output gate (B, T, H,
    dv), each as the recurrence takes it, and the projections ``[q | k
    | v]`` before the convolution (B, T, wide)."""
    wq, wk, wv, wc, wa, a_log, dt_bias, wb, wgate, _g_o, _wo = p
    z = sizes(cfg)
    H, dk, dv, K = z["Hl"], z["dk"], z["dv"], z["K"]
    B, T, _D = x.shape
    proj = jnp.concatenate([_mm(x, wq, quant), _mm(x, wk, quant),
                            _mm(x, wv, quant)], -1)
    seen = jnp.pad(proj, ((0, 0), (K - 1, 0), (0, 0)))          # causal
    y = jax.nn.silu(sum(seen[:, j:j + T] * wc[j].astype(F32)
                        for j in range(K)))
    q = y[..., :H * dk].reshape(B, T, H, dk)
    k = y[..., H * dk:2 * H * dk].reshape(B, T, H, dk)
    v = y[..., 2 * H * dk:].reshape(B, T, H, dv)

    def l2(a):
        return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    g = -jnp.exp(a_log) * jax.nn.softplus(_mm(x, wa, quant) + dt_bias)
    beta = jax.nn.sigmoid(_mm(x, wb, quant))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    gate = jax.nn.silu(_mm(x, wgate, quant)).reshape(B, T, H, dv)
    return l2(q) / math.sqrt(dk), l2(k), v, g, beta, gate, proj


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence as the paper writes it, a token a step.  q, k (B,
    T, H, dk); v (B, T, H, dv); g, beta (B, T, H).  Returns (o (B, T, H,
    dv), the last state (B, H, dk, dv))."""
    B, _T, H, dk = q.shape
    if state is None:
        state = jnp.zeros((B, H, dk, v.shape[-1]), F32)

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        # exp(g) (I - beta k k^T) s + beta k v^T
        ks = jnp.einsum("bhc,bhcv->bhv", k_t, s, precision=HI)
        s = jnp.exp(g_t)[..., None, None] * (
            s - b_t[..., None, None] * k_t[..., None] * ks[..., None, :]) \
            + b_t[..., None, None] * k_t[..., None] * v_t[..., None, :]
        return s, jnp.einsum("bhc,bhcv->bhv", q_t, s, precision=HI)

    state, o = lax.scan(token, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def gdn(cfg, x, p, quant=None, upto=None):
    """The mixer's output (B, T, D) and, with ``upto``, what the layer
    keeps of a sequence's first ``upto`` positions: the state after them
    (B, H, dk, dv) and the convolution's last taps - 1 inputs (B, K - 1,
    wide).  Past ``upto`` the recurrence is handed ``g = 0`` and ``beta
    = 0``, which leave the state as it is."""
    z = sizes(cfg)
    B, T, _D = x.shape
    q, k, v, g, beta, gate, proj = gdn_inputs(cfg, x, p, quant)
    if upto is not None:
        counts = (jnp.arange(T) < upto)[None, :, None]
        g, beta = jnp.where(counts, g, 0.0), jnp.where(counts, beta, 0.0)
    o, state = delta_rule(q, k, v, g, beta)
    o = _rms(o, p[9], cfg["rms_norm_eps"]) * gate
    out = _mm(o.reshape(B, T, z["Hl"] * z["dv"]), p[10], quant)
    if upto is None:
        return out, None
    seen = jnp.pad(proj, ((0, 0), (z["K"] - 1, 0), (0, 0)))
    return out, (state, lax.dynamic_slice_in_dim(seen, upto, z["K"] - 1, 1))


def attention(cfg, x, p, quant=None, upto=None):
    """The mixer's output (B, T, D) and, with ``upto``, what the layer
    caches a token: ``[K | V]`` (B, T, 2 x kv_heads x d_head), K normed."""
    wq, wk, wv, g_q, g_k, wo = p
    z = sizes(cfg)
    H, Hkv, dh = z["H"], z["Hkv"], z["dh"]
    G = H // Hkv
    B, T, _D = x.shape
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(T)
    q = _rms(_mm(x, wq, quant), g_q, eps).reshape(B, T, Hkv, G, dh)
    k = _rms(_mm(x, wk, quant), g_k, eps)
    v = _mm(x, wv, quant)
    kept = jnp.concatenate([k, v], -1)
    k, v = k.reshape(B, T, Hkv, dh), v.reshape(B, T, Hkv, dh)
    qq, kk = _q(quant, q, k)
    block = min(ROW_BLOCK, T)
    if T % block:
        raise ValueError("%d positions are no multiple of %d" % (T, block))

    def rows(i):
        at = i * block + jnp.arange(block)
        s = jnp.einsum("btkgd,bskd->bkgts", qq[:, at], kk,
                       precision=HI) / math.sqrt(dh)
        s = jnp.where(pos[None, :] <= at[:, None], s, -1e30)
        att, vv = _q(quant, jax.nn.softmax(s, axis=-1), v)
        return jnp.einsum("bkgts,bskd->btkgd", att, vv, precision=HI)

    o = lax.map(rows, jnp.arange(T // block))          # (blocks, B, block, ..)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * dh)
    return _mm(o, wo, quant), None if upto is None else kept


def hidden(cfg, params, tokens, quant=None, upto=None, kept=None):
    """tokens (B, T) int32 -> final-RMSNorm states (B, T, D).  With
    ``upto`` every layer's cache of the first ``upto`` positions is
    appended to ``kept`` (:func:`caches`)."""
    params = list(params)
    eps = cfg["rms_norm_eps"]
    x = params[0][tokens].astype(F32)
    at = 1
    for mix in layer_kinds(cfg):
        p = params[at:at + per_block(mix)]
        at += len(p)
        out, cache = (gdn if mix == "gdn" else attention)(
            cfg, x, p[1:-4], quant, upto)
        x = x + _rms(out, p[0], eps)
        if kept is not None:
            kept.append(cache)
        wg, wu, wd = p[-3:]
        mlp = _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd,
                  quant)
        x = x + _rms(mlp, p[-4], eps)
    return _rms(x, params[-2], eps)


def caches(cfg, params, tokens, upto, quant=None):
    """What every layer held keeps of a sequence's first ``upto``
    positions, as a serving engine's caches hold it once they are fed: a
    GDN layer ``(S (B, H, dk, dv), the convolution's tail (B, K - 1,
    wide))``, a full layer its rows ``[K | V]`` (B, T, 2 x kv_heads x
    d_head), of which the first ``upto`` count."""
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
    D = z["D"]
    if mix == "gdn":                 # q, k, v, decay, beta, gate, out
        Hl, dv = z["Hl"], z["dv"]
        return D * z["wide"] + 2 * D * Hl + D * Hl * dv + Hl * dv * D
    return 2 * D * z["H"] * z["dh"] + 2 * D * z["Hkv"] * z["dh"]


def mlp_params(cfg):
    return 3 * sizes(cfg)["D"] * sizes(cfg)["Fd"]


def serve_flops_per_token(cfg, context):
    """Forward of one position at ``context`` cached positions, the
    algorithm's operations: every mixer's and MLP's matrices, the head;
    a GDN layer's state update and read (decay, k^T S, the rank-one
    write, S^T q: 4 passes of 2 operations over H x dk x dv) and its
    convolution; a full layer's scores and values over the live
    positions (2 x 2 x heads x d_head a position)."""
    z = sizes(cfg)
    total = z["V"] * z["D"]
    per_pos = 0
    for mix in layer_kinds(cfg):
        total += mixer_params(cfg, mix) + mlp_params(cfg)
        if mix == "gdn":
            total += 4 * z["Hl"] * z["dk"] * z["dv"] + z["K"] * z["wide"]
        else:
            per_pos += 4 * z["H"] * z["dh"]
    return 2 * total + per_pos * int(context)


def forward_min_bytes(cfg, live_positions, slots, experts_touched=0):
    """The least bytes one decode forward moves through HBM whatever
    implements it: every weight it multiplies once in the type it is
    stored in (the head float32, the rest bfloat16; no experts: the
    model is dense, and ``experts_touched`` is taken and must be 0); the
    state of the ``slots`` active slots read and written once (float32
    S, the convolution's tail in bfloat16); ``[K | V]`` of the live
    positions once, bfloat16, a full layer."""
    if experts_touched:
        raise ValueError("a dense model touches no experts")
    z = sizes(cfg)
    weights = 4 * z["V"] * z["D"]
    state = rows = 0
    for mix in layer_kinds(cfg):
        weights += 2 * (mixer_params(cfg, mix) + mlp_params(cfg))
        if mix == "gdn":
            weights += 2 * z["K"] * z["wide"]
            state += 2 * (4 * z["Hl"] * z["dk"] * z["dv"]
                          + 2 * (z["K"] - 1) * z["wide"])
        else:
            rows += 2 * 2 * z["Hkv"] * z["dh"]
    return weights + state * slots + rows * live_positions


def gdn_chunk_flops(cfg, tokens):
    """The chunkwise delta rule's least operations over ``tokens``
    positions of one sequence, summed over the GDN layers held, whatever
    implements it, in sub-chunks of ``SUB``: a head's sub-chunk forms the
    lower triangles of ``K K^T`` and ``Q K^T`` (2 dk C^2), inverts its
    unit lower-triangular system (C^3 / 3) and applies it to the keys
    and values (C^2 (dk + dv)); its scan reads the state twice and
    writes it once (6 C dk dv) and adds the sub-chunk's own part (C^2
    dv).  The decay is one scalar a head: its masks are not counted."""
    z = sizes(cfg)
    C, dk, dv = SUB, z["dk"], z["dv"]
    per_token = (2 * dk * C + C * C / 3.0 + C * (dk + dv) + 6 * dk * dv
                 + C * dv)
    layers = layer_kinds(cfg).count("gdn")
    return layers * z["Hl"] * per_token * float(tokens)


def gdn_chunk_bytes(cfg, tokens):
    """The chunkwise delta rule's least bytes over ``tokens`` positions,
    summed over the GDN layers held: q, k, v, g and beta read once and
    the outputs written once, float32 (as the rule is computed), and
    the state read and written once, float32."""
    z = sizes(cfg)
    H, dk, dv = z["Hl"], z["dk"], z["dv"]
    per_layer = 4 * float(tokens) * H * (2 * dk + dv + 2 + dv) \
        + 2 * 4 * H * dk * dv
    return layer_kinds(cfg).count("gdn") * per_layer
