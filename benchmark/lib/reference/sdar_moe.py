"""Plain float32 reference of the ``sdar_moe`` decoder
(JetLM/SDAR-30B-A3B-Chat ``config.json``, ``model_type: sdar_moe``): a
Qwen3-MoE block (RMSNorm, grouped-query attention with per-head QK-norm
and rotate-half rotary positions, a softmax router over all experts with
the top k kept and renormalised, gated-SiLU experts, no shared expert,
no bias anywhere), a final RMSNorm and an untied output head, under a
**block-causal** mask, and generation by diffusion over blocks.

What the published config does not say is assumed here, mirrored by the
program (``mxnet_tpu.gluon.model_zoo.language.MoEDecoderLM`` and
``generate.PagedGenerationEngine``) and listed under ``assumed`` in the
configuration's file:

* QK-norm: an RMSNorm over ``head_dim`` with a learned weight on every
  q and k head before the rotation (the config has no key for it; the
  ``sdar_moe`` block derives from the Qwen3 block, which has it).
* the mask: position i attends j iff ``j // B <= i // B``, for prompt
  and answer alike; ``B = block_length`` (not given: 4).
* generation (noise schedule not given): see :func:`generate`.
* no shift: position i's logits predict position i.
* weights: normal, std 0.02, rounded to bfloat16 once (so that program
  and reference start from the same values); norm weights ones.

Nothing here imports the program.  Parameters are a flat list in the
order of :func:`param_specs`, in the shapes the program registers them
in (every matrix ``(out, in)``; a layer's experts side by side, ``(hidden,
experts x width)`` twice and ``(experts x width, hidden)``); every product runs in float32 under ``highest`` precision, the
experts densely (every expert for every token, weighted by the
renormalised top k, 0 for the others) in groups of ``EXPERT_GROUP`` so
that a layer's float32 copy never exists whole.  ``quant`` (None for the
reference) is the control's hook, on both operands of every matrix
product.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
PER_BLOCK = 12
EXPERT_GROUP = 16
F32 = jnp.float32


def sizes(cfg):
    return dict(
        D=cfg["hidden_size"], L=cfg["num_hidden_layers"],
        Hq=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
        dh=cfg["head_dim"], E=cfg["num_experts"],
        k=cfg["num_experts_per_tok"], F=cfg["moe_intermediate_size"],
        V=cfg["vocab_size"], B=cfg["assumed"]["block_length"])


def param_specs(cfg):
    z = sizes(cfg)
    D, dh, E, F = z["D"], z["dh"], z["E"], z["F"]
    specs = [("embed_weight", (z["V"], D), "matrix")]
    for i in range(z["L"]):
        h = "h%d_" % i
        specs += [(h + "attn_norm_gamma", (D,), "gamma"),
                  (h + "proj_q_weight", (z["Hq"] * dh, D), "matrix"),
                  (h + "proj_k_weight", (z["Hkv"] * dh, D), "matrix"),
                  (h + "proj_v_weight", (z["Hkv"] * dh, D), "matrix"),
                  (h + "q_norm_gamma", (dh,), "gamma"),
                  (h + "k_norm_gamma", (dh,), "gamma"),
                  (h + "attn_out_weight", (D, z["Hq"] * dh), "matrix"),
                  (h + "moe_norm_gamma", (D,), "gamma"),
                  (h + "router_weight", (E, D), "matrix"),
                  (h + "experts_gate_weight", (D, E * F), "matrix"),
                  (h + "experts_up_weight", (D, E * F), "matrix"),
                  (h + "experts_down_weight", (E * F, D), "matrix")]
    specs += [("final_norm_gamma", (D,), "gamma"),
              ("head_weight", (z["V"], D), "head")]
    return specs


def init_leaf(key, shape, kind):
    """Normal, std 0.02, rounded to bfloat16 once: a ``matrix`` is kept
    in bfloat16 (the type the program is handed it in), the ``head`` in
    float32 with the same rounded values (``bf16_mixed`` keeps the head
    in float32).  Norm weights are ones."""
    if kind == "gamma":
        return jnp.ones(shape, F32)
    w = (0.02 * jax.random.normal(key, shape, F32)).astype(jnp.bfloat16)
    return w.astype(F32) if kind == "head" else w


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (B, T, H, dh), pos (T,): rotate-half over the whole head."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = pos.astype(F32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _q(quant, *xs):
    return xs if quant is None else tuple(quant(x) for x in xs)


def _mm(x, w, quant):
    """x (..., in) times w (out, in) transposed."""
    x, w = _q(quant, x, w.astype(F32))
    return jnp.matmul(x, w.T, precision=HI)


def experts(cfg, n, wr, wg, wu, wd, quant=None, held=None):
    """The expert layer on normed states n (N, D): (out (N, D), margin
    (N,)).  ``margin`` is the router's gap between its k-th and
    (k+1)-th logit: how far each token is from another choice of
    experts.  ``held`` = (first, count) gives only those experts' part
    (the share test); the router is over all of them either way."""
    z = sizes(cfg)
    D, F = z["D"], z["F"]
    logits = _mm(n, wr, quant)                                   # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, z["k"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(n.shape[0])[:, None], top_i].set(top_p)       # (N, E)
    ranked = jnp.sort(logits, axis=-1)
    margin = ranked[:, -z["k"]] - ranked[:, -z["k"] - 1]
    first, count = held if held is not None else (0, z["E"])
    out = jnp.zeros_like(n)
    for e0 in range(first, first + count, EXPERT_GROUP):
        e1 = min(e0 + EXPERT_GROUP, first + count)
        # the experts lie side by side: expert e is columns (rows, for
        # the down matrix) [e * F, (e + 1) * F) of what is held
        here = slice((e0 - first) * F, (e1 - first) * F)
        g, u, d = (wg[:, here].astype(F32).reshape(D, e1 - e0, F),
                   wu[:, here].astype(F32).reshape(D, e1 - e0, F),
                   wd[here].astype(F32).reshape(e1 - e0, F, D))
        nq, gq, uq = _q(quant, n, g, u)
        a = jnp.einsum("nd,def->nef", nq, gq, precision=HI)
        b = jnp.einsum("nd,def->nef", nq, uq, precision=HI)
        h = jax.nn.silu(a) * b
        hq, dq = _q(quant, h, d)
        y = jnp.einsum("nef,efd->ned", hq, dq, precision=HI)
        out = out + jnp.einsum("ned,ne->nd", y, weight[:, e0:e1],
                               precision=HI)
    return out, margin


def _block(cfg, x, p, quant):
    g1, wq, wk, wv, gq, gk, wo, g2, wr, wg, wu, wd = p
    z = sizes(cfg)
    B_, T, D = x.shape
    Hq, Hkv, dh, eps = z["Hq"], z["Hkv"], z["dh"], cfg["rms_norm_eps"]
    pos = jnp.arange(T)
    n = _rms(x, g1, eps)
    q = _rms(_mm(n, wq, quant).reshape(B_, T, Hq, dh), gq, eps)
    k = _rms(_mm(n, wk, quant).reshape(B_, T, Hkv, dh), gk, eps)
    v = _mm(n, wv, quant).reshape(B_, T, Hkv, dh)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    # each key/value head serves Hq / Hkv query heads
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    q, k = _q(quant, q, k)
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) / math.sqrt(dh)
    blk = pos // z["B"]
    mask = blk[None, :] <= blk[:, None]                  # [i, j]
    att = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    att, v = _q(quant, att, v)
    o = jnp.einsum("bhts,bshd->bthd", att, v, precision=HI)
    x = x + _mm(o.reshape(B_, T, Hq * dh), wo, quant)
    m = _rms(x, g2, eps).reshape(B_ * T, D)
    y, margin = experts(cfg, m, wr, wg, wu, wd, quant)
    return x + y.reshape(B_, T, D), margin.reshape(B_, T)


def hidden(cfg, params, tokens, quant=None):
    """tokens (B, T) int32 -> (final-RMSNorm states (B, T, D), the
    least router margin over the layers (B, T))."""
    params = list(params)
    x = params[0][tokens].astype(F32)
    margin = None
    for i in range(cfg["num_hidden_layers"]):
        at = 1 + PER_BLOCK * i
        x, m = _block(cfg, x, params[at:at + PER_BLOCK], quant)
        margin = m if margin is None else jnp.minimum(margin, m)
    return _rms(x, params[-2], cfg["rms_norm_eps"]), margin


def logits_at(cfg, params, tokens, positions, quant=None):
    """(logits (B, n, V), router margin (B, n)) at the given positions
    (n,) of each row only: the head is the largest product."""
    h, margin = hidden(cfg, params, tokens, quant)
    return _mm(h[:, positions], list(params)[-1], quant), \
        margin[:, positions]


def forward(cfg, params, tokens):
    """Logits of the whole sequence (B, T, V)."""
    return logits_at(cfg, params, tokens, jnp.arange(tokens.shape[1]))[0]


def fix_count(masked_at_start, steps):
    """Positions a denoise pass fixes: ceil(masked at block start / T)."""
    return -(-masked_at_start // steps)


def choose(confidence, masked, count):
    """The ``count`` most confident masked positions, ties to the lower
    position (``confidence`` and ``masked`` are sequences of a block's
    length)."""
    order = sorted((i for i in range(len(masked)) if masked[i]),
                   key=lambda i: (-float(confidence[i]), i))
    return sorted(order[:count])


def generate(cfg, params, prompt, n_new, steps):
    """Generation by diffusion over blocks, as a plain loop over full
    forwards (all of it assumed, see the module's head):

    the prompt's first ``B * (n // B)`` tokens are context; its last
    ``n mod B`` open the first block as given positions; a block's other
    positions start masked and are fed ``mask_token_id``.  One denoise
    pass runs the sequence up to the block's end, takes at every masked
    position the argmax token and its softmax probability (the
    confidence), and fixes the ``ceil(masked at block start / T)`` most
    confident masked positions (static low-confidence remasking, greedy;
    ties to the lower position).  After ``T`` passes nothing is masked;
    the block joins the context (in the program: one commit pass writes
    its K and V), and the next block opens.  Whether a position is
    masked is state kept per position, never inferred from a token id.

    Returns (tokens, fixed_at, passes): the first ``n_new`` generated
    tokens, the pass (1..T) at which each was fixed, and for every
    denoise pass a dict of ``start``, ``pass``, ``fed`` (the block's
    token ids as fed), ``masked`` and ``logits`` (B, V) of the block's
    positions."""
    import numpy as np

    z = sizes(cfg)
    Bl, mask_id = z["B"], cfg["assumed"]["mask_token_id"]
    prompt = [int(t) for t in prompt]
    n = len(prompt)
    ctx = prompt[:Bl * (n // Bl)]
    given = prompt[len(ctx):]
    out, fixed_at, passes = [], [], []
    fwd = jax.jit(lambda toks: forward(cfg, params, toks))
    while len(out) < n_new:
        tok = given + [0] * (Bl - len(given))
        masked = [False] * len(given) + [True] * (Bl - len(given))
        at = [0] * Bl
        m0 = sum(masked)
        for t in range(1, steps + 1):
            fed = [mask_id if m else v for v, m in zip(tok, masked)]
            seq = np.asarray([ctx + fed], np.int32)
            lg = np.asarray(fwd(seq))[0, len(ctx):]
            passes.append({"start": len(ctx), "pass": t, "fed": fed,
                           "masked": list(masked), "logits": lg})
            top = lg.max(-1, keepdims=True)
            logp = lg - top - np.log(np.sum(np.exp(lg - top), -1,
                                            keepdims=True))
            conf = logp.max(-1)
            for i in choose(conf, masked,
                            min(fix_count(m0, steps), sum(masked))):
                tok[i], masked[i], at[i] = int(lg[i].argmax()), False, t
        for i in range(len(given), Bl):
            out.append(tok[i])
            fixed_at.append(at[i])
        ctx, given = ctx + tok, []
    return out[:n_new], fixed_at[:n_new], passes


# -- counts, from the configuration alone ------------------------------------

def layer_matmul_params(cfg, experts_counted):
    """Multiplied parameters of one layer with ``experts_counted``
    experts: q, k, v, out, router, three matrices an expert."""
    z = sizes(cfg)
    D, dh = z["D"], z["dh"]
    return 2 * D * z["Hq"] * dh + 2 * D * z["Hkv"] * dh + D * z["E"] \
        + experts_counted * 3 * D * z["F"]


def serve_flops_per_token(cfg, context):
    """Forward of one position at ``context`` attended positions, the
    ACTIVE parameters only: attention projections, router, the k experts
    a token is routed to, the head; and the attention over the context,
    2 x 2 x query heads x head size a position."""
    z = sizes(cfg)
    return 2 * (z["L"] * layer_matmul_params(cfg, z["k"])
                + z["V"] * z["D"]) \
        + 4 * z["L"] * z["Hq"] * z["dh"] * int(context)


def forward_min_bytes(cfg, live_positions):
    """The least bytes one forward moves through HBM whatever implements
    it: every multiplied weight once in the type it is stored in (all
    experts: a forward of 128 rows of 8 choices touches every one; the
    head in float32), and K and V of the live positions in the cache's
    bfloat16."""
    z = sizes(cfg)
    weights = 2 * z["L"] * layer_matmul_params(cfg, z["E"]) \
        + 4 * z["V"] * z["D"]
    kv = 2 * z["L"] * z["Hkv"] * z["dh"] * 2 * int(live_positions)
    return weights + kv
