"""Plain float32 reference of the OPT decoder (Zhang et al.,
arXiv:2205.01068; facebook/opt-1.3b ``config.json``): learned positions,
pre-LayerNorm blocks of causal multi-head attention and a ReLU
feed-forward, a final LayerNorm and an output head.

Departures from the published model, which the configuration's file
lists under ``assumed`` (they are those of the program's only LM,
``examples/transformer_lm.TransformerLM``): no bias on the four
attention projections, an output head that is not tied to the
embedding, and position rows without OPT's offset of 2.

Nothing here imports the program.  Parameters are a flat list in the
order of :func:`param_specs`; ``quant`` (None for the reference) is the
control's hook, applied to both operands of every matrix product.
"""
import jax
import jax.numpy as jnp
from jax import lax

LN_EPS = 1e-5
HI = lax.Precision.HIGHEST
PER_BLOCK = 12


def param_specs(cfg):
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    specs = [("embed_weight", (v, d), "embed"),
             ("pos_embed_weight", (cfg["max_position_embeddings"], d), "embed")]
    for i in range(cfg["num_hidden_layers"]):
        h = "h%d_" % i
        specs += [(h + "ln1_gamma", (d,), "gamma"), (h + "ln1_beta", (d,), "beta"),
                  (h + "proj_q_weight", (d, d), "dense"),
                  (h + "proj_k_weight", (d, d), "dense"),
                  (h + "proj_v_weight", (d, d), "dense"),
                  (h + "attn_out_weight", (d, d), "dense"),
                  (h + "ln2_gamma", (d,), "gamma"), (h + "ln2_beta", (d,), "beta"),
                  (h + "ffn_up_weight", (f, d), "dense"),
                  (h + "ffn_up_bias", (f,), "bias"),
                  (h + "ffn_down_weight", (d, f), "dense"),
                  (h + "ffn_down_bias", (d,), "bias")]
    specs += [("ln_f_gamma", (d,), "gamma"), ("ln_f_beta", (d,), "beta"),
              ("head_weight", (v, d), "dense")]
    return specs


def init_leaf(key, shape, kind):
    """OPT's own initialisation: normal, std 0.02, for every matrix;
    LayerNorm scales near 1 and shifts and biases near 0 (not exactly,
    so that no leaf is a constant)."""
    n = jax.random.normal(key, shape, jnp.float32)
    if kind in ("embed", "dense"):
        return 0.02 * n
    if kind == "gamma":
        return 1.0 + 0.1 * n
    return 0.02 * n


def _ln(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * g + b


def _mm(x, w, quant):
    """x (..., in) times w (out, in) transposed."""
    if quant is not None:
        x, w = quant(x), quant(w)
    return jnp.matmul(x, w.T, precision=HI)


def _block(cfg, x, p, quant):
    g1, b1, wq, wk, wv, wo, g2, b2, wu, bu, wd, bd = p
    B, T, D = x.shape
    H = cfg["num_attention_heads"]
    dh = D // H
    h = _ln(x, g1, b1)

    def heads(a):
        return a.reshape(B, T, H, dh).transpose(0, 2, 1, 3)

    q, k, v = heads(_mm(h, wq, quant)), heads(_mm(h, wk, quant)), \
        heads(_mm(h, wv, quant))
    if quant is not None:
        q, k = quant(q), quant(k)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=HI) * dh ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    if quant is not None:
        att, v = quant(att), quant(v)
    o = jnp.einsum("bhts,bhsd->bhtd", att, v, precision=HI)
    x = x + _mm(o.transpose(0, 2, 1, 3).reshape(B, T, D), wo, quant)
    u = jax.nn.relu(_mm(_ln(x, g2, b2), wu, quant) + bu)
    return x + _mm(u, wd, quant) + bd


def hidden(cfg, params, tokens, quant=None):
    """tokens (B, T) int32 -> final-LayerNorm states (B, T, D)."""
    params = list(params)
    T = tokens.shape[1]
    x = params[0][tokens] + params[1][:T][None]
    for i in range(cfg["num_hidden_layers"]):
        at = 2 + PER_BLOCK * i
        x = _block(cfg, x, params[at:at + PER_BLOCK], quant)
    return _ln(x, params[-3], params[-2])


def logits_at(cfg, params, tokens, positions, quant=None):
    """Logits (B, n, V) at the given positions (n,) of each row only: the
    head is the largest product, and serving compares a few positions."""
    h = hidden(cfg, params, tokens, quant)[:, positions]
    return _mm(h, list(params)[-1], quant)


def n_params(cfg):
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    return 2 * v * d + cfg["max_position_embeddings"] * d + \
        cfg["num_hidden_layers"] * (4 * d * d + 2 * d * f + f + 5 * d) + 2 * d


def matmul_params(cfg):
    """Parameters that a token multiplies: every matrix but the two
    embedding tables, which are looked up."""
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (4 * d * d + 2 * d * f) + v * d


def train_flops_per_sample(cfg, traffic):
    """A sample is a token: 6 x parameters (the PaLM appendix's count,
    embeddings included as the issue gives it) + the attention term
    12 x layers x d_model x seq."""
    seq = traffic["seq"]
    return 6 * n_params(cfg) + \
        12 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq


def serve_flops_per_token(cfg, context):
    """Forward of one token at a context of ``context`` cached positions:
    2 x the multiplied parameters + 4 x layers x d_model x context."""
    return 2 * matmul_params(cfg) + \
        4 * cfg["num_hidden_layers"] * cfg["hidden_size"] * context
