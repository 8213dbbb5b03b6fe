"""Plain float32 reference of ResNet v1 (He et al., arXiv:1512.03385,
Table 1) in the bottleneck layout the Gluon model zoo uses: the stride
sits on the leading 1x1 convolution of a unit, the two 1x1 convolutions
carry a bias, every convolution is followed by BatchNorm (batch
statistics, eps 1e-5), and the shortcut of a unit that changes shape is
a strided 1x1 convolution with BatchNorm.

Nothing here imports the program.  Parameters are a flat list in the
order of :func:`param_specs`; ``quant`` (None for the reference) is the
control's hook: a function applied wherever the program under
``bf16_mixed`` holds a value in its compute type: both operands and the
result of every convolution and of the classifier's matrix product, the
batch statistics, and the result of every BatchNorm and residual sum.
"""
import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
HI = lax.Precision.HIGHEST


def _unit_plan(cfg):
    """Yield (in_ch, out_ch, stride, has_shortcut) for every unit."""
    channels, units = cfg["channels"], cfg["units"]
    in_ch = channels[0]
    for si, (out_ch, n) in enumerate(zip(channels[1:], units)):
        for ui in range(n):
            stride = 2 if (ui == 0 and si > 0) else 1
            yield in_ch, out_ch, stride, not (stride == 1 and in_ch == out_ch)
            in_ch = out_ch


def _bn_specs(prefix, c):
    return [(prefix + "_gamma", (c,), "gamma"), (prefix + "_beta", (c,), "beta"),
            (prefix + "_running_mean", (c,), "zeros"),
            (prefix + "_running_var", (c,), "ones")]


def param_specs(cfg):
    """[(name, shape, kind)] in the order the forward pass consumes them
    (which is the order Gluon registers them)."""
    ch0 = cfg["channels"][0]
    specs = [("stem_conv_weight", (ch0, cfg["in_channels"], 7, 7), "conv")]
    specs += _bn_specs("stem_bn", ch0)
    for i, (cin, cout, _s, shortcut) in enumerate(_unit_plan(cfg)):
        mid = cout // 4
        u = "unit%d" % i
        specs += [(u + "_conv0_weight", (mid, cin, 1, 1), "conv"),
                  (u + "_conv0_bias", (mid,), "bias")]
        specs += _bn_specs(u + "_bn0", mid)
        specs += [(u + "_conv1_weight", (mid, mid, 3, 3), "conv")]
        specs += _bn_specs(u + "_bn1", mid)
        specs += [(u + "_conv2_weight", (cout, mid, 1, 1), "conv"),
                  (u + "_conv2_bias", (cout,), "bias")]
        specs += _bn_specs(u + "_bn2", cout)
        if shortcut:
            specs += [(u + "_sc_weight", (cout, cin, 1, 1), "conv")]
            specs += _bn_specs(u + "_sc_bn", cout)
    specs += [("dense_weight", (cfg["classes"], cfg["channels"][-1]), "dense"),
              ("dense_bias", (cfg["classes"],), "zeros")]
    return specs


def trainable(cfg):
    return [not n.endswith(("_running_mean", "_running_var"))
            for n, _s, _k in param_specs(cfg)]


def init_leaf(key, shape, kind):
    """One leaf from its key: He-normal convolutions, a narrow classifier,
    BatchNorm scales near 1 and shifts near 0 (not exactly, so that no
    leaf is a constant)."""
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "conv":
        fan_in = shape[1] * shape[2] * shape[3]
        return n * (2.0 / fan_in) ** 0.5
    if kind == "dense":
        return n * 0.01
    if kind == "gamma":
        return 1.0 + 0.1 * n
    if kind == "beta":
        return 0.1 * n
    if kind == "bias":
        return 0.01 * n
    return jnp.ones(shape, jnp.float32) if kind == "ones" \
        else jnp.zeros(shape, jnp.float32)


def _conv(x, w, stride, pad, quant):
    q = quant if quant is not None else (lambda a: a)
    return q(lax.conv_general_dilated(
        q(x), q(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HI))


def _bn(x, gamma, beta, quant=None):
    q = quant if quant is not None else (lambda a: a)
    mean = q(jnp.mean(x, axis=(0, 2, 3), keepdims=True))
    var = q(jnp.var(x, axis=(0, 2, 3), keepdims=True))
    return q((x - mean) * q(lax.rsqrt(var + BN_EPS))
             * gamma.reshape(1, -1, 1, 1) + beta.reshape(1, -1, 1, 1))


def _unit(x, p, stride, shortcut, quant):
    it = iter(p)
    nxt = lambda: next(it)  # noqa: E731
    y = _conv(x, nxt(), stride, 0, quant) + nxt().reshape(1, -1, 1, 1)
    g, b, _m, _v = nxt(), nxt(), nxt(), nxt()
    y = jax.nn.relu(_bn(y, g, b, quant))
    y = _conv(y, nxt(), 1, 1, quant)
    g, b, _m, _v = nxt(), nxt(), nxt(), nxt()
    y = jax.nn.relu(_bn(y, g, b, quant))
    y = _conv(y, nxt(), 1, 0, quant) + nxt().reshape(1, -1, 1, 1)
    g, b, _m, _v = nxt(), nxt(), nxt(), nxt()
    y = _bn(y, g, b, quant)
    if shortcut:
        r = _conv(x, nxt(), stride, 0, quant)
        g, b, _m, _v = nxt(), nxt(), nxt(), nxt()
        r = _bn(r, g, b, quant)
    else:
        r = x
    out = jax.nn.relu(r + y)
    return out if quant is None else quant(out)


def forward(cfg, params, x, quant=None):
    """Training-mode forward: images (N, C, H, W) float32 -> logits
    (N, classes).  Each unit is rematerialised in the backward pass, so
    that batch 256 in float32 fits one chip; that changes no number."""
    params = list(params)
    x = _conv(x, params[0], 2, 3, quant)
    x = jax.nn.relu(_bn(x, params[1], params[2], quant))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    at = 5
    for _cin, _cout, stride, shortcut in _unit_plan(cfg):
        n = 17 + (5 if shortcut else 0)
        fn = jax.checkpoint(
            lambda x_, p_, s=stride, sc=shortcut: _unit(x_, p_, s, sc, quant))
        x = fn(x, params[at:at + n])
        at += n
    x = jnp.mean(x, axis=(2, 3))
    w, b = params[at], params[at + 1]
    if quant is not None:
        x, w = quant(x), quant(w)
    return jnp.matmul(x, w.T, precision=HI) + b


def loss(cfg, params, x, y, quant=None):
    """Mean softmax cross-entropy of integer labels y (N,)."""
    logp = jax.nn.log_softmax(forward(cfg, params, x, quant), axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, y.astype(jnp.int32)[:, None], axis=-1))


def make_batch(cfg, key, batch):
    """One batch from its key: images uniform in [0, 1), labels uniform
    over the classes (carried as float32, as the trainer takes them)."""
    kx, ky = jax.random.split(key)
    size = cfg["image_size"]
    x = jax.random.uniform(kx, (batch, cfg["in_channels"], size, size),
                           jnp.float32)
    y = jax.random.randint(ky, (batch,), 0, cfg["classes"]).astype(jnp.float32)
    return x, y


def conv_flops_forward(cfg):
    """Multiply-adds x 2 of one image's forward pass: every convolution
    and the classifier (BatchNorm, ReLU and pooling are not counted)."""
    size = cfg["image_size"]
    h = (size + 2 * 3 - 7) // 2 + 1
    total = 2 * cfg["channels"][0] * cfg["in_channels"] * 49 * h * h
    h = (h + 2 - 3) // 2 + 1
    for cin, cout, stride, shortcut in _unit_plan(cfg):
        mid = cout // 4
        ho = (h - 1) // stride + 1
        total += 2 * mid * cin * ho * ho          # 1x1, strided
        total += 2 * mid * mid * 9 * ho * ho      # 3x3
        total += 2 * cout * mid * ho * ho         # 1x1
        if shortcut:
            total += 2 * cout * cin * ho * ho
        h = ho
    return total + 2 * cfg["classes"] * cfg["channels"][-1]


def train_flops_per_sample(cfg, traffic=None):
    """Forward + backward of one image, no recompute: 3 x forward."""
    return 3 * conv_flops_forward(cfg)
