"""The reference's first three training steps: plain float32 gradients
of the family's loss and momentum SGD's update written out, for any
family.  Returns what the comparison reads: each step's loss, every
trainable leaf's first gradient, and every leaf's change after the
steps."""
import jax


def first_steps(fam, cfg, params, batches, quant=None, half_batch=False):
    """Three steps over ``batches[:3]``.  ``quant`` puts the control's
    precision in; ``half_batch`` plants the fault of half the batch left
    out with the mean taken over the rest."""
    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise SystemExit("the reference has no optimizer %r" % opt["name"])
    lr, mu = opt["learning_rate"], opt.get("momentum", 0.0)
    mask = fam.trainable(cfg)

    def loss_of(train, frozen, x, y):
        it_t, it_f = iter(train), iter(frozen)
        full = [next(it_t) if t else next(it_f) for t in mask]
        return fam.loss(cfg, full, x, y, quant)

    @jax.jit
    def step(train, frozen, mom, x, y):
        if half_batch:
            x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
        loss, grads = jax.value_and_grad(loss_of)(train, frozen, x, y)
        mom = [mu * m - lr * g for m, g in zip(mom, grads)]
        return [p + m for p, m in zip(train, mom)], mom, loss, grads

    train = [p for p, t in zip(params, mask) if t]
    frozen = [p for p, t in zip(params, mask) if not t]
    start = train
    mom = jax.jit(lambda ps: [0.0 * p for p in ps])(train)
    losses, first = [], None
    for x, y in batches[:3]:
        train, mom, loss, grads = step(train, frozen, mom, x, y)
        losses.append(float(loss))
        first = grads if first is None else first
    deltas = jax.jit(lambda a, b: [x - y for x, y in zip(a, b)])(train, start)
    return {"losses": losses, "grads": first, "deltas": deltas}
