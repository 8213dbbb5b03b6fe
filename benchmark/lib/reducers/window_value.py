"""A number the driver took from the window's own stamps and counters."""


def reduce(ctx, key):
    return ctx["window"].get(key)
