"""Weights and batches from ``--seed``, made on the device in one jitted
call each, in float32 (the type both programs hold them in).  The
benchmark makes them, hands them to the program, and makes them again
for the reference: the same seed gives the same arrays."""
import importlib

import jax


def family(cfg):
    """The plain-reference module of a configuration's family."""
    return importlib.import_module(
        "benchmark.lib.reference." + cfg["family"])


def seed_key(seed, stream, impl=None):
    """A key from any whole number (the driver's seeds pass 2**31) and a
    stream number that keeps weights, batches and traffic apart."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl=impl)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


# the chip's own bit generator: a billion values in about a second, where
# threefry takes seven
IMPL = "rbg"


def make_params(cfg, seed):
    """The flat parameter list of ``family(cfg).param_specs`` order."""
    fam = family(cfg)
    specs = fam.param_specs(cfg)

    def build(key):
        return [fam.init_leaf(jax.random.fold_in(key, i), shape, kind)
                for i, (_name, shape, kind) in enumerate(specs)]

    return jax.jit(build)(seed_key(seed, 0, IMPL))


def make_batches(cfg, seed, n, batch):
    """``n`` batches (x, y), all rows different."""
    fam = family(cfg)

    def build(key):
        return [fam.make_batch(cfg, jax.random.fold_in(key, i), batch)
                for i in range(n)]

    return jax.jit(build)(seed_key(seed, 1, IMPL))
