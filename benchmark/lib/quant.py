"""The control's arithmetic: the plain reference with every value that
the program holds in bfloat16 under ``bf16_mixed`` rounded instead to
8-bit floating point (e4m3, scaled per tensor to its range, as an fp8
recipe would), one precision below.  Products still accumulate in
float32, and the backward pass sees the rounded values through a
straight-through estimate.  Each reference says where it applies the
hook."""
import jax
import jax.numpy as jnp

E4M3_MAX = 240.0   # 4 exponent bits, 3 of mantissa, IEEE-style


def fp8(x):
    """``reduce_precision`` and not a pair of casts, which the compiler
    is free to drop or to widen (``xla_allow_excess_precision``)."""
    x = x.astype(jnp.float32)
    scale = E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = jax.lax.reduce_precision(x * scale, 4, 3) / scale
    return x + jax.lax.stop_gradient(q - x)


def bf16(x):
    """The program's own precision, put into the reference the same way:
    a witness of where sound runs should read, never a control."""
    q = jax.lax.reduce_precision(x.astype(jnp.float32), 8, 7)
    return x + jax.lax.stop_gradient(q - x)

