"""2-bit gradient compression with error feedback.

Reference parity: ``src/kvstore/gradient_compression.h:38-47`` and the
CPU/GPU kernels in ``gradient_compression-inl.h`` (Quantize2BitImpl /
Dequantize2BitImpl), surfaced through
``python/mxnet/kvstore.py:394`` (``set_gradient_compression``).

Semantics (identical to the reference): per element,
``residual += grad``; emit +threshold and subtract it from the residual
when ``residual >= threshold``; emit -threshold and add when
``residual <= -threshold``; emit 0 otherwise.  Codes are 2 bits each
(01 -> +t, 10 -> -t, 00 -> 0), 16 codes packed per uint32 — a 16x wire
compression for fp32 gradients.

TPU-native: the quantize/dequantize hot loops are Pallas kernels — the
gradient streams HBM->VMEM once per grid step, the VPU computes codes
for a (128, 128) fp32 tile and packs them into an (8, 128) int32 block
(16 consecutive sublanes fold into each code row, keeping the 128-lane
dimension dense).  Off the TPU (the CPU test harness) the same kernels run
through the Pallas interpreter, so one code path serves tests and
production.
"""
from __future__ import annotations

import functools

import numpy as np

from ..base import MXNetError

_GROUP = 16            # codes per uint32
_LANES = 128           # TPU lane width
# one grid step: (_BLOCK_ROWS, _LANES) fp32 tile -> (_CODE_ROWS, _LANES)
# uint32 codes; 8 sublanes of codes keeps the output tile legal
_CODE_ROWS = 8
_BLOCK_ROWS = _GROUP * _CODE_ROWS        # 128
_TILE = _BLOCK_ROWS * _LANES


def _use_interpret():
    """Interpret the kernels only where the devices are not TPUs (the
    CPU test harness); on the chip they compile through Mosaic."""
    import jax

    return jax.devices()[0].platform != "tpu"


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------


def _quantize_kernel(g_ref, r_ref, codes_ref, nres_ref, *, threshold):
    import jax.numpy as jnp

    g = g_ref[:] + r_ref[:]                       # error feedback
    pos = g >= threshold
    neg = g <= -threshold
    nres_ref[:] = g - jnp.where(pos, threshold, 0.0) \
        + jnp.where(neg, threshold, 0.0)
    # int32 container (mosaic can't reduce unsigned); the 2-bit fields
    # are disjoint, so sum == bitwise-or and the sign bit is just bit 31
    code = pos.astype(jnp.int32) | (neg.astype(jnp.int32) << 1)
    # pack 16 consecutive sublanes into each code row: reshape the
    # (128, 128) code tile to (8, 16, 128) and fold the middle axis
    grouped = code.reshape(_CODE_ROWS, _GROUP, _LANES)
    shifts = jnp.arange(_GROUP, dtype=jnp.int32).reshape(1, _GROUP, 1) * 2
    codes_ref[:] = jnp.sum(grouped << shifts, axis=1)


def _dequantize_kernel(codes_ref, out_ref, *, threshold):
    import jax.numpy as jnp
    from jax import lax

    packed = codes_ref[:]                         # (_CODE_ROWS, _LANES)
    shifts = jnp.arange(_GROUP, dtype=jnp.int32).reshape(1, _GROUP, 1) * 2
    # logical (not arithmetic) shift: bit 31 is data, not a sign
    bits = lax.shift_right_logical(
        jnp.broadcast_to(packed[:, None, :],
                         (_CODE_ROWS, _GROUP, _LANES)),
        jnp.broadcast_to(shifts, (_CODE_ROWS, _GROUP, _LANES))) \
        & jnp.int32(3)
    vals = jnp.where(bits == 1, threshold,
                     jnp.where(bits == 2, -threshold, 0.0))
    out_ref[:] = vals.reshape(_BLOCK_ROWS, _LANES).astype(jnp.float32)


@functools.lru_cache(maxsize=64)
def _quantize_call(n_rows, threshold, interpret):
    import jax
    from jax.experimental import pallas as pl

    grid = n_rows // _BLOCK_ROWS
    return jax.jit(lambda g, r: pl.pallas_call(
        functools.partial(_quantize_kernel, threshold=threshold),
        grid=(grid,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
                  pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((_CODE_ROWS, _LANES), lambda i: (i, 0)),
                   pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid * _CODE_ROWS, _LANES),
                                        jax.numpy.int32),
                   jax.ShapeDtypeStruct((n_rows, _LANES),
                                        jax.numpy.float32)],
        interpret=interpret,
    )(g, r))


@functools.lru_cache(maxsize=64)
def _dequantize_call(n_rows, threshold, interpret):
    import jax
    from jax.experimental import pallas as pl

    grid = n_rows // _BLOCK_ROWS
    return jax.jit(lambda c: pl.pallas_call(
        functools.partial(_dequantize_kernel, threshold=threshold),
        grid=(grid,),
        in_specs=[pl.BlockSpec((_CODE_ROWS, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, _LANES),
                                       jax.numpy.float32),
        interpret=interpret,
    )(c))


# ---------------------------------------------------------------------------
# array-level API
# ---------------------------------------------------------------------------


def _padded_rows(size):
    return max(_BLOCK_ROWS, -(-size // _TILE) * _TILE // _LANES)


def quantize_2bit(grad, residual, threshold=0.5):
    """(codes int32 (rows, 128), new_residual flat) from a flat fp32
    gradient + residual.  Arrays beyond ``grad.size`` are zero-padded."""
    import jax.numpy as jnp

    size = grad.size
    rows = _padded_rows(size)
    pad = rows * _LANES - size
    g = jnp.pad(grad.reshape(-1).astype(jnp.float32), (0, pad)) \
        .reshape(rows, _LANES)
    r = jnp.pad(residual.reshape(-1).astype(jnp.float32), (0, pad)) \
        .reshape(rows, _LANES)
    codes, nres = _quantize_call(rows, float(threshold),
                                 _use_interpret())(g, r)
    return codes, nres.reshape(-1)[:size]


def dequantize_2bit(codes, size, threshold=0.5):
    """Flat fp32 gradient of ``size`` elements from packed codes."""
    rows = codes.shape[0] * _GROUP
    out = _dequantize_call(rows, float(threshold), _use_interpret())(codes)
    return out.reshape(-1)[:size]


class GradientCompression:
    """Stateful compressor: per-key residuals, reference parameter names
    (type='2bit', threshold)."""

    def __init__(self, type="2bit", threshold=0.5, **kwargs):
        if str(type) != "2bit":
            raise MXNetError("unsupported gradient compression type %r "
                             "(only '2bit')" % (type,))
        self.type = "2bit"
        self.threshold = float(threshold)
        if self.threshold <= 0:
            raise MXNetError("threshold must be positive")
        self._residuals = {}

    def compress(self, key, grad_flat):
        """codes for one worker's flat gradient, updating its residual."""
        import jax.numpy as jnp

        res = self._residuals.get(key)
        if res is None or res.size != grad_flat.size:
            res = jnp.zeros(grad_flat.size, jnp.float32)
        codes, new_res = quantize_2bit(grad_flat, res, self.threshold)
        self._residuals[key] = new_res
        return codes

    def compress_dequantize(self, key, grad_nd):
        """Round-trip one gradient NDArray: what the receiving end of a
        compressed push reconstructs (error feedback retained here)."""
        from ..ndarray.ndarray import NDArray

        flat = grad_nd._data.reshape(-1)
        codes = self.compress(key, flat)
        deq = dequantize_2bit(codes, flat.size, self.threshold)
        return NDArray(deq.reshape(grad_nd._data.shape), grad_nd._ctx)
