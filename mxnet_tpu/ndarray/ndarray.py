"""NDArray: the user-visible mutable n-dim array, TPU-native.

Reference parity: include/mxnet/ndarray.h:82 + src/ndarray/ (mutable array
whose every op schedules through the dependency engine) and the Python
class python/mxnet/ndarray/ndarray.py:174.

TPU-native design: an NDArray is a *handle* holding the current immutable
jax.Array plus a version counter.  Ops produce new jax.Arrays; in-place
operations rebind the handle and bump the version — the same observable
semantics as the reference's engine-var version bumps, but expressed
functionally so XLA can fuse and async-dispatch freely.  `asnumpy()` is
the sync point (parity: WaitToRead -> Engine::WaitForVar).  Under a jit
trace the handle holds a tracer, which is how hybridized blocks compile.
"""
from __future__ import annotations

import inspect

import numpy as np

from time import perf_counter as _perf_counter

from ..base import MXNetError, dtype_np_to_str, dtype_str_to_np
from ..context import Context, current_context, cpu
from .. import engine as _engine
from .. import profiler as _profiler
from ..ops.registry import get_op, clean_attrs

__all__ = ["NDArray", "array", "zeros", "ones", "empty", "full", "arange",
           "concatenate", "moveaxis", "waitall", "save", "load", "_invoke_nd",
           "concat", "stack", "onehot_encode", "imports"]


def _jnp():
    import jax.numpy as jnp

    return jnp


def _is_jax_array(x):
    import jax

    return isinstance(x, (jax.Array, jax.core.Tracer))


class NDArray:
    __slots__ = ("_data", "_ctx", "_tape_ref", "_grad", "_grad_req", "_stype",
                 "__weakref__")

    # numpy operators defer to us
    __array_priority__ = 1000.0

    def __init__(self, data, ctx=None, stype="default"):
        self._data = data
        self._ctx = ctx or current_context()
        self._tape_ref = None
        self._grad = None
        self._grad_req = "null"
        self._stype = stype

    # ------------------------------------------------------------------
    # core properties
    # ------------------------------------------------------------------
    @property
    def data(self):
        return self._data

    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def dtype(self):
        return np.dtype(self._data.dtype).type

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return self._stype

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        if self.ndim < 2:
            return _invoke_nd("_copy", [self], {})
        return _invoke_nd("transpose", [self], {})

    # ------------------------------------------------------------------
    # mutation: rebind + version bump (the in-place story)
    # ------------------------------------------------------------------
    def _rebind(self, new_data):
        self._data = _engine.get().maybe_block(new_data)
        return self

    # ------------------------------------------------------------------
    # sync / conversion
    # ------------------------------------------------------------------
    def asnumpy(self):
        _engine.get().wait_for_var(self._data)
        return np.asarray(self._data)

    def __array__(self, dtype=None, copy=None):
        # one device fetch for np.asarray(nd_arr) — without this numpy
        # falls back to the sequence protocol (one eager __getitem__
        # dispatch per row: thousands of device round-trips).  The
        # numpy>=2.0 `copy` keyword: the fetch always materializes a
        # fresh host buffer, so copy=False is satisfiable and
        # copy=True just copies once more.
        out = self.asnumpy()
        if dtype is not None:
            out = out.astype(dtype, copy=False)
        if copy:
            out = out.copy()
        return out

    def asscalar(self):
        return self.asnumpy().item()

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        return self.shape[0]

    def wait_to_read(self):
        _engine.get().wait_for_var(self._data)

    def wait_to_write(self):
        _engine.get().wait_for_var(self._data)

    def astype(self, dtype, copy=True):
        if not copy and np.dtype(self._data.dtype) == np.dtype(
                dtype_str_to_np(dtype) if isinstance(dtype, str) else dtype):
            return self
        return _invoke_nd("Cast", [self], {"dtype": dtype})

    def copy(self):
        return _invoke_nd("_copy", [self], {})

    def copyto(self, other):
        import jax

        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device), other)
        if isinstance(other, NDArray):
            other._rebind(self._data.astype(other._data.dtype))
            return other
        raise MXNetError("copyto target must be NDArray or Context")

    def as_in_context(self, ctx):
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def as_nd_ndarray(self):
        return self

    def detach(self):
        out = NDArray(self._data, self._ctx)
        return out

    def tostype(self, stype):
        from . import sparse as _sp

        if stype == "default":
            return self
        return _sp.cast_storage(self, stype)

    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if kwargs.get("shape"):
            shape = tuple(kwargs["shape"])
        return _invoke_nd("Reshape", [self], {"shape": shape,
                                              "reverse": kwargs.get("reverse", False)})

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def expand_dims(self, axis):
        return _invoke_nd("expand_dims", [self], {"axis": axis})

    def flatten(self):
        return _invoke_nd("Flatten", [self], {})

    def squeeze(self, axis=None):
        return _invoke_nd("squeeze", [self], {"axis": axis})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return _invoke_nd("transpose", [self], {"axes": axes or None})

    def swapaxes(self, dim1, dim2):
        return _invoke_nd("swapaxes", [self], {"dim1": dim1, "dim2": dim2})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return _invoke_nd("SliceChannel", [self],
                          {"num_outputs": num_outputs, "axis": axis,
                           "squeeze_axis": squeeze_axis})

    def slice(self, begin, end, step=None):
        return _invoke_nd("slice", [self], {"begin": begin, "end": end, "step": step})

    def slice_axis(self, axis, begin, end):
        return _invoke_nd("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return _invoke_nd("take", [self, _as_nd(indices)], {"axis": axis, "mode": mode})

    def one_hot(self, depth, **kw):
        return _invoke_nd("one_hot", [self], dict(kw, depth=depth))

    def pick(self, index, axis=-1, keepdims=False):
        return _invoke_nd("pick", [self, _as_nd(index)],
                          {"axis": axis, "keepdims": keepdims})

    def clip(self, a_min, a_max):
        return _invoke_nd("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self):
        return _invoke_nd("abs", [self], {})

    def sign(self):
        return _invoke_nd("sign", [self], {})

    def sqrt(self):
        return _invoke_nd("sqrt", [self], {})

    def square(self):
        return _invoke_nd("square", [self], {})

    def exp(self):
        return _invoke_nd("exp", [self], {})

    def log(self):
        return _invoke_nd("log", [self], {})

    def relu(self):
        return _invoke_nd("relu", [self], {})

    def sigmoid(self):
        return _invoke_nd("sigmoid", [self], {})

    def tanh(self):
        return _invoke_nd("tanh", [self], {})

    def softmax(self, axis=-1):
        return _invoke_nd("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return _invoke_nd("log_softmax", [self], {"axis": axis})

    def sum(self, axis=None, keepdims=False, **kw):
        return _invoke_nd("sum", [self], {"axis": axis, "keepdims": keepdims})

    def nansum(self, axis=None, keepdims=False, **kw):
        return _invoke_nd("nansum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False, **kw):
        return _invoke_nd("mean", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False, **kw):
        return _invoke_nd("prod", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False, **kw):
        return _invoke_nd("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False, **kw):
        return _invoke_nd("min", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return _invoke_nd("norm", [self], {"ord": ord, "axis": axis,
                                           "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return _invoke_nd("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return _invoke_nd("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True):
        return _invoke_nd("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return _invoke_nd("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return _invoke_nd("topk", [self], {"axis": axis, "k": k,
                                           "ret_typ": ret_typ, "is_ascend": is_ascend})

    def dot(self, other, transpose_a=False, transpose_b=False):
        return _invoke_nd("dot", [self, _as_nd(other)],
                          {"transpose_a": transpose_a, "transpose_b": transpose_b})

    def broadcast_to(self, shape):
        return _invoke_nd("broadcast_to", [self], {"shape": shape})

    def broadcast_like(self, other):
        return _invoke_nd("broadcast_like", [self, other], {})

    def tile(self, reps):
        return _invoke_nd("tile", [self], {"reps": reps})

    def repeat(self, repeats=1, axis=None):
        return _invoke_nd("repeat", [self], {"repeats": repeats, "axis": axis})

    def flip(self, axis):
        return _invoke_nd("reverse", [self], {"axis": axis})

    def zeros_like(self, **kw):
        return _invoke_nd("zeros_like", [self], {})

    def ones_like(self, **kw):
        return _invoke_nd("ones_like", [self], {})

    # ------------------------------------------------------------------
    # autograd surface (parity: ndarray.py attach_grad/backward)
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        from .. import autograd

        autograd.mark_variables([self], [zeros(self.shape, dtype=self.dtype,
                                               ctx=self._ctx)],
                                grad_reqs=grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd

        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def _conv_index(self, key):
        if isinstance(key, NDArray):
            return key._data
        if isinstance(key, list):
            # numpy/reference-style list indexing: a[[0, 2]] is an
            # integer-array index (jax rejects bare sequences; an empty
            # list must coerce to an INT indexer, not float64)
            return np.asarray(key) if key else np.asarray(key, np.int64)
        if isinstance(key, tuple):
            return tuple(
                k._data if isinstance(k, NDArray)
                else (np.asarray(k) if k else np.asarray(k, np.int64))
                if isinstance(k, list) else k
                for k in key)
        return key

    @staticmethod
    def _key_has_arrays(key):
        if _is_jax_array(key) or isinstance(key, np.ndarray):
            return True
        if isinstance(key, tuple):
            return any(_is_jax_array(k) or isinstance(k, np.ndarray)
                       for k in key)
        return False

    def __getitem__(self, key):
        from .. import autograd

        key = self._conv_index(key)
        if not self._key_has_arrays(key):
            return _invoke_nd("_index_static", [self], {"key": key})
        if not isinstance(key, tuple):
            return _invoke_nd("_index_array",
                              [self, NDArray(_jnp().asarray(key))], {})
        # tuple mixing arrays and slices: not taped (rare path)
        if autograd.is_recording() and self._tape_ref is not None:
            raise MXNetError(
                "mixed array/slice indexing is not differentiable; "
                "call .detach() first or index with a single array")
        return NDArray(self._data[key], self._ctx)

    def __setitem__(self, key, value):
        from .. import autograd

        if autograd.is_recording() and self._tape_ref is not None:
            # parity: reference raises on in-place writes to arrays in a
            # recorded graph (version check in imperative autograd)
            raise MXNetError(
                "in-place assignment to an NDArray that is part of a "
                "recorded computation is not supported; use .detach()")
        jnp = _jnp()
        key = self._conv_index(key)
        if isinstance(value, NDArray):
            value = value._data
        if key is None or (isinstance(key, slice)
                           and key == slice(None, None, None)):
            if np.isscalar(value):
                self._rebind(jnp.full_like(self._data, value))
            else:
                v = jnp.asarray(value, dtype=self._data.dtype)
                self._rebind(jnp.broadcast_to(v, self.shape) + jnp.zeros_like(self._data))
            return
        if np.isscalar(value):
            self._rebind(self._data.at[key].set(value))
        else:
            self._rebind(self._data.at[key].set(
                jnp.asarray(value, dtype=self._data.dtype)))

    # ------------------------------------------------------------------
    # arithmetic operators
    # ------------------------------------------------------------------
    def _binop(self, other, op_nd, op_sc, reverse=False):
        if isinstance(other, NDArray):
            lhs, rhs = (other, self) if reverse else (self, other)
            return _invoke_nd(op_nd, [lhs, rhs], {})
        return _invoke_nd(op_sc, [self], {"scalar": float(other)})

    def __add__(self, o):
        return self._binop(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binop(o, "broadcast_sub", "_rminus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binop(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        return self._binop(o, "broadcast_div", "_rdiv_scalar", reverse=True)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __mod__(self, o):
        return self._binop(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        return self._binop(o, "broadcast_mod", "_rmod_scalar", reverse=True)

    def __pow__(self, o):
        return self._binop(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return self._binop(o, "broadcast_power", "_rpower_scalar", reverse=True)

    def __neg__(self):
        return _invoke_nd("negative", [self], {})

    def __abs__(self):
        return _invoke_nd("abs", [self], {})

    def __eq__(self, o):
        if o is None:
            return False
        return self._binop(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binop(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binop(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binop(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binop(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binop(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    # in-place: rebind (version bump)
    def __iadd__(self, o):
        return self._rebind(self.__add__(o)._data)

    def __isub__(self, o):
        return self._rebind(self.__sub__(o)._data)

    def __imul__(self, o):
        return self._rebind(self.__mul__(o)._data)

    def __itruediv__(self, o):
        return self._rebind(self.__truediv__(o)._data)

    __idiv__ = __itruediv__

    def __imod__(self, o):
        return self._rebind(self.__mod__(o)._data)

    def __repr__(self):
        try:
            arr = self.asnumpy()
            body = str(arr)
        except Exception:  # under trace
            body = "<traced %s>" % (self.shape,)
        return "\n%s\n<NDArray %s @%s>" % (
            body, "x".join(str(d) for d in self.shape), self._ctx)

    def __getstate__(self):
        return {"data": self.asnumpy(), "ctx": str(self._ctx)}

    def __setstate__(self, state):
        import jax.numpy as jnp

        self._data = jnp.asarray(state["data"])
        self._ctx = cpu()
        self._tape_ref = None
        self._grad = None
        self._grad_req = "null"
        self._stype = "default"


def _as_nd(x, dtype=None, ctx=None):
    if isinstance(x, NDArray):
        return x
    jnp = _jnp()
    if np.isscalar(x) or isinstance(x, (list, tuple, np.ndarray)):
        return NDArray(jnp.asarray(np.asarray(
            x, dtype=dtype if dtype is not None else None)), ctx)
    if _is_jax_array(x):
        return NDArray(x, ctx)
    raise MXNetError("cannot convert %r to NDArray" % (type(x),))


# ---------------------------------------------------------------------------
# op dispatch: unwrap -> jax fn -> wrap (+ tape recording + mutation rebind)
# This is the TPU-native analogue of MXImperativeInvokeEx ->
# Imperative::Invoke -> Engine::PushAsync (src/c_api/c_api_ndarray.cc:81-143,
# src/imperative/imperative.cc:89).
# ---------------------------------------------------------------------------

_SIG_CACHE = {}


def _array_kwarg_order(info):
    if info.name not in _SIG_CACHE:
        try:
            params = list(inspect.signature(info.fn).parameters)
        except (TypeError, ValueError):
            params = []
        _SIG_CACHE[info.name] = params
    return _SIG_CACHE[info.name]


# ---------------------------------------------------------------------------
# eager dispatch: per-op jit cache
#
# The reference keeps eager ops cheap with the dependency engine + cached
# kernels (src/imperative/imperative.cc:89).  The TPU-native counterpart:
# every eager op call dispatches through a cached jax.jit program keyed on
# (op, static attrs); XLA's own per-shape executable cache then makes
# repeated same-shape calls microseconds instead of a fresh trace+compile.
# Ops with data-dependent output shapes fail jit once and are blacklisted
# to direct (op-by-op) dispatch.
# ---------------------------------------------------------------------------

_EAGER_JIT_CACHE = {}
# ops never worth a jit trace: zero-FLOP indexing where the index value
# itself would key the cache (every distinct slice = a fresh compile)
# ops that must see CONCRETE inputs when eager: _index_static bakes the
# key into the trace; take's mode='raise' bounds check needs host values
_EAGER_JIT_SKIP = {"_index_static", "take"}


def _trace_state_clean():
    """True when no jax trace (jit/vjp/eval_shape) is in progress."""
    import jax

    return jax.core.trace_ctx.is_top_level()


def _freeze_attrs(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze_attrs(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze_attrs(x)) for k, x in v.items()))
    if isinstance(v, np.ndarray):
        return (v.shape, str(v.dtype), v.tobytes())
    return v


def _eager_apply(info, raw, attrs, rng=None):
    """Run an op's jax fn, through the per-op jit cache when eager.

    `rng` (a PRNG key) is supplied by the caller when the call is being
    recorded on the autograd tape, so the backward replay can re-draw the
    same randomness (Dropout's backward mask must equal the forward's).
    """
    import jax

    from .. import random as _random

    def _direct():
        if rng is not None:
            _random.push_trace_key(rng)
            try:
                return info.fn(*raw, **attrs)
            finally:
                _random.pop_trace_key()
        return info.fn(*raw, **attrs)

    if info.name in _EAGER_JIT_SKIP or not _trace_state_clean():
        # inside an outer trace (CachedOp / ShardedTrainer / eval_shape):
        # inline directly, the outer jit owns compilation
        return _direct()
    from .. import autograd

    try:
        # ambient train/predict mode is read inside some op fns (Dropout,
        # BatchNorm) and baked into the trace — it must key the cache
        ckey = (info.name, autograd.is_training(), _freeze_attrs(attrs))
        hash(ckey)
    except TypeError:
        return _direct()
    takes_key = info.uses_rng or rng is not None
    jitted = _EAGER_JIT_CACHE.get((ckey, takes_key))
    if jitted is None:
        fn, static_attrs = info.fn, dict(attrs)

        if takes_key:
            def _wrapped(key, arrays):
                _random.push_trace_key(key)
                try:
                    return fn(*arrays, **static_attrs)
                finally:
                    _random.pop_trace_key()
        else:
            # deterministic op: no key argument, no per-call stream split
            def _wrapped(arrays):
                return fn(*arrays, **static_attrs)

        jitted = jax.jit(_wrapped)
        _EAGER_JIT_CACHE[(ckey, takes_key)] = jitted
    try:
        if takes_key:
            return jitted(rng if rng is not None else _random.next_key(),
                          tuple(raw))
        return jitted(tuple(raw))
    except Exception:
        _EAGER_JIT_CACHE.pop((ckey, takes_key), None)
        # distinguish "op is not jittable" (fallback succeeds -> blacklist)
        # from an ordinary user error (fallback raises the real error)
        result = _direct()
        _EAGER_JIT_SKIP.add(info.name)
        return result


_f64_warned = False


def _warn_f64_downcast():
    """One-time warning: the reference preserves numpy float64; here it is
    downcast to float32 (jax x64 is off by default on TPU)."""
    global _f64_warned
    if not _f64_warned:
        _f64_warned = True
        import warnings

        warnings.warn(
            "mx.nd.array: float64 input downcast to float32 (TPU-native "
            "default; pass dtype='float64' with jax_enable_x64 to keep "
            "double precision)", stacklevel=3)


def _invoke_nd(op_name, inputs, attrs, out=None):
    from .. import autograd

    info = get_op(op_name)
    attrs = clean_attrs(attrs)

    # split array-valued kwargs into positional inputs ordered by fn signature
    arr_kwargs = {k: v for k, v in attrs.items()
                  if isinstance(v, NDArray)}
    if arr_kwargs:
        order = _array_kwarg_order(info)
        for k in sorted(arr_kwargs, key=lambda k: order.index(k) if k in order else 999):
            inputs = list(inputs) + [arr_kwargs[k]]
            del attrs[k]

    nd_inputs = [x if isinstance(x, NDArray) else _as_nd(x) for x in inputs]
    raw = [x._data for x in nd_inputs]

    # a recorded rng-op pins its key so the backward replay re-draws the
    # identical randomness (Dropout's grad mask == its forward mask)
    rng = None
    if info.uses_rng and autograd.is_recording() and info.differentiable:
        from .. import random as _random

        rng = _random.next_key()

    try:
        if _profiler.aggregate_enabled():
            import jax as _jax

            _t0 = _perf_counter()
            result = _eager_apply(info, raw, attrs, rng=rng)
            # async dispatch returns futures: block so the timing covers
            # device execution, not just dispatch
            _jax.block_until_ready(result)
            _profiler.record_op_time(info.name, _perf_counter() - _t0)
        else:
            result = _eager_apply(info, raw, attrs, rng=rng)
    except Exception as e:
        raise MXNetError("error in operator %s: %s" % (op_name, e)) from e

    is_tuple = isinstance(result, tuple)
    rets = result if is_tuple else (result,)

    # mutation rebinding (optimizer kernels etc.)
    if info.mutate_inputs:
        for idx, r in zip(info.mutate_inputs, rets):
            if idx < len(nd_inputs):
                nd_inputs[idx]._rebind(r)
        main = nd_inputs[info.mutate_inputs[0]]
        if out is not None and out is not main:
            out._rebind(main._data)
            return out
        return main

    eng = _engine.get()
    outputs = [NDArray(eng.maybe_block(r),
                       nd_inputs[0]._ctx if nd_inputs else current_context())
               for r in rets]

    # autograd tape
    if autograd.is_recording() and info.differentiable:
        autograd.record_op(info, attrs, nd_inputs, outputs, rng_key=rng)

    if out is not None:
        if isinstance(out, (list, tuple)):
            for o, r in zip(out, outputs):
                o._rebind(r._data)
            return list(out)
        out._rebind(outputs[0]._data)
        return out
    if len(outputs) == 1:
        return outputs[0]
    return outputs


# ---------------------------------------------------------------------------
# creation / module-level API (parity: mx.nd.{array,zeros,ones,...})
# ---------------------------------------------------------------------------


def array(source_array, ctx=None, dtype=None):
    jnp = _jnp()
    if isinstance(source_array, NDArray):
        d = source_array._data
        if dtype is not None:
            d = d.astype(dtype_str_to_np(dtype))
        return NDArray(d, ctx or source_array._ctx)
    npv = np.asarray(source_array)
    if dtype is None:
        # python lists default to float32 (reference: mx.nd.array);
        # explicit numpy arrays keep their dtype (except f64 -> f32)
        if not isinstance(source_array, np.ndarray):
            dtype = np.float32 if npv.dtype.kind in "fiub" and \
                npv.dtype != np.bool_ else npv.dtype
        else:
            if npv.dtype == np.float64:
                _warn_f64_downcast()
            dtype = np.float32 if npv.dtype == np.float64 else npv.dtype
    npv = npv.astype(dtype_str_to_np(dtype) if isinstance(dtype, str) else dtype)
    import jax

    if npv.dtype in (np.int64, np.uint64) and npv.size \
            and not jax.config.jax_enable_x64:
        # jax downcasts 64-bit ints to 32-bit at device_put when x64 is
        # off; values beyond the 32-bit range would TRUNCATE silently —
        # make it loud (the reference's large-tensor int64 support is a
        # build flag; here it is jax_enable_x64).  Bounds differ by
        # signedness: uint64 -> uint32 keeps values up to 2**32-1.
        hi = 2**32 - 1 if npv.dtype == np.uint64 else 2**31 - 1
        mx_, mn_ = int(npv.max()), int(npv.min())
        if mx_ > hi or mn_ < -2**31:
            import warnings
            warnings.warn(
                "mx.nd.array: %s values exceed the 32-bit range and "
                "will be truncated (jax x64 is off); enable "
                "large-tensor mode with "
                "jax.config.update('jax_enable_x64', True) before any "
                "array creation" % npv.dtype, stacklevel=2)
    ctx = ctx or current_context()
    return NDArray(jax.device_put(npv, ctx.jax_device), ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    return _invoke_nd("_zeros", [], {"shape": shape, "dtype": dtype or "float32"})


def ones(shape, ctx=None, dtype=None, **kwargs):
    return _invoke_nd("_ones", [], {"shape": shape, "dtype": dtype or "float32"})


def full(shape, val, ctx=None, dtype=None, out=None):
    return _invoke_nd("_full", [], {"shape": shape, "value": val,
                                    "dtype": dtype or "float32"}, out=out)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    return _invoke_nd("_arange", [], {"start": start, "stop": stop, "step": step,
                                      "repeat": repeat, "dtype": dtype or "float32"})


def concatenate(arrays, axis=0, always_copy=True):
    return _invoke_nd("Concat", list(arrays), {"dim": axis})


def concat(*arrays, dim=1, **kw):
    return _invoke_nd("Concat", list(arrays), {"dim": dim})


def stack(*arrays, axis=0, **kw):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return _invoke_nd("stack", list(arrays), {"axis": axis})


def moveaxis(tensor, source, destination):
    return _invoke_nd("moveaxis", [tensor],
                      {"source": source, "destination": destination})


def onehot_encode(indices, out):
    depth = out.shape[1]
    res = _invoke_nd("one_hot", [indices], {"depth": depth})
    out._rebind(res._data.astype(out._data.dtype))
    return out


def waitall():
    _engine.get().wait_for_all()


# ---------------------------------------------------------------------------
# serialization (parity: mx.nd.save/load, src/ndarray/ndarray.cc ser/de).
# Two on-disk formats, distinguished by content sniffing on load:
#   - "binary": the reference's magic-numbered record format — upstream
#     *.params files load directly and saves load in upstream
#     (ndarray/legacy_io.py)
#   - "npz" (default): npz with a manifest — portable, versioned via key
#     prefix
# ---------------------------------------------------------------------------

_SAVE_PREFIX = "mxtpu:v1:"


def save(fname, data, format="npz"):
    if format == "binary":
        from . import legacy_io

        if isinstance(data, NDArray):
            legacy_io.save_binary(fname, [data.asnumpy()])
        elif isinstance(data, (list, tuple)):
            legacy_io.save_binary(fname, [a.asnumpy() for a in data])
        elif isinstance(data, dict):
            keys = list(data.keys())
            legacy_io.save_binary(fname,
                                  [data[k].asnumpy() for k in keys], keys)
        else:
            raise MXNetError("save expects NDArray, list or dict")
        return
    if format != "npz":
        raise MXNetError("unknown save format %r (use 'npz' or 'binary')"
                         % (format,))
    arrays = {}
    if isinstance(data, NDArray):
        arrays["%s0" % _SAVE_PREFIX] = data.asnumpy()
    elif isinstance(data, (list, tuple)):
        for i, a in enumerate(data):
            arrays["%s%d" % (_SAVE_PREFIX, i)] = a.asnumpy()
    elif isinstance(data, dict):
        for k, a in data.items():
            arrays["%sdict:%s" % (_SAVE_PREFIX, k)] = a.asnumpy()
    else:
        raise MXNetError("save expects NDArray, list or dict")
    # atomic: np.savez into a temp file + fsync + os.replace, so a crash
    # mid-save never leaves a torn .params at the final path (and the
    # file-object form keeps numpy from appending .npz to the name)
    from ..checkpoint import atomic_writer

    with atomic_writer(fname) as f:
        np.savez(f, **arrays)


def load(fname):
    from . import legacy_io

    if legacy_io.is_binary_format(fname):
        arrays, names = legacy_io.load_binary(fname)
        if names:
            return {k: array(a) for k, a in zip(names, arrays)}
        return [array(a) for a in arrays]
    with np.load(fname, allow_pickle=False) as f:
        keys = list(f.keys())
        if any(k.startswith(_SAVE_PREFIX + "dict:") for k in keys):
            return {k[len(_SAVE_PREFIX) + 5:]: array(f[k]) for k in keys}
        items = sorted(
            ((int(k[len(_SAVE_PREFIX):]), k) for k in keys), key=lambda t: t[0])
        out = [array(f[k]) for _, k in items]
        return out


def imports(*args, **kwargs):  # pragma: no cover - placeholder
    raise NotImplementedError
