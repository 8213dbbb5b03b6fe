"""Custom operator framework: CustomOp / CustomOpProp / register.

Reference parity: ``python/mxnet/operator.py:426`` (CustomOp),
``:472`` (CustomOpProp), ``:692`` (register), driven in the reference by
``src/operator/custom/custom.cc``.  Usage is identical to upstream::

    @mx.operator.register("sqr")
    class SqrProp(mx.operator.CustomOpProp):
        ...
    y = mx.nd.Custom(x, op_type="sqr")
    s = mx.sym.Custom(data=d, op_type="sqr")

TPU-native design: the user's numpy-level ``forward``/``backward`` run on
the *host* through ``jax.pure_callback``, so a Custom op is legal inside
jit / hybridize / the Symbol executor — XLA suspends, calls back into
Python, and resumes.  Gradients are wired with ``jax.custom_vjp``: the
backward callback invokes ``CustomOp.backward`` with the same
(out_grad, in_data, out_data) contract as the reference engine.  This
replaces the reference's dedicated C++ driver + engine-thread handshake;
the dependency bookkeeping it did is inherited from XLA's data flow.
"""
from __future__ import annotations

import numpy as onp

from .base import MXNetError

__all__ = ["CustomOp", "CustomOpProp", "register", "get_prop_cls"]

_CUSTOM_REGISTRY = {}


class CustomOp:
    """Base class for operators implemented in Python (parity:
    operator.py:426)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        pass

    def assign(self, dst, req, src):
        """Assign ``src`` to ``dst`` honoring the write request."""
        if req == "null":
            return
        if req in ("write", "inplace"):
            dst[:] = src
        elif req == "add":
            dst[:] = dst + src


class CustomOpProp:
    """Describes a custom op: arity, shapes, dtypes (parity:
    operator.py:472)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return []

    def infer_shape(self, in_shape):
        return in_shape, (in_shape[0],) * len(self.list_outputs()), ()

    def infer_type(self, in_type):
        return (in_type,
                [in_type[0]] * len(self.list_outputs()),
                [in_type[0]] * len(self.list_auxiliary_states()))

    def infer_storage_type(self, in_stype):
        return in_stype, ["default"] * len(self.list_outputs()), \
            ["default"] * len(self.list_auxiliary_states())

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad_:
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return CustomOp()


def register(reg_name):
    """Decorator registering a CustomOpProp subclass under ``op_type``
    (parity: operator.py:692)."""

    def _do(prop_cls):
        if not issubclass(prop_cls, CustomOpProp):
            raise MXNetError("can only register subclass of CustomOpProp")
        _CUSTOM_REGISTRY[reg_name] = prop_cls
        return prop_cls

    return _do


def get_prop_cls(op_type):
    if op_type not in _CUSTOM_REGISTRY:
        raise MXNetError("custom op type %r is not registered with "
                         "mx.operator.register" % op_type)
    return _CUSTOM_REGISTRY[op_type]


_PROP_CACHE = {}


def _make_prop(op_type, ctor_kwargs):
    # reference custom.cc hands ctor kwargs to the prop as strings;
    # memoized since num_outputs/shape queries re-ask per node access
    key = (op_type, tuple(sorted((k, str(v))
                                 for k, v in ctor_kwargs.items())))
    prop = _PROP_CACHE.get(key)
    if prop is None:
        prop = get_prop_cls(op_type)(**{k: str(v) for k, v in
                                        ctor_kwargs.items()})
        _PROP_CACHE[key] = prop
    return prop


def _cpu_nd(arr):
    """numpy -> NDArray on the host backend (no accelerator round-trip)."""
    import jax
    import jax.numpy as jnp

    from .context import cpu
    from .ndarray.ndarray import NDArray

    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        return NDArray(jnp.asarray(arr), ctx=cpu())


def _custom_num_outputs(attrs):
    ctor = {k: v for k, v in attrs.items() if k != "op_type"}
    return len(_make_prop(attrs["op_type"], ctor).list_outputs())


def _shapes3(res, what):
    """Normalize infer_shape/infer_type's 2-or-3-tuple return."""
    if len(res) == 2:
        return res[0], res[1], ()
    if len(res) == 3:
        return res
    raise MXNetError("CustomOpProp.%s must return 2 or 3 lists" % what)


def _custom_fn(*arrays, op_type=None, **ctor_kwargs):
    """The registered 'Custom' op body: host callbacks wired into the
    trace with pure_callback, gradients via custom_vjp."""
    import jax

    from . import autograd

    if op_type is None:
        raise MXNetError("Custom op requires op_type=")
    prop = _make_prop(op_type, ctor_kwargs)
    arg_names = prop.list_arguments()
    aux_names = prop.list_auxiliary_states()
    n_args = len(arg_names)
    if len(arrays) != n_args + len(aux_names):
        raise MXNetError(
            "Custom op %r expects %d arguments + %d auxiliary states, "
            "got %d inputs" % (op_type, n_args, len(aux_names),
                               len(arrays)))
    args, auxs = arrays[:n_args], arrays[n_args:]
    if auxs:
        import warnings

        warnings.warn(
            "Custom op %r: auxiliary-state mutations inside a traced "
            "(hybridized/jitted) region are not propagated back to the "
            "aux NDArrays; run the op eagerly if forward must update aux "
            "state" % op_type, RuntimeWarning, stacklevel=3)

    in_shapes = [tuple(a.shape) for a in args]
    _, out_shapes, _ = _shapes3(prop.infer_shape([list(s) for s in
                                                  in_shapes]),
                                "infer_shape")
    in_types = [onp.dtype(a.dtype) for a in args]
    _, out_types, _ = _shapes3(prop.infer_type(list(in_types)),
                               "infer_type")
    out_avals = tuple(jax.ShapeDtypeStruct(tuple(s), onp.dtype(t))
                      for s, t in zip(out_shapes, out_types))
    in_avals = tuple(jax.ShapeDtypeStruct(s, t)
                     for s, t in zip(in_shapes, in_types))
    op = prop.create_operator(None, [list(s) for s in in_shapes],
                              in_types)
    is_train = autograd.is_training()
    n_out = len(out_avals)

    def host_forward(*vals):
        in_nd = [_cpu_nd(v) for v in vals[:n_args]]
        aux_nd = [_cpu_nd(v) for v in vals[n_args:]]
        out_nd = [_cpu_nd(onp.zeros(a.shape, a.dtype)) for a in out_avals]
        op.forward(is_train, ["write"] * n_out, in_nd, out_nd, aux_nd)
        return tuple(onp.asarray(o.asnumpy(), a.dtype)
                     for o, a in zip(out_nd, out_avals))

    def host_backward(*vals):
        k = 0
        ins = [_cpu_nd(v) for v in vals[:n_args]]
        k = n_args
        aux_nd = [_cpu_nd(v) for v in vals[k:k + len(auxs)]]
        k += len(auxs)
        outs = [_cpu_nd(v) for v in vals[k:k + n_out]]
        k += n_out
        ograds = [_cpu_nd(v) for v in vals[k:]]
        igrads = [_cpu_nd(onp.zeros(a.shape, a.dtype)) for a in in_avals]
        op.backward(["write"] * n_args, ograds, ins, outs, igrads,
                    aux_nd)
        return tuple(onp.asarray(g.asnumpy(), a.dtype)
                     for g, a in zip(igrads, in_avals))

    @jax.custom_vjp
    def call(*flat):
        res = jax.pure_callback(host_forward, out_avals, *flat)
        return tuple(res)

    def call_fwd(*flat):
        res = call(*flat)
        return res, (flat, res)

    def call_bwd(saved, cts):
        flat, outs = saved
        igrads = jax.pure_callback(host_backward, in_avals,
                                   *(flat + tuple(outs) + tuple(cts)))
        # aux states receive no gradient
        return tuple(igrads) + tuple(jax.numpy.zeros(x.shape, x.dtype)
                                     for x in auxs)

    call.defvjp(call_fwd, call_bwd)
    outs = call(*args, *auxs)
    return outs if n_out > 1 else outs[0]


def _register_custom_op():
    from .ops.registry import register as _reg_op

    _reg_op("Custom", num_inputs=-1, num_outputs=_custom_num_outputs)(
        _custom_fn)


_register_custom_op()


# ---------------------------------------------------------------------------
# nd.Custom / sym.Custom surfaces (kwarg inputs ordered by the prop's
# declared argument names, as the reference C++ driver does)
# ---------------------------------------------------------------------------


def _order_inputs(prop, pos_args, array_kwargs):
    names = prop.list_arguments() + prop.list_auxiliary_states()
    inputs = []
    pos = list(pos_args)
    missing = []
    for n in names:
        if n in array_kwargs:
            inputs.append(array_kwargs.pop(n))
        elif pos:
            inputs.append(pos.pop(0))
        else:
            missing.append(n)
    if missing:
        raise MXNetError("Custom op %s: missing inputs %s"
                         % (type(prop).__name__, missing))
    if pos or array_kwargs:
        raise MXNetError(
            "Custom op %s: unmatched inputs (extra positional: %d, "
            "unknown names: %s)" % (type(prop).__name__, len(pos),
                                    sorted(array_kwargs)))
    return inputs


def _custom_surface(array_type, invoke):
    def Custom(*args, **kwargs):
        op_type = kwargs.pop("op_type", None)
        name = kwargs.pop("name", None)
        if op_type is None:
            raise MXNetError("Custom requires op_type=")
        arr_kw = {k: v for k, v in kwargs.items()
                  if isinstance(v, array_type)}
        ctor = {k: str(v) for k, v in kwargs.items() if k not in arr_kw}
        prop = _make_prop(op_type, ctor)
        inputs = _order_inputs(prop, args, dict(arr_kw))
        attrs = dict(ctor)
        attrs["op_type"] = op_type
        return invoke(inputs, attrs, name)

    Custom.__doc__ = "Invoke a registered custom operator (op_type=...)."
    return Custom


def _eager_custom(prop, inputs, n_out):
    """Concrete (non-traced) execution: run the user op directly on host
    numpy — no host callback needed outside a trace — and tape a custom
    backward that reuses the SAME operator instance and the saved
    forward tensors (stateful/nondeterministic ops stay consistent)."""
    from . import autograd
    from .ndarray.ndarray import NDArray

    arg_names = prop.list_arguments()
    n_args = len(arg_names)
    in_shapes = [tuple(a.shape) for a in inputs[:n_args]]
    _, out_shapes, _ = _shapes3(prop.infer_shape([list(s) for s in
                                                  in_shapes]),
                                "infer_shape")
    in_types = [onp.dtype(a.dtype) for a in inputs[:n_args]]
    _, out_types, _ = _shapes3(prop.infer_type(list(in_types)),
                               "infer_type")
    op = prop.create_operator(None, [list(s) for s in in_shapes], in_types)

    in_nd = [_cpu_nd(a.asnumpy()) for a in inputs[:n_args]]
    aux_nd = [_cpu_nd(a.asnumpy()) for a in inputs[n_args:]]
    out_nd = [_cpu_nd(onp.zeros(tuple(s), onp.dtype(t)))
              for s, t in zip(out_shapes, out_types)]
    op.forward(autograd.is_training(), ["write"] * n_out, in_nd, out_nd,
               aux_nd)
    # aux mutation is visible eagerly, as in the reference engine
    for dst, src in zip(inputs[n_args:], aux_nd):
        dst._rebind(src.copyto(dst.context)._data)
    outputs = [o.copyto(inputs[0].context) if inputs else o
               for o in out_nd]

    if autograd.is_recording():
        from .ops.registry import OpInfo

        def custom_backward(out_grads_raw):
            ograds = [_cpu_nd(onp.asarray(g)) for g in out_grads_raw]
            igrads = [_cpu_nd(onp.zeros(tuple(s), t))
                      for s, t in zip(in_shapes, in_types)]
            op.backward(["write"] * n_args, ograds, in_nd, out_nd,
                        igrads, aux_nd)
            # aux inputs get no gradient
            return [g._data for g in igrads] + \
                [onp.zeros(a.shape, a.dtype) for a in aux_nd]

        info = OpInfo("Custom", None, num_inputs=len(inputs),
                      num_outputs=n_out)
        autograd.record_op(info, {}, list(inputs), outputs,
                           custom_backward=custom_backward)
    return outputs if n_out > 1 else outputs[0]


def make_nd_custom():
    import jax

    from .ndarray.ndarray import NDArray, _invoke_nd

    def invoke(inputs, attrs, name):
        if not any(isinstance(a._data, jax.core.Tracer) for a in inputs):
            prop = _make_prop(attrs["op_type"],
                              {k: v for k, v in attrs.items()
                               if k != "op_type"})
            return _eager_custom(prop, inputs,
                                 len(prop.list_outputs()))
        return _invoke_nd("Custom", inputs, attrs)

    return _custom_surface(NDArray, invoke)


def make_sym_custom():
    from .symbol.symbol import Symbol, _invoke_sym

    return _custom_surface(
        Symbol, lambda inputs, attrs, name: _invoke_sym("Custom", inputs,
                                                        attrs, name=name))
