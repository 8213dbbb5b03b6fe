"""Gluon Block / HybridBlock / SymbolBlock + CachedOp.

Reference parity: python/mxnet/gluon/block.py (Block:127, __call__:535;
HybridBlock:671 — hybridize():832 -> _build_cache:748 -> CachedOp:785;
SymbolBlock:952) and src/imperative/cached_op.{h,cc} (the hybridize/JIT
engine: Forward:889, StaticForward:728 static memory planning + bulking).

TPU-native design: CachedOp IS jax.jit.  hybridize() traces the block's
hybrid_forward with NDArrays wrapping jax tracers and compiles one XLA
program per (train/eval, input signature) — XLA does the memory planning
and fusion CachedOp's StaticForward did by hand.  BatchNorm-style
moving-stat updates are threaded functionally through a trace-time sink
and rebound after each call; dropout keys are jit arguments so masks
re-randomize every step (unlike a baked constant).
"""
from __future__ import annotations

import contextlib
import copy
import re
import threading
from collections import OrderedDict

import jax
import numpy as np

from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray.ndarray import NDArray, array, _invoke_nd
from ..ops.registry import OpInfo
from .. import autograd
from .. import profiler as _profiler
from .. import random as _random
from ..symbol import symbol as _symbol
from ..name import NameManager
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedOp"]

_aux_sink = threading.local()


def _current_aux_sink():
    return getattr(_aux_sink, "sink", None)


_trace_state = threading.local()


def _is_tracing():
    return getattr(_trace_state, "active", False)


# Tracing a block swaps its Parameters' arrays for tracers IN PLACE, so
# two threads tracing blocks that share Parameters (AsyncPredictor builds
# one Predictor per device over one net, and each compiles on its own
# worker thread) would save and restore each other's tracers — leaked
# tracers, or a Parameter left holding another trace's cast weights.
# Every swap-trace-restore window holds this lock; it is re-entrant
# (traces nest within a thread) and is only ever held while tracing,
# never while a compiled program runs.
_param_swap_lock = threading.RLock()


@contextlib.contextmanager
def swapped_params(params, arrays, training=False):
    """Trace a block's forward against externally supplied parameter
    arrays: swaps each gluon ``Parameter``'s device array for the
    matching entry of ``arrays`` (typically jit tracers), activates the
    NDArray trace state, pins autograd ``training``, and restores
    everything on exit.  The one param-swap recipe shared by the traced
    front-ends (``serving.Predictor.from_block``,
    ``generate.PagedGenerationEngine``, ``tools/bench_decode.py``).  Holds
    :data:`_param_swap_lock` for the whole window."""
    from .. import autograd

    with _param_swap_lock:
        saved = []
        prev_train = autograd.set_training(training)
        prev_trace = getattr(_trace_state, "active", False)
        _trace_state.active = True
        try:
            for p, arr in zip(params, arrays):
                d = p.data()
                saved.append((d, d._data))
                d._data = arr
            yield
        finally:
            _trace_state.active = prev_trace
            autograd.set_training(prev_train)
            for d, old in saved:
                d._data = old


def _abstract_eval_forward(block, args):
    """Finish deferred parameter inits by abstract-evaluating the forward.

    TPU-native replacement for an eager warm-up pass: jax.eval_shape runs
    the whole forward with abstract values — shapes propagate, deferred
    params initialize (host numpy + device_put), but no device program is
    traced or compiled.  On TPU an eager warm-up would be hundreds of
    one-op compilations (the round-1 bench timeout); this is milliseconds.
    Counterpart of the reference's shape-inference pass
    (src/executor/infer_graph_attr_pass.cc:647).
    """
    import jax
    import numpy as _np

    from ..ndarray.ndarray import NDArray as _ND

    raws = [a._data if isinstance(a, _ND) else a for a in args]

    def probe(*xs):
        prev_sink = getattr(_aux_sink, "sink", None)
        prev_tr = getattr(_trace_state, "active", False)
        _aux_sink.sink = []  # discard moving-stat updates (tracers)
        _trace_state.active = True
        try:
            out = block.forward(*[_ND(x) for x in xs])
        finally:
            _aux_sink.sink = prev_sink
            _trace_state.active = prev_tr
        flat, _tmpl = _flatten_nested(out)
        return tuple(o._data for o in flat)

    specs = [jax.ShapeDtypeStruct(tuple(_np.shape(r)) if not hasattr(r, "shape")
                                  else tuple(r.shape),
                                  getattr(r, "dtype", _np.float32))
             for r in raws]
    with _param_swap_lock:   # reads the live params: no swap in flight
        return jax.eval_shape(probe, *specs)


def _flatten_nested(out):
    """Flatten arbitrarily nested list/tuple output into (flat NDArray
    list, template); the template mirrors the nesting with flat-list
    indices at leaf positions (parity: block.py _flatten/_regroup —
    lets hybrid_forward return e.g. (output, [state_h, state_c]))."""
    flat = []

    def rec(o):
        if isinstance(o, (list, tuple)):
            t = [rec(x) for x in o]
            return t if isinstance(o, list) else tuple(t)
        flat.append(o)
        return len(flat) - 1

    return flat, rec(out)


def _regroup_nested(tmpl, flat):
    if isinstance(tmpl, (list, tuple)):
        vals = [_regroup_nested(t, flat) for t in tmpl]
        return vals if isinstance(tmpl, list) else tuple(vals)
    return flat[tmpl]


class _BlockScope:
    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = NameManager.current().get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        from ..name import Prefix

        self._name_scope = Prefix(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, *a):
        if self._block._empty_prefix:
            return
        self._name_scope.__exit__(*a)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


class Block:
    """Base class for all layers/models (parity: block.py:127)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def _alias(self):
        return self.__class__.__name__.lower()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(
            key=key, block=_indent(str(block), 2))
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)):
                raise TypeError("Changing attribute type for {name} from "
                                "{type1} to {type2} is not allowed.".format(
                                    name=name, type1=type(existing),
                                    type2=type(value)))
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or \
                self._reg_params[name] is value, \
                "Overriding Parameter attribute %s is not allowed." % name
            self._reg_params[name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children.values():
            ret.update(cld.collect_params(select=select))
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks[len(self._forward_pre_hooks)] = hook
        return _HookHandle(self._forward_pre_hooks,
                           len(self._forward_pre_hooks) - 1)

    def register_forward_hook(self, hook):
        self._forward_hooks[len(self._forward_hooks)] = hook
        return _HookHandle(self._forward_hooks, len(self._forward_hooks) - 1)

    def apply(self, fn):
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        # what is traced inside carries the block's name to a device
        # trace, as MXNet's profiler named its operators
        # (profiler.device_table); nothing at run time, 2 us of an
        # eager call
        with jax.named_scope(self._name):
            out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        summary_rows = []

        def walk(block, prefix=""):
            n_params = sum(int(np.prod(p.shape or ()))
                           for p in block._reg_params.values())
            summary_rows.append((prefix + block.name,
                                 block.__class__.__name__, n_params))
            for c in block._children.values():
                walk(c, prefix + "  ")

        walk(self)
        print("%-50s %-20s %s" % ("Layer", "Type", "Params"))
        for name, typ, n in summary_rows:
            print("%-50s %-20s %d" % (name, typ, n))

    # -- (de)serialization ----------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        from ..ndarray import ndarray as _nd

        arg_dict = {key: val._reduce() for key, val in params.items()}
        _nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        from ..ndarray import ndarray as _nd

        loaded = _nd.load(filename)
        params = self._collect_params_with_prefix()
        if not isinstance(loaded, dict):
            raise MXNetError("load_parameters expects a dict file")
        if not any("." in k for k in loaded) and loaded and params and \
                not set(loaded).intersection(set(params)):
            # file saved with full-prefix names (ParameterDict.save)
            full = self.collect_params()
            full.load(filename, ctx, allow_missing, ignore_extra)
            return
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise MXNetError("Parameter '%s' is missing in file %s"
                                     % (name, filename))
        for name in loaded:
            if name not in params:
                if not ignore_extra:
                    raise MXNetError("Parameter '%s' in file is not present "
                                     "in this Block" % name)
                continue
            param = params[name]
            if param._data is None and param._deferred_init == ():
                param._shape = loaded[name].shape
                param.initialize(ctx=ctx or [current_context()])
            param.set_data(loaded[name])

    # legacy names
    save_params = save_parameters

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        self.load_parameters(filename, ctx, allow_missing, ignore_extra)


class _HookHandle:
    def __init__(self, hooks, idx):
        self._hooks = hooks
        self._idx = idx

    def detach(self):
        self._hooks.pop(self._idx, None)


def _indent(s_, num_spaces):
    lines = s_.split("\n")
    first = lines.pop(0)
    lines = [num_spaces * " " + line for line in lines]
    return "\n".join([first] + lines)


# ---------------------------------------------------------------------------
# CachedOp: jit-compiled block execution
# ---------------------------------------------------------------------------


class CachedOp:
    """Compiled forward for a HybridBlock (parity: src/imperative/
    cached_op.cc via MXCreateCachedOpEx)."""

    def __init__(self, block, static_alloc=False, static_shape=False,
                 remat_policy=None, fusion=None, aot=None,
                 dtype_policy=None):
        import jax

        from ..remat import resolve_policy
        from .. import fusion_cost as _fc
        from .. import aot as _aot
        from .. import dtype_policy as _dtp

        self._block = block
        self._jits = {}  # is_train -> jitted fn
        self._param_list = None  # stable order, captured at first call
        self._aux_params = None  # params receiving moving-stat updates
        self._jax = jax
        # fail fast on a typo'd policy; None defers to MXNET_REMAT_POLICY
        resolve_policy(remat_policy)
        self._remat_policy = remat_policy
        # block traces have no Symbol graph to rewrite; the plan
        # (hybridize(fusion=...) or the MXNET_FUSION default) is
        # installed around the trace and shape-specialized op fast
        # paths consult it per concrete shape (fusion_cost.scope).
        # Validate the spec now (fail fast on a typo), but keep the raw
        # spec and re-resolve per trace so a cost table installed after
        # construction (config.fusion_cost_table / MXNET_FUSION_TUNE)
        # applies to new-shape retraces — same contract as Executor,
        # which re-resolves per bind.
        _fc.resolve_fusion(fusion)
        self._fusion = fusion
        # AOT executable store (hybridize(aot=...) or the MXNET_AOT
        # default): validate now, resolve per jit creation so
        # config.enable_aot after construction still applies
        _aot.resolve_aot(aot)
        self._aot = aot
        # mixed-precision dtype policy (hybridize(dtype_policy=...) or
        # the MXNET_DTYPE_POLICY default): per-parameter compute casts
        # by rule name inside the traced fn, op-level harmonization via
        # the policy scope, outputs/moving stats cast back at the
        # program boundary.  Validated now, re-resolved per trace.
        _dtp.resolve_policy(dtype_policy)
        self._dtype_policy = dtype_policy

    def _wrap_aot(self, jit_fn, tag):
        """AOT-wrap one freshly created jit (no-op when AOT is off)."""
        from .. import aot as _aot
        from .. import dtype_policy as _dtp

        store = _aot.resolve_aot(self._aot)
        if store is None:
            return jit_fn
        dtag = _dtp.policy_tag(_dtp.resolve_policy(self._dtype_policy))
        fp = "remat=%s|fusion=%s|dtype=%s" % (
            self._remat_policy or "",
            self._fusion if self._fusion is not None else "", dtag)
        return _aot.AOTFunction(
            jit_fn, "cachedop:%s:%s" % (self._block.name, tag), store,
            fingerprint_extra=fp, manifest_kind="cachedop",
            manifest_extra={"dtype_policy": dtag})

    def _make_fn(self, is_train, n_inputs, n_params):
        block = self._block

        def raw_fn(rng, inputs, params):
            from .. import fusion_cost as _fc
            from .. import dtype_policy as _dtp
            from contextlib import ExitStack

            # resolved per trace (not at construction) so a cost table
            # installed later applies to new-shape retraces; resolve
            # BEFORE mutating the global trace state so a bad
            # MXNET_FUSION set after construction cannot leak it
            fusion_plan = _fc.resolve_fusion(self._fusion)
            dt_policy = _dtp.resolve_policy(self._dtype_policy)
            _random.push_trace_key(rng)
            prev_t = autograd.set_training(is_train)
            prev_r = autograd.set_recording(False)
            sink = []
            _aux_sink.sink = sink
            _trace_state.active = True
            stack = ExitStack()
            stack.enter_context(_fc.scope(fusion_plan))
            stack.enter_context(_dtp.scope(dt_policy))
            try:
                nd_inputs = [NDArray(x) for x in inputs]
                # rebind live param NDArrays to tracers for the trace
                # (cast to the policy compute dtype per override rule —
                # norm params stay f32 under bf16_mixed)
                saved = []
                with _param_swap_lock:
                    for p, arr in zip(self._param_list, params):
                        d = p.data()
                        saved.append((d, d._data))
                        d._data = arr if dt_policy is None else \
                            dt_policy.cast_compute(p.name, arr)
                    try:
                        out = block.hybrid_forward_dispatch(*nd_inputs)
                    finally:
                        for d, old in saved:
                            d._data = old
                flat_out, tmpl = _flatten_nested(out)
                outs = [o._data for o in flat_out]
                aux_params = [p for (p, _v) in sink]
                aux_vals = [v._data if isinstance(v, NDArray) else v
                            for (_p, v) in sink]
                if dt_policy is not None:
                    # boundary casts inside the jit: outputs to the
                    # policy's output dtype, moving-stat updates back
                    # to their STORAGE dtype (a bf16 aux rebind would
                    # flip the traced signature and recompile)
                    outs = [dt_policy.cast_output(o) for o in outs]
                    aux_vals = [
                        v.astype(p.data()._data.dtype)
                        if hasattr(v, "astype") else v
                        for p, v in zip(aux_params, aux_vals)]
                return tuple(outs), tuple(aux_vals), tmpl, aux_params
            finally:
                stack.close()
                _trace_state.active = False
                _aux_sink.sink = None
                autograd.set_recording(prev_r)
                autograd.set_training(prev_t)
                _random.pop_trace_key()

        return raw_fn

    def __call__(self, *inputs):
        import jax

        block = self._block
        if self._param_list is None:
            params = block.collect_params()
            # every param is a jit input (frozen ones simply get no
            # gradient); filtering would change the traced signature
            self._param_list = list(params.values())
        if not getattr(self, "_params_committed", False):
            # params start as host numpy (batched lazy init) and the
            # optimizer returns committed jit outputs — upload them
            # committed NOW so the first compile uses the same jit cache
            # key as every later step (host->committed flip = recompile)
            dev = jax.devices()[0]
            for p in self._param_list:
                d = p.data()
                arr = d._data
                if not (hasattr(arr, "committed") and arr.committed):
                    d._rebind(jax.device_put(arr, dev))
            self._params_committed = True
        in_arrays = tuple(x._data for x in inputs)
        param_arrays = tuple(p.data()._data for p in self._param_list)
        is_train = autograd.is_training()
        key = bool(is_train)
        if key not in self._jits:
            raw_fn = self._make_fn(is_train, len(inputs),
                                   len(self._param_list))
            meta = {}

            def pure(rng, inputs_, params_):
                outs, aux_vals, tmpl, aux_params = raw_fn(rng, inputs_,
                                                          params_)
                meta["tmpl"] = tmpl
                meta["aux_params"] = aux_params
                return outs, aux_vals

            fn_for_jit = pure
            if is_train:
                # activation-remat policy (hybridize(remat_policy=...)
                # or MXNET_REMAT_POLICY): the vjp taken in the grad path
                # below recomputes activations per the policy instead of
                # saving them — no-op when the policy is off
                from ..remat import apply_remat

                fn_for_jit = apply_remat(pure, self._remat_policy)
            self._jits[key] = (self._wrap_aot(
                jax.jit(fn_for_jit), "train" if is_train else "eval"),
                meta)
        jit_fn, meta = self._jits[key]
        rng = _random.next_key()
        mode = "[train]" if is_train else "[eval]"
        outs, aux_vals = _profiler.timed_call(
            "CachedOp:%s%s" % (self._block.name, mode), jit_fn,
            (rng, in_arrays, param_arrays))
        if _profiler.aggregate_enabled() and "xla_cost" not in meta:
            meta["xla_cost"] = True
            try:
                # Lowered.cost_analysis reads the HLO without paying a
                # second backend compile
                cost = jit_fn.lower(rng, in_arrays,
                                    param_arrays).cost_analysis()
                if isinstance(cost, (list, tuple)):
                    cost = cost[0] if cost else {}
                _profiler.record_xla_cost(
                    "CachedOp:%s%s" % (self._block.name, mode), cost)
            except Exception:
                pass
        # apply moving-stat updates
        for p, v in zip(meta.get("aux_params", []), aux_vals):
            p.data()._rebind(v)

        out_nds = [NDArray(o) for o in outs]
        if autograd.is_recording():
            # one tape node for the whole compiled block: backward is the
            # jit'd vjp of the same pure fn (parity: _backward_CachedOp)
            grad_key = ("grad", key)
            if grad_key not in self._jits:
                from .. import aot as _aot

                # the vjp traces THROUGH the forward — only the raw jit
                # can inline under a trace, never a loaded executable
                raw_fwd = _aot.unwrap(jit_fn)

                def grad_fn(rng_, inputs_, params_, cots):
                    def f2(ins, ps):
                        o, _aux = raw_fwd(rng_, ins, ps)
                        return o

                    _, vjp = jax.vjp(f2, inputs_, params_)
                    gin, gpar = vjp(cots)
                    return gin, gpar

                self._jits[grad_key] = self._wrap_aot(
                    jax.jit(grad_fn), "grad")
            grad_jit = self._jits[grad_key]
            param_nds = [p.data() for p in self._param_list]

            def custom_backward(out_grads_raw, _rng=rng, _in=in_arrays,
                                _par=param_arrays):
                gin, gpar = grad_jit(_rng, _in, _par, tuple(out_grads_raw))
                return list(gin) + list(gpar)

            info = OpInfo("_cached_op_%s" % block.name, None,
                          num_inputs=len(inputs) + len(param_nds),
                          num_outputs=len(out_nds))
            autograd.record_op(info, {}, list(inputs) + param_nds, out_nds,
                               custom_backward=custom_backward)
        # template regroup restores the nesting hybrid_forward returned;
        # a single-output template is the bare index 0
        return _regroup_nested(meta["tmpl"], out_nds)


class HybridBlock(Block):
    """Block that can be traced+compiled (parity: block.py:671)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._flags = {}

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = kwargs
        self._cached_op = None
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._cached_op = None
        super().cast(dtype)

    def infer_shape(self, *args):
        """Deferred-shape completion from inputs; layers override
        _infer_param_shapes."""
        self._infer_param_shapes(*args)
        for c in self._children.values():
            pass  # children complete lazily on their own calls

    def _infer_param_shapes(self, *args):
        pass

    def hybrid_forward_dispatch(self, *args):
        """Run hybrid_forward with this block's params as NDArrays."""
        from .. import ndarray as F

        params = {k: p.data() for k, p in self._reg_params.items()}
        return self.hybrid_forward(F, *args, **params)

    def _ensure_initialized(self, *args):
        try:
            for p in self._reg_params.values():
                p.data()
        except DeferredInitializationError:
            self._infer_param_shapes(*args)
            for p in self._reg_params.values():
                p._finish_deferred_init()

    def forward(self, x, *args):
        if isinstance(x, NDArray):
            self._ensure_initialized(x, *args)
            if self._active and not _is_tracing():
                if self._cached_op is None:
                    # eager warm-up pass finishes deferred inits everywhere
                    self._warm_up(x, *args)
                    self._cached_op = CachedOp(self, **self._flags)
                return self._cached_op(x, *args)
            from .. import ndarray as F

            try:
                params = {k: p.data() for k, p in self._reg_params.items()}
            except DeferredInitializationError:
                self._infer_param_shapes(x, *args)
                for p in self._reg_params.values():
                    p._finish_deferred_init()
                params = {k: p.data() for k, p in self._reg_params.items()}
            return self.hybrid_forward(F, x, *args, **params)
        # symbolic path
        if isinstance(x, _symbol.Symbol):
            from .. import symbol as F

            params = {k: p.var() for k, p in self._reg_params.items()}
            with self.name_scope():
                return self.hybrid_forward(F, x, *args, **params)
        raise MXNetError("forward expects NDArray or Symbol, got %r" % type(x))

    def _warm_up(self, *args):
        """Finish deferred inits everywhere without device compute."""
        prev = self._active
        self._active = False
        try:
            with autograd.pause():
                _abstract_eval_forward(self, args)
        finally:
            self._active = prev

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- export ----------------------------------------------------------
    def export(self, path, epoch=0, remove_amp_cast=True):
        """Serialize symbol json + params (parity: block.py:987)."""
        from ..ndarray import ndarray as _nd

        sym = self._to_symbol()
        sym.save("%s-symbol.json" % path)
        arg_dict = {}
        existing = set(sym.list_arguments()) | set(sym.list_auxiliary_states())
        aux_names = set(sym.list_auxiliary_states())
        for name, param in self.collect_params().items():
            if name in existing:
                kind = "aux:" if name in aux_names else "arg:"
                arg_dict["%s%s" % (kind, name)] = param._reduce()
        fname = "%s-%04d.params" % (path, epoch)
        _nd.save(fname, arg_dict)
        return fname

    def _to_symbol(self):
        data = _symbol.var("data")
        out = self(data)
        if isinstance(out, (list, tuple)):
            out = _symbol.Group(out)
        return out


class SymbolBlock(HybridBlock):
    """Wrap a Symbol (+ loaded params) as a Block (parity: block.py:952)."""

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        sym = _symbol.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [_symbol.var(n) for n in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            from ..ndarray import ndarray as _nd

            loaded = _nd.load(param_file)
            loaded = {k.split(":", 1)[-1]: v for k, v in loaded.items()}
            for name, param in ret.collect_params().items():
                if name in loaded:
                    param._shape = loaded[name].shape
                    param.initialize(ctx=ctx or [current_context()])
                    param.set_data(loaded[name])
                else:
                    param.initialize(ctx=ctx or [current_context()])
        return ret

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=None)
        if isinstance(outputs, (list, tuple)):
            outputs = _symbol.Group(outputs)
        if isinstance(inputs, _symbol.Symbol):
            inputs = [inputs]
        self._symbol = outputs
        self._input_names = [i.name for i in inputs]
        arg_names = outputs.list_arguments()
        aux_names = set(outputs.list_auxiliary_states())
        for name in arg_names + list(aux_names):
            if name not in self._input_names:
                self.params.get(name, allow_deferred_init=True,
                                grad_req="null" if name in aux_names else "write")
        self._fn = None

    def forward(self, *args):
        if self._fn is None:
            self._fn, _, _ = self._symbol._build_fn()
        vmap = {}
        for name, x in zip(self._input_names, args):
            vmap[name] = x._data
        for name, p in self.params.items():
            if name not in vmap:
                if p._data is None and p.shape is not None and \
                        all(s > 0 for s in p.shape):
                    p.initialize(ctx=[current_context()])
                vmap[name] = p.data()._data
        outs, _aux = self._fn(vmap, is_train=autograd.is_training())
        nds = [NDArray(o) for o in outs]
        return nds[0] if len(nds) == 1 else nds
