"""Weight initializers (reference parity: python/mxnet/initializer.py:34-702).

Serialized-by-string into symbol/parameter attrs exactly as the reference
does (InitDesc + dumps/loads via json)."""
from __future__ import annotations

import json
import re

import numpy as np

from .base import MXNetError
from . import random as _random
from .ndarray.ndarray import NDArray, array

__all__ = ["InitDesc", "Initializer", "Zero", "One", "Constant", "Uniform",
           "Normal", "Orthogonal", "Xavier", "MSRAPrelu", "Bilinear",
           "LSTMBias", "Load", "Mixed", "register"]

_INIT_REGISTRY = {}


def register(klass):
    _INIT_REGISTRY[klass.__name__.lower()] = klass
    return klass


class InitDesc(str):
    """Name + attrs descriptor handed to initializers."""

    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


class Initializer:
    def __init__(self, **kwargs):
        self._kwargs = kwargs
        self._verbose = False
        self._print_func = None

    def set_verbosity(self, verbose=False, print_func=None):
        self._verbose = verbose
        self._print_func = print_func
        return self

    def dumps(self):
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, desc, arr):
        if not isinstance(desc, InitDesc):
            desc = InitDesc(str(desc))
        init = desc.attrs.get("__init__", "")
        if init:
            klass, kwargs = json.loads(init)
            _INIT_REGISTRY[klass.lower()](**kwargs)._init_weight(desc, arr)
            return
        name = desc.lower()
        if name.endswith("weight"):
            self._init_weight(desc, arr)
        elif name.endswith("bias"):
            self._init_bias(desc, arr)
        elif name.endswith("gamma"):
            self._init_gamma(desc, arr)
        elif name.endswith("beta"):
            self._init_beta(desc, arr)
        elif name.endswith("moving_mean") or name.endswith("running_mean"):
            self._init_zero(desc, arr)
        elif name.endswith("moving_var") or name.endswith("running_var"):
            self._init_one(desc, arr)
        elif name.endswith("moving_inv_var") or name.endswith("moving_avg"):
            self._init_zero(desc, arr)
        elif name.endswith("min") or name.endswith("max"):
            self._init_zero(desc, arr)
        else:
            self._init_default(desc, arr)

    # helpers write via rebind (in-place semantics).  The value stays a
    # HOST numpy array: the first jitted step (or the trainer's batched
    # device_put) uploads all params at once and commits them, instead
    # of one transfer per parameter at init time.
    @staticmethod
    def _set(arr, value):
        npv = np.asarray(value).astype(np.dtype(arr.dtype), copy=False) \
            .reshape(arr.shape)
        arr._rebind(npv)

    # constants are made in the array's own type: a float64 detour
    # through ``astype`` costs seconds a billion values (bfloat16 most)
    def _init_zero(self, _, arr):
        self._set(arr, np.zeros(arr.shape, np.dtype(arr.dtype)))

    def _init_one(self, _, arr):
        self._set(arr, np.ones(arr.shape, np.dtype(arr.dtype)))

    _init_bias = _init_beta = _init_zero
    _init_gamma = _init_one

    def _init_weight(self, desc, arr):
        raise NotImplementedError

    def _init_default(self, desc, arr):
        raise MXNetError(
            "Unknown initialization pattern for %s" % desc)


@register
class Zero(Initializer):
    _init_weight = _init_default = Initializer._init_zero


@register
class One(Initializer):
    _init_weight = _init_default = Initializer._init_one


@register
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, _, arr):
        self._set(arr, np.full(arr.shape, self.value))

    _init_default = _init_weight


@register
class Uniform(Initializer):
    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, _, arr):
        self._set(arr, _random.host_rng().uniform(
            -self.scale, self.scale, arr.shape))

    _init_default = _init_weight


@register
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, _, arr):
        self._set(arr, _random.host_rng().normal(0, self.sigma, arr.shape))

    _init_default = _init_weight


@register
class Orthogonal(Initializer):
    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, _, arr):
        nout = arr.shape[0]
        nin = int(np.prod(arr.shape[1:]))
        if self.rand_type == "uniform":
            tmp = np.random.uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = np.random.normal(0.0, 1.0, (nout, nin))
        u, _, v = np.linalg.svd(tmp, full_matrices=False)
        q = u if u.shape == tmp.shape else v
        self._set(arr, self.scale * q.reshape(arr.shape))

    _init_default = _init_weight


@register
class Xavier(Initializer):
    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, desc, arr):
        shape = arr.shape
        hw_scale = 1.0
        if len(shape) < 2:
            raise MXNetError("Xavier requires ndim >= 2: %s %s" % (desc, shape))
        if len(shape) > 2:
            hw_scale = float(np.prod(shape[2:]))
        fan_in = shape[1] * hw_scale
        fan_out = shape[0] * hw_scale
        if self.factor_type == "avg":
            factor = (fan_in + fan_out) / 2.0
        elif self.factor_type == "in":
            factor = fan_in
        elif self.factor_type == "out":
            factor = fan_out
        else:
            raise MXNetError("Incorrect factor type")
        scale = np.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            self._set(arr, _random.host_rng().uniform(-scale, scale, shape))
        elif self.rnd_type == "gaussian":
            self._set(arr, _random.host_rng().normal(0, scale, shape))
        else:
            raise MXNetError("Unknown random type")

    _init_default = _init_weight


@register
class MSRAPrelu(Xavier):
    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    def _init_weight(self, _, arr):
        weight = np.zeros(int(np.prod(arr.shape)), dtype="float32")
        shape = arr.shape
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        self._set(arr, weight.reshape(shape))

    _init_default = _init_weight


@register
class LSTMBias(Initializer):
    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        b = np.zeros(arr.shape, dtype="float32")
        num_hidden = arr.shape[0] // 4
        b[num_hidden:2 * num_hidden] = self.forget_bias
        self._set(arr, b)

    _init_default = _init_weight


class Load:
    """Init from a dict of arrays, fall back to default_init."""

    def __init__(self, param, default_init=None, verbose=False):
        self.param = {
            (k[4:] if k.startswith(("arg:", "aux:")) else k): v
            for k, v in param.items()}
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr):
        if name in self.param:
            src = self.param[name]
            if tuple(src.shape) != tuple(arr.shape):
                raise MXNetError("shape mismatch for %s" % name)
            arr._rebind(src._data if isinstance(src, NDArray)
                        else array(src)._data)
        else:
            if self.default_init is None:
                raise MXNetError("no initializer for %s" % name)
            self.default_init(name, arr)


class Mixed:
    def __init__(self, patterns, initializers):
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(str(name)):
                init(name, arr)
                return
        raise MXNetError("no matching initializer pattern for %s" % name)


_INIT_ALIASES = {"zeros": "zero", "ones": "one", "gaussian": "normal"}


def create(name, **kwargs):
    if isinstance(name, Initializer):
        return name
    key = name.lower()
    key = _INIT_ALIASES.get(key, key)
    return _INIT_REGISTRY[key](**kwargs)
