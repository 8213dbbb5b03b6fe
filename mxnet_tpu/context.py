"""Device context, TPU-native.

Reference parity: python/mxnet/context.py (Context stack, mx.cpu()/mx.gpu()).
TPU-native design: a Context names a jax.Device.  ``tpu(i)`` is the native
accelerator context; ``gpu(i)`` is accepted as an alias for the i-th
accelerator so reference scripts run unmodified; ``cpu()`` maps to the host
platform.  Under jit tracing, contexts are advisory — XLA owns placement.

A ``tpu(i)``/``gpu(i)`` context never silently lands somewhere else: an
index past the accelerator count raises, and so does asking for an
accelerator when JAX found none — unless the process was started with
``JAX_PLATFORMS=cpu`` (the test harness), where the alias names the host
devices on purpose.
"""
from __future__ import annotations

import os
import threading

from .base import MXNetError

__all__ = [
    "Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
    "num_gpus", "num_tpus", "device",
]

_context_stack = threading.local()


def _jax():
    import jax

    return jax


class Context:
    """A device context. devtype 'cpu'|'tpu' ('gpu' aliases 'tpu')."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 5}
    _accel_cache = None

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    # --- jax integration -------------------------------------------------
    @staticmethod
    def _accelerators():
        if Context._accel_cache is None:
            jax = _jax()
            accels = [d for d in jax.devices() if d.platform != "cpu"]
            Context._accel_cache = accels
        return Context._accel_cache

    @property
    def jax_device(self):
        """The jax.Device this context names."""
        jax = _jax()
        if self.device_type in ("cpu", "cpu_pinned"):
            try:
                return jax.local_devices(backend="cpu")[0]
            except RuntimeError:
                return jax.devices()[0]
        devs = Context._accelerators()
        if not devs:
            if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
                raise MXNetError(
                    "%s requested but JAX found no accelerator "
                    "(jax.devices() = %s); start the process with "
                    "JAX_PLATFORMS=cpu to run on the host on purpose"
                    % (self, jax.devices()))
            devs = jax.devices()
        if not 0 <= self.device_id < len(devs):
            raise MXNetError("%s requested but only %d device(s) present"
                             % (self, len(devs)))
        return devs[self.device_id]

    # --- parity API ------------------------------------------------------
    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(_context_stack, "stack"):
            _context_stack.stack = []
        _context_stack.stack.append(self)
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        _context_stack.stack.pop()

    def empty_cache(self):
        """Parity no-op: XLA owns the HBM allocator."""


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Alias for the i-th accelerator (TPU chip) for script compat."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


# `device` alias matching later-mxnet naming
device = Context


def num_gpus():
    return len(Context._accelerators())


def num_tpus():
    return len(Context._accelerators())


def default_context():
    """The implicit context: the accelerator when one is present.

    TPU-native departure from the reference (which defaults to cpu):
    on a TPU host the chip is the default compute device — data created
    without an explicit ctx lands in HBM and eager/jit programs run on
    the MXU, mirroring jax's own default-backend rule.  `mx.cpu()` still
    pins host placement explicitly."""
    if Context._accelerators():
        return Context("tpu", 0)
    return Context("cpu", 0)


def current_context():
    stack = getattr(_context_stack, "stack", None)
    if stack:
        return stack[-1]
    return default_context()
