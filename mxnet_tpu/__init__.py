"""mxnet_tpu — a TPU-native framework with the capabilities of Apache
MXNet 1.5 (reference: /root/reference), built on jax/XLA/pallas.

Import as `import mxnet_tpu as mx`: the namespace mirrors the reference's
`import mxnet as mx` surface (mx.nd, mx.sym, mx.gluon, mx.autograd,
mx.cpu()/mx.gpu()/mx.tpu(), mx.io, mx.kvstore, ...).
"""
from .base import MXNetError, MXTpuError  # noqa: F401
from .context import (Context, cpu, gpu, tpu, cpu_pinned, current_context,  # noqa: F401
                      num_gpus, num_tpus)
from . import engine  # noqa: F401
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from . import random  # noqa: F401
from . import random as rnd  # noqa: F401
from . import autograd  # noqa: F401
from . import symbol  # noqa: F401
from . import symbol as sym  # noqa: F401
from .symbol import Symbol  # noqa: F401
from . import initializer  # noqa: F401
from . import initializer as init  # noqa: F401
from . import optimizer  # noqa: F401
from .optimizer import Optimizer  # noqa: F401
from . import metric  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import callback  # noqa: F401
from . import monitor  # noqa: F401
from .monitor import Monitor  # noqa: F401
from . import kvstore  # noqa: F401
from . import kvstore as kv  # noqa: F401  (reference alias mx.kv)
from . import gluon  # noqa: F401
from . import module  # noqa: F401
from . import module as mod  # noqa: F401
from . import model  # noqa: F401
from .model import FeedForward  # noqa: F401
from . import io  # noqa: F401
from . import recordio  # noqa: F401
from . import image  # noqa: F401
from . import executor  # noqa: F401
from . import profiler  # noqa: F401
from . import rnn  # noqa: F401
from . import runtime  # noqa: F401
from . import test_utils  # noqa: F401
from . import visualization  # noqa: F401
from . import visualization as viz  # noqa: F401
from . import parallel  # noqa: F401
from . import attribute  # noqa: F401
from .attribute import AttrScope  # noqa: F401
from . import name  # noqa: F401
from .name import NameManager  # noqa: F401
from . import rtc  # noqa: F401
from . import config  # noqa: F401
from . import native  # noqa: F401
from . import storage  # noqa: F401
from . import contrib  # noqa: F401
from . import operator  # noqa: F401
from . import util  # noqa: F401

from . import remat  # noqa: F401
from . import dtype_policy  # noqa: F401  (MXNET_DTYPE_POLICY default)
from . import telemetry  # noqa: F401  (MXNET_TELEMETRY enables at import)
from . import tracing  # noqa: F401  (MXNET_TRACE / MXNET_FLIGHT_RECORDER)
from . import events  # noqa: F401  (MXNET_EVENTS wide-event layer)
from . import checkpoint  # noqa: F401
from .checkpoint import CheckpointManager  # noqa: F401

__version__ = "2.0.0.tpu1"

config.warn_unknown()
if config.get("MXNET_PROFILER_AUTOSTART"):
    profiler.start()
if config.get("MXNET_COMPILE_CACHE"):
    # persistent XLA compilation cache: JAX_COMPILATION_CACHE_DIR places
    # it (jax reads the variable itself); unset, it lives at one fixed
    # path inside the checkout (config.enable_compile_cache)
    config.enable_compile_cache()
