"""Sharded training step: the TPU-native data/tensor-parallel hot path.

Reference counterpart: the whole DataParallelExecutorGroup + KVStore
push/pull machinery (python/mxnet/module/executor_group.py:436,
src/kvstore/comm.h, kvstore_nccl.h).  TPU-native: ONE jitted program per
step — forward, backward, gradient allreduce and optimizer update fused by
XLA over a jax.sharding.Mesh.  Gradients ride ICI via compiler-inserted
psums (the 'nccl' allreduce path reduced to a sharding annotation);
optimizer state is donated so weights update in-place in HBM.

Works with any gluon HybridBlock: parameters are viewed as a jax pytree,
traced through the same NDArray-wrapping trick CachedOp uses, and synced
back to the Parameter objects on demand.
"""
from __future__ import annotations

import functools
import queue as _queue
import signal as _signal
import sys as _sys
import threading as _threading
import time as _time

import numpy as np

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .. import autograd
from .. import events as _events
from .. import random as _random
from .. import telemetry as _telemetry
from .. import tracing as _tracing
from ..gluon import block as _block_mod

__all__ = ["ShardedTrainer", "sgd_init", "adam_init"]


# device-resident metric accumulator: one f32 vector riding the compiled
# step's donated carry, transferred to the host only at flush boundaries
# (every ``metrics_every`` steps) instead of per step.  Layout:
#   [0] sum of FINITE losses   [1] steps accumulated
#   [2] non-finite loss count  [3] loss of the newest step (raw)
#   [4] current loss scale     [5] loss-scale backoffs (overflow skips)
_M_LOSS_SUM, _M_STEPS, _M_NONFINITE, _M_LAST, _M_LS_SCALE, \
    _M_LS_BACKOFF = range(6)
_METRICS_WIDTH = 6


class _MetricFetcher:
    """Bounded background device->host metric pull.

    jax arrays are futures: ``np.asarray`` here blocks until the device
    values land, so the *dispatch* thread never does — the reference
    dependency engine's read-dependency resolution, reduced to one
    consumer thread.  The queue bound doubles as backpressure: once
    ``depth`` flushes are in flight, the next submit blocks the
    dispatch loop until the chip catches up, so the host can never run
    unboundedly ahead of device execution.
    """

    def __init__(self, apply_fn, depth=2):
        self._apply = apply_fn
        self.error = None  # first fetch/apply failure (drain re-raises)
        self._q = _queue.Queue(maxsize=max(1, int(depth)))
        self._thread = _threading.Thread(
            target=self._run, name="mxnet_tpu-metric-fetch", daemon=True)
        self._thread.start()

    def submit(self, step, n_steps, acc):
        self._q.put((step, n_steps, acc))
        if _telemetry.enabled():
            _telemetry.ASYNC_FETCH_INFLIGHT.set(self._q.qsize())

    def wait(self):
        """Block until every submitted fetch has completed AND been
        applied (the drain barrier)."""
        self._q.join()

    def close(self):
        self._q.put(None)
        self._thread.join(timeout=5.0)

    def _run(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                step, n_steps, acc = item
                try:
                    # step-level span: kept whether or not tracing is on
                    with _tracing.begin("step:fetch", args={
                            "step": step, "steps": n_steps}):
                        host = np.asarray(acc)  # blocks on the device
                        self._apply(step, n_steps, host, async_mode=True)
                except Exception as e:
                    # never let a poisoned fetch kill the thread: wait()
                    # would deadlock with no consumer left.  The first
                    # error is kept for the next drain boundary.
                    if self.error is None:
                        self.error = e
            finally:
                self._q.task_done()
                if _telemetry.enabled():
                    _telemetry.ASYNC_FETCH_INFLIGHT.set(
                        max(0, self._q.qsize()))
                    if item is not None:
                        _telemetry.ASYNC_METRIC_FETCHES.inc()


# ---- functional optimizers (pytree-level, fused into the step) ----------

def sgd_init(params, momentum=0.0):
    import jax.numpy as jnp

    if momentum == 0.0:
        return {"mom": None}
    return {"mom": [jnp.zeros_like(p) for p in params]}


def _sgd_update(params, grads, state, lr, momentum, wd):
    new_params = []
    new_mom = []
    for i, (p, g) in enumerate(zip(params, grads)):
        g = g + wd * p
        if state["mom"] is not None:
            m = momentum * state["mom"][i] - lr * g
            new_mom.append(m)
            new_params.append(p + m)
        else:
            new_params.append(p - lr * g)
    return new_params, {"mom": new_mom if state["mom"] is not None else None}


def adam_init(params, **kw):
    import jax.numpy as jnp

    return {"m": [jnp.zeros_like(p) for p in params],
            "v": [jnp.zeros_like(p) for p in params],
            "t": jnp.zeros((), jnp.int32)}


def _adam_update(params, grads, state, lr, beta1, beta2, eps, wd):
    import jax.numpy as jnp

    t = state["t"] + 1
    new_p, new_m, new_v = [], [], []
    corr = jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
    for p, g, m, v in zip(params, grads, state["m"], state["v"]):
        g = g + wd * p
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * jnp.square(g)
        new_p.append(p - lr * corr * m / (jnp.sqrt(v) + eps))
        new_m.append(m)
        new_v.append(v)
    return new_p, {"m": new_m, "v": new_v, "t": t}


class ShardedTrainer:
    """Compile a full train step over a Mesh.

    Parameters
    ----------
    net : gluon.HybridBlock (initialized)
    loss_fn : callable(F_outputs NDArray, label NDArray) -> scalar NDArray,
        traced along with the net.
    mesh : jax.sharding.Mesh, an ``"dp=2,fsdp=2,tp=2"`` spec string /
        axis dict / MeshConfig (built via parallel.mesh.make_mesh), or
        None — the ``MXNET_MESH`` env default ('' = single device)
    optimizer : 'sgd' | 'adam'
    layout : spec-rule layout naming the per-parameter PartitionSpecs
        (parallel.layout registry: 'data_parallel' | 'fsdp' | 'fsdp_tp'
        | a Layout object | a user-registered name).  None defers to
        ``MXNET_LAYOUT``, else the canonical layout for the mesh's axes
        (fsdp_tp when tp present, fsdp for fsdp, else data_parallel).
        Resolved once against the parameter names/shapes at bind time
        and cached; optimizer state is sharded like its parameter.
    batch_axis_spec : mesh axis name(s) the batch dim is sharded over
        (default None = the layout's data axes present in the mesh —
        ('dp', 'fsdp') when both exist; grads psum over them implicitly)
    param_spec_fn : optional callable(name, shape) -> PartitionSpec —
        the pre-layout escape hatch; when given it wins over ``layout``
    dtype : compute dtype for activations (bf16 default on TPU; params and
        optimizer state stay fp32 — the MultiPrecision recipe)
    async_metrics : non-blocking step dispatch (None = the
        ``MXNET_ASYNC_METRICS`` env default).  ``step`` returns device
        arrays without syncing; loss/skip-count/heartbeat values are
        pulled by a bounded background fetch thread and consumed one
        flush late.  Hard syncs remain only at checkpoint boundaries
        and :meth:`drain`.  Under the ``"raise"`` non-finite policy the
        error surfaces at the next ``step``/``drain`` call after the
        fetch lands instead of inside the offending step.
    steps_per_call : K>1 enables :meth:`step_many` — K pre-staged
        microbatches run as ONE compiled ``lax.scan`` program with the
        params/opt-state/metrics carry donated (None = the
        ``MXNET_STEPS_PER_CALL`` env default).  Numerics are bit-for-bit
        identical to K sequential ``step`` calls.
    metrics_every : transfer the device-resident metric accumulator
        (loss sum / step count / non-finite count / last loss) to the
        host every N steps (default: once per dispatch call).
    fetch_depth : bound on in-flight background fetches; a full queue
        backpressures dispatch so the host can never run unboundedly
        ahead of the chip (default 2).
    """

    def __init__(self, net, loss_fn, mesh=None, optimizer="sgd",
                 optimizer_params=None, batch_axis_spec=None,
                 param_spec_fn=None, dtype=None, donate=True,
                 remat_policy=None, fusion=None, on_nonfinite=None,
                 aot=None, aot_spec=None, layout=None,
                 async_metrics=None, steps_per_call=None,
                 metrics_every=None, fetch_depth=2, dtype_policy=None,
                 distributed="auto"):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..remat import resolve_policy
        from ..checkpoint import nonfinite_policy
        from .. import config as _config
        from .. import fusion_cost as _fc
        from .. import aot as _aot
        from .. import dtype_policy as _dtp
        from .mesh import resolve_mesh, bootstrap_distributed
        from . import layout as _layout

        # pod-scale bootstrap BEFORE the first device query: when the
        # launcher's env names a coordinator (MXNET_DIST_COORDINATOR or
        # the DMLC_ legacy spellings), join
        # the jax.distributed runtime; quietly single-process when not
        # configured.  Configured-but-unreachable raises the typed
        # DistributedUnavailable — silently training a disjoint model
        # per host would be far worse.  distributed=False opts out.
        if distributed:
            bootstrap_distributed()

        self.net = net
        self.loss_fn = loss_fn
        # fail fast on a typo'd policy; None defers to MXNET_REMAT_POLICY
        resolve_policy(remat_policy)
        self._remat_policy = remat_policy
        # fusion spec for the step trace (fusion= or the MXNET_FUSION
        # default): installed around the forward trace so shape-
        # specialized op fast paths can consult the measured cost
        # table.  Validated now (fail fast on a typo) but re-resolved
        # per trace, so a table installed after construction still
        # applies to new-shape retraces — same contract as Executor.
        _fc.resolve_fusion(fusion)
        self._fusion = fusion
        # AOT executable store (aot= or the MXNET_AOT default): the
        # compiled step is serialized/loaded by content hash so a
        # restarted trainer (rollout, preemption resume) skips the
        # cold compile.  Validated now, resolved at _build like the
        # fusion plan.  ``aot_spec`` names this model in the store's
        # signature manifest so tools/prewarm.py can rebuild it.
        _aot.resolve_aot(aot)
        self._aot = aot
        self._aot_spec = aot_spec
        # NaN/Inf step guard (None defers to MXNET_NONFINITE_POLICY):
        # "skip" compiles a select into the step so a non-finite loss
        # discards the whole update (params, optimizer state, moving
        # stats) and keeps the previous state
        self._on_nonfinite = nonfinite_policy(on_nonfinite)
        # mixed-precision dtype policy (None defers to
        # MXNET_DTYPE_POLICY; '' / 'f32' = the historical f32 path):
        # per-parameter compute casts by the policy's override rules,
        # compute-follows-the-weight harmonization inside the traced
        # ops, and — for loss-scaling policies — dynamic loss scaling
        # whose overflow skip reuses the non-finite select above.  The
        # legacy ``dtype=`` blanket cast survives as the escape hatch
        # but cannot be combined with a policy.
        self._dtype_policy = _dtp.resolve_policy(dtype_policy)
        if self._dtype_policy is not None and dtype is not None:
            raise MXNetError(
                "pass dtype= (legacy blanket compute cast) or "
                "dtype_policy=, not both")
        self._ls_cfg = _dtp.LossScaleConfig() \
            if (self._dtype_policy is not None
                and self._dtype_policy.loss_scaling) else None
        self._ls_active = self._ls_cfg is not None
        self._cast_bytes = 0
        _dtp.note_policy(self._dtype_policy, "trainer")
        # host-overlap knobs (ISSUE 10 — the dependency-engine overlap):
        # async_metrics moves every loss/metric host read off the
        # dispatch path onto a bounded fetch thread; steps_per_call=K
        # fuses K microbatch steps into one lax.scan program
        # (step_many).  Both default from the MXNET_* env knobs.
        self._async = _config.get("MXNET_ASYNC_METRICS") \
            if async_metrics is None else bool(async_metrics)
        k = _config.get("MXNET_STEPS_PER_CALL") \
            if steps_per_call is None else int(steps_per_call)
        if k < 1:
            raise MXNetError("steps_per_call must be >= 1; got %d" % k)
        self.steps_per_call = k
        # flush the device accumulator every N steps; default = one
        # flush per dispatch call (per step when K=1 — the historical
        # per-step loss cadence, just non-blocking under async)
        self._metrics_every_explicit = metrics_every is not None
        self._metrics_every = max(1, int(metrics_every)) \
            if metrics_every is not None else k
        self._fetch_depth = max(1, int(fetch_depth))
        self._fetcher = None
        self._pending_exc = None
        self._metrics_acc = None
        self._metrics_pending = 0
        self._last_dispatch_end = None
        self._step_k_fn = None
        self._step_core = None
        self._last_rng = None
        self.global_step = 0
        self.skipped_steps = 0
        self._step_flops = None  # one-time XLA cost attribution (telemetry)
        self._committed = None   # (params, opt_state, step, rng) snapshot
        self._ckpt_manager = None
        self._ckpt_period = 0
        self._pending_restore = None
        # mesh= accepts a Mesh, a "dp=2,fsdp=2" spec, a dict, or None
        # (the MXNET_MESH env default; '' = single device)
        self.mesh = resolve_mesh(mesh)
        # spec-rule layout: the Layout OBJECT resolves now (fail fast on
        # an unregistered name); the per-parameter resolution needs
        # materialized shapes and happens once in _shard_params.  An
        # explicit param_spec_fn is the pre-layout escape hatch and wins.
        self._layout = None
        self._layout_res = None
        if self.mesh is not None and param_spec_fn is None:
            self._layout = _layout.resolve_layout(layout, self.mesh)
        elif isinstance(layout, str):
            _layout.get_layout(layout)  # typo'd name fails fast anyway
        self._collective_plan = []
        self._param_shardings = None
        self._opt_shardings = None
        self._params = [p for p in net.collect_params().values()]
        self._trainable = [p.grad_req != "null" for p in self._params]
        opts = dict(optimizer_params or {})
        self._lr = float(opts.get("learning_rate", 0.01))
        self._wd = float(opts.get("wd", 0.0))
        self._momentum = float(opts.get("momentum", 0.0))
        self._beta1 = float(opts.get("beta1", 0.9))
        self._beta2 = float(opts.get("beta2", 0.999))
        self._eps = float(opts.get("epsilon", 1e-8))
        self._opt_name = optimizer
        self._dtype = dtype
        self._donate = donate
        self._step_fn = None
        self._batch_spec = batch_axis_spec
        self._param_spec_fn = param_spec_fn

        if optimizer not in ("sgd", "adam"):
            raise MXNetError("ShardedTrainer supports sgd/adam; got %r"
                             % optimizer)
        self.param_arrays = None  # filled by _lazy_init (deferred shapes)
        self.opt_state = None
        try:
            self._lazy_init()
        except Exception:
            pass  # deferred-shape params: init on first step

    def _lazy_init(self, example_inputs=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.param_arrays is not None:
            return
        from .. import autograd as _ag

        if example_inputs is not None:
            try:
                for p in self._params:
                    p.data()
            except Exception:
                # finish deferred shapes by abstract evaluation — no
                # device compute (the round-1 eager warm-up was a ~100s
                # compile storm on TPU)
                with _ag.pause():
                    _block_mod._abstract_eval_forward(
                        self.net, list(example_inputs))
        # one batched host→HBM upload (params may still be host numpy
        # from the initializer); also keeps the jit signature stable so
        # the step compiles exactly once.  Mesh runs re-place below.
        arrays = [p.data()._data for p in self._params]
        if self.mesh is None:
            # explicit device => committed arrays; jit outputs are also
            # committed, so the step's input signature never changes and
            # XLA compiles the program exactly once
            dev = jax.devices()[0]
            arrays = list(jax.device_put(arrays, dev))
        self.param_arrays = arrays
        self._trainable = [p.grad_req != "null" for p in self._params]
        self._param_index = {id(p): i for i, p in enumerate(self._params)}
        train_arrays = [a for a, t in zip(self.param_arrays, self._trainable)
                        if t]
        if self._opt_name == "sgd":
            self.opt_state = sgd_init(train_arrays, momentum=self._momentum)
        else:
            self.opt_state = adam_init(train_arrays)
        if self._ls_active:
            # the dynamic loss-scale state rides the optimizer-state
            # pytree: donation, out-sharding pinning, checkpointing and
            # reshard-on-load all handle it with zero extra plumbing —
            # a save/resume round-trip preserves the scale exactly
            from .. import dtype_policy as _dtp

            self.opt_state = {"base": self.opt_state,
                              "loss_scale": _dtp.init_loss_scale(
                                  self._ls_cfg)}
        if self.mesh is not None:
            self._shard_params(jax, NamedSharding, P)
        else:
            # commit optimizer state like the params (see above)
            dev = jax.devices()[0]
            self.opt_state = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, dev), self.opt_state)
        # the device-resident metric accumulator rides the step carry
        # (donated in/out); replicated so every shard agrees
        self._metrics_acc = self._fresh_metrics()
        if self._pending_restore is not None:
            # checkpoint attached before shapes were known: apply now
            ckpt, self._pending_restore = self._pending_restore, None
            self._apply_restore(ckpt)

    def _fresh_metrics(self):
        """A zeroed, committed metric-accumulator buffer (a new one is
        needed after every flush: the previous buffer was donated to
        the fetch)."""
        import jax

        z = np.zeros((_METRICS_WIDTH,), np.float32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            return self._global_put(jax, z, NamedSharding(self.mesh, P()))
        return jax.device_put(z, jax.devices()[0])

    # -- sharding placement ----------------------------------------------
    @property
    def mesh_shape(self):
        """``{axis: size}`` of the trainer's mesh (``{}`` single-device)
        — the BENCH-JSON / checkpoint-manifest topology record."""
        from .mesh import mesh_shape

        return mesh_shape(self.mesh)

    @property
    def layout_name(self):
        """Name of the active parameter layout (``"param_spec_fn"`` for
        the legacy callable path, None when no mesh)."""
        if self._layout is not None:
            return self._layout.name
        if self._param_spec_fn is not None:
            return "param_spec_fn"
        return None

    def layout_resolution(self):
        """The cached per-parameter :class:`LayoutResolution` (resolved
        at bind time; None for the legacy/no-mesh paths) — inspect with
        ``.describe()``."""
        return self._layout_res

    @property
    def dtype_policy(self):
        """The resolved :class:`~mxnet_tpu.dtype_policy.DtypePolicy`
        (None = the historical f32 path)."""
        return self._dtype_policy

    @property
    def dtype_policy_tag(self):
        """Policy tag for BENCH JSON / manifests (``"f32"`` when no
        policy is active)."""
        from .. import dtype_policy as _dtp

        return _dtp.policy_tag(self._dtype_policy)

    def loss_scale(self):
        """Current dynamic loss scale (host read — a device sync; call
        at drain/checkpoint boundaries, not per step).  None when the
        active policy does not loss-scale."""
        if not self._ls_active:
            return None
        if self.opt_state is None:  # deferred shapes: not yet stepped
            return float(self._ls_cfg.init)
        return float(np.asarray(self.opt_state["loss_scale"])[0])

    def _resolve_layout_specs(self):
        """Resolve the layout against the materialized param shapes —
        once; the Layout caches by (params, mesh) so trainer No. 2 on
        the same model reuses it."""
        if self._layout is None or self._layout_res is not None:
            return
        params = [(p.name, tuple(arr.shape))
                  for p, arr in zip(self._params, self.param_arrays)]
        self._layout_res = self._layout.resolve(params, self.mesh)

    def _param_sharding(self, P, NamedSharding, p, arr):
        if self._param_spec_fn is not None:
            spec = self._param_spec_fn(p.name, arr.shape)
            if spec is not None:
                return NamedSharding(self.mesh, spec)
        elif self._layout_res is not None:
            return NamedSharding(self.mesh, self._layout_res.spec(p.name))
        return NamedSharding(self.mesh, P())  # replicated

    @staticmethod
    def _global_put(jax, arr, sh):
        """Place host data onto a (possibly multi-process) sharding.

        Single-process: plain device_put.  Multi-process (jax.distributed
        over DCN, SURVEY §2.3): device_put cannot target non-addressable
        devices, so build a global Array from this process's local block
        — for a dp-across-hosts batch axis that block is the per-worker
        batch shard, exactly the reference's per-worker data loading."""
        if jax.process_count() == 1:
            return jax.device_put(arr, sh)
        return jax.make_array_from_process_local_data(
            sh, np.asarray(arr))

    def _shard_params(self, jax, NamedSharding, P):
        self._resolve_layout_specs()
        self._param_shardings = []
        new_arrays = []
        for p, arr in zip(self._params, self.param_arrays):
            sh = self._param_sharding(P, NamedSharding, p, arr)
            self._param_shardings.append(sh)
            new_arrays.append(self._global_put(jax, arr, sh))
        self.param_arrays = new_arrays
        # optimizer state shards LIKE ITS PARAMETER (the ZeRO discipline
        # that makes fsdp cut state bytes, not just weight bytes): the
        # m/v/mom leaf lists align with the trainable params by index,
        # and scalar leaves (adam's t) replicate.
        train_sh = [sh for sh, t in zip(self._param_shardings,
                                        self._trainable) if t]
        repl = NamedSharding(self.mesh, P())
        base_state = self.opt_state["base"] if self._ls_active \
            else self.opt_state
        if self._opt_name == "sgd":
            opt_sh = {"mom": None if base_state["mom"] is None
                      else list(train_sh)}
        else:
            opt_sh = {"m": list(train_sh), "v": list(train_sh), "t": repl}
        if self._ls_active:
            opt_sh = {"base": opt_sh, "loss_scale": repl}
        self._opt_shardings = opt_sh
        self.opt_state = jax.tree_util.tree_map(
            lambda a, sh: self._global_put(jax, a, sh),
            self.opt_state, opt_sh)
        self._build_collective_plan()
        self._record_state_bytes(jax)

    def _build_collective_plan(self):
        """Host-side per-step collective payload accounting (telemetry
        satellite): over each data axis a parameter's gradient either
        full-psums (parameter replicated along that axis) or
        reduce_scatters (parameter sharded along it — the GSPMD grad
        reduction IS the scatter, never a psum on top); fsdp-sharded
        params additionally regather forward (all_gather).  tp
        activation collectives depend on the traced graph and are not
        estimated here (the explicit engines — moe, ring, ulysses —
        count their own)."""
        batch_axes = self._batch_axes()
        if isinstance(batch_axes, str):
            batch_axes = (batch_axes,)
        psum = {ax: 0 for ax in batch_axes}
        rs = {ax: 0 for ax in batch_axes}
        ag = 0
        for arr, sh, t in zip(self.param_arrays, self._param_shardings,
                              self._trainable):
            axes = set()
            for entry in sh.spec:
                axes.update((entry,) if isinstance(entry, str)
                            else tuple(entry or ()))
            if "fsdp" in axes:
                ag += arr.nbytes
            if not t:
                continue
            for ax in batch_axes:
                if ax in axes:
                    rs[ax] += arr.nbytes
                else:
                    psum[ax] += arr.nbytes
        plan = [(ax, "psum", b) for ax, b in psum.items() if b]
        plan += [(ax, "reduce_scatter", b) for ax, b in rs.items() if b]
        if ag:
            plan.append(("fsdp", "all_gather", ag))
        self._collective_plan = plan

    def _record_state_bytes(self, jax):
        """Per-device params + opt-state bytes actually resident, from
        the addressable shards (works where the backend allocator
        reports no HBM stats — the CPU harness): the measured fsdp
        memory win next to the PR 5 watermark gauges."""
        if not _telemetry.enabled():
            return
        per_dev = {}
        leaves = list(self.param_arrays) + \
            jax.tree_util.tree_leaves(self.opt_state)
        for arr in leaves:
            for s in getattr(arr, "addressable_shards", ()):
                d = str(s.device)
                per_dev[d] = per_dev.get(d, 0) + int(s.data.nbytes)
        for d, b in per_dev.items():
            _telemetry.TRAIN_STATE_BYTES.set(b, device=d)

    def _batch_axes(self):
        """Mesh axes the batch dim shards over: the explicit
        batch_axis_spec if given, else the layout's data axes present in
        the mesh (('dp', 'fsdp') under fsdp layouts), else whatever
        DATA_AXES the mesh carries (legacy param_spec_fn path)."""
        if self._batch_spec is not None:
            return self._batch_spec
        if self.mesh is None:
            return ()
        if self._layout is not None:
            return self._layout.batch_axes(self.mesh)
        from .mesh import DATA_AXES

        return tuple(a for a in self.mesh.axis_names if a in DATA_AXES)

    def _batch_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.mesh is None:
            return None
        axes = self._batch_axes()
        if isinstance(axes, str):
            spec = P(axes)
        elif not axes:
            spec = P()
        else:
            spec = P(tuple(axes) if len(axes) > 1 else axes[0])
        return NamedSharding(self.mesh, spec)

    def shard_batch(self, *arrays):
        """Place per-host batch arrays onto the mesh (dp-sharded).

        Under multi-process jax.distributed, pass this process's LOCAL
        batch shard (global batch = concat over workers in rank order)."""
        import jax

        sh = self._batch_sharding()
        out = []
        for a in arrays:
            raw = a._data if isinstance(a, NDArray) else a
            out.append(self._global_put(jax, raw, sh)
                       if sh is not None else raw)
        return out

    # -- the compiled step ----------------------------------------------
    def _build(self, n_inputs):
        import jax

        net = self.net
        params_objs = self._params
        loss_fn = self.loss_fn
        trainable = self._trainable
        cdtype = self._dtype
        policy = self._dtype_policy

        # per-parameter compute-cast plan, resolved ONCE at build: the
        # policy's ordered override rules fire by name (norm params and
        # the loss head stay f32 under bf16_mixed), everything else
        # casts to the compute dtype.  None = no cast.  The legacy
        # ``dtype=`` arg keeps its blanket-cast semantics.
        cast_dtypes = [None] * len(params_objs)
        self._cast_bytes = 0
        for i, (p, arr) in enumerate(zip(params_objs, self.param_arrays)):
            if not np.issubdtype(np.dtype(arr.dtype), np.floating):
                continue
            if policy is not None:
                tgt = policy.param_cast_dtype(p.name, tuple(arr.shape))
                if np.dtype(arr.dtype) != tgt:
                    cast_dtypes[i] = tgt
                    self._cast_bytes += int(arr.nbytes)
            elif cdtype is not None:
                cast_dtypes[i] = np.dtype(cdtype)
                self._cast_bytes += int(arr.nbytes)

        fusion_spec = self._fusion

        def forward_loss(param_arrays, inputs, label, rng):
            from contextlib import ExitStack

            from .. import dtype_policy as _dtp
            from .. import fusion_cost as _fc

            # resolved per trace, not at build: a cost table installed
            # after construction applies to new-shape retraces; resolve
            # BEFORE mutating the global trace state so a bad
            # MXNET_FUSION set after construction cannot leak it
            fusion_plan = _fc.resolve_fusion(fusion_spec)
            _random.push_trace_key(rng)
            prev_t = autograd.set_training(True)
            prev_r = autograd.set_recording(False)
            sink = []
            _block_mod._aux_sink.sink = sink
            _block_mod._trace_state.active = True
            stack = ExitStack()
            stack.enter_context(_fc.scope(fusion_plan))
            # the policy scope makes FullyConnected/Convolution
            # harmonize activations to their weight's dtype (compute
            # follows the weight — see dtype_policy module doc)
            stack.enter_context(_dtp.scope(policy))
            try:
                # the in-place param swap is shared state across threads
                # tracing this net (see block._param_swap_lock)
                stack.enter_context(_block_mod._param_swap_lock)
                saved = []
                for i, (p, arr) in enumerate(zip(params_objs,
                                                 param_arrays)):
                    d = p.data()
                    saved.append((d, d._data))
                    ct = cast_dtypes[i]
                    d._data = arr.astype(ct) if ct is not None else arr
                try:
                    # inputs are NOT blanket-cast under a policy: token
                    # ids ride f32 carriers that bf16 would corrupt;
                    # the op-level harmonize casts real activations at
                    # each parameterized op instead.  The legacy
                    # ``dtype=`` path keeps its historical input cast.
                    nd_inputs = [NDArray(x.astype(cdtype)
                                         if cdtype is not None else x)
                                 for x in inputs]
                    out = net.hybrid_forward_dispatch(*nd_inputs)
                    if policy is not None and \
                            policy.cast_outputs is not None:
                        # the loss head boundary: logits in f32 before
                        # the softmax/CE (the bf16_mixed recipe), so
                        # the loss reduction never quantizes to bf16
                        def _co(o):
                            if isinstance(o, NDArray):
                                return NDArray(policy.cast_output(o._data))
                            if isinstance(o, (list, tuple)):
                                return type(o)(_co(v) for v in o)
                            return o

                        out = _co(out)
                    loss = loss_fn(out, NDArray(label))
                finally:
                    for d, old in saved:
                        d._data = old
                # aux params are static per model: record the Parameter
                # objects out-of-band so the traced function takes and
                # returns jax arrays only (a requirement for wrapping it
                # in jax.checkpoint below)
                aux_meta["params"] = [p for (p, _v) in sink]
                aux_vals = tuple(v._data if isinstance(v, NDArray) else v
                                 for (_p, v) in sink)
                import jax.numpy as jnp

                # reduce in f32: a bf16 mean quantizes the reported
                # loss to ~3 decimal digits
                return jnp.mean(loss._data.astype(jnp.float32)), aux_vals
            finally:
                stack.close()
                _block_mod._trace_state.active = False
                _block_mod._aux_sink.sink = None
                autograd.set_recording(prev_r)
                autograd.set_training(prev_t)
                _random.pop_trace_key()

        aux_meta = {"params": []}
        from ..remat import apply_remat

        # activation-remat policy: the value_and_grad below recomputes
        # activations per the policy instead of re-reading them from HBM
        # (no-op when the policy is off)
        forward_loss = apply_remat(forward_loss, self._remat_policy)

        opt_name = self._opt_name
        lr, wd, momentum = self._lr, self._wd, self._momentum
        beta1, beta2, eps = self._beta1, self._beta2, self._eps
        pidx = self._param_index
        ls_active = self._ls_active
        ls_cfg = self._ls_cfg
        # loss scaling reuses the non-finite select: an overflowed
        # scaled step must always be discarded in-graph, whatever the
        # host-side non-finite policy says
        guard_skip = self._on_nonfinite == "skip" or ls_active

        def step(param_arrays, opt_state, inputs, label, rng, metrics):
            import jax.numpy as jnp

            base_state = opt_state["base"] if ls_active else opt_state
            scale = opt_state["loss_scale"][0] if ls_active else None

            def lf(train_params):
                full = []
                ti = 0
                for i, p in enumerate(param_arrays):
                    if trainable[i]:
                        full.append(train_params[ti])
                        ti += 1
                    else:
                        full.append(p)
                loss, aux = forward_loss(full, inputs, label, rng)
                # the SCALED loss drives the backward pass: gradients
                # too small for bf16 ride up out of the flush-to-zero
                # band, and are unscaled below in f32
                scaled = loss * scale if ls_active else loss
                return scaled, (loss, aux)

            train_params = [p for i, p in enumerate(param_arrays)
                            if trainable[i]]
            (_scaled, (loss, aux)), grads = jax.value_and_grad(
                lf, has_aux=True)(train_params)
            if ls_active:
                inv = 1.0 / scale
                grads = [g * inv for g in grads]
                # overflow check on the unscaled master grads: inf/nan
                # survives the unscale, so this catches both a scaled
                # overflow and a genuinely poisoned batch
                grads_finite = jnp.all(jnp.stack(
                    [jnp.all(jnp.isfinite(g)) for g in grads])) \
                    if grads else jnp.bool_(True)
                keep = jnp.logical_and(jnp.isfinite(loss), grads_finite)
            else:
                keep = jnp.isfinite(loss)
            if opt_name == "sgd":
                new_train, new_base = _sgd_update(train_params, grads,
                                                  base_state, lr, momentum,
                                                  wd)
            else:
                new_train, new_base = _adam_update(train_params, grads,
                                                   base_state, lr, beta1,
                                                   beta2, eps, wd)
            new_params = []
            ti = 0
            for i, p in enumerate(param_arrays):
                if trainable[i]:
                    new_params.append(new_train[ti])
                    ti += 1
                else:
                    new_params.append(p)
            # moving-stat (aux) updates fused into the same program —
            # cast back to storage dtype inside the jit, so no per-aux
            # eager dispatch/compile happens on the host afterwards
            for p, v in zip(aux_meta["params"], aux):
                i = pidx[id(p)]
                new_params[i] = v.astype(new_params[i].dtype)
            if guard_skip:
                # non-finite guard fused into the step: a NaN/Inf loss
                # (or, under loss scaling, an overflowed gradient)
                # selects the PREVIOUS params/opt-state/moving-stats, so
                # one poisoned batch or scaled overflow cannot corrupt
                # training state — no extra host sync, just a
                # per-buffer select XLA folds into the update
                new_params = [jnp.where(keep, n, o)
                              for n, o in zip(new_params, param_arrays)]
                new_base = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(keep, n, o), new_base,
                    base_state)
            if ls_active:
                from .. import dtype_policy as _dtp

                new_ls = _dtp.loss_scale_update(
                    opt_state["loss_scale"], keep, ls_cfg)
                new_state = {"base": new_base, "loss_scale": new_ls}
            else:
                new_state = new_base
            # device-resident metric accumulation (no host sync): the
            # vector is donated in/out, so across steps the running
            # sums never leave HBM until a flush boundary.  Under loss
            # scaling "finite" means the whole step (loss AND unscaled
            # grads) was finite, and the backoff slot counts skips.
            finite = keep if ls_active else jnp.isfinite(loss)
            one = jnp.ones((), jnp.float32)
            zero = jnp.zeros((), jnp.float32)
            new_metrics = metrics + jnp.stack(
                [jnp.where(finite, loss, 0.0), one,
                 jnp.where(finite, 0.0, 1.0), zero, zero,
                 jnp.where(finite, 0.0, 1.0) if ls_active else zero])
            new_metrics = new_metrics.at[_M_LAST].set(loss)
            if ls_active:
                new_metrics = new_metrics.at[_M_LS_SCALE].set(new_ls[0])
            return new_params, new_state, loss, new_metrics

        self._step_core = step
        self._step_fn = self._jit_and_wrap(
            step, "sharded_step:%s" % self.net.name,
            self._aot_fingerprint(guard_skip))

    def _aot_fingerprint(self, guard_skip):
        from .. import dtype_policy as _dtp

        # the dtype policy rides the AOT content hash: an f32-compiled
        # executable can never be loaded under a bf16 policy (the cast
        # plan already reshapes the HLO, but the explicit tag holds
        # even for policies that happen to lower identically)
        return "remat=%s|fusion=%s|opt=%s|donate=%s|guard=%s|dtype=%s" % (
            self._remat_policy or "",
            self._fusion if self._fusion is not None else "",
            self._opt_name, self._donate, guard_skip,
            _dtp.policy_tag(self._dtype_policy))

    def _jit_and_wrap(self, fn, label, fp_extra):
        """jit (donated params/opt/metrics, outputs pinned to the input
        placement) + optional AOT-store wrap — shared by the single-step
        and K-step builds so the sharding/donation contract cannot
        drift between them."""
        import jax

        donate = (0, 1, 5) if self._donate else (5,)
        jit_kw = {}
        if self.mesh is not None and self._param_shardings is not None:
            # pin the output shardings to the input placement: without
            # this GSPMD may pick a different layout for the updated
            # state, and step N+1 would silently re-place (or retrace)
            # every buffer it was just donated
            from jax.sharding import NamedSharding, PartitionSpec as SP

            repl = NamedSharding(self.mesh, SP())
            jit_kw["out_shardings"] = (
                list(self._param_shardings), self._opt_shardings,
                repl, repl)
        jitted = jax.jit(fn, donate_argnums=donate, **jit_kw)
        from .. import aot as _aot
        from .. import dtype_policy as _dtp

        store = _aot.resolve_aot(self._aot)
        if store is not None:
            jitted = _aot.AOTFunction(
                jitted, label, store, fingerprint_extra=fp_extra,
                manifest_kind="trainer", manifest_spec=self._aot_spec,
                manifest_extra={
                    "dtype_policy": _dtp.policy_tag(self._dtype_policy)})
        return jitted

    def _build_k(self, n_inputs):
        """Compile the K-step fused train loop: ``lax.scan`` over K
        pre-staged microbatches with the params/opt-state/metrics carry
        donated — per-step Python dispatch, signature hashing, and
        executor launch are paid once per K steps.  The scan body IS
        the single-step program, so numerics match K sequential steps
        bit-for-bit.  Keyed into the AOT store separately from the
        single-step executable (``k=`` rides the fingerprint)."""
        import jax
        import jax.numpy as jnp

        step_core = self._step_core
        K = self.steps_per_call

        def step_k(param_arrays, opt_state, inputs_k, labels_k, keys,
                   metrics):
            # stack INSIDE the program: the K pre-staged microbatches
            # keep their individual shardings at the call boundary and
            # XLA sees one fused loop over the stacked [K, ...] views
            stacked = tuple(jnp.stack([ink[j] for ink in inputs_k])
                            for j in range(n_inputs))
            labels = jnp.stack(labels_k)

            def body(carry, xs):
                p, s, m = carry
                ins, lab, key = xs
                p, s, loss, m = step_core(p, s, ins, lab, key, m)
                return (p, s, m), loss

            (p, s, m), losses = jax.lax.scan(
                body, (param_arrays, opt_state, metrics),
                (stacked, labels, keys))
            return p, s, losses, m

        self._step_k_fn = self._jit_and_wrap(
            step_k, "sharded_step_k:%s" % self.net.name,
            self._aot_fingerprint(self._on_nonfinite == "skip"
                                  or self._ls_active)
            + "|k=%d" % K)

    def step(self, inputs, label):
        """Run one compiled train step. inputs: list of NDArray/jax arrays
        (already shard_batch'ed for mesh runs); returns loss (a jax
        scalar — a device *future*: reading it with ``float()``/
        ``np.asarray`` blocks until the step finishes, which the
        trainer itself never does under ``async_metrics``)."""
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        raw_in = [x._data if isinstance(x, NDArray) else x for x in inputs]
        raw_label = label._data if isinstance(label, NDArray) else label
        if self.param_arrays is None:
            self._lazy_init(example_inputs=raw_in)
        if self._step_fn is None:
            self._build(len(raw_in))
        # step-level span: kept whether or not tracing is on, with the
        # calling thread's CPU time (a stalled step's first question)
        try:
            with _tracing.begin("ShardedTrainer.step", cpu=True,
                                args={"step": self.global_step + 1}):
                return self._step_inner(raw_in, raw_label)
        except Exception as e:
            # black-box bundle for the crashing step (no-op unless the
            # flight recorder is armed; the span above is already closed
            # with status=error so the bundle shows it).  The reason is
            # layer-qualified: the per-reason rate limiter must not let
            # a trainer crash suppress an unrelated serving/fit bundle.
            _tracing.record_crash("exception-step", e,
                                  extra={"layer": "ShardedTrainer.step"})
            raise

    def step_many(self, batches):
        """Run ``steps_per_call`` train steps as ONE fused XLA call.

        ``batches``: sequence of exactly ``steps_per_call`` pairs
        ``(inputs, label)`` — inputs a list of NDArray/jax arrays,
        already ``shard_batch``'ed for mesh runs (io.DevicePrefetcher
        stages exactly this).  The microbatches run under ``lax.scan``
        with the params/opt-state/metrics carry donated; the PRNG keys
        are consumed from the framework stream host-side, so the loss/
        param/opt trajectory is bit-for-bit identical to sequential
        :meth:`step` calls.  Returns the per-microbatch loss vector
        (device array, shape ``[K]``)."""
        K = self.steps_per_call
        if len(batches) != K:
            raise MXNetError(
                "step_many needs exactly steps_per_call=%d batches; "
                "got %d" % (K, len(batches)))
        if K == 1:
            inputs, label = batches[0]
            import jax.numpy as jnp

            return jnp.reshape(self.step(inputs, label), (1,))
        raws = []
        for inputs, label in batches:
            if not isinstance(inputs, (list, tuple)):
                inputs = [inputs]
            raw_in = tuple(x._data if isinstance(x, NDArray) else x
                           for x in inputs)
            raw_label = label._data if isinstance(label, NDArray) else label
            raws.append((raw_in, raw_label))
        n_in = len(raws[0][0])
        if any(len(r[0]) != n_in for r in raws):
            raise MXNetError("step_many batches disagree on input arity")
        if self.param_arrays is None:
            self._lazy_init(example_inputs=list(raws[0][0]))
        if self._step_fn is None:
            self._build(n_in)
        if self._step_k_fn is None:
            self._build_k(n_in)
        try:
            with _tracing.begin("ShardedTrainer.step_many", cpu=True, args={
                    "step": self.global_step + 1, "k": K}):
                return self._step_many_inner(raws)
        except Exception as e:
            _tracing.record_crash("exception-step", e,
                                  extra={"layer": "ShardedTrainer.step_many"})
            raise

    def prewarm(self, inputs, label):
        """Compile — or load from the AOT store — the step executable
        for these input shapes WITHOUT running a step (no state is
        touched, no PRNG key is consumed, donated buffers stay live).

        With ``aot=`` enabled this is the trainer half of the
        ``tools/prewarm.py`` contract: run it ahead of rollout and the
        first real ``step`` starts at warm-cache speed.  Returns the
        acquisition info dict (``status`` hit/compiled/warm/fallback,
        ``seconds``), or ``{"status": "disabled"}`` when AOT is off
        (plain jit has no executable cache to pre-populate)."""
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        raw_in = [x._data if isinstance(x, NDArray) else x for x in inputs]
        raw_label = label._data if isinstance(label, NDArray) else label
        if self.param_arrays is None:
            self._lazy_init(example_inputs=raw_in)
        if self._step_fn is None:
            self._build(len(raw_in))
        from .. import aot as _aot

        if not isinstance(self._step_fn, _aot.AOTFunction):
            return {"label": "sharded_step:%s" % self.net.name,
                    "status": "disabled"}
        # an aval-identical dummy key: snapshot the stream, split once,
        # restore — prewarm must not shift the training PRNG sequence
        snap = _random.get_key_data()
        rng = _random.next_key()
        _random.set_key_data(snap)
        return self._step_fn.prewarm(
            self.param_arrays, self.opt_state, tuple(raw_in), raw_label,
            rng, self._fresh_metrics())

    def _step_inner(self, raw_in, raw_label):
        # HOT PATH (see _dispatch_commit for the no-host-sync contract)
        rng = _random.next_key()
        self._last_rng = rng
        return self._dispatch_commit(
            self._step_fn, "ShardedTrainer.step",
            (tuple(raw_in), raw_label, rng), 1, raw_in, raw_label)

    def _step_many_inner(self, raws):
        # HOT PATH — same contract as _step_inner
        import jax.numpy as jnp

        K = len(raws)
        # one PRNG key per microbatch, consumed from the stream in step
        # order: the scan sees exactly the key sequence K sequential
        # step() calls would have drawn (bit-for-bit parity)
        keys = jnp.stack([_random.next_key() for _ in range(K)])
        self._last_rng = keys[0]
        return self._dispatch_commit(
            self._step_k_fn, "ShardedTrainer.step_many",
            (tuple(r[0] for r in raws), tuple(r[1] for r in raws), keys),
            K, raws[0][0], raws[0][1])

    def _dispatch_commit(self, fn, label, call_args, n, raw_in,
                         raw_label):
        """The one dispatch+commit sequence both the single-step and the
        fused K-step paths run — the invariants (single-assignment
        snapshot, PRNG-in-snapshot, signal-mask ordering) live in
        exactly one place.

        HOT PATH.  No unconditional host sync lives here (or in
        _flush_metrics/_account): every loss/metric host read happens
        in _consume_metrics_sync (sync mode) or on the fetch thread
        (async mode) — guarded by
        tests/test_async_train.py::test_hot_path_has_no_host_sync.
        """
        self._raise_pending()
        from .. import profiler as _profiler

        tel = _telemetry.enabled()
        # the step timestamp serves telemetry, the wide-event layer
        # and the goodput ledger — each is independently enableable
        _gp0 = _sys.modules.get("mxnet_tpu.goodput")
        gp_live = _gp0 is not None and _gp0.active()
        t_step0 = _time.perf_counter() if tel or _events.enabled() \
            or gp_live else None
        # compile wall that lands INSIDE this step window (first-step
        # jit, bucket recompiles) is compile badput, not goodput —
        # snapshot the ledger's compile counter so _account can carve
        # the delta out of the productive_step segment
        self._gp_compile0 = _gp0.compile_seconds_total() if gp_live \
            else None
        if tel and self._last_dispatch_end is not None:
            # dispatch-to-dispatch idle: host time spent OUTSIDE step
            # dispatch (data wait, metric bookkeeping) — the quantity
            # async dispatch + device prefetch exist to shrink
            _telemetry.HOST_GAP_SECONDS.observe(
                max(0.0, t_step0 - self._last_dispatch_end),
                loop="sharded")
        # With a checkpoint manager attached, SIGTERM/SIGINT are masked
        # across dispatch+commit: donation invalidates the previous
        # committed snapshot's buffers the moment the jitted step is
        # called, so a preemption flush landing inside this window would
        # read deleted arrays.  The pending signal is delivered at
        # unmask, when the new snapshot is consistent.
        mask = self._ckpt_manager is not None and \
            hasattr(_signal, "pthread_sigmask")
        if mask:
            _signal.pthread_sigmask(
                _signal.SIG_BLOCK, {_signal.SIGTERM, _signal.SIGINT})
        try:
            span_args = {"step": self.global_step + 1}
            if n > 1:
                span_args["k"] = n
            with _tracing.begin("step:dispatch", args=span_args):
                new_params, new_state, loss_out, new_metrics = \
                    _profiler.timed_call(
                        label, fn,
                        (self.param_arrays, self.opt_state) + call_args
                        + (self._metrics_acc,))
            next_step = self.global_step + n
            # single-assignment snapshot: the preemption handler may fire
            # between any two bytecodes, and must never observe params
            # from step N next to optimizer state from step N-1.  The
            # PRNG stream state rides in the snapshot too — reading it
            # live at flush time would leak a key consumed by a step
            # that never committed, breaking bit-for-bit resume.  Under
            # async dispatch the arrays are device futures; a flush
            # landing now simply blocks in the host gather until the
            # step completes (the drain-before-snapshot contract).
            self._committed = (new_params, new_state, next_step,
                               _random.get_key_data())
            self.param_arrays = new_params
            self.opt_state = new_state
            self.global_step = next_step
            self._metrics_acc = new_metrics
            self._metrics_pending += n
        finally:
            if mask:
                _signal.pthread_sigmask(
                    _signal.SIG_UNBLOCK,
                    {_signal.SIGTERM, _signal.SIGINT})
        self._flush_metrics(next_step)
        self._account(t_step0, n, raw_in, raw_label)
        # coordinated commit BEFORE the periodic check: when it fires it
        # sets manager.preempted, which the periodic save honors — the
        # final checkpoint is written exactly once
        self._maybe_coordinated_commit(next_step, n)
        self._maybe_periodic_checkpoint(next_step, n)
        return loss_out

    # -- metric flush / drain boundaries ---------------------------------
    def _flush_metrics(self, step, force=False):
        """Hand the device-resident accumulator off every
        ``metrics_every`` steps: to the bounded fetch thread (async) or
        to the synchronous consumer.  A fresh zeroed buffer replaces it
        (the old one was donated away)."""
        if self._metrics_acc is None or self._metrics_pending == 0:
            return
        if not force and self._metrics_pending < self._metrics_every:
            return
        acc, self._metrics_acc = self._metrics_acc, self._fresh_metrics()
        n, self._metrics_pending = self._metrics_pending, 0
        if self._async:
            if self._fetcher is None:
                self._fetcher = _MetricFetcher(self._apply_metrics_host,
                                               depth=self._fetch_depth)
            self._fetcher.submit(step, n, acc)
        else:
            self._consume_metrics_sync(step, n, acc)

    def _consume_metrics_sync(self, step, n, acc):
        """The synchronous (historical) metric path: block on the loss
        accumulator right inside the step.  Lives OUTSIDE the hot-path
        functions so the no-host-sync guard can assert the async path
        never reaches a blocking read."""
        with _tracing.begin("step:fetch",
                            args={"step": step, "steps": n, "sync": True}):
            host = np.asarray(acc)
        self._apply_metrics_host(step, n, host, async_mode=False)

    def _apply_metrics_host(self, step, n, host, async_mode=True):
        """Consume one flushed accumulator (host side): heartbeat loss
        gauge, non-finite policy, skip counting.  Runs on the fetch
        thread under async dispatch, inline otherwise."""
        tel = _telemetry.enabled()
        nonfinite = int(host[_M_NONFINITE])
        if tel:
            _telemetry.TRAIN_LOSS.set(float(host[_M_LAST]))
        if self._ls_active:
            # loss-scaling mode: a scaled overflow is ROUTINE — the
            # update was already discarded in-graph and the scale
            # backed off, so it is counted (skip semantics), not
            # warned or raised through the non-finite policy.
            backoffs = int(host[_M_LS_BACKOFF])
            scale_now = float(host[_M_LS_SCALE])
            if tel:
                _telemetry.LOSS_SCALE.set(scale_now)
            if backoffs:
                self.skipped_steps += backoffs
                if tel:
                    _telemetry.LOSS_SCALE_BACKOFFS.inc(backoffs)
                    _telemetry.TRAIN_SKIPPED_STEPS.inc(backoffs,
                                                       loop="sharded")
                if scale_now <= 1.0 and \
                        self._on_nonfinite in ("warn", "raise"):
                    # the scale has bottomed out at its floor and steps
                    # STILL overflow: this is a genuinely poisoned run
                    # (NaN data / diverged model), not a routine scaled
                    # overflow — honor the caller's non-finite policy
                    # instead of silently skipping forever
                    from .. import checkpoint as _ckpt

                    what = ("loss/gradients (%d of %d steps ending at "
                            "step %d; loss scale at floor %.1f)"
                            % (backoffs, n, step, scale_now))
                    try:
                        _ckpt.check_finite(np.float32(np.nan),
                                           self._on_nonfinite, what=what)
                    except Exception as e:  # NonfiniteError ("raise")
                        if not async_mode:
                            raise
                        self._pending_exc = e
            return
        if self._on_nonfinite != "off" and nonfinite:
            from .. import checkpoint as _ckpt

            what = "loss (%d of %d steps ending at step %d)" % (
                nonfinite, n, step)
            try:
                applied = _ckpt.check_finite(
                    np.float32(np.nan), self._on_nonfinite, what=what)
            except Exception as e:  # NonfiniteError under "raise"
                if not async_mode:
                    raise
                # deferred raise: surfaces at the next step()/drain()
                self._pending_exc = e
                return
            if not applied:  # "skip": the compiled select already
                # discarded the updates — this only counts them
                self.skipped_steps += nonfinite
                _telemetry.TRAIN_SKIPPED_STEPS.inc(nonfinite,
                                                   loop="sharded")

    def _raise_pending(self):
        exc, self._pending_exc = self._pending_exc, None
        if exc is not None:
            raise exc

    def drain(self):
        """Hard sync boundary for async dispatch: flush the
        device-resident metric accumulator, wait for every in-flight
        background fetch to complete AND apply, then re-raise any
        deferred non-finite error.  Call before reading
        ``skipped_steps``/heartbeat gauges, at epoch ends, or before
        tearing the trainer down.  A no-op in sync mode (metrics were
        consumed inside each step)."""
        t0 = _time.perf_counter()
        self._flush_metrics(self.global_step, force=True)
        if self._fetcher is not None:
            self._fetcher.wait()
            if self._fetcher.error is not None:
                err, self._fetcher.error = self._fetcher.error, None
                raise err
        self._raise_pending()
        _gp = _sys.modules.get("mxnet_tpu.goodput")
        if _gp is not None and _gp.active():
            _gp.record_segment("drain", _time.perf_counter() - t0,
                               step=self.global_step)
        return self

    def step_breakdown(self):
        """Where did this trainer's step milliseconds go: a
        :class:`~mxnet_tpu.perf_ledger.StepBreakdown` over the
        telemetry window (since the last ``telemetry.reset()``) —
        device_compute / compile / aot_load / data_wait / host_other
        buckets that sum to the measured wall per step, plus the
        per-axis collective payload.  Drains first so async-mode
        metrics are complete.  Returns None when telemetry recorded no
        steps (collection off, or no step since the last reset)."""
        from .. import perf_ledger as _pl

        self.drain()
        return _pl.StepBreakdown.from_telemetry(loop="sharded")

    def close(self):
        """Release background resources: drain pending metric fetches
        and stop the fetch thread.  Safe to call repeatedly, and the
        trainer keeps working afterwards (a fresh fetch thread starts
        lazily on the next async flush)."""
        self.drain()
        if self._fetcher is not None:
            fetcher, self._fetcher = self._fetcher, None
            fetcher.close()
        return self

    def configure_overlap(self, async_metrics=None, steps_per_call=None,
                          metrics_every=None):
        """Re-knob the dispatch-overlap machinery after construction
        (the bench A/B path).  Drains first so a toggle can neither
        lose nor double-count in-flight metrics; changing
        ``steps_per_call`` invalidates the fused executable (rebuilt
        lazily on the next :meth:`step_many`)."""
        self.drain()
        if async_metrics is not None:
            self._async = bool(async_metrics)
            if not self._async and self._fetcher is not None:
                # release the fetch thread (drained above, so the
                # sentinel put cannot block); the A/B toggle path must
                # not accumulate one idle thread per flip
                fetcher, self._fetcher = self._fetcher, None
                fetcher.close()
        if steps_per_call is not None:
            k = int(steps_per_call)
            if k < 1:
                raise MXNetError("steps_per_call must be >= 1; got %d" % k)
            if k != self.steps_per_call:
                self.steps_per_call = k
                self._step_k_fn = None
            if not self._metrics_every_explicit:
                self._metrics_every = k
        if metrics_every is not None:
            self._metrics_every = max(1, int(metrics_every))
            self._metrics_every_explicit = True
        return self

    def _account(self, t_step0, n, raw_in, raw_label):
        """Post-dispatch telemetry for a call covering ``n`` steps.
        Under async dispatch the window covers dispatch only; steady
        state still converges to true step time via fetch-queue and
        dispatch-queue backpressure.  Under the sync metric path the
        flush already blocked on the device, so the window covers
        execution (the historical semantics)."""
        # t_step0 is None when both layers were off at dispatch time —
        # an enable() racing in mid-step must not crash the accounting
        tel = _telemetry.enabled() and t_step0 is not None
        ev_on = _events.enabled() and t_step0 is not None
        _gp = _sys.modules.get("mxnet_tpu.goodput")
        gp_on = _gp is not None and _gp.active() and t_step0 is not None
        if tel or ev_on or gp_on:
            dt = _time.perf_counter() - t_step0
            bs = 0
            for a in (raw_label,) + tuple(raw_in):
                shp = getattr(a, "shape", None)
                if shp:
                    bs = int(shp[0])
                    break
        if tel:
            for ax, op, b in self._collective_plan:
                _telemetry.COLLECTIVE_BYTES.inc(b * n, axis=ax, op=op)
            if self._cast_bytes:
                _telemetry.DTYPE_CAST_BYTES.inc(
                    self._cast_bytes * n, policy=self.dtype_policy_tag)
            _telemetry.TRAIN_STEP_SECONDS.observe(dt / n, loop="sharded")
            _telemetry.TRAIN_STEPS.inc(n, loop="sharded")
            if bs and dt > 0:
                _telemetry.TRAIN_SAMPLES_PER_SEC.set(bs * n / dt)
            self._record_step_cost(raw_in, raw_label)
            if self._step_flops:
                _telemetry.TRAIN_STEP_FLOPS.set(self._step_flops)
                peak = _telemetry.peak_flops()
                if peak and dt > 0:
                    _telemetry.TRAIN_MFU.set(self._step_flops * n / dt
                                             / peak)
            self._last_dispatch_end = _time.perf_counter()
        if ev_on:
            # one wide event per dispatch window (n steps under the
            # fused K-step loop): the per-step evidence row the
            # steady-state histograms anonymize.  OK-sampled like
            # every ok outcome; slow windows survive via tail-keep.
            # Independent of telemetry — each knob stands alone.
            _events.emit(
                "train_step", dur_s=dt, steps=n,
                step=self.global_step, loop="sharded",
                batch_rows=bs or None,
                samples_per_sec=round(bs * n / dt, 3)
                if bs and dt > 0 else None)
        if gp_on:
            # the goodput ledger's productive_step segment: the same
            # dispatch-window wall the step histogram observes, minus
            # any compile wall recorded inside the window (already a
            # compile segment), tagged with the step reached so
            # lost-work pricing can anchor on the last committed
            # checkpoint
            comp0 = getattr(self, "_gp_compile0", None)
            comp = max(0.0, _gp.compile_seconds_total() - comp0) \
                if comp0 is not None else 0.0
            _gp.record_segment("productive_step",
                               max(0.0, dt - comp),
                               step=self.global_step, steps=n)
        if tel or _tracing.enabled():
            # per-step HBM watermark sample: live/peak gauges per device
            # plus a counter track in the exported chrome trace
            _tracing.sample_device_memory()

    def _maybe_periodic_checkpoint(self, next_step, n):
        """Periodic save, fused-loop aware: fires when the call crossed
        a period boundary (a K-step call saves once, at its end)."""
        m = self._ckpt_manager
        if m is not None and self._ckpt_period and not m.preempted and \
                (next_step // self._ckpt_period) > \
                ((next_step - n) // self._ckpt_period):
            if self._async and self._on_nonfinite == "raise":
                # a parked NonfiniteError must abort BEFORE the save:
                # under "raise" the poisoned update was applied, and
                # persisting it as the newest checkpoint would hand
                # auto-resume NaN state.  The checkpoint boundary is a
                # documented hard-sync point, so the drain is free to
                # block here.
                self.drain()
            self.save_checkpoint(m, step=next_step)

    def _maybe_coordinated_commit(self, step, n, force=False):
        """Poll the coordinated-preemption flag at a step boundary.

        Under sharded multi-process checkpointing a SIGTERM on ANY host
        does not save locally — it publishes a target step through an
        atomic flag file in the shared checkpoint directory.  The final
        commit then rides the first PERIODIC boundary at or past the
        target: periodic saves are the pod's existing synchronization
        points (every host passes each one, in order, through the shard
        barrier), so aligning to them guarantees every host picks the
        SAME final step without any new cross-host agreement — the flag
        is durable before the preemptor's next shard write, hence
        visible to every peer no later than the barrier of the commit
        boundary.  With no periodic cadence (``period=0``) every
        boundary qualifies; then ``MXNET_DIST_PREEMPT_GATE`` must
        exceed the pod's worst-case step drift.

        Returns True while a request is pending or was just committed
        (training loops should exit when ``manager.preempted``).
        """
        m = self._ckpt_manager
        if m is None or m.preempted or not getattr(m, "sharded", False):
            return False
        req = m.coordinated_commit_request()
        if req is None:
            return False
        if not force:
            if step < int(req.get("target_step", step)):
                return True  # flag seen; commit at the gated boundary
            P = self._ckpt_period
            if P and (step // P) <= ((step - n) // P):
                return True  # wait for the next pod-wide sync point
        if self._async and self._on_nonfinite == "raise":
            self.drain()  # same poisoned-save hazard as periodic saves
        payload = self._checkpoint_payload()
        if payload is None:
            return True
        s, arrays, blobs, meta = payload
        meta = dict(meta)
        meta["preempted"] = True
        meta["coordinated"] = True
        m.save(s, arrays, blobs=blobs, meta=meta, block=True)
        m.preempted = True
        m.clear_coordinated_commit()
        _gp = _sys.modules.get("mxnet_tpu.goodput")
        if _gp is not None:
            # the coordinated-commit exit boundary: everything up to
            # the committed step is goodput, nothing is lost work
            _gp.note_exit("preempt", step=s)
        return True

    def check_preemption(self, force=False):
        """Public poll for loops that pace themselves (e.g. between
        epochs).  ``force=True`` commits at the CURRENT step even off
        the periodic cadence or below the gated target — the
        end-of-data backstop, where every host sits at the same final
        step by construction."""
        return self._maybe_coordinated_commit(self.global_step, 0,
                                              force=force)

    def _record_step_cost(self, raw_in, raw_label):
        """One-time XLA cost attribution for the compiled step.

        ``Lowered.cost_analysis`` reads the HLO without a second backend
        compile (same trick as the CachedOp hook); the flops feed the
        telemetry MFU gauge and ``profiler._xla_costs`` so ``dumps()``
        shows the train step next to the compiled-program cost table.
        Costs one extra host-side trace, paid once per process and only
        when telemetry is on.  Always lowers the SINGLE-step program
        (per-step flops), also when training runs the fused loop.
        """
        if self._step_flops is not None:
            return
        self._step_flops = 0.0
        try:
            lowered = self._step_fn.lower(
                self.param_arrays, self.opt_state, tuple(raw_in),
                raw_label, self._last_rng, self._metrics_acc)
            cost = lowered.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            if cost:
                from .. import profiler as _profiler

                _profiler.record_xla_cost("ShardedTrainer.step", cost)
                flops = float(cost.get("flops", 0.0) or 0.0)
                if flops > 0:
                    self._step_flops = flops
        except Exception:
            pass  # cost analysis is best-effort; never fail a step

    # -- fault tolerance -------------------------------------------------
    def attach_checkpoint_manager(self, manager, period=0,
                                  auto_resume=True,
                                  install_signal_handler=True):
        """Wire a :class:`mxnet_tpu.checkpoint.CheckpointManager` into
        the step loop.

        * ``auto_resume``: load the newest *intact* checkpoint (params,
          optimizer state, PRNG stream, global_step) if one exists —
          corrupt ones are skipped with a loud warning.  With the PRNG
          stream restored, the resumed loss trajectory is bit-for-bit
          identical to an uninterrupted run.
        * ``period``: save every N steps (async per the manager's
          config); 0 = only explicit/preemption saves.
        * ``install_signal_handler``: SIGTERM/SIGINT flush a final
          checkpoint from the last committed step snapshot and set
          ``manager.preempted`` so the training loop can exit.

        Returns the resumed ``global_step`` (0 for a fresh start).
        """
        self._ckpt_manager = manager
        self._ckpt_period = int(period)
        if getattr(manager, "sharded", False) and \
                manager._procinfo()[0] == 0:
            # attach is the one moment no peer can be mid-save (workers
            # attach before their first step, and the first dispatch
            # costs a compile — far longer than this sweep): process 0
            # alone clears aborted-save debris and any stale preemption
            # flag a previous incarnation left behind
            manager.sweep_orphans()
        resumed = False
        t_load0 = _time.perf_counter()
        if auto_resume:
            ckpt = manager.load(
                restrict=self._elastic_restrict(manager),
                context={"mesh_axes": self.mesh_shape,
                         "layout": self.layout_name})
            if ckpt is not None:
                self.restore_checkpoint(ckpt)
                resumed = True
                _telemetry.TRAIN_RESUMES.inc()
                if getattr(ckpt, "resharded", False) and \
                        getattr(ckpt, "sharded", False):
                    _telemetry.ELASTIC_RESUMES.inc()
        load_s = _time.perf_counter() - t_load0
        from .. import config as _config

        gdir = str(_config.get("MXNET_GOODPUT_DIR") or "")
        if gdir:
            # attach is the incarnation boundary: one recorder per
            # process, begun with the resume provenance the lost-work
            # rule prices against.  The restore above ran before the
            # recorder existed, so its wall is recorded here (a direct
            # manager.load under a live recorder is covered by the
            # CheckpointManager hook instead).
            from .. import goodput as _goodput

            if not _goodput.active():
                rec = _goodput.GoodputRecorder(gdir).begin(
                    start_reason="resume" if resumed else "fresh",
                    resumed_from_step=self.global_step if resumed
                    else None)
                if resumed:
                    rec.segment("ckpt_restore", load_s,
                                step=self.global_step)
        if install_signal_handler:
            gate = max(1, int(_config.get("MXNET_DIST_PREEMPT_GATE"))) \
                * max(1, self.steps_per_call)
            manager.install_preemption_handler(self._checkpoint_payload,
                                               gate=gate)
        return self.global_step

    def _elastic_restrict(self, manager):
        """Bounds map of THIS process's addressable blocks (params +
        optimizer leaves) so a sharded restore reads only overlapping
        shard files.  None (= load everything) for single-process runs,
        deferred-shape params, or dense managers."""
        import jax

        if not getattr(manager, "sharded", False) \
                or jax.process_count() <= 1 \
                or self.param_arrays is None:
            return None
        from ..checkpoint import _index_bounds

        def bounds_of(a):
            if not hasattr(a, "addressable_shards") \
                    or getattr(a, "sharding", None) is None:
                return None
            out, seen = [], set()
            for sh in a.addressable_shards:
                b = _index_bounds(sh.index, a.shape)
                k = tuple(tuple(x) for x in b)
                if k not in seen:
                    seen.add(k)
                    out.append(b)
            return out

        restrict = {}
        for i, a in enumerate(self.param_arrays):
            b = bounds_of(a)
            if b is not None:
                restrict["param:%04d" % i] = b
        for i, leaf in enumerate(
                jax.tree_util.tree_leaves(self.opt_state)):
            b = bounds_of(leaf)
            if b is not None:
                restrict["opt:%04d" % i] = b
        # "rng" and any host-resident leaves are absent from the map —
        # _load_sharded loads unlisted names in full on every host
        return restrict or None

    def _checkpoint_payload(self, step=None):
        """(step, arrays, blobs, meta) from the last committed snapshot."""
        if self._committed is not None:
            params, opt_state, gstep, key_data = self._committed
        elif self.param_arrays is not None:
            params, opt_state, gstep, key_data = (
                self.param_arrays, self.opt_state, self.global_step,
                _random.get_key_data())
        else:
            return None  # nothing initialized yet — nothing to flush
        import jax

        arrays = {}
        # index-keyed: gluon auto-names (dense0_...) depend on process-
        # global counters and would spuriously mismatch across restarts;
        # the manifest meta keeps the names for human debugging
        for i, a in enumerate(params):
            arrays["param:%04d" % i] = a
        for i, leaf in enumerate(jax.tree_util.tree_leaves(opt_state)):
            arrays["opt:%04d" % i] = leaf
        arrays["rng"] = key_data
        meta = {"kind": "sharded_trainer", "step": int(gstep),
                "optimizer": self._opt_name,
                "param_names": [p.name for p in self._params],
                # the saving topology: dense saves host-gather FULL
                # arrays; sharded saves keep global shapes in the
                # manifest instead — either way a restore under a
                # different mesh shape resplits on load (_apply_restore
                # detects and counts the topology change)
                "mesh_axes": self.mesh_shape,
                "layout": self.layout_name,
                "n_processes": int(jax.process_count()),
                # the precision recipe the state was trained under (the
                # loss-scale leaf rides the opt:* arrays when active)
                "dtype_policy": self.dtype_policy_tag}
        if self._layout_res is not None:
            meta["param_specs"] = self._layout_res.spec_strings()
        return (int(gstep) if step is None else int(step)), arrays, {}, meta

    def save_checkpoint(self, manager, step=None, block=None):
        """Snapshot params + optimizer state + PRNG stream to
        ``manager`` (async by default; ``manager.wait()`` is the
        barrier)."""
        payload = self._checkpoint_payload(step)
        if payload is None:
            raise MXNetError("ShardedTrainer has no state to checkpoint "
                             "yet (run a step or initialize params first)")
        s, arrays, blobs, meta = payload
        manager.save(s, arrays, blobs=blobs, meta=meta, block=block)
        return s

    def restore_checkpoint(self, ckpt):
        """Restore from a loaded :class:`Checkpoint` (params, optimizer
        state, PRNG stream, global_step), re-placing arrays onto the
        trainer's mesh/device sharding.  With deferred-shape params the
        restore is applied when shapes materialize on the first step."""
        if ckpt.meta.get("kind") != "sharded_trainer":
            raise MXNetError("checkpoint step %d was not written by "
                             "ShardedTrainer (kind=%r)"
                             % (ckpt.step, ckpt.meta.get("kind")))
        self.global_step = int(ckpt.meta.get("step", ckpt.step))
        if "rng" in ckpt.arrays:
            _random.set_key_data(ckpt.arrays["rng"])
        self._committed = None
        if self.param_arrays is None:
            self._pending_restore = ckpt
            return
        self._apply_restore(ckpt)

    def _put_like(self, jax, val, old):
        """Place a host array like an existing trainer array (same
        sharding/device; multi-process meshes go through the global-put
        path)."""
        val = np.asarray(val)
        old_dtype = np.dtype(old.dtype)
        if val.dtype != old_dtype:
            val = val.astype(old_dtype)
        sh = getattr(old, "sharding", None)
        if sh is None:
            return jax.device_put(val)
        if jax.process_count() > 1:
            # val holds the GLOBAL array with this host's addressable
            # regions populated (restricted sharded loads zero-fill the
            # rest); the callback is only invoked for addressable
            # device indices, so no host ever reads a region it didn't
            # load and no cross-host gather happens.
            return jax.make_array_from_callback(
                tuple(val.shape), sh, lambda idx: val[idx])
        return jax.device_put(val, sh)

    def _apply_restore(self, ckpt):
        import jax

        # reshard-on-load: manifests record the saving topology; when
        # the restoring trainer's mesh/layout differ, _put_like below
        # resplits every full array onto the NEW sharding — same
        # digest-verified values, different placement (elastic resume).
        saved_axes = ckpt.meta.get("mesh_axes")
        saved_layout = ckpt.meta.get("layout")
        if saved_axes is not None and (
                dict(saved_axes) != self.mesh_shape
                or saved_layout != self.layout_name):
            import logging

            logging.getLogger("mxnet_tpu.parallel").info(
                "resharding checkpoint step %d: saved mesh=%s layout=%r "
                "-> restoring mesh=%s layout=%r", ckpt.step, saved_axes,
                saved_layout, self.mesh_shape, self.layout_name)
            _telemetry.CHECKPOINT_RESHARDS.inc()
        n_ckpt = sum(1 for k in ckpt.arrays if k.startswith("param:"))
        if n_ckpt != len(self.param_arrays):
            raise MXNetError(
                "checkpoint step %d holds %d params, model has %d — was "
                "it written by a different model? (checkpoint names: %s)"
                % (ckpt.step, n_ckpt, len(self.param_arrays),
                   ckpt.meta.get("param_names")))
        new_arrays = []
        for i, (p, old) in enumerate(zip(self._params, self.param_arrays)):
            key = "param:%04d" % i
            val = ckpt.arrays[key]
            if tuple(val.shape) != tuple(old.shape):
                raise MXNetError(
                    "checkpoint step %d: %r (%s) shape %s != model shape "
                    "%s" % (ckpt.step, key, p.name, tuple(val.shape),
                            tuple(old.shape)))
            new_arrays.append(self._put_like(jax, val, old))
        flat, treedef = jax.tree_util.tree_flatten(self.opt_state)
        new_flat = []
        for i, old in enumerate(flat):
            key = "opt:%04d" % i
            if key not in ckpt.arrays:
                raise MXNetError(
                    "checkpoint step %d is missing optimizer leaf %r "
                    "(optimizer %r vs checkpoint %r)"
                    % (ckpt.step, key, self._opt_name,
                       ckpt.meta.get("optimizer")))
            new_flat.append(self._put_like(jax, ckpt.arrays[key], old))
        self.param_arrays = new_arrays
        self.opt_state = jax.tree_util.tree_unflatten(treedef, new_flat)

    def sync_to_net(self):
        """Write the pytree back into the gluon Parameters (gathered to a
        single addressable array so eager use works).

        Under multi-process jax.distributed this is a COLLECTIVE call
        (every process must call it): sharded params are re-replicated
        through a jitted identity before the host fetch, since a global
        Array spanning non-addressable devices cannot be np.asarray'd."""
        import jax
        import jax.numpy as jnp

        replicate = None
        if jax.process_count() > 1 and self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            replicate = jax.jit(
                lambda a: a,
                out_shardings=NamedSharding(self.mesh, P()))

        for p, arr in zip(self._params, self.param_arrays):
            if replicate is not None and hasattr(arr, "is_fully_replicated") \
                    and not arr.is_fully_replicated:
                arr = replicate(arr)
            host = np.asarray(arr)
            p.data()._rebind(jnp.asarray(host))
