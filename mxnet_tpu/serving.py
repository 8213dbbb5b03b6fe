"""Small-batch inference serving harness.

Reference context: ``docs/faq/perf.md:181-199`` benchmarks small-batch
(bs32) inference throughput.  On this stack two costs dominate, and the
design attacks both:

1. **Dispatch latency**: ``chain`` microbatches are fused into one XLA
   program (a ``lax.scan`` over microbatches), so one Python dispatch
   and one device->host fetch serve K batches.
2. **Host->device input bytes**: the host never stacks, casts, or
   normalizes.  Each incoming batch is ``device_put`` as-is — ideally
   raw ``uint8`` NCHW, 4x fewer bytes than fp32, 2x fewer than bf16 —
   the moment it arrives (``device_put`` is async, so the upload of
   batch i+1 streams while the chain containing batch i computes), and
   all arithmetic (cast / scale / normalize via ``preprocess``) happens
   on device inside the compiled program, fused into the first conv.

Throughput: not measured on the current machine (``chip_smoke.py`` runs
this path on the chip for correctness only).
"""
from __future__ import annotations

import logging
import time as _time

import numpy as np

from . import telemetry as _telemetry
from . import tracing as _tracing

__all__ = ["Predictor", "uint8_normalizer"]

_logger = logging.getLogger("mxnet_tpu.serving")


def uint8_normalizer(mean=(123.68, 116.779, 103.939), std=(58.393, 57.12, 57.375),
                     dtype="bfloat16"):
    """Build a device-side preprocess fn: uint8 NCHW -> normalized dtype.

    The returned fn runs inside the Predictor's compiled program, so the
    cast/scale fuses into the model's first convolution — the host ships
    raw bytes only.
    """
    import jax.numpy as jnp

    def prep(x):
        c = x.shape[1]
        m = jnp.asarray(mean[:c], jnp.float32).reshape(1, c, 1, 1)
        s = jnp.asarray(std[:c], jnp.float32).reshape(1, c, 1, 1)
        return ((x.astype(jnp.float32) - m) / s).astype(dtype)

    return prep


class Predictor:
    """Chained-dispatch, streaming-upload predictor over a jittable forward.

    forward(x, params) -> out, with x one batch.  ``chain`` microbatches
    are fused into one compiled program; ``predict`` streams outputs in
    submission order.  ``preprocess`` (optional, jittable) runs on device
    on each batch before ``forward`` — pass :func:`uint8_normalizer` and
    feed raw uint8 batches to minimize host->device bytes.
    """

    def __init__(self, forward, params, chain=8, preprocess=None,
                 postprocess=None, batch_shape=None, batch_dtype=None,
                 device=None, aot=None, aot_spec=None, dtype_policy=None,
                 param_names=None, aot_policy_tag=None):
        import jax
        from jax import lax

        from . import aot as _aot
        from . import dtype_policy as _dtp

        assert chain >= 1
        self._chain = int(chain)
        self._preprocess = preprocess
        self._postprocess = postprocess
        # mixed-precision dtype policy (None defers to
        # MXNET_DTYPE_POLICY): params cast to the compute dtype inside
        # the compiled program (per override rule when ``param_names``
        # names the leaves — from_block/from_symbol wire them), ops
        # harmonize to the weight dtype, floating outputs cast back at
        # the boundary.  Params stay committed in storage dtype — the
        # cast fuses into the first consumer on device.
        dt_policy = _dtp.resolve_policy(dtype_policy)
        self._dtype_policy = dt_policy
        self._param_names = list(param_names) if param_names else None
        _dtp.note_policy(dt_policy, "predictor")

        def _cast_param_tree(tree):
            if dt_policy is None:
                return tree
            if isinstance(tree, dict):
                return {n: dt_policy.cast_compute(n, a)
                        for n, a in tree.items()}
            if self._param_names is not None and \
                    isinstance(tree, (list, tuple)) and \
                    len(tree) == len(self._param_names):
                return tuple(dt_policy.cast_compute(n, a)
                             for n, a in zip(self._param_names, tree))
            # anonymous pytree: blanket compute cast on floating leaves
            return jax.tree_util.tree_map(
                lambda a: a.astype(dt_policy.compute_dtype)
                if _dtp._is_float(a.dtype) else a, tree)
        # commit every param to the device ONCE: host-resident params
        # would re-upload on every dispatch, and an uncommitted array
        # flips the jit cache key.  ``device`` pins
        # the replica to a specific mesh device (serving_async places
        # one Predictor per device); default stays device 0.
        self._dev = device if device is not None else jax.devices()[0]
        self._params = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._dev), params)
        jax.block_until_ready(self._params)

        def one(x, params_):
            from . import dtype_policy as _dtp_mod

            if preprocess is not None:
                x = preprocess(x)
            with _dtp_mod.scope(dt_policy):
                out = forward(x, _cast_param_tree(params_))
            if dt_policy is not None:
                out = dt_policy.cast_output(out)
            if postprocess is not None:
                # device-side output reduction (e.g. top-k for a
                # classify API): shrinks the device->host fetch from
                # full logits to a few values per row.  Must return a
                # single array with leading batch dim.
                out = postprocess(out)
            return out

        self._jit_one = jax.jit(one)

        def chained(xs_tuple, params_):
            # stack happens ON DEVICE (a free layout op under XLA); the
            # host-side jnp.stack of the old design serialized a full
            # chunk-sized host copy + upload per dispatch
            import jax.numpy as jnp

            xs = jnp.stack(xs_tuple)

            def step(carry, x):
                return carry, one(x, params_)

            _, outs = lax.scan(step, 0, xs)
            return outs

        self._jit_chain = jax.jit(chained)
        # AOT executable store (aot= or the MXNET_AOT default): a
        # freshly spawned replica deserializes the chain executable
        # instead of recompiling it — the warm-pool/restart path.  The
        # device rides in the signature (one executable per replica
        # device), so per-device replicas each hit their own entry.
        self._aot_spec = aot_spec
        store = _aot.resolve_aot(aot)
        if store is not None:
            # the dtype-policy tag rides the content hash AND the
            # manifest: an f32-compiled executable can never be served
            # under a bf16 (or int8) policy — key separation by
            # construction
            # aot_policy_tag overrides for graph-level precision the
            # cast policy cannot express (the int8 quantize rewrite)
            dtag = aot_policy_tag or _dtp.policy_tag(dt_policy)
            fp = "dtype=%s" % dtag
            mext = {"dtype_policy": dtag}
            self._jit_one = _aot.AOTFunction(
                self._jit_one, "predictor:one", store,
                fingerprint_extra=fp, manifest_kind="predictor",
                manifest_spec=aot_spec, manifest_extra=mext)
            self._jit_chain = _aot.AOTFunction(
                self._jit_chain, "predictor:chain", store,
                fingerprint_extra=fp, manifest_kind="predictor",
                manifest_spec=aot_spec, manifest_extra=mext)
        # serving batch contract.  Pass batch_shape (or build via
        # from_block, which seeds it from the example input) so a
        # ragged FIRST request pads up to the intended size; with
        # neither, the first batch seen defines the contract.
        self._batch_shape = tuple(batch_shape) if batch_shape else None
        self._batch_dtype = np.dtype(batch_dtype) if batch_dtype else None

    @property
    def chain(self):
        """Microbatches fused per dispatch (compile-time constant)."""
        return self._chain

    @property
    def batch_shape(self):
        """The compiled per-batch shape contract (None until pinned)."""
        return self._batch_shape

    @property
    def batch_dtype(self):
        """The compiled batch dtype contract (None until pinned)."""
        return self._batch_dtype

    @property
    def device(self):
        """The jax device this replica's params are committed to."""
        return self._dev

    def prewarm(self):
        """Compile — or load from the AOT store — this replica's
        dispatch executables without serving a request.

        Requires a pinned batch contract (``batch_shape``/
        ``batch_dtype`` or :meth:`from_block`): the compiled program is
        shape-specialized, so there is nothing to pre-build for an
        implicit contract.  Returns a list of acquisition info dicts
        (one per executable) — ``tools/prewarm.py`` and the
        serving warm pool aggregate these."""
        import jax

        from . import aot as _aot
        from .base import MXNetError

        if self._batch_shape is None or self._batch_dtype is None:
            raise MXNetError(
                "Predictor.prewarm() needs a pinned batch contract "
                "(pass batch_shape=/batch_dtype= or build via "
                "from_block)")
        infos = []
        zeros = np.zeros(self._batch_shape, self._batch_dtype)
        arr = jax.device_put(zeros, self._dev)
        if self._chain == 1:
            # chain-1 dispatch only ever uses the single-batch program
            if isinstance(self._jit_one, _aot.AOTFunction):
                infos.append(self._jit_one.prewarm(arr, self._params))
        elif isinstance(self._jit_chain, _aot.AOTFunction):
            infos.append(self._jit_chain.prewarm(
                tuple([arr] * self._chain), self._params))
        if not infos:
            infos.append({"label": "predictor", "status": "disabled"})
        return infos

    @classmethod
    def from_block(cls, net, example_input, chain=8, preprocess=None,
                   postprocess=None, device=None, aot=None,
                   aot_spec=None, dtype_policy=None):
        """Build from a gluon HybridBlock: traces the block's forward the
        same way CachedOp does (moving stats frozen — inference).

        If ``preprocess`` is given, ``example_input`` should be the RAW
        (pre-preprocess) input, e.g. a uint8 batch.
        """
        import jax.numpy as jnp

        from . import autograd
        from . import dtype_policy as _dtp
        from .gluon import block as block_mod
        from .ndarray.ndarray import NDArray, array

        x_nd = example_input if isinstance(example_input, NDArray) \
            else array(np.asarray(example_input))
        probe = x_nd[:1]
        if preprocess is not None:
            probe = NDArray(preprocess(probe._data))
        # the shape probe runs under the policy scope like the compiled
        # forward: a bf16 preprocess output meets f32 storage weights
        # here, and only the scope's compute-follows-the-weight cast
        # lets the first convolution take both
        with autograd.pause(), _dtp.scope(_dtp.resolve_policy(dtype_policy)):
            block_mod._abstract_eval_forward(net, [probe])
        params = list(net.collect_params().values())
        param_arrays = tuple(p.data()._data for p in params)

        def forward(x, param_arrays_):
            with block_mod.swapped_params(params, param_arrays_):
                return net.hybrid_forward_dispatch(NDArray(x))._data

        pred = cls(forward, param_arrays, chain=chain,
                   preprocess=preprocess, postprocess=postprocess,
                   batch_shape=tuple(x_nd.shape),
                   batch_dtype=np.dtype(x_nd.dtype), device=device,
                   aot=aot, aot_spec=aot_spec, dtype_policy=dtype_policy,
                   param_names=[p.name for p in params])
        return pred, jnp.asarray(x_nd._data)

    @classmethod
    def from_symbol(cls, sym, arg_params, aux_params=None,
                    data_name="data", chain=8, preprocess=None,
                    postprocess=None, batch_shape=None, batch_dtype=None,
                    device=None, aot=None, aot_spec=None,
                    dtype_policy=None, aot_policy_tag=None):
        """Build from a symbolic model: the whole graph evaluates as one
        pure fn over named arrays, params committed to the device once.

        This is the serving entry point for graph-rewritten models that
        have no gluon block — most importantly the int8 artifacts
        ``tools/quantize_model.py`` emits (quantized symbol + int8
        weight params + range scalars; see
        ``contrib.quantization.load_artifact``).  ``arg_params`` /
        ``aux_params`` take NDArray or raw arrays; ``data_name`` is the
        one free data variable fed per batch.
        """
        from .ndarray.ndarray import NDArray

        if aot_policy_tag is not None and dtype_policy is None:
            # graph-level precision (the int8 quantize rewrite): the
            # artifact's numerics were validated by the accuracy gate
            # EXACTLY as stored — pin the cast policy OFF so an
            # ambient MXNET_DTYPE_POLICY cannot re-cast range scalars
            # or the excluded-fp32 layers of a gated artifact
            dtype_policy = "f32"
        fn, _, _ = sym._build_fn()
        params = {}
        for src in (arg_params or {}), (aux_params or {}):
            for n, a in src.items():
                if n == data_name:
                    continue
                params[n] = a._data if isinstance(a, NDArray) else a

        def forward(x, params_):
            values = dict(params_)
            values[data_name] = x
            outs, _aux = fn(values, is_train=False)
            return outs[0]

        return cls(forward, params, chain=chain, preprocess=preprocess,
                   postprocess=postprocess, batch_shape=batch_shape,
                   batch_dtype=batch_dtype, device=device, aot=aot,
                   aot_spec=aot_spec, dtype_policy=dtype_policy,
                   aot_policy_tag=aot_policy_tag)

    def _upload(self, b, request_id=None):
        """Async host->device transfer of one raw batch.

        Pads a ragged final batch up to the compiled batch size on the
        host (cheap: raw bytes, no arithmetic) so no second XLA program
        is ever compiled; returns (device_array, valid_rows)."""
        try:
            return self._upload_impl(b)
        except (TypeError, ValueError) as e:
            # batch-contract violations (shape/dtype) — caller bug
            self._count_error("contract", request_id, e)
            raise
        except Exception as e:
            # retry-exhausted host->device transfer and anything else
            self._count_error("transfer", request_id, e)
            raise

    # per-request error series are bounded: past this many distinct ids
    # the overflow bucket absorbs the rest (a misbehaving client hammering
    # the contract must not grow the registry without bound — the log
    # line and the trace span still carry every individual id)
    _MAX_ERROR_ID_SERIES = 128

    @classmethod
    def _count_error(cls, kind, request_id, exc):
        """Failure bookkeeping with a greppable request id: the id is
        the request's root span id when tracing is on, else minted here
        (errors only — the happy path never pays for one)."""
        rid = request_id or _tracing.new_request_id()
        _telemetry.SERVING_ERRORS.inc(kind=kind)
        label = rid if len(_telemetry.SERVING_REQUEST_ERRORS._series) \
            < cls._MAX_ERROR_ID_SERIES else "overflow"
        _telemetry.SERVING_REQUEST_ERRORS.inc(kind=kind, request_id=label)
        _logger.error("serving request %s failed (%s): %s", rid, kind, exc)

    def _upload_impl(self, b):
        import jax

        if not isinstance(b, (np.ndarray, jax.Array)):
            # NDArray / lists / anything else: coerce via __array__
            # (device jax arrays must NOT round-trip through the host)
            b = np.asarray(b)
        if self._batch_shape is None:
            # the first observed batch fixes the compiled contract: every
            # later batch may only shrink in the leading dim.  Warn only
            # when the dtype is ALSO unpinned — a fully implicit contract
            # is where a ragged/garbage first request silently locks out
            # every later batch (ADVICE r4); a Predictor constructed with
            # batch_dtype= (the common programmatic path) has declared
            # intent and stays quiet.
            if self._batch_dtype is None:
                import warnings

                warnings.warn(
                    "Predictor batch contract implicitly set to %s/%s by "
                    "the first request; larger batches will be rejected — "
                    "pass batch_shape=/batch_dtype= to pin it explicitly"
                    % (tuple(b.shape), np.dtype(b.dtype)), stacklevel=4)
            self._batch_shape = tuple(b.shape)
        if self._batch_dtype is None:
            self._batch_dtype = np.dtype(b.dtype)
        if np.dtype(b.dtype) != self._batch_dtype:
            # a silent dtype flip would recompile a second XLA program
            # and (with a uint8 preprocess) normalize garbage
            raise TypeError(
                "batch dtype %s != compiled dtype %s"
                % (np.dtype(b.dtype), self._batch_dtype))
        n_valid = b.shape[0]
        if tuple(b.shape) != self._batch_shape:
            if tuple(b.shape[1:]) != self._batch_shape[1:] or \
                    n_valid > self._batch_shape[0]:
                raise ValueError(
                    "batch shape %s incompatible with compiled shape %s: "
                    "only the leading (batch) dim may shrink"
                    % (tuple(b.shape), self._batch_shape))
            b = np.asarray(b)  # single fetch if device-resident
            pad = np.zeros((self._batch_shape[0] - n_valid,)
                           + tuple(b.shape[1:]), b.dtype)
            b = np.concatenate([b, pad], axis=0)
        from .checkpoint import retry

        # the host->device upload is the serving path's only I/O edge:
        # retry transient transfer failures (e.g. a transient OOM
        # while an old chunk drains) with backoff instead of
        # dropping the request.  Contract violations raise above and are
        # never retried.
        put = retry(jax.device_put, retries=2, backoff=0.05,
                    exceptions=(OSError, RuntimeError))
        return put(b, self._dev), n_valid

    def predict(self, batches):
        """Yield one output (numpy) per input batch, in order.

        Uploads stream ahead of compute: each batch is ``device_put``
        (async) as soon as it is pulled from ``batches``; chunks of
        ``chain`` device-resident batches run as single dispatches; while
        chunk i's outputs are fetched, chunk i+1 is already executing."""
        chunk = []            # [(device_array, n_valid, t_submit, span)]
        pending = None        # (stacked device outputs, [(n, t, span)..])
        tel = _telemetry.enabled()
        tr_on = _tracing.enabled()
        outstanding = [0]     # uploads not yet drained (gauge bookkeeping)
        live_spans = []       # request spans not yet closed (bounded by
                              # ~2 chunks; drained entries are removed)

        def dispatch(items):
            arrs = [a for a, _n, _t, _s in items]
            valid = [(n, t, s) for _a, n, t, s in items]
            if len(arrs) == 1 and self._chain == 1:
                out = self._jit_one(arrs[0], self._params)
                return out[None], valid
            if len(arrs) < self._chain:
                # pad the tail chunk with repeats of an already-uploaded
                # device array: zero extra host->device traffic
                arrs = arrs + [arrs[-1]] * (self._chain - len(arrs))
            return self._jit_chain(tuple(arrs), self._params), valid

        def drain(p):
            out, valid = p
            # ONE bulk device->host fetch per chunk: row-by-row indexing
            # would pay a device sync per batch
            host = np.asarray(out)
            bs = self._batch_shape[0]
            pos = 0
            try:
                for i, (n, t0, sp) in enumerate(valid):
                    # finalize BEFORE the yield: a consumer that breaks
                    # mid-chunk (GeneratorExit lands on the yield below)
                    # must not strand this request's gauge/span until
                    # the blanket finally
                    pos = i + 1
                    if t0 is not None:
                        # latency = upload submission -> output on host
                        # (exemplar: the request's own detached root
                        # span — the contextvar lookup would miss it)
                        _telemetry.SERVING_REQUEST_SECONDS.observe(
                            _time.perf_counter() - t0,
                            exemplar={"trace_id": _tracing.TRACE_ID,
                                      "span_id": sp.span_id}
                            if sp is not None else None)
                        _telemetry.SERVING_IN_FLIGHT.dec()
                        outstanding[0] -= 1
                    if sp is not None:
                        sp.set(rows=n).end()
                        live_spans.remove(sp)
                    yield host[i] if n == bs else host[i, :n]
            finally:
                # abandoned mid-drain: the rest of the chunk was computed
                # but never consumed — close its requests here (error:
                # the client went away) so the exit path sees a clean
                # gauge/span table no matter which chunk broke
                for n, t0, sp in valid[pos:]:
                    if t0 is not None:
                        _telemetry.SERVING_IN_FLIGHT.dec()
                        outstanding[0] -= 1
                    if sp is not None:
                        sp.set(rows=n, abandoned=True).end(error=True)
                        live_spans.remove(sp)

        try:
            for b in batches:
                t0 = _time.perf_counter() if tel else None
                # one root span per request; its span_id IS the
                # request_id the error paths log and label.  Requests
                # overlap in flight, so the span is detached
                # (activate=False) rather than a contextvar parent.
                sp = _tracing.begin("serving.request", activate=False) \
                    if tr_on else None
                if sp is not None:
                    live_spans.append(sp)
                try:
                    arr, n_valid = self._upload(
                        b, sp.span_id if sp is not None else None)
                except BaseException:
                    if sp is not None:
                        sp.end(error=True)
                        live_spans.remove(sp)
                    raise
                if tel:
                    _telemetry.SERVING_REQUESTS.inc()
                    _telemetry.SERVING_BATCH_SIZE.observe(n_valid)
                    _telemetry.SERVING_IN_FLIGHT.inc()
                    outstanding[0] += 1
                chunk.append((arr, n_valid, t0, sp))
                if len(chunk) == self._chain:
                    out_n = dispatch(chunk)
                    chunk = []
                    if pending is not None:
                        yield from drain(pending)
                    pending = out_n
            if chunk:
                out_n = dispatch(chunk)
                if pending is not None:
                    yield from drain(pending)
                pending = out_n
            if pending is not None:
                yield from drain(pending)
        except Exception as e:
            # black-box bundle for a failed request stream (no-op
            # unless the flight recorder is armed)
            _tracing.record_crash("exception-serving", e,
                                  extra={"layer": "serving.Predictor"})
            raise
        finally:
            # a stream abandoned early (consumer break / GeneratorExit)
            # or killed by a contract error must not leave phantom
            # requests on the in-flight gauge forever — nor phantom open
            # spans that would show up as stuck requests in every later
            # postmortem
            if outstanding[0]:
                _telemetry.SERVING_IN_FLIGHT.dec(outstanding[0])
                outstanding[0] = 0
            for sp in live_spans:
                sp.end(error=True)
