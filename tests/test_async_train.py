"""Async step dispatch + K-step fused train loop (ISSUE 10).

The invariant under test is BIT-FOR-BIT numerics: non-blocking metric
dispatch, the device-resident metric accumulator, and the ``lax.scan``
fused loop may only move host work around — the loss/param/opt-state/
PRNG trajectory must equal the synchronous per-step baseline exactly.
Plus the no-host-sync guard for the hot path and the io.DevicePrefetcher
ordering/error contract.
"""
import inspect
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, parallel, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.io.prefetch import DevicePrefetcher


def _make_trainer(seed, **kw):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(1))
    net.initialize()
    loss_fn = gluon.loss.L2Loss()
    tr = parallel.ShardedTrainer(net, lambda o, l: loss_fn(o, l),
                                 optimizer="adam",
                                 optimizer_params={"learning_rate": 0.05},
                                 **kw)
    return net, tr


_RNG = np.random.RandomState(0)
_X = _RNG.rand(16, 6).astype(np.float32)
_Y = (_X @ _RNG.rand(6, 1)).astype(np.float32)


def _batch(i):
    return [nd.array(_X + 0.01 * i)], nd.array(_Y)


def _state(tr):
    import jax

    params = [np.asarray(a) for a in tr.param_arrays]
    opt = [np.asarray(x) for x in jax.tree_util.tree_leaves(tr.opt_state)]
    return params, opt


def test_async_fused_parity_bit_for_bit():
    """sync per-step == async K=1 == async fused K=4: losses, params,
    optimizer state and the PRNG stream all EXACTLY equal (the
    acceptance invariant — same keys, same update math, one program)."""
    from mxnet_tpu import random as _random

    n_steps = 8
    _, ref = _make_trainer(7)
    ref_losses = []
    for i in range(n_steps):
        x, y = _batch(i)
        ref_losses.append(float(np.asarray(ref.step(x, y))))
    ref_params, ref_opt = _state(ref)
    ref_rng = np.asarray(_random.get_key_data()).copy()

    # async K=1: same compiled program, metrics pulled in the background
    _, tr1 = _make_trainer(7, async_metrics=True)
    a1 = [float(np.asarray(tr1.step(*_batch(i)))) for i in range(n_steps)]
    tr1.drain()
    assert a1 == ref_losses
    p1, o1 = _state(tr1)
    assert all(np.array_equal(a, b) for a, b in zip(p1, ref_params))
    assert all(np.array_equal(a, b) for a, b in zip(o1, ref_opt))
    assert np.array_equal(np.asarray(_random.get_key_data()), ref_rng)

    # async fused K=4: two lax.scan calls covering the same 8 steps
    _, tr4 = _make_trainer(7, async_metrics=True, steps_per_call=4)
    a4 = []
    for c in range(n_steps // 4):
        batches = [_batch(c * 4 + j) for j in range(4)]
        a4.extend(float(v) for v in np.asarray(tr4.step_many(batches)))
    tr4.drain()
    assert a4 == ref_losses
    assert tr4.global_step == n_steps
    p4, o4 = _state(tr4)
    assert all(np.array_equal(a, b) for a, b in zip(p4, ref_params))
    assert all(np.array_equal(a, b) for a, b in zip(o4, ref_opt))
    assert np.array_equal(np.asarray(_random.get_key_data()), ref_rng)


def test_hot_path_has_no_host_sync():
    """The dispatch hot path must never force a device sync: no
    ``np.asarray``/``float(``/``.item(`` in the hot-path functions
    (host reads live in _consume_metrics_sync / the fetch thread), and
    under async metrics the sync consumer is never called."""
    hot = [parallel.ShardedTrainer._step_inner,
           parallel.ShardedTrainer._step_many_inner,
           parallel.ShardedTrainer._dispatch_commit,
           parallel.ShardedTrainer._flush_metrics,
           parallel.ShardedTrainer._account]
    for fn in hot:
        src = inspect.getsource(fn)
        for needle in ("np.asarray", "float(", ".item("):
            assert needle not in src, (
                "%s contains %r — loss/metric host reads belong in "
                "_consume_metrics_sync or the fetch thread"
                % (fn.__name__, needle))

    # behavioral guard: async steps never reach the blocking consumer
    _, tr = _make_trainer(3, async_metrics=True)

    def boom(*a, **kw):
        raise AssertionError("sync metric consumer on the async path")

    tr._consume_metrics_sync = boom
    for i in range(3):
        tr.step(*_batch(i))
    tr.drain()
    # ...and the heartbeat loss still lands via the background fetch
    telemetry.enable()
    try:
        telemetry.reset()
        loss = tr.step(*_batch(3))
        tr.drain()
        assert telemetry.TRAIN_LOSS.value() == float(np.asarray(loss))
        assert telemetry.ASYNC_METRIC_FETCHES.value() >= 1
    finally:
        telemetry.reset()
        telemetry.disable()


def test_async_skip_policy_counts_after_drain():
    """Non-finite guard composes with async dispatch: the compiled
    select discards the update on device; the skip count lands at the
    drain boundary (one fetch late, never a sync in step())."""
    _, tr = _make_trainer(9, on_nonfinite="skip", async_metrics=True)
    x, y = _batch(0)
    tr.step(x, y)
    tr.drain()
    before = [np.asarray(a).copy() for a in tr.param_arrays]
    xb = _X.copy()
    xb[0, 0] = np.nan
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        tr.step([nd.array(xb)], y)
        tr.drain()
    assert tr.skipped_steps == 1
    after = [np.asarray(a) for a in tr.param_arrays]
    assert all(np.array_equal(a, b) for a, b in zip(before, after))


def test_fused_loop_fsdp_tp_aot_roundtrip(tmp_path):
    """steps_per_call composes with the PR 9 layouts and the PR 8 AOT
    store: dp=2 x fsdp=2 x tp=2 fused loop, second trainer round-trips
    through the store (a cache hit, loaded onto the mesh's devices and
    executed) — numerics identical."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    store = str(tmp_path / "store")
    telemetry.enable()
    try:
        telemetry.reset()

        def build():
            mx.random.seed(3)
            net = nn.HybridSequential()
            net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
            net.initialize()
            loss_fn = gluon.loss.L2Loss()
            return parallel.ShardedTrainer(
                net, lambda o, l: loss_fn(o, l), mesh="dp=2,fsdp=2,tp=2",
                layout="fsdp_tp", optimizer="sgd", async_metrics=True,
                steps_per_call=2, aot=store)

        rng = np.random.RandomState(0)
        X = rng.rand(8, 8).astype(np.float32)
        Y = rng.rand(8, 4).astype(np.float32)
        runs = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for _ in range(2):
                tr = build()
                assert tr.layout_name == "fsdp_tp"
                xs, ys = tr.shard_batch(nd.array(X), nd.array(Y))
                losses = tr.step_many([([xs], ys), ([xs], ys)])
                tr.drain()
                runs.append(np.asarray(losses).copy())
        np.testing.assert_array_equal(runs[0], runs[1])
        # the second trainer loaded the 8-device executable onto the
        # mesh's devices and ran it (no silent fallback to the jit)
        assert telemetry.AOT_CACHE_HITS.value() >= 1
        assert telemetry.AOT_FALLBACKS.value(reason="dispatch") == 0
        assert all(e is not tr._step_k_fn._FALLBACK
                   for e in tr._step_k_fn._compiled.values())
    finally:
        telemetry.reset()
        telemetry.disable()


def test_device_prefetcher_order_count_and_errors():
    """DevicePrefetcher is numerics-transparent: same batches, same
    order, same count; source exceptions surface at next() after the
    staged batches; depth=0 degrades to a passthrough."""
    batches = [(np.full((2, 2), i, np.float32),
                np.full((2,), i, np.float32)) for i in range(5)]
    out = list(DevicePrefetcher(iter(batches), depth=2))
    assert len(out) == 5
    for i, (x, y) in enumerate(out):
        np.testing.assert_array_equal(np.asarray(x), batches[i][0])
        np.testing.assert_array_equal(np.asarray(y), batches[i][1])

    out0 = list(DevicePrefetcher(iter(batches), depth=0))
    assert len(out0) == 5

    def bad_source():
        yield batches[0]
        raise RuntimeError("decode failed")

    it = DevicePrefetcher(bad_source(), depth=2)
    first = next(it)
    np.testing.assert_array_equal(np.asarray(first[0]), batches[0][0])
    with pytest.raises(RuntimeError, match="decode failed"):
        next(it)


def test_dataloader_device_prefetch_bridge():
    """gluon DataLoader(device_prefetch=...) stages batches through
    io.DevicePrefetcher without changing their values or order."""
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    X = np.arange(24, dtype=np.float32).reshape(12, 2)
    Y = np.arange(12, dtype=np.float32)
    ds = ArrayDataset(nd.array(X), nd.array(Y))
    plain = [(np.asarray(x.asnumpy()), np.asarray(y.asnumpy()))
             for x, y in DataLoader(ds, batch_size=4)]
    staged = list(DataLoader(ds, batch_size=4, device_prefetch=True))
    assert len(staged) == len(plain)
    for (px, py), (sx, sy) in zip(plain, staged):
        np.testing.assert_array_equal(px, np.asarray(sx))
        np.testing.assert_array_equal(py, np.asarray(sy))


def test_prefetcher_feeds_trainer_steps():
    """End-to-end bridge: DataLoader -> DevicePrefetcher(trainer=...)
    -> step, same losses as the unprefetched loop."""
    _, tr = _make_trainer(11)
    batches = [_batch(i) for i in range(4)]
    ref = [float(np.asarray(tr.step(x, y))) for x, y in batches]

    _, tr2 = _make_trainer(11)
    with DevicePrefetcher(iter([(x[0], y) for x, y in batches]),
                          trainer=tr2, depth=2) as staged:
        got = [float(np.asarray(tr2.step([x], y))) for x, y in staged]
    assert got == ref
