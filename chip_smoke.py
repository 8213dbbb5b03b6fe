#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children.  Drives the repo's main paths once through the
entry points a user calls, at the published width of the model of record
(ResNet-50 v1, 1000 classes, 3x224x224, random weights from a seed), and
checks what comes out by the repo's own means:

1. device   print what JAX sees; anything but a TPU exits non-zero here,
            before a model is built (no CPU branch, no batch shrink)
2. train    bench.build_trainer at batch 256 under bf16_mixed: 8 single
            steps, then the async + K=4 fused loop (both executables the
            headline path uses); finite, decreasing loss, state on the
            chip, no recompile after each program's first call
3. serve    AsyncPredictor over the same network, bs32 uint8 requests incl.
            a short one (the pad path), top-1 against a direct net(x)
4. kernels  both Pallas kernels compiled by Mosaic (interpret=False) and
            compared with their references

Mesh: MXNET_MESH as ShardedTrainer reads it (unset = one device;
``MXNET_MESH=dp=4 python chip_smoke.py`` on a four-chip host).  Every
phase is fatal: the failing phase is named on the last line of output and
the exit code is non-zero.  On success the last line of stdout is one JSON
object ``{"ok": true, "device": {...}}`` with the device as JAX reports it.
The timings printed are facts of this run, not benchmark results.
"""
import json
import sys
import time
import traceback

import numpy as np

PLATFORM = "tpu"            # the only platform this script passes on
BATCH = 256                 # the benchmark-of-record train batch
SYNC_STEPS = 8
FUSED_K = 4
FUSED_CALLS = 3             # first compiles, the rest are steady
SERVE_BATCH = 32
SERVE_CHAIN = 8
SERVE_REQUESTS = 36
SERVE_SHORT_ROWS = 5        # one request shorter than the batch: pad path
# Serving computes in bf16 (8 mantissa bits, ~0.4 % per rounding) through
# ~50 layers with f32 accumulation; the direct forward keeps f32
# activations.  Logits may differ by a few percent of their scale, so
# top-1 "agrees" when the served winner's reference logit is within
# SERVE_TOL of the row's logit spread from the reference winner (an exact
# argmax match is the common case and is reported).
SERVE_TOL = 0.05
# flash attention: bf16 outputs of O(1) values round at 2^-8; lse is f32
# but the MXU multiplies at bf16 precision unless told otherwise
FLASH_O_TOL = 3e-2
FLASH_LSE_TOL = 2e-2
# ResNet-50 v1 (1000 classes) has 25,557,032 parameters: its flat gradient
# is not a multiple of the 16,384-element kernel tile; 1563 tiles is the
# aligned size next to it
COMPRESS_SIZES = (1563 * 16384, 25557032)

_DEV = {}                   # platform / kind / count, as JAX reports them


def log(msg):
    print(msg, flush=True)


def fact(phase, **kv):
    """One result line; each names the device it was observed on."""
    body = " ".join("%s=%s" % (k, v) for k, v in kv.items())
    log("[smoke] %s %s platform=%s device_kind=%r count=%d"
        % (phase, body, _DEV["platform"], _DEV["kind"], _DEV["count"]))


def check(cond, msg):
    """assert that survives ``python -O``."""
    if not cond:
        raise AssertionError(msg)


def platform_of(arr):
    return next(iter(arr.devices())).platform


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------

def device_phase():
    import jax

    devs = jax.devices()
    _DEV.update(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))
    log("[smoke] device jax=%s platform=%s device_kind=%r count=%d"
        % (jax.__version__, _DEV["platform"], _DEV["kind"], _DEV["count"]))
    check(_DEV["platform"] == PLATFORM,
          "no accelerator: jax.devices()[0].platform is %r, need %r — "
          "this script has no CPU path" % (_DEV["platform"], PLATFORM))
    import mxnet_tpu  # noqa: F401  (package bootstrap places the cache)

    fact("device", compile_cache_dir=jax.config.jax_compilation_cache_dir)


# ---------------------------------------------------------------------------
# phase 2: train
# ---------------------------------------------------------------------------

def _compiles():
    from mxnet_tpu import telemetry

    return int(telemetry.COMPILES.value())


def _cache_counts():
    from mxnet_tpu import telemetry

    return (int(telemetry.COMPILE_CACHE_HITS.value()),
            int(telemetry.COMPILE_CACHE_MISSES.value()))


def _bytes_in_use(device):
    return device.memory_stats()["bytes_in_use"]


def _timed(fn):
    """(result, wall seconds) with the device work inside the window."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def train_phase(batch=BATCH):
    import bench
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    telemetry.enable()
    mx.random.seed(0)           # weights and data are made from a seed
    trainer, x, y, got_batch, on_accel = bench.build_trainer(
        batch=batch, dtype_policy="bf16_mixed")
    check(got_batch == batch and on_accel,
          "bench.build_trainer shrank the batch to %d (asked %d)"
          % (got_batch, batch))
    mesh = trainer.mesh
    n_mesh = mesh.devices.size if mesh is not None else 1
    fact("train", model="resnet50_v1", batch=batch,
         dtype_policy=trainer.dtype_policy_tag, mesh=trainer.mesh_shape,
         layout=trainer.layout_name)

    # -- program 1: the single fused step --------------------------------
    losses, walls = [], []
    for i in range(SYNC_STEPS):
        loss, dt = _timed(lambda: trainer.step([x], y))
        losses.append(float(loss))
        walls.append(dt)
        if i == 0:
            after_first = _compiles()
    check(all(np.isfinite(losses)), "non-finite loss: %r" % losses)
    check(losses[-1] < losses[0],
          "loss did not decrease on a repeated batch: %r" % losses)
    check(_compiles() == after_first,
          "step recompiled after its first call (%d -> %d compiles): a "
          "jit key flipped between calls" % (after_first, _compiles()))
    check(platform_of(loss) == PLATFORM
          and all(platform_of(a) == PLATFORM for a in trainer.param_arrays),
          "loss/params are not on a %s device" % PLATFORM)
    if mesh is not None:
        x_raw = getattr(x, "_data", x)
        check(len(x_raw.sharding.device_set) == n_mesh
              and len(trainer.param_arrays[0].sharding.device_set) == n_mesh,
              "batch/params do not span all %d mesh devices" % n_mesh)
        in_use = [_bytes_in_use(d) for d in mesh.devices.flat]
        # every chip holds at least its own copy/shard of the 100 MB of
        # f32 weights plus its slice of the batch
        check(min(in_use) > 50e6,
              "a mesh device holds almost nothing: bytes_in_use=%r"
              % in_use)
        fact("train", mesh_devices=n_mesh,
             bytes_in_use_mb=[round(b / 1e6) for b in in_use])
    hits, misses = _cache_counts()
    fact("train", program="step", compile_wall_s=round(walls[0], 2),
         steady_ms_per_step=round(1e3 * float(np.median(walls[2:])), 2),
         loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
         cache_hits=hits, cache_misses=misses)

    # -- program 2: async dispatch + K-step lax.scan loop ----------------
    trainer.configure_overlap(async_metrics=True, steps_per_call=FUSED_K)
    fused = [([x], y)] * FUSED_K
    k_walls = []
    for i in range(FUSED_CALLS):
        k_losses, dt = _timed(lambda: trainer.step_many(fused))
        k_walls.append(dt)
        k_host = np.asarray(k_losses)
        check(k_host.shape == (FUSED_K,) and np.all(np.isfinite(k_host)),
              "fused losses wrong: %r" % (k_host,))
        if i == 0:
            after_first = _compiles()
    trainer.drain()
    check(_compiles() == after_first,
          "step_many recompiled after its first call (%d -> %d compiles)"
          % (after_first, _compiles()))
    hits, misses = _cache_counts()
    fact("train", program="step_many", k=FUSED_K,
         compile_wall_s=round(k_walls[0], 2),
         steady_ms_per_step=round(
             1e3 * float(np.median(k_walls[1:])) / FUSED_K, 2),
         loss_last=round(float(k_host[-1]), 4),
         loss_scale=trainer.loss_scale(),
         skipped_steps=trainer.skipped_steps,
         global_step=trainer.global_step,
         cache_hits=hits, cache_misses=misses)
    trainer.close()
    return trainer.net


# ---------------------------------------------------------------------------
# phase 3: serve
# ---------------------------------------------------------------------------

def serve_phase(net, batch=SERVE_BATCH, image=(3, 224, 224)):
    """``net`` still holds its seeded initial weights (the trainer trains
    its own donated copy), so this check does not depend on phase 2."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.serving import uint8_normalizer
    from mxnet_tpu.serving_async import AsyncPredictor

    n_dev = len(jax.devices())
    rng = np.random.RandomState(1)
    requests = [rng.randint(0, 256, (batch,) + image).astype(np.uint8)
                for _ in range(SERVE_REQUESTS)]
    requests[SERVE_REQUESTS // 2] = \
        requests[SERVE_REQUESTS // 2][:SERVE_SHORT_ROWS]

    server = AsyncPredictor.from_block(
        net, requests[0], replicas=n_dev, chain=SERVE_CHAIN,
        preprocess=uint8_normalizer(), dtype_policy="bf16_mixed")
    placed = sorted(str(r.pred.device) for r in server._replicas)
    check(placed == sorted(str(d) for d in jax.devices()),
          "replicas are not one per device: %r" % placed)
    t0 = time.perf_counter()
    futures = [server.submit(r) for r in requests]
    outs = [f.result(timeout=900) for f in futures]   # raises unless ok
    wall = time.perf_counter() - t0
    server.close(drain=True)
    check(server.stats()["inflight"] == 0, "requests left in flight")
    for r, o in zip(requests, outs):
        check(o.shape == (r.shape[0], 1000) and np.all(np.isfinite(o)),
              "bad served output: shape %r" % (o.shape,))

    # the reference: a direct (hybridized, f32) forward of the same block
    # on the same pixels, put through the same normalizer in f32; the
    # short request is among the four compared
    to_f32 = uint8_normalizer(dtype="float32")
    net.hybridize()
    exact = rows = 0
    worst = 0.0
    for i in (0, SERVE_REQUESTS // 3, SERVE_REQUESTS // 2,
              SERVE_REQUESTS - 1):
        pix = np.zeros((batch,) + image, np.uint8)
        n = requests[i].shape[0]
        pix[:n] = requests[i]
        ref = net(mx.nd.array(np.asarray(to_f32(pix))))
        ref = np.asarray(jax.block_until_ready(ref._data))[:n]
        got = outs[i].astype(np.float32)
        spread = ref.max(axis=1) - ref.min(axis=1)
        served_top = got.argmax(axis=1)
        gap = ref.max(axis=1) - ref[np.arange(n), served_top]
        check(np.all(gap <= SERVE_TOL * spread),
              "request %d: served top-1 disagrees with net(x) beyond the "
              "bf16 tolerance (gap/spread max %.4f)"
              % (i, float((gap / spread).max())))
        exact += int((served_top == ref.argmax(axis=1)).sum())
        rows += n
        worst = max(worst, float(np.abs(got - ref).max()
                                 / np.abs(ref).max()))
    hits, misses = _cache_counts()
    fact("serve", requests=SERVE_REQUESTS, replicas=n_dev,
         short_rows=SERVE_SHORT_ROWS, wall_incl_compile_s=round(wall, 2),
         top1_exact="%d/%d" % (exact, rows),
         max_logit_err_rel=round(worst, 4),
         cache_hits=hits, cache_misses=misses)


# ---------------------------------------------------------------------------
# phase 4: Pallas kernels, compiled
# ---------------------------------------------------------------------------

def _check_flash(B, T, H, D, blk_k, causal):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention_pallas import flash_attention_with_lse
    from mxnet_tpu.parallel.ring_attention import local_attention

    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)
               for _ in range(3))
    (o, lse), wall = _timed(lambda: flash_attention_with_lse(
        q, k, v, causal=causal, blk_k=blk_k, interpret=False))
    check(o.shape == (B, T, H, D) and o.dtype == jnp.bfloat16
          and lse.shape == (B, T, H) and platform_of(o) == PLATFORM,
          "flash output has the wrong shape/dtype/placement")
    q32, k32, v32 = (a.astype(jnp.float32) for a in (q, k, v))
    with jax.default_matmul_precision("float32"):
        ref = local_attention(q32, k32, v32, causal=causal)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, k32) * (D ** -0.5)
        if causal:
            s = jnp.where(jnp.arange(T)[:, None] >= jnp.arange(T)[None, :],
                          s, -jnp.inf)
        lse_ref = jnp.swapaxes(jax.nn.logsumexp(s, axis=-1), 1, 2)
    o_err = float(jnp.abs(o.astype(jnp.float32) - ref).max())
    lse_err = float(jnp.abs(lse - lse_ref).max())
    check(o_err < FLASH_O_TOL and lse_err < FLASH_LSE_TOL,
          "flash attention off its reference: |do|=%.4g |dlse|=%.4g"
          % (o_err, lse_err))
    fact("kernels", kernel="flash_attention", shape=(B, T, H, D),
         blk_k=blk_k, causal=causal, o_err=round(o_err, 5),
         lse_err=round(lse_err, 5), first_call_s=round(wall, 2))


def _ref_2bit(grad, residual, t):
    """NumPy rendering of gradient_compression's 2-bit semantics:
    (dequantized values, new residual)."""
    g = grad + residual
    pos, neg = g >= t, g <= -t
    new_res = g - np.where(pos, t, np.float32(0)) \
        + np.where(neg, t, np.float32(0))
    return np.where(pos, t, np.where(neg, -t, np.float32(0))), new_res


def _check_compression(size, t=np.float32(0.5)):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.contrib import compression

    check(compression._use_interpret() is False,
          "compression would run through the Pallas interpreter")
    rng = np.random.RandomState(3)
    residual = np.zeros(size, np.float32)
    res_dev = jnp.zeros(size, jnp.float32)
    walls = []
    for rnd in range(2):            # round 2 carries the error feedback
        grad = rng.randn(size).astype(np.float32)
        grad_dev = jax.block_until_ready(jnp.asarray(grad))
        (codes, res_dev), wall = _timed(lambda: compression.quantize_2bit(
            grad_dev, res_dev, float(t)))
        walls.append(wall)
        deq = jax.block_until_ready(
            compression.dequantize_2bit(codes, size, float(t)))
        check(platform_of(codes) == PLATFORM, "codes not on the chip")
        deq_ref, residual = _ref_2bit(grad, residual, t)
        check(np.array_equal(np.asarray(deq), deq_ref),
              "dequantize(quantize(g)) differs from the reference "
              "(size %d, round %d)" % (size, rnd))
        check(np.allclose(np.asarray(res_dev), residual, rtol=0, atol=1e-6),
              "error-feedback residual differs from the reference")
        # bit layout, independent of the dequantize kernel: element
        # (row r, lane l) of the first tile is bits 2*(r%16).. of
        # codes[r//16, l]; 01 = +t, 10 = -t
        tile = np.asarray(codes[:8]).view(np.uint32)
        bits = (tile[:, None, :] >> (2 * np.arange(16, dtype=np.uint32)
                                     )[None, :, None]) & 3
        want = np.where(deq_ref[:16384] > 0, 1,
                        np.where(deq_ref[:16384] < 0, 2, 0))
        check(np.array_equal(bits.reshape(128, 128),
                             want.reshape(128, 128)),
              "packed 2-bit codes are not in the reference layout")
    fact("kernels", kernel="compression_2bit", size=size,
         tile_aligned=size % 16384 == 0, first_call_s=round(walls[0], 2),
         second_call_ms=round(1e3 * walls[1], 2))


def kernels_phase():
    for D, blk_k in ((128, 128), (64, 64)):
        for causal in (False, True):
            _check_flash(4, 2048, 8, D, blk_k, causal)
    for size in COMPRESS_SIZES:
        _check_compression(size)


# ---------------------------------------------------------------------------

def main():
    state = {}
    phases = (
        ("device", device_phase),
        ("train", lambda: state.update(net=train_phase())),
        ("serve", lambda: serve_phase(state["net"])),
        ("kernels", kernels_phase),
    )
    t_all = time.perf_counter()
    for name, run in phases:
        t0 = time.perf_counter()
        log("[smoke] == phase %s" % name)
        try:
            run()
        except BaseException:
            traceback.print_exc()
            sys.stderr.flush()
            log("[smoke] FAILED phase=%s" % name)
            sys.exit(1)
        log("[smoke] == phase %s ok (%.1fs)" % (name, time.perf_counter() - t0))
    log("[smoke] all phases ok in %.1fs" % (time.perf_counter() - t_all))
    print(json.dumps({"ok": True, "device": _DEV}), flush=True)


if __name__ == "__main__":
    main()
