"""Benchmark: ResNet-50 training throughput (images/sec/chip).

Counterpart of the reference's `train_imagenet.py --benchmark 1`
(synthetic data) + docs/faq/perf.md methodology.  Baseline of record
(BASELINE.md): V100 fp16 training ≈ 364 img/s at batch 128; fp32 ≈ 300.

Runs the fused sharded train step (mxnet_tpu.parallel.ShardedTrainer):
one XLA program per step (fwd+bwd+update, donated buffers), bf16 compute
with fp32 params — the TPU-native equivalent of the reference's
Module + kvstore('device') training loop.

Prints ONE ``BENCH {json}`` marker line on stdout (the schema-versioned
record of mxnet_tpu/perf_ledger.py, appended to the MXNET_PERF_LEDGER
run ledger when set): {"metric", "value", "unit", "vs_baseline", ...}
plus provenance and the step-time ``attribution`` breakdown.  Progress
goes to stderr.
"""
import json
import os
import sys
import time

import numpy as np

_T0 = time.time()


def log(msg):
    print("[bench %6.1fs] %s" % (time.time() - _T0, msg), file=sys.stderr,
          flush=True)


def build_trainer(batch=None, remat_policy=None, aot=None,
                  aot_spec="bench_resnet50", mesh=None, layout=None,
                  dtype_policy=None):
    """The benchmark-of-record configuration: ResNet-50 v1, bf16
    compute + fp32 master (on accelerator), momentum SGD, one fused XLA
    program per step, synthetic bs-`batch` data.  Shared by bench.py,
    tools/mfu_accounting.py and tools/bench_remat_sweep.py so the
    roofline accounting always describes the exact program the headline
    number comes from.

    ``remat_policy`` (or the MXNET_REMAT_POLICY env default) selects an
    activation-rematerialization policy for the backward pass — see
    mxnet_tpu.remat.list_policies().  ``aot`` (or the MXNET_AOT env
    default) enables the serialized-executable store, so a prewarmed
    machine skips the step-0 compile (tools/prewarm.py).
    ``mesh``/``layout`` (or MXNET_MESH / MXNET_LAYOUT) select a named
    sharding topology + per-parameter layout (docs/sharding.md); the
    defaults stay single-device, and the emitted BENCH JSON records
    mesh_shape/layout so the throughput trajectory is attributable to
    topology.

    Returns (trainer, x, y, batch, on_tpu)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    if batch is None:
        batch = int(os.environ.get("BENCH_BATCH", "256"))
    on_tpu = any(d.platform != "cpu" for d in jax.devices())
    if not on_tpu:
        # the CPU callers (tools/prewarm.py specs, the remat sweep's
        # smoke mode) only need the program, not the size; main()
        # refuses to report a number from here
        batch = min(batch, 16)

    # precision: an explicit dtype_policy= (or BENCH_DTYPE_POLICY) wins;
    # default is the mixed-precision recipe on the chip (bf16 compute,
    # f32 master + loss scaling — supersedes the old blanket bf16 cast)
    # and f32 on the CPU smoke harness
    if dtype_policy is None:
        dtype_policy = os.environ.get("BENCH_DTYPE_POLICY") or \
            ("bf16_mixed" if on_tpu else None)

    net = vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = parallel.ShardedTrainer(
        net, lambda o, l: loss_fn(o, l), mesh=mesh, layout=layout,
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        dtype_policy=dtype_policy,
        remat_policy=remat_policy, aot=aot, aot_spec=aot_spec)

    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(batch, 3, 224, 224).astype(np.float32))
    y = nd.array(rng.randint(0, 1000, batch).astype(np.float32))
    if trainer.mesh is not None:
        x, y = trainer.shard_batch(x, y)
    return trainer, x, y, batch, on_tpu


def run_prewarm():
    """BENCH_PREWARM=1: run tools/prewarm.py first, so this process's
    warmup step 0 is a *warm start* (deserialize) and the subprocess's
    measured compile is the *cold start* — both become parsed BENCH
    JSON fields and the cold-start trajectory is tracked like img/s.

    The child needs the chip, and a chip belongs to one process at a
    time: main() calls this before it imports jax, and the child has
    exited (chip released) before this process initialises a backend."""
    import subprocess

    if "jax" in sys.modules:
        raise RuntimeError(
            "run_prewarm() must run before this process imports jax: the "
            "prewarm child needs the chip this process would hold")
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "prewarm.py"),
           "--model", "bench_resnet50", "--json"]
    log("BENCH_PREWARM: %s" % " ".join(cmd))
    proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                          env=dict(os.environ, MXNET_AOT="1"))
    if proc.returncode not in (0, 2):
        # rc 2 = valid run with some AOT fallbacks: the JSON summary
        # (and the populated store) is still there and still worth
        # reporting — anything else has no cold numbers to report
        sys.exit("prewarm exited %d: no cold-start measurement"
                 % proc.returncode)
    info = json.loads(proc.stdout.strip().splitlines()[-1])
    if proc.returncode == 2:
        log("prewarm reported %d fallback(s); cold numbers still "
            "recorded" % info.get("fallbacks", 0))
    log("prewarm: %d compiled, %d already warm, cold cost %.1fs"
        % (info.get("compiled", 0), info.get("hits", 0),
           info.get("cold_seconds", 0.0)))
    return info


def _host_gap_p50():
    from mxnet_tpu import telemetry

    return telemetry.HOST_GAP_SECONDS.quantile(0.5, loop="sharded")


def ledger_records(result):
    """The run's perf_ledger record(s): the classic bench fields stay
    top-level, the topology/precision fields are
    ALSO stamped into provenance so every ledger row is comparable
    without knowing this emitter's layout.  The tier-1 schema guard
    calls this with a canned result."""
    from mxnet_tpu import perf_ledger

    prov = {"mesh_shape": result.get("mesh_shape"),
            "layout": result.get("layout"),
            "dtype_policy": result.get("dtype_policy"),
            "steps_per_call": result.get("steps_per_call", 1)}
    fields = {k: v for k, v in result.items()
              if k not in ("metric", "value", "unit", "attribution")}
    return [perf_ledger.make_record(
        result["metric"], result["value"], result["unit"], prov=prov,
        attribution=result.get("attribution"), **fields)]


def run_dtype_compare(policies, steps):
    """BENCH_DTYPE_COMPARE=1: one short synchronous phase per dtype
    policy on a FRESH trainer each, so the headline number's precision
    choice is an A/B measured in the same run (the payoff sweep flips
    the default from this field when bf16 wins on-chip)."""
    import jax

    out = {}
    for pol in policies:
        trainer, x, y, batch, _on_tpu = build_trainer(dtype_policy=pol)
        loss = trainer.step([x], y)  # compile + warm
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = trainer.step([x], y)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        trainer.drain()
        out[trainer.dtype_policy_tag] = {
            "images_per_sec": round(batch * steps / dt, 2),
            "loss_scale": trainer.loss_scale(),
        }
        log("[dtype %s] %d steps in %.3fs (%.1f img/s)"
            % (trainer.dtype_policy_tag, steps, dt, batch * steps / dt))
    return out


def main():
    prewarm_info = None
    if os.environ.get("BENCH_PREWARM", "0") not in ("", "0"):
        prewarm_info = run_prewarm()   # before jax: see its docstring
        os.environ.setdefault("MXNET_AOT", "1")
    log("importing jax/mxnet_tpu")
    import jax

    from mxnet_tpu import telemetry

    if all(d.platform == "cpu" for d in jax.devices()):
        sys.exit("bench.py reports a device metric and found no "
                 "accelerator (jax.devices() = %s); it has no CPU mode"
                 % (jax.devices(),))
    steps = int(os.environ.get("BENCH_STEPS", "40"))
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    trainer, x, y, batch, _on_tpu = build_trainer()
    # fused-loop K (the scan executable is its own compile, amortized
    # by the AOT store / persistent cache)
    k = int(os.environ.get("BENCH_STEPS_PER_CALL", "") or 4)
    log("devices=%s batch=%d steps=%d" % (jax.devices(), batch, steps))
    log("model built + host-initialized; compiling train step")
    # host-gap attribution (mxnet_tpu_host_gap_seconds) for both phases
    telemetry.enable()

    # warmup/compile — timed per step so the cold start (the
    # ROADMAP AOT-compile item) is a parsed per-run metric with a
    # trajectory, not a stderr-only log line.  Step 0 carries the XLA
    # compile (or the persistent-cache load); later warmup steps are
    # steady-state and bound the residual trace/dispatch cost.
    warmup_step_secs = []
    t_w0 = time.perf_counter()
    for i in range(warmup):
        t_s = time.perf_counter()
        loss = trainer.step([x], y)
        jax.block_until_ready(loss)
        warmup_step_secs.append(round(time.perf_counter() - t_s, 3))
        log("warmup step %d done (loss=%.4f, %.1fs)"
            % (i, float(loss), warmup_step_secs[-1]))
    warmup_secs = time.perf_counter() - t_w0

    # phase 1 — synchronous per-step dispatch (the historical number:
    # the loop pays a loss host-sync every step under the default
    # non-finite policy)
    telemetry.reset()
    t0 = time.perf_counter()
    for i in range(steps):
        loss = trainer.step([x], y)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    ips_sync = batch * steps / dt
    gap_sync = _host_gap_p50()
    log("[sync]  %d steps in %.3fs (%.1f img/s)" % (steps, dt, ips_sync))

    # phase 2 — async dispatch + K-step fused loop (ISSUE 10): loss and
    # metric host reads move to the background fetch; K microbatch
    # steps run as one lax.scan program.  Warm one fused call first
    # (the scan executable is its own compile / AOT entry).
    trainer.configure_overlap(async_metrics=True, steps_per_call=k)
    fused_batch = [([x], y)] * k
    losses = trainer.step_many(fused_batch)
    jax.block_until_ready(losses)
    trainer.drain()
    telemetry.reset()
    calls = max(1, steps // k)
    t0 = time.perf_counter()
    for i in range(calls):
        losses = trainer.step_many(fused_batch)
    jax.block_until_ready(losses)
    trainer.drain()
    dt_async = time.perf_counter() - t0
    ips_async = batch * calls * k / dt_async
    gap_async = _host_gap_p50()
    # where did the milliseconds go, over the async (headline) phase:
    # the attribution every ledger row carries so perf_gate can name
    # the bucket that moved when the img/s number does
    breakdown = trainer.step_breakdown()
    if breakdown is not None:
        log("\n" + breakdown.describe())
    log("[async] %d steps (%d fused calls of %d) in %.3fs (%.1f img/s)"
        % (calls * k, calls, k, dt_async, ips_async))

    ips = ips_async  # headline: the overlapped path is the new default
    baseline = 364.0  # V100 fp16 train img/s @ bs128 (BASELINE.md)
    result = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips / baseline, 3),
        "warmup_seconds": round(warmup_secs, 2),
        "warmup_step_seconds": warmup_step_secs,
        # topology attribution (docs/sharding.md): {} / null =
        # single-device
        "mesh_shape": trainer.mesh_shape,
        "layout": trainer.layout_name,
        # host-overlap attribution (ISSUE 10): sync vs async+fused
        # throughput and the dispatch-to-dispatch host idle they imply
        "images_per_sec_sync": round(ips_sync, 2),
        "images_per_sec_async": round(ips_async, 2),
        "async_speedup": round(ips_async / ips_sync, 3) if ips_sync else
        None,
        "steps_per_call": k,
        "async_metrics": True,
        "host_gap_seconds": {
            "sync": round(gap_sync, 6) if gap_sync is not None else None,
            "async": round(gap_async, 6) if gap_async is not None
            else None},
        # precision attribution (docs/mixed_precision.md): the policy
        # the headline number was measured under, plus the loss-scale
        # endpoint state when the policy scales
        "dtype_policy": trainer.dtype_policy_tag,
        "loss_scale": trainer.loss_scale(),
        "loss_scale_backoffs": trainer.skipped_steps
        if trainer.dtype_policy is not None
        and trainer.dtype_policy.loss_scaling else None,
    }
    if os.environ.get("BENCH_DTYPE_COMPARE", "0") not in ("", "0"):
        result["dtype_compare"] = run_dtype_compare(
            ("f32", "bf16_mixed"), steps)
    if prewarm_info is not None:
        # cold = trace+compile paid by the prewarm subprocess (or
        # recorded in the store meta when it was already warm);
        # warm = this process's step 0, which deserialized instead
        # (BENCH_WARMUP=0 leaves no warm-start sample to report)
        result["cold_start_seconds"] = prewarm_info.get("cold_seconds")
        if warmup_step_secs:
            result["warm_start_seconds"] = warmup_step_secs[0]
    if breakdown is not None:
        result["attribution"] = breakdown.as_dict()
    from mxnet_tpu import perf_ledger

    for rec in ledger_records(result):
        perf_ledger.emit(rec)


if __name__ == "__main__":
    main()
