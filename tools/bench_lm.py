"""Benchmark: transformer-LM training throughput (tokens/sec + MFU).

The second hot-path profile next to bench.py's ResNet-50 (ROADMAP "New
workload"): a decoder-only LM (examples/transformer_lm.py) trained by
ShardedTrainer over a named dp x fsdp x tp mesh with a spec-rule layout
(docs/sharding.md).  Emits ONE ``BENCH {json}`` marker line on stdout
(a schema-versioned perf_ledger record, appended to the
MXNET_PERF_LEDGER run ledger when set) carrying
``tokens_per_sec``, ``mfu`` (model-FLOPs accounting over the PR 4 peak
gauge), and the ``mesh_shape``/``layout`` the number was measured under
— so the perf trajectory is attributable to topology.  Since ISSUE 10
the run measures BOTH dispatch modes — synchronous per-step and async
+ K-step fused loop — and reports ``tokens_per_sec_sync``/``_async``,
``async_speedup``, ``steps_per_call`` and the per-phase
``host_gap_seconds`` p50; ``--trace-out`` writes the unified chrome
trace that ``tools/autotune.py --lm`` folds into the fusion cost
table.

    # 8-virtual-device CPU harness, canonical LLM layout:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/bench_lm.py --mesh dp=2,fsdp=2,tp=2 --layout fsdp_tp

    # real chip (defaults scale up on accelerator backends):
    python tools/bench_lm.py --mesh fsdp=4,tp=2

Progress goes to stderr; stdout is the marked record line only.
"""
import argparse
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for p in (REPO, os.path.join(REPO, "examples")):
    if p not in sys.path:
        sys.path.insert(0, p)

_T0 = time.time()


def log(msg):
    print("[bench_lm %6.1fs] %s" % (time.time() - _T0, msg),
          file=sys.stderr, flush=True)


def ledger_records(result):
    """perf_ledger record(s) for one bench_lm run: classic fields stay
    top-level, topology/precision ALSO stamp provenance (the schema
    guard test calls this with a canned result)."""
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


def build_lm_trainer(mesh=None, layout=None, vocab=None, d_model=None,
                     n_heads=None, n_layers=None, seq=None, batch=None,
                     optimizer="adam", dtype_policy=None):
    """The LM benchmark-of-record configuration, shared with the tier-1
    smoke test (tests/test_sharding_layouts.py) so the committed BENCH
    numbers describe the exact program the suite guards.

    Returns (trainer, tokens, labels, cfg_dict)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from transformer_lm import TransformerLM, lm_loss_fn

    on_tpu = any(d.platform != "cpu" for d in jax.devices())
    # accelerator defaults vs CPU smoke defaults (bench.py discipline:
    # the CPU harness proves the program, the chip proves the number)
    vocab = vocab or (32000 if on_tpu else 256)
    d_model = d_model or (512 if on_tpu else 64)
    n_heads = n_heads or (8 if on_tpu else 4)
    n_layers = n_layers or (8 if on_tpu else 2)
    seq = seq or (512 if on_tpu else 32)
    batch = batch or (32 if on_tpu else 8)

    # precision: explicit dtype_policy= wins; default is the mixed
    # recipe on the chip (supersedes the old blanket bf16 cast, which
    # also bf16-rounded the f32 token-id carriers) and f32 on CPU
    if dtype_policy is None:
        dtype_policy = os.environ.get("BENCH_DTYPE_POLICY") or             ("bf16_mixed" if on_tpu else None)
    lm = TransformerLM(vocab_size=vocab, d_model=d_model, n_heads=n_heads,
                       n_layers=n_layers, max_len=max(seq, 64))
    lm.initialize(mx.init.Xavier())
    trainer = parallel.ShardedTrainer(
        lm, lm_loss_fn(vocab), mesh=mesh, layout=layout,
        optimizer=optimizer, optimizer_params={"learning_rate": 1e-3},
        dtype_policy=dtype_policy)
    rng = np.random.RandomState(0)
    tokens = nd.array(rng.randint(0, vocab, (batch, seq))
                      .astype(np.float32))
    labels = nd.array(rng.randint(0, vocab, (batch, seq))
                      .astype(np.float32))
    cfg = dict(vocab=vocab, d_model=d_model, n_heads=n_heads,
               n_layers=n_layers, seq=seq, batch=batch, on_tpu=on_tpu,
               flops_per_token=lm.flops_per_token(seq_len=seq))
    return trainer, tokens, labels, cfg


def run(mesh=None, layout=None, steps=20, warmup=2, steps_per_call=None,
        trace_out=None, dtype_compare=False, **model_kw):
    import jax

    from mxnet_tpu import telemetry, tracing

    telemetry.enable()  # MFU gauge + collective/state-bytes accounting
    if trace_out:
        # unified chrome trace of the measured run: the attention/
        # matmul profile tools/autotune.py --lm folds into the fusion
        # cost table (same artifact as tracing.export_trace)
        tracing.enable()
        from mxnet_tpu import profiler

        profiler.set_config(aggregate_stats=True)
    trainer, tokens, labels, cfg = build_lm_trainer(
        mesh=mesh, layout=layout, **model_kw)
    k = int(steps_per_call) if steps_per_call else \
        (4 if cfg["on_tpu"] else 2)
    if not cfg["on_tpu"]:
        # the LM smoke model is ms-scale per step: 12 steps keep the
        # sync-vs-async A/B above the noise floor without moving the
        # suite budget (bench.py's ResNet stays at 4)
        steps = min(steps, 12)
        warmup = min(warmup, 1)
    log("devices=%d mesh=%s layout=%s model=%s"
        % (len(jax.devices()), trainer.mesh_shape, trainer.layout_name,
           {k_: cfg[k_] for k_ in ("vocab", "d_model", "n_heads",
                                   "n_layers", "seq", "batch")}))
    xs, ys = trainer.shard_batch(tokens, labels)

    warmup_step_secs = []
    for i in range(max(warmup, 1)):
        t_s = time.perf_counter()
        loss = trainer.step([xs], ys)
        jax.block_until_ready(loss)
        warmup_step_secs.append(round(time.perf_counter() - t_s, 3))
        log("warmup step %d done (loss=%.4f, %.1fs)"
            % (i, float(loss), warmup_step_secs[-1]))

    # phase 1 — synchronous per-step dispatch (historical semantics)
    telemetry.reset()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step([xs], ys)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    gap_sync = telemetry.HOST_GAP_SECONDS.quantile(0.5, loop="sharded")
    log("[sync] %d steps in %.3fs (loss=%.4f)" % (steps, dt, float(loss)))

    # phase 2 — async dispatch + K-step fused loop (ISSUE 10)
    trainer.configure_overlap(async_metrics=True, steps_per_call=k)
    fused = [([xs], ys)] * k
    losses = trainer.step_many(fused)
    jax.block_until_ready(losses)
    trainer.drain()
    telemetry.reset()
    calls = max(1, steps // k)
    t0 = time.perf_counter()
    for _ in range(calls):
        losses = trainer.step_many(fused)
    jax.block_until_ready(losses)
    trainer.drain()
    dt_async = time.perf_counter() - t0
    gap_async = telemetry.HOST_GAP_SECONDS.quantile(0.5, loop="sharded")
    # step-time attribution over the async (headline) phase — rides
    # the BENCH record so perf_gate can name the moving bucket
    breakdown = trainer.step_breakdown()
    if breakdown is not None:
        log("\n" + breakdown.describe())
    log("[async] %d steps (%d fused calls of %d) in %.3fs"
        % (calls * k, calls, k, dt_async))

    tokens_per_step = cfg["batch"] * cfg["seq"]
    tps_sync = tokens_per_step * steps / dt
    tps = tokens_per_step * calls * k / dt_async
    # MFU from the 6N analytic accounting against the published peak of
    # this device kind times the devices the step ran on; a device the
    # table does not list (the CPU harness) has no peak and gets no MFU
    peak = telemetry.peak_flops()
    step_secs = dt_async / (calls * k)
    model_flops = cfg["flops_per_token"] * tokens_per_step
    n_dev = trainer.mesh.devices.size if trainer.mesh is not None else 1
    mfu = round(model_flops / step_secs / (peak * n_dev), 4) \
        if peak else None
    result = {
        "metric": "transformer_lm_train_tokens_per_sec",
        "value": round(tps, 2),
        "unit": "tokens/sec",
        "tokens_per_sec": round(tps, 2),
        "tokens_per_sec_sync": round(tps_sync, 2),
        "tokens_per_sec_async": round(tps, 2),
        "async_speedup": round(tps / tps_sync, 3) if tps_sync else None,
        "steps_per_call": k,
        "async_metrics": True,
        "host_gap_seconds": {
            "sync": round(gap_sync, 6) if gap_sync is not None else None,
            "async": round(gap_async, 6) if gap_async is not None
            else None},
        "mfu": mfu,
        "model_flops_per_step": model_flops,
        "mesh_shape": trainer.mesh_shape,
        "layout": trainer.layout_name,
        "batch": cfg["batch"],
        "seq_len": cfg["seq"],
        "warmup_step_seconds": warmup_step_secs,
        # precision attribution (docs/mixed_precision.md)
        "dtype_policy": trainer.dtype_policy_tag,
        "loss_scale": trainer.loss_scale(),
        "loss_scale_backoffs": trainer.skipped_steps
        if trainer.dtype_policy is not None
        and trainer.dtype_policy.loss_scaling else None,
    }
    if breakdown is not None:
        result["attribution"] = breakdown.as_dict()
    if dtype_compare:
        # one short synchronous phase per policy on a fresh trainer:
        # the f32-vs-bf16 A/B the on-chip payoff sweep flips on
        comp = {}
        mk = {k: v for k, v in model_kw.items() if k != "dtype_policy"}
        for pol in ("f32", "bf16_mixed"):
            t2, tok2, lab2, c2 = build_lm_trainer(
                mesh=mesh, layout=layout, dtype_policy=pol, **mk)
            x2, y2 = t2.shard_batch(tok2, lab2)
            loss2 = t2.step([x2], y2)
            jax.block_until_ready(loss2)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss2 = t2.step([x2], y2)
            jax.block_until_ready(loss2)
            dt2 = time.perf_counter() - t0
            t2.drain()
            comp[t2.dtype_policy_tag] = {
                "tokens_per_sec": round(
                    c2["batch"] * c2["seq"] * steps / dt2, 2),
                "loss_scale": t2.loss_scale(),
            }
            log("[dtype %s] %d steps in %.3fs"
                % (t2.dtype_policy_tag, steps, dt2))
        result["dtype_compare"] = comp
    if trace_out:
        tracing.export_trace(trace_out)
        log("unified trace written to %s" % trace_out)
    return result


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--mesh", default=None,
                   help="mesh spec, e.g. dp=2,fsdp=2,tp=2 (default: "
                        "MXNET_MESH, else single device)")
    p.add_argument("--layout", default=None,
                   help="layout name (default: MXNET_LAYOUT, else the "
                        "canonical layout for the mesh axes)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--steps-per-call", type=int, default=None,
                   help="K for the fused-loop phase (default: 4 on "
                        "TPU, 2 on the CPU harness)")
    p.add_argument("--dtype-policy", default=None,
                   help="mixed-precision dtype policy for the measured "
                        "trainer (f32/bf16_mixed/bf16_pure; default: "
                        "BENCH_DTYPE_POLICY, else bf16_mixed on TPU)")
    p.add_argument("--dtype-compare", action="store_true",
                   help="also measure one short f32 AND bf16_mixed "
                        "phase (fresh trainers) and emit dtype_compare")
    p.add_argument("--trace-out", default=None,
                   help="write the measured run's unified chrome trace "
                        "here (tools/autotune.py --lm consumes it)")
    p.add_argument("--vocab", type=int, default=None)
    p.add_argument("--d-model", type=int, default=None)
    p.add_argument("--n-heads", type=int, default=None)
    p.add_argument("--n-layers", type=int, default=None)
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--batch", type=int, default=None)
    a = p.parse_args(argv)
    result = run(mesh=a.mesh, layout=a.layout, steps=a.steps,
                 warmup=a.warmup, steps_per_call=a.steps_per_call,
                 trace_out=a.trace_out, dtype_compare=a.dtype_compare,
                 vocab=a.vocab, d_model=a.d_model,
                 n_heads=a.n_heads, n_layers=a.n_layers, seq=a.seq,
                 batch=a.batch, dtype_policy=a.dtype_policy)
    from mxnet_tpu import perf_ledger

    for rec in ledger_records(result):
        perf_ledger.emit(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
