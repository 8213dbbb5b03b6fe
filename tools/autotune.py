"""Trace-guided fusion autotuner: measure fused-vs-unfused per shape,
persist the shape-keyed cost table `symbol/fusion.py` consults at bind.

Tuning replays the PR 5 unified timeline to rank where the time and
HBM traffic actually go, then micro-benchmarks every registered fusion
pattern's canonical chain (``FusionPattern.bench_builder``) fused vs
unfused per input shape on the *current* backend, and writes the table
atomically (``checkpoint.atomic_write``)::

    python tools/autotune.py --out docs/fusion_cost_cpu.json \
        [--trace trace.json] [--patterns add_act,layer_norm_fast] \
        [--shapes 64x1024 256x4096] [--iters 20] [--lm]

``--lm`` additionally profiles the transformer-LM bench model
(tools/bench_lm.py) live: its hot-op timeline ranking lands in the
table meta and its attention/matmul operand shapes join every
pattern's microbench — the second hot-path profile next to the
ResNet-50 trace (ROADMAP sharding follow-on).

``--trace`` takes a ``tracing.export_trace`` / ``profiler.dump()`` /
flight-recorder artifact; its op-timeline ranking (total time + est.
HBM bytes from the XLA cost table — the same view as
``trace_view.py --top-ops``) is printed and embedded in the table meta
so a tuning run documents *why* those rewrites matter on that run.

Validation mode mirrors telemetry_dump's behavior — nonzero exit on
malformed input, loud but zero on stale entries::

    python tools/autotune.py --check table.json [--max-age-days 90]
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))  # trace_view (shared ranking)


def log(msg):
    print("[autotune] %s" % msg, file=sys.stderr, flush=True)


def rank_trace_ops(path, top=10):
    """(name, total_ms, calls, est_bytes|None) rows from a unified
    chrome-trace export, most expensive first — the exact
    ``trace_view.py --top-ops`` ranking (shared aggregation)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError as e:
        raise SystemExit("%s: cannot read (%s)" % (path, e))
    except ValueError as e:
        raise SystemExit("%s: malformed JSON (%s)" % (path, e))
    if not isinstance(data, dict) or "traceEvents" not in data:
        raise SystemExit("%s: not a chrome trace (no 'traceEvents')" % path)
    import trace_view

    return trace_view.aggregate_op_costs(data)[:top]


def run_check(path, max_age_days):
    from mxnet_tpu import fusion_cost as fc

    try:
        with open(path) as f:
            data = json.load(f)
    except OSError as e:
        print("%s: cannot read (%s)" % (path, e), file=sys.stderr)
        return 1
    except ValueError as e:
        print("%s: malformed JSON (%s)" % (path, e), file=sys.stderr)
        return 1
    problems, stale = fc.validate_table(data, max_age_days=max_age_days)
    entries = data.get("entries") if isinstance(data, dict) else None
    n = len(entries) if isinstance(entries, dict) else 0
    print("%s: %d entries, backend=%s, created=%s"
          % (path, n, data.get("backend", "?") if isinstance(data, dict)
             else "?",
             data.get("created", "?") if isinstance(data, dict) else "?"))
    for msg in stale:
        print("STALE: %s" % msg)
    for msg in problems:
        print("MALFORMED: %s" % msg, file=sys.stderr)
    return 1 if problems else 0


def profile_lm(args):
    """Run the transformer-LM bench model (tools/bench_lm.py) for a few
    steps under the unified trace and return its hot-op ranking plus
    the LM's matmul/attention operand shapes — the second hot-path
    profile the cost-table machinery has been waiting for (ROADMAP).
    The shapes feed every pattern's microbench next to its canonical
    ``bench_shapes``, so the table carries measured fused-vs-unfused
    numbers at the sizes the LM actually runs."""
    import tempfile

    import jax

    import bench_lm
    from mxnet_tpu import profiler, telemetry, tracing

    tracing.enable()
    profiler.set_config(aggregate_stats=True)
    telemetry.enable()
    log("profiling transformer-LM bench model (%d steps, mesh=%s)"
        % (args.lm_steps, args.lm_mesh or "single-device"))
    trainer, tokens, labels, cfg = bench_lm.build_lm_trainer(
        mesh=args.lm_mesh)
    xs, ys = trainer.shard_batch(tokens, labels)
    loss = None
    for _ in range(max(1, args.lm_steps)):
        loss = trainer.step([xs], ys)
    jax.block_until_ready(loss)
    path = os.path.join(tempfile.mkdtemp(prefix="mxnet_tpu_lm_"),
                        "lm_trace.json")
    tracing.export_trace(path)
    hot = rank_trace_ops(path)
    B, S, D = cfg["batch"], cfg["seq"], cfg["d_model"]
    # the LM's three dominant GEMM operand shapes: attention/residual
    # projections (B*S x D), the 4x MLP hidden (B*S x 4D), and the
    # vocab head (B*S x V)
    shapes = [(B * S, D), (B * S, 4 * D), (B * S, cfg["vocab"])]
    meta = {"model": {k: cfg[k] for k in ("vocab", "d_model", "n_heads",
                                          "n_layers", "seq", "batch")},
            "mesh": args.lm_mesh, "steps": args.lm_steps,
            "shapes": [list(s) for s in shapes],
            "trace": path,
            "hot_ops": [{"name": n, "total_ms": round(ms, 3), "calls": c,
                         "est_hbm_bytes": est}
                        for n, ms, c, est in hot]}
    return meta, hot, shapes


def profile_decode(args):
    """Run the KV-cache decode engine (tools/bench_decode.py model) for
    a few steps under the unified trace and return its hot-op ranking
    plus the SMALL-BATCH, cache-length-keyed operand shapes decode
    actually runs — token-step GEMMs are (slots x d_model)-thin and
    the attention softmax·V chain is keyed by the cache length, shapes
    the train-profile corpus never sees."""
    import tempfile

    import jax

    import bench_decode
    from mxnet_tpu import generate, profiler, telemetry, tracing

    tracing.enable()
    profiler.set_config(aggregate_stats=True)
    telemetry.enable()
    log("profiling KV-cache decode engine (%d steps)"
        % args.decode_steps)
    lm, cfg = bench_decode.build_lm(max_len=args.decode_cache_len)
    eng = generate.PagedGenerationEngine(
        lm, slots=args.decode_slots, cache_len=args.decode_cache_len,
        dtype_policy=args.dtype_policy)
    import numpy as np

    rng = np.random.RandomState(0)
    for s in range(min(eng.slots, 4)):
        eng.admit(rng.randint(0, cfg["vocab"], 8))
    out = None
    for _ in range(max(1, args.decode_steps)):
        out = eng.decode_step()
    jax.block_until_ready(eng._pool_k)
    del out
    path = os.path.join(tempfile.mkdtemp(prefix="mxnet_tpu_decode_"),
                        "decode_trace.json")
    tracing.export_trace(path)
    hot = rank_trace_ops(path)
    B, D, V = eng.slots, cfg["d_model"], cfg["vocab"]
    H, S = cfg["n_heads"], eng.cache_len
    # decode's dominant GEMM operand shapes: the (slots x D) token-step
    # projections/FFN/head, and the (slots*heads x cache_len) attention
    # score/value rows the softmax·V fusion would act on
    shapes = [(B, D), (B, 4 * D), (B, V), (B * H, S)]
    meta = {"model": {k: cfg[k] for k in ("vocab", "d_model", "n_heads",
                                          "n_layers")},
            "slots": B, "cache_len": S, "steps": args.decode_steps,
            "shapes": [list(s) for s in shapes],
            "trace": path,
            "hot_ops": [{"name": n, "total_ms": round(ms, 3), "calls": c,
                         "est_hbm_bytes": est}
                        for n, ms, c, est in hot]}
    return meta, hot, shapes


def run_migrate(path, max_age_days):
    """Rewrite a pre-dtype (legacy) table in place: every key gains the
    f32 tag its measurements were taken under, then the migrated table
    is re-validated."""
    from mxnet_tpu import fusion_cost as fc

    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        print("%s: cannot read (%s)" % (path, e), file=sys.stderr)
        return 1
    data, n = fc.migrate_legacy_table(data)
    data.setdefault("dtype_policy", "f32")
    fc.save_table(path, data)
    log("migrated %d legacy key(s) in %s (assumed f32)" % (n, path))
    return run_check(path, max_age_days)


def run_tune(args):
    import mxnet_tpu  # noqa: F401  (backend init)
    import jax

    from mxnet_tpu import dtype_policy as dtp
    from mxnet_tpu import fusion_cost as fc
    from mxnet_tpu.symbol import fusion as F

    # measurement precision (--dtype-policy): operands bound in the
    # policy's compute dtype, the policy tag stamped into the table
    # meta, and every emitted key carrying the dtype tag — bf16
    # measurements never reuse (or pollute) f32 entries
    policy = dtp.resolve_policy(args.dtype_policy)
    bench_dtype = str(policy.compute_dtype) if policy is not None         else "float32"

    hot = None
    if args.trace:
        hot = rank_trace_ops(args.trace)
        log("timeline ranking from %s (total ms | calls | est HBM bytes):"
            % args.trace)
        for name, ms, n, est in hot:
            log("  %-40s %10.3f %6d %s"
                % (name, ms, n, "%12.0f" % est if est else "           -"))

    lm_shapes = []
    lm_meta = None
    if args.lm:
        lm_meta, lm_hot, lm_shapes = profile_lm(args)
        log("LM timeline ranking (total ms | calls | est HBM bytes):")
        for name, ms, n, est in lm_hot:
            log("  %-40s %10.3f %6d %s"
                % (name, ms, n, "%12.0f" % est if est else "           -"))
    decode_meta = None
    if args.decode:
        decode_meta, dec_hot, dec_shapes = profile_decode(args)
        log("decode timeline ranking (total ms | calls | est HBM "
            "bytes):")
        for name, ms, n, est in dec_hot:
            log("  %-40s %10.3f %6d %s"
                % (name, ms, n, "%12.0f" % est if est else "           -"))
        for s in dec_shapes:
            if s not in lm_shapes:
                lm_shapes.append(s)

    names = ([p for p in args.patterns.split(",") if p]
             if args.patterns else F.list_patterns())
    shapes = None
    if args.shapes:
        shapes = [tuple(int(d) for d in s.lower().split("x"))
                  for s in args.shapes]

    table = fc.CostTable(meta={
        "version": fc.TABLE_VERSION,
        "backend": jax.default_backend(),
        "devices": [str(d) for d in jax.devices()],
        "jax": jax.__version__,
        "created": __import__("datetime").datetime.now(
            __import__("datetime").timezone.utc).isoformat(
                timespec="seconds"),
        "iters": args.iters,
        "dtype_policy": dtp.policy_tag(policy),
        "bench_dtype": bench_dtype,
    })
    if hot:
        table.meta["trace_hot_ops"] = [
            {"name": n, "total_ms": round(ms, 3), "calls": c,
             "est_hbm_bytes": est} for n, ms, c, est in hot]
    if lm_meta is not None:
        table.meta["lm_profile"] = lm_meta
    if decode_meta is not None:
        table.meta["decode_profile"] = decode_meta

    for name in names:
        pattern = F.get_pattern(name)
        if pattern.bench_builder is None:
            log("skip %s: no bench_builder" % name)
            continue
        pattern_shapes = list(shapes or pattern.bench_shapes)
        # the LM's rank-2 GEMM shapes ride along only where the
        # pattern's own bench chain is rank-2 (matmul/elementwise);
        # conv patterns expect NCHW and would just trace-and-skip
        if all(len(s) == 2 for s in pattern.bench_shapes):
            for s in lm_shapes:
                if s not in pattern_shapes:
                    pattern_shapes.append(s)
        for shape in pattern_shapes:
            if len(shape) < 2:
                log("skip %s @ %s: chain needs >=2 dims" % (name, shape))
                continue
            try:
                res = F.microbench(name, shape, iters=args.iters,
                                   grad=not args.no_grad,
                                   dtype=bench_dtype)
            except Exception as e:
                log("skip %s @ %s: %s" % (name, shape, e))
                continue
            if not res["fired"]:
                log("WARNING: pattern %s did not match its own bench "
                    "chain at %s" % (name, shape))
                continue
            extra = {"shape": list(shape),
                     "fused_fwd_ms": round(res["fused_fwd_ms"], 6),
                     "unfused_fwd_ms": round(res["unfused_fwd_ms"], 6),
                     "speedup_infer": round(res["speedup_infer"], 4)}
            fused = res.get("fused_train_ms", res["fused_fwd_ms"])
            unfused = res.get("unfused_train_ms", res["unfused_fwd_ms"])
            e = table.add(res["key"], fused, unfused, **extra)
            log("%-48s fused %8.3f ms  unfused %8.3f ms  speedup %.2fx"
                % (res["key"], fused, unfused, e["speedup"]))

    fc.save_table(args.out, table)
    fires = sum(1 for e in table.entries.values()
                if e["speedup"] >= fc.SPEEDUP_FIRE)
    slower = sum(1 for e in table.entries.values()
                 if e["speedup"] < fc.SPEEDUP_KEEP)
    log("wrote %s: %d entries (%d fire >=%.2fx, %d measured slower -> "
        "suppressed)" % (args.out, len(table.entries), fires,
                         fc.SPEEDUP_FIRE, slower))
    log("activate with MXNET_FUSION_TUNE=%s (or "
        "mxnet_tpu.config.fusion_cost_table(%r))" % (args.out, args.out))
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Measure fused-vs-unfused per shape and write the "
                    "fusion cost table (or --check an existing one)")
    p.add_argument("--out", help="cost-table JSON to write (tuning mode)")
    p.add_argument("--check", metavar="TABLE",
                   help="validate a cost-table JSON instead of tuning")
    p.add_argument("--migrate", metavar="TABLE",
                   help="rewrite a pre-dtype (legacy) table in place: "
                        "keys gain the f32 tag, then the table is "
                        "re-validated")
    p.add_argument("--dtype-policy", default=None,
                   help="measure under this dtype policy's compute "
                        "dtype (f32/bf16_mixed/bf16_pure; default: "
                        "MXNET_DTYPE_POLICY) and stamp the tag into "
                        "the table meta")
    p.add_argument("--trace", help="chrome-trace export to rank hot ops "
                                   "from (tracing.export_trace output)")
    p.add_argument("--lm", action="store_true",
                   help="profile the transformer-LM bench model "
                        "(tools/bench_lm.py) live and fold its hot-op "
                        "ranking + matmul/attention operand shapes into "
                        "the tuning run")
    p.add_argument("--lm-steps", type=int, default=2,
                   help="--lm: traced LM steps (default 2)")
    p.add_argument("--lm-mesh", default=None,
                   help="--lm: mesh spec for the profiled LM trainer "
                        "(default: MXNET_MESH, else single device)")
    p.add_argument("--decode", action="store_true",
                   help="profile the KV-cache decode engine "
                        "(mxnet_tpu/generate.py via tools/"
                        "bench_decode.py's model) live and fold its "
                        "small-batch, cache-length-keyed hot shapes "
                        "into the tuning run — the shapes token decode "
                        "actually runs")
    p.add_argument("--decode-steps", type=int, default=4,
                   help="--decode: traced decode steps (default 4)")
    p.add_argument("--decode-slots", type=int, default=8,
                   help="--decode: engine decode slots (default 8)")
    p.add_argument("--decode-cache-len", type=int, default=128,
                   help="--decode: KV cache length profiled (default "
                        "128)")
    p.add_argument("--patterns", help="comma list (default: all "
                                      "registered)")
    p.add_argument("--shapes", nargs="*",
                   help="shapes like 64x1024 (default: per-pattern "
                        "bench_shapes)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--no-grad", action="store_true",
                   help="time forward only (serving-shaped tables)")
    p.add_argument("--max-age-days", type=float, default=90.0,
                   help="--check: flag entries older than this")
    args = p.parse_args(argv)
    if args.check:
        return run_check(args.check, args.max_age_days)
    if args.migrate:
        return run_migrate(args.migrate, args.max_age_days)
    if not args.out:
        p.error("--out is required in tuning mode (or use --check)")
    return run_tune(args)


if __name__ == "__main__":
    sys.exit(main())
