"""Pre-warm the AOT executable store: compile and persist every
executable a workload needs BEFORE rollout, so a restarting trainer or
a freshly spawned serving replica starts at warm-cache speed.

Given a model spec (the registry below) — or the signature manifest the
trainer/Predictor append to on their first compile — this builds the
exact callables the runtime jits and runs their ``prewarm`` entry
points through the store (``mxnet_tpu.aot``)::

    python tools/prewarm.py --model bench_resnet50 [--store DIR]
    python tools/prewarm.py --manifest [--store DIR]
    python tools/prewarm.py --check [--store DIR] [--max-age-days 90]

``--check`` mirrors ``autotune.py --check``: it validates the store
(schema, payload digests, environment staleness, manifest) and exits
nonzero on a malformed store — CI-friendly.  ``--json`` emits one
machine-parsable summary line on stdout (``bench.py BENCH_PREWARM=1``
consumes it to report ``cold_start_seconds``).

Model specs are intentionally the *same builders the benchmarks use*
(``bench.build_trainer``), so the content-hash keys match what the real
process looks up.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))  # quantize_model (int8 spec)


def log(msg):
    print("[prewarm] %s" % msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# model-spec registry: name -> builder(store, batch) yielding info dicts
# ---------------------------------------------------------------------------

MODELS = {}


def model(name, doc):
    def deco(fn):
        fn.doc = doc
        MODELS[name] = fn
        return fn
    return deco


@model("tiny_mlp", "2-layer MLP trainer + predictor at toy shapes "
                   "(seconds; exercises every path — used by the tests)")
def _tiny_mlp(store, batch=None, dtype_policy=None):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon, parallel
    from mxnet_tpu.serving import Predictor

    batch = int(batch or 4)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu"))
        net.add(gluon.nn.Dense(2))
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = parallel.ShardedTrainer(
        net, lambda o, l: loss_fn(o, l), mesh=None, optimizer="sgd",
        aot=store, aot_spec="tiny_mlp", dtype_policy=dtype_policy)
    x = nd.array(np.zeros((batch, 16), np.float32))
    y = nd.array(np.zeros((batch,), np.float32))
    yield trainer.prewarm([x], y)
    pred, _ = Predictor.from_block(net, np.zeros((batch, 16), np.float32),
                                   chain=2, aot=store,
                                   aot_spec="tiny_mlp",
                                   dtype_policy=dtype_policy)
    for info in pred.prewarm():
        yield info


@model("bench_resnet50", "the bench.py trainer-of-record (ResNet-50 "
                         "bf16/fp32 fused step; BENCH_BATCH honored)")
def _bench_resnet50(store, batch=None, dtype_policy=None):
    import bench

    trainer, x, y, _b, _on_tpu = bench.build_trainer(
        batch=int(batch) if batch else None, aot=store,
        aot_spec="bench_resnet50", dtype_policy=dtype_policy)
    yield trainer.prewarm([x], y)


@model("resnet18_serving", "ResNet-18 serving replica (Predictor "
                           "chain=2) — the CPU-measurable cold-start "
                           "probe for the serving tier")
def _resnet18_serving(store, batch=None, dtype_policy=None):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.serving import Predictor

    batch = int(batch or 8)
    net = vision.resnet18_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    x = np.zeros((batch, 3, 224, 224), np.float32)
    pred, _ = Predictor.from_block(net, x, chain=2, aot=store,
                                   aot_spec="resnet18_serving",
                                   dtype_policy=dtype_policy)
    for info in pred.prewarm():
        yield info


@model("resnet50_serving", "the serving tier of record (perf_notes "
                           "'Small-batch serving'): ResNet-50 bs32 "
                           "uint8 input, chain=8, device-side top-5")
def _resnet50_serving(store, batch=None, dtype_policy=None):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.serving import Predictor, uint8_normalizer

    import jax

    def top5(logits):
        _v, i = jax.lax.top_k(logits, 5)
        return i

    batch = int(batch or 32)
    net = vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    x = np.zeros((batch, 3, 224, 224), np.uint8)
    on_tpu = any(d.platform != "cpu" for d in jax.devices())
    prep = uint8_normalizer() if on_tpu \
        else uint8_normalizer(dtype="float32")
    pred, _ = Predictor.from_block(
        net, x, chain=8, preprocess=prep,
        postprocess=top5, aot=store, aot_spec="resnet50_serving",
        dtype_policy=dtype_policy)
    for info in pred.prewarm():
        yield info


@model("resnet50_serving_int8", "int8 variant of resnet50_serving: "
                                "accuracy-gated quantize (BN fold + "
                                "int8 rewrite) then prewarm the "
                                "quantized executables — warm-pool "
                                "replicas come up already quantized")
def _resnet50_serving_int8(store, batch=None, dtype_policy=None):
    import numpy as np

    import quantize_model as qm
    from mxnet_tpu.contrib import quantization as q
    from mxnet_tpu.serving import Predictor

    art = os.path.join(store.path, "quantized", "resnet50_serving_int8")
    try:
        # one load serves both validation and serving (a ResNet-50
        # params blob is too big to deserialize twice on the cold path)
        qsym, qargs, qaux, meta = q.load_artifact(art)
    except Exception:
        # no committed artifact (or a damaged one): rebuild through the
        # gate.  A refused gate aborts the spec — a degraded int8
        # replica must never be prewarmed into the fleet.
        log("building gated int8 artifact at %s" % art)
        sym, data_shape = qm.build_resnet50()
        if batch:
            data_shape = (int(batch),) + tuple(data_shape[1:])
        arg_p, aux_p = qm.init_params(sym, data_shape)
        calib = np.random.RandomState(1).rand(*data_shape) \
            .astype(np.float32)
        qsym, qargs, qaux, report = q.quantize_serving_artifact(
            sym, arg_p, aux_p, calib, logger=log)
        q.save_artifact(art, qsym, qargs, qaux, report)
        meta = dict(report)
    pred = Predictor.from_symbol(
        qsym, qargs, qaux, data_name=meta.get("data_name", "data"),
        chain=8, batch_shape=tuple(meta["data_shape"]),
        batch_dtype=meta.get("data_dtype", "float32"), aot=store,
        aot_spec="resnet50_serving_int8", aot_policy_tag="int8")
    for info in pred.prewarm():
        yield info


@model("lm_decode", "transformer-LM generation tier: the decode "
                    "engine's chunk family (prefill chunk, decode, "
                    "speculative verify) — one manifest row per "
                    "signature; warms everything a decode replica "
                    "needs at spawn")
def _lm_decode(store, batch=None, dtype_policy=None):
    import mxnet_tpu as mx
    from mxnet_tpu import generate

    ex_dir = os.path.join(REPO, "examples")
    if ex_dir not in sys.path:
        sys.path.insert(0, ex_dir)
    from transformer_lm import TransformerLM

    # the bench_decode.py CPU-smoke decode configuration (the chip
    # spec passes --batch to widen slots); cache_len kept modest so
    # the prewarm stays seconds-level
    slots = int(batch or 4)
    mx.random.seed(0)
    lm = TransformerLM(vocab_size=256, d_model=64, n_heads=4,
                       n_layers=2, max_len=64)
    lm.initialize(mx.init.Xavier())
    # the replica's three chunk-family signatures: a (1, chunk)
    # prefill chunk, the (slots, 1) decode step, and the (slots, K+1)
    # speculative verify
    eng = generate.PagedGenerationEngine(
        lm, slots=slots, cache_len=64, page_size=16, prefill_chunk=16,
        spec_k=2, aot=store, aot_spec="lm_decode",
        dtype_policy=dtype_policy,
        sampling=generate.SamplingConfig(greedy=True))
    for info in eng.prewarm():
        yield info


# ---------------------------------------------------------------------------
# modes
# ---------------------------------------------------------------------------


def _resolve_store(path):
    from mxnet_tpu import aot

    if path:
        return aot.AOTStore(path)
    return aot.default_store()


def _run_specs(store, specs, batch, dtype_policy=None):
    infos = []
    for name in specs:
        if name not in MODELS:
            raise SystemExit(
                "unknown model spec %r; registered: %s"
                % (name, ", ".join(sorted(MODELS))))
        log("building %s%s ..." % (name, " [dtype_policy=%s]"
                                   % dtype_policy if dtype_policy else ""))
        t0 = time.perf_counter()
        for info in MODELS[name](store, batch=batch,
                                 dtype_policy=dtype_policy):
            info = dict(info or {})
            info["spec"] = name
            infos.append(info)
            log("  %-28s %-9s %6.1fs%s"
                % (info.get("label", "?"), info.get("status", "?"),
                   info.get("seconds", 0.0),
                   "  (compile %.1fs)" % info["compile_seconds"]
                   if info.get("compile_seconds") else ""))
        log("%s done in %.1fs" % (name, time.perf_counter() - t0))
    return infos


def run_prewarm(args):
    store = _resolve_store(args.store)
    log("store: %s" % store.path)
    t0 = time.perf_counter()
    infos = _run_specs(store, args.model, args.batch,
                       args.dtype_policy)
    total = time.perf_counter() - t0
    compiled = [i for i in infos if i.get("status") == "compiled"]
    hits = [i for i in infos if i.get("status") == "hit"]
    fallbacks = [i for i in infos
                 if i.get("status") in ("fallback", "disabled")]
    # the cold cost this store now absorbs: measured compile seconds
    # for fresh entries, recorded compile seconds for ones already
    # present — so warm reruns still report what cold would have cost
    cold = sum(i.get("compile_seconds") or 0.0 for i in infos)
    log("%d executables: %d compiled, %d already warm, %d fallbacks "
        "(%.1fs total)" % (len(infos), len(compiled), len(hits),
                           len(fallbacks), total))
    if fallbacks:
        log("WARNING: %d executable(s) could not use the AOT store"
            % len(fallbacks))
    if args.json:
        print(json.dumps({
            "store": store.path,
            "entries": infos,
            "compiled": len(compiled),
            "hits": len(hits),
            "fallbacks": len(fallbacks),
            "cold_seconds": round(cold, 2),
            "total_seconds": round(total, 2),
        }))
    return 0 if not fallbacks else 2


def run_manifest(args):
    store = _resolve_store(args.store)
    entries, problems = store.manifest_entries()
    for msg in problems:
        print("MALFORMED: %s" % msg, file=sys.stderr)
    if not entries and not problems:
        log("manifest at %s is empty — run the workload once with "
            "MXNET_AOT=1 (or prewarm --model) to record signatures"
            % store.manifest_path())
    specs, unknown = [], []
    # rebuild each (spec, dtype_policy) pair the manifest recorded: the
    # policy tag is part of the AOT key, so replaying a bf16_mixed row
    # under f32 would compile the WRONG executable and leave the
    # promised one cold.  An explicit --dtype-policy overrides all rows
    # (operator intent); the int8 spec carries its policy in the graph.
    groups = []
    for e in entries:
        spec = e.get("spec")
        if spec and spec in MODELS:
            pol = args.dtype_policy or e.get("dtype_policy") or None
            if pol in ("f32", "int8"):
                pol = None
            if spec not in specs:
                specs.append(spec)
            if (spec, pol) not in groups:
                groups.append((spec, pol))
        else:
            unknown.append(e)
    for e in unknown:
        log("skip manifest entry %s (%s): spec %r is not in this "
            "CLI's registry — prewarm it from its own entry point"
            % (e.get("key", "?")[:12], e.get("label"), e.get("spec")))
    infos = []
    for spec, pol in groups:
        infos += _run_specs(store, [spec], args.batch, pol)
    if args.json:
        print(json.dumps({"store": store.path, "specs": specs,
                          "spec_policies": [[s, p or "f32"]
                                            for s, p in groups],
                          "skipped": len(unknown),
                          "entries": infos}))
    if problems:
        return 1
    return 0 if all(i.get("status") in ("compiled", "hit", "warm")
                    for i in infos) else 2


def _check_paged_row(e):
    """Shape-consistency problems for one ``generate:paged_chunk``
    manifest row (empty list = healthy).  The paged engine compiles a
    closed family of signatures — the two page-pool leaves, which come
    right before the page table, have one row a (layer, token) (rank
    2), so a whole number of pages of rows, and the token block is one
    of (1, chunk) / (slots, 1) / (slots, K+1) — so a row whose recorded
    shapes disagree with its own page_size/prefill_chunk/spec_k extras
    means the store was written by a mismatched build and would miss at
    load."""
    who = "manifest entry %s (%s)" % (e.get("key", "?")[:12],
                                      e.get("label"))
    page = e.get("page_size")
    chunk = e.get("prefill_chunk")
    spec_k = e.get("spec_k")
    if page is None or chunk is None or spec_k is None:
        return ["%s: paged row missing page_size/prefill_chunk/spec_k "
                "extras" % who]
    sig = e.get("signature") or []
    leaves = [(tuple(s[0]), s[1]) for s in sig
              if isinstance(s, (list, tuple)) and len(s) >= 2
              and isinstance(s[0], (list, tuple))]
    msgs = []
    # in flatten order: the model's parameters, the two pools, then the
    # page table, the first rank-2 int32 leaf
    table = next((i for i, (s, d) in enumerate(leaves)
                  if len(s) == 2 and d == "int32"), 0)
    pools = [s for s, d in leaves[max(table - 2, 0):table]
             if len(s) == 2 and d != "int32"]
    if len(pools) != 2 or pools[0] != pools[1]:
        msgs.append("%s: no pair of page-pool leaves before the page "
                    "table in the recorded signature" % who)
    else:
        for s in pools:
            if s[0] % page:
                msgs.append("%s: pool of %d rows is no whole number of "
                            "pages of page_size %d" % (who, s[0], page))
    # the model params are float leaves; the engine's only rank-2
    # int32 leaves are, in flatten order, page_table (slots, P) then
    # the token block (B, C)
    rank2 = [s for s, d in leaves if len(s) == 2 and d == "int32"]
    if len(rank2) < 2:
        msgs.append("%s: no token-block leaf in the recorded signature"
                    % who)
    else:
        width = rank2[1][1]
        allowed = {1, chunk} | ({spec_k + 1} if spec_k else set())
        if width not in allowed:
            msgs.append("%s: token block width %d is none of the "
                        "compiled family %s (chunk=%d spec_k=%d)"
                        % (who, width, sorted(allowed), chunk, spec_k))
    return msgs


def run_check(args):
    from mxnet_tpu import dtype_policy as _dtp

    store = _resolve_store(args.store)
    problems, stale = store.check(max_age_days=args.max_age_days)
    entries = store.entries()
    manifest, _ = store.manifest_entries()
    for e in manifest:
        if e.get("label") == "generate:paged_chunk":
            problems.extend(_check_paged_row(e))
    # every manifest signature must carry a recognized dtype-policy tag
    # (a registered policy name, or "int8" for quantized artifacts): a
    # wrong tag would prewarm the wrong executable.  Rows recorded
    # BEFORE the tag existed were f32 by construction (current builds
    # always stamp one) — reported as LEGACY, not fatal, so a store
    # that was green yesterday stays green.
    known_tags = set(_dtp.list_policies()) | {"int8"}
    legacy = []
    for e in manifest:
        tag = e.get("dtype_policy")
        if tag is None:
            legacy.append(
                "manifest entry %s (%s): no dtype_policy tag "
                "(pre-policy row, implied f32) — re-record with a "
                "current build to tag it"
                % (e.get("key", "?")[:12], e.get("label")))
        elif tag not in known_tags:
            problems.append(
                "manifest entry %s (%s): unknown dtype_policy %r "
                "(known: %s)" % (e.get("key", "?")[:12],
                                 e.get("label"), tag,
                                 sorted(known_tags)))
    print("%s: %d executables, %d manifest signatures"
          % (store.path, len(entries), len(manifest)))
    for key, meta in entries:
        print("  %s  %-28s %s  %.1fs compile"
              % (key[:12], meta.get("label", "?"),
                 (meta.get("fingerprint") or {}).get("backend", "?"),
                 meta.get("compile_seconds") or 0.0))
    for msg in stale:
        print("STALE: %s" % msg)
    for msg in legacy:
        print("LEGACY: %s" % msg)
    for msg in problems:
        print("MALFORMED: %s" % msg, file=sys.stderr)
    return 1 if problems else 0


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Compile + persist a workload's executables into "
                    "the AOT store ahead of rollout (or --check the "
                    "store's integrity)")
    p.add_argument("--store", help="store directory (default: "
                                   "MXNET_AOT_DIR)")
    p.add_argument("--model", action="append",
                   help="model spec to prewarm (repeatable): %s"
                        % ", ".join(sorted(MODELS)))
    p.add_argument("--manifest", action="store_true",
                   help="prewarm every spec recorded in the store's "
                        "signature manifest")
    p.add_argument("--check", action="store_true",
                   help="validate the store instead of compiling; "
                        "nonzero exit on a malformed store")
    p.add_argument("--dtype-policy", default=None,
                   help="mixed-precision dtype policy for the built "
                        "specs (f32/bf16_mixed/bf16_pure; default: the "
                        "MXNET_DTYPE_POLICY env default) — each policy "
                        "compiles its own AOT entries, keyed apart by "
                        "the policy tag")
    p.add_argument("--batch", type=int,
                   help="override the spec's batch size")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON summary line on stdout")
    p.add_argument("--max-age-days", type=float, default=90.0,
                   help="--check: flag entries older than this")
    args = p.parse_args(argv)
    if args.check:
        return run_check(args)
    if args.manifest:
        return run_manifest(args)
    if not args.model:
        p.error("pick a mode: --model NAME (see --help for the "
                "registry), --manifest, or --check")
    return run_prewarm(args)


if __name__ == "__main__":
    sys.exit(main())
