"""Benchmark: LM generation — KV-cache decode vs full re-forward.

The framework's first LATENCY-bound hot path (ISSUE 13): where
``bench_lm.py`` measures train tokens/s and MFU, this bench measures
the serving side of the same transformer LM through
``mxnet_tpu/generate.py`` — tokens/s/user, time-to-first-token
p50/p99, KV-cache occupancy, and the continuous-batching batch-size
profile — against the no-cache baseline that re-runs the full context
for every token (what decode costs without the engine).

Two measured phases after warmup, both jit-compiled (the comparison is
the algorithm, not eager dispatch overhead):

1. **Baseline**: one fixed-shape full-context forward per generated
   token (compiled once at ``--ctx``), greedy next-token on the host.
2. **Engine**: ``PagedGenerationEngine`` + ``TokenServer`` serving
   ``--users`` concurrent prompts with the KV-cache decode step
   (prefix sharing and drafting off, so the number moves only with the
   page pool's mechanics); plus a single-user pass for the
   apples-to-apples per-sequence rate.

Emits TWO ``BENCH {json}`` records through the perf ledger (the
``lm_decode`` record kind): ``lm_decode_tokens_per_sec_per_user``
(tokens/sec/user, higher-better) and ``lm_decode_ttft_p99_ms`` (ms,
LOWER-better — ``tools/perf_gate.py`` gates latency units upward).
``cache_speedup`` is aggregate KV-cache tokens/s over the re-forward
baseline.

    # CPU smoke:
    python tools/bench_decode.py

    # real chip:
    python tools/bench_decode.py --users 16 --ctx 512

The lever modes (ISSUE 16) measure each serving lever behind its
own perf-ledger metric so ``tools/perf_gate.py`` can gate them
independently:

* ``--prefix-share`` — N users behind ONE system prompt, aggregate
  tokens/s with copy-on-write prefix sharing vs the same engine with
  sharing disabled: ``lm_decode_prefix_share_tokens_per_sec`` (up) and
  ``lm_decode_prefix_hit_rate`` (ratio, up).
* ``--chunked-prefill`` — short-prompt TTFT p99 while long prompts
  prefill in fixed chunks interleaved with decode, vs monolithic
  single-chunk prefill: ``lm_decode_ttft_interference_p99_ms`` (ms,
  LOWER-better).
* ``--spec`` — n-gram self-speculative decoding on a repetitive
  prompt, drafted-and-accepted tokens per verify step plus the
  wall-clock speedup over the same engine without drafting:
  ``lm_decode_spec_accepted_per_step`` (tokens/step, up).

Progress goes to stderr; stdout is the marked record lines only.
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
    print("[bench_decode %6.1fs] %s" % (time.time() - _T0, msg),
          file=sys.stderr, flush=True)


# canonical canned result for the schema-guard tests (tests/
# test_generate.py and tests/test_perf_observatory.py import THIS so
# the two guards can never drift apart)
CANNED_RESULT = {
    "metric": "lm_decode_tokens_per_sec_per_user", "value": 733.4,
    "unit": "tokens/sec/user", "tokens_per_sec": 5866.9,
    "tokens_per_sec_single_user": 1163.0,
    "baseline_tokens_per_sec": 199.0, "cache_speedup": 29.5,
    "ttft_ms": {"p50": 8.9, "p99": 15.7}, "cache_occupancy": 0.23,
    "batch_tokens_mean": 7.0, "users": 8, "slots": 8, "cache_len": 256,
    "page_size": 16, "num_pages": 129, "pages_in_use_peak": 128,
    "prefill_chunk": 32, "ctx": 256, "prompt_len": 16,
    "gen_tokens": 48, "sampling": "greedy", "dtype_policy": "f32",
    "mesh_shape": {}, "layout": None, "devices": 1,
}

# per-mode canned results: same contract as CANNED_RESULT — the
# schema guard feeds each through ledger_records so a field rename in
# run_* shows up as a tier-1 failure, not a silently-reshaped record
CANNED_PREFIX_SHARE_RESULT = {
    "metric": "lm_decode_prefix_share_tokens_per_sec", "value": 18774.9,
    "unit": "tokens/sec", "noshare_tokens_per_sec": 16223.5,
    "prefix_speedup": 1.16, "prefix_hit_rate": 0.5,
    "prefix_hit_tokens_per_user": 112, "system_len": 112, "tail_len": 8,
    "users": 8, "slots": 4, "page_size": 16, "cache_len": 256,
    "gen_tokens": 32, "sampling": "greedy", "dtype_policy": "f32",
    "mesh_shape": {}, "layout": None, "devices": 1,
}

CANNED_CHUNKED_PREFILL_RESULT = {
    "metric": "lm_decode_ttft_interference_p99_ms", "value": 5.73,
    "unit": "ms", "ttft_interference_p50_ms": 2.09,
    "monolithic_ttft_p99_ms": 26.91, "interference_ratio": 4.7,
    "prefill_chunk": 16, "long_prompt_len": 160, "short_prompt_len": 8,
    "foreground_requests": 6, "background_users": 2, "slots": 4,
    "page_size": 16, "cache_len": 256, "sampling": "greedy",
    "dtype_policy": "f32", "mesh_shape": {}, "layout": None,
    "devices": 1,
}

CANNED_SPEC_RESULT = {
    "metric": "lm_decode_spec_accepted_per_step", "value": 0.6667,
    "unit": "tokens/step", "spec_accept_rate": 0.2235,
    "spec_tokens_per_sec": 1790.5, "nospec_tokens_per_sec": 2156.0,
    "spec_speedup": 0.83, "spec_k": 4, "spec_ngram": 3, "slots": 2,
    "page_size": 16, "cache_len": 256, "prompt_len": 24,
    "gen_tokens": 160, "sampling": "greedy", "dtype_policy": "f32",
    "mesh_shape": {}, "layout": None, "devices": 1,
}

# mode name -> canned result (tests iterate this to guard every mode)
CANNED_MODE_RESULTS = {
    "default": CANNED_RESULT,
    "prefix_share": CANNED_PREFIX_SHARE_RESULT,
    "chunked_prefill": CANNED_CHUNKED_PREFILL_RESULT,
    "spec": CANNED_SPEC_RESULT,
}


def ledger_records(result):
    """perf_ledger records for one bench_decode run: the ``lm_decode``
    record kind — the mode's headline metric plus its companion rows
    (TTFT p99 for the throughput modes, the prefix hit-rate ratio for
    ``--prefix-share``), topology/precision stamping provenance.  The
    tier-1 schema guard calls this with the canned results."""
    from mxnet_tpu import perf_ledger

    prov = {"mesh_shape": result.get("mesh_shape"),
            "layout": result.get("layout"),
            "dtype_policy": result.get("dtype_policy")}
    fields = {k: v for k, v in result.items()
              if k not in ("metric", "value", "unit")}
    recs = [perf_ledger.make_record(
        result["metric"], result["value"], result["unit"], prov=prov,
        **fields)]
    ttft = result.get("ttft_ms") or {}
    if ttft.get("p99") is not None:
        recs.append(perf_ledger.make_record(
            "lm_decode_ttft_p99_ms", ttft["p99"], "ms", prov=prov,
            ttft_p50_ms=ttft.get("p50"), users=result.get("users"),
            slots=result.get("slots"),
            prompt_len=result.get("prompt_len")))
    if result.get("prefix_hit_rate") is not None:
        recs.append(perf_ledger.make_record(
            "lm_decode_prefix_hit_rate", result["prefix_hit_rate"],
            "ratio", prov=prov, users=result.get("users"),
            system_len=result.get("system_len"),
            page_size=result.get("page_size")))
    return recs


def build_lm(vocab=None, d_model=None, n_heads=None, n_layers=None,
             max_len=256):
    """The decode benchmark-of-record model: bench_lm's CPU-smoke /
    TPU defaults at inference shapes, shared with tests and
    ``tools/autotune.py --decode``."""
    import jax

    import mxnet_tpu as mx
    from transformer_lm import TransformerLM

    on_tpu = any(d.platform != "cpu" for d in jax.devices())
    vocab = vocab or (32000 if on_tpu else 256)
    d_model = d_model or (512 if on_tpu else 64)
    n_heads = n_heads or (8 if on_tpu else 4)
    n_layers = n_layers or (8 if on_tpu else 2)
    mx.random.seed(0)
    lm = TransformerLM(vocab_size=vocab, d_model=d_model,
                       n_heads=n_heads, n_layers=n_layers,
                       max_len=max_len)
    lm.initialize(mx.init.Xavier())
    cfg = dict(vocab=vocab, d_model=d_model, n_heads=n_heads,
               n_layers=n_layers, max_len=max_len, on_tpu=on_tpu)
    return lm, cfg


def make_full_forward(lm):
    """One jitted full-context forward over committed params — the
    no-cache re-forward baseline's compiled program."""
    import jax

    from mxnet_tpu.gluon import block as block_mod
    from mxnet_tpu.ndarray import NDArray

    params = list(lm.collect_params().values())
    arrays = tuple(jax.device_put(p.data()._data) for p in params)

    def forward(tokens, params_):
        with block_mod.swapped_params(params, params_):
            return lm(NDArray(tokens))._data

    return jax.jit(forward), arrays


def run_baseline(lm, ctx, prompt, gen_tokens):
    """Greedy generation by full-context re-forward at ONE compiled
    shape (1, ctx): the cost of decode without a KV cache."""
    fwd, arrays = make_full_forward(lm)
    toks = np.zeros((1, ctx), np.int32)
    n = prompt.size
    toks[0, :n] = prompt
    gen_tokens = min(gen_tokens, ctx - n)
    # warmup: the one compile
    np.asarray(fwd(toks, arrays))
    t0 = time.perf_counter()
    pos = n - 1
    for _ in range(gen_tokens):
        logits = np.asarray(fwd(toks, arrays))
        nxt = int(logits[0, pos].argmax())
        pos += 1
        toks[0, pos] = nxt
    dt = time.perf_counter() - t0
    log("[baseline] %d tokens by re-forward @ ctx %d in %.3fs "
        "(%.1f tok/s)" % (gen_tokens, ctx, dt, gen_tokens / dt))
    return gen_tokens / dt


def run(users=None, slots=None, ctx=256, prompt_len=16, gen_tokens=None,
        dtype_policy=None, mesh=None, layout=None, trace_out=None,
        baseline=True, page_size=None, prefill_chunk=None, **model_kw):
    import jax

    from mxnet_tpu import generate, telemetry, tracing

    telemetry.enable()
    if trace_out:
        tracing.enable()
        from mxnet_tpu import profiler

        profiler.set_config(aggregate_stats=True)
    lm, cfg = build_lm(max_len=ctx, **model_kw)
    if slots is None:
        slots = 16 if cfg["on_tpu"] else 8
    if users is None:
        users = slots
    if gen_tokens is None:
        gen_tokens = 128 if cfg["on_tpu"] else 48
    gen_tokens = min(gen_tokens, ctx - prompt_len)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg["vocab"], prompt_len).astype(np.int32)

    if dtype_policy is None:
        dtype_policy = os.environ.get("BENCH_DTYPE_POLICY") or \
            ("bf16_mixed" if cfg["on_tpu"] else None)
    # the isolated measurement of the page pool: sharing and drafting
    # off so the number moves only with the pool's mechanics
    eng = generate.PagedGenerationEngine(
        lm, slots=slots, cache_len=ctx, page_size=page_size,
        prefill_chunk=prefill_chunk, spec_k=0, prefix_share=False,
        mesh=mesh, layout=layout, dtype_policy=dtype_policy,
        sampling=generate.SamplingConfig(greedy=True))
    log("engine: slots=%d cache_len=%d page=%d pages=%d chunk=%d "
        "dtype=%s mesh=%s"
        % (eng.slots, eng.cache_len, eng.page_size, eng.num_pages,
           eng.prefill_chunk, eng.dtype_policy_tag, eng.mesh_shape))

    baseline_tps = None
    if baseline:
        baseline_tps = run_baseline(lm, ctx, prompt, gen_tokens)

    srv = generate.TokenServer(eng, queue_depth=max(users, 4),
                               max_new_tokens=gen_tokens)
    # warmup: one short request compiles the prefill chunk + the
    # decode step (or loads them from the AOT store)
    srv.generate(prompt, max_new_tokens=2, timeout=600)
    telemetry.reset()

    # phase 1 — single user: the apples-to-apples per-sequence rate
    t0 = time.perf_counter()
    r1 = srv.generate(prompt, max_new_tokens=gen_tokens, timeout=600)
    dt1 = time.perf_counter() - t0
    single_tps = len(r1.tokens) / dt1
    log("[engine 1 user] %d tokens in %.3fs (%.1f tok/s)"
        % (len(r1.tokens), dt1, single_tps))

    # phase 2 — continuous batching at --users concurrency
    telemetry.reset()
    t0 = time.perf_counter()
    futs = [srv.submit(prompt, block=True, timeout=600)
            for _ in range(users)]
    # peak cache occupancy, polled while the batch decodes (admissions
    # land on the worker thread after submit returns)
    occ_peak = 0.0
    pages_peak = 0
    while not all(f.done() for f in futs):
        occ = eng.occupancy()
        occ_peak = max(occ_peak, occ["occupancy"])
        pages_peak = max(pages_peak, occ["pages_in_use"])
        time.sleep(0.002)
    results = [f.result(timeout=600) for f in futs]
    dt = time.perf_counter() - t0
    total = sum(len(r.tokens) for r in results)
    agg_tps = total / dt
    per_user = agg_tps / users
    ttfts = sorted(r.ttft_s for r in results)
    p50 = float(np.percentile(ttfts, 50)) * 1e3
    p99 = float(np.percentile(ttfts, 99)) * 1e3
    bt_count = telemetry.DECODE_BATCH_TOKENS.count()
    bt_mean = (telemetry.DECODE_BATCH_TOKENS.sum() / bt_count) \
        if bt_count else None
    srv.close()
    log("[engine %d users] %d tokens in %.3fs (%.1f tok/s aggregate, "
        "%.1f tok/s/user, TTFT p50 %.1f ms p99 %.1f ms)"
        % (users, total, dt, agg_tps, per_user, p50, p99))

    result = {
        "metric": "lm_decode_tokens_per_sec_per_user",
        "value": round(per_user, 2),
        "unit": "tokens/sec/user",
        "tokens_per_sec": round(agg_tps, 2),
        "tokens_per_sec_single_user": round(single_tps, 2),
        "baseline_tokens_per_sec": round(baseline_tps, 2)
        if baseline_tps else None,
        "cache_speedup": round(agg_tps / baseline_tps, 2)
        if baseline_tps else None,
        "ttft_ms": {"p50": round(p50, 2), "p99": round(p99, 2)},
        "cache_occupancy": round(occ_peak, 4),
        "batch_tokens_mean": round(bt_mean, 2)
        if bt_mean is not None else None,
        "users": users,
        "slots": eng.slots,
        "cache_len": eng.cache_len,
        "page_size": eng.page_size,
        "num_pages": eng.num_pages,
        "pages_in_use_peak": pages_peak,
        "prefill_chunk": eng.prefill_chunk,
        "ctx": ctx,
        "prompt_len": prompt_len,
        "gen_tokens": gen_tokens,
        "sampling": eng.sampling.tag,
        "dtype_policy": eng.dtype_policy_tag,
        "mesh_shape": eng.mesh_shape,
        "layout": eng.layout_name,
        "devices": len(jax.devices()),
    }
    if baseline_tps:
        log("cache speedup vs re-forward @ ctx %d: %.2fx (aggregate), "
            "%.2fx (single user)" % (ctx, agg_tps / baseline_tps,
                                     single_tps / baseline_tps))
    if trace_out:
        from mxnet_tpu import tracing as _tr

        _tr.export_trace(trace_out)
        log("unified trace written to %s" % trace_out)
    return result


def _paged_server(lm, gen_tokens, **eng_kw):
    """PagedGenerationEngine + TokenServer with one warmup request so
    timed phases never include the chunk/decode/verify compiles."""
    import numpy as _np

    from mxnet_tpu import generate

    eng = generate.PagedGenerationEngine(
        lm, sampling=generate.SamplingConfig(greedy=True), **eng_kw)
    srv = generate.TokenServer(eng, queue_depth=64,
                               max_new_tokens=gen_tokens)
    warm = _np.arange(2, dtype=_np.int32)
    srv.generate(warm, max_new_tokens=2, timeout=600)
    return eng, srv


def run_prefix_share(users=8, slots=None, ctx=256, system_len=112,
                     tail_len=8, gen_tokens=32, page_size=None,
                     dtype_policy=None, mesh=None, layout=None,
                     **model_kw):
    """--prefix-share: N users behind one system prompt.  Aggregate
    tokens/s (prompt + generated, since sharing's win is prefill work
    avoided) with copy-on-write sharing on vs the same engine with it
    off — the ISSUE's committed CPU aggregate-throughput win."""
    import jax

    from mxnet_tpu import telemetry

    telemetry.enable()
    lm, cfg = build_lm(max_len=ctx, **model_kw)
    if slots is None:
        slots = 8 if cfg["on_tpu"] else 4
    rng = np.random.RandomState(0)
    system = rng.randint(0, cfg["vocab"], system_len).astype(np.int32)
    prompts = [np.concatenate([system, rng.randint(
        0, cfg["vocab"], tail_len).astype(np.int32)])
        for _ in range(users)]
    gen_tokens = min(gen_tokens, ctx - system_len - tail_len)

    def phase(share):
        eng, srv = _paged_server(
            lm, gen_tokens, slots=slots, cache_len=ctx,
            page_size=page_size, spec_k=0, prefix_share=share,
            mesh=mesh, layout=layout, dtype_policy=dtype_policy)
        t0 = time.perf_counter()
        futs = [srv.submit(pr, block=True, timeout=600)
                for pr in prompts]
        results = [f.result(timeout=600) for f in futs]
        dt = time.perf_counter() - t0
        # prompt tokens count: sharing's saving is prefill compute, so
        # the aggregate rate must include the tokens being prefilled
        total = sum(len(pr) + len(r.tokens)
                    for pr, r in zip(prompts, results))
        hit = eng.prefix_hit_rate()
        srv.close()
        log("[prefix share=%s] %d users x (%d prompt + %d gen) in "
            "%.3fs (%.1f tok/s aggregate, hit_rate %s)"
            % (share, users, system_len + tail_len, gen_tokens, dt,
               total / dt, "%.3f" % hit if hit is not None else "n/a"))
        return total / dt, hit, eng

    share_tps, hit_rate, eng = phase(True)
    noshare_tps, _, _ = phase(False)
    log("prefix-share aggregate win: %.2fx" % (share_tps / noshare_tps))
    return {
        "metric": "lm_decode_prefix_share_tokens_per_sec",
        "value": round(share_tps, 2),
        "unit": "tokens/sec",
        "noshare_tokens_per_sec": round(noshare_tps, 2),
        "prefix_speedup": round(share_tps / noshare_tps, 2),
        "prefix_hit_rate": round(hit_rate, 4)
        if hit_rate is not None else None,
        "prefix_hit_tokens_per_user":
            system_len // eng.page_size * eng.page_size,
        "system_len": system_len,
        "tail_len": tail_len,
        "users": users,
        "slots": slots,
        "page_size": eng.page_size,
        "cache_len": eng.cache_len,
        "gen_tokens": gen_tokens,
        "sampling": eng.sampling.tag,
        "dtype_policy": eng.dtype_policy_tag,
        "mesh_shape": eng.mesh_shape,
        "layout": eng.layout_name,
        "devices": len(jax.devices()),
    }


def run_chunked_prefill(slots=None, ctx=256, prefill_chunk=16,
                        long_prompt=160, short_prompt=8, rounds=6,
                        page_size=None, dtype_policy=None, mesh=None,
                        layout=None, **model_kw):
    """--chunked-prefill: the scheduling latency win.  Two background
    users decode continuously; each round submits a LONG prompt and a
    short prompt together and measures the short request's TTFT.  With
    chunked prefill the short prompt's one chunk interleaves between
    the long prompt's chunks and the decode steps; the comparison run
    prefills monolithically (chunk = full capacity), so the short
    request waits out the whole long dispatch."""
    import jax

    from mxnet_tpu import telemetry

    telemetry.enable()
    lm, cfg = build_lm(max_len=ctx, **model_kw)
    if slots is None:
        slots = 4
    rng = np.random.RandomState(0)
    bg_prompt = rng.randint(0, cfg["vocab"], short_prompt) \
        .astype(np.int32)
    long_p = rng.randint(0, cfg["vocab"], long_prompt).astype(np.int32)
    short_p = rng.randint(0, cfg["vocab"], short_prompt) \
        .astype(np.int32)
    bg_gen = min(ctx - short_prompt - 1, 200)

    def phase(chunk):
        eng, srv = _paged_server(
            lm, bg_gen, slots=slots, cache_len=ctx, page_size=page_size,
            prefill_chunk=chunk, spec_k=0, prefix_share=False,
            mesh=mesh, layout=layout, dtype_policy=dtype_policy)
        bg = [srv.submit(bg_prompt, block=True, timeout=600)
              for _ in range(2)]
        ttfts = []
        for _ in range(rounds):
            fl = srv.submit(long_p, max_new_tokens=2, block=True,
                            timeout=600)
            fs = srv.submit(short_p, max_new_tokens=2, block=True,
                            timeout=600)
            rs = fs.result(timeout=600)
            fl.result(timeout=600)
            ttfts.append(rs.ttft_s)
        for f in bg:
            f.result(timeout=600)
        srv.close()
        p50 = float(np.percentile(ttfts, 50)) * 1e3
        p99 = float(np.percentile(ttfts, 99)) * 1e3
        log("[chunk=%d] short-prompt TTFT under long-prefill "
            "interference: p50 %.1f ms p99 %.1f ms over %d rounds"
            % (chunk, p50, p99, rounds))
        return p50, p99, eng

    p50, p99, eng = phase(prefill_chunk)
    # monolithic = one chunk spanning the whole capacity
    _, mono_p99, _ = phase(ctx)
    log("prefill-interference win: monolithic p99 %.1f ms vs chunked "
        "%.1f ms (%.2fx)" % (mono_p99, p99, mono_p99 / p99))
    return {
        "metric": "lm_decode_ttft_interference_p99_ms",
        "value": round(p99, 2),
        "unit": "ms",
        "ttft_interference_p50_ms": round(p50, 2),
        "monolithic_ttft_p99_ms": round(mono_p99, 2),
        "interference_ratio": round(mono_p99 / p99, 2),
        "prefill_chunk": prefill_chunk,
        "long_prompt_len": long_prompt,
        "short_prompt_len": short_prompt,
        "foreground_requests": rounds,
        "background_users": 2,
        "slots": slots,
        "page_size": eng.page_size,
        "cache_len": eng.cache_len,
        "sampling": eng.sampling.tag,
        "dtype_policy": eng.dtype_policy_tag,
        "mesh_shape": eng.mesh_shape,
        "layout": eng.layout_name,
        "devices": len(jax.devices()),
    }


def run_spec(slots=2, ctx=256, prompt_len=24, gen_tokens=160, spec_k=4,
             spec_ngram=3, page_size=None, dtype_policy=None,
             mesh=None, layout=None, **model_kw):
    """--spec: n-gram self-speculative decoding on a REPETITIVE prompt
    (a tiled pattern, the draft source's best case — real LM output
    loops similarly at small scale).  Accepted tokens per verify step
    plus the single-user wall-clock speedup over the same engine with
    drafting off.  Greedy, so the output is bit-identical either way —
    the bench asserts that too."""
    import jax

    from mxnet_tpu import telemetry

    telemetry.enable()
    lm, cfg = build_lm(max_len=ctx, **model_kw)
    rng = np.random.RandomState(0)
    base = rng.randint(0, cfg["vocab"], 6).astype(np.int32)
    prompt = np.tile(base, -(-prompt_len // 6))[:prompt_len]
    gen_tokens = min(gen_tokens, ctx - prompt_len - spec_k - 1)

    def phase(k):
        eng, srv = _paged_server(
            lm, gen_tokens, slots=slots, cache_len=ctx,
            page_size=page_size, spec_k=k, spec_ngram=spec_ngram,
            prefix_share=False, mesh=mesh, layout=layout,
            dtype_policy=dtype_policy)
        t0 = time.perf_counter()
        r = srv.generate(prompt, max_new_tokens=gen_tokens, timeout=600)
        dt = time.perf_counter() - t0
        aps = eng.spec_accepted_per_step()
        rate = eng.spec_accept_rate()
        srv.close()
        log("[spec_k=%d] %d tokens in %.3fs (%.1f tok/s, "
            "accepted/step %s, accept_rate %s)"
            % (k, len(r.tokens), dt, len(r.tokens) / dt,
               "%.2f" % aps if aps is not None else "n/a",
               "%.2f" % rate if rate is not None else "n/a"))
        return len(r.tokens) / dt, r.tokens, aps, rate, eng

    spec_tps, spec_toks, aps, rate, eng = phase(spec_k)
    nospec_tps, nospec_toks, _, _, _ = phase(0)
    if list(spec_toks) != list(nospec_toks):
        raise AssertionError(
            "speculative greedy decode diverged from the plain engine")
    log("spec speedup: %.2fx (greedy outputs identical)"
        % (spec_tps / nospec_tps))
    return {
        "metric": "lm_decode_spec_accepted_per_step",
        "value": round(aps, 4) if aps is not None else 0.0,
        "unit": "tokens/step",
        "spec_accept_rate": round(rate, 4)
        if rate is not None else None,
        "spec_tokens_per_sec": round(spec_tps, 2),
        "nospec_tokens_per_sec": round(nospec_tps, 2),
        "spec_speedup": round(spec_tps / nospec_tps, 2),
        "spec_k": spec_k,
        "spec_ngram": spec_ngram,
        "slots": slots,
        "page_size": eng.page_size,
        "cache_len": eng.cache_len,
        "prompt_len": prompt_len,
        "gen_tokens": gen_tokens,
        "sampling": eng.sampling.tag,
        "dtype_policy": eng.dtype_policy_tag,
        "mesh_shape": eng.mesh_shape,
        "layout": eng.layout_name,
        "devices": len(jax.devices()),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--users", type=int, default=None,
                   help="concurrent generation requests (default: "
                        "= slots)")
    p.add_argument("--slots", type=int, default=None,
                   help="decode slots / KV-cache lanes (default 8 CPU, "
                        "16 TPU)")
    p.add_argument("--ctx", type=int, default=256,
                   help="context window: a slot's cache length AND the "
                        "baseline's fixed re-forward shape (default "
                        "256)")
    p.add_argument("--prompt-len", type=int, default=None,
                   help="prompt length (default 16; --spec 24, "
                        "--chunked-prefill's short prompt 8)")
    p.add_argument("--gen-tokens", type=int, default=None,
                   help="tokens generated per request (default 48 CPU, "
                        "128 TPU)")
    p.add_argument("--dtype-policy", default=None,
                   help="engine dtype policy (cache dtype follows its "
                        "compute dtype; default BENCH_DTYPE_POLICY, "
                        "else bf16_mixed on TPU)")
    p.add_argument("--mesh", default=None,
                   help="mesh spec for tp-sharded serving, e.g. "
                        "dp=1,tp=8 (default: MXNET_MESH)")
    p.add_argument("--layout", default=None)
    p.add_argument("--no-baseline", action="store_true",
                   help="skip the re-forward baseline phase")
    p.add_argument("--trace-out", default=None,
                   help="write the measured run's unified chrome trace "
                        "(tools/autotune.py --decode consumes it)")
    p.add_argument("--vocab", type=int, default=None)
    p.add_argument("--d-model", type=int, default=None)
    p.add_argument("--n-heads", type=int, default=None)
    p.add_argument("--n-layers", type=int, default=None)
    mode = p.add_mutually_exclusive_group()
    mode.add_argument("--prefix-share", action="store_true",
                      help="N users behind one system prompt: "
                           "aggregate tokens/s, sharing on vs off")
    mode.add_argument("--chunked-prefill", action="store_true",
                      help="short-prompt TTFT p99 under long-prompt "
                           "prefill interference, chunked vs "
                           "monolithic")
    mode.add_argument("--spec", action="store_true",
                      help="n-gram speculative decoding: accepted "
                           "tokens per verify step + speedup vs "
                           "drafting off")
    p.add_argument("--page-size", type=int, default=None,
                   help="positions per KV page (default "
                        "MXNET_DECODE_PAGE_SIZE)")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="prefill chunk length (default "
                        "MXNET_DECODE_PREFILL_CHUNK)")
    p.add_argument("--system-len", type=int, default=112,
                   help="--prefix-share: shared system-prompt length")
    p.add_argument("--spec-k", type=int, default=4,
                   help="--spec: draft tokens per verify step")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="--spec: n-gram match length for drafting")
    a = p.parse_args(argv)
    common = dict(dtype_policy=a.dtype_policy, mesh=a.mesh,
                  layout=a.layout, vocab=a.vocab, d_model=a.d_model,
                  n_heads=a.n_heads, n_layers=a.n_layers)
    if a.prefix_share:
        result = run_prefix_share(
            users=a.users or 8, slots=a.slots, ctx=a.ctx,
            system_len=a.system_len,
            gen_tokens=a.gen_tokens or 32, page_size=a.page_size,
            **common)
    elif a.chunked_prefill:
        result = run_chunked_prefill(
            slots=a.slots, ctx=a.ctx,
            prefill_chunk=a.prefill_chunk or 16,
            short_prompt=a.prompt_len or 8,
            page_size=a.page_size, **common)
    elif a.spec:
        result = run_spec(
            slots=a.slots or 2, ctx=a.ctx,
            prompt_len=a.prompt_len or 24,
            gen_tokens=a.gen_tokens or 160, spec_k=a.spec_k,
            spec_ngram=a.spec_ngram, page_size=a.page_size, **common)
    else:
        result = run(users=a.users, slots=a.slots, ctx=a.ctx,
                     prompt_len=a.prompt_len or 16,
                     gen_tokens=a.gen_tokens,
                     trace_out=a.trace_out,
                     baseline=not a.no_baseline, page_size=a.page_size,
                     prefill_chunk=a.prefill_chunk, **common)
    from mxnet_tpu import perf_ledger

    for rec in ledger_records(result):
        perf_ledger.emit(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
