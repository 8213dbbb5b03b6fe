"""The block-diffusion serving driver: ``TokenServer.submit`` over a
``PagedGenerationEngine`` whose model decodes by diffusion over blocks
(``docs/lm_serving.md``, "Block-diffusion decoding"), under
``serve_lm``'s closed loop.  A tick delivers nothing for a slot whose
block is open and a burst of up to ``block_length`` tokens when it
commits; every token is stamped where the client receives it.

What ``correct`` compares (after the window, the engine freed): of a
seeded sample of finished requests (the longest among them), a seeded
sample of blocks that always holds the last one; every denoise pass of
each is rebuilt from the served tokens and the pass at which each was
fixed (the result's ``fixed_at``) and run through the plain reference's
full forward.  A request's last block is cut by ``max_new_tokens`` more
often than not, and the client never sees what the cut positions held,
so of a cut block only pass 1 (everything masked) can be rebuilt."""
import gc
import time

import numpy as np

from benchmark.drivers import common
from benchmark.drivers.serve_lm import (ClosedLoop, EngineSteps,
                                        check_sample)


FAULTS = ("commit_unwritten", "denoise_written", "expert_left_out")


def build(run, fault=None):
    """Network, weights, engine and server; ``fault`` (never given by
    ``benchmark/run.py``) plants one of ``FAULTS`` in the program, the
    reference keeping the whole weights."""
    import jax

    from mxnet_tpu import generate
    from benchmark import programs
    from benchmark.lib import weights

    cfg, traffic = run.cfg, run.traffic
    if traffic["block_length"] != cfg["assumed"]["block_length"]:
        raise SystemExit("the traffic's block_length is not the "
                         "configuration's")
    fam = weights.family(cfg)
    net = programs.program(cfg).build_net(cfg)
    run.log("network built")
    arrays = weights.make_params(cfg, run.seed)
    jax.block_until_ready(arrays)
    run.log("weights made")
    programs.set_weights(
        net, fam.param_specs(cfg), without_an_expert(cfg, arrays)
        if fault == "expert_left_out" else arrays)
    engine = make_engine(run, net)
    if fault in ("commit_unwritten", "denoise_written"):
        plant_write_fault(engine, fault)
    elif fault not in (None, "expert_left_out"):
        raise ValueError("no such fault: %r" % (fault,))
    server = generate.TokenServer(
        engine, queue_depth=4 * traffic["clients"], deadline_ms=0,
        max_new_tokens=traffic["answer_len"]["hi"])
    run.log("engine and server made")
    return net, arrays, engine, server


def make_engine(run, net):
    from mxnet_tpu import generate

    traffic = run.traffic
    return generate.PagedGenerationEngine(
        net, slots=traffic["slots"], cache_len=traffic["cache_len"],
        page_size=traffic["page_size"], num_pages=traffic["num_pages"],
        prefill_chunk=traffic["prefill_chunk"], spec_k=0,
        prefix_share=traffic["prefix_share"],
        denoise_steps=traffic["denoise_steps"],
        dtype_policy=run.cfg["dtype_policy"],
        sampling=generate.SamplingConfig(greedy=True))


def window_numbers(run, loop, steps, t0, t1):
    """Everything the window's stamps give.  The algorithm's operations:
    every prompt position once, and every position of a generated block
    ``denoise_steps + 1`` times, each over the cache up to its block's
    end."""
    from benchmark.lib import flops

    cfg, traffic = run.cfg, run.traffic
    Bl, passes = traffic["block_length"], traffic["denoise_steps"] + 1
    tokens, gaps, contexts = 0, [], []
    for req in loop.requests:
        n = len(req.prompt)
        pre = Bl * (n // Bl)
        for j, t in enumerate(req.stamps):
            if not t0 < t <= t1:
                continue
            tokens += 1
            if j == 0:                              # its prompt ran here
                contexts.extend(Bl * (i // Bl) + Bl for i in range(pre))
                contexts.extend([pre + Bl] * (passes * (n - pre)))
            elif req.stamps[j - 1] > t0:
                gaps.append(t - req.stamps[j - 1])
            contexts.extend([Bl * ((n + j) // Bl) + Bl] * passes)
    rows = steps.inside(t0, t1)
    dec = [r for r in rows if r[0] == "decode"]
    pre = [r for r in rows if r[0] == "prefill"]
    out = {"seconds": t1 - t0, "output_tokens": tokens,
           "output_tok_s": tokens / (t1 - t0),
           "itl_gaps": len(gaps),
           "itl_p95_ms": 1e3 * common.percentile(gaps, 95) if gaps else None,
           "itl_p50_ms": 1e3 * common.percentile(gaps, 50) if gaps else None,
           "decode_steps": len(dec), "prefill_chunks": len(pre),
           "serve_flops": flops.serve_flops(cfg, contexts)}
    if dec:
        durs = [r[2] - r[1] for r in dec]
        out.update({
            "decode_step_ms_mean": 1e3 * sum(durs) / len(durs),
            "decode_step_ms_max": 1e3 * max(durs),
            "decode_slot_occupancy": 100.0 * sum(r[3] for r in dec)
            / (traffic["slots"] * len(dec)),
            "decode_live_positions_mean": sum(r[4] for r in dec) / len(dec),
            "prefill_share": 100.0 * len(pre) / (len(pre) + len(dec))})
    return out


def phase_means(run, t0, t1):
    """Log, from the program's own spans, the count and the mean length
    of every span that ended inside the window: which phase of a tick a
    slow window was slow in."""
    from mxnet_tpu import tracing

    total = {}
    for r in tracing.records():
        if t0 < r["t0"] + r["dur"] <= t1:
            n, sec = total.get(r["name"], (0, 0.0))
            total[r["name"]] = (n + 1, sec + r["dur"])
    run.log("spans in the window, count and mean ms: %s" % ", ".join(
        "%s %d x %.3f" % (k, n, 1e3 * sec / n)
        for k, (n, sec) in sorted(total.items())))
    # the ticks over twice the mean: when in the window, how long, the
    # thread's own CPU time, and the phases inside each
    ticks = [r for r in tracing.records() if r["name"] == "serve.tick"
             and t0 < r["t0"] + r["dur"] <= t1]
    if not ticks:
        return
    mean = sum(r["dur"] for r in ticks) / len(ticks)
    for tick in [r for r in ticks if r["dur"] > 2 * mean][:12]:
        inside = [r for r in tracing.records() if r is not tick
                  and tick["t0"] <= r["t0"] <= tick["t0"] + tick["dur"]]
        run.log("slow tick at %.3f s: %.1f ms (cpu %.1f): %s" % (
            tick["t0"] - t0, 1e3 * tick["dur"],
            (tick.get("args") or {}).get("cpu_ms", -1), ", ".join(
                "%s %.1f" % (r["name"], 1e3 * r["dur"]) for r in inside
                if r["dur"] > 0.002)))


# -- the comparison -----------------------------------------------------------

def block_states(run, req, fixed_at, confidence):
    """The denoise passes of the request's sampled blocks that can be
    rebuilt: [(context tokens, fed block, masked, fixed now, block's
    tokens, the program's confidence in each, how many the pass fixes by
    the rule)], lists of a block's length but the first and the last."""
    traffic = run.traffic
    Bl, T = traffic["block_length"], traffic["denoise_steps"]
    n, m = len(req.prompt), len(req.tokens)
    mask_id = run.cfg["assumed"]["mask_token_id"]
    seq = [int(t) for t in req.prompt] + [int(t) for t in req.tokens]
    at = [0] * n + [int(t) for t in fixed_at]
    said = [0.0] * n + [float(c) for c in confidence]
    first, last = n // Bl, (n + m - 1) // Bl
    whole = (n + m) % Bl == 0
    rng = np.random.default_rng([int(run.seed) & 0xFFFFFFFF, n, m, 13])
    others = list(range(first, last))
    picks = sorted(set([last] + [others[i] for i in rng.permutation(
        len(others))[:traffic["check_blocks"] - 1]]))
    out = []
    for b in picks:
        lo = b * Bl
        tok = (seq[lo:lo + Bl] + [0] * Bl)[:Bl]
        conf = (said[lo:lo + Bl] + [0.0] * Bl)[:Bl]
        fix = (at[lo:lo + Bl] + [T + 1] * Bl)[:Bl]      # cut: never seen
        given = [lo + i < n for i in range(Bl)]
        each = -(-(Bl - sum(given)) // T)       # ceil(masked at start / T)
        for t in range(1, T + 1 if (b < last or whole) else 2):
            masked = [not g and f >= t for g, f in zip(given, fix)]
            fed = [mask_id if k else v for v, k in zip(tok, masked)]
            now = [not g and f == t for g, f in zip(given, fix)]
            if any(now):
                out.append((seq[:lo], fed, masked, now, tok, conf,
                            min(each, sum(masked))))
    return out


def reference_numbers(run, params, sample, quant=None):
    """For every rebuilt pass of the sample, per position the program
    fixed there, a row of: the gap by which the served token's logit
    lies below the reference's best; the gap by which the reference's
    confidence there lies below the least of the reference's own choices
    of that pass (its k most confident masked positions, k the number
    the rule has the pass fix); the reference's router margin at the
    position (the least gap, over the layers, between the k-th and the
    next expert's logit); and the distance between the log-probability
    the program gave the served token (the result's ``confidence``) and
    the reference's for the same token.  With ``quant`` the tokens, the
    choices and the log-probabilities judged are not the served ones but
    those the control's precision makes on the same state."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights

    cfg, traffic = run.cfg, run.traffic
    fam = weights.family(cfg)
    length, Bl = traffic["cache_len"], traffic["block_length"]

    def confidence(lg):
        return jnp.max(lg, -1) - jax.scipy.special.logsumexp(lg, -1)

    @jax.jit
    def read(params, tokens, positions, served, masked, now, k, said):
        ref, margin = fam.logits_at(cfg, params, tokens, positions)
        ref, margin = ref[0], margin[0]
        conf = confidence(ref)
        if quant is not None:
            ctl = fam.logits_at(cfg, params, tokens, positions, quant)[0][0]
            served, said = jnp.argmax(ctl, -1), confidence(ctl)
            rank = jnp.argsort(jnp.argsort(
                jnp.where(masked, -confidence(ctl), jnp.inf)))
            now = masked & (rank < k)
        chosen = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        open_conf = jnp.sort(jnp.where(masked, conf, -jnp.inf))[::-1]
        least = open_conf[jnp.maximum(k - 1, 0)]
        logp = chosen - jax.scipy.special.logsumexp(ref, -1)
        return (jnp.max(ref, -1) - chosen, jnp.maximum(least - conf, 0.0),
                margin, jnp.abs(said - logp), now)

    rows = []
    for req, result in sample:
        for ctx, fed, masked, now, tok, said, k in block_states(
                run, req, result["fixed_at"], result["confidence"]):
            seq = np.zeros((1, length), np.int32)
            seq[0, :len(ctx)] = ctx
            seq[0, len(ctx):len(ctx) + Bl] = fed
            pos = len(ctx) + np.arange(Bl, dtype=np.int32)
            lg, cf, mg, lp, nw = (np.asarray(a) for a in read(
                params, seq, pos, np.asarray(tok, np.int32),
                np.asarray(masked), np.asarray(now), np.int32(k),
                np.asarray(said, np.float32)))
            rows.extend((float(lg[i]), float(cf[i]), float(mg[i]),
                         float(lp[i])) for i in range(Bl) if nw[i])
    return rows


def numbers_of(rows):
    """The numbers of the rows ``reference_numbers`` gives: compared
    are ``logit_gap_max`` (a wrong token reads whole units) and
    ``logprob_gap_mean`` (the mean over every compared position: what a
    small systematic fault moves, and what stands the routing near-ties:
    where the router's last choice is all but tied, bfloat16 activations
    now and then take another expert than float32 does, which moves that
    one position by far more than rounding, and the mean by next to
    nothing).  The others are logged beside them: the widest
    log-probability and confidence gaps, and the share of positions whose
    router margin is under 0.005."""
    if not rows:
        return {"logit_gap_max": None, "logprob_gap_mean": None}
    return {
        "logit_gap_max": max(r[0] for r in rows),
        "logprob_gap_mean": sum(r[3] for r in rows) / len(rows),
        "logprob_gap_max": max(r[3] for r in rows),
        "confidence_gap_max": max(r[1] for r in rows),
        "near_tie_share": sum(r[2] < 0.005 for r in rows) / len(rows),
        "positions_compared": len(rows)}


# -- planted faults (tests/test_block_diffusion.py, tools/blockgen_limits.py)

def plant_write_fault(engine, kind):
    """``commit_unwritten``: a commit pass's K/V rows go to the trash
    page.  ``denoise_written``: a denoise pass's rows go to the pool,
    where they can be read: written at the open block's own positions
    they would be masked (the view ends at the block's start) and then
    overwritten by the commit, so the fault puts them on the block
    before, whose committed K/V every later pass attends."""
    inner, page = engine._jit_chunk, engine.page_size

    def chunk(params, pk, pv, table, tokens, start, wpage, woff, keys,
              *block):
        rows, Bl = tokens.shape
        if rows > 1 and kind == "commit_unwritten":
            wpage, woff = np.zeros_like(wpage), np.zeros_like(woff)
        elif rows > 1 and kind == "denoise_written":
            for b in range(rows):
                mine = slice(b * Bl, (b + 1) * Bl)
                p = int(start[b]) - Bl + np.arange(Bl)
                if wpage[mine].any() or p[0] < 0 or not table[b].any():
                    continue
                wpage[mine], woff[mine] = table[b, p // page], p % page
        return inner(params, pk, pv, table, tokens, start, wpage, woff,
                     keys, *block)

    engine._jit_chunk = chunk


def without_an_expert(cfg, arrays):
    """The weights with one expert's part left out: the first expert of
    every layer adds nothing (one expert in one layer of SDAR-30B-A3B's
    six is 1/768 of the expert parameters and reads like one of
    bfloat16's own routing flips: ``PERF.md``, section 2)."""
    from benchmark.lib import weights

    names = [n for n, _s, _k in weights.family(cfg).param_specs(cfg)]
    width, out = cfg["moe_intermediate_size"], list(arrays)
    for i, name in enumerate(names):
        if name.endswith("experts_down_weight"):
            out[i] = out[i].at[:width].set(0)
    return out


# -- one run ------------------------------------------------------------------

def main(run, fault=None):
    import jax

    compiles = common.CompileCounter()
    t_build = time.perf_counter()
    net, arrays, engine, server = build(run, fault)
    steps = EngineSteps(engine)
    run.window["setup_build_s"] = time.perf_counter() - t_build
    traffic = run.traffic
    loop = ClosedLoop(run, server)
    t_compile = time.perf_counter()
    # warm-up: the first clients fill the slots, which runs the engine's two
    # shapes (a prefill chunk, a pass over all slots) and leaves them full
    first = [loop.submit(c) for c in range(traffic["slots"])]
    loop.serve_until(lambda: all(r.tokens for r in first)
                     and any(r[0] == "decode" for r in steps.rows))
    run.window["setup_compile_s"] = time.perf_counter() - t_compile
    run.window["setup_programs"] = compiles.count
    tracer = common.Tracer(run.out_dir, run.trace)
    for c in range(traffic["slots"], traffic["clients"]):
        loop.submit(c)
    if run.trace:
        with tracer:
            t_traced = time.perf_counter()
            loop.serve_until(t_traced + traffic["trace_seconds"])
            traced = [r for r in steps.inside(t_traced, time.perf_counter())
                      if r[0] == "decode"]
        if traced:
            run.window["traced_decode_live_positions_mean"] = \
                sum(r[4] for r in traced) / len(traced)
    compiles0 = compiles.count
    run.setup_done()
    t0 = time.perf_counter()
    loop.serve_until(t0 + run.seconds)
    t1 = t0 + run.seconds
    server.close(drain=False)
    phase_means(run, t0, t1)
    win = window_numbers(run, loop, steps, t0, t1)
    win["compiles_in_window"] = compiles.count - compiles0
    run.window.update(win)
    # a request cut at the window's end is cancelled, not failed
    failed = sum(1 for r in loop.requests if r.future.done()
                 and not r.future.cancelled()
                 and r.future.exception(0) is not None)
    run.attempted, run.failed = len(loop.requests), failed
    run.log("window: %d tokens in %.3f s, %.2f tokens/s; %d gaps, p95 %.1f ms, "
            "p50 %.3f ms; %d passes over the slots, longest %.1f ms; %d "
            "prefill chunks; %d compiles in the window; %d requests sent, "
            "%d failed"
            % (win["output_tokens"], win["seconds"], win["output_tok_s"],
               win["itl_gaps"], win["itl_p95_ms"] or -1,
               win["itl_p50_ms"] or -1, win["decode_steps"],
               win.get("decode_step_ms_max", -1), win["prefill_chunks"],
               win["compiles_in_window"], run.attempted, failed))
    run.end_to_end["serve_output_tok_s"] = win["output_tok_s"]
    run.end_to_end["serve_itl_p95_ms"] = win["itl_p95_ms"]
    run.memory_peak = common.memory_peak_bytes(jax.local_devices(), run.log)
    run.tracer = tracer
    finished = [r for r in loop.requests if r.future.done()
                and not r.future.cancelled()
                and r.future.exception(0) is None]
    results = {id(r): r.future.result(0) for r in finished}
    wrong = sum(1 for r in finished
                if len(r.tokens) != r.want
                or results[id(r)]["tokens"] != r.tokens
                or len(results[id(r)].get("fixed_at", ())) != r.want
                or len(results[id(r)].get("confidence", ())) != r.want)
    sample = [(r, results[id(r)]) for r in check_sample(run, finished)
              if "confidence" in results[id(r)]]
    del engine, server, steps, net, loop
    gc.collect()
    t_check = time.perf_counter()
    rows = reference_numbers(run, arrays, sample)
    run.numbers = dict(numbers_of(rows), wrong_length=wrong)
    run.log("reference: %d requests, %d fixed positions compared in %.1f s; "
            "the widest log-probability gaps (router margins): %s" % (
                len(sample), len(rows), time.perf_counter() - t_check,
                ", ".join("%.4f (margin %.4f)" % (r[3], r[2]) for r in
                          sorted(rows, key=lambda r: -r[3])[:8])))
    run.sample, run.params, run.rows = sample, arrays, rows
