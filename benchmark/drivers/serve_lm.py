"""The LM serving driver: ``TokenServer.submit`` over a
``PagedGenerationEngine``, a closed loop of clients from one thread, every
token stamped where the client receives it."""
import gc
import queue
import time

import numpy as np

from benchmark.drivers import common


class Request:
    __slots__ = ("client", "prompt", "want", "tokens", "stamps", "future",
                 "t_submit")

    def __init__(self, client, prompt, want):
        self.client, self.prompt, self.want = client, prompt, want
        self.tokens, self.stamps = [], []
        self.future = self.t_submit = None


class EngineSteps:
    """Host stamps round each engine step, taken by wrapping the two
    calls the server's loop makes (engine-side stamps are the program's
    to add; PERF.md, Open questions)."""

    def __init__(self, engine):
        import jax

        self.engine = engine
        self.rows = []   # (kind, t_start, t_end, active slots, live positions)
        span = jax.profiler.TraceAnnotation
        decode, prefill = engine.decode_step, engine.prefill_step

        def decode_step():
            active = engine.active_slots()
            if not active:
                return decode()
            live = sum(engine.position(s) for s in active)
            t = time.perf_counter()
            with span("bench:engine.decode"):
                out = decode()
            self.rows.append(("decode", t, time.perf_counter(), len(active),
                              live))
            return out

        def prefill_step(*a, **kw):
            if not engine.pending_prefill():
                return prefill(*a, **kw)
            t = time.perf_counter()
            with span("bench:engine.prefill"):
                out = prefill(*a, **kw)
            self.rows.append(("prefill", t, time.perf_counter(), 0, 0))
            return out

        engine.decode_step, engine.prefill_step = decode_step, prefill_step

    def inside(self, t0, t1):
        return [r for r in self.rows if t0 < r[2] <= t1]


def build(run):
    import jax

    from mxnet_tpu import generate
    from benchmark import programs
    from benchmark.lib import weights

    cfg, traffic = run.cfg, run.traffic
    fam = weights.family(cfg)
    net = programs.program(cfg).build_net(cfg)
    run.log("network built")
    arrays = weights.make_params(cfg, run.seed)
    jax.block_until_ready(arrays)
    run.log("weights made")
    programs.set_weights(net, fam.param_specs(cfg), arrays)
    engine = generate.PagedGenerationEngine(
        net, slots=traffic["slots"], cache_len=traffic["cache_len"],
        page_size=traffic["page_size"], num_pages=traffic["num_pages"],
        prefill_chunk=traffic.get("prefill_chunk"), spec_k=0,
        prefix_share=traffic.get("prefix_share", True),
        dtype_policy=cfg["dtype_policy"],
        sampling=generate.SamplingConfig(greedy=True))
    server = generate.TokenServer(
        engine, queue_depth=4 * traffic["clients"], deadline_ms=0,
        max_new_tokens=traffic["answer_len"]["hi"])
    run.log("engine and server made")
    return net, arrays, engine, server


class ClosedLoop:
    """``clients`` callers, each sending its next request when the last
    one finishes; all driven from the calling thread, woken by the
    server's own token callback."""

    def __init__(self, run, server):
        import jax

        from benchmark.lib import lengths

        self.server = server
        self.stream = lengths.request_stream(run.traffic, run.seed,
                                             run.cfg["vocab_size"])
        self.done = queue.Queue()
        self.requests = []
        self.span = jax.profiler.TraceAnnotation

    def submit(self, client):
        prompt, want = next(self.stream)
        req = Request(client, prompt, want)

        def on_token(tok, req=req):
            req.stamps.append(time.perf_counter())
            req.tokens.append(int(tok))
            if len(req.tokens) == req.want:
                self.done.put(req)

        req.t_submit = time.perf_counter()
        req.future = self.server.submit(prompt, max_new_tokens=want,
                                        on_token=on_token)
        self.requests.append(req)
        return req

    def serve_until(self, deadline):
        """Resubmit for every client whose request finishes, until
        ``deadline`` (a perf_counter time) or, with ``deadline`` a
        callable, until it returns true."""
        while True:
            now = time.perf_counter()
            if callable(deadline):
                self.raise_if_failed()
                if deadline():
                    return
                wait = 0.02
            else:
                if now >= deadline:
                    return
                wait = min(0.05, deadline - now)
            try:
                with self.span("bench:loadgen.wait"):
                    req = self.done.get(timeout=wait)
            except queue.Empty:
                continue
            self.submit(req.client)

    def raise_if_failed(self):
        """A request that resolved with an error (a broken decode loop
        fails them all) ends the run, rather than leaving it to wait."""
        for r in self.requests:
            if r.future.done() and not r.future.cancelled() \
                    and r.future.exception(0) is not None:
                raise SystemExit("request failed: %r" % (r.future.exception(0),))


def window_numbers(run, loop, steps, t0, t1):
    """Everything the window's stamps give."""
    from benchmark.lib import flops

    cfg = run.cfg
    tokens, gaps, contexts = 0, [], []
    for req in loop.requests:
        n = len(req.prompt)
        for j, t in enumerate(req.stamps):
            if t0 < t <= t1:
                tokens += 1
                if j == 0:
                    contexts.extend(range(n))      # its prompt ran here
                else:
                    contexts.append(n + j - 1)
                    if req.stamps[j - 1] > t0:
                        gaps.append(t - req.stamps[j - 1])
    rows = steps.inside(t0, t1)
    dec = [r for r in rows if r[0] == "decode"]
    pre = [r for r in rows if r[0] == "prefill"]
    slots = run.traffic["slots"]
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
            / (slots * len(dec)),
            "decode_live_positions_mean": sum(r[4] for r in dec) / len(dec),
            "prefill_share": 100.0 * len(pre) / (len(pre) + len(dec))})
    return out


def check_sample(run, requests):
    """The requests the comparison reads: a sample drawn from the seed of
    those the window finished, with the longest in it."""
    done = [r for r in requests if len(r.tokens) >= r.want]
    if not done:
        return []
    rng = np.random.default_rng([int(run.seed) & 0xFFFFFFFF, 11])
    k = min(run.traffic["check_requests"], len(done))
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].tokens))
    picks = {longest}
    for i in rng.permutation(len(done)):
        if len(picks) >= k:
            break
        picks.add(int(i))
    return [done[i] for i in sorted(picks)]


def reference_gaps(run, params, sample, quant=None):
    """For every served token of the sample: the gap by which its logit
    lies below the reference's best, the reference run once over each
    prompt with its served tokens (float32, ``highest``).  With ``quant``
    the token judged is not the served one but the one the control's
    precision puts first at that position."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights

    cfg, traffic = run.cfg, run.traffic
    fam = weights.family(cfg)
    length, most = traffic["cache_len"], traffic["answer_len"]["hi"]

    @jax.jit
    def gaps_of(params, tokens, positions, served):
        ref = fam.logits_at(cfg, params, tokens, positions)[0]
        if quant is not None:
            served = jnp.argmax(fam.logits_at(cfg, params, tokens, positions,
                                              quant)[0], axis=-1)
        chosen = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        return jnp.max(ref, axis=-1) - chosen

    out = []
    for req in sample:
        n, m = len(req.prompt), len(req.tokens)
        seq = np.zeros((1, length), np.int32)
        seq[0, :n] = req.prompt
        seq[0, n:n + m - 1] = req.tokens[:m - 1]
        pos = np.minimum(n - 1 + np.arange(most), length - 1).astype(np.int32)
        served = np.zeros(most, np.int32)
        served[:m] = req.tokens
        g = np.asarray(gaps_of(params, seq, pos, served))[:m]
        out.append(g)
    return out


def main(run):
    import jax

    compiles = common.CompileCounter()
    t_build = time.perf_counter()
    net, arrays, engine, server = build(run)
    steps = EngineSteps(engine)
    run.window["setup_build_s"] = time.perf_counter() - t_build
    traffic = run.traffic
    loop = ClosedLoop(run, server)
    t_compile = time.perf_counter()
    # warm-up: the first clients fill the slots, which runs the engine's two
    # shapes (a prefill chunk, a decode step over all slots) and leaves
    # the slots full
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
    win = window_numbers(run, loop, steps, t0, t1)
    win["compiles_in_window"] = compiles.count - compiles0
    run.window.update(win)
    # a request cut at the window's end is cancelled, not failed
    failed = sum(1 for r in loop.requests if r.future.done()
                 and not r.future.cancelled()
                 and r.future.exception(0) is not None)
    run.attempted, run.failed = len(loop.requests), failed
    run.log("window: %d tokens in %.3f s, %.2f tokens/s; %d gaps, p95 %.1f ms; "
            "%d decode steps, longest %.1f ms; %d prefill chunks; %d compiles "
            "in the window; %d requests sent, %d failed"
            % (win["output_tokens"], win["seconds"], win["output_tok_s"],
               win["itl_gaps"], win["itl_p95_ms"] or -1, win["decode_steps"],
               win.get("decode_step_ms_max", -1), win["prefill_chunks"],
               win["compiles_in_window"], run.attempted, failed))
    run.end_to_end["serve_output_tok_s"] = win["output_tok_s"]
    run.end_to_end["serve_itl_p95_ms"] = win["itl_p95_ms"]
    run.memory_peak = common.memory_peak_bytes(jax.local_devices(), run.log)
    run.tracer = tracer
    sample = check_sample(run, loop.requests)
    finished = [r for r in loop.requests if r.future.done()
                and not r.future.cancelled()]
    wrong = sum(1 for r in finished if len(r.tokens) != r.want)
    del engine, server, steps, net, loop
    gc.collect()
    gaps = reference_gaps(run, arrays, sample)
    served = sum(len(g) for g in gaps)
    run.log("reference: %d requests, %d served tokens compared" % (
        len(sample), served))
    run.numbers = {
        "logit_gap_max": float(max(g.max() for g in gaps)) if gaps else None,
        "wrong_length": wrong}
    run.sample, run.params = sample, arrays
