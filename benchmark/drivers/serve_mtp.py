"""The serving driver of a model that drafts for itself (family
``deepseek_v3``: a multi-token-prediction module beside a trunk of
latent-attention layers): ``serve_lm``'s run, whole (its closed loop,
window and sample), over an engine built with the traffic's ``spec_k``
(1: every decode call is a verify step of the current token and the
model's own draft), under ``serve_hybrid``'s comparison, which reads
every token of the sample and every layer's cache, and one number more.

* ``logit_gap_mean`` over the served tokens of the sample, teacher-forced
  through the plain reference (``logit_gap_max``, ``logit_gap_p90`` and
  ``off_first_choice_share`` logged: a 256-way sigmoid router times 2.5
  gives the maximum ``ling3flash_serve_longdoc``'s heavy tail).
* ``latent_rows_gap_max``: when the window has closed, ``check_slots``
  slots' caches are read off the engine (``serve_hybrid.tap_caches``):
  the trunk's five layers' rows and the draft module's, ``[RMSNorm(c) |
  rope(k_r)]`` a position, as thousands of chunks and verify steps left
  them, rejected rows overwritten; the largest ``|rows - ref| / |ref|``
  over layers and slots against the reference's ``caches`` of the same
  ids (the draft module's last row was fed the id after the cached
  ones: the snapshot's ``next_token``).
* ``draft_logit_gap_mean``: every served token came with a draft, the
  draft module's choice at the row the token was chosen at (the
  result's ``drafts``).  The reference's draft module is run over the
  same ids, and the gap is that by which its logit of the token the
  program drafted lies under its best, mean over the sample's tokens:
  what a fault in the module (its projection, its block, its rows)
  moves, and nothing in the trunk's logits does.
* ``wrong_length``, exact."""
import numpy as np

from benchmark.drivers import serve_hybrid, serve_lm


def build(run):
    """``serve_lm.build`` with the traffic's speculation depth."""
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
        prefill_chunk=traffic["prefill_chunk"], spec_k=traffic["spec_k"],
        prefix_share=traffic["prefix_share"],
        dtype_policy=cfg["dtype_policy"],
        sampling=generate.SamplingConfig(greedy=True))
    server = generate.TokenServer(
        engine, queue_depth=4 * traffic["clients"], deadline_ms=0,
        max_new_tokens=traffic["answer_len"]["hi"])
    run.log("engine and server made (spec_k %d, drafts from the %s)" % (
        engine.spec_k, "model" if engine.drafted(0) is not None
        else "host"))
    return net, arrays, engine, server


def sample_feeds(run, sample):
    """For every request of the sample: (the prompt with ALL its served
    tokens (1, cache_len), the positions its tokens were chosen at,
    the served tokens, the drafts it was served with, how many)."""
    length, most = run.traffic["cache_len"], run.traffic["answer_len"]["hi"]
    feeds = []
    for req in sample:
        n, m = len(req.prompt), len(req.tokens)
        seq = np.zeros((1, length), np.int32)
        seq[0, :n] = req.prompt
        fit = min(m, length - n)
        seq[0, n:n + fit] = req.tokens[:fit]
        pos = np.minimum(n - 1 + np.arange(most), length - 1).astype(np.int32)
        served, drafted = np.zeros(most, np.int32), np.zeros(most, np.int32)
        served[:m] = req.tokens
        drafts = req.future.result(0)["drafts"]
        drafted[:m] = drafts[:m]
        feeds.append((seq, pos, served, drafted, m))
    return feeds


def reference_gaps(run, params, sample, quant=None):
    """``serve_lm.reference_gaps`` and the draft module's in one pass of
    the reference over each request: for every served token the gap by
    which its logit lies below the reference's best, and (kept on
    ``run.draft_gaps``) the same for the token the program drafted
    beside it under the reference's draft module."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights

    cfg = run.cfg
    fam = weights.family(cfg)

    @jax.jit
    def gaps_of(params, tokens, positions, served, drafted):
        trunk, draft = fam.both_logits_at(cfg, params, tokens, positions)
        if quant is not None:
            mine = fam.both_logits_at(cfg, params, tokens, positions, quant)
            served = jnp.argmax(mine[0][0], axis=-1)
            drafted = jnp.argmax(mine[1][0], axis=-1)

        def under(ref, chosen):
            got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
            return jnp.max(ref, axis=-1) - got

        return under(trunk[0], served), under(draft[0], drafted)

    out, run.draft_gaps = [], []
    for seq, pos, served, drafted, m in sample_feeds(run, sample):
        g, d = jax.device_get(gaps_of(params, seq, pos, served, drafted))
        out.append(g[:m])
        # (a request that filled its slot has no id after its last
        # token's row in the sequence: that one draft is left out)
        run.draft_gaps.append(d[:min(m, seq.shape[1] - int(pos[0]) - 1)])
    return out


def cache_numbers(run, params, taken, quant=None):
    """``serve_hybrid.cache_numbers`` for blocks that keep rows alone,
    the draft module's among them: the reference's ``caches`` of a
    snapshot's ids and the id after them, and the largest relative gap
    of a layer's rows.  With ``quant`` the reference in the control's
    precision stands in the snapshot's place."""
    import jax

    from benchmark.lib import weights

    cfg, length = run.cfg, run.traffic["cache_len"]
    fam = weights.family(cfg)
    caches = {None: jax.jit(lambda p, t, n: fam.caches(cfg, p, t, n))}
    if quant is not None:
        caches[quant] = jax.jit(
            lambda p, t, n: fam.caches(cfg, p, t, n, quant))
    rows = []
    for snap in taken:
        n = snap["position"]
        if not n:
            continue
        seq = np.zeros((1, length), np.int32)
        seq[0, :n] = snap["tokens"]
        # the draft module's row of the last position was fed the next
        # id; a slot filled to its end has none, and that row is left out
        upto = [n] * len(snap["layers"])
        if n < length and snap["next_token"] is not None:
            seq[0, n] = snap["next_token"]
        else:
            upto[-1] = n - 1
        want = jax.device_get(caches[None](params, seq, np.int32(n)))
        got = snap["layers"] if quant is None else [
            c[0] for c in jax.device_get(caches[quant](params, seq,
                                                       np.int32(n)))]
        gaps = [serve_hybrid._gap(mine[:k], ref[0, :k])
                for mine, ref, k in zip(got, want, upto)]
        rows.extend(gaps)
        run.log("%d positions cached, rows layer by layer (the draft "
                "module's last): %s" % (n, " ".join("%.4f" % g
                                                    for g in gaps)))
    return {"latent_rows_gap_max": max(rows, default=None)}


def draft_numbers(gaps):
    if not gaps or not sum(len(g) for g in gaps):
        return {"draft_logit_gap_mean": None}
    every = np.concatenate([np.asarray(g, np.float64) for g in gaps])
    return {"draft_logit_gap_mean": float(every.mean()),
            "draft_logit_gap_max": float(every.max()),
            "draft_off_first_choice_share": float((every > 0).mean())}


def main(run):
    """``serve_lm.main`` over this file's engine, its comparison widened
    as ``serve_hybrid``'s is: the gaps are kept, the caches read when the
    window has closed."""
    gaps, taken = [], []
    plain_gaps, plain_build = serve_lm.reference_gaps, serve_lm.build

    def keeping(run_, params, sample, quant=None):
        gaps.append(reference_gaps(run_, params, sample, quant))
        return gaps[-1]

    def building(run_):
        built = build(run_)
        serve_hybrid.tap_caches(run_, built[2], taken)
        return built

    serve_lm.reference_gaps, serve_lm.build = keeping, building
    try:
        serve_lm.main(run)
    finally:
        serve_lm.reference_gaps, serve_lm.build = plain_gaps, plain_build
    more = serve_hybrid.sample_numbers(gaps[-1])
    more["logit_gap_max"] = run.numbers.get("logit_gap_max")
    more.update(draft_numbers(run.draft_gaps))
    more.update(cache_numbers(run, run.params, taken))
    run.numbers.update(more)
    run.taken = taken
    run.log("caches of %d slots (%s positions); " % (
        len(taken), ", ".join(str(s["position"]) for s in taken))
        + ", ".join("%s %s" % (k, "missing" if v is None else "%.6g" % v)
                    for k, v in more.items()))
