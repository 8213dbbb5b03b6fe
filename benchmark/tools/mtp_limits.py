"""Readings that the self-drafting (``deepseek_v3``) serving cell's limits
are set from, taken on the chip at the cell's own size, several seeds in
one process:

    python3 benchmark/tools/mtp_limits.py --workload <name> \\
        --seeds 1,2,3 [--controls 1,2] [--witness 1] [--faults 3] \\
        [--seconds s]

For every seed it serves the cell's traffic for ``--seconds`` through the
cell's own driver (``drivers/serve_mtp.py``) and prints the program's
numbers (what a run compares, and what it logs).  For the seeds under
``--controls`` it also puts the plain reference in the program's place
on the very requests and slots the program served, computed in fp8 (both
operands of every matrix product; one precision below ``bf16_mixed``:
the control); for those under ``--witness`` computed in the program's
own bfloat16 (the witness: where a sound program reads).  For the seeds
under ``--faults`` it serves four more times, each with one fault
planted in what the PROGRAM is handed (the reference keeps the sound
weights and the sound mathematics, and neither the program nor the
driver has a switch for it), each in ONE block:

* ``mtp_no_hidden``: the draft module's ``h`` half is left out of
  ``W_eh``'s input (the columns of ``mtp_proj_weight`` that multiply
  ``RMSNorm_h(h)`` zero): the module drafts from the token alone;
* ``no_yarn_in_cached_k_r``: in one trunk layer the rotary part of the
  cached row is turned at the plain frequencies, YaRN's ramp left out
  (a wrapper round the model's rotation, in this process only, that
  drops the ramp for that layer's ``k_r``);
* ``no_mscale_queries``: ``m^2`` is left out of one trunk layer's score
  scale, on the query's side (that layer's ``W_qb`` divided by it);
* ``no_shared_expert``: one trunk layer's shared expert adds nothing
  (its down matrix zero).

A line carries no verdict: ``tests/test_mtp_bench.py`` judges every line
of ``benchmark/limits/<workload>.readings.jsonl`` by the committed
limits, as a run would be judged (the verdict under the limits of the
tree that ran is printed beside the line, for the eye).  Every line also
goes to ``chiprun_out/mtp_readings.jsonl``.  The benchmark's own runs
never run this; ``PERF.md`` records what it printed."""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import run as bench_run  # noqa: E402

FAULT_LAYER = 3     # a trunk expert layer of the cut (published layer 5)
FAULTS = ("mtp_no_hidden", "no_yarn_in_cached_k_r", "no_mscale_queries",
          "no_shared_expert")


def planted(cfg, specs, arrays, kind):
    """The flat weights with one fault planted; every other leaf is the
    array handed in (all of them for the fault that is planted in the
    model's code, :func:`plain_k_r`)."""
    import jax.numpy as jnp

    from benchmark.lib.reference import deepseek_v3 as ref

    names = [n for n, _s, _k in specs]
    out = list(arrays)

    def put(name, fn):
        i = names.index(name)
        out[i] = fn(out[i])

    h = "h%d_" % FAULT_LAYER
    if kind == "mtp_no_hidden":
        D = cfg["hidden_size"]
        put("mtp_proj_weight", lambda a: jnp.where(
            jnp.arange(2 * D)[None, :] < D, 0, a).astype(a.dtype))
    elif kind == "no_mscale_queries":
        m2 = ref.score_scale(cfg) / ref.score_scale(
            dict(cfg, rope_scaling=None))
        put(h + "proj_q_weight", lambda a: (
            a.astype(jnp.float32) / m2).astype(a.dtype))
    elif kind == "no_shared_expert":
        put(h + "shared_down_weight", jnp.zeros_like)
    elif kind != "no_yarn_in_cached_k_r":
        raise SystemExit("no fault %r" % kind)
    return out


def plain_k_r(net):
    """Plant ``no_yarn_in_cached_k_r`` in ``net``: while block
    ``FAULT_LAYER`` runs, the rotation of the one shared ``k_r`` (the
    array without a heads dimension) drops YaRN's ramp.  Returns the
    function that takes the fault out again."""
    from mxnet_tpu.gluon.model_zoo.language import hybrid_decoder as hd

    sound_block, sound_rope = net._block, hd._rope_pairs
    inside = []

    def block(x, params, *rest):
        if params is net._layers[FAULT_LAYER]:
            inside.append(True)
        try:
            return sound_block(x, params, *rest)
        finally:
            del inside[:]

    def rope(x, pos, theta, yarn=None):
        return sound_rope(x, pos, theta,
                          None if inside and x.ndim == 3 else yarn)

    net._block, hd._rope_pairs = block, rope

    def undo():
        del net._block
        hd._rope_pairs = sound_rope

    return undo


def one_run(args, man, cell, seed, fault=None):
    import importlib

    from benchmark import programs
    from benchmark.lib import manifest

    ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                            rehearse=args.rehearse)
    run = bench_run.Run(
        ns, man, cell,
        manifest.config(man, cell["config"], rehearse=args.rehearse),
        manifest.traffic(cell["traffic"], rehearse=args.rehearse),
        manifest.limits(cell["name"]))
    bench_run.find_devices(run)
    os.makedirs(run.out_dir, exist_ok=True)
    sound = programs.set_weights
    undo = []
    if fault is not None:
        # the program's network takes the fault; the driver keeps the
        # sound weights for the reference
        def faulty(net, specs, arrays):
            if fault == "no_yarn_in_cached_k_r":
                undo.append(plain_k_r(net))
            return sound(net, specs, planted(run.cfg, specs, arrays, fault))

        programs.set_weights = faulty
    try:
        importlib.import_module(
            "benchmark.drivers." + run.traffic["driver"]).main(run)
    finally:
        programs.set_weights = sound
        for fn in undo:
            fn()
    return run


def control_gaps(run, quant):
    """``serve_mtp.reference_gaps`` with the reference and the control in
    two programs, one after the other (two forward passes in one program
    want twice the temporaries beside 10.8 GB of weights).  For every
    served token of the sample: the gap by which the token that
    ``quant``'s precision puts first lies below the reference's best,
    for the trunk and for the draft module.  The reference's logits are
    kept on the host (``run.ref_logits``) for the next control of the
    same run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.drivers import serve_mtp
    from benchmark.lib import weights

    cfg = run.cfg
    fam = weights.family(cfg)

    def logits(fn):
        return jax.jit(lambda params, tokens, positions: tuple(
            a[0] for a in fam.both_logits_at(cfg, params, tokens,
                                             positions, fn)))

    @jax.jit
    def gaps_of(ref, other):
        chosen = jnp.take_along_axis(
            ref, jnp.argmax(other, axis=-1)[:, None], axis=-1)[:, 0]
        return jnp.max(ref, axis=-1) - chosen

    feeds = serve_mtp.sample_feeds(run, run.sample)
    if getattr(run, "ref_logits", None) is None:
        plain = logits(None)
        run.ref_logits = [jax.device_get(plain(run.params, seq, pos))
                          for seq, pos, _s, _d, _m in feeds]
    control = logits(quant)
    trunk, draft = [], []
    for (ref_t, ref_d), (seq, pos, _s, _d, m) in zip(run.ref_logits, feeds):
        mine_t, mine_d = control(run.params, seq, pos)
        trunk.append(np.asarray(gaps_of(ref_t, mine_t))[:m])
        draft.append(np.asarray(gaps_of(ref_d, mine_d))[
            :min(m, seq.shape[1] - int(pos[0]) - 1)])
    return trunk, draft


def control_numbers(run, quant):
    """What a run compares, with the reference computed in ``quant``'s
    precision in the program's place."""
    from benchmark.drivers import serve_hybrid, serve_mtp

    trunk, draft = control_gaps(run, quant)
    numbers = serve_hybrid.sample_numbers(trunk)
    numbers["logit_gap_max"] = float(max(g.max() for g in trunk))
    numbers.update(serve_mtp.draft_numbers(draft))
    numbers.update(serve_mtp.cache_numbers(run, run.params, run.taken,
                                           quant))
    return numbers


def report(run, who, numbers, out):
    from benchmark.lib import compare

    verdict = compare.judge(numbers, {k: v for k, v in run.limits.items()
                                      if k in numbers})[0]
    line = json.dumps({
        "workload": run.workload, "seed": run.seed, "who": who,
        "served_tokens": sum(len(r.tokens) for r in run.sample),
        "cached_positions": [s["position"] for s in run.taken],
        "output_tok_s": run.window.get("output_tok_s"),
        "itl_p95_ms": run.window.get("itl_p95_ms"), **numbers})
    print("READINGS", line, "| correct by this tree's limits:", verdict,
          flush=True)
    out.write(line + "\n")
    out.flush()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--witness", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args()
    from benchmark.lib import manifest, quant

    def ints(text):
        return [int(s) for s in text.split(",") if s]

    bench_run.place_caches()
    man = manifest.manifest()
    cell = manifest.workload(man, args.workload)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "mtp_readings.jsonl"), "a")

    def let_go(run):
        run.params = run.sample = run.taken = run.ref_logits = None
        gc.collect()

    for seed in ints(args.seeds):
        run = one_run(args, man, cell, seed)
        report(run, "program", dict(run.numbers), out)
        for who, fn, seeds in (
                ("control_fp8", quant.fp8, args.controls),
                ("witness_bf16", quant.bf16, args.witness)):
            if seed in ints(seeds):
                report(run, who, control_numbers(run, fn), out)
        let_go(run)
        del run
        if seed in ints(args.faults):
            for kind in FAULTS:
                run = one_run(args, man, cell, seed, fault=kind)
                report(run, "fault_" + kind, dict(run.numbers), out)
                let_go(run)
                del run


if __name__ == "__main__":
    main()
