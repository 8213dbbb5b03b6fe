"""Readings that the hybrid (``bailing_hybrid``) serving cell's limits are
set from, taken on the chip at the cell's own size, several seeds in one
process:

    python3 benchmark/tools/hybrid_limits.py --workload <name> \\
        --seeds 1,2,3 [--controls 1,2] [--witness 1] [--faults 3] \\
        [--seconds s]

For every seed it serves the cell's traffic for ``--seconds`` through the
cell's own driver (``drivers/serve_hybrid.py``) and prints the program's
numbers (what a run compares, and what it logs).  For the seeds under
``--controls`` it also puts the plain reference in the program's place
on the very requests and slots the program served, computed in fp8 (both
operands of every matrix product; one precision below ``bf16_mixed``:
the control); for those under ``--witness`` computed in the program's
own bfloat16 (the witness: where a sound program reads).  For the
seeds under ``--faults`` it serves four more times, each with one fault
planted in the weights the PROGRAM is handed (the reference keeps the
sound ones, and neither the program nor the driver has a switch for
it), each in ONE layer:

* ``kda_no_decay``: one KDA layer's decay gate reads ``g = 0`` (its
  ``W_f`` zero, its ``dt_bias`` so low that the sigmoid is an exact 0):
  that layer's state never forgets;
* ``no_shared_expert``: one layer's shared expert adds nothing (its down
  matrix zero);
* ``mla_no_rope_scores``: the rotary part of MLA's scores is left out
  where the latent pages hold it (the ``qk_rope_head_dim`` rows of
  ``W_dkv`` zero: the cached ``k_r`` is 0, so ``q_r . k_r`` is 0
  whatever the positions);
* ``mla_no_rope_queries``: the same part left out on the query's side
  (the ``qk_rope_head_dim`` rows a head of ``W_q`` zero).  Recorded as
  what the comparison CANNOT see: at random weights the one MLA layer
  averages thousands of values and adds half a percent of the residual
  stream, so what its scores do reaches neither a logit nor a later
  layer's state; ``tests/test_hybrid_decoder.py`` holds that
  arithmetic at float32.

A line carries no verdict: ``tests/test_hybrid_bench.py`` judges every
line of ``benchmark/limits/<workload>.readings.jsonl`` by the committed
limits, as a run would be judged (the verdict under the limits of the
tree that ran is printed beside the line, for the eye).  Every line also
goes to ``chiprun_out/hybrid_readings.jsonl``.  The benchmark's own runs
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

FAULT_LAYER = 3     # a KDA expert layer of the cut (and of the rehearsal)


def planted(cfg, specs, arrays, kind):
    """The flat weights with one fault planted; every other leaf is the
    array handed in."""
    import jax.numpy as jnp

    names = [n for n, _s, _k in specs]
    out = list(arrays)

    def put(name, fn):
        i = names.index(name)
        out[i] = fn(out[i])

    h = "h%d_" % FAULT_LAYER
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    if kind == "kda_no_decay":
        put(h + "decay_weight", jnp.zeros_like)
        put(h + "decay_dt_bias", lambda a: jnp.full_like(a, -1e4))
    elif kind == "no_shared_expert":
        put(h + "shared_down_weight", jnp.zeros_like)
    elif kind == "mla_no_rope_scores":
        name = next(n for n in names if n.endswith("kv_down_weight"))
        rope_row = jnp.arange(arrays[names.index(name)].shape[0]) \
            >= cfg["kv_lora_rank"]
        put(name, lambda a: jnp.where(rope_row[:, None], 0, a)
            .astype(a.dtype))
    elif kind == "mla_no_rope_queries":
        name = next(n for n in names if n.endswith("proj_q_weight"))
        rope_row = (jnp.arange(arrays[names.index(name)].shape[0])
                    % (dn + dr)) >= dn
        put(name, lambda a: jnp.where(rope_row[:, None], 0, a)
            .astype(a.dtype))
    else:
        raise SystemExit("no fault %r" % kind)
    return out


FAULTS = ("kda_no_decay", "no_shared_expert", "mla_no_rope_scores",
          "mla_no_rope_queries")


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
    if fault is not None:
        # the program's network takes the faulty weights; the driver
        # keeps the sound ones for the reference
        programs.set_weights = lambda net, specs, arrays: sound(
            net, specs, planted(run.cfg, specs, arrays, fault))
    try:
        importlib.import_module(
            "benchmark.drivers." + run.traffic["driver"]).main(run)
    finally:
        programs.set_weights = sound
    return run


def control_gaps(run, quant):
    """``serve_lm.reference_gaps`` with the reference and the control in
    two programs, one after the other: at 9216 positions the two
    forward passes in one program want 6.9 GB of temporaries beside
    9.8 GB of weights.  For every served token of the sample: the gap
    by which the token that ``quant``'s precision puts first lies below
    the reference's best, both over the prompt with the served tokens
    teacher-forced.  The reference's logits are kept on the host
    (``run.ref_logits``) for the next control of the same run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import weights

    cfg, traffic = run.cfg, run.traffic
    fam = weights.family(cfg)
    length, most = traffic["cache_len"], traffic["answer_len"]["hi"]

    def logits(fn):
        return jax.jit(lambda params, tokens, positions:
                       fam.logits_at(cfg, params, tokens, positions, fn)[0])

    @jax.jit
    def gaps_of(ref, other):
        chosen = jnp.take_along_axis(
            ref, jnp.argmax(other, axis=-1)[:, None], axis=-1)[:, 0]
        return jnp.max(ref, axis=-1) - chosen

    feeds = []
    for req in run.sample:
        n, m = len(req.prompt), len(req.tokens)
        seq = np.zeros((1, length), np.int32)
        seq[0, :n] = req.prompt
        seq[0, n:n + m - 1] = req.tokens[:m - 1]
        feeds.append((seq, np.minimum(n - 1 + np.arange(most), length - 1)
                      .astype(np.int32), m))
    if getattr(run, "ref_logits", None) is None:
        plain = logits(None)
        run.ref_logits = [np.asarray(plain(run.params, seq, pos))
                          for seq, pos, _m in feeds]
    control = logits(quant)
    return [np.asarray(gaps_of(ref, control(run.params, seq, pos)))[:m]
            for ref, (seq, pos, m) in zip(run.ref_logits, feeds)]


def control_numbers(run, quant):
    """What a run compares, with the reference computed in ``quant``'s
    precision in the program's place."""
    from benchmark.drivers import serve_hybrid

    gaps = control_gaps(run, quant)
    numbers = serve_hybrid.sample_numbers(gaps)
    numbers["logit_gap_max"] = float(max(g.max() for g in gaps))
    numbers.update(serve_hybrid.cache_numbers(run, run.params, run.taken,
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
        "output_tok_s": run.window.get("output_tok_s"), **numbers})
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
    out = open(os.path.join(ROOT, "chiprun_out", "hybrid_readings.jsonl"),
               "a")
    for seed in ints(args.seeds):
        run = one_run(args, man, cell, seed)
        report(run, "program", dict(run.numbers), out)
        for who, fn, seeds in (
                ("control_fp8", quant.fp8, args.controls),
                ("witness_bf16", quant.bf16, args.witness)):
            if seed in ints(seeds):
                report(run, who, control_numbers(run, fn), out)
        run.params = run.sample = run.taken = run.ref_logits = None
        del run
        gc.collect()
        if seed in ints(args.faults):
            for kind in FAULTS:
                run = one_run(args, man, cell, seed, fault=kind)
                report(run, "fault_" + kind, dict(run.numbers), out)
                run.params = run.sample = run.taken = run.ref_logits = None
                del run
                gc.collect()


if __name__ == "__main__":
    main()
