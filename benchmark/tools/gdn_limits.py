"""Readings that the Gated DeltaNet (``olmo_hybrid``) serving cell's
limits are set from, taken on the chip at the cell's own size, several
seeds in one process:

    python3 benchmark/tools/gdn_limits.py --workload <name> \\
        --seeds 1,2,3 [--controls 1,2] [--witness 1] [--faults 3] \\
        [--only gdn_no_decay,...] [--seconds s] [--out file]

As ``tools/hybrid_limits.py`` (whose control and report this file
uses): for every seed it serves the cell's traffic for ``--seconds``
through the cell's own driver (``drivers/serve_gdn.py``) and prints the
program's numbers; for the seeds under ``--controls`` the plain
reference computed in fp8 stands in the program's place on the very
requests and slots the program served (one precision below
``bf16_mixed``: the control), under ``--witness`` in the program's own
bfloat16.  For the seeds under ``--faults`` it serves once more a
fault, each in ONE layer of the PROGRAM (the reference keeps the sound
mathematics, and neither the program nor the cell's driver has a
switch for any of them):

* ``gdn_no_neg_eigval``: GDN layer ``GDN_LAYER`` takes ``beta =
  sigmoid(W_b x)``, in [0, 1], without the factor 2 of
  ``linear_allow_neg_eigval``;
* ``gdn_no_decay``: the same layer's decay reads ``g = 0`` (its ``W_a``
  zero and its ``dt_bias`` so low that the softplus is an exact 0, in
  the weights it is handed): its state never forgets;
* ``pre_norm_block``: block ``PRE_NORM_LAYER`` runs pre-norm, ``h = x +
  Mix(RMSNorm(x))``, ``y = h + MLP(RMSNorm(h))``, with the same norm
  weights;
* ``qk_norm_per_head``: full layer ``FULL_LAYER`` norms q and k a head
  at a time (the whole projection's weight read a head at a time)
  where the model norms the whole projection.

A line carries no verdict: ``tests/test_olmo_hybrid_bench.py`` judges
every line of ``benchmark/limits/<workload>.readings.jsonl`` by the
committed limits.  Every line is also appended to ``--out``
(``.bench_out/gdn_readings.jsonl`` under the checkout by default).  The
benchmark's own runs never run this; ``PERF.md`` records what it
printed."""
import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "benchmark"),
           os.path.join(ROOT, "benchmark", "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402
import hybrid_limits  # noqa: E402

FAULTS = ("gdn_no_neg_eigval", "gdn_no_decay", "pre_norm_block",
          "qk_norm_per_head")
# the layers of the cut (and of the rehearsal) a fault is planted in:
# a GDN layer, another GDN block, the first full layer
GDN_LAYER, PRE_NORM_LAYER, FULL_LAYER = 1, 2, 3


def planted(specs, arrays, kind):
    """The flat weights with a weight fault planted; every other leaf is
    the array handed in."""
    import jax.numpy as jnp

    names = [n for n, _s, _k in specs]
    out = list(arrays)
    if kind == "gdn_no_decay":
        h = "h%d_" % GDN_LAYER
        for name, fn in ((h + "decay_weight", jnp.zeros_like),
                         (h + "decay_dt_bias",
                          lambda a: jnp.full_like(a, -1e4))):
            i = names.index(name)
            out[i] = fn(out[i])
    return out


def plant(net, kind):
    """Plant ``kind`` in the program's network, for its one layer: the
    block's own call runs with the fault, every other as built.
    Returns what takes it out."""
    layer = {"gdn_no_neg_eigval": GDN_LAYER, "gdn_no_decay": None,
             "pre_norm_block": PRE_NORM_LAYER,
             "qk_norm_per_head": FULL_LAYER}
    if kind not in layer:
        raise SystemExit("no fault %r" % kind)
    if layer[kind] is None:
        return []
    sound_block, sound_gqa = net._block, net._gqa
    mine = net._layers[layer[kind]]
    dh = net._sizes["dh"]

    def per_head_gqa(n, p, *args):
        p = list(p)
        p[3], p[4] = p[3].reshape((-1, dh)), p[4].reshape((-1, dh))
        return sound_gqa(n, p, *args)

    def block(x, params, *args):
        if params is not mine:
            return sound_block(x, params, *args)
        was = (net._neg_eigval, net._post_norm, net._qk_norm)
        if kind == "gdn_no_neg_eigval":
            net._neg_eigval = False
        elif kind == "pre_norm_block":
            net._post_norm = False
        else:
            net._qk_norm, net._gqa = "head", per_head_gqa
        try:
            return sound_block(x, params, *args)
        finally:
            net._neg_eigval, net._post_norm, net._qk_norm = was
            net._gqa = sound_gqa

    net._block = block
    return [lambda: vars(net).pop("_block", None)]


def one_run(args, man, cell, seed, fault=None):
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
        # the program's network takes the fault as it takes its weights,
        # before the engine is built over it; the cell's driver keeps the
        # sound weights for the reference
        def faulty(net, specs, arrays):
            undo.extend(plant(net, fault))
            return sound(net, specs, planted(specs, arrays, fault))

        programs.set_weights = faulty
    try:
        importlib.import_module(
            "benchmark.drivers." + run.traffic["driver"]).main(run)
    finally:
        programs.set_weights = sound
        for fn in undo:
            fn()
    return run


def control_numbers(run, quant):
    """What a run compares, with the reference computed in ``quant``'s
    precision in the program's place."""
    from benchmark.drivers import serve_gdn

    return serve_gdn.renamed(hybrid_limits.control_numbers(run, quant))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--witness", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--only", default=",".join(FAULTS))
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, ".bench_out",
                                                  "gdn_readings.jsonl"))
    args = ap.parse_args()
    from benchmark.lib import manifest, quant

    def ints(text):
        return [int(s) for s in text.split(",") if s]

    bench_run.place_caches()
    man = manifest.manifest()
    cell = manifest.workload(man, args.workload)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out = open(args.out, "a")

    def done(run):
        run.params = run.sample = run.taken = run.ref_logits = None
        gc.collect()

    for seed in ints(args.seeds):
        run = one_run(args, man, cell, seed)
        hybrid_limits.report(run, "program", dict(run.numbers), out)
        for who, fn, seeds in (
                ("control_fp8", quant.fp8, args.controls),
                ("witness_bf16", quant.bf16, args.witness)):
            if seed in ints(seeds):
                hybrid_limits.report(run, who, control_numbers(run, fn), out)
        done(run)
        if seed in ints(args.faults):
            for kind in [k for k in args.only.split(",") if k]:
                run = one_run(args, man, cell, seed, fault=kind)
                hybrid_limits.report(run, "fault_" + kind, dict(run.numbers),
                                     out)
                done(run)
    print(json.dumps({"readings": os.path.relpath(out.name, ROOT)}))


if __name__ == "__main__":
    main()
