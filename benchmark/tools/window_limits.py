"""Readings that the window/full-attention (``exaone_moe``) serving
cell's limits are set from, taken on the chip at the cell's own size,
several seeds in one process:

    python3 benchmark/tools/window_limits.py --workload <name> \\
        --seeds 1,2,3 [--controls 1,2] [--witness 1] [--faults 3] \\
        [--only window_127,...] [--seconds s]

As ``tools/mtp_limits.py`` (whose control and report this file uses):
for every seed it serves the cell's traffic for ``--seconds`` through
the cell's own driver (``drivers/serve_window.py``) and prints the
program's numbers; for the seeds under ``--controls`` the plain
reference computed in fp8 stands in the program's place on the very
requests and slots the program served (one precision below
``bf16_mixed``: the control), under ``--witness`` in the program's own
bfloat16.  For the seeds under ``--faults`` it serves once more a fault,
each planted in the PROGRAM's network as it is handed its weights (the
reference keeps the sound mathematics, and neither the program nor the
driver has a switch for any of them):

* ``window_127`` / ``window_129``: the windowed layers attend 127 / 129
  positions (the band's width in ``HybridDecoderLM._gqa``; the rings
  keep their rows);
* ``rope_on_full``: the full-attention blocks (the trunk's and the
  draft module's) rotate their queries and keys like the windowed ones;
* ``no_qk_norm``: the RMSNorm of every q and k head is left out;
* ``ring_one_row_short``: the rings hold ``window - 1`` rows.  The
  engine attends before it writes, so ``window - 1 + spec_k`` rows are
  the least a verify step can do with: with 127 the row of the draft
  rejected at ``p + 1`` lies where position ``p - 126`` lay, which the
  query at ``p + 1`` still attends.  (An engine that wrote first would
  fail one row earlier, at exactly 128; this one serves 128 right, as
  ``tests/test_window_cache.py`` shows.)

A line carries no verdict: ``tests/test_window_bench.py`` judges every
line of ``benchmark/limits/<workload>.readings.jsonl`` by the committed
limits.  Every line also goes to ``chiprun_out/window_readings.jsonl``.
The benchmark's own runs never run this; ``PERF.md`` records what it
printed."""
import argparse
import gc
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "benchmark"),
           os.path.join(ROOT, "benchmark", "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402
import mtp_limits  # noqa: E402

FAULTS = ("window_127", "window_129", "rope_on_full", "no_qk_norm",
          "ring_one_row_short")


def plant(net, kind):
    """Plant ``kind`` in the program's network (and, for the ring, in
    the rule the engine sizes it by); returns what takes it out."""
    from mxnet_tpu.ops import attention_rows

    undo = []
    if kind in ("window_127", "window_129"):
        net._sizes["window"] = int(kind.rsplit("_", 1)[1])
    elif kind == "rope_on_full":
        net._rotary = ("swa", "gqa")
    elif kind == "no_qk_norm":
        net._qk_norm = False
    elif kind == "ring_one_row_short":
        sound = attention_rows.ring_rows
        attention_rows.ring_rows = \
            lambda window, spec_k=0, itemsize=2: int(window) - 1
        undo.append(lambda: setattr(attention_rows, "ring_rows", sound))
    else:
        raise SystemExit("no fault %r" % kind)
    return undo


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
        # before the engine is built over it
        def faulty(net, specs, arrays):
            undo.extend(plant(net, fault))
            return sound(net, specs, arrays)

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
    from benchmark.drivers import serve_hybrid, serve_mtp, serve_window

    trunk, draft = mtp_limits.control_gaps(run, quant)
    numbers = serve_hybrid.sample_numbers(trunk)
    numbers["logit_gap_max"] = float(max(g.max() for g in trunk))
    numbers.update(serve_mtp.draft_numbers(draft))
    numbers.update(serve_window.cache_numbers(run, run.params, run.taken,
                                              quant))
    return numbers


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
    args = ap.parse_args()
    from benchmark.lib import manifest, quant

    def ints(text):
        return [int(s) for s in text.split(",") if s]

    bench_run.place_caches()
    man = manifest.manifest()
    cell = manifest.workload(man, args.workload)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "window_readings.jsonl"),
               "a")

    def let_go(run):
        run.params = run.sample = run.taken = run.ref_logits = None
        gc.collect()

    for seed in ints(args.seeds):
        run = one_run(args, man, cell, seed)
        mtp_limits.report(run, "program", dict(run.numbers), out)
        for who, fn, seeds in (
                ("control_fp8", quant.fp8, args.controls),
                ("witness_bf16", quant.bf16, args.witness)):
            if seed in ints(seeds):
                mtp_limits.report(run, who, control_numbers(run, fn), out)
        let_go(run)
        del run
        if seed in ints(args.faults):
            for kind in args.only.split(","):
                run = one_run(args, man, cell, seed, fault=kind)
                mtp_limits.report(run, "fault_" + kind, dict(run.numbers),
                                  out)
                let_go(run)
                del run


if __name__ == "__main__":
    main()
