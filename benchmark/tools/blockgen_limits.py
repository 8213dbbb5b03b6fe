"""Readings that the block-diffusion serving cell's limits are set
from, taken on the chip at the cell's own size, several seeds in one
process:

    python3 benchmark/tools/blockgen_limits.py --workload <name> \\
        --seeds 1,2,3 [--controls 1,2] [--faults 3] [--seconds s]

For every seed it serves the cell's traffic for ``--seconds`` and prints
the program's numbers (what a run compares).  For the seeds under
``--controls`` it also puts the plain reference in the program's place on
the very states the program served: computed in fp8 (both operands of
every matrix product; one precision below ``bf16_mixed``: the control)
and in the program's own bfloat16 (the witness).  For the seeds under
``--faults`` it serves three more times, each with one planted fault: a
commit pass whose K/V is not written, a denoise pass that writes the
pool, one expert's part left out.  Each is judged against the cell's
limits as a run would be: controls and faults have to come out not
correct.  Every compared position's (logit gap, confidence gap, router
margin) goes to ``chiprun_out/blockgen_rows.jsonl``, which is what the
near-tie margin is chosen from.  The benchmark's own runs never run
this; ``PERF.md`` records what it printed."""
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


def one_run(args, man, cell, seed, fault=None):
    from benchmark.drivers import serve_blockgen
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
    serve_blockgen.main(run, fault=fault)
    return run


def report(run, who, numbers, rows, out):
    from benchmark.lib import compare

    verdict = compare.judge(numbers, {k: v for k, v in run.limits.items()
                                      if k in numbers})[0]
    print("READINGS", json.dumps({
        "workload": run.workload, "seed": run.seed, "who": who,
        "correct": verdict, "positions": len(rows),
        "output_tok_s": run.window.get("output_tok_s"), **numbers}),
        flush=True)
    out.write(json.dumps({"seed": run.seed, "who": who, "rows": rows}) + "\n")
    out.flush()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args()
    from benchmark.drivers import serve_blockgen
    from benchmark.lib import manifest, quant

    def ints(text):
        return [int(s) for s in text.split(",") if s]

    bench_run.place_caches()
    man = manifest.manifest()
    cell = manifest.workload(man, args.workload)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "blockgen_rows.jsonl"), "a")
    for seed in ints(args.seeds):
        run = one_run(args, man, cell, seed)
        report(run, "program", run.numbers, run.rows, out)
        if seed in ints(args.controls):
            for who, fn in (("control_fp8", quant.fp8),
                            ("witness_bf16", quant.bf16)):
                rows = serve_blockgen.reference_numbers(
                    run, run.params, run.sample, quant=fn)
                report(run, who, serve_blockgen.numbers_of(rows),
                       rows, out)
        run.params = run.sample = None
        del run
        gc.collect()
        if seed in ints(args.faults):
            for kind in serve_blockgen.FAULTS:
                run = one_run(args, man, cell, seed, fault=kind)
                report(run, "fault_" + kind, run.numbers, run.rows, out)
                run.params = run.sample = None
                del run
                gc.collect()


if __name__ == "__main__":
    main()
