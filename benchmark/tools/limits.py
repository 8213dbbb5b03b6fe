"""Readings that a cell's limits are set from, taken on the chip at the
cell's own size, several seeds in one process:

    python3 benchmark/tools/limits.py --workload <name> --seeds 1,2,3 [--seconds s]

For every seed it prints the program's numbers (what a run compares), the
control's (the plain reference put in the program's place and computed
in fp8, one precision below ``bf16_mixed``), a witness's (the same in the
program's own bfloat16) and, for a training cell, the planted fault's
(half of the batch left out), and judges each against the cell's limits
as a run would: the control and the fault have to come out not correct.
The benchmark's own runs never run this; ``PERF.md`` records what it
printed."""
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


def train_readings(run):
    from benchmark.drivers import train
    from benchmark.lib import compare, quant

    trainer, batches = train.build(run)
    prog = train.first_steps(run, trainer, batches)
    trainer.close()
    del trainer, batches
    gc.collect()
    ref = train.reference(run)
    out = {"program": compare.train_numbers(prog, ref)}
    for name, kw in (("control_fp8", {"quant": quant.fp8}),
                     ("witness_bf16", {"quant": quant.bf16}),
                     ("fault_half_batch", {"half_batch": True})):
        out[name] = compare.train_numbers(train.reference(run, **kw), ref)
    return out


def serve_readings(run):
    from benchmark.drivers import serve_lm
    from benchmark.lib import quant

    serve_lm.main(run)
    out = {"program": dict(run.numbers),
           "served_tokens": sum(len(r.tokens) for r in run.sample)}
    for name, fn in (("control_fp8", quant.fp8), ("witness_bf16", quant.bf16)):
        gaps = serve_lm.reference_gaps(run, run.params, run.sample, quant=fn)
        out[name] = {"logit_gap_max": float(max(g.max() for g in gaps))}
    run.params = run.sample = None
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args()
    from benchmark.lib import manifest

    bench_run.place_caches()
    man = manifest.manifest()
    cell = manifest.workload(man, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                                rehearse=args.rehearse)
        run = bench_run.Run(
            ns, man, cell,
            manifest.config(man, cell["config"], rehearse=args.rehearse),
            manifest.traffic(cell["traffic"], rehearse=args.rehearse),
            manifest.limits(cell["name"]))
        bench_run.find_devices(run)
        os.makedirs(run.out_dir, exist_ok=True)
        fn = train_readings if run.traffic["driver"] == "train" \
            else serve_readings
        got = fn(run)
        from benchmark.lib import compare
        # each is judged on the numbers it has (the serving control does
        # not decode, so it has no lengths)
        verdicts = {who: compare.judge(numbers, {
            k: v for k, v in run.limits.items() if k in numbers})[0]
            for who, numbers in got.items() if isinstance(numbers, dict)}
        print("READINGS", json.dumps({"workload": args.workload, "seed": seed,
                                      "correct": verdicts, **got}),
              flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
