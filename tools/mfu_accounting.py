"""Roofline accounting for the bench.py train step (VERDICT r4 #1).

Builds the exact bench.py trainer (ResNet-50 v1, bf16 compute + fp32
master, momentum SGD, one fused XLA program), compiles it, pulls XLA's
own cost analysis (flops + bytes accessed) for the compiled program,
times real steps, and decomposes the step time against the ceilings
measured by tools/bench_mfu.py:

    t_compute        = flops / conv_ceiling   (MXU lower bound; real)
    t_memory_upper   = bytes / stream_bw      (pre-fusion byte count ->
                                               an UPPER bound on memory
                                               time, not a lower bound)
    implied_hbm_gbs  = bytes / measured_step  (the rate the pre-fusion
                                               traffic would require)

`cost_analysis` counts bytes before fusion, so t_memory_upper can
exceed the measured step; the decisive signals for "memory-bound" are
(a) t_compute << measured (the MXU is idle most of the step) and
(b) implied_hbm_gbs at or above the chip's stream bandwidth (even with
fusion discounting real traffic, the program is bandwidth-limited).

Run on an idle chip:
    python tools/bench_mfu.py --json probe.json
    python tools/mfu_accounting.py --mfu-probe probe.json [--batch 256]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

T0 = time.time()


def log(msg):
    print("[acct %6.1fs] %s" % (time.time() - T0, msg), file=sys.stderr,
          flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int,
                   default=int(os.environ.get("BENCH_BATCH", "256")))
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--json", default=None)
    p.add_argument("--mfu-probe", default=None,
                   help="ceilings JSON written by tools/bench_mfu.py "
                        "--json on the same device (none = raw "
                        "counters, no roofline verdict)")
    args = p.parse_args()

    import jax

    from mxnet_tpu import random as _random
    import bench

    # the exact bench.py program (shared builder, same model/optimizer/
    # dtype/synthetic data) so the accounting describes the headline run
    trainer, x, y, batch, on_tpu = bench.build_trainer(args.batch)
    steps = args.steps if on_tpu else 2
    log("devices=%s batch=%d" % (jax.devices(), batch))

    loss = trainer.step([x], y)  # compile + init
    log("warmup done (loss=%.3f)" % float(loss))

    # XLA's own accounting of the compiled fused program
    lowered = trainer._step_fn.lower(
        trainer.param_arrays, trainer.opt_state, (x._data,), y._data,
        _random.next_key())
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0))
    bytes_acc = float(cost.get("bytes accessed", 0))
    log("cost_analysis: %.1f GFLOP, %.2f GB accessed per step"
        % (flops / 1e9, bytes_acc / 1e9))

    # time real steps (async dispatch; final loss fetch forces the chain)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step([x], y)
    lv = float(loss)
    secs = (time.perf_counter() - t0) / steps
    img_s = batch / secs
    log("measured: %.1f ms/step, %.0f img/s (loss=%.3f)"
        % (secs * 1e3, img_s, lv))

    ceilings = {}
    if not args.mfu_probe:
        log("no --mfu-probe: emitting raw counters WITHOUT the roofline "
            "verdict (run tools/bench_mfu.py --json first)")
    else:
        with open(args.mfu_probe) as f:
            probe = json.load(f)
        ceilings = {
            "matmul_tflops": max(r["tflops"] for r in probe["matmul"]),
            "conv_tflops": probe["conv"]["tflops"],
            "hbm_gbs": probe["hbm"]["gb_per_s"],
        }

    out = {"batch": batch, "steps": steps, "ms_per_step": secs * 1e3,
           "img_per_sec": img_s, "xla_gflop_per_step": flops / 1e9,
           "xla_gb_accessed_per_step": bytes_acc / 1e9,
           "arithmetic_intensity_flop_per_byte":
               flops / bytes_acc if bytes_acc else None,
           "ceilings": ceilings}
    if ceilings:
        t_compute = flops / (ceilings["conv_tflops"] * 1e12)
        t_memory_upper = bytes_acc / (ceilings["hbm_gbs"] * 1e9)
        implied_gbs = bytes_acc / secs / 1e9
        # memory-bound iff the MXU lower bound explains well under the
        # measured time AND the pre-fusion traffic would need >= the
        # chip's stream rate (see module docstring)
        memory_bound = t_compute < 0.7 * secs and \
            implied_gbs >= 0.8 * ceilings["hbm_gbs"]
        out.update({
            "t_compute_ms": t_compute * 1e3,
            "t_memory_upper_ms": t_memory_upper * 1e3,
            "implied_hbm_gbs_prefusion": implied_gbs,
            "mxu_busy_fraction": t_compute / secs,
            "roofline_bound": "memory" if memory_bound else "compute",
        })
    print(json.dumps(out, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        log("wrote %s" % args.json)


if __name__ == "__main__":
    main()
