"""One process, one cell, one run:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to run without the cell's chips, builds the weights on the device
from ``--seed``, warms up exactly the shapes the window uses, measures,
checks what the timed path produced against the plain reference, and
prints one JSON object as the last line of standard output.  ``--rehearse
1`` (which the driver's command never passes) runs the same code on
whatever JAX finds, at the tiny sizes the files give under ``rehearsal``,
and prints a line that names the platform and carries no metric.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Run:
    """What one run carries from set-up to its last line."""

    def __init__(self, args, man, cell, cfg, traffic, limits):
        self.workload, self.seed = cell["name"], args.seed
        self.seconds, self.trace = args.seconds, bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.man, self.cell, self.cfg = man, cell, cfg
        self.traffic, self.limits = traffic, limits
        self.out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
        self.window, self.end_to_end, self.numbers = {}, {}, {}
        self.attempted = self.failed = 0
        self.memory_peak, self.tracer = 0, None

    def log(self, msg):
        print("[bench %7.2fs] %s" % (time.perf_counter() - T_START, msg),
              file=sys.stderr, flush=True)

    def setup_done(self):
        """Called by the driver as the last thing before the window."""
        self.end_to_end["setup_s"] = time.perf_counter() - T_START
        self.log("set-up %.2f s (build %.2f, first calls %.2f, %d programs "
                 "built or loaded)" % (
                     self.end_to_end["setup_s"],
                     self.window.get("setup_build_s", 0.0),
                     self.window.get("setup_compile_s", 0.0),
                     self.window.get("setup_programs", 0)))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def place_caches():
    """JAX's persistent compilation cache at one fixed path inside the
    checkout (the path is part of the cache's key), unless the environment
    places it; every program cached, however quick its compile, so that
    only a checkout's first run compiles."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def find_devices(run):
    """The cell's chips, or no run: a CPU, too few chips or a device kind
    that the benchmark's table of peaks does not hold all exit non-zero."""
    import jax

    from benchmark.lib import peaks

    devices = jax.devices()
    kind, platform = devices[0].device_kind, devices[0].platform
    if run.rehearse:
        return devices, {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    if platform == "cpu":
        raise SystemExit("no accelerator: JAX found only %r" % (kind,))
    table = peaks.peaks_for(kind)
    if len(devices) != run.cell["chips"]:
        raise SystemExit("cell %s needs %d chip(s), JAX found %d" % (
            run.workload, run.cell["chips"], len(devices)))
    return devices, table


def layer_metrics(run, planes, table):
    """Every per-layer metric of this cell whose reader finds something to
    read; a reader that returns nothing leaves its metric out."""
    from benchmark.lib import manifest

    ctx = {"window": run.window, "planes": planes, "cfg": run.cfg,
           "traffic": run.traffic, "peaks": table, "chips": run.cell["chips"],
           "end_to_end": run.end_to_end}
    out = {}
    for m in manifest.metrics_of(run.man, "per_layer", run.workload):
        spec = manifest.layer_metric(m["name"])
        reader = importlib.import_module(
            "benchmark.lib.reducers." + spec["reducer"])
        value = reader.reduce(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    args = parse(argv)
    from benchmark.lib import manifest

    man = manifest.manifest()
    cell = manifest.workload(man, args.workload)
    run = Run(args, man, cell,
              manifest.config(man, cell["config"], rehearse=args.rehearse),
              manifest.traffic(cell["traffic"], rehearse=args.rehearse),
              manifest.limits(cell["name"]))
    place_caches()
    devices, table = find_devices(run)
    os.makedirs(run.out_dir, exist_ok=True)
    run.log("%s seed %d: %d x %s" % (run.workload, run.seed, len(devices),
                                     devices[0].device_kind))
    driver = importlib.import_module(
        "benchmark.drivers." + run.traffic["driver"])
    driver.main(run)

    from benchmark.lib import compare, xplane

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak}
    line = {"attempted": run.attempted, "failed": run.failed}
    planes = None
    if run.trace and run.tracer is not None and run.tracer.traced:
        planes = xplane.load(xplane.find_trace(run.tracer.dir))
        found = xplane.busy_and_window(planes)
        if found is not None:
            device["busy_s"], device["window_s"] = found[0], found[1]
        line["breakdown"] = {"device_ops": xplane.top_ops(planes),
                             "idle_gaps": xplane.idle_gaps(planes)}
    if run.rehearse:
        line["metrics"] = {}
        line["rehearsal"] = True
    elif run.trace:
        line["metrics"] = layer_metrics(run, planes, table)
    else:
        units = {m["name"]: m["unit"] for m in
                 manifest.metrics_of(man, "end_to_end", run.workload)}
        line["metrics"] = {k: {"value": float(run.end_to_end[k]), "unit": u}
                           for k, u in units.items()}
    correct, rows = compare.judge(run.numbers, run.limits)
    correct = correct and run.failed == 0
    line = dict({"correct": correct}, **line)
    line["device"] = device
    line["compared"] = rows
    run.log("window numbers: %s" % json.dumps(
        {k: v for k, v in run.window.items()}, default=float))
    compare.report(rows, correct)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
