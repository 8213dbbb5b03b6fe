"""The training driver: builds ``ShardedTrainer`` for a configuration
with its constructor's defaults for every speed lever, drives it from the
seed through its first three steps (which the reference follows), and
hands that same object to the measured window."""
import collections
import gc
import time

from benchmark.drivers import common


def _first_gradients(trainer, cfg):
    """Each leaf's first gradient as the optimizer got it, worked out from
    its state after one step: momentum SGD then holds m = -lr * g."""
    import jax

    state = trainer.opt_state
    state = state["base"] if "base" in state else state
    lr = cfg["optimizer"]["learning_rate"]
    return jax.jit(lambda ms: [m / -lr for m in ms])(list(state["mom"]))


def _to_host(arrays):
    """Off the chip until the comparison, so that the window's memory
    holds nothing of the benchmark's."""
    import jax
    import numpy as np

    return [np.asarray(a, np.float32) for a in jax.device_get(arrays)]


def build(run):
    """(trainer, batches)."""
    import jax

    from mxnet_tpu import parallel
    from benchmark import programs
    from benchmark.lib import weights

    cfg, traffic = run.cfg, run.traffic
    prog = programs.program(cfg)
    net = prog.build_net(cfg)
    run.log("network built")
    arrays = weights.make_params(cfg, run.seed)
    jax.block_until_ready(arrays)
    run.log("weights made")
    programs.set_weights(net, weights.family(cfg).param_specs(cfg), arrays)
    del arrays
    opt = dict(cfg["optimizer"])
    trainer = parallel.ShardedTrainer(
        net, prog.loss_fn(cfg), optimizer=opt.pop("name"),
        optimizer_params=opt, dtype_policy=cfg["dtype_policy"])
    batches = weights.make_batches(cfg, run.seed, traffic["pool"],
                                   traffic["batch"])
    jax.block_until_ready(batches)
    run.log("trainer and %d batches made" % len(batches))
    return trainer, batches


def first_steps(run, trainer, batches):
    """Steps 1 to 3 through the window's own call and feed; returns what
    the comparison reads of the program: each step's loss, every
    trainable leaf's first gradient and its change after the three."""
    import jax

    from benchmark.lib import weights

    cfg = run.cfg
    mask = weights.family(cfg).trainable(cfg)
    losses = []
    for i in range(3):
        x, y = batches[i % len(batches)]
        losses.append(float(trainer.step([x], y)))
        if i == 0:
            grads = _to_host(_first_gradients(trainer, cfg))
    start = weights.make_params(cfg, run.seed)
    delta = _to_host(jax.jit(lambda a, b: [x - y for x, y in zip(a, b)])(
        [a for a, t in zip(trainer.param_arrays, mask) if t],
        [a for a, t in zip(start, mask) if t]))
    del start
    return {"losses": losses, "grads": grads, "deltas": delta}


def window(run, trainer, batches, compiles, seconds, first=3):
    """The measured window: ``trainer.step`` as a user's loop calls it,
    two calls in flight, every completion stamped; from ``t0`` (the last
    warm-up step seen complete) to the first completion at or after
    ``t0 + seconds``."""
    import jax

    step_span = jax.profiler.TraceAnnotation
    inflight = collections.deque()
    stamps, dispatch = [], []
    skipped0, compiles0 = trainer.skipped_steps, compiles.count
    i = first
    t0 = time.perf_counter()
    stamps.append(t0)
    while stamps[-1] < t0 + seconds:
        x, y = batches[i % len(batches)]
        i += 1
        t_call = time.perf_counter()
        with step_span("bench:trainer.step"):
            loss = trainer.step([x], y)
        dispatch.append(time.perf_counter() - t_call)
        inflight.append(loss)
        # stamp what has completed; then hold the queue to two calls
        while inflight and (inflight[0].is_ready() or len(inflight) >= 2):
            with step_span("bench:await"):
                inflight.popleft().block_until_ready()
            stamps.append(time.perf_counter())
    t_end = stamps[-1]
    for loss in inflight:
        loss.block_until_ready()
    steps = len(stamps) - 1
    out = {"steps": steps, "seconds": t_end - t0,
           "samples_per_s": steps * run.traffic["batch"] / (t_end - t0),
           "dispatch_ms_p50": 1e3 * common.percentile(dispatch, 50),
           "compiles_in_window": compiles.count - compiles0,
           "skipped_steps": trainer.skipped_steps - skipped0}
    out.update(common.step_stats(stamps))
    return out


def reference(run, quant=None, half_batch=False):
    """The reference's (or, with ``quant``, the control's) first three
    steps on the same weights and batches, made again from the seed."""
    from benchmark.lib import weights
    from benchmark.lib.reference import train as ref_train

    cfg = run.cfg
    params = weights.make_params(cfg, run.seed)
    batches = weights.make_batches(cfg, run.seed, 3, run.traffic["batch"])
    out = ref_train.first_steps(weights.family(cfg), cfg, params, batches,
                                quant=quant, half_batch=half_batch)
    return dict(out, grads=_to_host(out["grads"]),
                deltas=_to_host(out["deltas"]))


def main(run):
    import jax

    from benchmark.lib import compare

    compiles = common.CompileCounter()
    t_build = time.perf_counter()
    trainer, batches = build(run)
    run.window["setup_build_s"] = time.perf_counter() - t_build
    t_compile = time.perf_counter()
    prog = first_steps(run, trainer, batches)
    run.window["setup_compile_s"] = time.perf_counter() - t_compile
    run.window["setup_programs"] = compiles.count
    tracer = common.Tracer(run.out_dir, run.trace)
    if run.trace:
        with tracer:
            traced = window(run, trainer, batches, compiles,
                            run.traffic["trace_seconds"])
        run.window["traced_steps"] = traced["steps"]
    run.setup_done()
    win = window(run, trainer, batches, compiles, run.seconds)
    run.window.update(win)
    run.log("window: %d steps in %.3f s, %.2f samples/s; longest step %.1f ms, "
            "%d slow steps, %d compiles in the window, %d skipped"
            % (win["steps"], win["seconds"], win["samples_per_s"],
               win.get("step_ms_max", 0.0), win.get("slow_steps", 0),
               win["compiles_in_window"], win["skipped_steps"]))
    run.attempted, run.failed = win["steps"], win["skipped_steps"]
    run.end_to_end["train_throughput"] = win["samples_per_s"]
    run.memory_peak = common.memory_peak_bytes(jax.local_devices(), run.log)
    run.tracer = tracer
    trainer.close()
    del trainer, batches
    gc.collect()
    ref = reference(run)
    run.numbers = compare.train_numbers(prog, ref)
    run.log("reference: losses %s; program: %s; all readings, compared or "
            "not: %s" % (["%.5f" % v for v in ref["losses"]],
                         ["%.5f" % v for v in prog["losses"]], run.numbers))
