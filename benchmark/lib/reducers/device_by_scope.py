"""Device milliseconds of one part of a program, from the named scopes
its operations were traced under (``mxnet_tpu.profiler.device_table``
reads them from the trace's own ``tf_op`` records, which
``jax.profiler.ProfileData``, and so ``ctx["planes"]``, drops).

Serving (``span`` = ``mx:engine.decode`` or ``mx:engine.prefill``): every
run of a program is charged to the engine's span inside which the host
launched it, as ``xplane.program_seconds_by_span`` charges it to a
``bench:engine.*`` span (launch on the calling thread, the enqueue that
follows it, the run on the device by their ``run_id``); the operations
that started inside a charged run's interval are summed by their
innermost part scope (``profiler.part_scope``: the names of Gluon blocks
around and below it are passed over), over the spans of that name that
launched a program, the last one left out.  ``scope`` names the part;
``share`` instead gives the operations under no part scope as a
percentage of all.  Training (``match``, ``steps_key``): the operations
of the busiest device with ``match`` in a component of their scope path
(a block's name), over the steps the driver counted in the traced span.

Once a run the whole table goes to standard error: every scope of each
kind of span with its milliseconds a step, and the unscoped rest by the
source line that traced it.  ``None`` where the program opens no such
scope (a parent of the PR that added them) or the trace has no device.

The trace's path is not in ``ctx``: the newest ``*.xplane.pb`` under
``<ROOT>/.bench_out/*/trace`` is read (``Tracer`` empties its directory
before it starts).
"""
import bisect
import glob
import os
import re
import sys
import time

from benchmark.lib import manifest, xplane

SPANS = ("mx:engine.decode", "mx:engine.prefill")
UNSCOPED = "(unscoped)"


def _log(msg):
    print("[scopes] " + msg, file=sys.stderr, flush=True)


def device_ops(ctx):
    """{device: [DeviceOp] by start} of the run's trace, read once; None
    where the program has no ``device_table`` or the trace no device."""
    if "_device_ops" not in ctx:
        from mxnet_tpu import profiler

        found, read = {}, getattr(profiler, "device_table", None)
        traces = glob.glob(os.path.join(
            manifest.ROOT, ".bench_out", "*", "trace", "plugins", "profile",
            "*", "*.xplane.pb"))
        if read is not None and traces and ctx.get("planes"):
            newest, t0 = max(traces, key=os.path.getmtime), time.perf_counter()
            for op in read(newest):
                found.setdefault(op.device, []).append(op)
            _log("read %d device operations from %s in %.2f s" % (
                sum(map(len, found.values())),
                os.path.relpath(newest, manifest.ROOT),
                time.perf_counter() - t0))
        ctx["_device_ops"] = found or None
    return ctx["_device_ops"]


def runs_by_span(planes, names=SPANS):
    """[(span name, [(start_ns, duration_ns)])] in the order of the
    spans: the runs on the busiest device of the programs launched
    inside each host span whose name is one of ``names`` (spans that do
    not nest in one another), tied as ``xplane.program_seconds_by_span``
    ties them; and that device's index."""
    spans = [sp for sp in xplane.host_spans(planes, prefix="mx:")
             if sp[0] in names]
    starts = [s for _n, s, _e in spans]
    runs = planes.get(xplane.RUNS, {})
    first = {}
    for run_id, at, _d in runs.get("enqueued", ()):
        first[run_id] = min(at, first.get(run_id, at))
    enqueued = sorted((at, run_id) for run_id, at in first.items())
    launches = sorted(
        s for plane, lines in planes.items()
        if not xplane.DEVICE_PLANE.match(plane) and plane != xplane.RUNS
        for events in lines.values()
        for n, s, _d in events if n == xplane.LAUNCH)
    span_of, j = {}, 0
    for launch in launches:
        while j < len(enqueued) and enqueued[j][0] < launch:
            j += 1            # handed over before this call: not its own
        if j == len(enqueued):
            break
        at = bisect.bisect_right(starts, launch) - 1
        if at >= 0 and launch < spans[at][2]:
            span_of[enqueued[j][1]] = at
        j += 1
    best, device = {}, None
    for line, events in runs.items():
        if not line.startswith("device:"):
            continue
        charged = {}
        for run_id, start, dur in events:
            if run_id in span_of:
                charged.setdefault(span_of[run_id], []).append((start, dur))
        if sum(d for rs in charged.values() for _s, d in rs) > \
                sum(d for rs in best.values() for _s, d in rs):
            best, device = charged, int(line.split(":")[1])
    return [(spans[at][0], rs) for at, rs in sorted(best.items())], device


def span_tables(ctx):
    """{span name: {"spans": how many launched a program, "run_ns":
    their runs' time, "scopes": {part scope or UNSCOPED: ns},
    "sources": {source line of an unscoped operation: ns}}}, the last
    charged span left out (the trace may end before its programs do)."""
    if "_scope_tables" in ctx:
        return ctx["_scope_tables"]
    from mxnet_tpu import profiler

    ops, tables = device_ops(ctx), {}
    charged, device = runs_by_span(ctx["planes"]) if ops else ([], None)
    mine = (ops or {}).get(device, [])
    op_starts = [op.start_ns for op in mine]
    for name, runs in charged[:-1]:
        t = tables.setdefault(name, {"spans": 0, "run_ns": 0.0,
                                     "scopes": {}, "sources": {}})
        t["spans"] += 1
        for start, dur in runs:
            t["run_ns"] += dur
            # the two readers round a start differently: a nanosecond
            lo = bisect.bisect_left(op_starts, start - 1.0)
            hi = bisect.bisect_left(op_starts, start + dur + 1.0)
            for op in mine[lo:hi]:
                part = profiler.part_scope(op.scope) or UNSCOPED
                t["scopes"][part] = t["scopes"].get(part, 0.0) \
                    + op.duration_ns
                if part == UNSCOPED:
                    src = _where(op)
                    t["sources"][src] = t["sources"].get(src, 0.0) \
                        + op.duration_ns
    ctx["_scope_tables"] = tables
    for name, t in sorted(tables.items()):
        _report(name, t)
    return tables


def _where(op):
    """What an unscoped operation is known by: the line that traced it,
    else (an operation the compiler added) its kind."""
    if op.source:
        return os.path.relpath(op.source, manifest.ROOT) \
            if op.source.startswith(manifest.ROOT) else op.source
    return "%s (%s)" % (op.hlo_category or "no category",
                        xplane.op_group(op.name))


def _report(name, t):
    n, total = t["spans"], sum(t["scopes"].values())
    _log("%s: %d spans launched a program; their runs held the device "
         "%.4f ms a span, the operations inside them %.4f ms (%.2f %%)" % (
             name, n, t["run_ns"] / n / 1e6, total / n / 1e6,
             100.0 * total / t["run_ns"] if t["run_ns"] else 0.0))
    for part, ns in sorted(t["scopes"].items(), key=lambda kv: -kv[1]):
        _log("  %-16s %9.4f ms a span  %6.2f %%" % (
            part, ns / n / 1e6, 100.0 * ns / total if total else 0.0))
    for src, ns in sorted(t["sources"].items(), key=lambda kv: -kv[1])[:12]:
        _log("    unscoped: %-48s %9.4f ms a span" % (src, ns / n / 1e6))


def block_kind(name):
    """A Gluon block's kind from its name: ``resnetv10_stage1_batchnorm3``
    -> ``batchnorm``."""
    return re.sub(r"\d+$", "", name.rsplit("_", 1)[-1]) or name


def _train(ctx, match, steps_key):
    ops, steps = device_ops(ctx), ctx["window"].get(steps_key)
    if not ops or not steps:
        return None
    mine = max(ops.values(), key=lambda v: sum(op.duration_ns for op in v))
    if not ctx.get("_train_scopes_reported"):
        ctx["_train_scopes_reported"] = True
        kinds = {}
        for op in mine:
            kind = block_kind(op.scope[-1]) if op.scope else UNSCOPED
            kinds[kind] = kinds.get(kind, 0.0) + op.duration_ns
        total = sum(kinds.values())
        _log("training: %d traced steps, the busiest device's operations "
             "%.4f ms a step, by the kind of the innermost block" % (
                 steps, total / steps / 1e6))
        for kind, ns in sorted(kinds.items(), key=lambda kv: -kv[1])[:16]:
            _log("  %-16s %9.4f ms a step  %6.2f %%" % (
                kind, ns / steps / 1e6, 100.0 * ns / total))
    found = [op.duration_ns for op in mine
             if any(match in part for part in op.scope)]
    return sum(found) / steps / 1e6 if found else None


def reduce(ctx, scope=None, span=None, share=False, match=None,
           steps_key=None):
    if not ctx.get("planes"):
        return None
    if match is not None:
        return _train(ctx, match, steps_key)
    t = span_tables(ctx).get(span)
    if not t or set(t["scopes"]) <= {UNSCOPED}:
        return None                    # the program opens no scope
    if share:
        return 100.0 * t["scopes"].get(UNSCOPED, 0.0) \
            / sum(t["scopes"].values())
    if scope not in t["scopes"]:
        return None
    return t["scopes"][scope] / t["spans"] / 1e6
