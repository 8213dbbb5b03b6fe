"""Device idle time charged to the program's own spans.  In a running
profiler trace every span of ``mxnet_tpu.tracing`` is a host annotation
``mx:<name>`` on the thread that opened it, on the clock of the runtime's
host events.  Each idle gap of the busiest device inside the traced span
is split among the ``mx:*`` spans by what was innermost on the host while
it lasted (a gap of 3 ms that begins inside a read-back and ends inside
the next launch is charged to both, and to what lay between), summed over
the names in ``spans``, over the number of ``per`` spans (steps or ticks)
that start inside the device's window; in milliseconds.  ``None`` where
the trace holds no ``mx:*`` span.  The whole table goes to standard error
once, with the reading of ``xplane.idle_gaps``' rule beside it (each gap
whole to the span open at its middle).

The device's lines run on the device's clock, which leads or trails the
host's by up to a millisecond or two; a gap is a few.  So the device's
times are first shifted by the smallest distance, over all runs of a
program, from the host handing the run over (``DoEnqueueProgram``) to its
start on the device (tied by ``run_id``): the run that started soonest
after its hand-over is taken to have started at once."""
import sys

from benchmark.lib import xplane

PREFIX = "mx:"
NO_SPAN = "_no_mx_span_"


def charge(gaps, spans, split=True, no_span=NO_SPAN):
    """{span name: seconds}: every gap (start_ns, end_ns) charged to the
    shortest of ``spans`` (name, start_ns, end_ns; sorted by start) open
    while it lasts, piece by piece; with ``split`` false, whole to the
    one open at its middle."""
    sums, open_, nxt = {}, [], 0
    for g0, g1 in sorted(gaps):
        while nxt < len(spans) and spans[nxt][1] < g1:
            open_.append(spans[nxt])
            nxt += 1
        open_ = [sp for sp in open_ if sp[2] > g0]
        cuts = [g0, g1]
        if split:
            cuts = sorted(set(cuts).union(
                t for sp in open_ for t in sp[1:] if g0 < t < g1))
        for a, b in zip(cuts, cuts[1:]):
            inner = [sp for sp in open_ if sp[1] <= (a + b) / 2 < sp[2]]
            key = min(inner, key=lambda sp: sp[2] - sp[1])[0] \
                if inner else no_span
            sums[key] = sums.get(key, 0.0) + (b - a) / 1e9
    return sums


def device_lead_ns(planes, device):
    """How far device ``device``'s clock reads ahead of the host's: the
    least distance from a run's hand-over on the host to its start on the
    device; 0 where the trace ties no run to its hand-over."""
    runs = planes.get(xplane.RUNS, {})
    handed = {}
    for run_id, at, _d in runs.get("enqueued", ()):
        handed[run_id] = min(at, handed.get(run_id, at))
    leads = [start - handed[run_id] for run_id, start, _d
             in runs.get("device:%d" % device, ()) if run_id in handed]
    return min(leads) if leads else 0.0


def table(planes):
    """What ``reduce`` and ``report`` read of the busiest device: idle
    seconds by span (split, and by the gap's middle), how many spans of
    each name start inside the device's window, the window's seconds and
    the clock's lead in ns; or None."""
    ops = xplane.device_ops(planes)
    spans = xplane.host_spans(planes, prefix=PREFIX)
    if not ops or not spans:
        return None
    device = max(ops, key=lambda i: sum(d for _n, _s, d in ops[i]))
    lead = device_lead_ns(planes, device)
    inter = [(s - lead, e - lead) for s, e in xplane.busy_intervals(ops[device])]
    gaps = [(e0, s1) for (_s0, e0), (s1, _e1) in zip(inter, inter[1:])]
    counts = {}
    for name, start, _end in spans:
        if inter[0][0] <= start <= inter[-1][1]:
            counts[name] = counts.get(name, 0) + 1
    return {"idle": charge(gaps, spans), "counts": counts,
            "by_middle": charge(gaps, spans, split=False),
            "window_s": (inter[-1][1] - inter[0][0]) / 1e9, "lead_ns": lead}


def threads(planes):
    """{host line (thread) name: sorted names of the ``mx:*`` spans on it}."""
    out = {}
    for plane, lines in planes.items():
        if xplane.DEVICE_PLANE.match(plane) or plane == xplane.RUNS:
            continue
        for line, events in lines.items():
            names = {n for n, _s, _d in events if n.startswith(PREFIX)}
            if names:
                out["%s %s" % (plane, line)] = sorted(names)
    return out


def report(ctx, found):
    if ctx.get("_idle_reported"):
        return
    ctx["_idle_reported"] = True
    sums, counts = found["idle"], found["counts"]
    idle = sum(sums.values())
    named = idle - sums.get(NO_SPAN, 0.0)

    def log(msg):
        print("[idle] " + msg, file=sys.stderr, flush=True)

    log("busiest device idle %.6f s of %.6f s; %.1f %% of it charged to a "
        "named %s* span; device clock taken to lead the host's by %.3f ms" % (
            idle, found["window_s"], 100.0 * named / idle if idle else 0.0,
            PREFIX, found["lead_ns"] / 1e6))
    for name, sec in sorted(sums.items(), key=lambda kv: -kv[1]):
        log("  %-28s %.6f s  (whole gaps by their middle: %.6f s; %d spans "
            "start in the device's window)" % (
                name, sec, found["by_middle"].get(name, 0.0),
                counts.get(name, 0)))
    for line, names in sorted(threads(ctx["planes"]).items()):
        log("  thread %s holds %s" % (line, ", ".join(names)))


def reduce(ctx, spans, per):
    planes = ctx.get("planes")
    found = table(planes) if planes else None
    if found is None:
        return None
    report(ctx, found)
    if not found["counts"].get(per):
        return None
    return 1e3 * sum(found["idle"].get(n, 0.0) for n in spans) \
        / found["counts"][per]
