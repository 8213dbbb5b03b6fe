"""Reduction from a profiler trace (``.xplane.pb``) to numbers.

The trace is first read into plain tuples (:func:`load`), and every
reduction works on those, so the arithmetic is tested without a chip and
the reading is tested against the small recorded trace kept with the
tests.

A device plane is one named ``/device:TPU:<n>``; its line ``XLA Ops``
holds one event for every operation that ran on the chip, its line ``XLA
Modules`` one for every run of a program.  Host planes
hold the threads of the process, where ``jax.profiler.TraceAnnotation``
spans (the benchmark's ``bench:*``) appear by name.
"""
import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"      # one event for every run of a program
LAUNCH = "PJRT_LoadedExecutable_Execute"   # the host calling a program
ENQUEUE = "DoEnqueueProgram"      # the runtime handing that run to the device
RUNS = "program runs"
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def find_trace(log_dir):
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % log_dir)
    return found[-1]


def load(path):
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}.
    Lines of the same name in one plane (host threads) are merged.  One
    more plane, ``RUNS``, ties every run of a program on a device to the
    moment the host enqueued it, by the ``run_id`` both events carry."""
    from jax.profiler import ProfileData

    planes, runs = {}, {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, float(ev.start_ns),
                               float(ev.duration_ns)))
                if device and line.name == MODULES_LINE:
                    to = runs.setdefault("device:" + device.group(2), [])
                elif not device and ev.name == ENQUEUE:
                    to = runs.setdefault("enqueued", [])
                else:
                    continue
                run_id = dict(ev.stats).get("run_id")
                if run_id is not None:
                    to.append((str(run_id),) + events[-1][1:])
    planes[RUNS] = runs
    return planes


def device_ops(planes):
    """{device index: [(name, start_ns, duration_ns)]} sorted by start."""
    out = {}
    for name, lines in planes.items():
        m = DEVICE_PLANE.match(name)
        if m and lines.get(OPS_LINE):
            out[int(m.group(2))] = sorted(lines[OPS_LINE], key=lambda e: e[1])
    return out


def busy_intervals(events):
    """Union of [start, end) of the events, as a sorted list."""
    merged = []
    for _n, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def busy_and_window(planes):
    """(busy_s averaged over the devices, window_s, busiest device's
    busy_s).  The window is the same for every device: from the first
    operation on any of them to the end of the last."""
    ops = device_ops(planes)
    if not ops:
        return None
    t0 = min(ev[0][1] for ev in ops.values())
    t1 = max(max(s + d for _n, s, d in ev) for ev in ops.values())
    busy = [sum(e - s for s, e in busy_intervals(ev)) for ev in ops.values()]
    return (sum(busy) / len(busy) / 1e9, (t1 - t0) / 1e9, max(busy) / 1e9)


SHAPE = re.compile(r"([a-z]+\d+)\[([\d,]*)\]")


def op_name(event_name):
    """The operation's own name: on a TPU an event carries the whole HLO
    line (``%fusion.6 = (bf16[256]{...}, ...) fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_group(event_name):
    """Operations of one kind and result under one name: the instance
    number goes and the largest array of the result is put in its place
    (``%copy.7 = bf16[24,513,32,16,64]{...} copy(...)`` ->
    ``copy_bf16_24_513_32_16_64``)."""
    kind = re.sub(r"(\.\d+)+$", "", op_name(event_name))
    if " = " not in event_name:
        return kind
    rhs = event_name.split(" = ", 1)[1]
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result = rhs[:i + 1]
    else:
        result = rhs.split(" ", 1)[0]
    best, size = None, -1
    for dtype, dims in SHAPE.findall(result):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if n > size:
            best, size = "%s_%s" % (dtype, dims.replace(",", "_")), n
    return kind if best is None else "%s_%s" % (kind, best.rstrip("_"))


def top_ops(planes, n=10):
    """[[name, seconds]] of the operation groups that took most device
    time on the busiest device; ``__x<k>`` is how many events a group
    holds."""
    ops = device_ops(planes)
    if not ops:
        return []
    busiest = max(ops.values(), key=lambda ev: sum(d for _n, _s, d in ev))
    sums, counts = {}, {}
    for name, _s, dur in busiest:
        key = op_group(name)
        sums[key] = sums.get(key, 0.0) + dur / 1e9
        counts[key] = counts.get(key, 0) + 1
    return [["%s__x%d" % (k, counts[k]), v] for k, v in
            sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def collective_seconds(planes):
    """Summed device time of collective operations on the busiest device
    (all-reduce, all-gather, reduce-scatter, collective-permute,
    all-to-all and fusions named for them)."""
    best = 0.0
    for ev in device_ops(planes).values():
        best = max(best, sum(d for n, _s, d in ev
                             if COLLECTIVE.search(op_name(n).lower())) / 1e9)
    return best


def host_spans(planes, prefix="bench:"):
    """[(name, start_ns, end_ns)] of the host annotations that start with
    ``prefix``, over all host threads, sorted by start."""
    out = []
    for name, lines in planes.items():
        if DEVICE_PLANE.match(name) or name == RUNS:
            continue
        for events in lines.values():
            out.extend((n, s, s + d) for n, s, d in events
                       if n.startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def _host_events(planes, name):
    return sorted(s for plane, lines in planes.items()
                  if not DEVICE_PLANE.match(plane) and plane != RUNS
                  for events in lines.values()
                  for n, s, _d in events if n == name)


def program_seconds_by_span(planes, prefix):
    """[(host span name, seconds)], one entry for every host span named
    ``prefix...`` that launched a program: the time its programs held the
    busiest device.  A run of a program is charged whole to the span
    inside which the host launched it, wherever on the device's time line
    it ran: also the work that a step leaves running after the host has
    its answer.  Three records are tied together: the launch on the
    calling thread (inside the span), the enqueue that follows it (in the
    order of the launches; the runtime hands a program over a millisecond
    or two after the call, often when a short span has already closed),
    and the run on the device (by the ``run_id`` it shares with the
    enqueue).  Only the host's clock orders anything; the device's is a
    millisecond off it."""
    spans = host_spans(planes, prefix)
    starts = [s for _n, s, _e in spans]
    runs = planes.get(RUNS, {})
    first = {}
    for run_id, at, _d in runs.get("enqueued", ()):
        first[run_id] = min(at, first.get(run_id, at))
    enqueued = sorted((at, run_id) for run_id, at in first.items())
    span_of, j = {}, 0
    for launch in _host_events(planes, LAUNCH):
        while j < len(enqueued) and enqueued[j][0] < launch:
            j += 1            # handed over before this call: not its own
        if j == len(enqueued):
            break
        at = bisect.bisect_right(starts, launch) - 1
        if at >= 0 and launch < spans[at][2]:
            span_of[enqueued[j][1]] = at
        j += 1
    best = {}
    for line, events in runs.items():
        if not line.startswith("device:"):
            continue
        charged = {}
        for run_id, _start, dur in events:
            if run_id in span_of:
                at = span_of[run_id]
                charged[at] = charged.get(at, 0.0) + dur / 1e9
        if sum(charged.values()) > sum(best.values()):
            best = charged
    return [(spans[at][0], sec) for at, sec in sorted(best.items())]


def idle_gaps(planes, n=10, no_span="_no_host_span_"):
    """[[host span name, seconds]]: the idle time of the busiest device,
    each gap charged to the innermost ``bench:*`` span open on the host
    at the gap's middle, summed by name, longest first."""
    ops = device_ops(planes)
    if not ops:
        return []
    busiest = max(ops.values(), key=lambda ev: sum(d for _n, _s, d in ev))
    spans = host_spans(planes)
    sums = {}
    inter = busy_intervals(busiest)
    for (_s0, e0), (s1, _e1) in zip(inter, inter[1:]):
        mid = (e0 + s1) / 2
        inner = [sp for sp in spans if sp[1] <= mid < sp[2]]
        key = min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner else no_span
        sums[key] = sums.get(key, 0.0) + (s1 - e0) / 1e9
    return [[k, v] for k, v in
            sorted(sums.items(), key=lambda kv: -kv[1])[:n]]
