"""Numbers from the program's own spans: the records that
``mxnet_tpu.tracing`` keeps in its ring (the two loops' step-level spans,
``gc`` and ``compile:*`` are kept whether or not tracing is enabled),
read in-process after the window.

The window on the program's clock (``time.perf_counter``) opens at
``T_START`` of the running ``benchmark/run.py`` plus ``setup_s`` (both
drivers take their ``t0`` right after ``run.setup_done()``) and lasts
``window["seconds"]``.  A record belongs to the window if it ends inside
it, to set-up (``setup_spans.py``) if it ends before it opens.  A program
without the ring's reader, a window without a single ``step`` span, and a
ring that evicted records the question needs all give ``None``, and the
metric stays out of the line.
"""
import statistics
import sys

SLOW = 1.5          # a step over this many times the median is reported


def log(msg):
    print("[spans] " + msg, file=sys.stderr, flush=True)


def ring():
    """(records, evicted) of the imported program, or None where the
    program has no ring to read."""
    tracing = sys.modules.get("mxnet_tpu.tracing")
    if tracing is None or not hasattr(tracing, "records"):
        return None
    return tracing.records(), tracing.dropped()


def window_of(ctx):
    """(opening, closing) on the program's clock, or None."""
    t_start = getattr(sys.modules.get("__main__"), "T_START", None)
    setup = ctx.get("end_to_end", {}).get("setup_s")
    seconds = ctx.get("window", {}).get("seconds")
    if t_start is None or setup is None or not seconds:
        return None
    return t_start + setup, t_start + setup + seconds


def end(rec):
    return rec["t0"] + rec["dur"]


def union_seconds(spans):
    """Summed length of the spans' intervals, nested ones counted once (a
    jit traced inside another's trace reports both)."""
    total, reach = 0.0, float("-inf")
    for r in sorted(spans, key=lambda r: r["t0"]):
        if end(r) > reach:
            total += end(r) - max(r["t0"], reach)
            reach = end(r)
    return total


def step_values(records, step, w0, w1, measure="duration", less=()):
    """[(seconds, record)] for every ``step`` span that ends in
    (w0, w1]: its length, or with ``measure="period"`` its start to the
    next one's start on the same thread (the last one has no next and is
    left out), less the time that thread spent inside the spans named in
    ``less`` over the same stretch."""
    steps = sorted((r for r in records if r["name"] == step),
                   key=lambda r: r["t0"])
    taken = [r for r in records if r["name"] in less]
    out = []
    for i, r in enumerate(steps):
        if not w0 < end(r) <= w1:
            continue
        if measure == "period":
            nxt = next((s for s in steps[i + 1:] if s["tid"] == r["tid"]),
                       None)
            if nxt is None:
                continue
            stop = nxt["t0"]
        else:
            stop = end(r)
        out.append((stop - r["t0"] - sum(
            t["dur"] for t in taken if t["tid"] == r["tid"]
            and t["t0"] >= r["t0"] and end(t) <= stop), r))
    return out


def percentile(values, q):
    """The q-th percentile by the nearest rank above (the drivers' rule)."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(-(-q * len(s) // 100)) - 1))]


STATS = {"p50": lambda v: percentile(v, 50), "p95": lambda v: percentile(v, 95),
         "max": max, "mean": statistics.fmean}


def describe(records, rec, seconds):
    """One line for one step or tick: its phases (direct and deeper
    children by name), ``cpu_ms``, and the ``gc`` spans inside it."""
    kids = {}
    for r in records:
        if r is not rec and r["tid"] == rec["tid"] \
                and r["t0"] >= rec["t0"] and end(r) <= end(rec):
            kids[r["name"]] = kids.get(r["name"], 0.0) + r["dur"]
    gcs = [r for r in records if r["name"] == "gc"
           and r["t0"] < end(rec) and end(r) > rec["t0"]]
    args = rec.get("args") or {}
    return "%s %.2f ms (span %.2f ms, cpu_ms %s, args %s): %s; gc inside: %s" % (
        rec["name"], 1e3 * seconds, 1e3 * rec["dur"],
        "%.2f" % args["cpu_ms"] if "cpu_ms" in args else "n/a",
        {k: v for k, v in args.items() if k != "cpu_ms"},
        ", ".join("%s %.2f" % (k, 1e3 * v) for k, v in
                  sorted(kids.items(), key=lambda kv: -kv[1])) or "no phases",
        ", ".join("gen%s %.2f ms on thread %d" % (
            (g.get("args") or {}).get("generation"), 1e3 * g["dur"], g["tid"])
            for g in gcs) or "none")


def report_slowest(records, values):
    """The slowest step of the window by its period, and up to five more
    over ``SLOW`` times the median, on standard error: what a reader of
    a stalled run wants."""
    med = statistics.median(v for v, _r in values)
    worst = sorted(values, key=lambda vr: -vr[0])
    for k, (v, rec) in enumerate(worst[:6]):
        if k and v <= SLOW * med:
            break
        log("%s of %d (median %.2f ms): %s" % (
            "slowest" if k == 0 else "slow", len(values), 1e3 * med,
            describe(records, rec, v)))


def reduce(ctx, spans, stat, step, measure="duration", less=()):
    """``stat`` over the spans named in ``spans`` that end in the window,
    in milliseconds.  ``step`` names the loop's step span, whose presence
    in the window shows that the program records it at all: then a window
    with none of ``spans`` (no ``gc`` long enough to be kept) reads 0."""
    found, win = ring(), window_of(ctx)
    if found is None or win is None:
        return None
    (records, evicted), (w0, w1) = found, win
    if not any(r["name"] == step and w0 < end(r) <= w1 for r in records):
        return None
    if evicted and min(r["t0"] for r in records) > w0:
        log("the ring evicted %d records and its oldest starts %.3f s after "
            "the window opened: window not reduced" % (
                evicted, min(r["t0"] for r in records) - w0))
        return None
    if list(spans) != [step]:
        mine = [r["dur"] for r in records
                if r["name"] in spans and w0 < end(r) <= w1]
        return 1e3 * STATS[stat](mine) if mine else 0.0
    values = step_values(records, step, w0, w1, measure, less)
    if not values:
        return None
    if not ctx.get("_spans_reported"):
        ctx["_spans_reported"] = True
        log("ring holds %d records, %d evicted; %d %s spans end in the window"
            % (len(records), evicted, len(values), step))
        report_slowest(records, step_values(records, step, w0, w1, "period")
                       or values)
    return 1e3 * STATS[stat]([v for v, _r in values])
