"""A decode step's share of the HBM roofline for a model whose step
touches only part of what the chip holds (an expert layer's chosen
experts, the active slots' recurrent state): the least bytes of one
``(slots, 1)`` forward, the family's ``forward_min_bytes`` at the live
positions of the decode steps inside the traced span (``live_key`` of
the window) and at what the program's own ``ring_span`` spans say of the
window's steps, the mean of their ``slots_arg`` (active slots) and of
their ``touched_arg`` (the (layer, held expert) pairs some row chose),
over the chip's bandwidth, against the device time of the programs that
a step enqueues in the traced span.  The traced span and the window are
the same loop a few seconds apart, so what a step touches is read where
the ring holds it whole.  ``None`` without a trace, and for a program
that writes no such arguments."""
from benchmark.lib.reducers import device_busy_per_step, program_spans
from benchmark.lib.weights import family


def step_means(ctx, ring_span, names):
    """Mean of each argument over the window's ``ring_span`` spans that
    carry them all, or None."""
    found, win = program_spans.ring(), program_spans.window_of(ctx)
    if found is None or win is None:
        return None
    rows = [r["args"] for r in found[0]
            if r["name"] == ring_span and r.get("args")
            and win[0] < program_spans.end(r) <= win[1]
            and all(k in r["args"] for k in names)]
    if not rows:
        return None
    return [sum(a[k] for a in rows) / len(rows) for k in names]


def reduce(ctx, span, among, live_key, ring_span, slots_arg, touched_arg):
    live = ctx["window"].get(live_key)
    fam = family(ctx["cfg"])
    if not ctx.get("planes") or not live \
            or not hasattr(fam, "forward_min_bytes"):
        return None
    means = step_means(ctx, ring_span, [slots_arg, touched_arg])
    busy = device_busy_per_step.seconds_charged_to(ctx["planes"], span, among)
    if means is None or busy is None or busy[0] <= 0:
        return None
    least = fam.forward_min_bytes(ctx["cfg"], live, *means) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (busy[0] / busy[1])
