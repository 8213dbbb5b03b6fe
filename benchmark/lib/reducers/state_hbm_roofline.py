"""A decode step's share of the HBM roofline for a dense model whose step
reads every weight, the active slots' recurrent state and the live
rows of its paged layers: the family's ``forward_min_bytes`` at the
live positions of the decode steps inside the traced span (``live_key``
of the window) and at the mean of ``slots_arg`` over the window's
``ring_span`` spans, over the chip's bandwidth, against the device time
of the programs that a step enqueues in the traced span
(``hybrid_hbm_roofline``'s reading with no experts to count).  ``None``
without a trace, and for a program that writes no such argument."""
from benchmark.lib.reducers import device_busy_per_step, hybrid_hbm_roofline
from benchmark.lib.weights import family


def reduce(ctx, span, among, live_key, ring_span, slots_arg):
    live = ctx["window"].get(live_key)
    fam = family(ctx["cfg"])
    if not ctx.get("planes") or not live \
            or not hasattr(fam, "forward_min_bytes"):
        return None
    means = hybrid_hbm_roofline.step_means(ctx, ring_span, [slots_arg])
    busy = device_busy_per_step.seconds_charged_to(ctx["planes"], span, among)
    if means is None or busy is None or busy[0] <= 0:
        return None
    least = fam.forward_min_bytes(ctx["cfg"], live, means[0]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (busy[0] / busy[1])
