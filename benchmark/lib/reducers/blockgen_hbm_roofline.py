"""A block-diffusion pass's share of the HBM roofline: the least bytes
of one ``(slots, block)`` forward, from the configuration alone (the
family's ``forward_min_bytes``: every multiplied weight once in the type
it is stored in, K and V of the live positions; at the mean live
positions of the passes inside the traced span), over the chip's
bandwidth, against the device time of the programs that a pass enqueues
in the same span."""
from benchmark.lib.reducers import device_busy_per_step
from benchmark.lib.weights import family


def reduce(ctx, span, among, live_key):
    live = ctx["window"].get(live_key)
    fam = family(ctx["cfg"])
    if not ctx.get("planes") or not live \
            or not hasattr(fam, "forward_min_bytes"):
        return None
    busy = device_busy_per_step.seconds_charged_to(ctx["planes"], span, among)
    if busy is None or busy[0] <= 0:
        return None
    least = fam.forward_min_bytes(ctx["cfg"], live) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (busy[0] / busy[1])
