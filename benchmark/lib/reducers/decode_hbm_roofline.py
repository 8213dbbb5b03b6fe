"""The decode program's share of the HBM roofline: the least bytes a
decode step must move (``benchmark/lib/bytes.py``, at the mean live
positions of the decode steps inside the traced span) over the chip's
bandwidth, against the device time of the programs that a decode step
enqueues in the same span."""
from benchmark.lib import bytes as byte_counts
from benchmark.lib.reducers import device_busy_per_step


def reduce(ctx, span, among, live_key):
    live = ctx["window"].get(live_key)
    if not ctx.get("planes") or not live:
        return None
    busy = device_busy_per_step.seconds_charged_to(ctx["planes"], span, among)
    if busy is None or busy[0] <= 0:
        return None
    least = byte_counts.decode_step_min_bytes(ctx["cfg"], live) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (busy[0] / busy[1])
