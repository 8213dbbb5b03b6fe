"""Device milliseconds of one step, on the busiest device: either the
union of the intervals in which an operation ran, over the steps that the
driver counted inside the traced span (``steps_key`` of the window); or,
where steps of several kinds take turns (a decode step, a prefill chunk),
the time that the programs enqueued by the host spans named ``span`` held
the device, over those spans."""
from benchmark.lib import xplane


def reduce(ctx, steps_key=None, span=None, among=None):
    planes = ctx.get("planes")
    if not planes:
        return None
    if span is not None:
        busy = seconds_charged_to(planes, span, among)
        return None if busy is None else 1e3 * busy[0] / busy[1]
    steps = ctx["window"].get(steps_key)
    found = xplane.busy_and_window(planes)
    return None if found is None or not steps else 1e3 * found[2] / steps


def seconds_charged_to(planes, span, among):
    """(seconds, number of spans): the device time of every program
    charged to a host span named ``span``, among the spans whose names
    start with ``among`` (``xplane.program_seconds_by_span``).  The last
    of them is left out: the trace may end before its programs do."""
    charged = xplane.program_seconds_by_span(planes, among)[:-1]
    mine = [sec for name, sec in charged if name == span]
    return (sum(mine), len(mine)) if mine else None
