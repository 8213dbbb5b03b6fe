"""Summed device milliseconds of collective operations in one step, on
the busiest device; 0 where the cell runs on one chip."""
from benchmark.lib import xplane


def reduce(ctx, steps_key):
    planes, steps = ctx.get("planes"), ctx["window"].get(steps_key)
    if not planes or not steps:
        return None
    return 1e3 * xplane.collective_seconds(planes) / steps
