"""Share of the traced window in which no operation ran on the device,
from the profiler's trace (percent)."""

from benchmark import xplane


def read(ctx):
    return xplane.idle_share(ctx.trace)
