"""Share of its roofline that the search program reached (percent),
from the profiler's trace and the work of every request served in the
window (``work/brute_force.py``).  Every batch of the cells that report it
holds one request."""

from benchmark import xplane


def read(ctx):
    return xplane.roofline_share(ctx.trace, ctx.work, ctx.peaks)
