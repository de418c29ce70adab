"""Share of its roofline that the search program reached (percent),
from the profiler's trace and the work of every request served in the
window (``work/ivf_pq.py``: the coarse ranking, the lookup tables, the PQ
scan of the probed lists and the exact re-rank).  Every batch of the
cells that report it holds one request."""

from benchmark import xplane


def read(ctx):
    return xplane.roofline_share(ctx.trace, ctx.work, ctx.peaks)
