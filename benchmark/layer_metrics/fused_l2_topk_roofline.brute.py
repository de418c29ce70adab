"""Share of its roofline that the ``fused_l2_topk`` shortlist kernel
reached (percent): ``xplane.roofline_share`` with the kernel's self time
in place of the device's busy time.

The kernel's time is the summed self time of the trace's device ops
whose instruction starts with ``fused_l2_topk`` (the ``pallas_call``'s
name); the work is every request's (``work/brute_force.py``: 2·rows·n·d
operations, the base read once), which is the shortlist's own all-pairs
work.  ``None`` where no such op is among the trace's top ops.
"""

import dataclasses

from benchmark import xplane

KERNEL = "fused_l2_topk"


def read(ctx):
    r = ctx.trace
    if r is None:
        return None
    kernel_s = sum(s for name, s in r.device_ops
                   if name.rsplit("/", 1)[-1].startswith(KERNEL))
    if kernel_s <= 0:
        return None
    return xplane.roofline_share(dataclasses.replace(r, busy_s=kernel_s),
                                 ctx.work, ctx.peaks)
