"""Host time per batch (ms): the median, over the batches whose ``fetch``
ends inside the window, of the dispatch thread's time from the end of one
batch's ``fetch`` to the start of the next batch's ``fetch``.  That is the
host's share of a batch outside the wait on the device: reply, the next
batch's forming, staging and launch, and any wait for requests.

Read from the program's own spans: the ``serve.dispatch(...):fetch``
ranges that ``raft_tpu.core.tracing`` records into the process-wide
flight recorder (``raft_tpu.obs.spans.recorder()``), on the same
monotonic clock as the window.  The ring keeps 4096 spans per thread; at
about five phases per batch the last ~800 batches of a window are read,
which is the whole of an IVF-Flat window (~600 batches) and the last
tenth of a brute-force one (~8,000).  ``None`` below 50 batches, where
the program records no phase spans, and in a run with no device trace
(off the chip, where the host's times say nothing of the cell).
"""

import statistics

from raft_tpu.obs.spans import recorder

PREFIX = "serve.dispatch("
FETCH = ":fetch"
LEAST = 50


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.window.t0 * 1e9, ctx.window.t1 * 1e9
    fetches = {}
    for s in recorder().snapshot():
        if s.name.startswith(PREFIX) and s.name.endswith(FETCH):
            fetches.setdefault(s.tid, []).append(s)
    host = []
    for spans in fetches.values():
        spans.sort(key=lambda s: s.t_start_ns)
        host += [b.t_start_ns - a.t_end_ns for a, b in zip(spans, spans[1:])
                 if lo <= a.t_end_ns <= hi]
    if len(host) < LEAST:
        return None
    return statistics.median(host) / 1e6
