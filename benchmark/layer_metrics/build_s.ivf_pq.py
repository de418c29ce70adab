"""Seconds of the IVF-PQ index build inside set-up: the sum over its
stages of the gauge ``raft_index_build_seconds{family="ivf_pq",stage}``
that the build records in the process-wide metric registry, each stage
timed to its results being ready on the device.  ``None`` where the
program records no such gauge, and in a run with no device trace (off
the chip, where the build's times say nothing of the cell)."""


def read(ctx):
    from raft_tpu.obs.metrics import registry

    if ctx.trace is None:
        return None
    gauge = registry().get("raft_index_build_seconds")
    if gauge is None:
        return None
    stages = [v for labels, v in gauge.samples()
              if labels.get("family") == "ivf_pq"]
    return sum(stages) if stages else None
