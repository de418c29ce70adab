"""IVF-PQ with exact re-ranking through the public API:
``ivf_pq.build_chunked`` with the configuration's ``index`` parameters
(the streaming build keeps one chunk's assignment and encoding on the
device at a time, which a 10M-row corpus needs on one chip), served as
``refine.Refined(index, base, refine_ratio)`` with its ``search``
parameters; every knob the configuration leaves out keeps the library's
default."""

import jax


def build(base, config):
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors.refine import Refined

    index = jax.block_until_ready(ivf_pq.build_chunked(
        base, ivf_pq.IvfPqIndexParams(**config["index"])))
    return (Refined(index, base, int(config["refine_ratio"])),
            ivf_pq.IvfPqSearchParams(**config["search"]))
