"""IVF-Flat through the public API: ``ivf_flat.build`` with the
configuration's ``index`` parameters, searched with its ``search``
parameters; every knob the configuration leaves out keeps the library's
default."""

import jax


def build(base, config):
    from raft_tpu.neighbors import ivf_flat

    index = ivf_flat.build(base, ivf_flat.IvfFlatIndexParams(
        **config["index"]))
    return (jax.block_until_ready(index),
            ivf_flat.IvfFlatSearchParams(**config["search"]))
