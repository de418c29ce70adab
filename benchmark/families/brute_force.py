"""Brute force through the public API: the server is handed the raw
base array and ``BruteForceSearchParams`` with the configuration's
``search`` parameters; every knob it leaves out keeps the library's
default."""


def build(base, config):
    from raft_tpu.serve.searchers import BruteForceSearchParams

    return base, BruteForceSearchParams(**config["search"])
