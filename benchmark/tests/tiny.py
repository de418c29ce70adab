"""Cells shrunk to a size a CPU test can run in seconds: every width and
the traffic's shape as in the cell, fewer rows, lists and queries."""

from benchmark import spec

WORKLOADS = ("sift1m-ivf_flat.batch", "sift1m-brute.batch")
SEED = 2**31 + 17


def shrink(cell):
    cell.config["data"].update(rows=20_000, queries=512)
    if "index" in cell.config:
        cell.config["index"]["n_lists"] = 64
        cell.config["search"]["n_probes"] = 16
    return cell


def cell(workload, root=spec.ROOT):
    return shrink(spec.load_cell(workload, root))
