"""Recall@k of an IVF-Flat configuration against ``n_probes``, at the
configuration's full size, for choosing its ``n_probes``.

    python3 benchmark/recall_curve.py --config sift1m-ivf_flat --seed <n>

For each probe count it prints one JSON line: the recall of
``ivf_flat.search`` against the plain reference, and the share of the
true neighbours stored in lists the queries probe (what an exact scan of
those lists can reach at most).  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def curve(config: dict, seed: int, probes=(1, 2, 4, 8, 16, 32, 64)):
    import dataclasses

    import numpy as np

    from benchmark import mixture, reference, spec
    from raft_tpu.neighbors import ivf_flat

    k = int(config["data"]["k"])
    base, queries = mixture.make(config["data"], config["mixture"], seed)
    queries = np.asarray(queries)
    _, ref_ids = reference.exact_knn(base, queries, k)
    fam = spec.load_module(ROOT, "families", config["family"])
    index, params = fam.build(base, config)
    ids = np.asarray(index.ids)
    home = np.full(int(base.shape[0]), -1, np.int64)
    lists, _ = np.nonzero(ids >= 0)
    home[ids[ids >= 0]] = lists
    c = np.asarray(index.centroids, np.float64)
    qf = queries.astype(np.float64)
    d = (qf * qf).sum(1)[:, None] - 2 * qf @ c.T + (c * c).sum(1)[None]
    order = np.argsort(d, axis=1)
    out = []
    for p in probes:
        sp = dataclasses.replace(params, n_probes=p)
        # 512 queries a call, the served top bucket: a larger call gathers
        # more probed lists than one chip holds
        got = np.concatenate([
            np.asarray(ivf_flat.search(index, queries[lo:lo + 512], k, sp)[1])
            for lo in range(0, len(queries), 512)])
        probed = order[:, :p]
        share = float(np.mean([np.isin(home[g], pr).mean()
                               for g, pr in zip(ref_ids, probed)]))
        line = {"n_probes": p,
                "recall": reference.recall(got, ref_ids),
                "true_neighbours_in_probed_lists": share}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[0] = ROOT
    with open(os.path.join(ROOT, "benchmark", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    from benchmark import harness

    harness.enable_compile_cache(ROOT)
    print(json.dumps(harness.device_info(1)), flush=True)
    curve(config, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
